"""Share of the HBM roofline the state-space scan of one prefill dispatch
reaches, in %.

The least bytes the scan of a dispatch must move whatever implements it —
``prefill_scan_min_bytes`` of the ``shapes.py`` in the configuration's
architecture package, over the prompt tokens and the prompts a dispatch
carried (the program's ``serve_scan_tokens``, which counts a token once a
scanning layer, over ``scan_layers`` of the same ``shapes.py`` and over
``serve_prefill_dispatches``; ``serve_admitted`` over the same) — over the
chip's published bytes per second and the device time one prefill program
spends under the named device scopes (``scope_time``).  It reads the same
work whether XLA or a kernel scans.  None where the package has no such
function, the program no such counter (a parent without the scan), or the
trace no scoped prefill program.
"""

from harness import arch, peaks
from readers import counter_ratio, scope_time


def read(ctx, program: str, scopes, exclude: str = ""):
    shapes = arch.load_shapes(ctx["conf"]).shapes
    least = getattr(shapes, "prefill_scan_min_bytes", None)
    layers = getattr(shapes, "scan_layers", None)
    dispatches = ["serve_prefill_dispatches"]
    layer_tokens = counter_ratio.read(ctx, ["serve_scan_tokens"], dispatches)
    prompts = counter_ratio.read(ctx, ["serve_admitted"], dispatches)
    if least is None or layers is None or not layer_tokens or not prompts:
        return None
    scan_ms = scope_time.read(ctx, program, scopes, exclude)
    if not scan_ms:
        return None
    bandwidth = peaks.peaks_of(ctx["device"]["kind"])["hbm_bytes_per_s"]
    moved = least(ctx["conf"], layer_tokens / layers(ctx["conf"]), prompts)
    return 100.0 * (moved / bandwidth) / (scan_ms * 1e-3)
