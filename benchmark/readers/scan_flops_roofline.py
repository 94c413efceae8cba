"""Share of the chip's bf16 peak the scan of one prefill dispatch reaches,
in % — for a scan that arithmetic bounds (``scan_roofline`` charges
bytes).

The least arithmetic the scan of a dispatch must do whatever implements
it — ``prefill_scan_min_flops`` of the ``shapes.py`` in the configuration's
architecture package, over the prompt tokens and the prompts a dispatch
carried (the program's ``serve_scan_tokens``, which counts a token once a
scanning layer, over ``scan_layers`` of the same ``shapes.py`` and over
``serve_prefill_dispatches``; ``serve_admitted`` over the same) — over the
chip's published bf16 FLOP/s and the device time one prefill program
spends under the named device scopes (``scope_time``).  None where the
package has no such function, the program no such counter (a parent
without the scan), or the trace no scoped prefill program.
"""

from harness import arch, peaks
from readers import counter_ratio, scope_time


def read(ctx, program: str, scopes, exclude: str = ""):
    shapes = arch.load_shapes(ctx["conf"]).shapes
    least = getattr(shapes, "prefill_scan_min_flops", None)
    layers = getattr(shapes, "scan_layers", None)
    dispatches = ["serve_prefill_dispatches"]
    layer_tokens = counter_ratio.read(ctx, ["serve_scan_tokens"], dispatches)
    prompts = counter_ratio.read(ctx, ["serve_admitted"], dispatches)
    if least is None or layers is None or not layer_tokens or not prompts:
        return None
    scan_ms = scope_time.read(ctx, program, scopes, exclude)
    if not scan_ms:
        return None
    peak = peaks.peaks_of(ctx["device"]["kind"])["bf16_flops"]
    flops = least(ctx["conf"], layer_tokens / layers(ctx["conf"]), prompts)
    return 100.0 * (flops / peak) / (scan_ms * 1e-3)
