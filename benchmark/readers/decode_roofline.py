"""Share of the HBM roofline one decode step reaches, in %.

least bytes a chip must read for one step (its share of the streamed
weights at their stored width + its share of the live keys and values:
``decode_step_min_bytes`` of the ``shapes.py`` in the configuration's
architecture package) / the chip's published bytes per second, over the
measured device time of one step (``trace_program``).  Decode at these
batch sizes is bandwidth-bound, so the bound is the byte one.
"""

from harness import arch, peaks
from readers import trace_program


def read(ctx, program: str, exclude: str = "", per=1):
    step_ms = trace_program.read(ctx, program, exclude, per)
    live = [p["kv_tokens"] for p in ctx.get("polled") or []
            if p.get("kv_tokens") is not None]
    if step_ms is None or not live:
        return None
    bandwidth = peaks.peaks_of(ctx["device"]["kind"])["hbm_bytes_per_s"]
    least = arch.load_shapes(ctx["conf"]).shapes.decode_step_min_bytes(
        ctx["conf"], sum(live) / len(live), int(ctx["cell"]["chips"])
    )
    return 100.0 * (least / bandwidth) / (step_ms * 1e-3)
