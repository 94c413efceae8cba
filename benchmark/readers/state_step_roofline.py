"""Share of the HBM roofline the lane-state part of one decode step
reaches, in %.

The least bytes a step must move of lane state whatever implements it —
``state_step_min_bytes`` of the ``shapes.py`` in the configuration's
architecture package, at the live positions polled (every live lane's
state read and written once, lanes counted at their fewest) — over the
chip's published bytes per second and the device time one step spends
under the named device scopes (``scope_time``, divided by ``per`` as it
divides).  It reads the same work whether XLA or a kernel advances the
state.  None where the package has no such function, nothing was polled,
or the trace holds no scoped decode program.
"""

from harness import arch, peaks
from readers import scope_time


def read(ctx, program: str, scopes, exclude: str = "", per=1):
    least = getattr(
        arch.load_shapes(ctx["conf"]).shapes, "state_step_min_bytes", None)
    live = [p["kv_tokens"] for p in ctx.get("polled") or []
            if p.get("kv_tokens") is not None]
    if least is None or not live:
        return None
    step_ms = scope_time.read(ctx, program, scopes, exclude, per)
    if not step_ms:
        return None
    bandwidth = peaks.peaks_of(ctx["device"]["kind"])["hbm_bytes_per_s"]
    moved = least(ctx["conf"], sum(live) / len(live))
    return 100.0 * (moved / bandwidth) / (step_ms * 1e-3)
