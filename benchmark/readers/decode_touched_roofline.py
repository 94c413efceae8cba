"""Share of the HBM roofline one decode step of a ROUTED block reaches, in
%, charged with the experts the step DID touch.

``decode_step_roofline`` charges the least any step must read, which for a
routed layer leaves the experts' weights out or nearly so; what a step has
to read given what its tokens chose is that plus the distinct experts
touched.  The program counts them (``serve_moe_experts_touched``, distinct
held experts summed over (routed layer, step); ``serve_moe_layer_steps``,
the (routed layer, step)s counted): their ratio is the experts a routed
layer touched in a step, and ``decode_step_touched_bytes`` of the
``shapes.py`` in the configuration's architecture package, which knows how
many layers route, turns it into a step's bytes, with the live cache rows
polled as for ``decode_step_roofline``.
None where the program has no such counters, the package no such function,
or the trace no decode program.
"""

from harness import arch, peaks
from readers import counter_ratio, trace_program


def read(ctx, program: str, exclude: str = "", per=1):
    step_ms = trace_program.read(ctx, program, exclude, per)
    live = [p["kv_tokens"] for p in ctx.get("polled") or []
            if p.get("kv_tokens") is not None]
    touched = counter_ratio.read(
        ctx, ["serve_moe_experts_touched"], ["serve_moe_layer_steps"]
    )
    shapes = arch.load_shapes(ctx["conf"]).shapes
    touched_bytes = getattr(shapes, "decode_step_touched_bytes", None)
    if step_ms is None or not live or touched is None or touched_bytes is None:
        return None
    bandwidth = peaks.peaks_of(ctx["device"]["kind"])["hbm_bytes_per_s"]
    read_bytes = touched_bytes(
        ctx["conf"], sum(live) / len(live), touched,
        int(ctx["cell"]["chips"]),
    )
    return 100.0 * (read_bytes / bandwidth) / (step_ms * 1e-3)
