"""Share, in %, of a decode step's least bytes that one of the program's
byte counters accounts for: what the counter gained over the window, per
decode step (``chunks`` counter x the steps of a chunk, ``per``: a number
or a dotted key of the configuration's ``serving`` block), over
``decode_step_min_bytes`` of the ``shapes.py`` in the configuration's
architecture package at the live cache rows polled.  None when a counter
did not move, as under a program that has none."""

from harness import arch
from readers import counter_ratio, trace_program


def read(ctx, counter: str, chunks: str, per=1):
    a_chunk = counter_ratio.read(ctx, [counter], [chunks])
    live = [p["kv_tokens"] for p in ctx.get("polled") or []
            if p.get("kv_tokens") is not None]
    if a_chunk is None or not a_chunk or not live:
        return None
    least = arch.load_shapes(ctx["conf"]).shapes.decode_step_min_bytes(
        ctx["conf"], sum(live) / len(live), int(ctx["cell"]["chips"])
    )
    return 100.0 * a_chunk / trace_program.per_value(ctx, per) / least
