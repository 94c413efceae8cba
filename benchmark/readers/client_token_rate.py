"""Tokens completed inside the window over the window's length.

Every streamed request counts, whichever part of it falls inside [t0, t1)
(ramp requests still streaming at t0, requests still streaming at t1):
token-time accounting over all the work and all the time of the window.
A delivery's tokens are credited to the interval they were produced over
(``stats.credited_tokens``), because the server delivers 16 tokens at a
time.  With the hash tokenizer the configurations assume, one token is one
SSE delta.

In a lockstep cell a window holds a few whole rounds and a part of one, and
which requests the batcher admitted first in that last round moves this
rate by a few per cent from seed to seed (PERF.md §2): it is reported per
layer, not held to a bound.
"""

from harness import stats


def read(ctx):
    streamed = [s for s in ctx["samples"] if s.delta_times]
    if not streamed:
        return None
    n = sum(stats.credited_tokens(s.sent, s.delta_times, ctx["t0"], ctx["t1"])
            for s in streamed)
    return n / (ctx["t1"] - ctx["t0"])
