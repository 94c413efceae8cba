"""A percentile of what the client timed, over the requests of the window
that were served in full.

``what``: "total" (due -> done: an open loop's wait for a free sender
counts), "ttft" (sent -> first SSE delta) or "tpot" ((last - first delta) /
tokens after the first; deltas land a decode chunk at a time, so only this
whole-request mean is meaningful).
"""

from harness import stats


def read(ctx, what: str, percentile: float):
    values = []
    for s in ctx["window"]:
        if s.failed:
            continue
        if what == "total":
            v = (s.done - s.due) * 1e3
        elif what == "ttft":
            v = stats.ttft_ms(s.sent, s.delta_times)
        elif what == "tpot":
            v = stats.tpot_ms(s.delta_times)
        else:
            raise ValueError(f"unknown latency {what!r}")
        if v is not None:
            values.append(v)
    if not values:
        return None
    return stats.percentile(values, percentile)
