"""A percentile, over the window's requests, of one span of the program's
own per-request timeline (``/api/trace/<id>``), in ms."""

from harness import stats


def read(ctx, span: str, percentile: float):
    values = []
    for trace in (ctx.get("request_traces") or {}).values():
        for sp in trace.get("spans", []):
            if sp.get("name") == span and sp.get("duration_ms") is not None:
                values.append(float(sp["duration_ms"]))
    if not values:
        return None
    return stats.percentile(values, percentile)
