"""A percentile, over the window's requests, of the time one request spent
in the named spans of the program's own per-request timeline
(``/api/trace/<id>``), in ms: the durations of ``spans`` summed per
request.  A request that lacks one of them is left out: a part of the sum
is another quantity."""

from harness import stats


def read(ctx, spans, percentile: float):
    values = []
    for trace in (ctx.get("request_traces") or {}).values():
        found = {}
        for sp in trace.get("spans", []):
            if sp.get("name") in spans and sp.get("duration_ms") is not None:
                found[sp["name"]] = (
                    found.get(sp["name"], 0.0) + float(sp["duration_ms"])
                )
        if len(found) == len(set(spans)):
            values.append(sum(found.values()))
    if not values:
        return None
    return stats.percentile(values, percentile)
