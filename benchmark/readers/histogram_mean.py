"""Mean over the window of one of the program's ``<span>_ms`` histograms
(``runtime/metrics.span``): lifetime count and sum at the window's two
ends, subtracted."""

from harness import stats


def read(ctx, histogram: str):
    before, after = ctx["before"].get("metrics"), ctx["after"].get("metrics")
    if not before or not after:
        return None
    count, total = stats.histogram_delta(before, after, histogram)
    if count <= 0:
        return None
    return total / count
