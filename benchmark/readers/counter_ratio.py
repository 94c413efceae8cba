"""What some of the program's counters (``/api/metrics`` ``counters``) gained
over the window, over what others gained, times ``scale``: requests per
admission round, stale chunks per chunk.  ``complement`` reads the share
that is left: padding rows = 1 - tokens / budget rows.  None when the
denominator did not move, as under a program that has no such counter."""


def read(ctx, numerator, denominator, scale=1.0, complement=False):
    before = (ctx["before"].get("metrics") or {}).get("counters")
    after = (ctx["after"].get("metrics") or {}).get("counters")
    if before is None or after is None:
        return None

    def gained(names):
        return sum(after.get(n, 0) - before.get(n, 0) for n in names)

    below = gained(denominator)
    if below <= 0:
        return None
    ratio = gained(numerator) / below
    return scale * (1.0 - ratio if complement else ratio)
