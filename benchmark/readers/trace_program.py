"""Device time of the programs whose name matches ``program`` (and not
``exclude``) in the traced slice.

``stat``: "median_ms" of one execution, divided by ``per`` (a number, or a
dotted key of the configuration's ``serving`` block, e.g. the decode chunk
so that a chunk program reads as time per step).
"""

import re


def matching(ctx, program: str, exclude: str = ""):
    trace = ctx.get("trace") or {}
    out = {}
    for name, row in (trace.get("programs") or {}).items():
        if re.search(program, name) and not (exclude and re.search(exclude, name)):
            out[name] = row
    return out


def per_value(ctx, per):
    if isinstance(per, str):
        return float(ctx["conf"]["serving"][per])
    return float(per)


def read(ctx, program: str, exclude: str = "", per=1):
    rows = matching(ctx, program, exclude)
    if not rows:
        return None
    # the variant that ran most often stands for the program
    row = max(rows.values(), key=lambda r: r["count"])
    return 1e3 * row["median_s"] / per_value(ctx, per)
