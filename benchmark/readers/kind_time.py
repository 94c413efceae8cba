"""Device time one execution of a program spends under the named LAYER
KINDS, in ms, divided by ``per`` as ``trace_program`` divides.

``program`` / ``exclude`` choose the program as ``trace_program`` does
(among the names that match, the one that ran most often).  ``kinds`` is
a list of names of ``docqa_tpu/ops/scopes.py:layer_kind``'s vocabulary (a
mixer kind of ``models/hybrid.MIXERS``, ``dense``, ``routed``): the self
time of the kind's ops plus the holes between two of them;  ``"*none"``
stands for the execution's time minus EVERY kind the trace holds —
``embed``, ``head``, ``sample``, the loop, the holes at a kind's edge —
so the groups of a program's metrics add up to its ``trace_program``
time.  TIME only: a scope is charged the op line's time in it, not the
bytes that stream meanwhile (a weight prefetched a layer ahead moves time
between kinds at an unchanged sum), so no share of a roofline is read by
kind.

The trace is read where ``harness/child.py`` left it by
``harness/xplane_kinds.py`` — standard library, so it runs here, in the
parent, ONCE per run: the result is kept in ``ctx``, written as
``kinds.json`` beside the trace and ``scopes.json``, and its tables
printed once on standard error.  One more whole pass over the slice's op
line, as ``scope_time`` makes.

None where the slice holds no such program, or where no op of it carries
a kind: a block that opens none, a program compiled before the kinds, or
one found in a compile cache filled before them (JAX leaves metadata out
of the cache's key).
"""

import json
import os
import re
import sys
import time

from harness import xplane_kinds
from readers import scope_time, trace_program

NONE = "*none"


def reduced_of(ctx):
    """The run's reduction by kind; read once, then kept in ``ctx``."""
    if "kind_times" not in ctx:
        ctx["kind_times"] = {}
        work = scope_time.work_dir(ctx)
        try:
            path = xplane_kinds.find_xplane(os.path.join(work, "trace"))
        except FileNotFoundError:
            return ctx["kind_times"]
        t0 = time.monotonic()
        ctx["kind_times"] = reduced = xplane_kinds.reduce_file(path)
        took = time.monotonic() - t0
        with open(os.path.join(work, "kinds.json"), "w",
                  encoding="utf-8") as f:
            json.dump(reduced, f, indent=1)
        steps = xplane_kinds.decode_steps(
            reduced, ctx["conf"]["serving"].get("generate.decode_chunk"))
        print("device time by layer kind (harness/xplane_kinds.py, "
              f"{took:.1f} s on the host):\n"
              + xplane_kinds.table(reduced, steps), file=sys.stderr,
              flush=True)
    return ctx["kind_times"]


def read(ctx, program: str, kinds, exclude: str = "", per=1):
    rows = [
        row for name, row in reduced_of(ctx).items()
        if re.search(program, name)
        and not (exclude and re.search(exclude, name))
    ]
    if not rows:
        return None
    row = max(rows, key=lambda r: r["executions"])
    named = {k: r["self_s"] + r["holes_s"] for k, r in row["kinds"].items()
             if k != xplane_kinds.NO_KIND}
    if not named:
        return None
    seconds = sum(named.get(k, 0.0) for k in kinds if k != NONE)
    if NONE in kinds:
        seconds += row["median_s"] - sum(named.values())
    return 1e3 * seconds / trace_program.per_value(ctx, per)
