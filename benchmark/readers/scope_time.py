"""Device time one execution of a program spends under the named DEVICE
SCOPES, in ms, divided by ``per`` as ``trace_program`` divides.

``program`` / ``exclude`` choose the program as ``trace_program`` does
(among the names that match, the one that ran most often).  ``scopes`` is
a list of names of ``docqa_tpu/ops/scopes.py``; ``"*rest"`` stands for
the execution's time minus the self time under EVERY scope the trace
holds — ops under no scope, and holes between ops — so the groups of a
program's metrics add up to its ``trace_program`` time.

The trace is read where ``harness/child.py`` left it
(``.benchmark_work/<cell>/trace``) by ``harness/xplane_scopes.py`` —
standard library, so it runs here, in the parent, ONCE per run: the
result is kept in ``ctx``, written as ``scopes.json`` beside the trace,
and its tables printed once on standard error.  It makes one whole pass
over the slice's op line (2–4 s of a 6 s slice, PERF.md §3).

None where the slice holds no such program, or where no op of it carries
a scope (a program compiled before the scopes, or found in a compile
cache filled before them: JAX leaves metadata out of the cache's key).
"""

import json
import os
import re
import sys

from harness import xplane_scopes
from readers import trace_program

REST = "*rest"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def work_dir(ctx) -> str:
    return os.path.join(ROOT, ".benchmark_work", ctx["cell"]["name"])


def reduced_of(ctx):
    """The run's reduction by scope; read once, then kept in ``ctx``."""
    if "scope_times" not in ctx:
        ctx["scope_times"] = {}
        work = work_dir(ctx)
        try:
            path = xplane_scopes.find_xplane(os.path.join(work, "trace"))
        except FileNotFoundError:
            return ctx["scope_times"]
        ctx["scope_times"] = reduced = xplane_scopes.reduce_file(path)
        with open(os.path.join(work, "scopes.json"), "w",
                  encoding="utf-8") as f:
            json.dump(reduced, f, indent=1)
        print("device time by scope (harness/xplane_scopes.py):\n"
              + xplane_scopes.table(reduced), file=sys.stderr, flush=True)
    return ctx["scope_times"]


def read(ctx, program: str, scopes, exclude: str = "", per=1):
    rows = [
        row for name, row in reduced_of(ctx).items()
        if re.search(program, name)
        and not (exclude and re.search(exclude, name))
    ]
    if not rows:
        return None
    row = max(rows, key=lambda r: r["executions"])
    named = {s: t for s, t in row["scopes"].items()
             if s != xplane_scopes.NO_SCOPE}
    if not named:
        return None
    seconds = sum(named.get(s, 0.0) for s in scopes if s != REST)
    if REST in scopes:
        seconds += row["median_s"] - sum(named.values())
    return 1e3 * seconds / trace_program.per_value(ctx, per)
