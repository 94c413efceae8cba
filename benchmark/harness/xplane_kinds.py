#!/usr/bin/env python3
"""A program's device time by LAYER KIND, scope and op, from a profiler
trace.

    python3 benchmark/harness/xplane_kinds.py <logdir or .xplane.pb> [steps]

prints, per program of the trace, the two tables this module reduces it
to — the operator's use, for the directory ``POST /api/profiler/stop``
returns.  ``steps``: the steps of a decode chunk (``generate.decode_chunk``);
a decode program then reads in ms a STEP.

``xplane_scopes.py`` reads a program by PHASE: the innermost
``dq.<name>`` of an op.  The stack of several mixer kinds
(``docqa_tpu/models/hybrid.py``) also opens ``jax.named_scope("dk.<kind>")``
around each half of a layer, outside the phase scopes
(``docqa_tpu/ops/scopes.py:layer_kind``), so an op's ``op_name`` reads
``…/dk.window/dq.attend/…`` and the 24 window layers' attention no longer
hides in the 8 global layers'.  This module sums the same ops by
**(kind, scope, stem)**: kind the innermost ``dk.<name>`` (``-`` without
one: ``embed``, ``head``, ``sample``, the rope tables, the loop), scope
``xplane_scopes.scope_of``'s, read independently of each other.

Everything else is that module's, imported and not copied: the file
reader, the stem, the chip (0), and the execution that stands for a
program — ``reduce_scopes`` is asked for it, on the ``XLA Modules`` line
alone.  An op the compiler put in (``slice-done``, ``copy-done``, a layout
``copy``: no ``op_name``, so neither kind nor scope) takes kind AND scope
of the op that takes its result, by ``_by_consumer``'s rule; an op with a
scope and no kind has its kind already — none — and keeps it.

New here, the HOLES by kind.  Between two small ops the op line is empty
for a microsecond or so; inside a chunk's ``while`` that time is the
loop's own (``-`` in the by-scope table).  A hole between two consecutive
ops of the SAME kind, inside whatever op holds both, is charged to that
kind — kept apart from its ops' self time (``holes_s``), beside the count
of op events the execution held under the kind, so that "64 events of
1.5 µs and 1 µs apart" reads off the table.  Every other hole stays where
it was: the holding op's self time, or the execution's holes (``-``).
Self times and holes add up to the execution.
"""

from __future__ import annotations

import bisect
import os
import re
import sys
from types import SimpleNamespace
from typing import Dict, List, Tuple

# as a command this file's own directory leads sys.path, not benchmark/
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import xplane_scopes  # noqa: E402  (standard library at import)
from harness.xplane import (  # noqa: E402
    DEVICE_PLANE,
    MODULES_LINE,
    OPS_LINE,
    program_name,
)
from harness.xplane_scopes import (  # noqa: E402
    NO_SCOPE,
    _by_consumer,
    find_xplane,
    op_stem,
    read_xspace,
    scope_of,
)

PREFIX = "dk."
KIND = re.compile(r"dk\.([a-z_]+)")
NO_KIND = NO_SCOPE  # ``_by_consumer`` hands on whatever is not this
_OUTSIDE = ""  # while ``_by_consumer`` runs: a scope and no kind
TOP_ROWS = 30


def kind_of(event) -> str:
    """The innermost ``dk.<name>`` the event carries: in its name, else
    in a string stat (``tf_op`` on a TPU); ``-`` without one."""
    found = KIND.findall(event.name)
    if not found:
        for _key, value in (event.stats or ()):
            if isinstance(value, str) and PREFIX in value:
                found = KIND.findall(value)
                if found:
                    break
    return found[-1] if found else NO_KIND


def _own_kind(kind: str, scope: str) -> str:
    """What ``_by_consumer`` is to see as an op's kind: its own; ``-``
    (it waits for its taker's) only where it has no scope either — an op
    with a scope and no kind (``embed``) lies outside every kind."""
    if kind == NO_KIND and scope != NO_SCOPE:
        return _OUTSIDE
    return kind


def chosen_executions(plane: str, modules) -> Dict[str, Tuple]:
    """``{program: (start s, end s, xplane_scopes' row)}``: the execution
    ``reduce_scopes`` lets stand for each program.  Asked of it, on the
    modules' line alone (no op: nothing to sum, milliseconds), and found
    again by its length, which is computed here as it is there."""
    picked = xplane_scopes.reduce_scopes(SimpleNamespace(planes=[
        SimpleNamespace(name=plane, lines=[
            SimpleNamespace(name=MODULES_LINE, events=modules),
            SimpleNamespace(name=OPS_LINE, events=[])])]))
    out: Dict[str, Tuple] = {}
    for e in sorted(modules, key=lambda e: e.start_ns):
        a, b = e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9
        program = program_name(e.name)
        row = picked.get(program)
        if row and program not in out and b - a == row["median_s"]:
            out[program] = (a, b, row)
    return out


def _kind_times(ops: List[tuple], a: float, b: float):
    """Of the ops inside one execution ``[a, b]`` — (start, end, kind,
    scope, stem), sorted by start, an op that holds others before them —:
    self seconds by (kind, scope, stem), hole seconds by kind and op
    events by kind.  The hole before an op is its kind's where the op
    before it, inside the same holding op, was of that kind; what is left
    of the execution under no op and in no such hole is ``-``'s."""
    rows: Dict[Tuple[str, str, str], float] = {}
    holes: Dict[str, float] = {}
    events: Dict[str, int] = {}
    # the ops open at this point: [end, covered until, covered, key,
    # start, kind of the last op inside]; the execution is the root
    root = [b, a, 0.0, None, a, None]
    stack = [root]

    def close(top):
        self_s = (top[0] - top[4]) - top[2]
        rows[top[3]] = rows.get(top[3], 0.0) + max(self_s, 0.0)

    for start, end, kind, scope, stem in ops:
        while len(stack) > 1 and stack[-1][0] <= start:
            close(stack.pop())
        parent = stack[-1]
        lo, hi = max(start, parent[1]), min(end, parent[0])
        if kind != NO_KIND and kind == parent[5] and lo > parent[1]:
            holes[kind] = holes.get(kind, 0.0) + lo - parent[1]
            parent[2] += lo - parent[1]
        if hi > lo:  # what of its parent this op covers, counted once
            parent[2] += hi - lo
            parent[1] = hi
        parent[5] = kind
        events[kind] = events.get(kind, 0) + 1
        stack.append([end, start, 0.0, (kind, scope, stem), start, None])
    while len(stack) > 1:
        close(stack.pop())
    holes[NO_KIND] = holes.get(NO_KIND, 0.0) + max(b - a - root[2], 0.0)
    return rows, holes, events


def reduce_kinds(profile, top_rows: int = TOP_ROWS) -> Dict:
    """``profile``: what ``xplane_scopes.reduce_scopes`` takes.

    Returns, for chip 0, ``{program: {variants, executions, whole,
    median_s, kinds: {kind: {self_s, holes_s, events, scopes: {scope:
    s}}}, rows: [[kind, scope, stem, s], …]}}``; a program with no whole
    execution is left out."""
    planes = sorted(
        (p for p in profile.planes if DEVICE_PLANE.match(p.name)),
        key=lambda p: p.name,
    )
    lines = {ln.name: ln for ln in planes[0].lines} if planes else {}
    if OPS_LINE not in lines or MODULES_LINE not in lines:
        return {}
    chosen = chosen_executions(planes[0].name, lines[MODULES_LINE].events)
    spans = sorted((a, b, program) for program, (a, b, _r) in chosen.items())
    starts = [sp[0] for sp in spans]
    inside: Dict[str, List[tuple]] = {program: [] for program in chosen}
    named: Dict[Tuple[str, str], Tuple[str, str, str]] = {}
    for e in lines[OPS_LINE].events:
        start = e.start_ns * 1e-9
        at = bisect.bisect_right(starts, start) - 1
        if at < 0 or start >= spans[at][1]:
            continue
        key = (spans[at][2], e.name)  # every event of an op: look once
        found = named.get(key)
        if found is None:
            found = named[key] = (kind_of(e), scope_of(e), op_stem(e.name))
        inside[spans[at][2]].append(
            (start, start + e.duration_ns * 1e-9, found, e.name))
    out = {}
    for program, (a, b, picked) in chosen.items():
        ops = sorted(inside[program], key=lambda o: (o[0], -o[1]))
        # the two axes, each by the rule of today: a scope as the
        # by-scope table has it, a kind to the ops that have NEITHER
        scopes = [[s, e, f[1], f[2], line] for s, e, f, line in ops]
        kinds = [[s, e, _own_kind(*f[:2]), f[2], line]
                 for s, e, f, line in ops]
        _by_consumer(scopes)
        _by_consumer(kinds)
        rows, holes, events = _kind_times(
            [(s[0], s[1], k[2] or NO_KIND, s[2], s[3])
             for s, k in zip(scopes, kinds)], a, b)
        by_kind: Dict[str, Dict] = {}
        for kind in set(events) | set(holes):
            by_kind[kind] = {"self_s": 0.0, "holes_s": holes.get(kind, 0.0),
                             "events": events.get(kind, 0), "scopes": {}}
        for (kind, scope, _stem), s in rows.items():
            row = by_kind[kind]
            row["self_s"] += s
            row["scopes"][scope] = row["scopes"].get(scope, 0.0) + s
        top = sorted(rows.items(), key=lambda kv: -kv[1])[:top_rows]
        out[program] = {
            "variants": picked["variants"],
            "executions": picked["executions"],
            "whole": picked["whole"],
            "median_s": b - a,
            "kinds": by_kind,
            "rows": [[kind, scope, stem, s]
                     for (kind, scope, stem), s in top],
        }
    return out


def reduce_file(path: str) -> Dict:
    return reduce_kinds(read_xspace(find_xplane(path)))


def decode_steps(reduced: Dict, chunk) -> Dict[str, float]:
    """``{program: steps an execution makes}`` for :func:`table`: a
    decode program (and no prefill one) makes ``chunk``."""
    if not chunk:
        return {}
    return {name: float(chunk) for name in reduced
            if "decode" in name and "prefill" not in name}


def table(reduced: Dict, steps: Dict[str, float] = None,
          min_s: float = 1e-4) -> str:
    """The reduction as text, per program of at least ``min_s``: kind x
    scope with each kind's ops, holes and events, then the largest
    (kind, scope, stem) rows; in ms of the median execution, over
    ``steps[program]`` where that is given (a decode chunk: ms a step,
    events a step)."""
    out = []
    for program, row in sorted(
            reduced.items(), key=lambda kv: -kv[1]["median_s"]):
        total = row["median_s"]
        if total < min_s:
            continue
        per = (steps or {}).get(program, 1.0)
        out.append(
            f"{program}: the median of {row['whole']} whole executions "
            f"({row['executions']} in the slice, {row['variants']} "
            f"variant(s)), {1e3 * total:.3f} ms"
            + (f" = {per:g} steps of {1e3 * total / per:.3f} ms; below "
               "per step" if per != 1.0 else "")
        )
        kinds = sorted(
            row["kinds"].items(),
            key=lambda kv: -(kv[1]["self_s"] + kv[1]["holes_s"]))
        columns = sorted(
            {s for _k, r in kinds for s in r["scopes"]},
            key=lambda s: -sum(r["scopes"].get(s, 0.0) for _k, r in kinds))
        out.append("  " + f"{'kind':<10}" + "".join(
            f"{c:>12}" for c in columns + ["ops", "holes", "all", "%"])
            + f"{'events':>9}")
        for kind, r in kinds:
            cells = [r["scopes"].get(c, 0.0) for c in columns]
            cells += [r["self_s"], r["holes_s"], r["self_s"] + r["holes_s"]]
            out.append(
                "  " + f"{kind:<10}"
                + "".join(f"{1e3 * s / per:12.3f}" for s in cells)
                + f"{100 * cells[-1] / total:12.1f}"
                + f"{r['events'] / per:9.0f}")
        for kind, scope, stem, s in row["rows"]:
            out.append(f"    {kind:<10} {scope:<12} {stem:<42} "
                       f"{1e3 * s / per:10.3f} ms")
    return "\n".join(out)


def main(argv: List[str]) -> int:
    if len(argv) not in (1, 2):
        print("usage: xplane_kinds.py <profiler logdir or .xplane.pb> "
              "[steps of a decode chunk]", file=sys.stderr)
        return 2
    reduced = reduce_file(argv[0])
    chunk = float(argv[1]) if len(argv) == 2 else None
    print(table(reduced, decode_steps(reduced, chunk))
          or "no device plane with a whole execution in the trace")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
