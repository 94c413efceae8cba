#!/usr/bin/env python3
"""A program's device time by DEVICE SCOPE, from a profiler trace.

    python3 benchmark/harness/xplane_scopes.py <logdir or .xplane.pb>

prints, per program of the trace, the table this module reduces it to —
the operator's use, for the directory ``POST /api/profiler/stop`` returns.

The program opens ``jax.named_scope("dq.<name>")`` where its work is
(``docqa_tpu/ops/scopes.py``: the closed vocabulary); XLA keeps the scope
in each op's ``op_name``.  Where a trace carries it (step 0 of PR 40, TPU
v5e, jax 0.9.0): NOT in an event's name (the HLO line, printed without
its ``metadata={…}``) and in no stat of the event, but in the ``tf_op``
stat of the event's METADATA — ``jit(f)/while/body/closed_call/dq.attend/
dot_general:`` — which ``jax.profiler.ProfileData`` does not hand out
(its ``event.stats`` are the event's own three).  So this module reads the
file itself: :func:`read_xspace` is a reader of the protobuf wire format
for the dozen fields of ``XSpace`` that matter (standard library, no JAX:
the benchmark's parent may import it), and hands back objects of the shape
``ProfileData`` has — ``planes[].lines[].events[]`` with ``name``,
``start_ns``, ``duration_ns`` and ``stats`` — with the metadata's stats
among an event's.  :func:`reduce_scopes` takes anything of that shape, and
looks for the scope in the event's name first and in its string stats
second, so a trace that carries it elsewhere, a ``ProfileData`` of a later
JAX and a test's fake all reduce the same way.

The reduction, on chip 0: for each program of the ``XLA Modules`` line,
the SELF device time of every op of the ``XLA Ops`` line that lies inside
an execution — an op that holds others (a ``while``, a ``conditional``, a
``call``) is charged only what no op nested in its interval covers —
keyed by (scope, stem).  The scope is the innermost ``dq.<name>`` of the
op's ``op_name``.  A fusion has ONE ``op_name``, the one XLA kept for it
(its root's, or the dot's of an output fusion): a norm that XLA fuses
into the residual add or the matmul before it is charged to THAT op's
scope, so a few microseconds move between neighbours at every scope edge
and no time is counted twice or lost.  An op the compiler put in carries
no ``op_name`` at all (``slice-start`` / ``slice-done``, ``copy-start`` /
``copy-done``, a ``copy`` to another layout, ``ConcatBitcast``: a
weight's way into fast memory): it is charged to the scope of the first
later op of the execution that takes its result, through further such ops
— the wait for a weight's slice to the matmul that waits.  What is left
under no scope goes to ``-``: the loop itself, copies at the head of a
chunk, and prefetches a loop's iteration makes for the NEXT one (their
taker is reached through the loop's carry, which names nothing).  The
stem is the op's name without its number (``convert_multiply_fusion``,
``slice-done``, ``fusion``, ``custom-call``).

Per program: the variant (fingerprint) that ran most often, over its
WHOLE executions, the MEDIAN execution's seconds per scope, its holes
(execution − union of ops) and its 20 largest (scope, stem) rows.  One
execution stands for the program so that the parts add up to a duration
the trace really holds; only the ops inside those executions are reduced
(the whole file is decoded once: 6–8 s for the 1.5 M ops of a 6 s slice).
The slice's edge cuts an execution without a mark (its event is shortened
to the slice), so the first execution to start and the last to end on
the line are taken as cut and left out.
"""

from __future__ import annotations

import bisect
import os
import re
import struct
import sys
from types import SimpleNamespace
from typing import Dict, List, Optional, Tuple

# as a command this file's own directory leads sys.path, not benchmark/
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import xplane  # noqa: E402  (standard library at import)
from harness.xplane import (  # noqa: E402
    DEVICE_PLANE,
    MODULES_LINE,
    OPS_LINE,
    program_name,
)

PREFIX = "dq."
SCOPE = re.compile(r"dq\.([a-z_]+)")
NO_SCOPE = "-"
_OPERAND = re.compile(r"%([^\s,(){}=]+)")
TOP_ROWS = 20
LINES_WANTED = (OPS_LINE, MODULES_LINE)
_OP_NAME = re.compile(r"^%?([^\s=(]+)")
_NUMBER = re.compile(r"(\.clone|\.remat\d*|[.\-_]\d+)+$")


# ---- the file: protobuf wire format, the fields of XSpace that matter ----

def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, start: int, end: int):
    """(field number, wire type, value, ...) of one message: a varint's
    value, or the (start, end) of a length-delimited field."""
    i = start
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield key >> 3, wire, value


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _text(buf, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_values(buf, spans):
    """The value's fields of each entry of a protobuf map (key 1, value
    2), entry by entry."""
    for sp in spans:
        for f, _w, v in _fields(buf, *sp):
            if f == 2:
                yield _fields(buf, *v)


def _stat(buf, span, stat_names: Dict[int, str]):
    """One XStat -> (name, value): strings as text (a ``ref_value`` is
    the name of another stat's metadata), numbers as they are."""
    key, value = None, None
    for f, _w, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f in (5, 6):
            value = _text(buf, v)
        elif f == 7:
            value = stat_names.get(v, "")
        elif f in (3, 4):
            value = _signed(v)
        elif f == 2:
            value = struct.unpack("<d", v)[0]
    return stat_names.get(key, str(key)), value


class Event:
    __slots__ = ("name", "start_ns", "duration_ns", "stats")

    def __init__(self, name, start_ns, duration_ns, stats):
        self.name, self.start_ns = name, start_ns
        self.duration_ns, self.stats = duration_ns, stats


def _plane(buf, span):
    """One XPlane, if it is a device's: its two lines."""
    name, line_spans, meta_spans, stat_spans = "", [], [], []
    for f, _w, v in _fields(buf, *span):
        if f == 2:
            name = _text(buf, v)
        elif f == 3:
            line_spans.append(v)
        elif f == 4:
            meta_spans.append(v)
        elif f == 5:
            stat_spans.append(v)
    if not DEVICE_PLANE.match(name):
        return None
    stat_names: Dict[int, str] = {}
    for entry in _map_values(buf, stat_spans):  # XStatMetadata
        found = {f: v for f, _w, v in entry if f in (1, 2)}
        stat_names[found.get(1)] = _text(buf, found[2]) if 2 in found else ""
    metadata: Dict[int, Tuple[str, list]] = {}
    for entry in _map_values(buf, meta_spans):  # XEventMetadata
        mid, mname, stats = None, "", []
        for f, _w, v in entry:
            if f == 1:
                mid = v
            elif f == 2:
                mname = _text(buf, v)
            elif f == 5:
                stats.append(_stat(buf, v, stat_names))
        metadata[mid] = (mname, stats)
    lines = [_line(buf, a, b, metadata) for a, b in line_spans]
    return SimpleNamespace(name=name, lines=[ln for ln in lines if ln])


def _line(raw: bytes, i: int, end: int, metadata):
    """One XLine of ``raw[i:end]``, if it is one of the two wanted.  The
    hot loop of the file (1.5 M events in a 6 s slice of ``rag_closed``):
    one pass, varints decoded in place, an event's own stats skipped."""
    name, t0_ns, events, unknown = None, 0, [], ("", [])
    while i < end:
        key, i = _varint(raw, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(raw, i)
            if key == 24:  # XLine.timestamp_ns = 3
                t0_ns = _signed(value)
            continue
        if wire != 2:
            i += 8 if wire == 1 else 4
            continue
        n, i = _varint(raw, i)
        stop = i + n
        if key == 18:  # XLine.name = 2: before the events, as written
            name = raw[i:stop].decode("utf-8", "replace")
            if name not in LINES_WANTED:
                return None
        if key != 34:  # XLine.events = 4
            i = stop
            continue
        mid = offset_ps = duration_ps = 0
        while i < stop:
            k = raw[i]
            i += 1
            if k & 7 == 0:
                v = shift = 0
                while True:
                    b = raw[i]
                    i += 1
                    v |= (b & 0x7F) << shift
                    if b < 0x80:
                        break
                    shift += 7
                if k == 8:  # XEvent.metadata_id = 1
                    mid = v
                elif k == 16:  # .offset_ps = 2
                    offset_ps = v
                elif k == 24:  # .duration_ps = 3
                    duration_ps = v
            elif k & 7 == 2:
                m, i = _varint(raw, i)
                i += m
            else:
                i += 8 if k & 7 == 1 else 4
        mname, stats = metadata.get(mid, unknown)
        events.append(Event(mname, offset_ps * 1e-3, duration_ps * 1e-3,
                            stats))
    if name not in LINES_WANTED:
        return None
    for e in events:
        e.start_ns += t0_ns
    return SimpleNamespace(name=name, events=events)


def read_xspace(path: str):
    """The device planes of an ``.xplane.pb``, their two lines, every
    event with the stats of its metadata."""
    with open(path, "rb") as f:
        buf = f.read()
    planes = (_plane(buf, v) for f_no, _w, v in _fields(buf, 0, len(buf))
              if f_no == 1)
    return SimpleNamespace(planes=[p for p in planes if p is not None])


def find_xplane(path: str) -> str:
    """``path`` itself, or the newest trace under a profiler's logdir."""
    return path if os.path.isfile(path) else xplane.find_xplane(path)


# ---- the reduction ---------------------------------------------------------

def op_name(event_name: str) -> str:
    """``%slice-done.41 = s8[…] async-done(…)`` -> ``slice-done.41``."""
    m = _OP_NAME.match(event_name)
    return m.group(1) if m else event_name


def op_stem(event_name: str) -> str:
    """``%convert_multiply_fusion.12 = bf16[…] fusion(…)`` ->
    ``convert_multiply_fusion``."""
    name = op_name(event_name)
    return _NUMBER.sub("", name) or name


def scope_of(event) -> str:
    """The innermost ``dq.<name>`` the event carries: in its name, else
    in a string stat (``tf_op`` on a TPU); ``-`` without one."""
    found = SCOPE.findall(event.name)
    if not found:
        for _key, value in (event.stats or ()):
            if isinstance(value, str) and PREFIX in value:
                found = SCOPE.findall(value)
                if found:
                    break
    return found[-1] if found else NO_SCOPE


def _by_consumer(ops: List[list]) -> None:
    """Charge each op of one execution that carries no scope to the scope
    of the first later op that takes its result — through further ops
    without one (``copy-start`` -> ``copy-done`` -> the fusion).  ``ops``:
    [start, end, scope, stem, HLO line], sorted by start; in place.  Ops
    that wait for their taker travel as one list, the smaller joined to
    the larger, so a program without any scope costs no more."""
    waiting: Dict[str, List[list]] = {}
    for op in ops:
        line = op[4]
        fed: List[list] = []
        if waiting:
            at = line.find("=")
            for operand in set(_OPERAND.findall(line, max(at, 0))):
                group = waiting.pop(operand, None)
                if group is not None:
                    if len(group) > len(fed):
                        group, fed = fed, group
                    fed.extend(group)
        if op[2] != NO_SCOPE:
            for producer in fed:
                producer[2] = op[2]
        else:  # wait, with whoever fed this op, for ITS taker
            fed.append(op)
            waiting[op_name(line)] = fed


def _self_times(ops: List[list], a: float, b: float):
    """Self seconds by (scope, stem) of the ops inside one execution
    ``[a, b]`` (sorted by start, an op that holds others before them), and
    the seconds of it that some op covers."""
    rows: Dict[Tuple[str, str], float] = {}
    # the ops open at this point: [end, covered until, covered, key,
    # start]; the execution itself is the root
    root = [b, a, 0.0, None, a]
    stack = [root]

    def close(top):
        self_s = (top[0] - top[4]) - top[2]
        rows[top[3]] = rows.get(top[3], 0.0) + max(self_s, 0.0)

    for start, end, scope, stem, _line in ops:
        while len(stack) > 1 and stack[-1][0] <= start:
            close(stack.pop())
        parent = stack[-1]
        lo, hi = max(start, parent[1]), min(end, parent[0])
        if hi > lo:  # what of its parent this op covers, counted once
            parent[2] += hi - lo
            parent[1] = hi
        stack.append([end, start, 0.0, (scope, stem), start])
    while len(stack) > 1:
        close(stack.pop())
    return rows, root[2]


def reduce_scopes(profile, top_rows: int = TOP_ROWS) -> Dict:
    """``profile``: anything with ``planes[].lines[].events[]`` whose
    events have ``name``, ``start_ns``, ``duration_ns`` and ``stats``
    (pairs) — :func:`read_xspace`'s, a ``ProfileData``, a test's fake.

    Returns, for chip 0, ``{program: {variants, executions, whole,
    median_s, scopes: {scope: s}, holes_s, rows: [[scope, stem, s], …]}}``;
    a program with no whole execution is left out."""
    planes = sorted(
        (p for p in profile.planes if DEVICE_PLANE.match(p.name)),
        key=lambda p: p.name,
    )
    lines = {ln.name: ln for ln in planes[0].lines} if planes else {}
    if OPS_LINE not in lines or MODULES_LINE not in lines:
        return {}
    modules = sorted(
        ((e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name)
         for e in lines[MODULES_LINE].events),
    )
    if not modules:
        return {}
    # the slice's edge cuts an execution without a mark: its event is
    # shortened to the slice.  The first to start and the last to end are
    # taken as cut.
    cut = {modules[0], max(modules, key=lambda m: m[1])}
    by_program: Dict[str, Dict[str, list]] = {}
    for m in modules:
        by_program.setdefault(program_name(m[2]), {}).setdefault(
            m[2], []).append(m)
    # one execution stands for a program: the median one of the variant
    # that ran most often
    chosen = {}
    for program, variants in by_program.items():
        runs = max(variants.values(), key=len)
        whole = sorted((m[1] - m[0], m) for m in runs if m not in cut)
        if whole:
            chosen[program] = (
                whole[(len(whole) - 1) // 2][1], len(variants), len(runs),
                len(whole))
    spans = sorted((m[0], m[1], program)
                   for program, (m, *_n) in chosen.items())
    starts = [sp[0] for sp in spans]
    inside: Dict[str, List[list]] = {program: [] for program in chosen}
    named: Dict[Tuple[str, str], Tuple[str, str]] = {}
    for e in lines[OPS_LINE].events:
        start = e.start_ns * 1e-9
        at = bisect.bisect_right(starts, start) - 1
        if at < 0 or start >= spans[at][1]:
            continue
        key = (spans[at][2], e.name)  # every event of an op: look once
        found = named.get(key)
        if found is None:
            found = named[key] = (scope_of(e), op_stem(e.name))
        inside[spans[at][2]].append(
            [start, start + e.duration_ns * 1e-9, found[0], found[1], e.name])
    out = {}
    for program, (m, n_variants, n_runs, n_whole) in chosen.items():
        ops = sorted(inside[program], key=lambda o: (o[0], -o[1]))
        _by_consumer(ops)
        rows, covered = _self_times(ops, m[0], m[1])
        scopes: Dict[str, float] = {}
        for (scope, _stem), s in rows.items():
            scopes[scope] = scopes.get(scope, 0.0) + s
        top = sorted(rows.items(), key=lambda kv: -kv[1])[:top_rows]
        out[program] = {
            "variants": n_variants,
            "executions": n_runs,
            "whole": n_whole,
            "median_s": m[1] - m[0],
            "scopes": scopes,
            "holes_s": max(m[1] - m[0] - covered, 0.0),
            "rows": [[scope, stem, s] for (scope, stem), s in top],
        }
    return out


def reduce_file(path: str) -> Dict:
    return reduce_scopes(read_xspace(find_xplane(path)))


def table(reduced: Dict, min_s: float = 1e-4) -> str:
    """The reduction as text: per program of at least ``min_s`` its
    scopes and its largest rows, in ms of the median execution."""
    out = []
    for program, row in sorted(
            reduced.items(), key=lambda kv: -kv[1]["median_s"]):
        total = row["median_s"]
        if total < min_s:
            continue
        out.append(
            f"{program}: the median of {row['whole']} whole executions "
            f"({row['executions']} in the slice, {row['variants']} "
            f"variant(s)), {1e3 * total:.3f} ms"
        )
        parts = sorted(row["scopes"].items(), key=lambda kv: -kv[1])
        for scope, s in parts + [("holes", row["holes_s"])]:
            out.append(f"  {scope:<12} {1e3 * s:10.3f} ms "
                       f"{100 * s / total:5.1f} %")
        for scope, stem, s in row["rows"]:
            out.append(f"    {scope:<12} {stem:<42} {1e3 * s:10.3f} ms")
    return "\n".join(out)


def main(argv: List[str]) -> int:
    if len(argv) != 1:
        print("usage: xplane_scopes.py <profiler logdir or .xplane.pb>",
              file=sys.stderr)
        return 2
    print(table(reduce_file(argv[0]))
          or "no device plane with a whole execution in the trace")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
