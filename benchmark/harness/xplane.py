"""From a profiler trace (``*.xplane.pb``) to numbers.

The only reader of a trace in this repo; kept with the benchmark so every
PR reduces a trace the same way.  Needs nothing but JAX's own
``ProfileData``.

Layout of a TPU trace as JAX writes it: one plane per chip
(``/device:TPU:<n>``) whose line ``XLA Modules`` has one event per
executed program (named ``<jit name>(<fingerprint>)``) and whose line
``XLA Ops`` has one event per operation; one plane ``/host:CPU`` with a
line per host thread, holding the runtime's own events, the Python
tracer's frames (names starting with ``$``) and every
``jax.profiler.TraceAnnotation`` the program opened.

* ``busy_s``    union of the op intervals of a chip, averaged over chips
* ``window_s``  first event start to last event end over all planes
* programs      per program name: count, total and median seconds (one
                chip's ``XLA Modules`` line)
* collectives   device time of collective ops that ran inside a program
* idle gaps     the gaps of chip 0's op timeline, each named after the
                shortest host event open for at least half of it
* timeline      chip 0's programs and the host's events of at least
                ``TIMELINE_MIN_S``, in order of their start: what ran
                between a request's arrival and its first token
"""

from __future__ import annotations

import glob
import os
import re
import statistics
from typing import Dict, Iterable, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all",
    re.I,
)
_FINGERPRINT = re.compile(r"\(\d+\)$")
MIN_GAP_S = 50e-6  # shorter holes between ops are launch latency, not idling
MAX_NAMED_GAPS = 200
MAX_NAME = 160  # an op's name in a trace is its whole HLO line
TIMELINE_MIN_S = 5e-3
MAX_TIMELINE = 240

Interval = Tuple[float, float]  # (start_s, end_s)


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(
        os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def union_length(intervals: Iterable[Interval]) -> float:
    """Total length covered by possibly overlapping intervals."""
    total, end = 0.0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def gaps_of(intervals: Iterable[Interval], min_gap: float) -> List[Interval]:
    """Holes of at least ``min_gap`` between the merged intervals."""
    out, end = [], None
    for a, b in sorted(intervals):
        if end is not None and a - end >= min_gap:
            out.append((end, a))
        end = b if end is None else max(end, b)
    return out


def _events(line) -> List[Tuple[str, float, float]]:
    return [
        (e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
        for e in line.events
    ]


def program_name(event_name: str) -> str:
    return _FINGERPRINT.sub("", event_name)


def reduce_profile(profile, timeline_min_s: float = TIMELINE_MIN_S) -> Dict:
    """``profile``: a ``jax.profiler.ProfileData``."""
    device_planes, host_events = [], []
    layout: Dict[str, List[str]] = {}
    lo, hi = None, None
    for plane in profile.planes:
        is_device = bool(DEVICE_PLANE.match(plane.name))
        lines = {}
        for line in plane.lines:
            evs = _events(line)
            if is_device or len(evs) > 0:
                layout.setdefault(plane.name, []).append(
                    f"{line.name} ({len(evs)})"
                )
            if not evs:
                continue
            lo = min(e[1] for e in evs) if lo is None else min(lo, min(e[1] for e in evs))
            hi = max(e[2] for e in evs) if hi is None else max(hi, max(e[2] for e in evs))
            if is_device:
                lines[line.name] = evs
            elif plane.name.startswith("/host:"):
                host_events.extend(
                    e for e in evs if not e[0].startswith("$") and e[2] > e[1]
                )
        if is_device and OPS_LINE in lines:
            device_planes.append((plane.name, lines))
    if not device_planes:
        return {"devices": 0, "busy_s": 0.0, "window_s": 0.0, "programs": {},
                "collective_s": {}, "device_ops": [], "idle_gaps": [],
                "timeline": [], "layout": layout}
    device_planes.sort()
    window = hi - lo
    busy = [union_length((a, b) for _, a, b in lines[OPS_LINE])
            for _, lines in device_planes]

    # programs and the collectives inside them, on the first chip (every
    # chip of a mesh runs the same programs)
    first = device_planes[0][1]
    programs: Dict[str, Dict] = {}
    collective_s: Dict[str, float] = {}
    modules = sorted(first.get(MODULES_LINE, []), key=lambda e: e[1])
    for name, a, b in modules:
        row = programs.setdefault(program_name(name), {"durations": []})
        row["durations"].append(b - a)
    coll = sorted(
        ((a, b) for name, a, b in first[OPS_LINE] if COLLECTIVE.search(name))
    )
    i = 0
    for name, a, b in modules:
        while i < len(coll) and coll[i][1] <= a:
            i += 1
        j, inside = i, 0.0
        while j < len(coll) and coll[j][0] < b:
            inside += min(coll[j][1], b) - max(coll[j][0], a)
            j += 1
        key = program_name(name)
        collective_s[key] = collective_s.get(key, 0.0) + inside
    for row in programs.values():
        d = row.pop("durations")
        row.update(count=len(d), total_s=sum(d), median_s=statistics.median(d))

    by_op: Dict[str, float] = {}
    for name, a, b in first[OPS_LINE]:
        by_op[name] = by_op.get(name, 0.0) + (b - a)
    device_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]

    by_host: Dict[str, float] = {}
    host_events.sort(key=lambda e: e[1])
    gaps = sorted(
        gaps_of(((a, b) for _, a, b in first[OPS_LINE]), MIN_GAP_S),
        key=lambda g: g[0] - g[1],
    )
    if len(gaps) > MAX_NAMED_GAPS:  # name the longest; lump the rest
        by_host["shorter gaps, not attributed"] = sum(
            b - a for a, b in gaps[MAX_NAMED_GAPS:]
        )
    for a, b in gaps[:MAX_NAMED_GAPS]:
        # the most specific host event that was open for at least half of
        # the gap (the shortest such); failing that, the longest overlap
        best, best_len = None, None
        fallback, fallback_overlap = "nothing recorded on the host", 0.0
        for name, ha, hb in host_events:
            if ha >= b:
                break
            overlap = min(hb, b) - max(ha, a)
            if overlap >= 0.5 * (b - a) and (best is None or hb - ha < best_len):
                best, best_len = name, hb - ha
            if overlap > fallback_overlap:
                fallback, fallback_overlap = name, overlap
        best = best or fallback
        by_host[best] = by_host.get(best, 0.0) + (b - a)
    idle_gaps = sorted(by_host.items(), key=lambda kv: -kv[1])[:10]
    timeline = sorted(
        [(a - lo, b - a, "device", program_name(n)) for n, a, b in modules
         if b - a >= timeline_min_s]
        + [(a - lo, b - a, "host", n) for n, a, b in host_events
           if b - a >= timeline_min_s]
    )[:MAX_TIMELINE]
    return {
        "devices": len(device_planes),
        "busy_s": sum(busy) / len(busy),
        "window_s": window,
        "programs": programs,
        "collective_s": collective_s,
        "device_ops": [[n[:MAX_NAME], s] for n, s in device_ops],
        "idle_gaps": [[n[:MAX_NAME], s] for n, s in idle_gaps],
        "timeline": [[round(t, 4), round(d, 4), where, n[:60]]
                     for t, d, where, n in timeline],
        "layout": {k: v[:12] for k, v in layout.items() if k.startswith("/device")},
    }


def reduce_file(path: str) -> Dict:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path))
