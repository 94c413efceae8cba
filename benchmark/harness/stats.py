"""Percentiles and token-time arithmetic (stdlib only; the benchmark's own
copy, so no PR that claims a gain can change how a number is reduced)."""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between
    closest ranks (numpy's default).  NaN for no values."""
    xs = sorted(values)
    if not xs:
        return math.nan
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


BURST_GAP_S = 0.02  # deltas closer than this came in one delivery


def credited_tokens(sent: float, delta_times: Sequence[float],
                    t0: float, t1: float) -> float:
    """Tokens of one request credited to [t0, t1) by WHEN THEY WERE MADE.

    The server delivers a decode chunk at a time (16 tokens in one burst
    every few hundred ms), so counting arrivals would swing a 30 s window
    by a whole chunk of every live request at each edge.  Each delivery's
    tokens were produced over the interval since the request's previous
    delivery (for the first: since it was sent, but no longer than the
    request's usual interval); they are credited uniformly over it, and the
    part of the interval inside the window counts."""
    bursts: List[List[float]] = []
    for t in delta_times:
        if bursts and t - bursts[-1][-1] < BURST_GAP_S:
            bursts[-1].append(t)
        else:
            bursts.append([t])
    if not bursts:
        return 0.0
    ends = [b[-1] for b in bursts]
    gaps = sorted(b - a for a, b in zip(ends, ends[1:]))
    usual = gaps[len(gaps) // 2] if gaps else ends[0] - sent
    total, prev = 0.0, max(sent, ends[0] - usual)
    for burst, end in zip(bursts, ends):
        span = end - prev
        if span <= 0:
            inside = 1.0 if t0 <= end < t1 else 0.0
        else:
            inside = max(0.0, min(end, t1) - max(prev, t0)) / span
        total += len(burst) * inside
        prev = end
    return total


def ttft_ms(sent: float, delta_times: Sequence[float]) -> Optional[float]:
    """Send -> first delta, in ms; None for a request that delivered
    nothing."""
    if not delta_times:
        return None
    return (delta_times[0] - sent) * 1e3


def tpot_ms(delta_times: Sequence[float]) -> Optional[float]:
    """(last delta - first delta) / tokens delivered after the first, in
    ms.  Deltas land a decode chunk at a time, so only this whole-request
    mean is meaningful; a per-gap percentile would read chunk boundaries.
    None below two tokens."""
    if len(delta_times) < 2:
        return None
    return (delta_times[-1] - delta_times[0]) * 1e3 / (len(delta_times) - 1)


def histogram_delta(before: Dict, after: Dict, name: str):
    """(count, sum) of a ``/api/metrics`` histogram between two snapshots:
    ``count`` and ``mean`` there are lifetime totals, so their products
    subtract exactly."""
    def total(snap):
        h = (snap.get("histograms") or {}).get(name)
        if not h or not h.get("count"):
            return 0, 0.0
        return int(h["count"]), float(h["mean"]) * int(h["count"])

    c0, s0 = total(before)
    c1, s1 = total(after)
    return c1 - c0, s1 - s0


def summarize(values: List[float]) -> Dict[str, float]:
    return {
        "n": len(values),
        "p50": percentile(values, 50),
        "p95": percentile(values, 95),
        "max": max(values) if values else math.nan,
    }
