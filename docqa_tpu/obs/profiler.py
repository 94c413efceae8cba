"""On-demand ``jax.profiler`` window + per-stage attribution analysis.

Two tools on top of the span recorder:

* :class:`ProfilerWindow` — start/stop a ``jax.profiler`` trace from
  the HTTP surface (``POST /api/profiler/start|stop``) for the rare
  deep-dive that needs XLA-level detail.  Strictly **jit-exterior**: it
  is only ever invoked from the HTTP layer / scripts, never from traced
  code (the jit-purity rule flags any profiler/span call that leaks into
  a jit root), and one window at a time (starting twice is an error, not
  a nested trace).
* :func:`attribution` — the everyday answer: fold a set of completed
  request timelines into a per-stage table (count / total / p50 / p95 /
  share of wall) with each stage classified **device** or **host** along
  the one-fetch-per-dispatch boundary the serving path already enforces:
  a span that blocks on the single device→host fetch of a dispatch
  (``serve_decode_chunk``, ``fused_query`` …) measures device execution;
  everything else is host time.  ``bench.py rag_load`` prints this
  table, and the "(unattributed)" row makes coverage gaps visible
  instead of silently summing to less than the wall.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, Iterable, List, Optional

from docqa_tpu.obs.export import coverage
from docqa_tpu.obs.spans import Trace, percentile_nearest_rank

# Stage → device/host classification along the one-fetch-per-dispatch
# boundary: a "device" span's wall time is dominated by
# blocking on the dispatch's single device→host fetch (i.e. device
# execution); a "host" span is pure host work or waiting on host events.
# Add new stages here when instrumenting a new engine path — the
# attribution table labels unknown stages "host" (the conservative read).
DEVICE_STAGES = frozenset(
    {
        "serve_prefill",
        "serve_decode_chunk",
        "encode_batch",
        "fused_query",
        "fused_tiered_query",
        "store_search",
        "store_add",
        "generate",
        "seq2seq_generate",
        "fused_rag_generate",
        "ivf_build",
        "ivf_search",
        "tiered_search",
        "tiered_rebuild",
        "deid_batch",
        "index_batch",
    }
)


def stage_kind(name: str) -> str:
    # "dispatch:<stage>" spans are recorded by the dispatch spine
    # (engines/spine.py) around device work items — device by
    # construction, whatever the stage is called
    if name.startswith("dispatch:"):
        return "device"
    return "device" if name in DEVICE_STAGES else "host"


def attribution(traces: Iterable[Trace]) -> List[Dict[str, Any]]:
    """Per-stage rows over completed traces, sorted by total time desc,
    with an "(unattributed)" row for wall time no span covered.  Share
    is of total request wall (root durations summed), so overlapping
    spans (result-wait over decode chunks) can push the stage SUM past
    100% — share answers "how much wall does this stage touch", not a
    partition; the device/host split plus the unattributed row are the
    partition-style reads."""
    traces = [t for t in traces if t is not None]
    per_stage: Dict[str, List[float]] = {}
    wall_total = 0.0
    covered_total = 0.0
    for trace in traces:
        wall = trace.duration_ms
        wall_total += wall
        covered_total += coverage(trace) * wall
        for sp in trace.snapshot_spans():
            if sp is trace.root:
                continue
            per_stage.setdefault(sp.name, []).append(sp.duration_ms)
    rows: List[Dict[str, Any]] = []
    for name, durs in per_stage.items():
        durs.sort()
        total = sum(durs)
        rows.append(
            {
                "stage": name,
                "kind": stage_kind(name),
                "count": len(durs),
                "total_ms": round(total, 1),
                "mean_ms": round(total / len(durs), 2),
                "p50_ms": round(percentile_nearest_rank(durs, 50), 2),
                "p95_ms": round(percentile_nearest_rank(durs, 95), 2),
                "share_pct": round(100.0 * total / wall_total, 1)
                if wall_total
                else 0.0,
            }
        )
    rows.sort(key=lambda r: -r["total_ms"])
    if wall_total:
        rows.append(
            {
                "stage": "(unattributed)",
                "kind": "host",
                "count": len(traces),
                "total_ms": round(wall_total - covered_total, 1),
                "mean_ms": round(
                    (wall_total - covered_total) / max(len(traces), 1), 2
                ),
                "p50_ms": None,
                "p95_ms": None,
                "share_pct": round(
                    100.0 * (wall_total - covered_total) / wall_total, 1
                ),
            }
        )
    return rows


def format_table(rows: List[Dict[str, Any]]) -> str:
    """Fixed-width text table for bench/script output."""
    header = (
        f"{'stage':<24} {'kind':<6} {'count':>6} {'total_ms':>10} "
        f"{'mean_ms':>8} {'p50_ms':>8} {'p95_ms':>8} {'share%':>7}"
    )
    lines = [header, "-" * len(header)]
    for r in rows:
        p50 = "-" if r["p50_ms"] is None else f"{r['p50_ms']:.2f}"
        p95 = "-" if r["p95_ms"] is None else f"{r['p95_ms']:.2f}"
        lines.append(
            f"{r['stage']:<24} {r['kind']:<6} {r['count']:>6} "
            f"{r['total_ms']:>10.1f} {r['mean_ms']:>8.2f} {p50:>8} "
            f"{p95:>8} {r['share_pct']:>7.1f}"
        )
    return "\n".join(lines)


def device_host_split(traces: Iterable[Trace]) -> Dict[str, float]:
    """Aggregate device-ms vs host-ms over the traces (host = wall not
    inside a device-classified span)."""
    device = 0.0
    wall = 0.0
    for trace in traces:
        if trace is None:
            continue
        wall += trace.duration_ms
        for sp in trace.snapshot_spans():
            if sp is not trace.root and stage_kind(sp.name) == "device":
                device += sp.duration_ms
    return {
        "device_ms": round(device, 1),
        "host_ms": round(max(wall - device, 0.0), 1),
        "wall_ms": round(wall, 1),
    }


class ProfilerWindow:
    """One guarded ``jax.profiler`` start/stop window (HTTP-surfaced).

    jax is imported inside the methods so the obs package stays
    importable on hosts without an accelerator stack, and so importing
    obs never pays a jax import."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._logdir: Optional[str] = None

    @property
    def active(self) -> bool:
        with self._lock:
            return self._logdir is not None

    @property
    def logdir(self) -> Optional[str]:
        with self._lock:
            return self._logdir

    def start(self, logdir: Optional[str] = None) -> str:
        import tempfile

        import jax.profiler

        with self._lock:
            if self._logdir is not None:
                raise RuntimeError(
                    f"profiler window already active ({self._logdir})"
                )
            if logdir is None:
                logdir = tempfile.mkdtemp(prefix="docqa_profile_")
            jax.profiler.start_trace(logdir)
            self._logdir = logdir
            return logdir

    def stop(self) -> str:
        import jax.profiler

        with self._lock:
            if self._logdir is None:
                raise RuntimeError("no profiler window active")
            jax.profiler.stop_trace()
            logdir, self._logdir = self._logdir, None
            return logdir


DEFAULT_PROFILER = ProfilerWindow()
