"""docqa-recallscope: online retrieval-quality estimation for the tiered index.

Every observability layer so far measures *time* (traces, time-series,
dispatch/MFU); nothing measures *retrieval quality* — yet the IVF tier
trades recall for latency on a knob (``nprobe``) nobody can see the
frontier of, and ROADMAP item 2 is blocked on "tune nprobe against a
measured recall target".  This module is the measurement substrate:

* **shadow sampling** — a configurable fraction of live tiered
  retrievals (default 1/32, deterministic seeded sampler so replayed
  workloads sample identically across restarts) gets an asynchronous
  exact-scan shadow query: the ground truth the tier approximates,
  dispatched on the spine's *background* stream (capped at n_lanes-1,
  never blocking a serving lane) under its own ``retrieve_shadow``
  stage so ``dispatch_*`` telemetry attributes its cost;
* **online recall@k** — shadow top-k vs served top-k (tie-tolerant:
  a served row scoring at least the shadow's k-th score counts — two
  equal-scored rows are interchangeable evidence) folded into windowed
  estimates with Wilson confidence intervals, per (tier, nprobe);
* **drift digests** — served score margins and raw query norms feed
  registry histograms (``retrieve_score_margin`` / ``retrieve_query_
  norm``): an embedding-distribution shift moves these before recall
  visibly degrades;
* **the measured nprobe frontier** — every Nth sampled shadow also
  re-probes the IVF tier at neighboring nprobe values, yielding an
  *observed* recall/latency curve and a recommended nprobe for the
  configured recall target.  Recommendation only by default;
  ``auto_apply`` (config ``retrieval_quality.auto_apply_nprobe``,
  default OFF) lets the observatory apply it live via a callback the
  runtime wires to ``TieredIndex.set_nprobe``;
* **the recall SLO** — per-comparison expected/missed counts ride
  registry counters (``retrieve_shadow_expected`` / ``retrieve_shadow_
  missed``) that the telemetry sampler rolls into windows, so
  ``obs/slo.py:default_retrieval_slos`` evaluates a ratio-kind burn
  exactly like availability: a recall regression fires an alert and
  flags the window's /ask traces anomalous.

Stdlib-only like the rest of ``docqa_tpu/obs`` — jax is never imported
here.  The device work lives in closures built by the call sites
(``index/tiered.py``, ``engines/retrieve.py``) over their own
snapshotted state; the observatory only runs them on its worker thread,
where each internal dispatch rides the spine like any other submitter's.

PHI policy: everything the observatory *stores, exports, or logs* —
comparison windows, frontier evidence, counters, ``/api/retrieval`` —
carries row ids, scores, latencies, and norms only, never query or
document text.  That now includes the pending queue itself: a queued
:class:`ShadowJob` holds query EMBEDDINGS (the served dispatch returns
them, so the shadow never re-encodes) plus a salted content hash for
dedup/labels — no raw query text is reachable from a queued job, so a
diagnostic that serialized the queue could not leak one (the fused
path's former raw-text closure is gone; regression-tested in
``tests/test_retrieval_obs.py``).
"""

from __future__ import annotations

import collections
import logging
import math
import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

log = logging.getLogger("docqa.recallscope")

# same deterministic multiplicative hash the telemetry digests use for
# their sample slots: no RNG, so a replayed workload shadows the exact
# same request indices across restarts
_HASH_MULT = 2654435761
_SEED_MULT = 40503
_TIE_EPS = 1e-6


def wilson_interval(
    hits: int, total: int, z: float = 1.96
) -> Tuple[float, float]:
    """Wilson score interval for a binomial proportion — the small-n
    honest alternative to the normal approximation (which collapses to a
    zero-width interval at recall 1.0 and escapes [0, 1] near the
    edges).  Returns ``(lo, hi)``; ``(0.0, 1.0)`` when ``total == 0``
    (no evidence constrains nothing)."""
    if total <= 0:
        return 0.0, 1.0
    p = hits / total
    z2 = z * z
    denom = 1.0 + z2 / total
    center = (p + z2 / (2.0 * total)) / denom
    spread = (
        z
        * math.sqrt(p * (1.0 - p) / total + z2 / (4.0 * total * total))
        / denom
    )
    lo = max(0.0, center - spread)
    hi = min(1.0, center + spread)
    # the degenerate edges are EXACT mathematically (center ± spread
    # telescopes to the boundary at p ∈ {0, 1}); pin them so float
    # round-off can't report hi=0.99999... for a perfect window
    if hits >= total:
        hi = 1.0
    if hits <= 0:
        lo = 0.0
    return lo, hi


def compare_topk(
    served: Sequence[Tuple[int, float]],
    shadow: Sequence[Tuple[int, float]],
    k: int,
) -> Tuple[int, int]:
    """(hits, expected) for one query's served vs exact-shadow top-k.

    ``expected`` is what the exact scan actually found (min(k,
    len(shadow)) — a corpus with 2 live rows owes nobody 10).  A served
    row is a hit when its id is in the shadow set, OR when its score
    reaches the shadow's k-th (minimum) score within a tie epsilon:
    under duplicate scores exact top-k picks an arbitrary
    representative, and a served row of equal score is equally correct
    evidence, not a recall miss."""
    expected = min(k, len(shadow))
    if expected == 0:
        return 0, 0
    shadow_ids = {int(rid) for rid, _ in shadow[:expected]}
    kth = min(float(s) for _, s in shadow[:expected])
    hits = 0
    for rid, score in served[:expected]:
        if int(rid) in shadow_ids or float(score) >= kth - _TIE_EPS:
            hits += 1
    return min(hits, expected), expected


class _EstimateWindow:
    """Bounded window of PER-QUERY (hits, expected) comparison pairs;
    the estimate is hits/expected over the retained window with a
    Wilson CI.  One pair per query, not per shadow job — otherwise
    ``comparisons`` (and every ``min_frontier_n``-style evidence floor
    read against it) would mean 20x different evidence at batch 20 than
    at batch 1."""

    def __init__(self, window: int = 512) -> None:
        self._pairs: collections.deque = collections.deque(maxlen=window)

    def add(self, hits: int, expected: int) -> None:
        if expected > 0:
            self._pairs.append((int(hits), int(expected)))

    def estimate(self) -> Optional[Dict[str, Any]]:
        if not self._pairs:
            return None
        hits = sum(h for h, _ in self._pairs)
        total = sum(e for _, e in self._pairs)
        lo, hi = wilson_interval(hits, total)
        return {
            "recall": round(hits / total, 4) if total else None,
            "ci_lo": round(lo, 4),
            "ci_hi": round(hi, 4),
            "hits": hits,
            "expected": total,
            "comparisons": len(self._pairs),
        }


@dataclass
class ShadowJob:
    """One sampled retrieval, queued for the worker thread.

    ``served``: per query a list of (row_id, score).  ``shadow_fn``
    returns ``(shadow_rows, queries_or_None)`` — the exact ground truth
    plus (when cheaply available) the query embeddings the frontier
    probes reuse.  ``frontier_fn(queries, nprobe)`` returns
    ``(rows, seconds)`` for one neighbor probe.  Both closures run ONLY
    on the observatory worker; every device dispatch inside them rides
    the spine's background ``probe`` stream under the
    ``retrieve_shadow`` stage."""

    tier: str
    nprobe: int
    k: int
    served: List[List[Tuple[int, float]]]
    shadow_fn: Callable[[], Tuple[List[List[Tuple[int, float]]], Any]]
    frontier_fn: Optional[Callable[[Any, int], Tuple[list, float]]] = None
    covered: Optional[int] = None
    n_clusters: Optional[int] = None
    query_norms: Optional[List[float]] = None
    served_margins: Optional[List[float]] = None
    seq: int = 0
    attrs: Dict[str, Any] = field(default_factory=dict)


class RetrievalObservatory:
    """Shadow-sampling online recall estimator + nprobe-frontier plane.

    Thread model: serving threads call :meth:`sample` (a counter bump +
    one deterministic hash) and, on a hit, :meth:`submit` (a bounded
    enqueue); ONE worker thread drains jobs and does all comparison /
    estimation / frontier work, so the serving path never waits on a
    shadow.  All mutable state is guarded by ``_lock``; the worker is
    joined in :meth:`stop` (thread-lifecycle rule).
    """

    def __init__(
        self,
        sample_every: int = 32,
        seed: int = 0,
        window: int = 512,
        max_pending: int = 8,
        frontier_every: int = 4,
        frontier_factors: Sequence[float] = (0.25, 0.5, 1.0, 2.0, 4.0),
        min_frontier_n: int = 5,
        recall_target: float = 0.95,
        auto_apply: bool = False,
        apply_nprobe: Optional[Callable[[int], Any]] = None,
        registry=None,  # runtime.metrics.MetricsRegistry (duck-typed)
    ) -> None:
        self.sample_every = max(1, int(sample_every))
        self.seed = int(seed)
        self.window = int(window)
        self.max_pending = max(1, int(max_pending))
        # every Nth sampled shadow also probes the frontier; 0 disables
        # frontier probing entirely
        self.frontier_every = max(0, int(frontier_every))
        self.frontier_factors = tuple(frontier_factors)
        self.min_frontier_n = int(min_frontier_n)
        self.recall_target = float(recall_target)
        self.auto_apply = bool(auto_apply)
        self.apply_nprobe = apply_nprobe
        self.registry = registry
        self._lock = threading.Lock()
        self._pending: collections.deque = collections.deque()
        self._seq = 0  # retrieval sequence number (sampler input)
        self._n_sampled = 0
        self._n_dropped = 0
        self._n_errors = 0
        self._n_shadows = 0
        # (tier, nprobe) -> _EstimateWindow; _current_key tracks the
        # serving configuration the gauge surface reports
        self._windows: Dict[Tuple[str, int], _EstimateWindow] = {}
        self._current_key: Optional[Tuple[str, int]] = None
        # nprobe -> {"window": _EstimateWindow, "lat_ms": deque,
        #            "compiled": bool}; _frontier_sig is the tier-build
        # signature (n_clusters, covered) the evidence was measured
        # against — a rebuild reclusters, which changes what any given
        # nprobe MEANS, so stale windows must not feed the
        # recommendation (let alone auto-apply)
        self._frontier: Dict[int, Dict[str, Any]] = {}
        self._frontier_sig: Optional[Tuple[Any, Any]] = None
        self._applied_nprobe: Optional[int] = None
        self._busy = False  # worker mid-_process (drain() observability)
        self._stop = threading.Event()
        self._wake = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # ---- lifecycle -----------------------------------------------------------

    def start(self) -> "RetrievalObservatory":
        if self._thread is not None:
            return self
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="recallscope"
        )
        self._thread.start()
        return self

    def stop(self, join_timeout: float = 10.0) -> None:
        """Idempotent; joins the worker.  Shadow closures only run
        bounded device probes, so the join bound is slack."""
        self._stop.set()
        self._wake.set()
        t = self._thread
        if t is not None:
            t.join(timeout=join_timeout)
            if t.is_alive():
                log.warning("recallscope worker still alive after stop()")
            else:
                self._thread = None

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    # ---- sampling (serving-thread surface) -----------------------------------

    def _sampled(self, seq: int) -> bool:
        """Deterministic per-sequence decision, exact 1-in-N for ANY
        rate: every window of ``sample_every`` consecutive retrievals
        samples exactly one, at a slot chosen by a pure hash of (seed,
        window index).  A restarted process replaying the same workload
        shadows the same request indices — no RNG state to diverge —
        and the hashed slot keeps the cadence from phase-locking onto a
        periodic workload the way a bare ``seq % N == 0`` would.  (A
        residue of the raw hash is only window-exact for power-of-two
        rates; the per-window slot holds the '2x the rate
        contains real shadows' sizing for every operator-tuned N.)"""
        win, offset = divmod(seq, self.sample_every)
        h = ((win + 1) * _HASH_MULT + self.seed * _SEED_MULT) & 0xFFFFFFFF
        return offset == h % self.sample_every

    def sample(self) -> bool:
        """Called once per tiered retrieval.  Counts it, returns whether
        this one is shadow-sampled; the caller only builds a job on
        True.  Never samples while the worker is not running (disabled
        observability must cost zero shadow dispatches)."""
        with self._lock:
            seq = self._seq
            self._seq += 1
        self._count("retrieve_served_total")
        if not self.running:
            return False
        return self._sampled(seq)

    def submit(self, job: ShadowJob) -> bool:
        """Bounded enqueue (serving thread).  Returns False (and counts
        the drop) when the worker is behind — shadow evidence is
        sampled anyway, so dropping beats unbounded queueing."""
        with self._lock:
            job.seq = self._n_sampled
            self._n_sampled += 1
            if len(self._pending) >= self.max_pending:
                self._n_dropped += 1
                dropped = True
            else:
                self._pending.append(job)
                dropped = False
        if dropped:
            self._count("retrieve_shadow_dropped")
            return False
        self._wake.set()
        return True

    # ---- worker --------------------------------------------------------------

    def _run(self) -> None:
        while not self._stop.is_set():
            with self._lock:
                job = self._pending.popleft() if self._pending else None
                self._busy = job is not None
            if job is None:
                # idle: wait for a submit (or stop); 0.2s re-check keeps
                # shutdown prompt even if a wake is lost
                self._wake.wait(0.2)
                self._wake.clear()
                continue
            try:
                self._process(job)
            except Exception:
                # a failing shadow must never kill the worker — the
                # whole point is observing the index while it misbehaves
                with self._lock:
                    self._n_errors += 1
                self._count("retrieve_shadow_errors")
                log.exception("shadow job failed (tier=%s)", job.tier)
            finally:
                with self._lock:
                    self._busy = False

    def drain(self, timeout: float = 10.0) -> bool:
        """Block until every queued job has been processed AND the
        worker is idle (tests, measurement windows).  True on
        success; False when the timeout expired first."""
        import time as _time

        deadline = _time.monotonic() + timeout
        while _time.monotonic() < deadline:
            with self._lock:
                idle = not self._pending and not self._busy
            if idle:
                return True
            self._wake.set()
            _time.sleep(0.02)
        return False

    def _process(self, job: ShadowJob) -> None:
        shadow_rows, queries = job.shadow_fn()
        key = (job.tier, int(job.nprobe))
        hits_total = expected_total = 0
        recalls: List[float] = []
        pairs: List[Tuple[int, int]] = []
        for qi, served_row in enumerate(job.served):
            shadow_row = shadow_rows[qi] if qi < len(shadow_rows) else []
            hits, expected = compare_topk(served_row, shadow_row, job.k)
            hits_total += hits
            expected_total += expected
            if expected:
                recalls.append(hits / expected)
                pairs.append((hits, expected))
        with self._lock:
            self._n_shadows += 1
            win = self._windows.get(key)
            if win is None:
                win = self._windows[key] = _EstimateWindow(self.window)
            for h, e in pairs:
                win.add(h, e)
            self._current_key = key
        self._count("retrieve_shadow_total")
        self._count("retrieve_shadow_expected", expected_total)
        self._count(
            "retrieve_shadow_missed", expected_total - hits_total
        )
        reg = self.registry
        if reg is not None:
            for r in recalls:
                reg.histogram("retrieve_recall").observe(r)
            for m in job.served_margins or ():
                reg.histogram("retrieve_score_margin").observe(float(m))
            for n in job.query_norms or ():
                reg.histogram("retrieve_query_norm").observe(float(n))
        if (
            job.frontier_fn is not None
            and queries is not None
            and self.frontier_every > 0
            and job.seq % self.frontier_every == 0
        ):
            self._probe_frontier(job, shadow_rows, queries)

    # ---- frontier ------------------------------------------------------------

    def frontier_candidates(
        self, nprobe: int, n_clusters: Optional[int]
    ) -> List[int]:
        cap = int(n_clusters) if n_clusters else max(1, nprobe)
        out = sorted(
            {
                min(cap, max(1, int(round(nprobe * f))))
                for f in self.frontier_factors
            }
        )
        return out

    def _probe_frontier(self, job: ShadowJob, shadow_rows, queries) -> None:
        """Re-probe the bulk tier at neighboring nprobe values against
        the shadow's *bulk* ground truth (ids below the tier watermark:
        the tail is exact at every nprobe, so only bulk recall moves
        with the knob)."""
        covered = job.covered
        # (n_clusters, covered) only changes when the tier is rebuilt:
        # both are fixed at build time (the tail grows, the watermark
        # doesn't).  Evidence measured against the old clustering says
        # nothing about recall at any nprobe under the new one.
        sig = (job.n_clusters, job.covered)
        with self._lock:
            if self._frontier_sig != sig:
                if self._frontier:
                    log.info(
                        "recallscope: tier rebuilt (%s -> %s); frontier "
                        "evidence reset", self._frontier_sig, sig,
                    )
                self._frontier.clear()
                self._frontier_sig = sig
        bulk_truth: List[List[Tuple[int, float]]] = []
        for row in shadow_rows:
            if covered is None:
                bulk_truth.append(list(row))
            else:
                bulk_truth.append(
                    [(rid, s) for rid, s in row if int(rid) < covered]
                )
        for p in self.frontier_candidates(job.nprobe, job.n_clusters):
            try:
                res = job.frontier_fn(queries, p)
            except Exception:
                self._count("retrieve_shadow_errors")
                log.exception("frontier probe failed at nprobe=%d", p)
                continue
            # IVFIndex.timed_probe reports per-shape compile freshness
            # as a third element; plain (rows, seconds) closures fall
            # back to the first-sample-per-nprobe drop below
            if len(res) == 3:
                rows, seconds, fresh = res
            else:
                rows, seconds = res
                fresh = None
            probe_pairs: List[Tuple[int, int]] = []
            for qi, truth in enumerate(bulk_truth):
                served = rows[qi] if qi < len(rows) else []
                h, e = compare_topk(served, truth, job.k)
                if e:
                    probe_pairs.append((h, e))
            with self._lock:
                entry = self._frontier.get(p)
                if entry is None:
                    entry = self._frontier[p] = {
                        "window": _EstimateWindow(self.window),
                        "lat_ms": collections.deque(maxlen=64),
                        "compiled": False,
                    }
                for h, e in probe_pairs:
                    entry["window"].add(h, e)
                if fresh is not None:
                    # authoritative: the probe itself says whether this
                    # sample paid a trace+compile (keyed per shape, so a
                    # new batch size at an old nprobe is still excluded)
                    if not fresh:
                        entry["lat_ms"].append(seconds * 1e3)
                elif entry["compiled"]:
                    entry["lat_ms"].append(seconds * 1e3)
                else:
                    # the first probe at a new nprobe traces+compiles on
                    # the lane — recording it would poison the latency
                    # axis with a one-time cost
                    entry["compiled"] = True
        self._maybe_auto_apply(job.nprobe)

    def recommended_nprobe(self) -> Optional[int]:
        """Smallest frontier nprobe whose measured recall estimate meets
        the target over at least ``min_frontier_n`` comparisons; None
        until the frontier has enough evidence."""
        with self._lock:
            rows = [
                (p, e["window"].estimate())
                for p, e in sorted(self._frontier.items())
            ]
        qualified = [
            p
            for p, est in rows
            if est is not None
            and est["comparisons"] >= self.min_frontier_n
            and est["recall"] is not None
            and est["recall"] >= self.recall_target
        ]
        return min(qualified) if qualified else None

    def _maybe_auto_apply(self, current_nprobe: int) -> None:
        if not self.auto_apply or self.apply_nprobe is None:
            return
        rec = self.recommended_nprobe()
        with self._lock:
            already = self._applied_nprobe
        if rec is None or rec == current_nprobe or rec == already:
            return
        try:
            self.apply_nprobe(rec)
        except Exception:
            log.exception("auto-apply of nprobe=%d failed", rec)
            return
        with self._lock:
            self._applied_nprobe = rec
        self._count("retrieve_nprobe_autoapplied")
        log.warning(
            "recallscope auto-applied nprobe %d -> %d (measured frontier "
            "meets recall target %.3f)",
            current_nprobe, rec, self.recall_target,
        )

    # ---- surfaces ------------------------------------------------------------

    def _count(self, name: str, n: int = 1) -> None:
        if self.registry is not None and n:
            self.registry.counter(name).inc(n)

    def _estimates_locked(self) -> Dict[str, Any]:
        out = {}
        for (tier, nprobe), win in sorted(self._windows.items()):
            est = win.estimate()
            if est is not None:
                out[f"{tier}@nprobe={nprobe}"] = est
        return out

    def status(self) -> Dict[str, Any]:
        """The ``/api/retrieval`` payload: live estimates, drift
        digests, the observed frontier, and the recommendation."""
        with self._lock:
            current = self._current_key
            cur_est = (
                self._windows[current].estimate() if current else None
            )
            estimates = self._estimates_locked()
            frontier_rows = []
            for p, entry in sorted(self._frontier.items()):
                est = entry["window"].estimate()
                if est is None:
                    continue
                lats = sorted(entry["lat_ms"])
                frontier_rows.append(
                    {
                        "nprobe": p,
                        "recall": est["recall"],
                        "ci_lo": est["ci_lo"],
                        "ci_hi": est["ci_hi"],
                        "comparisons": est["comparisons"],
                        # bulk-probe device latency (compile-excluded);
                        # the serving_latency digests below carry what
                        # /ask pays end to end per tier stage
                        "probe_ms_p50": (
                            round(lats[len(lats) // 2], 3) if lats else None
                        ),
                    }
                )
            counts = {
                "served": self._seq,
                "sampled": self._n_sampled,
                "shadows": self._n_shadows,
                "dropped": self._n_dropped,
                "errors": self._n_errors,
                "pending": len(self._pending),
            }
            applied = self._applied_nprobe
        drift = {}
        if self.registry is not None:
            for name in (
                "retrieve_score_margin",
                "retrieve_query_norm",
                "retrieve_tier_ms_bulk_ivf",
                "retrieve_tier_ms_tail_exact",
                "retrieve_tier_ms_merge",
                "retrieve_tier_ms_fused_probe",
            ):
                s = self.registry.histogram(name).summary()
                if s.get("count"):
                    drift[name] = {
                        k: s.get(k) for k in ("count", "p50", "p95")
                    }
        return {
            "enabled": True,
            "running": self.running,
            "sample_every": self.sample_every,
            "seed": self.seed,
            "recall_target": self.recall_target,
            "counts": counts,
            "estimate": cur_est,
            "current": (
                {"tier": current[0], "nprobe": current[1]}
                if current
                else None
            ),
            "estimates": estimates,
            "frontier": frontier_rows,
            "recommended_nprobe": self.recommended_nprobe(),
            "auto_apply": self.auto_apply,
            "applied_nprobe": applied,
            "drift": drift,
        }

    def telemetry_gauges(self) -> Dict[str, float]:
        """Live gauges for the telemetry sampler (``retrieve_recall_*``
        series on /api/telemetry and both /metrics dialects)."""
        with self._lock:
            current = self._current_key
            est = self._windows[current].estimate() if current else None
            pending = float(len(self._pending))
            nprobe = float(current[1]) if current else 0.0
        out = {
            "retrieve_shadow_pending": pending,
            "retrieve_sample_every": float(self.sample_every),
        }
        if est is not None:
            out["retrieve_recall_estimate"] = float(est["recall"])
            out["retrieve_recall_ci_lo"] = float(est["ci_lo"])
            out["retrieve_recall_ci_hi"] = float(est["ci_hi"])
            out["retrieve_recall_window_n"] = float(est["comparisons"])
            out["retrieve_nprobe_current"] = nprobe
        rec = self.recommended_nprobe()
        if rec is not None:
            out["retrieve_nprobe_recommended"] = float(rec)
        return out


# ---------------------------------------------------------------------------
# process singleton (the serving hooks' lookup point)
# ---------------------------------------------------------------------------

_GLOBAL_LOCK = threading.Lock()
_GLOBAL: Optional[RetrievalObservatory] = None


def get_retrieval_observatory() -> Optional[RetrievalObservatory]:
    """The process observatory, or None when retrieval-quality
    observation is not wired (hooks no-op on None — zero cost)."""
    return _GLOBAL


def set_retrieval_observatory(
    observatory: Optional[RetrievalObservatory],
) -> Optional[RetrievalObservatory]:
    """Swap the process observatory (runtime boot, tests).  Returns the
    previous one; the CALLER owns stopping it."""
    global _GLOBAL
    with _GLOBAL_LOCK:
        prev, _GLOBAL = _GLOBAL, observatory
        return prev
