"""Replicated decode-engine pool with health-checked failover (docqa-pool).

``engines/serve.py`` gave the serving plane continuous batching; this
module removes its single point of failure.  An :class:`EnginePool` owns
N :class:`~docqa_tpu.engines.serve.ContinuousBatcher` replicas over ONE
shared :class:`GenerateEngine` (weights are read-only — replicas differ
only in KV cache, RNG stream, and worker thread; on a mesh each replica
is a same-host decode lane, on a multi-slice deployment each would sit on
its own mesh slice) and becomes the single submit surface for
``service/qa.py`` / ``service/app.py``.

Liveness contract, per replica (the reference system had none, SURVEY §5):

* **worker heartbeat** — the batcher loop stamps a beat every iteration;
  a stale beat WITH work pending means the loop is wedged inside one
  iteration (hung device fetch, injected stall), not idle;
* **synthetic canary** — a periodic 2-token generate with its own
  deadline; the outcome feeds the replica's breaker, so a replica that
  answers the canary slowly/never stops receiving traffic before real
  requests pile onto it;
* **per-replica circuit breaker** (PR 1's :class:`CircuitBreaker`) —
  deaths and canary failures open it; an open breaker makes the replica
  unroutable, and the half-open probe gates the rebuild of a
  crash-looping replica.

Robustness mechanics:

* **routing** — least-queued among healthy replicas (drain state, worker
  liveness, heartbeat freshness, breaker state all disqualify);
* **failover** — on replica death/wedge, queued-but-unadmitted requests
  transparently requeue to a healthy replica (deadline-aware, at most
  ``requeue_max_hops`` hops — the SAME ``_Request`` object moves, so the
  caller's handle never notices); admitted requests fail FAST with a
  typed :class:`WorkerDied` instead of hanging to ``ResultTimeout`` —
  ``service/qa.py`` turns that into the degraded extractive answer;
* **graceful drain** — :meth:`drain` stops admitting, finishes in-flight
  work, and releases the replica; :meth:`rolling_restart` drains and
  rebuilds each replica in turn (hot restart / weight reload with zero
  dropped requests).  While no replica is routable but at least one is
  coming back, submissions PARK in a pool-level pending queue and flush
  on recovery — a 1-replica pool survives its own rolling restart;
* **hedged dispatch** (optional) — a request with no first token after a
  p95-based delay is duplicated onto a second replica; the first token
  wins, the loser is cancelled at its next admit round (tail-latency
  insurance against one slow replica).

Every hop is attributable: routing, failover, hedging, and replica state
changes land as events on the request's trace (PR 5), so a timeline shows
which replica served, where a failover happened, and why.

No new jit roots: the pool is pure host-side orchestration over the
batcher's already-ledgered programs (compile_budget.json unchanged).
"""

from __future__ import annotations

import collections
import threading
import zlib
from time import monotonic as time_monotonic
from time import perf_counter as _now
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from docqa_tpu.engines.qos import QoSPolicy, request_class
from docqa_tpu.engines.serve import (
    DEFAULT_RESULT_TIMEOUT,
    ContinuousBatcher,
    DeferredByPolicy,
    Draining,
    Handle,
    QueueFull,
    RequestCancelled,
    ResultTimeout,
    WorkerDied,
    _finish,
    _req_mark,
    make_request,
)
from docqa_tpu.obs.costs import DEFAULT_COST_LEDGER
from docqa_tpu.resilience.breaker import OPEN, CircuitBreaker
from docqa_tpu.resilience.deadline import Deadline, DeadlineExceeded
from docqa_tpu.runtime.metrics import DEFAULT_REGISTRY, get_logger

log = get_logger("docqa.pool")

# replica health states (surfaced on /api/pool)
HEALTHY = "healthy"
DRAINING = "draining"
REBUILDING = "rebuilding"
DEAD = "dead"


class FailoverExhausted(WorkerDied):
    """A request's replica died and it had no failover budget left
    (``requeue_max_hops`` already spent, or no healthy replica to take
    it).  Typed so the QA layer degrades it like any decoder outage."""


class _Replica:
    """One pooled decode lane: the batcher plus its health bookkeeping.

    The pool lock guards ``state`` transitions; counters are monotonic
    ints written under the GIL (status reads may be one tick stale,
    which is fine for an operator surface)."""

    def __init__(self, idx: int, batcher: ContinuousBatcher,
                 breaker: CircuitBreaker) -> None:
        self.idx = idx
        self.batcher = batcher
        self.breaker = breaker
        self.state = HEALTHY
        self.generation = 0  # bumps on every rebuild
        self.deaths = 0
        self.routed = 0
        self.canary_ok = 0
        self.canary_failed = 0
        # first canary waits one full interval: a canary at t=0 would
        # race the replica's cold-start compiles for nothing
        self.last_canary_at = time_monotonic()
        self.canary: Optional[Handle] = None
        self.canary_deadline: Optional[Deadline] = None

    def routable(self, heartbeat_max_age_s: float) -> bool:
        b = self.batcher
        return (
            self.state == HEALTHY
            and b.worker_alive
            and not b.draining
            and b.heartbeat_age_s < heartbeat_max_age_s
            and self.breaker.state != OPEN
        )


class PoolHandle:
    """Future-like result for a pooled request.  Mirrors the batcher
    :class:`Handle` contract (``result`` / ``text`` / ``iter_tokens`` /
    ``cancel``) so QA/summarize callers cannot tell pool from replica.

    Failover is invisible here: the underlying ``_Request`` object is
    requeued across replicas, and this handle keeps waiting on its one
    ``done`` event.  Hedging adds a twin request; whichever produces the
    answer first wins, and an error on one side only loses if the other
    side has also failed."""

    def __init__(self, pool: "EnginePool", req) -> None:
        self._pool = pool
        self._req = req

    # the hedge twin lives on the pool's in-flight entry (the monitor
    # creates it after the hedge delay); None until then
    def _twin(self):
        return self._pool._hedge_twin(self._req)

    def cancel(self) -> None:
        self._req.cancelled = True
        twin = self._twin()
        if twin is not None:
            twin.cancelled = True

    @property
    def started(self) -> bool:
        return bool(self._req.tokens) or self._req.done.is_set()

    def result(
        self, timeout: Optional[float] = DEFAULT_RESULT_TIMEOUT
    ) -> List[int]:
        t0 = _now()
        try:
            if not self._pool.hedge_enabled:
                out = Handle(self._req).result(timeout)
                self._pool._observe_latency(_now() - t0)
                return out
            out = self._result_hedged(timeout)
            self._pool._observe_latency(_now() - t0)
            return out
        finally:
            self._pool._inflight_done(self._req)

    @staticmethod
    def _losing_error(candidates) -> BaseException:
        """Both hedge lanes failed: surface the most ACTIONABLE error.
        A RequestCancelled on one lane is this pool's own first-token-
        wins bookkeeping, not the request's fate — reporting it would
        turn a typed replica failure (WorkerDied, DeadlineExceeded) on
        the other lane into an unclassifiable 500."""
        errs = [c.error for c in candidates if c.error is not None]
        real = [e for e in errs if not isinstance(e, RequestCancelled)]
        return (real or errs)[0]

    def _await_winner(self, timeout: Optional[float], win):
        """The ONE hedge wait protocol (result() and iter_tokens() both
        use it — they drifted when each carried its own copy): cycle
        over (primary, twin-if-any) until a candidate satisfies ``win``,
        every candidate has failed, or the deadline/timeout lapses.
        One side's failure defers to the other until both have failed
        (hedging doubles as failure insurance).  The twin appears
        asynchronously (monitor thread), so this is a short bounded cv
        cycle — ≤20 ms of discovery latency per transition, only ever
        paid by hedging-enabled pools.  Returns ``(winner,
        candidates_at_win)``."""
        req = self._req
        dl = req.deadline
        if dl is not None:
            timeout = dl.bound(timeout)
        end = None if timeout is None else time_monotonic() + timeout
        while True:
            twin = self._twin()
            candidates = [c for c in (req, twin) if c is not None]
            for cand in candidates:
                if win(cand):
                    return cand, candidates
            if all(c.done.is_set() for c in candidates):
                raise self._losing_error(candidates)
            remaining = None if end is None else end - time_monotonic()
            if remaining is not None and remaining <= 0:
                if dl is not None and dl.expired:
                    raise DeadlineExceeded("pool_result", -dl.remaining())
                raise ResultTimeout(timeout)
            wait_s = 0.02 if remaining is None else min(0.02, remaining)
            waiter = next(
                (c for c in candidates if not c.done.is_set()), req
            )
            with waiter.cv:
                if not waiter.done.is_set() and not win(waiter):
                    waiter.cv.wait(wait_s)

    def _result_hedged(self, timeout: Optional[float]) -> List[int]:
        """First clean COMPLETION wins; the loser is cancelled."""
        winner, candidates = self._await_winner(
            timeout, lambda c: c.done.is_set() and c.error is None
        )
        for other in candidates:
            if other is not winner:
                other.cancelled = True
        return list(winner.tokens)

    def text(
        self, tokenizer, timeout: Optional[float] = DEFAULT_RESULT_TIMEOUT
    ) -> str:
        return tokenizer.decode_ids(self.result(timeout))

    def iter_tokens(self, timeout: Optional[float] = DEFAULT_RESULT_TIMEOUT):
        """Stream tokens.  With hedging on, the stream pins to whichever
        request produces the FIRST token (the other is cancelled); from
        then on it is a plain replica stream."""
        # clean exhaustion feeds the hedge p95 like result() does — a
        # mostly-streaming workload must not leave the latency histogram
        # cold (hedge_delay_s would sit on the floor and duplicate
        # everything).  The observe lines run only on natural stream end:
        # errors and client disconnects (GeneratorExit) skip them.
        t0 = _now()
        try:
            if not self._pool.hedge_enabled:
                yield from Handle(self._req).iter_tokens(timeout)
                self._pool._observe_latency(_now() - t0)
                return
            req = self._req
            # a lane wins with its first token or a clean (error-free)
            # completion — but a lane that already FAILED never wins,
            # even if it produced tokens before dying: the healthy twin
            # may still deliver the whole answer (the same one-side-
            # failure insurance _result_hedged provides; an earlier copy
            # of this loop let a crashed-with-partial-tokens primary
            # beat a live twin)
            winner, _ = self._await_winner(
                timeout,
                lambda c: c.error is None
                and (bool(c.tokens) or c.done.is_set()),
            )
            for other in (req, self._twin()):
                if other is not None and other is not winner:
                    other.cancelled = True
            yield from Handle(winner).iter_tokens(timeout)
            self._pool._observe_latency(_now() - t0)
        finally:
            self._pool._inflight_done(self._req)


class EnginePool:
    """N health-checked ContinuousBatcher replicas behind one submit
    surface.  Drop-in for a bare batcher everywhere the runtime wired
    one (same ``submit_ids`` / ``submit_text`` / ``generate_texts`` /
    ``warmup`` / ``stop`` / ``n_active`` / ``n_queued`` / ``engine`` /
    ``gen`` surface)."""

    def __init__(
        self,
        engine,  # GenerateEngine shared by every replica (read-only weights)
        cfg=None,  # config.PoolConfig; kwargs below override per-field
        *,
        replicas: Optional[int] = None,
        n_slots: Optional[int] = None,
        chunk: Optional[int] = None,
        cache_len: Optional[int] = None,
        max_queue: Optional[int] = None,
        seed: int = 0,
        heartbeat_max_age_s: Optional[float] = None,
        canary_interval_s: Optional[float] = None,
        canary_timeout_s: Optional[float] = None,
        health_interval_s: Optional[float] = None,
        requeue_max_hops: Optional[int] = None,
        hedge: Optional[bool] = None,
        hedge_min_delay_s: Optional[float] = None,
        hedge_warmup: Optional[int] = None,
        session_affinity: Optional[bool] = None,
        affinity_max_queue_delta: Optional[int] = None,
        breaker_failure_threshold: int = 3,
        breaker_reset_s: float = 10.0,
        qos=None,  # config.QoSConfig | qos.QoSPolicy | None (FIFO pool)
    ) -> None:
        def pick(override, field, default):
            if override is not None:
                return override
            if cfg is not None:
                return getattr(cfg, field)
            return default

        self.engine = engine
        self.gen = engine.gen
        self.n_replicas = max(1, int(pick(replicas, "replicas", 1)))
        self._n_slots = pick(n_slots, "n_slots", None)
        self._chunk = chunk
        self._cache_len = cache_len
        self.max_queue = pick(max_queue, "max_queue", 256)
        self._seed = seed
        # generous default: the heartbeat stamps once per WORKER
        # ITERATION, and a legitimate iteration can contain a first-shape
        # XLA compile (tens of seconds on a real chip).  Deployments that
        # pre-warm every shape (startup_warm_buckets=-1) can drop this to
        # a few seconds for faster wedge detection.
        self.heartbeat_max_age_s = pick(
            heartbeat_max_age_s, "heartbeat_max_age_s", 60.0
        )
        self.canary_interval_s = pick(
            canary_interval_s, "canary_interval_s", 20.0
        )
        self.canary_timeout_s = pick(
            canary_timeout_s, "canary_timeout_s", 30.0
        )
        self.health_interval_s = pick(
            health_interval_s, "health_interval_s", 0.5
        )
        self.requeue_max_hops = pick(requeue_max_hops, "requeue_max_hops", 1)
        self.hedge_enabled = bool(pick(hedge, "hedge", False))
        self.hedge_min_delay_s = pick(
            hedge_min_delay_s, "hedge_min_delay_s", 0.75
        )
        self.hedge_warmup = pick(hedge_warmup, "hedge_warmup", 20)
        # session-affine routing (docqa-prefix): a request with a
        # prefix_key prefers the replica hash(key) names, so a
        # patient's warm KV blocks live on the replica that serves
        # their next question — warm hits are per-replica caches
        self.session_affinity = bool(
            pick(session_affinity, "session_affinity", True)
        )
        self.affinity_max_queue_delta = int(
            pick(affinity_max_queue_delta, "affinity_max_queue_delta", 4)
        )

        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._stopped = False
        # parked submissions: requests minted while NO replica was
        # routable but at least one was draining/rebuilding — flushed by
        # the monitor the moment a replica comes back.  Bounded by
        # max_queue like any admission queue.
        self._pending: collections.deque = collections.deque()
        # hedging bookkeeping: req id() -> {"req", "twin", "t", "replica"}
        self._inflight: Dict[int, Dict[str, Any]] = {}
        # completion latencies (seconds) feeding the p95 hedge delay
        self._lat: collections.deque = collections.deque(maxlen=512)
        self._warmups: List[threading.Thread] = []
        self._breakers = [
            CircuitBreaker(
                f"decode_replica_{i}",
                failure_threshold=breaker_failure_threshold,
                reset_timeout_s=breaker_reset_s,
            )
            for i in range(self.n_replicas)
        ]
        # ---- multi-tenant QoS (docqa-qos) ----
        # the raw config threads to every replica batcher (weighted-fair
        # queues + preemption live there); the coerced policy drives the
        # pool's OWN dispatch-time deferral check, so a deferral is one
        # decision at the front door, not one per refusing replica
        self._qos_cfg = qos
        self.qos: Optional[QoSPolicy] = QoSPolicy.coerce(qos)
        self._slo_probe = None
        # ONE compiled program set for the whole pool (see _build_replica)
        self._programs = None
        self._replicas: List[_Replica] = [
            self._build_replica(i) for i in range(self.n_replicas)
        ]
        # batcher knobs are identical across replicas; template truncation
        # (submit_text) needs the shared usable-cache budget
        b0 = self._replicas[0].batcher
        self._usable = b0.cache_len - 2 - b0.spec_k
        self._monitor_stop = threading.Event()
        self._monitor = threading.Thread(
            target=self._monitor_loop, daemon=True, name="pool-monitor"
        )
        self._monitor.start()

    # ---- replica lifecycle ---------------------------------------------------

    def _build_replica(self, idx: int, generation: int = 0) -> _Replica:
        batcher = ContinuousBatcher(
            self.engine,
            n_slots=self._n_slots,
            chunk=self._chunk,
            cache_len=self._cache_len,
            # distinct RNG stream per replica AND per generation: a
            # rebuilt replica must not replay its predecessor's keys
            seed=self._seed + 1009 * idx + 7 * generation,
            max_queue=self.max_queue,
            qos=self._qos_cfg,
        )
        batcher.on_worker_death = (
            lambda b, queued, _i=idx: self._on_worker_death(_i, b, queued)
        )
        # preemption victims ride the SAME requeue/rescue machinery as
        # failover: deadline-aware, hop-bounded, parking as fallback —
        # a victim may land on a replica with free blocks right now
        batcher.on_preempt = (
            lambda b, req, _i=idx: self._requeue(req, from_idx=_i)
        )
        if self._slo_probe is not None:
            # rebuilds must re-inherit the burn probe (a fresh batcher
            # defaults to None — deferral would silently die with it)
            batcher.set_slo_probe(self._slo_probe)
        # Share ONE compiled program set across replicas AND rebuild
        # generations: every replica has identical (n_slots, chunk,
        # cache_len, spec_k) over the same engine, so the jit programs
        # are identical HLO — but each fresh jit wrapper would recompile
        # the whole shape ladder from scratch.  Without sharing, a
        # rolling restart pays ~2·buckets+1 XLA compiles per replica
        # while serving traffic (a hot restart that recompiles the world
        # is not hot: the compile storm starves co-located workers, and
        # on a loaded host it pushed request waits past their deadlines).
        # jit executables are thread-safe for concurrent dispatch, and
        # donation is per-call, so replicas can share freely.  The first
        # batcher's bound methods back the jits — it stays referenced;
        # _rebuild_replica scrubs dead batchers' device state so that
        # shell cannot pin a KV cache.
        if self._programs is None:
            self._programs = (
                batcher._get_prefill_fn(),
                batcher._get_prefill_warm_fn(),
                batcher._get_decode_fn(),
            )
        else:
            (
                batcher._prefill_fn,
                batcher._prefill_warm_fn,
                batcher._decode_fn,
            ) = self._programs
        r = _Replica(idx, batcher, self._breakers[idx])
        r.generation = generation
        return r

    def _rebuild_replica(self, r: _Replica) -> None:
        """Fresh batcher (fresh KV cache + worker) in place of a dead or
        restarting one.  Weight reload happens implicitly: the batcher
        reads ``engine.params`` at every dispatch, so an engine whose
        params were swapped serves the new weights from the first round."""
        log.warning(
            "rebuilding replica %d (generation %d -> %d)",
            r.idx, r.generation, r.generation + 1,
        )
        old = r.batcher
        # read before teardown: did the dying replica already clear its
        # cold start over the SHARED program set?
        old_was_cold = old.cold
        try:
            if old.worker_alive:
                old.kill(WorkerDied("replica rebuilt"))
            # catch admission-window stragglers: a request the worker had
            # popped (but not yet made slot-resident) when kill() ran is
            # invisible to kill's queue+slot sweep; if it became slot-
            # resident afterwards and the worker exited before finishing
            # it, it would hang to ResultTimeout.  fail_active is
            # idempotent (skips done requests), so this is free when
            # there are none.
            old.fail_active(WorkerDied(f"replica {r.idx} rebuilt"))
        except Exception:
            log.exception("old batcher teardown failed (continuing)")
        # drop the dead batcher's device state: the pool's shared jit
        # programs keep the FIRST batcher's shell alive (bound methods),
        # and without this scrub that shell would pin a full KV cache
        # across every later generation.  A still-wedged worker that
        # wakes into the None state errors into _fail_active, which
        # skips its reset for stopped batchers and exits the loop.
        # "_pools" is the paged KV block pool (docqa-paged renamed it
        # from the pre-paged "_cache", which the old scrub list still
        # named — a dead shell was pinning the whole HBM pool)
        for name in ("_pools", "_tok", "_lengths", "_active", "_table"):
            setattr(old, name, None)
        fresh = self._build_replica(r.idx, generation=r.generation + 1)
        r.batcher = fresh.batcher
        r.generation += 1
        r.canary = None
        r.canary_deadline = None
        with self._lock:
            r.state = HEALTHY
            self._cv.notify_all()
        DEFAULT_REGISTRY.counter("pool_rebuilds").inc()
        if not old_was_cold:
            # The dead replica had already cleared cold over the SAME
            # shared program set, so every shape it ever compiled is
            # still compiled — the fresh batcher's first iterations
            # compile nothing and a rebuild-time warmup would be pure
            # redundant load at the worst possible moment.  (Observed on
            # CPU smoke: the warmup's sharded dispatches + the fresh
            # worker's first admission + the next request's device ops
            # exceeded the virtual-device client's collective scheduling
            # capacity and deadlocked the process at 0% CPU.)  Liveness
            # judgment may engage immediately.
            r.batcher._cold = False
            return
        # The old replica died DURING its own cold start: the shared
        # programs may hold none of the admission shapes yet, so
        # pre-compile them off the serving path (safe concurrently with
        # traffic: warmup donates throwaway state).  Tracked so stop()
        # can join: an XLA compile still running on a daemon thread at
        # interpreter exit aborts the process (std::terminate) —
        # observed under pytest.
        t = threading.Thread(
            target=self._warm_replica, args=(r.batcher,), daemon=True,
            name=f"pool-warmup-{r.idx}",
        )
        # prune finished warmups so a crash-looping replica cannot grow
        # this list unboundedly (stop() joins whatever is still live)
        self._warmups = [w for w in self._warmups if w.is_alive()] + [t]
        t.start()

    def _warm_replica(self, batcher: ContinuousBatcher) -> None:
        # the FULL bucket ladder: a partially-warmed replica flips
        # ``cold`` off and then pays a live compile on the first unwarmed
        # bucket — which a tight heartbeat bound would misread as a wedge
        try:
            batcher.warmup()
        except Exception:
            log.exception("replica warmup failed (serving continues cold)")

    # ---- submit surface ------------------------------------------------------

    @property
    def prefix_cache_enabled(self) -> bool:
        """Pool passthrough of the batcher surface (service/qa.py checks
        this before threading a ``prefix_key``)."""
        return any(
            getattr(r.batcher, "prefix_cache_enabled", False)
            for r in self._replicas
        )

    def expect_arrival(self) -> Callable[[], None]:
        """Pool passthrough of ``ContinuousBatcher.expect_arrival`` (the
        HTTP layer calls it for every ask it takes in, ``service/app.py``).
        The count is the POOL's: routing happens at the submit, so every
        replica is told, and with several replicas one may gather for an
        ask that lands on its sibling — until the count drops, or at most
        the batcher's bound an arrival."""
        arrived = [r.batcher.expect_arrival() for r in self._replicas]

        def arrived_all() -> None:
            for fn in arrived:
                fn()

        return arrived_all

    def submit_ids(
        self,
        prompt_ids: Sequence[int],
        max_new_tokens: Optional[int] = None,
        deadline: Optional[Deadline] = None,
        prefix_key: Optional[str] = None,
        req_class: Optional[str] = None,
    ) -> PoolHandle:
        max_new = max_new_tokens or self.gen.max_new_tokens
        req = make_request(
            prompt_ids, max_new, deadline=deadline, prefix_key=prefix_key,
            req_class=req_class,
        )
        self._dispatch(req)
        return PoolHandle(self, req)

    def submit_text(
        self,
        prompt: str,
        max_new_tokens: Optional[int] = None,
        deadline: Optional[Deadline] = None,
        prefix_key: Optional[str] = None,
        req_class: Optional[str] = None,
    ) -> PoolHandle:
        # same template-aware truncation contract as the bare batcher:
        # pool answers match solo-engine answers token-for-token
        return self.submit_ids(
            self.engine.encode_prompt(prompt, self._usable),
            max_new_tokens,
            deadline=deadline,
            prefix_key=prefix_key,
            req_class=req_class,
        )

    def _routable(self, exclude=()) -> List[_Replica]:
        return [
            r
            for r in self._replicas
            if r.idx not in exclude
            and r.routable(self.heartbeat_max_age_s)
        ]

    def _preferred_replica(self, req) -> Optional[int]:
        """Session-affine preference: the replica a request's prefix key
        hashes to (stable across processes — zlib.crc32, not the seeded
        builtin), or None when affinity is off / the request is cold."""
        key = getattr(req, "prefix_key", None)
        if not self.session_affinity or not key or self.n_replicas < 2:
            return None
        return zlib.crc32(key.encode("utf-8")) % self.n_replicas

    def _try_place(self, req, exclude=()):
        """The ONE routing policy (dispatch, failover requeue, and park
        flush all use it): offer ``req`` to routable replicas in
        least-queued order until one accepts — except that a request
        with a prefix key tries its SESSION-AFFINE replica first (its
        warm KV blocks live there), as long as that replica is not more
        than ``affinity_max_queue_delta`` requests deeper than the
        least-queued one (affinity is a preference, never a hotspot
        amplifier; fallback is plain least-queued).  Returns
        ``(replica_or_None, n_full, n_candidates)`` where ``n_full``
        counts replicas that refused specifically because their queue is
        at capacity.  A :class:`Draining` refusal (the replica began
        draining between the routable snapshot and the submit — drain
        marks the state FIRST, so by now it reads as coming back) routes
        around WITHOUT counting: a drain is never an at-capacity shed,
        or a rolling restart would 503 requests it promised to park.
        WorkerDied/RuntimeError mean the replica died in the same window
        — the monitor will notice; try the next one."""
        candidates = sorted(
            self._routable(exclude),
            key=lambda r: (r.batcher.n_queued, r.batcher.n_active),
        )
        want = self._preferred_replica(req)
        # affine = the preference actually holds (preferred replica is
        # first, naturally or by promotion); a preferred replica that
        # was too deep and merely accepts LAST in least-queued order is
        # NOT an affinity route and must not inflate the gauge
        affine = False
        if want is not None and candidates:
            floor_q = candidates[0].batcher.n_queued
            for i, r in enumerate(candidates):
                if r.idx != want:
                    continue
                if i == 0:
                    affine = True
                elif (
                    r.batcher.n_queued
                    <= floor_q + self.affinity_max_queue_delta
                ):
                    candidates.insert(0, candidates.pop(i))
                    affine = True
                break
        n_full = 0
        for r in candidates:
            try:
                r.batcher.submit_request(req)
            except Draining:
                continue
            except QueueFull:
                n_full += 1
                continue
            except (WorkerDied, RuntimeError):
                continue
            if affine and r.idx == want:
                # counted only when the PREFERRED replica accepted as
                # the preference (front of the list) — neither a
                # refused preference nor a too-deep preferred replica
                # that happens to accept last counts
                DEFAULT_REGISTRY.counter("pool_affinity_routed").inc()
            return r, n_full, len(candidates)
        return None, n_full, len(candidates)

    def _shed(self, req, kind: str, exc: QueueFull) -> QueueFull:
        """Terminal pool-level shed: forensics snapshot + cost-record
        retirement (the pool owns the decision — per-replica refusals
        along the way were routing, not sheds)."""
        cls = req.cost.cls if req.cost is not None else None
        DEFAULT_COST_LEDGER.record_shed(
            kind, cls=cls, stage="pool_dispatch",
            n_queued=exc.n_queued, n_active=exc.n_active,
        )
        if req.cost is not None:
            DEFAULT_COST_LEDGER.retire(req.cost, "shed_queue")
        return exc

    def _dispatch(self, req, exclude=()) -> None:
        """Route to the least-queued healthy replica; park when nothing
        is routable but a replica is draining/rebuilding (rolling
        restarts must not drop); shed only when genuinely out of
        capacity everywhere."""
        # replica-level refusals are routing decisions, not terminal
        # sheds: the flag keeps a refusing batcher from retiring the
        # cost record a later replica will keep accruing to
        req.pool_managed = True
        # SLO-aware self-protection: while the /ask burn-rate alert
        # fires, batch-class work is deferred HERE — once, at pool
        # dispatch (replicas skip the check for pool_managed requests
        # so a deferral can't double-count as the request hops).
        # Typed DeferredByPolicy (a QueueFull subclass: same 503
        # surface) so callers can tell policy from genuine capacity.
        if self.qos is not None and not getattr(req, "hops", 0):
            cls = request_class(req)
            firing = self._slo_firing()
            if self.qos.should_defer(cls, firing):
                DEFAULT_REGISTRY.counter("qos_deferred").inc()
                DEFAULT_REGISTRY.counter(f"qos_deferred_{cls}").inc()
                _req_mark(
                    req, "qos_deferred", stage="pool_dispatch",
                    firing=",".join(firing),
                )
                DEFAULT_COST_LEDGER.record_shed(
                    "deferred_by_policy", cls=cls, stage="pool_dispatch",
                    firing=",".join(firing),
                )
                if req.cost is not None:
                    DEFAULT_COST_LEDGER.retire(req.cost, "shed_deferred")
                raise DeferredByPolicy(
                    f"{cls} deferred while SLO burn active: {firing}",
                    n_queued=self.n_queued,
                    n_active=self.n_active,
                )
        placed, n_full, n_candidates = self._try_place(req, exclude)
        if placed is not None:
            placed.routed += 1
            _req_mark(
                req, "pool_route", anomalous=False,
                replica=placed.idx, generation=placed.generation,
            )
            if self.hedge_enabled:
                self._inflight[id(req)] = {
                    "req": req, "twin": None, "t": time_monotonic(),
                    "replica": placed.idx,
                }
            return
        if n_full and n_full == n_candidates:
            # every healthy replica is at queue capacity: aggregate 503
            DEFAULT_REGISTRY.counter("pool_shed").inc()
            raise self._shed(req, "queue_full", QueueFull(
                f"all {n_candidates} healthy replica(s) at capacity",
                n_queued=self.n_queued,
                n_active=self.n_active,
            ))
        # no routable replica at all: park if one is coming back,
        # otherwise this IS an outage — shed typed
        with self._lock:
            if self._stopped:
                raise RuntimeError("pool is stopped")
            coming_back = any(
                r.state in (DRAINING, REBUILDING, DEAD)
                for r in self._replicas
            )
            if not coming_back:
                # count parked directly: the n_queued property takes
                # self._lock, which this thread already holds
                raise self._shed(req, "no_routable_replica", QueueFull(
                    "no routable replica",
                    n_queued=len(self._pending) + sum(
                        r.batcher.n_queued for r in self._replicas
                    ),
                    n_active=self.n_active,
                ))
            if len(self._pending) >= (self.max_queue or 256):
                DEFAULT_REGISTRY.counter("pool_shed").inc()
                raise self._shed(req, "queue_full", QueueFull(
                    "pool pending queue at capacity",
                    n_queued=len(self._pending),
                    n_active=self.n_active,
                ))
            self._pending.append(req)
            DEFAULT_REGISTRY.counter("pool_parked").inc()
        _req_mark(req, "pool_parked", anomalous=False)

    def generate_texts(
        self, prompts: Sequence[str], max_new_tokens: Optional[int] = None
    ) -> List[str]:
        """Bulk convenience (same contract as the batcher's): waits for
        capacity instead of shedding, bounded end to end."""
        deadline = Deadline.after(DEFAULT_RESULT_TIMEOUT)
        handles = []
        for p in prompts:
            while True:
                try:
                    handles.append(
                        self.submit_text(p, max_new_tokens, deadline=deadline)
                    )
                    break
                except DeadlineExceeded as e:
                    raise QueueFull(
                        "pool stayed saturated past the bulk budget "
                        f"({e})",
                        n_queued=self.n_queued,
                        n_active=self.n_active,
                    ) from e
                except QueueFull:
                    if deadline.expired:
                        raise
                    with self._cv:
                        # woken by monitor ticks / replica recovery; the
                        # cap bounds the wait against a stalled monitor
                        self._cv.wait(deadline.bound(0.05))
        return [h.text(self.engine.tokenizer) for h in handles]

    def warmup(self, buckets: Optional[Sequence[int]] = None) -> None:
        for r in self._replicas:
            r.batcher.warmup(buckets=buckets)

    def annotate_costs(self) -> bool:
        """Register program cost models with the observatory (batcher
        passthrough).  ONE replica suffices: the pool shares a single
        compiled program set across replicas, so the cost model of
        replica 0's programs is the cost model of every replica's."""
        return self._replicas[0].batcher.annotate_costs()

    @property
    def decode_kernel_calls(self) -> Optional[int]:
        """Mosaic custom calls in the shared decode program (batcher
        passthrough; None before :meth:`annotate_costs`)."""
        return self._replicas[0].batcher.decode_kernel_calls

    # ---- failover ------------------------------------------------------------

    def _on_worker_death(self, idx: int, batcher: ContinuousBatcher, queued):
        """Runs in the DYING replica's worker thread: mark the replica
        dead, requeue its unadmitted requests, hand back the unrescued
        remainder for typed failure.  Fast path only — the heavy rebuild
        happens on the monitor thread."""
        r = self._replicas[idx]
        if r.batcher is not batcher:
            return queued  # a stale generation's death; nothing to mark
        with self._lock:
            r.state = DEAD
        r.deaths += 1
        r.breaker.record_failure()
        DEFAULT_REGISTRY.counter("pool_replica_deaths").inc()
        log.error(
            "replica %d worker died (%d queued to fail over)",
            idx, len(queued),
        )
        unrescued = []
        for req in queued:
            if not self._requeue(req, from_idx=idx):
                unrescued.append(req)
        with self._cv:
            self._cv.notify_all()  # wake the monitor's capacity waiters
        return unrescued

    def _requeue(self, req, from_idx: int) -> bool:
        """Move one queued-but-unadmitted request to a healthy replica.
        Deadline-aware and hop-bounded; returns False when the caller
        must fail it typed instead."""
        if req.done.is_set() or req.cancelled:
            return True  # nothing left to rescue
        if req.deadline is not None and req.deadline.expired:
            req.error = DeadlineExceeded("pool_requeue")
            DEFAULT_REGISTRY.counter("serve_deadline_shed").inc()
            _req_mark(req, "deadline_exceeded", stage="pool_requeue")
            DEFAULT_COST_LEDGER.record_shed(
                "deadline",
                cls=req.cost.cls if req.cost is not None else None,
                stage="pool_requeue",
            )
            _finish(req)
            return True  # handled (typed), not silently lost
        if req.hops >= self.requeue_max_hops:
            return False
        req.hops += 1
        placed, _, _ = self._try_place(req, exclude=(from_idx,))
        if placed is not None:
            DEFAULT_REGISTRY.counter("pool_requeued").inc()
            _req_mark(
                req, "pool_failover",
                from_replica=from_idx, to_replica=placed.idx, hop=req.hops,
            )
            return True
        # nowhere healthy right now: park it (monitor flushes on
        # recovery; deadline shedding still applies at flush time)
        with self._lock:
            if self._stopped or len(self._pending) >= (self.max_queue or 256):
                return False
            self._pending.append(req)
        _req_mark(req, "pool_failover_parked", from_replica=from_idx)
        return True

    # ---- health monitor ------------------------------------------------------

    def _transition(self, r: _Replica, from_states, to_state: str) -> bool:
        """Compare-and-set a replica state under the pool lock.  Every
        state-changing path (monitor wedge/death/rebuild, operator
        drain/resume) goes through this: the pre-PR-8 pattern read
        ``r.state`` lock-free and then wrote it under the lock, so the
        monitor's DEAD→REBUILDING and an operator's
        ``resume(rebuild=True)`` could BOTH decide to rebuild one
        replica — two fresh batchers, one leaked with a live worker
        thread and a pinned KV cache (guarded-state true positive;
        regression-tested in tests/test_racecheck.py)."""
        with self._lock:
            if r.state not in from_states:
                return False
            r.state = to_state
            self._cv.notify_all()
            return True

    def _monitor_loop(self) -> None:
        while not self._monitor_stop.wait(self.health_interval_s):
            try:
                self._tick()
            except Exception:
                log.exception("pool monitor tick failed (ignored)")

    def _tick(self) -> None:
        if self._stopped:
            return  # stop() owns teardown; don't start rebuilds under it
        now = time_monotonic()
        # COMPILE-STORM GRACE: while any replica is rebuilding or still
        # cold, its warmup compiles hog the host (observed on CPU smoke:
        # a rebuild's XLA compiles starve the HEALTHY replica's worker,
        # its heartbeat goes stale under load, and the wedge detector
        # kills it — a cascading rebuild storm).  Liveness JUDGMENT
        # (wedge declaration, canary verdicts) is suspended for the
        # storm; detection resumes the tick after the storm clears.
        # DEAD replicas do NOT count: a dead replica sitting out its
        # breaker backoff compiles nothing, and counting it would let one
        # crash-looping replica suspend liveness judgment for the whole
        # pool indefinitely (its DEAD->REBUILDING->DEAD cycle keeps the
        # flag up; the rebuild itself is covered by REBUILDING + cold).
        storm = any(
            r.state == REBUILDING or r.batcher.cold
            for r in self._replicas
        )
        for r in self._replicas:
            self._check_replica(r, now, storm)
        self._flush_pending()
        if self.hedge_enabled:
            self._hedge_tick(now)
        with self._cv:
            self._cv.notify_all()  # wake bulk submitters waiting on capacity

    def _check_replica(self, r: _Replica, now: float, storm: bool) -> None:
        b = r.batcher
        if r.state == DRAINING:
            return  # operator-owned; resume()/rolling_restart() ends it
        if r.state == HEALTHY and not b.worker_alive:
            # a CRASHED worker already ran the failover hook (which set
            # DEAD under the lock, so this path never sees it); reaching
            # here means the worker exited WITHOUT the hook — external
            # kill/stop — so the death is counted here instead.  CAS:
            # an operator drain/resume that won the race owns the state.
            if self._transition(r, (HEALTHY,), DEAD):
                r.deaths += 1
                r.breaker.record_failure()
                DEFAULT_REGISTRY.counter("pool_replica_deaths").inc()
                log.error(
                    "replica %d worker found dead by monitor", r.idx
                )
        if (
            r.state == HEALTHY
            and not b.cold  # a cold iteration is an XLA compile, not a wedge
            and not storm  # host-wide compile storm slows healthy workers
            and b.heartbeat_age_s > self.heartbeat_max_age_s
            # n_admitting: a worker can wedge INSIDE the admission window
            # (queue already popped, slots not yet assigned) — both
            # n_queued and n_active read 0 there, but work is pending
            and (b.n_active > 0 or b.n_queued > 0 or b.n_admitting > 0)
        ):
            # WEDGE: the loop is stuck inside one iteration with work
            # pending.  Queued requests are still rescuable; admitted
            # ones fail fast into the degraded path instead of hanging.
            # CAS from HEALTHY: if an operator drain() set DRAINING
            # between the (lock-free) wedge evaluation above and here,
            # the drain owns the replica — killing a draining batcher
            # would fail the very in-flight requests the drain promised
            # to finish.
            if not self._transition(r, (HEALTHY,), DEAD):
                return
            log.error(
                "replica %d wedged (heartbeat %.1fs stale, %d active, "
                "%d queued) — failing over",
                r.idx, b.heartbeat_age_s, b.n_active, b.n_queued,
            )
            r.deaths += 1
            r.breaker.record_failure()
            DEFAULT_REGISTRY.counter("pool_replica_wedges").inc()
            for req in b.steal_queued():
                if not self._requeue(req, from_idx=r.idx):
                    if not req.done.is_set():
                        req.error = FailoverExhausted(
                            f"replica {r.idx} wedged; no failover left"
                        )
                        _req_mark(req, "failover_exhausted")
                        _finish(req)
            b.kill(WorkerDied(f"replica {r.idx} wedged (heartbeat stale)"))
        if r.state == DEAD:
            # rebuild gated by the breaker: a crash-looping replica sits
            # out its reset window, then one half-open probe rebuild whose
            # canary outcome closes or re-opens the circuit.  CAS: an
            # operator resume(rebuild=True) that won the race is already
            # rebuilding — a second rebuild would leak its worker.
            if r.breaker.allow() and self._transition(
                r, (DEAD,), REBUILDING
            ):
                try:
                    self._rebuild_replica(r)
                    # the post-rebuild canary below reports the probe
                    # outcome; fire it immediately
                    r.last_canary_at = 0.0
                except Exception:
                    log.exception("replica %d rebuild failed", r.idx)
                    self._transition(r, (REBUILDING,), DEAD)
                    r.breaker.record_failure()
            return
        if r.state != HEALTHY:
            return
        # ---- canary: a tiny real generate, outcome feeds the breaker
        if r.canary is not None:
            dl = r.canary_deadline
            creq = r.canary._req
            if storm and (
                (creq.done.is_set() and creq.error is not None)
                or (dl is not None and dl.expired)
            ):
                # a canary that failed/expired DURING a compile storm is
                # evidence about the storm, not the replica — discard the
                # probe without a breaker verdict
                creq.cancelled = True
                r.canary = None
                r.canary_deadline = None
            elif creq.done.is_set():
                if creq.error is None:
                    r.canary_ok += 1
                    r.breaker.record_success()
                else:
                    r.canary_failed += 1
                    r.breaker.record_failure()
                    log.warning(
                        "replica %d canary failed: %r", r.idx, creq.error
                    )
                r.canary = None
                r.canary_deadline = None
            elif dl is not None and dl.expired:
                # canary never came back inside its own deadline: the
                # replica is slow-or-stuck — breaker pressure now, wedge
                # detection (above) handles the hard-stuck case
                r.canary_failed += 1
                r.breaker.record_failure()
                DEFAULT_REGISTRY.counter("pool_canary_timeouts").inc()
                log.warning("replica %d canary timed out", r.idx)
                creq.cancelled = True
                r.canary = None
                r.canary_deadline = None
        elif b.cold or storm:
            # no canaries into a cold replica (the probe would race the
            # cold-start compiles, time out, and open the breaker on a
            # replica that is merely warming up) nor during a pool-wide
            # compile storm.  Push the schedule so the first canary lands
            # one interval after quiet.
            r.last_canary_at = now
        elif b.last_progress_age_s < self.canary_interval_s:
            # the replica fetched a decode chunk within the canary
            # interval: real traffic already proved the full
            # dispatch→device→fetch path, which is exactly what the
            # probe would test.  Count it as a passed probe once per
            # interval (so the half-open breaker still closes under real
            # load) and spend no decode lane — a synthetic generate
            # under load is pure overhead, and on the CPU smoke client
            # one more concurrent sharded dispatch.  Synthetic canaries
            # now only probe IDLE replicas, where they contend with
            # nothing.
            if now - r.last_canary_at >= self.canary_interval_s:
                r.last_canary_at = now
                r.breaker.record_success()
        elif now - r.last_canary_at >= self.canary_interval_s:
            r.last_canary_at = now
            dl = Deadline.after(self.canary_timeout_s)
            try:
                r.canary = r.batcher.submit_request(
                    make_request(
                        [1, 2, 3], 2, deadline=dl, req_class="background"
                    )
                )
                r.canary_deadline = dl
            except Exception as e:
                r.canary_failed += 1
                r.breaker.record_failure()
                log.warning(
                    "replica %d canary submit failed: %r", r.idx, e
                )

    def _flush_pending(self) -> None:
        while True:
            with self._lock:
                if not self._pending:
                    return
                req = self._pending.popleft()
            if req.done.is_set() or req.cancelled:
                continue
            if req.deadline is not None and req.deadline.expired:
                req.error = DeadlineExceeded("pool_pending")
                DEFAULT_REGISTRY.counter("serve_deadline_shed").inc()
                _req_mark(req, "deadline_exceeded", stage="pool_pending")
                DEFAULT_COST_LEDGER.record_shed(
                    "deadline",
                    cls=req.cost.cls if req.cost is not None else None,
                    stage="pool_pending",
                )
                _finish(req)
                continue
            placed, _, _ = self._try_place(req)
            if placed is not None:
                placed.routed += 1
                _req_mark(
                    req, "pool_route", anomalous=False,
                    replica=placed.idx, parked=True,
                )
            else:
                with self._lock:
                    if not self._stopped:
                        self._pending.appendleft(req)
                        return
                # stop() already swept _pending — putting the request
                # back would strand it on a deque nobody drains; fail it
                # typed like the sweep would have
                if not req.done.is_set():
                    req.error = RuntimeError("pool stopped")
                    _finish(req)
                return

    # ---- hedged dispatch -----------------------------------------------------

    def hedge_delay_s(self) -> float:
        """p95 of observed completion latencies, floored by the
        configured minimum; the floor alone until warmup samples exist
        (hedging off a cold histogram would duplicate everything)."""
        lat = list(self._lat)
        if len(lat) < self.hedge_warmup:
            return self.hedge_min_delay_s
        return max(
            float(np.percentile(lat, 95)), self.hedge_min_delay_s
        )

    def _observe_latency(self, seconds: float) -> None:
        self._lat.append(seconds)

    def _hedge_twin(self, req):
        entry = self._inflight.get(id(req))
        return entry["twin"] if entry else None

    def _inflight_done(self, req) -> None:
        self._inflight.pop(id(req), None)

    def _hedge_tick(self, now: float) -> None:
        delay = self.hedge_delay_s()
        for entry in list(self._inflight.values()):
            req, twin = entry["req"], entry["twin"]
            if twin is not None:
                # first token wins: cancel the laggard the moment one
                # side has produced output
                if req.tokens and not twin.tokens:
                    twin.cancelled = True
                elif twin.tokens and not req.tokens:
                    req.cancelled = True
            if req.done.is_set() and (twin is None or twin.done.is_set()):
                # Settled — GC with a GRACE window, never instantly: the
                # waiter discovers the twin THROUGH this entry, so a pop
                # at the instant both lanes settle can hide a winning
                # twin from a waiter descheduled mid-discovery (it would
                # see only its cancelled primary and raise
                # RequestCancelled for a request that actually won).
                # result()/iter_tokens() pop eagerly via _inflight_done;
                # this path only collects abandoned handles.
                if "done_at" not in entry:
                    entry["done_at"] = now
                elif now - entry["done_at"] > 60.0:
                    self._inflight.pop(id(req), None)
                continue
            if twin is not None:
                continue
            if req.tokens or req.cancelled:
                continue  # already started (or abandoned): no hedge
            if now - entry["t"] < delay:
                continue
            if req.deadline is not None and req.deadline.remaining() < 0.1:
                continue  # no budget left to win anything
            targets = self._routable(exclude=(entry["replica"],))
            if not targets:
                continue
            r = min(
                targets,
                key=lambda x: (x.batcher.n_queued, x.batcher.n_active),
            )
            # the twin rides the SAME trace (the timeline shows both
            # lanes racing) and the SAME cost record, passed into
            # make_request so no orphan record is ever minted — the
            # duplicated decode is real cost of the one logical
            # request.  cost_shadow keeps the twin's _finish from
            # retiring the shared record.
            twin = make_request(
                list(req.prompt_ids), req.max_new, deadline=req.deadline,
                prefix_key=req.prefix_key, cost=req.cost,
            )
            twin.trace = req.trace
            twin.span_parent = req.span_parent
            twin.cost_shadow = True
            try:
                r.batcher.submit_request(twin)
            except Exception:
                continue
            entry["twin"] = twin
            DEFAULT_REGISTRY.counter("pool_hedges").inc()
            _req_mark(
                req, "pool_hedged", anomalous=False,
                to_replica=r.idx, after_ms=round((now - entry["t"]) * 1e3),
            )

    # ---- drain / rolling restart --------------------------------------------

    def drain(self, replica: int, timeout: float = 30.0) -> Dict[str, Any]:
        """Stop admitting to one replica and wait for its in-flight work
        to finish.  Routing avoids it from the first instant, so under a
        multi-replica pool a drain is invisible to clients; a 1-replica
        pool parks arrivals until :meth:`resume`."""
        r = self._replicas[replica]
        if not self._transition(r, (HEALTHY, DRAINING, DEAD), DRAINING):
            # mid-rebuild: there is no batcher to quiesce yet — report
            # honestly instead of stomping the monitor's REBUILDING state
            return {
                "replica": replica,
                "drained": False,
                "skipped": "rebuild in flight",
                "n_queued": r.batcher.n_queued,
                "n_active": r.batcher.n_active,
            }
        drained = r.batcher.drain(timeout)
        DEFAULT_REGISTRY.counter("pool_drains").inc()
        return {
            "replica": replica,
            "drained": drained,
            "n_queued": r.batcher.n_queued,
            "n_active": r.batcher.n_active,
        }

    def resume(self, replica: int, rebuild: bool = False) -> Dict[str, Any]:
        """Re-open a drained replica — in place (``rebuild=False``) or as
        a fresh batcher (fresh KV cache + worker + recompiled programs;
        the hot-restart / weight-reload path).  Rebuilds are CAS-gated:
        if the monitor already moved this replica into REBUILDING, a
        concurrent operator resume reports that instead of building a
        second batcher over the first (which leaked a live worker thread
        and its KV cache)."""
        r = self._replicas[replica]
        if rebuild or not r.batcher.worker_alive:
            if not self._transition(
                r, (HEALTHY, DRAINING, DEAD), REBUILDING
            ):
                return {
                    "replica": replica,
                    "state": r.state,
                    "generation": r.generation,
                    "skipped": "rebuild already in flight",
                }
            try:
                self._rebuild_replica(r)
            except Exception:
                self._transition(r, (REBUILDING,), DEAD)
                raise
        else:
            r.batcher.resume()
            self._transition(r, (DRAINING, HEALTHY), HEALTHY)
        return {"replica": replica, "state": r.state,
                "generation": r.generation}

    def rolling_restart(
        self, timeout_per_replica: float = 30.0
    ) -> Dict[str, Any]:
        """Drain → rebuild → resume each replica in turn.  In-flight
        requests finish on their replica before it restarts; new
        arrivals route around (or park, in a 1-replica pool) — zero
        dropped requests by construction."""
        steps = []
        for i in range(self.n_replicas):
            step = self.drain(i, timeout=timeout_per_replica)
            self.resume(i, rebuild=True)
            step["rebuilt"] = True
            steps.append(step)
        DEFAULT_REGISTRY.counter("pool_rolling_restarts").inc()
        return {"replicas": steps, "ok": all(s["drained"] for s in steps)}

    # ---- status / compat surface --------------------------------------------

    @property
    def n_active(self) -> int:
        return sum(r.batcher.n_active for r in self._replicas)

    @property
    def n_queued(self) -> int:
        with self._lock:
            parked = len(self._pending)
        return parked + sum(r.batcher.n_queued for r in self._replicas)

    @property
    def n_admitting(self) -> int:
        return sum(r.batcher.n_admitting for r in self._replicas)

    def kv_block_occupancy(self) -> Dict[str, float]:
        """Pool-wide KV block-pool occupancy (telemetry scrape surface —
        same shape as the solo batcher's; counts/bytes sum over
        replicas, per-token byte cost and block size are config-wide)."""
        out: Dict[str, float] = {}
        for r in self._replicas:
            occ = r.batcher.kv_block_occupancy()
            for key in (
                "blocks_total", "blocks_used", "pool_bytes", "used_bytes",
                "tokens_committed", "prefix_entries", "prefix_blocks",
                "prefix_hits", "prefix_misses", "prefix_tokens_avoided",
            ):
                if key in occ:
                    out[key] = out.get(key, 0) + occ[key]
            out["block_size"] = occ["block_size"]
            out["bytes_per_token"] = occ["bytes_per_token"]
        if out.get("blocks_total"):
            out["utilization"] = out["blocks_used"] / out["blocks_total"]
        # cross-replica hit rate re-derived from the summed raw counts
        # (a mean of per-replica ratios would mis-weight uneven traffic)
        lookups = out.get("prefix_hits", 0) + out.get("prefix_misses", 0)
        if lookups:
            out["prefix_hit_rate"] = round(
                out["prefix_hits"] / lookups, 4
            )
        return out

    def block_seconds(self) -> Dict[str, float]:
        """Pool-wide block-second ledger (sums over replicas — each
        allocator's total/billed/residual; docqa-costscope)."""
        out = {"total": 0.0, "billed": 0.0, "residual": 0.0}
        for r in self._replicas:
            bs = r.batcher.block_seconds()
            for k in out:
                out[k] += bs[k]
        return out

    def pressure_by_class(self) -> Dict[str, Any]:
        """Pool-wide shed-forensics snapshot: per-class KV blocks /
        lanes / queue slots summed over replicas plus the pool-level
        pending queue.  LOCK-FREE like the batcher's (it can run on a
        shedding thread that already holds this pool's lock)."""
        by: Dict[str, Dict[str, int]] = {}
        out: Dict[str, Any] = {
            "by_class": by, "free_blocks": 0, "blocks_total": 0,
        }
        for r in self._replicas:
            snap = r.batcher.pressure_by_class()
            for cls, row in snap.get("by_class", {}).items():
                dst = by.setdefault(
                    cls, {"kv_blocks": 0, "lanes": 0, "queued": 0}
                )
                for k in ("kv_blocks", "lanes", "queued"):
                    dst[k] += row.get(k, 0)
            out["free_blocks"] += snap.get("free_blocks", 0)
            out["blocks_total"] += snap.get("blocks_total", 0)
            if "prefix_cache_blocks" in snap:
                out["prefix_cache_blocks"] = (
                    out.get("prefix_cache_blocks", 0)
                    + snap["prefix_cache_blocks"]
                )
        try:
            parked = list(self._pending)
        except RuntimeError:  # deque mutated mid-iteration (lock-free)
            parked = []
        for req in parked:
            cls = req.cost.cls if req.cost is not None else "other"
            by.setdefault(
                cls, {"kv_blocks": 0, "lanes": 0, "queued": 0}
            )["queued"] += 1
        return out

    def set_slo_probe(self, probe) -> None:
        """Wire the SLO burn-rate probe (callable -> list of firing
        alert names) into the pool and every current replica; rebuilds
        re-inherit it via _build_replica."""
        self._slo_probe = probe
        for r in self._replicas:
            try:
                r.batcher.set_slo_probe(probe)
            except Exception:
                pass

    def _slo_firing(self):
        if self._slo_probe is None:
            return []
        try:
            return list(self._slo_probe())
        except Exception:
            return []

    def preemption_candidates(
        self, pressure_cls: str = "interactive"
    ) -> List[Dict[str, Any]]:
        """Pool-wide dry-run victim list: what KV preemption WOULD
        evict if a `pressure_cls` request hit block exhaustion right
        now.  Works in every preemption mode (including off) so
        operators can rehearse the policy before enabling it."""
        out: List[Dict[str, Any]] = []
        for r in self._replicas:
            fn = getattr(r.batcher, "preemption_candidates", None)
            if fn is None:
                continue
            try:
                for row in fn(pressure_cls):
                    out.append({"replica": r.idx, **row})
            except Exception:
                continue
        return out

    def qos_status(self) -> Dict[str, Any]:
        """Aggregate QoS policy state: config + live burn/deferral
        view plus per-replica queue depths by class."""
        if self.qos is None:
            return {"enabled": False}
        firing = self._slo_firing()
        out = self.qos.status()
        out["slo_firing"] = firing
        out["defer_active"] = self.qos.should_defer("batch", firing)
        queued: Dict[str, int] = {}
        for r in self._replicas:
            st = getattr(r.batcher, "qos_status", None)
            if st is None:
                continue
            try:
                for cls, n in st().get("queued_by_class", {}).items():
                    queued[cls] = queued.get(cls, 0) + n
            except Exception:
                continue
        out["queued_by_class"] = queued
        return out

    def status(self) -> Dict[str, Any]:
        with self._lock:
            parked = len(self._pending)
        return {
            "qos": self.qos_status(),
            "replicas": [
                {
                    "replica": r.idx,
                    "state": r.state,
                    "generation": r.generation,
                    "worker_alive": r.batcher.worker_alive,
                    "heartbeat_age_s": round(r.batcher.heartbeat_age_s, 3),
                    "n_queued": r.batcher.n_queued,
                    "n_active": r.batcher.n_active,
                    "breaker": r.breaker.state,
                    "routed": r.routed,
                    "deaths": r.deaths,
                    "canary_ok": r.canary_ok,
                    "canary_failed": r.canary_failed,
                }
                for r in self._replicas
            ],
            "pending": parked,
            "hedge": {
                "enabled": self.hedge_enabled,
                "delay_s": round(self.hedge_delay_s(), 3),
                "samples": len(self._lat),
            },
        }

    def stop(self) -> None:
        # _stopped FIRST: it gates _tick (no new rebuilds start under
        # teardown) and _flush_pending's put-back (no request re-parked
        # onto a deque nobody will drain)
        with self._lock:
            self._stopped = True
            self._cv.notify_all()
        self._monitor_stop.set()
        # a tick already inside a rebuild can legitimately outlive a
        # short join (fresh-batcher construction + KV alloc on a loaded
        # host); abandoning it could let the monitor swap in a fresh
        # worker AFTER the replica sweep below, leaking a live thread
        self._monitor.join(timeout=30)
        if self._monitor.is_alive():
            log.warning("pool monitor still alive after stop() join")
        with self._lock:
            pending = list(self._pending)
            self._pending.clear()
        for req in pending:
            if not req.done.is_set():
                req.error = RuntimeError("pool stopped")
                _finish(req)
        for r in self._replicas:
            try:
                r.batcher.stop()
            except Exception:
                log.exception("replica %d stop failed", r.idx)
        # rebuild warmups may still be compiling; a live XLA compile on a
        # daemon thread at process exit aborts the interpreter
        for t in self._warmups:
            t.join(timeout=60)
