"""Bounded async dispatch spine: the ONE executor device work flows through.

ROADMAP open item 5(c): the process used to grow device-dispatching
threads one PR at a time — batcher worker, pool monitor rebuilds, warmup
threads, sharded retrieves, the telemetry HBM probe — and the reproduced
CPU-client capacity deadlock (``dispatch_streams.json`` budget.evidence:
>= 3 threads holding concurrent sharded dispatches park the process at
0% CPU) was held off by a static ledger instead of an architecture.
This module is the architecture:

* every device dispatch is a **work item** submitted to a per-process
  :class:`DispatchSpine` (``spine_run(stage, closure)``); the submitting
  thread blocks for the result, so call-site semantics — including the
  batcher's one-chunk pipeline, which relies only on *issue order* — are
  unchanged;
* the spine executes items on ``n_lanes`` owned lane threads (default
  2, the count ``serve_cluster_loop.py`` measured clean), so the number
  of threads concurrently inside jax dispatch/compile is **bounded by
  construction** — a third logical stream queues for a lane instead of
  becoming the third concurrent client stream that deadlocks;
* background work (warmups, probes, index rebuilds) is capped at
  ``n_lanes - 1`` concurrent lanes, so serving-class items can always
  make progress even mid compile storm;
* because the spine is the single chokepoint it is the observability
  substrate for free: every item records a ``queue_wait`` /
  ``device_time`` split (``device_time`` = lane-entry to completion of
  the closure, which at fetch sites blocks on ``block_until_ready`` /
  the one device→host fetch — the existing one-fetch-per-dispatch
  boundary), per-stage aggregates feed ``obs.observatory`` (FLOPs/MFU
  accounting), gauges feed the telemetry sampler (``dispatch_*``
  series), and a traced submitter gets a ``dispatch:<stage>`` span.

Work-item closures must be PURE DEVICE PHASES: no app locks acquired
inside an item (submitters may hold locks while blocked on the spine —
an item that takes one could deadlock against its own submitter), no
host bookkeeping that belongs to the calling thread.  The dispatch
sites in serve/generate/retrieve/store keep that discipline; the
``dispatch-streams`` analyzer verifies statically that no OTHER thread
reaches jax except by submitting here.
"""

from __future__ import annotations

import atexit
import collections
import os
import threading
from time import monotonic as _mono
from time import perf_counter as _now
from typing import Any, Callable, Dict, List, Optional

import jax

from docqa_tpu.obs.observatory import DEFAULT_OBSERVATORY
from docqa_tpu.runtime.metrics import DEFAULT_REGISTRY, get_logger

log = get_logger("docqa.spine")

# serving-class streams get lane priority; everything else is background
# ("probe" also carries the retrieval observatory's exact-scan shadow
# queries and nprobe-frontier probes — stage "retrieve_shadow" — so
# shadow sampling can never occupy the last serving lane)
BACKGROUND_STREAMS = frozenset({"warmup", "probe", "rebuild", "background"})
# the disaggregated admission lane (docqa-prefix): prefill work items
# are serving-class but schedule BELOW decode-class items, so one
# replica's long admission prefill cannot head-of-line block another
# replica's decode chunks on the shared lanes.  An aged prefill head
# (waited past this bound) promotes to serving priority — steady decode
# load can delay admissions, never starve them.
PREFILL_STREAMS = frozenset({"prefill"})
PREFILL_MAX_WAIT_S = 0.1


class SpineSaturated(RuntimeError):
    """The spine's bounded queue is full.  Submitters are synchronous,
    so depth tracks the number of live submitting threads — saturation
    means a runaway producer, not normal load, and failing typed beats
    queueing device work without bound."""

    def __init__(self, message: str, depth: Optional[int] = None) -> None:
        self.depth = depth
        if depth is not None:
            message = f"{message} (depth={depth})"
        super().__init__(message)


class SpineClosed(RuntimeError):
    """Submit after :meth:`DispatchSpine.close` — the process is
    tearing down; nothing may enqueue new device work."""


class SpineCancelled(RuntimeError):
    """The ticket was cancelled before a lane picked it up."""


class _Item:
    __slots__ = (
        "stage", "stream", "fn", "args", "kwargs", "cost_key", "sync",
        "deadline", "trace", "span_parent", "t_submit", "done", "result",
        "error", "cancelled", "started", "queue_wait_s", "device_s",
    )

    def __init__(self, stage, stream, fn, args, kwargs, cost_key, sync,
                 deadline, trace, span_parent):
        self.stage = stage
        self.stream = stream
        self.fn = fn
        self.args = args
        self.kwargs = kwargs
        self.cost_key = cost_key
        self.sync = sync
        self.deadline = deadline
        self.trace = trace
        self.span_parent = span_parent
        self.t_submit = _now()
        self.done = threading.Event()
        self.result: Any = None
        self.error: Optional[BaseException] = None
        self.cancelled = False
        self.started = False
        # filled by _account; SpineTicket exposes them so call sites
        # (the batcher's cost attribution) can read an item's measured
        # split without re-deriving it from wall clocks
        self.queue_wait_s = 0.0
        self.device_s = 0.0


class SpineTicket:
    """Future-like handle for a submitted work item."""

    def __init__(self, spine: "DispatchSpine", item: _Item) -> None:
        self._spine = spine
        self._item = item

    def result(self, timeout: Optional[float] = None) -> Any:
        it = self._item
        if it.deadline is not None:
            timeout = it.deadline.bound(timeout)
        if not it.done.wait(timeout):
            if it.deadline is not None and it.deadline.expired:
                # the deadline was the binding constraint: pull the item
                # off the queue if a lane never reached it and report
                # the budget shed, not a generic timeout
                from docqa_tpu.resilience.deadline import DeadlineExceeded

                if not self.cancel():
                    # already on a lane: honor the submitter-blocks
                    # contract — a running closure must never outlive
                    # its submitter's lock scope (store dispatches rely
                    # on that exclusivity), so wait it out, THEN report
                    # the shed.  Same economics as pre-spine, where a
                    # slow dispatch also pinned its calling thread.
                    it.done.wait()
                raise DeadlineExceeded(
                    f"spine:{it.stage}", -it.deadline.remaining()
                )
            raise TimeoutError(
                f"spine item {it.stage!r} did not complete in time"
            )
        if it.error is not None:
            raise it.error
        return it.result

    def cancel(self) -> bool:
        """Best-effort: True when the item had not started and will
        never run (its waiter gets :class:`SpineCancelled`)."""
        return self._spine._cancel(self._item)

    @property
    def done(self) -> bool:
        return self._item.done.is_set()

    @property
    def queue_wait_s(self) -> float:
        """Measured submit→lane wait (valid once done)."""
        return self._item.queue_wait_s

    @property
    def device_s(self) -> float:
        """Measured lane-entry→completion time — the item's device time
        at the one-fetch-per-dispatch boundary (valid once done).  The
        batcher's per-request cost attribution splits exactly this
        value across the requests a fetch covered, so attributed cost
        and the ``dispatch_*`` series can cross-check to ~1.0."""
        return self._item.device_s


class DispatchSpine:
    """Bounded executor for device dispatches (one per process)."""

    def __init__(
        self,
        n_lanes: int = 2,
        max_depth: int = 256,
        inline: bool = False,
        name: str = "spine",
    ) -> None:
        self.n_lanes = max(1, int(n_lanes))
        self.max_depth = max(1, int(max_depth))
        self.inline = bool(inline)
        self.name = name
        self._cv = threading.Condition()
        # three FIFO queues: decode/serving-class items beat prefill
        # items (unless the prefill head has aged — see PREFILL_STREAMS)
        # and both beat background
        self._ready: collections.deque = collections.deque()
        self._ready_pf: collections.deque = collections.deque()
        self._ready_bg: collections.deque = collections.deque()
        self._busy = 0
        self._busy_bg = 0
        self._closed = False
        self._lanes: List[threading.Thread] = []
        self._lane_ids: set = set()
        # strict mode: block_until_ready EVERY item on the lane, so the
        # number of device programs in flight can never exceed the lane
        # count.  None = auto-detect on first execution: ON for the
        # multi-device CPU client (whose collective scheduling deadlocks
        # at >= 3 concurrent sharded programs — dispatch_streams.json
        # budget.evidence; async dispatches would keep programs in
        # flight AFTER their lane freed, re-creating the trio the lanes
        # exist to prevent), OFF elsewhere (single-device and real TPU
        # runtimes keep the async decode pipeline / fused chaining).
        self._strict: Optional[bool] = None
        # per-stage aggregates, guarded by _cv's lock via _stats_lock
        self._stats_lock = threading.Lock()
        self._stage_stats: Dict[str, Dict[str, float]] = {}
        self._submitted = 0
        self._completed = 0
        self._errors = 0
        self._peak_depth = 0

    # ---- lanes ---------------------------------------------------------------

    def _ensure_lanes_locked(self) -> None:
        # a lane that somehow died (its loop is hardened, but belt and
        # braces) is pruned so capacity self-heals instead of silently
        # shrinking one permanent lane at a time
        self._lanes = [t for t in self._lanes if t.is_alive()]
        while len(self._lanes) < self.n_lanes:
            t = threading.Thread(
                target=self._lane_loop,
                daemon=True,
                name=f"{self.name}-lane-{len(self._lanes)}",
            )
            self._lanes.append(t)
            t.start()

    def _lane_loop(self) -> None:
        """THE device stream: the only thread family in the process that
        issues jax dispatches (``dispatch_streams.json`` ledgers exactly
        this entry).  Picks serving items first; background items run on
        at most ``n_lanes - 1`` lanes concurrently.  In STRICT mode
        (the multi-device CPU client) at most ONE lane runs at a time —
        combined with per-item sync that makes device work fully
        serialized, the only bound that client honors (PR-6 notes: even
        2 concurrent sharded dispatches parked it 1-in-4)."""
        self._lane_ids.add(threading.get_ident())
        # resolve the auto-detect ONCE, outside the cv (jax backend init
        # must never run under the spine lock); afterwards the gate
        # reads the live field so reconfigure(strict_sync=...) applies
        # immediately, not per-lane-lifetime
        self.strict_sync()
        while True:
            with self._cv:
                item = None
                while item is None:
                    gate = not self._strict or self._busy == 0
                    # prefill lane discipline: an aged prefill head wins
                    # over fresh decode items (no starvation); otherwise
                    # decode/serving work always runs first
                    pf_aged = bool(self._ready_pf) and (
                        _now() - self._ready_pf[0].t_submit
                        > PREFILL_MAX_WAIT_S
                    )
                    if self._ready and gate and not pf_aged:
                        item = self._ready.popleft()
                    elif self._ready_pf and gate:
                        # covers both "serving queue empty" and the
                        # aged-head promotion (pf_aged implies this
                        # queue is non-empty)
                        item = self._ready_pf.popleft()
                    elif self._ready_bg and gate and (
                        self._busy_bg < max(1, self.n_lanes - 1)
                        or self.n_lanes == 1
                    ):
                        item = self._ready_bg.popleft()
                        self._busy_bg += 1
                    elif self._closed:
                        return
                    else:
                        self._cv.wait(0.5)
                self._busy += 1
            bg = item.stream in BACKGROUND_STREAMS
            try:
                self._execute(item)
            finally:
                with self._cv:
                    self._busy -= 1
                    if bg:
                        self._busy_bg -= 1
                    self._cv.notify_all()

    # ---- execution -----------------------------------------------------------

    def strict_sync(self) -> bool:
        """True when every item must synchronize on its lane (device
        program concurrency == lane concurrency, by construction).
        Auto-detected once (see ``_strict`` in ``__init__``); override
        via :meth:`reconfigure` / ``DOCQA_SPINE_STRICT``."""
        s = self._strict
        if s is None:
            env = os.environ.get("DOCQA_SPINE_STRICT", "")
            if env:
                s = env in ("1", "true", "yes")
            else:
                try:
                    s = (
                        jax.default_backend() == "cpu"
                        and jax.device_count() > 1
                    )
                except Exception:
                    s = False
            self._strict = s
        return s

    def _execute(self, item: _Item) -> None:
        t_start = _now()
        item.started = True
        try:
            if item.deadline is not None and item.deadline.expired:
                # shed before issuing: a dispatch whose answer nobody
                # can use must not spend a lane (mirrors
                # engines.dispatch); accounted below like any error
                from docqa_tpu.resilience.deadline import DeadlineExceeded

                raise DeadlineExceeded(
                    f"spine:{item.stage}", -item.deadline.remaining()
                )
            out = item.fn(*item.args, **item.kwargs)
            if (item.sync or self.strict_sync()) and out is not None:
                out = jax.block_until_ready(out)
            item.result = out
        except BaseException as e:  # propagated to the submitter
            item.error = e
        finally:
            # accounting is best-effort and done.set() is UNCONDITIONAL:
            # an accounting surprise must neither strand the submitter
            # on its ticket nor kill the lane thread
            try:
                self._account(item, t_start, _now())
            except Exception:
                log.exception(
                    "spine accounting failed for stage %r", item.stage
                )
            item.done.set()

    def _account(self, item: _Item, t_start: float, t_end: float) -> None:
        queue_wait = max(t_start - item.t_submit, 0.0)
        device_s = max(t_end - t_start, 0.0)
        item.queue_wait_s = queue_wait
        item.device_s = device_s
        with self._stats_lock:
            row = self._stage_stats.setdefault(
                item.stage,
                {"count": 0, "queue_wait_s": 0.0, "device_s": 0.0,
                 "errors": 0},
            )
            row["count"] += 1
            row["queue_wait_s"] += queue_wait
            row["device_s"] += device_s
            if item.error is not None:
                row["errors"] += 1
                self._errors += 1
            self._completed += 1
        DEFAULT_REGISTRY.histogram("dispatch_queue_wait_ms").observe(
            queue_wait * 1e3
        )
        DEFAULT_REGISTRY.histogram("dispatch_device_ms").observe(
            device_s * 1e3
        )
        if item.error is None:
            try:
                DEFAULT_OBSERVATORY.record(
                    item.stage, item.cost_key, device_s
                )
            except Exception:  # e.g. an unhashable cost_key from a new
                # call site — never the submitter's problem
                log.exception("observatory record failed for %r", item.stage)
        if item.trace is not None:
            try:
                item.trace.record_span(
                    f"dispatch:{item.stage}", item.t_submit, t_end,
                    parent_id=item.span_parent,
                    queue_wait_ms=round(queue_wait * 1e3, 3),
                    device_ms=round(device_s * 1e3, 3),
                    stream=item.stream,
                )
            except Exception:  # a finished trace must never fail a dispatch
                pass
            # per-class cost attribution (docqa-costscope): a submitter
            # -side item under a traced request accrues its measured
            # split to the request's CostRecord (retrieval, store
            # search, solo generate).  Worker-side serve items carry no
            # trace and are attributed explicitly by the batcher — no
            # stage is ever counted twice.
            if item.error is None:
                rec = getattr(item.trace, "cost_record", None)
                if rec is not None:
                    try:
                        rec.account_dispatch(
                            item.stage, queue_wait, device_s
                        )
                    except Exception:
                        pass

    # ---- public API ----------------------------------------------------------

    def submit(
        self,
        stage: str,
        fn: Callable,
        *args,
        stream: str = "serve",
        cost_key: Any = None,
        sync: bool = False,
        deadline=None,
        **kwargs,
    ) -> SpineTicket:
        """Enqueue a device work item; returns a :class:`SpineTicket`.

        ``sync=True`` additionally ``block_until_ready``s the closure's
        return value on the lane — the issue→ready delta IS the item's
        ``device_time`` (use for one-shot dispatch+compute items; the
        batcher's pipelined chunks instead split dispatch and fetch into
        two items so the pipeline overlap survives).  ``cost_key`` links
        the item to a cost model registered with the observatory."""
        from docqa_tpu import obs

        ctx = obs.current()
        trace = ctx.trace if ctx is not None else None
        span_parent = ctx.span_id if ctx is not None else None
        item = _Item(
            stage, stream, fn, args, kwargs, cost_key, sync, deadline,
            trace, span_parent,
        )
        if threading.get_ident() in self._lane_ids:
            # lane re-entrancy (an item whose closure reaches another
            # routed call) executes on the current thread: a lane
            # waiting on its own queue would deadlock the spine
            with self._cv:
                self._submitted += 1
            self._execute(item)
            return SpineTicket(self, item)
        with self._cv:
            # closed-spine and submission accounting apply in BOTH
            # modes: inline must not become a way to enqueue device work
            # mid-teardown, and submitted/completed must stay comparable
            if self._closed:
                raise SpineClosed("dispatch spine is closed")
            self._submitted += 1
            run_inline = self.inline
            if not run_inline:
                depth = (
                    len(self._ready) + len(self._ready_pf)
                    + len(self._ready_bg)
                )
                if depth >= self.max_depth:
                    self._submitted -= 1
                    # shed forensics (docqa-costscope): who held the
                    # machine when the spine refused work — lazy import
                    # (obs.costs is stdlib-only; never a cycle) and
                    # fenced (accounting must not fail the shed path)
                    try:
                        from docqa_tpu.obs.costs import DEFAULT_COST_LEDGER

                        rec = getattr(trace, "cost_record", None)
                        DEFAULT_COST_LEDGER.record_shed(
                            "spine_saturated",
                            cls=rec.cls if rec is not None else None,
                            stage=stage,
                            depth=depth,
                        )
                    except Exception:
                        pass
                    raise SpineSaturated(
                        f"spine queue at capacity for {stage!r}", depth=depth
                    )
                if stream in BACKGROUND_STREAMS:
                    self._ready_bg.append(item)
                elif stream in PREFILL_STREAMS:
                    self._ready_pf.append(item)
                else:
                    self._ready.append(item)
                self._peak_depth = max(self._peak_depth, depth + 1)
                self._ensure_lanes_locked()
                self._cv.notify_all()
        if run_inline:
            # inline mode (a dispatch-overhead A/B's OFF arm, tiny
            # tools): the work item runs on the submitting thread
            self._execute(item)
        return SpineTicket(self, item)

    def run(
        self,
        stage: str,
        fn: Callable,
        *args,
        stream: str = "serve",
        cost_key: Any = None,
        sync: bool = False,
        deadline=None,
        **kwargs,
    ) -> Any:
        """Submit and wait — the call-site idiom (the submitting thread
        keeps its program order, so donated-buffer dispatch sequencing
        is exactly what it was when the thread dispatched directly).
        The wait is clamped to the request deadline when one rides the
        item."""
        ticket = self.submit(
            stage, fn, *args, stream=stream, cost_key=cost_key, sync=sync,
            deadline=deadline, **kwargs,
        )
        timeout = None if deadline is None else deadline.bound(None)
        return ticket.result(timeout=timeout)

    def reconfigure(
        self,
        n_lanes: Optional[int] = None,
        max_depth: Optional[int] = None,
        inline: Optional[bool] = None,
        strict_sync: Optional[bool] = None,
    ) -> "DispatchSpine":
        """Apply runtime config.  Lane count can only change before the
        first lane spins up (the runtime configures at boot); depth,
        inline, and strict apply live."""
        if strict_sync is not None:
            self._strict = bool(strict_sync)
        with self._cv:
            if n_lanes is not None:
                if not self._lanes:
                    self.n_lanes = max(1, int(n_lanes))
                elif int(n_lanes) != self.n_lanes:
                    # never silent: an operator setting dispatch.n_lanes
                    # must know when an earlier spine touch already
                    # pinned the lane count
                    log.warning(
                        "spine lanes already started at n_lanes=%d; "
                        "requested n_lanes=%d ignored (configure the "
                        "spine before the first device dispatch)",
                        self.n_lanes, int(n_lanes),
                    )
            if max_depth is not None:
                self.max_depth = max(1, int(max_depth))
            if inline is not None:
                self.inline = bool(inline)
        return self

    def _cancel(self, item: _Item) -> bool:
        with self._cv:
            for q in (self._ready, self._ready_pf, self._ready_bg):
                try:
                    q.remove(item)
                except ValueError:
                    continue
                item.cancelled = True
                item.error = SpineCancelled(
                    f"spine item {item.stage!r} cancelled before start"
                )
                break
            else:
                return False
        # accounted like any terminal outcome (error row; zero device
        # time) so submitted == completed + in-flight always holds
        t = _now()
        self._account(item, t, t)
        item.done.set()
        return True

    # ---- observability surface ----------------------------------------------

    @property
    def queue_depth(self) -> int:
        with self._cv:
            return (
                len(self._ready) + len(self._ready_pf)
                + len(self._ready_bg)
            )

    @property
    def occupancy(self) -> float:
        """Busy lanes / total lanes — the live value of the concurrency
        bound the ledger used to gate statically."""
        with self._cv:
            return self._busy / self.n_lanes

    def stats(self) -> Dict[str, Any]:
        """Aggregate + per-stage snapshot (``/api/status``)."""
        with self._stats_lock:
            stages = {
                name: dict(row) for name, row in self._stage_stats.items()
            }
            completed, errors = self._completed, self._errors
        with self._cv:
            depth = (
                len(self._ready) + len(self._ready_pf)
                + len(self._ready_bg)
            )
            busy, busy_bg = self._busy, self._busy_bg
            n_lanes, max_depth = self.n_lanes, self.max_depth
            inline, peak = self.inline, self._peak_depth
            submitted = self._submitted
        for row in stages.values():
            n = max(row["count"], 1)
            row["queue_wait_mean_ms"] = round(row["queue_wait_s"] / n * 1e3, 3)
            row["device_mean_ms"] = round(row["device_s"] / n * 1e3, 3)
            row["queue_wait_s"] = round(row["queue_wait_s"], 6)
            row["device_s"] = round(row["device_s"], 6)
        return {
            "n_lanes": n_lanes,
            "max_depth": max_depth,
            "inline": inline,
            "queue_depth": depth,
            "peak_depth": peak,
            "busy_lanes": busy,
            "busy_background": busy_bg,
            "submitted": submitted,
            "completed": completed,
            "errors": errors,
            "stages": stages,
        }

    def telemetry_gauges(self) -> Dict[str, float]:
        """Live gauges for the telemetry sampler (``dispatch_*``)."""
        with self._cv:
            depth = (
                len(self._ready) + len(self._ready_pf)
                + len(self._ready_bg)
            )
            pf_depth = len(self._ready_pf)
            busy, busy_bg = self._busy, self._busy_bg
            n_lanes = self.n_lanes
        return {
            "dispatch_queue_depth": float(depth),
            "dispatch_prefill_queue_depth": float(pf_depth),
            "dispatch_occupancy": busy / n_lanes,
            "dispatch_lanes": float(n_lanes),
            "dispatch_busy_background": float(busy_bg),
        }

    def telemetry_counters(self) -> Dict[str, float]:
        """Cumulative per-stage device/queue time (ms) + item counts —
        the sampler records these as counter series, so ``/api/telemetry``
        serves per-window device-time deltas per stage."""
        out: Dict[str, float] = {}
        with self._stats_lock:
            out["dispatch_items_total"] = float(self._completed)
            out["dispatch_errors_total"] = float(self._errors)
            for name, row in self._stage_stats.items():
                out[f"dispatch_device_ms_{name}"] = row["device_s"] * 1e3
                out[f"dispatch_queue_wait_ms_{name}"] = (
                    row["queue_wait_s"] * 1e3
                )
                out[f"dispatch_count_{name}"] = float(row["count"])
        return out

    def reset_stats(self) -> None:
        """Zero the per-stage aggregates (A/B windows)."""
        with self._stats_lock:
            self._stage_stats.clear()

    # ---- lifecycle -----------------------------------------------------------

    def close(self, timeout: float = 10.0) -> None:
        """Stop accepting work, fail queued items typed, join lanes.
        A lane mid-compile at interpreter exit aborts the process, so
        the atexit hook (and DocQARuntime.stop) calls this."""
        with self._cv:
            if not self._closed:
                self._closed = True
                queued = (
                    list(self._ready) + list(self._ready_pf)
                    + list(self._ready_bg)
                )
                self._ready.clear()
                self._ready_pf.clear()
                self._ready_bg.clear()
                self._cv.notify_all()
                t_close = _now()
                for item in queued:
                    item.error = SpineClosed(
                        f"spine closed before {item.stage!r} ran"
                    )
                    # terminal outcome, accounted like every other
                    # (error row, zero device time): submitted ==
                    # completed holds through teardown too
                    self._account(item, t_close, t_close)
                    item.done.set()
        deadline = _mono() + timeout
        # _lanes is append-only after construction; iterating the live
        # list outside the cv is safe (no lane starts once _closed)
        for t in self._lanes:
            t.join(timeout=max(deadline - _mono(), 0.1))


# ---------------------------------------------------------------------------
# process singleton
# ---------------------------------------------------------------------------

_GLOBAL_LOCK = threading.Lock()
_GLOBAL: Optional[DispatchSpine] = None


def _default_spine() -> DispatchSpine:
    n_lanes = int(os.environ.get("DOCQA_SPINE_LANES", "2") or 2)
    inline = os.environ.get("DOCQA_SPINE_INLINE", "") in ("1", "true", "yes")
    return DispatchSpine(n_lanes=n_lanes, inline=inline)


def get_spine() -> DispatchSpine:
    global _GLOBAL
    # lock-free fast path: every device dispatch calls this, and a
    # CPython reference read is atomic — the lock only guards creation
    s = _GLOBAL
    if s is not None:
        return s
    with _GLOBAL_LOCK:
        if _GLOBAL is None:
            _GLOBAL = _default_spine()
            atexit.register(_GLOBAL.close, 5.0)
        return _GLOBAL


def set_spine(spine: Optional[DispatchSpine]) -> Optional[DispatchSpine]:
    """Swap the process spine (tests, runtime config).  Returns the
    previous one; the CALLER owns closing it."""
    global _GLOBAL
    with _GLOBAL_LOCK:
        prev, _GLOBAL = _GLOBAL, spine
        return prev


def configure(
    n_lanes: Optional[int] = None,
    max_depth: Optional[int] = None,
    inline: Optional[bool] = None,
    strict_sync: Optional[bool] = None,
) -> DispatchSpine:
    """Apply runtime config to the process spine (see
    :meth:`DispatchSpine.reconfigure`)."""
    return get_spine().reconfigure(
        n_lanes=n_lanes, max_depth=max_depth, inline=inline,
        strict_sync=strict_sync,
    )


def spine_run(stage: str, fn: Callable, *args, **kwargs) -> Any:
    """The ONE call-site idiom for routing device work through the
    process spine (the ``dispatch-streams`` analyzer recognizes closures
    passed to this name as spine-delegated, not thread-owned)."""
    return get_spine().run(stage, fn, *args, **kwargs)


def spine_submit(stage: str, fn: Callable, *args, **kwargs) -> SpineTicket:
    """Async variant of :func:`spine_run` (see its docstring)."""
    return get_spine().submit(stage, fn, *args, **kwargs)
