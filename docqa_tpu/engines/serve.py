"""Continuous-batching decode scheduler (BASELINE config 5: full RAG, QPS 16).

The reference served generation through one external Ollama process per
request (``llm-qa/main.py:66-69``) — no batching, no admission control.
Here a fixed pool of decode *slots* shares one PAGED KV block pool and a
two-program compile surface (docqa-paged; ROADMAP item 1, Ragged Paged
Attention arXiv 2604.15464):

* KV paging: prompt and decode K/V live in fixed-size blocks of one flat
  HBM pool (``engines/paged.py``).  A host-side allocator hands each
  request a block table at admission, grows it as decode advances, and
  frees it at retirement — a slot holds HBM proportional to the tokens
  it actually produced, never a worst-case bucket for its lifetime (the
  pre-paged model pinned bucket-sized rows per slot; the `_slot_bucket`
  gauges PR 7 added existed to show exactly that waste).  Pool
  exhaustion is a typed, deadline-aware admission signal
  (:class:`BlockPoolExhausted`), not an OOM.
* admission: every free slot is filled from the queue in one round of
  ragged prefill dispatches — mixed-length prompts PACK into a flat
  token axis (starts 128-aligned, see ``ops/attention.RAGGED_ALIGN``)
  and scatter straight into their block tables.  No shape families, no
  per-bucket padding: the compile key is the packed token budget alone
  (``gen.prefill_token_buckets``, <= 2 programs), versus the old
  (2 families x buckets) matrix — ``compile_budget.json`` gates the
  collapse.  A round's prompts are partitioned so that NO dispatch runs
  a budget larger than its largest prompt needs alone
  (:func:`partition_prefill_round`): a program's cost grows faster
  than its rows (the attention is quadratic in them), so three prompts
  of 384 rows go as three dispatches of the 512-row program, not as
  one of the full-capacity program; short prompts still ride along in
  the free rows of a dispatch that a long prompt needs anyway.  Every
  dispatch is one of the warmed shapes (zero retraces) and the
  round's first tokens are fetched together;
* decode: ONE program advances all slots a chunk of tokens per dispatch
  (``lax.fori_loop`` inside jit — no host round-trip per token, SURVEY §7
  hard part (b)), gathering K/V through the block tables; finished lanes
  go inactive inside the chunk;
* retirement: a slot frees — and returns its KV blocks — as soon as its
  lane hits EOS or its token budget, and the next queued request takes
  it: throughput tracks the number of *live* requests, HBM tracks the
  number of *live tokens*;
* pipelining: the worker keeps ONE decode chunk in flight past the host —
  chunk N+1 is dispatched on chunk N's device-side output state (a pure
  data dependency, no host sync) *before* chunk N's packed results are
  fetched, so the device→host fetch and all host-side token bookkeeping
  overlap the next chunk's device execution.
  Correctness rests on the dispatch-time snapshot: every chunk carries
  the slot→request mapping of its own dispatch, and tokens are
  delivered only to slots whose occupant is still that request — so a
  lane retired between a chunk's dispatch and its fetch (its first token
  was EOS, its budget ran out) can never be misdelivered to.  A chunk is
  dispatched only while some occupied slot can still be owed a token
  after the chunk in flight (``_any_lane_owed``): the budget is the
  host's to enforce, and the host can count — a lane that stays active
  emits at least ``chunk`` tokens a chunk — so a lane that retires on its
  BUDGET decodes no extra chunk, and a batcher whose lanes all end so
  idles with nothing in flight (``serve_decode_chunks_skipped``).  What
  the host cannot foresee still costs one: a lane that retires on EOS, a
  deadline or a cancellation decodes one extra chunk whose tokens are
  discarded (``serve_decode_chunks_stale``) — wasted compute, never wrong
  output — and an in-program capacity guard deactivates any lane before a
  K/V write could land past its allocated blocks (such writes are
  additionally dropped, never clamped, by the paged scatter).  Freed
  blocks can be re-used by the very next admission because the worker
  fetches the chunk in flight, if there is one, before it admits, and
  the pool is DONATED through every dispatch: an overshoot chunk's stale
  writes have landed before the prefill that re-populates those rows is
  dispatched.

Prefix reuse (docqa-prefix, ROADMAP item 1 follow-through): a refcounted
copy-on-write prefix cache (``engines/paged.PrefixCache``) keyed by the
submitter's ``prefix_key`` — for /ask, (template hash, retrieved-chunk-
set hash) — lets the repeat-heavy clinical pattern (many consecutive
questions against one patient's chunk set) map the shared prompt prefix
into a new request's block table at refcount+1 and ragged-prefill ONLY
the novel suffix.  Shared runs are full blocks and 128-aligned, so warm
output is bitwise-identical to a cold prefill; ``release`` decrements
instead of freeing, double frees still raise, and the cache gives its
HBM back (LRU) under :class:`BlockPoolExhausted` pressure before any
live work is shed.  The worker loop admits PREFILL FIRST: a round's
prefill is the next device program and the iteration's one decode chunk
follows it, carrying the live lanes and the lanes just admitted — one
device runs the two back to back either way, so new requests get their
first token a chunk sooner and live lanes finish when they did.  The
prefill rides its own spine stream, ranked below decode-class items, so
one replica's long prefill never holds another replica's chunks.

TP shardings come from ``parallel/sharding.py`` (block pool: kv-heads over
the model axis, block rows replicated); slots ride the batch axis.
"""

from __future__ import annotations

import collections
import itertools
import threading
from dataclasses import dataclass, field
from time import monotonic as time_monotonic
from time import perf_counter as _now
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from docqa_tpu import obs
from docqa_tpu.obs.costs import DEFAULT_COST_LEDGER, cost_record_of
from docqa_tpu.obs.observatory import DEFAULT_OBSERVATORY
from docqa_tpu.engines.paged import (
    STATE_SLOT,
    WINDOW_PAGES,
    BlockAllocator,
    OutOfBlocks,
    PrefixCache,
    init_paged_pools,
    kv_bytes_per_token,
    paged_decode_forward,
    ragged_prefill_forward,
    share_alignment,
)
from docqa_tpu.engines.generate import accept_drafts, draft_tokens
from docqa_tpu.engines.qos import QoSPolicy, request_class
from docqa_tpu.engines.spine import spine_run, spine_submit
from docqa_tpu.ops.attention import (
    RAGGED_ALIGN,
    paged_block_pages,
    paged_block_runs,
)
from docqa_tpu.ops.sampling import sample
from docqa_tpu.ops.scopes import scope
from docqa_tpu.resilience import faults
from docqa_tpu.resilience.deadline import Deadline, DeadlineExceeded
from docqa_tpu.runtime.metrics import DEFAULT_REGISTRY, get_logger, span
from docqa_tpu.utils import round_up

log = get_logger("docqa.serve")

@dataclass
class _Request:
    prompt_ids: List[int]
    max_new: int
    done: threading.Event = field(default_factory=threading.Event)
    tokens: List[int] = field(default_factory=list)
    error: Optional[BaseException] = None
    # notified whenever tokens grow or the request finishes (streaming)
    cv: threading.Condition = field(default_factory=threading.Condition)
    # end-to-end budget stamped at HTTP admission (resilience/deadline.py);
    # the worker sheds this request — from the queue or from a live slot —
    # the moment the budget is gone, instead of decoding for nobody
    deadline: Optional[Deadline] = None
    # request trace (docqa_tpu/obs): the worker thread serves MANY
    # requests, so spans are recorded on each request's own Trace with
    # explicit times — never through the context var (which belongs to
    # the submitting thread).  None = untraced, every hook no-ops.
    trace: Optional[obs.Trace] = None
    span_parent: Optional[str] = None
    t_submit: float = 0.0
    # when the request last ENTERED a queue (reset on every requeue /
    # block-pool bounce): the cost ledger's queue-wait field sums
    # disjoint per-entry intervals, so a bounced request never counts
    # the same wait twice.  t_submit stays the original submission time
    # (the trace span's anchor).
    t_queue: float = 0.0
    # when ``_pop_free_slots`` last popped the request (0 = never): the
    # end of ``serve_queue_wait`` and the start of ``serve_admit_hold``,
    # so the four serve spans tile submit -> first token end to start
    t_pop: float = 0.0
    # pool failover budget (engines/pool.py): how many replica hops this
    # request has already made.  A request is requeued at most
    # ``requeue_max_hops`` times — unbounded hopping would let one poison
    # prompt tour every replica.
    hops: int = 0
    # cooperative cancellation (hedged-dispatch losers, abandoned
    # clients): the worker drops a cancelled request at its next
    # admission round, or retires its slot at the next chunk boundary.
    # A plain bool is enough — one writer flips it, the worker only reads.
    cancelled: bool = False
    # prefix-cache key (docqa-prefix): for /ask this is the
    # (template hash, retrieved-chunk-set hash) pair service/qa.py
    # computes — requests sharing it share a prompt prefix the batcher
    # can serve from cached KV blocks instead of re-prefilling.  Also
    # the session-affinity routing key in engines/pool.py.  None =
    # always-cold (canaries, bulk tools, foreign prompts).
    prefix_key: Optional[str] = None
    # prompt tokens the lane was admitted with (worker-written at
    # admission; it stays true after the slot is handed on): with the
    # delivered-token count this is the worker's host-side KV length of
    # the lane — it drives grow-at-decode and the block-occupancy
    # gauges, and says what a chunk's steps had to read
    # (``_chunk_kv_rows``)
    kv_prompt: int = 0
    # per-class cost attribution (docqa-costscope; obs/costs.py): the
    # request's CostRecord — queue wait, prefill/decode device-ms, KV
    # block-seconds all land here; retired exactly once at _finish.
    # None = unaccounted (ledger disabled).
    cost: Optional[Any] = None
    # a hedge twin SHARES its primary's record (the duplicated decode is
    # real cost of the one logical request) but must not retire it
    cost_shadow: bool = False
    # pool-managed requests are shed/retired by the POOL's terminal
    # decision, not by one replica's refusal (which routing may retry)
    pool_managed: bool = False


def make_request(
    prompt_ids: Sequence[int],
    max_new: int,
    deadline: Optional[Deadline] = None,
    prefix_key: Optional[str] = None,
    req_class: Optional[str] = None,
    cost: Optional[Any] = None,
) -> _Request:
    """Build a :class:`_Request`, capturing the SUBMITTER's trace position
    (the worker thread records every later stage on it explicitly).

    ``req_class`` stamps the request's cost class (docqa-costscope) when
    no class-stamped record is already attached to the submitter's
    trace — the HTTP layer attaches one per endpoint; canaries, warmups
    and bulk tools pass their class explicitly.

    Module-level so :class:`~docqa_tpu.engines.pool.EnginePool` can mint a
    request before it knows which replica will run it — the same request
    object can then be queued, stolen back, and requeued across replicas
    while its Handle keeps waiting on the one ``done``/``cv`` pair."""
    if deadline is not None and deadline.expired:
        # admission is the cheapest place to shed: a request that
        # arrives already out of budget must not take a queue slot
        DEFAULT_REGISTRY.counter("serve_deadline_shed").inc()
        deadline.check("serve_submit")
    req = _Request(
        list(prompt_ids), max_new, deadline=deadline, prefix_key=prefix_key
    )
    ctx = obs.current()
    if ctx is not None:
        req.trace = ctx.trace
        req.span_parent = ctx.span_id
    # record resolution order: an explicitly shared record (the pool's
    # hedge twin rides its primary's), else the trace's endpoint-stamped
    # one, else a fresh open — never two records for one request
    req.cost = cost if cost is not None else cost_record_of(req.trace)
    if req.cost is None:
        req.cost = DEFAULT_COST_LEDGER.open(
            req_class or "interactive", session=prefix_key
        )
    else:
        req.cost.set_session(prefix_key)
    req.t_submit = _now()
    req.t_queue = req.t_submit
    return req


def _cost_add(req: _Request, field: str, value: float) -> None:
    if req.cost is not None and value:
        req.cost.add(field, value)


def _cost_outcome(req: _Request) -> str:
    """Map a finished request's typed error to its ledger outcome."""
    from docqa_tpu.engines.spine import SpineSaturated

    e = req.error
    if e is None:
        return "ok"
    if isinstance(e, DeadlineExceeded):
        return "shed_deadline"
    if isinstance(e, BlockPoolExhausted):
        return "shed_block_pool"
    if isinstance(e, SpineSaturated):
        return "shed_spine"
    if isinstance(e, DeferredByPolicy):
        # checked before the QueueFull catch-all it subclasses: a QoS
        # deferral is a policy choice, not a capacity shed, and the
        # per-class ledger must keep them distinguishable
        return "shed_deferred"
    if isinstance(e, QueueFull):
        return "shed_queue"
    if isinstance(e, RequestCancelled):
        return "cancelled"
    if isinstance(e, WorkerDied):
        return "failed_replica"
    return "error"


def _req_span(req: _Request, name: str, t0: float, t1: float, **attrs) -> None:
    """Attribute a measured interval to the request's trace (no-op when
    untraced).  The one worker-side recording path — spans parent under
    the span that was current at submit time, so a question's whole
    submit→admit→prefill→decode→result-wait is ONE linked timeline."""
    if req.trace is not None:
        req.trace.record_span(
            name, t0, t1, parent_id=req.span_parent, **attrs
        )


def _req_mark(req: _Request, reason: str, anomalous: bool = True, **attrs):
    """Record an instant event on the request's trace; ``anomalous=True``
    also flags it for the flight recorder's always-keep ring."""
    if req.trace is not None:
        if anomalous:
            req.trace.flag(reason)
        req.trace.add_event(reason, span_id=req.span_parent, **attrs)


# How long a gathering round waits for ONE arrival it was told to expect
# (``ContinuousBatcher.expect_arrival``) before it goes without it.  The
# wait normally ends far sooner, on the arrival or on the count dropping:
# an ask is a retrieval long (5-7 ms on an idle device; the widest spacing
# any benchmark cell shows is 45 ms, 9k prompt tokens hashed per ask).  The
# bound is what a miss costs the other way — a request that misses its
# round is admitted a decode chunk later (16 steps of 8-23 ms) — so past
# about one chunk, waiting for it costs the round more than leaving it.
_EXPECTED_ARRIVAL_BOUND_S = 0.2


# One wait policy for every consumer of a Handle (qa /ask, summarize,
# generate_texts) — change it here, not at call sites.
DEFAULT_RESULT_TIMEOUT = 600.0


def _finish(req: _Request) -> None:
    """Mark a request terminal and wake streamers — the ONE completion
    path (done without a cv notify would leave ``iter_tokens`` blocked
    until its wait timeout).  Also the one cost-retirement point: the
    record folds into the per-class ledger with a TYPED outcome
    (exactly once — the ledger guards; a hedge twin never retires its
    shared record)."""
    if req.cost is not None and not req.cost_shadow:
        DEFAULT_COST_LEDGER.retire(req.cost, _cost_outcome(req))
    req.done.set()
    with req.cv:
        req.cv.notify_all()


class WorkerDied(RuntimeError):
    """The batcher's worker thread died (crashed out of its loop — bug,
    injected fault, or a kill by the pool's wedge detector).  Typed so
    waiters get an immediate, attributable failure instead of hanging to
    their :class:`ResultTimeout` — the QA layer maps it into the degraded
    extractive path, and :class:`~docqa_tpu.engines.pool.EnginePool`
    treats it as the replica-death failover trigger."""


class RequestCancelled(RuntimeError):
    """The request was cancelled (hedged-dispatch loser, abandoned
    client) — its lane was released before completion.  Nobody should be
    waiting on a cancelled request; the type exists so an accidental
    waiter sees WHY the tokens never arrived."""


class ResultTimeout(TimeoutError):
    """``Handle.result()``/``iter_tokens()`` waited out its timeout while
    the request was still decoding.  Typed (vs a bare TimeoutError) so
    callers can distinguish *slow* from *shed* (``QueueFull``) and from a
    budget shed (``DeadlineExceeded``) — three different operator
    stories."""

    def __init__(self, waited_s: Optional[float]) -> None:
        self.waited_s = waited_s
        detail = "" if waited_s is None else f" after {waited_s:.1f}s"
        super().__init__(f"generation timed out{detail}")


class Handle:
    """Future-like result for a submitted request."""

    def __init__(self, req: _Request) -> None:
        self._req = req

    def result(
        self, timeout: Optional[float] = DEFAULT_RESULT_TIMEOUT
    ) -> List[int]:
        # a request-scoped deadline bounds the wait below any caller
        # timeout: waiting past it can only ever produce a late answer
        t0 = _now()
        try:
            dl = self._req.deadline
            if dl is not None:
                timeout = dl.bound(timeout)
            if not self._req.done.wait(timeout):
                if dl is not None and dl.expired:
                    # the deadline was the binding constraint: report the
                    # budget shed, not a generic slow-decode timeout (the
                    # worker's own shed may still be a chunk round away)
                    _req_mark(
                        self._req, "deadline_exceeded", stage="serve_result"
                    )
                    raise DeadlineExceeded("serve_result", -dl.remaining())
                _req_mark(self._req, "result_timeout")
                raise ResultTimeout(timeout)
            if self._req.error is not None:
                raise self._req.error
            return list(self._req.tokens)
        finally:
            # the waiter-side span: overlaps the decode-chunk spans the
            # worker records, so the union (coverage) stays gapless from
            # submission to delivery
            _req_span(self._req, "serve_result_wait", t0, _now())

    def text(
        self, tokenizer, timeout: Optional[float] = DEFAULT_RESULT_TIMEOUT
    ) -> str:
        """Wait and detokenize — the shared resolve path."""
        return tokenizer.decode_ids(self.result(timeout))

    def cancel(self) -> None:
        """Best-effort cancellation: the worker drops the request at its
        next admission round (still queued) or retires its slot at the
        next chunk boundary (already decoding).  Used by hedged dispatch
        to release the losing replica's lane — the winner's tokens were
        already delivered through the other handle."""
        self._req.cancelled = True

    @property
    def started(self) -> bool:
        """True once the request has produced at least one token (the
        hedging trigger reads this: a request with a first token has won
        a lane and must not be duplicated)."""
        return bool(self._req.tokens) or self._req.done.is_set()

    def iter_tokens(self, timeout: Optional[float] = DEFAULT_RESULT_TIMEOUT):
        """Stream token ids as decode chunks land (the batcher appends a
        chunk's worth at a time; each append notifies).  Yields every token
        exactly once, in order; raises the request's error (or
        TimeoutError) instead of returning partial output silently."""
        req = self._req
        sent = 0
        if req.deadline is not None:
            timeout = req.deadline.bound(timeout)

        def _timed_out():
            if req.deadline is not None and req.deadline.expired:
                _req_mark(req, "deadline_exceeded", stage="serve_result")
                raise DeadlineExceeded(
                    "serve_result", -req.deadline.remaining()
                )
            _req_mark(req, "result_timeout")
            raise ResultTimeout(timeout)

        deadline = (
            None if timeout is None else time_monotonic() + timeout
        )
        t0 = _now()
        try:
            while True:
                with req.cv:
                    while len(req.tokens) <= sent and not req.done.is_set():
                        remaining = (
                            None
                            if deadline is None
                            else deadline - time_monotonic()
                        )
                        if remaining is not None and remaining <= 0:
                            _timed_out()
                        if not req.cv.wait(remaining):
                            _timed_out()
                    fresh = list(req.tokens[sent:])
                sent += len(fresh)
                for t in fresh:
                    yield t
                if req.done.is_set() and sent >= len(req.tokens):
                    if req.error is not None:
                        raise req.error
                    return
        finally:
            # runs on exhaust, error, AND generator close (client
            # disconnect) — the streaming analogue of result()'s span
            _req_span(req, "serve_result_wait", t0, _now(), streaming=True)


class QueueFull(RuntimeError):
    """Admission control: the wait queue is at capacity.  The HTTP layer
    maps this to 503 — bounded queueing beats unbounded latency growth
    when arrival rate exceeds decode throughput.

    Carries the load snapshot at rejection time (``n_queued`` /
    ``n_active``) so callers — and the 503 body — can say HOW overloaded
    the batcher was, not just that it shed."""

    def __init__(
        self,
        message: str,
        n_queued: Optional[int] = None,
        n_active: Optional[int] = None,
    ) -> None:
        self.n_queued = n_queued
        self.n_active = n_active
        if n_queued is not None or n_active is not None:
            message = (
                f"{message} (queued={n_queued}, active={n_active})"
            )
        super().__init__(message)


class Draining(QueueFull):
    """Admission refused because the batcher is draining (graceful
    restart / weight reload).  A subclass of :class:`QueueFull` so every
    existing 503-mapping keeps working — operationally a drain IS
    transient overload: retry and you land on a healthy replica (the
    pool routes around draining replicas before this is ever raised)."""


class BlockPoolExhausted(QueueFull):
    """The KV block pool ran dry (docs/OPERATIONS.md "Paged KV cache").

    Raised two ways, both typed so the operator story is never a generic
    timeout: (1) at submit, when the queue is full AND the pool has zero
    free blocks — the 503 then names the real bottleneck (HBM, not queue
    capacity; a :class:`QueueFull` subclass so every existing mapping
    holds); (2) on a request's own handle when its lane could not GROW
    mid-decode in an overcommitted pool (``gen.kv_pool_tokens`` below
    worst case) — the QA layer degrades that extractively like any other
    decode failure.  Requests merely WAITING for blocks stay queued and
    keep their deadline semantics: the shed is deadline-aware, with a
    ``block_pool_exhausted`` trace event marking why they waited."""


class DeferredByPolicy(QueueFull):
    """QoS self-protection (docqa-qos): batch-class admission deferred
    because an interactive SLO is burning (obs/slo.py burn-rate
    evaluator — the /ask p95 or availability burn; see
    ``qos.DEFER_SLOS``).  A :class:`QueueFull` subclass so every
    existing 503 mapping and retry policy holds — to a batch client a
    deferral IS transient overload: retry after the burn clears.  Typed
    distinctly because the operator story differs: the queue may be
    nearly EMPTY when this is raised — the runtime is choosing to keep
    it that way for interactive traffic, and relaxes automatically (the
    SLO probe is consulted per submission, so no un-defer edge exists
    to miss).  Ledger outcome ``shed_deferred``, never ``shed_queue``."""


def _pick_budget(budgets: Sequence[int], n_rows: int) -> int:
    """Smallest of the ascending ``budgets`` covering ``n_rows`` (the
    largest for anything bigger)."""
    for t in budgets:
        if n_rows <= t:
            return t
    return budgets[-1]


def partition_prefill_round(
    rows: Sequence[int], warm: Sequence[bool], budgets: Sequence[int]
) -> List[Tuple[bool, int, List[int]]]:
    """Partition one admission round into prefill dispatch groups.

    ``rows[i]``: packed rows entry ``i``'s novel part takes at its
    ``RAGGED_ALIGN`` start; ``warm[i]``: it maps a cached prefix (the
    warm program); ``budgets``: the compiled token budgets, ascending.
    Returns ``(warm, budget, members)`` per dispatch, in dispatch order.

    1. A group never runs a budget larger than its largest member needs
       alone — ``budget = _pick_budget(max member rows)`` — and its rows
       sum to at most that budget.  A program's time grows faster than
       its rows (the float32 attention covers all T x T of them; on a
       v5e the 4096-row Mistral-7B program takes 13x the 512-row one),
       so one large dispatch for several small prompts loses to one
       small dispatch each; a prompt that needs the large budget alone
       has paid for it, and smaller ones ride along in its free rows.
    2. Among such partitions, few groups: first-fit over the members in
       decreasing order of rows (the first member of a group is its
       largest and fixes its budget).
    3. Cold and warm members never share a group (two programs), and
       every cold group comes before every warm one: a warm lane may
       read rows that a cold lane of this very round writes.

    A pure function of shapes: no threshold, no timing, no model name.
    With one budget it is plain first-fit-decreasing bin packing."""
    groups: List[list] = []  # [warm, budget, free rows, members]
    for flag in (False, True):
        opened = len(groups)
        members = [i for i, w in enumerate(warm) if bool(w) == flag]
        # sorted() is stable: members of equal rows keep arrival order
        for i in sorted(members, key=lambda i: -rows[i]):
            for g in groups[opened:]:
                if rows[i] <= g[2]:
                    g[2] -= rows[i]
                    g[3].append(i)
                    break
            else:
                budget = _pick_budget(budgets, rows[i])
                groups.append([flag, budget, budget - rows[i], [i]])
    return [(flag, budget, members) for flag, budget, _free, members in groups]


class _ChunkSnap(list):
    """The slot -> request map at a decode chunk's DISPATCH, and beside it
    ``runs``: what :meth:`ContinuousBatcher._table_runs` read of the block
    tables then (a lane retired before the fetch has none left)."""

    runs: Tuple = ()


class ContinuousBatcher:
    """Slot-based continuous batching over a ``GenerateEngine``'s model."""

    def __init__(
        self,
        engine,  # GenerateEngine: supplies cfg/gen/params/tokenizer/mesh
        n_slots: Optional[int] = None,
        chunk: Optional[int] = None,
        cache_len: Optional[int] = None,
        seed: int = 0,
        max_queue: Optional[int] = 256,
        kv_block_size: Optional[int] = None,
        kv_pool_tokens: Optional[int] = None,
        prefix_cache: Optional[bool] = None,
        qos=None,  # config.QoSConfig | qos.QoSPolicy | None (FIFO)
    ) -> None:
        self.engine = engine
        self.cfg = engine.cfg
        self.gen = engine.gen
        self.mesh = engine.mesh
        self.n_slots = n_slots or self.gen.max_concurrent
        if self.mesh is not None and self.n_slots % self.mesh.n_data:
            self.n_slots = round_up(self.n_slots, self.mesh.n_data)
        # On a mesh every array that travels from one dispatch's outputs
        # to the next dispatch's inputs has ONE fixed sharding: the KV
        # pools under the pool sharding, the slot state replicated.
        # jit keys its cache on input shardings, so a state array the
        # compiler was free to place (or a fresh, uncommitted one) would
        # give the next dispatch a new signature — a recompile of the
        # whole layer stack inside a request (seen on a 1x4 v5e mesh:
        # tens of seconds, every concurrent ask past its deadline).
        self._pool_sharding = self._state_sharding = None
        if self.mesh is not None and self.mesh.n_devices > 1:
            from docqa_tpu.parallel.sharding import paged_pool_sharding

            self._pool_sharding = paged_pool_sharding(self.mesh)
            self._state_sharding = self.mesh.replicated
        self.chunk = chunk or getattr(self.gen, "decode_chunk", 8)
        self._admit_hold_s = max(
            0.0, float(getattr(self.gen, "admit_hold_ms", 0.0))
        ) / 1e3
        self.cache_len = round_up(cache_len or self.cfg.max_seq_len, 128)
        self._seed = seed
        self._rng_counter = itertools.count(1)
        self.max_queue = max_queue
        # prompt-lookup speculation in the served path (greedy only): each
        # chunk iteration verifies spec_k tokens per slot in one weight
        # read; served output stays exactly the solo greedy output
        self.spec_k = (
            self.gen.speculative_k
            if self.gen.speculative_k >= 2 and self.gen.temperature == 0.0
            else 0
        )

        # ---- paged KV geometry (engines/paged.py) ----
        self.block_size = int(
            kv_block_size or getattr(self.gen, "kv_block_size", 16)
        )
        self.block_size = max(1, min(self.block_size, self.cache_len))
        # blocks a single maximal request needs; its table never grows
        # past this, so per-request capacity == the old cache_len budget
        self.blocks_per_seq = -(-self.cache_len // self.block_size)
        self.seq_capacity = self.blocks_per_seq * self.block_size
        pool_tokens = (
            kv_pool_tokens
            or getattr(self.gen, "kv_pool_tokens", None)
            or self.n_slots * self.seq_capacity  # worst-case provisioning
        )
        self.n_blocks = max(
            self.blocks_per_seq, -(-int(pool_tokens) // self.block_size)
        )
        # ragged-prefill token budgets: the WHOLE prefill compile surface.
        # Budgets clamp to the packed capacity one maximal prompt needs
        # (RAGGED_ALIGN-aligned), dedupe, and always include it — so any
        # admissible prompt fits a single dispatch and the set stays <= 2.
        usable = self.cache_len - 2 - self.spec_k
        full_t = round_up(max(usable, 1), RAGGED_ALIGN)
        self._token_buckets = sorted(
            {
                min(round_up(int(t), RAGGED_ALIGN), full_t)
                for t in getattr(
                    self.gen, "prefill_token_buckets", (full_t,)
                )
                if int(t) > 0
            }
            | {full_t}
        )
        # grow-at-decode margin: a pipelined chunk can run one dispatch
        # past the host's token count, and a spec dispatch emits up to
        # chunk-1+K — two dispatches' worth of headroom guarantees the
        # in-program capacity guard is never the thing that stops a live
        # lane (it exists as defense in depth, like the old cache-bound
        # guard)
        self._grow_margin = 2 * (self.chunk + max(self.spec_k, 1)) + 2

        # device state (host-held references; donated through each dispatch).
        # Allocation is a spine work item like every other device phase:
        # a pool-monitor rebuild constructing a replacement batcher must
        # not become its own device stream (engines/spine.py).
        self._alloc = BlockAllocator(self.n_blocks, self.block_size)
        # ---- copy-on-write prefix cache (docqa-prefix) ----
        # Shared runs are full blocks AND 128-aligned (immutability +
        # bitwise warm-vs-cold equality; engines/paged.share_alignment).
        # A cache whose alignment reaches the packed capacity could
        # never leave >= 1 suffix token — disabled rather than dead
        # weight (tiny-cache test configs).
        self._share_align = share_alignment(self.block_size)
        self._prefix_cache: Optional[PrefixCache] = None
        want_cache = (
            bool(getattr(self.gen, "prefix_cache", True))
            if prefix_cache is None
            else bool(prefix_cache)  # A/B + test override
        )
        # what the block kind asks of its surroundings (the record its
        # model module keeps, ``models/serving.py``) and the kernels its
        # programs run (the engine's choice, by what it observed): the
        # batcher reads both and branches on no kind
        self._block = self.engine.block
        self._kernels = self.engine.kernel_forms(block_size=self.block_size)
        # refused here, at construction, by name — not when a request's
        # warm prefill or verify step is traced on the worker thread
        policy = QoSPolicy.coerce(qos)
        on = {
            "generate.prefix_cache": want_cache,
            "generate.speculative_k": self.spec_k,
            "qos.preemption": policy is not None
            and policy.preemption != "off",
        }
        refused = [name for name in self._block.unserved if on[name]]
        if refused:
            raise ValueError(
                f"{self._block.label} is served without "
                + " and ".join(refused) + ": " + self._block.advice
            )
        # a kind whose window layers hold a RING of pages a lane: a second
        # extent with an allocator of its own (a ring a slot: a free slot
        # always finds one), taken and released with the lane's table
        self._ring_pages = (
            self._block.ring_pages(self.block_size)
            if self._block.ring_pages is not None else 0)
        self._ring_alloc = (
            BlockAllocator(self.n_slots * self._ring_pages, self.block_size)
            if self._ring_pages else None)
        if want_cache and self._share_align < self.seq_capacity:
            self._prefix_cache = PrefixCache(
                self._alloc, self._share_align,
                max_entries=int(
                    getattr(self.gen, "prefix_cache_entries", 32)
                ),
            )
        spine_run("serve_alloc", self._init_device_state_on_lane)

        # host-side slot bookkeeping
        self._slot_req: List[Optional[_Request]] = [None] * self.n_slots
        self._slot_budget = np.zeros((self.n_slots,), np.int64)
        # per-request block tables + their device mirror.  _block_rows
        # holds the flat [n_slots, blocks_per_seq] int32 table the decode
        # program indexes (sentinel n_blocks = hole); it re-uploads only
        # when dirty (admission / growth / retirement), so steady decode
        # chunks re-use one device array.  Worker-thread state, like the
        # slot lists above.
        self._slot_table: List[Optional[Any]] = [None] * self.n_slots
        self._block_rows = np.full(
            (self.n_slots, self.blocks_per_seq), self.n_blocks, np.int32
        )
        self._caps_np = np.zeros((self.n_slots,), np.int32)
        self._tables_dev = None
        self._caps_dev = None
        self._tables_dirty = True
        # slots retired on host whose device-side `active` lane has not
        # been cleared yet: applied FIRST inside the next device work
        # item (prefill or decode), so the worker never touches device
        # state outside a spine lane.  Worker-thread state.
        self._deact_pending: List[int] = []
        # id() of the queue head last marked block-starved: one trace
        # event + one serve_block_pool_wait count per starvation
        # episode, not per worker poll (guarded by _cv like the queue)
        self._block_wait_marked: Optional[int] = None

        # ---- multi-tenant QoS (docqa-qos; engines/qos.py) ----
        # With a policy, the admission queue is per-class weighted-fair
        # (same deque surface, so every sweep below is policy-blind);
        # without one (qos=None) it is the plain FIFO deque — bit-for-
        # bit the pre-QoS batcher.
        self._qos: Optional[QoSPolicy] = QoSPolicy.coerce(qos)
        if self._qos is not None:
            self._queue: Any = self._qos.make_queue(now_fn=_now)
        else:
            self._queue = collections.deque()
        # burn-rate probe (obs/slo.BurnRateEvaluator.firing, wired by
        # the service layer): () -> list of firing SLO names.  Consulted
        # per submission — deferral relaxes the instant the burn clears.
        self._slo_probe = None
        # pool hook: called (from the worker thread, outside _cv) with
        # (batcher, victim_request) when a preemption needs a requeue;
        # returns True when the pool placed/parked/typed-failed it —
        # False (or no hook) requeues locally at the victim's class head.
        self.on_preempt = None
        self._cv = threading.Condition()
        self._stopped = False
        # arrivals the caller can already see (``expect_arrival``): asks
        # the HTTP layer has taken in and not yet submitted.  Guarded by
        # ``_cv``; the round that gathers for them is ``_run_loop``'s.
        self._expected = 0
        # requests popped from the queue but not yet slot-resident (the
        # worker's admission round holds them in a local list).  Guarded
        # by ``_cv``.  drain() must count these as pending work: between
        # the queue pop and the slot assignment BOTH "queue empty" and
        # "no active slots" are true, and a drain that declared
        # quiescence in that window would let the pool kill the batcher
        # out from under an admission in flight.
        self._admitting = 0
        # the request OBJECTS of that window, kept in sync with the count
        # (guarded by ``_cv``): the death/kill sweeps must be able to see
        # them — they are in neither ``_queue`` nor ``_slot_req``, and a
        # failure path that only sweeps those two strands them to a bare
        # ResultTimeout (the hang this module promises can't happen)
        self._admitting_reqs: List[_Request] = []
        # liveness contract (engines/pool.py reads all three): the worker
        # stamps ``_beat`` every loop iteration AND every idle wakeup, so
        # a stale heartbeat means the loop is WEDGED inside one iteration
        # (hung device fetch, injected stall) — not merely idle.
        self._beat = time_monotonic()
        # last REAL decode progress (a processed chunk): the pool's
        # canary scheduler treats recent progress as a passed probe —
        # a replica visibly delivering tokens needs no synthetic
        # generate spending a decode lane (and, on the CPU smoke
        # client, adding one more concurrent sharded dispatch)
        self._last_progress = 0.0
        self._worker_dead = False
        self._draining = False
        # cold-start flag: True until warmup() completes or the worker
        # finishes its first decode chunk.  A COLD worker iteration
        # legitimately blocks for a multi-second XLA compile, which looks
        # exactly like a wedge to a heartbeat monitor — the pool skips
        # wedge detection (and canaries) while cold, otherwise a tight
        # heartbeat bound kills every replica mid-first-compile and the
        # rebuild (also cold) spirals.
        self._cold = True
        # pool failover hook: called (from the dying worker thread) with
        # (batcher, queued_requests) when the loop dies; returns the
        # requests it could NOT rescue — those fail typed here.  None =
        # solo batcher, every request fails typed immediately.
        self.on_worker_death = None
        self._prefill_fn = None
        self._prefill_warm_fn = None
        self._decode_fn = None
        # Mosaic custom calls in the lowered decode program, counted by
        # annotate_costs (None until then; 0 = XLA reference attention)
        self.decode_kernel_calls: Optional[int] = None
        # a kind whose lanes keep state beside their rows: which state
        # entry a lane owns travels in the pools (``paged.STATE_SLOT``:
        # keyed by the pool row of a lane's first token); this is its host
        # copy, written at admission and uploaded with the round's prefill
        self._state_slot_np = (
            np.zeros((self.n_blocks * self.block_size,), np.int32)
            if self._block.lane_state else None
        )
        # the pages of each slot's ring (``paged.WINDOW_PAGES``), its host
        # copy: written at admission from the ring's table, uploaded with
        # the slot map (a hole — the extent's size — until a slot admits)
        self._ring_pages_np = (
            np.full((self.n_slots, self._ring_pages),
                    self.n_slots * self._ring_pages, np.int32)
            if self._ring_pages else None
        )
        self._worker = threading.Thread(
            target=self._run, daemon=True, name="continuous-batcher"
        )
        self._worker.start()

    # ---- device programs -----------------------------------------------------

    def _next_rng(self) -> jax.Array:
        # next() on itertools.count is atomic (C level): warmup() runs
        # from a background thread while the worker dispatches, and a
        # torn `+= 1` would mint the SAME PRNGKey for two dispatches
        # (correlated sampling across requests)
        return jax.random.PRNGKey(
            self._seed * 100_003 + next(self._rng_counter)
        )

    def _prefill_program(self, params, pools, ids, seg, pos, dest,
                         last_rows, slots, rng, table=None,
                         block_tables=None, prefix_lens=None):
        """Ragged prefill: one PACKED dispatch admits a whole round of
        mixed-length prompts (engines/paged.py).

        ``ids``/``seg``/``pos``/``dest`` [T] are the packed token stream
        (lane index, in-sequence position, flat block-pool row; padding
        carries seg = -1 and an out-of-bounds dest so its scatter drops),
        ``last_rows`` [n_slots] the packed row of each lane's last prompt
        token, ``slots`` [n_slots] the destination slot per lane (padding
        lanes carry ``n_slots`` — out of bounds, dropped).  T is the only
        compile key: no batch family, no prompt bucket.

        With speculation on, ``table`` rows for the admitted slots are
        REPLACED by each prompt's bigram table (built from the same
        packed stream: consecutive same-segment pairs) plus the confirmed
        last-prompt-token -> first-token pair — the drafting source for
        the speculative decode chunks.

        WARM variant (``block_tables``/``prefix_lens`` set — the prefix
        -cache path): the packed stream carries only each lane's novel
        SUFFIX and attention additionally reads the cached prefix K/V
        through the block tables (engines/paged.py).  A warm lane's
        bigram drafting table covers only its suffix — drafts stay
        verified, so output is unaffected, just fewer accepted drafts
        on heavily-cached prompts."""
        S = self.n_slots
        warm_kw = {}
        if block_tables is not None:
            warm_kw = dict(
                block_tables=block_tables, prefix_lens=prefix_lens,
                n_prefix_rows=self.seq_capacity,
                block_size=self.block_size,
            )
        # a block that routes hands back its record too: its sums ride
        # behind the first tokens (the block's ``prefill_sums``)
        logits, pools, *routed = ragged_prefill_forward(
            params, self.cfg, pools, ids, seg, pos, dest, last_rows,
            rope_len=self.seq_capacity, kernels=self._kernels,
            mesh=self.mesh, **warm_kw,
        )
        with scope("sample"):
            toks = sample(
                logits, rng, self.gen.temperature, self.gen.top_k,
                self.gen.top_p,
            )
            if self._block.prefill_sums is not None:
                toks = jnp.concatenate(
                    [toks, self._block.prefill_sums(routed[0], seg)]
                )
            if table is None:
                return pools, toks
            # per-lane bigram rows from the packed stream: a (prev, next)
            # pair exists wherever two adjacent packed tokens share a
            # segment
            prev, nxt = ids[:-1], ids[1:]
            pair_ok = (seg[:-1] == seg[1:]) & (seg[:-1] >= 0)
            lane = jnp.where(pair_ok, seg[:-1], S)  # OOB -> dropped
            prev = jnp.where(pair_ok, prev, self.cfg.vocab_size)
            rows = jnp.full((S, self.cfg.vocab_size), -1, jnp.int32)
            rows = rows.at[lane, prev].set(nxt, mode="drop")
            rows = rows.at[jnp.arange(S), ids[last_rows]].set(toks)
            table = table.at[slots].set(rows, mode="drop")
            return pools, table, toks

    def _decode_program(self, params, pools, tables, caps, tok, lengths,
                        active, rng):
        """Advance every active slot by ``self.chunk`` tokens in one
        dispatch, reading and writing K/V through the block tables.

        Returns out [S, chunk] (pad on inactive steps), valid [S, chunk]
        (True where the token is a real emission, EOS excluded — so a
        legitimately *sampled* pad_id is preserved), plus updated state.
        The host-facing results are additionally packed into ONE int32
        array so the worker fetches them in a single device→host transfer
        (three separate fetches would be three host round-trips)."""
        S = self.n_slots
        with scope("sample"):
            out0 = jnp.full((S, self.chunk), self.gen.pad_id, jnp.int32)
            valid0 = jnp.zeros((S, self.chunk), bool)
            # the chunk's sums of the block's record; an empty pytree
            # (nothing in the program) for a block that hands none back
            moe0 = (
                (jnp.zeros((len(self._block.step_sum_names),), jnp.int32),)
                if self._block.step_sum_names else ()
            )

        def body(t, carry):
            pools, tok, lengths, active, out, valid, rng, moe = carry
            logits, pools, *routed = paged_decode_forward(
                params, self.cfg, pools, tables, tok[:, None], lengths,
                block_size=self.block_size, rope_len=self.seq_capacity,
                kernels=self._kernels, mesh=self.mesh,
            )
            with scope("sample"):
                if routed:
                    moe = (moe[0] + self._block.step_sums(
                        routed[0], lengths, active),)
                rng, sub = jax.random.split(rng)
                nxt = sample(
                    logits[:, 0], sub, self.gen.temperature, self.gen.top_k,
                    self.gen.top_p,
                )
                nxt = jnp.where(active, nxt, self.gen.pad_id)
                is_eos = active & (nxt == self.gen.eos_id)
                out = out.at[:, t].set(nxt)
                valid = valid.at[:, t].set(active & ~is_eos)
                lengths = lengths + active.astype(jnp.int32)
                active = active & ~is_eos
                # capacity guard: the next step writes row ``lengths``; a
                # lane at its last ALLOCATED row stops here.  The worker's
                # grow-at-decode margin keeps live lanes comfortably under
                # their caps, but a pipelined chunk can run one dispatch
                # past the host-enforced budget (tokens discarded) —
                # without this guard that overshoot's K/V write would be
                # dropped at a position attention could later read as
                # garbage.
                active = (
                    active & (lengths < caps) & (lengths < self.cache_len)
                )
                tok = jnp.where(active, nxt, tok)
            return pools, tok, lengths, active, out, valid, rng, moe

        pools, tok, lengths, active, out, valid, _, moe = jax.lax.fori_loop(
            0,
            self.chunk,
            body,
            (pools, tok, lengths, active, out0, valid0, rng, moe0),
        )
        with scope("sample"):
            packed = jnp.concatenate(
                [out, valid.astype(jnp.int32),
                 active.astype(jnp.int32)[:, None]],
                axis=1,
            )  # [S, 2*chunk + 1] — one D2H fetch for the worker
            if moe:  # one more row of the same fetch: the sums, then zeros
                packed = jnp.concatenate(
                    [packed, jnp.pad(
                        moe[0], (0, packed.shape[1] - moe[0].shape[0])
                    )[None, :]], axis=0,
                )
        return pools, tok, lengths, active, packed

    def _decode_spec_program(self, params, pools, tables, caps, table, tok,
                             lengths, active):
        """Speculative decode chunk over the block pool: loop verify-steps
        until every live slot has emitted >= ``chunk`` tokens (or retired
        on EOS).  Each step drafts ``spec_k - 1`` tokens per slot from its
        bigram table and verifies them in ONE forward of q_len=spec_k (the
        same ``draft_tokens``/``accept_drafts`` halves the solo engine's
        ``spec_verify_step`` uses, composed around the paged forward) —
        the same weight read a single-token step costs — emitting the
        matched prefix + bonus.  Output-exact with the plain chunk program
        (every emitted token is an argmax of the model's logits).

        Returns (pools, table, tok, lengths, active, packed) with packed
        [S, chunk + 2K + 2]: token slab (sized so the K-wide slice write
        can never clamp — see the ``width`` comment), per-slot emission
        count, active flag."""
        S, K = self.n_slots, self.spec_k
        pad = self.gen.pad_id
        # Slab sizing vs the write window: an emitting iteration starts at
        # n_out < chunk and can add up to K tokens, so n_out caps at
        # chunk-1+K; the unconditional K-wide dynamic_update_slice then
        # spans at most chunk-1+2K.  Anything tighter lets the slice CLAMP
        # its start downward and overwrite already-emitted tokens with the
        # pad tail (observed as trailing pads inside a slot's count).
        width = self.chunk + 2 * K
        karange = jnp.arange(K)[None, :]
        out0 = jnp.full((S, width), pad, jnp.int32)
        n0 = jnp.zeros((S,), jnp.int32)

        def cond(st):
            _, _, _, _, active, _, n_out = st
            return jnp.any(active & (n_out < self.chunk))

        def body(st):
            pools, table, tok, lengths, active, out, n_out = st
            with scope("sample"):
                drafts = draft_tokens(table, tok, K)
                verify_in = jnp.concatenate([tok[:, None], drafts], axis=1)
            # (a routing block's record is not counted under speculation)
            logits, pools, *_ = paged_decode_forward(
                params, self.cfg, pools, tables, verify_in, lengths,
                block_size=self.block_size, rope_len=self.seq_capacity,
                kernels=self._kernels, mesh=self.mesh,
            )
            with scope("sample"):
                g, m, cand, is_eos, eos_pos = accept_drafts(
                    logits, drafts, self.gen.eos_id
                )
                # freeze slots that already filled their chunk quota: the loop
                # keeps running for slower slots, and a frozen slot must not
                # emit, advance, or retire until the next dispatch
                live = active & (n_out < self.chunk)
                emit_valid = (
                    cand
                    & (karange < eos_pos[:, None])
                    & live[:, None]
                )
                emitted = jnp.where(emit_valid, g, pad)
                out = jax.vmap(
                    lambda o, v, off: jax.lax.dynamic_update_slice(
                        o, v, (off,))
                )(out, emitted, n_out)
                n_valid = jnp.sum(emit_valid.astype(jnp.int32), axis=1)
                n_out = n_out + n_valid
                # a frozen slot's un-consumed EOS re-derives next dispatch
                saw_eos = live & jnp.any(is_eos, 1)
                last_tok = jnp.take_along_axis(
                    emitted, jnp.maximum(n_valid - 1, 0)[:, None], 1
                )[:, 0]
                table = self.engine.confirm_bigrams(table, tok, g, emit_valid)
                lengths = lengths + jnp.where(active, n_valid, 0)
                active = active & ~saw_eos
                # capacity guard (see _decode_program): a verify writes the
                # K-row window [lengths, lengths+K) — stop the lane while
                # that window still fits its ALLOCATED blocks, so a pipelined
                # overshoot chunk can only ever drop writes, never land them
                # where attention could read them back.
                active = (
                    active
                    & (lengths <= caps - K)
                    & (lengths < self.cache_len - K)
                )
                tok = jnp.where(active & (n_valid > 0), last_tok, tok)
            return pools, table, tok, lengths, active, out, n_out

        pools, table, tok, lengths, active, out, n_out = jax.lax.while_loop(
            cond, body, (pools, table, tok, lengths, active, out0, n0)
        )
        with scope("sample"):
            packed = jnp.concatenate(
                [out, n_out[:, None], active.astype(jnp.int32)[:, None]],
                axis=1,
            )  # [S, width + 2] — one D2H fetch for the worker
        return pools, table, tok, lengths, active, packed

    # The three adapters below bind ``_prefill_program``'s keyword
    # arguments for the speculative table and the prefix-cache (warm)
    # inputs.  Named methods, not lambdas: a jitted function's name is the
    # program's name in a device trace and in the compile log, and
    # ``jit__lambda`` says nothing there.

    def _prefill_spec_program(self, p, c, t, i, sg, po, d, lr, sl, r):
        return self._prefill_program(p, c, i, sg, po, d, lr, sl, r, table=t)

    def _prefill_warm_spec_program(
        self, p, c, t, i, sg, po, d, lr, sl, bt, pl, r
    ):
        return self._prefill_program(
            p, c, i, sg, po, d, lr, sl, r, table=t,
            block_tables=bt, prefix_lens=pl,
        )

    def _prefill_warm_program(self, p, c, i, sg, po, d, lr, sl, bt, pl, r):
        return self._prefill_program(
            p, c, i, sg, po, d, lr, sl, r,
            block_tables=bt, prefix_lens=pl,
        )

    def _pinned(self, n_state_out: int) -> dict:
        """``jax.jit`` kwargs pinning a serve program's output shardings
        on a mesh — ``(pools, *n_state_out slot-state arrays)`` — see
        ``_pool_sharding`` in ``__init__``; nothing without a mesh."""
        if self._state_sharding is None:
            return {}
        return {
            "out_shardings": (self._pool_sharding,)
            + (self._state_sharding,) * n_state_out
        }

    def _get_prefill_fn(self):
        """One jit object; XLA re-specializes per packed-token-budget
        shape T alone.  ``_admit_round`` runs each of a round's dispatch
        groups at the smallest budget in ``self._token_buckets`` that
        holds its largest prompt, so the WHOLE prefill compile surface is
        ``len(self._token_buckets)`` programs (<= 2) — the old policy of
        two batch families x every prompt bucket is gone, and
        :meth:`warmup` pre-compiles the full set before traffic (the
        compile audit holds the steady state to zero retraces against
        ``compile_budget.json``)."""
        if self._prefill_fn is None:
            if self.spec_k:
                self._prefill_fn = jax.jit(
                    self._prefill_spec_program,
                    donate_argnums=(1, 2), **self._pinned(2),
                )
            else:
                self._prefill_fn = jax.jit(
                    self._prefill_program, donate_argnums=(1,),
                    **self._pinned(1),
                )
        return self._prefill_fn

    def _get_prefill_warm_fn(self):
        """The WARM ragged-prefill jit (prefix-cache admissions): same
        packed-token-budget shapes as the cold program plus the block
        tables / per-lane prefix lengths.  A separate jit object so COLD
        rounds keep compiling (and running) exactly the pre-prefix
        program — cold numerics and cold cost are untouched; the warm
        family adds at most ``len(self._token_buckets)`` programs to the
        compile surface (compile_budget.json gates the new total)."""
        if self._prefill_warm_fn is None:
            if self.spec_k:
                self._prefill_warm_fn = jax.jit(
                    self._prefill_warm_spec_program,
                    donate_argnums=(1, 2), **self._pinned(2),
                )
            else:
                self._prefill_warm_fn = jax.jit(
                    self._prefill_warm_program,
                    donate_argnums=(1,), **self._pinned(1),
                )
        return self._prefill_warm_fn

    def _get_decode_fn(self):
        if self._decode_fn is None:
            if self.spec_k:
                # donate the pool + spec table; block tables and caps are
                # small host-refreshed arrays reused across chunks
                self._decode_fn = jax.jit(
                    self._decode_spec_program, donate_argnums=(1, 4),
                    **self._pinned(5),
                )
            else:
                self._decode_fn = jax.jit(
                    self._decode_program, donate_argnums=(1,),
                    **self._pinned(4),
                )
        return self._decode_fn

    def _fresh_device_state(self):
        """A throwaway (pools, table, tok, lengths, active) tuple with the
        exact shapes/dtypes/shardings of the live slot state — warmup
        dispatches donate THESE instead of the live buffers, so a warmup
        can run concurrently with serving without ever racing the worker
        for ``self._pools``."""
        pools = init_paged_pools(
            self.cfg, self.n_blocks, self.block_size,
            sharding=self._pool_sharding, n_lanes=self.n_slots,
        )
        rep = self._state_sharding
        table = (
            jnp.full(
                (self.n_slots, self.cfg.vocab_size), -1, jnp.int32, device=rep
            )
            if self.spec_k
            else None
        )
        tok = jnp.zeros((self.n_slots,), jnp.int32, device=rep)
        lengths = jnp.zeros((self.n_slots,), jnp.int32, device=rep)
        active = jnp.zeros((self.n_slots,), bool, device=rep)
        return pools, table, tok, lengths, active

    def _init_device_state_on_lane(self):
        """Fresh pools + zeroed slot state ASSIGNED to self — the ONE
        initialization shared by construction (``serve_alloc``) and the
        failed-dispatch reset (``serve_reset``), both spine work items.
        Returns the new device arrays so strict mode can sync them (a
        None-returning closure would leave the allocation programs in
        flight after the lane freed)."""
        pools, table, tok, lengths, active = self._fresh_device_state()
        self._pools = pools
        self._table = table
        self._tok = tok
        self._lengths = lengths
        self._active = active
        return pools, table, tok, lengths, active

    def warmup(self, buckets: Optional[Sequence[int]] = None) -> None:
        """Compile the whole admission-path shape set ahead of traffic.

        The set is small by construction now: one ragged prefill program
        per packed token budget (``self._token_buckets``, <= 2) plus the
        one decode chunk — versus the pre-paged (2 shape families x
        prompt buckets) matrix this replaces.  Warming still matters: a
        shape left cold compiles inside the first live request that hits
        it (the r05 open-loop runs paid exactly that).

        Every warm dispatch donates a throwaway state tuple
        (``_fresh_device_state``) and scatters all tokens/lanes out of
        bounds, so live slots are untouched and the method is safe to run
        from a background thread while traffic arrives.  ``buckets``
        narrows the warmed token budgets (legacy arg: values are mapped
        onto the budgets they pack into); default warms every budget.
        """
        if buckets is None:
            warm = list(self._token_buckets)
        else:
            # map requested prompt sizes onto the token budgets their
            # admission rounds would actually dispatch
            warm = sorted({self._pick_token_bucket(int(b)) for b in buckets})
        fn = self._get_prefill_fn()
        S = self.n_slots
        oob_row = self.n_blocks * self.block_size

        # each warm compile is one BACKGROUND spine item: a warmup can
        # never again become the third concurrent client stream (the
        # serve_cluster_loop --warm-thread deadlock), and it can occupy
        # at most n_lanes-1 lanes while live traffic keeps the rest
        def _warm_prefill_on_lane(T: int, prefix: bool = False):
            pools, table, _tok, _lengths, _active = (
                self._fresh_device_state()
            )
            ids = jnp.full((T,), self.gen.pad_id, jnp.int32)
            seg = jnp.full((T,), -1, jnp.int32)  # every token is padding
            pos = jnp.zeros((T,), jnp.int32)
            dest = jnp.full((T,), oob_row, jnp.int32)  # dropped writes
            last_rows = jnp.zeros((S,), jnp.int32)
            slots = jnp.full((S,), S, jnp.int32)  # OOB == dropped
            args = (ids, seg, pos, dest, last_rows, slots)
            if prefix:
                # the warm-admission program family: all-sentinel tables
                # and zero prefix lengths trace/compile the full prefix
                # -gather path without reading a live block
                tabs = jnp.full(
                    (S, self.blocks_per_seq), self.n_blocks, jnp.int32
                )
                plens = jnp.zeros((S,), jnp.int32)
                args = args + (tabs, plens)
                use = self._get_prefill_warm_fn()
            else:
                use = fn
            if self.spec_k:
                out = use(
                    self.engine.params, pools, table, *args,
                    self._next_rng(),
                )
            else:
                out = use(
                    self.engine.params, pools, *args, self._next_rng(),
                )
            return out

        for T in warm:
            spine_run(
                "serve_warmup", _warm_prefill_on_lane, T,
                stream="warmup", sync=True,
            )
            if self._prefix_cache is not None:
                spine_run(
                    "serve_warmup", _warm_prefill_on_lane, T, True,
                    stream="warmup", sync=True,
                )

        # decode chunk: one shape regardless of prompt mix — all-inactive
        # lanes still trace/compile the full program (all-sentinel tables)
        dfn = self._get_decode_fn()

        def _warm_decode_on_lane():
            pools, table, tok, lengths, active = self._fresh_device_state()
            tables = jnp.full(
                (S, self.blocks_per_seq), self.n_blocks, jnp.int32
            )
            caps = jnp.zeros((S,), jnp.int32)
            if self.spec_k:
                out = dfn(self.engine.params, pools, tables, caps, table,
                          tok, lengths, active)
            else:
                out = dfn(
                    self.engine.params, pools, tables, caps, tok, lengths,
                    active, self._next_rng(),
                )
            return out

        spine_run(
            "serve_warmup", _warm_decode_on_lane, stream="warmup", sync=True,
        )
        # warmed shapes cover the admission path: worker iterations are
        # now bounded by real chunk rounds, so liveness checks may engage
        self._cold = False

    def annotate_costs(self) -> bool:
        """Register the prefill/decode programs' ``cost_analysis()``
        FLOPs/bytes with the observatory (``obs/observatory.py``), so
        the spine's measured device time yields per-stage MFU instead
        of wall-clock guesses.

        Costs key the stages that MEASURE device time at the one-fetch
        boundary: each prefill token budget T under
        ``("serve_prefill_fetch", T)`` and the decode chunk under
        ``("serve_decode_chunk", "decode")``.  Pure host tracing
        (``lower()`` on abstract shapes — no allocation, no compile),
        still routed as a background probe item so no caller thread
        grows a client stream.  Returns False when the backend offers
        no estimate; never raises."""
        from docqa_tpu.obs.observatory import DEFAULT_OBSERVATORY

        S = self.n_slots

        def _annotate_on_lane() -> bool:
            try:
                pools_s = jax.eval_shape(
                    lambda: init_paged_pools(
                        self.cfg, self.n_blocks, self.block_size,
                        n_lanes=self.n_slots,
                    )
                )
                params_s = jax.tree_util.tree_map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                    self.engine.params,
                )
                i32 = jnp.int32
                rng_s = jax.ShapeDtypeStruct((2,), jnp.uint32)
                table_s = jax.ShapeDtypeStruct(
                    (S, self.cfg.vocab_size), i32
                )
                ok = False
                fn = self._get_prefill_fn()
                tabs_s = jax.ShapeDtypeStruct(
                    (S, self.blocks_per_seq), i32
                )
                plens_s = jax.ShapeDtypeStruct((S,), i32)
                for T in self._token_buckets:
                    packed = (
                        jax.ShapeDtypeStruct((T,), i32),  # ids
                        jax.ShapeDtypeStruct((T,), i32),  # seg
                        jax.ShapeDtypeStruct((T,), i32),  # pos
                        jax.ShapeDtypeStruct((T,), i32),  # dest
                        jax.ShapeDtypeStruct((S,), i32),  # last_rows
                        jax.ShapeDtypeStruct((S,), i32),  # slots
                    )
                    args = packed + (rng_s,)
                    if self.spec_k:
                        low = fn.lower(params_s, pools_s, table_s, *args)
                    else:
                        low = fn.lower(params_s, pools_s, *args)
                    ok = DEFAULT_OBSERVATORY.annotate_lowered(
                        "serve_prefill_fetch", low, key=T
                    ) or ok
                    if self._prefix_cache is not None:
                        # the warm program has its own cost model (the
                        # prefix gather + wider score axis); its fetch
                        # accrues under ("warm", T) cost keys
                        wargs = packed + (tabs_s, plens_s, rng_s)
                        wfn = self._get_prefill_warm_fn()
                        if self.spec_k:
                            wlow = wfn.lower(
                                params_s, pools_s, table_s, *wargs
                            )
                        else:
                            wlow = wfn.lower(params_s, pools_s, *wargs)
                        ok = DEFAULT_OBSERVATORY.annotate_lowered(
                            "serve_prefill_fetch", wlow, key=("warm", T)
                        ) or ok
                dfn = self._get_decode_fn()
                tables_s = jax.ShapeDtypeStruct(
                    (S, self.blocks_per_seq), i32
                )
                caps_s = jax.ShapeDtypeStruct((S,), i32)
                tok_s = jax.ShapeDtypeStruct((S,), i32)
                len_s = jax.ShapeDtypeStruct((S,), i32)
                act_s = jax.ShapeDtypeStruct((S,), jnp.bool_)
                if self.spec_k:
                    low = dfn.lower(params_s, pools_s, tables_s, caps_s,
                                    table_s, tok_s, len_s, act_s)
                else:
                    low = dfn.lower(params_s, pools_s, tables_s, caps_s,
                                    tok_s, len_s, act_s, rng_s)
                ok = DEFAULT_OBSERVATORY.annotate_lowered(
                    "serve_decode_chunk", low, key="decode"
                ) or ok
                self.decode_kernel_calls = low.as_text().count(
                    "tpu_custom_call"
                )
                return ok
            except Exception:
                log.exception("cost annotation failed (MFU stays unknown)")
                return False

        return bool(spine_run("serve_costs", _annotate_on_lane,
                              stream="probe"))

    def _pick_token_bucket(self, n_tokens: int) -> int:
        """Smallest packed token budget covering ``n_tokens`` (the
        largest budget for anything bigger — which admission never
        packs: a prompt is truncated to fit the largest budget alone,
        and :func:`partition_prefill_round` fills a group only up to
        the budget its largest member picked)."""
        return _pick_budget(self._token_buckets, n_tokens)

    # ---- public API ----------------------------------------------------------

    @property
    def prefix_cache_enabled(self) -> bool:
        """Submitters (service/qa.py) check this before passing a
        ``prefix_key`` — batcher stand-ins without the kwarg stay
        compatible."""
        return self._prefix_cache is not None

    def submit_ids(
        self,
        prompt_ids: Sequence[int],
        max_new_tokens: Optional[int] = None,
        deadline: Optional[Deadline] = None,
        prefix_key: Optional[str] = None,
        req_class: Optional[str] = None,
    ) -> Handle:
        max_new = max_new_tokens or self.gen.max_new_tokens
        return self.submit_request(
            make_request(
                prompt_ids, max_new, deadline=deadline,
                prefix_key=prefix_key, req_class=req_class,
            )
        )

    def submit_request(self, req: _Request) -> Handle:
        """Admit an already-built :class:`_Request` (the pool's requeue
        path re-admits the SAME object on a different replica, so the
        original Handle keeps waiting on the same ``done``/``cv``)."""
        with self._cv:
            # every early refusal retires the record make_request just
            # opened (via _record_shed, which correctly SKIPS pool-
            # managed requests — for those a refusal is routing, and
            # the record lives on to the replica that places or the
            # pool's terminal _shed).  Caught by the ledger witness:
            # a direct submit bouncing off a stopped/dead/draining
            # batcher stranded its cost record forever.
            if self._worker_dead:
                self._record_shed(
                    req, "worker_dead", outcome="failed_replica",
                    stage="serve_submit",
                )
                raise WorkerDied("batcher worker is dead")
            if self._stopped:
                self._record_shed(
                    req, "stopped", outcome="error", stage="serve_submit",
                )
                raise RuntimeError("batcher is stopped")
            if self._draining:
                self._record_shed(
                    req, "draining", outcome="shed_queue",
                    stage="serve_submit", n_queued=len(self._queue),
                )
                raise Draining(
                    "batcher is draining",
                    n_queued=len(self._queue),
                    n_active=sum(1 for r in self._slot_req if r is not None),
                )
            if not req.pool_managed and self._qos is not None:
                # SLO-aware self-protection (docqa-qos): while an
                # interactive SLO burns, batch-class admission defers
                # typed.  Pool-managed requests skip this — the pool
                # already ran the same check once at dispatch, and a
                # per-replica re-check would turn one deferral decision
                # into N (inflating counters and double-retiring costs).
                cls = request_class(req)
                firing = self._slo_firing()
                if self._qos.should_defer(cls, firing):
                    DEFAULT_REGISTRY.counter("qos_deferred").inc()
                    DEFAULT_REGISTRY.counter(f"qos_deferred_{cls}").inc()
                    _req_mark(
                        req, "qos_deferred", stage="serve_submit",
                        firing=",".join(firing),
                    )
                    self._record_shed(
                        req, "deferred_by_policy", outcome="shed_deferred",
                        stage="serve_submit", firing=",".join(firing),
                    )
                    raise DeferredByPolicy(
                        "batch admission deferred: interactive SLO "
                        f"burning ({', '.join(firing)})",
                        n_queued=len(self._queue),
                        n_active=sum(
                            1 for r in self._slot_req if r is not None
                        ),
                    )
            if (
                self.max_queue is not None
                and len(self._queue) >= self.max_queue
            ):
                DEFAULT_REGISTRY.counter("serve_shed").inc()
                n_active = sum(1 for r in self._slot_req if r is not None)
                if self._alloc.n_free == 0 and self._prefix_cache is not None:
                    # under BlockPoolExhausted pressure, cached-but-idle
                    # prefixes give their HBM back BEFORE live work is
                    # shed — only refcount-1 (cache-only) blocks free
                    self._prefix_cache.evict_for(1)
                if self._alloc.n_free == 0:
                    # the queue backed up BECAUSE the block pool is dry:
                    # name the real bottleneck (HBM overcommit, not queue
                    # sizing) — same 503, different operator story
                    DEFAULT_REGISTRY.counter("serve_block_shed").inc()
                    _req_mark(
                        req, "block_pool_exhausted",
                        n_queued=len(self._queue),
                    )
                    self._record_shed(
                        req, "block_pool_exhausted", stage="serve_submit",
                        n_queued=len(self._queue), n_active=n_active,
                    )
                    raise BlockPoolExhausted(
                        "KV block pool exhausted and generation queue at "
                        f"capacity ({self.max_queue})",
                        n_queued=len(self._queue),
                        n_active=n_active,
                    )
                _req_mark(
                    req, "queue_full", n_queued=len(self._queue)
                )
                self._record_shed(
                    req, "queue_full", stage="serve_submit",
                    n_queued=len(self._queue), n_active=n_active,
                )
                raise QueueFull(
                    f"generation queue at capacity ({self.max_queue})",
                    n_queued=len(self._queue),
                    n_active=n_active,
                )
            req.t_queue = _now()  # (re-)entering this queue: the cost
            # ledger's queue-wait interval restarts (requeue-safe)
            self._queue.append(req)
            n_queued = len(self._queue)
            self._cv.notify_all()
        _req_mark(
            req, "serve_submit", anomalous=False,
            n_queued=n_queued, prompt_len=len(req.prompt_ids),
        )
        DEFAULT_REGISTRY.counter("serve_submitted").inc()
        return Handle(req)

    def submit_text(
        self,
        prompt: str,
        max_new_tokens: Optional[int] = None,
        deadline: Optional[Deadline] = None,
        prefix_key: Optional[str] = None,
        req_class: Optional[str] = None,
    ) -> Handle:
        # same text entry contract as GenerateEngine.generate_texts: the
        # configured chat template wraps here too (template-aware
        # truncation against THIS batcher's cache budget), so /ask answers
        # from a batcher match solo-engine answers token-for-token
        usable = self.cache_len - 2 - self.spec_k
        return self.submit_ids(
            self.engine.encode_prompt(prompt, usable),
            max_new_tokens,
            deadline=deadline,
            prefix_key=prefix_key,
            req_class=req_class,
        )

    def generate_texts(
        self, prompts: Sequence[str], max_new_tokens: Optional[int] = None
    ) -> List[str]:
        """Batch-convenience API (same contract as GenerateEngine): accepts
        any N.  Backpressure (``max_queue``) is an admission-control signal
        for ONLINE callers; a bulk batch instead waits for the queue to
        drain — shedding mid-batch would abandon already-admitted work.
        The whole call is bounded end to end (``DEFAULT_RESULT_TIMEOUT``
        as a :class:`Deadline` threaded through every submit and wait),
        and a batcher with queueing disabled outright (``max_queue=0``)
        fails fast.  Queue-full waits ride the batcher's condition
        variable — ``_pop_free_slots`` notifies as admissions drain the
        queue — instead of sleep-polling the serving path."""
        if self.max_queue == 0:
            raise QueueFull("batcher has queueing disabled (max_queue=0)")
        deadline = Deadline.after(DEFAULT_RESULT_TIMEOUT)
        handles = []
        for p in prompts:
            while True:
                try:
                    handles.append(
                        self.submit_text(
                            p, max_new_tokens, deadline=deadline,
                            req_class="batch",
                        )
                    )
                    break
                except DeadlineExceeded as e:
                    # the bulk budget lapsed between the capacity wait and
                    # this resubmit (admission sheds expired deadlines) —
                    # keep the method's documented failure mode
                    raise QueueFull(
                        "generation queue stayed full past the bulk "
                        f"budget ({e})",
                        n_queued=self.n_queued,
                        n_active=self.n_active,
                    ) from e
                except QueueFull:
                    if deadline.expired:
                        raise
                    # woken when an admission round frees queue space; the
                    # 50 ms cap bounds the wait against a stalled worker
                    with self._cv:
                        self._cv.wait(deadline.bound(0.05))
        return [h.text(self.engine.tokenizer) for h in handles]

    def expect_arrival(self) -> Callable[[], None]:
        """Say that a request is on its way; call what this returns when
        it has been submitted, or never will be (any number of times, from
        any thread).

        A round that pops its first request into an idle batcher keeps
        gathering while arrivals are expected and slots are free
        (``_run_loop``), so requests that leave the caller's preamble
        milliseconds apart are ONE admission — and a request with nobody
        behind it waits for nobody.  The caller submits BEFORE it calls
        back: the worker then never sees "none expected, queue empty"
        with a request in between.  Callers that expect nothing (direct
        ``submit_*``) are admitted as ever."""
        with self._cv:
            self._expected += 1
        counted = True

        def arrived() -> None:
            nonlocal counted
            with self._cv:
                if not counted:
                    return
                counted = False
                self._expected -= 1
                if not self._expected:
                    # the last one may have left without a submit (routed
                    # away, shed, failed): the gather ends on this, not
                    # on its bound
                    self._cv.notify_all()

        return arrived

    def stop(self) -> None:
        with self._cv:
            self._stopped = True
            self._cv.notify_all()
        self._worker.join(timeout=10)
        # Sweep UNDER the lock, and include the admission window: a
        # worker that outlived the join (wedged in a device fetch) can
        # still mutate the deque mid-iteration, and requests it had
        # popped but not yet made slot-resident live in NEITHER _queue
        # nor _slot_req — the pre-PR-8 sweep read both lock-free and
        # missed the window entirely, so a stop() against a wedged
        # worker stranded those requests to their ResultTimeout
        # (guarded-state true positive; regression-tested in
        # tests/test_racecheck.py).
        with self._cv:
            swept = (
                self._admitting_reqs
                + list(self._queue)
                + [r for r in self._slot_req if r]
            )
            self._admitting_reqs = []
            self._admitting = 0
            self._queue.clear()
        for req in swept:
            if not req.done.is_set():
                req.error = RuntimeError("batcher stopped")
                _finish(req)
        # block accounting closes with the batcher: every slot's table
        # returns to the pool exactly once (release is idempotent and
        # allocator-locked, so a wedged worker racing its own retire
        # cannot double-free), and the prefix cache's pins go with it
        for slot in range(self.n_slots):
            self._release_slot_blocks(slot)
        if self._prefix_cache is not None:
            self._prefix_cache.clear()

    # ---- liveness / graceful-drain contract (engines/pool.py) ---------------

    @property
    def worker_alive(self) -> bool:
        """The worker loop can still make progress (thread running and
        not past its death handler)."""
        return self._worker.is_alive() and not self._worker_dead

    @property
    def heartbeat_age_s(self) -> float:
        """Seconds since the worker last stamped its loop heartbeat.  An
        idle worker re-stamps every 0.5 s wakeup, so a large age with
        work pending means the loop is wedged INSIDE one iteration."""
        return time_monotonic() - self._beat

    @property
    def draining(self) -> bool:
        return self._draining

    @property
    def cold(self) -> bool:
        """True until warmup() completes or the first decode chunk lands.
        A cold worker's iteration can legitimately block in a
        multi-second XLA compile — liveness monitors must not read a
        stale heartbeat as a wedge until this clears."""
        return self._cold

    def drain(self, timeout: Optional[float] = 30.0) -> bool:
        """Graceful quiesce: stop admitting (new submissions raise
        :class:`Draining` → 503/route-around), let queued + in-flight
        requests FINISH, then return True.  False = not quiescent within
        ``timeout`` (or the worker died mid-drain).  The batcher stays
        alive either way; :meth:`resume` re-opens admission — the
        drain→restart→resume cycle is how the pool hot-reloads a replica
        with zero dropped requests."""
        deadline = Deadline.after(timeout) if timeout is not None else None
        with self._cv:
            self._draining = True
            self._cv.notify_all()
            while (
                self._queue
                or self._admitting
                or any(r is not None for r in self._slot_req)
            ):
                if self._stopped or self._worker_dead:
                    return False
                if deadline is not None and deadline.expired:
                    return False
                # periodic re-check (no completion signal targets this
                # cv on retire); the bound rides the drain budget
                wait_s = 0.1 if deadline is None else deadline.bound(0.1)
                self._cv.wait(wait_s)
            return True

    def resume(self) -> None:
        with self._cv:
            self._draining = False
            self._cv.notify_all()

    def steal_queued(self) -> List[_Request]:
        """Atomically take every queued-but-unadmitted request (the pool
        requeues them onto a healthy replica when this one wedges).  The
        stolen requests are exactly the ones with no slot, no tokens, no
        device state — safe to re-admit elsewhere."""
        with self._cv:
            out = list(self._queue)
            self._queue.clear()
            self._cv.notify_all()
        return out

    def fail_active(self, error: BaseException) -> None:
        """Typed-fail every admitted (slot-resident) request — the pool's
        fail-fast for a wedged replica being discarded.  Device state is
        untouched (the wedged worker may still own it); callers must not
        route new work here afterwards.  A worker that later un-wedges
        and delivers tokens to a finished request is harmless: ``done``
        is already set and ``_finish`` is idempotent."""
        for slot in range(self.n_slots):
            req = self._slot_req[slot]
            if req is not None and not req.done.is_set():
                req.error = error
                _req_mark(req, "replica_failed", slot=slot)
                _finish(req)

    def kill(self, error: BaseException) -> None:
        """Fail-fast teardown for a wedged replica: mark stopped (the
        worker exits at its next wakeup — it is NOT joined, it may be
        hung in a device fetch), fail everything typed.  Unlike
        :meth:`stop` this never blocks on the worker thread."""
        with self._cv:
            self._stopped = True
            # a killed batcher can never make progress again even though
            # its (possibly hung) thread may linger: mark the worker dead
            # so ``worker_alive`` reads False — submits fail typed
            # WorkerDied, routing disqualifies it, and the pool's
            # resume(rebuild=False) cannot re-open it in place
            self._worker_dead = True
            # admission-window requests fail TYPED here, never rescued:
            # unlike a crashed worker, a wedged one may un-wedge later
            # and deliver tokens into these very objects — re-admitting
            # them elsewhere could interleave two replicas' tokens.
            # (_finish is idempotent, so a zombie completing a
            # failed-typed request is harmless.)  Dedup by identity —
            # see _worker_died.
            queued = list(
                {
                    id(r): r
                    for r in self._admitting_reqs + list(self._queue)
                }.values()
            )
            self._admitting_reqs = []
            self._admitting = 0
            self._queue.clear()
            self._cv.notify_all()
        for req in queued:
            if not req.done.is_set():
                req.error = error
                _req_mark(req, "replica_killed", queued=True)
                _finish(req)
        self.fail_active(error)
        # close the block accounting (idempotent; a later zombie retire
        # is a no-op).  The pool itself dies with this batcher — the
        # rebuild allocates a fresh one — so freed ids are never handed
        # to a new admission a zombie write could corrupt.  Cache pins
        # release too: a killed batcher's pool is garbage.
        for slot in range(self.n_slots):
            self._release_slot_blocks(slot)
        if self._prefix_cache is not None:
            self._prefix_cache.clear()

    @property
    def n_active(self) -> int:
        return sum(1 for r in self._slot_req if r is not None)

    @property
    def n_queued(self) -> int:
        return len(self._queue)

    @property
    def last_progress_age_s(self) -> float:
        """Seconds since the worker last fetched a decode chunk —
        ``inf`` until the first one.  Recent progress is stronger
        liveness evidence than any synthetic probe."""
        if not self._last_progress:
            return float("inf")
        return time_monotonic() - self._last_progress

    @property
    def n_admitting(self) -> int:
        """Requests in the admission window: popped from the queue but
        not yet slot-resident.  Work-pending for liveness purposes — a
        worker wedged here shows 0 queued AND 0 active."""
        return self._admitting

    @property
    def kv_bytes_per_token(self) -> int:
        """HBM bytes one token of KV occupies (all layers) — the paged
        accounting unit: HBM cost is tokens x this, block-granular,
        never per-bucket."""
        return kv_bytes_per_token(self.cfg)

    def kv_block_occupancy(self) -> Dict[str, float]:
        """Block-pool occupancy snapshot (telemetry gauges
        ``serve_kv_blocks_*`` / ``serve_kv_bytes_per_token`` — the
        replacement for the pre-paged per-bucket slot gauges).  Unlocked
        reads of the allocator counters and the same host-side lists
        ``n_active`` reads — a sample mid-transition miscounting one
        block is fine for a 2 Hz occupancy series."""
        bpt = self.kv_bytes_per_token
        used = self._alloc.blocks_in_use
        tokens = 0
        for slot in range(self.n_slots):
            req = self._slot_req[slot]
            if req is not None:
                tokens += req.kv_prompt + len(req.tokens)
        out = {
            # the kind's own: ``state_bytes_per_lane``, ``loop_steps``
            **self._block.occupancy,
            "blocks_total": self.n_blocks,
            "blocks_used": used,
            "block_size": self.block_size,
            "bytes_per_token": bpt,
            "pool_bytes": self.n_blocks * self.block_size * bpt,
            "used_bytes": used * self.block_size * bpt,
            "tokens_committed": tokens,
            "utilization": used / self.n_blocks,
        }
        if "state_bytes_per_lane" in out:
            # what the pools spend on lane STATE, one entry a slot whatever
            # the lanes' lengths — all of their memory where no layer keeps
            # a row (``bytes_per_token`` 0: a page is then the unit of
            # admission alone and ``pool_bytes`` reads 0)
            out["state_pool_bytes"] = (
                self.n_slots * out["state_bytes_per_lane"])
        if self._ring_alloc is not None:
            # the second extent: rows the window layers' pools hold for
            # the lanes admitted (a ring each, whatever their lengths)
            out["window_rows_held"] = (
                self._ring_alloc.blocks_in_use * self.block_size)
            out["window_rows_total"] = (
                self._ring_alloc.n_blocks * self.block_size)
        if self._prefix_cache is not None:
            # prefix-cache occupancy (docqa-prefix): entries + the
            # blocks the cache pins, plus the lifetime hit economics —
            # the sampler turns these into serve_kv_prefix_* gauges.
            # Raw hit/miss counts ride along so aggregators (the pool's
            # cross-replica rate, chaos evidence) can sum THIS surface
            # instead of reaching into the cache object.
            pstats = self._prefix_cache.stats()
            out["prefix_entries"] = pstats["entries"]
            out["prefix_blocks"] = pstats["pinned_blocks"]
            out["prefix_hits"] = pstats["hits"]
            out["prefix_misses"] = pstats["misses"]
            out["prefix_hit_rate"] = round(pstats["hit_rate"], 4)
            out["prefix_tokens_avoided"] = pstats["tokens_avoided"]
        return out

    def block_seconds(self) -> Dict[str, float]:
        """The paged pool's block-second ledger (docqa-costscope):
        total/billed/residual — residual must read ~0 after drain/stop
        (tests + chaos assert it)."""
        return self._alloc.block_seconds()

    def pressure_by_class(self) -> Dict[str, Any]:
        """Per-class holdings snapshot for shed forensics
        (obs/costs.py): which classes hold how many KV blocks, decode
        lanes, and queue slots RIGHT NOW.  Deliberately LOCK-FREE — it
        runs on the shedding thread, possibly under this batcher's own
        ``_cv`` (submit-path sheds) or from another replica's context,
        and a probe that took locks could order them against every
        replica's.  A snapshot racing a transition miscounting one lane
        is fine for forensics."""

        def _cls(req) -> str:
            return req.cost.cls if req.cost is not None else "other"

        by: Dict[str, Dict[str, int]] = {}

        def row(cls: str) -> Dict[str, int]:
            return by.setdefault(
                cls, {"kv_blocks": 0, "lanes": 0, "queued": 0}
            )

        for slot in range(self.n_slots):
            req = self._slot_req[slot]
            if req is None:
                continue
            r = row(_cls(req))
            r["lanes"] += 1
            table = self._slot_table[slot]
            if table is not None:
                r["kv_blocks"] += len(table.blocks)
        try:
            queued = list(self._queue)
        except RuntimeError:  # deque mutated mid-iteration (lock-free)
            queued = []
        for req in queued:
            row(_cls(req))["queued"] += 1
        out: Dict[str, Any] = {
            "by_class": by,
            "free_blocks": self._alloc.n_free,
            "blocks_total": self.n_blocks,
        }
        if self._prefix_cache is not None:
            out["prefix_cache_blocks"] = int(
                self._prefix_cache.stats()["pinned_blocks"]
            )
        return out

    # ---- multi-tenant QoS (docqa-qos) ----------------------------------------

    def set_slo_probe(self, probe) -> None:
        """Wire the burn-rate probe (``BurnRateEvaluator.firing``) that
        drives batch-class deferral.  Safe to call any time; None
        disables deferral (preemption and weighted-fair are probe-free)."""
        self._slo_probe = probe

    def _slo_firing(self) -> List[str]:
        probe = self._slo_probe
        if probe is None:
            return []
        try:
            return list(probe() or [])
        except Exception:
            # a broken probe must never take admission down with it
            return []

    def qos_status(self) -> Dict[str, Any]:
        """Policy state for /api/status: mode, weights, live deferral,
        and per-class queue depths.  Lock-free snapshot like
        ``pressure_by_class``."""
        if self._qos is None:
            return {"enabled": False}
        out: Dict[str, Any] = {"enabled": True}
        out.update(self._qos.status())
        firing = self._slo_firing()
        out["slo_firing"] = firing
        out["defer_active"] = self._qos.should_defer("batch", firing)
        depths = getattr(self._queue, "depths", None)
        if depths is not None:
            out["queued_by_class"] = depths()
        return out

    def _holders_snapshot(
        self, exclude_slot: Optional[int] = None
    ) -> List[Tuple[int, str, int]]:
        """(slot, class, reclaimable_blocks) for every live lane — the
        victim-selection input.  Worker-thread accurate; merely
        advisory from other threads (preemption_candidates)."""
        out = []
        for slot in range(self.n_slots):
            if slot == exclude_slot:
                continue
            req = self._slot_req[slot]
            table = self._slot_table[slot]
            if req is None or table is None:
                continue
            out.append(
                (slot, request_class(req), self._alloc.reclaimable(table))
            )
        return out

    def preemption_candidates(
        self, pressure_cls: str = "interactive"
    ) -> List[Dict[str, Any]]:
        """What the preemption policy WOULD evict for ``pressure_cls``
        pressure, in eviction order — the operator dry-run surface
        (rides the shed-forensics pressure snapshot onto
        /api/costs/sheds).  Works in every mode including ``off``:
        candidates are how an operator decides whether to turn the
        policy on.  Lock-free by the pressure-probe contract."""
        if self._qos is None:
            return []
        victims = QoSPolicy.order_victims(
            self._holders_snapshot(), pressure_cls
        )
        return [
            {"slot": s, "class": c, "reclaimable_blocks": r}
            for s, c, r in victims
        ]

    def _preempt_slot(
        self, slot: int, pressure_cls: str
    ) -> Optional["_Request"]:
        """Evict one victim lane's KV blocks (worker thread only; does
        not touch ``_cv``).  Releases and BILLS the held block-seconds
        exactly (the same late-add path a retirement uses), then bills
        the identical amount to the ``preempted_block_seconds`` ledger
        line — the wasted-work annotation; ``kv_block_seconds`` keeps
        the accounting identity, the preempted line names the waste.

        Returns the victim for the caller to requeue (its generated
        tokens stay on the request for token-preserving re-prefill), or
        None when the victim's deadline cannot survive a second prefill
        — then it degrades typed here instead of bouncing to a
        guaranteed deadline shed."""
        req = self._slot_req[slot]
        table = self._slot_table[slot]
        self._slot_req[slot] = None
        cls = request_class(req)
        was_released = table.released if table is not None else True
        self._release_slot_blocks(slot, req=req)
        if table is not None and not was_released:
            _cost_add(
                req, "preempted_block_seconds", table.billed_block_seconds
            )
        # device-side lane deactivation rides the next device work item
        # (the worker never issues device ops from its own thread)
        self._deact_pending.append(slot)
        DEFAULT_REGISTRY.counter("qos_preempted").inc()
        DEFAULT_REGISTRY.counter(f"qos_preempted_{cls}").inc()
        _req_mark(
            req, "pool_preempted", slot=slot,
            pressure_class=pressure_cls,
            tokens_so_far=len(req.tokens),
        )
        if req.deadline is not None and (
            req.deadline.expired
            or req.deadline.remaining() < self._qos.preempt_min_resume_s
        ):
            req.error = BlockPoolExhausted(
                f"preempted by {pressure_cls} pressure with too little "
                "deadline budget left to re-prefill",
                n_active=self.n_active,
            )
            DEFAULT_REGISTRY.counter("serve_block_shed").inc()
            DEFAULT_COST_LEDGER.record_shed(
                "preempted", cls=cls, stage="serve_preempt",
                pressure_class=pressure_cls,
            )
            _finish(req)
            return None
        return req

    def _requeue_preempted(self, victim: "_Request") -> None:
        """Requeue a preemption victim: the pool's requeue/rescue
        machinery first (it may place the victim on a replica with free
        blocks RIGHT NOW, and it owns hop/park bookkeeping), local
        class-head requeue as the fallback.  Called OUTSIDE ``_cv`` —
        the pool hook takes the pool lock and other replicas' ``_cv``s,
        and nesting those under ours would order locks across
        batchers."""
        cb = self.on_preempt
        if cb is not None:
            try:
                if cb(self, victim):
                    return
            except Exception:
                log.exception("on_preempt hook failed; requeueing locally")
        with self._cv:
            victim.t_queue = _now()
            self._queue.appendleft(victim)
            self._cv.notify_all()

    def _admission_preempt(
        self, head: "_Request", planned: int, need: int,
        requeue_out: List["_Request"],
    ) -> int:
        """Admission-side preemption (caller holds ``_cv``): evict
        lower-ranked lanes until ``planned + need`` blocks fit, after
        the prefix-cache valve failed and before the head is left
        block-starved.  Victims go into ``requeue_out`` — the caller
        requeues them AFTER it pops the head, so the head peek the
        block-planning was computed against stays the next pop.
        Returns the head's re-estimated block need (eviction may have
        freed the head's own cached prefix, staling the old peek
        discount).  Advisory mode only counts; ``off`` was gated by the
        caller."""
        cls = request_class(head)
        victims = QoSPolicy.order_victims(self._holders_snapshot(), cls)
        if not victims:
            return need
        if self._qos.preemption == "advisory":
            if self._block_wait_marked != id(head):
                # once per starvation episode, like the wait mark below
                DEFAULT_REGISTRY.counter("qos_preempt_advisory").inc()
                _req_mark(
                    head, "qos_preempt_advisory", anomalous=False,
                    candidates=[s for s, _c, _r in victims],
                )
            return need
        for slot, _vcls, _reclaim in victims:
            if self._alloc.can_alloc(planned + need):
                break
            victim = self._preempt_slot(slot, cls)
            if victim is not None:
                requeue_out.append(victim)
            need = self._blocks_for_admission(head)
        return need

    def _grow_preempt(self, slot: int, req: "_Request", table, target) -> bool:
        """Mid-decode preemption (worker thread, outside ``_cv``): a
        live lane that cannot grow evicts lower-ranked lanes before it
        sheds itself.  Evicts one victim at a time, retrying the grow
        after each — stale in-flight writes to the freed blocks are
        safe by the same device-sequencing argument admission re-use
        relies on (the chunk that still maps them was dispatched
        earlier on the chained pool state, and the grown lane never
        reads a row it has not yet written).  Returns True when the
        grow succeeded."""
        if self._qos is None or self._qos.preemption != "on":
            return False
        victims = QoSPolicy.order_victims(
            self._holders_snapshot(exclude_slot=slot), request_class(req)
        )
        for vslot, _vcls, _reclaim in victims:
            victim = self._preempt_slot(vslot, request_class(req))
            if victim is not None:
                self._requeue_preempted(victim)
            try:
                table.ensure(target)
            except OutOfBlocks:
                continue
            row = self._block_rows[slot]
            row[: len(table.blocks)] = table.blocks
            self._caps_np[slot] = table.capacity
            self._tables_dirty = True
            return True
        return False

    def _record_shed(
        self, req: "_Request", kind: str,
        outcome: Optional[str] = None, **attrs,
    ) -> None:
        """Shed forensics + terminal cost retirement for a request this
        batcher refuses at submit.  POOL-MANAGED requests skip BOTH: a
        single replica's refusal is a routing decision the pool may
        still resolve on another replica — only the pool's terminal
        ``_shed`` records forensics (once, not once per refusing
        replica) and retires the record.  Safe under ``self._cv``: the
        pressure probe is lock-free by design."""
        if req.pool_managed or req.cost_shadow:
            # routing refusals, not sheds: the pool may place a managed
            # request elsewhere, and a refused HEDGE TWIN leaves its
            # primary running — retiring the twin's SHARED record here
            # would mark a request that goes on to answer OK as shed
            return
        cls = req.cost.cls if req.cost is not None else None
        DEFAULT_COST_LEDGER.record_shed(kind, cls=cls, **attrs)
        if req.cost is not None:
            DEFAULT_COST_LEDGER.retire(
                req.cost,
                outcome
                or (
                    "shed_block_pool"
                    if kind == "block_pool_exhausted"
                    else "shed_queue"
                ),
            )

    # ---- worker loop ---------------------------------------------------------

    def _admit_round(
        self,
        pairs: List[Tuple[int, "_Request"]],
        drained_at: Optional[float] = None,
    ):
        """Prefill every (slot, request) pair of this round through the
        ragged packed program (async — no device sync; the round is
        finalized with ONE host fetch of every group's first tokens in
        ``_finalize_admissions``).

        Prompts pack into flat token streams (starts RAGGED_ALIGN-
        aligned), one per dispatch group, and :func:`partition_prefill_round`
        decides the groups: each runs the smallest configured token
        budget that holds its LARGEST prompt, never a larger one because
        several prompts were summed — mixed lengths still share a
        dispatch with no shape family and no per-bucket padding, and
        every dispatch is one of the warmed shapes (no retrace).  Each
        request's KV blocks are allocated here (prompt + grow margin);
        a request the pool cannot currently hold goes BACK to the queue
        head (traced, deadline still enforced there) instead of failing
        — ``_pop_free_slots`` pre-checks capacity, so that path is a
        rare race, not the norm.
        A request whose prompt cannot be marshalled fails alone, before
        the dispatch — not with the whole round.

        ``drained_at``: when the worker finished processing a pending
        chunk before this round (None: it had none) — a request popped
        before then sat that drain out, and its ``serve_admit_hold``
        span says so."""
        # Truncation limit mirrors the budget formula in
        # _finalize_admissions (cache_len - n_ids - 1 - spec_k) with one
        # extra row reserved, so a maximally-long prompt still gets
        # budget >= 1 — otherwise prompts in the band truncate "in bounds"
        # but retire with zero output (a 200 with an empty answer).
        usable = self.cache_len - 2 - self.spec_k
        # entry: (slot, req, ids, table, shared) — shared > 0 marks a
        # WARM lane whose leading blocks were mapped from the prefix
        # cache (only the novel suffix ids[shared:] is packed/prefilled)
        good: List[Tuple[int, "_Request", List[int], Any, int]] = []
        send_back: List["_Request"] = []
        for slot, req in pairs:
            if req.deadline is not None and req.deadline.expired:
                # the budget lapsed between queue pop and this round
                # (e.g. while the previous chunk drained) — shed before
                # the prefill spends a lane on it
                req.error = DeadlineExceeded(
                    "serve_admit", -req.deadline.remaining()
                )
                DEFAULT_REGISTRY.counter("serve_deadline_shed").inc()
                _req_mark(req, "deadline_exceeded", stage="serve_admit")
                DEFAULT_COST_LEDGER.record_shed(
                    "deadline",
                    cls=req.cost.cls if req.cost is not None else None,
                    stage="serve_admit",
                )
                _finish(req)
                continue
            try:
                # token-preserving re-prefill (docqa-qos): a preemption
                # victim re-admits with its generated-so-far tokens
                # appended to the prompt, so the prefill's sampled
                # "first" token is exactly the NEXT greedy continuation
                # and the handle's token stream never rewinds.  Fresh
                # requests have no tokens — this is the old expression.
                ids = (
                    [int(t) for t in req.prompt_ids]
                    + [int(t) for t in req.tokens]
                )[-usable:] or [self.gen.pad_id]
            except (TypeError, ValueError) as e:  # bad request; fail it alone
                req.error = e
                _finish(req)
                continue
            table = self._alloc.new_table()
            if self._ring_alloc is not None:
                table.ring = self._ring_alloc.new_table()
            shared = 0
            try:
                if self._prefix_cache is not None:
                    # longest cached, token-verified, aligned prefix in
                    # at refcount+1 — this is the prefill work avoided
                    shared = self._prefix_cache.acquire(
                        req.prefix_key, ids, table
                    )
                table.ensure(
                    min(len(ids) + self._grow_margin, self.seq_capacity)
                )
                if table.ring is not None:
                    # all or nothing with the table above: the release
                    # below returns both
                    table.ring.ensure(self._ring_pages * self.block_size)
            except OutOfBlocks:
                # the pool drained between the _pop_free_slots capacity
                # check and here (same thread, so only by THIS round's
                # earlier allocations) — requeue at the head, keep
                # order.  Release FIRST: a partial share would otherwise
                # strand refcounts on a table nobody owns.  The moment
                # of holding still bills (exact accounting: the bounce
                # held real blocks, however briefly).
                table.release()
                _cost_add(req, "kv_block_seconds", table.billed_block_seconds)
                DEFAULT_REGISTRY.counter("serve_block_pool_wait").inc()
                _req_mark(
                    req, "block_pool_exhausted", queued=True,
                    free_blocks=self._alloc.n_free,
                )
                send_back.append(req)
                continue
            try:
                if (
                    self._prefix_cache is not None
                    and req.prefix_key is not None
                ):
                    # stats credit only AFTER ensure() held: a bounced
                    # admission re-acquires next round and must not
                    # count twice (cache stats and registry counters
                    # stay in step)
                    self._prefix_cache.credit(shared)
                if shared:
                    DEFAULT_REGISTRY.counter("serve_prefix_hits").inc()
                    DEFAULT_REGISTRY.counter(
                        "serve_prefix_tokens_avoided"
                    ).inc(shared)
                    _req_mark(
                        req, "prefix_hit", anomalous=False,
                        shared_tokens=shared, prompt_tokens=len(ids),
                    )
                if self._prefix_cache is not None and not shared:
                    # a COLD lane inserts IN the allocation loop, not
                    # after it: a later request of the SAME key in this
                    # very round then acquires this entry and shares
                    # in-round (consecutive questions of one session
                    # routinely land in one admission round under load).
                    # Device ordering makes it exact: cold groups
                    # dispatch before warm ones — the shared rows are
                    # always written before any sharer reads them.  A
                    # WARM lane (it may lengthen its key's entry by
                    # rows of its own suffix) inserts after the loop,
                    # below: warm groups dispatch in the packer's order,
                    # not in arrival order, so no lane of this round may
                    # read what a warm lane of this round writes.  Abort
                    # paths stay leak-free: a failed round clears the
                    # whole cache.
                    self._prefix_cache.insert(req.prefix_key, ids, table)
            except BaseException:
                # between ensure() and the good-list handoff the table
                # is registered in no slot, so no later cleanup
                # (_fail_active, _retire) can ever see it — a raise
                # here would shrink the block pool permanently.
                # Release first, bill the held interval, then let the
                # failure propagate as a worker death.
                table.release()
                _cost_add(req, "kv_block_seconds", table.billed_block_seconds)
                raise
            good.append((slot, req, ids, table, shared))
        if send_back:
            sent = {id(r) for r in send_back}
            with self._cv:
                for req in reversed(send_back):
                    req.t_queue = _now()  # fresh queue-wait interval
                    _req_span(
                        req, "serve_admit_hold", req.t_pop, req.t_queue,
                        bounced=True,
                    )
                    self._queue.appendleft(req)
                # queue-resident again: drop them from the admission
                # window NOW, not at the round's end — a worker death in
                # between must see each request in exactly ONE of
                # (_admitting_reqs, _queue), or the rescue hook would
                # offer it twice and two replicas could decode it
                self._admitting_reqs = [
                    r for r in self._admitting_reqs if id(r) not in sent
                ]
                self._admitting = len(self._admitting_reqs)
                self._cv.notify_all()
        if not good:
            return [], None, [], 0.0

        # Register slot state BEFORE the dispatch: if the dispatch dies,
        # _fail_active sweeps these slots and releases their fresh block
        # tables along with everything else (exactly-once accounting).
        for slot, req, ids, table, _shared in good:
            n_ids = len(ids)
            # resumed (preempted) requests folded generated tokens into
            # ids: the retire check compares len(req.tokens) — which
            # still counts them — against this budget, so they must be
            # added back or a resumed request retires short of its
            # max_new (the capacity term already charges them via n_ids)
            resumed = min(len(req.tokens), n_ids)
            budget = resumed + min(
                req.max_new - resumed,
                self.cache_len - n_ids - 1 - self.spec_k,
            )
            self._slot_req[slot] = req
            self._slot_budget[slot] = budget
            # subtract resumed tokens so kv_prompt + len(req.tokens)
            # stays the lane's exact KV length (grow estimates and the
            # occupancy gauges depend on that identity)
            req.kv_prompt = n_ids - resumed
            self._slot_table[slot] = table
            row = self._block_rows[slot]
            row[:] = self.n_blocks
            row[: len(table.blocks)] = table.blocks
            self._caps_np[slot] = table.capacity
            if self._state_slot_np is not None:
                # the slot owns state entry ``slot`` until it retires; the
                # round's prefill starts it from zeros (a reused slot
                # inherits nothing) and the map goes up with that dispatch
                self._state_slot_np[
                    table.blocks[0] * self.block_size
                ] = slot
            if table.ring is not None:
                self._ring_pages_np[slot] = table.ring.blocks
        self._tables_dirty = True
        for _slot, req, ids, table, shared in good:
            if shared and self._prefix_cache is not None:
                # see the allocation loop: visible from the next round on
                self._prefix_cache.insert(req.prefix_key, ids, table)

        # pack into dispatch groups: each prompt's NOVEL portion starts
        # on a RAGGED_ALIGN boundary (the exactness contract in
        # ops/attention.py) and a group runs the budget its largest
        # member needs alone (partition_prefill_round).  Warm lanes
        # (shared > 0) pack only their suffix and group separately from
        # cold ones: cold rounds keep dispatching the exact pre-prefix
        # program (numerics untouched by construction), warm rounds pay
        # the prefix-gather program.
        packed_rows = [
            round_up(len(ids) - shared, RAGGED_ALIGN)
            for _slot, _req, ids, _table, shared in good
        ]
        is_warm = [bool(shared) for *_entry, shared in good]
        plan = partition_prefill_round(
            packed_rows, is_warm, self._token_buckets
        )

        fn = self._get_prefill_fn()
        S = self.n_slots
        oob_row = self.n_blocks * self.block_size
        # host marshal: one packed numpy input set per dispatch group —
        # everything that touches the device happens inside the spine
        # work item below
        group_inputs = []
        # per group: (token budget T the dispatch runs, rows its prompts
        # take at their RAGGED_ALIGN starts, novel tokens among them) —
        # what the prefill spans and the padding counters report
        group_rows: List[Tuple[int, int, int]] = []
        groups: List[Tuple[bool, List[tuple]]] = []
        # (seg, pos) of each COLD group: what its attention layers see
        cold_packings = []
        for warm_flag, T, members in plan:
            group = [good[i] for i in members]
            groups.append((warm_flag, group))
            total = sum(packed_rows[i] for i in members)
            group_rows.append(
                (T, total, sum(len(e[2]) - e[4] for e in group))
            )
            ids_flat = np.full((T,), self.gen.pad_id, np.int32)
            seg = np.full((T,), -1, np.int32)
            pos = np.zeros((T,), np.int32)
            dest = np.full((T,), oob_row, np.int32)
            last_rows = np.zeros((S,), np.int32)
            slots_arr = np.full((S,), S, np.int32)  # OOB == dropped
            tables_np = plens_np = None
            if warm_flag:
                tables_np = np.full(
                    (S, self.blocks_per_seq), self.n_blocks, np.int32
                )
                plens_np = np.zeros((S,), np.int32)
            off = 0
            for lane, (slot, _req, ids, table, shared) in enumerate(group):
                n = len(ids)
                # pack only the novel suffix; positions stay ABSOLUTE
                # (warm queries RoPE/attend at their true offsets; the
                # cached prefix rows cover positions [0, shared))
                p = np.arange(shared, n, dtype=np.int32)
                n_sfx = n - shared
                ids_flat[off: off + n_sfx] = ids[shared:]
                seg[off: off + n_sfx] = lane
                pos[off: off + n_sfx] = p
                blocks = np.asarray(table.blocks, np.int64)
                dest[off: off + n_sfx] = (
                    blocks[p // self.block_size] * self.block_size
                    + p % self.block_size
                )
                last_rows[lane] = off + n_sfx - 1
                slots_arr[lane] = slot
                if warm_flag:
                    tables_np[lane, : len(table.blocks)] = table.blocks
                    plens_np[lane] = shared
                off += round_up(n_sfx, RAGGED_ALIGN)
            group_inputs.append(
                (T, ids_flat, seg, pos, dest, last_rows, slots_arr,
                 len(group), warm_flag, tables_np, plens_np)
            )
            if not warm_flag:
                cold_packings.append((seg, pos))
        # flattened group-major order: slot scatters and the first-token
        # fetch must line up with the concatenated dispatch outputs
        ordered = [e for _w, group in groups for e in group]
        G = len(ordered)
        slots_np = np.empty((G,), np.int32)
        lens_np = np.empty((G,), np.int32)
        budget_ok = np.empty((G,), bool)
        for i, (slot, req, ids, _table, _shared) in enumerate(ordered):
            slots_np[i] = slot
            lens_np[i] = len(ids)
            budget_ok[i] = self._slot_budget[slot] >= 2

        def _prefill_on_lane():
            """Device phase of the round (spine work item): pending lane
            deactivations, one packed dispatch per group, then the slot
            -state scatter.  Slot state updates ride the device (the
            sampled first tokens are already there) — alive = (first !=
            eos) & (budget >= 2) needs no host fetch, so the worker
            dispatches the decode chunk for the live lanes and these
            right behind it; the host-side fetch of first tokens
            (_finalize_admissions) then overlaps that chunk."""
            self._apply_deact_on_lane()
            if self._state_slot_np is not None:
                self._pools[STATE_SLOT] = jax.device_put(
                    self._state_slot_np, self._state_sharding
                )
            if self._ring_pages_np is not None:
                self._pools[WINDOW_PAGES] = jax.device_put(
                    self._ring_pages_np, self._state_sharding
                )
            parts, sums = [], []
            for (T, ids_flat, seg, pos, dest, last_rows, slots_arr,
                 n_lanes, warm_flag, tables_np, plens_np) in group_inputs:
                packed = (
                    jnp.asarray(ids_flat),
                    jnp.asarray(seg),
                    jnp.asarray(pos),
                    jnp.asarray(dest),
                    jnp.asarray(last_rows),
                    jnp.asarray(slots_arr),
                )
                if warm_flag:
                    use = self._get_prefill_warm_fn()
                    args = packed + (
                        jnp.asarray(tables_np), jnp.asarray(plens_np),
                        self._next_rng(),
                    )
                else:
                    use = fn
                    args = packed + (self._next_rng(),)
                if self.spec_k:
                    self._pools, self._table, toks = use(
                        self.engine.params, self._pools, self._table, *args
                    )
                else:
                    self._pools, toks = use(
                        self.engine.params, self._pools, *args
                    )
                parts.append(toks[:n_lanes])
                if self._block.prefill_sum_names:  # behind the tokens
                    sums.append(toks[S:])
            first = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
            idx = jnp.asarray(slots_np)
            alive = (first != self.gen.eos_id) & jnp.asarray(budget_ok)
            self._tok = self._tok.at[idx].set(first)
            self._lengths = self._lengths.at[idx].set(jnp.asarray(lens_np))
            self._active = self._active.at[idx].set(alive)
            # the scatters are DOWNSTREAM of `first` — returned alongside
            # it so strict mode's block_until_ready covers every program
            # this item issued, not just the first-token chain
            if sums:  # a row a dispatch, behind the round's tokens
                first = jnp.concatenate([first, *sums])
            return first, self._tok, self._lengths, self._active

        t_prefill0 = _now()
        with span("serve_prefill", DEFAULT_REGISTRY):
            # the prefill rides its own spine stream ("prefill"): lanes
            # schedule decode-class items ahead of it, so one replica's
            # long admission prefill cannot head-of-line block another
            # replica's decode chunks (the disaggregated-lane split)
            first_toks = spine_run(
                "serve_prefill", _prefill_on_lane, stream="prefill"
            )[0]
        t_prefill1 = _now()
        DEFAULT_REGISTRY.counter("serve_admit_rounds").inc()
        DEFAULT_REGISTRY.counter("serve_admitted").inc(len(ordered))
        DEFAULT_REGISTRY.counter("serve_prefill_dispatches").inc(len(groups))
        # split: the round ran more groups than it would have had every
        # group been allowed the largest budget — how often rule 1 of
        # partition_prefill_round engages
        if len(groups) > len(partition_prefill_round(
            packed_rows, is_warm, self._token_buckets[-1:]
        )):
            DEFAULT_REGISTRY.counter("serve_prefill_rounds_split").inc()
        DEFAULT_REGISTRY.counter("serve_prefill_budget_tokens").inc(
            sum(T for T, _rows, _novel in group_rows)
        )
        prefill_tokens = sum(novel for _T, _rows, novel in group_rows)
        DEFAULT_REGISTRY.counter("serve_prefill_tokens").inc(prefill_tokens)
        # what the block kind counts of a round (lane states reset, rows
        # scanned, dispatches that scanned in the kernel)
        for name, amount in self._block.prefill_counts(
            lanes=len(ordered), tokens=prefill_tokens,
            dispatches=len(groups), kernels=self._kernels,
        ).items():
            DEFAULT_REGISTRY.counter(name).inc(amount)
        if self._kernels.ragged:
            # the cold dispatches attended in the flash kernel: over
            # ``serve_prefill_dispatches`` 1.0 where every one did, and
            # the key blocks it visited of the packed square's
            for name, amount in self.engine.ragged_prefill_counts(
                cold_packings, max_segment=self.seq_capacity,
            ).items():
                DEFAULT_REGISTRY.counter(name).inc(amount)
        # group-major, like ``ordered``: (slot, req, prompt tokens,
        # shared tokens, what the dispatch that carried it ran)
        meta = []
        for gi, (warm_flag, group) in enumerate(groups):
            T, rows, _novel = group_rows[gi]
            for slot, req, ids, table, shared in group:
                _req_span(
                    req, "serve_admit_hold", req.t_pop, t_prefill0,
                    drained=(
                        drained_at is not None and req.t_pop < drained_at
                    ),
                    round=len(good),
                )
                _req_span(
                    req, "serve_prefill", t_prefill0, t_prefill1,
                    batch=len(good), dispatch=gi,
                    dispatches=len(groups), slot=slot,
                    prompt_tokens=len(ids), blocks=len(table.blocks),
                    shared_tokens=shared, budget_tokens=T,
                    packed_tokens=rows,
                    **self._block.prefill_attrs(len(ids), len(good)),
                    **self._block.span_attrs,
                )
                meta.append((
                    slot, req, len(ids), shared,
                    {"budget_tokens": T, "packed_tokens": rows,
                     "batch": len(good)},
                ))
        # the groups' token budgets ride along as the admission fetch's
        # cost keys (observatory MFU accounting; warm groups accrue
        # under their own ("warm", T) cost models)
        cost_keys = [
            ("warm", g[0]) if g[8] else g[0] for g in group_inputs
        ]
        return meta, first_toks, cost_keys, t_prefill1

    def _finalize_admissions(self, admitted) -> bool:
        """Host-side bookkeeping for an admission round: ONE device fetch
        of the round's first tokens, then per-request delivery/retirement.

        Device-side slot state (tok/lengths/active + budgets) was already
        written by ``_admit_round`` without a fetch, so the worker calls
        this AFTER dispatching the decode chunk that carries the round's
        lanes — the fetch waits for the prefill alone and its round-trip
        overlaps that chunk's execution.  A lane retired here (first
        token EOS, budget spent) is in that chunk's snapshot: the device
        ``active`` flag kept the chunk off it and ``_process_chunk``
        drops it.  The budget math mirrors
        ``_admit_round``: the prefill token counts as one, and speculation
        reserves ``spec_k`` rows of K/V headroom (a verify writes K rows
        from the current length, and dynamic_update_slice CLAMPS an
        out-of-range window downward onto confirmed rows).

        Returns False when the fetch itself failed (prefill died on
        device) — the caller must treat the whole pipeline as poisoned."""
        meta, round_toks, cost_keys, t_dispatched = admitted
        try:
            # ONE device fetch, on a spine lane: its duration is the
            # round's device time at the one-fetch boundary, and the
            # group token budgets are the cost keys MFU accrues under.
            # Submitted (not run) so the ticket's measured
            # queue-wait/device split survives for cost attribution.
            with span("serve_first_token_fetch", DEFAULT_REGISTRY):
                ticket = spine_submit(
                    "serve_prefill_fetch",
                    lambda: np.asarray(round_toks),
                    cost_key=cost_keys,
                )
                fetched = ticket.result()
                firsts = fetched[: len(meta)]
        except Exception as e:
            log.exception("admission fetch failed; resetting")
            self._fail_active(e)
            return False
        names = self._block.prefill_sum_names
        if names:  # a row a dispatch, summed on the device
            sums = fetched[len(meta):].reshape(-1, len(names))
            for name, value in zip(names, sums.sum(axis=0)):
                DEFAULT_REGISTRY.counter(name).inc(int(value))
        # ---- per-request cost attribution (docqa-costscope): split the
        # round's measured device time across its requests proportional
        # to the NOVEL (suffix) tokens each one packed — warm lanes bill
        # under the warm field with their avoided tokens recorded, so
        # the per-class sums reconcile against the serve_prefill_fetch
        # dispatch series exactly (same measured value, partitioned).
        sfx = [
            max(n_ids - shared, 1) for _s, _r, n_ids, shared, _d in meta
        ]
        total_sfx = float(sum(sfx)) or 1.0
        flops_total = 0.0
        for key in cost_keys:
            c = DEFAULT_OBSERVATORY.cost_of("serve_prefill_fetch", key)
            if c is not None:
                flops_total += c["flops"]
        dev_ms = ticket.device_s * 1e3
        qw_ms = ticket.queue_wait_s * 1e3
        for (slot, req, n_ids, shared, _d), n_sfx in zip(meta, sfx):
            share = n_sfx / total_sfx
            field = (
                "prefill_device_ms_warm" if shared
                else "prefill_device_ms_cold"
            )
            _cost_add(req, field, dev_ms * share)
            _cost_add(req, "spine_queue_wait_ms", qw_ms * share)
            _cost_add(req, "prefill_tokens", n_ids)
            _cost_add(req, "prefill_tokens_avoided", shared)
            if flops_total:
                _cost_add(req, "flops_est", flops_total * share)
        for (slot, req, _n_ids, _shared, dispatch), first in zip(
            meta, firsts
        ):
            first = int(first)
            budget = self._slot_budget[slot]
            # dispatched -> here: the device ran the prefill (the
            # round's chunk is queued behind it) and the worker fetched
            # the round's first tokens
            _req_span(
                req, "serve_first_token", t_dispatched, _now(), **dispatch
            )
            if first == self.gen.eos_id or budget <= 0:
                self._retire(slot)
            else:
                req.tokens.append(first)
                _cost_add(req, "decode_tokens", 1)
                _req_mark(req, "first_token", anomalous=False, slot=slot)
                with req.cv:  # the first streamed token
                    req.cv.notify_all()
                if len(req.tokens) >= budget:
                    self._retire(slot)
        return True

    def _apply_deact_on_lane(self) -> None:
        """Clear device-side ``active`` lanes for host-retired slots.
        Called FIRST inside every device work item (prefill / decode
        closures) — the worker thread only QUEUES deactivations
        (``_deact_pending``); it never touches device state itself."""
        if self._deact_pending:
            idx = jnp.asarray(self._deact_pending, jnp.int32)
            self._active = self._active.at[idx].set(False)
            self._deact_pending = []

    def _release_slot_blocks(
        self, slot: int, req: Optional[_Request] = None
    ) -> None:
        """Return a slot's KV blocks to the pool (idempotent via the
        allocator) and sentinel its device-table row so in-flight
        programs drop any further write through it.

        The release is also where the slot's KV **block-seconds** bill
        lands (docqa-costscope): the allocator computes the exact
        refcount-aware integral at release, and it is credited to the
        occupant's cost record — including POST-retirement (late-add),
        so a teardown sweep that releases after the typed failure still
        bills exactly once (the ``was_released`` guard: only the call
        that performed the release credits)."""
        if req is None:
            req = self._slot_req[slot]
        table = self._slot_table[slot]
        self._slot_table[slot] = None
        self._block_rows[slot, :] = self.n_blocks
        self._caps_np[slot] = 0
        self._tables_dirty = True
        if table is not None:
            was_released = table.released
            table.release()
            if not was_released and req is not None:
                _cost_add(
                    req, "kv_block_seconds", table.billed_block_seconds
                )

    def _fail_active(self, err: BaseException) -> None:
        """Fail all in-flight requests, free their blocks, and rebuild
        clean device state."""
        for slot in range(self.n_slots):
            req = self._slot_req[slot]
            self._slot_req[slot] = None
            # release (and bill KV block-seconds) BEFORE _finish retires
            # the cost record, so the victim's trace summary carries
            # what it held — same order as _retire
            self._release_slot_blocks(slot, req=req)
            if req is not None:
                req.error = RuntimeError(f"decode failed: {err!r}")
                _req_mark(req, "decode_failed", slot=slot)
                _finish(req)
        # the reset below replaces the device pools: every cached prefix
        # row is garbage from here — invalidate the whole cache (pins
        # release; warm admissions start over against the fresh pools)
        if self._prefix_cache is not None:
            self._prefix_cache.clear()
        if self._stopped:
            # a killed batcher never serves again — re-allocating a fresh
            # block pool here would waste HBM right as the pool's rebuild
            # allocates the replacement replica's (and would undo the
            # pool's device-state scrub of this shell)
            return
        # the poisoned lanes are gone with the reset — nothing pending
        # to deactivate on fresh all-inactive state
        self._deact_pending = []
        spine_run("serve_reset", self._init_device_state_on_lane)
        DEFAULT_REGISTRY.counter("serve_decode_failures").inc()

    def _retire(self, slot: int) -> None:
        req = self._slot_req[slot]
        self._slot_req[slot] = None
        # eviction returns blocks IMMEDIATELY: the freed HBM admits the
        # next queued request this same worker iteration — the whole
        # point of paging over per-slot worst-case reservation.  The
        # occupant rides along so its KV bill lands BEFORE _finish
        # retires the cost record.
        self._release_slot_blocks(slot, req=req)
        if req is not None:
            _finish(req)
            # serve_completed counts SUCCESSES: a lane retired carrying
            # a typed error (deadline shed, cancellation, block-pool
            # exhaustion) already incremented its own shed counter, and
            # counting it here too would inflate the success rate
            # exactly when the shed metrics say the pool is thrashing
            if req.error is None:
                DEFAULT_REGISTRY.counter("serve_completed").inc()

    def _process_chunk(
        self, packed_dev, snap: List[Optional[_Request]]
    ) -> bool:
        """Fetch one decode chunk's packed results and deliver its tokens.

        ``snap`` is the slot→request mapping at the chunk's DISPATCH time;
        tokens are delivered only to a slot whose occupant is still that
        request (a slot retired while the chunk was in flight decoded one
        discarded chunk — wasted compute, never misdelivered tokens); the
        worker's is a :class:`_ChunkSnap`, which also keeps the tables'
        runs as they stood then.
        Returns False when the fetch failed: the device state chained from
        this chunk is poisoned and ``_fail_active`` has reset it."""
        t_fetch0 = _now()
        try:
            # resilience_site: serve.decode_chunk — a delay rule here is
            # a SLOW-DECODE replica (chunk rounds stretch, deadlines shed,
            # the pool's canary/p95 hedging reacts); a raise is a decode
            # failure (the _fail_active typed-error path below)
            faults.perturb("serve.decode_chunk")
            # the span blocks until the chunk's device execution completes,
            # so serve_decode_chunk_ms keeps measuring real chunk rounds
            # (minus whatever host work the pipeline already overlapped) —
            # the dispatch itself is an async enqueue and times ~0.  The
            # ONE fetch per chunk runs as a spine work item: its measured
            # duration is the chunk's device time at the one-fetch
            # boundary, accrued under the decode program's cost model.
            with span("serve_decode_chunk", DEFAULT_REGISTRY):
                ticket = spine_submit(
                    "serve_decode_chunk",
                    lambda: np.asarray(packed_dev),
                    cost_key="decode",
                )
                packed_h = ticket.result()
        except Exception as e:
            # the cache was donated into a failed dispatch — fail every
            # in-flight request, reset device state, and keep serving
            # (a dead daemon thread would strand all current AND future
            # requests with no error)
            log.exception("decode chunk failed; resetting slot state")
            self._fail_active(e)
            return False
        t_fetch1 = _now()
        # first chunk landed: all request-path shapes are compiled, so
        # iteration time is now bounded by real chunk rounds — liveness
        # monitoring (pool wedge detection, canaries) may engage
        self._cold = False
        # a fetched chunk is REAL liveness evidence (the full dispatch →
        # device → fetch path just worked); the pool skips synthetic
        # canaries while this stays fresh
        self._last_progress = time_monotonic()
        # ---- per-request cost attribution (docqa-costscope): the
        # chunk's measured device time splits EQUALLY across the lanes
        # live at dispatch (every live lane advanced the same number of
        # in-program steps) — a retired-in-flight occupant still owns
        # its share (late-add).  Partitioning the same measured value
        # keeps per-class sums reconcilable against the
        # serve_decode_chunk dispatch series.
        charged = [r for r in snap if r is not None]
        if charged:
            dev_ms = ticket.device_s * 1e3 / len(charged)
            qw_ms = ticket.queue_wait_s * 1e3 / len(charged)
            cost_model = DEFAULT_OBSERVATORY.cost_of(
                "serve_decode_chunk", "decode"
            )
            fl = (
                cost_model["flops"] / len(charged) if cost_model else 0.0
            )
            for req in charged:
                _cost_add(req, "decode_device_ms", dev_ms)
                _cost_add(req, "spine_queue_wait_ms", qw_ms)
                if fl:
                    _cost_add(req, "flops_est", fl)
        sums_row = None  # the block's step sums, one more row of the fetch
        if self._block.step_sum_names and not self.spec_k:
            sums_row = packed_h[self.n_slots]
            packed_h = packed_h[: self.n_slots]
        if self.spec_k:
            width = self.chunk + 2 * self.spec_k
            out_h = packed_h[:, :width]
            counts_h = packed_h[:, width]
            active_h = packed_h[:, width + 1].astype(bool)
            # every emitted token is real (EOS excluded in-program)
            valid_h = np.arange(width)[None, :] < counts_h[:, None]
            n_cols = width
        else:
            out_h = packed_h[:, : self.chunk]
            valid_h = packed_h[:, self.chunk : 2 * self.chunk].astype(bool)
            active_h = packed_h[:, -1].astype(bool)
            n_cols = self.chunk
        deactivate = []
        n_appended = 0
        n_held = 0  # snapshot slots whose request is still the occupant
        # (KV length at dispatch, positions advanced) per snapshot lane:
        # what the chunk's steps read (serve_decode_kv_rows_*)
        lanes = []
        for slot in range(self.n_slots):
            req = snap[slot]
            if req is None:
                continue
            # every token delivered so far but the newest has its K/V
            # written; a lane retired in flight advanced nothing it kept
            lanes.append((
                req.kv_prompt + max(len(req.tokens) - 1, 0),
                int(valid_h[slot].sum()), slot,
            ))
            if self._slot_req[slot] is not req:
                continue
            n_held += 1
            before = len(req.tokens)
            for t in range(n_cols):
                if not valid_h[slot, t]:
                    continue
                if len(req.tokens) >= self._slot_budget[slot]:
                    break
                req.tokens.append(int(out_h[slot, t]))
                n_appended += 1
            # the fetch-block interval IS this slot's share of device
            # time for the round (one-fetch-per-dispatch boundary) —
            # recorded per request so a timeline shows every chunk the
            # request decoded through
            _req_span(
                req, "serve_decode_chunk", t_fetch0, t_fetch1,
                slot=slot, tokens=len(req.tokens) - before,
                **self._block.span_attrs,
            )
            _cost_add(req, "decode_tokens", len(req.tokens) - before)
            if len(req.tokens) > before:  # wake streamers per chunk
                with req.cv:
                    req.cv.notify_all()
            # early-retire a lane whose budget ran out mid-decode: nobody
            # is waiting for the rest of its tokens, and the freed slot
            # admits queued work a whole chunk sooner.  Only STILL-RUNNING
            # lanes shed — a request that completed (EOS / token budget)
            # in this same chunk has a full answer, and marking it failed
            # would discard finished work for nothing.
            finished = (
                not active_h[slot]
                or len(req.tokens) >= self._slot_budget[slot]
            )
            expired = (
                not finished
                and req.deadline is not None
                and req.deadline.expired
            )
            if expired:
                req.error = DeadlineExceeded(
                    "serve_decode", -req.deadline.remaining()
                )
                DEFAULT_REGISTRY.counter("serve_deadline_shed").inc()
                _req_mark(req, "deadline_exceeded", stage="serve_decode")
                DEFAULT_COST_LEDGER.record_shed(
                    "deadline",
                    cls=req.cost.cls if req.cost is not None else None,
                    stage="serve_decode",
                )
            # hedged-dispatch loser retires at this chunk boundary: the
            # winning replica already owns the answer, so the lane frees
            # for queued work instead of decoding a duplicate to the end
            cancelled = not finished and not expired and req.cancelled
            if cancelled and not req.done.is_set():
                req.error = RequestCancelled("cancelled mid-decode")
                _req_mark(
                    req, "cancelled", anomalous=False, stage="serve_decode"
                )
            if finished or expired or cancelled:
                deactivate.append(slot)
                self._retire(slot)
        # tokens delivered per dispatch: with speculation this exceeds
        # chunk x live-slots when drafts accept — the acceptance signal
        # an operator watches on /metrics
        DEFAULT_REGISTRY.histogram("serve_tokens_per_chunk").observe(
            float(n_appended)
        )
        DEFAULT_REGISTRY.counter("serve_decode_chunks").inc()
        # what the block kind counts of a chunk, from its sums row and
        # from the positions the lanes advanced, which the host holds
        counts, samples = self._block.chunk_counts(
            lane_steps=sum(adv for _, adv, _ in lanes), row=sums_row,
            kernels=self._kernels, n_lanes=self.n_slots,
        )
        for name, amount in counts.items():
            DEFAULT_REGISTRY.counter(name).inc(amount)
        for name, sample_ in samples.items():
            DEFAULT_REGISTRY.histogram(name).observe(sample_)
        lens = self._chunk_lens(lanes)
        rows_read, rows_live = self._chunk_kv_rows(lens)
        DEFAULT_REGISTRY.counter("serve_decode_kv_rows_read").inc(rows_read)
        DEFAULT_REGISTRY.counter("serve_decode_kv_rows_live").inc(rows_live)
        for name, amount in zip(
                ("serve_decode_kv_copies", "serve_decode_kv_blocks",
                 "serve_decode_kv_run_blocks"),
                self._chunk_kv_copies(
                    lens, [slot for _, _, slot in lanes], rows_read,
                    getattr(snap, "runs", None) or self._table_runs())):
            DEFAULT_REGISTRY.counter(name).inc(amount)
        if not n_held:
            # every lane the chunk advanced had retired by the time it
            # was fetched (the overshoot chunk dispatched ahead): device
            # time that delivered nothing
            DEFAULT_REGISTRY.counter("serve_decode_chunks_stale").inc()
        if deactivate:
            # queued for the next device work item (_apply_deact_on_lane)
            # — the worker never issues device ops from its own thread
            self._deact_pending.extend(deactivate)
        return True

    def _chunk_lens(self, lanes) -> np.ndarray:
        """[lanes, steps]: the KV length each step of a fetched chunk
        attended.  ``lanes``: per lane of the chunk's snapshot, (KV length
        at dispatch, positions it advanced, slot).  A step attends
        ``width`` new positions (1, or ``speculative_k``) past what the
        lane has advanced so far; a lane that stopped keeps attending
        where it stood.  The plain program runs ``chunk`` steps; the
        speculative one loops on the device until every lane has emitted
        a chunk, which the host does not see — counted as its fewest
        possible forwards."""
        width = max(self.spec_k, 1)
        steps = self.chunk
        if self.spec_k:
            most = max((adv for _, adv, _ in lanes), default=0)
            steps = max(-(-most // width), 1)
        grown = np.arange(steps) * width
        return np.array(
            [length + width + np.minimum(grown, adv)
             for length, adv, _ in lanes], np.int64,
        ).reshape(len(lanes), steps)

    def _chunk_kv_rows(self, lens) -> Tuple[int, int]:
        """(KV rows fetched, KV rows live) PER CACHE ENTRY — a layer's; a
        (step, layer)'s under the looped trunk, whose every pass reads its
        own entry over the same lengths, so the two stay a ratio — over
        one chunk's steps (``lens``: :meth:`_chunk_lens`), as the decode
        program is built — host arithmetic on what ``_process_chunk``
        holds, no device fetch.  Live rows are those lengths summed over
        lanes and steps.  Fetched rows: under the paged kernel the live
        PAGES of each lane (``ceil(len / block_size)`` of them); under the
        gather reference every slot's whole block table, live or not,
        each step."""
        live = int(lens.sum())
        read = int((-(-lens // self.block_size)).sum()) * self.block_size
        if self._block.kv_rows_read is not None:
            # a kind that reads by a rule of its own (a layer that selects,
            # layer kinds that read differently): what it counts beside
            read, counts = self._block.kv_rows_read(
                lens, kernels=self._kernels, block_size=self.block_size,
                table_rows=self.n_slots * self.seq_capacity,
            )
            for name, amount in counts.items():
                DEFAULT_REGISTRY.counter(name).inc(amount)
        elif not self._kernels.paged:
            read = lens.shape[1] * self.n_slots * self.seq_capacity
        return read, live

    def _table_runs(self):
        """Per layer kind of the block's ``paged_reads``: [slots, compute
        blocks] bool, the blocks of each slot's table — its ring for a
        windowed kind — whose page ids are one ascending run, as the paged
        kernel's wrapper marks them (``ops/attention.paged_block_runs``,
        on the host's copy of the tables).  Nothing where no such kernel
        runs."""
        if not self._kernels.paged:
            return ()
        ppb = paged_block_pages(self.blocks_per_seq, self.block_size)
        runs = []
        for _, window in self._block.paged_reads:
            tables, n_blocks = self._block_rows, self.n_blocks
            if window is not None and self._ring_pages:
                tables = self._ring_pages_np[
                    :, np.arange(self.blocks_per_seq) % self._ring_pages]
                n_blocks = self._ring_pages_np.size
            runs.append(paged_block_runs(tables, ppb, n_blocks))
        return tuple(runs)

    def _chunk_kv_copies(self, lens, slots, rows_read,
                         runs) -> Tuple[int, int, int]:
        """(page copies, compute blocks, blocks fetched as ONE run) the
        paged kernel's program issued over one chunk's steps PER CACHE
        ENTRY and per pool (K; V's are as many again) — host arithmetic as
        :meth:`_chunk_kv_rows` is (``lens``: :meth:`_chunk_lens`, ``slots``
        its lanes' slots), on the kernel's own decisions: a lane's step
        walks the compute blocks from the first its window still sees to
        its last; a block whose pages are all live and whose ids are one
        run (``runs``: :meth:`_table_runs` at dispatch) is one copy, any
        other a copy a live page.  Where the host cannot see the tables
        the kernel walks (``paged_reads`` empty: a layer that selects, the
        latent block's own kernel) every page read is a copy and no block
        is counted; nothing on the gather reference."""
        if not (self._kernels.paged or self._kernels.sparse_paged):
            return 0, 0, 0
        if not runs or not slots:
            return rows_read // self.block_size, 0, 0
        ppb = paged_block_pages(self.blocks_per_seq, self.block_size)
        pages = -(-lens // self.block_size)
        lane = np.asarray(slots)[:, None]
        copies = blocks = run_blocks = layers = 0
        for (n_layers, window), kind_runs in zip(
                self._block.paged_reads, runs):
            # run blocks before block j of each slot's table
            before = np.pad(np.cumsum(kind_runs, axis=1), ((0, 0), (1, 0)))
            first = np.zeros_like(pages)
            if window is not None:
                first = np.maximum(lens - window, 0) // (
                    ppb * self.block_size)
            full = np.maximum(pages // ppb, first)
            as_runs = int((before[lane, full] - before[lane, first]).sum())
            copies += n_layers * (
                int((pages - first * ppb).sum()) - as_runs * (ppb - 1))
            blocks += n_layers * int((-(-pages // ppb) - first).sum())
            run_blocks += n_layers * as_runs
            layers += n_layers
        return copies // layers, blocks // layers, run_blocks // layers

    def _blocks_for_admission(self, req: "_Request") -> int:
        """FRESH blocks an admission would allocate for ``req`` (prompt
        after truncation plus the grow margin, capped at one sequence) —
        net of any cached prefix the request would map in shared (warm
        admissions cost the pool only their novel suffix, which is what
        lets a repeat-heavy mix admit deeper into the same HBM)."""
        usable = self.cache_len - 2 - self.spec_k
        # + generated-so-far: a preemption victim re-prefills its tokens
        # too (token-preserving resume), so its block need grows with it
        n_ids = max(
            1, min(len(req.prompt_ids) + len(req.tokens), usable)
        )
        total = self._alloc.blocks_for(
            min(n_ids + self._grow_margin, self.seq_capacity)
        )
        if self._prefix_cache is not None and req.prefix_key is not None:
            try:
                ids = (
                    [int(t) for t in req.prompt_ids]
                    + [int(t) for t in req.tokens]
                )[-usable:]
            except (TypeError, ValueError):
                return total  # bad request: _admit_round fails it alone
            shared = self._prefix_cache.peek(req.prefix_key, ids)
            total -= shared // self.block_size
        return max(total, 0)

    def _pop_free_slots(
        self, pairs: List[Tuple[int, "_Request"]]
    ) -> None:
        """Fill every free slot from the queue into ``pairs`` (the ONE
        admission-selection policy; caller holds ``self._cv``).

        Requests whose deadline lapsed *while queued* are failed here —
        never admitted: prefilling them would spend a batched forward on
        answers nobody is waiting for.  A request
        the block pool cannot hold right now STOPS the fill (FIFO is
        preserved — no head-of-line skipping to smaller prompts): it
        stays queued, traced, and deadline-governed until retirements
        free blocks, which the very next worker iteration re-checks."""
        taken = {s for s, _ in pairs}
        drained = False
        # blocks this call has already earmarked (the allocator only
        # commits in _admit_round, so the capacity check must account
        # for earlier picks in the same round)
        planned = sum(self._blocks_for_admission(r) for _, r in pairs)
        blocked = False
        # preemption victims buffered for requeue AFTER the fill: they
        # must not enter the queue while the head the block plan was
        # computed against is still peeked (docqa-qos)
        preempted_back: List[_Request] = []
        for slot in range(self.n_slots):
            if blocked or self._slot_req[slot] is not None or slot in taken:
                continue
            filled = False
            while self._queue and not filled:
                head = self._queue[0]
                need = self._blocks_for_admission(head)
                head_live = (
                    head.deadline is None or not head.deadline.expired
                ) and not head.cancelled
                if (
                    head_live
                    and self._prefix_cache is not None
                    and not self._alloc.can_alloc(planned + need)
                ):
                    # starving LIVE head (a cancelled/expired one is
                    # about to be shed below — never dump warm state
                    # for it): cached-but-idle prefixes give their HBM
                    # back before the head is left queued (the
                    # BlockPoolExhausted-pressure valve).  Re-estimate
                    # afterwards: the eviction may have taken the
                    # head's OWN entry, so its peek-discounted need is
                    # stale and admitting on it would just bounce off
                    # OutOfBlocks in _admit_round.
                    if self._prefix_cache.evict_for(planned + need):
                        need = self._blocks_for_admission(head)
                if (
                    head_live
                    and self._qos is not None
                    and self._qos.preemption != "off"
                    and not self._alloc.can_alloc(planned + need)
                ):
                    # KV preemption (docqa-qos): after the prefix-cache
                    # valve gave back idle HBM, before the head is left
                    # block-starved — a higher-ranked head may evict
                    # lower-ranked LIVE lanes.  Advisory mode only
                    # counts what it would have done.
                    need = self._admission_preempt(
                        head, planned, need, preempted_back
                    )
                if head_live and not self._alloc.can_alloc(
                    planned + need
                ):
                    # pool exhausted for now: leave it queued (typed
                    # trace event; the deadline check below still sheds
                    # it if the budget lapses while it waits).  Mark and
                    # count ONCE per starvation episode — the worker
                    # re-polls this head every iteration (and every
                    # 50 ms while idle), and per-poll marking would
                    # bloat the request's trace and turn the counter
                    # into a poll-rate meter instead of a wait meter.
                    if self._block_wait_marked != id(head):
                        self._block_wait_marked = id(head)
                        _req_mark(
                            head, "block_pool_exhausted", queued=True,
                            anomalous=False,
                            free_blocks=self._alloc.n_free,
                        )
                        DEFAULT_REGISTRY.counter(
                            "serve_block_pool_wait"
                        ).inc()
                    blocked = True
                    break
                req = self._queue.popleft()
                if self._block_wait_marked == id(req):
                    # the starved head is leaving the queue: clear the
                    # episode marker so a FUTURE request reusing this
                    # object's address still gets its own mark/count
                    self._block_wait_marked = None
                drained = True
                # queue-wait is over either way (admitted or shed).  A
                # request popped before (bounced off the block pool,
                # preempted) waited from its re-entry, not from submit:
                # each entry records one set of spans, end to start
                t_pop = _now()
                _req_span(
                    req, "serve_queue_wait",
                    req.t_queue if req.t_pop else req.t_submit, t_pop,
                )
                req.t_pop = t_pop
                # cost wait = THIS queue entry's interval only (t_queue
                # resets on every requeue, so bounced/rescued requests
                # sum disjoint intervals instead of re-counting)
                _cost_add(
                    req, "queue_wait_ms",
                    (t_pop - (req.t_queue or req.t_submit)) * 1e3,
                )
                if req.cancelled:
                    # hedged-dispatch loser (or abandoned client) still
                    # queued: drop before it costs a prefill lane
                    if not req.done.is_set():
                        req.error = RequestCancelled(
                            "cancelled before admission"
                        )
                        _req_mark(
                            req, "cancelled", anomalous=False,
                            stage="serve_queue",
                        )
                        _finish(req)
                    continue
                if req.deadline is not None and req.deadline.expired:
                    req.error = DeadlineExceeded(
                        "serve_queue", -req.deadline.remaining()
                    )
                    DEFAULT_REGISTRY.counter("serve_deadline_shed").inc()
                    _req_mark(
                        req, "deadline_exceeded", stage="serve_queue"
                    )
                    DEFAULT_COST_LEDGER.record_shed(
                        "deadline",
                        cls=req.cost.cls if req.cost is not None else None,
                        stage="serve_queue",
                    )
                    _finish(req)
                    continue
                pairs.append((slot, req))
                planned += need
                filled = True
            if not self._queue and not filled:
                break
        for victim in preempted_back:
            # requeued at their class head with tokens preserved: the
            # next admission re-prefills prompt + generated-so-far and
            # decoding continues exactly where it stopped (greedy).
            # Local requeue by design — the caller holds _cv, and the
            # pool's requeue hook takes locks that must never nest
            # under it; the mid-decode path (outside _cv) does offer
            # victims to the pool first.
            victim.t_queue = _now()
            self._queue.appendleft(victim)
        # pairs are now this round's in-flight admissions (cumulative
        # across the pipeline-drain top-up call); the worker clears the
        # count once _admit_round has made them slot-resident
        self._admitting = len(pairs)
        self._admitting_reqs = [r for _, r in pairs]
        if drained:
            # wake bulk submitters blocked on queue capacity
            # (generate_texts waits on this condition, not a sleep poll)
            self._cv.notify_all()

    def _run(self) -> None:
        """Worker entry: the loop body must NEVER die silently — a dead
        daemon thread would strand every current and future request with
        no error until their result timeouts (the exact hang the
        replica-pool failover exists to prevent)."""
        try:
            self._run_loop()
        except BaseException as e:
            self._worker_died(e)
        finally:
            # a kill() that lands mid-iteration lets THIS loop finish
            # its admission round — registering fresh block tables
            # AFTER the kill's own release sweep — before it notices
            # _stopped and exits.  Close the accounting on the way
            # out (release is idempotent and allocator-locked, so
            # racing stop()'s sweep is safe); crash exits already
            # swept in _worker_died, and a live batcher never takes
            # this branch.
            with self._cv:
                stopped = self._stopped
            if stopped:
                for slot in range(self.n_slots):
                    self._release_slot_blocks(slot)
                if self._prefix_cache is not None:
                    self._prefix_cache.clear()

    def _worker_died(self, e: BaseException) -> None:
        """The loop crashed out: fail-fast every request with a TYPED
        error.  Queued (unadmitted) requests are first offered to the
        pool's ``on_worker_death`` hook, which requeues them onto a
        healthy replica — only the unrescued remainder fails.  Admitted
        requests always fail here (their KV state died with the worker);
        the QA layer turns that into a degraded extractive answer."""
        log.error("batcher worker died: %r — failing in-flight typed", e)
        DEFAULT_REGISTRY.counter("serve_worker_deaths").inc()
        with self._cv:
            self._worker_dead = True
            # admission-window requests (popped but never slot-resident)
            # count as queued for rescue purposes: the dead worker can
            # never touch them again, and like the queue they carry no
            # tokens or device state — safe to re-admit elsewhere.
            # Dedup by identity: a block-starved requeue transiently has
            # a request in both lists, and offering it twice would let
            # two replicas decode into one token stream.
            queued = list(
                {
                    id(r): r
                    for r in self._admitting_reqs + list(self._queue)
                }.values()
            )
            self._admitting_reqs = []
            self._admitting = 0
            self._queue.clear()
            self._cv.notify_all()
        cb = self.on_worker_death
        if cb is not None:
            try:
                queued = list(cb(self, queued) or [])
            except Exception:
                log.exception("on_worker_death hook failed; failing queue")
        err = WorkerDied(f"batcher worker died: {e!r}")
        for req in queued:
            if not req.done.is_set():
                req.error = err
                _req_mark(req, "worker_died", queued=True)
                _finish(req)
        for slot in range(self.n_slots):
            req = self._slot_req[slot]
            self._slot_req[slot] = None
            self._release_slot_blocks(slot, req=req)
            if req is not None and not req.done.is_set():
                req.error = err
                _req_mark(req, "worker_died", slot=slot)
                _finish(req)
        # the dead worker's device state dies with it: cached prefix
        # rows are unreachable garbage — release the pins so the
        # allocator balances to zero on this generation
        if self._prefix_cache is not None:
            self._prefix_cache.clear()

    def _any_lane_owed(self, in_flight) -> bool:
        """Whether some occupied slot can still be owed a token once the
        chunk in flight (``in_flight``: its dispatch-time snapshot, None
        with nothing pending) has been fetched — the condition for
        dispatching another chunk.

        The budget is the host's alone to enforce, but the host can
        count: a lane that stays active through a chunk emits at least
        ``self.chunk`` tokens (exactly that many in the plain program;
        the speculative one loops until every live lane has), and a lane
        that emits fewer has ended (EOS, capacity).  So a lane the chunk
        in flight advances, with ``len(tokens) + chunk >= budget``, is
        finished when that chunk is fetched, whatever it samples.  A lane
        that chunk does not hold (nothing pending, or admitted since) is
        owed while it is short of its budget — every lane
        ``_finalize_admissions`` has not retired.  Resumed tokens count
        on both sides, as in ``_process_chunk``'s own comparison.  A lane
        judged not owed that is live after the fetch all the same is owed
        in the next iteration, where nothing is pending."""
        for slot, req in enumerate(self._slot_req):
            if req is None:
                continue
            due = len(req.tokens)
            if in_flight is not None and in_flight[slot] is req:
                due += self.chunk
            if due < self._slot_budget[slot]:
                return True
        return False

    def _run_loop(self) -> None:
        # The one dispatched-but-unprocessed decode chunk: (packed device
        # array, dispatch-time slot→request snapshot).  The snapshot is
        # taken at DISPATCH time, and the guard in _process_chunk
        # delivers tokens only where the occupant is still the
        # snapshot's request.
        pending: Optional[Tuple[jax.Array, List[Optional[_Request]]]] = None
        while True:
            self._beat = time_monotonic()
            # resilience_site: serve.worker_loop — a raise here is a
            # worker CRASH (escapes to _worker_died: queued requests
            # requeue via the pool, admitted fail typed); a delay rule is
            # a worker WEDGE (the heartbeat goes stale mid-iteration and
            # the pool's health monitor declares the replica dead)
            faults.perturb("serve.worker_loop")
            pairs: List[Tuple[int, _Request]] = []
            with self._cv:
                while (
                    not self._stopped
                    and not self._queue
                    and not any(self._slot_req)
                ):
                    self._beat = time_monotonic()
                    with span("serve_idle_wait", DEFAULT_REGISTRY):
                        self._cv.wait(0.5)
                if self._stopped:
                    return
                # admission: fill every free slot from the queue; the whole
                # round prefills in one batched dispatch below
                self._pop_free_slots(pairs)
                # ---- gather: with a round's first requests popped and
                # slots still free, wait for the next arrival and pop it
                # into the same round.  Every arrival restarts the wait.
                # It ends when the slots are full, on stop, when a popped
                # request's budget runs out, when the head is block-
                # starved (the fill stopped: waiting would not admit it),
                # or when NO ARRIVAL IS EXPECTED and nothing arrived for
                # generate.admit_hold_ms (0 by default: at once).  While
                # arrivals are expected (expect_arrival) one may take
                # _EXPECTED_ARRIVAL_BOUND_S; the count dropping to zero
                # ends the wait, the bound is for an ask that never
                # leaves.  FIFO order is _pop_free_slots's.
                #
                # Expected arrivals count only for a round into an IDLE
                # batcher.  With lanes live a chunk is in flight, its
                # fetch below is the wait this round owes anyway and the
                # top-up after it takes whoever arrived meanwhile (an
                # expected ask's retrieval sits behind that chunk on the
                # device); gathering first would add a second wait, which
                # every live lane's next chunk pays.
                idle = pending is None and not any(self._slot_req)
                if pairs and (self._admit_hold_s or (idle and self._expected)):
                    free = sum(1 for r in self._slot_req if r is None)
                    t_gather = last = _now()
                    seen = 0  # most arrivals expected at once, as waited on
                    with span("serve_admit_gather", DEFAULT_REGISTRY):
                        while True:
                            if len(pairs) >= free:
                                ended_by = "full"
                                break
                            if self._stopped:
                                ended_by = "stop"
                                break
                            expected = self._expected if idle else 0
                            wait_s = self._admit_hold_s
                            if expected:
                                wait_s = max(wait_s, _EXPECTED_ARRIVAL_BOUND_S)
                            left = last + wait_s - _now()
                            if left <= 0:
                                ended_by = "expired" if expected else "quiet"
                                break
                            for _, held in pairs:
                                if held.deadline is not None:
                                    left = held.deadline.bound(left)
                            if left <= 0:
                                ended_by = "deadline"
                                break
                            self._beat = time_monotonic()
                            if not self._queue:
                                if expected and not seen:
                                    DEFAULT_REGISTRY.counter(
                                        "serve_admit_expected_rounds"
                                    ).inc()
                                seen = max(seen, expected)
                                self._cv.wait(left)
                            n = len(pairs)
                            self._pop_free_slots(pairs)
                            if len(pairs) > n:
                                last = _now()
                            elif self._queue:
                                ended_by = "starved"
                                break
                    if ended_by == "expired":
                        DEFAULT_REGISTRY.counter(
                            "serve_admit_expected_expired"
                        ).inc()
                    t_gathered = _now()
                    for _, req in pairs:
                        _req_span(
                            req, "serve_admit_gather",
                            max(t_gather, req.t_pop), t_gathered,
                            expected=seen, ended_by=ended_by,
                        )
                if (
                    not pairs
                    and self._queue
                    and not any(self._slot_req)
                ):
                    # queue head is block-starved with every slot idle
                    # (pool held outside the slot set — a test harness
                    # or a teardown window): bounded wait instead of a
                    # hot spin; retirements notify this cv
                    self._beat = time_monotonic()
                    with span("serve_idle_wait", DEFAULT_REGISTRY):
                        self._cv.wait(0.05)
                    self._pop_free_slots(pairs)
            drained_at = None
            if pairs:
                # drain the pipeline before admitting: processing may
                # retire slots this round can refill, and the fetch is the
                # PR-9 re-use guarantee — the only chunk in flight, stale
                # writes to retired lanes' rows included, has LANDED
                # before the prefill that re-populates them is dispatched.
                # The span is every admission round's: with nothing in
                # flight (the lanes before it retired on their budgets
                # and no chunk was sent past them) the round waited 0 ms
                # for the pipeline, and the last real chunk was fetched
                # before its lanes retired — the same guarantee.
                with span("serve_admit_drain", DEFAULT_REGISTRY):
                    drained_ok = (
                        pending is not None and self._process_chunk(*pending)
                    )
                if pending is not None:
                    drained_at = _now()
                    pending = None
                if drained_ok:
                    with self._cv:  # top-up from slots freed by the drain
                        self._pop_free_slots(pairs)
                # on drain failure the device state was reset; the popped
                # requests were never slot-resident, so admit them into
                # the fresh state below
            # grow-at-decode: top up every live lane's block table to the
            # margin BEFORE dispatching, and ahead of the round's own
            # allocation (the in-program capacity guard must never be
            # what stops a live lane; a live lane outranks a popped
            # request for the last blocks).  A lane the pool
            # cannot grow sheds TYPED here — in an overcommitted pool
            # (gen.kv_pool_tokens < worst case) that is the designed
            # failure mode, and it frees the lane's blocks for the rest.
            shed_slots = []
            for slot in range(self.n_slots):
                req = self._slot_req[slot]
                table = self._slot_table[slot]
                if req is None or table is None:
                    continue
                est = req.kv_prompt + len(req.tokens)
                target = min(est + self._grow_margin, self.seq_capacity)
                if table.capacity >= target:
                    continue
                try:
                    freed = 0
                    try:
                        table.ensure(target)
                    except OutOfBlocks:
                        if self._prefix_cache is None:
                            raise
                        # a live lane beats a cached idle prefix: evict
                        # LRU pins and retry once before shedding typed
                        freed = self._prefix_cache.evict_for(
                            self._alloc.blocks_for(target)
                            - len(table.blocks)
                        )
                        try:
                            table.ensure(target)
                        except OutOfBlocks:
                            if not freed:
                                raise
                            # the valve DID evict between the attempts,
                            # but a concurrent release/alloc raced the
                            # retry: one more try before degrading a
                            # live lane whose pressure freed real HBM
                            table.ensure(target)
                    row = self._block_rows[slot]
                    row[: len(table.blocks)] = table.blocks
                    self._caps_np[slot] = table.capacity
                    self._tables_dirty = True
                except OutOfBlocks:
                    if self._grow_preempt(slot, req, table, target):
                        # a lower-ranked lane gave up its blocks and
                        # requeued (tokens preserved); this lane decodes
                        # on — preemption before any shed (docqa-qos)
                        continue
                    with self._cv:
                        n_queued = len(self._queue)
                    req.error = BlockPoolExhausted(
                        "KV block pool exhausted mid-decode "
                        f"(lane at {est} tokens, pool "
                        f"{self.n_blocks}x{self.block_size})",
                        n_queued=n_queued,
                        n_active=self.n_active,
                    )
                    DEFAULT_REGISTRY.counter("serve_block_shed").inc()
                    _req_mark(req, "block_pool_exhausted", slot=slot)
                    # forensics BEFORE the retire frees its blocks: the
                    # snapshot must show the holdings that caused the
                    # shed, including the victim's own
                    DEFAULT_COST_LEDGER.record_shed(
                        "block_pool_exhausted",
                        cls=req.cost.cls if req.cost is not None else None,
                        stage="serve_decode_grow",
                        lane_tokens=est,
                    )
                    self._retire(slot)
                    shed_slots.append(slot)
            if shed_slots:
                # queued for the next device closure (the worker never
                # issues device ops from its own thread)
                self._deact_pending.extend(shed_slots)
            # ---- prefill first: a round's prefill is the next device
            # program, and the iteration's ONE decode chunk follows it
            # with the live lanes AND the lanes just admitted in its
            # snapshot.  One device runs the two back to back in either
            # order, so the order only decides who waits: the new
            # requests get their first token a chunk sooner, and a live
            # lane's chunk lands one prefill later — where its next
            # chunk would have landed behind that prefill anyway.  The
            # spine's "prefill" stream still ranks below decode-class
            # items: that protects OTHER replicas' chunks on a shared
            # lane, not this worker's order.
            admitted = None
            if pairs:
                # lanes live now are due a chunk: the round goes ahead
                # of it (a round into an idle batcher has nothing to pass)
                ahead = any(self._slot_req)
                try:
                    with span("serve_admit_round", DEFAULT_REGISTRY):
                        admitted = self._admit_round(pairs, drained_at)
                    if not admitted[0]:
                        admitted = None
                    elif ahead:
                        DEFAULT_REGISTRY.counter("serve_prefill_ahead").inc()
                except Exception as e:
                    # the round's dispatch died; the pool was donated
                    # through it — fail in-flight and reset.  Requests
                    # _admit_round already sent BACK to the queue
                    # (block-starved) were never in the dispatch: they
                    # stay queued for the next round, not failed here.
                    log.exception("admission round failed; resetting")
                    with self._cv:
                        requeued = {id(r) for r in self._queue}
                    for _slot, req in pairs:
                        if id(req) in requeued:
                            continue
                        if not req.done.is_set():
                            req.error = RuntimeError(f"prefill failed: {e!r}")
                            _finish(req)
                    self._fail_active(e)
                    pending = None
                    continue
                finally:
                    # every pair is slot-resident or finished by now —
                    # drain() may judge quiescence again
                    with self._cv:
                        self._admitting = 0
                        self._admitting_reqs = []
                        self._cv.notify_all()
            # one decode chunk for every live slot, dispatched BEFORE the
            # round's first tokens and the previous chunk's results are
            # fetched — the fetches and host work below overlap it
            fn = self._get_decode_fn()

            def _decode_on_lane():
                """Device phase (spine work item): pending deactivations,
                dirty block-table upload, then the one chunk dispatch —
                an async enqueue chained on the previous chunk's device
                state, so the pipeline overlap is unchanged."""
                self._apply_deact_on_lane()
                if self._tables_dirty:
                    self._tables_dev = jnp.asarray(self._block_rows)
                    self._caps_dev = jnp.asarray(self._caps_np)
                    self._tables_dirty = False
                if self.spec_k:
                    (
                        self._pools,
                        self._table,
                        self._tok,
                        self._lengths,
                        self._active,
                        out,
                    ) = fn(
                        self.engine.params,
                        self._pools,
                        self._tables_dev,
                        self._caps_dev,
                        self._table,
                        self._tok,
                        self._lengths,
                        self._active,
                    )
                else:
                    (
                        self._pools,
                        self._tok,
                        self._lengths,
                        self._active,
                        out,
                    ) = fn(
                        self.engine.params,
                        self._pools,
                        self._tables_dev,
                        self._caps_dev,
                        self._tok,
                        self._lengths,
                        self._active,
                        self._next_rng(),
                    )
                return out

            packed = snap = None
            occupied = any(self._slot_req)
            if occupied and not self._any_lane_owed(
                pending[1] if pending is not None else None
            ):
                # every occupied lane reaches its budget inside the chunk
                # in flight: the fetch below retires them all, nothing is
                # sent past them, and the loop idles with an empty
                # pipeline (the next round has nothing to drain)
                DEFAULT_REGISTRY.counter("serve_decode_chunks_skipped").inc()
            elif occupied:
                # snapshot at DISPATCH time: slots this chunk advances,
                # this round's lanes among them.  One whose first token
                # retires it in _finalize_admissions below (EOS, budget
                # < 2) is inactive on the device, and the guard in
                # _process_chunk drops any slot whose occupant changed.
                snap = _ChunkSnap(self._slot_req)
                snap.runs = self._table_runs()
                try:
                    with span("serve_decode_dispatch", DEFAULT_REGISTRY):
                        packed = spine_run("serve_decode", _decode_on_lane)
                except Exception as e:
                    log.exception(
                        "decode dispatch failed; resetting slot state"
                    )
                    self._fail_active(e)
                    pending = None
                    continue
            ok = True
            if admitted is not None:
                # blocks on the prefill alone (the chunk above runs
                # behind it): a request's first token is delivered
                # before any token of that chunk
                ok = self._finalize_admissions(admitted)
            if ok and pending is not None:
                ok = self._process_chunk(*pending)
            pending = (packed, snap) if ok and packed is not None else None
