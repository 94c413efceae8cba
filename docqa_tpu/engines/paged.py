"""Block-table paged KV cache for the continuous batcher (ROADMAP item 1;
Ragged Paged Attention, arXiv 2604.15464).

The bucket-padded slot model this replaces pinned worst-case-bucket HBM
per slot for the slot's whole lifetime and compiled one prefill program
per (shape family x prompt bucket).  Here KV lives in ONE flat HBM block
pool shared by every slot:

* **host side** — :class:`BlockAllocator`: a lock-disciplined free list
  of fixed-size KV blocks with per-request :class:`BlockTable`\\ s.
  Blocks are allocated at admission (prompt + a grow margin), grown at
  decode as a lane's length approaches its allocated capacity, and freed
  at retirement — a long-running request holds blocks proportional to
  the tokens it has actually produced, not to the worst-case bucket.
  Release is idempotent AND double-free-guarded (the drain / steal /
  failover paths must free exactly once; tests/test_paged.py).
* **device side** — :func:`ragged_prefill_forward` scatters a PACKED
  batch of mixed-length prompts into their block tables in one dispatch
  (no shape families, no per-bucket padding: any length mix that fits
  the token budget shares one compiled program), and
  :func:`paged_decode_forward` advances lanes by gathering K/V through
  the block table.  Both are thin compositions of the shared decoder
  trunk (:func:`~docqa_tpu.models.decoder.decoder_layer_stack`) with the
  ragged/paged attention ops (``ops/attention.py``), so the layer math
  can never drift from the dense solo engine — the serve-vs-solo
  token-equality invariant holds by construction.

The allocator is HOST-ONLY and thread-light by design: the batcher
worker is the single caller of alloc/grow on the hot path, other threads
only read stats or release tables — no new thread ever reaches a jax
dispatch (``dispatch_streams.json`` is unchanged by this module).
"""

from __future__ import annotations

import collections
import math
import threading
from time import monotonic as _mono
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from docqa_tpu.config import DecoderConfig
from docqa_tpu.models.decoder import (
    Params,
    block_serving,
    decoder_head,
    decoder_layer_stack,
    kernel_forms,
    kv_entries,
    kv_row_shapes,
    lane_state_shapes,
)
from docqa_tpu.models.hybrid import (
    ATTENTION,
    LINEAR,
    MAMBA,
    RETENTION,
    SPARSE,
    STATE_SLOT,
    WINDOW,
    WINDOW_PAGES,
    decay_slopes,
    hybrid_head,
    hybrid_layer_stack,
    is_hybrid,
    lane_state_entries,
    layers_of,
    ring_pages,
    sparse_layers,
    ssm_constants,
    window_layers,
)
from docqa_tpu.models.latent import (
    absorb_query,
    expand_output,
    is_latent,
    latent_layer_stack,
    softmax_scale,
    up_projected,
)
from docqa_tpu.models.serving import KernelForms
from docqa_tpu.ops.attention import (
    RAGGED_ALIGN,
    compressed_keys,
    latent_decode_attention,
    linear_attention_prefill,
    linear_attention_step,
    paged_decode_attention,
    power_retention_prefill,
    ragged_prefill_attention,
    sparse_decode_attention,
    sparse_prefill_attention,
)
from docqa_tpu.ops.retention import retention_decode_step
from docqa_tpu.ops.scopes import scope
from docqa_tpu.ops.ssm import (
    causal_conv_prefill,
    causal_conv_step,
    conv_window_of,
    selective_scan_prefill,
    selective_scan_step,
)

# "k0".."k{L-1}", "v0".."v{L-1}"; the latent block: "c0".."c{L-1}"; the
# stack of mixer kinds: "k{i}" / "v{i}" of its row-keeping layers ("ck{i}"
# where one selects; a WINDOW layer's hold a ring of pages a lane), the
# lane-state entries of its state-keeping layers, STATE_SLOT and
# WINDOW_PAGES (``_init_hybrid_pools``)
PagedPools = Dict[str, "jnp.ndarray"]


class OutOfBlocks(RuntimeError):
    """The allocator could not satisfy a block request.  Internal to the
    paging layer: the batcher maps it to its typed admission/decode shed
    (``serve.BlockPoolExhausted``) with the request context attached."""


class BlockTable:
    """Per-request block list.  All mutation goes through the owning
    :class:`BlockAllocator` (one lock for table + free list, so a
    release racing a grow can never tear the accounting).

    The first ``n_shared`` blocks may be SHARED with other tables (a
    cached prompt prefix mapped in at refcount+1 — see
    :class:`PrefixCache`).  Shared blocks are immutable by contract:
    they hold a full-block-aligned prompt prefix, and every write a
    request ever issues lands at positions >= its own prompt length,
    which is past the shared region by construction (copy-on-write
    realized as never-write-shared).  ``grow`` only ever APPENDS fresh
    private blocks; ``release`` decrements instead of freeing blocks
    other tables still reference."""

    __slots__ = (
        "blocks", "n_shared", "released", "_alloc", "acc_base",
        "billed_block_seconds", "ring",
    )

    def __init__(self, alloc: "BlockAllocator") -> None:
        self.blocks: List[int] = []
        self.n_shared = 0
        self.released = False
        self._alloc = alloc
        # block-second accounting (docqa-costscope): acc_base[i] is
        # block blocks[i]'s unit-accrual reading at acquisition; the
        # table's bill at release is the sum of deltas — ∫ dt/refcount
        # over the holding interval per block, so prefix-SHARED blocks
        # bill each holder fractionally and the sum over holders equals
        # the block's total in-use time (exactness under sharing).
        self.acc_base: List[float] = []
        self.billed_block_seconds = 0.0
        # a lane's SECOND table, of a second allocator: the pages of its
        # ring in the pools of window layers (``models/hybrid.ring_pages``),
        # taken with this table at admission and released with it, so that
        # every path that frees a lane frees both exactly once
        self.ring: Optional["BlockTable"] = None

    @property
    def capacity(self) -> int:
        """Tokens this table can currently hold."""
        return self._alloc.capacity_of(self)

    def ensure(self, n_tokens: int) -> None:
        """Grow to cover ``n_tokens`` (no-op when already covered).
        Raises :class:`OutOfBlocks` atomically: either every needed
        block is taken or none are."""
        self._alloc.grow(self, n_tokens)

    def release(self) -> None:
        """Return every block to the pool (and the lane's ring to its
        own).  Idempotent and thread-safe: retire (worker), stop-sweep
        (caller thread), and failover paths may all reach a table —
        exactly one of them frees it."""
        self._alloc.release(self)
        if self.ring is not None:
            self.ring.release()


class BlockAllocator:
    """Free-list allocator over a fixed pool of KV blocks, REFCOUNTED
    for copy-on-write prefix sharing (docqa-prefix).

    LIFO reuse keeps recently-freed blocks hot — a released table's
    blocks are handed out again in the order the table held them, so runs
    of ascending ids survive a release —; allocation is
    all-or-nothing so a half-admitted request never strands blocks.
    A block's refcount is 1 when privately owned and +1 per table the
    prefix cache mapped it into; ``release`` decrements and only a
    0-refcount block returns to the free list.  Double frees raise
    (rather than silently inflating the free list) — the accounting IS
    the leak detector the chaos/drain tests assert on, and it stays
    exact under sharing: ``blocks_in_use`` counts UNIQUE live blocks,
    so shared-release-is-not-a-free is directly observable.
    """

    def __init__(
        self,
        n_blocks: int,
        block_size: int,
        now_fn: Optional[Callable[[], float]] = None,
    ) -> None:
        if n_blocks <= 0 or block_size <= 0:
            raise ValueError("n_blocks and block_size must be positive")
        self.n_blocks = int(n_blocks)
        self.block_size = int(block_size)
        self._lock = threading.Lock()
        # LIFO stack: low block ids hand out first (stable tests/debug)
        self._free: List[int] = list(range(self.n_blocks - 1, -1, -1))
        self._refs = [0] * self.n_blocks
        self._in_use = 0
        # ---- block-second ledger (docqa-costscope) ----
        # Event-driven exact integrals on an injectable clock (tests
        # step time explicitly).  Per block, _unit_acc accrues
        # ∫ dt / refcount while the block is live — settled at every
        # refcount change — so a holder's bill over [t0, t1] is the
        # _unit_acc delta, and Σ over all holders of a block equals its
        # plain in-use time.  _pool_acc is ∫ blocks_in_use dt (the pool
        # total); _billed sums every released table's bill, so
        # residual = total - billed is exactly the accrual still held
        # by live tables: ZERO once everything has released (the
        # drain/stop/chaos assertion).
        self._now = now_fn or _mono
        self._unit_acc = [0.0] * self.n_blocks
        self._last_evt = [0.0] * self.n_blocks
        self._pool_acc = 0.0
        self._pool_last = self._now()
        self._billed = 0.0

    # ---- block-second ledger internals (caller holds self._lock) ---------

    def _touch_pool_locked(self, now: float) -> None:
        self._pool_acc += (now - self._pool_last) * self._in_use
        self._pool_last = now

    def _settle_locked(self, b: int, now: float) -> None:
        if self._refs[b] > 0:
            self._unit_acc[b] += (now - self._last_evt[b]) / self._refs[b]
        self._last_evt[b] = now

    # ---- table lifecycle -------------------------------------------------

    def new_table(self) -> BlockTable:
        return BlockTable(self)

    def capacity_of(self, table: BlockTable) -> int:
        with self._lock:
            return len(table.blocks) * self.block_size

    def grow(self, table: BlockTable, n_tokens: int) -> None:
        with self._lock:
            need = -(-int(n_tokens) // self.block_size) - len(table.blocks)
            if need <= 0:
                return
            if table.released:
                raise OutOfBlocks("table already released")
            if need > len(self._free):
                raise OutOfBlocks(
                    f"need {need} block(s), {len(self._free)} free "
                    f"(pool {self.n_blocks} x {self.block_size} tokens)"
                )
            now = self._now()
            self._touch_pool_locked(now)
            for _ in range(need):
                b = self._free.pop()
                self._refs[b] = 1
                self._last_evt[b] = now  # accrual restarts at refcount 0->1
                table.blocks.append(b)
                table.acc_base.append(self._unit_acc[b])
            self._in_use += need

    def share(self, table: BlockTable, blocks: Sequence[int]) -> None:
        """Map an already-live block run into ``table`` at refcount+1 —
        the warm-admission path (and the cache's own pin).  The shared
        run must be the table's LEADING blocks (a prompt prefix), so the
        table must still be empty; all-or-nothing like ``grow``."""
        blocks = [int(b) for b in blocks]
        with self._lock:
            if table.released:
                raise OutOfBlocks("table already released")
            if table.blocks:
                raise ValueError(
                    "shared prefix blocks must be mapped before any "
                    "private growth (they are the table's leading run)"
                )
            for b in blocks:
                if self._refs[b] <= 0:
                    # sharing a freed block would resurrect it under a
                    # live table — the exactly-once contract broke
                    raise RuntimeError(
                        f"share of a free block (id {b}): the prefix "
                        "cache pinned a block the allocator no longer "
                        "considers live"
                    )
            now = self._now()
            for b in blocks:
                # settle at the OLD refcount first: the interval up to
                # now belongs to the existing holders alone
                self._settle_locked(b, now)
                self._refs[b] += 1
            table.blocks = list(blocks)
            table.n_shared = len(blocks)
            table.acc_base = [self._unit_acc[b] for b in blocks]

    def release(self, table: BlockTable) -> None:
        with self._lock:
            if table.released:
                return
            table.released = True
            if not table.blocks:
                return
            if len(set(table.blocks)) != len(table.blocks):
                # a block may be referenced by many tables, but never
                # twice by ONE — a duplicate means the table tore
                raise RuntimeError(
                    "double free detected: table lists a block twice"
                )
            for b in table.blocks:
                if self._refs[b] <= 0:
                    # decrementing past zero means a second release path
                    # reached blocks already fully freed — fail loudly,
                    # never double-add to the free list
                    raise RuntimeError(
                        f"double free detected: block {b} already at "
                        "refcount 0"
                    )
            now = self._now()
            self._touch_pool_locked(now)
            earned = 0.0
            bases = table.acc_base
            freed = []
            for i, b in enumerate(table.blocks):
                self._settle_locked(b, now)
                if i < len(bases):
                    earned += self._unit_acc[b] - bases[i]
                self._refs[b] -= 1
                if self._refs[b] == 0:
                    # a SHARED release is not a free: the block returns
                    # only when its last referencing table lets go
                    freed.append(b)
                    self._in_use -= 1
            # last block first, so that the stack hands the table's blocks
            # out again in the order it held them: a run of ascending ids
            # stays one (the paged kernel fetches a compute block whose
            # ids are a run with one copy, ``ops/attention.paged_block_runs``)
            self._free.extend(reversed(freed))
            table.billed_block_seconds = earned
            self._billed += earned
            table.blocks = []
            table.n_shared = 0
            table.acc_base = []

    # ---- sizing / stats --------------------------------------------------

    def blocks_for(self, n_tokens: int) -> int:
        return -(-int(n_tokens) // self.block_size)

    def can_alloc(self, n_blocks: int) -> bool:
        with self._lock:
            return int(n_blocks) <= len(self._free)

    @property
    def n_free(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def blocks_in_use(self) -> int:
        with self._lock:
            return self._in_use

    def refcount(self, block: int) -> int:
        with self._lock:
            return self._refs[int(block)]

    def reclaimable(self, table: BlockTable) -> int:
        """Blocks releasing ``table`` would actually return to the free
        list RIGHT NOW (refcount 1 — not also pinned by the prefix cache
        or another sharer).  The QoS preemption policy (docqa-qos) ranks
        victims by this, not by ``len(blocks)``: evicting a lane whose
        blocks are mostly shared prefix frees almost nothing.  One lock
        hold so the count is coherent against a concurrent release."""
        with self._lock:
            if table.released:
                return 0
            return sum(1 for b in table.blocks if self._refs[b] == 1)

    def block_seconds(self) -> Dict[str, float]:
        """The pool's block-second ledger (docqa-costscope): ``total``
        is ∫ blocks_in_use dt since construction, ``billed`` the sum of
        every released table's bill, ``residual`` the accrual still
        held by live tables — exactly zero after a full drain/stop (the
        chaos/test assertion; shared blocks bill each holder
        1/refcount, so the identity holds under prefix sharing too)."""
        with self._lock:
            self._touch_pool_locked(self._now())
            total = self._pool_acc
            billed = self._billed
        return {
            "total": total,
            "billed": billed,
            "residual": total - billed,
        }


# ---------------------------------------------------------------------------
# prefix cache: refcounted KV block sharing (docqa-prefix)
# ---------------------------------------------------------------------------


class _PrefixEntry:
    __slots__ = ("tokens", "pin", "n_tokens")

    def __init__(self, tokens: Tuple[int, ...], pin: BlockTable) -> None:
        self.tokens = tokens
        self.pin = pin  # a BlockTable of shared refs: the cache's pin
        self.n_tokens = len(tokens)


class PrefixCache:
    """LRU cache of immutable, full-block KV prompt prefixes.

    Keyed by the submitter's prefix key — for /ask that is
    ``(template hash, retrieved-chunk-set hash)`` (service/qa.py), the
    repeat-heavy clinical unit: many consecutive questions against one
    patient's chunk set share the whole template+context prefix.  An
    entry pins its blocks through its own :class:`BlockTable` of shared
    refs, so eviction and teardown reuse the allocator's exactly-once
    release accounting verbatim.  Entries store the prefix TOKEN IDS and
    admission verifies them against the new prompt token by token — a
    key collision (or template drift) degrades to a shorter shared run
    or a miss, never to wrong attention.

    Alignment contract: a shared run is always a multiple of
    ``align`` = lcm(RAGGED_ALIGN, block_size) tokens — full blocks only
    (immutability: no writer ever lands in a shared block) and
    128-aligned (the packed-softmax reduction trees, and therefore the
    emitted tokens, stay bitwise identical to a cold prefill — see
    ops/attention.RAGGED_ALIGN).

    Thread-safety: one lock, ordered BEFORE the allocator's (every path
    that takes both nests cache -> allocator).  The batcher worker is
    the only caller of lookup/insert; eviction may also come from
    submit threads under :class:`BlockPoolExhausted` pressure.
    """

    def __init__(
        self, alloc: BlockAllocator, align: int, max_entries: int = 32
    ) -> None:
        if align % alloc.block_size:
            raise ValueError(
                f"share alignment {align} must be a multiple of the "
                f"block size {alloc.block_size} (full blocks only)"
            )
        self._alloc = alloc
        self.align = int(align)
        self.max_entries = max(1, int(max_entries))
        self._lock = threading.Lock()
        self._entries: "collections.OrderedDict[str, _PrefixEntry]" = (
            collections.OrderedDict()
        )
        # lifetime counters (scraped into serve_kv_prefix_* gauges and
        # the serve_prefix_* registry counters by the batcher)
        self.hits = 0
        self.misses = 0
        self.insertions = 0
        self.evictions = 0
        self.tokens_avoided = 0

    # ---- admission-side API (batcher worker) ----------------------------

    def _shared_len_locked(
        self, entry: _PrefixEntry, ids: Sequence[int]
    ) -> int:
        """Longest verified, aligned, suffix-preserving shared run.

        Capped one align-unit below the prompt length: the suffix must
        keep >= 1 real token, because the prefill head samples the first
        output from the LAST PROMPT TOKEN's hidden state — a
        fully-cached prompt still prefills its final tokens."""
        n = min(entry.n_tokens, len(ids))
        n_match = 0
        toks = entry.tokens
        for i in range(n):
            if toks[i] != ids[i]:
                break
            n_match += 1
        return max(
            0,
            min(
                (n_match // self.align) * self.align,
                ((len(ids) - 1) // self.align) * self.align,
            ),
        )

    def peek(self, key: Optional[str], ids: Sequence[int]) -> int:
        """Shared-token estimate for capacity planning (the batcher's
        admission pre-check) — no counters, no recency bump, no share."""
        if key is None:
            return 0
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                return 0
            return self._shared_len_locked(entry, ids)

    def acquire(
        self, key: Optional[str], ids: Sequence[int], table: BlockTable
    ) -> int:
        """Map the longest cached, verified, aligned prefix of ``ids``
        into ``table`` at refcount+1; returns the shared token count
        (0 = miss).  Atomic with eviction (one lock), so a concurrent
        LRU eviction can never free a block between lookup and share.

        Does NOT update the hit/miss stats: the caller credits via
        :meth:`credit` once the admission actually holds — an
        OutOfBlocks bounce-and-requeue would otherwise count the same
        request twice, inflating the hit gauges exactly under the
        pool pressure they exist to diagnose."""
        if key is None:
            return 0
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                return 0
            shared = self._shared_len_locked(entry, ids)
            if shared <= 0:
                return 0
            self._alloc.share(
                table, entry.pin.blocks[: shared // self._alloc.block_size]
            )
            self._entries.move_to_end(key)
            return shared

    def credit(self, shared: int) -> None:
        """Record one keyed admission's outcome in the hit stats —
        called only after the admission's block allocation succeeded."""
        with self._lock:
            if shared > 0:
                self.hits += 1
                self.tokens_avoided += shared
            else:
                self.misses += 1

    def insert(self, key: Optional[str], ids: Sequence[int],
               table: BlockTable) -> bool:
        """Cache the aligned prefix of a just-admitted prompt (its K/V
        will be written by the admission dispatch; the device sequences
        every later reader after it).  Keeps the LONGEST prefix per key;
        shorter re-inserts only refresh recency."""
        if key is None:
            return False
        n = (len(ids) // self.align) * self.align
        if n <= 0:
            return False
        with self._lock:
            old = self._entries.get(key)
            if old is not None:
                self._entries.move_to_end(key)
                if old.n_tokens >= n:
                    return False
            pin = self._alloc.new_table()
            try:
                self._alloc.share(
                    pin, table.blocks[: n // self._alloc.block_size]
                )
            except BaseException:
                # a partial share (released/free source block) must not
                # strand the refs already taken: nobody owns `pin` yet
                pin.release()
                raise
            self._entries[key] = _PrefixEntry(tuple(ids[:n]), pin)
            self._entries.move_to_end(key)
            self.insertions += 1
            evict_old = old
            while len(self._entries) > self.max_entries:
                _, lru = self._entries.popitem(last=False)
                lru.pin.release()
                self.evictions += 1
        if evict_old is not None:
            evict_old.pin.release()
        return True

    # ---- pressure / lifecycle -------------------------------------------

    def evict_for(self, n_blocks: int) -> int:
        """Evict entries until the allocator could satisfy an
        ``n_blocks`` request (or nothing evictable remains) — the
        BlockPoolExhausted-pressure valve: cached-but-IDLE prefixes are
        the first HBM to give back, always before shedding live work.

        "Idle" is literal: only entries whose pin would actually free
        blocks now (refcount 1 — the cache is the sole reference) are
        candidates, in LRU order.  An entry whose blocks are still
        shared by in-flight lanes is in active use — evicting it frees
        nothing today and only destroys the session's future hits, so
        it is skipped (an earlier draft looped LRU-blind and could
        empty the whole cache under live-lane pressure while recovering
        zero HBM).  Returns the number of entries evicted."""
        n_evicted = 0
        with self._lock:
            while self._entries and not self._alloc.can_alloc(n_blocks):
                victim = None
                for key, entry in self._entries.items():  # LRU order
                    if any(
                        self._alloc.refcount(b) == 1
                        for b in entry.pin.blocks
                    ):
                        victim = key
                        break
                if victim is None:
                    break  # nothing idle: every pin is also live
                self._entries.pop(victim).pin.release()
                self.evictions += 1
                n_evicted += 1
        return n_evicted

    def clear(self) -> int:
        """Release every pin (teardown / device-state reset: pool
        contents are gone, so cached rows are garbage)."""
        with self._lock:
            entries = list(self._entries.values())
            self._entries.clear()
        for e in entries:
            e.pin.release()
        return len(entries)

    def stats(self) -> Dict[str, float]:
        with self._lock:
            pinned = sum(len(e.pin.blocks) for e in self._entries.values())
            n = len(self._entries)
            hits, misses = self.hits, self.misses
            return {
                "entries": float(n),
                "pinned_blocks": float(pinned),
                "hits": float(hits),
                "misses": float(misses),
                "hit_rate": hits / (hits + misses) if hits + misses else 0.0,
                "tokens_avoided": float(self.tokens_avoided),
                "evictions": float(self.evictions),
            }


def share_alignment(block_size: int) -> int:
    """Tokens per shareable prefix unit: full blocks AND 128-row aligned
    (both the immutability and the bitwise-exactness contract)."""
    from docqa_tpu.ops.attention import RAGGED_ALIGN

    return math.lcm(int(block_size), RAGGED_ALIGN)


# ---------------------------------------------------------------------------
# device side: block pool init + ragged/paged forwards
# ---------------------------------------------------------------------------


def init_paged_pools(
    cfg: DecoderConfig, n_blocks: int, block_size: int,
    dtype: Optional["jnp.dtype"] = None,
    sharding=None,
    n_lanes: Optional[int] = None,
) -> PagedPools:
    """Flat per-layer block pools, one per kind of row the block caches
    (``models/decoder.kv_row_shapes``): K and V pools of [n_blocks *
    block_size, kv_heads, head_dim] for the GQA block, ONE pool of
    [n_blocks * block_size, 1, latent + rope] for the latent block.
    Row ``b * block_size + o`` is offset ``o`` of block ``b``
    — the one flat axis the prefill scatter, the decode write and the
    decode read index, so a block id IS a row range: ``block_size``
    consecutive rows, one contiguous page the TPU decode kernel DMAs as a
    whole (``ops/attention.paged_flash_decode``).

    Under the looped trunk (``models/decoder.kv_entries`` T > 1) a layer's
    pool holds T such ranges one after the other, ``[T * n_blocks *
    block_size, ...]``: step ``t`` reads and writes block ``b`` at page
    ``t * n_blocks + b`` (:func:`_step_view`), so a block id stays ONE
    allocation that owns its rows in every step's range.

    ``sharding`` (``parallel.sharding.paged_pool_sharding`` on a mesh):
    each pool is CREATED under it — every device zero-fills only its own
    kv-head slice, nothing pool-sized is staged on one device first.  The
    latent row has no head axis to divide: its pool is replicated."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    if sharding is not None and (is_latent(cfg) or is_hybrid(cfg)):
        from jax.sharding import NamedSharding, PartitionSpec

        sharding = NamedSharding(sharding.mesh, PartitionSpec())
    if is_hybrid(cfg):
        return _init_hybrid_pools(
            cfg, n_blocks, block_size, dtype, sharding, n_lanes)
    pools: PagedPools = {}
    rows = kv_entries(cfg) * n_blocks * block_size
    for i in range(cfg.num_layers):
        for prefix, (heads, width) in kv_row_shapes(cfg).items():
            pools[f"{prefix}{i}"] = jnp.zeros(
                (rows, heads, width), dtype, device=sharding
            )
    return pools


def _step_view(cfg, step, n_rows, dest, block_tables=None, block_size=None):
    """Where step ``step`` of the looped trunk writes and reads: ``dest``
    (flat pool rows) and ``block_tables`` (block ids) moved into the
    step's own range of a pool that holds ``kv_entries(cfg)`` ranges of
    ``n_rows`` rows.  A hole stays a hole: a row or a block id past ONE
    range (a dropped write, an unallocated or retired table entry) goes
    past the WHOLE pool, never into the next step's range.  ``step`` None
    (the plain trunk): both as they came."""
    if step is None:
        return dest, block_tables
    total = kv_entries(cfg) * n_rows
    dest = jnp.where(dest < n_rows, dest + step * n_rows, total)
    if block_tables is not None:
        n_blocks = n_rows // block_size
        block_tables = jnp.where(
            block_tables < n_blocks, block_tables + step * n_blocks,
            total // block_size)
    return dest, block_tables


def kv_bytes_per_token(cfg: DecoderConfig) -> int:
    """HBM bytes one token of KV occupies across every layer — the
    block-granular accounting unit telemetry reports
    (ROADMAP item 1: per-token bytes instead of per-bucket).  The stack of
    mixer kinds: the K and V rows of its row-keeping layers (sparse,
    attention), plus a SPARSE layer's share of a compressed key (one per
    ``sparse_kernel_stride`` tokens); its state-keeping layers (linear,
    state-space, retention) keep nothing a token
    (``models/hybrid.lane_state_bytes``), and neither does a WINDOW layer:
    what it holds is a ring a lane, whatever the lane's length
    (``models/hybrid.ring_pages``).  A stack in which NO layer keeps a row
    answers 0: its pages stay the unit of admission (a lane's positions)
    and weigh nothing; what its memory is spent on is the state a lane
    (the occupancy's ``state_bytes_per_lane`` / ``state_pool_bytes``).
    The looped trunk: an entry a (step, layer), ``kv_entries`` times a
    plain model's."""
    item = jnp.dtype(cfg.dtype).itemsize
    per_layer = sum(h * w for h, w in kv_row_shapes(cfg).values())
    if is_hybrid(cfg):
        key = cfg.num_kv_heads * cfg.head_dim
        return item * (
            len(layers_of(cfg, SPARSE, ATTENTION)) * per_layer
            + len(sparse_layers(cfg)) * (key // cfg.sparse_kernel_stride))
    return kv_entries(cfg) * cfg.num_layers * per_layer * item


def _forms(cfg, kernels, use_flash, mesh, block_size) -> KernelForms:
    """The kernel forms a public forward runs: the engine's, handed down
    by the batcher (``kernels``); else derived here as the engine derives
    them, from the ``use_flash`` / ``mesh`` of a caller without an engine
    (the benchmark's comparison).  The backend is asked for a caller that
    observed nothing (``None``) and for the latent block, whose
    ``use_flash`` says nothing of it (its engine keeps the flag false)."""
    if kernels is not None:
        return kernels
    if use_flash is None or is_latent(cfg):
        use_flash = jax.default_backend() == "tpu"
    return kernel_forms(
        cfg, on_tpu=use_flash, mesh=mesh, block_size=block_size)


def ragged_prefill_forward(
    params: Params,
    cfg: DecoderConfig,
    pools: PagedPools,
    ids,  # [T] packed prompt tokens (pad elsewhere)
    seg_ids,  # [T] int32 lane index per token; -1 = padding
    positions,  # [T] int32 position within its own sequence
    dest_rows,  # [T] int32 flat pool row per token; >= P = dropped
    last_rows,  # [B] int32 packed row of each lane's last prompt token
    *,
    rope_len: int,
    block_tables=None,  # [B, NB] int32 (warm mode): per-lane block table
    prefix_lens=None,  # [B] int32 (warm mode): cached tokens per lane
    n_prefix_rows: int = 0,  # static prefix window (warm mode)
    block_size: Optional[int] = None,
    use_flash: Optional[bool] = None,
    mesh=None,
    kernels: Optional[KernelForms] = None,
):
    """Prefill a whole admission round of MIXED-length prompts in one
    dispatch: every token computes through the shared trunk, scatters its
    K/V straight into its block-table rows, and each lane's last-token
    hidden state feeds the head.

    Returns (last_logits [B, vocab] f32, pools).  Padding lanes produce
    garbage logits the caller ignores (their scatter rows are
    out-of-bounds and dropped).  No shape family, no prompt bucket: the
    compile key is the token budget T alone.

    WARM mode (``n_prefix_rows > 0``): the packed stream holds only each
    lane's NOVEL SUFFIX (positions start at the lane's cached prefix
    length); attention additionally reads the cached prefix K/V from the
    pool through ``block_tables`` / ``prefix_lens``.  The prefix rows
    are untouched by this dispatch's scatter (suffix positions map past
    them — copy-on-write as never-write-shared), and the pool stores the
    same bf16 K/V a cold prefill computes in flight, so warm output is
    bitwise-identical to cold (the token-equality gate in
    tests/test_prefix.py).

    The latent block (``cfg.block``) returns a THIRD value, its routing
    record (:func:`_latent_prefill_forward`).

    ``kernels``: the forms the engine chose, for the prefill ops that
    have a Pallas form — a state-space layer's scan (``ops/ssm.py``), a
    routed layer's grouped product (``ops/grouped.py``); derived from
    ``use_flash`` / ``mesh`` for a caller without an engine (:func:`_forms`).
    """
    kernels = _forms(cfg, kernels, use_flash, mesh, block_size)
    warm = n_prefix_rows > 0  # static host int, never a tracer
    if warm and "generate.prefix_cache" in block_serving(cfg).unserved:
        raise NotImplementedError(  # why: the kind's record, models/
            f"{block_serving(cfg).label} prefills cold only: set "
            "generate.prefix_cache false")
    if is_latent(cfg):
        return _latent_prefill_forward(
            params, cfg, pools, ids, seg_ids, positions, dest_rows,
            last_rows, rope_len, kernels.grouped,
        )
    if is_hybrid(cfg):
        return _hybrid_prefill_forward(
            params, cfg, pools, ids, seg_ids, positions, dest_rows,
            last_rows, rope_len, kernels,
        )
    n_rows = pools["k0"].shape[0] // kv_entries(cfg)

    def attend(i, q, k, v, step=None):
        with scope("cache_write"):
            dest, _ = _step_view(cfg, step, n_rows, dest_rows)
            kp = pools[f"k{i}"]
            pools[f"k{i}"] = kp.at[dest].set(
                k[0].astype(kp.dtype), mode="drop"
            )
            vp = pools[f"v{i}"]
            pools[f"v{i}"] = vp.at[dest].set(
                v[0].astype(vp.dtype), mode="drop"
            )
        # attention over the packed batch itself (cold: every KV row a
        # prompt token needs is in-flight in this very dispatch), plus —
        # warm — the cached prefix rows of the post-scatter pool (the
        # scatter only touches suffix rows, so prefix reads are stable)
        kwargs = {}
        if warm:
            kwargs = dict(
                k_pool=pools[f"k{i}"], v_pool=pools[f"v{i}"],
                block_tables=block_tables, prefix_lens=prefix_lens,
                n_prefix_rows=n_prefix_rows, block_size=block_size,
            )
        with scope("attend"):
            return ragged_prefill_attention(
                q[0], k[0], v[0], seg_ids, positions,
                sliding_window=cfg.sliding_window, max_segment=rope_len,
                use_flash=kernels.ragged, **kwargs,
            )[None]

    x = decoder_layer_stack(
        params, cfg, ids[None, :], positions[None, :], rope_len, attend,
        cache=pools,
    )
    with scope("head"):
        x_last = x[0][last_rows]  # [B, hidden]
        logits = decoder_head(params, cfg, x_last[:, None, :])
        return logits[:, 0], pools


def paged_decode_forward(
    params: Params,
    cfg: DecoderConfig,
    pools: PagedPools,
    block_tables,  # [S, NB] int32; entries >= n_blocks are holes
    tok,  # [S, s] next token(s) per lane (s=1 plain, K spec verify)
    lengths,  # [S] tokens already in each lane's KV
    *,
    block_size: int,
    rope_len: int,
    use_flash: bool = False,
    mesh=None,  # MeshContext: the paged kernel shards over it (ops/attention)
    kernels: Optional[KernelForms] = None,
):
    """Advance every lane ``s`` tokens against the block pool: write each
    new token's K/V at its table-mapped row (in place: a scatter of
    ``S x s`` rows into the donated pool), attend through the table —
    under ``kernels.paged`` by a kernel that reads only each lane's live
    pages (``kernels``: the engine's choice, or derived from ``use_flash``
    / ``mesh`` as :func:`ragged_prefill_forward` says).

    Writes whose position falls past a lane's allocated blocks (hole
    entries / retired lanes whose table row went sentinel) are DROPPED —
    the in-program capacity guard in the batcher's chunk programs stops
    live lanes before that can happen, so a dropped write only ever
    belongs to an inactive lane re-writing its scratch row.

    Returns (logits [S, s, vocab] f32, pools) — and, from the latent
    block, its routing record (:func:`_latent_decode_forward`): under
    ``kernels.paged`` its attention reads live pages through a kernel of
    its own (else the XLA gather, which GSPMD places on a mesh), and
    ``kernels.grouped`` is the form of its routed layers' product."""
    kernels = _forms(cfg, kernels, use_flash, mesh, block_size)
    if is_latent(cfg):
        return _latent_decode_forward(
            params, cfg, pools, block_tables, tok, lengths, block_size,
            rope_len, kernels,
        )
    if is_hybrid(cfg):
        return _hybrid_decode_forward(
            params, cfg, pools, block_tables, tok, lengths, block_size,
            rope_len, kernels, mesh,
        )
    S, s = tok.shape
    nb = block_tables.shape[1]
    P = pools["k0"].shape[0] // kv_entries(cfg)  # rows of ONE step's range
    n_blocks = P // block_size

    with scope("cache_write"):
        pos = lengths[:, None] + jnp.arange(s)[None, :]  # [S, s]
        blk_idx = pos // block_size
        blk = jnp.take_along_axis(
            block_tables, jnp.minimum(blk_idx, nb - 1), axis=1
        )
        dest = jnp.where(
            (blk_idx < nb) & (blk < n_blocks),
            blk * block_size + pos % block_size,
            P,  # out of bounds -> dropped write
        )
    rope_pos = jnp.minimum(pos, rope_len - 1)
    attn_lengths = lengths + s

    def attend(i, q, k, v, step=None):
        with scope("cache_write"):
            rows, tables = _step_view(
                cfg, step, P, dest, block_tables, block_size)
            kp = pools[f"k{i}"]
            pools[f"k{i}"] = kp.at[rows].set(
                k.astype(kp.dtype), mode="drop")
            vp = pools[f"v{i}"]
            pools[f"v{i}"] = vp.at[rows].set(
                v.astype(vp.dtype), mode="drop")
        with scope("attend"):
            return paged_decode_attention(
                q, pools[f"k{i}"], pools[f"v{i}"], tables,
                attn_lengths, block_size=block_size, q_offset=lengths,
                sliding_window=cfg.sliding_window, use_flash=kernels.paged,
                mesh=mesh,
            )

    x = decoder_layer_stack(
        params, cfg, tok, rope_pos, rope_len, attend, cache=pools)
    logits = decoder_head(params, cfg, x)
    return logits, pools


# ---- the latent block (models/latent.py) over the same pool and tables ----


def _with_record(logits, pools, record):
    """A block that routes hands back its expert ids as a third value; one
    that does not hands back two, as the GQA block does."""
    if record is None:
        return logits, pools
    return logits, pools, record


def _latent_prefill_forward(params, cfg, pools, ids, seg_ids, positions,
                            dest_rows, last_rows, rope_len, grouped):
    """The packed prefill of the latent block: each token's ONE cache row
    is scattered to its table-mapped pool row, and attention runs over
    the rows in flight in the non-absorbed form — keys and values
    up-projected per head, the packed ragged attention the GQA block
    uses (wider keys than values).

    Returns (last_logits [B, vocab] f32, pools, routing record int32
    [routed_layers, T, experts_per_token] of the packed rows, padding
    included)."""
    scale = softmax_scale(cfg)

    def attend(i, q_nope, q_rope, row):
        with scope("cache_write"):
            pool = pools[f"c{i}"]
            pools[f"c{i}"] = pool.at[dest_rows].set(
                row[0][:, None, :].astype(pool.dtype), mode="drop"
            )
        k, v = up_projected(params, cfg, i, row[0])
        with scope("attend"):
            q = jnp.concatenate([q_nope[0], q_rope[0]], axis=-1)
            return ragged_prefill_attention(
                q, k, v, seg_ids, positions, scale=scale
            )[None]

    x, record = latent_layer_stack(
        params, cfg, ids[None, :], positions[None, :], rope_len, attend,
        use_flash=grouped,
    )
    with scope("head"):
        logits = decoder_head(params, cfg, x[0][last_rows][:, None, :])
    return _with_record(
        logits[:, 0], pools, None if record is None else record[:, 0]
    )


def _latent_decode_forward(params, cfg, pools, block_tables, tok, lengths,
                           block_size, rope_len, kernels):
    """A decode step of the latent block in the ABSORBED form: the new
    rows are written at their table-mapped pool rows, each head's query is
    carried into latent space, scores and the weighted sum are taken
    against the pool rows as stored (one read serves key and value; live
    pages in place under ``kernels.paged``) and go back through the value
    half of the up-projection.  No per-head key or value ever exists.

    Returns (logits [S, s, vocab] f32, pools, routing record int32
    [routed_layers, S, s, experts_per_token])."""
    S, s = tok.shape
    nb = block_tables.shape[1]
    P = pools["c0"].shape[0]
    n_blocks = P // block_size
    with scope("cache_write"):
        pos = lengths[:, None] + jnp.arange(s)[None, :]
        blk_idx = pos // block_size
        blk = jnp.take_along_axis(
            block_tables, jnp.minimum(blk_idx, nb - 1), axis=1
        )
        dest = jnp.where(
            (blk_idx < nb) & (blk < n_blocks),
            blk * block_size + pos % block_size,
            P,  # out of bounds -> dropped write
        )
    rope_pos = jnp.minimum(pos, rope_len - 1)
    scale = softmax_scale(cfg)

    def attend(i, q_nope, q_rope, row):
        with scope("cache_write"):
            pool = pools[f"c{i}"]
            pools[f"c{i}"] = pool.at[dest].set(
                row[:, :, None, :].astype(pool.dtype), mode="drop"
            )
        q_lat = absorb_query(params, cfg, i, q_nope)
        with scope("attend"):
            o_lat = latent_decode_attention(
                q_lat, q_rope, pools[f"c{i}"], block_tables, lengths + s,
                block_size=block_size, q_offset=lengths, scale=scale,
                use_flash=kernels.paged)
        return expand_output(params, cfg, i, o_lat)

    x, record = latent_layer_stack(
        params, cfg, tok, rope_pos, rope_len, attend,
        use_flash=kernels.grouped)
    return _with_record(decoder_head(params, cfg, x), pools, record)


# ---- the stack of mixer kinds (models/hybrid.py): rows AND a state a lane ---


def _init_hybrid_pools(cfg, n_blocks, block_size, dtype, sharding, n_lanes):
    """The pools of the stack of mixer kinds, by what each layer's kind
    keeps (``models/hybrid.MIXERS``):

    * ``k{i}`` / ``v{i}`` [n_lanes * ring_pages * block_size, kv heads, d]
      of each WINDOW layer: a second extent, in which a lane owns a RING of
      ``models/hybrid.ring_pages`` pages — position ``p`` lives in page
      ``(p // block_size) % ring_pages`` of it — named by its row of
      ``window_pages`` [n_lanes, ring_pages] int32 (the lane's entry is
      found through ``state_slot``, as its state is).  It starts as lane
      after lane (entry ``l`` owns pages ``l * ring_pages ...``); an
      allocator's owner writes the pages it took at admission
      (``engines/serve.py``).  Pages past the window alias newer ones and
      are never read: the window's mask, and the kernel's first block;
    * ``k{i}`` / ``v{i}`` [n_blocks * block_size, kv heads, d] of each
      other row-keeping layer (sparse, attention), and of a SPARSE layer
      ``ck{i}`` [rows / sparse_kernel_stride, kv heads, d]: the
      mean-pooled key of the window that STARTS at that stride of that
      page (written when the window completes, by the prefill and by the
      decode step that completes it);
    * a lane's state, one entry a lane (``lane_state_shapes`` /
      ``lane_state_dtypes``): ``s{i}`` [n_lanes, heads, d, d] float32 of
      each LINEAR layer; ``s{i}`` [n_lanes, d + 1, kv heads, d (d + 1) /
      2] float32 of each RETENTION layer; ``h{i}`` [n_lanes, state, inner]
      float32 and ``u{i}`` [n_lanes, taps - 1, inner] (the activation
      type) of each state-space layer.  ``n_lanes`` defaults to the lanes
      of ``cfg.max_seq_len`` positions the pool holds.  A stack in which
      no layer keeps a row holds these and the slot map ONLY;
    * ``state_slot`` [n_blocks * block_size] int32: the state entry of
      the lane whose FIRST token lives at that pool row (only rows that
      start a block are ever looked up).  Both forwards find a lane's
      state through it — the prefill from a segment's first destination
      row, the decode step from the first entry of the lane's table — so
      whatever addresses a lane travels in ``pools``.  It starts as
      ``block // blocks of a lane``: right for tables laid out lane after
      lane (the benchmark's comparison); an allocator's owner writes
      ``state_slot[first block * block_size] = lane`` at admission
      (``engines/serve.py``).
    """
    st = cfg.sparse_kernel_stride
    selecting = sparse_layers(cfg)
    if selecting and block_size % st:
        raise ValueError(
            f"kv_block_size {block_size} is no multiple of "
            f"sparse_kernel_stride {st}")
    rows = n_blocks * block_size
    per_lane = -(-cfg.max_seq_len // block_size)
    n_lanes = n_lanes or max(1, n_blocks // per_lane)
    pools: PagedPools = {}
    ring = ring_pages(cfg, block_size) if window_layers(cfg) else 0
    for i in range(cfg.num_layers):
        held = (n_lanes * ring * block_size
                if cfg.mixer_types[i] == WINDOW else rows)
        for prefix, (heads, width) in kv_row_shapes(cfg, i).items():
            pools[f"{prefix}{i}"] = jnp.zeros(
                (held, heads, width), dtype, device=sharding)
    if ring:
        pages = jnp.arange(n_lanes * ring, dtype=jnp.int32).reshape(
            n_lanes, ring)
        pools[WINDOW_PAGES] = (
            pages if sharding is None
            else jnp.asarray(pages, device=sharding))
    for i in selecting:
        pools[f"ck{i}"] = jnp.zeros(
            (rows // st, cfg.num_kv_heads, cfg.head_dim), dtype,
            device=sharding)
    for name, (shape, kind) in lane_state_entries(cfg).items():
        pools[name] = jnp.zeros(
            (n_lanes, *shape), jnp.dtype(kind), device=sharding)
    slot = jnp.minimum(
        jnp.arange(rows, dtype=jnp.int32) // (per_lane * block_size),
        n_lanes - 1)
    pools[STATE_SLOT] = (
        slot if sharding is None else jnp.asarray(slot, device=sharding))
    return pools


def _sparse_sizes(cfg) -> dict:
    return dict(
        kernel_size=cfg.sparse_kernel_size, stride=cfg.sparse_kernel_stride,
        block=cfg.sparse_block_size, topk=cfg.sparse_topk,
        init_blocks=cfg.sparse_init_blocks, window=cfg.sparse_window_size,
        dense_len=cfg.sparse_dense_len,
    )


def _n_state_entries(pools, cfg) -> int:
    """Entries a lane-keyed pool of the stack holds (one a lane: a state,
    or a ring's row of ``window_pages``)."""
    return pools[next(iter(lane_state_shapes(cfg)), WINDOW_PAGES)].shape[0]


def _state_slots(pools, cfg, first_rows, ok=True):
    """The state entry of the lanes whose first token lives at the pool
    rows ``first_rows``; out of bounds (a zero read, a dropped write)
    where ``ok`` is false or the row is past the pool (a hole)."""
    slot_of = pools[STATE_SLOT]
    n_slots = _n_state_entries(pools, cfg)
    slot = slot_of[jnp.minimum(first_rows, slot_of.shape[0] - 1)]
    return jnp.where(ok & (first_rows < slot_of.shape[0]), slot, n_slots)


def _ring_rows(pools, cfg, slots, pos, ok=True):
    """Flat rows in a WINDOW layer's pools of the positions ``pos`` of the
    lanes whose entries are ``slots`` (same shape) — out of bounds, a
    dropped write, where ``ok`` is false or the entry or its page is a
    hole.  The block size is read off the pools: the one extent a prefill
    is not told."""
    pages = pools[WINDOW_PAGES]
    n_pages = pages.size
    held = pools[f"k{window_layers(cfg)[0]}"].shape[0]
    block_size = held // n_pages
    page = pages.at[slots, (pos // block_size) % pages.shape[1]].get(
        mode="fill", fill_value=n_pages)
    return jnp.where(
        ok & (page < n_pages), page * block_size + pos % block_size, held)


def _write_rows(pools, i, dest, k, v):
    """K and V of one row-keeping layer into their pools at ``dest``."""
    for name, new in (("k", k), ("v", v)):
        pool = pools[f"{name}{i}"]
        pools[f"{name}{i}"] = pool.at[dest].set(
            new.astype(pool.dtype), mode="drop")


def _hybrid_prefill_forward(params, cfg, pools, ids, seg_ids, positions,
                            dest_rows, last_rows, rope_len, kernels):
    """One packed COLD prefill dispatch of the stack of mixer kinds, one
    handler a kind.  A row-keeping layer scatters K and V rows — a SPARSE
    one also the compressed keys of the windows that lie whole inside a
    segment (none straddles two), and every row selects for itself; an
    ATTENTION one attends over the rows in flight.  A state-keeping layer
    starts from ZERO at each segment's first row and leaves what the
    segment ends with in the lane's entry (found through ``state_slot``
    from the segment's first destination row): a LINEAR layer its chunked
    scan's state, a RETENTION layer the same of its own scan (chunks of
    ``RAGGED_ALIGN`` rows: the attention form inside one, the expanded
    state across them), a state-space layer its scan's state (the Pallas
    kernel under ``kernels.scan``, ``ops/ssm.py``) and its last conv
    inputs.

    A WINDOW layer scatters into the lane's ring only the rows a later
    step can still see and attends over the rows in flight inside the
    window (the key blocks wholly outside it skipped).

    Returns (last_logits [B, vocab] f32, pools, selection record int32
    [sparse layers x kv heads, T, sparse_topk] of the packed rows — the
    routing record int32 [routed layers, T, experts_per_token] of a stack
    that routes) — two values where no layer selects or routes."""
    sizes = _sparse_sizes(cfg)
    st = sizes["stride"]
    t = ids.shape[0]
    kinds = set(cfg.mixer_types)
    # a segment: its rows are one contiguous run from position 0
    seg_ok = seg_ids[last_rows] == jnp.arange(last_rows.shape[0])
    seg_lens = None
    if SPARSE in kinds:
        seg_len = jnp.where(seg_ok, positions[last_rows] + 1, 0)
        seg_lens = jnp.where(
            seg_ids >= 0, seg_len[jnp.maximum(seg_ids, 0)], 0)
    slots = chunk_slot = ring_dest = None
    # several kv heads: a group's query heads share ONE copy of a head's
    # K and V (a single kv head's repeat is a broadcast as it stands); no
    # segment is longer than the sequence capacity, ``rope_len``
    grouped_heads = cfg.num_kv_heads > 1
    if kinds & {LINEAR, MAMBA, WINDOW, RETENTION}:
        with scope("state"):
            first_rows = last_rows - positions[last_rows]
            slots = _state_slots(pools, cfg, dest_rows[first_rows], seg_ok)
            if kinds & {LINEAR, RETENTION}:
                # the chunk that holds a segment's last row takes its state
                chunk_seg = seg_ids[:: RAGGED_ALIGN]
                at = jnp.maximum(chunk_seg, 0)
                is_last = (chunk_seg >= 0) & (
                    last_rows[at] // RAGGED_ALIGN
                    == jnp.arange(t // RAGGED_ALIGN))
                chunk_slot = jnp.where(
                    is_last, slots[at], jnp.iinfo(jnp.int32).max)
    if WINDOW in kinds:
        with scope("cache_write"):
            # a window layer keeps the rows a LATER step can still see —
            # the first one, at the segment's length, sees the positions
            # above ``length - window`` — at their places in the lane's ring
            lane = jnp.maximum(seg_ids, 0)
            kept = (seg_ids >= 0) & (
                positions > (positions[last_rows] + 1)[lane]
                - cfg.sliding_window)
            ring_dest = _ring_rows(pools, cfg, slots[lane], positions, kept)

    def linear(i, q, k, v):
        with scope("state"):
            out, pools[f"s{i}"] = linear_attention_prefill(
                q[0], k[0], v[0], seg_ids, positions,
                decay_slopes(cfg, i), pools[f"s{i}"], chunk_slot,
            )
            return out[None], None

    def retention(i, q, k, v, log_gate):
        with scope("state"):
            out, pools[f"s{i}"] = power_retention_prefill(
                q[0], k[0], v[0], log_gate[0], seg_ids, positions,
                pools[f"s{i}"], chunk_slot,
            )
            return out[None], None

    def sparse(i, q, k, v):
        with scope("cache_write"):
            _write_rows(pools, i, dest_rows, k[0], v[0])
            ck, ck_ok, ck_seg, ck_end = compressed_keys(
                k[0], seg_ids, positions, sizes["kernel_size"], st)
            cpool = pools[f"ck{i}"]
            pools[f"ck{i}"] = cpool.at[
                jnp.where(ck_ok, dest_rows[::st] // st, cpool.shape[0])
            ].set(ck.astype(cpool.dtype), mode="drop")
        with scope("attend"):  # the selection inside opens ``select``
            out, taken = sparse_prefill_attention(
                q[0], k[0], v[0], seg_ids, positions, seg_lens, ck, ck_ok,
                ck_seg, ck_end, **sizes,
            )
            return out[None], taken[:, None]

    def rows_then_attend(i, dest, q, k, v, window):
        with scope("cache_write"):
            _write_rows(pools, i, dest, k[0], v[0])
        with scope("attend"):
            out = ragged_prefill_attention(
                q[0], k[0], v[0], seg_ids, positions, sliding_window=window,
                grouped_heads=grouped_heads, max_segment=rope_len,
                use_flash=kernels.ragged)
        if grouped_heads:
            # nothing later in the program reads the pools, so the
            # scheduler would leave every layer's scatter to the end and
            # hold K and V of them all until then (78 MB a layer at
            # 37,888 rows x 4 kv heads): the rows go in with their layer
            pools[f"k{i}"], pools[f"v{i}"], out = (
                jax.lax.optimization_barrier(
                    (pools[f"k{i}"], pools[f"v{i}"], out)))
        return out[None], None

    def attention(i, q, k, v):
        return rows_then_attend(i, dest_rows, q, k, v, None)

    def window(i, q, k, v):
        return rows_then_attend(i, ring_dest, q, k, v, cfg.sliding_window)

    def mamba(i, u, project):
        conv_w, conv_b, a, d_skip = ssm_constants(params, cfg, i)
        with scope("state"):
            c = causal_conv_prefill(u[0], conv_w, conv_b, positions)
            window = pools[f"u{i}"]
            pools[f"u{i}"] = window.at[slots].set(
                conv_window_of(
                    u[0], positions, last_rows, window.shape[1]
                ).astype(window.dtype), mode="drop")
        delta, b_in, c_out = project(c[None])
        with scope("state"):
            g, h = selective_scan_prefill(
                c, delta[0], a, b_in[0], c_out[0], d_skip, seg_ids,
                positions, last_rows, use_flash=kernels.scan)
            pools[f"h{i}"] = pools[f"h{i}"].at[slots].set(h, mode="drop")
            return g[None], None

    handlers = {LINEAR: linear, SPARSE: sparse, ATTENTION: attention,
                WINDOW: window, MAMBA: mamba, RETENTION: retention}
    x, record = hybrid_layer_stack(
        params, cfg, ids[None, :], positions[None, :], rope_len,
        lambda i, kind, *args: handlers[kind](i, *args),
        grouped=kernels.grouped,
    )
    with scope("head"):
        x_last = x[0][last_rows][:, None, :]
    logits = hybrid_head(params, cfg, x_last)
    return _with_record(
        logits[:, 0], pools, None if record is None else record[:, 0]
    )


def _hybrid_decode_forward(params, cfg, pools, block_tables, tok, lengths,
                           block_size, rope_len, kernels, mesh):
    """A decode step of the stack of mixer kinds, one token a lane, one
    handler a kind.  A row-keeping layer writes the token's K and V at its
    table-mapped row; a SPARSE one also writes the compressed key of the
    window the token COMPLETES (if it does), selects among the lane's
    compressed keys and reads the rows of the blocks taken (as pages,
    through the paged kernel, under ``kernels.sparse_paged``); an
    ATTENTION one reads the lane's live pages
    (``paged_decode_attention``: the paged kernel under
    ``kernels.paged``); a WINDOW one writes the token at its place in the
    lane's ring and reads, through the ring as a table, the pages the
    window still sees.  A state-keeping layer advances the
    lane's entries IN PLACE (read, one step, written back): a LINEAR layer
    its state, a state-space layer its conv window (shifted by the token)
    and its state.  A RETENTION layer's step runs over its pool's ENTRIES
    where they lie — each handed the token of the lane that owns it (the
    inverse of the slot map's answer), an entry no live lane owns keeps
    what it holds — so the 34 MB a lane and layer are neither gathered
    out of the pool nor scattered back: under ``kernels.retention`` one
    pass of a kernel over the entries a live lane owns
    (``ops/retention.retention_decode_step``), else the XLA form over
    every entry (an unowned one under a gate of 1, nothing added).  A
    lane whose table starts with a hole (a retired slot) reads zeros and
    writes nothing.

    Returns (logits [S, 1, vocab] f32, pools, selection record int32
    [sparse layers x kv heads, S, 1, sparse_topk] — the routing record
    int32 [routed layers, S, 1, experts_per_token] of a stack that routes)
    — two values where no layer selects or routes."""
    S, s = tok.shape
    if s != 1:
        raise NotImplementedError(
            "a stack with a state-keeping mixer decodes one token a lane a "
            "step (set generate.speculative_k 0): a verify step of several "
            "would need the state after each of them")
    sizes = _sparse_sizes(cfg)
    ks, st = sizes["kernel_size"], sizes["stride"]
    nb = block_tables.shape[1]
    P = pools[STATE_SLOT].shape[0]
    n_blocks = P // block_size
    kinds = set(cfg.mixer_types)

    def pool_rows(pos):
        """Flat pool rows of the positions ``pos`` [S, n] of each lane;
        ``P`` (out of bounds) past the table or on a hole."""
        idx = pos // block_size
        blk = jnp.take_along_axis(
            block_tables, jnp.clip(idx, 0, nb - 1), axis=1)
        ok = (pos >= 0) & (idx < nb) & (blk < n_blocks)
        return jnp.where(ok, blk * block_size + pos % block_size, P)

    with scope("cache_write"):
        dest = pool_rows(lengths[:, None])[:, 0]
        if SPARSE in kinds:
            # the window this token completes, if any: its first token,
            # its rows
            w_first = lengths - (ks - 1)
            w_done = (w_first >= 0) & (w_first % st == 0)
            w_rows = pool_rows(w_first[:, None] + jnp.arange(ks)[None, :])
            w_dest = jnp.where(
                w_done & (w_rows[:, 0] < P), w_rows[:, 0] // st, P // st)
    slots = None
    if kinds & {LINEAR, MAMBA, WINDOW, RETENTION}:
        with scope("state"):
            slots = _state_slots(
                pools, cfg, block_tables[:, 0] * block_size)
            if RETENTION in kinds:
                # the lane that owns each state entry; ``S``: none
                lane_of = jnp.full(
                    (_n_state_entries(pools, cfg),), S, jnp.int32,
                ).at[slots].set(jnp.arange(S, dtype=jnp.int32), mode="drop")
                owned = n_owned = None
                if kernels.retention:
                    # the entries a live lane owns, first: what the
                    # step's kernel walks
                    owned = jnp.argsort(lane_of >= S, stable=True).astype(
                        jnp.int32)
                    n_owned = jnp.sum(lane_of < S, dtype=jnp.int32)
    if WINDOW in kinds:
        with scope("cache_write"):
            ring_dest = _ring_rows(pools, cfg, slots, lengths)
            # the lane's ring as a table of ``nb`` pages: page ``j`` of the
            # lane lives in ring page ``j % ring``; what that names for a
            # ``j`` the window has left is a newer page, which the window's
            # mask (and the kernel's first block) never reads
            pages = pools[WINDOW_PAGES]
            ring_tables = pages.at[slots].get(
                mode="fill", fill_value=pages.size
            )[:, jnp.arange(nb) % pages.shape[1]]
    rope_pos = jnp.minimum(lengths, rope_len - 1)[:, None]

    def linear(i, q, k, v):
        with scope("state"):
            pool = pools[f"s{i}"]
            state = pool.at[slots].get(mode="fill", fill_value=0.0)
            out, state = linear_attention_step(
                q[:, 0], k[:, 0], v[:, 0], state, decay_slopes(cfg, i))
            pools[f"s{i}"] = pool.at[slots].set(state, mode="drop")
            return out[:, None], None

    def retention(i, q, k, v, log_gate):
        def of_entries(x):  # zeros (and a gate of 1) for an unowned entry
            return x[:, 0].at[lane_of].get(mode="fill", fill_value=0)

        with scope("state"):
            out, pools[f"s{i}"] = retention_decode_step(
                of_entries(q), of_entries(k), of_entries(v),
                of_entries(log_gate), pools[f"s{i}"], owned, n_owned,
                use_flash=kernels.retention)
            return out.at[slots].get(
                mode="fill", fill_value=0)[:, None], None

    def sparse(i, q, k, v):
        with scope("cache_write"):
            _write_rows(pools, i, dest, k[:, 0], v[:, 0])
            kp, cpool = pools[f"k{i}"], pools[f"ck{i}"]
            mean = kp[jnp.minimum(w_rows, P - 1)].astype(
                jnp.float32).mean(1)
            pools[f"ck{i}"] = cpool.at[w_dest].set(
                mean.astype(cpool.dtype), mode="drop")
        with scope("attend"):  # the selection inside opens ``select``
            out, taken = sparse_decode_attention(
                q[:, 0], pools[f"k{i}"], pools[f"v{i}"], pools[f"ck{i}"],
                block_tables, lengths + 1, block_size=block_size, **sizes,
                use_flash=kernels.sparse_paged,
            )
            return out[:, None], taken[:, :, None]

    def attention(i, q, k, v):
        with scope("cache_write"):
            _write_rows(pools, i, dest, k[:, 0], v[:, 0])
        with scope("attend"):
            return paged_decode_attention(
                q, pools[f"k{i}"], pools[f"v{i}"], block_tables,
                lengths + 1, block_size=block_size, q_offset=lengths,
                use_flash=kernels.paged, mesh=mesh,
            ), None

    def window(i, q, k, v):
        with scope("cache_write"):
            _write_rows(pools, i, ring_dest, k[:, 0], v[:, 0])
        with scope("attend"):
            return paged_decode_attention(
                q, pools[f"k{i}"], pools[f"v{i}"], ring_tables,
                lengths + 1, block_size=block_size, q_offset=lengths,
                sliding_window=cfg.sliding_window, use_flash=kernels.paged,
                mesh=mesh,
            ), None

    def mamba(i, u, project):
        conv_w, conv_b, a, d_skip = ssm_constants(params, cfg, i)
        with scope("state"):
            wpool = pools[f"u{i}"]
            window = wpool.at[slots].get(mode="fill", fill_value=0)
            c, window = causal_conv_step(u[:, 0], window, conv_w, conv_b)
            pools[f"u{i}"] = wpool.at[slots].set(window, mode="drop")
        delta, b_in, c_out = project(c[:, None])
        with scope("state"):
            hpool = pools[f"h{i}"]
            h = hpool.at[slots].get(mode="fill", fill_value=0.0)
            g, h = selective_scan_step(
                c, delta[:, 0], a, b_in[:, 0], c_out[:, 0], d_skip, h)
            pools[f"h{i}"] = hpool.at[slots].set(h, mode="drop")
            return g[:, None], None

    handlers = {LINEAR: linear, SPARSE: sparse, ATTENTION: attention,
                WINDOW: window, MAMBA: mamba, RETENTION: retention}
    x, record = hybrid_layer_stack(
        params, cfg, tok, rope_pos, rope_len,
        lambda i, kind, *args: handlers[kind](i, *args),
        grouped=kernels.grouped)
    return _with_record(hybrid_head(params, cfg, x), pools, record)
