"""HBM-resident sharded vector store.

Replaces FAISS ``IndexFlatL2`` + pickle metadata + the shared-filesystem
handoff (``semantic-indexer/indexer.py:17-48,26-30``; ``llm-qa/main.py:35-58``).
Reference defects fixed by design (SURVEY §5 "race detection"):

* the indexer rewrote the whole index to disk after **every** message while
  the QA service read the same files unlocked → here both planes share one
  in-process store; snapshots are atomic (write-temp + rename) and versioned;
* the QA service loaded the index **once at startup** → here every search
  sees the current device buffer (device-side append, no restart);
* metadata recorded only a source string (``indexer.py:123``) so
  patient-level retrieval was unimplementable (SURVEY appendix) → here
  metadata carries first-class ``patient_id`` / ``doc_type`` / ``date``.

Device layout: one [capacity, dim] bf16 buffer, rows sharded over the
``model`` mesh axis.  Search = one MXU matmul + per-shard ``lax.top_k`` +
tiny all-gather merge (``ops/topk.py``) under ``shard_map``.  Appends write
into preallocated capacity via donated ``dynamic_update_slice`` — no
reallocation, no recompilation until capacity doubles (shape bucketing,
SURVEY §7 hard part (a)).

Scores are dot products over L2-normalized embeddings == cosine; identical
ranking to the reference's L2-over-MiniLM (SURVEY appendix).
"""

from __future__ import annotations

import functools
import json
import os
import tempfile
import threading
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from docqa_tpu.config import StoreConfig
from docqa_tpu.engines.spine import spine_run
from docqa_tpu.ops.topk import sharded_topk
from docqa_tpu.runtime import native
from docqa_tpu.runtime.mesh import MeshContext
from docqa_tpu.runtime.metrics import DEFAULT_REGISTRY, get_logger, span
from docqa_tpu.utils import round_up

log = get_logger("docqa.store")

NEG_INF = -1e30


@dataclass
class SearchResult:
    score: float
    row_id: int
    metadata: Dict[str, Any]


def _search_kernel(
    vectors, queries, count, filter_mask, k: int, axis: str
):
    """Runs inside shard_map.  vectors [n_local, d], queries [q, d] replicated,
    count/filter replicated; returns replicated (vals [q,k], global ids).

    ``filter_mask`` may be ``None``: unfiltered searches skip it entirely —
    the [capacity] bool would otherwise be uploaded host→device on EVERY
    query (a ~1 MB transfer per search at the 1M-row target)."""
    n_local = vectors.shape[0]
    shard = jax.lax.axis_index(axis)
    offset = shard * n_local
    scores = jax.lax.dot_general(
        queries,
        vectors,
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [q, n_local]
    rows = offset + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    live = rows < count
    if filter_mask is not None:
        mask_local = jax.lax.dynamic_slice_in_dim(
            filter_mask, offset, n_local, 0
        )
        live = live & mask_local[None, :]
    scores = jnp.where(live, scores, NEG_INF)
    return sharded_topk(scores, offset, k, axis)


def _search_single(vectors, queries, count, filter_mask, k: int):
    scores = jax.lax.dot_general(
        queries, vectors, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    rows = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    live = rows < count
    if filter_mask is not None:
        live = live & filter_mask[None, :]
    scores = jnp.where(live, scores, NEG_INF)
    return jax.lax.top_k(scores, k)


def _append_kernel(buf, rows, offset):
    return jax.lax.dynamic_update_slice_in_dim(buf, rows, offset, 0)


_NO_DATE = np.int32(-1)


def _date_code(value: Optional[str]) -> int:
    """ISO ``YYYY-MM-DD`` (or any prefix-ISO string) → sortable int code;
    anything unparseable → -1 (treated as 'no date')."""
    if not value:
        return int(_NO_DATE)
    digits = "".join(c for c in str(value)[:10] if c.isdigit())
    if len(digits) < 8:
        return int(_NO_DATE)
    return int(digits[:8])


class VectorStore:
    """Append + exact-search over device-sharded vectors with host metadata.

    Metadata filters are **columnar**: ``patient_id`` / ``doc_type`` are
    interned to int codes and ``doc_date`` to a sortable int, each kept in a
    capacity-doubling numpy column.  Filtered search builds its device mask
    with vectorized compares — O(1) numpy ops, not an O(corpus) Python
    predicate loop (the round-1 flaw: ~1M Python calls per patient-snippet
    search at the 1M-chunk target)."""

    _FILTER_KEYS = ("patient_id", "doc_type", "date_from", "date_to")

    def __init__(
        self,
        cfg: StoreConfig,
        mesh: Optional[MeshContext] = None,
    ):
        self.cfg = cfg
        self.mesh = mesh
        self._lock = threading.RLock()
        self._meta: List[Dict[str, Any]] = []
        self._host = np.zeros((0, cfg.dim), np.float32)  # durable master copy
        self._count = 0
        self._version = 0
        self._n_shards = mesh.n_model if mesh is not None else 1
        self._capacity = self._round_capacity(cfg.shard_capacity)
        self._dtype = jnp.dtype(cfg.dtype)
        self._dev = self._alloc(self._capacity)
        self._search_fns: Dict[Tuple[int, int, int], Callable] = {}
        self._append_jit = jax.jit(_append_kernel, donate_argnums=(0,))
        # columnar metadata (code -1 == absent; intern code space per column)
        self._codes: Dict[str, Dict[str, int]] = {
            "patient_id": {}, "doc_type": {}, "doc_id": {},
        }
        self._cols: Dict[str, np.ndarray] = {
            "patient_id": np.zeros((0,), np.int32),
            "doc_type": np.zeros((0,), np.int32),
            "doc_id": np.zeros((0,), np.int32),
            "doc_date": np.zeros((0,), np.int32),
        }
        # tombstones: deleted rows stay in HBM (append-only buffer) but are
        # masked out of every search; ``compact_deleted`` erases for real
        self._deleted = np.zeros((0,), bool)
        self._n_deleted = 0
        # compaction generation: the ONLY operation that renumbers rows.
        # Derived indexes that cached row ids (the tiered tier's exact
        # re-rank) compare this against the value they captured at build
        # time — a mismatch means their ids no longer address these rows
        # (see TieredIndex._rerank_active).
        self._n_compactions = 0
        # index sinks (docqa-lexroute): secondary index consumers that
        # must stay row-aligned with THIS store — the lexical tier
        # registers here.  Sinks are notified inside the same locked
        # mutation that commits the dense change, on every path that
        # reaches add/delete/compact — including journal replay and
        # snapshot restore, which re-drive add() — so a crash-replayed
        # ingest converges every tier, not just the dense one.
        self._index_sinks: List[Any] = []

    def _intern(self, column: str, value: Optional[str]) -> int:
        if value is None:
            return -1
        table = self._codes[column]
        code = table.get(value)
        if code is None:
            code = len(table)
            table[value] = code
        return code

    def _append_columns(self, metadata: Sequence[Dict[str, Any]]) -> None:
        n = len(metadata)
        start = self._count
        for name, col in self._cols.items():
            if col.shape[0] < start + n:
                grown = np.full(
                    (max(start + n, 2 * max(1, col.shape[0])),), -1, np.int32
                )
                grown[: col.shape[0]] = col
                self._cols[name] = grown
        if self._deleted.shape[0] < start + n:
            grown_d = np.zeros(
                (max(start + n, 2 * max(1, self._deleted.shape[0])),), bool
            )
            grown_d[: self._deleted.shape[0]] = self._deleted
            self._deleted = grown_d
        for i, md in enumerate(metadata):
            self._cols["patient_id"][start + i] = self._intern(
                "patient_id", md.get("patient_id")
            )
            self._cols["doc_type"][start + i] = self._intern(
                "doc_type", md.get("doc_type")
            )
            self._cols["doc_id"][start + i] = self._intern(
                "doc_id", md.get("doc_id")
            )
            self._cols["doc_date"][start + i] = _date_code(md.get("doc_date"))
            if md.get("deleted"):  # restore path: tombstones persist
                self._deleted[start + i] = True
                self._n_deleted += 1

    # ---- capacity management -------------------------------------------------

    def _round_capacity(self, n: int) -> int:
        """Round up to a multiple of 128*n_shards (MXU sublane + even shards)."""
        quantum = 128 * self._n_shards
        return max(quantum, round_up(n, quantum))

    def _place_rows(self, arr: jax.Array) -> jax.Array:
        """Shard a [capacity, ...] array's rows over the model axis (no-op
        without a mesh)."""
        if self.mesh is None:
            return arr
        return jax.device_put(arr, self.mesh.row_sharded)

    def _alloc(self, capacity: int) -> jax.Array:
        return self._place_rows(jnp.zeros((capacity, self.cfg.dim), self._dtype))

    def _grow_to(self, needed: int) -> None:
        new_cap = self._capacity
        while new_cap < needed:
            new_cap *= 2
        if new_cap == self._capacity:
            return
        log.info("store grow %d -> %d rows", self._capacity, new_cap)
        self._capacity = new_cap
        buf = np.zeros((new_cap, self.cfg.dim), np.float32)
        buf[: self._count] = self._host[: self._count]
        self._dev = self._place_rows(jnp.asarray(buf, self._dtype))

    # ---- public API ----------------------------------------------------------

    @property
    def count(self) -> int:
        return self._count

    @property
    def deleted_count(self) -> int:
        """Tombstoned rows still occupying buffer slots (0 after
        ``compact_deleted``)."""
        return self._n_deleted

    @property
    def compactions(self) -> int:
        """How many times rows have been renumbered (``compact_deleted``
        erasures).  Captured at tier build and re-checked before any
        host-row re-rank: stale row ids must never index the compacted
        buffer."""
        with self._lock:
            return self._n_compactions

    @property
    def version(self) -> int:
        return self._version

    @property
    def n_devices(self) -> int:
        """Devices the vector buffer is resident on, read off the live
        array: 1 without a mesh, the model-axis size when row-sharded."""
        with self._lock:
            return len(self._dev.sharding.device_set)

    @property
    def dim(self) -> int:
        return self.cfg.dim

    def register_index_sink(self, sink: Any) -> None:
        """Register a secondary index consumer (protocol: ``on_add(row_ids,
        metadata)``, ``on_delete(row_ids)``, ``on_compact(keep_mask)``).
        One seam, every mutation path: the pipeline's journal-replayed
        ingest lands in :meth:`add`, so a registered sink needs no
        replay-awareness of its own.

        Registration is order-independent: rows already committed (e.g.
        a snapshot restore that ran before the sink existed) are
        back-filled through ``on_add`` immediately, tombstones included
        (the metadata row carries ``deleted`` — the sink decides)."""
        with self._lock:
            self._index_sinks.append(sink)
            if self._count:
                try:
                    sink.on_add(
                        list(range(self._count)), self._meta[: self._count]
                    )
                except Exception:
                    DEFAULT_REGISTRY.counter("index_sink_errors").inc()
                    log.exception("index sink %s backfill failed", sink)

    def _notify_sinks(self, method: str, *args) -> None:
        """Best-effort fan-out (called with the store lock held, after
        the dense mutation committed): a broken sink must not take dense
        ingest down with it, but it fails LOUDLY — the counter feeds the
        replay-convergence witness."""
        for sink in self._index_sinks:
            try:
                getattr(sink, method)(*args)
            except Exception:
                DEFAULT_REGISTRY.counter("index_sink_errors").inc()
                log.exception("index sink %s.%s failed", sink, method)

    def add(
        self,
        vectors: np.ndarray,
        metadata: Sequence[Dict[str, Any]],
    ) -> List[int]:
        """Append normalized vectors + metadata rows; returns global row ids.

        Visible to searches immediately (device-side append — the reference
        required a service restart, ``llm-qa/main.py:35``).
        """
        vectors = np.asarray(vectors, np.float32)
        if vectors.ndim != 2 or vectors.shape[1] != self.cfg.dim:
            raise ValueError(f"expected [n, {self.cfg.dim}] vectors, got {vectors.shape}")
        if len(vectors) != len(metadata):
            raise ValueError("vectors/metadata length mismatch")
        norms = np.linalg.norm(vectors, axis=1, keepdims=True)
        vectors = vectors / np.maximum(norms, 1e-9)

        with self._lock, span("store_add", DEFAULT_REGISTRY):
            start = self._count
            n = len(vectors)
            if self._host.shape[0] < start + n:
                grow = max(start + n, 2 * max(1, self._host.shape[0]))
                host = np.zeros((grow, self.cfg.dim), np.float32)
                host[:start] = self._host[:start]
                self._host = host
            self._host[start : start + n] = vectors
            # pad the appended block to a 64-row bucket so repeated adds of
            # varying sizes reuse a handful of compiled programs; the padding
            # lands beyond count (zeros over zeros) and capacity is grown to
            # keep the padded write in bounds
            n_pad = round_up(n, 64)

            def _append_on_lane():
                """Device phase (spine work item; submitter holds the
                store lock while blocked — the closure acquires
                nothing): capacity growth and the donated buffer
                append.  Returns the written device array so strict mode
                syncs the program this item issued before the lane
                frees."""
                self._grow_to(start + n_pad)
                rows = np.zeros((n_pad, self.cfg.dim), np.float32)
                rows[:n] = vectors
                self._dev = self._append_jit(
                    self._dev, jnp.asarray(rows, self._dtype), start
                )
                return self._dev

            spine_run("store_add", _append_on_lane)
            self._meta.extend(dict(m) for m in metadata)
            self._append_columns(metadata)
            self._count = start + n
            self._version += 1
            row_ids = list(range(start, start + n))
            self._notify_sinks("on_add", row_ids, metadata)
            return row_ids

    def _get_search_fn(self, q: int, k: int, masked: bool) -> Callable:
        key = (self._capacity, q, k, masked)
        fn = self._search_fns.get(key)
        if fn is not None:
            return fn
        if self.mesh is not None and self._n_shards > 1:
            kernel = functools.partial(
                _search_kernel, k=k, axis=self.mesh.model_axis
            )
            in_specs = [
                P(self.mesh.model_axis, None),  # vectors row-sharded
                P(),  # queries replicated
                P(),  # count
            ]
            if masked:
                in_specs.append(P())  # filter mask replicated
                wrapped = kernel
            else:
                def wrapped(vectors, queries, count):
                    return kernel(vectors, queries, count, None)

            fn = jax.jit(
                shard_map(
                    wrapped,
                    mesh=self.mesh.mesh,
                    in_specs=tuple(in_specs),
                    out_specs=(P(), P()),
                    check_vma=False,
                )
            )
        else:
            single = functools.partial(_search_single, k=k)
            if masked:
                fn = jax.jit(single)
            else:
                fn = jax.jit(lambda v, q, c: single(v, q, c, None))
        self._search_fns[key] = fn
        return fn

    def _filter_mask_locked(self, filters: Dict[str, Any]) -> np.ndarray:
        """Vectorized [capacity] bool mask from a columnar filter spec
        (keys: patient_id, doc_type, date_from, date_to).  Rows without a
        date are excluded when a date bound is given — the reference's
        patient-snippet semantics (``qa.py`` belongs())."""
        unknown = set(filters) - set(self._FILTER_KEYS)
        if unknown:
            raise ValueError(f"unknown filter keys: {sorted(unknown)}")
        count, capacity = self._count, self._capacity
        mask = np.zeros((capacity,), bool)
        live = np.ones((count,), bool)
        for column in ("patient_id", "doc_type"):
            value = filters.get(column)
            if value is not None:
                # unseen value interns to no row: code -2 matches nothing
                code = self._codes[column].get(value, -2)
                live &= self._cols[column][:count] == code
        dates = self._cols["doc_date"][:count]
        for bound in ("date_from", "date_to"):
            value = filters.get(bound)
            if not value:  # None OR '' — unfilled form fields mean no bound
                continue
            code = _date_code(value)
            if code < 0:
                # silent mis-parses would alter medical-record query
                # semantics (a dropped lower bound over-returns; a poisoned
                # upper bound returns nothing) — reject loudly instead
                raise ValueError(
                    f"{bound}={value!r} is not an ISO date (YYYY-MM-DD)"
                )
            if bound == "date_from":
                live &= dates >= code
            else:
                live &= dates <= code
        if filters.get("date_from") or filters.get("date_to"):
            live &= dates >= 0  # undated rows excluded when bounds given
        if self._n_deleted:
            live &= ~self._deleted[:count]
        mask[:count] = live
        return mask

    def _live_mask_locked(self) -> Optional[np.ndarray]:
        """[capacity] live mask, or None when nothing is deleted — the
        zero-tombstone path keeps unfiltered searches mask-free (a mask
        upload costs a host->device transfer per query batch)."""
        if not self._n_deleted:
            return None
        mask = np.zeros((self._capacity,), bool)
        mask[: self._count] = ~self._deleted[: self._count]
        return mask

    def _compose_live_locked(
        self, mask: Optional[np.ndarray], already_live: bool
    ) -> Optional[np.ndarray]:
        """Fold the tombstone mask into an (optional) filter mask — the ONE
        place the live-rows invariant lives, so every search surface
        composes it identically.  ``already_live``: the mask came from
        ``_filter_mask_locked`` (which ANDs tombstones itself)."""
        if already_live or not self._n_deleted:
            return mask
        live = self._live_mask_locked()
        return live if mask is None else (mask & live)

    def delete_docs(self, doc_ids: Sequence[str]) -> int:
        """Tombstone every chunk of the given documents: rows vanish from
        all searches/listings immediately; vector bytes remain in HBM and
        snapshots until ``compact_deleted``.  Returns rows tombstoned."""
        with self._lock:
            count = self._count
            if count == 0:
                return 0
            codes = [
                self._codes["doc_id"].get(d)
                for d in doc_ids
                if self._codes["doc_id"].get(d) is not None
            ]
            if not codes:
                return 0
            hit = np.isin(self._cols["doc_id"][:count], codes)
            hit &= ~self._deleted[:count]
            n = int(hit.sum())
            if n == 0:
                return 0
            self._deleted[:count] |= hit
            self._n_deleted += n
            for i in np.nonzero(hit)[0]:
                self._meta[int(i)]["deleted"] = True  # persists via snapshot
            self._version += 1
            self._notify_sinks(
                "on_delete", [int(i) for i in np.nonzero(hit)[0]]
            )
            log.info("tombstoned %d rows across %d docs", n, len(codes))
            return n

    def compact_deleted(self) -> int:
        """Physically remove tombstoned rows (real erasure, not a mask):
        rewrites the host copy, columns, and the device buffer.  Row ids
        change — any derived index (IVF/tiered) must rebuild from the new
        state.  Returns rows removed."""
        with self._lock:
            count = self._count
            if not self._n_deleted:
                return 0
            keep = ~self._deleted[:count]
            removed = count - int(keep.sum())
            self._host = self._host[:count][keep].copy()
            self._meta = [
                md for md, k in zip(self._meta, keep) if k
            ]
            self._count = int(keep.sum())
            # rebuild interned columns from scratch (codes for deleted-only
            # values are dropped with them)
            self._codes = {"patient_id": {}, "doc_type": {}, "doc_id": {}}
            self._cols = {
                "patient_id": np.zeros((0,), np.int32),
                "doc_type": np.zeros((0,), np.int32),
                "doc_id": np.zeros((0,), np.int32),
                "doc_date": np.zeros((0,), np.int32),
            }
            self._deleted = np.zeros((0,), bool)
            self._n_deleted = 0
            saved_count = self._count
            self._count = 0
            self._append_columns(self._meta)
            self._count = saved_count
            # fresh device buffer from the compacted host copy
            n_pad = round_up(max(self._count, 1), 64)
            self._capacity = self._round_capacity(max(n_pad, 128))

            def _reupload_on_lane():
                buf = np.zeros((self._capacity, self.cfg.dim), np.float32)
                buf[: self._count] = self._host[: self._count]
                self._dev = self._place_rows(jnp.asarray(buf, self._dtype))
                return self._dev

            spine_run("store_add", _reupload_on_lane)
            if self._count == 0:  # keep a 1-row pad so slicing stays valid
                self._host = np.zeros((1, self.cfg.dim), np.float32)
            self._n_compactions += 1
            self._version += 1
            self._notify_sinks("on_compact", keep.copy())
            log.info("compacted %d deleted rows; %d remain", removed, self._count)
            return removed

    def metadata_select(
        self,
        limit: Optional[int] = None,
        **filters: Any,
    ) -> List[Dict[str, Any]]:
        """Filtered metadata listing (row order) via the columnar mask —
        the non-semantic patient-snippets path, O(matches) not O(corpus)."""
        with self._lock:
            count = self._count
            if count == 0:
                return []
            idx = np.nonzero(self._filter_mask_locked(filters)[:count])[0]
            if limit is not None:
                idx = idx[:limit]
            return [self._meta[int(i)] for i in idx]

    def search(
        self,
        queries: np.ndarray,
        k: Optional[int] = None,
        where: Optional[Callable[[Dict[str, Any]], bool]] = None,
        filters: Optional[Dict[str, Any]] = None,
    ) -> List[List[SearchResult]]:
        """Exact top-k over the live buffer.

        ``filters``: columnar metadata filter (patient_id / doc_type /
        date_from / date_to) built into the device mask with vectorized
        compares — the fast path.  ``where``: arbitrary host predicate,
        O(corpus) Python — escape hatch only; both compose with AND.
        """
        k = k or self.cfg.default_k
        queries = np.asarray(queries, np.float32)
        if queries.ndim == 1:
            queries = queries[None]
        qn = queries / np.maximum(
            np.linalg.norm(queries, axis=1, keepdims=True), 1e-9
        )
        # Dispatch under the lock: add() donates the device buffer, so the
        # buffer reference must not be used for a new dispatch after an add
        # replaced it.  The enqueued computation holds its own runtime
        # reference, so only the dispatch (not the result fetch) needs the
        # lock.  _meta is append-only, so rows < count are stable to read
        # outside the lock.
        with self._lock:
            count = self._count
            capacity = self._capacity
            if count == 0:
                return [[] for _ in queries]
            k_eff = min(k, count)
            mask = None
            if filters:
                mask = self._filter_mask_locked(filters)
            if where is not None:
                host = np.zeros((capacity,), bool)
                for i in range(count):
                    host[i] = bool(where(self._meta[i]))
                mask = host if mask is None else (mask & host)
            mask = self._compose_live_locked(mask, already_live=bool(filters))

            def _search_on_lane():
                """Dispatch phase (spine work item; submitter holds the
                lock while blocked): program build, query upload, and
                the async enqueue against the current buffer."""
                fn = self._get_search_fn(
                    len(qn), k_eff, masked=mask is not None
                )
                args = [
                    self._dev, jnp.asarray(qn, self._dtype), jnp.int32(count)
                ]
                if mask is not None:
                    args.append(jnp.asarray(mask))
                return fn(*args)

            with span("store_search", DEFAULT_REGISTRY):
                vals_dev, ids_dev = spine_run("store_search", _search_on_lane)
        # the fetch runs OUTSIDE the lock (the enqueued computation holds
        # its own buffer reference) but still on a spine lane: blocking
        # on the device result is device time, and bounded like any other
        vals, ids = spine_run(
            "store_search_fetch",
            lambda: (np.asarray(vals_dev), np.asarray(ids_dev)),
        )
        return self.assemble_results(vals, ids)

    def shadow_search(
        self, queries: np.ndarray, k: int, count_cap: Optional[int] = None
    ) -> List[List[SearchResult]]:
        """Exact tombstone-masked top-k as a BACKGROUND probe — the
        retrieval observatory's ground-truth scan (``obs/retrieval_
        observatory.py``).  Identical ranking semantics to :meth:`search`
        (same kernels, same live-mask composition, no filters), but the
        device work rides the spine's background ``probe`` stream under
        the dedicated ``retrieve_shadow`` stage: capped at n_lanes-1, it
        can never occupy the last serving lane, and ``dispatch_*``
        telemetry attributes exactly what shadow sampling costs.

        ``count_cap`` bounds the scanned rows to the corpus size the
        SERVED query saw: a shadow that lags a concurrent ingest must
        not count rows the tier could not have returned as misses."""
        queries = np.asarray(queries, np.float32)
        if queries.ndim == 1:
            queries = queries[None]
        qn = queries / np.maximum(
            np.linalg.norm(queries, axis=1, keepdims=True), 1e-9
        )
        # dispatch under the lock / fetch outside: the same donation
        # discipline as search() (see the comment there)
        with self._lock:
            count = self._count
            if count_cap is not None:
                count = min(count, int(count_cap))
            if count == 0:
                return [[] for _ in queries]
            k_eff = min(k, count)
            mask = self._compose_live_locked(None, already_live=False)

            def _shadow_on_lane():
                """Dispatch phase (spine work item; submitter holds the
                lock while blocked — the closure acquires nothing)."""
                fn = self._get_search_fn(
                    len(qn), k_eff, masked=mask is not None
                )
                args = [
                    self._dev, jnp.asarray(qn, self._dtype), jnp.int32(count)
                ]
                if mask is not None:
                    args.append(jnp.asarray(mask))
                return fn(*args)

            vals_dev, ids_dev = spine_run(
                "retrieve_shadow", _shadow_on_lane, stream="probe"
            )
        vals, ids = spine_run(
            "retrieve_shadow",
            lambda: (np.asarray(vals_dev), np.asarray(ids_dev)),
            stream="probe",
        )
        return self.assemble_results(vals, ids)

    def assemble_results(
        self, vals: np.ndarray, ids: np.ndarray
    ) -> List[List[SearchResult]]:
        """Host-side (score, row-id) -> SearchResult rows with metadata;
        shared by ``search`` and the fused text-query path
        (``engines/retrieve.py``).  ``_meta`` is append-only, so reading it
        lock-free for rows the device has already scored is safe."""
        out: List[List[SearchResult]] = []
        for qi in range(len(vals)):
            row: List[SearchResult] = []
            for score, rid in zip(vals[qi], ids[qi]):
                if score <= NEG_INF / 2:
                    continue  # filtered / dead row
                row.append(
                    SearchResult(float(score), int(rid), self._meta[int(rid)])
                )
            out.append(row)
        return out

    def metadata_rows(self) -> List[Dict[str, Any]]:
        """Stable copy of the live metadata (row order == insertion order) —
        backs non-semantic listings like patient-snippet retrieval without a
        device round-trip."""
        with self._lock:
            return list(self._meta[: self._count])

    def host_rows(self, ids: np.ndarray) -> np.ndarray:
        """L2-normalized f32 vectors for the given row ids, from the host
        master copy — the full-precision view the tiered index's exact
        re-rank scores against (``index/tiered.py:_rerank_bulk``; the
        int8 tier's quantization error is confined to candidate
        selection this way).  Lock-free by the same append-only argument
        as ``assemble_results``: rows the caller already holds ids for
        are immutable, and ``_host`` reallocation publishes a whole new
        array reference (atomic under the GIL), never a torn row."""
        return self._host[np.asarray(ids, np.int64)]  # docqa-lint: disable=guarded-state

    def vectors_snapshot(
        self, start: int = 0
    ) -> Tuple[np.ndarray, List[Dict[str, Any]]]:
        """Consistent (vectors, metadata) pair for rows [start, count) under
        one lock acquisition — the safe input for offline rebuilds (IVF) and
        tail slices (TieredIndex) while add() runs concurrently."""
        with self._lock:
            return self._host[start : self._count].copy(), list(
                self._meta[start : self._count]
            )

    # ---- versioned snapshot (checkpoint/resume parity, SURVEY §5) -----------

    def snapshot(self, directory: str, keep_previous: bool = True) -> str:
        """Atomic versioned publish: vectors + metadata + manifest.

        Write-temp + rename — a reader never sees a half-written index
        (the reference's save had no such guarantee, ``indexer.py:26-30``).

        ``keep_previous=False`` prunes every superseded snapshot instead of
        retaining one rollback predecessor — required after an erasure
        compaction, where the predecessor still holds the erased vectors
        and de-identified text on disk."""
        os.makedirs(directory, exist_ok=True)
        with self._lock:
            count, version = self._count, self._version
            vectors = self._host[:count].copy()
            meta = list(self._meta)
        base = os.path.join(directory, f"index_v{version}")
        tmp = tempfile.mkdtemp(dir=directory)
        # checksummed native codec (C++ DNS1 shard, crc32-verified mmap read)
        # when the library is available; .npy otherwise
        vec_path = native.write_vectors(os.path.join(tmp, "vectors"), vectors)
        with open(os.path.join(tmp, "metadata.json"), "w") as f:
            json.dump(meta, f)
        manifest = {
            "version": version,
            "count": count,
            "dim": self.cfg.dim,
            "vectors": os.path.basename(vec_path),
        }
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
        import shutil

        if os.path.exists(base):
            # Same version number does NOT imply same content: after a
            # failed restore the runtime starts a fresh store at version 0
            # in a work dir that still holds old index_vN dirs — publishing
            # must REPLACE the stale dir, or data ingested since the failure
            # would be silently dropped while LATEST points at old vectors.
            shutil.rmtree(base)
        os.replace(tmp, base)
        latest = os.path.join(directory, "LATEST")
        with open(latest + ".tmp", "w") as f:
            f.write(f"index_v{version}")
        os.replace(latest + ".tmp", latest)
        # prune superseded snapshots (keep the published one + its
        # predecessor as a rollback safety net)
        versions = sorted(
            (
                int(d.split("index_v", 1)[1])
                for d in os.listdir(directory)
                if d.startswith("index_v")
                and d.split("index_v", 1)[1].isdigit()
            ),
            reverse=True,
        )
        for old in versions[1 if not keep_previous else 2:]:
            shutil.rmtree(
                os.path.join(directory, f"index_v{old}"), ignore_errors=True
            )
        return base

    @classmethod
    def restore(
        cls,
        directory: str,
        cfg: StoreConfig,
        mesh: Optional[MeshContext] = None,
    ) -> "VectorStore":
        with open(os.path.join(directory, "LATEST")) as f:
            base = os.path.join(directory, f.read().strip())
        with open(os.path.join(base, "manifest.json")) as f:
            manifest = json.load(f)
        # Only the entries read here are trusted to exist; anything else a
        # snapshot carries (an older writer's ``tokens`` / ``token_width``
        # sidecar arrays) is left on disk unread.
        vectors = native.read_vectors(
            os.path.join(base, manifest.get("vectors", "vectors.npy"))
        )
        with open(os.path.join(base, "metadata.json")) as f:
            meta = json.load(f)
        store = cls(cfg, mesh=mesh)
        if len(vectors):
            store.add(vectors, meta)
        store._version = manifest["version"]
        return store
