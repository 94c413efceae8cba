"""Tiered serving index: IVF over the compacted bulk + exact over the tail.

This is the composition ``index/ivf.py`` promises: the live ``VectorStore``
stays the single source of truth (appends, snapshots, metadata, filters);
an ``IVFIndex`` is periodically rebuilt from a consistent snapshot and
serves the *bulk* of the corpus with ``nprobe/n_clusters`` of the HBM
reads, while rows appended since the last rebuild — the *tail* — are
scored exactly (they are few, and recall on fresh documents must be 1.0:
"just ingested but unfindable" was the reference's defining race,
``llm-qa/main.py:35`` loads once at startup).

Query plan:

* unfiltered: IVF probe over bulk  ∪  exact matmul over the tail bucket →
  host top-k merge of ~2k candidates;
* filtered (patient snippets): delegate to the exact store — filters
  target small row subsets where masked exact search is both correct and
  cheap, and IVF cells carry no metadata columns;
* rebuild: when the tail outgrows ``rebuild_tail_rows``, a background
  thread rebuilds from ``store.vectors_snapshot()`` and atomically swaps
  ``(ivf, covered)``; serving never blocks on a rebuild.
"""

from __future__ import annotations

import functools
import threading
from time import perf_counter
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from docqa_tpu.engines.spine import spine_run
from docqa_tpu.index.ivf import IVFIndex
from docqa_tpu.index.store import NEG_INF, SearchResult, VectorStore
from docqa_tpu.obs.retrieval_observatory import (
    ShadowJob,
    get_retrieval_observatory,
)
from docqa_tpu.runtime.metrics import DEFAULT_REGISTRY, get_logger, span
from docqa_tpu.utils import round_up

log = get_logger("docqa.tiered")


@functools.partial(jax.jit, static_argnums=(3,))
def _tail_kernel(tail, queries, n_live, k: int):
    """Exact cosine top-k over the padded tail bucket [T, d]."""
    scores = jax.lax.dot_general(
        queries, tail, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [q, T]
    rows = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    scores = jnp.where(rows < n_live, scores, NEG_INF)
    return jax.lax.top_k(scores, k)


class TieredIndex:
    """Serving facade over (VectorStore, IVFIndex) with the store's search
    signature — drop-in for ``QAService``."""

    # docqa-lexroute: this surface accepts search(..., mode=, query_texts=)
    # — the QA service's tier-routing opt-in marker
    supports_modes = True

    def __init__(
        self,
        store: VectorStore,
        nprobe: int = 8,
        min_rows: int = 50_000,
        rebuild_tail_rows: int = 100_000,
        n_clusters: Optional[int] = None,
        seed: int = 0,
        storage: str = "int8",
        lexical=None,  # index.lexical.LexicalIndex: the exact-token tier
        hybrid_alpha: float = 0.6,
        default_mode: str = "dense",
    ) -> None:
        self.store = store
        self.nprobe = nprobe
        self.min_rows = min_rows
        self.rebuild_tail_rows = rebuild_tail_rows
        self.n_clusters = n_clusters
        self.seed = seed
        # docqa-lexroute: optional lexical tier + fusion knobs.  The
        # serving default stays "dense" unless the measured hybrid
        # recall CI-low beats dense-only on the labeled exact-token mix
        # — the PR 13 advisory-first rule;
        # hybrid/lexical modes are always available per request.
        self.lexical = lexical
        self.hybrid_alpha = float(hybrid_alpha)
        self.default_mode = default_mode
        # bulk-tier cell format: "int8" (per-row-scaled tiles, the
        # mesh-shardable HBM-resident layout) or "float" (store dtype,
        # exact scores, 2x bytes, single-device only)
        self.storage = storage
        # the active tier is published as ONE tuple (ivf, covered) — readers
        # take a single reference so they can never pair an old IVF with a
        # new watermark (rows in between would vanish from results)
        self._tier: Optional[tuple] = None  # (IVFIndex, covered_rows)
        self._rebuild_lock = threading.Lock()
        self._rebuilding = False
        # the in-flight background rebuild thread, KEPT so close() can
        # join it: the old fire-and-forget `Thread(...).start()` left a
        # daemon thread whose IVF build (a jit kmeans) could still be
        # inside an XLA compile at interpreter exit — the same
        # std::terminate abort the pool joins its rebuild warmups for
        # (thread-lifecycle true positive, PR 8)
        self._rebuild_thread: Optional[threading.Thread] = None
        # bumped by reset(): a rebuild begun against a pre-reset snapshot
        # must NOT publish (it would resurrect erased vectors and set a
        # stale covered watermark that hides newer rows)
        self._gen = 0
        # device-resident tail: (covered, count, padded_dev, n_live, meta);
        # rebuilt only when the store grows, so queries between appends pay
        # zero host→device traffic
        self._tail_cache: Optional[tuple] = None

    # ---- rebuild -------------------------------------------------------------

    @property
    def covered(self) -> int:
        tier = self._tier
        return tier[1] if tier else 0

    @property
    def tail_rows(self) -> int:
        return self.store.count - self.covered

    def rebuild(self) -> bool:
        """Synchronous rebuild from a consistent store snapshot; returns
        whether an IVF tier is now active (False below ``min_rows`` — exact
        search is already optimal there)."""
        gen = self._gen
        # captured BEFORE the snapshot: a compaction landing between the
        # two reads makes the re-rank guard trip conservatively (skip
        # the exact re-rank) instead of ever matching stale ids
        comp_gen = self.store.compactions
        vectors, meta = self.store.vectors_snapshot()
        if len(vectors) < self.min_rows:
            return self._tier is not None
        with span("tiered_rebuild", DEFAULT_REGISTRY):
            # the tier shards where the store shards: cell tiles ride
            # the same model axis as the exact buffer's row shards, so
            # a mesh serving 10M chunks holds 1/n of the tier per chip
            ivf = IVFIndex(
                vectors,
                meta,
                n_clusters=self.n_clusters,
                nprobe=self.nprobe,
                seed=self.seed,
                dtype=str(self.store.cfg.dtype),
                mesh=self.store.mesh,
                storage=self.storage,
            )
        # the store generation this tier's row ids address (the exact
        # re-rank refuses to index a renumbered host copy)
        ivf._store_compactions = comp_gen
        with self._rebuild_lock:
            if gen != self._gen:
                log.info("discarding rebuild begun before reset()")
                return self._tier is not None
            self._tier = (ivf, len(vectors))  # single-reference publish
        log.info("tiered: ivf tier now covers %d rows", len(vectors))
        return True

    def _maybe_background_rebuild(self) -> None:
        if self.tail_rows < self.rebuild_tail_rows and self._tier is not None:
            return
        if self.store.count < self.min_rows:
            return
        with self._rebuild_lock:
            if self._rebuilding:
                return
            self._rebuilding = True

        def run():
            try:
                self.rebuild()
            except Exception:
                log.exception("tiered rebuild failed")
            finally:
                with self._rebuild_lock:
                    self._rebuilding = False

        t = threading.Thread(target=run, daemon=True, name="ivf-rebuild")
        self._rebuild_thread = t
        t.start()

    def close(self, timeout: float = 60.0) -> None:
        """Join an in-flight background rebuild.  Call on shutdown — an
        IVF build still inside XLA on a daemon thread at interpreter
        exit aborts the process.  The bound is generous because a
        legitimate rebuild is minutes of kmeans at 10M rows; an exceeded
        bound logs and leaks (the pre-close behavior) rather than
        hanging shutdown forever."""
        t = self._rebuild_thread
        if t is not None and t.is_alive():
            t.join(timeout=timeout)
            if t.is_alive():
                log.warning("ivf-rebuild still alive after close() join")

    # ---- search --------------------------------------------------------------

    def _k_bulk(self, k: int, covered: int) -> int:
        """Candidate fetch size for the IVF tier.

        Tombstoned rows are filtered host-side AFTER top-k; without
        headroom a query between rebuilds could return fewer than k live
        results even when enough exist in the tier.  The over-fetch is
        QUANTIZED to {k, 2k, 4k} — a continuously varying fetch would
        recompile the probe/tail kernels on every deletion (both are
        jit-specialized on k) — and backstopped by the exact-search
        fallback in ``_merge`` for the correlated case (deleting one
        document tombstones mutually-similar chunks that cluster at the
        top of the ranking for related queries, which no fraction-based
        headroom can bound)."""
        deleted_frac = self.store.deleted_count / max(self.store.count, 1)
        if deleted_frac == 0:
            return k
        if deleted_frac <= 0.25:
            return min(covered, 2 * k)
        return min(covered, 4 * k)

    def _rerank_active(self, ivf: IVFIndex) -> bool:
        """Whether the exact host re-rank applies to this tier: int8
        storage (float tiers already score exactly) AND the store's
        host copy is still the one the tier's row ids address — a
        ``compact_deleted`` erasure renumbers rows, and between the
        compaction and the operator's ``reset()`` a stale tier must
        fall back to its own (internally consistent) quantized scores
        rather than index the shrunk/renumbered buffer."""
        return (
            ivf.storage == "int8"
            and getattr(ivf, "_store_compactions", None)
            == self.store.compactions
        )

    def _rerank_order(
        self, qn_row: np.ndarray, ids: np.ndarray, k: int
    ) -> Tuple[np.ndarray, np.ndarray]:
        """The ONE exact-re-rank core both the serving path and the
        frontier instrument use (they must never drift): true f32
        cosines of ``ids`` against one normalized query from the
        store's host master copy, plus the descending order cut to
        ``k``.  ``store.host_rows`` is lock-free by its append-only
        argument, and ``add()`` stores rows L2-normalized, so one
        [m, d] @ [d] is the true cosine."""
        scores = self.store.host_rows(ids) @ qn_row
        return np.argsort(-scores)[:k], scores

    def _rerank_bulk(
        self,
        queries_n: np.ndarray,
        bulk: List[List[tuple]],
        ivf: IVFIndex,
        k_bulk: int,
    ) -> List[List[tuple]]:
        """Exact f32 re-rank of the int8 tier's candidate pool against
        the store's host master copy, cut back to ``k_bulk``.

        The int8 tiles decide WHICH candidates surface; this confines
        their quantization error to candidate selection — the served
        scores and ranking are full precision, so recall loss only
        occurs when a true top-k row misses the (widened, ``dedup_full``)
        candidate pool entirely.  Skipped (quantized scores served, cut
        to k) for float tiers and across a compaction window
        (:meth:`_rerank_active`).  Host cost: ~``k*(n_assign+1)`` dot
        products per query — noise next to the probe dispatch."""
        if not self._rerank_active(ivf):
            return [row[:k_bulk] for row in bulk]
        out: List[List[tuple]] = []
        for qi, row in enumerate(bulk):
            if not row:
                out.append(row)
                continue
            ids = np.fromiter(
                (rid for _s, rid, _m in row), np.int64, len(row)
            )
            order, scores = self._rerank_order(queries_n[qi], ids, k_bulk)
            out.append(
                [(float(scores[j]), row[j][1], row[j][2]) for j in order]
            )
        return out

    def _merge(
        self,
        queries: np.ndarray,
        bulk: List[List[tuple]],
        tail_vals: np.ndarray,
        tail_ids: np.ndarray,
        tail_meta: List[Dict[str, Any]],
        covered: int,
        k: int,
    ) -> List[List[SearchResult]]:
        """Host-side tier merge: tombstone filter, score sort, and the
        exact fallback for under-filled queries.  Shared by the two-step
        path (``search``) and the fused one-dispatch path
        (``engines/retrieve.py:FusedTieredRetriever``)."""
        out: List[List[SearchResult]] = []
        short: List[int] = []
        for qi in range(len(queries)):
            # tombstoned rows are filtered here between rebuilds (the IVF
            # tier still physically holds them); compaction + reset() is
            # the erasure path
            cands: List[SearchResult] = [
                SearchResult(s, rid, md)
                for s, rid, md in bulk[qi]
                if not md.get("deleted")
            ]
            for s, tid in zip(tail_vals[qi], tail_ids[qi]):
                if s <= NEG_INF / 2:
                    continue
                md = tail_meta[int(tid)]
                if md.get("deleted"):
                    continue
                cands.append(SearchResult(float(s), covered + int(tid), md))
            cands.sort(key=lambda r: -r.score)
            out.append(cands[:k])
            if len(cands) < k:
                short.append(qi)
        if short and (self.store.count - self.store.deleted_count) > 0:
            # under-filled despite the head-room: tombstones clustered at
            # the top of this query's ranking (e.g. a just-deleted document
            # whose chunks all match).  Exact tombstone-masked search is
            # always correct; this path is rare and vanishes at the next
            # compaction/rebuild.
            exact = self.store.search(queries[short], k=k)
            for j, qi in enumerate(short):
                if len(exact[j]) > len(out[qi]):
                    out[qi] = exact[j]
        return out

    def search(
        self,
        queries: np.ndarray,
        k: Optional[int] = None,
        where: Optional[Callable[[Dict[str, Any]], bool]] = None,
        filters: Optional[Dict[str, Any]] = None,
        mode: Optional[str] = None,
        query_texts: Optional[List[str]] = None,
    ) -> List[List[SearchResult]]:
        """Mode-aware retrieval (docqa-lexroute): ``mode`` is one of
        ``dense`` (the embedding tiers, unchanged), ``lexical`` (the
        exact-token impact tier alone), or ``hybrid`` (both, fused by
        ``engines.router.fuse_scores``).  Lexical evidence needs the raw
        ``query_texts`` (the clinical tokenizer runs on text, not
        embeddings); without them — or with metadata filters, which only
        the dense store implements — non-dense modes fall back to dense
        and count ``retrieve_mode_fallback``."""
        k_final = k or self.store.cfg.default_k
        mode = self._resolve_mode(mode, query_texts, where, filters)
        DEFAULT_REGISTRY.counter(f"retrieve_mode_{mode}").inc()
        if mode == "lexical":
            return self._search_lexical(query_texts, k_final)
        dense = self._search_dense(
            queries, k, where, filters, observe=mode == "dense"
        )
        if mode == "dense":
            return dense
        return self._fuse_hybrid(queries, query_texts, dense, k_final)

    def _resolve_mode(self, mode, query_texts, where, filters) -> str:
        mode = mode or self.default_mode
        if mode not in ("dense", "lexical", "hybrid"):
            log.warning("unknown retrieve mode %r; serving dense", mode)
            mode = "dense"
        if mode != "dense" and (
            self.lexical is None
            or query_texts is None
            or where is not None
            or filters
        ):
            DEFAULT_REGISTRY.counter("retrieve_mode_fallback").inc()
            return "dense"
        return mode

    def _search_dense(
        self,
        queries: np.ndarray,
        k: Optional[int] = None,
        where: Optional[Callable[[Dict[str, Any]], bool]] = None,
        filters: Optional[Dict[str, Any]] = None,
        observe: bool = True,
    ) -> List[List[SearchResult]]:
        self._maybe_background_rebuild()
        tier = self._tier  # one read: (ivf, covered) stay consistent
        if tier is None or where is not None or filters:
            # filtered or pre-IVF: masked exact search is the right tool
            return self.store.search(queries, k=k, where=where, filters=filters)
        ivf, covered = tier

        k = k or self.store.cfg.default_k
        queries = np.asarray(queries, np.float32)
        if queries.ndim == 1:
            queries = queries[None]
        k_bulk = self._k_bulk(k, covered)
        with span("tiered_search", DEFAULT_REGISTRY):
            # per-tier latency split (docqa-recallscope): bulk probe /
            # tail scan / host merge each get their own digest, so the
            # nprobe frontier's latency axis can be read against what
            # /ask actually pays per stage (the aggregate retrieve span
            # alone could not attribute a regression to a tier)
            t_stage = perf_counter()
            # one nprobe read: a set_nprobe landing mid-request must not
            # make _observe_quality label this comparison with a value
            # the probe above never used
            nprobe_now = self.nprobe
            qn = queries / np.maximum(
                np.linalg.norm(queries, axis=1, keepdims=True), 1e-9
            )
            bulk = ivf.search(
                queries, k=k_bulk, nprobe=nprobe_now, dedup_full=True
            )
            bulk = self._rerank_bulk(qn, bulk, ivf, k_bulk)
            DEFAULT_REGISTRY.histogram("retrieve_tier_ms_bulk_ivf").observe(
                (perf_counter() - t_stage) * 1e3
            )

            _, _, tail_dev, n_live, tail_meta = self._tail_device(covered)
            t_stage = perf_counter()
            if n_live == 0:
                # empty tail: bulk-only, but still through the merge loop
                # below so the under-fill fallback applies
                vals = np.empty((len(queries), 0), np.float32)
                ids = np.empty((len(queries), 0), np.int32)
            else:
                # tombstone headroom like the bulk fetch, but never below k
                # (k_bulk is capped at `covered`), and NOT clamped to
                # n_live: rows past n_live are NEG_INF-masked and dropped
                # in the merge, so the quantized ladder value keeps ONE
                # compiled tail kernel while the tail grows instead of
                # recompiling per append.  The padded bucket size bounds
                # top_k's k and only changes when the bucket grows.
                k_tail = min(max(k_bulk, k), int(tail_dev.shape[0]))

                def _tail_on_lane():
                    v, i = _tail_kernel(
                        tail_dev,
                        jnp.asarray(qn, jnp.dtype(self.store.cfg.dtype)),
                        jnp.int32(n_live),
                        k_tail,
                    )
                    return np.asarray(v, np.float32), np.asarray(i)

                vals, ids = spine_run("tiered_tail", _tail_on_lane)
            DEFAULT_REGISTRY.histogram("retrieve_tier_ms_tail_exact").observe(
                (perf_counter() - t_stage) * 1e3
            )

        t_stage = perf_counter()
        out = self._merge(
            queries, bulk, vals, ids, tail_meta, covered, k
        )
        DEFAULT_REGISTRY.histogram("retrieve_tier_ms_merge").observe(
            (perf_counter() - t_stage) * 1e3
        )
        if observe:
            # hybrid/lexical modes submit their OWN per-tier shadow jobs
            # (one sampled job per request, labeled with the served tier)
            self._observe_quality(
                queries, out, ivf, covered, covered + n_live, k, nprobe_now
            )
        return out

    # ---- lexical / hybrid serving (docqa-lexroute) ---------------------------

    def _row_meta(self, rid: int) -> Optional[Dict[str, Any]]:
        """Metadata for a lexical-surfaced row id (the dense candidates
        carry theirs already).  Lock-held read of the store's row-aligned
        metadata list."""
        store = self.store
        with store._lock:
            if 0 <= rid < store._count:
                return store._meta[rid]
        return None

    def _search_lexical(
        self, texts: List[str], k: int
    ) -> List[List[SearchResult]]:
        """Pure lexical serving: impact-tile top-k mapped onto the dense
        store's metadata (same row-id space by the index-sink contract),
        tombstones filtered like every tier."""
        lex = self.lexical.search(texts, k=k)
        out: List[List[SearchResult]] = []
        for row in lex:
            res = []
            for score, rid in row:
                md = self._row_meta(rid)
                if md is None or md.get("deleted"):
                    continue
                res.append(SearchResult(float(score), rid, md))
            out.append(res)
        self._observe_lexical(texts, out, k)
        return out

    def _fuse_hybrid(
        self,
        queries: np.ndarray,
        texts: List[str],
        dense: List[List[SearchResult]],
        k: int,
    ) -> List[List[SearchResult]]:
        """Hybrid merge: normalized dense + lexical mix
        (``engines.router.fuse_scores``) over the candidate union, cut
        to ``k``.  The dense candidates were produced by the unchanged
        dense path (nprobe snapshot discipline and all); the lexical
        dispatch is the tier's own single program."""
        queries = np.asarray(queries, np.float32)
        if queries.ndim == 1:
            queries = queries[None]
        seen_count = self.store.count  # shadow horizon: pre-fusion view
        t_stage = perf_counter()
        lex = self.lexical.search(texts, k=k)
        DEFAULT_REGISTRY.histogram("retrieve_tier_ms_lexical").observe(
            (perf_counter() - t_stage) * 1e3
        )
        out = self._fuse_rows(dense, lex, k)
        self._observe_hybrid(queries, texts, out, k, seen_count)
        return out

    def _fuse_rows(
        self,
        dense: List[List[SearchResult]],
        lex: List[List[Tuple[float, int]]],
        k: int,
    ) -> List[List[SearchResult]]:
        """The fusion core shared by the two-step path above and the
        one-dispatch fused path (``engines/retrieve.py``, which hands
        in the lexical candidates its own program produced)."""
        from docqa_tpu.engines.router import fuse_scores

        out: List[List[SearchResult]] = []
        for qi, drow in enumerate(dense):
            lrow = lex[qi] if qi < len(lex) else []
            md_by: Dict[int, Dict[str, Any]] = {
                r.row_id: r.metadata for r in drow
            }
            fused = fuse_scores(
                [(r.score, r.row_id) for r in drow],
                lrow,
                self.hybrid_alpha,
            )
            res: List[SearchResult] = []
            for score, rid in fused:
                md = md_by.get(rid)
                if md is None:
                    md = self._row_meta(rid)
                if md is None or md.get("deleted"):
                    continue
                res.append(SearchResult(float(score), rid, md))
                if len(res) >= k:
                    break
            out.append(res)
        return out

    def _observe_lexical(
        self, texts: List[str], out: List[List[SearchResult]], k: int
    ) -> None:
        """Per-tier shadow job for the lexical tier (docqa-recallscope):
        ground truth is the EXACT host-side reference scoring
        (full-precision impacts, ``LexicalIndex.host_topk``), computed
        EAGERLY on sampled requests so the pending job never holds raw
        query text (the PHI rule: jobs hold embeddings and salted
        hashes, never text — a lexical job holds only row/score pairs)."""
        robs = get_retrieval_observatory()
        if robs is None or not robs.sample():
            return
        served = [[(r.row_id, r.score) for r in row] for row in out]
        reference = self.lexical.host_topk(texts, k)

        def shadow_fn():
            return [[(rid, s) for rid, s in row] for row in reference], None

        robs.submit(
            ShadowJob(
                tier="lexical",
                nprobe=0,  # no probe axis on this tier
                k=k,
                served=served,
                shadow_fn=shadow_fn,
            )
        )

    def _observe_hybrid(
        self,
        queries: np.ndarray,
        texts: List[str],
        out: List[List[SearchResult]],
        k: int,
        seen_count: int,
    ) -> None:
        """Per-tier shadow job for the hybrid tier: ground truth fuses
        the store's exact dense shadow scan with the lexical tier's
        exact host reference under the SAME alpha the serving merge
        used, so a fusion-weight drift fires the existing recall SLO.
        The lexical half is computed eagerly (no text in the pending
        job); the dense half runs on the background probe stream as
        usual."""
        robs = get_retrieval_observatory()
        if robs is None or not robs.sample():
            return
        served = [[(r.row_id, r.score) for r in row] for row in out]
        alpha = self.hybrid_alpha
        lex_ref = self.lexical.host_topk(texts, k, count_cap=seen_count)
        q_copy = np.array(queries, np.float32, copy=True)
        store = self.store

        def shadow_fn():
            from docqa_tpu.engines.router import fuse_scores

            rows = store.shadow_search(q_copy, k, count_cap=seen_count)
            fused = []
            for qi, row in enumerate(rows):
                dense_pairs = [(r.score, r.row_id) for r in row]
                lrow = [
                    (s, rid)
                    for rid, s in (lex_ref[qi] if qi < len(lex_ref) else [])
                ]
                fused.append(
                    [
                        (rid, s)
                        for s, rid in fuse_scores(dense_pairs, lrow, alpha, k=k)
                    ]
                )
            return fused, q_copy

        robs.submit(
            ShadowJob(
                tier="hybrid",
                nprobe=0,
                k=k,
                served=served,
                shadow_fn=shadow_fn,
                query_norms=[
                    float(x) for x in np.linalg.norm(q_copy, axis=1)
                ],
                attrs={"alpha": alpha},
            )
        )

    def _observe_quality(
        self,
        queries: np.ndarray,
        out: List[List[SearchResult]],
        ivf: IVFIndex,
        covered: int,
        seen_count: int,
        k: int,
        nprobe: int,
    ) -> None:
        """Shadow-sampling hook (docqa-recallscope): hand the retrieval
        observatory this request's served top-k plus closures that
        reproduce the exact ground truth and the neighbor-nprobe probes
        on the spine's background stream.  ``seen_count`` pins the
        shadow's corpus view to the rows this query could have seen, so
        a concurrent ingest cannot read as a recall miss.  Non-sampled
        calls cost one counter bump and one hash."""
        robs = get_retrieval_observatory()
        if robs is None or not robs.sample():
            return
        served = [[(r.row_id, r.score) for r in row] for row in out]
        margins = [
            row[0].score - row[-1].score for row in out if len(row) >= 2
        ]
        norms = [float(n) for n in np.linalg.norm(queries, axis=1)]
        q_copy = np.array(queries, np.float32, copy=True)
        store = self.store

        def shadow_fn():
            rows = store.shadow_search(q_copy, k, count_cap=seen_count)
            return (
                [[(r.row_id, r.score) for r in row] for row in rows],
                q_copy,
            )

        robs.submit(
            ShadowJob(
                tier="tiered",
                # the nprobe the served probe actually used, not a
                # re-read racing a concurrent set_nprobe
                nprobe=int(min(nprobe, ivf.n_clusters)),
                k=k,
                served=served,
                shadow_fn=shadow_fn,
                frontier_fn=lambda qn, p: self._frontier_probe(
                    ivf, qn, k, p
                ),
                covered=covered,
                n_clusters=ivf.n_clusters,
                query_norms=norms,
                served_margins=margins,
            )
        )

    def _frontier_probe(self, ivf: IVFIndex, queries, k: int, nprobe: int):
        """Frontier probe with SERVING semantics (the recallscope
        ``frontier_fn``): widened candidate pool + the int8 path's exact
        f32 re-rank, so the observed recall/latency frontier measures
        what ``search`` would deliver at that nprobe — the raw quantized
        ranking would understate served recall and recommend a bigger
        nprobe than the target needs.  ``seconds`` stays the device
        probe (the host re-rank is ~µs of numpy)."""
        rows, seconds, fresh = ivf.timed_probe(
            queries, k=k, nprobe=nprobe, dedup_full=True
        )
        if not self._rerank_active(ivf):
            return [r[:k] for r in rows], seconds, fresh
        q = np.asarray(queries, np.float32)
        if q.ndim == 1:
            q = q[None]
        qn = q / np.maximum(
            np.linalg.norm(q, axis=1, keepdims=True), 1e-9
        )
        out = []
        for qi, row in enumerate(rows):
            if not row:
                out.append(row)
                continue
            ids = np.fromiter((rid for rid, _s in row), np.int64, len(row))
            order, scores = self._rerank_order(qn[qi], ids, k)
            out.append([(int(ids[j]), float(scores[j])) for j in order])
        return out, seconds, fresh

    def set_nprobe(self, nprobe: int) -> int:
        """Apply a new serving nprobe live — the observatory's
        recommendation hook (``retrieval_quality.auto_apply_nprobe``)
        and the operator's /api/retrieval-guided knob.  Covers both the
        two-step path (reads ``self.nprobe`` per search) and the fused
        program path (reads the active tier's ``ivf.nprobe``); future
        rebuilds inherit it via ``self.nprobe``."""
        n = max(1, int(nprobe))
        tier = self._tier  # one read: (ivf, covered) stay consistent
        # plain int publishes (GIL-atomic): a search mid-flight reads
        # either the old or the new value, both coherent configurations
        self.nprobe = n
        if tier is not None:
            tier[0].nprobe = min(n, tier[0].n_clusters)
        log.info("tiered: serving nprobe set to %d", n)
        return n

    def reset(self) -> None:
        """Drop the IVF tier and tail cache (searches fall back to exact
        until the next rebuild).  Required after ``store.compact_deleted``:
        compaction renumbers rows, and a stale tier would both misattribute
        ids and keep serving erased vectors.  Bumps the generation so an
        in-flight background rebuild (whose snapshot predates the reset)
        discards itself instead of publishing."""
        with self._rebuild_lock:
            self._gen += 1
            self._tier = None
            self._tail_cache = None

    def _tail_device(self, covered: int):
        """Device-resident padded tail, rebuilt only when the store has
        grown — the per-query cost is zero host→device traffic (a naive
        re-upload would move the whole tail across PCIe on every search).
        Returns (covered, count, padded_dev, n_live, meta)."""
        cache = self._tail_cache
        if cache is not None and cache[0] == covered:
            if cache[1] == self.store.count:
                return cache
        gen = self._gen
        vecs, meta = self.store.vectors_snapshot(start=covered)
        n_live = len(vecs)
        bucket = round_up(max(n_live, 1), 4096)  # stable jit shapes
        padded = np.zeros((bucket, self.store.cfg.dim), np.float32)
        padded[:n_live] = vecs
        tail_dev = spine_run(
            "tiered_tail",
            lambda: jnp.asarray(padded, jnp.dtype(self.store.cfg.dtype)),
        )
        cache = (
            covered,
            covered + n_live,
            tail_dev,
            n_live,
            meta,
        )
        # generation-checked publish UNDER the rebuild lock: a serving
        # thread that snapshotted before a concurrent reset() (erasure /
        # compaction) must not write its stale tail back — the pre-PR-8
        # lock-free store could resurrect erased vectors and serve them
        # until the next append invalidated the cache (guarded-state
        # true positive; regression-tested in tests/test_racecheck.py)
        with self._rebuild_lock:
            if gen == self._gen:
                self._tail_cache = cache
        return cache

    def index_stats(self) -> dict:
        """Tier layout + byte accounting for ``/api/retrieval``."""
        with self._rebuild_lock:
            tier = self._tier
        if tier is None:
            out = {"active": False}
        else:
            ivf, covered = tier
            out = {
                "active": True,
                "covered": covered,
                "n_clusters": ivf.n_clusters,
                "nprobe": self.nprobe,
                "n_assign": ivf.n_assign,
                "cap": ivf.cap,
                "spilled": ivf.n_spilled,
            }
            out.update(ivf.index_bytes())
        if self.lexical is not None:
            out["lexical"] = self.lexical.stats()
            out["retrieve_mode_default"] = self.default_mode
            out["hybrid_alpha"] = self.hybrid_alpha
        return out

    # ---- store passthroughs (QAService drop-in) -----------------------------

    @property
    def count(self) -> int:
        return self.store.count

    def metadata_select(self, limit=None, **filters):
        return self.store.metadata_select(limit=limit, **filters)

    def metadata_rows(self):
        return self.store.metadata_rows()
