"""Fused query path: tokenize on host, then ONE device dispatch runs the
query encoder forward -> L2 normalize -> exact top-k over the store buffer.

The reference's query path was two host libraries glued by a host-side
embedding round-trip: sentence-transformers batch-1 encode, then FAISS
``IndexFlatL2.search`` (``llm-qa/main.py:25,101``; SURVEY §3.2 HOT marks).
The round-1 build kept that two-dispatch shape (encoder program, then
search program): each dispatch carries a fixed host<->device round-trip
next to the ~1 ms of device time either program needs, and the
intermediate embedding paid an extra device->host->device hop.  Fusing
collapses /ask retrieval to one XLA program and keeps the embedding
on-device.

Mesh composition: with a row-sharded store (n_model > 1) the fused
program keeps ONE dispatch — the encoder forward runs replicated under
the jit, and the search enters the same ``shard_map`` kernel the store's
own search uses (per-shard MXU matmul + local top-k + tiny all-gather
merge, ``index/store.py:_search_kernel``), with the freshly-computed
query embedding replicated into the shard bodies.  A v5e-8 serving mesh
therefore pays the same single host->device round-trip as one chip.
"""

from __future__ import annotations

import functools
import hashlib
import secrets
from time import perf_counter
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from jax import shard_map
from jax.sharding import PartitionSpec as P

from docqa_tpu.engines.dispatch import dispatch_with_donation_retry
from docqa_tpu.engines.encoder import marshal_texts
from docqa_tpu.engines.spine import spine_run
from docqa_tpu.index.store import (
    SearchResult,
    VectorStore,
    _search_kernel,
    _search_single,
)
from docqa_tpu.models.encoder import encode_batch
from docqa_tpu.obs.retrieval_observatory import (
    ShadowJob,
    get_retrieval_observatory,
)
from docqa_tpu.runtime.metrics import DEFAULT_REGISTRY, get_logger, span

log = get_logger("docqa.retrieve")

QUERY_BATCH_BUCKETS = (1, 4, 16)

# per-process random salt for shadow-job query hashes: same query ->
# same label within a process (dedup), unlinkable to content across
# processes or dumps (PHI policy — the hash is of the query EMBEDDING,
# so no reversible text derivative exists anywhere in the shadow queue)
_SHADOW_HASH_SALT = secrets.token_bytes(16)


def salted_query_hashes(emb) -> List[str]:
    """Salted, process-local content labels for sampled shadow queries
    (obs/retrieval_observatory.py job attrs): dedup/diagnostics without
    holding any text."""
    rows = np.asarray(emb, np.float32)
    return [
        hashlib.sha1(
            _SHADOW_HASH_SALT + row.tobytes()
        ).hexdigest()[:12]
        for row in rows
    ]


def sharded_search(store_mesh, emb, buf, count, mask, k: int):
    """Exact top-k over a row-sharded buffer from an in-program query
    embedding: the same ``shard_map`` kernel ``VectorStore`` searches
    with, entered from INSIDE a jit (the embedding never leaves the
    device).  ``mask`` may be None.  Returns replicated (vals, ids)."""
    axis = store_mesh.model_axis
    kernel = functools.partial(_search_kernel, k=k, axis=axis)
    in_specs = [P(axis, None), P(), P()]
    if mask is not None:
        in_specs.append(P())
        body = kernel
        args = (buf, emb, count, mask)
    else:
        def body(vectors, queries, cnt):
            return kernel(vectors, queries, cnt, None)

        args = (buf, emb, count)
    return shard_map(
        body,
        mesh=store_mesh.mesh,
        in_specs=tuple(in_specs),
        out_specs=(P(), P()),
        check_vma=False,
    )(*args)


def build_fused_search_program(enc_cfg, mesh, k: int, masked: bool):
    """The single-dispatch retrieve program: encoder forward -> L2
    normalize -> exact top-k (sharded kernel when the store mesh has
    model parallelism).  Returns the un-jitted callable — arity 6 when
    ``masked``, 5 otherwise — so both :class:`FusedRetriever` (which jits
    it per cache key) and the sharding audit
    (``docqa_tpu/analysis/shard_audit.py``, which lowers it on virtual
    meshes to count its collectives against ``shard_budget.json``) build
    the exact same program."""
    sharded = mesh is not None and mesh.n_model > 1

    def program(enc_params, ids, lengths, buf, count, mask):
        emb = encode_batch(enc_params, enc_cfg, ids, lengths)
        # store.search L2-normalizes queries unconditionally (scores
        # are cosine); match it even when the encoder config skips
        # its own normalize — idempotent when it doesn't
        emb = emb / jnp.maximum(
            jnp.linalg.norm(emb, axis=-1, keepdims=True), 1e-9
        )
        q = emb.astype(buf.dtype)
        if sharded:
            vals, row_ids = sharded_search(mesh, q, buf, count, mask, k)
        else:
            vals, row_ids = _search_single(buf, q, count, mask, k)
        return vals, row_ids, emb

    if masked:
        return program
    return lambda p, i, l, b, c: program(p, i, l, b, c, None)


class FusedRetriever:
    """Text-in, ranked-rows-out retrieval in a single dispatch.

    Wraps an :class:`EncoderEngine` (for its params/config/tokenizer) and a
    :class:`VectorStore` (for its device buffer + host metadata).  The
    compiled program is cached per (batch-bucket, seq-bucket, k, masked,
    store-capacity) — capacity participates because the store reallocates
    its buffer when it doubles.
    """

    def __init__(self, encoder, store: VectorStore):
        self.encoder = encoder
        self.store = store
        self._fns: Dict[Any, Any] = {}

    def _get_fn(self, k: int, masked: bool):
        key = (k, masked)
        fn = self._fns.get(key)
        if fn is None:
            fn = jax.jit(
                build_fused_search_program(
                    self.encoder.cfg, self.store.mesh, k, masked
                )
            )
            self._fns[key] = fn
        return fn

    def search_texts(
        self,
        texts: Sequence[str],
        k: Optional[int] = None,
        filters: Optional[Dict[str, Any]] = None,
        deadline=None,  # resilience.Deadline: shed before marshal/dispatch
        stage: str = "retrieve",
        stream: str = "serve",
        return_emb: bool = False,
    ) -> Any:
        """Same contract as ``store.search`` but from raw query texts.

        ``stage``/``stream`` relabel the spine work item — the retrieval
        observatory's exact-scan shadow runs THIS path under
        ``("retrieve_shadow", "probe")`` so ground truth and serving can
        never drift.  ``return_emb=True`` additionally returns the
        program's query embeddings as ``(results, emb [n, d] float32)``
        (the frontier probes reuse them instead of re-encoding)."""
        store = self.store
        k = k or store.cfg.default_k
        if not len(texts):
            return ([], np.zeros((0, 0), np.float32)) if return_emb else []
        if deadline is not None:
            deadline.check("retrieve")
        n = len(texts)
        ids_p, len_p = marshal_texts(
            self.encoder.tokenizer,
            self.encoder.cfg,
            texts,
            batch_buckets=QUERY_BATCH_BUCKETS,
        )

        def snapshot_and_build():
            """Consistent (fn, args) from ONE lock acquisition; the
            dispatch discipline lives in ``engines.dispatch``."""
            with store._lock:
                count = store._count
                if count == 0:
                    return None, None
                k_eff = min(k, count)
                mask = None
                if filters:
                    mask = store._filter_mask_locked(filters)
                mask = store._compose_live_locked(
                    mask, already_live=bool(filters)
                )
                fn = self._get_fn(k_eff, masked=mask is not None)
                args = [
                    self.encoder.params,
                    jnp.asarray(ids_p),
                    jnp.asarray(len_p),
                    store._dev,
                    jnp.int32(count),
                ]
                if mask is not None:
                    args.append(jnp.asarray(mask))
            return fn, args

        # shadow relabels keep their own histogram: a background-stream
        # ground-truth scan must not pollute the SERVING fused_query
        # percentiles it exists to audit
        span_name = "fused_query" if stage == "retrieve" else stage
        with span(span_name, DEFAULT_REGISTRY):
            out = dispatch_with_donation_retry(
                store._lock, snapshot_and_build, deadline=deadline,
                stage=stage, stream=stream,
            )
        if out is None:  # empty store
            empty: List[List[SearchResult]] = [[] for _ in texts]
            if return_emb:
                return empty, np.zeros(
                    (n, self.encoder.cfg.embed_dim), np.float32
                )
            return empty
        vals, row_ids, emb = out
        vals = np.asarray(vals)[:n]
        row_ids = np.asarray(row_ids)[:n]
        results = store.assemble_results(vals, row_ids)
        if return_emb:
            return results, np.asarray(emb, np.float32)[:n]
        return results


def build_tiered_search_program(
    enc_cfg,
    mesh,
    *,
    nprobe: int,
    fetch: int,
    k_tail: int,
    n_real_cells: Optional[int] = None,
):
    """The single-dispatch tiered retrieve program: encoder forward ->
    L2 normalize -> coarse probe over the (int8, mesh-sharded) IVF cell
    tiles -> exact tail scan -> per-tier top-k.  Mesh-native: with
    ``mesh.n_model > 1`` the probe enters the ``shard_map`` merge kernel
    (``index/ivf.py:_probe_kernel_sharded``) — the coarse centroid score
    replicates, each shard scores its local tiles, and the merge is
    exactly the 2-gather top-k of the exact store's path.  Returns the
    un-jitted callable with arity (enc_params, ids, lengths, cells,
    cell_scale, cell_ids, centroids, spill, spill_ids, tail, n_live) so
    both :class:`FusedTieredRetriever` (which jits it per cache key) and
    the sharding audit (``analysis/shard_audit.py`` program
    ``retrieve_ivf_sharded``, which lowers it on virtual meshes to count
    its collectives against ``shard_budget.json``) build the exact same
    program."""
    from docqa_tpu.index.ivf import (
        _probe_kernel,
        _probe_kernel_sharded,
        ivf_cell_specs,
    )
    from docqa_tpu.index.tiered import _tail_kernel

    sharded = mesh is not None and mesh.n_model > 1

    def program(
        enc_params, ids, lengths, cells, cell_scale, cell_ids,
        centroids, spill, spill_ids, tail, n_live,
    ):
        emb = encode_batch(enc_params, enc_cfg, ids, lengths)
        emb = emb / jnp.maximum(
            jnp.linalg.norm(emb, axis=-1, keepdims=True), 1e-9
        )
        q = emb.astype(centroids.dtype)
        if sharded:
            kernel = functools.partial(
                _probe_kernel_sharded,
                nprobe=nprobe, k=fetch,
                n_real_cells=n_real_cells or cells.shape[0],
                axis=mesh.model_axis,
            )

            def tiered_probe_body(bcells, bscale, bids, bcent, bsp, bsp_ids, bq):
                return kernel(bcells, bscale, bids, bcent, bsp, bsp_ids, bq)

            bulk_vals, bulk_ids = shard_map(
                tiered_probe_body,
                mesh=mesh.mesh,
                in_specs=ivf_cell_specs(mesh.model_axis),
                out_specs=(P(), P()),
                check_vma=False,
            )(cells, cell_scale, cell_ids, centroids, spill, spill_ids, q)
        else:
            bulk_vals, bulk_ids = _probe_kernel(
                cells, cell_scale, cell_ids, centroids, spill,
                spill_ids, q, nprobe=nprobe, k=fetch,
                n_real_cells=n_real_cells,
            )
        if k_tail:
            tail_vals, tail_ids = _tail_kernel(tail, q, n_live, k_tail)
        else:  # empty tail: nothing to scan
            tail_vals = jnp.zeros((q.shape[0], 0), jnp.float32)
            tail_ids = jnp.zeros((q.shape[0], 0), jnp.int32)
        # the query embeddings ride out too (tiny [n, d] fetch): the
        # shadow-sampling hook holds THEM — never the raw query texts —
        # for its exact ground-truth scan and the frontier probes (PHI
        # policy, obs/retrieval_observatory)
        return bulk_vals, bulk_ids, tail_vals, tail_ids, emb

    return program


def build_hybrid_search_program(
    enc_cfg,
    mesh,
    *,
    nprobe: int,
    fetch: int,
    k_tail: int,
    k_lex: int,
    n_real_cells: Optional[int] = None,
):
    """The single-dispatch HYBRID retrieve program (docqa-lexroute): the
    tiered dense program (encoder forward -> coarse probe -> exact tail)
    plus the lexical impact-tile kernel, all in one XLA program — the
    lexical tier adds five operands (term_ids, impacts, row_live,
    q_terms, q_weights; the term encoding is host work, no device
    round-trip) and one extra (vals, ids) output pair.  On a mesh both
    the probe and the lexical scorer enter their ``shard_map`` merge
    kernels inside the SAME dispatch, so the hybrid program owes exactly
    TWO 2-gather merge pairs (audited as ``retrieve_hybrid_sharded`` in
    shard_budget.json) and the off-mesh-fallback ban carries over
    unchanged.  Fusion itself (score normalization + mix) is host work
    on the k-sized candidate lists — ``engines/router.py:fuse_scores``."""
    from docqa_tpu.index.ivf import (
        _probe_kernel,
        _probe_kernel_sharded,
        ivf_cell_specs,
    )
    from docqa_tpu.index.lexical import (
        _lexical_kernel,
        _lexical_kernel_sharded,
        lexical_specs,
    )
    from docqa_tpu.index.tiered import _tail_kernel

    sharded = mesh is not None and mesh.n_model > 1

    def program(
        enc_params, ids, lengths, cells, cell_scale, cell_ids,
        centroids, spill, spill_ids, tail, n_live,
        term_ids, impacts, row_live, q_terms, q_weights,
    ):
        emb = encode_batch(enc_params, enc_cfg, ids, lengths)
        emb = emb / jnp.maximum(
            jnp.linalg.norm(emb, axis=-1, keepdims=True), 1e-9
        )
        q = emb.astype(centroids.dtype)
        if sharded:
            kernel = functools.partial(
                _probe_kernel_sharded,
                nprobe=nprobe, k=fetch,
                n_real_cells=n_real_cells or cells.shape[0],
                axis=mesh.model_axis,
            )

            def hybrid_probe_body(bcells, bscale, bids, bcent, bsp, bsp_ids, bq):
                return kernel(bcells, bscale, bids, bcent, bsp, bsp_ids, bq)

            bulk_vals, bulk_ids = shard_map(
                hybrid_probe_body,
                mesh=mesh.mesh,
                in_specs=ivf_cell_specs(mesh.model_axis),
                out_specs=(P(), P()),
                check_vma=False,
            )(cells, cell_scale, cell_ids, centroids, spill, spill_ids, q)
            lex_kernel = functools.partial(
                _lexical_kernel_sharded, k=k_lex, axis=mesh.model_axis
            )

            def hybrid_lexical_body(tids, timp, tlive, qt, qw):
                return lex_kernel(tids, timp, tlive, qt, qw)

            lex_vals, lex_ids = shard_map(
                hybrid_lexical_body,
                mesh=mesh.mesh,
                in_specs=lexical_specs(mesh.model_axis),
                out_specs=(P(), P()),
                check_vma=False,
            )(term_ids, impacts, row_live, q_terms, q_weights)
        else:
            bulk_vals, bulk_ids = _probe_kernel(
                cells, cell_scale, cell_ids, centroids, spill,
                spill_ids, q, nprobe=nprobe, k=fetch,
                n_real_cells=n_real_cells,
            )
            lex_vals, lex_ids = _lexical_kernel(
                term_ids, impacts, row_live, q_terms, q_weights, k=k_lex
            )
        if k_tail:
            tail_vals, tail_ids = _tail_kernel(tail, q, n_live, k_tail)
        else:
            tail_vals = jnp.zeros((q.shape[0], 0), jnp.float32)
            tail_ids = jnp.zeros((q.shape[0], 0), jnp.int32)
        return (
            bulk_vals, bulk_ids, tail_vals, tail_ids,
            lex_vals, lex_ids, emb,
        )

    return program


class FusedTieredRetriever:
    """Text-in, ranked-rows-out over a :class:`TieredIndex` in ONE dispatch.

    The two-step tiered query costs three dispatches (encoder forward, IVF
    probe, exact tail), each carrying the fixed host<->device round-trip
    the module docstring describes.  This program fuses all three:
    encode -> L2 normalize -> coarse probe over the IVF cells -> exact tail
    scan -> both tiers' top-k, one XLA program.  Host-side work (duplicate
    -id dedup, tombstone filtering, tier merge, the under-fill exact
    fallback) is shared with ``TieredIndex.search`` via ``_merge``.

    Falls back to the fused-exact path (``FusedRetriever``) whenever the
    tiered index itself would: no IVF tier yet, or filtered queries.
    MESH-NATIVE (docqa-meshindex): on a multi-device mesh the probe
    enters the sharded merge kernel inside the SAME single dispatch —
    the former three-dispatch off-mesh fallback (and its loud
    ``retrieve_offmesh_fallback_total`` counter) is structurally gone;
    ``tests/test_ivf_sharded.py`` holds that counter to zero on the
    multi-device path.
    """

    # docqa-lexroute: search_texts accepts mode= — the QA service's
    # tier-routing opt-in marker (plain FusedRetriever stays dense-only)
    supports_modes = True

    def __init__(self, encoder, tiered):
        self.encoder = encoder
        self.tiered = tiered
        self._exact = FusedRetriever(encoder, tiered.store)
        self._fns: Dict[Any, Any] = {}
        self._tier_token: Any = None  # evicts _fns when the tier swaps

    def _get_fn(
        self, fetch: int, nprobe: int, k_tail: int, ivf,
        k_lex: Optional[int] = None,
    ):
        key = (fetch, nprobe, k_tail, k_lex)
        fn = self._fns.get(key)
        if fn is None:
            if k_lex is None:
                fn = jax.jit(
                    build_tiered_search_program(
                        self.encoder.cfg,
                        self.tiered.store.mesh,
                        nprobe=nprobe,
                        fetch=fetch,
                        k_tail=k_tail,
                        n_real_cells=ivf.n_real_cells,
                    )
                )
            else:
                fn = jax.jit(
                    build_hybrid_search_program(
                        self.encoder.cfg,
                        self.tiered.store.mesh,
                        nprobe=nprobe,
                        fetch=fetch,
                        k_tail=k_tail,
                        k_lex=k_lex,
                        n_real_cells=ivf.n_real_cells,
                    )
                )
            self._fns[key] = fn
        return fn

    def search_texts(
        self,
        texts: Sequence[str],
        k: Optional[int] = None,
        filters: Optional[Dict[str, Any]] = None,
        deadline=None,  # resilience.Deadline: shed before marshal/dispatch
        mode: Optional[str] = None,
    ) -> List[List[SearchResult]]:
        """Same contract as ``TieredIndex.search`` but from raw texts.

        ``mode`` (docqa-lexroute): dense (default) / lexical / hybrid.
        Hybrid keeps the ONE-dispatch shape — the lexical kernel rides
        the same fused program (``build_hybrid_search_program``), so the
        off-mesh-fallback ban and the nprobe-snapshot discipline carry
        over verbatim.  Lexical mode skips the encoder entirely (the
        term encoding is host work)."""
        tiered = self.tiered
        store = tiered.store
        k = k or store.cfg.default_k
        if not len(texts):
            return []
        if deadline is not None:
            deadline.check("retrieve")
        mode = tiered._resolve_mode(mode, texts, None, filters)
        DEFAULT_REGISTRY.counter(f"retrieve_mode_{mode}").inc()
        if mode == "lexical":
            return tiered._search_lexical(list(texts), k)
        lex_tiles = None
        if mode == "hybrid":
            lex_tiles = tiered.lexical.device_tiles()
            if lex_tiles is None:  # empty lexical tier: nothing to fuse
                mode = "dense"
        tiered._maybe_background_rebuild()
        tier = tiered._tier  # one read: (ivf, covered) stay consistent
        if tier is None or filters:
            # pre-IVF or filtered: the (masked) exact fused path is the
            # right tool — identical policy to TieredIndex.search.  A
            # pre-IVF hybrid pays the bootstrap's second dispatch; the
            # one-dispatch claim is for the steady tiered serving state.
            if mode == "hybrid":
                seen_count = store.count
                dense, emb = self._exact.search_texts(
                    texts, k=k, deadline=deadline, return_emb=True
                )
                lex = tiered.lexical.search(list(texts), k=k)
                out = tiered._fuse_rows(dense, lex, k)
                tiered._observe_hybrid(emb, list(texts), out, k, seen_count)
                return out
            return self._exact.search_texts(
                texts, k=k, filters=filters, deadline=deadline
            )
        ivf, covered = tier

        n = len(texts)
        ids_p, len_p = marshal_texts(
            self.encoder.tokenizer,
            self.encoder.cfg,
            texts,
            batch_buckets=QUERY_BATCH_BUCKETS,
        )
        if self._tier_token is not ivf:
            # a rebuild swapped the tier: every cached program holds the
            # OLD cell tensors' shapes — evict so dead executables don't
            # accumulate across the service's lifetime
            self._fns.clear()
            self._tier_token = ivf
        k_bulk = tiered._k_bulk(k, covered)
        # mirror IVFIndex.search's duplicate-id over-fetch: rows assigned
        # to multiple cells can appear nprobe times in the raw top list.
        # ONE nprobe read: set_nprobe (auto-apply/operator) may land
        # mid-request, and pool/fetch derived from two different values
        # could hand the program a top_k k larger than its candidate axis
        nprobe_live = ivf.nprobe
        pool = nprobe_live * ivf.cap + int(ivf._spill_ids.shape[0])
        nprobe = min(nprobe_live, ivf.n_clusters)
        fetch = min(min(k_bulk, ivf.n) * (ivf.n_assign + 1), pool)

        _, _, tail_dev, n_live, tail_meta = tiered._tail_device(covered)
        # NOT clamped to n_live: the tail buffer is NEG_INF-masked past
        # n_live and the merge drops those rows, so asking for the full
        # quantized ladder keeps ONE compiled program while the tail grows
        # (an n_live-dependent k would recompile the whole fused program —
        # encoder included — on every append while the tail is small).
        # The padded bucket size bounds top_k's k.
        k_tail = min(max(k_bulk, k), int(tail_dev.shape[0]))
        lex_vals = lex_ids = None
        lex_count = 0
        if mode == "hybrid":
            lex_term_ids, lex_impacts, lex_live, lex_count = lex_tiles
            # the term encoding is pure host work; batch-bucket ladders
            # match marshal_texts' so the query axes stay aligned
            q_terms, q_weights = tiered.lexical.encode_queries(texts)
            k_lex = min(k, lex_count)
            fn = self._get_fn(fetch, nprobe, k_tail, ivf, k_lex=k_lex)
        else:
            fn = self._get_fn(fetch, nprobe, k_tail, ivf)
        if deadline is not None:  # marshal/rebuild may have eaten the budget
            deadline.check("retrieve_dispatch")
        def _tiered_on_lane():
            args = [
                self.encoder.params,
                jnp.asarray(ids_p),
                jnp.asarray(len_p),
                ivf._cells,
                ivf._cell_scale,
                ivf._cell_ids,
                ivf._centroids,
                ivf._spill,
                ivf._spill_ids,
                tail_dev,
                jnp.int32(n_live),
            ]
            if mode == "hybrid":
                args += [
                    lex_term_ids, lex_impacts, lex_live,
                    jnp.asarray(q_terms), jnp.asarray(q_weights),
                ]
            return fn(*args)

        t_probe = perf_counter()
        seen_count = store.count  # hybrid shadow horizon (pre-dispatch)
        with span("fused_tiered_query", DEFAULT_REGISTRY):
            # async like the exact path: the lane covers trace/compile +
            # enqueue; the np.asarray fetches below block on the caller
            # (an executor lane, not a dispatch stream) as before
            out_dev = spine_run("retrieve", _tiered_on_lane, deadline=deadline)
        if mode == "hybrid":
            (bulk_vals, bulk_ids, tail_vals, tail_ids,
             lex_vals, lex_ids, emb_dev) = out_dev
        else:
            bulk_vals, bulk_ids, tail_vals, tail_ids, emb_dev = out_dev
        bulk_vals = np.asarray(bulk_vals, np.float32)[:n]
        bulk_ids = np.asarray(bulk_ids)[:n]
        tail_vals = np.asarray(tail_vals, np.float32)[:n]
        tail_ids = np.asarray(tail_ids)[:n]
        # the fused program collapses encode+probe+tail into ONE
        # dispatch, so the split the two-step path reports per tier is
        # unobservable here — the combined dispatch+fetch gets its own
        # honestly-named digest instead of impersonating the bulk probe
        DEFAULT_REGISTRY.histogram("retrieve_tier_ms_fused_probe").observe(
            (perf_counter() - t_probe) * 1e3
        )

        # host dedup (IVFIndex.search's loop) -> bulk candidate rows
        from docqa_tpu.index.store import NEG_INF

        t_merge = perf_counter()
        bulk_rows = []
        for qi in range(n):
            # full candidate pool (no cut at k_bulk): the exact re-rank
            # below recovers rows the int8 ranking pushed past the cut
            row = []
            seen = set()
            for score, rid in zip(bulk_vals[qi], bulk_ids[qi]):
                if rid < 0 or score <= NEG_INF / 2 or int(rid) in seen:
                    continue
                seen.add(int(rid))
                row.append((float(score), int(rid), ivf._meta[int(rid)]))
            bulk_rows.append(row)
        if tiered._rerank_active(ivf):
            # exact f32 re-rank against the store's host master copy —
            # quantization error is confined to candidate selection
            # (TieredIndex._rerank_bulk; the program's normalized query
            # embeddings ride out of the dispatch either way).  Inactive
            # for float tiers and across a compaction window (stale row
            # ids must not index the renumbered host copy).
            emb_np = np.asarray(emb_dev, np.float32)[:n]
            bulk_rows = tiered._rerank_bulk(emb_np, bulk_rows, ivf, k_bulk)
        else:
            bulk_rows = [row[:k_bulk] for row in bulk_rows]

        # queries only matter to _merge for the under-fill exact fallback;
        # hand it the raw embeddings-equivalent texts' encodings lazily is
        # impossible here, so re-encode just the short ones via the store
        # path inside _merge — pass the normalized embeddings we already
        # computed?  The program keeps them on device; re-encoding a rare
        # fallback query host-side is cheaper than always fetching them.
        q_for_fallback = _FallbackQueries(self.encoder, texts)
        out = tiered._merge(
            q_for_fallback, bulk_rows, tail_vals, tail_ids, tail_meta,
            covered, k,
        )
        DEFAULT_REGISTRY.histogram("retrieve_tier_ms_merge").observe(
            (perf_counter() - t_merge) * 1e3
        )
        if mode == "hybrid":
            # lexical candidates from the SAME dispatch -> host fusion
            lex_vals = np.asarray(lex_vals, np.float32)[:n]
            lex_ids = np.asarray(lex_ids)[:n]
            lex_rows = []
            for qi in range(n):
                row = []
                for s, rid in zip(lex_vals[qi], lex_ids[qi]):
                    if s <= 0.0 or rid < 0 or rid >= lex_count:
                        continue
                    row.append((float(s), int(rid)))
                lex_rows.append(row)
            out = tiered._fuse_rows(out, lex_rows, k)
            tiered._observe_hybrid(
                np.asarray(emb_dev, np.float32)[:n], list(texts), out, k,
                seen_count,
            )
            return out
        self._observe_quality(
            emb_dev, out, ivf, covered, covered + n_live, k, nprobe
        )
        return out

    def _observe_quality(
        self,
        emb_dev,  # device array: materialized ONLY for sampled requests
        out: List[List[SearchResult]],
        ivf,
        covered: int,
        seen_count: int,
        k: int,
        nprobe: int,
    ) -> None:
        """Shadow-sampling hook for the fused path (docqa-recallscope).
        Ground truth is the store's exact shadow scan over the SERVED
        dispatch's own query embeddings (the fused program returns them
        — no re-encode, and crucially **no raw query text** is ever
        held by the pending shadow closure: only the embeddings plus a
        salted content hash for dedup/labels, closing the PHI caveat
        docs/OBSERVABILITY.md used to carry).  Runs on the background
        ``probe`` stream under ``retrieve_shadow``; the embeddings also
        feed the neighbor-nprobe frontier probes."""
        robs = get_retrieval_observatory()
        if robs is None or not robs.sample():
            # unsampled (or observatory off): the device embeddings are
            # never fetched — the hot path pays nothing beyond the
            # extra program output riding the already-async dispatch
            return
        served = [[(r.row_id, r.score) for r in row] for row in out]
        margins = [
            row[0].score - row[-1].score for row in out if len(row) >= 2
        ]
        q_copy = np.array(
            np.asarray(emb_dev, np.float32)[: len(out)], copy=True
        )
        norms = [float(x) for x in np.linalg.norm(q_copy, axis=1)]
        store = self.tiered.store
        count_cap = seen_count

        def shadow_fn():
            rows = store.shadow_search(q_copy, k, count_cap=count_cap)
            return (
                [[(r.row_id, r.score) for r in row] for row in rows],
                q_copy,
            )

        robs.submit(
            ShadowJob(
                tier="tiered_fused",
                # the nprobe the served dispatch actually used, not a
                # re-read racing a concurrent set_nprobe
                nprobe=int(nprobe),
                k=k,
                served=served,
                shadow_fn=shadow_fn,
                frontier_fn=lambda qn, p: self.tiered._frontier_probe(
                    ivf, qn, k, p
                ),
                covered=covered,
                n_clusters=ivf.n_clusters,
                query_norms=norms,
                served_margins=margins,
                attrs={"query_hashes": salted_query_hashes(q_copy)},
            )
        )


class _FallbackQueries:
    """Lazy query-embedding view for ``TieredIndex._merge``'s under-fill
    fallback: ``_merge`` only touches ``queries[short]`` (rare) and
    ``len(queries)``, so encoding is deferred until a fallback actually
    fires and then covers only the short queries."""

    def __init__(self, encoder, texts: Sequence[str]):
        self._encoder = encoder
        self._texts = list(texts)

    def __len__(self) -> int:
        return len(self._texts)

    def __getitem__(self, idx) -> np.ndarray:
        texts = [self._texts[i] for i in idx]
        return np.asarray(self._encoder.encode_texts(texts), np.float32)
