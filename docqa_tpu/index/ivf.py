"""IVF (inverted-file) coarse-quantized search for corpora beyond exact scale.

The reference's only index is exact ``IndexFlatL2`` over 649 vectors
(``semantic-indexer/indexer.py:39,104``).  The exact HBM store
(``index/store.py``) already beats that to ~1M chunks on TPU — one MXU
matmul per query batch is HBM-bandwidth bound, not compute bound.  IVF is
the next decade: probing ``nprobe`` of ``n_clusters`` cells cuts HBM reads
per query by ~``nprobe/n_clusters``, at a measured recall cost.

TPU-first layout (no pointer-chasing inverted lists):

* k-means runs ON DEVICE: assignment is one ``[n, d] x [d, C]`` matmul +
  argmax; the centroid update is a one-hot ``[C, n] x [n, d]`` matmul —
  both MXU shapes.  The build is decomposed into BOUNDED spine work items
  (seeding, one item per Lloyd iteration, one per assignment block) on the
  background ``rebuild`` stream, so a 10M-row build interleaves with
  serving instead of holding a lane — or, in strict mode, the whole
  device — for minutes.
* cells are stored as one dense ``[C, cap, d]`` buffer (uniform capacity,
  padded with zeros; padding rows carry id -1 and score -inf).  Probing is
  a static-shape ``take`` of ``[nprobe, cap, d]`` per query — XLA-friendly,
  no ragged gathers.
* the bulk tier is **int8-quantized tiles with per-row scales** by default
  (``storage="int8"``): ``q = round(v / s)``, ``s = max|v| / 127`` per
  row, scored as ``(q · query) * s`` with f32 accumulation
  (``preferred_element_type`` — the dtype-flow contract).  Per-chunk index
  bytes drop ~4x vs the f32 build buffer (~2x vs a bf16 tier), which is
  what makes 10M chunks HBM-resident on a v5e-8.  The recall cost of the
  quantization is *measured*, not assumed: the recallscope shadow scans
  the full-precision store, so quantization-induced ranking flips show up
  in the online recall estimate (obs/retrieval_observatory.py).
* on a multi-device mesh the cell tensors (tiles, scales, ids) are
  **row-sharded over the model axis** under ``shard_map``: the coarse
  centroid score stays replicated (identical top-nprobe probe list on
  every shard), each shard scores only the probed cells it owns, and the
  per-shard top-k merges through exactly the 2-gather budget the exact
  store's ``sharded_topk`` already pays (vals + ids; gated by
  ``analysis/shard_audit.py`` program ``retrieve_ivf_sharded``).
* cell overflow spills to a small exact buffer (replicated; scored on
  shard 0 only so the merge sees each spill row once), so recall degrades
  gracefully instead of silently dropping rows.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from docqa_tpu.engines.spine import spine_run
from docqa_tpu.ops.topk import merge_topk
from docqa_tpu.runtime.metrics import DEFAULT_REGISTRY, get_logger, span

log = get_logger("docqa.ivf")

NEG_INF = -1e30

# assignment-pass block: bounds both device memory and the duration of
# one background work item (a block is one [block, d] x [d, C] matmul)
_ASSIGN_BLOCK = 1 << 18


# ---------------------------------------------------------------------------
# int8 tile quantization
# ---------------------------------------------------------------------------


def quantize_rows_int8(x: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Symmetric per-row int8 quantization: ``q = round(x / s)`` with
    ``s = max|row| / 127``.  Returns ``(q int8, scales float32)`` where
    scales have ``x``'s shape minus the last axis.  Zero rows get scale
    0 (q all zero — dequantization is exact there).

    Round-trip bound: ``|x - q*s| <= s/2 = max|row|/254`` per component
    (tested in tests/test_ivf_sharded.py)."""
    x = np.asarray(x, np.float32)
    amax = np.abs(x).max(axis=-1)
    scale = (amax / 127.0).astype(np.float32)
    safe = np.where(scale > 0, scale, 1.0).astype(np.float32)
    q = np.clip(np.rint(x / safe[..., None]), -127, 127).astype(np.int8)
    return q, scale


# ---------------------------------------------------------------------------
# On-device k-means (bounded background work items)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(1,))
def _kcenter_init(vectors: jax.Array, c: int):
    """Greedy k-center (farthest-point) seeding, fully on device.

    Random seeding collapses on clustered corpora: by coupon-collector a
    large fraction of natural clusters get no seed, and with near-
    orthogonal clusters Lloyd cannot migrate centroids across them — the
    orphaned clusters' rows scatter over arbitrary cells and coarse
    ranking never finds them (measured recall@10 0.28 at 200k rows /
    2000 natural clusters with random init).  Farthest-point seeding
    covers distinct clusters first by construction.  Cost: ``c``
    sequential [n,d]@[d] matvecs under one jit."""
    n, d = vectors.shape

    def body(i, carry):
        best_sim, chosen = carry
        idx = jnp.argmin(best_sim)  # farthest from every chosen seed
        cvec = vectors[idx]
        chosen = chosen.at[i].set(cvec)
        best_sim = jnp.maximum(best_sim, vectors @ cvec)
        return best_sim, chosen

    best0 = jnp.full((n,), -2.0, vectors.dtype).at[0].set(2.0)
    chosen0 = jnp.zeros((c, d), vectors.dtype).at[0].set(vectors[0])
    best0 = jnp.maximum(best0, vectors @ vectors[0])
    _, chosen = jax.lax.fori_loop(1, c, body, (best0, chosen0))
    return chosen


@jax.jit
def _kmeans_step(vectors: jax.Array, centroids: jax.Array) -> jax.Array:
    """ONE Lloyd iteration.  vectors [n, d] (L2-normalized), centroids
    [C, d]; returns the updated L2-normalized centroids.  One iteration
    per spine work item keeps each background dispatch bounded — the
    old whole-fit ``fori_loop`` was a single device program that, at
    10M-corpus cluster counts, held the device for the entire fit."""
    c = centroids.shape[0]
    scores = vectors @ centroids.T  # [n, C] cosine
    assign = jnp.argmax(scores, axis=1)  # [n]
    onehot = jax.nn.one_hot(assign, c, dtype=vectors.dtype)  # [n, C]
    sums = onehot.T @ vectors  # [C, d]
    counts = jnp.sum(onehot, axis=0)[:, None]  # [C, 1]
    new = sums / jnp.maximum(counts, 1.0)
    # empty cell keeps its old centroid (avoids NaN / collapse)
    new = jnp.where(counts > 0, new, centroids)
    norm = jnp.linalg.norm(new, axis=1, keepdims=True)
    return new / jnp.maximum(norm, 1e-9)


@functools.partial(jax.jit, static_argnums=(2,))
def _assign_block(vectors: jax.Array, centroids: jax.Array, n_assign: int):
    """Top-``n_assign`` nearest cells for one block of rows."""
    scores = jax.lax.dot_general(
        vectors, centroids, (((1,), (1,)), ((), ())),
    )  # [block, C] f32
    return jax.lax.top_k(scores, n_assign)[1]


def kmeans(
    vectors: np.ndarray,
    n_clusters: int,
    n_iters: int = 10,
    seed: int = 0,
    sample: Optional[int] = 262_144,
    n_assign: int = 1,
) -> Tuple[np.ndarray, np.ndarray]:
    """Fit centroids (on a subsample for huge corpora), assign every row to
    its ``n_assign`` nearest cells.

    Returns (centroids [C, d] float32, assignments [n, n_assign] int32).
    ``n_assign > 1`` is redundant assignment: each row lives in several
    cells, trading cell memory for recall at fixed nprobe (boundary rows
    stop being missable).

    Every device phase queues as a BOUNDED work item on the spine's
    background ``rebuild`` stream: seeding, each Lloyd iteration, and
    each assignment block are separate items, so serving dispatches
    interleave with a 10M-row build instead of waiting minutes behind
    one monolithic item (critical in strict mode, where exactly one
    device program is ever in flight)."""
    vectors = np.asarray(vectors, np.float32)
    n = len(vectors)
    rng = np.random.default_rng(seed)
    fit_on = vectors
    if sample is not None and n > sample:
        fit_on = vectors[rng.choice(n, sample, replace=False)]
    n_assign = min(n_assign, n_clusters)

    def _seed_item():
        # greedy k-center seeding on a bounded subsample (cluster
        # coverage), random fallback only when the corpus is smaller
        # than the seed count
        if len(fit_on) > n_clusters:
            seed_pool = fit_on
            if len(seed_pool) > 65536:
                seed_pool = seed_pool[
                    rng.choice(len(seed_pool), 65536, replace=False)
                ]
            return np.asarray(
                _kcenter_init(jnp.asarray(seed_pool), n_clusters)
            )
        return fit_on[
            rng.choice(
                len(fit_on), n_clusters, replace=n_clusters > len(fit_on)
            )
        ]

    init = spine_run("ivf_build", _seed_item, stream="rebuild")
    fit_dev = spine_run(
        "ivf_build", lambda: jnp.asarray(fit_on), stream="rebuild"
    )
    cent = spine_run(
        "ivf_build", lambda: jnp.asarray(init, jnp.float32),
        stream="rebuild",
    )
    for _ in range(n_iters):
        cent = spine_run(
            "ivf_build", functools.partial(_kmeans_step, fit_dev, cent),
            stream="rebuild",
        )
    # final assignment over the full corpus, one bounded item per block
    assigns = []
    for start in range(0, n, _ASSIGN_BLOCK):
        blk = vectors[start : start + _ASSIGN_BLOCK]

        def _assign_item(blk=blk):
            return np.asarray(_assign_block(jnp.asarray(blk), cent, n_assign))

        assigns.append(spine_run("ivf_build", _assign_item, stream="rebuild"))
    centroids_h = spine_run(
        "ivf_build", lambda: np.asarray(cent, np.float32), stream="rebuild"
    )
    return centroids_h, np.concatenate(assigns).astype(np.int32)


# ---------------------------------------------------------------------------
# probe kernels
# ---------------------------------------------------------------------------


def _coarse_probe(queries, centroids, nprobe: int, n_real_cells):
    """Replicated coarse ranking: top-``nprobe`` cell ids per query.
    ``n_real_cells`` masks zero-padded centroid rows (cell count rounded
    up to the shard count) so padding can never displace a real cell
    from the probe list — the sharded and single-device instances then
    probe identical cells."""
    c_scores = jax.lax.dot_general(
        queries, centroids, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [q, C]
    if n_real_cells is not None and n_real_cells < centroids.shape[0]:
        cols = jax.lax.broadcasted_iota(jnp.int32, c_scores.shape, 1)
        c_scores = jnp.where(cols < n_real_cells, c_scores, NEG_INF)
    return jax.lax.top_k(c_scores, nprobe)[1]  # [q, nprobe]


def _score_probed(queries, cells_g, scale_g, ids_g, valid_g):
    """Score gathered cells against their queries.

    cells_g [q, nprobe, cap, d] (int8 tiles or float), scale_g
    [q, nprobe, cap] f32 per-row scales (None for float storage), ids_g
    [q, nprobe, cap] global row ids (-1 pad), valid_g [q, nprobe] bool
    (None when every gathered cell is live — the single-device path).
    Returns flat per-query (scores [q, nprobe*cap], ids)."""

    def one_query(qv, cq, sq, iq, vq):
        # All scores accumulate to f32 (preferred_element_type) — the
        # contract the dtype-flow lint rule enforces on every matmul
        # with a low-precision operand (docs/STATIC_ANALYSIS.md): a bf16
        # score output loses ~3 significant digits and near-tie rankings
        # with it — measured recall@10 0.91 vs 1.0 (f32 scores) on a
        # clustered 60k corpus with identical cells.  int8 tiles convert
        # inline (-127..127 is exact in bf16) and the per-row scale
        # multiplies the f32 accumulation, so the dequantized score is
        # bit-identical whether the tile lives on one device or a shard.
        s = jnp.einsum(
            "pcd,d->pc", cq.astype(qv.dtype), qv,
            preferred_element_type=jnp.float32,
        )  # [nprobe, cap] f32
        if sq is not None:
            s = s * sq
        live = iq >= 0
        if vq is not None:
            live = live & vq[:, None]
        s = jnp.where(live, s, NEG_INF)
        return s.reshape(-1), iq.reshape(-1)

    if scale_g is None and valid_g is None:
        return jax.vmap(lambda q, c, i: one_query(q, c, None, i, None))(
            queries, cells_g, ids_g
        )
    if valid_g is None:
        return jax.vmap(lambda q, c, s, i: one_query(q, c, s, i, None))(
            queries, cells_g, scale_g, ids_g
        )
    return jax.vmap(one_query)(queries, cells_g, scale_g, ids_g, valid_g)


def _probe_kernel(
    cells: jax.Array,  # [C, cap, d] int8 tiles or float
    cell_scale: Optional[jax.Array],  # [C, cap] f32 (None: float storage)
    cell_ids: jax.Array,  # [C, cap] int32 global row ids (-1 pad)
    centroids: jax.Array,  # [C, d]
    spill: jax.Array,  # [S, d]
    spill_ids: jax.Array,  # [S]
    queries: jax.Array,  # [q, d]
    *,
    nprobe: int,
    k: int,
    n_real_cells: Optional[int] = None,
):
    """Single-device probe: coarse rank -> gather nprobe cells -> score
    -> top-k over cells + spill."""
    probe = _coarse_probe(queries, centroids, nprobe, n_real_cells)
    cell_s, cell_i = _score_probed(
        queries, cells[probe],
        cell_scale[probe] if cell_scale is not None else None,
        cell_ids[probe], None,
    )

    spill_s = jax.lax.dot_general(
        queries, spill, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [q, S]
    spill_s = jnp.where(spill_ids[None, :] >= 0, spill_s, NEG_INF)

    q_n = queries.shape[0]
    all_s = jnp.concatenate(
        [cell_s, jnp.broadcast_to(spill_s, (q_n, spill_s.shape[1]))], axis=1
    )
    all_i = jnp.concatenate(
        [cell_i,
         jnp.broadcast_to(spill_ids[None, :], (q_n, spill_ids.shape[0]))],
        axis=1,
    )
    vals, pos = jax.lax.top_k(all_s, k)
    return vals, jnp.take_along_axis(all_i, pos, axis=1)


def _probe_kernel_sharded(
    cells: jax.Array,  # [C_local, cap, d] int8 — this shard's tiles
    cell_scale: jax.Array,  # [C_local, cap] f32
    cell_ids: jax.Array,  # [C_local, cap] int32
    centroids: jax.Array,  # [C_pad, d] replicated
    spill: jax.Array,  # [S, d] replicated
    spill_ids: jax.Array,  # [S] replicated
    queries: jax.Array,  # [q, d] replicated
    *,
    nprobe: int,
    k: int,
    n_real_cells: int,
    axis: str,
):
    """``shard_map`` body: mesh-sharded probe with the 2-gather merge.

    The coarse score is replicated (every shard ranks the same
    centroids, so the global top-nprobe probe list is identical
    everywhere); each shard then gathers/scores only the probed cells it
    OWNS — non-local probe slots clamp to local cell 0 and are masked to
    -inf, so per-shard HBM reads stay ~nprobe/n_shards of the tier.
    Local top-k candidates (global row ids) merge through ``all_gather``
    of (vals, ids) + a replicated re-rank — exactly the collective
    content of the exact store's ``sharded_topk``, budgeted as program
    ``retrieve_ivf_sharded`` in shard_budget.json.  Spill rows are
    replicated but scored on shard 0 only, so the merge sees each
    exactly once."""
    c_local = cells.shape[0]
    shard = jax.lax.axis_index(axis)
    probe = _coarse_probe(queries, centroids, nprobe, n_real_cells)
    local = probe - shard * c_local
    valid = (local >= 0) & (local < c_local)  # [q, nprobe]
    safe = jnp.where(valid, local, 0)
    cell_s, cell_i = _score_probed(
        queries, cells[safe], cell_scale[safe], cell_ids[safe], valid
    )

    spill_s = jax.lax.dot_general(
        queries, spill, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [q, S]
    spill_live = (spill_ids[None, :] >= 0) & (shard == 0)
    spill_s = jnp.where(spill_live, spill_s, NEG_INF)

    q_n = queries.shape[0]
    all_s = jnp.concatenate(
        [cell_s, jnp.broadcast_to(spill_s, (q_n, spill_s.shape[1]))], axis=1
    )
    all_i = jnp.concatenate(
        [cell_i,
         jnp.broadcast_to(spill_ids[None, :], (q_n, spill_ids.shape[0]))],
        axis=1,
    )
    vals, pos = jax.lax.top_k(all_s, k)
    ids = jnp.take_along_axis(all_i, pos, axis=1)
    # the 2-gather top-k merge (vals + ids ride ICI; k*n_shards
    # candidates per query, not the corpus)
    all_vals = jax.lax.all_gather(vals, axis)
    all_ids = jax.lax.all_gather(ids, axis)
    return merge_topk(all_vals, all_ids, k)


def ivf_cell_specs(model_axis: str) -> Tuple[P, ...]:
    """``shard_map`` in_specs for the probe kernel's seven operands:
    cell tiles/scales/ids row-sharded over the model axis, centroids /
    spill / queries replicated.  Shared by ``IVFIndex._get_fn``, the
    fused tiered program (``engines/retrieve.py``) and the shard audit
    (``analysis/shard_audit.py:retrieve_ivf_sharded``) so the audited
    layout IS the serving layout."""
    return (
        P(model_axis, None, None),  # cells [C, cap, d]
        P(model_axis, None),  # cell_scale [C, cap]
        P(model_axis, None),  # cell_ids [C, cap]
        P(),  # centroids (replicated: coarse score everywhere)
        P(),  # spill
        P(),  # spill_ids
        P(),  # queries
    )


# ---------------------------------------------------------------------------
# IVF index
# ---------------------------------------------------------------------------

class IVFIndex:
    """Coarse-quantized cosine search over a fixed corpus snapshot.

    Build once from vectors+metadata (or straight from a ``VectorStore``);
    rebuild periodically as the store grows — the serving pattern (exact
    search over the live append tail + IVF over the compacted bulk, with
    background rebuild and host top-k merge) is implemented by
    ``index/tiered.py:TieredIndex`` and enabled via
    ``StoreConfig.serving_index="tiered"``.

    ``storage="int8"`` (default) stores the cells as int8 tiles with
    per-row scales; ``"float"`` keeps ``dtype`` cells (exact scores, 2x
    the bytes — single-device only).  ``mesh`` with ``n_model > 1``
    row-shards the cell tensors over the model axis and serves through
    the ``shard_map`` merge kernel; sharding requires (and forces) int8
    storage — HBM capacity is the reason the tier shards at all.
    """

    def __init__(
        self,
        vectors: np.ndarray,
        metadata: Sequence[Dict[str, Any]],
        n_clusters: Optional[int] = None,
        nprobe: int = 8,
        cap_factor: float = 1.5,
        n_iters: int = 10,
        seed: int = 0,
        dtype: str = "bfloat16",
        n_assign: int = 2,
        mesh=None,  # runtime.mesh.MeshContext: shard cells over model
        storage: str = "int8",
    ) -> None:
        vectors = np.asarray(vectors, np.float32)
        n, d = vectors.shape
        norms = np.linalg.norm(vectors, axis=1, keepdims=True)
        vectors = vectors / np.maximum(norms, 1e-9)
        self._meta = list(metadata)
        self.n = n
        self.dim = d
        c = n_clusters or max(1, int(np.sqrt(max(n, 1))))
        self.n_clusters = c
        self.nprobe = min(nprobe, c)
        self.n_assign = max(1, min(n_assign, c))
        self._dtype = jnp.dtype(dtype)
        self.mesh = mesh
        self._sharded = mesh is not None and mesh.n_model > 1
        if self._sharded and storage != "int8":
            # HBM capacity is the point of sharding; a float tier would
            # double shard bytes for recall the shadow estimator could
            # measure the absence of — the sharded tier is int8 tiles.
            log.warning(
                "sharded IVF tier forces int8 storage (requested %r)",
                storage,
            )
            storage = "int8"
        self.storage = storage
        self.n_real_cells = c
        n_shards = mesh.n_model if self._sharded else 1
        # cell rows round up to the shard count for even row shards;
        # padded rows carry zero centroids/tiles and id -1, and the
        # coarse probe masks them (n_real_cells) so they are never
        # probed on any path
        c_pad = -(-c // n_shards) * n_shards
        self.cells_per_shard = c_pad // n_shards

        with span("ivf_build", DEFAULT_REGISTRY):
            # rank more choices than copies: the placement cascade needs
            # fallback cells when a row's best cells are full
            n_choices = max(4, self.n_assign)
            centroids, assign = kmeans(
                vectors, c, n_iters=n_iters, seed=seed,
                n_assign=min(n_choices, c),
            )
            if c_pad != c:
                centroids = np.vstack(
                    [centroids, np.zeros((c_pad - c, d), np.float32)]
                )
            cap = max(8, int(np.ceil(cap_factor * self.n_assign * n / c)))
            cells = np.zeros((c_pad, cap, d), np.float32)
            cell_ids = np.full((c_pad, cap), -1, np.int32)
            fill = np.zeros((c_pad,), np.int64)

            def place(rows: np.ndarray, target_cells: np.ndarray) -> np.ndarray:
                """Vectorized cap-aware placement: rows[i] -> its slot in
                target_cells[i] when the cell has room.  Returns the boolean
                placed-mask.  (The round-1 build looped this in Python over
                1M rows — and let copies overflow into a spill buffer that
                every query then scanned exactly: 22% of a 1M clustered
                corpus spilled, adding ~170 MB of HBM reads per query.)"""
                if len(rows) == 0:
                    return np.zeros((0,), bool)
                order = np.argsort(target_cells, kind="stable")
                tc = target_cells[order]
                # position of each row within its cell group
                group_change = np.r_[True, tc[1:] != tc[:-1]]
                group_start = np.nonzero(group_change)[0]
                within = np.arange(len(tc)) - np.repeat(
                    group_start, np.diff(np.r_[group_start, len(tc)])
                )
                slot = fill[tc] + within
                ok = slot < cap
                r_ok, c_ok, s_ok = rows[order][ok], tc[ok], slot[ok]
                cells[c_ok, s_ok] = vectors[r_ok]
                cell_ids[c_ok, s_ok] = r_ok
                placed_per_cell = np.bincount(c_ok, minlength=c_pad)
                fill[:] = fill + placed_per_cell
                placed = np.zeros((len(rows),), bool)
                placed[order[ok]] = True
                return placed

            # pass 1 — primary copy, cascading to the best cell with room:
            # rank-r failures retry at rank r+1 instead of spilling
            primary_cell = np.full((n,), -1, np.int64)
            pending = np.arange(n)
            # assign has min(n_choices, c) columns — iterate what exists
            # (tiny-c builds with small cap_factor can exhaust every rank
            # and still have pending rows; they spill below)
            for r in range(assign.shape[1]):
                if len(pending) == 0:
                    break
                targets = assign[pending, r]
                placed = place(pending, targets)
                primary_cell[pending[placed]] = targets[placed]
                pending = pending[~placed]
            spill_rows = list(pending)
            # pass 2 — redundant copies (recall: boundary rows reachable
            # from either side), best-effort within remaining capacity.
            # Skip rows whose primary already cascaded into this rank's
            # cell: a duplicate (vector, id) in the same cell burns a slot
            # in exactly the overfull cells the cascade is relieving.
            for r in range(1, self.n_assign):
                everyone = np.arange(n)
                fresh = assign[everyone, r] != primary_cell[everyone]
                rows = everyone[fresh]
                place(rows, assign[rows, r])
            spill_n = max(1, len(spill_rows))
            spill = np.zeros((spill_n, d), np.float32)
            spill_ids = np.full((spill_n,), -1, np.int32)
            for j, i in enumerate(spill_rows):
                spill[j] = vectors[i]
                spill_ids[j] = i
            self.cap = cap
            self.n_spilled = len(spill_rows)

            if storage == "int8":
                cells_up, cell_scale = quantize_rows_int8(cells)
            else:
                cells_up, cell_scale = cells, None
            del cells  # the f32 staging buffer is the build's peak RSS

            def _upload_on_lane():
                # returns the uploaded arrays: strict mode must sync
                # every transfer before the lane frees
                if self._sharded:
                    m = self.mesh
                    specs = ivf_cell_specs(m.model_axis)

                    def put(arr, spec):
                        return jax.device_put(
                            arr, NamedSharding(m.mesh, spec)
                        )

                    self._cells = put(cells_up, specs[0])
                    self._cell_scale = put(cell_scale, specs[1])
                    self._cell_ids = put(cell_ids, specs[2])
                    self._centroids = put(
                        centroids.astype(self._dtype), specs[3]
                    )
                    self._spill = put(spill.astype(self._dtype), specs[4])
                    self._spill_ids = put(spill_ids, specs[5])
                else:
                    self._cells = (
                        jnp.asarray(cells_up)
                        if storage == "int8"
                        else jnp.asarray(cells_up, self._dtype)
                    )
                    self._cell_scale = (
                        jnp.asarray(cell_scale)
                        if cell_scale is not None
                        else None
                    )
                    self._cell_ids = jnp.asarray(cell_ids)
                    self._centroids = jnp.asarray(centroids, self._dtype)
                    self._spill = jnp.asarray(spill, self._dtype)
                    self._spill_ids = jnp.asarray(spill_ids)
                return tuple(
                    a
                    for a in (
                        self._cells, self._cell_scale, self._cell_ids,
                        self._centroids, self._spill, self._spill_ids,
                    )
                    if a is not None
                )

            spine_run("ivf_build", _upload_on_lane, stream="rebuild")
        self._fns: Dict[Tuple[int, int, int], Any] = {}
        log.info(
            "ivf built: n=%d C=%d cap=%d spill=%d nprobe=%d storage=%s "
            "shards=%d bytes/chunk=%.0f",
            n, c, cap, self.n_spilled, self.nprobe, self.storage,
            n_shards, self.index_bytes()["bytes_per_chunk"],
        )

    @classmethod
    def from_store(cls, store, **kw) -> "IVFIndex":
        """Snapshot the live exact store into an IVF index (consistent
        vectors/metadata pair even while the store keeps appending).
        Inherits the store's mesh so the tier shards where the store
        shards."""
        vectors, meta = store.vectors_snapshot()
        kw.setdefault("mesh", store.mesh)
        return cls(vectors, meta, **kw)

    def index_bytes(self) -> Dict[str, Any]:
        """Device-resident byte accounting for the tier — the
        ``/api/retrieval`` capacity surface reads this.  ``per_shard`` is
        what ONE device holds (sharded tensors split n_shards ways;
        centroids/spill replicate)."""
        sharded_b = sum(
            int(np.prod(a.shape)) * a.dtype.itemsize
            for a in (self._cells, self._cell_scale, self._cell_ids)
            if a is not None
        )
        repl_b = sum(
            int(np.prod(a.shape)) * a.dtype.itemsize
            for a in (self._centroids, self._spill, self._spill_ids)
        )
        n_shards = self.mesh.n_model if self._sharded else 1
        total = sharded_b + repl_b
        return {
            "total_bytes": total,
            "bytes_per_chunk": round(total / max(self.n, 1), 2),
            "per_shard_bytes": sharded_b // n_shards + repl_b,
            "shards": n_shards,
            "storage": self.storage,
        }

    def _get_fn(self, q: int, k: int, nprobe: int):
        key = (q, k, nprobe)
        fn = self._fns.get(key)
        if fn is None:
            if self._sharded:
                m = self.mesh
                kernel = functools.partial(
                    _probe_kernel_sharded,
                    nprobe=nprobe, k=k,
                    n_real_cells=self.n_real_cells,
                    axis=m.model_axis,
                )

                def sharded_probe_body(cells, scale, cids, cent, sp, sp_ids, q):
                    return kernel(cells, scale, cids, cent, sp, sp_ids, q)

                fn = jax.jit(
                    shard_map(
                        sharded_probe_body,
                        mesh=m.mesh,
                        in_specs=ivf_cell_specs(m.model_axis),
                        out_specs=(P(), P()),
                        check_vma=False,
                    )
                )
            else:
                fn = jax.jit(
                    functools.partial(_probe_kernel, nprobe=nprobe, k=k)
                )
            self._fns[key] = fn
        return fn

    def search(
        self,
        queries: np.ndarray,
        k: int = 10,
        nprobe: Optional[int] = None,
        dedup_full: bool = False,
    ) -> List[List[Tuple[float, int, Dict[str, Any]]]]:
        """Returns per query a list of (score, row_id, metadata).

        ``dedup_full``: return every unique candidate the probe fetched
        (up to ``k * (n_assign + 1)`` rows) instead of cutting at ``k``
        — the tiered exact re-rank widens its pool this way so a row the
        quantized ranking pushed just past ``k`` can be recovered at
        full precision (same device program either way)."""
        queries = np.asarray(queries, np.float32)
        if queries.ndim == 1:
            queries = queries[None]
        qn = queries / np.maximum(
            np.linalg.norm(queries, axis=1, keepdims=True), 1e-9
        )
        nprobe = min(nprobe or self.nprobe, self.n_clusters)
        k_eff = min(k, self.n)
        # over-fetch when rows live in multiple cells: the raw top list can
        # contain duplicate row ids, which the host dedups back down to k —
        # clamped to the probed candidate pool (top_k beyond it would crash)
        pool = nprobe * self.cap + int(self._spill_ids.shape[0])
        fetch = min(k_eff * (self.n_assign + 1), pool)
        fn = self._get_fn(len(qn), fetch, nprobe)

        def _probe_on_lane():
            v, i = fn(
                self._cells,
                self._cell_scale,
                self._cell_ids,
                self._centroids,
                self._spill,
                self._spill_ids,
                jnp.asarray(qn, self._dtype),
            )
            return np.asarray(v, np.float32), np.asarray(i)

        with span("ivf_search", DEFAULT_REGISTRY):
            vals, ids = spine_run("ivf_search", _probe_on_lane)
        return self._dedup_rows(vals, ids, fetch if dedup_full else k_eff)

    def _dedup_rows(
        self, vals: np.ndarray, ids: np.ndarray, k_eff: int
    ) -> List[List[Tuple[float, int, Dict[str, Any]]]]:
        """Host dedup of the raw top list (rows assigned to multiple
        cells appear once per probed copy) down to k_eff per query —
        shared by :meth:`search` and :meth:`timed_probe`."""
        out = []
        for qi in range(len(vals)):
            row = []
            seen = set()
            for score, rid in zip(vals[qi], ids[qi]):
                if rid < 0 or score <= NEG_INF / 2 or int(rid) in seen:
                    continue
                seen.add(int(rid))
                row.append((float(score), int(rid), self._meta[int(rid)]))
                if len(row) >= k_eff:
                    break
            out.append(row)
        return out

    def timed_probe(
        self,
        queries: np.ndarray,
        k: int = 10,
        nprobe: Optional[int] = None,
        dedup_full: bool = False,
    ) -> Tuple[List[List[Tuple[int, float]]], float, bool]:
        """One coarse probe at an explicit ``nprobe`` as a BACKGROUND
        work item, timed on the lane — the retrieval observatory's
        nprobe-frontier instrument (``obs/retrieval_observatory.py``).

        Returns ``(rows, seconds, fresh_compile)`` where rows are
        per-query ``(row_id, score)`` pairs and ``seconds`` covers
        dispatch + device + fetch as measured AROUND the device phase on
        the lane (queue wait excluded — the frontier's latency axis must
        reflect the probe, not background-stream scheduling).  The first
        call at a new (batch, k, nprobe) shape traces+compiles inside
        the timed window; ``fresh_compile`` flags exactly those samples
        so the observatory can exclude them from the latency axis (a
        per-nprobe first-sample drop would miss later compiles at new
        batch sizes).  Works identically against the sharded tier — the
        probe fn is the shard_map merge kernel there."""
        from time import perf_counter

        queries = np.asarray(queries, np.float32)
        if queries.ndim == 1:
            queries = queries[None]
        qn = queries / np.maximum(
            np.linalg.norm(queries, axis=1, keepdims=True), 1e-9
        )
        nprobe = min(nprobe or self.nprobe, self.n_clusters)
        k_eff = min(k, self.n)
        pool = nprobe * self.cap + int(self._spill_ids.shape[0])
        fetch = min(k_eff * (self.n_assign + 1), pool)
        # a cached wrapper has been invoked (and so compiled) before:
        # search() and timed_probe() both go through _get_fn and always
        # call the fn they get back
        fresh_compile = (len(qn), fetch, nprobe) not in self._fns
        fn = self._get_fn(len(qn), fetch, nprobe)

        def _shadow_probe_on_lane():
            t0 = perf_counter()
            v, i = fn(
                self._cells,
                self._cell_scale,
                self._cell_ids,
                self._centroids,
                self._spill,
                self._spill_ids,
                jnp.asarray(qn, self._dtype),
            )
            v = np.asarray(v, np.float32)
            i = np.asarray(i)
            return v, i, perf_counter() - t0

        vals, ids, seconds = spine_run(
            "retrieve_shadow", _shadow_probe_on_lane, stream="probe"
        )
        rows = [
            [(rid, score) for score, rid, _md in row]
            for row in self._dedup_rows(
                vals, ids, fetch if dedup_full else k_eff
            )
        ]
        return rows, seconds, fresh_compile
