"""Attention ops: XLA reference path + Pallas TPU flash kernel.

Replaces what the reference outsourced entirely (attention lived inside
Ollama/llama.cpp and torch sentence-transformers — ``llm-qa/main.py:66-69``,
``semantic-indexer/indexer.py:21``).  Design per SURVEY §5 "long-context":
the kernel is blockwise over the KV axis with online softmax, so the sequence
axis can shard across devices — ``parallel/ring_attention.py`` reuses the
same blockwise accumulation over an ICI ring.

Layouts:
  q        [batch, q_len, num_q_heads, head_dim]
  k, v     [batch, kv_len, num_kv_heads, head_dim]   (GQA: q_heads % kv_heads == 0)
  lengths  [batch] int32 — valid KV prefix per example (padding mask)

The dispatcher :func:`attention` picks the Pallas kernel on TPU and the pure
XLA path elsewhere (CPU tests run the kernel in interpret mode explicitly).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


# --------------------------------------------------------------------------
# Reference XLA implementation (also the CPU path and the golden model)
# --------------------------------------------------------------------------

def attention_reference(
    q,
    k,
    v,
    *,
    causal: bool = False,
    lengths: Optional[jax.Array] = None,
    q_offset: Optional[jax.Array] = None,
    sliding_window: Optional[int] = None,
    scale: Optional[float] = None,
):
    """Plain XLA attention.  f32 softmax, bf16 matmuls via preferred type.

    The upcast-before-math recipe below (``astype(float32)`` on q/k/v,
    softmax over f32 scores) is the dtype contract the ``dtype-flow``
    lint rule enforces tree-wide (docs/STATIC_ANALYSIS.md): a bf16
    operand reaching an einsum/softmax without this upcast is a red
    build, not a convention.

    ``q_offset`` [batch]: absolute position of q[:, 0] (decode steps where
    q_len << kv_len).  Defaults to aligning the *ends* of q and kv when
    causal (standard prefill/decode convention).
    """
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    groups = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    if sliding_window is not None and not causal:
        raise ValueError("sliding_window requires causal=True (bidirectional local attention is not implemented)")

    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    if groups > 1:
        kf = jnp.repeat(kf, groups, axis=2)
        vf = jnp.repeat(vf, groups, axis=2)

    # [b, h, sq, skv]
    scores = jnp.einsum("bqhd,bkhd->bhqk", qf, kf)

    kv_pos = jnp.arange(skv)[None, None, None, :]
    mask = jnp.ones((b, 1, sq, skv), dtype=bool)
    if lengths is not None:
        mask &= kv_pos < lengths[:, None, None, None]
    if causal:
        if q_offset is None:
            q_abs = jnp.arange(sq)[None, :] + (
                (lengths[:, None] if lengths is not None else skv) - sq
            )
        else:
            q_abs = jnp.arange(sq)[None, :] + q_offset[:, None]
        q_abs = q_abs[:, None, :, None]  # [b,1,sq,1]
        mask &= kv_pos <= q_abs
        if sliding_window is not None:
            mask &= kv_pos > q_abs - sliding_window
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    # a row with no valid kv position (can only happen on padding rows)
    # outputs zeros, matching the flash kernel
    probs = jnp.where(jnp.any(mask, axis=-1, keepdims=True), probs, 0.0)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, vf)
    return out.astype(q.dtype)


# --------------------------------------------------------------------------
# Ragged / paged attention (block-table KV; arXiv 2604.15464 contract)
# --------------------------------------------------------------------------

# Sequence starts inside a packed ragged-prefill batch are aligned to this
# many rows.  The alignment exists for EXACTNESS, not speed: XLA's softmax
# reductions (strided SIMD accumulators, power-of-two trees, or sequential
# sums) all produce bitwise-identical partial sums when the non-zero
# segment of a masked row starts at a multiple of the reduction's lane
# width — so a prompt prefilled at offset 128k yields the SAME tokens as
# the solo engine's offset-0 prefill, which is the serve-vs-solo
# token-equality invariant every batcher test pins.  A production Pallas
# RPA kernel packs densely and masks in-kernel instead; this is the XLA
# reference path's price for bitwise parity.
RAGGED_ALIGN = 128


def ragged_prefill_attention(q, k, v, seg_ids, positions, *,
                             sliding_window=None, scale=None,
                             k_pool=None, v_pool=None, block_tables=None,
                             prefix_lens=None, n_prefix_rows=0,
                             block_size=None):
    """Self-attention over a PACKED batch of variable-length prompts —
    the prefill half of Ragged Paged Attention, XLA reference path.

    q, k, v   [T, heads, d] — ONE flat token axis; each prompt occupies a
              contiguous run of rows (starts aligned to RAGGED_ALIGN)
    seg_ids   [T] int32 — sequence id per token; negative = padding row
    positions [T] int32 — position of each token within its own sequence

    A token attends only within its own segment, causally by position
    (plus the optional sliding window).  f32 softmax, same dtype contract
    as :func:`attention_reference`; padding rows output zeros.  There is
    no shape family here: any mix of prompt lengths that fits T shares
    one compiled program.

    Computed in RAGGED_ALIGN-row query blocks (a ``lax.map`` over the
    packed axis) so the score transient is O(heads x ALIGN x T), never
    the full O(heads x T x T) — at a 4096-token budget and 7B head
    count the quadratic form would be ~2 GB of f32 per layer, which the
    bucketed prefill this replaced never materialized.  Per-row numerics
    are IDENTICAL to the single-shot form (each row still reduces over
    the same [T] axis), so the block split cannot perturb greedy
    outputs.  The Pallas RPA kernel that also skips cross-segment
    blocks entirely is the TPU follow-up.

    WARM mode (``n_prefix_rows > 0``, the copy-on-write prefix-cache
    path, docqa-prefix): each segment may additionally attend a CACHED
    prompt prefix read from the paged KV pool through its block table.
    ``positions`` then start at the segment's prefix length, and the key
    axis becomes ``[n_prefix_rows ; T]`` — per query block, the owning
    lane's first ``prefix_lens[lane]`` pool rows are gathered in
    position order ahead of the packed keys.  Because a shared prefix is
    RAGGED_ALIGN-aligned (engines/paged.py ``share_alignment``), every
    valid key keeps its position residue mod the alignment, and the
    pool's stored K/V are the very bf16 values a cold prefill would
    compute in-flight — so the softmax reduction trees, and therefore
    the sampled tokens, are bitwise identical to prefilling the whole
    prompt cold.  ``n_prefix_rows`` is a static shape (the sequence
    capacity); unused rows are masked.  Segment starts must be aligned
    (each query block then belongs to exactly one segment, so one block
    table row serves the whole block).
    """
    t, hq, d = q.shape
    _, hkv, _ = k.shape
    groups = hq // hkv
    scale = scale if scale is not None else d ** -0.5

    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    if groups > 1:
        kf = jnp.repeat(kf, groups, axis=1)
        vf = jnp.repeat(vf, groups, axis=1)

    valid = seg_ids >= 0
    # n_prefix_rows is a STATIC host int (the batcher's seq capacity) —
    # never a tracer; no cast so the jit-purity host-sync rule stays
    # meaningful here
    warm = n_prefix_rows > 0
    if warm:
        if t % RAGGED_ALIGN:
            raise ValueError(
                "warm ragged prefill needs a RAGGED_ALIGN-multiple "
                f"packed axis (got T={t})"
            )
        n_blocks = k_pool.shape[0] // block_size
        pool_rows = k_pool.shape[0]
        pfx_cols = jnp.arange(n_prefix_rows)

    def attend_rows(row_idx):
        """One query block: rows ``row_idx`` [bq] against all keys."""
        qb = qf[row_idx]  # [bq, hq, d]
        seg_q = seg_ids[row_idx]
        pos_q = positions[row_idx]
        scores = jnp.einsum("qhd,khd->hqk", qb, kf)  # [hq, bq, T]
        mask = (
            (seg_q[:, None] == seg_ids[None, :])
            & (valid[row_idx][:, None] & valid[None, :])
            & (positions[None, :] <= pos_q[:, None])
        )
        if sliding_window is not None:
            mask &= positions[None, :] > pos_q[:, None] - sliding_window
        mask = mask[None, :, :]  # [1, bq, T]
        if not warm:
            scores = jnp.where(mask, scores, NEG_INF)
            probs = jax.nn.softmax(scores, axis=-1)
            # fully-masked rows (padding) output zeros, like the dense
            # path
            probs = jnp.where(
                jnp.any(mask, axis=-1, keepdims=True), probs, 0.0
            )
            return jnp.einsum("hqk,khd->qhd", probs, vf)  # [bq, hq, d]
        # ---- warm: prepend the lane's cached prefix K/V (pool rows in
        # position order) to the key axis.  Aligned segment starts mean
        # this whole query block belongs to ONE lane (or is padding).
        lane = jnp.max(seg_q)  # -1 when the block is all padding
        lane_c = jnp.maximum(lane, 0)
        row_tab = jax.lax.dynamic_index_in_dim(
            block_tables, lane_c, axis=0, keepdims=False
        )  # [NB]
        blk = row_tab[pfx_cols // block_size]
        rows = jnp.minimum(
            blk * block_size + pfx_cols % block_size, pool_rows - 1
        )
        kp = k_pool[rows].astype(jnp.float32)  # [PFX, hkv, d]
        vp = v_pool[rows].astype(jnp.float32)
        if groups > 1:
            kp = jnp.repeat(kp, groups, axis=1)
            vp = jnp.repeat(vp, groups, axis=1)
        plen = jax.lax.dynamic_index_in_dim(
            prefix_lens, lane_c, axis=0, keepdims=False
        )
        scores_p = jnp.einsum("qhd,khd->hqk", qb, kp)  # [hq, bq, PFX]
        mask_p = (
            (lane >= 0)
            & valid[row_idx][:, None]
            & (pfx_cols[None, :] < plen)
            & (blk[None, :] < n_blocks)
            & (pfx_cols[None, :] <= pos_q[:, None])
        )
        if sliding_window is not None:
            mask_p &= pfx_cols[None, :] > pos_q[:, None] - sliding_window
        mask_p = mask_p[None, :, :]  # [1, bq, PFX]
        # ONE flat softmax over [prefix ; packed] in position order:
        # masked rows contribute exact zeros, and alignment keeps every
        # valid key's reduction-tile residue — bitwise equal to cold
        full_scores = jnp.concatenate([scores_p, scores], axis=-1)
        full_mask = jnp.concatenate([mask_p, mask], axis=-1)
        full_scores = jnp.where(full_mask, full_scores, NEG_INF)
        probs = jax.nn.softmax(full_scores, axis=-1)
        probs = jnp.where(
            jnp.any(full_mask, axis=-1, keepdims=True), probs, 0.0
        )
        vcat = jnp.concatenate([vp, vf], axis=0)  # [PFX + T, hq, d]
        return jnp.einsum("hqk,khd->qhd", probs, vcat)

    if t % RAGGED_ALIGN or t <= RAGGED_ALIGN:
        out = attend_rows(jnp.arange(t))
    else:
        blocks = jnp.arange(t).reshape(t // RAGGED_ALIGN, RAGGED_ALIGN)
        out = jax.lax.map(attend_rows, blocks).reshape(t, hq, d)
    return out.astype(q.dtype)


def gather_paged_kv(pool, block_tables, block_size):
    """Gather a per-sequence contiguous KV view out of a flat block pool.

    pool         [P, kv_heads, d] — P = n_blocks * block_size flat rows
    block_tables [S, NB] int32 — block ids per sequence; ids >= n_blocks
                 are holes (unallocated tail), clamped and later masked
                 by the caller's ``lengths``

    Returns [S, NB * block_size, kv_heads, d]: row p of sequence s is
    that sequence's token-position p, exactly the layout a dense
    per-lane cache would have — so downstream attention reductions are
    bitwise identical to the contiguous-cache path.
    """
    S, nb = block_tables.shape
    L = nb * block_size
    P = pool.shape[0]
    cols = jnp.arange(L)
    blk = jnp.take(block_tables, cols // block_size, axis=1)  # [S, L]
    rows = jnp.minimum(blk * block_size + cols[None, :] % block_size, P - 1)
    return pool[rows]


def paged_decode_attention(q, k_pool, v_pool, block_tables, lengths, *,
                           block_size, q_offset=None, sliding_window=None,
                           scale=None, use_flash=False, mesh=None):
    """Decode-side attention through a block table (the decode half of
    Ragged Paged Attention).  XLA reference path: gather the pages into a
    per-sequence contiguous view, then run the standard masked kernel —
    a TPU Pallas kernel would stream pages without materializing the
    gather; this backs it the same way :func:`attention_reference` backs
    :func:`flash_attention`.

    q            [S, s, q_heads, d] (s = 1 plain step, K spec verify)
    k/v_pool     [P, kv_heads, d] flat block pool
    block_tables [S, NB] int32
    lengths      [S] valid kv length per sequence AFTER this step
    """
    k = gather_paged_kv(k_pool, block_tables, block_size)
    v = gather_paged_kv(v_pool, block_tables, block_size)
    attn_fn = (
        functools.partial(flash_attention, mesh=mesh)
        if use_flash
        else attention_reference
    )
    return attn_fn(
        q, k, v, causal=True, lengths=lengths, q_offset=q_offset,
        sliding_window=sliding_window, scale=scale,
    )


# --------------------------------------------------------------------------
# Pallas flash kernel
# --------------------------------------------------------------------------

def _flash_kernel(
    # scalar prefetch
    lengths_ref,  # [b] int32 valid kv length
    qoff_ref,  # [b] int32 absolute position of q row 0
    # blocks
    q_ref,  # [1, bq, d]
    k_ref,  # [1, bkv, d]
    v_ref,  # [1, bkv, d]
    o_ref,  # [1, bq, d]
    # scratch
    acc_ref,  # [bq, d] f32
    m_ref,  # [bq, 128] f32 running max (lane-replicated)
    l_ref,  # [bq, 128] f32 running denom
    *,
    causal: bool,
    sliding_window: Optional[int],
    scale: float,
    block_kv: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_kv = pl.num_programs(2)

    # grid dim 0 is batch*q_heads; recover the batch index for scalars
    batch = pl.program_id(0) // (pl.num_programs(0) // lengths_ref.shape[0])
    kv_len = lengths_ref[batch]
    q_off = qoff_ref[batch]

    bq = q_ref.shape[1]

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    kv_start = ki * block_kv
    q_rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, block_kv), 0)
    kv_cols = kv_start + jax.lax.broadcasted_iota(jnp.int32, (bq, block_kv), 1)
    q_abs = q_rows + q_off

    mask = kv_cols < kv_len
    if causal:
        mask &= kv_cols <= q_abs
        if sliding_window is not None:
            mask &= kv_cols > q_abs - sliding_window

    # Skip fully-masked blocks: past kv_len, beyond the causal frontier, or
    # entirely before the sliding window of every q row in this block.
    block_live = kv_start < kv_len
    if causal:
        q_abs_max = qi * bq + bq - 1 + q_off
        block_live &= kv_start <= q_abs_max
        if sliding_window is not None:
            q_abs_min = qi * bq + q_off
            block_live &= kv_start + block_kv > q_abs_min - sliding_window

    @pl.when(block_live)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bq, bkv]
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]  # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        # explicit re-mask: in a fully-masked block m_new == NEG_INF and
        # exp(s - m_new) would be 1, not 0
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)  # [bq, bkv]
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)

        v = v_ref[0].astype(jnp.float32)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bq, d]
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == n_kv - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention(
    q,
    k,
    v,
    *,
    causal: bool = False,
    lengths: Optional[jax.Array] = None,
    q_offset: Optional[jax.Array] = None,
    sliding_window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_kv: int = 512,
    interpret: bool = False,
    mesh=None,
):
    """Blockwise flash attention as a Pallas TPU kernel.

    Grid: (batch*q_heads, q_blocks, kv_blocks) — the kv axis is innermost so
    the online-softmax scratch carries across kv steps on one core.  GQA is
    handled by indexing the kv head as ``q_head // group``.

    ``mesh`` (a ``MeshContext`` with more than one device): GSPMD cannot
    partition a Mosaic custom call, so the kernel runs under ``shard_map``
    — batch over the data axis, heads over the model axis (heads are
    independent, and contiguous head shards keep every q head next to its
    kv head), which is the layout the decoder's caches already have.
    """
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    if hq % hkv:
        raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
    if sliding_window is not None and not causal:
        raise ValueError("sliding_window requires causal=True (bidirectional local attention is not implemented)")
    scale = scale if scale is not None else d ** -0.5
    if lengths is None:
        lengths = jnp.full((b,), skv, jnp.int32)
    if q_offset is None:
        q_offset = lengths - sq if causal else jnp.zeros((b,), jnp.int32)
    if mesh is not None and mesh.n_devices > 1:
        if hkv % mesh.n_model or b % mesh.n_data:
            raise ValueError(
                f"flash_attention on a {mesh.n_data}x{mesh.n_model} mesh "
                f"needs kv heads ({hkv}) divisible by the model axis and "
                f"batch ({b}) by the data axis"
            )
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        local = functools.partial(
            flash_attention, causal=causal, sliding_window=sliding_window,
            scale=scale, block_q=block_q, block_kv=block_kv,
            interpret=interpret,
        )
        heads = P(mesh.data_axis, None, mesh.model_axis, None)
        lanes = P(mesh.data_axis)
        return shard_map(
            lambda q, k, v, n, off: local(q, k, v, lengths=n, q_offset=off),
            mesh=mesh.mesh,
            in_specs=(heads, heads, heads, lanes, lanes),
            out_specs=heads,
            check_vma=False,
        )(q, k, v, lengths, q_offset)
    groups = hq // hkv

    block_q = min(block_q, sq)
    block_kv = min(block_kv, skv)

    # Pad seq lengths up to block multiples (static shapes; masked out).
    pq = (-sq) % block_q
    pkv = (-skv) % block_kv
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pkv:
        k = jnp.pad(k, ((0, 0), (0, pkv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pkv), (0, 0), (0, 0)))
    sq_p, skv_p = sq + pq, skv + pkv

    # [b, s, h, d] -> [b*h, s, d]
    qr = q.transpose(0, 2, 1, 3).reshape(b * hq, sq_p, d)
    kr = k.transpose(0, 2, 1, 3).reshape(b * hkv, skv_p, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * hkv, skv_p, d)

    grid = (b * hq, sq_p // block_q, skv_p // block_kv)

    kernel = functools.partial(
        _flash_kernel,
        causal=causal,
        sliding_window=sliding_window,
        scale=scale,
        block_kv=block_kv,
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            # index maps receive (grid..., *scalar_prefetch_refs)
            in_specs=[
                pl.BlockSpec(
                    (1, block_q, d), lambda h, qi, ki, *_: (h, qi, 0)
                ),
                pl.BlockSpec(
                    (1, block_kv, d),
                    lambda h, qi, ki, *_, groups=groups: (h // groups, ki, 0),
                ),
                pl.BlockSpec(
                    (1, block_kv, d),
                    lambda h, qi, ki, *_, groups=groups: (h // groups, ki, 0),
                ),
            ],
            out_specs=pl.BlockSpec(
                (1, block_q, d), lambda h, qi, ki, *_: (h, qi, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),
                pltpu.VMEM((block_q, 128), jnp.float32),
                pltpu.VMEM((block_q, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq_p, d), q.dtype),
        interpret=interpret, name="_flash_kernel",
    )(lengths.astype(jnp.int32), q_offset.astype(jnp.int32), qr, kr, vr)

    out = out.reshape(b, hq, sq_p, d).transpose(0, 2, 1, 3)
    return out[:, :sq]


# --------------------------------------------------------------------------
# Dispatcher
# --------------------------------------------------------------------------

_FLASH_ONLY_KWARGS = ("block_q", "block_kv", "interpret", "mesh")


def attention(q, k, v, **kwargs):
    """Use the Pallas kernel on TPU, the XLA path elsewhere.

    Platform is resolved from the default backend (a host-side constant), not
    from the arrays — this function is called from inside ``jit`` where the
    inputs are tracers.
    """
    if jax.default_backend() == "tpu" and q.shape[-1] % 64 == 0:
        return flash_attention(q, k, v, **kwargs)
    for kw in _FLASH_ONLY_KWARGS:
        kwargs.pop(kw, None)
    return attention_reference(q, k, v, **kwargs)
