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

Paged KV (``engines/paged.py``): K and V live in flat block pools
``[n_blocks * block_size, num_kv_heads, head_dim]`` addressed through block
tables.  :func:`paged_decode_attention` is the decode step's attention over
them: on a TPU the Pallas kernel :func:`paged_flash_decode`, which DMAs each
lane's live pages out of the pool; elsewhere the gather reference.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from docqa_tpu.ops.scopes import scope
from docqa_tpu.utils import round_up

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


def _grouped_prefill_attention(q, k, v, seg_ids, positions, window, scale,
                               max_segment=None):
    """:func:`ragged_prefill_attention`, cold, for GROUPED heads: K and V
    keep their few kv heads (one float32 copy of each, never ``groups``
    of them) and a kv head's query heads go through one product with it.

    A row REACHES back no further than ``window`` rows, and no further
    than its segment's start: ``max_segment`` rows at most (the sequence
    capacity, where the caller states it).  Where that reach is at most
    half the packed axis a query block of ``RAGGED_ALIGN`` rows takes its
    keys from the ``reach + RAGGED_ALIGN`` packed rows that END with it —
    a segment is one contiguous run in position order, so every key a row
    of the block can see lies there — and the key blocks wholly outside
    are never multiplied: ``(reach + ALIGN) / T`` of the causal form's
    scores.  The masks and the float32 softmax are the general form's; a
    row reduces over the keys it is handed, in which every key it does
    not see is an exact zero."""
    t, hq, d = q.shape
    hkv = k.shape[1]
    span, front = t, 0
    reach = min(r for r in (window, max_segment, t) if r is not None)
    near = round_up(reach, RAGGED_ALIGN) + RAGGED_ALIGN
    if t >= 2 * near:
        # so that no slice starts before row 0
        span, front = near, near - RAGGED_ALIGN
    kf = jnp.pad(k.astype(jnp.float32), ((front, 0), (0, 0), (0, 0)))
    vf = jnp.pad(v.astype(jnp.float32), ((front, 0), (0, 0), (0, 0)))
    seg_k = jnp.pad(seg_ids, (front, 0), constant_values=-1)
    pos_k = jnp.pad(positions, (front, 0))

    def attend_block(r0):
        rows = r0 + jnp.arange(RAGGED_ALIGN)
        qb = (q[rows].astype(jnp.float32) * scale).reshape(
            RAGGED_ALIGN, hkv, hq // hkv, d)
        seg_q, pos_q = seg_ids[rows], positions[rows]
        # the keys of this block: all of them, or the span that ends here
        start = r0 if front else 0
        keys, values, seg_b, pos_b = (
            jax.lax.dynamic_slice_in_dim(a, start, span)
            for a in (kf, vf, seg_k, pos_k))
        mask = (
            (seg_q[:, None] == seg_b[None, :])
            & (seg_q >= 0)[:, None]
            & (pos_b[None, :] <= pos_q[:, None])
        )
        if window is not None:
            mask &= pos_b[None, :] > pos_q[:, None] - window
        mask = mask[None, None]
        scores = jnp.where(
            mask, jnp.einsum("qhgd,khd->hgqk", qb, keys), NEG_INF)
        probs = jnp.where(
            jnp.any(mask, axis=-1, keepdims=True),
            jax.nn.softmax(scores, axis=-1), 0.0)
        return jnp.einsum("hgqk,khd->qhgd", probs, values)

    out = jax.lax.map(attend_block, jnp.arange(0, t, RAGGED_ALIGN))
    return out.reshape(t, hq, v.shape[-1]).astype(q.dtype)


def ragged_prefill_attention(q, k, v, seg_ids, positions, *,
                             sliding_window=None, scale=None,
                             k_pool=None, v_pool=None, block_tables=None,
                             prefix_lens=None, n_prefix_rows=0,
                             block_size=None, grouped_heads=False,
                             max_segment=None, use_flash=False):
    """Self-attention over a PACKED batch of variable-length prompts —
    the prefill half of Ragged Paged Attention.

    Which form runs where: under ``use_flash``
    (``models/decoder.kernel_forms``'s ``ragged``: a TPU, no mesh, heads
    of whole 128-lane registers) a COLD dispatch attends in the Pallas
    kernel :func:`ragged_flash_prefill`, which visits only the key blocks
    a query block can see.  Everything else is the XLA forms below: every
    CPU run, a mesh, a WARM dispatch (no cell serves one on a chip), and
    the reference the kernel is tested and self-checked against.

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
    outputs.

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
    prompt cold IN THE XLA FORM (a CPU, a mesh): warm = cold bitwise is
    the XLA pair's property.  Where the cold dispatch runs the kernel (a
    TPU alone) a hit and a miss agree to the warm-up self-check's
    tolerance, as the decode kernel and the solo engine already do.
    ``n_prefix_rows`` is a static shape (the sequence
    capacity); unused rows are masked.  Segment starts must be aligned
    (each query block then belongs to exactly one segment, so one block
    table row serves the whole block).

    ``grouped_heads`` (cold, a whole number of ``RAGGED_ALIGN`` rows):
    K and V are not repeated over a group's query heads, and where a row
    reaches back (its ``sliding_window``; ``max_segment``, the rows of the
    longest segment there can be) at most half the packed axis, the key
    blocks wholly out of a query block's reach are not multiplied
    (:func:`_grouped_prefill_attention`) — the form of a stack whose
    attention layers hold several kv heads (``engines/paged.py``).
    """
    t, hq, d = q.shape
    _, hkv, _ = k.shape
    groups = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    if use_flash and not n_prefix_rows:
        return ragged_flash_prefill(
            q, k, v, seg_ids, positions, sliding_window=sliding_window,
            scale=scale, max_segment=max_segment)
    if grouped_heads and not n_prefix_rows and t % RAGGED_ALIGN == 0:
        return _grouped_prefill_attention(
            q, k, v, seg_ids, positions, sliding_window, scale, max_segment)

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
        out = jax.lax.map(attend_rows, blocks).reshape(t, hq, v.shape[-1])
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

    This is the REFERENCE, not the served path: it copies every slot's
    whole block table, allocated or not, so its cost is the table's span
    (S x NB x block_size rows per pool), whatever is live.  On a TPU the
    decode step reads pages in place (:func:`paged_flash_decode`); this
    backs the CPU path, the tests and the warm-up self-check.
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
    Ragged Paged Attention).

    ``use_flash`` (``models/decoder.kernel_forms``'s ``paged``: a TPU and
    a head geometry the kernel reads, :func:`paged_kernel_supported`): the
    Pallas kernel :func:`paged_flash_decode` reads each lane's live pages
    straight out of the pool — nothing of the table's span or the pool's
    size is gathered, transposed or fetched.  Otherwise (every CPU run)
    the XLA reference: gather the pages into a per-sequence contiguous
    view, then the standard masked attention — the plain reference the
    kernel is tested against, the same way :func:`attention_reference`
    backs :func:`flash_attention`.

    q            [S, s, q_heads, d] (s = 1 plain step, K spec verify)
    k/v_pool     [P, kv_heads, d] flat block pool
    block_tables [S, NB] int32
    lengths      [S] valid kv length per sequence AFTER this step
    """
    if use_flash:
        return paged_flash_decode(
            q, k_pool, v_pool, block_tables, lengths, q_offset,
            block_size=block_size, sliding_window=sliding_window,
            scale=scale, mesh=mesh,
        )
    k = gather_paged_kv(k_pool, block_tables, block_size)
    v = gather_paged_kv(v_pool, block_tables, block_size)
    return attention_reference(
        q, k, v, causal=True, lengths=lengths, q_offset=q_offset,
        sliding_window=sliding_window, scale=scale,
    )


def paged_latent_decode_attention(q_lat, q_rope, pool, block_tables, lengths,
                                  *, block_size, q_offset, scale):
    """Decode-side attention of the LATENT block (models/latent.py)
    through a block table, in its absorbed form: every head's key is the
    pool row itself — the normed latent ‖ the one rotated key all heads
    share — and its value the row's latent part, so one gather of a
    lane's pages serves scores and output, and no per-head key or value
    of a cached row is ever formed.  XLA reference (gather the table's
    span, mask by ``lengths``), as :func:`gather_paged_kv` backs the GQA
    block; f32 softmax, the same dtype contract.

    q_lat        [S, s, heads, r]  queries carried into latent space
    q_rope       [S, s, heads, dr] rotated query parts
    pool         [P, 1, r + dr] flat block pool (one shared row a token)
    block_tables [S, NB] int32;  lengths [S] valid rows AFTER this step
    q_offset     [S] absolute position of q row 0;  scale: softmax scale

    Returns [S, s, heads, r] (float32 sums cast to q's type): the
    attention-weighted latent, which the caller carries back through the
    value half of the up-projection."""
    r = q_lat.shape[-1]
    rows = gather_paged_kv(pool, block_tables, block_size)[:, :, 0, :]
    rows = rows.astype(jnp.float32)  # [S, L, r + dr]
    qf = jnp.concatenate([q_lat, q_rope], axis=-1).astype(jnp.float32) * scale
    scores = jnp.einsum("bqhd,bkd->bhqk", qf, rows)
    kv_pos = jnp.arange(rows.shape[1])[None, None, None, :]
    q_abs = (jnp.arange(q_lat.shape[1])[None, :] + q_offset[:, None])
    mask = (kv_pos < lengths[:, None, None, None]) & (
        kv_pos <= q_abs[:, None, :, None]
    )
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    probs = jnp.where(jnp.any(mask, axis=-1, keepdims=True), probs, 0.0)
    out = jnp.einsum("bhqk,bkd->bqhd", probs, rows[..., :r])
    return out.astype(q_lat.dtype)


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
# Pallas ragged prefill kernel
# --------------------------------------------------------------------------

# Key rows a grid step of the ragged prefill kernel covers: the largest of
# these that divides the packed axis; a query block is RAGGED_ALIGN rows
# (one segment's, or padding).  Of 128 / 256 / 512 query rows x 128 / 256 /
# 512 key rows, 128 x 512 was the fastest in a 9,728-row global layer of
# 32 / 4 heads (9.98 ms; 11.05 at 256 x 512, 17.88 at 128 x 256), in its
# window layer (4.71; 5.26; 7.66), and in a 512-row call of 32 / 8 heads
# (62 us; 81; 85) — v5e, chip run, PR 49 call 1; PERF.md §6.
RAGGED_KEY_BLOCKS = (512, 256, 128)


def ragged_key_block_rows(t: int) -> int:
    """Key rows a grid step of :func:`ragged_flash_prefill` covers on a
    packed axis of ``t`` rows — read from the shape."""
    fits = [b for b in RAGGED_KEY_BLOCKS if t % b == 0]
    if not fits:
        raise ValueError(
            f"ragged_flash_prefill needs a packed axis of whole "
            f"{RAGGED_ALIGN}-row blocks (got T={t})")
    return fits[0]


def ragged_key_steps(t, window, max_segment, block_kv) -> int:
    """The most key blocks one query block can see — the kernel's static
    innermost grid extent: a row reaches back less than ``window``, and
    less than ``max_segment`` (the rows of the longest segment there can
    be), so a query block's keys lie in ``reach - 1 + RAGGED_ALIGN``
    consecutive rows, at whatever offset inside a key block."""
    reach = min(r for r in (window, max_segment, t) if r is not None)
    return min((reach + RAGGED_ALIGN - 2) // block_kv + 2, t // block_kv)


def ragged_key_blocks(seg_ids, positions, window, block_kv, xp=jnp):
    """(first, count) int32 [T / RAGGED_ALIGN]: the key blocks of
    ``block_kv`` packed rows a query block of ``RAGGED_ALIGN`` rows can
    see — ``count`` consecutive ones from ``first``, 0 for a block of
    padding.

    A segment is one contiguous run in position order, so the key at
    position ``p - n`` of row ``r`` (position ``p``) is row ``r - n``: a
    live row sees the rows ``r - min(p, window - 1) .. r`` and nothing
    else, and a query block — one segment's rows, or padding — the rows
    from its first live row's first key to its last live row: every block
    of the range has a pair the mask lets through.  ``xp``: ``numpy`` for
    the host's counters (the batcher), the same arithmetic."""
    t = seg_ids.shape[0]
    rows = xp.arange(t, dtype=xp.int32)
    live = seg_ids >= 0
    back = positions if window is None else xp.minimum(positions, window - 1)
    seen_from = xp.where(live, xp.maximum(rows - back, 0), t)
    first = seen_from.reshape(-1, RAGGED_ALIGN).min(axis=1) // block_kv
    last = xp.where(live, rows, -1).reshape(-1, RAGGED_ALIGN).max(axis=1)
    count = xp.where(last >= 0, last // block_kv - first + 1, 0)
    first = xp.where(count > 0, first, 0)
    return first.astype(xp.int32), count.astype(xp.int32)


def ragged_key_block_counts(seg_ids, positions, window, max_segment=None):
    """(visited, packed): the (query block, key block) pairs one call of
    :func:`ragged_flash_prefill` multiplies on this packing, and the pairs
    of the whole packed square (what a form that masks and does not skip
    multiplies) — host arithmetic on ``numpy`` arrays, in units of one
    grid step, for the batcher's counters."""
    t = len(seg_ids)
    bk = ragged_key_block_rows(t)
    _, count = ragged_key_blocks(
        np.asarray(seg_ids), np.asarray(positions), window, bk, xp=np)
    steps = ragged_key_steps(t, window, max_segment, bk)
    visited = int(np.minimum(count, steps).sum())
    return visited, (t // RAGGED_ALIGN) * (t // bk)


def _ragged_prefill_kernel(
    # scalar prefetch, [T / bq] int32 each (:func:`ragged_key_blocks`)
    first_ref,  # the first key block a query block sees
    count_ref,  # how many it sees (0: a block of padding)
    # blocks
    q_ref,  # [bq, groups * d]: a kv head's query heads side by side
    k_ref,  # [bk, d]
    v_ref,  # [bk, d]
    seg_q_ref,  # [bq, 128] int32, a row's value in every lane
    pos_q_ref,
    seg_k_ref,  # [8, bk] int32, a key's value in every sublane
    pos_k_ref,
    o_ref,  # [bq, groups * d]
    # scratch
    qs_ref,  # [bq, groups * d]: q * scale, rounded once to q's type
    m_ref,  # [groups, bq, 128] f32 running max (lane-replicated)
    l_ref,  # [groups, bq, 128] f32 running denom
    acc_ref,  # [groups, bq, d] f32
    *,
    groups: int,
    sliding_window: Optional[int],
    scale: float,
):
    """One grid step = (kv head, query block, one of the key blocks the
    query block can see): ``_flash_kernel``'s online softmax for each of
    the kv head's query heads on ONE fetch of the head's keys and values.
    A step past the block's ``count`` multiplies nothing and — its index
    map names the block already there — fetches nothing."""
    qi = pl.program_id(1)
    step = pl.program_id(2)
    bk, d = k_ref.shape

    @pl.when(step == 0)
    def _init():
        qs_ref[...] = (
            q_ref[...].astype(jnp.float32) * scale).astype(qs_ref.dtype)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(step < count_ref[qi])
    def _attend():
        # the general form's mask, from the same two arrays
        seg_q = jnp.tile(seg_q_ref[...], (1, bk // 128))  # [bq, bk]
        pos_q = jnp.tile(pos_q_ref[...], (1, bk // 128))
        seg_k, pos_k = seg_k_ref[:1, :], pos_k_ref[:1, :]  # [1, bk]
        mask = (seg_q == seg_k) & (seg_q >= 0) & (pos_k <= pos_q)
        if sliding_window is not None:
            mask &= pos_k > pos_q - sliding_window
        k, v = k_ref[...], v_ref[...]
        for g in range(groups):
            s = jax.lax.dot_general(
                qs_ref[:, g * d: (g + 1) * d], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [bq, bk]
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_ref[g, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # no re-mask: a hidden key reads exp(NEG_INF - m_new) = 0 once
            # the row has seen a key; what a row gathers before its first
            # one (m_new == NEG_INF, p = 1) that key's alpha = 0 wipes, and
            # a row that never sees one is zeroed at the end
            p = jnp.exp(s - m_new)
            l_new = alpha * l_ref[g, :, :1] + jnp.sum(
                p, axis=-1, keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [bq, d]
            acc_ref[g] = acc_ref[g] * alpha + pv
            m_ref[g] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[g] = jnp.broadcast_to(l_new, l_ref.shape[1:])

    @pl.when(step == pl.num_programs(2) - 1)
    def _finalize():
        for g in range(groups):
            denom = jnp.maximum(l_ref[g, :, :1], 1e-30)
            has_key = m_ref[g, :, :1] > 0.5 * NEG_INF
            o_ref[:, g * d: (g + 1) * d] = jnp.where(
                has_key, acc_ref[g] / denom, 0.0).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("sliding_window", "scale", "max_segment", "interpret"),
)
def ragged_flash_prefill(q, k, v, seg_ids, positions, *, sliding_window=None,
                         scale=None, max_segment=None, interpret=False):
    """:func:`ragged_prefill_attention`, COLD, as one Pallas kernel that
    visits only the key blocks a query block can see.

    q [T, hq, d], k, v [T, hkv, d] as the projections leave them (no
    transpose, no float32 copy, no repeat over a group: a block is a
    column slice of the ``[T, heads * d]`` view); ``seg_ids``,
    ``positions`` [T]; ``T`` a whole number of ``RAGGED_ALIGN`` rows, ``d``
    of 128 lanes, segment starts aligned.  Returns [T, hq, d] in q's
    type; padding rows and rows with no key are exact zeros.

    Grid: kv head x query block x the key blocks it can see
    (:func:`ragged_key_blocks`, from ``seg_ids`` / ``positions`` in the
    program, scalar-prefetched): causal skipping in a global layer, window
    skipping in a window layer, nothing for a block of padding, nothing
    across segments.  The innermost extent is static
    (:func:`ragged_key_steps`: ``max_segment`` bounds it where many
    segments share a long axis).  Inside a visited block the mask is the
    general form's.  Arithmetic: MXU operands as stored (``q * scale``
    rounded once to q's type, ``p`` to v's — what the XLA form's
    default-precision products feed the MXU), float32 accumulation,
    running max and sum.  Jitted so that a prefill program traces and
    lowers the kernel once a (shape, window), as ``_paged_attend_local``."""
    t, hq, d = q.shape
    hkv = k.shape[1]
    if hq % hkv or d % 128:
        raise ValueError(
            f"ragged_flash_prefill: {hq} query heads over {hkv} kv heads "
            f"of width {d} (needs whole groups of 128-lane heads)")
    groups = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    bq, bk = RAGGED_ALIGN, ragged_key_block_rows(t)
    first, count = ragged_key_blocks(seg_ids, positions, sliding_window, bk)
    steps = ragged_key_steps(t, sliding_window, max_segment, bk)

    def key_block(i, j, first_ref, count_ref):
        # past the count: the block already there (no fetch)
        return first_ref[i] + jnp.minimum(j, jnp.maximum(count_ref[i] - 1, 0))

    q_spec = pl.BlockSpec((bq, groups * d), lambda h, i, j, *_: (i, h))
    kv_spec = pl.BlockSpec(
        (bk, d), lambda h, i, j, *refs: (key_block(i, j, *refs), h))
    row_spec = pl.BlockSpec((bq, 128), lambda h, i, j, *_: (i, 0))
    key_spec = pl.BlockSpec(
        (8, bk), lambda h, i, j, *refs: (0, key_block(i, j, *refs)))
    seg_ids, positions = seg_ids.astype(jnp.int32), positions.astype(jnp.int32)
    out = pl.pallas_call(
        functools.partial(
            _ragged_prefill_kernel, groups=groups,
            sliding_window=sliding_window, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(hkv, t // bq, steps),
            in_specs=[q_spec, kv_spec, kv_spec, row_spec, row_spec,
                      key_spec, key_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((bq, groups * d), q.dtype),
                pltpu.VMEM((groups, bq, 128), jnp.float32),
                pltpu.VMEM((groups, bq, 128), jnp.float32),
                pltpu.VMEM((groups, bq, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((t, hq * d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name="_ragged_prefill_kernel",
    )(
        first, count,
        q.reshape(t, hq * d), k.reshape(t, hkv * d), v.reshape(t, hkv * d),
        jnp.broadcast_to(seg_ids[:, None], (t, 128)),
        jnp.broadcast_to(positions[:, None], (t, 128)),
        jnp.broadcast_to(seg_ids[None, :], (8, t)),
        jnp.broadcast_to(positions[None, :], (8, t)),
    )
    return out.reshape(t, hq, d)


# --------------------------------------------------------------------------
# Pallas paged decode kernel
# --------------------------------------------------------------------------

# KV rows one compute block of the paged kernel covers (a whole number of
# pages): the double-buffered VMEM window a lane's live pages stream
# through; 512 rows x 8 kv heads x 128 x bf16 = 1 MiB per buffer, 4 MiB
# for K and V twice.  Of 128 / 256 / 512 the largest was the fastest at
# every length on a v5e (24.0 us a layer at 4 lanes x ~360 rows, 133.9 at
# 4 x 4096, 84.5 at 16 x ~360 — chip run, PR 29; PERF.md): a lane of a few
# hundred rows is one block, and a long one pays fewer loop turns.
PAGED_BLOCK_ROWS = 512


def paged_kernel_supported(pool_dtype, kv_heads, head_dim, mesh=None) -> bool:
    """Whether :func:`paged_flash_decode` reads a pool of this geometry:
    Mosaic's strided sublane read (one kv head out of a page's
    token-major rows) needs a 128-wide minor axis and 32-bit words, so a
    16-bit pool is read as packed head PAIRS — 1 or an even number of kv
    heads per device.  Anything else stays on the XLA reference."""
    if mesh is not None and mesh.n_devices > 1:
        if kv_heads % mesh.n_model:
            return False
        kv_heads //= mesh.n_model
    itemsize = jnp.dtype(pool_dtype).itemsize
    return head_dim == 128 and (
        itemsize == 4 or (itemsize == 2 and (kv_heads == 1 or kv_heads % 2 == 0))
    )


def paged_block_pages(table_width: int, block_size: int,
                      block_rows: Optional[int] = None) -> int:
    """Pages one compute block of the paged kernel covers, for tables of
    ``table_width`` entries."""
    block_rows = PAGED_BLOCK_ROWS if block_rows is None else block_rows
    return max(1, min(block_rows // block_size, table_width))


def paged_block_runs(tables, ppb: int, n_blocks: int):
    """[S, ceil(NB / ppb)] bool: the compute blocks of each table row whose
    ``ppb`` ids are ONE ascending run of allocated pages, which the paged
    kernel fetches with one copy a pool where every page is live (a block
    that holds a hole is none).  For a device array in the program and for
    the host's copy of the tables (the batcher's counters) alike."""
    xp = jnp if isinstance(tables, jax.Array) else np
    s_, nb = tables.shape
    pad = -nb % ppb
    if pad:
        tables = xp.concatenate(
            [tables, xp.full((s_, pad), n_blocks, tables.dtype)], axis=1)
    blocks = tables.reshape(s_, -1, ppb)
    first = blocks[:, :, :1]
    return xp.all(blocks == first + xp.arange(ppb), axis=-1) & (
        first[:, :, 0] + ppb <= n_blocks)


def _paged_decode_kernel(
    # scalar prefetch
    tables_ref,  # [S, NB] int32 block ids (the wrapper clamps holes in bounds)
    lengths_ref,  # [S] int32 valid (and allocated) kv length
    qoff_ref,  # [S] int32 absolute position of q row 0
    runs_ref,  # [S, ceil(NB / ppb)] int32: the compute block's ids are one run
    # blocks
    q_ref,  # [1, hkv, s * groups, d]: row r of a kv head is q position r // groups
    k_hbm,  # [n_blocks * block_size * hkv, d] — the layer's pool, left in HBM
    v_hbm,
    o_ref,  # [1, hkv, s * groups, d]
    # scratch
    k_buf,  # [2, ppb * block_size * hkv, d] double-buffered pages (token-major)
    v_buf,
    sems,  # DMA semaphores [k | v, buffer]
    first_buf_ref,  # [1] int32 (SMEM): the buffer this lane's first block is in
    m_ref,  # [hkv, s * groups, 128] f32 running max (lane-replicated)
    l_ref,  # [hkv, s * groups, 128] f32 running denom
    acc_ref,  # [hkv, s * groups, d] f32
    *,
    block_size: int,
    groups: int,
    sliding_window: Optional[int],
    scale: float,
):
    """One grid step = one lane: stream its LIVE pages (from the first one
    the sliding window can still see) through VMEM a compute block at a
    time, every kv head of a page in one contiguous DMA, and run
    ``_flash_kernel``'s online softmax per kv head on them — the q heads
    of a group share the head's rows.  While a block is computed the next
    one is in flight: the lane's next block, or, from its last block, the
    NEXT lane's first (the buffers and semaphores outlive a grid step).
    A lane of length 0 issues no DMA and writes zeros.

    What a block costs the scalar core follows what the kernel can read
    of it.  A FULL block (all its pages live) whose ids are one ascending
    run is two copies, K and V; any other full block a copy a page, the
    starts unrolled with no test between them; either way ONE wait a pool
    for the buffer's whole extent.  A lane's last, partial block starts
    and waits page by page under the length test."""
    lane = pl.program_id(0)
    n_lanes = pl.num_programs(0)
    hkv, gq, d = q_ref.shape[1:]
    page_rows = block_size * hkv  # a page: consecutive rows of the flat pool
    buf_rows = k_buf.shape[1]
    ppb = buf_rows // page_rows
    rows = ppb * block_size  # kv positions a compute block covers
    # a pool smaller than one compute block holds no run of one
    runs_fit = k_hbm.shape[0] >= buf_rows

    def blocks_of(ln):
        """(first block, end block, live pages) of lane ``ln``."""
        n_pages = pl.cdiv(lengths_ref[ln], block_size)
        first = 0
        if sliding_window is not None:
            first = jnp.maximum(qoff_ref[ln] - sliding_window + 1, 0) // rows
        return first, pl.cdiv(n_pages, ppb), n_pages

    def copies(page, n_rows, buf, dst_row):
        """(K copy, V copy) of ``n_rows`` pool rows from the first row of
        ``page`` on, into buffer ``buf``."""
        src = pl.ds(pl.multiple_of(page * page_rows, page_rows), n_rows)
        dst = pl.ds(dst_row, n_rows)
        return (
            pltpu.make_async_copy(
                k_hbm.at[src], k_buf.at[buf, dst], sems.at[0, buf]),
            pltpu.make_async_copy(
                v_hbm.at[src], v_buf.at[buf, dst], sems.at[1, buf]),
        )

    def page_copies(ln, j, buf, i):
        return copies(
            tables_ref[ln, j * ppb + i], page_rows, buf, i * page_rows)

    def for_live_pages(ln, j, buf, act):
        n_pages = blocks_of(ln)[2]

        def one(i, carry):
            @pl.when(j * ppb + i < n_pages)
            def _():
                for copy in page_copies(ln, j, buf, i):
                    act(copy)

            return carry

        jax.lax.fori_loop(0, ppb, one, 0)

    def is_full(ln, j):
        return (j + 1) * ppb <= blocks_of(ln)[2]

    def fetch(ln, j, buf):
        def as_a_run():
            for copy in copies(tables_ref[ln, j * ppb], buf_rows, buf, 0):
                copy.start()

        def page_by_page():
            for i in range(ppb):
                for copy in page_copies(ln, j, buf, i):
                    copy.start()

        def whole_block():
            if runs_fit:
                jax.lax.cond(runs_ref[ln, j] != 0, as_a_run, page_by_page)
            else:
                page_by_page()

        jax.lax.cond(
            is_full(ln, j), whole_block,
            lambda: for_live_pages(ln, j, buf, lambda copy: copy.start()))

    def wait(ln, j, buf):
        def whole_block():
            # a DMA semaphore counts bytes: the block's copies, however
            # many they were, add up to the buffer's extent — a descriptor
            # of that extent, never started, waits for them all at once
            for pool, sem in ((k_buf, 0), (v_buf, 1)):
                pltpu.make_async_copy(
                    pool.at[buf], pool.at[buf], sems.at[sem, buf]).wait()

        jax.lax.cond(
            is_full(ln, j), whole_block,
            lambda: for_live_pages(ln, j, buf, lambda copy: copy.wait()))

    def fetch_first_of_next_lane(buf):
        @pl.when(lane + 1 < n_lanes)
        def _():
            first, end, _ = blocks_of(lane + 1)

            @pl.when(first < end)
            def _():
                fetch(lane + 1, first, buf)

    kv_len = lengths_ref[lane]
    q_off = qoff_ref[lane]
    j0, j1, _ = blocks_of(lane)

    @pl.when(lane == 0)
    def _first_lane():
        # a block's tail pages past the lane's length are never fetched.
        # What K holds there is masked out of the scores, but in V a
        # probability of exactly 0 times a NaN bit pattern would still
        # poison the accumulator — so nothing but zeros and fetched
        # (finite) rows is ever in the V buffers
        v_buf[...] = jnp.zeros_like(v_buf)
        first_buf_ref[0] = 0

        @pl.when(j0 < j1)
        def _():
            fetch(lane, j0, 0)

    first_buf = first_buf_ref[0]
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    q_rows = jax.lax.broadcasted_iota(jnp.int32, (gq, rows), 0)
    kv_cols = jax.lax.broadcasted_iota(jnp.int32, (gq, rows), 1)
    q_abs = q_off + q_rows // groups

    def head_rows(buf_ref, buf, h):
        """[rows, d] f32: kv head ``h`` of every token in the buffer.  A
        token's heads are consecutive buffer rows, so a head is a strided
        sublane read — which Mosaic has for 32-bit data only.  bf16 rows
        are read as packed pairs (row 2i in the low half of a word, 2i+1
        in the high half) and widened by a shift or a mask: exactly the
        bf16 value as float32."""
        ref = buf_ref.at[buf]
        if hkv == 1:
            return ref[...].astype(jnp.float32)
        if jnp.dtype(ref.dtype).itemsize == 4:
            return ref[pl.ds(h, rows, stride=hkv), :].astype(jnp.float32)
        words = ref.bitcast(jnp.uint32)[pl.ds(h // 2, rows, stride=hkv // 2), :]
        if h % 2:
            words = words & jnp.uint32(0xFFFF0000)
        else:
            words = words << 16
        return pltpu.bitcast(words, jnp.float32)

    def compute_block(j, carry):
        buf = (first_buf + j - j0) % 2

        @pl.when(j + 1 < j1)
        def _():
            fetch(lane, j + 1, 1 - buf)

        @pl.when(j + 1 == j1)
        def _():
            fetch_first_of_next_lane(1 - buf)

        wait(lane, j, buf)

        kv_pos = j * rows + kv_cols
        mask = (kv_pos < kv_len) & (kv_pos <= q_abs)
        if sliding_window is not None:
            mask &= kv_pos > q_abs - sliding_window

        for h in range(hkv):
            q = q_ref[0, h].astype(jnp.float32) * scale  # [gq, d]
            k = head_rows(k_buf, buf, h)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [gq, rows]
            s = jnp.where(mask, s, NEG_INF)

            m_prev = m_ref[h, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # explicit re-mask, as in _flash_kernel: in a fully-masked
            # block m_new == NEG_INF and exp(s - m_new) would be 1
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            l_new = alpha * l_ref[h, :, :1] + jnp.sum(
                p, axis=-1, keepdims=True
            )
            pv = jax.lax.dot_general(
                p, head_rows(v_buf, buf, h), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [gq, d]
            acc_ref[h] = acc_ref[h] * alpha + pv
            m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[h] = jnp.broadcast_to(l_new, l_ref.shape[1:])
        return carry

    jax.lax.fori_loop(j0, j1, compute_block, 0)

    # where the next lane's first block is (being) fetched: the buffer
    # after this lane's last block — by this lane if it had none
    n_computed = jnp.maximum(j1 - j0, 0)
    next_first_buf = (first_buf + n_computed) % 2

    @pl.when(n_computed == 0)
    def _():
        fetch_first_of_next_lane(next_first_buf)

    first_buf_ref[0] = next_first_buf

    denom = jnp.maximum(l_ref[:, :, :1], 1e-30)
    o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def paged_flash_decode(
    q,
    k_pool,
    v_pool,
    block_tables,
    lengths,
    q_offset=None,
    *,
    block_size: int,
    sliding_window: Optional[int] = None,
    scale: Optional[float] = None,
    interpret: bool = False,
    mesh=None,
):
    """Causal decode attention that reads a lane's live pages IN PLACE.

    q            [S, s, q_heads, d] (s = 1 plain step, K spec verify —
                 read from the shape)
    k/v_pool     [P, kv_heads, d] flat block pool; stays in HBM, viewed as
                 [P * kv_heads, d] (a free reshape: a page is ``block_size
                 * kv_heads`` consecutive rows of it, a run of pages one
                 slice)
    block_tables [S, NB] int32; entries >= n_blocks are holes
    lengths      [S] valid kv length per lane AFTER this step
    q_offset     [S] absolute position of q[:, 0] (default lengths - s)

    Grid: one step per lane.  Per lane the kernel loops over
    ``ceil(len / block_size)`` pages only, ``PAGED_BLOCK_ROWS`` kv
    positions at a time, double-buffered with one DMA per page that
    carries every kv head of its 16 tokens (the way
    ``jax.experimental.pallas.ops.tpu.ragged_paged_attention`` does) —
    or ONE per compute block whose pages are all live and whose ids are
    an ascending run (:func:`paged_block_runs`), and one wait a block;
    the arithmetic is ``_flash_kernel``'s (f32 scores, running max,
    denominator and accumulator; bf16 in and out; the same masks).  A
    length is clamped to the lane's ALLOCATED pages, so a hole is never
    dereferenced: a free slot or a retired lane (all-hole table row)
    issues no DMA and outputs zeros, where the gather reference would
    attend to a clamped garbage row — only ever for lanes nobody reads.

    ``mesh``: under ``shard_map`` exactly as :func:`flash_attention` —
    kv heads over the model axis (the pool's own sharding), lanes over
    the data axis."""
    S, s, hq, d = q.shape
    n_rows, hkv, _ = k_pool.shape
    if hq % hkv:
        raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
    scale = scale if scale is not None else d ** -0.5
    if q_offset is None:
        q_offset = lengths - s
    if mesh is not None and mesh.n_devices > 1:
        if hkv % mesh.n_model or S % mesh.n_data:
            raise ValueError(
                f"paged_flash_decode on a {mesh.n_data}x{mesh.n_model} mesh "
                f"needs kv heads ({hkv}) divisible by the model axis and "
                f"lanes ({S}) by the data axis"
            )
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        local = functools.partial(
            paged_flash_decode, block_size=block_size,
            sliding_window=sliding_window, scale=scale, interpret=interpret,
        )
        heads = P(mesh.data_axis, None, mesh.model_axis, None)
        pool = P(None, mesh.model_axis, None)
        lanes = P(mesh.data_axis)
        return shard_map(
            local,
            mesh=mesh.mesh,
            in_specs=(heads, pool, pool, P(mesh.data_axis, None), lanes, lanes),
            out_specs=heads,
            check_vma=False,
        )(q, k_pool, v_pool, block_tables, lengths, q_offset)
    if not paged_kernel_supported(k_pool.dtype, hkv, d):
        raise NotImplementedError(
            f"paged_flash_decode does not read a {k_pool.dtype} pool of "
            f"{hkv} kv heads x {d} (paged_kernel_supported)"
        )
    return _paged_attend_local(
        q, k_pool, v_pool, block_tables, lengths, q_offset,
        block_size=block_size, sliding_window=sliding_window, scale=scale,
        block_rows=PAGED_BLOCK_ROWS, interpret=interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "block_size", "sliding_window", "scale", "block_rows", "interpret",
    ),
)
def _paged_attend_local(q, k_pool, v_pool, block_tables, lengths, q_offset,
                        *, block_size, sliding_window, scale, block_rows,
                        interpret):
    """One device's share of :func:`paged_flash_decode`.  Jitted so that a
    decode program traces and lowers the kernel ONCE and calls it from
    each of its layers — inlined by XLA, no program of its own (a
    32-layer program otherwise traces and serializes the Mosaic body 32
    times at every warm-up)."""
    S, s, hq, d = q.shape
    n_rows, hkv, _ = k_pool.shape
    groups = hq // hkv
    n_blocks = n_rows // block_size
    ppb = paged_block_pages(block_tables.shape[1], block_size, block_rows)
    buf_rows = ppb * block_size * hkv

    # never past the allocated pages (holes fill a table row's tail)
    allocated = jnp.sum(
        (block_tables < n_blocks).astype(jnp.int32), axis=1
    ) * block_size
    kv_len = jnp.minimum(lengths.astype(jnp.int32), allocated)
    tables = block_tables.astype(jnp.int32)

    # [S, s, hq, d] -> [S, hkv, s * groups, d]; a reshape when s == 1
    qr = q.reshape(S, s, hkv, groups, d).transpose(0, 2, 1, 3, 4)
    qr = qr.reshape(S, hkv, s * groups, d)
    # a page is ``block_size * hkv`` consecutive rows of the flat pool
    flat = (n_rows * hkv, d)

    kernel = functools.partial(
        _paged_decode_kernel,
        block_size=block_size,
        groups=groups,
        sliding_window=sliding_window,
        scale=scale,
    )
    lane_block = pl.BlockSpec(
        (1, hkv, s * groups, d), lambda i, *_: (i, 0, 0, 0)
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(S,),
            in_specs=[
                lane_block,
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=lane_block,
            scratch_shapes=[
                pltpu.VMEM((2, buf_rows, d), k_pool.dtype),
                pltpu.VMEM((2, buf_rows, d), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((hkv, s * groups, 128), jnp.float32),
                pltpu.VMEM((hkv, s * groups, 128), jnp.float32),
                pltpu.VMEM((hkv, s * groups, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((S, hkv, s * groups, d), q.dtype),
        interpret=interpret, name="_paged_decode_kernel",
    )(
        # a hole can only lie past the length (clamped to the allocated
        # pages above); the clamp keeps a DMA in bounds even if a caller
        # breaks that
        jnp.minimum(tables, n_blocks - 1), kv_len,
        q_offset.astype(jnp.int32),
        paged_block_runs(tables, ppb, n_blocks).astype(jnp.int32),
        qr, k_pool.reshape(flat), v_pool.reshape(flat),
    )
    out = out.reshape(S, hkv, s, groups, d).transpose(0, 2, 1, 3, 4)
    return out.reshape(S, s, hq, d)


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


# --------------------------------------------------------------------------
# The two-mixer block (models/hybrid.py): decayed linear attention whose
# past is a state a lane, and block-sparse attention that selects inside
# the paged cache.  XLA but for the decode step's read of the blocks taken,
# which on a TPU is :func:`paged_flash_decode` through a page table of its
# own (:func:`taken_page_tables`).
# --------------------------------------------------------------------------

_HIGHEST = jax.lax.Precision.HIGHEST
# the selection score of a block that is always taken: above any real one
# (a block's score is a sum of probabilities over a kv head's query heads)
FORCED_SCORE = 1e4


def linear_attention_prefill(q, k, v, seg_ids, positions, slopes, state_pool,
                             chunk_slot):
    """Decayed linear attention over a PACKED batch, in its chunked form:
    per head ``S_t = lambda S_{t-1} + k_t^T v_t``, ``o_t = (q_t /
    sqrt(d)) S_t`` with ``lambda = exp(-slope)``, computed
    ``RAGGED_ALIGN`` rows at a time — inside a chunk the quadratic form
    ``((q k^T) * lambda^(t-s)) v``, across chunks the carried state.

    q, k, v    [T, heads, d]; segments start on chunk boundaries, so a
               chunk belongs to one segment (or is padding)
    seg_ids, positions [T]; a chunk whose first position is 0 starts from
               a ZERO state (a segment's first: the lane's reset)
    slopes     [heads] float32
    state_pool [n_slots, heads, d, d] float32: a lane's state
    chunk_slot [T / RAGGED_ALIGN] int32: the pool entry that takes the
               state as it stands after this chunk (>= n_slots: none) —
               set for a segment's LAST chunk

    Returns (out [T, heads, d] in q's type, state_pool).  State and sums
    are float32 at full matmul precision."""
    t, heads, d = q.shape
    c = RAGGED_ALIGN
    n = t // c
    f32 = jnp.float32
    idx = jnp.arange(c, dtype=f32)
    rel = idx[:, None] - idx[None, :]  # t - s inside a chunk
    decay_ts = jnp.where(
        rel >= 0, jnp.exp(-slopes[:, None, None] * jnp.maximum(rel, 0.0)), 0.0
    )  # [heads, c, c]
    decay_q = jnp.exp(-slopes[None, :] * (idx[:, None] + 1.0))  # [c, heads]
    scale = d ** -0.5

    def step(carry, xs):
        state, pool = carry
        qb, kb, vb, ok, pos0, slot = xs
        state = jnp.where(pos0 == 0, 0.0, state)
        count = jnp.sum(ok).astype(f32)
        qf = qb.astype(f32) * scale
        kf = jnp.where(ok[:, None, None], kb.astype(f32), 0.0)
        vf = vb.astype(f32)
        a = jnp.einsum("thd,shd->hts", qf, kf, precision=_HIGHEST) * decay_ts
        o = jnp.einsum("hts,she->the", a, vf, precision=_HIGHEST)
        o = o + decay_q[:, :, None] * jnp.einsum(
            "thd,hde->the", qf, state, precision=_HIGHEST)
        # what row s still weighs when the chunk's last valid row is done
        decay_k = jnp.where(
            ok[:, None],
            jnp.exp(-slopes[None, :] * jnp.maximum(count - 1.0 - idx, 0.0)[
                :, None]),
            0.0,
        )  # [c, heads]
        state = jnp.exp(-slopes * count)[:, None, None] * state + jnp.einsum(
            "shd,she->hde", kf * decay_k[:, :, None], vf, precision=_HIGHEST)
        pool = pool.at[slot].set(state, mode="drop")
        return (state, pool), o.astype(q.dtype)

    chunks = lambda x: x.reshape(n, c, *x.shape[1:])  # noqa: E731
    (_, state_pool), out = jax.lax.scan(
        step,
        (jnp.zeros(state_pool.shape[1:], f32), state_pool),
        (chunks(q), chunks(k), chunks(v), chunks(seg_ids >= 0),
         chunks(positions)[:, 0], chunk_slot),
    )
    return out.reshape(t, heads, d), state_pool


def linear_attention_step(q, k, v, state, slopes):
    """One decode step of the same, the recurrence itself: q, k, v
    [S, heads, d] (one token a lane), state [S, heads, d, d] float32 ->
    (out [S, heads, d] in q's type, the advanced state)."""
    f32 = jnp.float32
    qf = q.astype(f32) * q.shape[-1] ** -0.5
    state = jnp.exp(-slopes)[None, :, None, None] * state + (
        k.astype(f32)[..., :, None] * v.astype(f32)[..., None, :]
    )
    out = jnp.einsum("shd,shde->she", qf, state, precision=_HIGHEST)
    return out.astype(q.dtype), state


def _windows_of_blocks(p, m: int, r: int, nb: int):
    """``p`` [..., nb * r] scores of the compressed-key windows (window j
    starts ``stride * j`` tokens in; ``m`` strides long; ``r`` window
    starts a block) -> [..., nb, r + m - 1]: for each block the windows
    that overlap it, ``r b - (m - 1) .. r b + r - 1`` (0 before the
    first window)."""
    pad = jnp.pad(p, [(0, 0)] * (p.ndim - 1) + [(m - 1, 0)])
    return jnp.stack(
        [pad[..., i:i + r * nb:r] for i in range(r + m - 1)], axis=-1
    )


def _taken_blocks(block_score, exists, forced, topk: int):
    """The blocks one row takes for one kv head: the ``topk`` best of the
    blocks that exist for it, the forced ones first.  ``block_score``
    [..., nb]; ``exists`` / ``forced`` broadcastable to it.  Returns (ids
    int32 [..., kk], took bool [..., kk]) with kk = min(topk, nb)."""
    score = jnp.where(
        exists, jnp.where(forced, FORCED_SCORE, block_score), -1.0)
    vals, ids = jax.lax.top_k(score, min(topk, score.shape[-1]))
    return ids.astype(jnp.int32), vals >= 0.0


def _pad_record(rec, topk: int, fill: int = -1):
    short = topk - rec.shape[-1]
    if short <= 0:
        return rec
    return jnp.pad(rec, [(0, 0)] * (rec.ndim - 1) + [(0, short)],
                   constant_values=fill)


def compressed_keys(k, seg_ids, positions, kernel_size: int, stride: int):
    """Mean-pooled keys of a packed batch: window j is the rows ``stride
    j .. stride j + kernel_size - 1``.  Returns (ck [T / stride, kv heads,
    d] in k's type — as a pool holds them —, ok [W]: the window lies
    whole inside ONE segment, its segment [W], the position of its last
    token [W])."""
    t, g, d = k.shape
    w, m = t // stride, kernel_size // stride
    k16 = k.astype(jnp.float32).reshape(w, stride, g, d).sum(1)
    pad = jnp.pad(k16, ((0, m - 1), (0, 0), (0, 0)))
    ck = sum(pad[i:i + w] for i in range(m)) / kernel_size
    first = jnp.arange(w) * stride
    last = first + kernel_size - 1
    last_c = jnp.minimum(last, t - 1)
    ok = (last < t) & (seg_ids[first] >= 0) & (
        seg_ids[first] == seg_ids[last_c])
    return ck.astype(k.dtype), ok, seg_ids[first], positions[last_c]


def sparse_prefill_attention(q, k, v, seg_ids, positions, seg_lens, ck, ck_ok,
                             ck_seg, ck_end, *, kernel_size: int, stride: int,
                             block: int, topk: int, init_blocks: int,
                             window: int, dense_len: int):
    """Block-sparse self-attention over a PACKED batch: every row selects
    for itself, per kv head, and attends causally to the rows of the
    blocks it took (of its own segment) alone; a row of a segment that
    holds fewer than ``dense_len`` tokens (``seg_lens`` [T]) attends to
    all of them.

    Selection (per row at position t, kv head g): softmax over the
    compressed keys whose window ended at or before t, per query head,
    summed over g's query heads; a block's score is the best of the
    windows that overlap it; the first ``init_blocks`` blocks and those
    over the last ``window`` tokens are always taken; ``topk`` in all.

    q [T, heads, d]; k, v [T, kv heads, d]; ``ck`` ... as
    :func:`compressed_keys` gives them.  Returns (out [T, heads, d],
    taken int32 [kv heads, T, topk]: block ids within the row's segment,
    the forced ones first, -1 where fewer exist and on rows that ran
    dense or are padding)."""
    t, hq, d = q.shape
    g = k.shape[1]
    per = hq // g
    if RAGGED_ALIGN % block or t % RAGGED_ALIGN:
        raise ValueError(
            f"sparse prefill: blocks of {block} tokens do not tile "
            f"{RAGGED_ALIGN}-row aligned segments of a {t}-row dispatch")
    m, r, nb = kernel_size // stride, block // stride, t // block
    scale = d ** -0.5
    f32 = jnp.float32
    ckf = ck.astype(f32)
    valid = seg_ids >= 0
    blk_first = jnp.arange(nb) * block
    blk_seg, blk_pos0 = seg_ids[blk_first], positions[blk_first]

    def attend_rows(rows):
        qb = q[rows].reshape(-1, g, per, d)
        seg_q, pos_q = seg_ids[rows], positions[rows]
        real = seg_q >= 0
        with scope("select"):
            s_sel = jnp.einsum(
                "qgpd,wgd->gpqw", qb.astype(f32), ckf, precision=_HIGHEST
            ) * scale
            ok_w = (ck_ok[None, :] & (ck_seg[None, :] == seg_q[:, None])
                    & (ck_end[None, :] <= pos_q[:, None]))
            p = jax.nn.softmax(jnp.where(ok_w, s_sel, NEG_INF), axis=-1)
            p = jnp.where(ok_w, p, 0.0).sum(axis=1)  # [g, bq, W]
            score = _windows_of_blocks(p, m, r, nb).max(axis=-1)
            exists = (real[:, None] & (blk_seg[None, :] == seg_q[:, None])
                      & (blk_pos0[None, :] <= pos_q[:, None]))
            forced = (blk_pos0[None, :] < init_blocks * block) | (
                blk_pos0[None, :] + block - 1
                >= pos_q[:, None] - window + 1)
            ids, took = _taken_blocks(
                score, exists[None], forced[None], topk)
            sparse_row = real & (seg_lens[rows] >= dense_len)
            first_block = (rows - pos_q) // block  # of the row's segment
            rec = jnp.where(
                took & sparse_row[None, :, None],
                ids - first_block[None, :, None], -1)
            sel = jnp.any(
                (ids[..., None] == jnp.arange(nb)) & took[..., None],
                axis=-2)
            blk_mask = sel | ~sparse_row[None, :, None]  # [g, bq, nb]
        base = (real[:, None] & valid[None, :]
                & (seg_q[:, None] == seg_ids[None, :])
                & (positions[None, :] <= pos_q[:, None]))
        mask = base[None] & jnp.repeat(blk_mask, block, axis=-1)
        scores = jnp.einsum(
            "qgpd,kgd->gpqk", qb, k, preferred_element_type=f32) * scale
        scores = jnp.where(mask[:, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        probs = jnp.where(
            jnp.any(mask, axis=-1, keepdims=True)[:, None], probs, 0.0)
        out = jnp.einsum(
            "gpqk,kgd->qgpd", probs.astype(v.dtype), v,
            preferred_element_type=f32)
        return out.reshape(-1, hq, d).astype(q.dtype), rec

    blocks = jnp.arange(t).reshape(t // RAGGED_ALIGN, RAGGED_ALIGN)
    out, rec = jax.lax.map(attend_rows, blocks)
    rec = jnp.moveaxis(rec, 1, 0).reshape(g, t, -1)  # [g, T, kk]
    return out.reshape(t, hq, d), _pad_record(rec, topk)


def taken_page_tables(ids, took, sparse_lane, block_tables, lengths, *,
                      block_size: int, block: int, dense_len: int,
                      n_blocks: int):
    """What a step's selection reads, as page tables the paged kernel
    walks: one VIRTUAL lane a (lane, kv head).

    ids, took    [S, g, kk] the blocks a lane's kv head took (any order)
    sparse_lane  [S] the lane selects (holds ``dense_len`` tokens or more)
    block_tables [S, NB] the lanes' own tables; entries >= ``n_blocks``
                 are holes
    lengths      [S] AFTER this step

    Returns (tables int32 [S * g, width], lengths int32 [S * g]).  A
    selecting lane's row: the pages of its taken blocks in ASCENDING block
    order (``block // block_size`` pages a block, in order), holes behind
    them; every taken block is full but the one that holds the query's
    position, which the window forces and ascending order puts last, so
    the length is ``(taken - 1) * block`` plus that block's rows up to the
    query.  A lane under ``dense_len``: its own table row and length
    (``width`` spans ``dense_len``).  An all-hole row stays all holes —
    the kernel clamps its length to the allocated pages, 0."""
    s_, g, kk = ids.shape
    nbt = block_tables.shape[1]
    ppb = block // block_size  # pages a block
    nb = nbt * block_size // block
    width = max(kk * ppb, min(nbt, -(-dense_len // block_size)))
    t = lengths - 1
    blocks = jnp.arange(nb)
    sel = jnp.any((ids[..., None] == blocks) & took[..., None], axis=-2)
    n_taken = jnp.sum(sel, axis=-1, dtype=jnp.int32)  # [S, g]
    # slot j holds the taken block that has j taken blocks before it
    slot = jnp.cumsum(sel, axis=-1, dtype=jnp.int32) - 1
    hit = sel[..., None, :] & (slot[..., None, :] == jnp.arange(kk)[:, None])
    ordered = jnp.sum(jnp.where(hit, blocks, 0), axis=-1)  # [S, g, kk]
    entry = (ordered[..., None] * ppb + jnp.arange(ppb)).reshape(s_, -1)
    pages = jnp.take_along_axis(block_tables, entry, axis=1)
    filled = jnp.repeat(jnp.arange(kk) < n_taken[..., None], ppb, axis=-1)
    pages = jnp.where(filled, pages.reshape(s_, g, kk * ppb), n_blocks)
    tables = jnp.where(
        sparse_lane[:, None, None], _pad_record(pages, width, n_blocks),
        _pad_record(block_tables[:, :width], width, n_blocks)[:, None, :])
    last = jnp.max(jnp.where(sel, blocks, -1), axis=-1)  # [S, g]
    rows_taken = jnp.where(
        n_taken > 0,
        (n_taken - 1) * block
        + jnp.clip(t[:, None] - last * block + 1, 0, block), 0)
    lens = jnp.where(sparse_lane[:, None], rows_taken, lengths[:, None])
    return tables.reshape(s_ * g, width), lens.reshape(s_ * g)


def sparse_decode_attention(q, k_pool, v_pool, ck_pool, block_tables, lengths,
                            *, block_size: int, kernel_size: int, stride: int,
                            block: int, topk: int, init_blocks: int,
                            window: int, dense_len: int, use_flash=False,
                            interpret: bool = False):
    """The decode step of the same THROUGH A BLOCK TABLE: one query a
    lane selects among the lane's compressed keys (gathered through the
    table: one row per ``stride`` tokens) and reads the K / V rows of the
    blocks it took, and only those — ``topk * block`` rows a kv head,
    whatever the lane's length.  A lane that holds fewer than
    ``dense_len`` tokens reads every row.

    Under ``use_flash`` (``models/decoder.kernel_forms``'s
    ``sparse_paged``: a TPU, no mesh, a geometry the paged kernel reads, a
    selection block a whole number of pages; ``interpret`` for a CPU test
    of it) ONE call of the paged kernel reads them in place, a virtual lane
    a (lane, kv head) through :func:`taken_page_tables`: a lane under
    ``dense_len`` is a virtual lane whose table is its own.  Otherwise the
    XLA form: a row gather of the blocks taken; while any LIVE lane
    (first table entry allocated) is under ``dense_len``, the step
    gathers every lane's whole table and masks (``lax.cond``: the other
    branch is not run).

    q [S, heads, d]; pools flat ([P, kv heads, d]; ``ck_pool`` [P /
    stride, ...]); ``lengths`` [S] AFTER this step.  Returns (out
    [S, heads, d], taken int32 [kv heads, S, topk] as the prefill's)."""
    s_, hq, d = q.shape
    pool_rows, g, _ = k_pool.shape
    per = hq // g
    nbt = block_tables.shape[1]
    cap = nbt * block_size
    if block_size % stride or cap % block:
        raise ValueError(
            f"sparse decode: pages of {block_size} and a table of {cap} "
            f"tokens do not tile windows every {stride} / blocks of {block}")
    m, r, nb = kernel_size // stride, block // stride, cap // block
    n_win = cap // stride
    scale = d ** -0.5
    f32 = jnp.float32
    t = lengths - 1  # the query's position
    lane = jnp.arange(s_)

    with scope("select"):
        w_tok = jnp.arange(n_win) * stride
        page = block_tables[:, w_tok // block_size]  # [S, W]
        ck_rows = jnp.minimum(
            page * (block_size // stride) + (w_tok % block_size) // stride,
            ck_pool.shape[0] - 1)
        ck = ck_pool[ck_rows].astype(f32)  # [S, W, g, d]
        ok_w = (
            w_tok[None, :] + kernel_size - 1 <= t[:, None])[:, None, None]
        qg = q.reshape(s_, g, per, d)
        s_sel = jnp.einsum(
            "sgpd,swgd->sgpw", qg.astype(f32), ck, precision=_HIGHEST
        ) * scale
        p = jax.nn.softmax(jnp.where(ok_w, s_sel, NEG_INF), axis=-1)
        p = jnp.where(ok_w, p, 0.0).sum(axis=2)  # [S, g, W]
        score = _windows_of_blocks(p, m, r, nb).max(axis=-1)
        blk_pos0 = jnp.arange(nb) * block
        exists = blk_pos0[None, :] <= t[:, None]
        forced = (blk_pos0[None, :] < init_blocks * block) | (
            blk_pos0[None, :] + block - 1 >= t[:, None] - window + 1)
        ids, took = _taken_blocks(
            score, exists[:, None], forced[:, None], topk)
        sparse_lane = lengths >= dense_len
        live = block_tables[:, 0] < pool_rows // block_size
        rec = jnp.where(took & sparse_lane[:, None, None], ids, -1)

    def taken_as_pages():
        tables, lens = taken_page_tables(
            ids, took, sparse_lane, block_tables, lengths,
            block_size=block_size, block=block, dense_len=dense_len,
            n_blocks=pool_rows // block_size)
        # the lane's query once a kv head (at position ``lens - 1`` of
        # what its table spans); a page carries every kv head, so a
        # virtual lane keeps its own head's share of what comes back
        out = paged_flash_decode(
            jnp.repeat(q[:, None], g, axis=0), k_pool, v_pool, tables, lens,
            block_size=block_size, interpret=interpret,
        ).reshape(s_, g, g, per, d)
        return jnp.stack([out[:, h, h] for h in range(g)], axis=1)

    def finish(scores, mask, values, spec):
        scores = jnp.where(mask[:, :, None], scores * scale, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        probs = jnp.where(
            jnp.any(mask, axis=-1)[:, :, None, None], probs, 0.0)
        return jnp.einsum(
            spec, probs.astype(values.dtype), values,
            preferred_element_type=f32)

    def taken_rows_only(_):
        tok = (ids[..., None] * block + jnp.arange(block)).reshape(
            s_, g, -1)  # [S, g, kk * block] positions in the lane
        pg = block_tables[lane[:, None, None],
                          jnp.minimum(tok // block_size, nbt - 1)]
        rows = jnp.minimum(pg * block_size + tok % block_size, pool_rows - 1)
        head = jnp.arange(g)[None, :, None]
        mask = jnp.repeat(took, block, axis=-1) & (tok <= t[:, None, None])
        scores = jnp.einsum(
            "sgpd,sgnd->sgpn", qg, k_pool[rows, head],
            preferred_element_type=f32)
        return finish(scores, mask, v_pool[rows, head], "sgpn,sgnd->sgpd")

    def whole_tables(_):
        sel = jnp.any(
            (ids[..., None] == jnp.arange(nb)) & took[..., None], axis=-2)
        blk_mask = sel | ~sparse_lane[:, None, None]  # [S, g, nb]
        mask = jnp.repeat(blk_mask, block, axis=-1) & (
            jnp.arange(cap)[None, None, :] <= t[:, None, None])
        scores = jnp.einsum(
            "sgpd,skgd->sgpk", qg,
            gather_paged_kv(k_pool, block_tables, block_size),
            preferred_element_type=f32)
        return finish(
            scores, mask, gather_paged_kv(v_pool, block_tables, block_size),
            "sgpk,skgd->sgpd")

    if interpret or use_flash:
        out = taken_as_pages()
    else:
        out = jax.lax.cond(
            jnp.any(live & ~sparse_lane), whole_tables, taken_rows_only, None)
    rec = jnp.moveaxis(rec, 1, 0)  # [g, S, kk]
    return out.reshape(s_, hq, d).astype(q.dtype), _pad_record(rec, topk)


# --------------------------------------------------------------------------
# The latent block's paged decode kernel (models/latent.py): one pool for
# key and value, one shared row a token, every head of a lane a row of ONE
# product — a geometry of its own beside ``_paged_decode_kernel`` (kv
# heads, head pairs, groups), so a body and a wrapper of its own.  Kept
# BELOW every other kernel of the file, so that the serialized bodies above
# keep their line numbers (a compile-cache key holds them).
# --------------------------------------------------------------------------

# pool rows one grid step covers (a whole number of pages, each an operand
# of the call).  Of 128 / 256 / 512 / 1024 on a v5e, us a layer-call with
# the schedule's share (chip runs, PR 50; PERF.md): 36.0 / 29.1 / 25.7 / 40.8
# at 8 lanes x ~450 rows, 231 / 178 / 147 / 133 at 8 x 4,096 — a lane of a
# few hundred rows is one step, and 64 page operands a step cost the
# pipeline more bookkeeping than the fewer steps save
LATENT_BLOCK_ROWS = 512


def paged_latent_kernel_supported(pool_dtype, rank, block_size) -> bool:
    """Whether :func:`paged_latent_flash_decode` reads a latent pool of
    this geometry: a page (``block_size`` rows) is whole sublane tiles of
    the pool's type — 16 rows of a 16-bit pool, 8 of a 32-bit one — so it
    is one contiguous window of the pool, and the latent part of a row
    (``rank`` values) is whole 128-lane registers, so the rotated key
    behind it starts on one.  Anything else stays on the XLA reference."""
    itemsize = jnp.dtype(pool_dtype).itemsize
    return (
        itemsize in (2, 4) and block_size is not None
        and block_size % (32 // itemsize) == 0 and rank % 128 == 0
    )


def latent_page_schedule(block_tables, lengths, *, block_size: int,
                         n_blocks: int, ppb: int):
    """The grid of :func:`paged_latent_flash_decode`, from the tables and
    lengths in the program: one step a (lane, compute block of ``ppb``
    pages) that is LIVE — a lane of no rows keeps one step, which writes
    its zeros — so the steps follow the rows the lanes hold, not the
    table's span.

    Returns int32 (steps [] — how many of the ``W = S * ceil(NB / ppb)``
    entries below count —, lane [W], block within the lane [W], blocks a
    lane [S], kv length a lane [S] clamped to its ALLOCATED pages so that a
    hole is never dereferenced, page [W * ppb]).  ``page`` names what each
    of a step's ``ppb`` page operands holds: the lane's live page, or,
    past the lane's last, WHAT THE OPERAND HELD the step before (page 0
    before its first) — an unchanged index is not fetched again, so only
    live pages are read, once.

    A few hundred integers a decode step (every layer of it shares them),
    linear in ``W``: only a lane's LAST block has operands past its pages,
    so what they keep is settled a (lane, operand) — the block before it
    in the lane, else the nearest earlier lane that fetched that operand.
    Comparisons and sums over small dense arrays, which XLA fuses, and
    three gathers; a cumulative sum and a sorted search are kernels each."""
    i32 = jnp.int32
    S, nb = block_tables.shape
    per_lane = -(-nb // ppb)
    tables = jnp.pad(
        block_tables.astype(i32), ((0, 0), (0, per_lane * ppb - nb)),
        constant_values=n_blocks)
    allocated = jnp.sum((tables < n_blocks).astype(i32), axis=1) * block_size
    tables = tables.reshape(S, per_lane, ppb)
    kv_len = jnp.minimum(lengths.astype(i32), allocated)
    n_pages = -(-kv_len // block_size)
    blocks = jnp.maximum(-(-n_pages // ppb), 1)
    tail = n_pages - (blocks - 1) * ppb  # operands its last block fetches
    lanes = jnp.arange(S, dtype=i32)
    slot = jnp.arange(ppb, dtype=i32)
    earlier = lanes[None, :] < lanes[:, None]  # [lane, an earlier lane]
    starts = jnp.sum(jnp.where(earlier, blocks[None, :], 0), axis=1)
    steps = starts[-1] + blocks[-1]

    def of_lane(values, which):
        """``values[which]`` of a few lanes, as a comparison and a sum."""
        return jnp.sum(jnp.where(
            which[..., None] == lanes, values, 0), axis=-1)

    step = jnp.arange(S * per_lane, dtype=i32)
    lane = jnp.minimum(jnp.sum(
        (step[:, None] >= (starts + blocks)[None, :]).astype(i32), axis=1),
        S - 1)
    blk = jnp.minimum(step - of_lane(starts, lane), per_lane - 1)
    live = (
        blk[:, None] * ppb + slot[None, :] < of_lane(n_pages, lane)[:, None]
    ) & (step < steps)[:, None]

    # [lane, operand]: whose block the operand still holds when the lane's
    # last block does not fetch it — its own block before (two or more
    # blocks), else the nearest earlier lane that fetched it, in ITS last
    # block or the one before
    fetches = (slot[None, :] < tail[:, None]) | (blocks[:, None] >= 2)
    source = jnp.max(jnp.where(
        (earlier[:, :, None] & fetches[None, :, :]) | (
            (lanes[None, :] == lanes[:, None]) & (blocks[None, :] >= 2)
        )[:, :, None], lanes[None, :, None], -1), axis=1)
    of = jnp.maximum(source, 0)
    in_last = (of != lanes[:, None]) & (slot[None, :] < of_lane(tail, of))
    kept = tables[
        of, jnp.maximum(of_lane(blocks, of) - jnp.where(in_last, 1, 2), 0),
        slot[None, :]]
    kept = jnp.where(source >= 0, kept, 0)  # before its first fetch: page 0

    page = jnp.where(live, tables[lane, blk], jnp.sum(jnp.where(
        (lane[:, None] == lanes)[:, :, None], kept[None, :, :], 0), axis=1))
    page = jnp.minimum(page, n_blocks - 1)
    return steps, lane, blk, blocks, kv_len, page.reshape(-1)


def _paged_latent_kernel(
    # scalar prefetch (:func:`latent_page_schedule`)
    lane_ref,  # [W] int32 the lane a grid step works on
    blk_ref,  # [W] int32 which of the lane's compute blocks
    blocks_ref,  # [S] int32 compute blocks a lane has (>= 1)
    lengths_ref,  # [S] int32 valid (and allocated) kv length
    qoff_ref,  # [S] int32 absolute position of q row 0
    page_ref,  # [W * ppb] int32: read by the page operands' index maps
    # blocks
    ql_ref,  # [1, s * heads, r]: row i is q position i // heads
    qr_ref,  # [1, s * heads, dr]
    *rest,  # ppb pages [1, block_size, r + dr] of the pool; then o_ref
    # [1, s * heads, r]; then the scratch: rows [ppb * block_size, r + dr]
    # (the step's pages side by side), qs [s * heads, r + dr] (q * scale,
    # rounded once to q's type), m and l [s * heads, 128] f32 (running max
    # and denominator, lane-replicated), acc [s * heads, r] f32
    heads: int,
    scale: float,
):
    """One grid step = one compute block of one lane: the pipeline has
    fetched its live pages (the next step's are in flight), ONE fetch of a
    row serves the score (all ``r + dr`` values: the latent and the
    rotated key) and the weighted sum (its first ``r``), and the lane's
    heads (times its ``s`` positions) are the rows of one product.  The
    online softmax is ``_paged_decode_kernel``'s; a lane's last block
    writes its output, zeros for a lane of no rows."""
    *pages, o_ref, rows_ref, qs_ref, m_ref, l_ref, acc_ref = rest
    step = pl.program_id(0)
    lane, j = lane_ref[step], blk_ref[step]
    n_q, r = acc_ref.shape
    rows = rows_ref.shape[0]
    block_size = rows // len(pages)
    kv_len = lengths_ref[lane]

    @pl.when(j == 0)
    def _init():
        qs_ref[:, :r] = (
            ql_ref[0].astype(jnp.float32) * scale).astype(qs_ref.dtype)
        qs_ref[:, r:] = (
            qr_ref[0].astype(jnp.float32) * scale).astype(qs_ref.dtype)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * rows < kv_len)
    def _attend():
        # what an operand past the lane's last page holds (an earlier
        # page, of any lane) is masked out of the scores and, a finite
        # VALUE times a probability of exactly 0, adds nothing
        for i, page in enumerate(pages):
            rows_ref[i * block_size: (i + 1) * block_size, :] = page[0]
        q_rows = jax.lax.broadcasted_iota(jnp.int32, (n_q, rows), 0)
        kv_pos = j * rows + jax.lax.broadcasted_iota(
            jnp.int32, (n_q, rows), 1)
        mask = (kv_pos < kv_len) & (
            kv_pos <= qoff_ref[lane] + q_rows // heads)
        # the latent against the latent, the rotated parts against each
        # other: two products, both halves on whole registers
        s = jax.lax.dot_general(
            qs_ref[:, :r], rows_ref[:, :r], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) + jax.lax.dot_general(
            qs_ref[:, r:], rows_ref[:, r:], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [n_q, rows]
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # explicit re-mask, as in _paged_decode_kernel: a query row that
        # sees no key of the block has m_new == NEG_INF, exp(s - m_new) 1
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(rows_ref.dtype), rows_ref[:, :r],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )  # [n_q, r]
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == blocks_ref[lane] - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def paged_latent_flash_decode(q_lat, q_rope, pool, block_tables, lengths, *,
                              block_size: int, q_offset, scale: float,
                              interpret: bool = False):
    """:func:`paged_latent_decode_attention` as a Pallas kernel that reads
    a lane's live pages IN PLACE — the same arguments, the same output
    ([S, s, heads, r] in q's type); ``s`` is read from the shape.

    A page is an OPERAND: the pool, viewed ``[n_blocks, block_size,
    r + dr]`` (a free reshape), is handed to the call once a page of a
    compute block (``LATENT_BLOCK_ROWS`` rows), each a window of one page
    whose index the schedule (:func:`latent_page_schedule`) names, and the
    call's own pipeline fetches a page where the index changed — one
    contiguous DMA of ``block_size x (r + dr)`` values, the next step's
    while this one computes.  (A row of 576 values is 4.5 registers wide,
    and Mosaic slices a memory reference by whole tiles only: a window of
    the whole row is what it does copy.)  The grid is DYNAMIC: the live
    compute blocks, lane after lane.  Nothing of the table's span or of
    the pool's size is gathered or copied to float32.

    Arithmetic: MXU operands as stored (``q * scale`` rounded once to q's
    type, ``p`` to the rows' type), float32 scores, running max,
    denominator and accumulator.  A length is clamped to the lane's
    ALLOCATED pages, so a hole is never dereferenced: a free slot or a
    retired lane (all-hole table row) reads nothing and outputs zeros,
    where the gather reference attends to a clamped garbage row — only
    ever for lanes nobody reads.  One device: the form a mesh runs is the
    reference."""
    r = q_lat.shape[-1]
    if pool.shape[1] != 1 or pool.shape[2] != r + q_rope.shape[-1]:
        raise ValueError(
            f"paged_latent_flash_decode: a pool of rows {pool.shape[1:]} "
            f"for queries of {r} + {q_rope.shape[-1]} values")
    if not (interpret
            or paged_latent_kernel_supported(pool.dtype, r, block_size)):
        raise NotImplementedError(
            f"paged_latent_flash_decode does not read a {pool.dtype} pool "
            f"of {r}-wide latents in pages of {block_size} "
            "(paged_latent_kernel_supported)")
    return _paged_latent_attend_local(
        q_lat, q_rope, pool, block_tables, lengths, q_offset,
        block_size=block_size, scale=float(scale),
        block_rows=LATENT_BLOCK_ROWS, interpret=interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "scale", "block_rows", "interpret"),
)
def _paged_latent_attend_local(q_lat, q_rope, pool, block_tables, lengths,
                               q_offset, *, block_size, scale, block_rows,
                               interpret):
    """Jitted, as ``_paged_attend_local``: a decode program traces and
    lowers the kernel once and calls it from each of its latent layers
    (the schedule, the same for every layer of a step, is XLA's to share)."""
    S, s, heads, r = q_lat.shape
    width = r + q_rope.shape[-1]
    n_blocks = pool.shape[0] // block_size
    ppb = max(1, min(block_rows // block_size, block_tables.shape[1]))
    n_q = s * heads
    steps, lane, blk, blocks, kv_len, page = latent_page_schedule(
        block_tables, lengths, block_size=block_size, n_blocks=n_blocks,
        ppb=ppb)

    def lane_block(cols):
        return pl.BlockSpec(
            (1, n_q, cols), lambda i, lane_ref, *_: (lane_ref[i], 0, 0))

    def page_block(k):
        return pl.BlockSpec(
            (1, block_size, width),
            lambda i, *refs: (refs[5][i * ppb + k], 0, 0))

    out = pl.pallas_call(
        functools.partial(_paged_latent_kernel, heads=heads, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(steps,),
            in_specs=[lane_block(r), lane_block(width - r)] + [
                page_block(k) for k in range(ppb)],
            out_specs=lane_block(r),
            scratch_shapes=[
                pltpu.VMEM((ppb * block_size, width), pool.dtype),
                pltpu.VMEM((n_q, width), q_lat.dtype),
                pltpu.VMEM((n_q, 128), jnp.float32),
                pltpu.VMEM((n_q, 128), jnp.float32),
                pltpu.VMEM((n_q, r), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((S, n_q, r), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name="_paged_latent_kernel",
    )(
        lane, blk, blocks, kv_len, q_offset.astype(jnp.int32), page,
        q_lat.reshape(S, n_q, r), q_rope.reshape(S, n_q, width - r),
        *([pool.reshape(n_blocks, block_size, width)] * ppb),
    )
    return out.reshape(S, s, heads, r)


def latent_decode_attention(q_lat, q_rope, pool, block_tables, lengths, *,
                            block_size, q_offset, scale, use_flash=False):
    """The latent block's decode attention in the form chosen for it:
    under ``use_flash`` (``models/decoder.kernel_forms``'s ``paged``: a
    TPU, no mesh, a pool :func:`paged_latent_kernel_supported` reads) the
    kernel :func:`paged_latent_flash_decode`; otherwise (every CPU run, a
    mesh) the XLA reference :func:`paged_latent_decode_attention`."""
    attend = (
        paged_latent_flash_decode if use_flash
        else paged_latent_decode_attention)
    return attend(
        q_lat, q_rope, pool, block_tables, lengths,
        block_size=block_size, q_offset=q_offset, scale=scale)


# --------------------------------------------------------------------------
# Gated power retention of degree 2 (models/hybrid.py, the ``retention``
# mixer; arXiv:2507.04239): attention whose weights are SQUARED scores under
# a gate computed from the token, ``a_tj = ((q_t . k_j) / sqrt(d))^2
# exp(G_t - G_j)``, normalised by their sum.  A square is a dot product of
# degree-2 features, ``phi(q) . phi(k) = (q . k)^2``, so the past of a lane
# is ONE state a kv head — ``sum_j decay phi(k_j) [v_j, 1]^T / d`` — and no
# row.  XLA: at the END of the file, so that no kernel above moves a line
# (a Mosaic payload carries source locations).
# --------------------------------------------------------------------------

RETENTION_EPS = 1e-6  # added to the running sum of weights a row divides by


def power_feature_count(d: int) -> int:
    """Degree-2 features of a ``d``-wide head: the ``d (d + 1) / 2``
    products ``x_a x_b``, a <= b (8,256 at 128)."""
    return d * (d + 1) // 2


@functools.lru_cache(maxsize=8)
def _pair_partners(d: int, key_side: bool) -> np.ndarray:
    """[d, (d / 2 + 1) d] of 0, 1 and 2: column ``(s, a)`` picks channel
    ``(a + s) mod d`` — the partner of channel ``a`` at circular distance
    ``s`` — weighed 2 on the key side where the two differ; the last
    block's upper half (the pairs half the head apart, a second time)
    picks nothing."""
    half = d // 2
    out = np.zeros((d, half + 1, d), np.float32)
    a = np.arange(d)
    for s in range(half + 1):
        live = a if s < half else a[:half]
        out[(live + s) % d, s, live] = 2.0 if key_side and s else 1.0
    return out.reshape(d, (half + 1) * d)


def power_features(x, *, key_side: bool = False):
    """``phi(x)`` [..., d (d + 1) / 2] float32 of ``x`` [..., d], d even:
    the products ``x_a x_b`` of every unordered pair, laid out by their
    circular distance ``s = (b - a) mod d`` — block ``s`` < d / 2 is ``x *
    roll(x, -s)`` (d pairs; block 0 the squares), the last block the d / 2
    pairs half the head apart.  The rotated copies are ONE product with a
    constant of zeros and ones (exact: a column picks one channel), the
    features one multiply over it: no gather, no concatenation of 65
    pieces.  A pair of different channels stands for both orders of the
    square's expansion: ``key_side`` weighs it by 2 (exact in any type), so
    ``power_features(q) . power_features(k, key_side=True) == (q . k)^2``
    — the published map puts ``sqrt(2)`` on either side; the product, and
    so the function, is the same."""
    d = x.shape[-1]
    blocks = d // 2 + 1
    partners = jnp.einsum(
        "...c,cf->...f", x, jnp.asarray(_pair_partners(d, key_side), x.dtype),
        precision=_HIGHEST, preferred_element_type=jnp.float32)
    pairs = (x.astype(jnp.float32)[..., None, :]
             * partners.reshape(*x.shape[:-1], blocks, d))
    return pairs.reshape(*x.shape[:-1], blocks * d)[
        ..., :power_feature_count(d)]


def power_retention_prefill(q, k, v, log_gate, seg_ids, positions,
                            state_pool, chunk_slot, *,
                            precision=None):
    """Gated power retention over a PACKED batch in its chunked form,
    ``RAGGED_ALIGN`` rows at a time: inside a chunk the attention form
    with the chunk's own cumulative log gate, across chunks the expanded
    state (read by ``phi(q)``, advanced by ``phi(k) [v, 1]^T``).

    q          [T, heads, d]; k, v [T, kv heads, d] — heads a multiple of
               kv heads, ``heads // kv heads`` query heads read one state;
               segments start on chunk boundaries (as
               :func:`linear_attention_prefill`)
    log_gate   [T, kv heads] float32, ``log sigmoid`` of the gate: <= 0
    seg_ids, positions [T]; a chunk whose first position is 0 starts from
               a ZERO state
    state_pool [n_slots, d + 1, kv heads, d (d + 1) / 2] float32: a lane's
               state, the value channels (and, last, the running sum of
               weights) by the kv heads by the features — the order the
               chip rests it in whatever its shape says (kv heads on the
               sublanes, features on the lanes: whole tiles)
    chunk_slot [T / RAGGED_ALIGN] int32: the entry that takes the state as
               it stands after this chunk (>= n_slots: none)
    precision  the TESTS' oracle only; no program path sets it.  ``None``,
               the one form served, rounds the inputs of the two LARGE
               products (``phi(q) S`` and ``[v, 1]^T phi(k)``) to bfloat16
               (one MXU pass, sums float32); ``Precision.HIGHEST`` keeps
               them float32 (six passes), so that a test can hold the
               chunked algebra to the attention form at 2e-4 and the
               served rounding to what it costs beside it (PERF.md
               section 2 has both readings on the chip, on gates that
               keep hundreds of tokens)

    Returns (out [T, heads, d] in q's type, state_pool).  The state, the
    gates' sums and every sum of weights are float32; every decay factor
    is ``exp`` of a difference <= 0 (nothing overflows however strong
    the gate)."""
    t, heads, d = q.shape
    kv_heads = k.shape[1]
    per = heads // kv_heads
    c = RAGGED_ALIGN
    n = t // c
    f32 = jnp.float32
    big = f32 if precision is not None else jnp.bfloat16
    causal = jnp.tril(jnp.ones((c, c), bool))
    ones = jnp.ones((c, kv_heads, 1), f32)

    def step(carry, xs):
        state, pool = carry
        qb, kb, vb, gb, ok, pos0, slot = xs
        # a segment's first chunk starts from ZERO: what the carried state
        # weighs is folded into the two factors it meets (no pass over it)
        kept = jnp.where(pos0 == 0, 0.0, 1.0)
        qg = qb.reshape(c, kv_heads, per, d)
        kf = jnp.where(ok[:, None, None], kb, 0).astype(kb.dtype)
        # G_t - G(chunk start), <= 0 and falling; a padding row adds nothing
        cum = jnp.cumsum(jnp.where(ok[:, None], gb, 0.0), axis=0)  # [c, g]
        # inside the chunk: the attention form over its own rows
        s = jnp.einsum("tgpd,sgd->gpts", qg, kf,
                       preferred_element_type=f32)
        rel = cum.T[:, :, None] - cum.T[:, None, :]  # [g, t, s]
        decay = jnp.where(causal, jnp.exp(jnp.minimum(rel, 0.0)), 0.0)
        a = s * s * decay[:, None] / d  # [g, p, t, s]
        vv = jnp.concatenate([vb.astype(f32), ones], axis=-1)  # [c, g, d+1]
        num = jnp.einsum("gpts,sge->tgpe", a, vv, precision=_HIGHEST)
        # across chunks: what the state holds, as row t's gate leaves it
        phi_q = power_features(qg).astype(big)
        read = jnp.einsum("tgpf,egf->tgpe", phi_q, state.astype(big),
                          preferred_element_type=f32, precision=precision)
        num = num + (kept * jnp.exp(cum))[:, :, None, None] * read
        out = num[..., :d] / (num[..., d:] + RETENTION_EPS)
        # the state after the chunk's last valid row
        left = jnp.where(ok[:, None], jnp.exp(cum[-1][None, :] - cum), 0.0)
        phi_k = power_features(kf, key_side=True).astype(big)
        state = (kept * jnp.exp(cum[-1]))[None, :, None] * state + jnp.einsum(
            "tge,tgf->egf", (vv * (left / d)[:, :, None]).astype(big), phi_k,
            preferred_element_type=f32, precision=precision)
        # written where the chunk is a segment's last, skipped elsewhere
        pool = jax.lax.cond(
            slot < pool.shape[0],
            lambda p: jax.lax.dynamic_update_slice(
                p, state[None], (slot, 0, 0, 0)),
            lambda p: p, pool)
        return (state, pool), out.reshape(c, heads, d).astype(q.dtype)

    chunks = lambda x: x.reshape(n, c, *x.shape[1:])  # noqa: E731
    (_, state_pool), out = jax.lax.scan(
        step,
        (jnp.zeros(state_pool.shape[1:], f32), state_pool),
        (chunks(q), chunks(k), chunks(v), chunks(log_gate.astype(f32)),
         chunks(seg_ids >= 0), chunks(positions)[:, 0], chunk_slot),
    )
    return out.reshape(t, heads, d), state_pool


def power_retention_step(q, k, v, log_gate, state):
    """One decode step of the same, the recurrence itself: q [S, heads,
    d]; k, v [S, kv heads, d]; log_gate [S, kv heads] float32; state [S, d +
    1, kv heads, d (d + 1) / 2] float32 -> (out [S, heads, d] in q's
    type, the advanced state ``e^gate S + [v, 1] phi(k)^T / d``).

    The XLA form — what a CPU, a mesh and the tests' oracle run; a TPU
    with no mesh steps the owned entries in ONE pass instead
    (``ops/retention.power_retention_step_fused``, chosen by
    ``models/decoder.kernel_forms``).  One elementwise pass advances the
    state where it lies and a multiply and sum over the features reads it
    again, float32 throughout: two fusions, the state read twice and
    written once, over every entry handed in (0.63-0.67 ms a layer-step
    of four lanes at the published widths whatever the lanes that are
    live, against 0.70 with the read as a product on the matmul unit;
    PERF.md section 6, PRs 51 and 52).  It leaves the pool in the order
    it rests in: the product wanted the value channels on the sublanes,
    and a decode chunk then re-laid every layer's pool on entry and on
    exit."""
    S, heads, d = q.shape
    kv_heads = k.shape[1]
    f32 = jnp.float32
    vv = jnp.concatenate(
        [v.astype(f32), jnp.ones((S, kv_heads, 1), f32)], axis=-1)
    phi_q = power_features(q.reshape(S, kv_heads, heads // kv_heads, d))
    phi_k = power_features(k, key_side=True)  # [S, g, F]
    state = (jnp.exp(log_gate.astype(f32))[:, None, :, None] * state
             + jnp.swapaxes(vv / d, 1, 2)[..., None] * phi_k[:, None])
    num = jnp.sum(phi_q[:, None] * state[:, :, :, None, :], axis=-1)
    num = jnp.transpose(num, (0, 2, 3, 1))  # [S, g, p, d + 1]
    out = num[..., :d] / (num[..., d:] + RETENTION_EPS)
    return out.reshape(S, heads, d).astype(q.dtype), state
