"""The Mamba-1 mixer's two stateful ops (models/hybrid.py, kind "mamba";
arXiv:2312.00752): a depthwise causal conv whose past is a window of its
last inputs, and the selective scan — a DIAGONAL state-space recurrence
per channel ``d`` and state index ``n``,

    h_t[n, d] = exp(dt_t[d] A[n, d]) h_{t-1}[n, d] + dt_t[d] c_t[d] B_t[n]
    g_t[d]    = sum_n C_t[n] h_t[n, d] + D[d] c_t[d]

Both in a packed prefill form (segment-aware: a segment's first row sees
neither the rows nor the state before it) and a one-token decode form.
The state is ``[n, d]`` — the wide channel axis minor, so that a float32
entry is whole (8, 128) tiles — float32 throughout; nothing here is a
matmul, so no precision flag applies.  ``[T, n, d]`` is never whole, in
either of the prefill scan's two forms (:func:`selective_scan_prefill`
chooses by what the engine observed of the backend, as
``ops/attention.paged_decode_attention`` does):

* the Pallas kernel (:func:`_selective_scan_kernel`, TPU, no mesh) walks
  the packed rows ONCE, in order, the decode step's own arithmetic a row,
  with ``h`` on the chip from a prompt's first row to its last (in
  registers through a chunk, in VMEM scratch between chunks): nothing of
  ``[chunks, n, d]`` is carried through HBM between rows, and what
  leaves the chip is ``g`` and the state at each chunk's end;
* the XLA form (CPU, a mesh, the tests' oracle) runs ``RAGGED_ALIGN``
  rows of EVERY chunk a step (all chunks side by side from a zero state),
  then one pass over the chunks carries the true state and adds what each
  row owes to the state its chunk started from.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from docqa_tpu.ops.attention import RAGGED_ALIGN


def causal_conv_prefill(u, weight, bias, positions):
    """``c_t = silu(bias + sum_j weight[j] * u_{t - (K-1) + j})`` per
    channel over PACKED rows: a tap that would reach before its segment's
    first row (``positions[t] < K - 1 - j``) reads zero, whatever row of
    another segment lies there.

    u [T, d]; weight [K, d]; bias [d] or None; positions [T] int32.
    Returns c [T, d] in u's type (sums in float32)."""
    taps = weight.shape[0]
    f32 = jnp.float32
    acc = u.astype(f32) * weight[taps - 1].astype(f32)
    for back in range(1, taps):
        past = jnp.pad(u, ((back, 0), (0, 0)))[: u.shape[0]]
        seen = (positions >= back)[:, None]
        acc = acc + jnp.where(seen, past.astype(f32), 0.0) * weight[
            taps - 1 - back].astype(f32)
    if bias is not None:
        acc = acc + bias.astype(f32)
    return jax.nn.silu(acc).astype(u.dtype)


def conv_window_of(u, positions, last_rows, width: int):
    """The last ``width`` conv inputs of each segment, oldest first, zero
    where the segment is shorter: [B, width, d] from u [T, d] and the
    packed row of each segment's last token."""
    back = jnp.arange(width - 1, -1, -1)
    rows = last_rows[:, None] - back[None, :]
    seen = positions[last_rows][:, None] >= back[None, :]
    return jnp.where(seen[..., None], u[jnp.maximum(rows, 0)], 0)


def causal_conv_step(u, window, weight, bias):
    """One decode step of the same: u [S, d] (one token a lane), window
    [S, K - 1, d] the lane's last inputs, oldest first.  Returns (c [S, d]
    in u's type, the window shifted by one)."""
    f32 = jnp.float32
    full = jnp.concatenate([window.astype(u.dtype), u[:, None, :]], axis=1)
    acc = jnp.sum(full.astype(f32) * weight.astype(f32)[None], axis=1)
    if bias is not None:
        acc = acc + bias.astype(f32)
    return jax.nn.silu(acc).astype(u.dtype), full[:, 1:].astype(window.dtype)


def selective_scan_prefill(c, delta, a, b, cc, d_skip, seg_ids, positions,
                           last_rows, *, use_flash=False, interpret=False):
    """The selective scan over a PACKED batch.

    c      [T, d] conv output; delta [T, d] float32 step sizes (> 0)
    a      [n, d] float32 (negative); d_skip [d]
    b, cc  [T, n] the input and output maps of each row
    seg_ids, positions [T]; segments start on ``RAGGED_ALIGN`` boundaries,
           so a chunk belongs to one segment (or is padding); a chunk whose
           first position is 0 starts from a ZERO state
    last_rows [B] the packed row of each segment's last token

    Returns (g [T, d] in c's type, the state after each segment's last row
    float32 [B, n, d]).  A padding row leaves the state as it is: its
    ``delta`` is masked to 0, so the decay is ``exp(0) = 1`` and the input
    term 0.  Under ``use_flash`` (``models/decoder.kernel_forms``'s
    ``scan``: a TPU and no mesh) the Pallas kernel walks the rows
    (``interpret`` for a CPU test of it); otherwise the XLA form."""
    f32 = jnp.float32
    delta = jnp.where((seg_ids >= 0)[:, None], delta.astype(f32), 0.0)
    if use_flash or interpret:
        g, h_chunk_end = _scan_rows_in_order(
            c, delta, a, b, cc, d_skip,
            (positions[::RAGGED_ALIGN] == 0).astype(jnp.int32),
            interpret=interpret)
        # rows past a segment's last token are padding: the state at its
        # last chunk's end IS the state after its last token.  ``g`` leaves
        # the kernel in float32 and is cast HERE, where XLA fuses the cast
        # into the gate that reads it, as it does the XLA form's: a
        # bfloat16 ``g`` in HBM is a rounding the XLA form never makes on
        # the chip (PERF.md 6, PR 43)
        return g.astype(c.dtype), h_chunk_end[last_rows // RAGGED_ALIGN]
    return _selective_scan_chunked(
        c, delta, a, b, cc, d_skip, positions, last_rows)


def _selective_scan_chunked(c, delta, a, b, cc, d_skip, positions, last_rows):
    """The XLA form of :func:`selective_scan_prefill` (``delta`` already
    masked): two passes, the states of all chunks side by side in HBM."""
    t, d = c.shape
    n = a.shape[0]
    rows = RAGGED_ALIGN
    chunks = t // rows
    f32 = jnp.float32

    def by_chunk(x):  # [T, w] -> [chunks, rows, w]
        return x.reshape(chunks, rows, x.shape[-1])

    def by_row(x):  # [T, w] -> [rows, chunks, w]: a step takes x[r], whole
        return by_chunk(x).swapaxes(0, 1)

    def local(h, xs):  # row r of every chunk, from a zero state
        dt, c_r, b_r, cc_r = (x.astype(f32) for x in xs)
        h = jnp.exp(dt[:, None, :] * a[None]) * h + (
            (dt * c_r)[:, None, :] * b_r[:, :, None])
        return h, jnp.sum(h * cc_r[:, :, None], axis=1)

    h_end, g_local = jax.lax.scan(
        local, jnp.zeros((chunks, n, d), f32),
        (by_row(delta), by_row(c), by_row(b), by_row(cc)))
    # delta summed from a chunk's first row: what the chunk's start state
    # has decayed by when row r is done
    since = jnp.cumsum(by_chunk(delta), axis=1)

    def carry(h, xs):  # the true state a chunk starts from and ends with
        h_chunk_end, total, pos0 = xs
        h = jnp.where(pos0 == 0, 0.0, h)
        return jnp.exp(total[None, :] * a) * h + h_chunk_end, h

    _, h_start = jax.lax.scan(
        carry, jnp.zeros((n, d), f32),
        (h_end, since[:, -1], positions[::rows]))
    # what each row owes to the state its chunk started from: ONE chain
    # of elementwise terms, a state index each, so that nothing of
    # [chunks, rows, n, d] is ever written out
    out_map = by_chunk(cc).astype(f32)
    owed = sum(
        jnp.exp(since * a[j]) * h_start[:, None, j, :] * out_map[:, :, j, None]
        for j in range(n))
    g = (g_local.swapaxes(0, 1) + owed).reshape(t, d)
    g = g + c.astype(f32) * d_skip.astype(f32)
    # a segment's last chunk: its rows past the last token are padding,
    # which leaves the state as the last token left it
    last = last_rows // rows
    h_last = jnp.exp(since[last, -1][:, None, :] * a[None]) * h_start[last] + (
        h_end[last])
    return g.astype(c.dtype), h_last


# channels a grid step walks with ``h`` in registers (at [16, 512] float32
# it is 8 of them; wider blocks read no faster on the chip, PERF.md 6,
# PR 43), and rows a loop body: one aligned (8, 128) tile of ``g``
_SCAN_BLOCK = 512
_SCAN_UNROLL = 8


def _scan_block(d: int) -> int:
    """Channels a grid step: whole lane tiles where ``d`` has them, else
    ``d`` itself (a toy)."""
    return next((w for w in (_SCAN_BLOCK, 128) if d % w == 0), d)


def _selective_scan_kernel(starts_ref, c_ref, dt_ref, bc_ref, a_ref, skip_ref,
                           g_ref, h_end_ref, h_scr, x_scr, cols_scr,
                           *, n):
    """One chunk (``RAGGED_ALIGN`` packed rows) of one block of channels.
    Grid (chunks, channel blocks), both in order: ``h_scr`` [blocks, n,
    d_blk] holds every block's state from one chunk to the next.

    starts_ref [chunks] int32 (scalar prefetch): 1 where the chunk's first
        position is 0 — the state starts from zero there
    c_ref, dt_ref [rows, d_blk]; a_ref [n, d_blk]; skip_ref [1, d_blk]
    bc_ref [1, 2n, rows]: the chunk's ``b`` over ``cc``, a row a LANE
    g_ref [rows, d_blk] float32; h_end_ref [1, n, d_blk] the state at the
        chunk's end
    x_scr [rows, d_blk]; cols_scr [rows, 2n, 128]: row t's ``b`` and
        ``cc`` down the sublanes, the same in every lane
    """
    chunk, block = pl.program_id(0), pl.program_id(1)
    rows, d_blk = c_ref.shape
    f32 = jnp.float32

    @pl.when((chunk == 0) | (starts_ref[chunk] == 1))
    def _():
        h_scr[block] = jnp.zeros((n, d_blk), f32)

    @pl.when(block == 0)  # once a chunk, for all its channel blocks
    def _():
        def spread(i, carry):
            at = pl.multiple_of(i * _SCAN_UNROLL, _SCAN_UNROLL)
            # lanes at .. at + 7 to the front: a dynamic rotate, then
            # static lane slices
            front = pltpu.roll(bc_ref[0], shift=(rows - at) % rows, axis=1)
            for j in range(_SCAN_UNROLL):
                cols_scr[at + j] = jnp.broadcast_to(
                    front[:, j:j + 1], (2 * n, 128))
            return carry

        jax.lax.fori_loop(0, rows // _SCAN_UNROLL, spread, 0)

    cf = c_ref[...].astype(f32)
    x_scr[...] = dt_ref[...] * cf
    a = a_ref[...]

    def over_the_block(col):  # [n, 128], every lane alike -> [n, d_blk]
        wide = jnp.concatenate([col] * -(-d_blk // 128), axis=1)
        return wide if wide.shape[1] == d_blk else wide[:, :d_blk]

    def walk(i, h):
        at = pl.multiple_of(i * _SCAN_UNROLL, _SCAN_UNROLL)
        dt = dt_ref[pl.ds(at, _SCAN_UNROLL), :]
        x = x_scr[pl.ds(at, _SCAN_UNROLL), :]
        out = []
        for j in range(_SCAN_UNROLL):  # selective_scan_step, a row
            cols = cols_scr[at + j]
            b_t, cc_t = over_the_block(cols[:n]), over_the_block(cols[n:])
            h = jnp.exp(dt[j:j + 1] * a) * h + x[j:j + 1] * b_t
            out.append(jnp.sum(h * cc_t, axis=0, keepdims=True))
        g_ref[pl.ds(at, _SCAN_UNROLL), :] = jnp.concatenate(out, axis=0)
        return h

    h_scr[block] = jax.lax.fori_loop(
        0, rows // _SCAN_UNROLL, walk, h_scr[block])
    g_ref[...] += cf * skip_ref[...]
    h_end_ref[0] = h_scr[block]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _scan_rows_in_order(c, delta, a, b, cc, d_skip, starts, *, interpret):
    """The kernel's call: (g [T, d], the state at every chunk's end
    [chunks, n, d]), both float32.  Jitted so that a prefill program
    traces and lowers the kernel ONCE and calls it from each of its
    state-space layers (``ops/attention._paged_attend_local`` likewise)."""
    t, d = c.shape
    n = a.shape[0]
    rows = RAGGED_ALIGN
    chunks = t // rows
    d_blk = _scan_block(d)
    f32 = jnp.float32
    # b over cc, a chunk's rows along the lanes: [chunks, 2n, rows]
    bc = jnp.concatenate([b, cc], axis=1).astype(f32)
    bc = bc.reshape(chunks, rows, 2 * n).swapaxes(1, 2)
    by_rows = pl.BlockSpec((rows, d_blk), lambda k, j, *_: (k, j))
    return pl.pallas_call(
        functools.partial(_selective_scan_kernel, n=n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(chunks, d // d_blk),
            in_specs=[
                by_rows,
                by_rows,
                pl.BlockSpec((1, 2 * n, rows), lambda k, j, *_: (k, 0, 0)),
                pl.BlockSpec((n, d_blk), lambda k, j, *_: (0, j)),
                pl.BlockSpec((1, d_blk), lambda k, j, *_: (0, j)),
            ],
            out_specs=[
                by_rows,
                pl.BlockSpec((1, n, d_blk), lambda k, j, *_: (k, 0, j)),
            ],
            scratch_shapes=[
                pltpu.VMEM((d // d_blk, n, d_blk), f32),
                pltpu.VMEM((rows, d_blk), f32),
                pltpu.VMEM((rows, 2 * n, 128), f32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((t, d), f32),
            jax.ShapeDtypeStruct((chunks, n, d), f32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret, name="_selective_scan_kernel",
    )(starts, c, delta, bc, a, d_skip.astype(f32).reshape(1, d))


def selective_scan_step(c, delta, a, b, cc, d_skip, h):
    """One decode step, the recurrence itself: c, delta [S, d]; b, cc
    [S, n]; h [S, n, d] float32 -> (g [S, d] in c's type, the advanced
    state)."""
    f32 = jnp.float32
    dt, cf = delta.astype(f32), c.astype(f32)
    h = jnp.exp(dt[:, None, :] * a[None]) * h + (
        (dt * cf)[:, None, :] * b.astype(f32)[:, :, None])
    g = jnp.sum(h * cc.astype(f32)[:, :, None], axis=1) + cf * d_skip.astype(
        f32)
    return g.astype(c.dtype), h
