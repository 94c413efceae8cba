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
matmul, so no precision flag applies.  XLA; ``[T, n, d]`` is never whole:
the prefill runs ``RAGGED_ALIGN`` rows of EVERY chunk a step (all chunks
side by side from a zero state), then one pass over the chunks carries
the true state and adds what each row owes to the state its chunk
started from.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

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
                           last_rows):
    """The selective scan over a PACKED batch.

    c      [T, d] conv output; delta [T, d] float32 step sizes (> 0)
    a      [n, d] float32 (negative); d_skip [d]
    b, cc  [T, n] the input and output maps of each row
    seg_ids, positions [T]; segments start on ``RAGGED_ALIGN`` boundaries,
           so a chunk belongs to one segment (or is padding); a chunk whose
           first position is 0 starts from a ZERO state
    last_rows [B] the packed row of each segment's last token

    Returns (g [T, d] in c's type, the state after each segment's last row
    float32 [B, n, d]).  A padding row leaves the state as it is."""
    t, d = c.shape
    n = a.shape[0]
    rows = RAGGED_ALIGN
    chunks = t // rows
    f32 = jnp.float32
    delta = jnp.where((seg_ids >= 0)[:, None], delta.astype(f32), 0.0)

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
