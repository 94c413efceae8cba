"""Rotary position embeddings (RoPE) for the decoder stack.

Angles are precomputed once per model (host) and passed in as an array; the
application is a pure elementwise op XLA fuses into the QK projections.
Uses the split-halves convention (Llama/Mistral style, matching HF weights).
"""

from __future__ import annotations

import math

import jax.numpy as jnp


def rope_angles(head_dim: int, max_len: int, theta: float = 10000.0):
    """Return (cos, sin), each [max_len, head_dim/2], float32."""
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    pos = jnp.arange(max_len, dtype=jnp.float32)
    angles = jnp.outer(pos, inv_freq)  # [max_len, head_dim/2]
    return jnp.cos(angles), jnp.sin(angles)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature (arXiv:2309.00071, as DeepSeek-V2
    applies it): ``0.1 * mscale * ln(factor) + 1``; 1 without scaling.
    The block multiplies its softmax scale by the SQUARE of
    ``yarn_mscale(factor, mscale_all_dim)`` and its cos / sin tables by
    ``yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)``."""
    if factor <= 1.0:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, factor: float, original_max_len: int,
                  beta_fast: float = 32.0, beta_slow: float = 1.0):
    """YaRN's blended inverse frequencies [dim/2], float32: pair j keeps
    its frequency where it turns more than ``beta_fast`` times within the
    original context (extrapolation), takes ``1 / factor`` of it where it
    turns fewer than ``beta_slow`` times (interpolation), and a linear
    ramp over the pair index in between."""

    def pair_turning(rotations: float) -> float:
        return (
            dim * math.log(original_max_len / (rotations * 2 * math.pi))
        ) / (2 * math.log(theta))

    low = max(math.floor(pair_turning(beta_fast)), 0)
    high = min(math.ceil(pair_turning(beta_slow)), dim - 1)
    if low == high:
        high += 0.001  # a ramp of no width is a step
    plain = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ramp = jnp.clip(
        (jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0
    )
    return plain / factor * ramp + plain * (1.0 - ramp)


def yarn_rope_angles(dim: int, max_len: int, theta: float, *, factor: float,
                     original_max_len: int, beta_fast: float = 32.0,
                     beta_slow: float = 1.0, mscale: float = 1.0,
                     mscale_all_dim: float = 0.0):
    """(cos, sin), each [max_len, dim/2] float32, of YaRN-scaled RoPE —
    what :func:`apply_rope` takes in place of :func:`rope_angles`'s tables."""
    inv_freq = yarn_inv_freq(
        dim, theta, factor, original_max_len, beta_fast, beta_slow
    )
    angles = jnp.outer(jnp.arange(max_len, dtype=jnp.float32), inv_freq)
    scale = yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)
    return jnp.cos(angles) * scale, jnp.sin(angles) * scale


def apply_rope(x, cos, sin, positions):
    """Rotate q or k.

    Args:
      x: [batch, seq, heads, head_dim]
      cos, sin: [max_len, head_dim/2] tables from :func:`rope_angles`
      positions: [batch, seq] int32 absolute positions (supports ragged
        decode — each lane carries its own offset).  Contract: positions
        MUST be < max_len — JAX gather clamps out-of-bounds indices, so a
        position past the table silently reuses the last row's angles.
        Size tables to the model's max_seq_len (the decode engine bounds
        positions accordingly).
    """
    dtype = x.dtype
    c = cos[positions][:, :, None, :]  # [b, s, 1, hd/2]
    s = sin[positions][:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(dtype)
