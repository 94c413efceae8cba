"""What any architecture's seeded weights and lower-precision controls
want: the per-channel int8 quantizer, the roundings a control puts a
weight or an activation through, and the ``Control`` the plain reference
takes.  The tensors themselves — their names, shapes, draw and sharding —
belong to the architecture's package (``architectures/<name>/weights.py``),
which imports from here.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax.numpy as jnp

SCALE = "__scale"  # suffix of a quantized tensor's per-channel scale


def quantize_int8(w):
    """w [in, out] f32 -> (int8 [in, out], f32 scale [out])."""
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=0) / 127.0, 1e-12)
    q = jnp.clip(jnp.round(w / scale[None, :]), -127, 127).astype(jnp.int8)
    return q, scale


# ---- lower-precision controls (benchmark/calibrate.py, tests) -------------

def requantize(w32, bits: int, group: int = 128):
    """float32 -> the nearest lower serving precision -> float32.

    8: per-output-channel absmax int8 (the step below bfloat16 weights).
    4: grouped absmax int4, groups of ``group`` along the input axis (the
    step below int8; ``models/quant.py``'s w4 scheme)."""
    if bits == 8:
        q, scale = quantize_int8(w32)
        return q.astype(jnp.float32) * scale[None, :]
    if bits != 4:
        raise ValueError("bits must be 8 or 4")
    n_in, n_out = w32.shape
    g = min(group, n_in)
    while n_in % g:
        g -= 1
    wg = w32.reshape(n_in // g, g, n_out)
    scale = jnp.maximum(jnp.max(jnp.abs(wg), axis=1) / 7.0, 1e-12)
    q = jnp.clip(jnp.round(wg / scale[:, None, :]), -7, 7)
    return (q * scale[:, None, :]).reshape(n_in, n_out)


def to_int4(w32):
    return requantize(w32, 4)


def to_int8(w32):
    return requantize(w32, 8)


def to_fp8(w32):
    """float32 -> float8 (e4m3, per-output-channel scaled to its range)
    -> float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(w32), axis=0) / 448.0, 1e-12)
    q = (w32 / scale[None, :]).astype(jnp.float8_e4m3fn)
    return q.astype(jnp.float32) * scale[None, :]


def act_int8(x):
    """float32 -> int8 and back, absmax over the last axis: a row of
    activations per token, or a key / value per token and head."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0,
                        1e-12)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def act_fp8(x):
    """The same through float8 (e4m3) scaled to its range.  A reading
    through this is a lower bound on the chip (see ``kv_only_controls``);
    int8, rounded in arithmetic, is the control a limit is set from."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 448.0,
                        1e-12)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


class Control(NamedTuple):
    """One lower-precision control: what the plain reference rounds.
    ``weights`` every float32 weight matrix, ``act`` every matmul input,
    ``kv`` the keys and values as a cache would hold them."""

    weights: Optional[Callable] = None
    act: Optional[Callable] = None
    kv: Optional[Callable] = None
