"""Int8 (w8a16) and grouped-int4 (w4a16) weight-only decoder quantization.

Why this exists: BASELINE config 3 names a Mistral-7B-class generator
(reference: Ollama/llama.cpp host-side, ``llm-qa/main.py:66-69``), but one
v5e chip has 16 GB HBM and a 7B bf16 weight tree is ~14.5 GB — it OOMs
once the KV cache and XLA workspace join it (measured).  Weight-only int8
halves the tree to ~7.2 GB *and* halves the bytes read per decode step,
which is the whole cost of bandwidth-bound decoding.  Int4 halves it
again (~3.6 GB at 7B) — the llama.cpp default the reference actually ran
(Ollama ships q4 GGUF) — at the cost of a coarser grid.

Schemes (for each 2-D weight ``w [in, out]``):

* **int8, per-output-channel absmax** —
  ``scale[out] = max(|w|, axis=in) / 127``; worst-case relative weight
  error ≤ 1/254.  No grouping needed at 8 bits.
* **int4, grouped absmax** — 15 levels is too coarse for a whole input
  column, so rows are grouped along ``in`` (default 128, llama.cpp/AWQ
  convention): ``scale[in//g, out] = absmax over the group / 7``.  The
  scale overhead is one f32 per 128 int4s (~6%).

The forward pass never holds a dequantized tree (``decoder._qmatmul``).
int8: the scale is per OUTPUT column, so it commutes with the contraction
— the dot reads a bare ``convert`` of the stored int8 array and its
small ``[rows, out]`` result is scaled in float32 by the stored scale and
rounded to the activation type.  Scaling the WEIGHT instead asks for an
``[in, out]`` product, which the compiler fuses into the dot's read at
some sites and writes to HBM as a bf16 copy of the weight at others
(PERF.md section 6).  int4: a scale per 128 input rows does not commute,
so ``q.astype(bf16) * scale`` feeds the dot as a broadcast-multiply
producer.  The int4 weight is STORED grouped-3-D ``[groups, g, out]`` so
its dequant is a reshape-free broadcast multiply (see
``decoder._qmatmul``; a 2-D store would interpose reshapes the compiler
may refuse to fuse through).  XLA TPU stores int4 packed two-per-byte.
Activations stay bf16: no calibration data needed.

Embeddings and norm gains stay in bf16/f32: ``tok_emb`` is a gather (only
``seq`` rows read per step — no bandwidth win) and norm vectors are tiny.

Memory discipline: ``init_quantized_decoder_params`` quantizes tensor-by-
tensor as it initializes, so peak HBM is the quantized tree plus ONE float
tensor — a quantize-after-full-init would need bf16 + int8 simultaneously
(~21 GB at 7B, un-materializable on the target chip).
"""

from __future__ import annotations

import functools
import logging
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from docqa_tpu.config import DecoderConfig

Params = Dict[str, jax.Array]

_log = logging.getLogger(__name__)
_WARNED_DEGRADED_DIMS: set = set()

SCALE_SUFFIX = "__scale"

GROUP_SIZE = 128  # int4 grouping along the `in` axis (llama.cpp/AWQ size)

# 2-D matmul weights that quantize; everything else passes through
# by NAME: the projections of every block's tree that end in one of these
# (the two-mixer block's output gate, ``w_ogate``, among them); norms,
# the embedding, routers and expert stacks stay in the activation type
_QUANT_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "w_ogate")


def _int4_group(in_dim: int, group: Optional[int] = None) -> int:
    """Largest usable group ≤ GROUP_SIZE that divides ``in_dim`` (tiny test
    configs have in_dim < 128)."""
    g = min(group or GROUP_SIZE, in_dim)
    while in_dim % g:
        g -= 1
    if g < 16 and in_dim >= 16 and in_dim not in _WARNED_DEGRADED_DIMS:
        # e.g. in_dim=298 degrades to g=2: the f32 scale tensor then costs
        # 2 bytes per 0.5-byte weight, so "int4" quietly lands larger than
        # int8 with only 15 quant levels — defeats the mode.  Warn once per
        # distinct in_dim: quantize_decoder_params hits this helper for
        # every quantized tensor (7 keys x layers).
        _WARNED_DEGRADED_DIMS.add(in_dim)
        _log.warning(
            "int4 group size degraded to %d for in_dim=%d (no divisor <= %d "
            ">= 16); scale overhead now exceeds int8 — prefer quant_bits=8 "
            "for this shape",
            g,
            in_dim,
            GROUP_SIZE,
        )
    return g


def probe_int4_support() -> Tuple[bool, str]:
    """Prove the backend can execute S4 (int4) programs end-to-end.

    ``(True, "")`` when a toy device_put + jit matmul + fetch succeeds;
    ``(False, reason)`` otherwise.  Callers gate any real int4 work on
    this: on a backend without S4 support a toy program fails fast, where
    a full-program int4 compile attempt costs minutes before it does.
    """
    import numpy as np

    try:
        w4 = jax.device_put(
            jnp.arange(256, dtype=jnp.int8).reshape(16, 16).astype(jnp.int4)
        )
        x4 = jnp.ones((4, 16), jnp.bfloat16)
        # One-shot capability probe: the throwaway wrapper and bf16
        # accumulation are the point — only "does an S4 program lower and
        # execute" matters, never the product's numerics or a warm cache.
        np.asarray(jax.jit(lambda x, w: x @ w.astype(jnp.bfloat16))(x4, w4))  # docqa-lint: disable=dtype-flow,retrace-hazard
        del w4, x4
        return True, ""
    except Exception as e:
        return False, f"{e!r:.200}"


def is_quantized(params: Params) -> bool:
    return any(k.endswith(SCALE_SUFFIX) for k in params)


def should_quantize(name: str) -> bool:
    if name == "lm_head":
        return True
    return any(name.endswith(f"_{k}") for k in _QUANT_KEYS)


@jax.jit
def quantize_array(w: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """w [in, out] → (int8 [in, out], f32 scale [out]) per-column absmax."""
    w32 = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w32), axis=0) / 127.0
    scale = jnp.maximum(scale, 1e-12)  # dead column → scale 0 → NaN guard
    q = jnp.clip(jnp.round(w32 / scale[None, :]), -127, 127).astype(jnp.int8)
    return q, scale


@functools.partial(jax.jit, static_argnums=(1,))
def _quantize_int4_jit(w: jax.Array, g: int) -> Tuple[jax.Array, jax.Array]:
    in_dim, out_dim = w.shape
    w32 = w.astype(jnp.float32).reshape(in_dim // g, g, out_dim)
    scale = jnp.max(jnp.abs(w32), axis=1) / 7.0  # [groups, out]
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(w32 / scale[:, None, :]), -7, 7)
    return q.astype(jnp.int4), scale


def quantize_array_int4(
    w: jax.Array, group: Optional[int] = None
) -> Tuple[jax.Array, jax.Array]:
    """w [in, out] → (int4 [in//g, g, out], f32 scale [in//g, out])
    grouped absmax.

    The quantized weight is STORED 3-D, grouped layout — dequant is then
    a pure broadcast multiply (``q.astype(bf16) * scale[:, None, :]``)
    feeding a two-axis ``dot_general``, a producer shape XLA can fuse into
    the dot's operand read.  A 2-D store would need
    reshape(dequant(reshape)) around the multiply, a pattern the compiler
    may materialize as a full bf16 tree (14.5 GB at 7B — un-servable).
    Fused under jit like ``quantize_array``: the eager op sequence would
    materialize several f32 temporaries per tensor on the transient-fit
    checkpoint-quantization path."""
    g = _int4_group(w.shape[0], group)
    return _quantize_int4_jit(w, g)


def quantize_decoder_params(params: Params, bits: int = 8) -> Params:
    """Quantize an existing float tree (fits when the float tree fits).

    In int4 mode ``lm_head`` stays int8: the output projection's logit
    errors bite directly into token choice (llama.cpp's q4 presets keep
    it at higher precision for the same reason) and it is ~3 % of a 7B
    tree's bytes — negligible bandwidth, meaningful quality."""
    if bits not in (4, 8):
        raise ValueError(f"quantization bits must be 4 or 8, got {bits}")
    out: Params = {}
    for name, w in params.items():
        if should_quantize(name) and w.ndim == 2:
            use_int8 = bits == 8 or name == "lm_head"
            q, scale = (
                quantize_array(w) if use_int8 else quantize_array_int4(w)
            )
            out[name] = q
            out[name + SCALE_SUFFIX] = scale
        else:
            out[name] = w
    return out


def init_quantized_decoder_params(
    rng: jax.Array,
    cfg: DecoderConfig,
    host_init: bool = False,
    bits: int = 8,
    host_seed: Optional[int] = None,
    mesh=None,
) -> Params:
    """Random-init directly into int8 — tensor-by-tensor, so a 7B tree
    peaks at ~7.2 GB + one float tensor instead of bf16+int8 together.

    Consumes ``decoder_param_schema`` (the same generator
    ``init_decoder_params`` uses), drawing RNG keys in the identical
    order — so this IS the float init, quantized, by construction.

    ``host_init``: draw AND quantize on the host (numpy), ``device_put``
    only the int8/scale/bf16 results — mirrors
    ``init_decoder_params(host_init=True)``'s numpy stream (so the int8
    engine at seed s is the quantization of the float engine at seed s),
    and with ``mesh`` every tensor lands directly under its target
    sharding (``decoder.param_putter``).  Rounding is numpy's
    round-half-to-even, same as XLA's."""
    from docqa_tpu.models.decoder import decoder_param_schema, param_putter

    import numpy as _np

    if bits not in (4, 8):
        raise ValueError(f"quantization bits must be 4 or 8, got {bits}")

    if host_init:
        import ml_dtypes as _ml

        from docqa_tpu.utils import host_seed_from_rng

        put = param_putter(cfg, mesh)
        host_rng = _np.random.default_rng(host_seed_from_rng(rng, host_seed))
        out: Params = {}
        for name, kind, shape, fan_in in decoder_param_schema(cfg):
            if kind == "ones":
                out[name] = put(name, _np.ones(shape, jnp.bfloat16))
                continue
            w = host_rng.standard_normal(shape, _np.float32) * (
                fan_in ** -0.5
            )
            if should_quantize(name) and (bits == 8 or name == "lm_head"):
                scale = _np.maximum(
                    _np.max(_np.abs(w), axis=0) / 127.0, 1e-12
                ).astype(_np.float32)
                q = _np.clip(
                    _np.round(w / scale[None, :]), -127, 127
                ).astype(_np.int8)
                out[name] = put(name, q)
                out[name + SCALE_SUFFIX] = put(name + SCALE_SUFFIX, scale)
            elif should_quantize(name):  # int4, grouped (3-D store)
                in_dim, out_dim = shape
                g = _int4_group(in_dim)
                wg = w.reshape(in_dim // g, g, out_dim)
                scale = _np.maximum(
                    _np.max(_np.abs(wg), axis=1) / 7.0, 1e-12
                ).astype(_np.float32)
                q = _np.clip(_np.round(wg / scale[:, None, :]), -7, 7)
                out[name] = put(name, q.astype(_ml.int4))
                out[name + SCALE_SUFFIX] = put(name + SCALE_SUFFIX, scale)
            else:
                out[name] = put(name, w.astype(jnp.bfloat16))
            del w
        return out

    keys = iter(jax.random.split(rng, 8 + 8 * cfg.num_layers))
    out = {}
    for name, kind, shape, fan_in in decoder_param_schema(cfg):
        if kind == "ones":
            out[name] = jnp.ones(shape, jnp.bfloat16)
            continue
        w = jax.random.normal(next(keys), shape, jnp.float32) * (
            fan_in ** -0.5
        )
        if should_quantize(name):
            use_int8 = bits == 8 or name == "lm_head"  # see above
            q, scale = (
                quantize_array(w) if use_int8 else quantize_array_int4(w)
            )
            out[name] = q
            out[name + SCALE_SUFFIX] = scale
        else:
            out[name] = w.astype(jnp.bfloat16)
        del w
    return out
