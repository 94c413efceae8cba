"""Decoder weights made on the device from the seed, in the type they are
served in — the benchmark's own draw and quantization.

The program's default (``GenerateEngine`` without ``params``) draws 7e9
normals with numpy on one host thread: 280 s of a cold boot (PERF.md,
PR 21).  Here one jitted program per layer (the same program 32 times)
draws with the device's bit generator and, for an int8 configuration,
quantizes per output channel (absmax / 127, the published w8 scheme of
``models/quant.py``) before anything leaves the device.  With a mesh every
tensor is born under its serving sharding.

The tree goes to the program through ``GenerateEngine(params=...)`` and to
the plain reference through :func:`dequantized`; the program makes no
weight, scale or table of its own.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from harness.weights import (
    SCALE,
    Control,
    act_fp8,
    act_int8,
    quantize_int8,
    to_fp8,
    to_int4,
    to_int8,
)

_LAYER_MATS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def layer_shapes(cfg) -> Dict[str, tuple]:
    h, qd = cfg.hidden_dim, cfg.num_heads * cfg.head_dim
    kvd = cfg.num_kv_heads * cfg.head_dim
    return {
        "wq": (h, qd), "wk": (h, kvd), "wv": (h, kvd), "wo": (qd, h),
        "w_gate": (h, cfg.mlp_dim), "w_up": (h, cfg.mlp_dim),
        "w_down": (cfg.mlp_dim, h),
    }


def _draw(key, shape, quantize: bool, dtype):
    w = jax.random.normal(key, shape, jnp.float32) * (shape[0] ** -0.5)
    if quantize:
        return quantize_int8(w)
    return (w.astype(dtype),)


def _layer_fn(cfg, quantize: bool, dtype):
    shapes = layer_shapes(cfg)

    def make(key):
        out = {}
        for name, k in zip(_LAYER_MATS, jax.random.split(key, len(_LAYER_MATS))):
            drawn = _draw(k, shapes[name], quantize, dtype)
            out[name] = drawn[0]
            if quantize:
                out[name + SCALE] = drawn[1]
        return out

    return make


def _ends_fn(cfg, quantize: bool, dtype):
    h, v = cfg.hidden_dim, cfg.vocab_size

    def make(key):
        k_emb, k_head = jax.random.split(key)
        out = {
            "tok_emb": (
                jax.random.normal(k_emb, (v, h), jnp.float32) * h ** -0.5
            ).astype(dtype)
        }
        drawn = _draw(k_head, (h, v), quantize, dtype)
        out["lm_head"] = drawn[0]
        if quantize:
            out["lm_head" + SCALE] = drawn[1]
        return out

    return make


def make_decoder_params(cfg, seed: int, mesh=None) -> Dict[str, jax.Array]:
    """The served parameter tree of ``cfg`` (names of
    ``models/decoder.decoder_param_schema``) from ``seed``."""
    dtype = jnp.dtype(cfg.dtype)
    quantize = bool(cfg.quantize_weights)
    if quantize and cfg.quant_bits != 8:
        raise ValueError("the benchmark makes int8 or float weights only")

    def sharding_of(name, shape):
        if mesh is None:
            return None
        from docqa_tpu.parallel.sharding import decoder_param_sharding

        return decoder_param_sharding(name, shape, cfg, mesh)

    def jit_with_shardings(fn, prefix):
        shapes = jax.eval_shape(fn, jax.random.key(0, impl="rbg"))
        out_sh = (
            None if mesh is None else
            {n: sharding_of(prefix + n, s.shape) for n, s in shapes.items()}
        )
        return jax.jit(fn, out_shardings=out_sh)

    # the device's own bit generator: an order of magnitude cheaper than
    # threefry for 7e9 draws, deterministic for a seed on one device kind
    root = jax.random.key(seed % (2**31), impl="rbg")
    keys = jax.random.split(root, cfg.num_layers + 1)
    params: Dict[str, jax.Array] = {}
    ends = jit_with_shardings(_ends_fn(cfg, quantize, dtype), "")(keys[0])
    params.update(ends)
    layer = jit_with_shardings(_layer_fn(cfg, quantize, dtype), "l0_")
    ones = jnp.ones((cfg.hidden_dim,), dtype)
    if mesh is not None:
        ones = jax.device_put(ones, sharding_of("final_norm_g", ones.shape))
    params["final_norm_g"] = ones
    for i in range(cfg.num_layers):
        for name, value in layer(keys[i + 1]).items():
            params[f"l{i}_{name}"] = value
        params[f"l{i}_attn_norm_g"] = ones
        params[f"l{i}_mlp_norm_g"] = ones
    return params


def dequantized(params, name: str):
    """One served tensor as float32: ``q * scale`` for a quantized one."""
    w = params[name]
    scale = params.get(name + SCALE)
    if scale is None:
        return w.astype(jnp.float32)
    return w.astype(jnp.float32) * scale.astype(jnp.float32)[None, :]


# ---- the controls of this block --------------------------------------------

def controls_for(cfg) -> Dict[str, Control]:
    """The controls of a configuration, each of which ``correct`` has to
    fail: the plain reference computed one step below what the
    configuration states.  Weights: int4 below int8; float8 and int8 below
    bfloat16.  Activations (bfloat16 in every configuration here): every
    matmul input and the cached keys and values in int8, and in float8."""
    if cfg.quantize_weights and cfg.quant_bits == 8:
        out = {"w_int4": Control(weights=to_int4)}
    else:
        out = {"w_fp8": Control(weights=to_fp8),
               "w_int8": Control(weights=to_int8)}
    out.update(
        a_int8=Control(act=act_int8, kv=act_int8),
        a_fp8=Control(act=act_fp8, kv=act_fp8),
    )
    return out


def kv_only_controls() -> Dict[str, Control]:
    """The cached keys and values alone in int8.  Read by calibrate.py,
    and NOT among the controls: with a scale per token and head this
    costs the logits less than the bfloat16 arithmetic of a sound run
    does (PERF.md §2), so no limit on the logit error can fail it.  What
    holds the cache to its stated type is the exact comparison
    ``check.kv_bits_missing``.  (No float8 reading here: on the chip the
    compiler may skip a float8 round trip of a float32 value, and the
    cache alone then read 1e-6, PR 24.)"""
    return {"kv_int8": Control(kv=act_int8)}
