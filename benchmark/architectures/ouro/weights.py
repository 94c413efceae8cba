"""The block's seeded tree, made on the device in the served type — the
benchmark's own draw, one jitted program per layer (the same program 48
times) — and the controls of this block.  Names and shapes are those of
``docqa_tpu/models/decoder.decoder_param_schema`` under ``loop_steps`` and
``sandwich_norm`` (tested against it); with a mesh every tensor is born
under its serving sharding.  The tree goes to the program through
``GenerateEngine(params=...)`` and to the plain reference as it is.

Matrices are seeded normal draws at ``fan_in ** -0.5``; every norm gain —
the four of a layer and the final one — is 1; the exit gate's weight and
bias are drawn (and read by nobody at ``early_exit_threshold`` 1)."""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from harness.weights import Control, act_int8, to_int8

LAYER_MATS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
LAYER_GAINS = (
    "attn_norm_g", "attn_post_norm_g", "mlp_norm_g", "mlp_post_norm_g")


def layer_shapes(cfg) -> Dict[str, tuple]:
    h, qd = cfg.hidden_dim, cfg.num_heads * cfg.head_dim
    kvd = cfg.num_kv_heads * cfg.head_dim
    return {
        "wq": (h, qd), "wk": (h, kvd), "wv": (h, kvd), "wo": (qd, h),
        "w_gate": (h, cfg.mlp_dim), "w_up": (h, cfg.mlp_dim),
        "w_down": (cfg.mlp_dim, h),
    }


def _draw(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, jnp.float32)
            * fan_in ** -0.5).astype(dtype)


def _layer_fn(cfg, dtype):
    shapes = layer_shapes(cfg)

    def make(key):
        keys = jax.random.split(key, len(LAYER_MATS))
        return {name: _draw(k, shapes[name], shapes[name][0], dtype)
                for name, k in zip(LAYER_MATS, keys)}

    return make


def _ends_fn(cfg, dtype):
    h, v = cfg.hidden_dim, cfg.vocab_size

    def make(key):
        k_emb, k_head, k_w, k_b = jax.random.split(key, 4)
        return {
            "tok_emb": _draw(k_emb, (v, h), h, dtype),
            "lm_head": _draw(k_head, (h, v), h, dtype),
            "exit_gate_w": _draw(k_w, (h, 1), h, dtype),
            "exit_gate_b": _draw(k_b, (1,), h, dtype),
        }

    return make


def make_decoder_params(cfg, seed: int, mesh=None) -> Dict[str, jax.Array]:
    """The served parameter tree of ``cfg`` from ``seed``."""
    dtype = jnp.dtype(cfg.dtype)
    if cfg.quantize_weights:
        raise ValueError("the looped block is served with float weights only")

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
    # threefry for 2.7e9 draws, deterministic for a seed on one device kind
    root = jax.random.key(seed % (2**31), impl="rbg")
    keys = jax.random.split(root, cfg.num_layers + 1)
    params: Dict[str, jax.Array] = dict(
        jit_with_shardings(_ends_fn(cfg, dtype), "")(keys[0]))
    if cfg.loop_steps == 1:  # the plain trunk's tree holds no gate
        del params["exit_gate_w"], params["exit_gate_b"]
    layer = jit_with_shardings(_layer_fn(cfg, dtype), "l0_")
    ones = jnp.ones((cfg.hidden_dim,), dtype)
    if mesh is not None:
        ones = jax.device_put(ones, sharding_of("final_norm_g", ones.shape))
    params["final_norm_g"] = ones
    gains = LAYER_GAINS if cfg.sandwich_norm else LAYER_GAINS[::2]
    for i in range(cfg.num_layers):
        for name, value in layer(keys[i + 1]).items():
            params[f"l{i}_{name}"] = value
        for name in gains:
            params[f"l{i}_{name}"] = ones
    return params


# ---- the controls of this block --------------------------------------------

def _e4m3(x):
    """float32 -> float8 (4 exponent bits, 3 of mantissa; largest finite
    value 240) -> float32, by ``lax.reduce_precision``: the one rounding
    the chip's compiler may not skip (a pair of ``astype``s it may: PR 42,
    PERF.md section 2)."""
    return jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)


def to_fp8(w32):
    """A weight matrix [in, out] through float8, per-output-channel scaled
    to its range."""
    scale = jnp.maximum(jnp.max(jnp.abs(w32), axis=0) / 240.0, 1e-12)
    return _e4m3(w32 / scale[None, :]) * scale[None, :]


def act_fp8(x):
    """Activations through float8, absmax over the last axis scaled to its
    range: a row per token, or a key / value per token and head."""
    scale = jnp.maximum(
        jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 240.0, 1e-12)
    return _e4m3(x / scale) * scale


def controls_for(cfg) -> Dict[str, Control]:
    """Each of which ``correct`` has to fail, one step below what the
    configuration states.  Weights: float8 and int8 below bfloat16 (every
    matrix a matmul streams, the head among them).  Activations: every
    matmul input and the cached keys and values of every (step, layer)
    entry in int8, and in float8."""
    return {
        "w_fp8": Control(weights=to_fp8),
        "w_int8": Control(weights=to_int8),
        "a_int8": Control(act=act_int8, kv=act_int8),
        "a_fp8": Control(act=act_fp8, kv=act_fp8),
    }


def kv_only_controls() -> Dict[str, Control]:
    """The cached keys and values alone in int8 (a scale per token and
    head).  Read by calibrate.py and NOT among the controls: what holds
    the cache to its stated type is the exact ``kv_cache_bits_missing``."""
    return {"kv_int8": Control(kv=act_int8)}
