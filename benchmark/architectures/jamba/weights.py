"""The block's seeded tree, made on the device in the served type — the
benchmark's own draw, one jitted program per KIND of layer (the same
program for every layer of a kind) — and the controls of this block.
Names and shapes are those of ``docqa_tpu/models/hybrid.py`` (tested
against its schema); with a mesh every tensor is born under its serving
sharding.  The tree goes to the program through
``GenerateEngine(params=...)`` and to the plain reference as it is.

The Mamba initialisation is the family's (``mamba_ssm`` / the slow path of
``modeling_jamba.py``; each under the file's ``assumed``): ``A_log =
log(1..state)`` a channel, float32; ``D = 1``; ``b_dt`` the inverse
softplus of a seeded log-uniform draw in [1e-3, 1e-1]; every norm gain 1.
Matrices, conv taps and the conv bias are seeded normal draws at
``fan_in ** -0.5``.  The head is tied: the tree holds no ``lm_head``."""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from harness.weights import Control, act_int8, to_int8

ATTENTION, MAMBA = "attention", "mamba"
DT_MIN, DT_MAX = 1e-3, 1e-1


def inner(cfg) -> int:
    return cfg.ssm_expand * cfg.hidden_dim


def layer_shapes(cfg, kind) -> Dict[str, tuple]:
    """Every seeded-normal tensor of one layer of a kind, by its short
    name: ``(shape, fan_in)``."""
    h, m = cfg.hidden_dim, cfg.mlp_dim
    mlp = {"w_gate": ((h, m), h), "w_up": ((h, m), h), "w_down": ((m, h), m)}
    if kind == ATTENTION:
        q, kv = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim
        return {"wq": ((h, q), h), "wk": ((h, kv), h), "wv": ((h, kv), h),
                "wo": ((q, h), q), **mlp}
    d, n, rank, taps = (inner(cfg), cfg.ssm_state_dim, cfg.ssm_dt_rank,
                        cfg.ssm_conv_width)
    out = {"w_in": ((h, 2 * d), h), "conv_w": ((taps, d), taps),
           "w_x": ((d, rank + 2 * n), d), "w_dt": ((rank, d), rank),
           "w_out": ((d, h), d), **mlp}
    if cfg.ssm_conv_bias:
        out["conv_b"] = ((d,), taps)
    if cfg.ssm_proj_bias:
        out["b_in"] = ((2 * d,), h)
        out["b_out"] = ((h,), d)
    return out


def layer_gains(cfg, kind) -> Dict[str, tuple]:
    out = {"attn_norm_g": (cfg.hidden_dim,), "mlp_norm_g": (cfg.hidden_dim,)}
    if kind == MAMBA:
        out.update(dt_norm_g=(cfg.ssm_dt_rank,), b_norm_g=(cfg.ssm_state_dim,),
                   c_norm_g=(cfg.ssm_state_dim,), d_skip=(inner(cfg),))
    return out


def _layer_fn(cfg, kind, dtype):
    shapes = layer_shapes(cfg, kind)

    def make(key):
        keys = jax.random.split(key, len(shapes) + 1)
        out = {
            name: (jax.random.normal(k, shape, jnp.float32)
                   * fan_in ** -0.5).astype(dtype)
            for (name, (shape, fan_in)), k in zip(shapes.items(), keys)
        }
        if kind == MAMBA:
            d, n = inner(cfg), cfg.ssm_state_dim
            out["a_log"] = jnp.broadcast_to(
                jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[:, None],
                (n, d))
            dt = jnp.exp(
                jax.random.uniform(keys[-1], (d,), jnp.float32)
                * (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN))
            out["b_dt"] = (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
        return out

    return make


def make_decoder_params(cfg, seed: int, mesh=None) -> Dict[str, jax.Array]:
    """The served parameter tree of ``cfg`` from ``seed``."""
    dtype = jnp.dtype(cfg.dtype)
    if cfg.quantize_weights:
        raise ValueError("the benchmark makes this block's weights float")
    if not cfg.tie_embeddings:
        raise ValueError("the benchmark makes this block's head tied")

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

    def ones(name, shape):
        return jnp.ones(shape, dtype, device=sharding_of(name, shape))

    def embedding(key):
        h, v = cfg.hidden_dim, cfg.vocab_size
        return {"tok_emb": (
            jax.random.normal(key, (v, h), jnp.float32) * h ** -0.5
        ).astype(dtype)}

    root = jax.random.key(seed % (2**31), impl="rbg")
    keys = jax.random.split(root, cfg.num_layers + 1)
    params: Dict[str, jax.Array] = dict(
        jit_with_shardings(embedding, "")(keys[0]))
    params["final_norm_g"] = ones("final_norm_g", (cfg.hidden_dim,))
    makers = {}
    for i, kind in enumerate(cfg.mixer_types):
        if kind not in makers:
            makers[kind] = jit_with_shardings(
                _layer_fn(cfg, kind, dtype), f"l{i}_")
        for name, value in makers[kind](keys[i + 1]).items():
            params[f"l{i}_{name}"] = value
        for name, shape in layer_gains(cfg, kind).items():
            params[f"l{i}_{name}"] = ones(f"l{i}_{name}", shape)
    return params


# ---- the controls of this block --------------------------------------------

def _e4m3(x):
    """float32 -> float8 (4 exponent bits, 3 of mantissa; largest finite
    value 240) -> float32, by ``lax.reduce_precision``: the one rounding
    the compiler may not skip.  A pair of ``astype``s it may (README): on
    the chip ``x.astype(bfloat16).astype(float32)`` came back UNROUNDED
    from a jitted program, and ``harness.weights.to_fp8`` of a weight
    widened from bfloat16 was skipped in this reference's layers — that
    control read 0.027 where int8 weights read 0.16 (PR 42's first two
    calibrate calls, PERF.md section 2)."""
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


def _rows_only(rounding):
    """A cache rounding of the K and V rows that leaves the lane's state
    and window alone."""
    return lambda x, what: rounding(x) if what in ("k", "v") else x


rows_int8, rows_fp8 = _rows_only(act_int8), _rows_only(act_fp8)


def state_bf16(x, what):
    """A lane's state ``h`` held in bfloat16: one step below the float32
    the program keeps, rounded after every token as a pool would hold it
    (by ``reduce_precision``: see :func:`_e4m3`)."""
    if what != "h":
        return x
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def controls_for(cfg) -> Dict[str, Control]:
    """Each of which ``correct`` has to fail, one step below what the
    configuration states.  Weights: float8 and int8 below bfloat16 (every
    matrix a matmul streams; the conv's taps, ``A_log``, ``D`` and the
    biases stay).  Activations: every matmul input and the cached keys
    and values in int8, and in float8.  The STATE: ``h`` in bfloat16 below
    float32 — a recurrence whose decay ``exp(D_t A)`` lies near 1 does NOT
    forgive the rounding of its own state: it reads 0.115–0.488 on ten
    seeds where a sound program reads 0.052–0.067 (PERF.md section 2).
    All fail the logits.

    ``kv`` callables of this block take ``(x, what)``, ``what`` one of
    "k", "v" (rows), "h" (the state), "u" (the conv window)."""
    return {
        "w_fp8": Control(weights=to_fp8),
        "w_int8": Control(weights=to_int8),
        "a_int8": Control(act=act_int8, kv=rows_int8),
        "a_fp8": Control(act=act_fp8, kv=rows_fp8),
        "state_bf16": Control(kv=state_bf16),
    }


def kv_only_controls() -> Dict[str, Control]:
    """What no logit limit can fail, read by calibrate.py and NOT among
    the controls: the cached rows alone in int8 (a scale per token and
    head; 0.0002 on the chip).  What holds the rows — and the conv window,
    a pool array like them — to their stated type is the exact
    ``kv_cache_bits_missing``."""
    return {"kv_int8": Control(kv=rows_int8)}
