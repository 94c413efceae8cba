"""The block's seeded tree, made on the device in the served type — the
benchmark's own draw and quantization, one jitted program per KIND of layer
(the same program for every layer of a kind) — and the controls of this
block.  Names and shapes are those of ``docqa_tpu/models/hybrid.py``
(tested against its schema); with a mesh every tensor is born under its
serving sharding.  The tree goes to the program through
``GenerateEngine(params=...)`` and to the plain reference through
:func:`dequantized`."""

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

SPARSE, LINEAR = "sparse", "linear"


def geometry(cfg, kind):
    """(query heads, kv heads, head width) of a mixer kind."""
    if kind == LINEAR:
        return cfg.linear_heads, cfg.linear_heads, cfg.linear_head_dim
    return cfg.num_heads, cfg.num_kv_heads, cfg.head_dim


def layer_shapes(cfg, kind) -> Dict[str, tuple]:
    """Every matrix of one layer of a kind, by its short name."""
    h = cfg.hidden_dim
    heads, kv_heads, d = geometry(cfg, kind)
    return {
        "wq": (h, heads * d), "wk": (h, kv_heads * d),
        "wv": (h, kv_heads * d), "w_ogate": (h, heads * d),
        "wo": (heads * d, h), "w_gate": (h, cfg.mlp_dim),
        "w_up": (h, cfg.mlp_dim), "w_down": (cfg.mlp_dim, h),
    }


def layer_gains(cfg, kind) -> Dict[str, tuple]:
    heads, _kv, d = geometry(cfg, kind)
    out = {"attn_norm_g": (cfg.hidden_dim,), "mlp_norm_g": (cfg.hidden_dim,),
           "q_norm_g": (d,), "k_norm_g": (d,)}
    if kind == LINEAR:
        out["o_norm_g"] = (heads * d,)
    return out


def _draw(key, shape, quantize: bool, dtype):
    w = jax.random.normal(key, shape, jnp.float32) * (shape[0] ** -0.5)
    if quantize:
        return quantize_int8(w)
    return (w.astype(dtype),)


def _layer_fn(cfg, kind, quantize: bool, dtype):
    shapes = layer_shapes(cfg, kind)

    def make(key):
        out = {}
        for name, k in zip(shapes, jax.random.split(key, len(shapes))):
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
        out = {"tok_emb": (
            jax.random.normal(k_emb, (v, h), jnp.float32) * h ** -0.5
        ).astype(dtype)}
        drawn = _draw(k_head, (h, v), quantize, dtype)
        out["lm_head"] = drawn[0]
        if quantize:
            out["lm_head" + SCALE] = drawn[1]
        return out

    return make


def make_decoder_params(cfg, seed: int, mesh=None) -> Dict[str, jax.Array]:
    """The served parameter tree of ``cfg`` from ``seed``."""
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

    def ones(name, shape):
        return jnp.ones(shape, dtype, device=sharding_of(name, shape))

    root = jax.random.key(seed % (2**31), impl="rbg")
    keys = jax.random.split(root, cfg.num_layers + 1)
    params: Dict[str, jax.Array] = dict(
        jit_with_shardings(_ends_fn(cfg, quantize, dtype), "")(keys[0]))
    params["final_norm_g"] = ones("final_norm_g", (cfg.hidden_dim,))
    makers = {}
    for i, kind in enumerate(cfg.mixer_types):
        if kind not in makers:
            makers[kind] = jit_with_shardings(
                _layer_fn(cfg, kind, quantize, dtype), f"l{i}_")
        for name, value in makers[kind](keys[i + 1]).items():
            params[f"l{i}_{name}"] = value
        for name, shape in layer_gains(cfg, kind).items():
            params[f"l{i}_{name}"] = ones(f"l{i}_{name}", shape)
    return params


def dequantized(params, name: str):
    """One served tensor as float32: ``q * scale`` for a quantized one."""
    w = params[name]
    scale = params.get(name + SCALE)
    if scale is None:
        return w.astype(jnp.float32)
    return w.astype(jnp.float32) * scale.astype(jnp.float32)[None, :]


# ---- the controls of this block --------------------------------------------

def _rows_only(rounding):
    """A cache rounding that leaves the lane state alone."""
    return lambda x, what: x if what == "state" else rounding(x)


rows_int8, rows_fp8 = _rows_only(act_int8), _rows_only(act_fp8)


def state_bf16(x, what):
    """A lane's state held in bfloat16: one step below the float32 the
    program keeps (rounded after every token, as a pool would hold it)."""
    if what != "state":
        return x
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def mean_over_windows(window_scores, overlaps):
    """The routing control's block score: the MEAN of the windows that
    overlap a block in place of their maximum."""
    count = jnp.maximum(overlaps.sum(axis=-2), 1)
    return jnp.where(overlaps, window_scores[..., None], 0.0).sum(-2) / count


def controls_for(cfg) -> Dict[str, Control]:
    """Each of which ``correct`` has to fail, one step below what the
    configuration states.  Weights: int4 below int8 (float8 and int8 below
    bfloat16).  Activations: every matmul input and the cached keys,
    values and compressed keys in int8, and in float8.  These fail the
    logits.  ``mean_over_windows`` is the wrong selection rule: under
    replay the logits cannot see it, and ``router_choice_gap`` has to fail.

    ``kv`` callables of this block take ``(x, what)``, ``what`` one of
    "k", "v", "ck", "state"; ``router`` ones ``(window scores [..., W],
    overlaps bool [W, blocks])`` -> block scores [..., blocks]."""
    if cfg.quantize_weights and cfg.quant_bits == 8:
        out = {"w_int4": Control(weights=to_int4)}
    else:
        out = {"w_fp8": Control(weights=to_fp8),
               "w_int8": Control(weights=to_int8)}
    out.update(
        a_int8=Control(act=act_int8, kv=rows_int8),
        a_fp8=Control(act=act_fp8, kv=rows_fp8),
        mean_over_windows=Control(router=mean_over_windows),
    )
    return out


def kv_only_controls() -> Dict[str, Control]:
    """What no logit limit can fail, read by calibrate.py and NOT among
    the controls.  The cached rows alone in int8 (a scale per token and
    head): what holds the cache to its stated type is the exact
    ``kv_cache_bits_missing``.  And the LANE STATE in bfloat16, one step
    below the float32 the program keeps: at the published sizes it reads
    0.007 where a sound bfloat16 program reads 0.070 (PERF.md section 2) —
    a decayed sum of thousands of outer products forgives its own rounding
    — so nothing in ``correct`` holds the state's type yet: an exact
    comparison of the state pool's element type needs ``harness/check.py``
    (its ``kv_bits`` takes the narrowest pool array, and the bfloat16 rows
    hide a bfloat16 state)."""
    return {"kv_int8": Control(kv=rows_int8),
            "state_bf16": Control(kv=state_bf16)}
