"""The block's seeded tree, made on the device in the served type — the
benchmark's own draw, one jitted program per KIND of layer (the same
program for every layer of a kind) — and the controls of this block.
Names and shapes are those of ``docqa_tpu/models/hybrid.py`` (tested
against its schema); with a mesh every tensor is born under its serving
sharding.

Matrices are seeded normal draws at ``fan_in ** -0.5``; every norm gain
is 1.  The routers are then made level and the selection bias drawn
(:func:`level_routers`, :func:`expert_bias`; each under the file's
``assumed``)."""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from harness.weights import Control, act_int8, to_int8

WINDOW, ATTENTION = "window", "attention"
# the spread of the selection bias: large beside what bfloat16 rounding
# moves a sigmoid score by (a sound program's choice gap), so that a
# router which leaves the bias out takes visibly other experts — and as
# large as the scores' own spread (~0.2), so that WHICH experts are taken
# often is set by values every share holds alike and not by how a share's
# columns happen to lie to the stream (at 0.1 the experts a decode step
# touched followed the seed: ``tpot_p50_ms`` spread 0.94 % over six seeds)
EXPERT_BIAS_STD = 0.25


def held_range(cfg):
    """(first expert id held here, how many): the chip's share."""
    return cfg.experts_held_start, cfg.experts_held or cfg.num_experts


def routes(cfg, i: int) -> bool:
    return i >= cfg.first_dense_layers


def layer_shapes(cfg, routed: bool) -> Dict[str, tuple]:
    """Every seeded-normal tensor of one layer, by its short name:
    ``(shape, fan_in)``.  The two attention kinds hold the same tensors."""
    h, d = cfg.hidden_dim, cfg.head_dim
    q, kv = cfg.num_heads * d, cfg.num_kv_heads * d
    out = {"wq": ((h, q), h), "wk": ((h, kv), h), "wv": ((h, kv), h),
           "w_ogate": ((h, q), h), "wo": ((q, h), q)}
    if not routed:
        m = cfg.mlp_dim
        out.update(w_gate=((h, m), h), w_up=((h, m), h), w_down=((m, h), m))
        return out
    _lo, held = held_range(cfg)
    f, fs = cfg.expert_dim, cfg.expert_dim * cfg.num_shared_experts
    out.update(router=((h, cfg.num_experts), h),
               e_gate=((held, h, f), h), e_up=((held, h, f), h),
               e_down=((held, f, h), f))
    if fs:
        out.update(s_gate=((h, fs), h), s_up=((h, fs), h),
                   s_down=((fs, h), fs))
    return out


def layer_gains(cfg) -> Dict[str, tuple]:
    h, d = cfg.hidden_dim, cfg.head_dim
    return {"attn_norm_g": (h,), "attn_post_norm_g": (h,),
            "mlp_norm_g": (h,), "mlp_post_norm_g": (h,),
            "q_norm_g": (d,), "k_norm_g": (d,)}


def _layer_fn(cfg, routed: bool, dtype):
    shapes = layer_shapes(cfg, routed)

    def make(key):
        keys = jax.random.split(key, len(shapes))
        return {
            name: (jax.random.normal(k, shape, jnp.float32)
                   * fan_in ** -0.5).astype(dtype)
            for (name, (shape, fan_in)), k in zip(shapes.items(), keys)
        }

    return make


def expert_bias(cfg, key):
    """A routed layer's selection bias, float32 [router_experts], made
    level as the routers are: every chip's share of the experts (a run of
    ``experts_held``) holds THE SAME values — the ``experts_held``
    quantiles of a normal law at ``EXPERT_BIAS_STD``, which sum to zero —
    in an order the seed draws per share.  So no share is favoured by the
    seed, as a whole or through its extremes (a top k takes the experts of
    largest bias far more often than their mean says: with values drawn
    freely a share's picks followed its largest draw, 10.9-12.5 % of them
    local by the seed, and with them a prefill's grouped products and the
    experts a decode step reads; PERF.md section 6)."""
    _lo, held = held_range(cfg)
    shares = cfg.num_experts // held
    values = jax.scipy.stats.norm.ppf(
        (jnp.arange(held, dtype=jnp.float32) + 0.5) / held)
    values = values * (EXPERT_BIAS_STD / jnp.std(values))
    order = jnp.argsort(
        jax.random.uniform(key, (shares, held), jnp.float32), axis=-1)
    return values[order].reshape(-1)


def make_decoder_params(cfg, seed: int, mesh=None) -> Dict[str, jax.Array]:
    """The served parameter tree of ``cfg`` from ``seed``."""
    dtype = jnp.dtype(cfg.dtype)
    if cfg.quantize_weights:
        raise ValueError("the benchmark makes this block's weights float")

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

    def ends(key):
        h, v = cfg.hidden_dim, cfg.vocab_size
        a, b = jax.random.split(key)
        return {
            "tok_emb": (jax.random.normal(a, (v, h), jnp.float32)
                        * h ** -0.5).astype(dtype),
            "lm_head": (jax.random.normal(b, (h, v), jnp.float32)
                        * h ** -0.5).astype(dtype),
        }

    # the device's own bit generator: an order of magnitude cheaper than
    # threefry for 4e9 draws, deterministic for a seed on one device kind
    root = jax.random.key(seed % (2**31), impl="rbg")
    keys = jax.random.split(root, 2 * cfg.num_layers + 1)
    params: Dict[str, jax.Array] = dict(jit_with_shardings(ends, "")(keys[0]))
    params["final_norm_g"] = ones("final_norm_g", (cfg.hidden_dim,))
    makers = {}
    for i in range(cfg.num_layers):
        routed = routes(cfg, i)
        if routed not in makers:
            makers[routed] = jit_with_shardings(
                _layer_fn(cfg, routed, dtype), f"l{i}_")
        for name, value in makers[routed](keys[1 + i]).items():
            params[f"l{i}_{name}"] = value
        for name, shape in layer_gains(cfg).items():
            params[f"l{i}_{name}"] = ones(f"l{i}_{name}", shape)
        if routed:
            name = f"l{i}_router_bias"
            params[name] = jax.device_put(
                expert_bias(cfg, keys[1 + cfg.num_layers + i]),
                sharding_of(name, (cfg.num_experts,)))
    with jax.default_matmul_precision("highest"):
        level = level_routers(params, cfg)
    for name, router in level.items():
        params[name] = jax.device_put(
            router.astype(dtype), sharding_of(name, router.shape))
    return params


def level_routers(params, cfg) -> Dict[str, jax.Array]:
    """The routers of ``params`` made level, float32 by name, as
    ``deepseek_v2/weights.level_routers`` makes that block's: no direction
    of a router's input that every token shares may favour an expert, so
    the share of picks that lands on the experts held here — and with it
    the experts a decode step reads — does not follow the seed.

    Attention under drawn weights is soft, so part of what a layer writes
    is its CONTEXT'S MEAN value: the same vector for every token over one
    corpus, which a router drawn at random turns into a favour for some
    experts.  Two properties, exact for any input:

    * a router's columns are orthogonal to the rows of ``R Wo`` of the
      attention layers before it (nearest first, up to half the width) —
      ``R`` [kv heads x d, heads x d] hands a kv head's value to the query
      heads of its group: what a layer writes when it averages its
      context (at an even output gate);
    * its columns sum to zero (one routing group): what is left of a
      shared direction favours no expert on the whole.

    And every column has the same length (1: the draw's ``fan_in ** -0.5``
    an entry), so that a sigmoid score spreads alike for every expert and
    none is taken more often for a longer column (re-centred after the
    scaling, which moves a length by under a hundredth)."""
    h = cfg.hidden_dim
    groups = cfg.num_heads // cfg.num_kv_heads
    f32 = lambda name: params[name].astype(jnp.float32)  # noqa: E731
    out: Dict[str, jax.Array] = {}
    written = []
    for i in range(cfg.num_layers):
        wo = f32(f"l{i}_wo").reshape(
            cfg.num_kv_heads, groups, cfg.head_dim, h)
        written.append(wo.sum(1).reshape(-1, h))  # [kv heads x d, h]
        if not routes(cfg, i):
            continue
        rows = []
        for block in reversed(written):
            if sum(len(b) for b in rows) + len(block) > h // 2:
                break
            rows.append(block)
        w = f32(f"l{i}_router")
        if rows:
            q, _ = jnp.linalg.qr(jnp.concatenate(rows).T)  # [h, rows]
            w = w - q @ (q.T @ w)
        w = w - w.mean(-1, keepdims=True)
        w = w / jnp.linalg.norm(w, axis=0, keepdims=True)
        out[f"l{i}_router"] = w - w.mean(-1, keepdims=True)
    return out


# ---- the controls of this block --------------------------------------------

def _e4m3(x):
    """float32 -> float8 (e4m3) -> float32 by ``lax.reduce_precision``:
    the one rounding the compiler may not skip (``jamba/weights.py``)."""
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


def without_bias(scores, _bias):
    """The routing control's selection: the plain top k of the scores, the
    selection bias left out."""
    return scores


def controls_for(cfg) -> Dict[str, Control]:
    """Each of which ``correct`` has to fail, one step below what the
    configuration states.  Weights: float8 and int8 below bfloat16 (every
    matrix a matmul streams, a routed expert's among them; the routers and
    their biases stay float32 of what is stored).  Every matmul input with
    the cache, and the cache alone: keys and values in float8 below
    bfloat16.  These fail the logits.
    ``without_bias`` is the wrong router (top 8 of the bare scores): under
    replay the logits cannot see it, and ``router_choice_gap`` has to
    fail."""
    return {
        "w_fp8": Control(weights=to_fp8),
        "w_int8": Control(weights=to_int8),
        "a_fp8": Control(act=act_fp8, kv=act_fp8),
        "kv_fp8": Control(kv=act_fp8),
        "without_bias": Control(router=without_bias),
    }


def kv_only_controls() -> Dict[str, Control]:
    """The cached rows alone in int8 (a scale per token and head).  Read by
    calibrate.py and NOT among the controls: what holds the rows to their
    stated type is the exact ``kv_cache_bits_missing``."""
    return {"kv_int8": Control(kv=act_int8)}
