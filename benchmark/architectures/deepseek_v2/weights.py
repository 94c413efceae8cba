"""The block's seeded tree, made on the device in the served type — the
benchmark's own draw, one jitted program per tensor SHAPE (a routed
layer's three expert stacks are 0.63 GB each at the published widths, and
their float32 draw is the largest transient) — and the controls of this
block.  Names and shapes are those of ``docqa_tpu/models/latent.py``
(tested against its schema); with a mesh every tensor is born under its
serving sharding."""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from harness.weights import Control, act_fp8, act_int8, to_fp8, to_int8


def held_range(cfg):
    """(first expert id held here, how many): the chip's share."""
    return cfg.experts_held_start, cfg.experts_held or cfg.num_experts


def shapes(cfg) -> Dict[str, tuple]:
    """Every matrix of the tree (the norm gains apart) by name."""
    h, heads = cfg.hidden_dim, cfg.num_heads
    r, dr, dn, dv = (cfg.kv_lora_rank, cfg.qk_rope_head_dim,
                     cfg.qk_nope_head_dim, cfg.v_head_dim)
    _lo, held = held_range(cfg)
    f, fs = cfg.expert_dim, cfg.expert_dim * cfg.num_shared_experts
    out = {"tok_emb": (cfg.vocab_size, h), "lm_head": (h, cfg.vocab_size)}
    for i in range(cfg.num_layers):
        layer = {
            "wq_a": (h, cfg.q_lora_rank),
            "wq_b": (cfg.q_lora_rank, heads * (dn + dr)),
            "wkv_a": (h, r + dr),
            "wk_b": (r, heads * dn), "wv_b": (r, heads * dv),
            "wo": (heads * dv, h),
        }
        if i < cfg.first_dense_layers:
            layer.update(w_gate=(h, cfg.mlp_dim), w_up=(h, cfg.mlp_dim),
                         w_down=(cfg.mlp_dim, h))
        else:
            layer.update(router=(h, cfg.num_experts),
                         e_gate=(held, h, f), e_up=(held, h, f),
                         e_down=(held, f, h))
            if fs:
                layer.update(s_gate=(h, fs), s_up=(h, fs), s_down=(fs, h))
        out.update({f"l{i}_{n}": shape for n, shape in layer.items()})
    return out


def norm_gains(cfg) -> Dict[str, tuple]:
    out = {"final_norm_g": (cfg.hidden_dim,)}
    for i in range(cfg.num_layers):
        out.update({
            f"l{i}_attn_norm_g": (cfg.hidden_dim,),
            f"l{i}_mlp_norm_g": (cfg.hidden_dim,),
            f"l{i}_q_norm_g": (cfg.q_lora_rank,),
            f"l{i}_kv_norm_g": (cfg.kv_lora_rank,),
        })
    return out


def make_decoder_params(cfg, seed: int, mesh=None) -> Dict[str, jax.Array]:
    """The served parameter tree of ``cfg`` from ``seed``."""
    if cfg.quantize_weights:
        raise ValueError("this block is served in float weights only")
    dtype = jnp.dtype(cfg.dtype)

    def sharding_of(name, shape):
        if mesh is None:
            return None
        from docqa_tpu.parallel.sharding import decoder_param_sharding

        return decoder_param_sharding(name, shape, cfg, mesh)

    @functools.lru_cache(maxsize=None)
    def drawer(shape, fan_in, sharding):
        def draw(key):
            w = jax.random.normal(key, shape, jnp.float32)
            return (w * fan_in ** -0.5).astype(dtype)

        return jax.jit(draw, out_shardings=sharding)

    # the device's own bit generator: an order of magnitude cheaper than
    # threefry for 5e9 draws, deterministic for a seed on one device kind
    names = shapes(cfg)
    keys = jax.random.split(
        jax.random.key(seed % (2**31), impl="rbg"), len(names)
    )
    params: Dict[str, jax.Array] = {}
    for (name, shape), key in zip(names.items(), keys):
        # fan-in: the axis before the last (the embedding: its width)
        fan_in = shape[-1] if name == "tok_emb" else shape[-2]
        params[name] = drawer(shape, fan_in, sharding_of(name, shape))(key)
    for name, shape in norm_gains(cfg).items():
        params[name] = jnp.ones(shape, dtype, device=sharding_of(name, shape))
    with jax.default_matmul_precision("highest"):
        level = level_routers(params, cfg)
    for name, router in level.items():
        params[name] = jax.device_put(
            router.astype(dtype), sharding_of(name, router.shape)
        )
    return params


def level_routers(params, cfg) -> Dict[str, jax.Array]:
    """The routers of ``params`` made level, float32 by name: no
    direction of a router's input that every token shares may favour an
    expert, so the share of picks that lands on the experts held here
    does not follow the seed.

    Why a seeded tree needs it: attention under drawn weights is soft (a
    row attends ~40 of ~330 context rows at the published widths), so a
    quarter of what it writes is the CONTEXT'S MEAN value — one vector for
    every token of every request over one corpus.  A router drawn at
    random turns that vector into a favour for some experts, drawn with
    the seed: 15 % of the variance of its logits, a local share of 21-28 %
    by the seed, and with it the experts a decode step reads (PERF.md
    section 6).  The published router is held level by its balance
    losses; this one by two properties, exact for any input:

    * its columns are orthogonal to the rows of ``Wv_b Wo`` of the
      attention layers before it (nearest first, up to half the width):
      what a layer writes when it averages its context;
    * each routing group's columns sum to zero: what is left of a shared
      direction favours no group as a whole, only experts within one.

    The columns keep the spread of the draw (fan_in ** -0.5)."""
    h, groups = cfg.hidden_dim, cfg.expert_groups
    f32 = lambda name: params[name].astype(jnp.float32)  # noqa: E731
    out: Dict[str, jax.Array] = {}
    if not cfg.num_experts:
        return out
    written = []  # per attention layer, an orthonormal basis [h, r] of it
    for i in range(cfg.num_layers):
        written.append(f32(f"l{i}_wv_b") @ f32(f"l{i}_wo"))  # [r, h]
        if i < cfg.first_dense_layers:
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
        grouped = w.reshape(h, groups, -1)
        w = (grouped - grouped.mean(-1, keepdims=True)).reshape(h, -1)
        out[f"l{i}_router"] = w * (h ** -0.5 / jnp.std(w))
    return out


# ---- the controls of this block --------------------------------------------

def no_group_limit(scores, _cfg):
    """The routing control's selection: the plain top k of ALL the
    experts' scores, the group limit dropped."""
    return scores


def controls_for(cfg) -> Dict[str, Control]:
    """Each of which ``correct`` has to fail.  Precision, one step below
    the bfloat16 the configuration states: float8 and int8 weights; every
    matmul input and the cached latent rows in int8, and in float8 — these
    fail the logits.  ``no_group_limit`` is the wrong router (plain top 6
    of 160): under replay the logits cannot see it, and
    ``router_choice_gap`` has to fail."""
    return {
        "w_fp8": Control(weights=to_fp8),
        "w_int8": Control(weights=to_int8),
        "a_int8": Control(act=act_int8, kv=act_int8),
        "a_fp8": Control(act=act_fp8, kv=act_fp8),
        "no_group_limit": Control(router=no_group_limit),
    }


def kv_only_controls() -> Dict[str, Control]:
    """The cached latent rows alone in int8 (a scale per token and part).
    Read by calibrate.py and NOT among the controls: what holds the cache
    to its stated type is the exact ``kv_cache_bits_missing``."""
    return {"kv_int8": Control(kv=act_int8)}
