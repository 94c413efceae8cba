"""The latent-attention / routed-expert decoder block (DeepSeek-V2,
arXiv:2405.04434) — ``DecoderConfig.block == "mla_moe"``.

Same shape as ``models/decoder.py``: a flat parameter tree, one
pure-functional trunk, and an ``attend`` callback that owns the cache
layout.  Per layer, ``x`` the residual stream:

    y    = rmsnorm(x)
    c_q  = rmsnorm(y Wq_a);  q = c_q Wq_b      -> heads x (nope | rope)
    [c_kv | k_r] = y Wkv_a;  c_kv = rmsnorm(c_kv);  k_r = rope(k_r)
    q_r  = rope(q_r)                            YaRN tables (ops/rope.py)
    row  = [c_kv | k_r]                         WHAT THE CACHE HOLDS
    a    = attend(i, q_nope, q_r, row)          heads x v_head_dim
    x    = x + a Wo
    y    = rmsnorm(x)
    x    = x + swiglu(y)                        the first dense layers
    x    = x + routed(y) + swiglu_shared(y)     every later layer

One row of ``kv_lora_rank + qk_rope_head_dim`` values per token and layer
is all a later step needs: keys are ``[row_latent Wk_b | row_rope]`` and
values ``row_latent Wv_b`` (``Wk_b`` / ``Wv_b``: the two halves of the
published ``kv_b_proj``, stored apart so that neither path slices a
weight).  Prefill up-projects the rows in flight
(:func:`up_projected`); decode never does — it carries the query into
latent space and the output back (:func:`absorb_query`,
:func:`expand_output`), so a cached row is read once, as key AND value.

Routed layer (``models/routed.py``, which the stack of mixer kinds shares;
group-limited greedy): float32 softmax scores over ALL ``num_experts``; a
group's score is its best expert's; the best ``expert_groups_per_token``
groups are kept; the top ``experts_per_token`` of the kept scores are
taken; gate = ``routed_scale x score``, not renormalised; the process
computes the part of the sum its held experts give.  The trunk hands back
the expert ids it took (the routing record, benchmark/README.md "A block
that routes").
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from docqa_tpu.config import DecoderConfig
from docqa_tpu.models.routed import (  # noqa: F401  (read as latent.*)
    LATENT_BLOCK,
    MOE_PREFILL_SUMS,
    MOE_SUMS,
    _swiglu,
    experts_held,
    held_experts_sum,
    moe_chunk_counts,
    moe_prefill_sums,
    moe_step_sums,
    routed_fused_counts,
    routed_layers,
    routed_mlp,
    routed_param_schema,
    routing_problems,
    select_experts,
)
from docqa_tpu.models.serving import BlockServing
from docqa_tpu.ops.norms import rms_norm
from docqa_tpu.ops.rope import apply_rope, yarn_mscale, yarn_rope_angles
from docqa_tpu.ops.scopes import scope

Params = Dict[str, jax.Array]

def is_latent(cfg: DecoderConfig) -> bool:
    return cfg.block == LATENT_BLOCK


def latent_row_width(cfg: DecoderConfig) -> int:
    return cfg.kv_lora_rank + cfg.qk_rope_head_dim


def softmax_scale(cfg: DecoderConfig) -> float:
    m = yarn_mscale(cfg.rope_scaling_factor, cfg.rope_mscale_all_dim)
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


def check_latent_config(cfg: DecoderConfig) -> None:
    """Refuse, by field, a configuration this block cannot run."""
    problems = []
    if cfg.head_dim != cfg.qk_nope_head_dim + cfg.qk_rope_head_dim:
        problems.append("head_dim != qk_nope_head_dim + qk_rope_head_dim")
    if cfg.num_kv_heads != 1:
        problems.append("num_kv_heads != 1 (one latent row serves every head)")
    if min(cfg.q_lora_rank, cfg.kv_lora_rank, cfg.v_head_dim) <= 0:
        problems.append("q_lora_rank / kv_lora_rank / v_head_dim unset")
    if cfg.qk_rope_head_dim % 2:
        problems.append("qk_rope_head_dim is odd")
    if cfg.quantize_weights:
        problems.append("quantize_weights (this block serves float weights)")
    if cfg.sliding_window is not None:
        problems.append("sliding_window (this block attends globally)")
    if cfg.num_experts:
        problems += routing_problems(cfg)
    elif cfg.first_dense_layers < cfg.num_layers:
        problems.append("layers past first_dense_layers need num_experts")
    if problems:
        raise ValueError(
            f'DecoderConfig(block="{LATENT_BLOCK}"): ' + "; ".join(problems)
        )


def latent_param_schema(cfg: DecoderConfig):
    """``(name, kind, shape, fan_in)`` of the block's tree, in the order
    of ``models/decoder.decoder_param_schema`` (which yields this for the
    block).  Expert tensors are stacked along a leading axis of the
    experts HELD here."""
    check_latent_config(cfg)
    h, heads = cfg.hidden_dim, cfg.num_heads
    r, dr, dn, dv = (cfg.kv_lora_rank, cfg.qk_rope_head_dim,
                     cfg.qk_nope_head_dim, cfg.v_head_dim)
    yield ("tok_emb", "normal", (cfg.vocab_size, h), h)
    yield ("final_norm_g", "ones", (h,), None)
    yield ("lm_head", "normal", (h, cfg.vocab_size), h)
    for i in range(cfg.num_layers):
        p = f"l{i}_"
        yield (p + "attn_norm_g", "ones", (h,), None)
        yield (p + "wq_a", "normal", (h, cfg.q_lora_rank), h)
        yield (p + "q_norm_g", "ones", (cfg.q_lora_rank,), None)
        yield (p + "wq_b", "normal", (cfg.q_lora_rank, heads * (dn + dr)),
               cfg.q_lora_rank)
        yield (p + "wkv_a", "normal", (h, r + dr), h)
        yield (p + "kv_norm_g", "ones", (r,), None)
        yield (p + "wk_b", "normal", (r, heads * dn), r)
        yield (p + "wv_b", "normal", (r, heads * dv), r)
        yield (p + "wo", "normal", (heads * dv, h), heads * dv)
        yield (p + "mlp_norm_g", "ones", (h,), None)
        if i < cfg.first_dense_layers:
            yield (p + "w_gate", "normal", (h, cfg.mlp_dim), h)
            yield (p + "w_up", "normal", (h, cfg.mlp_dim), h)
            yield (p + "w_down", "normal", (cfg.mlp_dim, h), cfg.mlp_dim)
            continue
        yield from routed_param_schema(cfg, p)


# ---- attention: the two forms of one product -----------------------------

def up_projected(params: Params, cfg: DecoderConfig, i: int, rows):
    """Keys [n, heads, nope + rope] and values [n, heads, v] of the
    latent rows ``rows`` [n, r + dr] — the PREFILL form, for rows in
    flight."""
    r, heads = cfg.kv_lora_rank, cfg.num_heads
    with scope("proj"):
        c_kv, k_r = rows[:, :r], rows[:, r:]
        k_nope = (c_kv @ params[f"l{i}_wk_b"].astype(rows.dtype)).reshape(
            -1, heads, cfg.qk_nope_head_dim
        )
        v = (c_kv @ params[f"l{i}_wv_b"].astype(rows.dtype)).reshape(
            -1, heads, cfg.v_head_dim
        )
        k_r = jnp.broadcast_to(
            k_r[:, None, :], (*k_nope.shape[:2], k_r.shape[-1])
        )
        return jnp.concatenate([k_nope, k_r], axis=-1), v


def absorb_query(params: Params, cfg: DecoderConfig, i: int, q_nope):
    """``q_nope`` [..., heads, nope] carried into latent space
    [..., heads, r]: ``q_nope . (c Wk_b) == (q_nope Wk_b^T) . c`` — the
    DECODE form, so that scores are taken against cached rows as stored."""
    with scope("proj"):
        w = params[f"l{i}_wk_b"].astype(q_nope.dtype).reshape(
            cfg.kv_lora_rank, cfg.num_heads, cfg.qk_nope_head_dim
        )
        return jnp.einsum("...hd,rhd->...hr", q_nope, w)


def expand_output(params: Params, cfg: DecoderConfig, i: int, o_lat):
    """The attention-weighted latent [..., heads, r] through the value
    half of the up-projection -> [..., heads, v]."""
    with scope("proj"):
        w = params[f"l{i}_wv_b"].astype(o_lat.dtype).reshape(
            cfg.kv_lora_rank, cfg.num_heads, cfg.v_head_dim
        )
        return jnp.einsum("...hr,rhd->...hd", o_lat, w)


# ---- the MLPs --------------------------------------------------------------

# ---- the trunk -------------------------------------------------------------

def latent_layer_stack(params: Params, cfg: DecoderConfig, ids, positions,
                       rope_len: int, attend, *, use_flash: bool = False):
    """The block's trunk, as ``decoder_layer_stack`` is the GQA block's.

    ``attend(i, q_nope [b, s, heads, nope], q_rope [b, s, heads, rope],
    row [b, s, r + rope]) -> [b, s, heads, v]`` owns the cache: it writes
    ``row`` and attends in whichever form suits it.  ``use_flash``: the
    form of the routed layers' grouped product (:func:`held_experts_sum`).

    Returns (hidden states [b, s, hidden] before the final norm, routing
    record int32 [routed_layers, b, s, experts_per_token])."""
    b, s = ids.shape
    dtype = jnp.dtype(cfg.dtype)
    heads, dn, dr = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    r = cfg.kv_lora_rank
    with scope("proj"):
        cos, sin = yarn_rope_angles(
            dr, rope_len, cfg.rope_theta, factor=cfg.rope_scaling_factor,
            original_max_len=cfg.rope_original_max_len,
            beta_fast=cfg.rope_beta_fast, beta_slow=cfg.rope_beta_slow,
            mscale=cfg.rope_mscale, mscale_all_dim=cfg.rope_mscale_all_dim,
        )
    with scope("embed"):
        x = params["tok_emb"][ids].astype(dtype)
    record = []
    for i in range(cfg.num_layers):
        p = f"l{i}_"
        with scope("proj"):
            y = rms_norm(x, params[p + "attn_norm_g"], cfg.norm_eps)
            c_q = rms_norm(
                y @ params[p + "wq_a"].astype(dtype), params[p + "q_norm_g"],
                cfg.norm_eps,
            )
            q = (c_q @ params[p + "wq_b"].astype(dtype)).reshape(
                b, s, heads, dn + dr
            )
            q_nope = q[..., :dn]
            q_rope = apply_rope(q[..., dn:], cos, sin, positions)
            ckv = y @ params[p + "wkv_a"].astype(dtype)
            c_kv = rms_norm(
                ckv[..., :r], params[p + "kv_norm_g"], cfg.norm_eps
            )
            k_rope = apply_rope(
                ckv[..., None, r:], cos, sin, positions
            )[:, :, 0]
            row = jnp.concatenate([c_kv, k_rope], axis=-1)

        attn = attend(i, q_nope, q_rope, row)
        with scope("proj"):
            x = x + attn.reshape(b, s, heads * cfg.v_head_dim) @ params[
                p + "wo"
            ].astype(dtype)

        if i < cfg.first_dense_layers:
            with scope("mlp"):
                y = rms_norm(x, params[p + "mlp_norm_g"], cfg.norm_eps)
                x = x + _swiglu(
                    y, params, p + "w_gate", p + "w_up", p + "w_down"
                )
            continue
        with scope("mlp"):
            y = rms_norm(x, params[p + "mlp_norm_g"], cfg.norm_eps)
            add, taken = routed_mlp(
                y.reshape(b * s, -1), params, cfg, i, use_flash=use_flash
            )
            x = x + add.reshape(b, s, -1)
        record.append(taken.reshape(b, s, -1))
    return x, (jnp.stack(record) if record else None)


# ---- what the block's surroundings ask of it (models/serving.py) ----------

def latent_param_pspecs(cfg: DecoderConfig, m: str) -> Dict[str, P]:
    """Attention: the low-rank down-projections and their norms replicated
    (every device forms the same latent row, and the row pool is
    replicated); the per-head up-projections column-parallel over heads,
    ``wo`` row-parallel — one psum, as for the GQA block.  Dense and
    shared MLPs: Megatron.  Routed experts: the EXPERT axis over ``model``
    — expert parallelism; the range a process holds (``experts_held``) is
    one device's shard of a layer's experts, and the router is
    replicated."""
    specs: Dict[str, P] = {}
    for i in range(cfg.num_layers):
        p = f"l{i}_"
        specs.update({
            p + "attn_norm_g": P(None), p + "mlp_norm_g": P(None),
            p + "wq_a": P(None, None), p + "q_norm_g": P(None),
            p + "wq_b": P(None, m),
            p + "wkv_a": P(None, None), p + "kv_norm_g": P(None),
            p + "wk_b": P(None, m), p + "wv_b": P(None, m),
            p + "wo": P(m, None),
        })
        if i < cfg.first_dense_layers:
            specs.update({p + "w_gate": P(None, m), p + "w_up": P(None, m),
                          p + "w_down": P(m, None)})
            continue
        specs.update({
            p + "router": P(None, None),
            p + "e_gate": P(m, None, None), p + "e_up": P(m, None, None),
            p + "e_down": P(m, None, None),
            p + "s_gate": P(None, m), p + "s_up": P(None, m),
            p + "s_down": P(m, None),
        })
    return specs


def latent_chunk_counts(cfg: DecoderConfig, *, row, kernels, n_lanes, **_):
    """One fetched chunk's counters and samples: its expert-choice sums
    where the block routes (``models/routed.moe_chunk_counts``: with
    whether its routed layers stepped in the kernel), and how its latent
    layers read the cache."""
    counts, samples = ({}, {})
    if row is not None:
        counts, samples = moe_chunk_counts(row=row)
        counts.update(
            routed_fused_counts(cfg, kernels=kernels, n_lanes=n_lanes))
    if kernels.paged:
        # over ``serve_decode_chunks``: 1.0 where every chunk's latent
        # layers read the lanes' live pages in place, absent elsewhere
        # (the gather of every slot's whole table is running)
        counts["serve_latent_paged_chunks"] = 1
    return counts, samples


def latent_serving(cfg: DecoderConfig) -> BlockServing:
    """The block's record (``cfg`` checked: :func:`check_latent_config`)."""
    # served cold and unspeculated: a warm prefill would up-project cached
    # rows, which no path does, and a speculative chunk would drop the
    # routing record.  The engine's ``use_flash`` reaches no kernel of the
    # block (no dense cache serves it: the forms its paged forwards run
    # are ``kernel_forms``'s alone); a block that does not route carries
    # no sums.  One row a token, no head axis: its pools are replicated
    routes = bool(routed_layers(cfg))
    sums = dict(
        step_sum_names=MOE_SUMS,
        step_sums=functools.partial(moe_step_sums, cfg),
        prefill_sum_names=MOE_PREFILL_SUMS,
        prefill_sums=functools.partial(moe_prefill_sums, cfg),
    ) if routes else {}
    return BlockServing(
        label=f'DecoderConfig(block="{cfg.block}")',
        unserved=("generate.prefix_cache", "generate.speculative_k"),
        advice="set prefix_cache false and speculative_k 0",
        solo=(
            f'has no "{LATENT_BLOCK}" block (model_type deepseek_v2): it '
            "serves through the batcher (engines/serve.ContinuousBatcher) "
            "over the paged latent cache (engines/paged.py) only"
        ),
        uses_flash=False,
        paged_reads=(),  # its kernel takes a page an operand: no copy by hand
        chunk_counts=functools.partial(latent_chunk_counts, cfg),
        param_pspecs=functools.partial(latent_param_pspecs, cfg),
        pool_pspecs=lambda: {f"c{i}": P() for i in range(cfg.num_layers)},
        **sums,
    )
