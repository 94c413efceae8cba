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

Routed layer (group-limited greedy): float32 softmax scores over ALL
``num_experts``; a group's score is its best expert's; the best
``expert_groups_per_token`` groups are kept; the top ``experts_per_token``
of the kept scores are taken; gate = ``routed_scale x score``, not
renormalised.  The process holds the experts ``[experts_held_start,
+ experts_held)`` and computes their part of the sum (plus the shared
experts, which every holder computes alike); the rest is left out —
expert parallelism's local half, with no stand-in for the exchange.  That
part is ONE grouped product over the dispatch's picks sorted by expert
(:func:`held_experts_sum`, ``ops/grouped.py``): a row passes through the
experts it picked, an expert no row picked is not read — the same form
for a prefill's hundreds of rows and a decode step's few lanes.  The
trunk hands back the expert ids it took (the routing record,
benchmark/README.md "A block that routes").
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from docqa_tpu.config import DecoderConfig
from docqa_tpu.models.serving import BlockServing
from docqa_tpu.ops.grouped import grouped_matmul, row_tile
from docqa_tpu.ops.norms import rms_norm
from docqa_tpu.ops.rope import apply_rope, yarn_mscale, yarn_rope_angles
from docqa_tpu.ops.scopes import scope
from docqa_tpu.utils import round_up

Params = Dict[str, jax.Array]

LATENT_BLOCK = "mla_moe"


def is_latent(cfg: DecoderConfig) -> bool:
    return cfg.block == LATENT_BLOCK


def routed_layers(cfg: DecoderConfig) -> int:
    """Layers that route (0 for a block that does not)."""
    if not is_latent(cfg) or not cfg.num_experts:
        return 0
    return cfg.num_layers - cfg.first_dense_layers


def experts_held(cfg: DecoderConfig) -> Tuple[int, int]:
    """(first expert id held here, how many)."""
    n = cfg.experts_held or cfg.num_experts
    return cfg.experts_held_start, n


def latent_row_width(cfg: DecoderConfig) -> int:
    return cfg.kv_lora_rank + cfg.qk_rope_head_dim


def softmax_scale(cfg: DecoderConfig) -> float:
    m = yarn_mscale(cfg.rope_scaling_factor, cfg.rope_mscale_all_dim)
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


def check_latent_config(cfg: DecoderConfig) -> None:
    """Refuse, by field, a configuration this block cannot run."""
    lo, n = experts_held(cfg)
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
        if cfg.num_experts % cfg.expert_groups:
            problems.append("num_experts % expert_groups")
        if not 0 <= lo <= lo + n <= cfg.num_experts:
            problems.append("experts held outside 0..num_experts")
        if not 0 < cfg.experts_per_token <= (
            cfg.expert_groups_per_token * cfg.num_experts // cfg.expert_groups
        ):
            problems.append("experts_per_token exceeds the kept groups")
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
    _lo, held = experts_held(cfg)
    f, fs = cfg.expert_dim, cfg.expert_dim * cfg.num_shared_experts
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
        yield (p + "router", "normal", (h, cfg.num_experts), h)
        yield (p + "e_gate", "normal", (held, h, f), h)
        yield (p + "e_up", "normal", (held, h, f), h)
        yield (p + "e_down", "normal", (held, f, h), f)
        if fs:
            yield (p + "s_gate", "normal", (h, fs), h)
            yield (p + "s_up", "normal", (h, fs), h)
            yield (p + "s_down", "normal", (fs, h), fs)


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

def _swiglu(y, params: Params, gate: str, up: str, down: str):
    dtype = y.dtype
    g = y @ params[gate].astype(dtype)
    u = y @ params[up].astype(dtype)
    act = jax.nn.silu(g.astype(jnp.float32)).astype(dtype) * u
    return act @ params[down].astype(dtype)


def select_experts(scores, cfg: DecoderConfig):
    """Group-limited greedy selection.  ``scores`` [n, num_experts]
    float32 -> (expert ids [n, k] int32, their scores [n, k]): only the
    experts of the ``expert_groups_per_token`` groups whose best expert
    scores highest may be taken."""
    n, e = scores.shape
    groups = cfg.expert_groups
    best = scores.reshape(n, groups, e // groups).max(-1)
    _, kept = jax.lax.top_k(best, cfg.expert_groups_per_token)
    keep = jnp.zeros((n, groups), bool).at[
        jnp.arange(n)[:, None], kept
    ].set(True)
    allowed = jnp.repeat(keep, e // groups, axis=1)
    taken_scores, taken = jax.lax.top_k(
        jnp.where(allowed, scores, 0.0), cfg.experts_per_token
    )
    return taken.astype(jnp.int32), taken_scores


def held_experts_sum(y, taken, gates, params: Params, cfg: DecoderConfig,
                     i: int, *, use_flash: bool = False):
    """``sum_e gate_e . swiglu_e(y)`` over the experts HELD here, float32
    [n, hidden].  ``taken`` [n, k] expert ids as the router numbers them,
    ``gates`` [n, k] float32.

    A GROUPED product (``ops/grouped.py``): the ``n . k`` picks are
    sorted by the held expert they fell on, ``y``'s rows gathered in that
    order, and each run of rows multiplied by its own expert's slice of
    the stacked weights.  A pick on an expert held elsewhere (or ``-1``)
    sorts behind every group and is never computed; an expert no row took
    is never read.  So a prefill of hundreds of rows streams each held
    expert once under the few rows that took it, and a decode step of a
    few lanes reads only the experts its tokens touched: its time follows
    the routing.  ``use_flash``: the product's form
    (``models/decoder.kernel_forms``'s ``grouped``), nothing else."""
    lo, held = experts_held(cfg)
    n, k = taken.shape
    dtype = y.dtype
    local = (taken - lo).reshape(-1)  # [n . k]
    local = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(local, stable=True)  # sorted pick -> flat pick
    sizes = jnp.sum(
        local[:, None] == jnp.arange(held), axis=0, dtype=jnp.int32
    )  # [held]: the picks on absent experts lie past their sum
    # whole row tiles; the rows that fill the last one lie there too
    m = round_up(n * k, row_tile(n * k))
    pick = jnp.pad(order, (0, m - n * k))
    rows = y[pick // k]
    product = functools.partial(
        grouped_matmul, group_sizes=sizes, use_flash=use_flash)
    g = product(rows, params[f"l{i}_e_gate"].astype(dtype), out_dtype=dtype)
    u = product(rows, params[f"l{i}_e_up"].astype(dtype), out_dtype=dtype)
    act = jax.nn.silu(g.astype(jnp.float32)).astype(dtype) * u
    out = product(
        act, params[f"l{i}_e_down"].astype(dtype), out_dtype=jnp.float32
    )
    # un-sort and sum a row's k picks in one pass over the product: a
    # pick held elsewhere points at a row past the groups, which holds
    # whatever was there — never a product
    back = jnp.argsort(order).reshape(n, k)  # pick -> its sorted row
    here = (local < held).reshape(n, k)
    acc = jnp.zeros((n, cfg.hidden_dim), jnp.float32)
    for j in range(k):
        acc = acc + jnp.where(
            here[:, j, None], out[back[:, j]] * gates[:, j, None], 0.0
        )
    return acc


def routed_mlp(y, params: Params, cfg: DecoderConfig, i: int, *,
               use_flash: bool = False):
    """(what the routed layer adds [n, hidden], expert ids taken [n, k])."""
    with scope("route"):
        logits = jnp.dot(
            y.astype(jnp.float32),
            params[f"l{i}_router"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        scores = jax.nn.softmax(logits, axis=-1)
        taken, taken_scores = select_experts(scores, cfg)
    with scope("experts"):
        out = held_experts_sum(
            y, taken, cfg.routed_scale * taken_scores, params, cfg, i,
            use_flash=use_flash,
        )
    with scope("mlp"):
        if cfg.num_shared_experts:
            out = out + _swiglu(
                y, params, f"l{i}_s_gate", f"l{i}_s_up", f"l{i}_s_down"
            ).astype(jnp.float32)
        return out.astype(y.dtype), taken


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

# counters of the block's decode chunks, in the order the decode program
# sums them on the device (:func:`moe_step_sums`) and the worker adds them
# (:func:`moe_chunk_counts`): expert picks of the live lanes; those that
# fell on an expert held here; distinct held experts touched, summed over
# (routed layer, step); and the (routed layer, step)s with a live lane
MOE_SUMS = (
    "serve_moe_picks", "serve_moe_picks_local", "serve_moe_experts_touched",
    "serve_moe_layer_steps",
)
# the same block's prefill dispatches (:func:`moe_prefill_sums`, behind the
# first tokens in the fetch the batcher's ``_finalize_admissions`` makes
# anyway): expert picks of the packed prompt rows, and those that fell on
# an expert held here — the row-expert products the grouped form runs
# (:func:`held_experts_sum`), of rows x held had every held expert run
# over every row
MOE_PREFILL_SUMS = (
    "serve_moe_prefill_picks", "serve_moe_prefill_picks_local",
)


def moe_step_sums(cfg: DecoderConfig, record, lengths, active):
    """``MOE_SUMS`` of one decode step, int32, from its routing record
    [routed_layers, S, 1, k] and the lanes live in it — summed on the
    device, so that the host reads a handful of numbers a chunk and
    nothing waits on them."""
    lo, held = experts_held(cfg)
    taken = record[:, :, 0, :]  # [layers, S, k]
    live = active[None, :, None]
    per_expert = jnp.sum(
        live[..., None] & (taken[..., None] - lo == jnp.arange(held)),
        axis=(1, 2),
    )  # [layers, held] live picks of each held expert
    return jnp.stack([
        jnp.sum(live & (taken >= 0)),
        jnp.sum(per_expert),
        jnp.sum(per_expert > 0),
        jnp.any(active) * record.shape[0],
    ]).astype(jnp.int32)


def moe_prefill_sums(cfg: DecoderConfig, record, seg):
    """``MOE_PREFILL_SUMS`` of one prefill dispatch, int32, from its
    routing record [routed_layers, T, k] and the packed rows' lanes
    (``seg`` < 0: padding, which routes too and is not counted) —
    summed on the device, as :func:`moe_step_sums` is."""
    lo, held = experts_held(cfg)
    live = (seg >= 0)[None, :, None]
    local = record - lo
    return jnp.stack([
        jnp.sum(live & (record >= 0)),
        jnp.sum(live & (local >= 0) & (local < held)),
    ]).astype(jnp.int32)


def moe_chunk_counts(*, row, **_):
    """One fetched chunk's expert-choice sums (``MOE_SUMS``, summed on
    the device over its steps and live lanes) as the counters the routed
    layer's metrics read: picks made, picks that fell on an expert held
    here, distinct held experts a (layer, step) touched — the weights a
    step had to read — and the (layer, step)s counted; and one sample of
    ``serve_moe_tokens_per_expert`` where an expert was touched."""
    sums = dict(zip(MOE_SUMS, (int(v) for v in row[: len(MOE_SUMS)])))
    samples = {}
    if sums["serve_moe_experts_touched"]:
        samples["serve_moe_tokens_per_expert"] = (
            sums["serve_moe_picks_local"] / sums["serve_moe_experts_touched"]
        )
    return sums, samples


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


def latent_serving(cfg: DecoderConfig) -> BlockServing:
    """The block's record (``cfg`` checked: :func:`check_latent_config`)."""
    # served cold and unspeculated: a warm prefill would up-project cached
    # rows, which no path does, and a speculative chunk would drop the
    # routing record.  No Pallas kernel reads a latent row; a block that
    # does not route carries no sums.  One row a token, no head axis: its
    # pools are replicated
    routes = bool(routed_layers(cfg))
    sums = dict(
        step_sum_names=MOE_SUMS,
        step_sums=functools.partial(moe_step_sums, cfg),
        prefill_sum_names=MOE_PREFILL_SUMS,
        prefill_sums=functools.partial(moe_prefill_sums, cfg),
        chunk_counts=moe_chunk_counts,
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
        param_pspecs=functools.partial(latent_param_pspecs, cfg),
        pool_pspecs=lambda: {f"c{i}": P() for i in range(cfg.num_layers)},
        **sums,
    )
