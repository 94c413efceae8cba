"""The routed feed-forward, the one module both trunks that route import
(``models/latent.py``: DeepSeek-V2's block; ``models/hybrid.py``: a stack
of mixer kinds whose layers past ``first_dense_layers`` route).

The router scores ALL ``num_experts`` in float32 — ``router_score``
``softmax`` over the experts, or ``sigmoid`` of each — and takes, per
row, the top ``experts_per_token`` of its SELECTION scores: the scores,
plus ``l{i}_router_bias`` under ``router_bias`` (a float32 bias that
decides WHO is taken and never what a taken expert weighs), limited to the
experts of the ``expert_groups_per_token`` groups whose best expert
selects highest (``expert_groups`` 1: no limit, the same code).  A gate is
the taken expert's own score — over the sum of the k taken (+ 1e-20)
under ``router_norm`` — times ``routed_scale``.

The process holds the experts ``[experts_held_start, + experts_held)`` and
computes their part of the sum (plus the shared experts, which every
holder computes alike); the rest is left out — expert parallelism's local
half, with no stand-in for the exchange.  That part is a GROUPED sum
(:func:`held_experts_sum`, ``ops/grouped.py``): a row passes through the
experts it picked, an expert no row picked is not read.  A prefill's many
rows are sorted by the expert they picked and run as three grouped
products; a decode step's few lanes (their picks fit one row tile) run
as ONE kernel a layer that reads the touched experts straight from HBM —
the same arithmetic at the same rounding points.  A trunk hands back the
expert ids it took (the routing record, benchmark/README.md "A block that
routes").
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from docqa_tpu.config import DecoderConfig
from docqa_tpu.ops.grouped import (
    grouped_matmul,
    grouped_swiglu_step,
    row_tile,
    step_form,
)
from docqa_tpu.ops.scopes import scope
from docqa_tpu.utils import round_up

Params = Dict[str, jax.Array]

# the blocks whose trunks route (their modules import this one)
LATENT_BLOCK, HYBRID_BLOCK = "mla_moe", "sparse_linear"
ROUTER_SCORES = ("softmax", "sigmoid")


def routed_layers(cfg: DecoderConfig) -> int:
    """Layers that route (0 for a block that does not).  The stack of
    mixer kinds reads ``num_experts`` 1 as one dense MLP a layer."""
    least = {LATENT_BLOCK: 1, HYBRID_BLOCK: 2}.get(cfg.block)
    if least is None or cfg.num_experts < least:
        return 0
    return cfg.num_layers - cfg.first_dense_layers


def experts_held(cfg: DecoderConfig) -> Tuple[int, int]:
    """(first expert id held here, how many)."""
    n = cfg.experts_held or cfg.num_experts
    return cfg.experts_held_start, n


def routing_problems(cfg: DecoderConfig) -> list:
    """What of the routing fields no router here can run, by field."""
    lo, n = experts_held(cfg)
    problems = []
    if cfg.router_score not in ROUTER_SCORES:
        problems.append(f"router_score (one of {ROUTER_SCORES})")
    if cfg.num_experts % cfg.expert_groups:
        problems.append("num_experts % expert_groups")
    if not 0 <= lo <= lo + n <= cfg.num_experts:
        problems.append("experts held outside 0..num_experts")
    if not 0 < cfg.experts_per_token <= (
        cfg.expert_groups_per_token * cfg.num_experts // cfg.expert_groups
    ):
        problems.append(
            "experts_per_token (not 1.. the experts of the kept groups of "
            "num_experts)")
    return problems


def routed_param_schema(cfg: DecoderConfig, p: str):
    """``(name, kind, shape, fan_in)`` of one routed layer's feed-forward
    (``p``: the layer's prefix).  Expert tensors are stacked along a
    leading axis of the experts HELD here; the bias is float32 whatever
    the tree's type (``models/decoder.FLOAT32_PARAMS``)."""
    h = cfg.hidden_dim
    _lo, held = experts_held(cfg)
    f, fs = cfg.expert_dim, cfg.expert_dim * cfg.num_shared_experts
    yield (p + "router", "normal", (h, cfg.num_experts), h)
    if cfg.router_bias:
        yield (p + "router_bias", "zeros_f32", (cfg.num_experts,), None)
    yield (p + "e_gate", "normal", (held, h, f), h)
    yield (p + "e_up", "normal", (held, h, f), h)
    yield (p + "e_down", "normal", (held, f, h), f)
    if fs:
        yield (p + "s_gate", "normal", (h, fs), h)
        yield (p + "s_up", "normal", (h, fs), h)
        yield (p + "s_down", "normal", (fs, h), fs)


def _swiglu(y, params: Params, gate: str, up: str, down: str):
    dtype = y.dtype
    g = y @ params[gate].astype(dtype)
    u = y @ params[up].astype(dtype)
    act = jax.nn.silu(g.astype(jnp.float32)).astype(dtype) * u
    return act @ params[down].astype(dtype)


def select_experts(scores, cfg: DecoderConfig, bias=None):
    """Group-limited greedy selection.  ``scores`` [n, num_experts]
    float32 -> (expert ids [n, k] int32, their scores [n, k]): only the
    experts of the ``expert_groups_per_token`` groups whose best expert
    selects highest may be taken.  ``bias`` [num_experts] float32: added
    to the scores for the CHOICE (of groups and of experts) alone; the
    scores handed back are the taken experts' own."""
    selection = scores if bias is None else scores + bias
    n, e = scores.shape
    groups = cfg.expert_groups
    best = selection.reshape(n, groups, e // groups).max(-1)
    _, kept = jax.lax.top_k(best, cfg.expert_groups_per_token)
    keep = jnp.zeros((n, groups), bool).at[
        jnp.arange(n)[:, None], kept
    ].set(True)
    allowed = jnp.repeat(keep, e // groups, axis=1)
    taken_scores, taken = jax.lax.top_k(
        jnp.where(allowed, selection, 0.0), cfg.experts_per_token
    )
    if bias is not None:
        taken_scores = jnp.take_along_axis(scores, taken, axis=-1)
    return taken.astype(jnp.int32), taken_scores


def held_experts_sum(y, taken, gates, params: Params, cfg: DecoderConfig,
                     i: int, *, use_flash: bool = False):
    """``sum_e gate_e . swiglu_e(y)`` over the experts HELD here, float32
    [n, hidden].  ``taken`` [n, k] expert ids as the router numbers them,
    ``gates`` [n, k] float32.

    A dispatch of MANY rows (a prefill's): a GROUPED product
    (``ops/grouped.grouped_matmul``) — the ``n . k`` picks are sorted by
    the held expert they fell on, ``y``'s rows gathered in that order,
    and each run of rows multiplied by its own expert's slice of the
    stacked weights.  A pick on an expert held elsewhere (or ``-1``)
    sorts behind every group and is never computed; an expert no row took
    is never read: each held expert streams once under the few rows that
    took it.

    A decode STEP (``ops/grouped.step_form``: the picks fit one row tile)
    under ``use_flash``: ONE kernel (``ops/grouped.grouped_swiglu_step``)
    whose stacked operands stay in HBM — every row through each TOUCHED
    expert, weighed by the gate it gave it; no sort, gather or un-sort,
    and no expert the step's tokens did not touch crosses the memory's
    wires (the three ``gmm`` calls' operands were copied whole into fast
    memory by the compiler, PERF.md section 5, PR 53).  The arithmetic of
    a pick is the same at the same rounding points; a row's picks are
    summed in the order the experts are held.  Its time follows the
    routing.

    ``use_flash``: the product's form (``models/decoder.kernel_forms``'s
    ``grouped``), nothing else."""
    lo, held = experts_held(cfg)
    n, k = taken.shape
    dtype = y.dtype
    weights = [
        params[f"l{i}_e_{name}"].astype(dtype)
        for name in ("gate", "up", "down")]
    if use_flash and step_form(n, k):
        return grouped_swiglu_step(y, taken - lo, gates, *weights)
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
    g = product(rows, weights[0], out_dtype=dtype)
    u = product(rows, weights[1], out_dtype=dtype)
    act = jax.nn.silu(g.astype(jnp.float32)).astype(dtype) * u
    out = product(act, weights[2], out_dtype=jnp.float32)
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
        if cfg.router_score == "sigmoid":
            scores = jax.nn.sigmoid(logits)
        else:
            scores = jax.nn.softmax(logits, axis=-1)
        taken, taken_scores = select_experts(
            scores, cfg,
            params[f"l{i}_router_bias"].astype(jnp.float32)
            if cfg.router_bias else None,
        )
        if cfg.router_norm:
            taken_scores = taken_scores / (
                jnp.sum(taken_scores, axis=-1, keepdims=True) + 1e-20)
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


def routed_fused_counts(cfg: DecoderConfig, *, kernels, n_lanes) -> dict:
    """``serve_routed_fused_chunks`` of one fetched chunk, by the rule
    :func:`held_experts_sum` goes by (``kernels``: the forms the decode
    program was built with; ``n_lanes``: the rows of its step): over
    ``serve_decode_chunks`` it reads 1.0 where every chunk's routed
    layers stepped in the one kernel that reads the touched experts from
    HBM, and is absent elsewhere."""
    if kernels.grouped and step_form(n_lanes, cfg.experts_per_token):
        return {"serve_routed_fused_chunks": 1}
    return {}
