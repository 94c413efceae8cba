"""Plain float32 reference of the DeepSeek-V2 decoder (arXiv:2405.04434;
``modeling_deepseek.py`` of the published checkpoint), NON-ABSORBED.

Straightforward ``jax.numpy``: no kernel, no cache, no paging.  Every
matmul runs under ``jax.default_matmul_precision("highest")``.  The weights
are the tensors the benchmark made from the seed (``weights.py``), widened
to float32 a layer at a time, a routed expert at a time, the output head a
block of the vocabulary at a time; attention runs a lane at a time — so
that the pass fits a chip beside the served tree.

Per layer, for hidden states x [s, h] of one lane:

    y    = rmsnorm(x) g_attn
    c_q  = rmsnorm(y Wq_a) g_q;   q = c_q Wq_b      -> heads x (nope | rope)
    [c_kv | k_r] = y Wkv_a;  c_kv = rmsnorm(c_kv) g_kv;  k_r = rope(k_r)
    q_r  = rope(q_r)                                 one k_r for all heads
    [k_nope | v] = c_kv [Wk_b | Wv_b]                per head
    a    = softmax_f32((q_nope.k_nope + q_r.k_r) (nope+rope)^-0.5 m^2
                       + causal) v
    x    = x + a Wo
    y    = rmsnorm(x) g_mlp
    x    = x + swiglu(y)                             the leading dense layers
    routed layers:
      s   = softmax_f32(y Wr)                        all router_experts
      G_g = max of group g's scores;  K = the topk_group groups of largest G
      T   = the k experts of largest s within K      -- or the RECORD's (replay)
      x   = x + sum_{e in T, held here} c s_e swiglu_e(y) + swiglu_shared(y)

RoPE: YaRN's blended frequencies (factor, beta_fast, beta_slow over the
original context), cos / sin scaled by mscale / mscale_all_dim,
m = 0.1 mscale_all_dim ln(factor) + 1.

Departures from the published code, each deliberate:

* RoPE pairs the two HALVES of the rotary slice (the program's
  ``ops/rope.py``); the published code de-interleaves adjacent pairs into
  the same halves first — a fixed permutation of wq_b's / wkv_a's rotary
  columns, identical under seeded random weights.
* The process holds a contiguous RANGE of the routed experts (the chip's
  share, ``cfg.experts_held_start`` / ``cfg.experts_held``): the router
  scores all of them, the sum runs over the held ones, and what absent
  experts would add is left out — as in the program.
* Under replay (``routing``) a layer computes with the recorded set; the
  gates stay THIS pass's float32 scores of those experts, times
  ``routed_scaling_factor`` (``norm_topk_prob`` false: not renormalised).
* The choice gap follows the two stages of the selection.  Groups: the most
  by which the ``topk_group``-th largest group score exceeds the group
  score of a group the record took from.  Experts: with the record's groups
  kept (filled up by the best other groups), the most by which a score of
  the top k the record left out exceeds a score it took instead.  The gap
  is the larger of the two, 0 where the sets agree.  (Held to the masked
  scores alone, a sound program whose rounding swaps two near-equal GROUPS
  would read the whole score of an expert, as a wrong router does.)
* ``seq_aux`` / ``aux_loss_alpha`` are training-only; nothing of them here.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from harness.weights import Control

from . import weights as _weights

HEAD_BLOCKS = 4  # the output head is widened a quarter at a time


def _same(x):
    return x


def _published(scores, cfg):
    """Selection scores of ``group_limited_greedy``: an expert's score if
    its group is one of the ``topk_group`` best, else 0."""
    return jnp.where(_expand(_top_groups(scores, cfg), cfg), scores, 0.0)


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn_tables(cfg, n):
    """(cos, sin) [n, rope/2] of YaRN-scaled RoPE, positions 0..n-1."""
    dim, base, factor = (cfg.qk_rope_head_dim, cfg.rope_theta,
                         cfg.rope_scaling_factor)

    def correction_dim(rotations):
        return dim * math.log(
            cfg.rope_original_max_len / (rotations * 2 * math.pi)
        ) / (2 * math.log(base))

    low = max(math.floor(correction_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.rope_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    exponent = np.arange(0, dim, 2, dtype=np.float64) / dim
    extrapolated = 1.0 / base ** exponent
    interpolated = 1.0 / (factor * base ** exponent)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp  # 1 where the pair keeps its own frequency
    inv_freq = interpolated * (1 - mask) + extrapolated * mask
    angles = np.arange(n)[:, None] * inv_freq[None, :]
    scale = _mscale(factor, cfg.rope_mscale) / _mscale(
        factor, cfg.rope_mscale_all_dim)
    return (jnp.asarray(np.cos(angles) * scale, jnp.float32),
            jnp.asarray(np.sin(angles) * scale, jnp.float32))


def _rope(x, cos, sin):
    """x [s, heads, d]; positions 0..s-1; halves paired."""
    d = x.shape[-1]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _attention(y, w, cfg, cos, sin, act, kv):
    """One lane: y [s, h] -> what attention adds [s, h]."""
    s = y.shape[0]
    heads, dn, dr, dv = (cfg.num_heads, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim, cfg.v_head_dim)
    r = cfg.kv_lora_rank
    c_q = act(_rmsnorm(y @ w["wq_a"], w["q_norm_g"], cfg.norm_eps))
    q = (c_q @ w["wq_b"]).reshape(s, heads, dn + dr)
    q_nope, q_r = q[..., :dn], _rope(q[..., dn:], cos, sin)
    ckv = y @ w["wkv_a"]
    # the row a cache would hold: the normed latent and the rotated key
    c_kv = kv(_rmsnorm(ckv[:, :r], w["kv_norm_g"], cfg.norm_eps))
    k_r = kv(_rope(ckv[:, None, r:], cos, sin)[:, 0])
    k_nope = (act(c_kv) @ w["wk_b"]).reshape(s, heads, dn)
    v = (act(c_kv) @ w["wv_b"]).reshape(s, heads, dv)
    m = _mscale(cfg.rope_scaling_factor, cfg.rope_mscale_all_dim)
    scores = (
        jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
        + jnp.einsum("qhd,kd->hqk", q_r, k_r)
    ) * ((dn + dr) ** -0.5 * m * m)
    seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scores = jnp.where(seen[None], scores, -jnp.inf)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return act(a.reshape(s, heads * dv)) @ w["wo"]


def _swiglu(y, gate, up, down, act):
    return act(jax.nn.silu(y @ gate) * (y @ up)) @ down


def _top_groups(scores, cfg):
    """bool [..., groups]: the ``topk_group`` groups of largest score."""
    best = _group_scores(scores, cfg)
    kth = jnp.sort(best, axis=-1)[..., -cfg.expert_groups_per_token]
    return best >= kth[..., None]


def _group_scores(scores, cfg):
    g = cfg.expert_groups
    return scores.reshape(*scores.shape[:-1], g, -1).max(-1)


def _expand(groups, cfg):
    return jnp.repeat(groups, cfg.num_experts // cfg.expert_groups, axis=-1)


def _membership(ids, n):
    return (ids[..., None] == jnp.arange(n)).any(-2)


def _choice_gap(scores, in_taken, cfg):
    """The two-stage gap of one layer's decisions (module docstring)."""
    k, per_group = cfg.experts_per_token, cfg.num_experts // cfg.expert_groups
    best = _group_scores(scores, cfg)
    from_group = in_taken.reshape(
        *in_taken.shape[:-1], cfg.expert_groups, per_group).any(-1)
    kth = jnp.sort(best, axis=-1)[..., -cfg.expert_groups_per_token]
    group_gap = jnp.where(from_group, kth[..., None] - best, 0.0).max(-1)
    # the record's groups, filled up by the best of the others
    n_keep = jnp.maximum(cfg.expert_groups_per_token, from_group.sum(-1))
    priority = jnp.where(from_group, jnp.inf, best)
    rank = jnp.argsort(jnp.argsort(-priority, axis=-1), axis=-1)
    kept = rank < n_keep[..., None]
    selection = jnp.where(_expand(kept, cfg), scores, 0.0)
    in_own = _membership(jax.lax.top_k(selection, k)[1], cfg.num_experts)
    left_out = jnp.where(in_own & ~in_taken, selection, -jnp.inf).max(-1)
    instead = jnp.where(in_taken & ~in_own, selection, jnp.inf).min(-1)
    expert_gap = jnp.where(left_out > -jnp.inf, left_out - instead, 0.0)
    return jnp.maximum(jnp.maximum(group_gap, expert_gap), 0.0)


def _routed(y, w, record, cfg, act, prep, select):
    """(what the layer adds to y's stream [n, h], choice gap [n], the sets
    taken [n, k]); ``record`` int32 [n, k], -1 where it holds nothing."""
    lo, held = _weights.held_range(cfg)
    scores = jax.nn.softmax(y @ w["router"], axis=-1)
    own = jax.lax.top_k(select(scores, cfg), cfg.experts_per_token)[1]
    taken = jnp.where(record[:, :1] >= 0, record, own)
    in_taken = _membership(taken, cfg.num_experts)
    gap = _choice_gap(scores, in_taken, cfg)
    gates = jnp.where(in_taken, cfg.routed_scale * scores, 0.0)
    y_in = act(y)

    def one_expert(e, acc):
        def widened(name):
            return prep(jax.lax.dynamic_index_in_dim(
                w[name], e, keepdims=False).astype(jnp.float32))

        out = _swiglu(y_in, widened("e_gate"), widened("e_up"),
                      widened("e_down"), act)
        gate = jax.lax.dynamic_index_in_dim(
            gates, lo + e, axis=1, keepdims=False)
        return acc + out * gate[:, None]

    routed = jax.lax.fori_loop(0, held, one_expert, jnp.zeros_like(y))
    if cfg.num_shared_experts:
        routed = routed + _swiglu(
            y_in, w["s_gate"], w["s_up"], w["s_down"], act)
    return routed, gap, taken


_ATTENTION = ("wq_a", "wq_b", "wkv_a", "wk_b", "wv_b", "wo")
_GAINS = ("attn_norm_g", "q_norm_g", "kv_norm_g", "mlp_norm_g")


@functools.lru_cache(maxsize=16)
def _programs(cfg, control):
    """The jitted pieces of one (configuration, control) pair: a dense
    layer, a routed layer, the head; every layer of a kind shares a trace."""
    control = control or Control()
    prep = control.weights or _same
    act, kv = control.act or _same, control.kv or _same
    select = control.router or _published

    def f32(w, names, rounded=True):
        return {n: (prep if rounded else _same)(w[n].astype(jnp.float32))
                for n in names}

    def attend(x, w):
        cos, sin = _yarn_tables(cfg, x.shape[1])
        wa = {**f32(w, _ATTENTION), **f32(w, _GAINS, rounded=False)}

        def lane(x_lane):
            y = act(_rmsnorm(x_lane, wa["attn_norm_g"], cfg.norm_eps))
            return x_lane + _attention(y, wa, cfg, cos, sin, act, kv)

        x = jax.lax.map(lane, x)
        return x, _rmsnorm(x, wa["mlp_norm_g"], cfg.norm_eps)

    @jax.jit
    def dense_layer(x, w):
        x, y = attend(x, w)
        m = f32(w, ("w_gate", "w_up", "w_down"))
        return x + _swiglu(act(y), m["w_gate"], m["w_up"], m["w_down"], act)

    @jax.jit
    def routed_layer(x, w, record):
        x, y = attend(x, w)
        b, s, h = y.shape
        # the router stays float32 of what is stored, never rounded; the
        # expert stacks are widened one expert at a time, inside the loop
        m = {**f32(w, ("router",), rounded=False),
             **{n: w[n] for n in ("e_gate", "e_up", "e_down")}}
        if cfg.num_shared_experts:
            m.update(f32(w, ("s_gate", "s_up", "s_down")))
        add, gap, taken = _routed(
            y.reshape(b * s, h), m, record.reshape(b * s, -1), cfg, act,
            prep, select)
        return (x + add.reshape(b, s, h), gap.reshape(b, s),
                taken.reshape(b, s, -1))

    @jax.jit
    def head(x, rows, g_final, w_head):
        picked = jnp.take_along_axis(x, rows[:, :, None], axis=1)
        y = act(_rmsnorm(picked, g_final.astype(jnp.float32), cfg.norm_eps))
        step = -(-w_head.shape[1] // HEAD_BLOCKS)
        return jnp.concatenate([
            y @ prep(w_head[:, a:a + step].astype(jnp.float32))
            for a in range(0, w_head.shape[1], step)
        ], axis=-1)

    return dense_layer, routed_layer, head


def _layer_weights(params, i):
    prefix = f"l{i}_"
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def _record(routing, cfg, ids) -> np.ndarray:
    """The record as this block wants it: int32 [routed_layers, b, s, k], a
    decision either whole or -1, every id an expert, none twice."""
    shape = (cfg.num_layers - cfg.first_dense_layers, *ids.shape,
             cfg.experts_per_token)
    if routing is None:
        return np.full(shape, -1, np.int32)
    record = np.asarray(routing)
    if record.shape != shape or not np.issubdtype(record.dtype, np.integer):
        raise ValueError(
            f"routing record {record.dtype}{list(record.shape)}: this block "
            f"wants int32{list(shape)}"
        )
    ordered = np.sort(record[record[..., 0] >= 0], axis=-1)
    if (ordered[:, 0] < 0).any() or (ordered >= cfg.num_experts).any() or (
            ordered[:, 1:] == ordered[:, :-1]).any():
        raise ValueError(
            "routing record: a decision names an expert that is none of "
            f"0..{cfg.num_experts - 1}, or one twice"
        )
    return record.astype(np.int32)


def forward_logits(params, cfg, ids, rows,
                   control: Optional[Control] = None, routing=None):
    """(logits float32 [b, n_rows, vocab] of one full forward pass over
    ``ids`` [b, s] at the positions ``rows`` [b, n_rows]; the choice gap of
    every decision float32 [routed_layers, b, s]; the expert sets the pass
    computed with int32 [routed_layers, b, s, k]).

    ``routing``: the program's record, replayed (-1: this pass's own
    choice); ``None``: own choices throughout.  ``control``: one of
    ``weights.controls_for(cfg)``."""
    dense_layer, routed_layer, head = _programs(cfg, control)
    record = _record(routing, cfg, ids)
    gaps, sets = [], []
    with jax.default_matmul_precision("highest"):
        x = params["tok_emb"][ids].astype(jnp.float32)
        for i in range(cfg.num_layers):
            w = _layer_weights(params, i)
            if i < cfg.first_dense_layers:
                x = dense_layer(x, w)
                continue
            x, gap, taken = routed_layer(
                x, w, jnp.asarray(record[i - cfg.first_dense_layers]))
            gaps.append(gap)
            sets.append(taken)
        logits = head(x, rows, params["final_norm_g"], params["lm_head"])
    return logits, jnp.stack(gaps), jnp.stack(sets)
