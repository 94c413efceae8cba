"""Plain float32 reference of the Arcee AFMoE decoder
(``https://huggingface.co/arcee-ai/Trinity-Mini``, ``model_type``
``afmoe``; the family's public ``modeling_afmoe.py``).

Straightforward ``jax.numpy``: no kernel, no cache, no paging, nothing
imported from the program.  Every matmul runs under
``jax.default_matmul_precision("highest")``.  The weights are the tensors
the benchmark made from the seed (``weights.py``), widened to float32 one
layer at a time, a routed expert at a time, the output head a block of the
vocabulary at a time; a layer runs one lane at a time and its attention a
block of query rows at a time, so that 4 x 9.4k rows fit beside the served
tree.

``y = N(x)`` is RMSNorm with a gain, eps from the file.  ``x0 = E[ids]
sqrt(hidden)`` (``mup_enabled``).  Layer i:

    a  = x + N2(Attn_i(N1(x)));   x' = a + N4(FF_i(N3(a)))
    Attn_i, y = N1(x):
        q = Nq(y Wq) [heads x d],  k = Nk(y Wk) [kv heads x d]   per head
        v = y Wv [kv heads x d]
        sliding_attention: q, k = rope(q), rope(k)  (theta from the file);
                           row t attends j <= t with j > t - sliding_window
        full_attention:    NO rotation; every j <= t
        o = softmax(q k^T / sqrt(d)) v;   Attn = (o * sigmoid(y Wg)) Wo
    FF_i, y = N3(a):
        i < num_dense_layers:  SwiGLU of intermediate_size
        else  s = sigmoid(y Wr)                  float32 [router_experts]
              T = top k of (s + b)   -- b: the selection bias; or the
                                        RECORD's set (replay)
              g_e = route_scale s_e / (sum_{e in T} s_e + 1e-20)
              FF = SwiGLU_shared(y) + sum_{e in T, held here} g_e SwiGLU_e(y)
    logits = N(x_L) W_head                       (untied)

What the catalog row's ``config`` does not spell — the four norms a layer,
the q / k norms, the output gate, rotation in the window layers alone, the
bias's role — is the family's code and listed under the file's
``assumed``.  Departures, each deliberate:

* RoPE pairs the two HALVES of a head (the program's ``ops/rope.py``); the
  published code rotates the same halves (``rotate_half``).
* The process holds a contiguous RANGE of the routed experts (the chip's
  share): the router scores all of them, the sum runs over the held ones,
  and what absent experts would add is left out — as in the program.
* Under replay (``routing``) a layer computes with the recorded set; the
  gates stay THIS pass's float32 scores of those experts, normalised over
  the set and scaled.
* The choice gap is taken on the SELECTION scores ``s + b`` (one group: no
  stage before the experts'): the most by which a selection score of this
  pass's own top k that the record left out exceeds one the record took
  instead, 0 where the sets agree.

``control`` (``weights.controls_for``): ``weights`` rounds every matrix a
matmul streams (the routers stay float32 of what is stored), ``act`` every
matmul input, ``kv`` the keys and values as a cache would hold them,
``router(scores, bias)`` gives the selection scores in the published
rule's place."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from harness.weights import Control

from . import weights as _weights

WINDOW = _weights.WINDOW
Q_ROWS = 256  # query rows a block of the attention holds
MLP_ROWS = 2048  # rows a block of a feed-forward holds
HEAD_BLOCKS = 4
_ATTENTION = ("wq", "wk", "wv", "w_ogate", "wo")
_GAINS = ("attn_norm_g", "attn_post_norm_g", "mlp_norm_g", "mlp_post_norm_g",
          "q_norm_g", "k_norm_g")


def _same(x):
    return x


def _published(scores, bias):
    """Selection scores of the published rule: the score plus the bias."""
    return scores + bias


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope_tables(cfg, n):
    """(cos, sin) [n, d / 2], positions 0..n-1."""
    d = cfg.head_dim
    inv_freq = 1.0 / cfg.rope_theta ** (
        np.arange(0, d, 2, dtype=np.float64) / d)
    angles = np.arange(n)[:, None] * inv_freq[None, :]
    return (jnp.asarray(np.cos(angles), jnp.float32),
            jnp.asarray(np.sin(angles), jnp.float32))


def _rope(x, cos, sin):
    """x [s, heads, d]; positions 0..s-1; halves paired."""
    d = x.shape[-1]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _attention(y, w, cfg, kind, tables, act, kv):
    """One lane: y [s, h] -> what attention adds [s, h], before N2."""
    s = y.shape[0]
    heads, kv_heads, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    y_in = act(y)
    q = _rmsnorm((y_in @ w["wq"]).reshape(s, heads, d), w["q_norm_g"],
                 cfg.norm_eps)
    k = _rmsnorm((y_in @ w["wk"]).reshape(s, kv_heads, d), w["k_norm_g"],
                 cfg.norm_eps)
    v = (y_in @ w["wv"]).reshape(s, kv_heads, d)
    if kind == WINDOW:
        q, k = _rope(q, *tables), _rope(k, *tables)
    # the rows a cache would hold
    k = jnp.repeat(kv(k), heads // kv_heads, axis=1)
    v = jnp.repeat(kv(v), heads // kv_heads, axis=1)
    cols = jnp.arange(s)

    def block(start):
        rows = start + jnp.arange(Q_ROWS)
        qb = jax.lax.dynamic_slice_in_dim(q, start, Q_ROWS)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * d ** -0.5
        seen = cols[None, :] <= rows[:, None]
        if kind == WINDOW:
            seen &= cols[None, :] > rows[:, None] - cfg.sliding_window
        scores = jnp.where(seen[None], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)

    pad = -s % Q_ROWS
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    o = jax.lax.map(block, jnp.arange(0, s + pad, Q_ROWS))
    o = o.reshape(s + pad, heads * d)[:s]
    gate = jax.nn.sigmoid(y_in @ w["w_ogate"])
    return act(o * gate) @ w["wo"]


def _swiglu(y, gate, up, down, act):
    return act(jax.nn.silu(y @ gate) * (y @ up)) @ down


def _membership(ids, n):
    return (ids[..., None] == jnp.arange(n)).any(-2)


def _choice_gap(selection, in_taken, k):
    """The most by which a selection score of the own top k that the
    record left out exceeds one it took instead; 0 where they agree."""
    in_own = _membership(jax.lax.top_k(selection, k)[1], selection.shape[-1])
    left_out = jnp.where(in_own & ~in_taken, selection, -jnp.inf).max(-1)
    instead = jnp.where(in_taken & ~in_own, selection, jnp.inf).min(-1)
    return jnp.maximum(
        jnp.where(left_out > -jnp.inf, left_out - instead, 0.0), 0.0)


def _routed(y, w, record, cfg, act, prep, select):
    """(what the layer's feed-forward gives for y [n, h], choice gap [n],
    the sets taken [n, k]); ``record`` int32 [n, k], -1 where it holds
    nothing."""
    lo, held = _weights.held_range(cfg)
    k = cfg.experts_per_token
    scores = jax.nn.sigmoid(y @ w["router"])
    own = jax.lax.top_k(select(scores, w["router_bias"]), k)[1]
    taken = jnp.where(record[:, :1] >= 0, record, own)
    in_taken = _membership(taken, cfg.num_experts)
    gap = _choice_gap(_published(scores, w["router_bias"]), in_taken, k)
    gates = jnp.where(in_taken, scores, 0.0)
    gates = cfg.routed_scale * gates / (
        gates.sum(-1, keepdims=True) + 1e-20)
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


def _row_blocks(fn, y, *more):
    """``fn(rows [MLP_ROWS, h], *more's rows)`` over y [n, h] a block of
    rows at a time; ``fn`` returns a tuple of arrays with a leading row
    axis."""
    n = y.shape[0]
    pad = -n % MLP_ROWS

    def blocked(a, fill=0):
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1),
                    constant_values=fill)
        return a.reshape(-1, MLP_ROWS, *a.shape[1:])

    out = jax.lax.map(
        lambda args: fn(*args),
        (blocked(y), *(blocked(m, -1) for m in more)))
    return tuple(o.reshape(-1, *o.shape[2:])[:n] for o in out)


@functools.lru_cache(maxsize=16)
def _programs(cfg, control):
    """The jitted pieces of one (configuration, control) pair: a layer of
    each (attention kind, feed-forward kind), the head; every layer of a
    kind shares a trace."""
    control = control or Control()
    prep = control.weights or _same
    act, kv = control.act or _same, control.kv or _same
    select = control.router or _published
    eps = cfg.norm_eps

    def f32(w, names, rounded=True):
        return {n: (prep if rounded else _same)(w[n].astype(jnp.float32))
                for n in names}

    def attend(x, w, kind):
        tables = _rope_tables(cfg, x.shape[1])
        wa = {**f32(w, _ATTENTION), **f32(w, _GAINS, rounded=False)}

        def lane(x_lane):
            y = _rmsnorm(x_lane, wa["attn_norm_g"], eps)
            branch = _attention(y, wa, cfg, kind, tables, act, kv)
            return x_lane + _rmsnorm(branch, wa["attn_post_norm_g"], eps)

        x = jax.lax.map(lane, x)
        return x, _rmsnorm(x, wa["mlp_norm_g"], eps), wa["mlp_post_norm_g"]

    @functools.partial(jax.jit, static_argnames="kind")
    def dense_layer(x, w, kind):
        x, y, g_post = attend(x, w, kind)
        m = f32(w, ("w_gate", "w_up", "w_down"))
        b, s, h = y.shape
        (ff,) = _row_blocks(
            lambda rows: (_swiglu(act(rows), m["w_gate"], m["w_up"],
                                  m["w_down"], act),),
            y.reshape(b * s, h))
        return x + _rmsnorm(ff.reshape(b, s, h), g_post, eps)

    @functools.partial(jax.jit, static_argnames="kind")
    def routed_layer(x, w, record, kind):
        x, y, g_post = attend(x, w, kind)
        b, s, h = y.shape
        # the router and its bias stay float32 of what is stored, never
        # rounded; the expert stacks are widened one expert at a time
        m = {**f32(w, ("router", "router_bias"), rounded=False),
             **{n: w[n] for n in ("e_gate", "e_up", "e_down")}}
        if cfg.num_shared_experts:
            m.update(f32(w, ("s_gate", "s_up", "s_down")))
        ff, gap, taken = _row_blocks(
            lambda rows, rec: _routed(rows, m, rec, cfg, act, prep, select),
            y.reshape(b * s, h), record.reshape(b * s, -1))
        return (x + _rmsnorm(ff.reshape(b, s, h), g_post, eps),
                gap.reshape(b, s), taken.reshape(b, s, -1))

    @jax.jit
    def head(x, rows, g_final, w_head):
        picked = jnp.take_along_axis(x, rows[:, :, None], axis=1)
        y = act(_rmsnorm(picked, g_final.astype(jnp.float32), eps))
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
        x = params["tok_emb"][ids].astype(jnp.float32) * cfg.hidden_dim ** 0.5
        for i, kind in enumerate(cfg.mixer_types):
            w = _layer_weights(params, i)
            if i < cfg.first_dense_layers:
                x = dense_layer(x, w, kind=kind)
                continue
            x, gap, taken = routed_layer(
                x, w, jnp.asarray(record[i - cfg.first_dense_layers]),
                kind=kind)
            gaps.append(gap)
            sets.append(taken)
        logits = head(x, rows, params["final_norm_g"], params["lm_head"])
    return logits, jnp.stack(gaps), jnp.stack(sets)
