"""Plain float32 reference of the AI21 Jamba decoder
(``https://huggingface.co/ai21labs/AI21-Jamba2-3B``, ``model_type``
``jamba``; the Mamba-1 mixer of arXiv:2312.00752 as the slow path of the
family's ``modeling_jamba.py`` writes it).

Straightforward ``jax.numpy``: no kernel, no cache, no paging, nothing
imported from the program.  Every matmul runs under
``jax.default_matmul_precision("highest")``.  The weights are the tensors
the benchmark made from the seed (``weights.py``), widened to float32 one
layer at a time; a layer runs one lane at a time, its MLP a block of rows
at a time and its attention a block of query rows at a time, so that 4 x
9.4k rows fit beside the served tree.

Every layer ``l``, ``x`` the residual stream, RMSNorm eps from the file:

    y = rmsnorm(x);  x = x + mixer_l(y)
    x = x + W_down(silu(W_gate y') * W_up y'),   y' = rmsnorm(x)
    attention (l % attn_layer_period == attn_layer_offset: 7, 21):
        q = y Wq [20 x 128];  k = y Wk, v = y Wv [1 x 128];  NO rotation,
        no position embedding anywhere
        o = softmax(q k^T / sqrt(128), causal) v;  mixer = o Wo
    mamba (every other layer; inner = mamba_expand * hidden = 5120):
        [u, z] = y W_in                          2560 -> 2 x 5120
        c_t = silu(b_conv + sum_{j=0..3} w_conv[j] * u_{t-3+j})
                                      depthwise, causal, u_{<0} = 0
        [dt, B, C] = c W_x                       5120 -> 160 + 16 + 16
        dt, B, C = rmsnorm(dt), rmsnorm(B), rmsnorm(C)   (Jamba's three
                                                          inner norms)
        D_t = softplus(dt W_dt + b_dt)           160 -> 5120
        A = -exp(A_log)                          [5120, 16]
        h_t = exp(D_t (x) A) * h_{t-1} + (D_t * c_t) (x) B_t,  h_{-1} = 0
              (THE RECURRENCE: a ``lax.scan`` over tokens, [5120, 16])
        g_t = h_t C_t + D * c_t;   mixer = (g * silu(z)) W_out
    logits = rmsnorm(x_L) E^T                    (tied)

Departures and what the row does not give (the configuration file lists
each under ``assumed``): the layer order comes from ``i % period ==
offset``, which the catalog lists as not given and the family's code
defines; ``num_experts`` is 1, so every layer's feed-forward is the dense
MLP above and the ``expert_layer_*`` keys say nothing; the tree stores
``A_log`` and the conv's taps with the channel axis LAST (``[16, 5120]``,
``[4, 5120]``: the program's layout) and this file transposes ``A_log``
to the equations' ``[5120, 16]``; the head multiplies by the embedding
itself (no ``lm_head`` array exists in the tree).

``control`` (``weights.controls_for``): ``weights`` rounds every matrix a
matmul streams, ``act`` every matmul input, ``kv(x, what)`` the K and V
rows ("k", "v"), the state after every token ("h") and the conv's inputs
as a window would hold them ("u")."""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from harness.weights import Control

from . import weights as _weights

ATTENTION, MAMBA = _weights.ATTENTION, _weights.MAMBA
Q_ROWS = 256  # query rows a block of the attention holds
MLP_ROWS = 2048
HEAD_BLOCKS = 4
_MATS = {
    ATTENTION: ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"),
    MAMBA: ("w_in", "w_x", "w_dt", "w_out", "w_gate", "w_up", "w_down"),
}


def _same(x):
    return x


def _same_kv(x, _what):
    return x


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _in_row_blocks(fn, x, rows: int):
    """``fn`` over ``x`` [s, ...] a block of ``rows`` rows at a time."""
    s = x.shape[0]
    if s <= rows:
        return fn(x)
    n = -(-s // rows)
    padded = jnp.pad(x, ((0, n * rows - s),) + ((0, 0),) * (x.ndim - 1))
    out = jax.lax.map(fn, padded.reshape(n, rows, *x.shape[1:]))
    return out.reshape(n * rows, *out.shape[2:])[:s]


def causal_attention(q, k, v):
    """q [s, heads, d]; k, v [s, kv heads, d] -> [s, heads, d]: causal
    softmax attention over all tokens, no rotation."""
    s, hq, d = q.shape
    g = k.shape[1]
    key_pos = jnp.arange(s)

    def rows(t):  # t [bq] positions
        qb = q[jnp.minimum(t, s - 1)].reshape(-1, g, hq // g, d)
        scores = jnp.einsum("qgpd,kgd->gpqk", qb, k) / math.sqrt(d)
        mask = key_pos[None, :] <= t[:, None]
        probs = jax.nn.softmax(
            jnp.where(mask[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("gpqk,kgd->qgpd", probs, v).reshape(-1, hq, d)

    n = -(-s // Q_ROWS)
    out = jax.lax.map(rows, jnp.arange(n * Q_ROWS).reshape(n, Q_ROWS))
    return out.reshape(n * Q_ROWS, hq, d)[:s]


def causal_conv(u, taps, bias):
    """u [s, inner]; taps [K, inner] -> the depthwise causal conv before
    its activation, ``u`` before the sequence zero."""
    k = taps.shape[0]
    padded = jnp.pad(u, ((k - 1, 0), (0, 0)))
    out = sum(padded[j:j + u.shape[0]] * taps[j] for j in range(k))
    return out if bias is None else out + bias


def selective_scan(c, delta, a, b, cc, d_skip, kv):
    """The recurrence, one token a step: c, delta [s, inner]; a [inner,
    state]; b, cc [s, state] -> g [s, inner].  ``kv(h, "h")`` rounds the
    state as a pool would hold it."""

    def step(h, xs):
        ct, dt, bt, cct = xs
        h = kv(jnp.exp(dt[:, None] * a) * h
               + (dt * ct)[:, None] * bt[None, :], "h")
        return h, h @ cct + d_skip * ct

    _, g = jax.lax.scan(
        step, jnp.zeros(a.shape, jnp.float32), (c, delta, b, cc), unroll=8)
    return g


@functools.lru_cache(maxsize=32)
def _programs(cfg, control):
    """The jitted pieces of one (configuration, control) pair: a layer of
    each kind, the head; every layer of a kind shares a trace."""
    control = control or Control()
    prep = control.weights or _same
    act, kv = control.act or _same, control.kv or _same_kv
    eps = cfg.norm_eps
    inner, n, rank = _weights.inner(cfg), cfg.ssm_state_dim, cfg.ssm_dt_rank

    def attention(y, w32):
        s = y.shape[0]
        heads, kv_heads, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        q = (y @ w32["wq"]).reshape(s, heads, d)
        k = kv((y @ w32["wk"]).reshape(s, kv_heads, d), "k")
        v = kv((y @ w32["wv"]).reshape(s, kv_heads, d), "v")
        o = causal_attention(q, k, v)
        return act(o.reshape(s, heads * d)) @ w32["wo"]

    def mamba(y, w32):
        uz = y @ w32["w_in"]
        if cfg.ssm_proj_bias:
            uz = uz + w32["b_in"]
        u, z = kv(uz[:, :inner], "u"), uz[:, inner:]
        c = jax.nn.silu(causal_conv(
            u, w32["conv_w"], w32["conv_b"] if cfg.ssm_conv_bias else None))
        dbc = act(c) @ w32["w_x"]
        dt = _rmsnorm(dbc[:, :rank], w32["dt_norm_g"], eps)
        b = _rmsnorm(dbc[:, rank:rank + n], w32["b_norm_g"], eps)
        cc = _rmsnorm(dbc[:, rank + n:], w32["c_norm_g"], eps)
        delta = jax.nn.softplus(act(dt) @ w32["w_dt"] + w32["b_dt"])
        g = selective_scan(
            c, delta, -jnp.exp(w32["a_log"].T), b, cc, w32["d_skip"], kv)
        out = act(g * jax.nn.silu(z)) @ w32["w_out"]
        return out + w32["b_out"] if cfg.ssm_proj_bias else out

    def make_layer(kind):
        mixer = attention if kind == ATTENTION else mamba

        def layer(x, w):
            w32 = {name: (prep if name in _MATS[kind] else _same)(
                value.astype(jnp.float32)) for name, value in w.items()}

            def mlp(rows):
                y = act(_rmsnorm(rows, w32["mlp_norm_g"], eps))
                return act(jax.nn.silu(y @ w32["w_gate"]) * (
                    y @ w32["w_up"])) @ w32["w_down"]

            def lane(x):
                y = act(_rmsnorm(x, w32["attn_norm_g"], eps))
                x = x + mixer(y, w32)
                return x + _in_row_blocks(mlp, x, MLP_ROWS)

            return jax.lax.map(lane, x)

        return jax.jit(layer)

    @jax.jit
    def head(x, rows, g_final, emb):
        picked = jnp.take_along_axis(x, rows[:, :, None], axis=1)
        y = act(_rmsnorm(picked, g_final.astype(jnp.float32), eps))
        step = -(-emb.shape[0] // HEAD_BLOCKS)
        return jnp.concatenate([
            y @ prep(emb[a:a + step].astype(jnp.float32).T)
            for a in range(0, emb.shape[0], step)], axis=-1)

    return {kind: make_layer(kind) for kind in (ATTENTION, MAMBA)}, head


def _layer_tensors(params, i):
    prefix = f"l{i}_"
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def forward_logits(params, cfg, ids, rows,
                   control: Optional[Control] = None):
    """Logits float32 [b, n_rows, vocab] of one full forward pass over
    ``ids`` [b, s] at the positions ``rows`` [b, n_rows].  ``control``:
    one of ``weights.controls_for(cfg)`` (module docstring)."""
    layers, head = _programs(cfg, control)
    ids = jnp.asarray(ids)
    with jax.default_matmul_precision("highest"):
        x = params["tok_emb"][ids].astype(jnp.float32)
        for i, kind in enumerate(cfg.mixer_types):
            x = layers[kind](x, _layer_tensors(params, i))
        return head(x, jnp.asarray(rows), params["final_norm_g"],
                    params["tok_emb"])
