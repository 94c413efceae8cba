"""Plain float32 reference of the Brumby decoder
(``https://huggingface.co/manifestai/Brumby-14B-Base``, ``model_type``
``brumby``: the Qwen3-14B trunk with every attention replaced by gated
power retention of degree 2; Manifest AI, "Scaling Context Requires
Rethinking Attention", arXiv:2507.04239).

Straightforward ``jax.numpy`` in the ATTENTION form: no state, no chunk, no
kernel, no cache, no paging, nothing imported from the program.  Every
matmul runs under ``jax.default_matmul_precision("highest")``.  The
weights are the tensors the benchmark made from the seed (``weights.py``),
dequantized to float32 one layer at a time; a layer runs one lane at a
time, its MLP a block of rows at a time and its retention a block of query
rows at a time, so that 4 x 9.4k rows at the published widths fit beside
the served tree.

Every layer, ``x`` the residual stream, ``d`` the head width, ``g(h)`` the
kv head of query head ``h``, RMSNorm eps from the file:

    y = rmsnorm(x)
    q = y Wq [40, d];  k = y Wk, v = y Wv [8, d]
    gamma = log sigmoid(y W_decay)  [8]     (<= 0: the token's log gate)
    q, k = rmsnorm_head(q), rmsnorm_head(k)        (Qwen3's q_norm / k_norm)
    q, k = rope(q), rope(k)                         (theta from the file)
    G_t = sum_{i <= t} gamma_i                      (per kv head)
    a_tj = ((q_t^h . k_j^g(h)) / sqrt(d))^2  exp(G_t - G_j),   j <= t
    o_t^h = sum_j a_tj v_j^g(h) / (sum_j a_tj + eps)
    x = x + o Wo
    x = x + W_down(silu(W_gate r) * W_up r),   r = rmsnorm(x)
    logits = rmsnorm(x_L) W_head                    (untied; no bias anywhere)

Assumed (the row gives no more; the configuration file lists each): the
power is 2; the gate is a bias-free projection to the kv heads through
``log sigmoid``; the weights are divided by their running sum plus ``eps``
(1e-6); the scale ``1 / sqrt(d)`` sits inside the square; no output gate,
no output norm.

``control`` (``weights.controls_for``): ``weights`` rounds every matrix a
matmul streams, ``act`` every matmul input.  ``kv(x, what)`` is handed the
state a lane would keep: a layer then runs :func:`retention_chunked`, the
same function with the past of a ``CHUNK`` of rows held as the expanded
state, float32 at highest precision — ``what`` "state" is the state as it
is kept from one chunk to the next (a pool's type), "carry" the copy of
it a chunk's rows READ (the type of the product that reads it, or nothing:
the control that forgets what a lane carried).  Nothing else runs that
form."""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from harness.weights import Control

from . import weights as _weights

EPS = 1e-6  # added to the running sum of weights a row divides by
Q_ROWS = 256  # query rows a block of the retention holds
MLP_ROWS = 2048
CHUNK = 128  # rows of the chunked form (the program's own, restated)
HEAD_BLOCKS = 4
_MATS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def _same(x):
    return x


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x [s, heads, d], positions 0..s-1, split-halves convention."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _in_row_blocks(fn, x, rows: int):
    """``fn`` over ``x`` [s, ...] a block of ``rows`` rows at a time."""
    s = x.shape[0]
    if s <= rows:
        return fn(x)
    n = -(-s // rows)
    padded = jnp.pad(x, ((0, n * rows - s),) + ((0, 0),) * (x.ndim - 1))
    out = jax.lax.map(fn, padded.reshape(n, rows, *x.shape[1:]))
    return out.reshape(n * rows, *out.shape[2:])[:s]


def power_retention(q, k, v, gamma):
    """The attention form, one lane: q [s, heads, d]; k, v [s, kv heads,
    d]; gamma [s, kv heads] -> [s, heads, d]."""
    s, hq, d = q.shape
    g = k.shape[1]
    big_g = jnp.cumsum(gamma, axis=0)  # [s, g]
    key_pos = jnp.arange(s)

    def rows(t):  # t [bq] positions
        at = jnp.minimum(t, s - 1)
        qb = q[at].reshape(-1, g, hq // g, d)
        score = jnp.einsum("qgpd,kgd->gpqk", qb, k) / math.sqrt(d)
        # G_t - G_j <= 0 wherever j <= t: the mask comes before the exp
        rel = big_g[at].T[:, :, None] - big_g.T[:, None, :]  # [g, q, k]
        mask = key_pos[None, :] <= t[:, None]
        decay = jnp.where(mask[None], jnp.exp(jnp.minimum(rel, 0.0)), 0.0)
        a = score * score * decay[:, None]
        num = jnp.einsum("gpqk,kgd->qgpd", a, v)
        den = a.sum(axis=-1).transpose(2, 0, 1)[..., None]  # [q, g, p, 1]
        return (num / (den + EPS)).reshape(-1, hq, d)

    n = -(-s // Q_ROWS)
    out = jax.lax.map(rows, jnp.arange(n * Q_ROWS).reshape(n, Q_ROWS))
    return out.reshape(n * Q_ROWS, hq, d)[:s]


def power_features(x):
    """``phi(x)`` [..., d (d + 1) / 2]: the products ``x_a x_b``, a <= b,
    a pair of different channels times ``sqrt(2)``: ``phi(q) . phi(k) =
    (q . k)^2``."""
    d = x.shape[-1]
    a, b = jnp.triu_indices(d)
    return x[..., a] * x[..., b] * jnp.where(a == b, 1.0, math.sqrt(2.0))


def retention_chunked(q, k, v, gamma, kv):
    """THE SAME FUNCTION with the past held as a state (the controls of the
    state alone run it), ``CHUNK`` rows at a time: inside a chunk the
    attention form, across chunks ``S = sum_j decay phi(k_j) [v_j, 1]^T /
    d`` (held transposed, the features last) — read by ``phi(q)``, advanced
    once a chunk.  ``kv(S, "carry")`` is the copy a chunk's rows read,
    ``kv(S, "state")`` what is kept for the next chunk."""
    s, hq, d = q.shape
    g = k.shape[1]
    n = -(-s // CHUNK)
    feats = d * (d + 1) // 2
    causal = jnp.tril(jnp.ones((CHUNK, CHUNK), bool))

    def chunks(x):  # rows past the end: no key, no value, a gate of 1
        x = jnp.pad(x, ((0, n * CHUNK - s),) + ((0, 0),) * (x.ndim - 1))
        return x.reshape(n, CHUNK, *x.shape[1:])

    def step(state, xs):
        qb, kb, vb, gb = xs
        cum = jnp.cumsum(gb, axis=0)  # [c, g]: G_t - G(chunk start)
        qg = qb.reshape(CHUNK, g, hq // g, d)
        score = jnp.einsum("tgpd,sgd->gpts", qg, kb) / math.sqrt(d)
        rel = cum.T[:, :, None] - cum.T[:, None, :]
        decay = jnp.where(causal, jnp.exp(jnp.minimum(rel, 0.0)), 0.0)
        vv = jnp.concatenate(
            [vb, jnp.ones((CHUNK, g, 1), jnp.float32)], axis=-1)
        num = jnp.einsum("gpts,sge->tgpe", score * score * decay[:, None], vv)
        read = jnp.einsum(
            "tgpf,gef->tgpe", power_features(qg), kv(state, "carry"))
        num = num + jnp.exp(cum)[:, :, None, None] * read
        left = jnp.exp(cum[-1][None, :] - cum)[:, :, None] / d
        state = kv(
            jnp.exp(cum[-1])[:, None, None] * state + jnp.einsum(
                "tge,tgf->gef", vv * left, power_features(kb)), "state")
        return state, (num[..., :d] / (num[..., d:] + EPS)).reshape(
            CHUNK, hq, d)

    _, out = jax.lax.scan(
        step, jnp.zeros((g, d + 1, feats), jnp.float32),
        (chunks(q), chunks(k), chunks(v), chunks(gamma)))
    return out.reshape(n * CHUNK, hq, d)[:s]


@functools.lru_cache(maxsize=32)
def _programs(cfg, control):
    """The jitted pieces of one (configuration, control) pair: the layer
    (every layer shares a trace) and the head."""
    control = control or Control()
    prep = control.weights or _same
    act = control.act or _same
    eps = cfg.norm_eps
    heads, kv_heads, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

    @jax.jit
    def layer(x, w):
        w32 = {n: prep(_weights.dequantized(w, n)) for n in _MATS}
        g32 = {n: v.astype(jnp.float32) for n, v in w.items()
               if n.endswith("_norm_g") or n == "w_decay"}

        def mlp(rows):
            y = act(_rmsnorm(rows, g32["mlp_norm_g"], eps))
            return act(jax.nn.silu(y @ w32["w_gate"]) * (
                y @ w32["w_up"])) @ w32["w_down"]

        def lane(x):
            s = x.shape[0]
            y = act(_rmsnorm(x, g32["attn_norm_g"], eps))
            q = (y @ w32["wq"]).reshape(s, heads, d)
            k = (y @ w32["wk"]).reshape(s, kv_heads, d)
            v = (y @ w32["wv"]).reshape(s, kv_heads, d)
            gamma = jax.nn.log_sigmoid(y @ g32["w_decay"])
            q = _rope(_rmsnorm(q, g32["q_norm_g"], eps), cfg.rope_theta)
            k = _rope(_rmsnorm(k, g32["k_norm_g"], eps), cfg.rope_theta)
            if control.kv is None:
                o = power_retention(q, k, v, gamma)
            else:
                o = retention_chunked(q, k, v, gamma, control.kv)
            x = x + act(o.reshape(s, heads * d)) @ w32["wo"]
            return x + _in_row_blocks(mlp, x, MLP_ROWS)

        return jax.lax.map(lane, x)

    @jax.jit
    def head(x, rows, g_final, w):
        picked = jnp.take_along_axis(x, rows[:, :, None], axis=1)
        y = act(_rmsnorm(picked, g_final.astype(jnp.float32), eps))
        w_head = w["lm_head"]
        scale = w.get("lm_head" + _weights.SCALE)
        step = -(-w_head.shape[1] // HEAD_BLOCKS)
        out = []
        for a in range(0, w_head.shape[1], step):
            part = {"lm_head": w_head[:, a:a + step]}
            if scale is not None:
                part["lm_head" + _weights.SCALE] = scale[a:a + step]
            out.append(y @ prep(_weights.dequantized(part, "lm_head")))
        return jnp.concatenate(out, axis=-1)

    return layer, head


def _layer_tensors(params, i):
    prefix = f"l{i}_"
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def forward_logits(params, cfg, ids, rows,
                   control: Optional[Control] = None):
    """Logits float32 [b, n_rows, vocab] of one full forward pass over
    ``ids`` [b, s] at the positions ``rows`` [b, n_rows].  ``control``:
    one of ``weights.controls_for(cfg)`` or ``weights.kv_only_controls()``
    (module docstring)."""
    layer, head = _programs(cfg, control)
    ids = jnp.asarray(ids)
    with jax.default_matmul_precision("highest"):
        x = params["tok_emb"][ids].astype(jnp.float32)
        for i in range(cfg.num_layers):
            x = layer(x, _layer_tensors(params, i))
        return head(
            x, jnp.asarray(rows), params["final_norm_g"],
            {k: v for k, v in params.items() if k.startswith("lm_head")})
