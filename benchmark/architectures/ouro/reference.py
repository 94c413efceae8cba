"""Plain float32 reference of the Ouro looped decoder
(``https://huggingface.co/ByteDance/Ouro-2.6B``, ``model_type`` ``ouro``;
arXiv:2510.25741, as the family's ``modeling_ouro.py`` writes it).

Straightforward ``jax.numpy``: no kernel, no cache, no paging, nothing
imported from the program.  Every matmul runs under
``jax.default_matmul_precision("highest")``.  The weights are the tensors
the benchmark made from the seed (``weights.py``), widened to float32 one
layer at a time — inside the layer's jitted program, so 10.7 GB of float32
weights never stand at once.

``T`` = ``total_ut_steps``, ``L`` layers, ``x`` the residual stream,
RMSNorm ``rms(x; g) = x / sqrt(mean(x^2) + eps) * g``:

    x = E[ids]
    for t in 0 .. T-1:                      the SAME parameters every t
        for i in 0 .. L-1:
            y = rms(x; g1_i);  q, k, v = y Wq_i, y Wk_i, y Wv_i
            q, k = rope(q, pos), rope(k, pos)      the token's position,
                                                   the same every t
            a = softmax(q k^T / sqrt(d), causal) v over THIS step's k, v
                (the cache entry (t, i): no step reads another's)
            x = x + rms(a Wo_i; g2_i)
            y = rms(x; g3_i)
            x = x + rms((silu(y Wgate_i) * y Wup_i) Wdown_i; g4_i)
        x = rms(x; g_final)                 closes EVERY step, enters t+1
        (lambda_t = sigmoid(x . w_exit + b_exit): the exit gate, NOT
         evaluated at early_exit_threshold 1 — no step exits early)
    logits = x W_head                       of the last step

``g1`` .. ``g4`` are ``input_layernorm``, ``input_layernorm_2``,
``post_attention_layernorm``, ``post_attention_layernorm_2``.  The catalog
row carries no key for the four norms, the closing norm, the entry a
(step, layer) or the gate's shape: each is the family's code, and each is
listed under ``assumed`` in the configuration file.

``control`` (``weights.controls_for``): ``weights`` rounds every matrix a
matmul streams, ``act`` every matmul input, ``kv`` the keys and values as
a cache entry would hold them (per token and head, in every step)."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from harness.weights import Control

_MATS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
_GAINS = ("attn_norm_g", "attn_post_norm_g", "mlp_norm_g", "mlp_post_norm_g")


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x [b, s, heads, d], positions 0..s-1, split-halves convention."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _same(x):
    return x


def _layer(x, w, g, *, n_heads, n_kv, d, theta, eps, act, kv):
    b, s, _ = x.shape
    y = act(_rms(x, g["attn_norm_g"], eps))
    q = (y @ w["wq"]).reshape(b, s, n_heads, d)
    k = (y @ w["wk"]).reshape(b, s, n_kv, d)
    v = (y @ w["wv"]).reshape(b, s, n_kv, d)
    q, k = _rope(q, theta), _rope(k, theta)
    k, v = kv(k), kv(v)
    group = n_heads // n_kv
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(d))
    ok = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scores = jnp.where(ok[None, None], scores, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    o = act(a.reshape(b, s, n_heads * d)) @ w["wo"]
    x = x + _rms(o, g["attn_post_norm_g"], eps)
    y = act(_rms(x, g["mlp_norm_g"], eps))
    m = act(jax.nn.silu(y @ w["w_gate"]) * (y @ w["w_up"])) @ w["w_down"]
    return x + _rms(m, g["mlp_post_norm_g"], eps)


@functools.lru_cache(maxsize=16)
def _programs(cfg, control):
    """The three jitted pieces for one (configuration, control) pair;
    cached so that 4 x 48 layer calls share one trace."""
    control = control or Control()
    prep = control.weights or _same
    act = control.act or _same

    @jax.jit
    def layer(x, w, g):
        w32 = {n: prep(w[n].astype(jnp.float32)) for n in _MATS}
        g32 = {n: g[n].astype(jnp.float32) for n in _GAINS}
        return _layer(
            x, w32, g32, n_heads=cfg.num_heads, n_kv=cfg.num_kv_heads,
            d=cfg.head_dim, theta=cfg.rope_theta, eps=cfg.norm_eps,
            act=act, kv=control.kv or _same,
        )

    @jax.jit
    def close(x, g_final):
        return _rms(x, g_final.astype(jnp.float32), cfg.norm_eps)

    @jax.jit
    def head(x, rows, w):
        picked = jnp.take_along_axis(x, rows[:, :, None], axis=1)
        return act(picked) @ prep(w.astype(jnp.float32))

    return layer, close, head


def forward_logits(params, cfg, ids, rows,
                   control: Optional[Control] = None):
    """Logits [b, n_rows, vocab] float32 of the full forward pass over
    ``ids`` [b, s] at the positions ``rows`` [b, n_rows].

    ``control``: one of ``weights.controls_for(cfg)``, the reference
    computed in a lower precision than the configuration states."""
    layer, close, head = _programs(cfg, control)
    with jax.default_matmul_precision("highest"):
        x = params["tok_emb"][ids].astype(jnp.float32)
        for _step in range(cfg.loop_steps):
            for i in range(cfg.num_layers):
                x = layer(
                    x, {n: params[f"l{i}_{n}"] for n in _MATS},
                    {n: params[f"l{i}_{n}"] for n in _GAINS},
                )
            x = close(x, params["final_norm_g"])
        return head(x, rows, params["lm_head"])
