"""Plain float32 reference of the Mistral-7B decoder (arXiv:2310.06825,
the Llama block with grouped-query attention and a sliding window).

Straightforward ``jax.numpy``: no kernel, no cache, no paging, no batching
trick.  Every matmul runs under ``jax.default_matmul_precision("highest")``
— on a TPU a float32 product is otherwise computed in bfloat16 passes.
The weights are the tensors the benchmark made from the seed
(``weights.py``), dequantized to float32 one layer at a time so that a 7B
tree never has to exist in float32 at once.

Per layer, for hidden states x [b, s, h]:

    y  = rmsnorm(x) * g_attn                       rmsnorm: x / sqrt(mean(x^2) + eps)
    q, k, v = y Wq, y Wk, y Wv                     heads of size d; kv heads shared by groups
    q, k = rope(q, pos), rope(k, pos)              split-halves rotation, theta from the config
    a  = softmax(q k^T / sqrt(d) + mask) v         mask: causal, and key > query - window
    x  = x + a Wo
    y  = rmsnorm(x) * g_mlp
    x  = x + (silu(y Wgate) * (y Wup)) Wdown

then logits = (rmsnorm(x) * g_final) Whead.  No departure from the
published description; the window only bites past ``sliding_window``
positions, which the checked prompts (<= 1024 tokens) never reach.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from . import weights as _weights

_MATS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def _rmsnorm(x, g, eps):
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


def _layer(x, w, g_attn, g_mlp, *, n_heads, n_kv, d, theta, eps, window,
           act=_same, kv=_same):
    """``act`` rounds every matmul input and ``kv`` the keys and values as a
    cache would hold them (per token, per head): identities in the
    reference, a coarser type in a lower-precision control."""
    b, s, _ = x.shape
    y = act(_rmsnorm(x, g_attn, eps))
    q = (y @ w["wq"]).reshape(b, s, n_heads, d)
    k = (y @ w["wk"]).reshape(b, s, n_kv, d)
    v = (y @ w["wv"]).reshape(b, s, n_kv, d)
    q, k = _rope(q, theta), _rope(k, theta)
    k, v = kv(k), kv(v)
    group = n_heads // n_kv
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(d))
    qi = jnp.arange(s)[:, None]
    ki = jnp.arange(s)[None, :]
    ok = ki <= qi
    if window is not None:
        ok = ok & (ki > qi - window)
    scores = jnp.where(ok[None, None], scores, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + act(a.reshape(b, s, n_heads * d)) @ w["wo"]
    y = act(_rmsnorm(x, g_mlp, eps))
    return x + act(jax.nn.silu(y @ w["w_gate"]) * (y @ w["w_up"])) @ w["w_down"]


@functools.lru_cache(maxsize=16)
def _programs(cfg, control):
    """The two jitted pieces for one (configuration, control) pair; cached
    so that 32 layers and every later call share one trace."""
    control = control or _weights.Control()
    prep = control.weights or _same

    @jax.jit
    def layer(x, w, g_attn, g_mlp):
        w32 = {n: prep(_weights.dequantized(w, n)) for n in _MATS}
        return _layer(
            x, w32, g_attn.astype(jnp.float32), g_mlp.astype(jnp.float32),
            n_heads=cfg.num_heads, n_kv=cfg.num_kv_heads, d=cfg.head_dim,
            theta=cfg.rope_theta, eps=cfg.norm_eps,
            window=cfg.sliding_window,
            act=control.act or _same, kv=control.kv or _same,
        )

    @jax.jit
    def head(x, rows, g_final, w):
        picked = jnp.take_along_axis(x, rows[:, :, None], axis=1)
        y = (control.act or _same)(
            _rmsnorm(picked, g_final.astype(jnp.float32), cfg.norm_eps)
        )
        return y @ prep(_weights.dequantized(w, "lm_head"))

    return layer, head


def _with_scale(params, names):
    out = {}
    for short, full in names.items():
        out[short] = params[full]
        scale = params.get(full + _weights.SCALE)
        if scale is not None:
            out[short + _weights.SCALE] = scale
    return out


def forward_logits(
    params, cfg, ids, rows, control: Optional[_weights.Control] = None,
):
    """Logits [b, n_rows, vocab] float32 of the full forward pass over
    ``ids`` [b, s] at the positions ``rows`` [b, n_rows].

    ``control``: one of ``weights.controls_for(cfg)``, the reference
    computed in a lower precision than the configuration states.
    """
    layer, head = _programs(cfg, control)
    with jax.default_matmul_precision("highest"):
        x = params["tok_emb"][ids].astype(jnp.float32)
        for i in range(cfg.num_layers):
            w = _with_scale(params, {n: f"l{i}_{n}" for n in _MATS})
            x = layer(
                x, w, params[f"l{i}_attn_norm_g"], params[f"l{i}_mlp_norm_g"]
            )
        return head(
            x, rows, params["final_norm_g"],
            _with_scale(params, {"lm_head": "lm_head"}),
        )
