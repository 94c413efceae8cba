"""This package's own plain float32 reference of the block: RMSNorm,
grouped-query attention with split-halves RoPE under a causal sliding
window, SwiGLU; ``jax.numpy``, no kernel, no cache."""

import jax
import jax.numpy as jnp

from . import weights

FINAL_NORM_GAIN = 1.0  # the test's broken copy doubles this


def _norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None]
    c, s = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], -1)


def forward_logits(params, cfg, ids, rows, control=None):
    if control is not None:
        raise NotImplementedError("the fixture has no control of its own")

    def w(name):
        return weights.dequantized(params, name)

    def g(name):
        return params[name].astype(jnp.float32)

    heads, kv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    with jax.default_matmul_precision("highest"):
        x = params["tok_emb"][ids].astype(jnp.float32)
        b, s, _ = x.shape
        qi, ki = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
        seen = ki <= qi
        if cfg.sliding_window is not None:
            seen = seen & (ki > qi - cfg.sliding_window)
        for i in range(cfg.num_layers):
            y = _norm(x, g(f"l{i}_attn_norm_g"), cfg.norm_eps)
            q = _rope((y @ w(f"l{i}_wq")).reshape(b, s, heads, d), cfg.rope_theta)
            k = _rope((y @ w(f"l{i}_wk")).reshape(b, s, kv, d), cfg.rope_theta)
            v = (y @ w(f"l{i}_wv")).reshape(b, s, kv, d)
            k, v = (jnp.repeat(t, heads // kv, axis=2) for t in (k, v))
            score = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(d))
            score = jnp.where(seen[None, None], score, -jnp.inf)
            a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(score, -1), v)
            x = x + a.reshape(b, s, heads * d) @ w(f"l{i}_wo")
            y = _norm(x, g(f"l{i}_mlp_norm_g"), cfg.norm_eps)
            x = x + (jax.nn.silu(y @ w(f"l{i}_w_gate")) * (y @ w(f"l{i}_w_up"))
                     ) @ w(f"l{i}_w_down")
        picked = jnp.take_along_axis(x, rows[:, :, None], axis=1)
        y = _norm(picked, FINAL_NORM_GAIN * g("final_norm_g"), cfg.norm_eps)
        return y @ w("lm_head")
