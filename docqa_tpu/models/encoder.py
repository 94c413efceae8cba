"""MiniLM-class sentence encoder — the device-plane replacement for
``SentenceTransformer('all-MiniLM-L6-v2')`` (``semantic-indexer/indexer.py:21``)
and ``HuggingFaceEmbeddings`` (``llm-qa/main.py:25``).

Pure-functional BERT stack: params are a plain pytree (dict of arrays), the
forward is a jit-compiled function.  The reference encoded one chunk at a
time on CPU (``indexer.py:37``, batch=1 — SURVEY §3.1 hot loop); here
encoding is batched on the ``data`` mesh axis with static shape buckets.

Matches the BERT/MiniLM architecture exactly (post-LN, GELU, learned
positions, token-type embeddings) so real all-MiniLM-L6-v2 safetensors can be
dropped in via :func:`load_hf_bert_weights`; falls back to seeded random
init in this zero-egress environment.  Pooling: masked mean over tokens +
L2 normalization, so dot product == cosine (SURVEY appendix: the reference
ran L2 over unnormalized embeddings; rankings agree once normalized).
"""

from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from docqa_tpu.config import EncoderConfig
from docqa_tpu.ops.attention import attention_reference
from docqa_tpu.ops.norms import layer_norm

Params = Dict[str, jax.Array]


def init_encoder_params(
    rng: jax.Array,
    cfg: EncoderConfig,
    host_init: bool = False,
    host_seed: Optional[int] = None,
) -> Params:
    """Seeded random init with BERT-style scales (trunc-normal 0.02).

    ``host_init`` draws on the host (numpy) and transfers — the path real
    safetensors checkpoints take, instead of ~112 eager device RNG
    programs (see models/decoder.py).  The serving engine defaults to
    it; the device path remains for training code that wants params
    born sharded."""
    if host_init:
        import numpy as _np

        from docqa_tpu.utils import host_seed_from_rng

        host_rng = _np.random.default_rng(host_seed_from_rng(rng, host_seed))

        def norm(shape, scale=0.02):
            return jax.device_put(
                (host_rng.standard_normal(shape) * scale).astype(_np.float32)
            )

    else:
        keys = iter(jax.random.split(rng, 16 + 16 * cfg.num_layers))

        def norm(shape, scale=0.02):
            return jax.random.normal(next(keys), shape, jnp.float32) * scale

    p: Params = {
        "tok_emb": norm((cfg.vocab_size, cfg.hidden_dim)),
        "pos_emb": norm((cfg.max_seq_len, cfg.hidden_dim)),
        "type_emb": norm((2, cfg.hidden_dim)),
        "emb_ln_g": jnp.ones((cfg.hidden_dim,)),
        "emb_ln_b": jnp.zeros((cfg.hidden_dim,)),
    }
    if cfg.embed_dim != cfg.hidden_dim:
        # sentence-transformers-style Dense head after pooling
        p["proj_w"] = norm((cfg.hidden_dim, cfg.embed_dim))
        p["proj_b"] = jnp.zeros((cfg.embed_dim,))
    for i in range(cfg.num_layers):
        h, m = cfg.hidden_dim, cfg.mlp_dim
        p.update(
            {
                f"l{i}_q_w": norm((h, h)), f"l{i}_q_b": jnp.zeros((h,)),
                f"l{i}_k_w": norm((h, h)), f"l{i}_k_b": jnp.zeros((h,)),
                f"l{i}_v_w": norm((h, h)), f"l{i}_v_b": jnp.zeros((h,)),
                f"l{i}_o_w": norm((h, h)), f"l{i}_o_b": jnp.zeros((h,)),
                f"l{i}_attn_ln_g": jnp.ones((h,)),
                f"l{i}_attn_ln_b": jnp.zeros((h,)),
                f"l{i}_up_w": norm((h, m)), f"l{i}_up_b": jnp.zeros((m,)),
                f"l{i}_down_w": norm((m, h)), f"l{i}_down_b": jnp.zeros((h,)),
                f"l{i}_mlp_ln_g": jnp.ones((h,)),
                f"l{i}_mlp_ln_b": jnp.zeros((h,)),
            }
        )
    return p


def encoder_forward(
    params: Params,
    cfg: EncoderConfig,
    ids: jax.Array,  # [b, s] int32, right-padded
    lengths: jax.Array,  # [b] int32
) -> jax.Array:
    """Token-level hidden states [b, s, hidden] (used by the NER head too)."""
    b, s = ids.shape
    h, nh = cfg.hidden_dim, cfg.num_heads
    hd = h // nh
    dtype = jnp.dtype(cfg.dtype)

    x = (
        params["tok_emb"][ids]
        + params["pos_emb"][None, :s]
        + params["type_emb"][0][None, None]
    )
    x = layer_norm(x, params["emb_ln_g"], params["emb_ln_b"]).astype(dtype)

    for i in range(cfg.num_layers):
        q = (x @ params[f"l{i}_q_w"].astype(dtype)) + params[f"l{i}_q_b"].astype(dtype)
        k = (x @ params[f"l{i}_k_w"].astype(dtype)) + params[f"l{i}_k_b"].astype(dtype)
        v = (x @ params[f"l{i}_v_w"].astype(dtype)) + params[f"l{i}_v_b"].astype(dtype)
        q = q.reshape(b, s, nh, hd)
        k = k.reshape(b, s, nh, hd)
        v = v.reshape(b, s, nh, hd)
        attn = attention_reference(q, k, v, lengths=lengths).reshape(b, s, h)
        attn = (attn @ params[f"l{i}_o_w"].astype(dtype)) + params[
            f"l{i}_o_b"
        ].astype(dtype)
        x = layer_norm(
            x + attn, params[f"l{i}_attn_ln_g"], params[f"l{i}_attn_ln_b"]
        ).astype(dtype)

        up = (x @ params[f"l{i}_up_w"].astype(dtype)) + params[f"l{i}_up_b"].astype(
            dtype
        )
        up = jax.nn.gelu(up.astype(jnp.float32), approximate=False).astype(dtype)
        down = (up @ params[f"l{i}_down_w"].astype(dtype)) + params[
            f"l{i}_down_b"
        ].astype(dtype)
        x = layer_norm(
            x + down, params[f"l{i}_mlp_ln_g"], params[f"l{i}_mlp_ln_b"]
        ).astype(dtype)
    return x


def mean_pool_normalize(hidden, lengths, normalize: bool = True):
    """Masked mean over valid tokens, then L2 normalize (f32)."""
    b, s, _ = hidden.shape
    mask = (jnp.arange(s)[None, :] < lengths[:, None]).astype(jnp.float32)
    hf = hidden.astype(jnp.float32)
    summed = jnp.einsum("bsh,bs->bh", hf, mask)
    pooled = summed / jnp.maximum(mask.sum(-1, keepdims=True), 1.0)
    if normalize:
        pooled = pooled / jnp.maximum(
            jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-9
        )
    return pooled


def encode_batch(
    params: Params, cfg: EncoderConfig, ids: jax.Array, lengths: jax.Array
) -> jax.Array:
    """[b, s] ids -> [b, embed_dim] normalized embeddings.  Jit this."""
    hidden = encoder_forward(params, cfg, ids, lengths)
    pooled = mean_pool_normalize(hidden, lengths, normalize=False)
    if cfg.embed_dim != cfg.hidden_dim:
        pooled = pooled @ params["proj_w"].astype(jnp.float32) + params[
            "proj_b"
        ].astype(jnp.float32)
    if cfg.normalize:
        pooled = pooled / jnp.maximum(
            jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-9
        )
    return pooled


# --------------------------------------------------------------------------
# HF weight import (offline-gated)
# --------------------------------------------------------------------------

_HF_LAYER_MAP = {
    "attention.self.query": ("q_w", "q_b"),
    "attention.self.key": ("k_w", "k_b"),
    "attention.self.value": ("v_w", "v_b"),
    "attention.output.dense": ("o_w", "o_b"),
    "intermediate.dense": ("up_w", "up_b"),
    "output.dense": ("down_w", "down_b"),
}


def load_hf_bert_weights(path: str, cfg: EncoderConfig) -> Params:
    """Map a HF BERT/MiniLM ``model.safetensors`` into our param tree.

    Torch ``nn.Linear`` stores [out, in]; we use [in, out] → transpose.
    """
    from safetensors.numpy import load_file

    raw = {k.replace("bert.", ""): v for k, v in load_file(path).items()}

    def t(name):
        return jnp.asarray(raw[name].T if raw[name].ndim == 2 else raw[name])

    p: Params = {
        "tok_emb": jnp.asarray(raw["embeddings.word_embeddings.weight"]),
        "pos_emb": jnp.asarray(raw["embeddings.position_embeddings.weight"]),
        "type_emb": jnp.asarray(raw["embeddings.token_type_embeddings.weight"]),
        "emb_ln_g": jnp.asarray(raw["embeddings.LayerNorm.weight"]),
        "emb_ln_b": jnp.asarray(raw["embeddings.LayerNorm.bias"]),
    }
    for i in range(cfg.num_layers):
        pre = f"encoder.layer.{i}."
        for hf_name, (w_key, b_key) in _HF_LAYER_MAP.items():
            p[f"l{i}_{w_key}"] = t(pre + hf_name + ".weight")
            p[f"l{i}_{b_key}"] = jnp.asarray(raw[pre + hf_name + ".bias"])
        p[f"l{i}_attn_ln_g"] = jnp.asarray(
            raw[pre + "attention.output.LayerNorm.weight"]
        )
        p[f"l{i}_attn_ln_b"] = jnp.asarray(
            raw[pre + "attention.output.LayerNorm.bias"]
        )
        p[f"l{i}_mlp_ln_g"] = jnp.asarray(raw[pre + "output.LayerNorm.weight"])
        p[f"l{i}_mlp_ln_b"] = jnp.asarray(raw[pre + "output.LayerNorm.bias"])
    return p
