"""A decoder stack of TWO mixer kinds — ``DecoderConfig.block ==
"sparse_linear"``: decayed linear attention (Lightning Attention-2,
arXiv:2401.04658) in most layers, block-sparse softmax attention
(InfLLM-V2, arXiv:2509.24663) in the rest, one name per layer in
``cfg.mixer_types``, chosen at trace time.

Same shape as ``models/decoder.py`` and ``models/latent.py``: a flat
parameter tree, one pure-functional trunk, and a ``mix`` callback that owns
what a layer keeps between steps.  Per layer ``l`` of ``L``, ``x`` the
residual stream, ``r = scale_depth / sqrt(L)``:

    y = rmsnorm(x)
    q, k, v = y Wq, y Wk, y Wv;  q, k = rmsnorm_head(q), rmsnorm_head(k)
    linear:  q, k = rope(q), rope(k)           32 heads = 32 kv heads
             S_t = lambda_h S_{t-1} + k_t^T v_t     [d, d] float32 A LANE
             o_t = (q_t / sqrt(d)) S_t;  o = rmsnorm_head(o)
    sparse:  no RoPE; GQA softmax attention over K / V rows in the cache —
             every row while the sequence holds fewer than
             ``sparse_dense_len`` tokens, else the rows of the
             ``sparse_topk`` blocks the row selects (ops/attention.py)
    x = x + r * (o * sigmoid(y Wg)) Wo
    x = x + r * swiglu(rmsnorm(x))

and ``h_0 = scale_emb * E[ids]``, logits ``= head(rmsnorm(h)) /
(hidden_dim / dim_model_base)``.  ``lambda_h = exp(-s_h)`` with the
family's slopes (:func:`decay_slopes`).

What a layer keeps: a sparse layer K and V rows a token and one
mean-pooled key per ``sparse_kernel_stride`` tokens; a linear layer NO row
— its whole past is the state, held per lane beside the paged rows
(``engines/paged.py``).  The trunk hands back the blocks each sparse layer
and kv head took (the selection record; benchmark/README.md "A block that
routes").
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from docqa_tpu.config import DecoderConfig
from docqa_tpu.ops.norms import rms_norm
from docqa_tpu.ops.rope import apply_rope, rope_angles
from docqa_tpu.ops.scopes import scope

Params = Dict[str, jax.Array]

HYBRID_BLOCK = "sparse_linear"
SPARSE, LINEAR = "sparse", "linear"
# prefill rows one MLP tile holds: the gate / up activations of a longer
# dispatch are never whole (38k rows x 16384 would be 1.2 GB each)
MLP_TILE_ROWS = 2048


def is_hybrid(cfg: DecoderConfig) -> bool:
    return cfg.block == HYBRID_BLOCK


def sparse_layers(cfg: DecoderConfig) -> Tuple[int, ...]:
    """Indices of the layers that keep rows in the cache and select."""
    if not is_hybrid(cfg):
        return ()
    return tuple(i for i, m in enumerate(cfg.mixer_types) if m == SPARSE)


def linear_layers(cfg: DecoderConfig) -> Tuple[int, ...]:
    """Indices of the layers whose past is a state a lane."""
    if not is_hybrid(cfg):
        return ()
    return tuple(i for i, m in enumerate(cfg.mixer_types) if m == LINEAR)


def mixer_geometry(cfg: DecoderConfig, kind: str) -> Tuple[int, int, int]:
    """(query heads, kv heads, head width) of one mixer kind."""
    if kind == LINEAR:
        return cfg.linear_heads, cfg.linear_heads, cfg.linear_head_dim
    return cfg.num_heads, cfg.num_kv_heads, cfg.head_dim


def lane_state_shape(cfg: DecoderConfig) -> Tuple[int, int, int]:
    """What ONE lane holds per linear layer: [heads, d, d], float32."""
    return (cfg.linear_heads, cfg.linear_head_dim, cfg.linear_head_dim)


def lane_state_bytes(cfg: DecoderConfig) -> int:
    """Bytes of one lane's state across every linear layer."""
    return len(linear_layers(cfg)) * 4 * math.prod(lane_state_shape(cfg))


def residual_scale(cfg: DecoderConfig) -> float:
    if not cfg.scale_depth:
        return 1.0
    return cfg.scale_depth / math.sqrt(cfg.num_layers)


def logit_scale(cfg: DecoderConfig) -> float:
    if not cfg.dim_model_base:
        return 1.0
    return cfg.dim_model_base / cfg.hidden_dim


def decay_slopes(cfg: DecoderConfig, layer: int):
    """``s_h`` [heads] float32 of one linear layer: ``2^(-8 (h+1) / H)``
    scaled by ``1 - l / (L - 1) + 1e-5`` (the family's convention)."""
    heads = cfg.linear_heads
    base = 2.0 ** (-8.0 * (jnp.arange(heads, dtype=jnp.float32) + 1) / heads)
    return base * (1.0 - layer / max(cfg.num_layers - 1, 1) + 1e-5)


def check_hybrid_config(cfg: DecoderConfig) -> None:
    """Refuse, by field, a configuration this block cannot run."""
    problems = []
    if len(cfg.mixer_types) != cfg.num_layers:
        problems.append("len(mixer_types) != num_layers")
    strange = sorted(set(cfg.mixer_types) - {SPARSE, LINEAR})
    if strange:
        problems.append(f"mixer_types names {strange}")
    if LINEAR in cfg.mixer_types and min(
            cfg.linear_heads, cfg.linear_head_dim) <= 0:
        problems.append("linear_heads / linear_head_dim unset")
    if cfg.linear_head_dim % 2:
        problems.append("linear_head_dim is odd")
    if cfg.sliding_window is not None:
        problems.append("sliding_window (the sparse mixer has its own)")
    if cfg.quantize_weights and cfg.quant_bits != 8:
        problems.append("quant_bits (this block serves int8 or float)")
    ks, st, bs = (cfg.sparse_kernel_size, cfg.sparse_kernel_stride,
                  cfg.sparse_block_size)
    if min(ks, st, bs, cfg.sparse_topk, cfg.sparse_dense_len) <= 0:
        problems.append("a sparse_* size is not positive")
    elif ks % st or bs % st or ks > bs:
        problems.append(
            "sparse_kernel_size and sparse_block_size are no multiples of "
            "sparse_kernel_stride, or a window is longer than a block")
    if problems:
        raise ValueError(
            f'DecoderConfig(block="{HYBRID_BLOCK}"): ' + "; ".join(problems)
        )


def hybrid_param_schema(cfg: DecoderConfig):
    """``(name, kind, shape, fan_in)`` of the block's tree, in the order of
    ``models/decoder.decoder_param_schema`` (which yields this for the
    block)."""
    check_hybrid_config(cfg)
    h = cfg.hidden_dim
    yield ("tok_emb", "normal", (cfg.vocab_size, h), h)
    yield ("final_norm_g", "ones", (h,), None)
    yield ("lm_head", "normal", (h, cfg.vocab_size), h)
    for i, kind in enumerate(cfg.mixer_types):
        p = f"l{i}_"
        heads, kv_heads, d = mixer_geometry(cfg, kind)
        yield (p + "attn_norm_g", "ones", (h,), None)
        yield (p + "wq", "normal", (h, heads * d), h)
        yield (p + "wk", "normal", (h, kv_heads * d), h)
        yield (p + "wv", "normal", (h, kv_heads * d), h)
        yield (p + "q_norm_g", "ones", (d,), None)
        yield (p + "k_norm_g", "ones", (d,), None)
        if kind == LINEAR:
            yield (p + "o_norm_g", "ones", (heads * d,), None)
        yield (p + "w_ogate", "normal", (h, heads * d), h)
        yield (p + "wo", "normal", (heads * d, h), heads * d)
        yield (p + "mlp_norm_g", "ones", (h,), None)
        yield (p + "w_gate", "normal", (h, cfg.mlp_dim), h)
        yield (p + "w_up", "normal", (h, cfg.mlp_dim), h)
        yield (p + "w_down", "normal", (cfg.mlp_dim, h), cfg.mlp_dim)


def _qmatmul(x, params: Params, name: str, dtype):
    """``models/decoder._qmatmul`` (float, int8 or int4 weights by what the
    tree holds).  Imported at the call: that module imports this one for
    the block's schema."""
    from docqa_tpu.models.decoder import _qmatmul as matmul

    return matmul(x, params, name, dtype)


def _swiglu_tiled(y, params: Params, p: str, dtype):
    """SwiGLU over ``y`` [b, s, h]; a long packed dispatch (b == 1) goes
    ``MLP_TILE_ROWS`` rows at a time."""
    def mlp(rows):
        gate = _qmatmul(rows, params, p + "w_gate", dtype)
        up = _qmatmul(rows, params, p + "w_up", dtype)
        act = jax.nn.silu(gate.astype(jnp.float32)).astype(dtype) * up
        return _qmatmul(act, params, p + "w_down", dtype)

    b, s, h = y.shape
    if b != 1 or s <= 2 * MLP_TILE_ROWS:
        return mlp(y)
    tiles = -(-s // MLP_TILE_ROWS)
    padded = jnp.pad(y[0], ((0, tiles * MLP_TILE_ROWS - s), (0, 0)))
    out = jax.lax.map(mlp, padded.reshape(tiles, MLP_TILE_ROWS, h))
    return out.reshape(tiles * MLP_TILE_ROWS, h)[None, :s]


def _residual(x, r: float, branch):
    """``x + r * branch``, summed in float32 and rounded once."""
    f32 = jnp.float32
    return (x.astype(f32) + r * branch.astype(f32)).astype(x.dtype)


def hybrid_layer_stack(params: Params, cfg: DecoderConfig, ids, positions,
                       rope_len: int, mix):
    """The block's trunk, as ``decoder_layer_stack`` is the GQA block's.

    ``mix(i, kind, q [b, s, heads, d], k, v [b, s, kv heads, d]) ->
    (out [b, s, heads, d], taken)`` owns the cache and the lane state:
    a sparse layer writes its rows and attends (``taken``: the blocks it
    took, int32 [kv heads, b, s, topk]), a linear layer advances its
    state (``taken`` None).

    Returns (hidden states [b, s, hidden] before the final norm, the
    selection record int32 [sparse layers x kv heads, b, s, topk])."""
    b, s = ids.shape
    dtype = jnp.dtype(cfg.dtype)
    r = residual_scale(cfg)
    eps = cfg.norm_eps
    cos = sin = None
    if LINEAR in cfg.mixer_types:
        with scope("proj"):
            cos, sin = rope_angles(
                cfg.linear_head_dim, rope_len, cfg.rope_theta)
    with scope("embed"):
        x = (params["tok_emb"][ids].astype(jnp.float32)
             * cfg.scale_emb).astype(dtype)
    record = []
    for i, kind in enumerate(cfg.mixer_types):
        p = f"l{i}_"
        heads, kv_heads, d = mixer_geometry(cfg, kind)
        with scope("proj"):
            y = rms_norm(x, params[p + "attn_norm_g"], eps)
            q = _qmatmul(y, params, p + "wq", dtype).reshape(b, s, heads, d)
            k = _qmatmul(y, params, p + "wk", dtype).reshape(
                b, s, kv_heads, d)
            v = _qmatmul(y, params, p + "wv", dtype).reshape(
                b, s, kv_heads, d)
            q = rms_norm(q, params[p + "q_norm_g"], eps)
            k = rms_norm(k, params[p + "k_norm_g"], eps)
            if kind == LINEAR:
                q = apply_rope(q, cos, sin, positions)
                k = apply_rope(k, cos, sin, positions)
        out, taken = mix(i, kind, q, k, v)
        if kind != LINEAR:
            record.append(taken)
        with scope("proj"):
            if kind == LINEAR:
                out = rms_norm(
                    out, params[p + "o_norm_g"].reshape(heads, d), eps)
            gate = jax.nn.sigmoid(
                _qmatmul(y, params, p + "w_ogate", dtype).astype(
                    jnp.float32))
            a = (out.reshape(b, s, heads * d).astype(jnp.float32)
                 * gate).astype(dtype)
            x = _residual(x, r, _qmatmul(a, params, p + "wo", dtype))
        with scope("mlp"):
            y = rms_norm(x, params[p + "mlp_norm_g"], eps)
            x = _residual(x, r, _swiglu_tiled(y, params, p, dtype))
        # the stream is rounded HERE: without the barrier XLA carries it
        # in excess precision and re-sums every earlier layer's branch
        # where it needs it, which keeps them all alive (3.4 GB at 9.7k
        # rows x 32 layers)
        x = jax.lax.optimization_barrier(x)
    return x, (jnp.concatenate(record) if record else None)


def hybrid_head(params: Params, cfg: DecoderConfig, x):
    """``decoder_head`` with the block's logit scale."""
    from docqa_tpu.models.decoder import decoder_head

    with scope("head"):
        return decoder_head(params, cfg, x) * logit_scale(cfg)
