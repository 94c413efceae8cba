"""A decoder stack of several MIXER KINDS — ``DecoderConfig.block ==
"sparse_linear"``: one name per layer in ``cfg.mixer_types``, chosen at
trace time.  Six kinds, one trunk:

* ``linear`` — decayed linear attention (Lightning Attention-2,
  arXiv:2401.04658): RoPE, a [d, d] float32 state a head A LANE;
* ``sparse`` — block-sparse softmax attention (InfLLM-V2,
  arXiv:2509.24663): no RoPE, K / V rows and compressed keys in the cache;
* ``attention`` — plain causal GQA / MQA softmax attention over every row:
  no RoPE, K / V rows in the cache, read at decode by the paged kernel;
* ``window`` — causal GQA softmax attention over the last
  ``sliding_window`` rows: RoPE, K / V rows in a RING of pages a lane (the
  window plus one page, whatever the lane's length), read at decode by the
  paged kernel from the first page the window can still see;
* ``mamba`` — a Mamba-1 state-space mixer (arXiv:2312.00752) with its own
  projections; a lane keeps its last conv inputs and one state;
* ``retention`` — gated power retention of degree 2 (arXiv:2507.04239):
  RoPE, GQA, weights that are SQUARED scores under a gate computed from
  the token, normalised by their sum; a lane keeps one float32 state a kv
  head, d + 1 by d (d + 1) / 2 (129 x 8,256 numbers at d = 128), and no
  row.

Same shape as ``models/decoder.py`` and ``models/latent.py``: a flat
parameter tree, one pure-functional trunk, and a ``mix`` callback that owns
what a layer keeps between steps.  What differs by kind is ONE entry of
:data:`MIXERS`: the layer's parameter schema, what a token and what a lane
keep, and the projections around the callback (the step itself is the
engine's: ``engines/paged.py`` holds one handler a kind and forward).  Per
layer ``l`` of ``L``, ``x`` the residual stream, ``r = scale_depth /
sqrt(L)`` (1 where ``scale_depth`` is 0):

    y = rmsnorm(x)
    attention kinds:
        q, k, v = y Wq, y Wk, y Wv
        q, k = rmsnorm_head(q), rmsnorm_head(k)          (``qk_norm``)
        linear:    q, k = rope(q), rope(k)      32 heads = 32 kv heads
                   S_t = lambda_h S_{t-1} + k_t^T v_t     [d, d] float32
                   o_t = (q_t / sqrt(d)) S_t
                   o = rmsnorm_head(o)             (``use_output_norm``)
        sparse:    softmax attention over K / V rows in the cache — every
                   row while the sequence holds fewer than
                   ``sparse_dense_len`` tokens, else the rows of the
                   ``sparse_topk`` blocks the row selects
        attention: causal softmax attention over every row
        window:    q, k = rope(q), rope(k); causal softmax attention over
                   the rows j > t - ``sliding_window``
        retention: q, k = rope(q), rope(k);  g(h) the kv head of head h
                   gamma_t = log sigmoid(y_t W_decay)    [kv heads] float32
                   S_t = e^gamma_t S_{t-1} + phi(k_t) [v_t, 1]^T / d
                   o_t^h = phi(q_t^h) S_t[:, :d] / (phi(q_t^h) S_t[:, d] + eps)
                   phi(x): the products x_a x_b, a <= b — phi(q) . phi(k) =
                   (q . k)^2 (``ops/attention.power_features``)
        branch = (o * sigmoid(y Wg)) Wo   (``use_output_gate``; else o Wo)
    mamba (``inner = ssm_expand * hidden``):
        [u, z] = y W_in
        c_t = silu(b_conv + sum_j w_conv[j] * u_{t-K+1+j})   depthwise
        [dt, B, C] = c W_x;  each RMS-normed
        D_t = softplus(dt W_dt + b_dt)
        h_t = exp(D_t (x) A) h_{t-1} + (D_t c_t) (x) B_t,  A = -exp(A_log)
        g_t = h_t C_t + D c_t                  [state, inner] float32
        branch = (g * silu(z)) W_out
    x = x + r * branch                  (``sandwich_norm``: rmsnorm(branch))
    x = x + r * ff(rmsnorm(x))          (``sandwich_norm``: rmsnorm(ff(..)))

``ff`` is one dense SwiGLU of ``mlp_dim`` — in every layer, or, where
``num_experts`` > 1, in the first ``first_dense_layers`` alone: the later
layers ROUTE (``models/routed.py``: the router's scores, the held experts'
grouped sum, the shared experts), a long packed dispatch a tile of rows
at a time.  ``h_0 = scale_emb * E[ids]``, logits ``= head(rmsnorm(h)) /
(hidden_dim / dim_model_base)`` (unscaled where ``dim_model_base`` is 0;
against ``E`` itself under ``tie_embeddings``).  ``lambda_h = exp(-s_h)``
with the family's slopes (:func:`decay_slopes`).

The trunk hands back the blocks each sparse layer and kv head took (the
selection record; benchmark/README.md "A block that routes") — in a stack
that ROUTES the expert ids each routed layer took instead (the routing
record; one that both routes and selects is refused by field) — ``None``
for a stack in which no layer does either.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from docqa_tpu.config import DecoderConfig
from docqa_tpu.models.routed import (
    MOE_PREFILL_SUMS,
    MOE_SUMS,
    experts_held,
    moe_chunk_counts,
    moe_prefill_sums,
    moe_step_sums,
    routed_fused_counts,
    routed_layers,
    routed_mlp,
    routed_param_schema,
    routing_problems,
)
from docqa_tpu.models.serving import BlockServing
from docqa_tpu.ops.attention import (
    PAGED_BLOCK_ROWS,
    paged_kernel_supported,
    power_feature_count,
)
from docqa_tpu.ops.norms import rms_norm
from docqa_tpu.ops.rope import apply_rope, rope_angles
from docqa_tpu.ops.scopes import layer_kind, scope

Params = Dict[str, jax.Array]

HYBRID_BLOCK = "sparse_linear"
SPARSE, LINEAR, ATTENTION, MAMBA = "sparse", "linear", "attention", "mamba"
WINDOW, RETENTION = "window", "retention"
# the kinds whose q and k are rotated (each at its own head width)
ROTATED = (LINEAR, WINDOW, RETENTION)
# the pool that maps a lane's first pool row to its state entry
# (``engines/paged._init_hybrid_pools``)
STATE_SLOT = "state_slot"
# the pool that maps a lane's entry to the pages of its ring, [lanes,
# ring_pages] int32 (``engines/paged._init_hybrid_pools``)
WINDOW_PAGES = "window_pages"
# prefill rows one MLP tile holds: the gate / up activations of a longer
# dispatch are never whole (38k rows x 16384 would be 1.2 GB each)
MLP_TILE_ROWS = 2048


def is_hybrid(cfg: DecoderConfig) -> bool:
    return cfg.block == HYBRID_BLOCK


def layers_of(cfg: DecoderConfig, *kinds: str) -> Tuple[int, ...]:
    """Indices of the layers of the named mixer kinds, in order."""
    if not is_hybrid(cfg):
        return ()
    return tuple(i for i, m in enumerate(cfg.mixer_types) if m in kinds)


def sparse_layers(cfg: DecoderConfig) -> Tuple[int, ...]:
    """Indices of the layers that keep rows in the cache and select."""
    return layers_of(cfg, SPARSE)


def window_layers(cfg: DecoderConfig) -> Tuple[int, ...]:
    """Indices of the layers that keep a ring of pages a lane."""
    return layers_of(cfg, WINDOW)


def ring_pages(cfg: DecoderConfig, block_size: int) -> int:
    """Pages of ``block_size`` rows one lane's ring holds in every window
    layer: position ``p`` lives in page ``(p // block_size) % ring_pages``
    of the lane's ring, so the page being written and the
    ``ring_pages - 1`` before it are whole — every row ``j > t -
    sliding_window`` of a step at ``t``, wherever ``t`` falls in its page.
    The window plus at most one page, whatever the lane's length."""
    return -(-(cfg.sliding_window - 1) // block_size) + 1


def mamba_layers(cfg: DecoderConfig) -> Tuple[int, ...]:
    """Indices of the state-space layers (a conv window and a state)."""
    return layers_of(cfg, MAMBA)


def retention_layers(cfg: DecoderConfig) -> Tuple[int, ...]:
    """Indices of the power-retention layers (one state a kv head)."""
    return layers_of(cfg, RETENTION)


def mixer_geometry(cfg: DecoderConfig, kind: str) -> Tuple[int, int, int]:
    """(query heads, kv heads, head width) of one attention kind — a
    state-keeping kind answers for itself: ``linear`` one state a head of
    its own count and width, ``retention`` one a KV head of the trunk's
    (``num_heads // num_kv_heads`` query heads read it)."""
    if kind == LINEAR:
        return cfg.linear_heads, cfg.linear_heads, cfg.linear_head_dim
    return cfg.num_heads, cfg.num_kv_heads, cfg.head_dim


def ssm_inner(cfg: DecoderConfig) -> int:
    """Channels of the state-space mixer: ``ssm_expand * hidden_dim``."""
    return cfg.ssm_expand * cfg.hidden_dim


def lane_state_shape(cfg: DecoderConfig) -> Tuple[int, int, int]:
    """What ONE lane holds per linear layer: [heads, d, d], float32."""
    return (cfg.linear_heads, cfg.linear_head_dim, cfg.linear_head_dim)


def retention_state_shape(cfg: DecoderConfig) -> Tuple[int, int, int]:
    """What ONE lane holds per retention layer: [d + 1, kv heads,
    d (d + 1) / 2] float32 — the value channels and, last, the running
    sum of weights, by the kv heads, by the degree-2 features of a key.
    The order the chip rests such an array in whatever its shape says:
    the kv heads on the sublanes and the features on the lanes are whole
    (8, 128) tiles (8,256 lanes pad to 8,320; a minor axis of 129 would
    pad to 256 and a second-minor one to 136)."""
    d = cfg.head_dim
    return (d + 1, cfg.num_kv_heads, power_feature_count(d))


def lane_state_entries(cfg: DecoderConfig) -> Dict[str, Tuple[tuple, str]]:
    """``{pool name: (shape of one lane's entry, element type)}`` over
    every state-keeping layer, in layer order."""
    out: Dict[str, Tuple[tuple, str]] = {}
    for i, kind in enumerate(cfg.mixer_types if is_hybrid(cfg) else ()):
        for prefix, entry in MIXERS[kind].lane_state(cfg).items():
            out[f"{prefix}{i}"] = entry
    return out


def lane_state_bytes(cfg: DecoderConfig) -> int:
    """Bytes of one lane's state across every state-keeping layer."""
    return sum(
        math.prod(shape) * jnp.dtype(dtype).itemsize
        for shape, dtype in lane_state_entries(cfg).values())


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


def ssm_constants(params: "Params", cfg: DecoderConfig, layer: int):
    """What a state-space layer's conv and scan read beside their inputs:
    (conv weight [taps, inner], conv bias [inner] or None, ``A = -exp(A_log)``
    [state, inner] float32, ``D`` [inner])."""
    p = f"l{layer}_"
    return (
        params[p + "conv_w"],
        params[p + "conv_b"] if cfg.ssm_conv_bias else None,
        -jnp.exp(params[p + "a_log"].astype(jnp.float32)),
        params[p + "d_skip"],
    )


def check_hybrid_config(cfg: DecoderConfig) -> None:
    """Refuse, by field, a configuration this block cannot run."""
    problems = []
    if len(cfg.mixer_types) != cfg.num_layers:
        problems.append("len(mixer_types) != num_layers")
    strange = sorted(set(cfg.mixer_types) - set(MIXERS))
    if strange:
        problems.append(f"mixer_types names {strange}")
    if LINEAR in cfg.mixer_types and min(
            cfg.linear_heads, cfg.linear_head_dim) <= 0:
        problems.append("linear_heads / linear_head_dim unset")
    if cfg.linear_head_dim % 2:
        problems.append("linear_head_dim is odd")
    if WINDOW in cfg.mixer_types:
        if not cfg.sliding_window or cfg.sliding_window < 2:
            problems.append(
                "sliding_window (a stack with a window layer sets it, to "
                "2 or more)")
        if cfg.head_dim % 2:
            problems.append("head_dim is odd (a window layer is rotated)")
    elif cfg.sliding_window is not None:
        problems.append(
            "sliding_window (a window layer reads it; the sparse mixer has "
            "its own)")
    if RETENTION in cfg.mixer_types:
        if cfg.head_dim % 2:
            problems.append(
                "head_dim is odd (a retention layer is rotated, and its "
                "features pair the channels by their distance)")
        if cfg.num_kv_heads <= 0 or cfg.num_heads % cfg.num_kv_heads:
            problems.append(
                "num_heads is no multiple of num_kv_heads (a retention "
                "layer keeps one state a kv head)")
    if cfg.quantize_weights and cfg.quant_bits != 8:
        problems.append("quant_bits (this block serves int8 or float)")
    if cfg.num_experts > 1:
        problems += routing_problems(cfg)
        if SPARSE in cfg.mixer_types:
            problems.append(
                "num_experts with a sparse layer (the forwards hand back "
                "ONE record: the routing or the selection)")
        if cfg.quantize_weights:
            problems.append(
                "quantize_weights (routed layers serve float weights)")
        if not 0 <= cfg.first_dense_layers <= cfg.num_layers:
            problems.append("first_dense_layers outside 0..num_layers")
    if MAMBA in cfg.mixer_types and min(
            cfg.ssm_state_dim, cfg.ssm_dt_rank, cfg.ssm_expand) <= 0:
        problems.append("ssm_state_dim / ssm_dt_rank / ssm_expand unset")
    if MAMBA in cfg.mixer_types and cfg.ssm_conv_width < 2:
        problems.append("ssm_conv_width under 2 (a lane keeps width - 1 rows)")
    ks, st, bs = (cfg.sparse_kernel_size, cfg.sparse_kernel_stride,
                  cfg.sparse_block_size)
    # where no layer selects the sparse_* sizes are read by nobody
    selects = SPARSE in cfg.mixer_types
    if selects and min(
            ks, st, bs, cfg.sparse_topk, cfg.sparse_dense_len) <= 0:
        problems.append("a sparse_* size is not positive")
    elif selects and (ks % st or bs % st or ks > bs):
        problems.append(
            "sparse_kernel_size and sparse_block_size are no multiples of "
            "sparse_kernel_stride, or a window is longer than a block")
    if problems:
        raise ValueError(
            f'DecoderConfig(block="{HYBRID_BLOCK}"): ' + "; ".join(problems)
        )


def _qmatmul(x, params: Params, name: str, dtype):
    """``models/decoder._qmatmul`` (float, int8 or int4 weights by what the
    tree holds).  Imported at the call: that module imports this one for
    the block's schema."""
    from docqa_tpu.models.decoder import _qmatmul as matmul

    return matmul(x, params, name, dtype)


# ---- the mixer kinds: one entry each ----------------------------------------


def _attention_schema(kind: str):
    def schema(cfg: DecoderConfig):
        h = cfg.hidden_dim
        heads, kv_heads, d = mixer_geometry(cfg, kind)
        yield ("wq", "normal", (h, heads * d), h)
        yield ("wk", "normal", (h, kv_heads * d), h)
        yield ("wv", "normal", (h, kv_heads * d), h)
        if cfg.qk_norm:
            yield ("q_norm_g", "ones", (d,), None)
            yield ("k_norm_g", "ones", (d,), None)
        if kind == LINEAR and cfg.use_output_norm:
            yield ("o_norm_g", "ones", (heads * d,), None)
        if kind == RETENTION:
            # a decay a token and kv head; 8 columns: never quantized
            # (``models/quant.should_quantize`` goes by name)
            yield ("w_decay", "normal", (h, kv_heads), h)
        if cfg.use_output_gate:
            yield ("w_ogate", "normal", (h, heads * d), h)
        yield ("wo", "normal", (heads * d, h), heads * d)

    return schema


def _attention_branch(params: Params, cfg: DecoderConfig, i: int, kind: str,
                      y, rope, mix):
    """q, k, v with what the file says of their norms (and RoPE for the
    ``ROTATED`` kinds), ``mix(i, kind, q, k, v) -> (out, taken)`` — a
    RETENTION layer's also takes ``log sigmoid`` of its decay projection,
    float32 [b, s, kv heads] —, then the output norm, gate and
    projection."""
    p = f"l{i}_"
    b, s, _ = y.shape
    dtype = jnp.dtype(cfg.dtype)
    eps = cfg.norm_eps
    heads, kv_heads, d = mixer_geometry(cfg, kind)
    with scope("proj"):
        q = _qmatmul(y, params, p + "wq", dtype).reshape(b, s, heads, d)
        k = _qmatmul(y, params, p + "wk", dtype).reshape(b, s, kv_heads, d)
        v = _qmatmul(y, params, p + "wv", dtype).reshape(b, s, kv_heads, d)
        if cfg.qk_norm:
            q = rms_norm(q, params[p + "q_norm_g"], eps)
            k = rms_norm(k, params[p + "k_norm_g"], eps)
        if kind in ROTATED:
            cos, sin, positions = rope[kind]
            q = apply_rope(q, cos, sin, positions)
            k = apply_rope(k, cos, sin, positions)
        gates = ()
        if kind == RETENTION:
            gates = (jax.nn.log_sigmoid(
                (y @ params[p + "w_decay"].astype(dtype)).astype(
                    jnp.float32)),)
    out, taken = mix(i, kind, q, k, v, *gates)
    with scope("proj"):
        if kind == LINEAR and cfg.use_output_norm:
            out = rms_norm(
                out, params[p + "o_norm_g"].reshape(heads, d), eps)
        if cfg.use_output_gate:
            gate = jax.nn.sigmoid(
                _qmatmul(y, params, p + "w_ogate", dtype).astype(
                    jnp.float32))
            a = (out.reshape(b, s, heads * d).astype(jnp.float32)
                 * gate).astype(dtype)
        else:
            a = out.reshape(b, s, heads * d)
        return _qmatmul(a, params, p + "wo", dtype), taken


def _kv_rows(cfg: DecoderConfig):
    return {"k": (cfg.num_kv_heads, cfg.head_dim),
            "v": (cfg.num_kv_heads, cfg.head_dim)}


def _mamba_schema(cfg: DecoderConfig):
    h, inner = cfg.hidden_dim, ssm_inner(cfg)
    n, rank, taps = cfg.ssm_state_dim, cfg.ssm_dt_rank, cfg.ssm_conv_width
    yield ("w_in", "normal", (h, 2 * inner), h)
    if cfg.ssm_proj_bias:
        yield ("b_in", "normal", (2 * inner,), 1)
    yield ("conv_w", "normal", (taps, inner), taps)
    if cfg.ssm_conv_bias:
        yield ("conv_b", "normal", (inner,), taps)
    yield ("w_x", "normal", (inner, rank + 2 * n), inner)
    yield ("dt_norm_g", "ones", (rank,), None)
    yield ("b_norm_g", "ones", (n,), None)
    yield ("c_norm_g", "ones", (n,), None)
    yield ("w_dt", "normal", (rank, inner), rank)
    yield ("b_dt", "normal", (inner,), 1)
    yield ("a_log", "ones", (n, inner), None)
    yield ("d_skip", "ones", (inner,), None)
    yield ("w_out", "normal", (inner, h), inner)
    if cfg.ssm_proj_bias:
        yield ("b_out", "normal", (h,), 1)


def _mamba_branch(params: Params, cfg: DecoderConfig, i: int, kind: str,
                  y, rope, mix):
    """``[u, z] = y W_in``; ``mix(i, kind, u, project) -> (g, None)`` owns
    the conv (its window) and the scan (its state) and calls
    ``project(c) -> (delta float32, B, C)`` between them; then the gate and
    ``W_out``."""
    p = f"l{i}_"
    dtype = jnp.dtype(cfg.dtype)
    eps, f32 = cfg.norm_eps, jnp.float32
    inner, n, rank = ssm_inner(cfg), cfg.ssm_state_dim, cfg.ssm_dt_rank
    with scope("proj"):
        uz = _qmatmul(y, params, p + "w_in", dtype)
        if cfg.ssm_proj_bias:
            uz = uz + params[p + "b_in"].astype(dtype)
        u, z = uz[..., :inner], uz[..., inner:]

    def project(c):
        with scope("proj"):
            dbc = _qmatmul(c, params, p + "w_x", dtype)
            dt = rms_norm(dbc[..., :rank], params[p + "dt_norm_g"], eps)
            bm = rms_norm(
                dbc[..., rank:rank + n], params[p + "b_norm_g"], eps)
            cm = rms_norm(dbc[..., rank + n:], params[p + "c_norm_g"], eps)
            delta = jax.nn.softplus(
                _qmatmul(dt, params, p + "w_dt", dtype).astype(f32)
                + params[p + "b_dt"].astype(f32))
            return delta, bm, cm

    g, taken = mix(i, kind, u, project)
    with scope("proj"):
        a = (g.astype(f32) * jax.nn.silu(z.astype(f32))).astype(dtype)
        out = _qmatmul(a, params, p + "w_out", dtype)
        if cfg.ssm_proj_bias:
            out = out + params[p + "b_out"].astype(dtype)
        return out, taken


@dataclasses.dataclass(frozen=True)
class Mixer:
    """What the stack asks of one mixer kind.  ``schema(cfg)`` yields the
    layer's mixer parameters ``(short name, init kind, shape, fan_in)``;
    ``rows(cfg)`` is what a TOKEN leaves in the cache, ``{pool prefix:
    (heads, width)}``; ``lane_state(cfg)`` what a LANE keeps whatever its
    length, ``{pool prefix: (shape, element type)}``; ``branch(params,
    cfg, i, kind, y, rope, mix) -> (the residual branch [b, s, hidden],
    the blocks taken or None)`` the projections around the engine's step."""

    schema: Callable
    rows: Callable
    lane_state: Callable
    branch: Callable


def _nothing(cfg: DecoderConfig) -> dict:
    return {}


MIXERS: Dict[str, Mixer] = {
    SPARSE: Mixer(_attention_schema(SPARSE), _kv_rows, _nothing,
                  _attention_branch),
    ATTENTION: Mixer(_attention_schema(ATTENTION), _kv_rows, _nothing,
                     _attention_branch),
    # the same rows as ``attention``; how many of them a lane's pools hold
    # is :func:`ring_pages`'
    WINDOW: Mixer(_attention_schema(WINDOW), _kv_rows, _nothing,
                  _attention_branch),
    LINEAR: Mixer(
        _attention_schema(LINEAR), _nothing,
        lambda cfg: {"s": (lane_state_shape(cfg), "float32")},
        _attention_branch),
    # no row at all: a stack of these alone has pools of states and the
    # slot map only (``engines/paged._init_hybrid_pools``)
    RETENTION: Mixer(
        _attention_schema(RETENTION), _nothing,
        lambda cfg: {"s": (retention_state_shape(cfg), "float32")},
        _attention_branch),
    # the window holds conv INPUTS as the in-projection rounded them (the
    # activation type: nothing is lost); the state is float32
    MAMBA: Mixer(
        _mamba_schema, _nothing,
        lambda cfg: {
            "h": ((cfg.ssm_state_dim, ssm_inner(cfg)), "float32"),
            "u": ((cfg.ssm_conv_width - 1, ssm_inner(cfg)), cfg.dtype),
        },
        _mamba_branch),
}


def hybrid_param_schema(cfg: DecoderConfig):
    """``(name, kind, shape, fan_in)`` of the block's tree, in the order of
    ``models/decoder.decoder_param_schema`` (which yields this for the
    block).  No ``lm_head`` under ``tie_embeddings``."""
    check_hybrid_config(cfg)
    h = cfg.hidden_dim
    yield ("tok_emb", "normal", (cfg.vocab_size, h), h)
    yield ("final_norm_g", "ones", (h,), None)
    if not cfg.tie_embeddings:
        yield ("lm_head", "normal", (h, cfg.vocab_size), h)
    for i, kind in enumerate(cfg.mixer_types):
        p = f"l{i}_"
        yield (p + "attn_norm_g", "ones", (h,), None)
        for name, init, shape, fan_in in MIXERS[kind].schema(cfg):
            yield (p + name, init, shape, fan_in)
        if cfg.sandwich_norm:
            yield (p + "attn_post_norm_g", "ones", (h,), None)
        yield (p + "mlp_norm_g", "ones", (h,), None)
        if layer_routes(cfg, i):
            yield from routed_param_schema(cfg, p)
        else:
            yield (p + "w_gate", "normal", (h, cfg.mlp_dim), h)
            yield (p + "w_up", "normal", (h, cfg.mlp_dim), h)
            yield (p + "w_down", "normal", (cfg.mlp_dim, h), cfg.mlp_dim)
        if cfg.sandwich_norm:
            yield (p + "mlp_post_norm_g", "ones", (h,), None)


def layer_routes(cfg: DecoderConfig, i: int) -> bool:
    """Whether layer ``i``'s feed-forward routes."""
    return routed_layers(cfg) > 0 and i >= cfg.first_dense_layers


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


def _routed_tiled(y, params: Params, cfg: DecoderConfig, i: int,
                  grouped: bool):
    """A routed layer's feed-forward over ``y`` [b, s, h] -> (what it adds
    [b, s, h], the expert ids taken [b, s, k]).  Of a long packed dispatch
    (b == 1) a tile of ``MLP_TILE_ROWS`` rows routes, gathers its picks
    and runs its grouped products on its own: the gathered rows and the
    float32 products of ``rows x k`` picks exist a tile at a time (at
    37,888 rows x 8 they would be 1.2 and 2.5 GB a layer), and a tile
    streams the held experts once more."""
    def route(rows):
        return routed_mlp(rows, params, cfg, i, use_flash=grouped)

    b, s, h = y.shape
    if b != 1 or s <= 2 * MLP_TILE_ROWS:
        add, taken = route(y.reshape(b * s, h))
        return add.reshape(b, s, h), taken.reshape(b, s, -1)
    tiles = -(-s // MLP_TILE_ROWS)
    padded = jnp.pad(y[0], ((0, tiles * MLP_TILE_ROWS - s), (0, 0)))
    add, taken = jax.lax.map(route, padded.reshape(tiles, MLP_TILE_ROWS, h))
    return (add.reshape(-1, h)[None, :s],
            taken.reshape(tiles * MLP_TILE_ROWS, -1)[None, :s])


def _residual(x, r: float, branch):
    """``x + r * branch``, summed in float32 and rounded once."""
    f32 = jnp.float32
    return (x.astype(f32) + r * branch.astype(f32)).astype(x.dtype)


def hybrid_layer_stack(params: Params, cfg: DecoderConfig, ids, positions,
                       rope_len: int, mix, *, grouped: bool = False):
    """The block's trunk, as ``decoder_layer_stack`` is the GQA block's.

    ``mix(i, kind, ...)`` owns the cache and the lane state; what it is
    handed is the kind's affair (:data:`MIXERS`).  An attention kind:
    ``mix(i, kind, q [b, s, heads, d], k, v [b, s, kv heads, d]) -> (out
    [b, s, heads, d], taken)`` — a row-keeping layer writes its rows and
    attends (``taken``: the blocks a SPARSE layer took, int32 [kv heads,
    b, s, topk]; None from every other kind), a linear layer advances its
    state, and so does a retention layer, which is handed its log gates
    [b, s, kv heads] float32 after ``v``.  The state-space kind:
    ``mix(i, kind, u [b, s, inner], project) -> (g [b, s, inner],
    None)``.

    ``grouped``: the form of the routed layers' product
    (``models/decoder.kernel_forms``).

    Returns (hidden states [b, s, hidden] before the final norm, the
    selection record int32 [sparse layers x kv heads, b, s, topk] — of a
    stack that routes the routing record int32 [routed layers, b, s,
    experts_per_token]; None where no layer selects or routes)."""
    dtype = jnp.dtype(cfg.dtype)
    r = residual_scale(cfg)
    eps = cfg.norm_eps
    rope = {}
    for kind in ROTATED:
        if kind in cfg.mixer_types:
            with scope("proj"):
                cos, sin = rope_angles(
                    mixer_geometry(cfg, kind)[2], rope_len, cfg.rope_theta)
                rope[kind] = (cos, sin, positions)
    with scope("embed"):
        x = (params["tok_emb"][ids].astype(jnp.float32)
             * cfg.scale_emb).astype(dtype)
    record = []
    for i, kind in enumerate(cfg.mixer_types):
        p = f"l{i}_"
        # each half under its KIND, the phase scopes inside it: a trace
        # reads the step by (kind, phase, op)
        with layer_kind(kind):
            with scope("proj"):
                y = rms_norm(x, params[p + "attn_norm_g"], eps)
            branch, taken = MIXERS[kind].branch(
                params, cfg, i, kind, y, rope, mix)
            if kind == SPARSE:
                record.append(taken)
            with scope("proj"):
                if cfg.sandwich_norm:
                    branch = rms_norm(
                        branch, params[p + "attn_post_norm_g"], eps)
                x = _residual(x, r, branch)
        routes = layer_routes(cfg, i)
        with layer_kind("routed" if routes else "dense"):
            with scope("mlp"):
                y = rms_norm(x, params[p + "mlp_norm_g"], eps)
                if routes:
                    ff, taken = _routed_tiled(y, params, cfg, i, grouped)
                    record.append(taken)
                else:
                    ff = _swiglu_tiled(y, params, p, dtype)
                if cfg.sandwich_norm:
                    ff = rms_norm(ff, params[p + "mlp_post_norm_g"], eps)
                x = _residual(x, r, ff)
            # the stream is rounded HERE: without the barrier XLA carries
            # it in excess precision and re-sums every earlier layer's
            # branch where it needs it, which keeps them all alive (3.4 GB
            # at 9.7k rows x 32 layers)
            x = jax.lax.optimization_barrier(x)
    if not record:
        return x, None
    return x, (jnp.stack(record) if routed_layers(cfg)
               else jnp.concatenate(record))


def hybrid_head(params: Params, cfg: DecoderConfig, x):
    """``decoder_head`` with the block's logit scale; under
    ``tie_embeddings`` the logits are taken against ``tok_emb`` itself
    (contracted over its hidden axis: no transposed copy is kept)."""
    from docqa_tpu.models.decoder import decoder_head

    with scope("head"):
        if cfg.tie_embeddings:
            y = rms_norm(x, params["final_norm_g"], cfg.norm_eps)
            logits = jnp.einsum(
                "bsh,vh->bsv", y,
                params["tok_emb"].astype(jnp.dtype(cfg.dtype)),
            ).astype(jnp.float32)
        else:
            logits = decoder_head(params, cfg, x)
        return logits * logit_scale(cfg)


# ---- what the stack's surroundings ask of it (models/serving.py) -----------

# counters of a stack in which a layer SELECTS (:func:`sparse_step_sums` /
# :func:`hybrid_chunk_counts`; a stack in which none does carries no such
# row and counts its lane-steps on the host), over a chunk's steps and live
# lanes: blocks the sparse layers' queries READ (the blocks taken; every
# live block on a lane still under ``sparse_dense_len``), blocks live for
# them, lane-steps that ran dense, and lane-steps in all (each reads and
# writes the lane's state once: ``serve_state_bytes_rw`` is that times the
# state's bytes)
SPARSE_SUMS = (
    "serve_sparse_blocks_selected", "serve_sparse_blocks_live",
    "serve_sparse_dense_lane_steps", "serve_state_lane_steps",
)


def sparse_step_sums(cfg: DecoderConfig, record, lengths, active):
    """``SPARSE_SUMS`` of one decode step, int32, from the selection
    record [sparse layers x kv heads, S, 1, topk], the lanes' lengths
    BEFORE the step and the lanes live in it — summed on the device, as
    ``models/routed.moe_step_sums`` is."""
    took = record[:, :, 0, :] >= 0  # [decisions, S, topk]
    selected = took[0, :, 0]  # a lane that selected: first id >= 0
    live_blocks = record.shape[0] * (
        lengths // cfg.sparse_block_size + 1
    )
    read = jnp.where(selected, jnp.sum(took, axis=(0, 2)), live_blocks)
    return jnp.stack([
        jnp.sum(jnp.where(active, read, 0)),
        jnp.sum(jnp.where(active, live_blocks, 0)),
        jnp.sum(active & ~selected),
        jnp.sum(active),
    ]).astype(jnp.int32)


def hybrid_chunk_counts(cfg: DecoderConfig, *, lane_steps, row, kernels,
                        **_):
    """One fetched chunk's counters and samples: its ``SPARSE_SUMS`` row
    where a layer selects, else the lane-steps the host holds."""
    counts, samples = {}, {}
    if row is not None:
        # the blocks the sparse layers' queries read and, one sample a
        # chunk, the tokens a selecting query read per layer and kv head
        counts = dict(
            zip(SPARSE_SUMS, (int(v) for v in row[: len(SPARSE_SUMS)])))
        lane_steps = counts.pop("serve_state_lane_steps")
        dense = counts["serve_sparse_dense_lane_steps"]
        selecting = lane_steps - dense
        if selecting and not dense:
            decisions = len(sparse_layers(cfg)) * cfg.num_kv_heads
            samples["serve_sparse_selected_tokens"] = (
                counts["serve_sparse_blocks_selected"]
                * cfg.sparse_block_size / (selecting * decisions)
            )
    # a lane-step read and wrote every entry the lane's state-keeping
    # layers hold, once
    counts["serve_state_lane_steps"] = lane_steps
    counts["serve_state_bytes_rw"] = (
        2 * lane_state_bytes(cfg) * lane_steps)
    if kernels.sparse_paged:
        # over ``serve_decode_chunks``: 1.0 where every chunk's sparse
        # layers read the blocks taken as pages, absent elsewhere
        counts["serve_sparse_paged_chunks"] = 1
    if kernels.retention:
        # over ``serve_decode_chunks``: 1.0 where every chunk's retention
        # layers stepped in the one-pass kernel, absent elsewhere
        counts["serve_retention_fused_chunks"] = 1
    return counts, samples


def routed_chunk_counts(cfg: DecoderConfig, *, lane_steps, row, kernels,
                        n_lanes):
    """The same of a stack that ROUTES: its row holds the expert-choice
    sums (``models/routed.moe_chunk_counts``) and says, by the forms and
    the lanes a step holds, whether its routed layers stepped in the
    kernel; the lane-steps are the host's."""
    counts, samples = moe_chunk_counts(row=row)
    state, _ = hybrid_chunk_counts(
        cfg, lane_steps=lane_steps, row=None, kernels=kernels)
    fused = routed_fused_counts(cfg, kernels=kernels, n_lanes=n_lanes)
    return {**counts, **state, **fused}, samples


def hybrid_prefill_counts(cfg: DecoderConfig, *, lanes, tokens, dispatches,
                          kernels):
    """One admission round's counters: lane states started from zeros."""
    counts = {"serve_lane_state_resets": lanes}
    scans = len(layers_of(cfg, MAMBA, RETENTION))
    if scans:
        # prompt tokens x the layers whose chunked scan ran over them
        # (state-space, retention)
        counts["serve_scan_tokens"] = tokens * scans
    if kernels.scan:
        # over ``serve_prefill_dispatches``: 1.0 where every dispatch's
        # state-space layers scanned in the kernel, absent elsewhere
        counts["serve_scan_kernel_dispatches"] = dispatches
    return counts


def hybrid_prefill_attrs(cfg: DecoderConfig, n_ids: int, n_lanes: int):
    """What the stack adds to a request's ``serve_prefill`` span: the
    lanes whose state the round started from zeros; where a layer
    SELECTS, the rows of the prompt that selected (all of them once it
    holds ``sparse_dense_len`` tokens, none under it); where a layer
    SCANS (state-space, retention), the rows its scan ran over; where a
    layer keeps
    a WINDOW, the rows of the prompt its ring kept."""
    out = {"state_lanes": n_lanes}
    if window_layers(cfg):
        out["window_rows_kept"] = min(n_ids, cfg.sliding_window - 1)
    if sparse_layers(cfg):
        selects = n_ids >= cfg.sparse_dense_len
        out["sparse_rows"] = n_ids if selects else 0
    if layers_of(cfg, MAMBA, RETENTION):
        out["scan_rows"] = n_ids
    return out


def sparse_rows_read(cfg: DecoderConfig, lens, *, kernels, block_size: int,
                     table_rows: int):
    """(KV rows a chunk's steps fetched per cache entry, no counter of its
    own), from the length each lane's step attended (``lens`` [lanes,
    steps])."""
    # a sparse layer reads the rows of the blocks taken; in the XLA form
    # every table once a lane of the step is still under dense_len
    # (ops/attention.sparse_decode_attention)
    taken = cfg.sparse_topk * cfg.sparse_block_size
    under = lens < cfg.sparse_dense_len
    if kernels.sparse_paged:
        # one virtual lane a (lane, kv head), and a page carries every kv
        # head: a selecting lane's taken rows, the live pages of a lane
        # under dense_len, kv-heads times
        pages = -(-lens // block_size) * block_size
        return cfg.num_kv_heads * int(np.where(
            under, pages, np.minimum(taken, pages)).sum()), {}
    return int(np.where(
        under.any(axis=0), table_rows, lens.shape[0] * taken).sum()), {}


# counters of a stack with WINDOW layers, over a fetched chunk's steps and
# live lanes, PER WINDOW LAYER (:func:`window_rows_read`): the rows its
# decode read, the rows its pools hold for those lanes (a ring each), and
# the rows the lanes' positions would hold unwindowed
WINDOW_COUNTS = (
    "serve_window_kv_rows_read", "serve_window_kv_rows_held",
    "serve_window_kv_rows_live",
)


def window_rows_read(cfg: DecoderConfig, lens, *, kernels, block_size: int,
                     table_rows: int):
    """(KV rows a chunk's steps fetched per cache entry — the mean over
    the row-keeping layers, each by its kind —, ``WINDOW_COUNTS``), from
    the length each lane's step attended (``lens`` [lanes, steps]).  A
    global layer reads a lane's live pages; a window layer the pages from
    the kernel's first compute block that the window can still see
    (``ops/attention.PAGED_BLOCK_ROWS`` rows, a whole number of pages) to
    the lane's last — the XLA form (every CPU run) gathers every table's
    whole span in either kind."""
    n_window = len(window_layers(cfg))
    n_global = len(layers_of(cfg, ATTENTION))
    steps = lens.shape[1] if lens.size else 0
    pages = -(-lens // block_size) * block_size
    if kernels.paged:
        rows = max(PAGED_BLOCK_ROWS // block_size, 1) * block_size
        first = np.maximum(lens - cfg.sliding_window, 0) // rows * rows
        in_window, in_global = int((pages - first).sum()), int(pages.sum())
    else:
        in_window = in_global = steps * table_rows
    held = lens.shape[0] * steps * ring_pages(cfg, block_size) * block_size
    counts = dict(zip(WINDOW_COUNTS, (in_window, held, int(lens.sum()))))
    mean = (n_window * in_window + n_global * in_global) // max(
        n_window + n_global, 1)
    return mean, counts


def hybrid_param_pspecs(cfg: DecoderConfig, m: str) -> Dict[str, P]:
    """Megatron per layer (a routed layer's experts: below).  An attention
    kind (``window`` as ``attention``): q, the output gate and the
    MLP's gate / up column-parallel, ``wo`` and ``w_down`` row-parallel; a
    linear layer's k and v are as wide as its q and go column-parallel
    with it; the few kv heads of a sparse or a plain attention layer are
    replicated (1 or 2 heads do not divide over 4 or 8 devices), as are
    the per-head norm gains; a retention layer's k, v and decay too,
    beside a state pool that is whole on every device (its q heads divide;
    each device advances the whole state and reads it with its own).
    The state-space kind along its INNER
    channels: ``w_in`` column-parallel over its ``2 x inner`` columns
    (GSPMD re-lays the ``u`` and the ``z`` half along ``inner``), the
    conv's taps and bias, ``w_x``'s input, ``w_dt``'s output, ``b_dt``,
    ``A_log`` and ``D`` along that axis, ``w_out`` row-parallel; the three
    inner norms replicated.  The pools — rows (ONE kv head cannot be
    divided), compressed keys, lane states and windows — are replicated
    (:func:`hybrid_pool_pspecs`)."""
    specs: Dict[str, P] = {}
    for i, kind in enumerate(cfg.mixer_types):
        p = f"l{i}_"
        specs.update({
            p + "attn_norm_g": P(None), p + "mlp_norm_g": P(None),
            p + "attn_post_norm_g": P(None), p + "mlp_post_norm_g": P(None),
            p + "w_gate": P(None, m), p + "w_up": P(None, m),
            p + "w_down": P(m, None),
        })
        if layer_routes(cfg, i):
            # the EXPERT axis over ``model`` (expert parallelism: the range
            # a process holds is one device's shard), the router and its
            # bias replicated, the shared experts Megatron
            specs.update({
                p + "router": P(None, None), p + "router_bias": P(None),
                p + "e_gate": P(m, None, None), p + "e_up": P(m, None, None),
                p + "e_down": P(m, None, None),
                p + "s_gate": P(None, m), p + "s_up": P(None, m),
                p + "s_down": P(m, None),
            })
        if kind == MAMBA:
            specs.update({
                p + "w_in": P(None, m), p + "b_in": P(m),
                p + "conv_w": P(None, m), p + "conv_b": P(m),
                p + "w_x": P(m, None), p + "dt_norm_g": P(None),
                p + "b_norm_g": P(None), p + "c_norm_g": P(None),
                p + "w_dt": P(None, m), p + "b_dt": P(m),
                p + "a_log": P(None, m), p + "d_skip": P(m),
                p + "w_out": P(m, None), p + "b_out": P(None),
            })
            continue
        kv = P(None, m) if kind == LINEAR else P(None, None)
        specs.update({
            p + "q_norm_g": P(None), p + "k_norm_g": P(None),
            p + "wq": P(None, m), p + "wk": kv, p + "wv": kv,
            p + "w_ogate": P(None, m), p + "wo": P(m, None),
        })
        if kind == LINEAR:
            specs[p + "o_norm_g"] = P(None)
        if kind == RETENTION:
            specs[p + "w_decay"] = P(None, None)
    return specs


def hybrid_pool_pspecs(cfg: DecoderConfig) -> Dict[str, P]:
    """Every pool of the stack (``engines/paged.py``), replicated."""
    names = [STATE_SLOT, *lane_state_entries(cfg)]
    if window_layers(cfg):
        names.append(WINDOW_PAGES)
    for i, kind in enumerate(cfg.mixer_types):
        names += [f"{prefix}{i}" for prefix in MIXERS[kind].rows(cfg)]
    names += [f"ck{i}" for i in sparse_layers(cfg)]
    return {name: P() for name in names}


def hybrid_serving(cfg: DecoderConfig) -> BlockServing:
    """The stack's record (``cfg`` checked: :func:`check_hybrid_config`)."""
    # served cold, unspeculated and unpreempted: a shared prefix is a run
    # of pages and a lane's state at the share boundary is in none of them;
    # a verify step of several tokens would need the state after each; and
    # a preempted lane resumes from pages alone.  ``use_flash`` reaches the
    # plain attention layers' decode, the state-space layers' prefill scan
    # and — where the paged kernel reads this geometry — the blocks a
    # sparse layer's decode step took, and nothing else of the stack
    kinds = set(cfg.mixer_types)
    selects = SPARSE in kinds
    sums = dict(
        step_sum_names=SPARSE_SUMS,
        step_sums=functools.partial(sparse_step_sums, cfg),
        kv_rows_read=functools.partial(sparse_rows_read, cfg),
    ) if selects else {}
    chunk_counts = functools.partial(hybrid_chunk_counts, cfg)
    attrs, occupancy = {}, {"state_bytes_per_lane": lane_state_bytes(cfg)}
    if routed_layers(cfg):
        # the routing record in the selection record's place, summed as
        # the latent block sums it (``models/routed.py``)
        sums = dict(
            step_sum_names=MOE_SUMS,
            step_sums=functools.partial(moe_step_sums, cfg),
            prefill_sum_names=MOE_PREFILL_SUMS,
            prefill_sums=functools.partial(moe_prefill_sums, cfg),
        )
        attrs["experts_held"] = experts_held(cfg)[1]
        chunk_counts = functools.partial(routed_chunk_counts, cfg)
    if RETENTION in kinds:
        attrs.update(retention_layers=len(retention_layers(cfg)),
                     state_bytes_a_lane=lane_state_bytes(cfg))
    if WINDOW in kinds:
        sums["kv_rows_read"] = functools.partial(window_rows_read, cfg)
        attrs.update(window_layers=len(window_layers(cfg)),
                     global_layers=len(layers_of(cfg, ATTENTION)))
        occupancy.update(window=cfg.sliding_window,
                         window_layers=len(window_layers(cfg)))
    return BlockServing(
        label=f'DecoderConfig(block="{cfg.block}")',
        unserved=("generate.prefix_cache", "generate.speculative_k",
                  "qos.preemption"),
        advice="set prefix_cache false, speculative_k 0 and "
               "qos.preemption off",
        solo=(
            f'has no "{HYBRID_BLOCK}" block: a stack of mixer kinds serves '
            "through the batcher (engines/serve.ContinuousBatcher) over "
            "the paged rows and the lane state (engines/paged.py) only"
        ),
        uses_flash=bool(
            {ATTENTION, WINDOW, MAMBA} & kinds
            or selects and paged_kernel_supported(
                cfg.dtype, cfg.num_kv_heads, cfg.head_dim)),
        # a layer that selects reads through tables the program builds
        paged_reads=() if selects else tuple(
            (len(layers_of(cfg, kind)), window)
            for kind, window in ((ATTENTION, None),
                                 (WINDOW, cfg.sliding_window))
            if kind in kinds),
        chunk_counts=chunk_counts,
        prefill_counts=functools.partial(hybrid_prefill_counts, cfg),
        prefill_attrs=functools.partial(hybrid_prefill_attrs, cfg),
        span_attrs=attrs,
        occupancy=occupancy,
        lane_state=True,
        ring_pages=(functools.partial(ring_pages, cfg)
                    if WINDOW in kinds else None),
        param_pspecs=functools.partial(hybrid_param_pspecs, cfg),
        pool_pspecs=functools.partial(hybrid_pool_pspecs, cfg),
        **sums,
    )
