"""The block's seeded tree, made on the device in the served type — the
benchmark's own draw and quantization, ONE jitted program a layer (every
layer is of the one kind and shares it) — and the controls of this block.
Names and shapes are those of ``docqa_tpu/models/hybrid.py`` (tested
against its schema); with a mesh every tensor is born under its serving
sharding.  The tree goes to the program through
``GenerateEngine(params=...)`` and to the plain reference through
:func:`dequantized`.

Every matrix a matmul streams is a seeded normal draw at ``fan_in ** -0.5``
(int8 per output channel where the configuration says so); the decay
projection [hidden, kv heads] is drawn the same and stays in the activation
type (5120 x 8: what ``models/quant.should_quantize`` leaves alone); every
norm gain is 1; the embedding is a normal draw of deviation ``EMB_STD``.

**Gates that keep hundreds of tokens** (REVIEW of PR 51).  The gate is
bias-free, ``log sigmoid(y W_decay)``, and a zero-mean draw of ``W_decay``
over a zero-mean ``y`` gives ``log sigmoid`` of a zero-mean number: -0.8 a
token, a lane that forgets in five tokens, and a comparison in which the
state a lane CARRIES weighs nothing.  A trained model's gates lie near 1
because its residual stream carries a few channels of large constant value
that a bias-free projection reads as a bias (Sun et al., "Massive
Activations in Large Language Models", arXiv:2402.17762).  So channel 0 of
the residual stream is such a channel, and the gate's alone:

* every token's embedding holds ``GATE_CHANNEL * sqrt(hidden)`` there;
* nothing writes it (column 0 of ``wo`` and ``w_down`` is zero), so it is
  that constant in every layer, exactly, in any type;
* nothing but the gate reads it (row 0 of ``wq``, ``wk``, ``wv``,
  ``w_gate``, ``w_up`` and ``lm_head`` is zero);
* row 0 of ``W_decay`` is ``GATE_LOGIT / sqrt(hidden)`` for every kv head.

After the RMSNorm the channel reads ``sqrt(hidden) / sqrt(1 + (r /
GATE_CHANNEL)^2)``, ``r`` the root mean square of the token's other
channels (``EMB_STD`` at the embedding; 0.51 at the first layer and 1.18
at the twelfth on the chip), so the gate's logit is ``GATE_LOGIT / sqrt(1
+ (r / GATE_CHANNEL)^2)`` plus the seeded draw's share of the token: a
gate computed from the token whose log averages -0.00035 at the first
layer and -0.0023 at the twelfth — a lane keeps 400 to 2,900 tokens —
whatever the width (the readings by layer at the published widths are in
the configuration's ``assumed`` and in PERF.md section 6, PR 51).  That
the channel is ONE bfloat16 number costs nothing: its rounding on the
gate's path alone moves the last rows' stream by 4e-5 (a CPU run at
hidden 2,048, 8 layers, 3,072 tokens; spread over 32 channels it was
1e-5, and not worth the code)."""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from harness.weights import (
    SCALE,
    Control,
    act_int8,
    quantize_int8,
    to_int4,
    to_int8,
)

DECAY = "w_decay"  # the one float matrix of a quantized layer
EMB_STD = 0.5  # of every embedding channel but the first
# channel 0 of the residual stream, the gate's constant (module docstring)
GATE_CHANNEL = 1.0  # its value, in units of sqrt(hidden)
GATE_LOGIT = 9.0  # the gate's logit where the channel is all a token holds
READS_STREAM = ("wq", "wk", "wv", "w_gate", "w_up", "lm_head")
WRITES_STREAM = ("wo", "w_down")


def layer_shapes(cfg) -> Dict[str, tuple]:
    """Every seeded-normal matrix of one layer, by its short name."""
    h, m = cfg.hidden_dim, cfg.mlp_dim
    q, kv = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim
    return {
        "wq": (h, q), "wk": (h, kv), "wv": (h, kv),
        DECAY: (h, cfg.num_kv_heads), "wo": (q, h),
        "w_gate": (h, m), "w_up": (h, m), "w_down": (m, h),
    }


def layer_gains(cfg) -> Dict[str, tuple]:
    return {"attn_norm_g": (cfg.hidden_dim,), "mlp_norm_g": (cfg.hidden_dim,),
            "q_norm_g": (cfg.head_dim,), "k_norm_g": (cfg.head_dim,)}


def _draw(key, name, shape, quantize: bool, dtype):
    w = jax.random.normal(key, shape, jnp.float32) * (shape[0] ** -0.5)
    if name in READS_STREAM:
        w = w.at[0].set(0.0)
    elif name in WRITES_STREAM:
        w = w.at[:, 0].set(0.0)
    elif name == DECAY:
        w = w.at[0].set(GATE_LOGIT * shape[0] ** -0.5)
    if quantize:
        return quantize_int8(w)
    return (w.astype(dtype),)


def _layer_fn(cfg, quantize: bool, dtype):
    shapes = layer_shapes(cfg)

    def make(key):
        out = {}
        for name, k in zip(shapes, jax.random.split(key, len(shapes))):
            drawn = _draw(
                k, name, shapes[name], quantize and name != DECAY, dtype)
            out[name] = drawn[0]
            if len(drawn) > 1:
                out[name + SCALE] = drawn[1]
        return out

    return make


def _ends_fn(cfg, quantize: bool, dtype):
    h, v = cfg.hidden_dim, cfg.vocab_size

    def make(key):
        k_emb, k_head = jax.random.split(key)
        out = {"tok_emb": (
            EMB_STD * jax.random.normal(k_emb, (v, h), jnp.float32)
        ).at[:, 0].set(GATE_CHANNEL * h ** 0.5).astype(dtype)}
        drawn = _draw(k_head, "lm_head", (h, v), quantize, dtype)
        out["lm_head"] = drawn[0]
        if quantize:
            out["lm_head" + SCALE] = drawn[1]
        return out

    return make


def make_decoder_params(cfg, seed: int, mesh=None) -> Dict[str, jax.Array]:
    """The served parameter tree of ``cfg`` from ``seed``."""
    dtype = jnp.dtype(cfg.dtype)
    quantize = bool(cfg.quantize_weights)
    if quantize and cfg.quant_bits != 8:
        raise ValueError("the benchmark makes int8 or float weights only")
    if cfg.tie_embeddings:
        raise ValueError("the benchmark makes this block's head untied")

    def sharding_of(name, shape):
        if mesh is None:
            return None
        from docqa_tpu.parallel.sharding import decoder_param_sharding

        return decoder_param_sharding(name, shape, cfg, mesh)

    def jit_with_shardings(fn, prefix):
        shapes = jax.eval_shape(fn, jax.random.key(0, impl="rbg"))
        out_sh = (
            None if mesh is None else
            {n: sharding_of(prefix + n, s.shape) for n, s in shapes.items()}
        )
        return jax.jit(fn, out_shardings=out_sh)

    def ones(name, shape):
        return jnp.ones(shape, dtype, device=sharding_of(name, shape))

    root = jax.random.key(seed % (2**31), impl="rbg")
    keys = jax.random.split(root, cfg.num_layers + 1)
    params: Dict[str, jax.Array] = dict(
        jit_with_shardings(_ends_fn(cfg, quantize, dtype), "")(keys[0]))
    params["final_norm_g"] = ones("final_norm_g", (cfg.hidden_dim,))
    make_layer = jit_with_shardings(_layer_fn(cfg, quantize, dtype), "l0_")
    for i in range(cfg.num_layers):
        for name, value in make_layer(keys[i + 1]).items():
            params[f"l{i}_{name}"] = value
        for name, shape in layer_gains(cfg).items():
            params[f"l{i}_{name}"] = ones(f"l{i}_{name}", shape)
    return params


def dequantized(params, name: str):
    """One served tensor as float32: ``q * scale`` for a quantized one."""
    w = params[name]
    scale = params.get(name + SCALE)
    if scale is None:
        return w.astype(jnp.float32)
    return w.astype(jnp.float32) * scale.astype(jnp.float32)[None, :]


# ---- the controls of this block --------------------------------------------

def _e4m3(x):
    """float32 -> float8 (4 exponent bits, 3 of mantissa; largest finite
    value 240) -> float32, by ``lax.reduce_precision``: the one rounding
    the chip's compiler may not skip (a pair of ``astype``s it may:
    PERF.md section 2, PR 42)."""
    return jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)


def to_fp8(w32):
    """A weight matrix [in, out] through float8, per-output-channel scaled
    to its range."""
    scale = jnp.maximum(jnp.max(jnp.abs(w32), axis=0) / 240.0, 1e-12)
    return _e4m3(w32 / scale[None, :]) * scale[None, :]


def act_fp8(x):
    """Activations through float8, absmax over the last axis scaled to its
    range: a row per token."""
    scale = jnp.maximum(
        jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 240.0, 1e-12)
    return _e4m3(x / scale) * scale


def _bf16(x):
    """float32 -> bfloat16 -> float32, by ``reduce_precision``: see
    :func:`_e4m3`."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def state_bf16(x, what):
    """A lane's state KEPT in bfloat16, one step below the float32 the
    program keeps: rounded wherever the chunked form hands it on, as a pool
    of that type would hold it between two dispatches."""
    return _bf16(x) if what == "state" else x


def read_bf16(x, what):
    """The state kept float32 and READ through bfloat16: what the program's
    prefill does where ``phi(q)`` meets the carried state (one pass of the
    matmul unit, the sum float32; ``assumed``, "retention precision")."""
    return _bf16(x) if what == "carry" else x


def carry_zero(x, what):
    """What a lane carried from chunk to chunk, FORGOTTEN: every row
    attends inside its own 128-row chunk alone.  It stands for a fault of
    the carry — a wrong reset at a segment's start, another chunk's state
    in the entry, the read of the state dropped — that arithmetic inside a
    chunk cannot show."""
    return jnp.zeros_like(x) if what == "carry" else x


def controls_for(cfg) -> Dict[str, Control]:
    """Each of which ``correct`` has to fail, one step below what the
    configuration states.  Weights: int4 below int8 (float8 and int8 below
    bfloat16; the decay projection stays as it is served).  Activations:
    every matmul input in int8, and in float8 — there is no cached row to
    round with them.  And the carry of the state, forgotten: the one
    control that is no rounding (:func:`carry_zero`) — with gates that keep
    hundreds of tokens every compared row reads mostly what its lane
    carried, and a comparison that passed this would not see the state at
    all.  These fail the logits.  The third thing the configuration
    states, the STATE's float32, is held exactly: :func:`kv_only_controls`."""
    if cfg.quantize_weights and cfg.quant_bits == 8:
        out = {"w_int4": Control(weights=to_int4)}
    else:
        out = {"w_fp8": Control(weights=to_fp8),
               "w_int8": Control(weights=to_int8)}
    out.update(a_int8=Control(act=act_int8), a_fp8=Control(act=act_fp8),
               carry_zero=Control(kv=carry_zero))
    return out


def kv_only_controls() -> Dict[str, Control]:
    """Read by calibrate.py beside the controls and NOT among them.
    ``state_bf16``: the lane STATE kept in bfloat16, one step below the
    float32 the program keeps — what the exact ``kv_cache_bits_missing``
    holds: the state is the narrowest array the pools of this stack hold
    (there is no K / V row to hide it behind: the configuration states
    ``kv_cache_bits`` 32), so a bfloat16 state pool fails by 16 bits
    whatever the logits make of it.  ``read_bf16``: the float32 state read
    through bfloat16, which is what the program's prefill DOES — a reading
    beside the sound program's that says what that choice costs where the
    carried state is most of what a row reads."""
    return {"state_bf16": Control(kv=state_bf16),
            "read_bf16": Control(kv=read_bf16)}
