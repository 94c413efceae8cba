"""Decoder-only generator (Mistral/Llama-class) — replaces the external
Ollama/llama.cpp runtime the reference shelled out to (``llm-qa/main.py:8,66-69``).

Pure-functional: params are a flat dict pytree, forward is jit/GSPMD-friendly
(static shapes, no data-dependent control flow).  Architecture: RMSNorm
pre-norm, GQA attention with RoPE, SwiGLU MLP, optional sliding window —
matching HF Mistral-7B / Llama-3 weights so real safetensors can be imported
via :func:`load_hf_llama_weights` (zero-egress: falls back to seeded init).

KV cache: preallocated [b, max_len, kv_heads, head_dim] per layer, updated
in place via per-lane ``dynamic_update_slice`` under ``jax.vmap`` — each
batch lane carries its own write offset, which is what continuous batching
needs (lanes at different sequence positions in one decode step).

Tensor parallelism: no explicit collectives here — ``parallel/sharding.py``
provides PartitionSpecs for every param (heads/mlp sharded over the
``model`` axis) and GSPMD inserts the psum/all-gathers on ICI.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from docqa_tpu.config import DecoderConfig
from docqa_tpu.models.hybrid import (
    MIXERS,
    ATTENTION,
    WINDOW,
    check_hybrid_config,
    hybrid_param_schema,
    hybrid_serving,
    is_hybrid,
    lane_state_entries,
    layers_of,
    mamba_layers,
    retention_layers,
    sparse_layers,
)
from docqa_tpu.models.latent import (
    check_latent_config,
    is_latent,
    latent_param_schema,
    latent_row_width,
    latent_serving,
)
from docqa_tpu.models.routed import routed_layers
from docqa_tpu.models.serving import BlockServing, KernelForms
from docqa_tpu.ops.attention import (
    attention_reference,
    flash_attention,
    paged_kernel_supported,
    paged_latent_kernel_supported,
    ragged_key_block_counts,
)
from docqa_tpu.ops.norms import rms_norm
from docqa_tpu.ops.retention import retention_kernel_supported
from docqa_tpu.ops.rope import apply_rope, rope_angles
from docqa_tpu.ops.scopes import scope

Params = Dict[str, jax.Array]
KVCache = Dict[str, jax.Array]  # "k0".."k{L-1}", "v0".."v{L-1}"


def decoder_param_schema(cfg: DecoderConfig):
    """The single source of truth for the decoder's parameter tree:
    yields ``(name, kind, shape, fan_in)`` with kind ∈ {"normal", "ones"}.
    (``"zeros_f32"``: float32 zeros whatever the tree's type, a router's
    selection bias.)  Both ``init_decoder_params`` and the int8 incremental
    init (``models/quant.py``) consume this — the RNG stream order is defined
    by the order of "normal" entries here, so the two inits can never
    desynchronize.  The latent block's tree is ``models/latent.py``'s,
    the stack of mixer kinds' ``models/hybrid.py``'s."""
    if is_latent(cfg):
        yield from latent_param_schema(cfg)
        return
    if is_hybrid(cfg):
        yield from hybrid_param_schema(cfg)
        return
    h = cfg.hidden_dim
    qd = cfg.num_heads * cfg.head_dim
    kvd = cfg.num_kv_heads * cfg.head_dim
    yield ("tok_emb", "normal", (cfg.vocab_size, h), h)
    yield ("final_norm_g", "ones", (h,), None)
    yield ("lm_head", "normal", (h, cfg.vocab_size), h)
    for i in range(cfg.num_layers):
        yield (f"l{i}_attn_norm_g", "ones", (h,), None)
        yield (f"l{i}_wq", "normal", (h, qd), h)
        yield (f"l{i}_wk", "normal", (h, kvd), h)
        yield (f"l{i}_wv", "normal", (h, kvd), h)
        yield (f"l{i}_wo", "normal", (qd, h), qd)
        yield (f"l{i}_mlp_norm_g", "ones", (h,), None)
        yield (f"l{i}_w_gate", "normal", (h, cfg.mlp_dim), h)
        yield (f"l{i}_w_up", "normal", (h, cfg.mlp_dim), h)
        yield (f"l{i}_w_down", "normal", (cfg.mlp_dim, h), cfg.mlp_dim)
    # behind every entry the plain block has, so that its draws keep
    # their place in the stream
    if cfg.sandwich_norm:
        for i in range(cfg.num_layers):
            yield (f"l{i}_attn_post_norm_g", "ones", (h,), None)
            yield (f"l{i}_mlp_post_norm_g", "ones", (h,), None)
    if cfg.loop_steps > 1:
        yield ("exit_gate_w", "normal", (h, 1), h)
        yield ("exit_gate_b", "normal", (1,), h)


def check_loop_config(cfg: DecoderConfig) -> None:
    """Refuse, by field, what the looped trunk cannot run.  The three
    fields belong to the ``gqa_swiglu`` block; the other blocks' trunks
    do not read them and say so here rather than run something else."""
    problems = []
    if cfg.loop_steps < 1:
        problems.append("loop_steps under 1")
    if cfg.loop_exit_threshold != 1.0:
        problems.append(
            "loop_exit_threshold (only 1.0 is served: every lane runs every "
            "step; a pass count that differs by lane needs a scheduler)")
    looped = cfg.loop_steps > 1 or cfg.sandwich_norm
    unread = cfg.loop_steps > 1 and cfg.block != "gqa_swiglu" or (
        cfg.sandwich_norm and is_latent(cfg))
    if unread:
        problems.append(
            f'loop_steps / sandwich_norm (block "{cfg.block}" does not '
            'read them: "gqa_swiglu" reads both, the stack of mixer kinds '
            "sandwich_norm alone)")
    if looped and cfg.quantize_weights:
        problems.append(
            "quantize_weights (int8 / int4 weights under the looped or "
            "sandwich-normed trunk are untested: float weights only)")
    if problems:
        raise ValueError(
            "DecoderConfig cannot be served: " + "; ".join(problems))


def kv_entries(cfg: DecoderConfig) -> int:
    """Cache entries a weight layer keeps per token: one, and
    ``loop_steps`` under the looped trunk — step ``t`` of layer ``i``
    attends over entry ``(t, i)`` alone.  The one place the pools' extent,
    the bytes a token, the allocator's accounting and the rows a decode
    chunk read hear it from.  Entry ``(t, i)`` is the ``t``-th range of
    ``n_blocks * block_size`` rows of layer ``i``'s pools
    (``engines/paged.init_paged_pools``): a block id owns its rows in
    every step's range."""
    return cfg.loop_steps


def kv_row_shapes(
    cfg: DecoderConfig, layer: Optional[int] = None
) -> Dict[str, Tuple[int, int]]:
    """What one token leaves in the cache, per layer: ``{pool prefix:
    (heads, width)}``.  The GQA block keeps a key and a value per kv
    head; the latent block ONE row (normed latent ‖ rotated key) that
    every head reads as key and as value.  The paged pools, their bytes
    per token and the choice of decode kernel are all asked of this.

    The stack of mixer kinds (``models/hybrid.py``) answers per LAYER KIND:
    a sparse or a plain attention layer keeps K and V rows (``layer``
    None: a row-keeping layer's answer), a linear or a state-space layer
    NO row — what it keeps is a state a lane, :func:`lane_state_shapes`."""
    if is_latent(cfg):
        return {"c": (1, latent_row_width(cfg))}
    if is_hybrid(cfg) and layer is not None:
        return MIXERS[cfg.mixer_types[layer]].rows(cfg)
    return {"k": (cfg.num_kv_heads, cfg.head_dim),
            "v": (cfg.num_kv_heads, cfg.head_dim)}


def lane_state_shapes(cfg: DecoderConfig) -> Dict[str, Tuple[int, ...]]:
    """What a LANE holds whatever its length, beside the rows its tokens
    left: ``{pool name: shape of one lane's entry}``.  Empty for every
    block but the stack of mixer kinds: a linear layer keeps one
    [heads, d, d] state (``s{i}``), a state-space layer TWO entries of
    different shape and type — its state ``h{i}`` [state, inner] and its
    last conv inputs ``u{i}`` [taps - 1, inner]
    (:func:`lane_state_dtypes`)."""
    return {n: shape for n, (shape, _) in lane_state_entries(cfg).items()}


def lane_state_dtypes(cfg: DecoderConfig) -> Dict[str, str]:
    """The element type of each entry of :func:`lane_state_shapes`:
    float32 for a state, the activation type for a conv window."""
    return {n: dtype for n, (_, dtype) in lane_state_entries(cfg).items()}


def _loop_chunk_counts(steps: int, *, lane_steps, **_):
    # the positions a fetched chunk's lanes advanced and the passes of the
    # stack they took (``loop_steps`` each while no step exits early):
    # their ratio is the passes a token
    return {"serve_loop_lane_steps": lane_steps,
            "serve_loop_passes": lane_steps * steps}, {}


def block_serving(cfg: DecoderConfig) -> BlockServing:
    """The record of ``cfg``'s block kind (``models/serving.py``).  The
    GQA block's is the defaults; its looped trunk (``loop_steps`` > 1) is
    served cold and unspeculated — a warm prefill and a verify step
    through the steps' ranges of the pools are untested — and counts what
    a token costs.  A configuration its kind cannot run is refused HERE,
    by field: the loop's fields first (the GQA block's alone)."""
    check_loop_config(cfg)
    if is_latent(cfg):
        check_latent_config(cfg)
        return latent_serving(cfg)
    if is_hybrid(cfg):
        check_hybrid_config(cfg)
        return hybrid_serving(cfg)
    steps = kv_entries(cfg)
    if steps == 1:
        return BlockServing(label=f'DecoderConfig(block="{cfg.block}")')
    attrs = {"loop_steps": steps}
    return BlockServing(
        label=f"DecoderConfig(loop_steps={steps})",
        unserved=("generate.prefix_cache", "generate.speculative_k"),
        advice="set prefix_cache false and speculative_k 0",
        solo=(
            f"runs loop_steps 1 only (got {steps}): a looped trunk keeps an "
            "entry a (step, layer) and serves through the batcher "
            "(engines/serve.ContinuousBatcher) over the paged cache "
            "(engines/paged.py) only"
        ),
        chunk_counts=functools.partial(_loop_chunk_counts, steps),
        span_attrs=attrs,
        occupancy=attrs,
    )


def packed_attention_layers(
    cfg: DecoderConfig,
) -> Tuple[Tuple[Optional[int], int], ...]:
    """((sliding window or None, calls a prefill dispatch), ...): the
    layers whose prefill attends over the packed rows in flight
    (``ops/attention.ragged_prefill_attention``), by the window they
    attend under — the GQA trunk's every layer, once a loop step; the
    mixer stack's ``attention`` and ``window`` kinds; none of the latent
    block's (its keys are 192 wide, its own form) nor of a layer that
    selects, scans or keeps a linear state."""
    if is_latent(cfg):
        return ()
    if not is_hybrid(cfg):
        return ((cfg.sliding_window, cfg.num_layers * kv_entries(cfg)),)
    kinds = ((None, len(layers_of(cfg, ATTENTION))),
             (cfg.sliding_window, len(layers_of(cfg, WINDOW))))
    return tuple((window, n) for window, n in kinds if n)


def ragged_prefill_counts(cfg: DecoderConfig, packings, *, max_segment):
    """What one admission round's COLD dispatches count where they attend
    in the kernel (``kernel_forms``'s ``ragged``): the dispatches, and the
    key blocks their attention layers visited beside the blocks of the
    whole packed square — host arithmetic on each dispatch's ``(seg_ids,
    positions)``, the same block ranges the kernel prefetches."""
    visited = packed = 0
    for seg_ids, positions in packings:
        for window, calls in packed_attention_layers(cfg):
            seen, square = ragged_key_block_counts(
                seg_ids, positions, window, max_segment)
            visited += calls * seen
            packed += calls * square
    return {
        "serve_prefill_attend_kernel_dispatches": len(packings),
        "serve_prefill_key_blocks_visited": visited,
        "serve_prefill_key_blocks_packed": packed,
    }


def kernel_forms(cfg: DecoderConfig, *, on_tpu: bool, mesh,
                 block_size: Optional[int]) -> KernelForms:
    """Which Pallas forms the paged forwards of ``cfg`` run — THE place
    the choice is made: the engine asks once with what it observed, the
    forwards hand the answer to their ops as static booleans, the batcher
    counts by it.  ``on_tpu``: the one observation — a TPU whose kernels
    read this head width, or what the engine's caller said
    (``use_flash``); ``block_size``: the pools' page, ``None`` for a
    prefill.

    * ``paged``: K / V rows of a geometry the paged kernel reads, sharded
      over ``mesh`` as they are; the latent block's one shared row a token
      through a kernel of its own (``ops/attention.
      paged_latent_flash_decode``), NO mesh (its pool is replicated there
      and the XLA form lowers as it stands), by the pool's element type,
      the latent's width and the page (``paged_latent_kernel_supported``)
      — a decode step of one token or of several alike (the kernel reads
      ``s`` from the shape);
    * ``sparse_paged``: a layer selects, NO mesh (the XLA form is what
      GSPMD places), and a selection block is a whole number of pages;
    * ``scan``: a state-space layer, and NO mesh (the ``ssm_*`` arrays are
      replicated there and the XLA form lowers as it stands);
    * ``grouped``: a routed layer, and NO mesh (``ragged_dot`` partitions
      experts sharded along their leading axis, a custom call cannot);
    * ``ragged``: a layer attends over the packed rows of a prefill
      (:func:`packed_attention_layers`), its heads are whole 128-lane
      registers, and NO mesh (the XLA forms are what GSPMD places) — the
      COLD dispatch's attention is ``ops/attention.ragged_flash_prefill``;
      a warm one (a cached prefix through the block table) stays XLA;
    * ``retention``: a retention layer, NO mesh (the state is replicated
      there and the XLA form lowers as it stands), and a state of a
      geometry the kernel's blocks hold (``ops/retention.
      retention_kernel_supported``) — the layer's decode step advances
      and reads the entries a live lane owns in ONE pass, in place
      (``ops/retention.power_retention_step_fused``)."""
    alone = on_tpu and mesh is None
    geometry = (cfg.dtype, cfg.num_kv_heads, cfg.head_dim)
    if is_latent(cfg):
        paged = alone and paged_latent_kernel_supported(
            cfg.dtype, cfg.kv_lora_rank, block_size)
    else:
        paged = on_tpu and paged_kernel_supported(*geometry, mesh)
    return KernelForms(
        paged=paged,
        sparse_paged=alone and len(sparse_layers(cfg)) > 0
        and block_size is not None
        and cfg.sparse_block_size % block_size == 0
        and paged_kernel_supported(*geometry),
        scan=alone and len(mamba_layers(cfg)) > 0,
        grouped=alone and routed_layers(cfg) > 0,
        ragged=alone and len(packed_attention_layers(cfg)) > 0
        and cfg.head_dim % 128 == 0,
        retention=alone and len(retention_layers(cfg)) > 0
        and retention_kernel_supported(
            cfg.num_heads, cfg.num_kv_heads, cfg.head_dim),
    )


def param_putter(cfg: DecoderConfig, mesh=None):
    """``put(name, host_array) -> device array``: with a mesh, each
    tensor goes host → devices under its FINAL sharding
    (``parallel.sharding.decoder_param_sharding``), so a tree larger
    than one device's memory never stages on the first device."""
    if mesh is None:
        return lambda name, value: jax.device_put(value)
    from docqa_tpu.parallel.sharding import decoder_param_sharding

    return lambda name, value: jax.device_put(
        value, decoder_param_sharding(name, value.shape, cfg, mesh)
    )


def init_decoder_params(
    rng: jax.Array, cfg: DecoderConfig, param_dtype=jnp.float32,
    host_init: bool = False, host_seed: Optional[int] = None, mesh=None,
) -> Params:
    """``param_dtype``: float32 default (training master weights); bf16 for
    inference-only at target scale — a 7B f32 tree (29 GB) cannot even be
    *materialized* on a 16 GB chip, so the cast happens per-tensor here,
    never on a whole f32 tree.

    ``host_init``: draw on the host (numpy) and ``device_put`` per tensor
    — the same transfer path real safetensors checkpoints take — placed
    by :func:`param_putter` (``mesh``: straight into the target
    sharding).  Callers that know their integer seed pass ``host_seed``
    so the numpy seed needs no ``key_data`` fetch."""
    param_dtype = jnp.dtype(param_dtype)
    p: Params = {}
    if host_init:
        import numpy as _np

        from docqa_tpu.utils import host_seed_from_rng

        put = param_putter(cfg, mesh)
        host_rng = _np.random.default_rng(host_seed_from_rng(rng, host_seed))
        for name, kind, shape, fan_in in decoder_param_schema(cfg):
            if kind == "ones":
                p[name] = put(name, _np.ones(shape, param_dtype))
            elif kind == "zeros_f32":
                p[name] = put(name, _np.zeros(shape, _np.float32))
            else:
                w = host_rng.standard_normal(shape, _np.float32) * (
                    fan_in ** -0.5
                )
                p[name] = put(name, w.astype(param_dtype))
        return p
    schema = list(decoder_param_schema(cfg))
    # a state-space layer draws more than eight tensors; every other tree
    # keeps the split (and so the stream) it has always had
    drawn = sum(kind == "normal" for _, kind, _, _ in schema)
    keys = iter(jax.random.split(rng, max(8 + 8 * cfg.num_layers, drawn)))
    for name, kind, shape, fan_in in schema:
        if kind == "ones":
            p[name] = jnp.ones(shape, param_dtype)
        elif kind == "zeros_f32":  # a router's selection bias
            p[name] = jnp.zeros(shape, jnp.float32)
        else:
            p[name] = (
                jax.random.normal(next(keys), shape, jnp.float32)
                * (fan_in ** -0.5)
            ).astype(param_dtype)
    return p


def init_kv_cache(
    cfg: DecoderConfig, batch: int, max_len: Optional[int] = None,
    dtype: Optional[jnp.dtype] = None,
) -> KVCache:
    max_len = max_len or cfg.max_seq_len
    dtype = dtype or jnp.dtype(cfg.dtype)
    shape = (batch, max_len, cfg.num_kv_heads, cfg.head_dim)
    cache: KVCache = {}
    for i in range(cfg.num_layers):
        cache[f"k{i}"] = jnp.zeros(shape, dtype)
        cache[f"v{i}"] = jnp.zeros(shape, dtype)
    return cache


def _write_cache(cache_layer: jax.Array, new: jax.Array, offsets: jax.Array):
    """Per-lane KV write.  cache [b, S, kh, d], new [b, s, kh, d],
    offsets [b] — lane i writes new[i] at row offsets[i]."""

    def one(c, n, off):
        return jax.lax.dynamic_update_slice_in_dim(c, n, off, axis=0)

    return jax.vmap(one)(cache_layer, new, offsets)


def _qmatmul(x: jax.Array, params: Params, name: str, dtype) -> jax.Array:
    """``x [..., in] @ W`` with no dequantized copy of ``W``.

    int8 (2-D store, scale [out]): the per-output-channel scale commutes
    with the contraction, ``x @ (W * s[None, :]) == (x @ W) * s[None, :]``,
    so the dot's weight operand is a bare ``convert`` of the stored int8
    array (exact in bf16) and the small ``[rows, out]`` result is scaled
    by the stored float32 scale, in float32, and rounded to ``dtype``.
    Scaling the WEIGHT asks for an ``[in, out]`` product that the compiler
    may write out as a bf16 array of the weight's shape (it did at the
    narrow projection sites; why the dot hands back ``dtype`` and not
    float32: PERF.md section 6).  One form for every row count.  int4
    (3-D grouped store [groups, g, out], scale [groups, out]: a scale per
    128 input rows does NOT commute with the contraction):
    broadcast-scale the operand — pure broadcast multiply, no reshape
    between the multiply and the dot — contracted over both group axes
    via ``dot_general``; the activation-side regroup is a free reshape of
    the small operand."""
    from docqa_tpu.models.quant import SCALE_SUFFIX

    w = params[name]
    scale = params.get(name + SCALE_SUFFIX)
    if scale is None:
        return x @ w.astype(dtype)
    if w.ndim == 2:  # int8
        y = x @ w.astype(dtype)
        return (y.astype(jnp.float32) * scale).astype(dtype)
    groups, g, _out = w.shape  # int4 grouped
    wf = w.astype(dtype) * scale.astype(dtype)[:, None, :]
    x3 = x.reshape(*x.shape[:-1], groups, g)
    n = x3.ndim
    return jax.lax.dot_general(
        x3, wf, (((n - 2, n - 1), (0, 1)), ((), ()))
    )


def decoder_layer_stack(
    params: Params,
    cfg: DecoderConfig,
    ids: jax.Array,  # [b, s]
    positions: jax.Array,  # [b, s] absolute position per token (RoPE)
    rope_len: int,  # RoPE table length (>= max position + 1)
    attend,  # attend(layer, q, k, v) -> [b, s, num_heads, head_dim]
    cache: Optional[Dict[str, jax.Array]] = None,
) -> jax.Array:
    """The shared transformer trunk: embed, then per layer project
    q/k/v, apply RoPE at ``positions``, delegate KV-cache writes AND
    attention to ``attend``, then the wo projection and SwiGLU MLP.

    ``attend(i, q, k, v)`` owns the cache layout: the dense path
    (:func:`decoder_forward`) writes a contiguous per-lane cache and
    attends over it; the paged path (``engines/paged.py``) scatters
    into a block pool and attends through a block table.  Factoring the
    trunk means the two layouts can never drift in the layer math —
    every op outside ``attend`` is shared code, so batcher output stays
    token-exact with the solo engine by construction.

    The looped trunk (``cfg.loop_steps`` T > 1): the layers run T times
    over the same parameters as ONE loop in the program — the layer
    bodies are traced once, whatever T — the final norm closes every
    step, and ``attend(i, q, k, v, step=t)`` is told the traced step.
    ``cache`` is the dict ``attend`` reads and writes its arrays in: the
    loop carries it, so ``attend`` must reach them through that dict and
    nothing else.

    Returns the final hidden states [b, s, hidden]: pre final-norm at
    ``loop_steps`` 1, normed by the last step's close under the loop
    (:func:`decoder_head` finishes the stack either way)."""
    b, s = ids.shape
    dtype = jnp.dtype(cfg.dtype)
    with scope("proj"):
        cos, sin = rope_angles(cfg.head_dim, rope_len, cfg.rope_theta)
    # the residual stream: the activation type — float32 under the loop,
    # where 4 x 96 adds into a bfloat16 stream of RMS ~10 each round away
    # 2 % of the unit-RMS branch they add, and four passes over the same
    # weights amplify what one pass injects (0.021 / 0.039 / 0.276 at 1 /
    # 2 / 4 steps on the chip, PERF.md section 6, PR 44); matmul inputs
    # stay the activation type
    stream = jnp.float32 if cfg.loop_steps > 1 else dtype
    with scope("embed"):
        x = params["tok_emb"][ids].astype(dtype).astype(stream)

    def pre(x, name):
        """The stream normed into a sublayer, in the activation type."""
        return rms_norm(x, params[name], cfg.norm_eps).astype(dtype)

    def post(y, name):
        """A sublayer's output on its way to the residual add."""
        if not cfg.sandwich_norm:
            return y
        return rms_norm(y.astype(stream), params[name], cfg.norm_eps)

    def heads(y, n):
        """A q / k / v product [b, s, n * d] as heads [b, s, n, d].  Under
        the loop the product is fenced first: left to fuse the reshape
        into the dot, the TPU compiler lays ``wq`` / ``wk`` / ``wv`` out a
        head at a time ([heads, d, in]) and — the weights being invariant
        of the step loop — keeps a transposed copy of all 3 x 48 of them
        for the life of EVERY loaded program: 1.2 GB each at the published
        widths, which the chip does not have beside the pools (PERF.md
        section 6, PR 44).  At ``loop_steps`` 1 the programs stay what
        they were."""
        if cfg.loop_steps > 1:
            y = jax.lax.optimization_barrier(y)
        return y.reshape(b, s, n, cfg.head_dim)

    def layers(x, **step):
        for i in range(cfg.num_layers):
            with scope("proj"):
                y = pre(x, f"l{i}_attn_norm_g")
                q = heads(_qmatmul(y, params, f"l{i}_wq", dtype),
                          cfg.num_heads)
                k = heads(_qmatmul(y, params, f"l{i}_wk", dtype),
                          cfg.num_kv_heads)
                v = heads(_qmatmul(y, params, f"l{i}_wv", dtype),
                          cfg.num_kv_heads)
                q = apply_rope(q, cos, sin, positions)
                k = apply_rope(k, cos, sin, positions)

            attn = attend(i, q, k, v, **step)
            with scope("proj"):
                attn = attn.reshape(b, s, cfg.num_heads * cfg.head_dim)
                x = x + post(_qmatmul(attn, params, f"l{i}_wo", dtype),
                             f"l{i}_attn_post_norm_g")

            with scope("mlp"):
                y = pre(x, f"l{i}_mlp_norm_g")
                gate = _qmatmul(y, params, f"l{i}_w_gate", dtype)
                up = _qmatmul(y, params, f"l{i}_w_up", dtype)
                act = jax.nn.silu(gate.astype(jnp.float32)).astype(dtype) * up
                x = x + post(_qmatmul(act, params, f"l{i}_w_down", dtype),
                             f"l{i}_mlp_post_norm_g")
        return x

    if cfg.loop_steps == 1:
        return layers(x)

    def one_step(t, carry):
        x, held = carry
        cache.update(held)
        x = layers(x, step=t)
        with scope("loop_close"):
            # the exit gate (sigmoid(x . exit_gate_w + exit_gate_b)) would
            # be read here; at loop_exit_threshold 1 no step exits early
            # and it is not evaluated
            x = rms_norm(x, params["final_norm_g"], cfg.norm_eps)
        return x, dict(cache)

    x, held = jax.lax.fori_loop(
        0, cfg.loop_steps, one_step, (x, dict(cache)))
    cache.update(held)
    return x


def decoder_head(
    params: Params,
    cfg: DecoderConfig,
    x: jax.Array,  # [b, s, hidden]
    new_lengths: Optional[jax.Array] = None,
    last_token_only: bool = False,
) -> jax.Array:
    """Final norm + lm_head over the trunk's hidden states (f32 logits).
    Under the looped trunk the norm is the last step's close, applied in
    the trunk: here it is the head alone."""
    dtype = jnp.dtype(cfg.dtype)
    with scope("head"):
        if last_token_only and x.shape[1] > 1:
            # prefill path: only the last valid row per lane feeds
            # sampling — skip the [s, vocab] lm_head matmul for the rest
            # (~s x fewer FLOPs)
            x = jnp.take_along_axis(
                x, (new_lengths - 1)[:, None, None], axis=1
            )
        if cfg.loop_steps == 1:  # the looped trunk closed its last step
            x = rms_norm(x, params["final_norm_g"], cfg.norm_eps)
        x = x.astype(dtype)  # the looped trunk's stream is float32
        return _qmatmul(x, params, "lm_head", dtype).astype(jnp.float32)


def decoder_forward(
    params: Params,
    cfg: DecoderConfig,
    ids: jax.Array,  # [b, s]
    cache: KVCache,
    cache_lengths: jax.Array,  # [b] tokens already in cache
    attn_lengths: Optional[jax.Array] = None,  # [b] valid kv after this step
    *,
    use_flash: bool = False,
    last_token_only: bool = False,
    mesh=None,  # MeshContext: the flash kernel shards over it (ops/attention)
) -> Tuple[jax.Array, KVCache]:
    """Run s new tokens through the stack, appending to the cache.

    Prefill: cache_lengths = 0, s = prompt bucket; pass the true prompt
    lengths as ``attn_lengths`` so right-padded tail rows are never attended
    (their K/V land beyond the valid length and are overwritten by decode
    steps).  Decode: s = 1, ``attn_lengths`` defaults to cache_lengths + 1.

    Returns (logits [b, s, vocab] f32, updated cache).
    """
    solo = block_serving(cfg).solo
    if solo is not None:
        raise NotImplementedError("the dense-cache solo forward " + solo)
    b, s = ids.shape
    max_len = cache["k0"].shape[1]

    positions = cache_lengths[:, None] + jnp.arange(s)[None, :]  # [b, s]
    positions = jnp.minimum(positions, max_len - 1)
    new_lengths = cache_lengths + s if attn_lengths is None else attn_lengths

    attn_fn = (
        functools.partial(flash_attention, mesh=mesh)
        if use_flash
        else attention_reference
    )

    def attend(i, q, k, v):
        with scope("cache_write"):
            cache[f"k{i}"] = _write_cache(cache[f"k{i}"], k, cache_lengths)
            cache[f"v{i}"] = _write_cache(cache[f"v{i}"], v, cache_lengths)
        with scope("attend"):
            return attn_fn(
                q,
                cache[f"k{i}"],
                cache[f"v{i}"],
                causal=True,
                lengths=new_lengths,
                q_offset=cache_lengths,
                sliding_window=cfg.sliding_window,
            )

    x = decoder_layer_stack(params, cfg, ids, positions, max_len, attend)
    logits = decoder_head(params, cfg, x, new_lengths, last_token_only)
    return logits, cache


# --------------------------------------------------------------------------
# HF weight import (Mistral-7B-Instruct / Llama-3 layout, offline-gated)
# --------------------------------------------------------------------------

def load_hf_llama_weights(paths, cfg: DecoderConfig) -> Params:
    """Map HF ``model*.safetensors`` shards into our param tree.

    Torch Linear stores [out, in] → transpose.  HF q/k-proj rows are in
    interleaved-rotary order for some exports; we assume the Llama/Mistral
    default (non-interleaved, matching our split-halves RoPE).
    """
    from safetensors.numpy import load_file

    raw = {}
    if isinstance(paths, str):
        paths = [paths]
    for p in paths:
        raw.update(load_file(p))

    def t(name):
        return jnp.asarray(raw[name].T)

    p: Params = {
        "tok_emb": jnp.asarray(raw["model.embed_tokens.weight"]),
        "final_norm_g": jnp.asarray(raw["model.norm.weight"]),
        "lm_head": (
            t("lm_head.weight")
            if "lm_head.weight" in raw
            else jnp.asarray(raw["model.embed_tokens.weight"]).T
        ),
    }
    for i in range(cfg.num_layers):
        pre = f"model.layers.{i}."
        p[f"l{i}_attn_norm_g"] = jnp.asarray(raw[pre + "input_layernorm.weight"])
        p[f"l{i}_wq"] = t(pre + "self_attn.q_proj.weight")
        p[f"l{i}_wk"] = t(pre + "self_attn.k_proj.weight")
        p[f"l{i}_wv"] = t(pre + "self_attn.v_proj.weight")
        p[f"l{i}_wo"] = t(pre + "self_attn.o_proj.weight")
        p[f"l{i}_mlp_norm_g"] = jnp.asarray(
            raw[pre + "post_attention_layernorm.weight"]
        )
        p[f"l{i}_w_gate"] = t(pre + "mlp.gate_proj.weight")
        p[f"l{i}_w_up"] = t(pre + "mlp.up_proj.weight")
        p[f"l{i}_w_down"] = t(pre + "mlp.down_proj.weight")
    return p
