"""Sharding layouts for the decoder (TP over ICI) — the scaling-book recipe:
pick a mesh, annotate param/activation shardings, let GSPMD insert the
collectives.  No hand-written NCCL-style calls (the reference had no device
parallelism at all — SURVEY §2c).

Megatron-style layout per layer (two Megatron blocks — attention, MLP):
  * wq/wk/wv: output (head) dim sharded       → column parallel
  * wo:       input (head) dim sharded        → row parallel, psum after
  * w_gate/w_up: output dim sharded           → column parallel
  * w_down:   input dim sharded               → row parallel, psum after
  * lm_head:  vocab dim sharded               → logits sharded, argmax local
  * KV cache: kv-heads dim sharded            → decode attention stays local
GSPMD derives exactly ONE all-reduce per Megatron block (after each
row-parallel projection: two per layer) and no other collective from
these specs.  That contract is no longer a comment: scripts/shard_audit.py
lowers a decoder step on virtual 1x1/2x4/1x8 meshes every CI run and
holds the partitioned HLO's collective counts to shard_budget.json
(docs/SHARDING.md); a spec edit that inserts an all-gather or drops a
psum fails the gate, not the next pod benchmark.
"""

from __future__ import annotations

from typing import Dict

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from docqa_tpu.config import DecoderConfig
from docqa_tpu.models.hybrid import LINEAR, MAMBA, is_hybrid
from docqa_tpu.models.latent import is_latent
from docqa_tpu.runtime.mesh import MeshContext


def decoder_param_pspecs(cfg: DecoderConfig, model_axis: str) -> Dict[str, P]:
    m = model_axis
    specs: Dict[str, P] = {
        "tok_emb": P(None, None),  # replicated (gather-heavy; small at 7B)
        "final_norm_g": P(None),
        "lm_head": P(None, m),  # vocab-sharded logits
    }
    if is_latent(cfg):
        specs.update(_latent_param_pspecs(cfg, m))
        return specs
    if is_hybrid(cfg):
        specs.update(_hybrid_param_pspecs(cfg, m))
        return specs
    for i in range(cfg.num_layers):
        specs.update(
            {
                f"l{i}_attn_norm_g": P(None),
                f"l{i}_wq": P(None, m),
                f"l{i}_wk": P(None, m),
                f"l{i}_wv": P(None, m),
                f"l{i}_wo": P(m, None),
                f"l{i}_mlp_norm_g": P(None),
                f"l{i}_w_gate": P(None, m),
                f"l{i}_w_up": P(None, m),
                f"l{i}_w_down": P(m, None),
            }
        )
        if cfg.sandwich_norm:  # gains over the hidden axis: replicated
            specs[f"l{i}_attn_post_norm_g"] = P(None)
            specs[f"l{i}_mlp_post_norm_g"] = P(None)
    if cfg.loop_steps > 1:  # the exit gate: 2,049 numbers, replicated
        specs["exit_gate_w"] = P(None, None)
        specs["exit_gate_b"] = P(None)
    return specs


def _latent_param_pspecs(cfg: DecoderConfig, m: str) -> Dict[str, P]:
    """The latent block (models/latent.py).  Attention: the low-rank
    down-projections and their norms replicated (every device forms the
    same latent row, and the row pool is replicated); the per-head
    up-projections column-parallel over heads, ``wo`` row-parallel — one
    psum, as for the GQA block.  Dense and shared MLPs: Megatron.  Routed
    experts: the EXPERT axis over ``model`` — expert parallelism; the
    range a process holds (``experts_held``) is one device's shard of a
    layer's experts, and the router is replicated."""
    specs: Dict[str, P] = {}
    for i in range(cfg.num_layers):
        p = f"l{i}_"
        specs.update({
            p + "attn_norm_g": P(None), p + "mlp_norm_g": P(None),
            p + "wq_a": P(None, None), p + "q_norm_g": P(None),
            p + "wq_b": P(None, m),
            p + "wkv_a": P(None, None), p + "kv_norm_g": P(None),
            p + "wk_b": P(None, m), p + "wv_b": P(None, m),
            p + "wo": P(m, None),
        })
        if i < cfg.first_dense_layers:
            specs.update({p + "w_gate": P(None, m), p + "w_up": P(None, m),
                          p + "w_down": P(m, None)})
            continue
        specs.update({
            p + "router": P(None, None),
            p + "e_gate": P(m, None, None), p + "e_up": P(m, None, None),
            p + "e_down": P(m, None, None),
            p + "s_gate": P(None, m), p + "s_up": P(None, m),
            p + "s_down": P(m, None),
        })
    return specs


def _hybrid_param_pspecs(cfg: DecoderConfig, m: str) -> Dict[str, P]:
    """The stack of mixer kinds (models/hybrid.py): Megatron per layer.
    An attention kind: q, the output gate and the MLP's gate / up
    column-parallel, ``wo`` and ``w_down`` row-parallel; a linear layer's
    k and v are as wide as its q and go column-parallel with it; the few
    kv heads of a sparse or a plain attention layer are replicated (1 or
    2 heads do not divide over 4 or 8 devices), as are the per-head norm
    gains.  The state-space kind along its INNER channels: ``w_in``
    column-parallel over its ``2 x inner`` columns (GSPMD re-lays the
    ``u`` and the ``z`` half along ``inner``), the conv's taps and bias,
    ``w_x``'s input, ``w_dt``'s output, ``b_dt``, ``A_log`` and ``D``
    along that axis, ``w_out`` row-parallel; the three inner norms
    replicated.  The pools — rows (ONE kv head cannot be divided),
    compressed keys, lane states and windows — are replicated
    (``paged_pool_pspecs``)."""
    specs: Dict[str, P] = {}
    for i, kind in enumerate(cfg.mixer_types):
        p = f"l{i}_"
        specs.update({
            p + "attn_norm_g": P(None), p + "mlp_norm_g": P(None),
            p + "w_gate": P(None, m), p + "w_up": P(None, m),
            p + "w_down": P(m, None),
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
    return specs


def cache_pspecs(cfg: DecoderConfig, mesh: MeshContext) -> Dict[str, P]:
    """KV cache [b, S, kv_heads, d]: batch over data, kv heads over model."""
    spec = P(mesh.data_axis, None, mesh.model_axis, None)
    out: Dict[str, P] = {}
    for i in range(cfg.num_layers):
        out[f"k{i}"] = spec
        out[f"v{i}"] = spec
    return out


def decoder_param_sharding(
    name: str, shape, cfg: DecoderConfig, mesh: MeshContext
) -> NamedSharding:
    """The target sharding of one decoder tensor (weights, quantization
    scales, int4 grouped stores) — the ONE placement rule, used both to
    re-shard an existing tree and to create tensors under their final
    sharding at init (so nothing full-size ever lands on one device)."""
    from docqa_tpu.models.quant import SCALE_SUFFIX

    specs = decoder_param_pspecs(cfg, mesh.model_axis)
    if name.endswith(SCALE_SUFFIX):
        # scales mirror their weight's sharding (models/quant.py):
        # int8 scale [out] → P(out_spec); int4 grouped scale
        # [groups, out] → the weight's own spec, because groups ride
        # the in axis (sharded for row-parallel wo/w_down, replicated
        # for column-parallel).  When a group spans shards (groups
        # not divisible — tiny configs), replicate the groups axis:
        # GSPMD broadcasts it into the dequant either way.
        base = specs[name[: -len(SCALE_SUFFIX)]]
        if len(shape) == 1:
            spec = P(base[1])
        else:
            d0 = base[0]
            if d0 is not None and shape[0] % mesh.mesh.shape[d0]:
                d0 = None
            spec = P(d0, base[1])
    else:
        spec = specs[name]
        if len(shape) == 3 and len(spec) == 2:
            # int4 grouped 3-D store [groups, g, out] for a 2-D weight
            # spec [in, out]: the in-axis sharding moves to the groups
            # axis (whole groups per shard keeps scale rows local); the
            # in-group axis is never sharded
            d0 = spec[0]
            if d0 is not None and shape[0] % mesh.mesh.shape[d0]:
                d0 = None  # a group would span shards: replicate instead
            spec = P(d0, None, spec[1])
    return NamedSharding(mesh.mesh, spec)


def shard_decoder_params(params, cfg: DecoderConfig, mesh: MeshContext):
    return {
        k: jax.device_put(v, decoder_param_sharding(k, v.shape, cfg, mesh))
        for k, v in params.items()
    }


def shard_kv_cache(cache, cfg: DecoderConfig, mesh: MeshContext):
    specs = cache_pspecs(cfg, mesh)
    return {
        k: jax.device_put(v, NamedSharding(mesh.mesh, specs[k]))
        for k, v in cache.items()
    }


def paged_pool_pspecs(cfg: DecoderConfig, mesh: MeshContext) -> Dict[str, P]:
    """Paged KV block pool [n_blocks * block_size, kv_heads, head_dim]
    (engines/paged.py): kv heads over the model axis — decode attention
    stays local per TP shard, exactly like the dense cache — and the
    flat block-row axis REPLICATED over data.  Blocks are a shared
    resource every slot allocates from, so unlike the dense per-lane
    cache there is no batch axis to split over ``data``; the scatter /
    gather ride the unsharded row axis and insert no collective (the
    shard audit's decoder_paged_decode program holds that to the same
    one-all-reduce-per-Megatron-block budget as the dense programs).
    The looped trunk's pools hold its steps' ranges along that same
    unsharded row axis (``engines/paged.init_paged_pools``)."""
    if is_latent(cfg):  # one row a token, no head axis: replicated
        return {f"c{i}": P() for i in range(cfg.num_layers)}
    if is_hybrid(cfg):  # rows of 1-2 kv heads, lane states, the slot map
        import jax

        from docqa_tpu.engines.paged import init_paged_pools

        names = jax.eval_shape(lambda: init_paged_pools(cfg, 1, 16))
        return {name: P() for name in names}
    spec = paged_pool_sharding(mesh).spec
    out: Dict[str, P] = {}
    for i in range(cfg.num_layers):
        out[f"k{i}"] = spec
        out[f"v{i}"] = spec
    return out


def paged_pool_sharding(mesh: MeshContext) -> NamedSharding:
    """One pool's sharding (every layer's K and V pool shares it) — what
    ``engines/paged.init_paged_pools`` allocates under on a mesh."""
    return NamedSharding(mesh.mesh, P(None, mesh.model_axis, None))
