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
from docqa_tpu.models.decoder import block_serving, decoder_param_schema
from docqa_tpu.runtime.mesh import MeshContext


def decoder_param_pspecs(cfg: DecoderConfig, model_axis: str) -> Dict[str, P]:
    """A kind with layers of its own names every per-layer parameter
    (``models/serving.BlockServing.param_pspecs``); the GQA tree takes the
    Megatron rules below, and whatever else it holds — the sandwich norms'
    gains, the looped trunk's exit gate of 2,049 numbers — is replicated."""
    m = model_axis
    specs: Dict[str, P] = {
        "tok_emb": P(None, None),  # replicated (gather-heavy; small at 7B)
        "final_norm_g": P(None),
        "lm_head": P(None, m),  # vocab-sharded logits
    }
    own = block_serving(cfg).param_pspecs
    if own is not None:
        specs.update(own(m))
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
    for name, _kind, shape, _fan_in in decoder_param_schema(cfg):
        specs.setdefault(name, P(*(None,) * len(shape)))
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
    own = block_serving(cfg).pool_pspecs
    if own is not None:  # a kind whose pools have no head axis to divide
        return own()
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
