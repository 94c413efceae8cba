"""The published keys of an AFMoE ``config.json`` as dotted overrides of
the program's ``Config`` (standard library).

Every published key is MAPPED to a field of the program's decoder, FIXED
(the program's block has no setting for it: it is this value), or IGNORED
by name (it says nothing about the forward pass this benchmark runs).  A
key that is none of the three, or a mapped key the file lacks, is a
``ConfigError`` that names it.

Two keys are the file's own, beside the published ones, because a
configuration here is ONE CHIP'S SHARE of a layer's experts (the
``model-configs`` guide, section 4): ``num_experts`` then counts the
experts HELD (and is listed in ``reduced``), ``router_experts`` states the
published count — the router's width, which is never cut — and
``experts_held_start`` the first expert id of the range held."""

from __future__ import annotations

import dataclasses
import math

from harness.arch import ConfigError, model_keys

TO_DECODER = {
    "hidden_size": "hidden_dim",
    "intermediate_size": "mlp_dim",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "vocab_size": "vocab_size",
    "max_position_embeddings": "max_seq_len",
    "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta",
    "sliding_window": "sliding_window",
    "num_dense_layers": "first_dense_layers",
    "moe_intermediate_size": "expert_dim",
    "num_shared_experts": "num_shared_experts",
    "num_experts_per_tok": "experts_per_token",
    "n_group": "expert_groups",
    "topk_group": "expert_groups_per_token",
    "route_scale": "routed_scale",
    "route_norm": "router_norm",
    "score_func": "router_score",
    "num_experts": "experts_held",
    "router_experts": "num_experts",
    "experts_held_start": "experts_held_start",
}
# the published names of the two attention kinds -> the program's mixers
MIXER_OF = {"sliding_attention": "window", "full_attention": "attention"}
WINDOW, ATTENTION = MIXER_OF["sliding_attention"], MIXER_OF["full_attention"]
# published keys the program's block has no setting for: it is this value.
# ``num_expert_groups`` / ``num_limited_groups`` restate ``n_group`` /
# ``topk_group`` (1: no group limit)
FIXED = {
    "model_type": "afmoe", "hidden_act": "silu", "rope_scaling": None,
    "tie_word_embeddings": False, "mup_enabled": True,
    "num_expert_groups": 1, "num_limited_groups": 1,
}
# read by nobody here, whatever they state: the balance loss's weight
# (training), which grouped-matmul kernel the family's own code picks, and
# the period that ``layer_types`` already spells out layer by layer
IGNORED = ("load_balance_coeff", "use_grouped_mm",
           "global_attn_every_n_layers")
OWN = ("torch_dtype",)
REQUIRED = tuple(TO_DECODER) + ("layer_types",)


def _program_fields() -> set:
    """The fields of the program's ``DecoderConfig`` (``docqa_tpu/config.py``
    imports the standard library alone); every mapped field where the
    program cannot be imported, so that the check says nothing."""
    try:
        from docqa_tpu.config import DecoderConfig
    except ImportError:
        return set(TO_DECODER.values())
    return {f.name for f in dataclasses.fields(DecoderConfig)}


def mixer_types(conf: dict) -> tuple:
    """One mixer name a layer, from ``layer_types``."""
    return tuple(MIXER_OF[t] for t in conf["layer_types"])


def program_overrides(conf: dict) -> dict:
    """``decoder.*`` overrides for the program's ``load_config``.  A model
    key this block does not know is an error, not silence."""
    model = model_keys(conf)
    known = (set(TO_DECODER) | {"layer_types"} | set(FIXED) | set(IGNORED)
             | set(OWN))
    unknown = sorted(set(model) - known)
    if unknown:
        raise ConfigError(
            'no key of architecture "afmoe": '
            + ", ".join(f'"{k}"' for k in unknown)
            + f" (it maps {sorted(known)})"
        )
    missing = [k for k in REQUIRED if k not in model]
    if missing:
        raise ConfigError(
            'architecture "afmoe" needs the keys '
            + ", ".join(f'"{k}"' for k in missing)
        )
    for key, value in FIXED.items():
        if key in model and model[key] != value:
            raise ConfigError(
                f'key "{key}": the block runs {value!r} only, '
                f"the file states {model[key]!r}"
            )
    kinds = model["layer_types"]
    strange = sorted(set(kinds) - set(MIXER_OF))
    if strange or len(kinds) != model["num_hidden_layers"]:
        raise ConfigError(
            'key "layer_types": one of '
            f"{sorted(MIXER_OF)} a layer, num_hidden_layers of them; the "
            f"file states {len(kinds)} entries" + (
                f" and names {strange}" if strange else "")
        )
    out = {f"decoder.{TO_DECODER[k]}": model[k] for k in TO_DECODER}
    lacking = sorted(set(TO_DECODER.values()) - _program_fields())
    if lacking:
        raise ConfigError(
            'architecture "afmoe": this program\'s DecoderConfig has no '
            + ", ".join(f'"{f}"' for f in lacking)
            + " (it cannot run the architecture: window layers beside "
            "global ones and a sigmoid router with a selection bias came "
            "with PR 48)"
        )
    out["decoder.mixer_types"] = mixer_types(model)
    out["decoder.block"] = "sparse_linear"
    # what the family's layer does around its softmax and its sublayers
    # (modeling_afmoe.py; the file's ``assumed`` says where each is from)
    out["decoder.qk_norm"] = True
    out["decoder.use_output_gate"] = True
    out["decoder.use_output_norm"] = False
    out["decoder.sandwich_norm"] = True
    out["decoder.router_bias"] = True
    out["decoder.scale_emb"] = math.sqrt(model["hidden_size"])  # mup_enabled
    out["decoder.dtype"] = model.get("torch_dtype", "bfloat16")
    return out
