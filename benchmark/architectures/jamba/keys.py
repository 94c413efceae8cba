"""The published keys of a Jamba ``config.json`` as dotted overrides of
the program's ``Config`` (standard library).

Every published key is MAPPED to a field of the program's decoder, FIXED
(the program's block has no setting for it: it is this value), or IGNORED
by name (it says nothing about the forward pass this benchmark runs).  A
key that is none of the three, or a mapped key the file lacks, is a
``ConfigError`` that names it."""

from __future__ import annotations

from harness.arch import ConfigError, model_keys

TO_DECODER = {
    "hidden_size": "hidden_dim",
    "intermediate_size": "mlp_dim",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "vocab_size": "vocab_size",
    "max_position_embeddings": "max_seq_len",
    "rms_norm_eps": "norm_eps",
    "mamba_d_state": "ssm_state_dim",
    "mamba_d_conv": "ssm_conv_width",
    "mamba_dt_rank": "ssm_dt_rank",
    "mamba_expand": "ssm_expand",
    "mamba_conv_bias": "ssm_conv_bias",
    "mamba_proj_bias": "ssm_proj_bias",
    "tie_word_embeddings": "tie_embeddings",
}
# the layer order: layer i is attention iff i % period == offset
# (modeling_jamba.py; the catalog row lists the order as not given)
ORDER = ("attn_layer_period", "attn_layer_offset")
# published keys the program's block has no setting for: it is this value.
# ``num_experts`` 1: every layer's feed-forward is ONE dense SwiGLU MLP
# (the larger Jambas' sparse-MoE feed-forward is not brought)
FIXED = {
    "model_type": "jamba", "hidden_act": "silu", "sliding_window": None,
    "num_experts": 1,
}
# read by nobody here, whatever they state: which layers WOULD hold experts
# and how many a token would take (nothing at one expert), which kernels
# the family's own code picks, how many logits its generate() keeps
IGNORED = (
    "expert_layer_offset", "expert_layer_period", "num_experts_per_tok",
    "use_mamba_kernels", "num_logits_to_keep",
)
# the file's own statement of the served types (no published key)
OWN = ("torch_dtype",)
REQUIRED = tuple(TO_DECODER) + ORDER + ("num_experts", "sliding_window")
# the program's names of the two mixers
ATTENTION, MAMBA = "attention", "mamba"


def mixer_types(conf: dict) -> tuple:
    """One mixer name a layer, from the period and the offset."""
    period, offset = (int(conf[k]) for k in ORDER)
    return tuple(
        ATTENTION if i % period == offset else MAMBA
        for i in range(int(conf["num_hidden_layers"])))


def program_overrides(conf: dict) -> dict:
    """``decoder.*`` overrides for the program's ``load_config``.  A model
    key this block does not know is an error, not silence."""
    model = model_keys(conf)
    known = (set(TO_DECODER) | set(ORDER) | set(FIXED) | set(IGNORED)
             | set(OWN))
    unknown = sorted(set(model) - known)
    if unknown:
        raise ConfigError(
            'no key of architecture "jamba": '
            + ", ".join(f'"{k}"' for k in unknown)
            + f" (it maps {sorted(known)})"
        )
    missing = [k for k in REQUIRED if k not in model]
    if missing:
        raise ConfigError(
            'architecture "jamba" needs the keys '
            + ", ".join(f'"{k}"' for k in missing)
        )
    for key, value in FIXED.items():
        if key in model and model[key] != value:
            raise ConfigError(
                f'key "{key}": the block runs {value!r} only, '
                f"the file states {model[key]!r}"
            )
    heads, hidden = model["num_attention_heads"], model["hidden_size"]
    period, offset = (model[k] for k in ORDER)
    if hidden % heads or not 0 <= offset < period:
        raise ConfigError(
            'keys "hidden_size" / "num_attention_heads" / '
            '"attn_layer_period" / "attn_layer_offset": a head is '
            "hidden_size / num_attention_heads wide and the offset lies "
            f"inside the period; the file states {hidden} / {heads} / "
            f"{period} / {offset}"
        )
    out = {f"decoder.{TO_DECODER[k]}": model[k] for k in TO_DECODER}
    out["decoder.head_dim"] = hidden // heads
    out["decoder.mixer_types"] = mixer_types(model)
    out["decoder.block"] = "sparse_linear"
    # what SALA's stack does around its softmax and this family does not
    out["decoder.qk_norm"] = False
    out["decoder.use_output_gate"] = False
    out["decoder.use_output_norm"] = False
    out["decoder.dtype"] = model.get("torch_dtype", "bfloat16")
    return out
