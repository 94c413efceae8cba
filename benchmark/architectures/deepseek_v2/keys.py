"""The published keys of a DeepSeek-V2 ``config.json`` as dotted overrides
of the program's ``Config`` (standard library).

Two keys are the file's own, beside the published ones, because a
configuration here is ONE CHIP'S SHARE of a layer's experts (the
``model-configs`` guide, section 4): ``n_routed_experts`` then counts the
experts HELD (and is listed in ``reduced``), ``router_experts`` states the
published count — the router's width, which is never cut — and
``experts_held_start`` the first expert id of the range held."""

from __future__ import annotations

from harness.arch import ConfigError, model_keys

TO_DECODER = {
    "hidden_size": "hidden_dim",
    "intermediate_size": "mlp_dim",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "vocab_size": "vocab_size",
    "max_position_embeddings": "max_seq_len",
    "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta",
    "q_lora_rank": "q_lora_rank",
    "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim",
    "first_k_dense_replace": "first_dense_layers",
    "moe_intermediate_size": "expert_dim",
    "n_shared_experts": "num_shared_experts",
    "num_experts_per_tok": "experts_per_token",
    "n_group": "expert_groups",
    "topk_group": "expert_groups_per_token",
    "routed_scaling_factor": "routed_scale",
    "n_routed_experts": "experts_held",
    "router_experts": "num_experts",
    "experts_held_start": "experts_held_start",
}
ROPE_SCALING = {
    "factor": "rope_scaling_factor",
    "original_max_position_embeddings": "rope_original_max_len",
    "beta_fast": "rope_beta_fast",
    "beta_slow": "rope_beta_slow",
    "mscale": "rope_mscale",
    "mscale_all_dim": "rope_mscale_all_dim",
}
# published keys the program's block has no setting for: it is this value.
# ``seq_aux`` (the sequence-wise balance loss) is training-only: nothing of
# it exists at inference.
FIXED = {
    "model_type": "deepseek_v2", "hidden_act": "silu",
    "attention_bias": False, "moe_layer_freq": 1, "norm_topk_prob": False,
    "scoring_func": "softmax", "topk_method": "group_limited_greedy",
    "tie_word_embeddings": False, "seq_aux": True,
}
REQUIRED = ("router_experts", "n_routed_experts", "qk_nope_head_dim",
            "qk_rope_head_dim", "num_attention_heads")


def program_overrides(conf: dict) -> dict:
    """``decoder.*`` overrides for the program's ``load_config``.  A model
    key this block does not know is an error, not silence."""
    model = model_keys(conf)
    known = set(TO_DECODER) | set(FIXED) | {
        "rope_scaling", "num_key_value_heads", "torch_dtype"}
    unknown = sorted(set(model) - known)
    if unknown:
        raise ConfigError(
            'no key of architecture "deepseek_v2": '
            + ", ".join(f'"{k}"' for k in unknown)
            + f" (it maps {sorted(known)})"
        )
    missing = [k for k in REQUIRED if k not in model]
    if missing:
        raise ConfigError(
            'architecture "deepseek_v2" needs the keys '
            + ", ".join(f'"{k}"' for k in missing)
        )
    for key, value in FIXED.items():
        if key in model and model[key] != value:
            raise ConfigError(
                f'key "{key}": the block runs {value!r} only, '
                f"the file states {model[key]!r}"
            )
    heads = model["num_attention_heads"]
    if model.get("num_key_value_heads", heads) != heads:
        raise ConfigError(
            'key "num_key_value_heads": latent attention gives every head '
            f"its own up-projected key and value ({heads}), the file states "
            f"{model['num_key_value_heads']!r}"
        )
    out = {f"decoder.{TO_DECODER[k]}": model[k]
           for k in TO_DECODER if k in model}
    scaling = dict(model.get("rope_scaling") or {})
    if scaling:
        if scaling.pop("type", "yarn") != "yarn":
            raise ConfigError('key "rope_scaling": the block runs "yarn" only')
        strange = sorted(set(scaling) - set(ROPE_SCALING))
        if strange:
            raise ConfigError(
                f'key "rope_scaling": no setting for {strange}'
            )
        for key, value in scaling.items():
            cast = int if key == "original_max_position_embeddings" else float
            out[f"decoder.{ROPE_SCALING[key]}"] = cast(value)
    out["decoder.block"] = "mla_moe"
    # what the program's generic fields mean for this block: the query/key
    # width, and ONE cached row a token that every head reads
    out["decoder.head_dim"] = (
        model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    )
    out["decoder.num_kv_heads"] = 1
    out["decoder.dtype"] = model.get("torch_dtype", "bfloat16")
    return out
