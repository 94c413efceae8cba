"""The published keys of a MiniCPM-SALA ``config.json`` as dotted overrides
of the program's ``Config`` (standard library).

The row carries no ``sparse_config``; the file may state one (the family's
published sizes, MiniCPM4 / InfLLM-V2), and what it leaves out takes
``SPARSE_DEFAULTS`` — the same numbers, listed under the file's
``assumed``."""

from __future__ import annotations

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
    "lightning_nh": "linear_heads",
    "lightning_head_dim": "linear_head_dim",
    "scale_emb": "scale_emb",
    "scale_depth": "scale_depth",
    "dim_model_base": "dim_model_base",
}
# the program's names of the two published mixers
MIXERS = {"minicpm4": "sparse", "lightning-attn": "linear"}
# published keys the program's block has no setting for: it is this value.
# ``mup_denominator`` and ``rand_init`` act at initialisation and in
# training only: nothing of them exists at inference, whatever they state.
FIXED = {
    "model_type": "minicpm_sala", "hidden_act": "silu",
    "attention_bias": False, "attn_use_rope": False,
    "lightning_use_rope": True, "lightning_scale": "1/sqrt(d)",
    "qk_norm": True, "use_output_gate": True, "use_output_norm": True,
    "attn_use_output_gate": True, "tie_word_embeddings": False,
}
IGNORED = ("mup_denominator", "rand_init")
SPARSE_CONFIG = {
    "kernel_size": "sparse_kernel_size",
    "kernel_stride": "sparse_kernel_stride",
    "block_size": "sparse_block_size",
    "topk": "sparse_topk",
    "init_blocks": "sparse_init_blocks",
    "window_size": "sparse_window_size",
    "dense_len": "sparse_dense_len",
}
SPARSE_DEFAULTS = {
    "kernel_size": 32, "kernel_stride": 16, "block_size": 64, "topk": 64,
    "init_blocks": 1, "window_size": 2048, "dense_len": 8192,
}
REQUIRED = ("mixer_types", "num_hidden_layers", "lightning_nh",
            "lightning_nkv", "lightning_head_dim", "num_key_value_heads")


def sparse_config(conf: dict) -> dict:
    """The sparse sizes the file runs: its ``sparse_config`` over the
    family's."""
    return {**SPARSE_DEFAULTS, **(conf.get("sparse_config") or {})}


def program_overrides(conf: dict) -> dict:
    """``decoder.*`` overrides for the program's ``load_config``.  A model
    key this block does not know is an error, not silence."""
    model = model_keys(conf)
    known = (set(TO_DECODER) | set(FIXED) | set(IGNORED)
             | {"mixer_types", "lightning_nkv", "sparse_config",
                "torch_dtype", "weight_quantization"})
    unknown = sorted(set(model) - known)
    if unknown:
        raise ConfigError(
            'no key of architecture "minicpm_sala": '
            + ", ".join(f'"{k}"' for k in unknown)
            + f" (it maps {sorted(known)})"
        )
    missing = [k for k in REQUIRED if k not in model]
    if missing:
        raise ConfigError(
            'architecture "minicpm_sala" needs the keys '
            + ", ".join(f'"{k}"' for k in missing)
        )
    for key, value in FIXED.items():
        if key in model and model[key] != value:
            raise ConfigError(
                f'key "{key}": the block runs {value!r} only, '
                f"the file states {model[key]!r}"
            )
    if model["lightning_nkv"] != model["lightning_nh"]:
        raise ConfigError(
            'key "lightning_nkv": the linear mixer keeps one state a head '
            f"({model['lightning_nh']}), the file states "
            f"{model['lightning_nkv']!r}"
        )
    strange = sorted(set(model["mixer_types"]) - set(MIXERS))
    if strange or len(model["mixer_types"]) != model["num_hidden_layers"]:
        raise ConfigError(
            f'key "mixer_types": one of {sorted(MIXERS)} per layer '
            f"({model['num_hidden_layers']}), the file states "
            f"{len(model['mixer_types'])} names, unknown: {strange}"
        )
    odd = sorted(set(model.get("sparse_config") or {}) - set(SPARSE_CONFIG))
    if odd:
        raise ConfigError(f'key "sparse_config": no setting for {odd}')
    out = {f"decoder.{TO_DECODER[k]}": model[k]
           for k in TO_DECODER if k in model}
    out["decoder.rope_theta"] = float(model.get("rope_theta", 10000.0))
    for key, value in sparse_config(model).items():
        out[f"decoder.{SPARSE_CONFIG[key]}"] = int(value)
    out["decoder.mixer_types"] = tuple(
        MIXERS[m] for m in model["mixer_types"])
    out["decoder.block"] = "sparse_linear"
    out["decoder.dtype"] = model.get("torch_dtype", "bfloat16")
    quant = model.get("weight_quantization")
    if quant not in (None, "int8"):
        raise ConfigError(
            f'key "weight_quantization": int8 or absent, the file states '
            f"{quant!r}")
    return out
