"""The published keys of a Mistral-style ``config.json`` as dotted
overrides of the program's ``Config`` (standard library)."""

from __future__ import annotations

from harness.arch import ConfigError, model_keys

HF_TO_DECODER = {
    "hidden_size": "hidden_dim",
    "intermediate_size": "mlp_dim",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "vocab_size": "vocab_size",
    "max_position_embeddings": "max_seq_len",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "sliding_window": "sliding_window",
}
# published keys the program's block has no setting for: it is this value
FIXED = {"model_type": "mistral", "hidden_act": "silu",
         "tie_word_embeddings": False}


def program_overrides(conf: dict) -> dict:
    """``decoder.*`` overrides for the program's ``load_config``.  A model
    key this block does not know is an error, not silence."""
    model = model_keys(conf)
    # "weight_quantization" is read by shapes.py (bytes a weight); the
    # program's settings for it are the file's ``serving`` block
    known = set(HF_TO_DECODER) | set(FIXED) | {"torch_dtype",
                                               "weight_quantization"}
    unknown = sorted(set(model) - known)
    if unknown:
        raise ConfigError(
            "no key of architecture \"mistral\": "
            + ", ".join(f'"{k}"' for k in unknown)
            + f" (it maps {sorted(known)})"
        )
    for key, value in FIXED.items():
        if key in model and model[key] != value:
            raise ConfigError(
                f'key "{key}": the block runs {value!r} only, '
                f"the file states {model[key]!r}"
            )
    out = {f"decoder.{HF_TO_DECODER[k]}": model[k]
           for k in HF_TO_DECODER if k in model}
    out["decoder.dtype"] = model.get("torch_dtype", "bfloat16")
    return out
