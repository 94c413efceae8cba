"""The published keys of an Ouro ``config.json`` as dotted overrides of
the program's ``Config`` (standard library).

Every published key is MAPPED to a field of the program's decoder, FIXED
(the program's block has no setting for it: it is this value), or IGNORED
by name (it says nothing the forward pass reads at the fixed values).  A
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
    "head_dim": "head_dim",
    "vocab_size": "vocab_size",
    "max_position_embeddings": "max_seq_len",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "total_ut_steps": "loop_steps",
    "early_exit_threshold": "loop_exit_threshold",
}
# published keys the program's block has no setting for: it is this value.
# No window anywhere (``sliding_window`` null, ``use_sliding_window``
# false), plain RoPE (``rope_scaling`` null), an untied head
FIXED = {
    "model_type": "ouro", "hidden_act": "silu", "tie_word_embeddings": False,
    "sliding_window": None, "use_sliding_window": False, "rope_scaling": None,
}
# say nothing at the fixed values: the layer from which a window WOULD
# apply; ``layer_types`` is held to all-"full_attention" below
IGNORED = ("max_window_layers",)
# the file's own statement of the served type (no published key)
OWN = ("torch_dtype",)
REQUIRED = tuple(TO_DECODER)


def program_overrides(conf: dict) -> dict:
    """``decoder.*`` overrides for the program's ``load_config``.  A model
    key this block does not know is an error, not silence."""
    model = model_keys(conf)
    known = (set(TO_DECODER) | set(FIXED) | set(IGNORED) | set(OWN)
             | {"layer_types"})
    unknown = sorted(set(model) - known)
    if unknown:
        raise ConfigError(
            'no key of architecture "ouro": '
            + ", ".join(f'"{k}"' for k in unknown)
            + f" (it maps {sorted(known)})"
        )
    missing = [k for k in REQUIRED if k not in model]
    if missing:
        raise ConfigError(
            'architecture "ouro" needs the keys '
            + ", ".join(f'"{k}"' for k in missing)
        )
    for key, value in FIXED.items():
        if key in model and model[key] != value:
            raise ConfigError(
                f'key "{key}": the block runs {value!r} only, '
                f"the file states {model[key]!r}"
            )
    kinds = model.get("layer_types")
    if kinds is not None and (
            len(kinds) != model["num_hidden_layers"]
            or set(kinds) != {"full_attention"}):
        raise ConfigError(
            'key "layer_types": the block runs "full_attention" in every '
            f"one of num_hidden_layers layers, the file states {kinds!r}"
        )
    if model["early_exit_threshold"] != 1:
        raise ConfigError(
            'key "early_exit_threshold": the program runs every step for '
            "every lane (1); a threshold under 1 needs trained gates and a "
            f"scheduler, the file states {model['early_exit_threshold']!r}"
        )
    out = {f"decoder.{TO_DECODER[k]}": model[k] for k in TO_DECODER}
    out["decoder.loop_exit_threshold"] = float(model["early_exit_threshold"])
    out["decoder.rope_theta"] = float(model["rope_theta"])
    out["decoder.sandwich_norm"] = True  # the family's four-norm layer
    out["decoder.sliding_window"] = None
    out["decoder.dtype"] = model.get("torch_dtype", "bfloat16")
    return out
