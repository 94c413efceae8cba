"""The published keys of a Brumby ``config.json`` as dotted overrides of
the program's ``Config`` (standard library).

Every published key is MAPPED to a field of the program's decoder, FIXED
(the program's block has no setting for it: it is this value), or IGNORED
by name (it says nothing about the forward pass this benchmark runs).  A
key that is none of the three, or a mapped key the file lacks, is a
``ConfigError`` that names it.

What the row does not carry — the degree of the power, the gate's
projection, the normaliser — is the family's published convention
(arXiv:2507.04239 and its ``retention`` package), listed under the file's
``assumed``; the program has ONE such mixer, ``retention``, and no setting
for any of them."""

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
    "tie_word_embeddings": "tie_embeddings",
}
# published keys the program's block has no setting for: it is this value.
# No window anywhere: the trunk's three window keys say so together.
FIXED = {
    "model_type": "brumby", "hidden_act": "silu", "attention_bias": False,
    "rope_scaling": None, "sliding_window": None,
    "use_sliding_window": False,
}
# read by nobody, whatever it states: the layer from which the trunk WOULD
# window (nothing while ``use_sliding_window`` is false)
IGNORED = ("max_window_layers",)
# the file's own statement of the served types (no published key)
OWN = ("torch_dtype", "weight_quantization")
REQUIRED = tuple(TO_DECODER) + ("sliding_window", "use_sliding_window")
# the program's name of the one mixer
RETENTION = "retention"


def program_overrides(conf: dict) -> dict:
    """``decoder.*`` overrides for the program's ``load_config``.  A model
    key this block does not know is an error, not silence."""
    model = model_keys(conf)
    known = set(TO_DECODER) | set(FIXED) | set(IGNORED) | set(OWN)
    unknown = sorted(set(model) - known)
    if unknown:
        raise ConfigError(
            'no key of architecture "brumby": '
            + ", ".join(f'"{k}"' for k in unknown)
            + f" (it maps {sorted(known)})"
        )
    missing = [k for k in REQUIRED if k not in model]
    if missing:
        raise ConfigError(
            'architecture "brumby" needs the keys '
            + ", ".join(f'"{k}"' for k in missing)
        )
    for key, value in FIXED.items():
        if key in model and model[key] != value:
            raise ConfigError(
                f'key "{key}": the block runs {value!r} only, '
                f"the file states {model[key]!r}"
            )
    heads = model["num_attention_heads"]
    kv_heads = model["num_key_value_heads"]
    if heads % kv_heads or model["head_dim"] % 2:
        raise ConfigError(
            'keys "num_attention_heads" / "num_key_value_heads" / '
            '"head_dim": a kv head\'s state is read by a whole number of '
            "query heads and a head is rotated in halves; the file states "
            f"{heads} / {kv_heads} / {model['head_dim']}"
        )
    quant = model.get("weight_quantization")
    if quant not in (None, "int8"):
        raise ConfigError(
            f'key "weight_quantization": int8 or absent, the file states '
            f"{quant!r}")
    out = {f"decoder.{TO_DECODER[k]}": model[k] for k in TO_DECODER}
    out["decoder.rope_theta"] = float(model["rope_theta"])
    out["decoder.mixer_types"] = (RETENTION,) * int(
        model["num_hidden_layers"])
    out["decoder.block"] = "sparse_linear"
    # Qwen3's per-head q / k norms stay; the retention has no output gate
    # and no output norm
    out["decoder.qk_norm"] = True
    out["decoder.use_output_gate"] = False
    out["decoder.use_output_norm"] = False
    out["decoder.dtype"] = model.get("torch_dtype", "bfloat16")
    return out
