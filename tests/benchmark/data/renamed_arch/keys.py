"""Other published names for the same sizes (standard library)."""

from harness.arch import ConfigError, model_keys

TO_DECODER = {
    "d_model": "hidden_dim", "d_ff": "mlp_dim", "n_layers": "num_layers",
    "n_heads": "num_heads", "n_kv_heads": "num_kv_heads", "d_head": "head_dim",
    "vocab": "vocab_size", "max_positions": "max_seq_len",
    "rope_base": "rope_theta", "norm_epsilon": "norm_eps",
    "attention_window": "sliding_window",
}


def program_overrides(conf):
    model = model_keys(conf)
    unknown = sorted(set(model) - set(TO_DECODER) - {"weights_in"})
    if unknown:
        raise ConfigError(f'keys {unknown} are no keys of architecture "renamed"')
    out = {f"decoder.{TO_DECODER[k]}": v for k, v in model.items()
           if k in TO_DECODER}
    out["decoder.dtype"] = "bfloat16"
    return out
