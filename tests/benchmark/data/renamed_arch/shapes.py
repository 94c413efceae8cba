"""Bytes a step must move, read from this package's own key names
(standard library)."""


def decoder_weight_bytes(conf):
    h, m, layers = conf["d_model"], conf["d_ff"], conf["n_layers"]
    q, kv = conf["n_heads"] * conf["d_head"], conf["n_kv_heads"] * conf["d_head"]
    wb = 1 if conf.get("weights_in") == "int8" else 2
    outs = layers * (q + 2 * kv + h + 2 * m + h) + conf["vocab"]
    mats = layers * (2 * h * q + 2 * h * kv + 3 * h * m) + h * conf["vocab"]
    return {
        "streamed": mats * wb + (4 * outs if wb == 1 else 0)
        + (2 * layers + 1) * h * 2,
        "embedding": conf["vocab"] * h * 2,
    }


def kv_bytes_per_token(conf):
    return 2 * conf["n_layers"] * conf["n_kv_heads"] * conf["d_head"] * 2


def decode_step_min_bytes(conf, live_kv_tokens, chips):
    streamed = decoder_weight_bytes(conf)["streamed"]
    return (streamed + live_kv_tokens * kv_bytes_per_token(conf)) / chips
