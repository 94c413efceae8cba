"""Bytes and operations a step must move, from shapes alone (stdlib).

Kept with the benchmark so that a PR which speeds a kernel up cannot also
change what the kernel is charged with.
"""

from __future__ import annotations

from typing import Dict

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


def decoder_weight_bytes(conf: Dict) -> Dict[str, float]:
    """Bytes of the decoder's tensors at their stored width (the embedding
    apart: a step gathers a few rows of it, it does not stream it)."""
    h, m = conf["hidden_size"], conf["intermediate_size"]
    heads, kv = conf["num_attention_heads"], conf["num_key_value_heads"]
    d = conf.get("head_dim") or h // heads
    layers, vocab = conf["num_hidden_layers"], conf["vocab_size"]
    act = _BYTES[conf.get("torch_dtype", "bfloat16")]
    quant = conf.get("weight_quantization") == "int8"
    wb = 1 if quant else act
    per_layer = (h * heads * d + 2 * h * kv * d + heads * d * h + 3 * h * m)
    mats = layers * per_layer + h * vocab  # lm_head streams every step
    scales = 0
    if quant:  # one float32 scale per output channel
        scales = 4 * (layers * (heads * d + 2 * kv * d + h + 2 * m + h) + vocab)
    return {
        "streamed": mats * wb + scales + (2 * layers + 1) * h * act,
        "embedding": vocab * h * act,
    }


def kv_bytes_per_token(conf: Dict) -> int:
    h, heads = conf["hidden_size"], conf["num_attention_heads"]
    d = conf.get("head_dim") or h // heads
    act = _BYTES[conf.get("torch_dtype", "bfloat16")]
    return 2 * conf["num_hidden_layers"] * conf["num_key_value_heads"] * d * act


def decode_step_min_bytes(conf: Dict, live_kv_tokens: float, chips: int) -> float:
    """The least one chip must read from HBM for one decode step of the
    whole batch: its share of the streamed weights (tensor parallelism
    divides them by ``chips``) plus its share of the LIVE keys and values
    (kv heads are divided the same way).  Decode at these batch sizes is
    bandwidth-bound: 2 FLOPs per weight byte per sequence is far below the
    chip's ~240 FLOP/byte ridge."""
    w = decoder_weight_bytes(conf)["streamed"]
    return (w + live_kv_tokens * kv_bytes_per_token(conf)) / chips
