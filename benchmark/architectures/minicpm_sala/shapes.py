"""Bytes a decode step must move and operations a prefill must do, from
shapes alone (standard library).

Kept with the benchmark so that a PR which speeds a step up cannot also
change what the step is charged with.  ``conf`` is the configuration file
(published keys; ``sparse_config`` over the family's sizes, ``keys.py``).
"""

from __future__ import annotations

from typing import Dict, Optional

from .keys import sparse_config

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
STATE_BYTES = 4  # a lane's state is float32 whatever the activations are


def _act(conf: Dict) -> int:
    return _BYTES[conf.get("torch_dtype", "bfloat16")]


def _layers(conf: Dict, kind: str) -> int:
    return sum(1 for m in conf["mixer_types"] if m == kind)


def layer_matrix_params(conf: Dict, kind: str) -> int:
    """Matrix parameters of one layer of a mixer kind: q, k, v, the output
    gate, o, and the SwiGLU MLP."""
    h, m = conf["hidden_size"], conf["intermediate_size"]
    if kind == "lightning-attn":
        width = conf["lightning_nh"] * conf["lightning_head_dim"]
        mixer = 5 * h * width
    else:
        q = conf["num_attention_heads"] * conf["head_dim"]
        kv = conf["num_key_value_heads"] * conf["head_dim"]
        mixer = 3 * h * q + 2 * h * kv
    return mixer + 3 * h * m


def layer_out_channels(conf: Dict, kind: str) -> int:
    """Output channels of one layer's matrices: an int8 tree keeps one
    float32 scale each."""
    h, m = conf["hidden_size"], conf["intermediate_size"]
    if kind == "lightning-attn":
        width = conf["lightning_nh"] * conf["lightning_head_dim"]
        return 4 * width + h + 2 * m + h
    q = conf["num_attention_heads"] * conf["head_dim"]
    kv = conf["num_key_value_heads"] * conf["head_dim"]
    return 2 * q + 2 * kv + h + 2 * m + h


def matrix_params(conf: Dict) -> Dict[str, int]:
    """Matrix parameters by part (9,476.8 M in all at the published
    sizes): the layers, the output head, the embedding."""
    layers = sum(
        _layers(conf, kind) * layer_matrix_params(conf, kind)
        for kind in ("lightning-attn", "minicpm4"))
    ends = conf["vocab_size"] * conf["hidden_size"]
    return {"layers": layers, "head": ends, "embedding": ends}


def decoder_weight_bytes(conf: Dict) -> Dict[str, float]:
    """Bytes of the tensors a step streams at their stored width (int8: 1
    B a parameter and a float32 scale an output channel; the norm gains in
    the activation type) and, apart, of the embedding: a step gathers a
    few rows of it, it does not stream it."""
    act = _act(conf)
    quant = conf.get("weight_quantization") == "int8"
    count = matrix_params(conf)
    mats = (count["layers"] + count["head"]) * (1 if quant else act)
    scales = 0
    if quant:
        scales = 4 * (conf["vocab_size"] + sum(
            _layers(conf, kind) * layer_out_channels(conf, kind)
            for kind in ("lightning-attn", "minicpm4")))
    h = conf["hidden_size"]
    gains = h + conf["num_hidden_layers"] * 2 * h + (
        _layers(conf, "lightning-attn") * (
            2 * conf["lightning_head_dim"]
            + conf["lightning_nh"] * conf["lightning_head_dim"])
        + _layers(conf, "minicpm4") * 2 * conf["head_dim"])
    return {"streamed": mats + scales + gains * act,
            "embedding": count["embedding"] * act}


def kv_row_bytes(conf: Dict) -> int:
    """Bytes one token leaves in ONE sparse layer's K and V pools (1,024
    at 2 kv heads x 128 in bfloat16)."""
    return 2 * conf["num_key_value_heads"] * conf["head_dim"] * _act(conf)


def kv_bytes_per_token(conf: Dict) -> int:
    """Bytes a token leaves in the cache across the sparse layers: K and V
    rows and its share of a compressed key (one per ``kernel_stride``
    tokens): 8,448 at the published sizes.  Linear layers keep no row."""
    stride = sparse_config(conf)["kernel_stride"]
    per_layer = kv_row_bytes(conf) + kv_row_bytes(conf) // 2 // stride
    return _layers(conf, "minicpm4") * per_layer


def lane_state_bytes(conf: Dict) -> int:
    """Bytes of one lane's state across the linear layers (50,331,648)."""
    d = conf["lightning_head_dim"]
    return _layers(conf, "lightning-attn") * conf["lightning_nh"] * d * d * (
        STATE_BYTES)


def least_lanes(conf: Dict, live_kv_tokens: float) -> float:
    """The fewest lanes that hold ``live_kv_tokens``: each at most
    ``max_position_embeddings``."""
    return live_kv_tokens / conf["max_position_embeddings"]


def state_step_bytes(conf: Dict, live_kv_tokens: float) -> float:
    """What a decode step moves of lane state at the least: every live
    lane's state is READ and WRITTEN once."""
    return 2 * lane_state_bytes(conf) * least_lanes(conf, live_kv_tokens)


def decode_step_min_bytes(conf: Dict, live_kv_tokens: float,
                          chips: int) -> float:
    """The least one chip must move for one decode step of the whole
    batch: the streamed weights; per live lane the state read and written;
    of the cache the rows of the blocks a lane's query TAKES — at most
    ``topk * block_size`` a lane and sparse layer, however long the lane —
    and the lane's compressed keys.  Lanes are counted at their fewest
    (``least_lanes``).  Bandwidth-bound at these batch sizes."""
    sparse = sparse_config(conf)
    lanes = least_lanes(conf, live_kv_tokens)
    taken = min(conf["max_position_embeddings"],
                sparse["topk"] * sparse["block_size"])
    rows = lanes * taken * kv_row_bytes(conf) * _layers(conf, "minicpm4")
    keys = live_kv_tokens * (
        kv_bytes_per_token(conf)
        - _layers(conf, "minicpm4") * kv_row_bytes(conf))
    w = decoder_weight_bytes(conf)["streamed"]
    return (w + state_step_bytes(conf, live_kv_tokens) + rows + keys) / chips


def prefill_flops(conf: Dict, tokens: float,
                  prompt_len: Optional[float] = None) -> float:
    """The least arithmetic of cold prefills over ``tokens`` prompt tokens
    in prompts of ``prompt_len`` (default: the mean base length of the
    file's ``check`` block, the lengths the cell sends): 2 x the layers'
    matrix parameters a token; a linear layer's recurrence, ``4 d^2`` a
    token and head (the update and the read of a [d, d] state); a sparse
    layer's attention over the rows of the blocks a row takes, ``4 d`` a
    key and query head, and its selection scores, ``2 d`` a window."""
    if prompt_len is None:
        lengths = conf["check"]["prompt_lengths"]
        prompt_len = sum(lengths) / len(lengths)
    sparse = sparse_config(conf)
    count = matrix_params(conf)
    d = conf["lightning_head_dim"]
    linear = _layers(conf, "lightning-attn") * conf["lightning_nh"] * 4 * d * d
    taken = sparse["topk"] * sparse["block_size"]
    full = min(prompt_len, taken)
    keys = full * (full + 1) / 2 + max(prompt_len - taken, 0) * taken
    windows = prompt_len * (prompt_len / 2) / sparse["kernel_stride"]
    heads, hd = conf["num_attention_heads"], conf["head_dim"]
    sparse_a_prompt = _layers(conf, "minicpm4") * heads * (
        4 * hd * keys + 2 * hd * windows)
    prompts = tokens / prompt_len
    return (tokens * (2 * count["layers"] + linear)
            + prompts * (sparse_a_prompt + 2 * count["head"]))
