"""Bytes a decode step must move and operations a prefill must do, from
shapes alone (standard library).

Kept with the benchmark so that a PR which speeds a step up cannot also
change what the step is charged with.  ``conf`` is the configuration file
(published keys, ``keys.py``).  The state is charged in its SYMMETRIC
layout — ``d (d + 1) / 2`` features by ``d + 1`` columns a kv head, float32
— whatever layout the program builds."""

from __future__ import annotations

from typing import Dict, Optional

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
STATE_BYTES = 4  # a lane's state is float32 whatever the activations are


def _act(conf: Dict) -> int:
    return _BYTES[conf.get("torch_dtype", "bfloat16")]


def scan_layers(conf: Dict) -> int:
    """Layers whose prefill scans: every one is a retention layer."""
    return conf["num_hidden_layers"]


def features(conf: Dict) -> int:
    """Degree-2 features of one key: ``d (d + 1) / 2`` (8,256 at 128)."""
    d = conf["head_dim"]
    return d * (d + 1) // 2


def layer_matrix_params(conf: Dict) -> int:
    """Matrix parameters of one layer a matmul streams quantized: q, o,
    k, v and the SwiGLU MLP (330,301,440 at the published sizes)."""
    h, m, d = (conf["hidden_size"], conf["intermediate_size"],
               conf["head_dim"])
    q, kv = conf["num_attention_heads"] * d, conf["num_key_value_heads"] * d
    return 2 * h * q + 2 * h * kv + 3 * h * m


def layer_out_channels(conf: Dict) -> int:
    """Output channels of those matrices: an int8 tree keeps one float32
    scale each."""
    h, m, d = (conf["hidden_size"], conf["intermediate_size"],
               conf["head_dim"])
    q, kv = conf["num_attention_heads"] * d, conf["num_key_value_heads"] * d
    return q + 2 * kv + h + 2 * m + h


def layer_small_params(conf: Dict) -> int:
    """What a layer keeps in the activation type whatever the matrices
    are: the decay projection [hidden, kv heads] (5120 x 8: never
    quantized), the two layer norms and the two head norms."""
    h = conf["hidden_size"]
    return (h * conf["num_key_value_heads"] + 2 * h
            + 2 * conf["head_dim"])


def matrix_params(conf: Dict) -> Dict[str, int]:
    """Matrix parameters by part: the layers, the output head, the
    embedding (777,912,320 each of the two at the published sizes)."""
    ends = conf["vocab_size"] * conf["hidden_size"]
    return {"layers": conf["num_hidden_layers"] * layer_matrix_params(conf),
            "head": ends, "embedding": ends}


def decoder_weight_bytes(conf: Dict) -> Dict[str, float]:
    """Bytes of the tensors a step streams at their stored width (int8: 1
    B a parameter and a float32 scale an output channel; the decay
    projection and the norm gains in the activation type) and, apart, of
    the embedding: a step gathers a few rows of it, it does not stream
    it."""
    act = _act(conf)
    quant = conf.get("weight_quantization") == "int8"
    count = matrix_params(conf)
    mats = (count["layers"] + count["head"]) * (1 if quant else act)
    scales = 0
    if quant:
        scales = 4 * (conf["vocab_size"]
                      + conf["num_hidden_layers"] * layer_out_channels(conf))
    small = conf["hidden_size"] + (
        conf["num_hidden_layers"] * layer_small_params(conf))
    return {"streamed": mats + scales + small * act,
            "embedding": count["embedding"] * act}


def kv_bytes_per_token(conf: Dict) -> int:
    """Bytes a token leaves in the cache: none.  No layer keeps a row."""
    return 0


def head_state_bytes(conf: Dict) -> int:
    """Bytes of ONE kv head's state in one layer: ``d (d + 1) / 2`` x
    ``d + 1`` float32 (4,260,096 at 128: 8,256 x 129)."""
    return features(conf) * (conf["head_dim"] + 1) * STATE_BYTES


def lane_state_bytes(conf: Dict) -> int:
    """Bytes of one lane's state across the layers (408,969,216 at 12
    layers x 8 kv heads)."""
    return (conf["num_hidden_layers"] * conf["num_key_value_heads"]
            * head_state_bytes(conf))


def least_lanes(conf: Dict, live_kv_tokens: float) -> float:
    """The fewest lanes that hold ``live_kv_tokens`` positions: each at
    most ``max_position_embeddings``."""
    return live_kv_tokens / conf["max_position_embeddings"]


def state_step_min_bytes(conf: Dict, live_kv_tokens: float) -> float:
    """What a decode step moves of lane state at the least: every live
    lane's state — counted at the fewest lanes — is READ and WRITTEN
    once."""
    return 2 * lane_state_bytes(conf) * least_lanes(conf, live_kv_tokens)


def decode_step_min_bytes(conf: Dict, live_kv_tokens: float,
                          chips: int) -> float:
    """The least one chip must move for one decode step of the whole
    batch: the streamed weights and, per live lane, the state read and
    written once (8.0 GB at four full lanes: 4.74 of weights, 3.27 of
    state).  No cache row is read: there is none.  Bandwidth-bound at
    these batch sizes."""
    return (decoder_weight_bytes(conf)["streamed"]
            + state_step_min_bytes(conf, live_kv_tokens)) / chips


def _prompt_len(conf: Dict, prompt_len: Optional[float]) -> float:
    if prompt_len is not None:
        return prompt_len
    lengths = conf["check"]["prompt_lengths"]
    return sum(lengths) / len(lengths)


def retention_flops(conf: Dict, prompt_len: float) -> Dict[str, float]:
    """The retention's arithmetic of ONE layer, in its two forms.  A token
    and query head in the ATTENTION form: ``4 d`` a key over the prompt's
    mean causal length (scores and values; the square and the gate are
    lower order).  In the STATE form: the read ``phi(q) S``, ``2 F (d +
    1)`` a query head, and its kv head's share of the update ``phi(k) [v,
    1]^T``, the same a kv head.  Whatever form the tokens take, the state
    a lane decodes from is BUILT once a prompt: ``2 F (d + 1)`` a token
    and kv head."""
    d, f = conf["head_dim"], features(conf)
    heads, kv_heads = (conf["num_attention_heads"],
                       conf["num_key_value_heads"])
    one = 2 * f * (d + 1)
    return {
        "attention_a_token": heads * 4 * d * (prompt_len + 1) / 2,
        "state_a_token": heads * one,
        "build_a_prompt": kv_heads * one * prompt_len,
    }


def prefill_scan_min_flops(conf: Dict, tokens: float,
                           prompts: float) -> float:
    """The least arithmetic the retention of prefills over ``tokens``
    prompt tokens in ``prompts`` prompts must do across the layers,
    whatever implements it: a token and layer, the lesser of the attention
    form at the prompt's mean causal length and the state form's read;
    plus the state built once a prompt and layer.  (At 9.2k tokens the
    read is the lesser: 85.2 MFLOP a token and layer against 94.2.)"""
    r = retention_flops(conf, tokens / prompts)
    return scan_layers(conf) * (
        tokens * min(r["attention_a_token"], r["state_a_token"])
        + prompts * r["build_a_prompt"])


def prefill_flops(conf: Dict, tokens: float,
                  prompt_len: Optional[float] = None) -> float:
    """The least arithmetic of cold prefills over ``tokens`` prompt tokens
    in prompts of ``prompt_len`` (default: the mean base length of the
    file's ``check`` block, the lengths the cell sends): 2 x the layers'
    matrix parameters and the decay projection a token; the retention
    (:func:`prefill_scan_min_flops`); the head once a prompt."""
    prompt_len = _prompt_len(conf, prompt_len)
    prompts = tokens / prompt_len
    layers = conf["num_hidden_layers"]
    decay = conf["hidden_size"] * conf["num_key_value_heads"]
    return (tokens * 2 * (matrix_params(conf)["layers"] + layers * decay)
            + prefill_scan_min_flops(conf, tokens, prompts)
            + prompts * 2 * matrix_params(conf)["head"])
