"""Bytes a decode step must move, operations a prefill must do and bytes
its scan must move, from shapes alone (standard library).

Kept with the benchmark so that a PR which speeds a step up cannot also
change what the step is charged with.  ``conf`` is the configuration file
(published keys, ``keys.py``)."""

from __future__ import annotations

from typing import Dict, Optional

from .keys import ATTENTION, MAMBA, mixer_types

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
STATE_BYTES = 4  # a lane's state and ``A_log`` are float32 whatever else is


def _act(conf: Dict) -> int:
    return _BYTES[conf.get("torch_dtype", "bfloat16")]


def _layers(conf: Dict, kind: str) -> int:
    return sum(1 for m in mixer_types(conf) if m == kind)


def scan_layers(conf: Dict) -> int:
    """Layers whose prefill scans (the Mamba layers: 26)."""
    return _layers(conf, MAMBA)


def inner(conf: Dict) -> int:
    """Channels of the state-space mixer (5,120)."""
    return conf["mamba_expand"] * conf["hidden_size"]


def head_dim(conf: Dict) -> int:
    return conf["hidden_size"] // conf["num_attention_heads"]


def mixer_params(conf: Dict, kind: str) -> Dict[str, int]:
    """Parameters of one layer's mixer by part: ``matrices`` (what a
    matmul streams: in, x, dt, out — or q, k, v, o), ``a_log`` (float32),
    ``small`` (conv taps and bias, ``b_dt``, ``D``, the three inner norm
    gains, projection biases)."""
    h = conf["hidden_size"]
    if kind == ATTENTION:
        q = conf["num_attention_heads"] * head_dim(conf)
        kv = conf["num_key_value_heads"] * head_dim(conf)
        return {"matrices": 2 * h * q + 2 * h * kv, "a_log": 0, "small": 0}
    d, n, rank = inner(conf), conf["mamba_d_state"], conf["mamba_dt_rank"]
    small = conf["mamba_d_conv"] * d + d + d + rank + 2 * n
    if conf["mamba_conv_bias"]:
        small += d
    if conf["mamba_proj_bias"]:
        small += 2 * d + h
    return {"matrices": h * 2 * d + d * (rank + 2 * n) + rank * d + d * h,
            "a_log": n * d, "small": small}


def layer_params(conf: Dict, kind: str) -> int:
    """Every parameter of one layer of a kind: the mixer, the SwiGLU MLP
    and the two norm gains (104,161,472 a Mamba layer and 76,682,240 an
    attention layer at the published sizes)."""
    h, m = conf["hidden_size"], conf["intermediate_size"]
    return sum(mixer_params(conf, kind).values()) + 3 * h * m + 2 * h


def parameters(conf: Dict) -> int:
    """Parameters of the whole model (3,029,337,472): the layers, the
    final norm and the embedding — once, where the head is tied to it."""
    ends = conf["vocab_size"] * conf["hidden_size"]
    if not conf["tie_word_embeddings"]:
        ends *= 2
    return conf["hidden_size"] + ends + sum(
        _layers(conf, kind) * layer_params(conf, kind)
        for kind in (MAMBA, ATTENTION))


def decoder_weight_bytes(conf: Dict) -> Dict[str, float]:
    """Bytes of the tensors a decode step streams, at their stored width
    (``A_log`` float32, everything else the served type): every layer, the
    final norm and the head — the embedding itself where they are tied
    (a step gathers a few rows of it AND multiplies by all of it)."""
    act = _act(conf)
    extra = (STATE_BYTES - act) * _layers(conf, MAMBA) * mixer_params(
        conf, MAMBA)["a_log"]
    return {"streamed": parameters(conf) * act + extra}


def kv_row_bytes(conf: Dict) -> int:
    """Bytes one token leaves in ONE attention layer's K and V pools (512
    at 1 kv head x 128 in bfloat16)."""
    return 2 * conf["num_key_value_heads"] * head_dim(conf) * _act(conf)


def kv_bytes_per_token(conf: Dict) -> int:
    """Bytes a token leaves in the cache: K and V rows of the attention
    layers (1,024 at the published sizes).  A Mamba layer keeps no row."""
    return _layers(conf, ATTENTION) * kv_row_bytes(conf)


def layer_state_bytes(conf: Dict) -> int:
    """Bytes ONE Mamba layer keeps a lane: the state ``h`` [state, inner]
    float32 (327,680) and the last ``d_conv - 1`` conv inputs in the
    served type (30,720)."""
    d = inner(conf)
    return (conf["mamba_d_state"] * d * STATE_BYTES
            + (conf["mamba_d_conv"] - 1) * d * _act(conf))


def lane_state_bytes(conf: Dict) -> int:
    """Bytes of one lane's state across the Mamba layers (9,318,400)."""
    return _layers(conf, MAMBA) * layer_state_bytes(conf)


def least_lanes(conf: Dict, live_kv_tokens: float) -> float:
    """The fewest lanes that hold ``live_kv_tokens``: each at most
    ``max_position_embeddings``."""
    return live_kv_tokens / conf["max_position_embeddings"]


def decode_step_min_bytes(conf: Dict, live_kv_tokens: float,
                          chips: int) -> float:
    """The least one chip must move for one decode step of the whole
    batch: the streamed weights, the live K and V rows of the attention
    layers, and per lane — counted at their fewest (``least_lanes``) — the
    lane's state READ and WRITTEN once.  Bandwidth-bound at these batch
    sizes."""
    state = 2 * lane_state_bytes(conf) * least_lanes(conf, live_kv_tokens)
    rows = live_kv_tokens * kv_bytes_per_token(conf)
    return (decoder_weight_bytes(conf)["streamed"] + state + rows) / chips


def _prompt_len(conf: Dict, prompt_len: Optional[float]) -> float:
    if prompt_len is not None:
        return prompt_len
    lengths = conf["check"]["prompt_lengths"]
    return sum(lengths) / len(lengths)


def prefill_flops(conf: Dict, tokens: float,
                  prompt_len: Optional[float] = None) -> float:
    """The least arithmetic of cold prefills over ``tokens`` prompt tokens
    in prompts of ``prompt_len`` (default: the mean base length of the
    file's ``check`` block, the lengths the cell sends): 2 x the layers'
    matrix parameters a token (the conv's taps among them); the
    recurrence, ``9 x inner x state`` a token and Mamba layer (the decay's
    product and exponential, the input's two products, the update's
    multiply-add, the read's multiply-add, the skip); causal attention of
    the attention layers, ``4 d`` a key and query head; the head once a
    prompt."""
    prompt_len = _prompt_len(conf, prompt_len)
    h, m = conf["hidden_size"], conf["intermediate_size"]
    d, n = inner(conf), conf["mamba_d_state"]
    mamba = _layers(conf, MAMBA) * (
        2 * (mixer_params(conf, MAMBA)["matrices"]
             + conf["mamba_d_conv"] * d + 3 * h * m)
        + 9 * d * n)
    attention = _layers(conf, ATTENTION) * 2 * (
        mixer_params(conf, ATTENTION)["matrices"] + 3 * h * m)
    keys = prompt_len * (prompt_len + 1) / 2
    a_prompt = (
        _layers(conf, ATTENTION) * conf["num_attention_heads"] * 4
        * head_dim(conf) * keys
        + 2 * conf["vocab_size"] * h)
    return tokens * (mamba + attention) + (tokens / prompt_len) * a_prompt


def prefill_scan_min_bytes(conf: Dict, tokens: float,
                           prompts: float) -> float:
    """What the scan of prefills over ``tokens`` prompt tokens in
    ``prompts`` prompts must move, whatever implements it: per token and
    Mamba layer the conv's output ``c`` and the step sizes ``D_t`` in and
    the scan's output ``g`` out, at the served width, plus ``B`` and
    ``C``; per prompt and layer the state and the window read and written
    once.  (The state between tokens need not leave the chip.)"""
    act = _act(conf)
    a_token = (3 * inner(conf) + 2 * conf["mamba_d_state"]) * act
    return _layers(conf, MAMBA) * (
        tokens * a_token + prompts * 2 * layer_state_bytes(conf))
