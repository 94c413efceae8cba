"""Bytes a decode step must move and operations a prefill must do, from
shapes alone (standard library).

Kept with the benchmark so that a PR which speeds a step up cannot also
change what the step is charged with.  ``conf`` is the configuration file
(published keys, ``keys.py``)."""

from __future__ import annotations

from typing import Dict, Optional

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _act(conf: Dict) -> int:
    return _BYTES[conf.get("torch_dtype", "bfloat16")]


def steps(conf: Dict) -> int:
    """Passes of the stack a token takes (``total_ut_steps``: 4)."""
    return int(conf["total_ut_steps"])


def layer_matrices(conf: Dict) -> int:
    """Parameters of one layer's seven matrices (51,380,224 at the
    published sizes): q, k, v, o and the SwiGLU's three."""
    h, m, d = conf["hidden_size"], conf["intermediate_size"], conf["head_dim"]
    q, kv = conf["num_attention_heads"] * d, conf["num_key_value_heads"] * d
    return 2 * h * q + 2 * h * kv + 3 * h * m


def layer_params(conf: Dict) -> int:
    """Every parameter of one layer: the matrices and FOUR norm gains
    (51,388,416)."""
    return layer_matrices(conf) + 4 * conf["hidden_size"]


def parameters(conf: Dict) -> int:
    """Parameters of the whole model (2,667,974,657): the layers ONCE
    (every step runs the same ones), embedding, untied head, the final
    norm and the exit gate (a ``[hidden] -> 1`` linear with bias)."""
    h = conf["hidden_size"]
    return (conf["num_hidden_layers"] * layer_params(conf)
            + 2 * conf["vocab_size"] * h + h + h + 1)


def decoder_weight_bytes(conf: Dict) -> Dict[str, float]:
    """``layers``: the bytes of the 48 layers, what ONE pass streams;
    ``head``: the final norm and ``lm_head``, streamed once a step;
    ``embedding``: gathered from, not streamed; ``gate``: not read at
    ``early_exit_threshold`` 1."""
    act, h = _act(conf), conf["hidden_size"]
    return {
        "layers": conf["num_hidden_layers"] * layer_params(conf) * act,
        "head": (conf["vocab_size"] * h + h) * act,
        "embedding": conf["vocab_size"] * h * act,
        "gate": (h + 1) * act,
    }


def kv_entries(conf: Dict) -> int:
    """Cache entries a token leaves: one a (step, layer) — 192."""
    return steps(conf) * conf["num_hidden_layers"]


def kv_bytes_per_token(conf: Dict) -> int:
    """Bytes a token leaves in the cache: a key and a value per kv head
    and ENTRY (1,572,864 at the published sizes)."""
    return (2 * conf["num_key_value_heads"] * conf["head_dim"] * _act(conf)
            * kv_entries(conf))


def decode_step_min_bytes(conf: Dict, live_kv_tokens: float,
                          chips: int) -> float:
    """The least one chip must read for one decode step of the whole
    batch: the layers ONCE A PASS (no chip holds 4.93 GB between passes:
    its fast memory is a few tens of MB), the head once, and the live K
    and V of every entry.  Bandwidth-bound at these batch sizes."""
    w = decoder_weight_bytes(conf)
    return (steps(conf) * w["layers"] + w["head"]
            + live_kv_tokens * kv_bytes_per_token(conf)) / chips


def _prompt_len(conf: Dict, prompt_len: Optional[float]) -> float:
    if prompt_len is not None:
        return prompt_len
    lengths = conf["check"]["prompt_lengths"]
    return sum(lengths) / len(lengths)


def prefill_flops(conf: Dict, tokens: float,
                  prompt_len: Optional[float] = None) -> float:
    """The least arithmetic of cold prefills over ``tokens`` prompt tokens
    in prompts of ``prompt_len`` (default: the mean base length of the
    file's ``check`` block): per PASS 2 x the layers' matrix parameters a
    token and causal attention, ``4 d`` a key and query head; the head
    once a prompt."""
    prompt_len = _prompt_len(conf, prompt_len)
    layers = conf["num_hidden_layers"]
    keys = prompt_len * (prompt_len + 1) / 2
    a_pass_token = 2 * layers * layer_matrices(conf)
    a_pass_prompt = (layers * conf["num_attention_heads"] * 4
                     * conf["head_dim"] * keys)
    head = 2 * conf["vocab_size"] * conf["hidden_size"]
    return steps(conf) * tokens * a_pass_token + (tokens / prompt_len) * (
        steps(conf) * a_pass_prompt + head)
