"""Bytes a decode step must move and operations a prefill must do, from
shapes alone (standard library).

Kept with the benchmark so that a PR which speeds a step up cannot also
change what the step is charged with.  ``conf`` is the configuration file:
``num_experts`` counts the experts HELD here, ``router_experts`` the
router's outputs (``keys.py``)."""

from __future__ import annotations

from typing import Dict, Optional

from .keys import ATTENTION, WINDOW, mixer_types

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
BIAS_BYTES = 4  # the router's selection bias is float32 whatever else is


def _act(conf: Dict) -> int:
    return _BYTES[conf.get("torch_dtype", "bfloat16")]


def _layers(conf: Dict, kind: str) -> int:
    return sum(1 for m in mixer_types(conf) if m == kind)


def routed_layers(conf: Dict) -> int:
    return conf["num_hidden_layers"] - conf["num_dense_layers"]


def attention_params(conf: Dict) -> int:
    """One layer's attention with the layer's FOUR norm gains (27,271,424
    at the published sizes): q, k, v, the output gate and o, the per-head
    q and k norm gains, and the norms before and after each sublayer."""
    h, d = conf["hidden_size"], conf["head_dim"]
    q, kv = conf["num_attention_heads"] * d, conf["num_key_value_heads"] * d
    return 3 * h * q + 2 * h * kv + 2 * d + 4 * h


def dense_mlp_params(conf: Dict) -> int:
    """A leading dense layer's SwiGLU (37,748,736)."""
    return 3 * conf["hidden_size"] * conf["intermediate_size"]


def expert_params(conf: Dict) -> int:
    """One expert's three matrices (6,291,456); the shared expert's too."""
    return 3 * conf["hidden_size"] * conf["moe_intermediate_size"]


def router_params(conf: Dict) -> int:
    """A routed layer's router and its selection bias (262,272)."""
    return (conf["hidden_size"] + 1) * conf["router_experts"]


def routed_layer_params(conf: Dict, experts: Optional[int] = None) -> int:
    """A routed layer whole with ``experts`` routed experts (default: those
    the file HOLDS): 134,488,448 at 16, 839,131,520 at the published 128."""
    experts = conf["num_experts"] if experts is None else experts
    return (attention_params(conf) + router_params(conf)
            + (experts + conf["num_shared_experts"]) * expert_params(conf))


def parameters(conf: Dict, experts: Optional[int] = None,
               vocab: Optional[int] = None) -> int:
    """Parameters of the tree with ``experts`` routed experts a layer and
    ``vocab`` ids (defaults: the file's own share — 4,267,194,112): the
    layers, the final norm, the embedding and the untied head.  At the
    published 128 and 200,192: 26,123,974,400."""
    vocab = conf["vocab_size"] if vocab is None else vocab
    dense = attention_params(conf) + dense_mlp_params(conf)
    return (conf["num_dense_layers"] * dense
            + routed_layers(conf) * routed_layer_params(conf, experts)
            + conf["hidden_size"] + 2 * vocab * conf["hidden_size"])


def non_expert_weight_bytes(conf: Dict) -> float:
    """Every tensor a step streams whatever it routes: attention and the
    norms of every layer, the dense MLPs, the routers with their biases,
    the shared experts, the final norm and the (sliced) head — the
    embedding apart: a step gathers a few rows of it."""
    h, act = conf["hidden_size"], _act(conf)
    count = (
        parameters(conf, experts=0) - conf["vocab_size"] * h
    )
    bias = routed_layers(conf) * conf["router_experts"]
    return count * act + bias * (BIAS_BYTES - act)


def kv_row_bytes(conf: Dict) -> int:
    """Bytes one token leaves in ONE layer's K and V pools (2,048)."""
    return 2 * conf["num_key_value_heads"] * conf["head_dim"] * _act(conf)


def kv_bytes_per_token(conf: Dict) -> int:
    """Bytes a token leaves in the cache FOR GOOD: K and V rows of the
    global layers (16,384).  A window layer keeps a ring a lane
    (``window_rows``), whatever the lane's length."""
    return _layers(conf, ATTENTION) * kv_row_bytes(conf)


def window_rows(conf: Dict, live_kv_tokens: float) -> float:
    """Rows the window layers' decode must read for ``live_kv_tokens``
    positions over the lanes: no more than the window a lane, over at most
    ``generate.max_concurrent`` lanes."""
    lanes = conf["serving"]["generate.max_concurrent"]
    return min(live_kv_tokens, lanes * conf["sliding_window"])


def decode_step_min_bytes(conf: Dict, live_kv_tokens: float,
                          chips: int) -> float:
    """The least one chip must read from HBM for one decode step of the
    whole batch: every non-expert weight, the live rows of the global
    layers and the rows the window layers can see.  Of the routed experts
    it charges the fewest a step must touch, and for a chip that holds a
    share of them that is NONE.  What a step DID touch is
    ``decode_step_touched_bytes``'s.  Decode at these batch sizes is
    bandwidth-bound."""
    rows = (_layers(conf, ATTENTION) * live_kv_tokens
            + _layers(conf, WINDOW) * window_rows(conf, live_kv_tokens))
    return (non_expert_weight_bytes(conf) + rows * kv_row_bytes(conf)) / chips


def decode_step_touched_bytes(conf: Dict, live_kv_tokens: float,
                              experts_touched: float, chips: int) -> float:
    """The same plus the routed experts one step DID touch
    (``experts_touched``: distinct held experts a routed layer touched in
    a step — the program's ``serve_moe_experts_touched`` over its
    ``serve_moe_layer_steps``; every routed layer is charged that many)."""
    return decode_step_min_bytes(conf, live_kv_tokens, chips) + (
        routed_layers(conf) * experts_touched * expert_params(conf)
        * _act(conf) / chips
    )


def _prompt_len(conf: Dict, prompt_len: Optional[float]) -> float:
    if prompt_len is not None:
        return prompt_len
    lengths = conf["check"]["prompt_lengths"]
    return sum(lengths) / len(lengths)


def prefill_flops(conf: Dict, tokens: float,
                  prompt_len: Optional[float] = None) -> float:
    """The least arithmetic of cold prefills over ``tokens`` prompt tokens
    in prompts of ``prompt_len`` (default: the mean base length of the
    file's ``check`` block, the lengths the cell sends).  A token: 2 x the
    matrix parameters it passes through — attention, the dense MLP or the
    router, the shared expert and the experts its LOCAL picks cost in
    expectation (``num_experts_per_tok`` x held / ``router_experts``: 1 of
    its 8).  A prompt: causal attention of the global layers, ``4 d`` a
    (key, query head) pair over ``n (n + 1) / 2`` pairs; of the window
    layers over the pairs inside the window, ``w n - w (w - 1) / 2`` past
    it; the head once."""
    n = _prompt_len(conf, prompt_len)
    h, d = conf["hidden_size"], conf["head_dim"]
    heads = conf["num_attention_heads"]
    attention = attention_params(conf) - 2 * d - 4 * h  # its matrices
    local = (conf["num_experts_per_tok"] * conf["num_experts"]
             / conf["router_experts"])
    routed = (h * conf["router_experts"]
              + (conf["num_shared_experts"] + local) * expert_params(conf))
    a_token = 2 * (
        conf["num_hidden_layers"] * attention
        + conf["num_dense_layers"] * dense_mlp_params(conf)
        + routed_layers(conf) * routed)
    w = min(conf["sliding_window"], n)
    pairs_global = n * (n + 1) / 2
    pairs_window = w * n - w * (w - 1) / 2
    a_prompt = 4 * d * heads * (
        _layers(conf, ATTENTION) * pairs_global
        + _layers(conf, WINDOW) * pairs_window
    ) + 2 * conf["vocab_size"] * h
    return tokens * a_token + (tokens / n) * a_prompt
