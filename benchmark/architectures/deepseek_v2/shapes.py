"""Bytes a decode step must move, from shapes alone (standard library).

Kept with the benchmark so that a PR which speeds the step up cannot also
change what the step is charged with.  ``conf`` is the configuration file:
``n_routed_experts`` counts the experts HELD here, ``router_experts`` the
router's outputs (``keys.py``)."""

from __future__ import annotations

from typing import Dict

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _act(conf: Dict) -> int:
    return _BYTES[conf.get("torch_dtype", "bfloat16")]


def latent_row_bytes(conf: Dict) -> int:
    """Bytes ONE token leaves in the cache per layer: the normed latent and
    the one rotated key every head shares (1,152 at the published 512 + 64
    in bfloat16) — read once a step, as key and as value."""
    return (conf["kv_lora_rank"] + conf["qk_rope_head_dim"]) * _act(conf)


def kv_bytes_per_token(conf: Dict) -> int:
    return conf["num_hidden_layers"] * latent_row_bytes(conf)


def expert_bytes(conf: Dict) -> int:
    """One routed expert's three matrices."""
    return 3 * conf["hidden_size"] * conf["moe_intermediate_size"] * _act(conf)


def non_expert_weight_bytes(conf: Dict) -> float:
    """Every tensor a step streams whatever it routes: the latent
    attention's projections, the dense and the shared MLPs, the routers,
    the norms and the output head (the embedding apart: a step gathers a
    few rows of it)."""
    h, heads = conf["hidden_size"], conf["num_attention_heads"]
    q_rank, r = conf["q_lora_rank"], conf["kv_lora_rank"]
    dn, dr, dv = (conf["qk_nope_head_dim"], conf["qk_rope_head_dim"],
                  conf["v_head_dim"])
    layers, dense = conf["num_hidden_layers"], conf["first_k_dense_replace"]
    attention = (
        h * q_rank + q_rank * heads * (dn + dr) + h * (r + dr)
        + r * heads * (dn + dv) + heads * dv * h
        + 2 * h + q_rank + r  # the four norms
    )
    routed_layer = (
        h * conf["router_experts"]
        + 3 * h * conf["moe_intermediate_size"] * conf["n_shared_experts"]
    )
    count = (
        layers * attention + dense * 3 * h * conf["intermediate_size"]
        + routed_layers(conf) * routed_layer + h + h * conf["vocab_size"]
    )
    return count * _act(conf)


def decode_step_min_bytes(conf: Dict, live_kv_tokens: float,
                          chips: int) -> float:
    """The least one chip must read from HBM for one decode step of the
    whole batch: every non-expert weight and the LIVE latent rows.  Of the
    routed experts it charges the fewest a step must touch, and for a chip
    that holds a share of them that is NONE: a step whose tokens all keep
    groups held elsewhere reads no expert here.  What a step DID touch is
    ``decode_step_touched_bytes``'s, from the program's counters.  Decode at
    these batch sizes is bandwidth-bound."""
    return (
        non_expert_weight_bytes(conf)
        + live_kv_tokens * kv_bytes_per_token(conf)
    ) / chips


def routed_layers(conf: Dict) -> int:
    return conf["num_hidden_layers"] - conf["first_k_dense_replace"]


def decode_step_touched_bytes(conf: Dict, live_kv_tokens: float,
                              experts_touched: float, chips: int) -> float:
    """The same plus the routed experts one step DID touch
    (``experts_touched``: distinct held experts a routed layer touched in
    a step — the program's ``serve_moe_experts_touched`` over its
    ``serve_moe_layer_steps``; every routed layer of the step is charged
    that many): what the step had to read given what its tokens chose."""
    return decode_step_min_bytes(conf, live_kv_tokens, chips) + (
        routed_layers(conf) * experts_touched * expert_bytes(conf) / chips
    )
