"""ISSUE 54: the routed layer's decode step as ONE kernel
(``ops/grouped.grouped_swiglu_step``) — here interpreted, against
``models/routed.held_experts_sum``'s ``ragged_dot`` path — at Trinity's
geometry (16 held of 128, 8 a token, 4 lanes) and DeepSeek-V2's (40 held of
160, 6 a token, 8 lanes) cut to test widths; the rule that sends a dispatch
to it; and the counter that says it ran.
"""

import dataclasses
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from docqa_tpu.config import DecoderConfig
from docqa_tpu.models import latent, routed
from docqa_tpu.models.decoder import block_serving, kernel_forms
from docqa_tpu.ops import grouped

# (experts, held, first held, picks a token, lanes, hidden, expert width,
# elements of a weight tile: the second geometry's experts come in three)
GEOMETRIES = {
    "trinity": (128, 16, 32, 8, 4, 256, 128, None),
    "deepseek-v2": (160, 40, 80, 6, 8, 128, 384, 128 * 128),
}
CASES = ("routed", "none_local", "one_expert", "retired_lane",
         "held_elsewhere", "one_row")


def _config(name):
    experts, held, lo, k, _lanes, h, f, _tile = GEOMETRIES[name]
    return DecoderConfig(
        vocab_size=64, hidden_dim=h, num_layers=2, num_heads=2,
        num_kv_heads=2, head_dim=16, mlp_dim=64, max_seq_len=64,
        dtype="bfloat16", num_experts=experts, experts_held=held,
        experts_held_start=lo, experts_per_token=k, expert_dim=f)


def _layer(cfg, seed, dtype=jnp.bfloat16):
    held, h, f = cfg.experts_held, cfg.hidden_dim, cfg.expert_dim
    keys = jax.random.split(jax.random.key(seed), 3)
    shapes = {"gate": (held, h, f), "up": (held, h, f), "down": (held, f, h)}
    return {
        f"l1_e_{name}": (jax.random.normal(key, shape, jnp.float32)
                         * shape[1] ** -0.5).astype(dtype)
        for key, (name, shape) in zip(keys, shapes.items())}


def _picks(case, cfg, lanes, rng):
    lo, held = routed.experts_held(cfg)
    k = cfg.experts_per_token
    here = lambda: lo + rng.choice(held, k, replace=False)  # noqa: E731
    if case == "none_local":  # every pick on an expert another chip holds
        return (lo + held + rng.integers(0, 8, (lanes, k))) % cfg.num_experts
    if case == "one_expert":  # every lane's every pick on ONE held expert
        return np.full((lanes, k), lo + 3)
    taken = np.stack([
        rng.choice(cfg.num_experts, k, replace=False) for _ in range(lanes)])
    taken[0] = here()  # some pick is local whatever the draw
    if case == "retired_lane":  # a lane the batcher retired routes to -1
        taken[1] = -1
        taken[-1, ::2] = -1
    if case == "held_elsewhere":  # below and above the held range
        taken[1] = (lo - 1 - np.arange(k)) % cfg.num_experts
        taken[2] = (lo + held + np.arange(k)) % cfg.num_experts
    return taken[:1] if case == "one_row" else taken


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("name", list(GEOMETRIES))
def test_the_step_kernel_is_the_grouped_sum_of_the_same_picks(
        name, case, monkeypatch):
    """Both forms in bfloat16, the kernel's tiles as the geometry cuts
    them: the same float32 sum, a rounding of the sum's order apart; zero
    to the bit for a row none of whose picks is held here."""
    cfg = _config(name)
    lanes, tile = GEOMETRIES[name][4], GEOMETRIES[name][7]
    if tile:
        monkeypatch.setattr(grouped, "_WEIGHT_TILE", tile)
    params = _layer(cfg, 54)
    rng = np.random.default_rng(len(case))
    taken = jnp.asarray(_picks(case, cfg, lanes, rng), jnp.int32)
    y = jnp.asarray(
        rng.standard_normal((taken.shape[0], cfg.hidden_dim)), jnp.bfloat16)
    gates = jnp.asarray(rng.random(taken.shape), jnp.float32)
    want = np.asarray(routed.held_experts_sum(y, taken, gates, params, cfg, 1))
    lo, held = routed.experts_held(cfg)
    got = np.asarray(grouped.grouped_swiglu_step(
        y, taken - lo, gates, params["l1_e_gate"], params["l1_e_up"],
        params["l1_e_down"], interpret=True))
    assert got.shape == want.shape and got.dtype == np.float32
    local = (np.asarray(taken) >= lo) & (np.asarray(taken) < lo + held)
    assert not got[~local.any(-1)].any()
    if case == "none_local":
        assert not local.any()
    else:
        assert np.abs(want).max() > 1e-2
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=2e-5)


def test_the_kernels_tiles_follow_the_widths(monkeypatch):
    """An expert whole where its operands are a ``_WEIGHT_TILE`` each
    (Trinity), in lane-wide column tiles that divide its width where they
    are more (DeepSeek-V2: four of 384)."""
    seen = {}

    def spy(local, y, *weights, tf, **_):
        seen["tf"] = tf
        return jnp.zeros(y.shape, jnp.float32)

    monkeypatch.setattr(grouped, "_swiglu_step_local", spy)
    bf16 = jnp.bfloat16
    for (h, f), tf in {(2048, 1024): 1024, (5120, 1536): 384,
                       (256, 128): 128}.items():
        jax.eval_shape(
            grouped.grouped_swiglu_step,
            jax.ShapeDtypeStruct((4, h), bf16),
            jax.ShapeDtypeStruct((4, 8), jnp.int32),
            jax.ShapeDtypeStruct((4, 8), jnp.float32),
            jax.ShapeDtypeStruct((16, h, f), bf16),
            jax.ShapeDtypeStruct((16, h, f), bf16),
            jax.ShapeDtypeStruct((16, f, h), bf16))
        assert seen["tf"] == tf, (h, f)


# ---- who takes it ----------------------------------------------------------

def _traced(cfg, rows, use_flash):
    params = jax.eval_shape(lambda: _layer(cfg, 0))
    k = cfg.experts_per_token
    return str(jax.make_jaxpr(
        lambda y, taken, gates, p: routed.held_experts_sum(
            y, taken, gates, p, cfg, 1, use_flash=use_flash)
    )(jax.ShapeDtypeStruct((rows, cfg.hidden_dim), jnp.bfloat16),
      jax.ShapeDtypeStruct((rows, k), jnp.int32),
      jax.ShapeDtypeStruct((rows, k), jnp.float32), params))


@pytest.mark.parametrize("name, rows, use_flash, form", [
    ("trinity", 4, True, "step"),  # 32 picks
    ("trinity", 16, True, "step"),  # 128 picks: one row tile, the last step
    ("trinity", 17, True, "gmm"),
    ("trinity", 2048, True, "gmm"),  # a prefill tile
    ("deepseek-v2", 8, True, "step"),  # 48 picks
    ("deepseek-v2", 21, True, "step"),
    ("deepseek-v2", 22, True, "gmm"),
    ("deepseek-v2", 512, True, "gmm"),
    ("trinity", 4, False, "ragged_dot"),  # a CPU, a mesh, the oracle
    ("deepseek-v2", 8, False, "ragged_dot"),
    ("trinity", 2048, False, "ragged_dot"),
])
def test_a_dispatch_goes_by_the_form_and_its_picks(
        name, rows, use_flash, form):
    """``kernel_forms``'s ``grouped`` and the dispatch's picks alone: a
    step (picks within one ``ROW_TILE``) is the step kernel and nothing of
    the sorted form, a prefill's rows are ``gmm``, and without the form
    everything is ``ragged_dot`` as it was."""
    cfg = _config(name)
    assert grouped.step_form(rows, cfg.experts_per_token) == (
        rows * cfg.experts_per_token <= grouped.ROW_TILE)
    text = _traced(cfg, rows, use_flash)
    has = {"step": "_swiglu_step_kernel" in text, "gmm": "name=gmm" in text,
           "ragged_dot": "ragged_dot" in text}
    assert has == {k: k == form for k in has}, has
    # the step form sorts nothing: no argsort, no gather of the rows
    assert (" sort[" in text) == (form != "step")


# ---- the counter -----------------------------------------------------------

HYBRID = DecoderConfig(
    vocab_size=256, hidden_dim=64, num_layers=6, num_heads=4, num_kv_heads=2,
    head_dim=16, mlp_dim=128, max_seq_len=512, norm_eps=1e-5,
    block="sparse_linear", dtype="float32",
    mixer_types=("window", "window", "attention") * 2, sliding_window=48,
    qk_norm=True, use_output_gate=True, use_output_norm=False,
    sandwich_norm=True, first_dense_layers=1, num_experts=16, experts_held=4,
    experts_held_start=4, experts_per_token=4, expert_dim=32,
    num_shared_experts=1, router_score="sigmoid", router_bias=True,
    router_norm=True,
)
LATENT = DecoderConfig(
    vocab_size=512, hidden_dim=128, num_layers=3, num_heads=4, num_kv_heads=1,
    head_dim=48, mlp_dim=256, max_seq_len=256, norm_eps=1e-6, block="mla_moe",
    q_lora_rank=64, kv_lora_rank=128, qk_nope_head_dim=32,
    qk_rope_head_dim=16, v_head_dim=32, first_dense_layers=1,
    num_experts=32, experts_per_token=4, expert_dim=64, num_shared_experts=2,
    expert_groups=8, expert_groups_per_token=3, routed_scale=16.0,
    experts_held_start=0, experts_held=8,
)
ROW = np.asarray([24, 6, 5, 4], np.int64)  # MOE_SUMS of one chunk


@pytest.mark.parametrize("cfg", [HYBRID, LATENT], ids=["stack", "latent"])
@pytest.mark.parametrize("grouped_form, n_lanes, counted", [
    (True, 4, True), (True, 32, True), (True, 33, False), (False, 4, False),
])
def test_the_counter_says_the_routed_layers_stepped_in_the_kernel(
        cfg, grouped_form, n_lanes, counted):
    """``serve_routed_fused_chunks``: 1 a fetched chunk where the form is
    on AND the step's lanes x picks fit a row tile — the rule
    ``held_experts_sum`` goes by —, absent otherwise; the expert-choice
    sums beside it are what they were."""
    forms = kernel_forms(
        cfg, on_tpu=True, mesh=None, block_size=16)._replace(
        grouped=grouped_form)
    counts, samples = block_serving(cfg).chunk_counts(
        lane_steps=12, row=ROW, kernels=forms, n_lanes=n_lanes)
    assert ("serve_routed_fused_chunks" in counts) == counted
    assert counts.get("serve_routed_fused_chunks", 1) == 1
    assert [counts[n] for n in routed.MOE_SUMS] == list(ROW)
    assert samples == {"serve_moe_tokens_per_expert": 6 / 5}
    assert routed.routed_fused_counts(
        cfg, kernels=forms, n_lanes=n_lanes) == (
        {"serve_routed_fused_chunks": 1} if counted else {})


def test_a_stack_that_does_not_route_never_counts_it():
    dense = dataclasses.replace(HYBRID, num_experts=0)
    forms = kernel_forms(dense, on_tpu=True, mesh=None, block_size=16)
    assert not forms.grouped
    counts, _ = block_serving(dense).chunk_counts(
        lane_steps=12, row=None, kernels=forms, n_lanes=4)
    assert "serve_routed_fused_chunks" not in counts
    counts, _ = latent.latent_chunk_counts(
        LATENT, row=None, kernels=forms._replace(grouped=True), n_lanes=4)
    assert "serve_routed_fused_chunks" not in counts


def test_the_counter_has_its_row_in_the_observability_table():
    table = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "docs", "OBSERVABILITY.md")
    with open(table, encoding="utf-8") as f:
        rows = [line for line in f if line.startswith(
            "| `serve_routed_fused_chunks` |")]
    assert len(rows) == 1 and "_swiglu_step_kernel" in rows[0]
    assert "serve_decode_chunks" in rows[0]
