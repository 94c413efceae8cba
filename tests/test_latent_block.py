"""The latent-attention / routed-expert block (``models/latent.py``,
ISSUE 35) at toy widths on the CPU, against the plain float32 reference of
its architecture package (``benchmark/architectures/deepseek_v2``):

* paged prefill then ABSORBED decode through the pool agree with the
  reference's one full forward pass, logits, per row, under replay of the
  program's expert choices;
* absorbed decode = non-absorbed decode on the same pool;
* the four chips' partial expert sums, the shared experts counted once, add
  up to the uncut layer — in the program and in the reference;
* the held experts' grouped sum against the plain sum over picks, at a
  decode step's and a prefill's row counts, in both forms of the product;
* group-limited selection on a hand-built case;
* the routing record's shapes and ids, and two values from the GQA block;
* the latent pool's bytes a token; YaRN's frequencies and m^2 by hand;
* the seeded routers are level (``weights.level_routers``);
* the solo dense-cache engine refuses the block by name, and the batcher
  refuses at construction what the block does not serve;
* the GQA block's toy programs lower to the text they lowered to before.
"""

import dataclasses
import hashlib
import math
import os
import sys
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmark")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

from docqa_tpu.config import DecoderConfig, GenerateConfig  # noqa: E402
from docqa_tpu.engines import paged  # noqa: E402
from docqa_tpu.engines.generate import GenerateEngine  # noqa: E402
from docqa_tpu.models import latent  # noqa: E402
from docqa_tpu.models.decoder import (  # noqa: E402
    decoder_param_schema,
    kernel_forms,
    kv_row_shapes,
)
from docqa_tpu.ops import rope  # noqa: E402
from docqa_tpu.ops.attention import attention_reference  # noqa: E402
from harness import arch, check  # noqa: E402

PACKAGE = arch.load({"architecture": "deepseek_v2"})
TOY = DecoderConfig(
    vocab_size=512, hidden_dim=128, num_layers=3, num_heads=4, num_kv_heads=1,
    head_dim=48, mlp_dim=256, max_seq_len=256, norm_eps=1e-6, block="mla_moe",
    q_lora_rank=64, kv_lora_rank=32, qk_nope_head_dim=32, qk_rope_head_dim=16,
    v_head_dim=32, rope_scaling_factor=40.0, rope_original_max_len=64,
    rope_mscale=0.707, rope_mscale_all_dim=0.707, first_dense_layers=1,
    num_experts=32, experts_per_token=4, expert_dim=64, num_shared_experts=2,
    expert_groups=8, expert_groups_per_token=3, routed_scale=16.0,
    experts_held_start=0, experts_held=8,
)
PUBLISHED = DecoderConfig(
    vocab_size=25600, hidden_dim=5120, num_layers=5, num_heads=128,
    num_kv_heads=1, head_dim=192, mlp_dim=12288, max_seq_len=4096,
    norm_eps=1e-6, block="mla_moe", q_lora_rank=1536, kv_lora_rank=512,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    rope_scaling_factor=40.0, rope_original_max_len=4096, rope_mscale=0.707,
    rope_mscale_all_dim=0.707, first_dense_layers=1, num_experts=160,
    experts_per_token=6, expert_dim=1536, num_shared_experts=2,
    expert_groups=8, expert_groups_per_token=3, routed_scale=16.0,
    experts_held_start=0, experts_held=40,
)
SPEC = {"prompt_lengths": [40, 60, 70, 80], "lane_rows": 128}
POOL = dict(n_blocks=64, block_size=16, seq_capacity=256, n_lanes=4,
            step_width=1)
N_ROWS = 1 + check.DECODE_STEPS
SEEDS = [1, 2, 3, 2**31 + 4]
# bfloat16 arithmetic through three layers against float32: the worst row
# of 12 reads 0.022-0.035 on these seeds (0.026-0.031 on the first three);
# the reference with int8 matmul inputs, the nearest precision below, reads
# 0.055 at the least.  A limit between the two.
TOLERANCE = 0.045


def engine_of(cfg, seed):
    return types.SimpleNamespace(
        cfg=cfg, use_flash=False,
        params=PACKAGE.weights.make_decoder_params(cfg, seed))


@pytest.fixture(scope="module")
def read():
    """decoder_check of the toy per seed, each computed once."""
    cache = {}

    def of(seed):
        if seed not in cache:
            cache[seed] = check.decoder_check(
                PACKAGE, SPEC, engine_of(TOY, seed), seed, control=True,
                **POOL)
        return cache[seed]

    return of


# ---- prefill then absorbed decode against the reference --------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_paged_prefill_then_absorbed_decode_agree_with_the_reference(
        read, seed):
    out = read(seed)
    said = f"seed {seed}: {out['program']}"
    assert 0 < out["program"]["prefill_rows"] < TOLERANCE, said
    assert 0 < out["program"]["decode_rows"] < TOLERANCE, said
    assert out["kv_bits"] == 16
    # the choices: a sound bfloat16 program differs from the float32
    # reference in a few decisions of a hundred, never by much
    assert 0 < out["routing"]["differing_share"] < 0.2
    assert out["routing"]["worst_gap"] < 0.01


@pytest.mark.parametrize("seed", SEEDS[:2])
def test_every_row_agrees_not_only_the_worst(seed):
    engine = engine_of(TOY, seed)
    ids, lengths = check.sample_prompts(seed, 512, 4, N_ROWS - 1, SPEC)
    got, bits, record = check.program_logits(
        engine, ids, lengths, check.DECODE_STEPS, 1, 64, 16, 256)
    want, _gap, taken = check.replayed_reference(
        PACKAGE, engine.params, TOY, ids, lengths, N_ROWS, record)
    err = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(
        want - want.mean(-1, keepdims=True), axis=-1)
    assert err.shape == (4, N_ROWS) and (err < TOLERANCE).all(), err
    # the reference computed with the program's sets wherever it held one
    held = record[..., 0] >= 0
    np.testing.assert_array_equal(taken[held], record[held])
    for lane, n in enumerate(lengths):
        assert held[:, lane, : n + check.DECODE_STEPS].all()
        assert not held[:, lane, n + check.DECODE_STEPS:].any()


@pytest.mark.parametrize("seed", SEEDS[:2])
def test_each_control_fails_what_it_has_to(read, seed):
    out = read(seed)
    assert set(out["controls"]) == {
        "w_fp8", "w_int8", "a_int8", "a_fp8", "no_group_limit"}
    for name in ("w_fp8", "w_int8", "a_int8", "a_fp8"):
        assert out["controls"][name]["worst_row"] > TOLERANCE, (name, out)
    wrong = out["controls"]["no_group_limit"]
    assert wrong["worst_row"] < 1e-4, "replay is blind to a wrong router"
    assert wrong["worst_gap"] > 0.03 > 3 * out["routing"]["worst_gap"]


# ---- absorbed = non-absorbed on the same pool ------------------------------

def test_absorbed_decode_equals_non_absorbed_decode_on_the_same_pool():
    """One layer's decode attention both ways over one pool of latent rows
    in float32: scores against the rows as stored (the query carried into
    latent space, the output carried back) against keys and values
    up-projected per head from the same rows."""
    cfg = dataclasses.replace(TOY, dtype="float32")
    params = PACKAGE.weights.make_decoder_params(cfg, 5)
    rng = np.random.default_rng(5)
    lanes, block, n_blocks, heads = 3, 16, 12, cfg.num_heads
    width = latent.latent_row_width(cfg)
    pool = jnp.asarray(
        rng.standard_normal((n_blocks * block, 1, width)), jnp.float32)
    tables = jnp.asarray(
        rng.permutation(n_blocks).reshape(lanes, 4), jnp.int32)
    lengths = jnp.asarray([7, 33, 64], jnp.int32)  # after this step
    q_nope = jnp.asarray(rng.standard_normal(
        (lanes, 1, heads, cfg.qk_nope_head_dim)), jnp.float32)
    q_rope = jnp.asarray(rng.standard_normal(
        (lanes, 1, heads, cfg.qk_rope_head_dim)), jnp.float32)
    scale = latent.softmax_scale(cfg)
    from docqa_tpu.ops.attention import (
        gather_paged_kv,
        paged_latent_decode_attention,
    )

    absorbed = latent.expand_output(params, cfg, 1, (
        paged_latent_decode_attention(
            latent.absorb_query(params, cfg, 1, q_nope), q_rope, pool, tables,
            lengths, block_size=block, q_offset=lengths - 1, scale=scale)))
    rows = gather_paged_kv(pool, tables, block)[:, :, 0, :]  # [lanes, L, w]
    plain = []
    for lane in range(lanes):
        k, v = latent.up_projected(params, cfg, 1, rows[lane])
        plain.append(attention_reference(
            jnp.concatenate([q_nope, q_rope], -1)[lane:lane + 1], k[None],
            v[None], causal=True, lengths=lengths[lane:lane + 1],
            q_offset=lengths[lane:lane + 1] - 1, scale=scale)[0])
    np.testing.assert_allclose(
        np.asarray(absorbed), np.asarray(jnp.stack(plain)), rtol=2e-4,
        atol=2e-5)
    assert absorbed.shape == (lanes, 1, heads, cfg.v_head_dim)


# ---- the chip's share ------------------------------------------------------

def _shares(cfg):
    step = cfg.num_experts // 4
    return [dataclasses.replace(cfg, experts_held_start=a, experts_held=step)
            for a in range(0, cfg.num_experts, step)]


def _cut(params, cfg, share, i):
    lo, n = latent.experts_held(share)
    out = dict(params)
    for name in ("e_gate", "e_up", "e_down"):
        out[f"l{i}_{name}"] = params[f"l{i}_{name}"][lo:lo + n]
    return out


def test_the_four_shares_partial_sums_add_up_to_the_uncut_layer():
    """Four chips each hold a quarter of a layer's experts; every one
    computes the shared experts.  Their parts, the shared experts counted
    once, are what the uncut layer gives — program and reference alike."""
    whole = dataclasses.replace(
        TOY, dtype="float32", experts_held_start=0, experts_held=32)
    params = PACKAGE.weights.make_decoder_params(whole, 9)
    y = jnp.asarray(
        np.random.default_rng(9).standard_normal((24, whole.hidden_dim)),
        jnp.float32)
    shared_alone = latent._swiglu(y, params, "l1_s_gate", "l1_s_up",
                                  "l1_s_down")
    uncut, taken = latent.routed_mlp(y, params, whole, 1)
    parts = []
    for share in _shares(whole):
        part, taken_here = latent.routed_mlp(
            y, _cut(params, whole, share, 1), share, 1)
        np.testing.assert_array_equal(taken_here, taken)  # all 32 scored
        parts.append(part - shared_alone)
    np.testing.assert_allclose(
        np.asarray(sum(parts) + shared_alone), np.asarray(uncut), rtol=1e-4,
        atol=1e-5)
    assert float(jnp.abs(parts[0]).max()) > 1e-3, "a share adds something"

    ref = PACKAGE.reference
    with jax.default_matmul_precision("highest"):
        w = {k[3:]: v for k, v in params.items() if k.startswith("l1_")}
        none = jnp.full((24, whole.experts_per_token), -1, jnp.int32)
        args = (none, ref._same, ref._same, ref._published)
        ref_uncut, gap, ref_taken = ref._routed(y, w, none, whole, *args[1:])
        ref_shared = ref._swiglu(y, w["s_gate"], w["s_up"], w["s_down"],
                                 ref._same)
        ref_parts = [
            ref._routed(y, {k[3:]: v for k, v in _cut(
                params, whole, share, 1).items() if k.startswith("l1_")},
                none, share, *args[1:])[0] - ref_shared
            for share in _shares(whole)]
    assert not np.asarray(gap).any()
    np.testing.assert_allclose(
        np.asarray(sum(ref_parts) + ref_shared), np.asarray(ref_uncut),
        rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(np.sort(ref_taken), np.sort(taken))
    np.testing.assert_allclose(np.asarray(uncut), np.asarray(ref_uncut),
                               rtol=2e-3, atol=2e-4)


# ---- the held experts' sum: a grouped product over rows sorted by expert ----

def _plain_sum_over_picks(y, taken, gates, params, cfg, i):
    """``sum_j gate_j . swiglu_{taken_j}(y)`` over the picks held here,
    one pick at a time in float32: what ``held_experts_sum`` regroups."""
    lo, held = latent.experts_held(cfg)
    y = np.asarray(y, np.float32)
    w = {n: np.asarray(params[f"l{i}_e_{n}"], np.float32)
         for n in ("gate", "up", "down")}
    out = np.zeros((y.shape[0], cfg.hidden_dim), np.float32)
    for row, (ids, row_gates) in enumerate(
            zip(np.asarray(taken), np.asarray(gates))):
        for e, gate in zip(ids - lo, row_gates):
            if 0 <= e < held:
                g = y[row] @ w["gate"][e]
                act = g / (1.0 + np.exp(-g)) * (y[row] @ w["up"][e])
                out[row] += gate * (act @ w["down"][e])
    return out


def _picks(case, n, cfg, rng):
    lo, held = latent.experts_held(cfg)
    k = cfg.experts_per_token
    if case == "none_local":  # every pick on an expert another chip holds
        return (lo + held + rng.integers(0, 8, (n, k))) % cfg.num_experts
    if case == "one_expert":  # the same held expert, k times a row
        return np.full((n, k), lo + 3)
    if case == "outside_and_absent":  # held, held elsewhere, and -1
        taken = rng.integers(0, cfg.num_experts, (n, k))
        taken[::3, 0] = -1
        taken[1::4, :] = -1
        return taken
    return np.stack([  # "routed": k distinct experts a row
        rng.choice(cfg.num_experts, k, replace=False) for _ in range(n)])


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["ragged_dot", "pallas_interpret"])
@pytest.mark.parametrize("n", [8, 512])
@pytest.mark.parametrize("case, start", [
    ("routed", 0), ("routed", 16), ("none_local", 0), ("none_local", 24),
    ("one_expert", 8), ("outside_and_absent", 0), ("outside_and_absent", 16),
])
def test_the_held_experts_sum_is_the_plain_sum_over_picks(
        case, start, n, interpret, monkeypatch):
    """Both forms of the grouped product (``ops/grouped.py``: the XLA one
    this CPU runs, the Pallas kernel a TPU runs — here in interpret mode,
    where the rows no group owns read NaN), at a decode step's row count
    and a prefill's: the float32 sum over the picks held here."""
    cfg = dataclasses.replace(
        TOY, dtype="float32", experts_held_start=start, experts_held=8)
    params = PACKAGE.weights.make_decoder_params(cfg, 5)
    rng = np.random.default_rng(n + start)
    y = jnp.asarray(rng.standard_normal((n, cfg.hidden_dim)), jnp.float32)
    taken = jnp.asarray(_picks(case, n, cfg, rng), jnp.int32)
    gates = jnp.asarray(rng.random(taken.shape), jnp.float32)
    if interpret:
        from docqa_tpu.models import routed  # the function's home, PR 48
        from docqa_tpu.ops import grouped

        monkeypatch.setattr(
            routed, "grouped_matmul",
            lambda *a, **kw: grouped.grouped_matmul(*a, **kw, interpret=True))
    got = np.asarray(latent.held_experts_sum(y, taken, gates, params, cfg, 1))
    want = _plain_sum_over_picks(y, taken, gates, params, cfg, 1)
    assert np.isfinite(got).all()
    if case == "none_local":
        assert not got.any()
    else:
        assert np.abs(want).max() > 1e-3
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-4)


def test_a_token_whose_groups_are_all_elsewhere_gets_the_shared_part_alone():
    cfg = dataclasses.replace(TOY, dtype="float32")  # holds experts 0..7
    params = PACKAGE.weights.make_decoder_params(cfg, 3)
    y = jnp.asarray(
        np.random.default_rng(3).standard_normal((64, cfg.hidden_dim)),
        jnp.float32)
    out, taken = latent.routed_mlp(y, params, cfg, 1)
    absent = np.asarray((taken >= 8).all(-1))
    assert absent.any() and not absent.all()
    shared = latent._swiglu(y, params, "l1_s_gate", "l1_s_up", "l1_s_down")
    np.testing.assert_allclose(np.asarray(out)[absent],
                               np.asarray(shared)[absent], rtol=1e-5,
                               atol=1e-6)
    assert np.abs(np.asarray(out - shared)[~absent]).max() > 1e-3


# ---- group-limited selection ----------------------------------------------

def hand_scores():
    """8 groups of 4 experts, k = 4 of the best 3 groups.  Expert 13
    (group 3) scores 0.10, the fourth-highest score of all — but groups 0,
    1 and 2 hold the three highest, so group 3 is dropped and 13 with it;
    the fourth pick is the best REMAINING expert of the kept groups."""
    scores = np.full((2, 32), 0.001, np.float32)
    scores[0, [0, 5, 9]] = [0.30, 0.20, 0.15]  # best of groups 0, 1, 2
    scores[0, 13] = 0.10  # group 3: dropped
    scores[0, 6] = 0.05  # group 1: kept, so this is the fourth pick
    scores[1, [28, 29, 30, 31]] = [0.2, 0.19, 0.18, 0.17]  # all of group 7
    scores[1, [0, 4]] = [0.1, 0.09]
    return scores


def test_a_high_score_in_a_dropped_group_is_not_taken():
    scores = hand_scores()
    taken, gates = latent.select_experts(jnp.asarray(scores), TOY)
    assert sorted(np.asarray(taken)[0]) == [0, 5, 6, 9]
    assert sorted(np.asarray(taken)[1]) == [28, 29, 30, 31]
    np.testing.assert_allclose(np.sort(np.asarray(gates)[0]),
                               [0.05, 0.15, 0.20, 0.30])
    ref = PACKAGE.reference
    own = jax.lax.top_k(ref._published(jnp.asarray(scores), TOY), 4)[1]
    assert sorted(np.asarray(own)[0]) == [0, 5, 6, 9]
    plain = jax.lax.top_k(
        PACKAGE.weights.no_group_limit(jnp.asarray(scores), TOY), 4)[1]
    assert sorted(np.asarray(plain)[0]) == [0, 5, 9, 13], "the control takes it"


def test_the_choice_gap_reads_groups_then_experts():
    ref, scores = PACKAGE.reference, jnp.asarray(hand_scores()[:1])

    def gap_of(ids):
        return float(ref._choice_gap(
            scores, ref._membership(jnp.asarray([ids]), 32), TOY)[0])

    assert gap_of([0, 5, 6, 9]) == 0.0
    # 13 for 6: its group's best (0.10) lies 0.05 under the third-best
    # group's (0.15)
    assert gap_of([0, 5, 9, 13]) == pytest.approx(0.05)
    # 7 for 6, both of a kept group: the scores' difference
    assert gap_of([0, 5, 7, 9]) == pytest.approx(0.05 - 0.001)


# ---- what the forwards hand back -------------------------------------------

def test_the_routing_record_has_the_contracts_shapes_and_ids():
    params = PACKAGE.weights.make_decoder_params(TOY, 1)
    pools = paged.init_paged_pools(TOY, 16, 16)
    assert sorted(pools) == ["c0", "c1", "c2"]
    assert pools["c0"].shape == (256, 1, 48) and pools["c0"].dtype == jnp.bfloat16
    t = 256
    ids = jnp.arange(t, dtype=jnp.int32) % 500 + 5
    seg = jnp.where(jnp.arange(t) < 100, 0, -1).astype(jnp.int32)
    pos = jnp.arange(t, dtype=jnp.int32)
    dest = jnp.where(seg >= 0, pos, 256).astype(jnp.int32)
    logits, pools, record = paged.ragged_prefill_forward(
        params, TOY, pools, ids, seg, pos, dest, jnp.asarray([99]),
        rope_len=256)
    assert logits.shape == (1, 512) and logits.dtype == jnp.float32
    assert record.shape == (2, t, 4) and record.dtype == jnp.int32
    assert int(record.min()) >= 0 and int(record.max()) < 32
    assert (np.diff(np.sort(np.asarray(record), -1), axis=-1) > 0).all()
    assert float(jnp.abs(pools["c1"][:100].astype(jnp.float32)).min()) >= 0
    assert not np.asarray(pools["c1"][100:]).any(), "padding rows dropped"
    tables = jnp.asarray([[0, 1, 2, 3, 4, 5, 6, 7, 16, 16, 16, 16, 16, 16,
                           16, 16]], jnp.int32)
    logits, pools, record = paged.paged_decode_forward(
        params, TOY, pools, tables, jnp.asarray([[7, 9]]), jnp.asarray([100]),
        block_size=16, rope_len=256)
    assert logits.shape == (1, 2, 512)
    assert record.shape == (2, 1, 2, 4) and record.dtype == jnp.int32
    assert np.asarray(pools["c2"][100:102]).any()
    assert not np.asarray(pools["c2"][102:]).any()


def test_the_gqa_block_still_hands_back_two_values():
    cfg = DecoderConfig(vocab_size=128, hidden_dim=32, num_layers=1,
                        num_heads=2, num_kv_heads=1, head_dim=16, mlp_dim=64,
                        max_seq_len=64)
    assert not latent.routed_layers(cfg)
    assert kv_row_shapes(cfg) == {"k": (1, 16), "v": (1, 16)}
    mistral = arch.load({"architecture": "mistral"})
    params = mistral.weights.make_decoder_params(cfg, 1)
    pools = paged.init_paged_pools(cfg, 8, 16)
    assert sorted(pools) == ["k0", "v0"]
    t = 128
    seg = jnp.where(jnp.arange(t) < 20, 0, -1).astype(jnp.int32)
    out = paged.ragged_prefill_forward(
        params, cfg, pools, jnp.ones((t,), jnp.int32), seg,
        jnp.arange(t, dtype=jnp.int32),
        jnp.where(seg >= 0, jnp.arange(t), 128).astype(jnp.int32),
        jnp.asarray([19]), rope_len=64)
    assert len(out) == 2
    out = paged.paged_decode_forward(
        params, cfg, out[1], jnp.asarray([[0, 1, 8, 8]]), jnp.asarray([[3]]),
        jnp.asarray([20]), block_size=16, rope_len=64)
    assert len(out) == 2


def test_a_dense_only_latent_block_hands_back_two_values_too():
    cfg = dataclasses.replace(TOY, num_layers=1)  # the dense layer alone
    assert latent.routed_layers(cfg) == 0
    params = PACKAGE.weights.make_decoder_params(cfg, 1)
    out = paged.paged_decode_forward(
        params, cfg, paged.init_paged_pools(cfg, 4, 16),
        jnp.asarray([[0, 1, 4, 4]]), jnp.asarray([[3]]), jnp.asarray([0]),
        block_size=16, rope_len=64)
    assert len(out) == 2


# ---- bytes, frequencies, the scale -----------------------------------------

def test_the_latent_pool_holds_1152_bytes_a_token_a_layer():
    assert kv_row_shapes(PUBLISHED) == {"c": (1, 576)}
    assert paged.kv_bytes_per_token(PUBLISHED) == 5 * 1152 == 5760
    mistral = DecoderConfig.mistral_7b()
    assert paged.kv_bytes_per_token(mistral) == 2 * 32 * 8 * 128 * 2 == 131072
    shapes = jax.eval_shape(lambda: paged.init_paged_pools(PUBLISHED, 2048, 16))
    assert {v.shape for v in shapes.values()} == {(32768, 1, 576)}
    assert sum(v.size * 2 for v in shapes.values()) == 32768 * 5760


def test_the_schema_is_the_trees_the_benchmark_makes():
    for cfg in (TOY, PUBLISHED):
        schema = {n: s for n, _k, s, _f in decoder_param_schema(cfg)}
        made = {**PACKAGE.weights.shapes(cfg),
                **PACKAGE.weights.norm_gains(cfg)}
        assert made == schema
    count = sum(math.prod(s) for s in PACKAGE.weights.shapes(PUBLISHED).values())
    assert round(count / 1e6, 1) == 5163.9, "ISSUE 35's arithmetic"


def test_yarn_frequencies_by_hand():
    """rope 64, theta 1e4, factor 40 over an original 4096: a pair that
    turns more than 32 times in 4096 positions keeps its frequency (pairs
    0..10), one that turns less than once takes a fortieth (pairs 23..31),
    a linear ramp between."""
    low = 64 * math.log(4096 / (32 * 2 * math.pi)) / (2 * math.log(1e4))
    high = 64 * math.log(4096 / (1 * 2 * math.pi)) / (2 * math.log(1e4))
    assert (math.floor(low), math.ceil(high)) == (10, 23)
    got = np.asarray(rope.yarn_inv_freq(64, 1e4, 40.0, 4096, 32.0, 1.0))
    plain = 1.0 / 1e4 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(got[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], plain[23:] / 40, rtol=1e-6)
    ramp = (17 - 10) / (23 - 10)
    np.testing.assert_allclose(
        got[17], plain[17] * (1 - ramp) + plain[17] / 40 * ramp, rtol=1e-6)
    # the reference's own tables (written after the published code) agree
    cos, sin = rope.yarn_rope_angles(
        64, 512, 1e4, factor=40.0, original_max_len=4096, mscale=0.707,
        mscale_all_dim=0.707)
    want_cos, want_sin = PACKAGE.reference._yarn_tables(PUBLISHED, 512)
    np.testing.assert_allclose(cos, want_cos, atol=2e-4)
    np.testing.assert_allclose(sin, want_sin, atol=2e-4)
    assert float(cos[0, 0]) == 1.0  # mscale / mscale_all_dim = 1


def test_the_softmax_scale_carries_m_squared():
    m = 0.1 * 0.707 * math.log(40) + 1
    assert m == pytest.approx(1.2608, abs=1e-4)
    assert rope.yarn_mscale(40.0, 0.707) == pytest.approx(m)
    assert rope.yarn_mscale(1.0, 0.707) == 1.0
    assert latent.softmax_scale(PUBLISHED) == pytest.approx(
        192 ** -0.5 * m * m)
    assert latent.softmax_scale(PUBLISHED) == pytest.approx(0.11472, abs=1e-5)


# ---- the seeded routers are level -------------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_the_seeded_routers_are_level(seed):
    """``weights.level_routers``: a router of the served tree answers
    nothing to what the attention layers before it write when they average
    their context (the rows of Wv_b Wo), and no direction favours a whole
    routing group — to bfloat16's rounding of the stored columns, where a
    column as drawn answers 0.09 (hidden ** -0.5) to a unit direction."""
    params = PACKAGE.weights.make_decoder_params(TOY, seed)
    h, groups = TOY.hidden_dim, TOY.expert_groups

    def f32(name):
        return np.asarray(params[name].astype(jnp.float32))

    written = [f32(f"l{i}_wv_b") @ f32(f"l{i}_wo") for i in range(3)]
    for i, before in ((1, written[:2]), (2, written[1:])):
        # nearest first, up to half the width: two layers of 32 rows
        w = f32(f"l{i}_router")
        assert w.shape == (h, TOY.num_experts)
        assert np.std(w) == pytest.approx(h ** -0.5, rel=0.01)
        sums = w.reshape(h, groups, -1).sum(-1)
        assert np.abs(sums).max() < 3e-3, "a group's columns sum to zero"
        for rows in before:
            unit = rows / np.linalg.norm(rows, axis=1, keepdims=True)
            assert np.abs(unit @ w).max() < 3e-3
    # and as drawn they are not: the dense layer's own w_up, same fan-in
    plain = f32("l0_w_up")[:, : TOY.num_experts]
    unit = written[0] / np.linalg.norm(written[0], axis=1, keepdims=True)
    assert np.abs(unit @ plain).max() > 0.1


# ---- no silent half-support -------------------------------------------------

def test_the_solo_engine_refuses_the_block_by_name():
    params = PACKAGE.weights.make_decoder_params(TOY, 1)
    engine = GenerateEngine(TOY, gen=GenerateConfig(), params=params,
                            use_flash=True)
    assert engine.use_flash is False, "no dense cache serves the block"
    with pytest.raises(NotImplementedError, match="deepseek_v2.*batcher"):
        engine.generate_ids([[5, 6, 7]], max_new_tokens=4)


@pytest.mark.parametrize("change, said", [
    (dict(head_dim=64), "head_dim"),
    (dict(num_kv_heads=2), "num_kv_heads"),
    (dict(quantize_weights=True), "quantize_weights"),
    (dict(experts_held_start=28), "experts held"),
    (dict(num_experts=0), "num_experts"),
    (dict(sliding_window=128), "sliding_window"),
])
def test_a_configuration_the_block_cannot_run_is_refused_by_field(
        change, said):
    with pytest.raises(ValueError, match=said):
        list(decoder_param_schema(dataclasses.replace(TOY, **change)))


def test_a_warm_prefill_of_the_latent_block_is_refused():
    with pytest.raises(NotImplementedError, match="prefix_cache"):
        paged.ragged_prefill_forward(
            {}, TOY, {}, None, None, None, None, None, rope_len=64,
            n_prefix_rows=256)


@pytest.mark.parametrize("gen, said", [
    (dict(), "generate.prefix_cache and generate.speculative_k"),
    (dict(speculative_k=0), "without generate.prefix_cache:"),
    (dict(prefix_cache=False), "without generate.speculative_k:"),
])
def test_the_batcher_refuses_at_construction_what_the_block_does_not_serve(
        gen, said):
    """With ``GenerateConfig``'s defaults (prefix cache on, speculation 4)
    the batcher must not construct over the latent block and fail later,
    on the worker thread, inside a request."""
    from docqa_tpu.engines.serve import ContinuousBatcher

    gen = dataclasses.replace(GenerateConfig(), max_concurrent=4, **gen)
    engine = GenerateEngine(
        TOY, gen=gen, params=PACKAGE.weights.make_decoder_params(TOY, 1))
    with pytest.raises(ValueError, match=said):
        ContinuousBatcher(engine, n_slots=4, chunk=4, cache_len=256,
                          kv_block_size=16)


# ---- the GQA block's programs came out the same ----------------------------

# sha256 (first 16 hex digits) and length of the lowered text of the toy
# Mistral batcher programs on the parent commit b097856 (jax 0.9.0, CPU):
# the programs ISSUE 35 may not move.  Byte-identical here.
LOWERED_BEFORE = {
    ("decode", 0): ("d8a690c2b01b84b4", 81076),
    ("decode", 4): ("0adc8a6d0f1a8b78", 95804),
    ("prefill", 0): ("be164d0b5721f45e", 66018),
    ("prefill", 4): ("ed3fc180859352bc", 73476),
    ("prefill_warm", 0): ("e0cf60edc30688e8", 90761),
    ("prefill_warm", 4): ("a0b9a0084309ff9f", 98219),
}


@pytest.fixture(scope="module")
def lowered():
    from docqa_tpu.engines.serve import ContinuousBatcher

    out = {}
    sds, i32 = jax.ShapeDtypeStruct, jnp.int32
    for spec_k in (0, 4):
        cfg = DecoderConfig(
            vocab_size=256, hidden_dim=64, num_layers=2, num_heads=4,
            num_kv_heads=2, head_dim=16, mlp_dim=128, max_seq_len=256,
            sliding_window=128)
        gen = dataclasses.replace(
            GenerateConfig(), speculative_k=spec_k, max_concurrent=4,
            decode_chunk=4)
        engine = GenerateEngine(cfg, gen=gen, seed=0, use_flash=False)
        b = ContinuousBatcher(engine, n_slots=4, chunk=4, cache_len=256,
                              kv_block_size=16)
        try:
            pools = jax.eval_shape(lambda: paged.init_paged_pools(
                b.cfg, b.n_blocks, b.block_size))
            params = jax.tree_util.tree_map(
                lambda a: sds(a.shape, a.dtype), engine.params)
            rng, table = sds((2,), jnp.uint32), sds((4, 256), i32)
            lane, flag = sds((4,), i32), sds((4,), jnp.bool_)
            spec = (table,) if spec_k else ()
            packed = (sds((256,), i32),) * 4 + (lane,) * 2
            tables = sds((4, b.blocks_per_seq), i32)
            out["prefill", spec_k] = b._get_prefill_fn().lower(
                params, pools, *spec, *packed, rng).as_text()
            out["prefill_warm", spec_k] = b._get_prefill_warm_fn().lower(
                params, pools, *spec, *packed, tables, lane, rng).as_text()
            args = (table, lane, lane, flag) if spec_k else (
                lane, lane, flag, rng)
            out["decode", spec_k] = b._get_decode_fn().lower(
                params, pools, tables, lane, *args).as_text()
        finally:
            b.stop()
    return out


@pytest.mark.parametrize("program", sorted(LOWERED_BEFORE))
def test_the_gqa_blocks_programs_lower_to_the_text_they_lowered_to(
        lowered, program):
    if jax.__version__ != "0.9.0":
        pytest.skip("the recorded text is jax 0.9.0's")
    text = lowered[program]
    digest, length = LOWERED_BEFORE[program]
    assert len(text) == length
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


# ---- through the batcher ----------------------------------------------------

def _counters():
    from docqa_tpu.models.latent import MOE_PREFILL_SUMS, MOE_SUMS
    from docqa_tpu.runtime.metrics import DEFAULT_REGISTRY

    return {n: DEFAULT_REGISTRY.counter(n).value
            for n in MOE_SUMS + MOE_PREFILL_SUMS}


def test_the_batcher_serves_the_block_and_counts_its_choices():
    """Prompts through the continuous batcher over the latent pool: the
    tokens are the greedy ones of the paged forwards run by hand, and the
    decode chunks' expert-choice sums land in the counters."""
    from docqa_tpu.engines.serve import ContinuousBatcher

    gen = dataclasses.replace(
        GenerateConfig(), speculative_k=0, prefix_cache=False, decode_chunk=4,
        max_concurrent=4)
    params = PACKAGE.weights.make_decoder_params(TOY, 2)
    engine = GenerateEngine(TOY, gen=gen, params=params)
    before = _counters()
    b = ContinuousBatcher(engine, n_slots=4, chunk=4, cache_len=256,
                          kv_block_size=16, prefix_cache=False)
    try:
        assert b._block.step_sum_names == latent.MOE_SUMS
        assert b.kv_bytes_per_token == 3 * 48 * 2
        assert not b._kernels.paged
        prompts = [[5 + (7 * i + j) % 500 for j in range(20 + 9 * i)]
                   for i in range(3)]
        handles = [b.submit_ids(p, max_new_tokens=9) for p in prompts]
        got = [h.result(timeout=300) for h in handles]
    finally:
        b.stop()
    gained = {k: v - before[k] for k, v in _counters().items()}
    k, layers = TOY.experts_per_token, 2
    # every live lane-step picks k experts in each routed layer; a lane
    # decodes 8 tokens after the one its prefill gave (the last chunk may
    # run a step past: 8 or 9 live steps a lane)
    lane_steps = gained["serve_moe_picks"] / (k * layers)
    assert lane_steps == int(lane_steps) and 3 * 8 <= lane_steps <= 3 * 9
    assert 0 < gained["serve_moe_picks_local"] < gained["serve_moe_picks"]
    assert 0 < gained["serve_moe_experts_touched"] <= gained[
        "serve_moe_picks_local"]
    assert gained["serve_moe_layer_steps"] % layers == 0
    assert gained["serve_moe_experts_touched"] <= 8 * gained[
        "serve_moe_layer_steps"]
    # the prefill's side: every prompt token (padding rows route too and
    # are not counted) picks k experts in each routed layer, some of them
    # held here
    assert gained["serve_moe_prefill_picks"] == k * layers * sum(
        len(p) for p in prompts)
    assert 0 < gained["serve_moe_prefill_picks_local"] < gained[
        "serve_moe_prefill_picks"]
    # the same answers by hand: prefill, then steps through a pool of one
    # lane, teacher-forced with the batcher's tokens — each of which has
    # to be the argmax there too, or within bfloat16's reach of it (the
    # chunk program's fused loop rounds otherwise than a step alone, and
    # seeded random weights leave near-ties: a second-best 0.06 under a
    # best of 2.67 was taken once; a wrong token would sit near the mean)
    def near_argmax(logits, token):
        top = float(logits.max())
        return top - float(logits[token]) <= 0.1 * (top - float(logits.mean()))

    for prompt, tokens in zip(prompts, got):
        assert len(tokens) == 9
        pools = paged.init_paged_pools(TOY, 16, 16)
        n = len(prompt)
        t = 128
        ids = jnp.asarray(prompt + [0] * (t - n), jnp.int32)
        seg = jnp.where(jnp.arange(t) < n, 0, -1).astype(jnp.int32)
        pos = jnp.arange(t, dtype=jnp.int32)
        logits, pools, _ = paged.ragged_prefill_forward(
            params, TOY, pools, ids, seg, pos,
            jnp.where(seg >= 0, pos, 256).astype(jnp.int32),
            jnp.asarray([n - 1]), rope_len=256)
        assert near_argmax(logits[0], tokens[0])
        tables = jnp.arange(16, dtype=jnp.int32)[None, :]
        for step in range(8):
            logits, pools, _ = paged.paged_decode_forward(
                params, TOY, pools, tables, jnp.asarray([[tokens[step]]]),
                jnp.asarray([n + step]), block_size=16, rope_len=256)
            assert near_argmax(logits[0, 0], tokens[step + 1]), (step, tokens)


# ---- the decode step's two forms (ISSUE 50) ---------------------------------

# the toy with a latent of one whole register: the narrowest the kernel reads
TOY128 = dataclasses.replace(TOY, kv_lora_rank=128)


@pytest.fixture
def latent_kernels_interpreted(monkeypatch):
    """``paged_latent_flash_decode`` and the grouped products (a prefill's
    ``gmm``, a decode step's kernel) as the forwards call them under
    ``use_flash``, interpreted: the one thing a CPU cannot take from
    them."""
    import importlib

    from docqa_tpu.models import routed
    from docqa_tpu.ops import grouped

    attention = importlib.import_module("docqa_tpu.ops.attention")
    real = attention.paged_latent_flash_decode
    monkeypatch.setattr(
        attention, "paged_latent_flash_decode",
        lambda *args, **kw: real(*args, **{**kw, "interpret": True}))
    monkeypatch.setattr(
        routed, "grouped_matmul",
        lambda *a, **kw: grouped.grouped_matmul(*a, **kw, interpret=True))
    monkeypatch.setattr(
        routed, "grouped_swiglu_step",
        lambda *a: grouped.grouped_swiglu_step(*a, interpret=True))


@pytest.mark.parametrize("s", [1, 3], ids=["a-step", "a-verify-step"])
def test_one_step_of_both_forms_leaves_the_same_record(
        s, latent_kernels_interpreted):
    """``paged_decode_forward`` with the kernel (``kernels.paged``) and with
    the gather on the same pools, through scattered tables, a free slot
    among the lanes: the routing record to the last id (a near-tie apart),
    the logits within the tolerance the block is held to against its
    reference."""
    cfg = TOY128
    params = PACKAGE.weights.make_decoder_params(cfg, 5)
    rng = np.random.default_rng(1)
    n_blocks, lanes = 64, 4
    pools = {
        k: jnp.asarray(0.5 * rng.standard_normal(v.shape, np.float32), v.dtype)
        for k, v in paged.init_paged_pools(cfg, n_blocks, 16).items()}
    tables = rng.permutation(n_blocks).reshape(lanes, 16).astype(np.int32)
    tables[2] = n_blocks  # a free slot: every entry a hole
    tok = jnp.asarray(rng.integers(5, 500, (lanes, s)), jnp.int32)
    lengths = jnp.asarray([200, 39, 0, 16], jnp.int32)
    forms = kernel_forms(cfg, on_tpu=True, mesh=None, block_size=16)
    assert forms.paged and forms.grouped
    out = {
        flash: paged.paged_decode_forward(
            params, cfg, dict(pools), jnp.asarray(tables), tok, lengths,
            block_size=16, rope_len=256,
            kernels=forms._replace(paged=flash))
        for flash in (True, False)}
    live = [0, 1, 3]
    record = np.asarray(out[False][2])
    assert record.shape == (2, lanes, s, 4)
    # the same choices, but for a near-tie a bfloat16 rounding tips (one
    # decision of the 18 under the verify step, one expert of its four)
    ids = [np.sort(np.asarray(out[f][2])[:, live], -1) for f in (True, False)]
    tipped = (ids[0] != ids[1]).any(-1)
    assert tipped.sum() <= 1, (ids[0][tipped], ids[1][tipped])
    for a, b in zip(ids[0][tipped], ids[1][tipped]):
        assert len(set(a) ^ set(b)) == 2
    got, want = (np.asarray(out[f][0], np.float32)[live] for f in (True, False))
    err = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(
        want - want.mean(-1, keepdims=True), axis=-1)
    assert err.shape == (3, s) and 0 < err.max() < TOLERANCE, err
    # both wrote the new rows at the same places, nothing for the free
    # slot: the first layer's to the bit (a row of it is the token's
    # alone), a later layer's to what the layers before it rounded
    for name in pools:
        wrote = [np.asarray(out[f][1][name], np.float32) for f in (True, False)]
        changed = [(w != np.asarray(pools[name], np.float32)).any(axis=(1, 2))
                   for w in wrote]
        assert (changed[0] == changed[1]).all() and changed[0].sum() == 3 * s
        np.testing.assert_allclose(
            *wrote, atol=0.0 if name == "c0" else 0.1, rtol=0.0)


def test_the_batcher_counts_the_chunks_that_read_live_pages_in_place(
        latent_kernels_interpreted):
    """An engine that saw a TPU (``use_flash``): every decode chunk's
    latent layers read the lanes' live pages through the kernel —
    interpreted here —, the counter over ``serve_decode_chunks`` reads 1.0
    and the rows read follow the form that ran; the tokens are the
    gather's."""
    from docqa_tpu.engines.serve import ContinuousBatcher
    from docqa_tpu.runtime.metrics import DEFAULT_REGISTRY

    params = PACKAGE.weights.make_decoder_params(TOY128, 2)
    names = ("serve_decode_chunks", "serve_latent_paged_chunks",
             "serve_routed_fused_chunks",
             "serve_decode_kv_rows_read", "serve_decode_kv_rows_live",
             "serve_moe_picks")
    prompts = [[5 + (7 * i + j) % 500 for j in range(20 + 9 * i)]
               for i in range(3)]
    gen = dataclasses.replace(
        GenerateConfig(), speculative_k=0, prefix_cache=False, decode_chunk=4,
        max_concurrent=4)
    got, gained = {}, {}
    for flash in (True, False):
        before = {n: DEFAULT_REGISTRY.counter(n).value for n in names}
        engine = GenerateEngine(TOY128, gen=gen, params=params, use_flash=flash)
        assert engine.use_flash is False, "no dense cache serves the block"
        b = ContinuousBatcher(engine, n_slots=4, chunk=4, cache_len=256,
                              kv_block_size=16, prefix_cache=False)
        try:
            assert b._kernels.paged == b._kernels.grouped == flash
            got[flash] = [list(h.result(timeout=600)) for h in
                          [b.submit_ids(p, max_new_tokens=9) for p in prompts]]
        finally:
            b.stop()
        gained[flash] = {
            n: DEFAULT_REGISTRY.counter(n).value - before[n] for n in names}
        chunks = gained[flash]["serve_decode_chunks"]
        assert chunks > 0
        assert gained[flash]["serve_latent_paged_chunks"] == (
            chunks if flash else 0)
        # four slots x four picks: a step to the routed layers' kernel
        # (ISSUE 54), interpreted here like the attention's
        assert gained[flash]["serve_routed_fused_chunks"] == (
            chunks if flash else 0)
    # the gather's tokens, until a near-tie of seeded random weights tips
    # (the first token is the prefill's, the same program in both)
    assert [len(t) for t in got[True]] == [9, 9, 9] == [
        len(t) for t in got[False]]
    same = [a == b for x, y in zip(got[True], got[False])
            for a, b in zip(x, y)]
    assert all(same[::9]) and sum(same) >= 18, (got, same)
    live = gained[True]["serve_decode_kv_rows_live"]
    assert live == gained[False]["serve_decode_kv_rows_live"] > 0
    # the kernel: a lane's live pages (at most a page of 16 rows over its
    # length); the gather: every slot's whole table, every step
    assert live <= gained[True]["serve_decode_kv_rows_read"] < live + 16 * (
        gained[True]["serve_moe_picks"] // (TOY128.experts_per_token * 2))
    assert gained[False]["serve_decode_kv_rows_read"] == (
        gained[False]["serve_decode_chunks"] * 4 * 4 * 256)


def test_a_block_that_does_not_route_adds_nothing_to_a_chunk():
    """The GQA block's batcher: no routed layers, so the decode program
    carries no sums (its lowered text is the parent's, above), its result
    array has one row a slot, and the worker's per-chunk branch is one
    integer test."""
    from docqa_tpu.engines.serve import ContinuousBatcher

    cfg = DecoderConfig(vocab_size=256, hidden_dim=64, num_layers=2,
                        num_heads=4, num_kv_heads=2, head_dim=16, mlp_dim=128,
                        max_seq_len=256)
    gen = dataclasses.replace(GenerateConfig(), speculative_k=0,
                              decode_chunk=4, max_concurrent=4)
    engine = GenerateEngine(cfg, gen=gen, seed=0, use_flash=False)
    before = _counters()
    b = ContinuousBatcher(engine, n_slots=4, chunk=4, cache_len=256,
                          kv_block_size=16)
    try:
        assert b._block.step_sum_names == ()
        out = jax.eval_shape(
            b._decode_program, engine.params,
            paged.init_paged_pools(cfg, b.n_blocks, b.block_size),
            jnp.zeros((4, b.blocks_per_seq), jnp.int32),
            jnp.zeros((4,), jnp.int32), jnp.zeros((4,), jnp.int32),
            jnp.zeros((4,), jnp.int32), jnp.zeros((4,), bool),
            jax.random.PRNGKey(0))
        assert out[-1].shape == (4, 2 * 4 + 1)
        assert b.submit_ids([5, 6, 7], max_new_tokens=6).result(timeout=300)
    finally:
        b.stop()
    assert _counters() == before
