"""ISSUE 38: the two-mixer block (``models/hybrid.py``: decayed linear
attention with a state a lane, block-sparse attention that selects inside
the paged cache) through the paged forwards and the batcher, against the
plain reference of ``benchmark/architectures/minicpm_sala/`` — CPU, small
sizes, seeded weights.

* paged prefill then decode equal the reference on logits, with toy sparse
  sizes so that a lane crosses ``dense_len`` mid-decode;
* recurrence = chunked form = quadratic form of the linear mixer;
* two prompts packed in one dispatch equal the same prompts alone (state
  reset, no compressed-key window across segments);
* a reused slot of the batcher equals a fresh batcher;
* ``mixer_types`` order is honoured, and each muP scaling bites;
* the int8 tree against the float tree within the int8 limit;
* the selection record has the contract's shape, the choice gap is 0 under
  the reference's own record, and the routing control fails it;
* the schema counts 9,476.8 M matrix parameters at the published sizes;
  state and row bytes by hand;
* the GQA and the latent block's toy programs lower to the text they
  lowered to on the parent;
* the solo engine and the batcher refuse by name what the block does not
  serve; a block without chunk sums adds nothing to a chunk.
"""

import dataclasses
import hashlib
import json
import math
import os
import sys

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
from docqa_tpu.models import hybrid  # noqa: E402
from docqa_tpu.models.decoder import (  # noqa: E402
    decoder_param_schema,
    init_decoder_params,
    kv_row_shapes,
    lane_state_shapes,
)
from docqa_tpu.ops.attention import (  # noqa: E402
    linear_attention_prefill,
    linear_attention_step,
)
from harness import arch, check, child  # noqa: E402

PACKAGE = arch.load({"architecture": "minicpm_sala"})
# float32 so that program and reference differ by rounding order alone;
# blocks of 8 tokens, top 4 (32 tokens), dense below 40: a 30-token lane
# crosses the switch after ten decode steps, a 50-token prompt selects
TOY = DecoderConfig(
    vocab_size=256, hidden_dim=64, num_layers=4, num_heads=4, num_kv_heads=2,
    head_dim=16, mlp_dim=128, max_seq_len=256, norm_eps=1e-6,
    block="sparse_linear", dtype="float32",
    mixer_types=("sparse", "linear", "linear", "sparse"), linear_heads=4,
    linear_head_dim=16, scale_emb=12.0, scale_depth=1.4, dim_model_base=16,
    sparse_kernel_size=8, sparse_kernel_stride=4, sparse_block_size=8,
    sparse_topk=4, sparse_init_blocks=1, sparse_window_size=8,
    sparse_dense_len=40,
)
BS, CAP = 16, 256
N_DECISIONS = 2 * 2  # sparse layers x kv heads


@pytest.fixture(scope="module")
def params():
    return init_decoder_params(jax.random.PRNGKey(0), TOY)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(5, 256, size=(2, 128))


def run_program(cfg, params, tokens, lengths, steps, starts=None, pools=None,
                tables=None):
    """Prefill ``lengths[b]`` tokens of lane b in ONE packed dispatch, then
    ``steps`` teacher-forced decode steps: (logits [lanes, 1 + steps, v],
    record int32 [decisions, lanes, 128, topk], pools)."""
    lanes = len(lengths)
    n_blocks = lanes * CAP // BS
    if pools is None:
        pools = paged.init_paged_pools(cfg, n_blocks, BS)
    starts = starts or [128 * b for b in range(lanes)]
    t = 128 * lanes
    ids = np.zeros(t, np.int32)
    seg = np.full(t, -1, np.int32)
    pos = np.zeros(t, np.int32)
    dest = np.full(t, n_blocks * BS, np.int32)
    last = np.zeros(lanes, np.int32)
    for b, (st, n) in enumerate(zip(starts, lengths)):
        ids[st:st + n] = tokens[b, :n]
        seg[st:st + n] = b
        pos[st:st + n] = np.arange(n)
        dest[st:st + n] = b * CAP + np.arange(n)
        last[b] = st + n - 1
    logits, pools, rec = paged.ragged_prefill_forward(
        params, cfg, pools, *map(jnp.asarray, (ids, seg, pos, dest, last)),
        rope_len=CAP)
    assert rec.shape == (N_DECISIONS, t, cfg.sparse_topk)
    record = np.full((rec.shape[0], lanes, 128, rec.shape[2]), -1, np.int32)
    for b, (st, n) in enumerate(zip(starts, lengths)):
        record[:, b, :n] = np.asarray(rec)[:, st:st + n]
    out = [np.asarray(logits)[:, None]]
    if tables is None:
        tables = jnp.arange(n_blocks, dtype=jnp.int32).reshape(lanes, -1)
    lens = np.asarray(lengths, np.int32)
    for _ in range(steps):
        tok = np.stack([tokens[b, lens[b]:lens[b] + 1] for b in range(lanes)])
        logits, pools, rec = paged.paged_decode_forward(
            params, cfg, pools, tables, jnp.asarray(tok), jnp.asarray(lens),
            block_size=BS, rope_len=CAP)
        assert rec.shape == (N_DECISIONS, lanes, 1, cfg.sparse_topk)
        out.append(np.asarray(logits))
        for b in range(lanes):
            record[:, b, lens[b]] = np.asarray(rec)[:, b, 0]
        lens = lens + 1
    return np.concatenate(out, 1), record, pools


def reference(cfg, params, tokens, lengths, steps, routing=None,
              control=None):
    s = max(lengths) + steps
    rows = np.asarray(lengths)[:, None] - 1 + np.arange(steps + 1)[None, :]
    if routing is not None:
        routing = routing[:, :, :s]
    logits, gap, taken = PACKAGE.reference.forward_logits(
        params, cfg, tokens[:, :s], rows, control=control, routing=routing,
        prompt_lengths=np.asarray(lengths))
    return np.asarray(logits), np.asarray(gap), np.asarray(taken)


def rel_err(got, want):
    centred = want - want.mean(-1, keepdims=True)
    return (np.linalg.norm(got - want, axis=-1)
            / np.linalg.norm(centred, axis=-1))


# ---- the program against the reference --------------------------------------

LENGTHS, STEPS = [50, 30], 14


@pytest.fixture(scope="module")
def served(params, tokens):
    return run_program(TOY, params, tokens, LENGTHS, STEPS)


@pytest.mark.parametrize("replay", [False, True], ids=["own", "replay"])
def test_paged_prefill_then_decode_agree_with_the_reference(
        params, tokens, served, replay):
    """Lane 0 (50 tokens) selects from its prefill on; lane 1 (30) runs
    dense and crosses ``dense_len`` 40 at its tenth decode step."""
    got, record, _ = served
    want, gap, taken = reference(
        TOY, params, tokens, LENGTHS, STEPS, record if replay else None)
    assert rel_err(got, want).max() < 1e-4
    held = record[..., 0] >= 0
    assert held[:, 0, :50 + STEPS].all() and not held[:, 1, :39].any()
    assert held[:, 1, 39:30 + STEPS].all()  # 40 tokens and more: selects
    # float32 on both sides: the program's sets are the reference's own
    assert gap.max() == 0.0
    s = max(LENGTHS) + STEPS
    assert (np.sort(taken[held[:, :, :s]]) == np.sort(
        record[:, :, :s][held[:, :, :s]])).all()


def test_the_record_has_the_contracts_shape_and_ids(served):
    """int32 [sparse layers x kv heads, lanes, rows, topk]; a row that
    selected starts with a forced block (id >= 0), holds no block twice and
    none past its own; -1 where fewer blocks exist and on dense rows."""
    _, record, _ = served
    assert record.dtype == np.int32 and record.shape == (N_DECISIONS, 2, 128, 4)
    for t in range(50 + STEPS):
        for row in record[:, 0, t]:
            ids = row[row >= 0]
            assert row[0] >= 0 and len(set(ids)) == len(ids)
            assert ids.max() <= t // 8 and 0 in ids and t // 8 in ids
    assert (record[:, 1, :39] == -1).all()


def test_the_routing_control_fails_the_gap_and_replay_hides_it_from_logits(
        params, tokens, served):
    _, record, _ = served
    control = PACKAGE.weights.controls_for(TOY)["mean_over_windows"]
    sound, _, _ = reference(TOY, params, tokens, LENGTHS, STEPS, record)
    _, _, own = reference(TOY, params, tokens, LENGTHS, STEPS, None, control)
    s = max(LENGTHS) + STEPS
    full = np.full_like(record, -1)
    full[:, :, :s] = own
    full[record < 0] = -1
    replayed, gap, _ = reference(TOY, params, tokens, LENGTHS, STEPS, full)
    assert gap.max() > 0.01  # the sound run's is 0
    # under the program's record the control's logits are the sound ones
    under, _, _ = reference(
        TOY, params, tokens, LENGTHS, STEPS, record, control)
    assert rel_err(under, sound).max() < 1e-5
    assert np.isfinite(replayed).all()


def test_a_record_that_names_no_block_set_is_refused(params, tokens, served):
    _, record, _ = served
    bad = record.copy()
    bad[0, 0, 45, 1] = bad[0, 0, 45, 2]
    with pytest.raises(ValueError, match="twice"):
        reference(TOY, params, tokens, LENGTHS, STEPS, bad)
    with pytest.raises(ValueError, match="wants int32"):
        PACKAGE.reference.forward_logits(
            params, TOY, tokens[:, :64], np.zeros((2, 1), np.int32),
            routing=record[:, :, :64, :2])


def test_the_lane_state_in_bfloat16_moves_the_logits(params, tokens, served):
    """The control one step below the float32 state."""
    got, record, _ = served
    control = PACKAGE.weights.kv_only_controls()["state_bf16"]
    sound, _, _ = reference(TOY, params, tokens, LENGTHS, STEPS, record)
    rounded, _, _ = reference(
        TOY, params, tokens, LENGTHS, STEPS, record, control)
    assert rel_err(rounded, sound).max() > 20 * rel_err(got, sound).max()


# ---- the linear mixer's three forms -----------------------------------------

def test_recurrence_chunked_form_and_quadratic_form_agree():
    rng = np.random.default_rng(1)
    t, heads, d = 256, 4, 16
    q, k, v = (jnp.asarray(rng.standard_normal((t, heads, d)), jnp.float32)
               for _ in range(3))
    slopes = hybrid.decay_slopes(TOY, 1)
    n = 200  # one segment of 200 rows, padding after
    seg = jnp.where(jnp.arange(t) < n, 0, -1)
    pos = jnp.where(jnp.arange(t) < n, jnp.arange(t), 0)
    pool = jnp.ones((2, heads, d, d), jnp.float32)  # stale: must be reset
    slot = jnp.asarray([7, 1], jnp.int32)  # the last chunk writes entry 1
    chunked, pool = linear_attention_prefill(
        q, k, v, seg, pos, slopes, pool, slot)
    with jax.default_matmul_precision("highest"):
        scan = PACKAGE.reference.lightning(
            q[:n], k[:n], v[:n], slopes, lambda x, _what: x)
        rel = jnp.arange(n)[:, None] - jnp.arange(n)[None, :]
        m = jnp.where(rel >= 0, jnp.exp(
            -slopes[:, None, None] * jnp.maximum(rel, 0)), 0.0)
        quad = jnp.einsum(
            "hts,she->the",
            jnp.einsum("thd,shd->hts", q[:n], k[:n]) / math.sqrt(d) * m,
            v[:n])
    assert np.allclose(chunked[:n], scan, rtol=1e-4, atol=1e-4)
    assert np.allclose(quad, scan, rtol=1e-4, atol=1e-4)
    # the state left in the pool is the recurrence's after row n - 1 ...
    state = jnp.zeros((heads, d, d))
    for i in range(n):
        state = jnp.exp(-slopes)[:, None, None] * state + (
            k[i][:, :, None] * v[i][:, None, :])
    assert np.allclose(pool[1], state, rtol=1e-4, atol=1e-4)
    assert (pool[0] == 1.0).all()
    # ... and one decode step from it is row n of a longer scan
    out, _ = linear_attention_step(
        q[n][None], k[n][None], v[n][None], pool[1][None], slopes)
    with jax.default_matmul_precision("highest"):
        longer = PACKAGE.reference.lightning(
            q[:n + 1], k[:n + 1], v[:n + 1], slopes, lambda x, _what: x)
    assert np.allclose(out[0], longer[n], rtol=1e-4, atol=1e-4)


# ---- packing, slots ---------------------------------------------------------

def test_two_prompts_packed_in_one_dispatch_equal_the_same_prompts_alone(
        params, tokens, served):
    """A segment's first row resets the state; no compressed-key window
    straddles two segments; tables need not lie lane after lane."""
    together, _, _ = served
    for b, n in enumerate(LENGTHS):
        alone, _, _ = run_program(TOY, params, tokens[b:b + 1], [n], STEPS)
        assert np.abs(alone[0] - together[b]).max() < 1e-4
    # lane 0 packed SECOND, its pages scattered, its state in entry 1
    n_blocks = 2 * CAP // BS
    perm = np.random.default_rng(3).permutation(n_blocks).astype(np.int32)
    pools = paged.init_paged_pools(TOY, n_blocks, BS)
    slot = np.zeros(n_blocks * BS, np.int32)
    slot[perm[0] * BS] = 1
    pools[paged.STATE_SLOT] = jnp.asarray(slot)
    t = 256
    ids, seg = np.zeros(t, np.int32), np.full(t, -1, np.int32)
    pos, dest = np.zeros(t, np.int32), np.full(t, n_blocks * BS, np.int32)
    n = LENGTHS[0]
    ids[128:128 + n], seg[128:128 + n] = tokens[0, :n], 0
    pos[128:128 + n] = np.arange(n)
    dest[128:128 + n] = perm[np.arange(n) // BS] * BS + np.arange(n) % BS
    logits, pools, _ = paged.ragged_prefill_forward(
        params, TOY, pools, *map(jnp.asarray, (ids, seg, pos, dest)),
        jnp.asarray([128 + n - 1]), rope_len=CAP)
    assert np.abs(np.asarray(logits)[0] - together[0, 0]).max() < 1e-4
    assert float(jnp.abs(pools["s1"][0]).max()) == 0.0
    assert float(jnp.abs(pools["s1"][1]).max()) > 0.0
    lg, pools, _ = paged.paged_decode_forward(
        params, TOY, pools, jnp.asarray(perm[None, :CAP // BS]),
        jnp.asarray(tokens[:1, n:n + 1]), jnp.asarray([n]),
        block_size=BS, rope_len=CAP)
    assert np.abs(np.asarray(lg)[0, 0] - together[0, 1]).max() < 1e-4


def test_a_retired_lane_reads_zeros_and_writes_nothing(params, tokens, served):
    _, _, pools = served
    before = {k: np.asarray(v) for k, v in pools.items()}
    holes = jnp.full((2, CAP // BS), 2 * CAP // BS, jnp.int32)
    _, after, _ = paged.paged_decode_forward(
        params, TOY, dict(pools), holes, jnp.asarray(tokens[:, :1]),
        jnp.asarray([60, 40]), block_size=BS, rope_len=CAP)
    for name, value in after.items():
        assert (np.asarray(value) == before[name]).all(), name


BF16 = dataclasses.replace(TOY, dtype="bfloat16", quantize_weights=True)


def _batcher(n_slots, params):
    from docqa_tpu.engines.serve import ContinuousBatcher

    gen = dataclasses.replace(
        GenerateConfig(), speculative_k=0, prefix_cache=False, decode_chunk=4,
        max_concurrent=n_slots)
    engine = GenerateEngine(BF16, gen=gen, params=params)
    return ContinuousBatcher(engine, n_slots=n_slots, chunk=4, cache_len=256,
                             kv_block_size=16, prefix_cache=False)


def _sums():
    from docqa_tpu.models.hybrid import SPARSE_SUMS
    from docqa_tpu.runtime.metrics import DEFAULT_REGISTRY

    names = SPARSE_SUMS + ("serve_state_bytes_rw", "serve_lane_state_resets")
    return {n: DEFAULT_REGISTRY.counter(n).value for n in names}


def test_a_reused_slot_equals_a_fresh_batcher_and_the_sums_are_counted():
    """Four prompts through two slots (each slot serves two lanes, the
    second from a zeroed state) give the tokens each gives alone in a fresh
    batcher; the decode chunks' sums land in the counters."""
    served_params = PACKAGE.weights.make_decoder_params(BF16, 3)
    prompts = [[5 + (7 * i + j) % 250 for j in range(30 + 15 * i)]
               for i in range(4)]
    before = _sums()
    b = _batcher(2, served_params)
    try:
        assert b.kv_bytes_per_token == 2 * (2 * 2 * 16 * 2 + 2 * 16 * 2 // 4)
        occ = b.kv_block_occupancy()
        assert occ["state_bytes_per_lane"] == 2 * 4 * 16 * 16 * 4
        got = [h.result(timeout=600) for h in
               [b.submit_ids(p, max_new_tokens=12) for p in prompts]]
    finally:
        b.stop()
    gained = {k: v - before[k] for k, v in _sums().items()}
    assert gained["serve_lane_state_resets"] == 4
    steps = gained["serve_state_lane_steps"]
    assert steps >= sum(len(g) - 1 for g in got)
    assert gained["serve_state_bytes_rw"] == steps * 2 * 2 * 4 * 16 * 16 * 4
    assert 0 < gained["serve_sparse_dense_lane_steps"] < steps
    assert 0 < gained["serve_sparse_blocks_selected"] < (
        gained["serve_sparse_blocks_live"])
    for prompt, tokens in zip(prompts, got):
        fresh = _batcher(1, served_params)
        try:
            alone = fresh.submit_ids(prompt, max_new_tokens=12).result(
                timeout=600)
        finally:
            fresh.stop()
        assert list(alone) == list(tokens)


def test_a_block_without_chunk_sums_adds_nothing_to_a_chunk():
    """The GQA block's decode program still hands back one row a slot and
    its batcher moves none of the new counters (PR 35's integer test,
    extended to the two-mixer block's sums)."""
    from docqa_tpu.engines.serve import ContinuousBatcher

    cfg = DecoderConfig(vocab_size=256, hidden_dim=64, num_layers=2,
                        num_heads=4, num_kv_heads=2, head_dim=16, mlp_dim=128,
                        max_seq_len=256)
    gen = dataclasses.replace(GenerateConfig(), speculative_k=0,
                              decode_chunk=4, max_concurrent=4)
    engine = GenerateEngine(cfg, gen=gen, seed=0, use_flash=False)
    before = _sums()
    b = ContinuousBatcher(engine, n_slots=4, chunk=4, cache_len=256,
                          kv_block_size=16)
    try:
        assert b._block.step_sum_names == () and not b._block.lane_state
        assert "state_bytes_per_lane" not in b.kv_block_occupancy()
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
    assert _sums() == before


# ---- what the configuration says bites ---------------------------------------

def _last_logits(cfg, params, tokens):
    got, _, _ = run_program(cfg, params, tokens[:1], [50], 0)
    return got[0, 0]


def test_the_order_of_mixer_types_is_honoured(params, tokens):
    """Two layers of each kind swapped: other shapes at those indices, so
    a tree made for the new order; with the SAME tensors under the new
    names the logits differ."""
    swapped = dataclasses.replace(
        TOY, mixer_types=("linear", "sparse", "linear", "sparse"))
    swap = {"l0_": "l1_", "l1_": "l0_"}
    moved = {swap.get(n[:3], n[:3]) + n[3:]: v for n, v in params.items()}
    assert {n: s for n, _k, s, _f in decoder_param_schema(swapped)} == {
        n: tuple(v.shape) for n, v in moved.items()}
    base = _last_logits(TOY, params, tokens)
    assert rel_err(_last_logits(swapped, moved, tokens)[None], base[None])[
        0] > 0.05


@pytest.mark.parametrize("field, plain", [
    ("scale_emb", 1.0), ("scale_depth", 0.0), ("dim_model_base", 0)])
def test_each_mup_scaling_bites(params, tokens, field, plain):
    base = _last_logits(TOY, params, tokens)
    dropped = _last_logits(
        dataclasses.replace(TOY, **{field: plain}), params, tokens)
    assert rel_err(dropped[None], base[None])[0] > 0.05
    want, _, _ = reference(TOY, params, tokens[:1], [50], 0)
    assert rel_err(base[None], want[0])[0] < 1e-4  # and the program has it


def test_by_hand_the_scalings_and_the_slopes():
    assert hybrid.residual_scale(TOY) == pytest.approx(1.4 / 2.0)
    assert hybrid.logit_scale(TOY) == pytest.approx(16 / 64)
    s = np.asarray(hybrid.decay_slopes(TOY, 1))
    assert s == pytest.approx(
        [2.0 ** (-8 * (h + 1) / 4) * (1 - 1 / 3 + 1e-5) for h in range(4)])
    assert np.asarray(PACKAGE.reference.slopes(TOY, 1)) == pytest.approx(s)


def test_the_int8_tree_against_the_float_tree(tokens):
    """The served int8 tree (w8a16) through the program, against the
    reference over the SAME draws unquantized: within the int8 step, and
    a tree that quantizes nothing would read 0."""
    served = PACKAGE.weights.make_decoder_params(BF16, 5)
    assert served["l0_w_ogate"].dtype == jnp.int8
    assert served["l1_o_norm_g"].dtype == jnp.bfloat16
    got, record, _ = run_program(BF16, served, tokens, LENGTHS, 2)
    want, gap, _ = reference(BF16, served, tokens, LENGTHS, 2, record)
    err = rel_err(got, want).max()
    assert 1e-4 < err < 0.05 and gap.max() < 5e-3
    coarse = PACKAGE.weights.controls_for(BF16)["w_int4"]
    lower, _, _ = reference(BF16, served, tokens, LENGTHS, 2, record, coarse)
    assert rel_err(lower, want).max() > 2 * err


# ---- sizes by hand -----------------------------------------------------------

def published():
    with open(os.path.join(
            BENCH_DIR, "configs", "minicpm-sala-int8.json")) as f:
        conf = json.load(f)
    from docqa_tpu.config import load_config

    return conf, load_config(
        env={}, overrides=child.program_overrides(conf)).decoder


def test_the_schema_counts_the_published_parameters():
    conf, cfg = published()
    mats = {n: s for n, k, s, _f in decoder_param_schema(cfg) if k == "normal"}
    count = sum(math.prod(s) for s in mats.values())
    lightning = 5 * 4096 * 4096 + 3 * 4096 * 16384
    sparse = 3 * 4096 * 4096 + 2 * 4096 * 256 + 3 * 4096 * 16384
    assert count == 24 * lightning + 8 * sparse + 2 * 73448 * 4096
    assert round(count / 1e6, 1) == 9476.8
    by_part = PACKAGE.shapes.matrix_params(conf)
    assert sum(by_part.values()) == count
    gains = sum(math.prod(s) for _n, k, s, _f in decoder_param_schema(cfg)
                if k == "ones")
    assert gains == 4096 + 32 * (2 * 4096 + 256) + 24 * 4096
    assert cfg.mixer_types.count("sparse") == 8
    assert [i for i, m in enumerate(cfg.mixer_types) if m == "sparse"] == [
        0, 9, 16, 17, 22, 29, 30, 31]


def test_the_tree_the_benchmark_makes_is_the_schemas():
    made = jax.eval_shape(
        lambda: PACKAGE.weights.make_decoder_params(BF16, 1))
    from docqa_tpu.models.quant import SCALE_SUFFIX, should_quantize

    want = {}
    for name, kind, shape, _f in decoder_param_schema(BF16):
        quant = kind == "normal" and should_quantize(name)
        want[name] = (shape, jnp.int8 if quant else jnp.bfloat16)
        if quant:
            want[name + SCALE_SUFFIX] = ((shape[1],), jnp.float32)
    assert {n: (tuple(v.shape), v.dtype) for n, v in made.items()} == want


def test_state_and_row_bytes_by_hand():
    conf, cfg = published()
    assert paged.kv_bytes_per_token(cfg) == 8 * (1024 + 32) == 8448
    assert PACKAGE.shapes.kv_bytes_per_token(conf) == 8448
    assert hybrid.lane_state_bytes(cfg) == 24 * 32 * 128 * 128 * 4 == 50331648
    assert PACKAGE.shapes.lane_state_bytes(conf) == 50331648
    assert kv_row_shapes(cfg, 0) == {"k": (2, 128), "v": (2, 128)}
    assert kv_row_shapes(cfg, 1) == {}
    assert len(lane_state_shapes(cfg)) == 24
    assert lane_state_shapes(TOY) == {"s1": (4, 16, 16), "s2": (4, 16, 16)}
    pools = jax.eval_shape(lambda: paged.init_paged_pools(cfg, 2432, 16))
    assert pools["ck0"].shape == (2432, 2, 128)
    assert pools["s1"].shape == (4, 32, 128, 128)
    assert pools["s1"].dtype == jnp.float32
    assert "k1" not in pools and "s0" not in pools
    assert min(8 * v.dtype.itemsize for v in pools.values()) == 16
    # the default slot map: lanes laid out one after the other
    slot = np.asarray(paged.init_paged_pools(TOY, 32, 16)[paged.STATE_SLOT])
    assert slot[0] == 0 and slot[255] == 0 and slot[256] == 1


# ---- refusals, by name -------------------------------------------------------

def test_the_solo_engine_refuses_the_block_by_name():
    gen = dataclasses.replace(GenerateConfig(), speculative_k=0)
    engine = GenerateEngine(TOY, gen=gen, seed=0)
    with pytest.raises(NotImplementedError, match="sparse_linear"):
        engine.generate_ids([[5, 6, 7]], max_new_tokens=2)


@pytest.mark.parametrize("gen, qos, said", [
    ({"prefix_cache": True, "speculative_k": 0}, None,
     "generate.prefix_cache"),
    ({"prefix_cache": False, "speculative_k": 4}, None,
     "generate.speculative_k"),
    ({"prefix_cache": False, "speculative_k": 0}, "on", "qos.preemption"),
], ids=["prefix_cache", "speculation", "preemption"])
def test_the_batcher_refuses_at_construction_what_the_block_does_not_serve(
        gen, qos, said):
    from docqa_tpu.engines.qos import QoSPolicy
    from docqa_tpu.engines.serve import ContinuousBatcher

    gen = dataclasses.replace(GenerateConfig(), max_concurrent=2, **gen)
    engine = GenerateEngine(TOY, gen=gen, seed=0)
    policy = QoSPolicy(preemption=qos) if qos else None
    with pytest.raises(ValueError, match=said):
        ContinuousBatcher(engine, n_slots=2, chunk=4, cache_len=256,
                          kv_block_size=16, qos=policy)


def test_a_warm_prefill_and_a_verify_step_are_refused(params):
    pools = paged.init_paged_pools(TOY, 16, 16)
    z = jnp.zeros((128,), jnp.int32)
    with pytest.raises(NotImplementedError, match="prefix_cache"):
        paged.ragged_prefill_forward(
            params, TOY, pools, z, z, z, z, jnp.zeros((1,), jnp.int32),
            rope_len=256, n_prefix_rows=256)
    with pytest.raises(NotImplementedError, match="speculative_k"):
        paged.paged_decode_forward(
            params, TOY, pools, jnp.zeros((1, 16), jnp.int32),
            jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32),
            block_size=16, rope_len=256)


@pytest.mark.parametrize("change, said", [
    ({"mixer_types": ("sparse", "linear")}, "len"),
    ({"mixer_types": ("sparse", "linear", "conv", "sparse")}, "conv"),
    ({"linear_heads": 0}, "linear_heads"),
    ({"sparse_block_size": 6}, "multiples"),
    ({"sliding_window": 64}, "sliding_window"),
])
def test_a_configuration_the_block_cannot_run_is_refused_by_field(
        change, said):
    with pytest.raises(ValueError, match=said):
        hybrid.check_hybrid_config(dataclasses.replace(TOY, **change))


# ---- the other blocks' programs came out the same ----------------------------

# sha256 (first 16 hex digits) and length of the lowered text of the toy
# LATENT batcher programs (jax 0.9.0, CPU): the programs a change to
# THIS stack may not move (the GQA block's: PR 35's test in
# tests/test_latent_block.py).  Recorded on the parent commit 6710925 by
# ISSUE 38 (decode c5237060d2c653f2 / 178793, prefill 2957852ca188bc6e /
# 164811) and again by ISSUE 45, which moved both on purpose: the held
# experts' forty ``lax.cond`` turns became one grouped product
# (models/latent.held_experts_sum) and the prefill sums its picks.
LATENT_LOWERED_BEFORE = {
    "decode": ("f50d0a54168d26ee", 174037),
    "prefill": ("9bf55701c84d7e7a", 163877),
}
LATENT_TOY = DecoderConfig(
    vocab_size=512, hidden_dim=128, num_layers=3, num_heads=4, num_kv_heads=1,
    head_dim=48, mlp_dim=256, max_seq_len=256, norm_eps=1e-6, block="mla_moe",
    q_lora_rank=64, kv_lora_rank=32, qk_nope_head_dim=32, qk_rope_head_dim=16,
    v_head_dim=32, rope_scaling_factor=40.0, rope_original_max_len=64,
    rope_mscale=0.707, rope_mscale_all_dim=0.707, first_dense_layers=1,
    num_experts=32, experts_per_token=4, expert_dim=64, num_shared_experts=2,
    expert_groups=4, expert_groups_per_token=2, routed_scale=16.0,
    experts_held_start=8, experts_held=8)


@pytest.fixture(scope="module")
def latent_lowered():
    from docqa_tpu.engines.serve import ContinuousBatcher

    package = arch.load({"architecture": "deepseek_v2"})
    sds, i32 = jax.ShapeDtypeStruct, jnp.int32
    gen = dataclasses.replace(
        GenerateConfig(), speculative_k=0, prefix_cache=False,
        max_concurrent=4, decode_chunk=4)
    engine = GenerateEngine(
        LATENT_TOY, gen=gen, use_flash=False,
        params=package.weights.make_decoder_params(LATENT_TOY, 1))
    b = ContinuousBatcher(engine, n_slots=4, chunk=4, cache_len=256,
                          kv_block_size=16, prefix_cache=False)
    try:
        pools = jax.eval_shape(lambda: paged.init_paged_pools(
            b.cfg, b.n_blocks, b.block_size))
        params = jax.tree_util.tree_map(
            lambda a: sds(a.shape, a.dtype), engine.params)
        rng = sds((2,), jnp.uint32)
        lane, flag = sds((4,), i32), sds((4,), jnp.bool_)
        packed = (sds((256,), i32),) * 4 + (lane,) * 2
        tables = sds((4, b.blocks_per_seq), i32)
        return {
            "prefill": b._get_prefill_fn().lower(
                params, pools, *packed, rng).as_text(),
            "decode": b._get_decode_fn().lower(
                params, pools, tables, lane, lane, lane, flag, rng).as_text(),
        }
    finally:
        b.stop()


@pytest.mark.parametrize("program", sorted(LATENT_LOWERED_BEFORE))
def test_the_latent_blocks_programs_lower_to_the_text_they_lowered_to(
        latent_lowered, program):
    if jax.__version__ != "0.9.0":
        pytest.skip("the recorded text is jax 0.9.0's")
    text = latent_lowered[program]
    digest, length = LATENT_LOWERED_BEFORE[program]
    assert len(text) == length
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


# ---- through the harness's own comparison ------------------------------------

def test_the_harness_comparison_at_a_small_size_with_every_control():
    """``check.decoder_check`` as ``calibrate.py`` runs it: the program
    under the limit a file of this size would state, every control over
    its own."""
    cfg = dataclasses.replace(
        BF16, max_seq_len=512, sparse_dense_len=256, sparse_block_size=16,
        sparse_topk=6, sparse_window_size=32)
    import types

    engine = types.SimpleNamespace(
        cfg=cfg, params=PACKAGE.weights.make_decoder_params(cfg, 11),
        use_flash=False)
    out = check.decoder_check(
        PACKAGE, {"prompt_lengths": [300, 330], "lane_rows": 384}, engine, 11,
        n_blocks=64, block_size=16, seq_capacity=512, n_lanes=2,
        step_width=1, control=True)
    assert out["kv_bits"] == 16
    program = out["program"]["worst_row"]
    assert program < 0.03 and out["routing"]["worst_gap"] < 2e-3
    assert out["routing"]["decisions"] > 1000
    for name, reading in out["controls"].items():
        if name == "mean_over_windows":
            assert reading["worst_gap"] > 5 * out["routing"]["worst_gap"]
        else:
            assert reading["worst_row"] > 1.2 * program, name
    assert set(out["kv_only"]) == {"kv_int8", "state_bf16"}


def test_the_lane_state_stays_float32_through_both_forwards(served):
    """What ``correct`` cannot hold yet (its ``kv_bits`` is the narrowest
    pool array, the bf16 rows; a bf16 state moves the logits a tenth of
    what bf16 arithmetic does): the state pools the prefill and the decode
    steps hand back are float32, and so is what the schema says a lane
    holds."""
    _, _, pools = served
    state = {n: v for n, v in pools.items() if n.startswith("s") and n[1:].isdigit()}
    assert sorted(state) == sorted(lane_state_shapes(TOY))
    assert all(v.dtype == np.float32 for v in state.values())
    bf16 = paged.init_paged_pools(BF16, 2 * CAP // BS, BS)
    assert all(bf16[n].dtype == jnp.float32 for n in state)
    assert bf16["k0"].dtype == jnp.bfloat16
