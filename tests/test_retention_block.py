"""The ``retention`` mixer of the stack of mixer kinds (``models/hybrid.py``:
gated power retention of degree 2, arXiv:2507.04239) at a size a CPU test
holds:

* the degree-2 features reproduce the squared score, at the published
  head width too; the chunked scan and the step (the STATE form) equal the
  ATTENTION form across chunk edges, under ragged lengths and strong gates;
  GQA equals the same with the kv heads repeated;
* prefill then decode through the paged pools agree with one full pass of
  the plain reference (``benchmark/architectures/brumby``), float weights
  and int8; packing, continuing, a retired lane, a slot another lane left;
* a stack with NO row-keeping layer: its pools are states and the slot map
  alone, a token weighs 0 bytes, the occupancy says what the memory is
  spent on; the state stays float32 through both forwards;
* the batcher serves it and counts it; what the kind cannot run is
  refused by field, what it is not served with by name;
* bytes and counts by hand at the published sizes.
"""

from __future__ import annotations

import dataclasses
import importlib
import math
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmark")
for p in (ROOT, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)

from docqa_tpu.config import DecoderConfig, GenerateConfig, load_config  # noqa: E402
from docqa_tpu.engines import paged  # noqa: E402
from docqa_tpu.engines.generate import GenerateEngine  # noqa: E402
from docqa_tpu.models import hybrid  # noqa: E402
from docqa_tpu.models.decoder import (  # noqa: E402
    block_serving,
    decoder_param_schema,
    kernel_forms,
    kv_row_shapes,
    lane_state_dtypes,
    lane_state_shapes,
)
from docqa_tpu.models.quant import should_quantize  # noqa: E402
from docqa_tpu.models.serving import KernelForms  # noqa: E402
from harness import arch  # noqa: E402
from harness.child import program_overrides  # noqa: E402

# the package exports a function of the module's name
ops = importlib.import_module("docqa_tpu.ops.attention")
PACKAGE = arch.load({"architecture": "brumby"})
HIGHEST = jax.lax.Precision.HIGHEST
# float32 so that program and reference differ by rounding order alone
TOY = DecoderConfig(
    vocab_size=256, hidden_dim=64, num_layers=4, num_heads=4, num_kv_heads=2,
    head_dim=16, mlp_dim=128, max_seq_len=512, norm_eps=1e-6,
    rope_theta=1e6, block="sparse_linear", dtype="float32",
    mixer_types=("retention",) * 4, qk_norm=True, use_output_gate=False,
    use_output_norm=False, tie_embeddings=False,
)
BS, CAP, ROWS = 16, 512, 384  # block, positions a lane, packed rows a lane
FEATURES = 16 * 17 // 2


@pytest.fixture(scope="module")
def params():
    return PACKAGE.weights.make_decoder_params(TOY, 3)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(5, 256, size=(2, ROWS))


def run_program(cfg, params, tokens, lengths, steps, starts=None,
                slot_of=None, kernels=None):
    """Prefill ``lengths[b]`` tokens of lane b in ONE packed dispatch (lane
    b from packed row ``starts[b]``), then ``steps`` teacher-forced decode
    steps: (logits [lanes, 1 + steps, vocab], pools).  ``slot_of``: the
    state entry each lane is given (default: its own index); ``kernels``:
    the forms the decode steps run (default: what a CPU is given, XLA)."""
    lanes = len(lengths)
    n_blocks = lanes * CAP // BS
    pools = paged.init_paged_pools(cfg, n_blocks, BS)
    if slot_of is not None:
        pools[hybrid.STATE_SLOT] = pools[hybrid.STATE_SLOT].at[
            jnp.arange(lanes) * CAP].set(jnp.asarray(slot_of, jnp.int32))
    starts = starts or [ROWS * b for b in range(lanes)]
    t = ROWS * lanes
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
    out = paged.ragged_prefill_forward(
        params, cfg, pools, *map(jnp.asarray, (ids, seg, pos, dest, last)),
        rope_len=CAP)
    assert len(out) == 2  # nothing selects or routes: no record
    logits, pools = out
    got = [np.asarray(logits)[:, None]]
    tables = jnp.arange(n_blocks, dtype=jnp.int32).reshape(lanes, -1)
    lens = np.asarray(lengths, np.int32)
    for _ in range(steps):
        tok = np.stack([tokens[b, lens[b]:lens[b] + 1] for b in range(lanes)])
        out = paged.paged_decode_forward(
            params, cfg, pools, tables, jnp.asarray(tok), jnp.asarray(lens),
            block_size=BS, rope_len=CAP, kernels=kernels)
        assert len(out) == 2
        got.append(np.asarray(out[0]))
        pools = out[1]
        lens = lens + 1
    return np.concatenate(got, 1), pools


def reference(cfg, params, tokens, lengths, steps, control=None):
    rows = np.asarray(lengths)[:, None] - 1 + np.arange(steps + 1)[None, :]
    return np.asarray(PACKAGE.reference.forward_logits(
        params, cfg, tokens[:, :max(lengths) + steps], rows, control=control))


def rel_err(got, want):
    centred = want - want.mean(-1, keepdims=True)
    return (np.linalg.norm(got - want, axis=-1)
            / np.linalg.norm(centred, axis=-1))


# ---- the ops: the state form against the attention form ---------------------

@pytest.mark.parametrize("d", [2, 8, 16, 128])
def test_the_features_of_q_and_k_multiply_to_the_squared_score(d):
    rng = np.random.default_rng(d)
    q, k = (jnp.asarray(rng.standard_normal((5, 3, d)), jnp.float32)
            for _ in range(2))
    phi_q = ops.power_features(q)
    phi_k = ops.power_features(k, key_side=True)
    assert phi_q.shape == (5, 3, d * (d + 1) // 2) == (
        5, 3, ops.power_feature_count(d))
    assert phi_q.dtype == jnp.float32
    want = np.einsum("tgd,tgd->tg", np.asarray(q, np.float64),
                     np.asarray(k, np.float64)) ** 2
    got = np.einsum("tgf,tgf->tg", np.asarray(phi_q, np.float64),
                    np.asarray(phi_k, np.float64))
    assert np.abs(got - want).max() <= 1e-5 * max(1.0, np.abs(want).max())
    # every unordered pair of channels once: the squares weigh 1, the rest 2
    ones = np.asarray(ops.power_features(
        jnp.ones((d,), jnp.float32), key_side=True))
    assert sorted(set(ones.tolist())) == ([1.0, 2.0] if d > 1 else [1.0])
    assert (ones == 1.0).sum() == d


def test_the_published_state_is_8256_by_129_numbers_a_kv_head():
    assert ops.power_feature_count(128) == 8256
    # bfloat16 inputs: every product is exact in float32
    x = jnp.asarray(np.random.default_rng(0).standard_normal(128),
                    jnp.bfloat16)
    phi = np.asarray(ops.power_features(x))
    x64 = np.asarray(x, np.float64)
    assert sorted(phi.astype(np.float64).tolist()) == sorted(
        (x64[a] * x64[b]) for a in range(128) for b in range(a, 128))


def attention_form(q, k, v, gamma):
    """One segment in float64: q [n, heads, d]; k, v [n, kv heads, d];
    gamma [n, kv heads] -> [n, heads, d]."""
    q, k, v, gamma = (np.asarray(x, np.float64) for x in (q, k, v, gamma))
    n, heads, d = q.shape
    per = heads // k.shape[1]
    big_g = np.cumsum(gamma, 0)
    out = np.zeros((n, heads, d))
    for h in range(heads):
        g = h // per
        s = (q[:, h] @ k[:, g].T) / math.sqrt(d)
        a = s * s * np.exp(np.minimum(
            big_g[:, g][:, None] - big_g[:, g][None, :], 0.0)) * np.tril(
            np.ones((n, n)))
        out[:, h] = (a @ v[:, g]) / (a.sum(-1, keepdims=True)
                                     + ops.RETENTION_EPS)
    return out


def _packed(t, segments):
    seg = np.full(t, -1, np.int32)
    pos = np.zeros(t, np.int32)
    for i, (start, n) in enumerate(segments):
        seg[start:start + n] = i
        pos[start:start + n] = np.arange(n)
    return seg, pos


def _op_inputs(t, heads, kv_heads, d, seed=0, shift=2.0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((t, heads, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((t, kv_heads, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((t, kv_heads, d)), jnp.float32)
    gamma = jax.nn.log_sigmoid(jnp.asarray(
        rng.standard_normal((t, kv_heads)) + shift, jnp.float32))
    return q, k, v, gamma


# segments from 128-row starts: one that crosses a chunk edge, one that ends
# ON one, a one-token one; the strong gate's sums reach -50 a chunk
SEGMENTS = [(0, 200), (256, 128), (384, 1), (512, 131)]


@pytest.mark.parametrize("shift", [7.0, 2.0, -0.5, -3.0],
                         ids=["keeps_a_thousand", "mild", "half", "strong"])
def test_the_chunked_scan_and_the_step_equal_the_attention_form(shift):
    t, heads, kv_heads, d = 768, 4, 2, 8
    q, k, v, gamma = _op_inputs(t, heads, kv_heads, d, shift=shift)
    seg, pos = _packed(t, SEGMENTS)
    # the state as it stands after a segment's LAST chunk goes to its entry
    slot = np.full(t // 128, 99, np.int32)
    for i, (start, n) in enumerate(SEGMENTS):
        slot[(start + n - 1) // 128] = len(SEGMENTS) - 1 - i
    pool = jnp.full((5, d + 1, kv_heads, ops.power_feature_count(d)), 7.0)
    out, pool = ops.power_retention_prefill(
        q, k, v, gamma, jnp.asarray(seg), jnp.asarray(pos), pool,
        jnp.asarray(slot), precision=HIGHEST)
    assert out.dtype == q.dtype and pool.dtype == jnp.float32
    for start, n in SEGMENTS:
        rows = slice(start, start + n)
        want = attention_form(q[rows], k[rows], v[rows], gamma[rows])
        assert np.abs(np.asarray(out[rows]) - want).max() < 2e-4
    assert (np.asarray(pool[4]) == 7.0).all()  # no segment's: untouched
    # the step continues each segment from its entry
    q1, k1, v1, g1 = _op_inputs(len(SEGMENTS), heads, kv_heads, d, seed=9,
                                shift=shift)
    entries = jnp.asarray([len(SEGMENTS) - 1 - i
                           for i in range(len(SEGMENTS))])
    step, state = ops.power_retention_step(q1, k1, v1, g1, pool[entries])
    assert state.dtype == jnp.float32 and state.shape == pool[entries].shape
    for i, (start, n) in enumerate(SEGMENTS):
        cat = lambda x, y: np.concatenate(  # noqa: E731
            [np.asarray(x[start:start + n]), np.asarray(y[i:i + 1])])
        want = attention_form(cat(q, q1), cat(k, k1), cat(v, v1),
                              cat(gamma, g1))[-1]
        assert np.abs(np.asarray(step[i]) - want).max() < 2e-4


@pytest.mark.parametrize("heads, kv_heads, d, shift", [
    (4, 2, 16, 2.0), (2, 1, 128, 7.0)],
    ids=["mild_gates", "published_head_width_gates_that_keep_a_thousand"])
def test_bfloat16_inputs_to_the_large_products_stay_close(
        heads, kv_heads, d, shift):
    """The form the program runs (``precision`` None: the features and the
    state rounded to bfloat16 on their way into the two large products,
    sums float32) against the float32 one — also at the published head
    width (8,256 features) under gates that keep a thousand tokens, where
    a row past its first chunk reads mostly the carried state."""
    t = 384
    q, k, v, gamma = _op_inputs(t, heads, kv_heads, d, seed=3, shift=shift)
    seg, pos = _packed(t, [(0, 300)])
    slot = jnp.asarray([9, 9, 0], jnp.int32)
    pool = jnp.zeros((1, d + 1, kv_heads, ops.power_feature_count(d)))
    run = lambda precision: ops.power_retention_prefill(  # noqa: E731
        q, k, v, gamma, jnp.asarray(seg), jnp.asarray(pos), pool, slot,
        precision=precision)
    (low, state_low), (high, state_high) = run(None), run(HIGHEST)
    assert np.abs(np.asarray(low[:300]) - np.asarray(high[:300])).max() < 0.02
    scale = np.abs(np.asarray(state_high)).max()
    assert np.abs(np.asarray(state_low)
                  - np.asarray(state_high)).max() < 0.01 * scale
    assert state_low.dtype == jnp.float32


def test_grouped_query_heads_equal_the_same_with_the_kv_heads_repeated():
    t, heads, kv_heads, d = 256, 10, 2, 8  # 5 query heads a kv head
    q, k, v, gamma = _op_inputs(t, heads, kv_heads, d, seed=5)
    seg, pos = _packed(t, [(0, 250)])
    slot = jnp.asarray([9, 0], jnp.int32)
    f = ops.power_feature_count(d)
    per = heads // kv_heads
    shared, pool = ops.power_retention_prefill(
        q, k, v, gamma, jnp.asarray(seg), jnp.asarray(pos),
        jnp.zeros((1, d + 1, kv_heads, f)), slot, precision=HIGHEST)
    repeat = lambda x: jnp.repeat(x, per, axis=1)  # noqa: E731
    alone, pool_r = ops.power_retention_prefill(
        q, repeat(k), repeat(v), repeat(gamma), jnp.asarray(seg),
        jnp.asarray(pos), jnp.zeros((1, d + 1, heads, f)), slot,
        precision=HIGHEST)
    assert np.abs(np.asarray(shared) - np.asarray(alone)).max() < 1e-5
    repeat_state = lambda x: jnp.repeat(x, per, axis=2)  # noqa: E731
    assert np.abs(np.asarray(repeat_state(pool))
                  - np.asarray(pool_r)).max() < 1e-5
    q1, k1, v1, g1 = _op_inputs(1, heads, kv_heads, d, seed=6)
    a, _ = ops.power_retention_step(q1, k1, v1, g1, pool)
    b, _ = ops.power_retention_step(
        q1, repeat(k1), repeat(v1), repeat(g1), pool_r)
    assert np.abs(np.asarray(a) - np.asarray(b)).max() < 1e-5


# ---- the program against the reference --------------------------------------

LENGTHS, STEPS = [300, 37], 5  # lane 0 crosses two chunk edges


@pytest.fixture(scope="module")
def served(params, tokens):
    return run_program(TOY, params, tokens, LENGTHS, STEPS)


# the step's kernel (``ops/retention.py``, ISSUE 52) and nothing else:
# what an engine that saw a TPU hands a stack whose head is whole
# registers (TOY's 16-wide one runs the kernel interpreted)
FUSED = KernelForms(*(f == "retention" for f in KernelForms._fields))
FORMS = pytest.mark.parametrize("form", ["xla", "kernel"])


@pytest.fixture
def retention_kernel_interpreted(monkeypatch):
    """``power_retention_step_fused`` as the decode step calls it under
    ``kernels.retention``, interpreted: the one thing a CPU cannot take
    from it."""
    retention = importlib.import_module("docqa_tpu.ops.retention")
    real = retention.power_retention_step_fused
    monkeypatch.setattr(
        retention, "power_retention_step_fused",
        lambda *args, **kw: real(*args, **{**kw, "interpret": True}))


@pytest.fixture
def served_by(params, tokens, served, retention_kernel_interpreted):
    """``served`` as the form named decodes it: the kernel's steps start
    from the same prefill."""
    def by(form):
        if form == "xla":
            return served
        return run_program(TOY, params, tokens, LENGTHS, STEPS, kernels=FUSED)

    return by


@FORMS
def test_paged_prefill_then_decode_agree_with_the_reference(
        form, params, tokens, served, served_by):
    got, pools = served_by(form)
    # one function in two forms: the kernel's steps give the XLA form's
    # logits and leave its states, to the order of a float32 sum
    assert np.abs(got - served[0]).max() < 1e-4
    for name in lane_state_shapes(TOY):
        a, b = np.asarray(pools[name]), np.asarray(served[1][name])
        assert np.abs(a - b).max() <= 1e-5 * np.abs(b).max(), name
    want = reference(TOY, params, tokens, LENGTHS, STEPS)
    assert got.shape == want.shape == (2, 1 + STEPS, 256)
    # the prefill's two large products take bfloat16 inputs (sums float32)
    assert rel_err(got, want).max() < 0.02


def test_the_references_two_forms_are_one_function(params, tokens):
    """The attention form (what ``correct`` compares with) and the
    chunked form the controls of the state run, with a rounding that
    rounds nothing; the two readings beside the controls round little (the
    state's type is held exactly, not by the logits); forgetting what a
    lane carried is wrong outright."""
    from harness.weights import Control

    want = reference(TOY, params, tokens, LENGTHS, 2)
    same = reference(TOY, params, tokens, LENGTHS, 2,
                     control=Control(kv=lambda x, what: x))
    assert rel_err(same, want).max() < 1e-4
    for name, control in PACKAGE.weights.kv_only_controls().items():
        rounded = reference(TOY, params, tokens, LENGTHS, 2, control=control)
        assert 1e-5 < rel_err(rounded, want).max() < 0.01, name
    forgot = reference(
        TOY, params, tokens, LENGTHS, 2,
        control=PACKAGE.weights.controls_for(TOY)["carry_zero"])
    assert rel_err(forgot, want).max() > 0.3


def test_int8_weights_agree_and_the_decay_projection_stays_float(tokens):
    cfg = dataclasses.replace(
        TOY, dtype="bfloat16", quantize_weights=True, quant_bits=8)
    served_params = PACKAGE.weights.make_decoder_params(cfg, 3)
    assert served_params["l0_wq"].dtype == jnp.int8
    assert served_params["l0_w_decay"].dtype == jnp.bfloat16
    assert "l0_w_decay__scale" not in served_params
    assert not should_quantize("l0_w_decay") and should_quantize("l0_w_gate")
    got, _ = run_program(cfg, served_params, tokens, LENGTHS, 2)
    want = reference(cfg, served_params, tokens, LENGTHS, 2)
    assert rel_err(got, want).max() < 0.03
    for name, control in PACKAGE.weights.controls_for(cfg).items():
        worse = reference(cfg, served_params, tokens, LENGTHS, 2, control)
        assert rel_err(worse, want).max() > 0.04, name


def test_the_seeded_gates_keep_hundreds_of_tokens(params, tokens):
    """Channel 0 of the residual stream is the gate's constant
    (``weights.py``): the embedding holds it, nothing writes it, nothing
    but the gate reads it — so a bias-free gate keeps hundreds of tokens
    and the compared rows read what their lane CARRIED."""
    w = PACKAGE.weights
    h = TOY.hidden_dim
    emb = np.asarray(params["tok_emb"], np.float32)
    assert np.all(emb[:, 0] == w.GATE_CHANNEL * h ** 0.5)
    assert abs(emb[:, 1:].std() - w.EMB_STD) < 0.02
    for i in range(TOY.num_layers):
        for name in w.READS_STREAM[:-1]:
            assert not np.asarray(params[f"l{i}_{name}"])[0].any(), name
        for name in w.WRITES_STREAM:
            assert not np.asarray(params[f"l{i}_{name}"])[:, 0].any(), name
    assert not np.asarray(params["lm_head"])[0].any()
    # the first layer's gates on real tokens: log gates of a few 1e-4
    x = emb[tokens[0, :300]]
    y = x / np.sqrt((x * x).mean(-1, keepdims=True) + TOY.norm_eps)
    gamma = np.asarray(jax.nn.log_sigmoid(
        y @ np.asarray(params["l0_w_decay"], np.float32)))
    assert -0.01 < gamma.min() and gamma.max() < 0
    assert -0.002 < gamma.mean() < -0.0001


def test_the_programs_own_initialisation_runs_the_stack(tokens):
    from docqa_tpu.models.decoder import init_decoder_params

    own = init_decoder_params(jax.random.PRNGKey(0), TOY)
    want = {n for n, *_ in decoder_param_schema(TOY)}
    assert set(own) == want == set(PACKAGE.weights.make_decoder_params(TOY, 1))
    assert "l3_w_decay" in want and own["l0_w_decay"].shape == (64, 2)
    got, _ = run_program(TOY, own, tokens, [90, 40], 1)
    assert np.isfinite(got).all()


# ---- packing, continuing, resetting -----------------------------------------

def test_two_prompts_packed_back_to_back_equal_the_same_prompts_alone(
        params, tokens):
    together, _ = run_program(TOY, params, tokens, [ROWS - 2, 90], 2)
    for b, n in enumerate([ROWS - 2, 90]):
        alone, _ = run_program(TOY, params, tokens[b:b + 1], [n], 2)
        assert np.abs(together[b] - alone[0]).max() < 2e-3
    full, _ = run_program(TOY, params, tokens, [ROWS, 90], 0)
    alone, _ = run_program(TOY, params, tokens[1:], [90], 0)
    assert np.abs(full[1] - alone[0]).max() < 2e-3


def test_a_prefill_of_n_plus_1_equals_a_prefill_of_n_and_one_step(
        params, tokens):
    for n in (127, 128, 200):  # the step that crosses a chunk edge too
        stepped, pools_a = run_program(TOY, params, tokens[:1], [n], 1)
        longer, pools_b = run_program(TOY, params, tokens[:1], [n + 1], 0)
        assert rel_err(stepped[:, 1], longer[:, 0]).max() < 0.02
        for name in lane_state_shapes(TOY):
            a, b = (np.asarray(p[name][0]) for p in (pools_a, pools_b))
            assert np.abs(a - b).max() < 0.02 * np.abs(b).max(), name


@FORMS
def test_a_retired_lane_reads_zeros_and_writes_nothing(
        form, params, tokens, served, retention_kernel_interpreted):
    """Both lanes retired: no entry is owned, and the kernel's one grid
    step hands its block back as it came.  One of two: the other lane's
    step is the step it takes beside a live neighbour."""
    _, pools = served
    kernels = FUSED if form == "kernel" else None
    before = {k: np.asarray(v) for k, v in pools.items()}
    n_pages = CAP // BS
    holes = jnp.full((2, n_pages), 2 * n_pages, jnp.int32)
    args = (jnp.asarray(tokens[:, :1]), jnp.asarray([60, 40]))
    _, after = paged.paged_decode_forward(
        params, TOY, dict(pools), holes, *args, block_size=BS, rope_len=CAP,
        kernels=kernels)
    for name, value in after.items():
        assert (np.asarray(value) == before[name]).all(), name
    tables = jnp.arange(2 * n_pages, dtype=jnp.int32).reshape(2, -1)
    logits, both = paged.paged_decode_forward(
        params, TOY, dict(pools), tables, *args, block_size=BS,
        rope_len=CAP, kernels=kernels)
    half, one = paged.paged_decode_forward(
        params, TOY, dict(pools), tables.at[0].set(holes[0]), *args,
        block_size=BS, rope_len=CAP, kernels=kernels)
    assert np.abs(np.asarray(half)[1] - np.asarray(logits)[1]).max() < 1e-5
    for name in lane_state_shapes(TOY):
        assert (np.asarray(one[name])[0] == before[name][0]).all(), name
        assert (np.asarray(one[name])[1] == np.asarray(both[name])[1]).all()
        assert (np.asarray(one[name])[1] != before[name][1]).any(), name


@FORMS
def test_a_lane_given_another_entry_finds_it_through_the_slot_map(
        form, params, tokens, served_by):
    """The lanes' entries swapped in ``state_slot``: the same logits, the
    states in each other's entries — the decode step runs over the pool's
    entries where they lie and hands each the token of the lane that owns
    it.  Each form against itself: the same sums in the same order."""
    got, pools = served_by(form)
    swapped, pools_s = run_program(
        TOY, params, tokens, LENGTHS, STEPS, slot_of=[1, 0],
        kernels=FUSED if form == "kernel" else None)
    assert np.abs(swapped - got).max() < 1e-5
    for name in lane_state_shapes(TOY):
        assert np.abs(np.asarray(pools_s[name])[::-1]
                      - np.asarray(pools[name])).max() < 1e-5, name


# ---- a stack in which no layer keeps a row ----------------------------------

def test_the_pools_hold_states_and_the_slot_map_alone(served):
    _, pools = served
    assert sorted(pools) == sorted(
        [hybrid.STATE_SLOT] + [f"s{i}" for i in range(4)])
    for i in range(4):
        assert kv_row_shapes(TOY, i) == {}
        assert pools[f"s{i}"].shape == (2, 17, 2, FEATURES)
        assert pools[f"s{i}"].dtype == jnp.float32  # through both forwards
    assert lane_state_shapes(TOY) == {
        f"s{i}": (17, 2, FEATURES) for i in range(4)}
    assert set(lane_state_dtypes(TOY).values()) == {"float32"}
    assert hybrid.lane_state_bytes(TOY) == 4 * 2 * 17 * FEATURES * 4
    assert paged.kv_bytes_per_token(TOY) == 0
    # the narrowest array of the pools is 32 bits wide: what the
    # benchmark's ``kv_cache_bits_missing`` reads
    assert min(8 * v.dtype.itemsize for v in pools.values()) == 32
    assert hybrid.mixer_geometry(TOY, hybrid.RETENTION) == (4, 2, 16)
    assert hybrid.retention_layers(TOY) == (0, 1, 2, 3)


def test_the_record_of_the_stack_counts_the_kind():
    block = block_serving(TOY)
    assert block.lane_state and block.ring_pages is None
    assert block.unserved == ("generate.prefix_cache",
                              "generate.speculative_k", "qos.preemption")
    assert block.uses_flash is False and block.step_sum_names == ()
    assert block.kv_rows_read is None
    state = hybrid.lane_state_bytes(TOY)
    assert block.span_attrs == {
        "retention_layers": 4, "state_bytes_a_lane": state}
    assert block.occupancy == {"state_bytes_per_lane": state}
    # the decode step's kernel (ISSUE 52) and nothing else — the prefill's
    # scan is XLA on every backend —, and not that at a head of 16
    forms = FUSED
    wide = kernel_forms(dataclasses.replace(TOY, head_dim=128), on_tpu=True,
                        mesh=None, block_size=16)
    assert wide._replace(paged=False) == forms  # no layer asks ``paged``
    assert not any(kernel_forms(TOY, on_tpu=True, mesh=None, block_size=16))
    assert block.prefill_counts(
        lanes=2, tokens=300, dispatches=2, kernels=forms) == {
        "serve_lane_state_resets": 2, "serve_scan_tokens": 4 * 300}
    assert block.prefill_attrs(100, 2) == {"state_lanes": 2, "scan_rows": 100}
    xla = kernel_forms(TOY, on_tpu=False, mesh=None, block_size=16)
    assert not any(xla)
    counts, samples = block.chunk_counts(lane_steps=8, row=None, kernels=xla)
    assert counts == {"serve_state_lane_steps": 8,
                      "serve_state_bytes_rw": 2 * state * 8}
    assert samples == {}
    assert block.chunk_counts(lane_steps=8, row=None, kernels=forms) == (
        {**counts, "serve_retention_fused_chunks": 1}, {})
    specs = block.param_pspecs("model")
    assert tuple(specs["l0_wq"]) == (None, "model")
    assert tuple(specs["l0_wk"]) == tuple(specs["l0_w_decay"]) == (None, None)
    assert set(block.pool_pspecs()) == {hybrid.STATE_SLOT, "s0", "s1", "s2",
                                        "s3"}


BF16 = dataclasses.replace(TOY, dtype="bfloat16", max_seq_len=256)
COUNTERS = (
    "serve_state_lane_steps", "serve_state_bytes_rw",
    "serve_lane_state_resets", "serve_scan_tokens", "serve_prefill_tokens",
    "serve_prefill_dispatches",
)


def _batcher(n_slots, params, **kw):
    from docqa_tpu.engines.serve import ContinuousBatcher

    gen = dataclasses.replace(
        GenerateConfig(), speculative_k=0, prefix_cache=False, decode_chunk=4,
        max_concurrent=n_slots)
    engine = GenerateEngine(BF16, gen=gen, params=params)
    return ContinuousBatcher(engine, n_slots=n_slots, chunk=4, cache_len=256,
                             kv_block_size=16, prefix_cache=False, **kw)


def _counters():
    from docqa_tpu.runtime.metrics import DEFAULT_REGISTRY

    return {n: DEFAULT_REGISTRY.counter(n).value for n in COUNTERS}


def test_a_slot_another_lane_left_starts_from_zeros_and_the_counters_count():
    """Four prompts through two slots, the longest first: each slot's
    second lane is shorter than the one that just left it and gives the
    tokens it gives alone in a fresh batcher.  A page holds no row: the
    pool weighs nothing a token and the occupancy says what the memory is
    spent on."""
    served_params = PACKAGE.weights.make_decoder_params(BF16, 3)
    prompts = [[5 + (7 * i + j) % 250 for j in range(150 - 35 * i)]
               for i in range(4)]
    state_bytes = 4 * 2 * 17 * FEATURES * 4
    before = _counters()
    b = _batcher(2, served_params)
    try:
        assert b._block.lane_state and not any(b._kernels)
        assert b.kv_bytes_per_token == 0
        occ = b.kv_block_occupancy()
        assert occ["state_bytes_per_lane"] == state_bytes
        assert occ["state_pool_bytes"] == 2 * state_bytes
        assert (occ["bytes_per_token"], occ["pool_bytes"],
                occ["used_bytes"]) == (0, 0, 0)
        assert occ["blocks_total"] == b.n_blocks > 0  # pages still admit
        got = [h.result(timeout=600) for h in
               [b.submit_ids(p, max_new_tokens=10) for p in prompts]]
        after = b.kv_block_occupancy()
        assert after["utilization"] == after["tokens_committed"] == 0
    finally:
        b.stop()
    gained = {k: v - before[k] for k, v in _counters().items()}
    assert gained["serve_lane_state_resets"] == 4
    assert gained["serve_prefill_tokens"] == sum(map(len, prompts))
    assert gained["serve_scan_tokens"] == 4 * sum(map(len, prompts))
    steps = gained["serve_state_lane_steps"]
    assert steps >= sum(len(g) - 1 for g in got) > 0
    assert gained["serve_state_bytes_rw"] == steps * 2 * state_bytes
    for prompt, toks in zip(prompts, got):
        fresh = _batcher(1, served_params)
        try:
            alone = fresh.submit_ids(prompt, max_new_tokens=10).result(
                timeout=600)
        finally:
            fresh.stop()
        assert list(alone) == list(toks)


# ---- refusals ---------------------------------------------------------------

@pytest.mark.parametrize("change, said", [
    (dict(head_dim=15), "head_dim is odd"),
    (dict(num_heads=5), "num_heads is no multiple of num_kv_heads"),
    (dict(mixer_types=("retention", "power", "retention", "retention")),
     "power"),
    (dict(quantize_weights=True, quant_bits=4), "quant_bits"),
    (dict(sliding_window=64), "sliding_window"),
])
def test_a_configuration_the_kind_cannot_run_is_refused_by_field(
        change, said):
    with pytest.raises(ValueError, match=said):
        hybrid.check_hybrid_config(dataclasses.replace(TOY, **change))


@pytest.mark.parametrize("gen, qos, said", [
    ({"prefix_cache": True, "speculative_k": 0}, None,
     "generate.prefix_cache"),
    ({"prefix_cache": False, "speculative_k": 4}, None,
     "generate.speculative_k"),
    ({"prefix_cache": False, "speculative_k": 0}, "on", "qos.preemption"),
], ids=["prefix_cache", "speculation", "preemption"])
def test_the_batcher_refuses_by_name_what_the_stack_does_not_serve(
        params, gen, qos, said):
    from docqa_tpu.engines.qos import QoSPolicy
    from docqa_tpu.engines.serve import ContinuousBatcher

    gen = dataclasses.replace(GenerateConfig(), max_concurrent=2, **gen)
    engine = GenerateEngine(TOY, gen=gen, params=params)
    policy = QoSPolicy(preemption=qos) if qos else None
    with pytest.raises(ValueError, match=said):
        ContinuousBatcher(engine, n_slots=2, chunk=4, cache_len=256,
                          kv_block_size=16, qos=policy)


def test_a_warm_prefill_and_a_verify_step_are_refused(params):
    pools = paged.init_paged_pools(TOY, 2 * CAP // BS, BS)
    z = jnp.zeros((128,), jnp.int32)
    with pytest.raises(NotImplementedError, match="prefix_cache"):
        paged.ragged_prefill_forward(
            params, TOY, pools, z, z, z, z, jnp.zeros((1,), jnp.int32),
            rope_len=CAP, n_prefix_rows=128, block_size=BS,
            block_tables=jnp.zeros((1, CAP // BS), jnp.int32),
            prefix_lens=jnp.zeros((1,), jnp.int32))
    with pytest.raises(NotImplementedError, match="speculative_k"):
        paged.paged_decode_forward(
            params, TOY, pools, jnp.zeros((2, CAP // BS), jnp.int32),
            jnp.zeros((2, 3), jnp.int32), jnp.zeros((2,), jnp.int32),
            block_size=BS, rope_len=CAP)


def test_the_solo_engine_refuses_the_stack_by_name(params):
    engine = GenerateEngine(TOY, gen=GenerateConfig(), params=params)
    with pytest.raises(NotImplementedError, match="sparse_linear"):
        engine.generate_ids([[5, 6, 7]], max_new_tokens=2)


# ---- bytes and counts by hand at the published sizes ------------------------

@pytest.fixture(scope="module")
def published():
    conf = arch.load_cell_config(
        os.path.join(BENCH_DIR, "configs", "brumby-14b-l12-int8.json"))
    return conf, load_config(env={}, overrides=program_overrides(conf)).decoder


def test_state_and_parameters_by_hand_at_the_published_sizes(published):
    conf, cfg = published
    assert cfg.mixer_types == ("retention",) * 12
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (40, 8, 128)
    assert hybrid.mixer_geometry(cfg, "retention") == (40, 8, 128)
    assert hybrid.retention_state_shape(cfg) == (129, 8, 8256)
    assert lane_state_shapes(cfg)["s11"] == (129, 8, 8256)
    a_head = 8256 * 129 * 4
    assert a_head == 4_260_096
    assert hybrid.lane_state_bytes(cfg) == 12 * 8 * a_head == 408_969_216
    assert paged.kv_bytes_per_token(cfg) == 0
    shapes = {n: s for n, _k, s, _f in decoder_param_schema(cfg)}
    assert shapes["l0_w_decay"] == (5120, 8)
    assert shapes["l0_wq"] == (5120, 5120) and shapes["l0_wk"] == (5120, 1024)
    assert shapes["lm_head"] == (5120, 151936)
    layer = sum(math.prod(s) for n, s in shapes.items()
                if n.startswith("l0_") and len(s) == 2)
    assert layer == 330_301_440 + 5120 * 8
    pools = jax.eval_shape(
        lambda: paged.init_paged_pools(cfg, 38912 // 16, 16, n_lanes=4))
    assert sorted(pools) == sorted(
        [hybrid.STATE_SLOT] + [f"s{i}" for i in range(12)])
    assert sum(math.prod(v.shape) * v.dtype.itemsize
               for n, v in pools.items() if n != hybrid.STATE_SLOT) == (
        4 * 408_969_216)
