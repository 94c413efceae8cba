"""ISSUE 42: two more mixer kinds in the one stack of ``models/hybrid.py`` —
``mamba`` (a Mamba-1 state-space mixer whose conv window and state live a
lane) and ``attention`` (plain position-free GQA / MQA over the paged
rows) — through the paged forwards and the batcher, against the plain
reference of ``benchmark/architectures/jamba/`` — CPU, tiny widths, seeded
weights.

* paged prefill then decode equal the reference on logits, for a stack
  that holds both new kinds; with the projection biases on and the conv
  bias off; ``mixer_types`` order is honoured;
* the chunked scan equals the token-by-token recurrence across chunk
  edges, and the step continues it;
* two prompts packed back to back in one dispatch give each the logits it
  gets alone (the conv window and the state reset at a segment's first
  row);
* a prefill of n + 1 tokens equals a prefill of n and one decode step;
* a slot a longer lane just left starts from zeros; the batcher's
  counters and span attributes for a stack that scans and does not select;
* the state stays float32 and the window in the activation type through
  both forwards; state, row and parameter counts by hand at the published
  sizes;
* the refusals by name;
* SALA's toy programs lower to the text they lowered to on the parent.
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

from docqa_tpu.config import DecoderConfig, GenerateConfig, load_config  # noqa: E402
from docqa_tpu.engines import paged  # noqa: E402
from docqa_tpu.engines.generate import GenerateEngine  # noqa: E402
from docqa_tpu.models import hybrid  # noqa: E402
from docqa_tpu.models.decoder import (  # noqa: E402
    decoder_param_schema,
    init_decoder_params,
    kv_row_shapes,
    lane_state_dtypes,
    lane_state_shapes,
)
from docqa_tpu.ops import ssm  # noqa: E402
from harness import arch, check  # noqa: E402
from harness.child import program_overrides  # noqa: E402

PACKAGE = arch.load({"architecture": "jamba"})
# float32 so that program and reference differ by rounding order alone
TOY = DecoderConfig(
    vocab_size=256, hidden_dim=64, num_layers=4, num_heads=4, num_kv_heads=1,
    head_dim=16, mlp_dim=128, max_seq_len=512, norm_eps=1e-6,
    block="sparse_linear", dtype="float32",
    mixer_types=("mamba", "attention", "mamba", "mamba"), qk_norm=False,
    use_output_gate=False, use_output_norm=False, tie_embeddings=True,
    ssm_state_dim=8, ssm_conv_width=4, ssm_dt_rank=8, ssm_expand=2,
)
BS, CAP, ROWS = 16, 512, 384  # block, positions a lane, packed rows a lane


@pytest.fixture(scope="module")
def params():
    return PACKAGE.weights.make_decoder_params(TOY, 3)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(5, 256, size=(2, ROWS))


def run_program(cfg, params, tokens, lengths, steps, starts=None):
    """Prefill ``lengths[b]`` tokens of lane b in ONE packed dispatch (lane
    b from packed row ``starts[b]``), then ``steps`` teacher-forced decode
    steps: (logits [lanes, 1 + steps, vocab], pools)."""
    lanes = len(lengths)
    n_blocks = lanes * CAP // BS
    pools = paged.init_paged_pools(cfg, n_blocks, BS)
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
    assert len(out) == 2  # no layer selects: no record
    logits, pools = out
    got = [np.asarray(logits)[:, None]]
    tables = jnp.arange(n_blocks, dtype=jnp.int32).reshape(lanes, -1)
    lens = np.asarray(lengths, np.int32)
    for _ in range(steps):
        tok = np.stack([tokens[b, lens[b]:lens[b] + 1] for b in range(lanes)])
        out = paged.paged_decode_forward(
            params, cfg, pools, tables, jnp.asarray(tok), jnp.asarray(lens),
            block_size=BS, rope_len=CAP)
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


# ---- the program against the reference --------------------------------------

LENGTHS, STEPS = [300, 37], 5  # lane 0 crosses two chunk edges


@pytest.fixture(scope="module")
def served(params, tokens):
    return run_program(TOY, params, tokens, LENGTHS, STEPS)


def test_paged_prefill_then_decode_agree_with_the_reference(
        params, tokens, served):
    got, _ = served
    want = reference(TOY, params, tokens, LENGTHS, STEPS)
    assert got.shape == want.shape == (2, 1 + STEPS, 256)
    assert rel_err(got, want).max() < 1e-4


@pytest.mark.parametrize("change", [
    dict(ssm_proj_bias=True), dict(ssm_conv_bias=False),
    dict(mixer_types=("attention", "mamba", "mamba", "attention")),
    dict(num_kv_heads=2), dict(ssm_conv_width=3, ssm_state_dim=4),
], ids=["proj_bias", "no_conv_bias", "order", "gqa", "taps3_state4"])
def test_what_the_configuration_says_bites_and_still_agrees(tokens, change):
    cfg = dataclasses.replace(TOY, **change)
    params = PACKAGE.weights.make_decoder_params(cfg, 5)
    got, _ = run_program(cfg, params, tokens, [140, 20], 2)
    want = reference(cfg, params, tokens, [140, 20], 2)
    assert rel_err(got, want).max() < 1e-4
    if "mixer_types" in change:
        # the same tree read in another order is another model: it lacks
        # the tensors the other order's layers would hold
        with pytest.raises(KeyError):
            run_program(TOY, params, tokens, [140, 20], 0)


def test_the_programs_own_initialisation_runs_the_stack(tokens):
    """``init_decoder_params`` (the zero-egress default) draws every tensor
    of the schema — a state-space layer more than eight — and the tree it
    makes agrees with the reference too."""
    params = init_decoder_params(jax.random.PRNGKey(1), TOY)
    assert set(params) == {n for n, *_ in decoder_param_schema(TOY)}
    assert "lm_head" not in params
    got, _ = run_program(TOY, params, tokens, [60, 20], 1)
    want = reference(TOY, params, tokens, [60, 20], 1)
    assert rel_err(got, want).max() < 1e-4


# ---- the scan ---------------------------------------------------------------

def _scan_inputs(t, d=24, n=4, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    return dict(
        c=f(t, d), delta=jax.nn.softplus(f(t, d) - 1.0),
        a=-jnp.exp(f(n, d) * 0.5), b=f(t, n), cc=f(t, n), d_skip=f(d))


@jax.jit
def _recurrence_of(x, start, rows):
    def step(h, xs):
        c, delta, b, cc, live = xs
        g, stepped = ssm.selective_scan_step(
            c[None], delta[None], x["a"], b[None], cc[None], x["d_skip"], h)
        return jnp.where(live, stepped, h), g[0]

    at = jnp.arange(x["c"].shape[0])
    h, g = jax.lax.scan(
        step, jnp.zeros((1, *x["a"].shape), jnp.float32),
        (x["c"], x["delta"], x["b"], x["cc"],
         (at >= start) & (at < start + rows)))
    return g, h[0]


def _recurrence(x, start, rows):
    """``selective_scan_step`` token by token over packed rows ``start ..
    start + rows`` from a zero state: (g of those rows, h after them)."""
    g, h = _recurrence_of(x, start, rows)
    return np.asarray(g[start:start + rows]), np.asarray(h)


# the XLA form, and the Pallas kernel a TPU runs (interpreted here)
SCAN_FORMS = {"xla": {}, "kernel": {"interpret": True}}


def _packed(t, segments):
    """(seg_ids, positions, last_rows) of ``segments`` [(first row,
    length)] in ``t`` packed rows; padding everywhere else."""
    seg = np.full(t, -1, np.int32)
    pos = np.zeros(t, np.int32)
    for s, (start, n) in enumerate(segments):
        seg[start:start + n] = s
        pos[start:start + n] = np.arange(n)
    last = [start + n - 1 for start, n in segments]
    return jnp.asarray(seg), jnp.asarray(pos), jnp.asarray(last, jnp.int32)


@pytest.mark.parametrize("form", sorted(SCAN_FORMS))
def test_the_chunked_scan_equals_the_recurrence_across_chunk_edges(form):
    """Two segments in one packed batch: 300 rows (two chunk edges inside)
    from row 0, 130 rows (one edge) from row 384; padding between."""
    t = 640
    x = _scan_inputs(t)
    segments = [(0, 300), (384, 130)]
    g, h = ssm.selective_scan_prefill(
        x["c"], x["delta"], x["a"], x["b"], x["cc"], x["d_skip"],
        *_packed(t, segments), **SCAN_FORMS[form])
    for s, (start, n) in enumerate(segments):
        want_g, want_h = _recurrence(x, start, n)
        assert np.abs(np.asarray(g[start:start + n]) - want_g).max() < 2e-4
        assert np.abs(np.asarray(h[s]) - want_h).max() < 2e-4
    assert g.dtype == jnp.float32 and h.dtype == jnp.float32


@pytest.mark.parametrize("t, d, n, segments", [
    (512, 24, 4, [(0, 256), (256, 200)]),
    (640, 24, 4, [(0, 128), (128, 256), (384, 250)]),
    (256, 24, 4, [(0, 130)]),
    (384, 24, 4, [(0, 128), (256, 100)]),
    (128, 24, 4, [(0, 128)]),
    (37 * 128, 24, 4, [(0, 2000), (2048, 37 * 128 - 2048 - 5)]),
    (384, 7 * 128, 8, [(0, 200), (256, 128)]),
    (384, 128, 16, [(0, 384)]),
    (256, 200, 4, [(0, 150)]),
], ids=["two-back-to-back", "three-back-to-back", "last-chunk-mostly-padding",
        "a-padding-chunk-between", "one-chunk", "thirty-seven-chunks",
        "seven-blocks-of-channels", "one-block-of-channels",
        "a-width-that-is-no-whole-lane-tile"])
def test_the_kernel_alone_equals_the_step_looped(t, d, n, segments):
    """The Pallas kernel (interpreted) against ``selective_scan_step`` row
    by row from a zero state: ``g`` over each segment's rows, and the
    state after each segment's LAST TOKEN (not after the padding that
    fills its last chunk)."""
    x = _scan_inputs(t, d=d, n=n, seed=t + d)
    g, h = ssm.selective_scan_prefill(
        x["c"], x["delta"], x["a"], x["b"], x["cc"], x["d_skip"],
        *_packed(t, segments), interpret=True)
    assert g.shape == (t, d) and h.shape == (len(segments), n, d)
    assert g.dtype == jnp.float32 and h.dtype == jnp.float32
    for s, (start, rows) in enumerate(segments):
        want_g, want_h = _recurrence(x, start, rows)
        assert np.abs(np.asarray(g[start:start + rows]) - want_g).max() < 2e-4
        assert np.abs(np.asarray(h[s]) - want_h).max() < 2e-4


def test_the_kernels_blocks_are_whole_lane_tiles_where_the_width_has_them():
    assert ssm._scan_block(5120) == 512  # Jamba2-3B: ten blocks a chunk
    assert ssm._scan_block(7 * 128) == 128
    assert ssm._scan_block(128) == 128
    assert ssm._scan_block(24) == 24  # a toy: the array itself


def test_the_conv_reads_nothing_before_a_segments_first_row():
    """Rows 0..255 are one segment, 256.. the next: the second's first
    three outputs are what they are with zeros before them, and the
    windows are each segment's own last inputs."""
    rng = np.random.default_rng(1)
    u = jnp.asarray(rng.standard_normal((384, 8)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((4, 8)), jnp.float32)
    bias = jnp.asarray(rng.standard_normal(8), jnp.float32)
    pos = jnp.asarray(np.r_[np.arange(256), np.arange(128)], jnp.int32)
    got = ssm.causal_conv_prefill(u, w, bias, pos)
    alone = ssm.causal_conv_prefill(u[256:], w, bias, pos[256:])
    assert np.abs(np.asarray(got[256:]) - np.asarray(alone)).max() == 0.0
    leaky = ssm.causal_conv_prefill(u, w, bias, jnp.arange(384))
    assert np.abs(np.asarray(leaky[256:259]) - np.asarray(alone[:3])).max() > 0.1
    window = ssm.conv_window_of(u, pos, jnp.asarray([255, 256 + 1]), 3)
    assert (np.asarray(window[0]) == np.asarray(u[253:256])).all()
    assert (np.asarray(window[1, 0]) == 0).all()  # a two-token segment
    assert (np.asarray(window[1, 1:]) == np.asarray(u[256:258])).all()
    # the step continues it: token 256 + 2 after the window of 256, 257
    c, shifted = ssm.causal_conv_step(u[258:259], window[1:2], w, bias)
    assert np.abs(np.asarray(c[0]) - np.asarray(alone[2])).max() < 1e-6
    assert (np.asarray(shifted[0]) == np.asarray(u[256:259])).all()


# ---- packing, continuing, resetting -----------------------------------------

def test_two_prompts_packed_back_to_back_equal_the_same_prompts_alone(
        params, tokens):
    """Lane 0 fills rows 0..383 to the seam, lane 1 starts on row 384: a
    conv that read across the seam, or a state that was not reset, would
    move lane 1's logits."""
    together, pools = run_program(TOY, params, tokens, [ROWS - 2, 90], 2)
    for b, n in enumerate([ROWS - 2, 90]):
        alone, _ = run_program(TOY, params, tokens[b:b + 1], [n], 2)
        assert np.abs(together[b] - alone[0]).max() < 1e-4
    # and with lane 0 right up to the seam (no decode room needed)
    full, _ = run_program(TOY, params, tokens, [ROWS, 90], 0)
    alone, _ = run_program(TOY, params, tokens[1:], [90], 0)
    assert np.abs(full[1] - alone[0]).max() < 1e-4
    want = reference(TOY, params, tokens[1:], [90], 0)
    assert rel_err(full[1:], want).max() < 1e-4


def test_a_prefill_of_n_plus_1_equals_a_prefill_of_n_and_one_step(
        params, tokens):
    for n in (127, 128, 200):  # the step that crosses a chunk edge too
        stepped, pools_a = run_program(TOY, params, tokens[:1], [n], 1)
        longer, pools_b = run_program(TOY, params, tokens[:1], [n + 1], 0)
        assert np.abs(stepped[0, 1] - longer[0, 0]).max() < 1e-4
        for name in lane_state_shapes(TOY):
            assert np.abs(np.asarray(pools_a[name][0], np.float32)
                          - np.asarray(pools_b[name][0], np.float32)
                          ).max() < 1e-4, name


def test_a_retired_lane_reads_zeros_and_writes_nothing(tokens, served):
    _, pools = served
    before = {k: np.asarray(v) for k, v in pools.items()}
    holes = jnp.full((2, CAP // BS), 2 * CAP // BS, jnp.int32)
    params = PACKAGE.weights.make_decoder_params(TOY, 3)
    _, after = paged.paged_decode_forward(
        params, TOY, dict(pools), holes, jnp.asarray(tokens[:, :1]),
        jnp.asarray([60, 40]), block_size=BS, rope_len=CAP)
    for name, value in after.items():
        assert (np.asarray(value) == before[name]).all(), name


BF16 = dataclasses.replace(TOY, dtype="bfloat16", max_seq_len=256)
COUNTERS = (
    "serve_state_lane_steps", "serve_state_bytes_rw",
    "serve_lane_state_resets", "serve_scan_tokens", "serve_prefill_tokens",
    "serve_sparse_blocks_selected", "serve_scan_kernel_dispatches",
    "serve_prefill_dispatches",
)


def _batcher(n_slots, params, use_flash=None, **kw):
    from docqa_tpu.engines.serve import ContinuousBatcher

    gen = dataclasses.replace(
        GenerateConfig(), speculative_k=0, prefix_cache=False, decode_chunk=4,
        max_concurrent=n_slots)
    engine = GenerateEngine(BF16, gen=gen, params=params, use_flash=use_flash)
    return ContinuousBatcher(engine, n_slots=n_slots, chunk=4, cache_len=256,
                             kv_block_size=16, prefix_cache=False, **kw)


def _counters():
    from docqa_tpu.runtime.metrics import DEFAULT_REGISTRY

    return {n: DEFAULT_REGISTRY.counter(n).value for n in COUNTERS}


def test_a_slot_a_longer_lane_left_starts_from_zeros_and_the_counters_count():
    """Four prompts through two slots, the longest first: each slot's
    second lane is shorter than the one that just left it and gives the
    tokens it gives alone in a fresh batcher.  The stack scans and does
    not select: no sums row rides a chunk, the host counts the lane-steps."""
    served_params = PACKAGE.weights.make_decoder_params(BF16, 3)
    prompts = [[5 + (7 * i + j) % 250 for j in range(150 - 35 * i)]
               for i in range(4)]
    state_bytes = 3 * (8 * 128 * 4 + 3 * 128 * 2)
    before = _counters()
    b = _batcher(2, served_params)
    try:
        assert b._block.step_sum_names == () and b._block.lane_state
        assert b._block.kv_rows_read is None  # no layer selects
        assert b.kv_bytes_per_token == 1 * (2 * 1 * 16) * 2
        assert b.kv_block_occupancy()["state_bytes_per_lane"] == state_bytes
        out = jax.eval_shape(
            b._decode_program, served_params,
            paged.init_paged_pools(BF16, b.n_blocks, b.block_size, n_lanes=2),
            jnp.zeros((2, b.blocks_per_seq), jnp.int32),
            jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
            jnp.zeros((2,), jnp.int32), jnp.zeros((2,), bool),
            jax.random.PRNGKey(0))
        assert out[-1].shape == (2, 2 * 4 + 1)  # one row a slot, no sums
        assert b._block.prefill_attrs(100, 2) == {
            "state_lanes": 2, "scan_rows": 100}  # no sparse_rows: none selects
        got = [h.result(timeout=600) for h in
               [b.submit_ids(p, max_new_tokens=10) for p in prompts]]
    finally:
        b.stop()
    gained = {k: v - before[k] for k, v in _counters().items()}
    assert gained["serve_lane_state_resets"] == 4
    assert gained["serve_prefill_tokens"] == sum(map(len, prompts))
    assert gained["serve_scan_tokens"] == 3 * sum(map(len, prompts))
    steps = gained["serve_state_lane_steps"]
    assert steps >= sum(len(g) - 1 for g in got) > 0
    assert gained["serve_state_bytes_rw"] == steps * 2 * state_bytes
    assert gained["serve_sparse_blocks_selected"] == 0
    # a CPU: the XLA form scanned, and the kernel's counter stays still
    assert gained["serve_prefill_dispatches"] > 0
    assert gained["serve_scan_kernel_dispatches"] == 0
    for prompt, toks in zip(prompts, got):
        fresh = _batcher(1, served_params)
        try:
            alone = fresh.submit_ids(prompt, max_new_tokens=10).result(
                timeout=600)
        finally:
            fresh.stop()
        assert list(alone) == list(toks)


def test_the_batcher_counts_the_dispatches_that_scanned_in_the_kernel(
        monkeypatch):
    """An engine that saw a TPU (``use_flash``): every prefill dispatch's
    state-space layers scan in the kernel — interpreted here, the one
    thing a CPU cannot take from it — and the counter over
    ``serve_prefill_dispatches`` reads 1.0; the tokens are the XLA form's."""
    real = ssm._scan_rows_in_order
    monkeypatch.setattr(
        ssm, "_scan_rows_in_order",
        lambda *args, interpret: real(*args, interpret=True))
    served_params = PACKAGE.weights.make_decoder_params(BF16, 3)
    prompts = [[5 + (11 * i + j) % 250 for j in range(140 - 50 * i)]
               for i in range(2)]
    got = {}
    for flash in (True, False):
        before = _counters()
        b = _batcher(2, served_params, use_flash=flash)
        try:
            assert b._kernels.scan == flash
            got[flash] = [list(h.result(timeout=600)) for h in
                          [b.submit_ids(p, max_new_tokens=6) for p in prompts]]
        finally:
            b.stop()
        gained = {k: v - before[k] for k, v in _counters().items()}
        assert gained["serve_prefill_dispatches"] > 0
        assert gained["serve_scan_kernel_dispatches"] == (
            gained["serve_prefill_dispatches"] if flash else 0)
    assert got[True] == got[False]


# ---- types, bytes and counts by hand ----------------------------------------

@pytest.fixture(scope="module")
def published():
    conf = arch.load_cell_config(
        os.path.join(BENCH_DIR, "configs", "jamba2-3b-bf16.json"))
    return conf, load_config(env={}, overrides=program_overrides(conf)).decoder


def test_the_state_stays_float32_and_the_window_in_the_activation_type(
        served):
    _, pools = served
    kinds = lane_state_dtypes(TOY)
    assert sorted(kinds) == ["h0", "h2", "h3", "u0", "u2", "u3"]
    assert all(pools[n].dtype == np.float32 for n in kinds)  # a float32 toy
    bf16 = paged.init_paged_pools(BF16, 2 * 256 // BS, BS)
    for i in (0, 2, 3):
        assert bf16[f"h{i}"].dtype == jnp.float32
        assert bf16[f"h{i}"].shape == (2, 8, 128)
        assert bf16[f"u{i}"].dtype == jnp.bfloat16
        assert bf16[f"u{i}"].shape == (2, 3, 128)
    assert bf16["k1"].dtype == jnp.bfloat16 and "k0" not in bf16
    assert "ck1" not in bf16 and paged.STATE_SLOT in bf16
    # through both forwards, in the served types
    params = PACKAGE.weights.make_decoder_params(BF16, 3)
    toks = np.random.default_rng(0).integers(5, 256, size=(2, ROWS))
    cfg = dataclasses.replace(BF16, max_seq_len=CAP)
    _, after = run_program(cfg, params, toks, [130, 20], 2)
    assert all(after[f"h{i}"].dtype == jnp.float32 for i in (0, 2, 3))
    assert all(after[f"u{i}"].dtype == jnp.bfloat16 for i in (0, 2, 3))
    assert float(jnp.abs(after["h0"]).max()) > 0


def test_state_rows_and_parameters_by_hand_at_the_published_sizes(published):
    conf, cfg = published
    assert cfg.mixer_types.count("mamba") == 26
    assert [i for i, m in enumerate(cfg.mixer_types) if m == "attention"] == [
        7, 21]
    shapes, kinds = lane_state_shapes(cfg), lane_state_dtypes(cfg)
    assert len(shapes) == 52 and "h7" not in shapes and "u21" not in shapes
    assert shapes["h0"] == (16, 5120) and kinds["h0"] == "float32"
    assert shapes["u0"] == (3, 5120) and kinds["u0"] == "bfloat16"
    assert 16 * 5120 * 4 == 327680 and 3 * 5120 * 2 == 30720
    assert hybrid.lane_state_bytes(cfg) == 26 * (327680 + 30720) == 9318400
    assert PACKAGE.shapes.lane_state_bytes(conf) == 9318400
    assert kv_row_shapes(cfg, 0) == {} and kv_row_shapes(cfg, 8) == {}
    assert kv_row_shapes(cfg, 7) == kv_row_shapes(cfg, 21) == {
        "k": (1, 128), "v": (1, 128)}
    assert paged.kv_bytes_per_token(cfg) == 2 * 2 * 128 * 2 == 1024
    assert PACKAGE.shapes.kv_bytes_per_token(conf) == 1024
    count = {}
    for name, _, shape, _ in decoder_param_schema(cfg):
        layer = name.split("_")[0] if name[0] == "l" and name[1].isdigit() else ""
        count[layer] = count.get(layer, 0) + math.prod(shape)
    assert count["l0"] == 104161472 and count["l7"] == 76682240
    assert count[""] == 65536 * 2560 + 2560  # tied: the embedding once
    assert sum(count.values()) == 3029337472 == PACKAGE.shapes.parameters(conf)
    made = jax.eval_shape(
        lambda: PACKAGE.weights.make_decoder_params(cfg, 1))
    schema = {n: tuple(s) for n, _, s, _ in decoder_param_schema(cfg)}
    assert {n: tuple(v.shape) for n, v in made.items()} == schema
    assert made["l0_a_log"].dtype == jnp.float32
    assert made["l0_w_in"].dtype == jnp.bfloat16


def test_sala_keeps_its_tree_and_its_sizes():
    """What the trunk hard-coded is now what the configuration says, and
    the defaults say what it hard-coded."""
    sala = arch.load_cell_config(
        os.path.join(BENCH_DIR, "configs", "minicpm-sala-int8.json"))
    cfg = load_config(env={}, overrides=program_overrides(sala)).decoder
    assert cfg.qk_norm and cfg.use_output_gate and cfg.use_output_norm
    assert not cfg.tie_embeddings
    names = {n for n, *_ in decoder_param_schema(cfg)}
    assert {"lm_head", "l0_q_norm_g", "l0_w_ogate", "l1_o_norm_g"} <= names
    assert "l0_o_norm_g" not in names  # the sparse kind has no output norm
    assert paged.kv_bytes_per_token(cfg) == 8448
    assert hybrid.lane_state_bytes(cfg) == 50331648
    assert set(lane_state_dtypes(cfg).values()) == {"float32"}


# ---- refusals ---------------------------------------------------------------

@pytest.mark.parametrize("change, said", [
    (dict(num_experts=2), "num_experts"),
    (dict(ssm_dt_rank=0), "ssm_dt_rank"),
    (dict(ssm_conv_width=1), "ssm_conv_width"),
    (dict(mixer_types=("mamba", "gated", "mamba", "mamba")), "gated"),
])
def test_a_configuration_the_stack_cannot_run_is_refused_by_field(
        change, said):
    with pytest.raises(ValueError, match=said):
        hybrid.check_hybrid_config(dataclasses.replace(TOY, **change))


def test_a_stack_that_does_not_select_needs_no_sparse_size():
    hybrid.check_hybrid_config(dataclasses.replace(
        TOY, sparse_kernel_stride=0, sparse_topk=0))
    pools = paged.init_paged_pools(
        dataclasses.replace(TOY, sparse_kernel_stride=5), 8, 16)
    assert "k1" in pools  # kv_block_size % stride is asked of nobody


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


def test_the_solo_engine_refuses_the_stack_by_name(params):
    engine = GenerateEngine(TOY, gen=GenerateConfig(), params=params)
    with pytest.raises(NotImplementedError, match="sparse_linear"):
        engine.generate_ids([[5, 6, 7]], max_new_tokens=2)


def test_the_engine_keeps_the_paged_kernel_for_a_stack_with_plain_attention(
        params):
    """``use_flash`` reaches the plain attention layers' decode and the
    state-space layers' prefill scan (ISSUE 43) and nothing else of the
    stack; a stack with neither kind has no use for it."""
    gen = GenerateConfig()
    assert GenerateEngine(TOY, gen=gen, params=params, use_flash=True).use_flash
    scans_only = dataclasses.replace(
        TOY, mixer_types=("mamba",) * 4)
    assert GenerateEngine(
        scans_only, gen=gen, use_flash=True,
        params=PACKAGE.weights.make_decoder_params(scans_only, 1)).use_flash
    sala = arch.load({"architecture": "minicpm_sala"})
    assert not GenerateEngine(
        SALA_TOY, gen=gen, use_flash=True,
        params=sala.weights.make_decoder_params(SALA_TOY, 1)).use_flash


# ---- through the harness's own comparison ------------------------------------

def test_the_harness_comparison_at_a_small_size_with_every_control():
    """``check.decoder_check`` as ``calibrate.py`` runs it, lanes packed
    back to back in ONE dispatch: the program under the limit a file of
    this size would state, every control over it; the package does not
    route, so the forwards' two values are what the harness wants."""
    cfg = dataclasses.replace(BF16, max_seq_len=512)
    engine = types.SimpleNamespace(
        cfg=cfg, params=PACKAGE.weights.make_decoder_params(cfg, 11),
        use_flash=False)
    assert not arch.routes(PACKAGE)
    out = check.decoder_check(
        PACKAGE, {"prompt_lengths": [300, 330], "lane_rows": 384}, engine, 11,
        n_blocks=64, block_size=16, seq_capacity=512, n_lanes=2,
        step_width=1, control=True)
    assert out["kv_bits"] == 16 and "routing" not in out
    program = out["program"]["worst_row"]
    assert program < 0.05
    assert set(out["controls"]) == {
        "w_fp8", "w_int8", "a_int8", "a_fp8", "state_bf16"}
    for name, reading in out["controls"].items():
        if name != "state_bf16":  # a toy's short memory forgives it
            assert reading["worst_row"] > 1.2 * program, name
    assert out["controls"]["state_bf16"]["worst_row"] > 5 * (
        out["kv_only"]["kv_int8"]["worst_row"])  # and it is read at all
    assert set(out["kv_only"]) == {"kv_int8"}


# ---- SALA's programs came out the same ---------------------------------------

# sha256 (first 16 hex digits) and length of the lowered text of the toy
# SALA batcher programs on the parent commit b517e33 (jax 0.9.0, CPU),
# recorded before ``models/hybrid.py`` was touched: the programs ISSUE 42
# may not move.  (The GQA and the latent block's: tests/test_latent_block.py
# and tests/test_hybrid_block.py, which still pass.)
SALA_LOWERED_BEFORE = {
    ("float32", "prefill"): ("8bbff325187cea7b", 196548),
    ("float32", "decode"): ("335f03f69c3bccf7", 217704),
    ("bf16_int8", "prefill"): ("ce7e594bf837d256", 226603),
    ("bf16_int8", "decode"): ("563dd9ccccf33338", 251034),
}
SALA_TOY = DecoderConfig(
    vocab_size=256, hidden_dim=64, num_layers=4, num_heads=4, num_kv_heads=2,
    head_dim=16, mlp_dim=128, max_seq_len=256, norm_eps=1e-6,
    block="sparse_linear", dtype="float32",
    mixer_types=("sparse", "linear", "linear", "sparse"), linear_heads=4,
    linear_head_dim=16, scale_emb=12.0, scale_depth=1.4, dim_model_base=16,
    sparse_kernel_size=8, sparse_kernel_stride=4, sparse_block_size=8,
    sparse_topk=4, sparse_init_blocks=1, sparse_window_size=8,
    sparse_dense_len=40,
)


def _batcher_programs_lowered(package, cfg, use_flash):
    """{"prefill", "decode"}: lowered text of a toy batcher's two programs
    for ``cfg``; under ``use_flash`` cross-lowered for a TPU (a Mosaic
    kernel does not lower for the CPU)."""
    from docqa_tpu.engines.serve import ContinuousBatcher

    sds, i32 = jax.ShapeDtypeStruct, jnp.int32
    gen = dataclasses.replace(
        GenerateConfig(), speculative_k=0, prefix_cache=False,
        max_concurrent=4, decode_chunk=4)
    engine = GenerateEngine(
        cfg, gen=gen, use_flash=use_flash,
        params=package.weights.make_decoder_params(cfg, 1))
    b = ContinuousBatcher(engine, n_slots=4, chunk=4, cache_len=256,
                          kv_block_size=16, prefix_cache=False)
    try:
        pools = jax.eval_shape(lambda: paged.init_paged_pools(
            b.cfg, b.n_blocks, b.block_size, n_lanes=b.n_slots))
        params = jax.tree_util.tree_map(
            lambda a: sds(a.shape, a.dtype), engine.params)
        rng = sds((2,), jnp.uint32)
        lane, flag = sds((4,), i32), sds((4,), jnp.bool_)
        packed = (sds((256,), i32),) * 4 + (lane,) * 2
        tables = sds((4, b.blocks_per_seq), i32)
        platforms = ("tpu" if use_flash else "cpu",)
        return {
            "prefill": b._get_prefill_fn().trace(
                params, pools, *packed, rng).lower(
                lowering_platforms=platforms).as_text(),
            "decode": b._get_decode_fn().trace(
                params, pools, tables, lane, lane, lane, flag, rng).lower(
                lowering_platforms=platforms).as_text(),
        }
    finally:
        b.stop()


@pytest.fixture(scope="module")
def sala_lowered():
    package = arch.load({"architecture": "minicpm_sala"})
    out = {}
    for kind, cfg in (
            ("float32", SALA_TOY),
            ("bf16_int8", dataclasses.replace(
                SALA_TOY, dtype="bfloat16", quantize_weights=True))):
        for program, text in _batcher_programs_lowered(
                package, cfg, False).items():
            out[kind, program] = text
    return out


@pytest.mark.parametrize(
    "program", sorted(SALA_LOWERED_BEFORE), ids="-".join)
def test_salas_programs_lower_to_the_text_they_lowered_to(
        sala_lowered, program):
    if jax.__version__ != "0.9.0":
        pytest.skip("the recorded text is jax 0.9.0's")
    text = sala_lowered[program]
    digest, length = SALA_LOWERED_BEFORE[program]
    assert len(text) == length
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


# ---- ISSUE 46: SALA's decode step under ``use_flash`` reads pages -------------

# the toy at a geometry the paged kernel reads (two bf16 kv heads x 128)
# and blocks of two pages: what an engine that saw a TPU builds
SALA_PAGED_TOY = dataclasses.replace(
    SALA_TOY, dtype="bfloat16", head_dim=128, sparse_block_size=32)
PAGED_KERNEL = "_paged_decode_kernel"
PAGED_KERNEL_CALL = "call @_paged_attend_local"
# [S, g, topk x block, d]: what ``taken_rows_only`` gathers a sparse layer
TAKEN_ROWS = "tensor<4x2x128x128xbf16>"


@pytest.mark.parametrize("use_flash", [False, True], ids=["xla", "flash"])
def test_salas_decode_program_reads_the_taken_blocks_as_pages_under_flash(
        use_flash):
    """The paged kernel lowered ONCE and called once a sparse layer, no
    gather of the taken rows and no ``cond`` between two forms; without
    ``use_flash`` the XLA form as it stands, and no kernel."""
    package = arch.load({"architecture": "minicpm_sala"})
    text = _batcher_programs_lowered(
        package, SALA_PAGED_TOY, use_flash)["decode"]
    layers = len(hybrid.sparse_layers(SALA_PAGED_TOY))
    assert layers == 2
    if use_flash:
        assert text.count(PAGED_KERNEL_CALL) == layers
        assert text.count("tpu_custom_call") == 1 and PAGED_KERNEL in text
        assert TAKEN_ROWS not in text
        assert "stablehlo.case" not in text and "stablehlo.if" not in text
    else:
        assert PAGED_KERNEL not in text and "tpu_custom_call" not in text
        assert text.count(TAKEN_ROWS) >= 2 * layers  # K and V a layer


@pytest.fixture
def paged_kernel_interpreted(monkeypatch):
    """``paged_flash_decode`` as the forward calls it, interpreted: the
    one thing a CPU cannot take from it."""
    import importlib

    attention = importlib.import_module("docqa_tpu.ops.attention")
    real = attention.paged_flash_decode
    monkeypatch.setattr(
        attention, "paged_flash_decode",
        lambda *args, **kw: real(*args, **{**kw, "interpret": True}))


def test_the_batcher_counts_the_chunks_that_read_the_taken_blocks_as_pages(
        paged_kernel_interpreted):
    """An engine that saw a TPU (``use_flash``): every decode chunk's
    sparse layers read the blocks taken through the paged kernel —
    interpreted here — and the counter over ``serve_decode_chunks`` reads
    1.0; the tokens and what the record sums to are the XLA form's."""
    from docqa_tpu.engines.serve import ContinuousBatcher
    from docqa_tpu.runtime.metrics import DEFAULT_REGISTRY

    package = arch.load({"architecture": "minicpm_sala"})
    params = package.weights.make_decoder_params(SALA_PAGED_TOY, 3)
    names = ("serve_decode_chunks", "serve_sparse_paged_chunks",
             "serve_sparse_blocks_selected", "serve_sparse_blocks_live",
             "serve_sparse_dense_lane_steps", "serve_state_lane_steps")
    # one lane selects from its first step, one crosses dense_len (40)
    # while it decodes: both kinds of virtual lane in one chunk
    prompts = [[5 + (11 * i + j) % 250 for j in range(150 - 115 * i)]
               for i in range(2)]
    gen = dataclasses.replace(
        GenerateConfig(), speculative_k=0, prefix_cache=False, decode_chunk=4,
        max_concurrent=2)
    got, gained = {}, {}
    for flash in (True, False):
        before = {n: DEFAULT_REGISTRY.counter(n).value for n in names}
        engine = GenerateEngine(
            SALA_PAGED_TOY, gen=gen, params=params, use_flash=flash)
        assert engine.use_flash == flash
        b = ContinuousBatcher(engine, n_slots=2, chunk=4, cache_len=256,
                              kv_block_size=16, prefix_cache=False)
        try:
            assert b._kernels.sparse_paged == flash
            got[flash] = [list(h.result(timeout=600)) for h in
                          [b.submit_ids(p, max_new_tokens=10) for p in prompts]]
        finally:
            b.stop()
        gained[flash] = {
            n: DEFAULT_REGISTRY.counter(n).value - before[n] for n in names}
        assert gained[flash]["serve_decode_chunks"] > 0
        assert gained[flash]["serve_sparse_paged_chunks"] == (
            gained[flash]["serve_decode_chunks"] if flash else 0)
    assert got[True] == got[False]
    assert gained[True]["serve_sparse_dense_lane_steps"] > 0
    for name in names[2:]:
        assert gained[True][name] == gained[False][name], name


def test_one_step_of_both_forms_leaves_the_same_record(
        paged_kernel_interpreted):
    """``paged_decode_forward`` with and without ``use_flash`` on the same
    pools: the record to the last id, the logits to bfloat16's rounding."""
    package = arch.load({"architecture": "minicpm_sala"})
    cfg = SALA_PAGED_TOY
    params = package.weights.make_decoder_params(cfg, 5)
    rng = np.random.default_rng(1)
    n_blocks, lanes = 48, 3
    pools = paged.init_paged_pools(cfg, n_blocks, 16, n_lanes=lanes)
    pools = {
        k: jnp.asarray(rng.standard_normal(v.shape, np.float32), v.dtype)
        if k[0] in "kvc" and k[1:].lstrip("k").isdigit() else v
        for k, v in pools.items()}
    tables = jnp.asarray(rng.permutation(n_blocks).reshape(lanes, 16), jnp.int32)
    tok = jnp.asarray(rng.integers(5, 256, (lanes, 1)), jnp.int32)
    lengths = jnp.asarray([200, 39, 20], jnp.int32)  # selects, AT 40, under
    out = {
        flash: paged.paged_decode_forward(
            params, cfg, dict(pools), tables, tok, lengths, block_size=16,
            rope_len=256, use_flash=flash)
        for flash in (True, False)}
    record = np.asarray(out[False][2])
    assert (np.asarray(out[True][2]) == record).all()
    assert (record[:, :2] >= 0).any(-1).all() and (record[:, 2] == -1).all()
    err = rel_err(np.asarray(out[True][0]), np.asarray(out[False][0]))
    assert err.max() < 0.02


# ---- who scans: the kernel under ``use_flash``, the XLA form otherwise ------

# sha256 (first 16 hex digits) and length of the lowered text of the toy
# Jamba batcher programs on the parent commit b1630a0 (jax 0.9.0, CPU),
# recorded before ISSUE 43 touched ``ops/ssm.py``: the decode program does
# not move whatever ``use_flash`` says (``selective_scan_step`` is not
# touched; at 16-wide heads the paged kernel is refused, so its text holds
# no kernel either way), and the prefill without ``use_flash`` still
# lowers the XLA form as it stood.
JAMBA_LOWERED_BEFORE = {
    ("float32", "prefill"): ("8fcfb247c2f4b909", 204322),
    ("float32", "decode"): ("d537244f293a18b0", 120176),
    ("bfloat16", "prefill"): ("3ce99c270df9f737", 217973),
    ("bfloat16", "decode"): ("6617afac339bd5a9", 131355),
}
KERNEL = "_selective_scan_kernel"
KERNEL_CALL = "call @_scan_rows_in_order"


@pytest.fixture(scope="module")
def jamba_lowered():
    """{(kind, program, use_flash): lowered text} of the toy batcher's two
    programs."""
    out = {}
    for kind, cfg in (
            ("float32", dataclasses.replace(TOY, max_seq_len=256)),
            ("bfloat16", BF16)):
        for flash in (False, True):
            for program, text in _batcher_programs_lowered(
                    PACKAGE, cfg, flash).items():
                out[kind, program, flash] = text
    return out


@pytest.mark.parametrize("use_flash", [False, True], ids=["xla", "flash"])
@pytest.mark.parametrize(
    "program", sorted(JAMBA_LOWERED_BEFORE), ids="-".join)
def test_jambas_programs_lower_to_the_text_they_lowered_to(
        jamba_lowered, program, use_flash):
    """The decode program whatever ``use_flash`` says, and the prefill
    without it, are the parent's to the byte; the prefill under it is
    another program: one kernel, lowered once, called a state-space layer."""
    if jax.__version__ != "0.9.0":
        pytest.skip("the recorded text is jax 0.9.0's")
    text = jamba_lowered[(*program, use_flash)]
    digest, length = JAMBA_LOWERED_BEFORE[program]
    if use_flash and program[1] == "prefill":
        assert text.count(KERNEL_CALL) == len(hybrid.mamba_layers(TOY)) == 3
        assert text.count("tpu_custom_call") == 1 and KERNEL in text
        return
    assert KERNEL not in text
    assert len(text) == length
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


@pytest.mark.parametrize("use_flash, meshed, calls", [
    (True, False, 3), (False, False, 0), (None, False, 0), (True, True, 0),
], ids=["flash", "no-flash", "nothing-observed-on-a-cpu", "flash-on-a-mesh"])
def test_the_forward_scans_in_the_kernel_only_under_flash_and_no_mesh(
        params, use_flash, meshed, calls):
    """``ragged_prefill_forward`` hands ``use_flash`` and ``mesh`` to the
    scan as it hands them to nothing else: the kernel a state-space layer
    under ``use_flash`` with no mesh, the XLA form otherwise.  Handed
    nothing (the harness's comparison) it asks the backend — a CPU here."""
    from docqa_tpu.runtime.mesh import host_cpu_mesh

    sds, i32 = jax.ShapeDtypeStruct, jnp.int32
    mesh = host_cpu_mesh(2) if meshed else None
    assert paged._forms(TOY, None, use_flash, mesh, BS).scan == bool(calls)
    pools = jax.eval_shape(lambda: paged.init_paged_pools(TOY, 32, BS))

    def prefill(params, pools, ids, seg, pos, dest, last):
        return paged.ragged_prefill_forward(
            params, TOY, pools, ids, seg, pos, dest, last, rope_len=CAP,
            use_flash=use_flash, mesh=mesh)

    text = jax.jit(prefill).trace(
        params, pools, *(sds((256,), i32),) * 4, sds((2,), i32)
    ).lower(lowering_platforms=("tpu",)).as_text()
    assert text.count(KERNEL_CALL) == calls
    assert (KERNEL in text) == bool(calls)
