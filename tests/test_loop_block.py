"""ISSUE 44: the looped trunk of the ``gqa_swiglu`` block — ``loop_steps``
passes of the whole stack over the same parameters as ONE loop in the
program, a norm on each sublayer's output (``sandwich_norm``), the final
norm closing every step, one cache entry a (step, layer) — through the
paged forwards and the batcher, against the plain reference of
``benchmark/architectures/ouro/`` — CPU, toy widths, seeded weights.

* paged prefill then decode equal the reference on logits, float32 and
  bfloat16; the harness's own comparison with every control;
* the plain trunk's toy programs lower to the text they lowered to on the
  parent, and the step loop is a loop in the looped programs' text;
* the four steps' cache entries are distinct: a program that shares one
  entry a layer, or runs three steps, is far over the tolerance;
* a hole stays a hole at every step's offset, and retirement frees a
  block for all four;
* bytes a token, the pools' shapes and the parameter count at the
  published sizes, by hand;
* the refusals by field name; the counters, the gauge and the span
  attributes; the shard audit's three meshes.
"""

import dataclasses
import hashlib
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
from docqa_tpu.models import decoder  # noqa: E402
from docqa_tpu.models.decoder import (  # noqa: E402
    check_loop_config,
    decoder_param_schema,
    init_decoder_params,
    kv_entries,
)
from docqa_tpu.ops.scopes import DEVICE_SCOPES, LOOP_SCOPES, scope  # noqa: E402
from harness import arch, check  # noqa: E402
from harness.child import program_overrides  # noqa: E402

PACKAGE = arch.load({"architecture": "ouro"})
# float32 so that program and reference differ by rounding order alone
TOY = DecoderConfig(
    vocab_size=256, hidden_dim=64, num_layers=3, num_heads=4, num_kv_heads=4,
    head_dim=16, mlp_dim=128, max_seq_len=256, norm_eps=1e-6, rope_theta=1e6,
    dtype="float32", loop_steps=4, sandwich_norm=True,
)
BF16 = dataclasses.replace(TOY, dtype="bfloat16")
BS, CAP, ROWS = 16, 256, 256  # block, positions a lane, packed rows a lane
LENGTHS, STEPS = [150, 37], 4


@pytest.fixture(scope="module")
def params():
    return PACKAGE.weights.make_decoder_params(TOY, 3)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(5, 256, size=(2, ROWS))


def run_program(cfg, params, tokens, lengths, steps):
    """Prefill ``lengths[b]`` tokens of lane b in ONE packed dispatch, then
    ``steps`` teacher-forced decode steps: (logits [lanes, 1 + steps,
    vocab], pools)."""
    lanes = len(lengths)
    n_blocks = lanes * CAP // BS
    pools = paged.init_paged_pools(cfg, n_blocks, BS)
    t = ROWS * lanes
    ids = np.zeros(t, np.int32)
    seg = np.full(t, -1, np.int32)
    pos = np.zeros(t, np.int32)
    dest = np.full(t, n_blocks * BS, np.int32)  # the harness's "dropped"
    last = np.zeros(lanes, np.int32)
    for b, n in enumerate(lengths):
        st = ROWS * b
        ids[st:st + n] = tokens[b, :n]
        seg[st:st + n] = b
        pos[st:st + n] = np.arange(n)
        dest[st:st + n] = b * CAP + np.arange(n)
        last[b] = st + n - 1
    logits, pools = paged.ragged_prefill_forward(
        params, cfg, pools, *map(jnp.asarray, (ids, seg, pos, dest, last)),
        rope_len=CAP)
    got = [np.asarray(logits)[:, None]]
    tables = jnp.arange(n_blocks, dtype=jnp.int32).reshape(lanes, -1)
    lens = np.asarray(lengths, np.int32)
    for _ in range(steps):
        tok = np.stack([tokens[b, lens[b]:lens[b] + 1] for b in range(lanes)])
        out, pools = paged.paged_decode_forward(
            params, cfg, pools, tables, jnp.asarray(tok), jnp.asarray(lens),
            block_size=BS, rope_len=CAP)
        got.append(np.asarray(out))
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


@pytest.fixture(scope="module")
def served(params, tokens):
    return run_program(TOY, params, tokens, LENGTHS, STEPS)


@pytest.fixture(scope="module")
def wanted(params, tokens):
    return reference(TOY, params, tokens, LENGTHS, STEPS)


# ---- (a) the program against the reference ----------------------------------

# float32 on both sides: what is left is the order of the sums (a packed
# prefill reduces over 512 rows, the reference over a lane's own), a few
# float32 ulps through 4 x 3 layers
TOLERANCE = 1e-4


def test_paged_prefill_then_decode_agree_with_the_reference(served, wanted):
    got, _ = served
    assert got.shape == wanted.shape == (2, 1 + STEPS, 256)
    assert rel_err(got, wanted).max() < TOLERANCE


def test_in_bfloat16_the_program_stays_under_every_control(tokens):
    """bfloat16 through 12 layer passes reads ~2 %; each control — the
    reference one precision lower — reads several times that."""
    served_params = PACKAGE.weights.make_decoder_params(BF16, 3)
    got, pools = run_program(BF16, served_params, tokens, LENGTHS, 2)
    want = reference(BF16, served_params, tokens, LENGTHS, 2)
    program = rel_err(got, want).max()
    assert 1e-3 < program < 0.05
    assert {v.dtype for v in pools.values()} == {jnp.dtype("bfloat16")}
    for name, control in PACKAGE.weights.controls_for(BF16).items():
        reading = rel_err(reference(
            BF16, served_params, tokens, LENGTHS, 2, control), want).max()
        assert reading > 1.5 * program, name


def test_the_harness_comparison_at_a_small_size_with_every_control():
    """``check.decoder_check`` as ``calibrate.py`` runs it, lanes packed
    back to back in ONE dispatch through a pool of the served shape."""
    engine = types.SimpleNamespace(
        cfg=BF16, params=PACKAGE.weights.make_decoder_params(BF16, 11),
        use_flash=False)
    assert not arch.routes(PACKAGE)
    out = check.decoder_check(
        PACKAGE, {"prompt_lengths": [100, 150], "lane_rows": 256}, engine, 11,
        n_blocks=32, block_size=16, seq_capacity=256, n_lanes=2,
        step_width=1, control=True)
    assert out["kv_bits"] == 16 and "routing" not in out
    program = out["program"]["worst_row"]
    assert program < 0.05
    assert set(out["controls"]) == {"w_fp8", "w_int8", "a_int8", "a_fp8"}
    for name, reading in out["controls"].items():
        assert reading["worst_row"] > 1.5 * program, name
    assert set(out["kv_only"]) == {"kv_int8"}


def test_an_int8_pool_reads_eight_bits_missing(params, tokens, monkeypatch):
    """What holds the 192 entries to their stated type is the exact
    comparison: a pool kept in int8 is 8 bits short of the file's 16."""
    real = paged.init_paged_pools
    monkeypatch.setattr(
        paged, "init_paged_pools",
        lambda cfg, *a, **kw: real(cfg, *a, **{**kw, "dtype": jnp.int8}))
    engine = types.SimpleNamespace(cfg=TOY, params=params, use_flash=False)
    ids, lengths = check.sample_prompts(
        5, TOY.vocab_size, 2, 2, {"prompt_lengths": [60], "lane_rows": 128})
    _, bits, record = check.program_logits(
        engine, ids, lengths, 2, 1, n_blocks=32, block_size=16,
        seq_capacity=256)
    assert record is None and bits == 8
    assert check.kv_bits_missing(16, bits) == 8


# ---- (b) the plain trunk's programs came out the same ------------------------

# sha256 (first 16 hex digits) and length of the lowered text of the toy
# Mistral batcher programs on the parent commit 938b901 (jax 0.9.0, CPU),
# recorded before ``models/decoder.py`` and ``engines/paged.py`` were
# touched: at ``loop_steps`` 1 with ``sandwich_norm`` false ISSUE 44 may not
# move them.  (The GQA block's under speculation and the prefix cache, the
# latent and both hybrid stacks': tests/test_latent_block.py,
# tests/test_hybrid_block.py and tests/test_ssm_block.py, which pass
# unedited.  A program with the Pallas kernel in it is not pinned: its text
# holds the kernel's source locations, which move with any line above them.)
MISTRAL_LOWERED_BEFORE = {
    ("float32", "prefill"): ("7acd79db5854c80d", 89942),
    ("float32", "decode"): ("74b297434c623767", 102836),
    ("bf16_int8", "prefill"): ("ce655cc1de0deed1", 106433),
    ("bf16_int8", "decode"): ("c31c26b9783a76c7", 121574),
}
MISTRAL_TOY = DecoderConfig(
    vocab_size=256, hidden_dim=64, num_layers=3, num_heads=4, num_kv_heads=2,
    head_dim=16, mlp_dim=128, max_seq_len=256, norm_eps=1e-6,
    sliding_window=128, dtype="float32",
)


def _batcher_programs_lowered(package, cfg):
    """{"prefill", "decode"}: lowered text of a toy batcher's two programs
    for ``cfg``."""
    from docqa_tpu.engines.serve import ContinuousBatcher

    sds, i32 = jax.ShapeDtypeStruct, jnp.int32
    gen = dataclasses.replace(
        GenerateConfig(), speculative_k=0, prefix_cache=False,
        max_concurrent=4, decode_chunk=4)
    engine = GenerateEngine(
        cfg, gen=gen, use_flash=False,
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
        return {
            "prefill": b._get_prefill_fn().trace(
                params, pools, *packed, rng).lower(
                lowering_platforms=("cpu",)).as_text(),
            "decode": b._get_decode_fn().trace(
                params, pools, tables, lane, lane, lane, flag, rng).lower(
                lowering_platforms=("cpu",)).as_text(),
        }
    finally:
        b.stop()


@pytest.fixture(scope="module")
def mistral_lowered():
    package = arch.load({"architecture": "mistral"})
    out = {}
    for kind, cfg in (
            ("float32", MISTRAL_TOY),
            ("bf16_int8", dataclasses.replace(
                MISTRAL_TOY, dtype="bfloat16", quantize_weights=True))):
        assert (cfg.loop_steps, cfg.sandwich_norm) == (1, False)
        for program, text in _batcher_programs_lowered(package, cfg).items():
            out[kind, program] = text
    return out


@pytest.mark.parametrize(
    "kind, program", sorted(MISTRAL_LOWERED_BEFORE))
def test_the_plain_trunks_programs_lower_to_the_text_they_lowered_to(
        mistral_lowered, kind, program):
    if jax.__version__ != "0.9.0":
        pytest.skip("the recorded text is jax 0.9.0's")
    text = mistral_lowered[kind, program]
    digest, length = MISTRAL_LOWERED_BEFORE[kind, program]
    assert len(text) == length
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


@pytest.fixture(scope="module")
def looped_lowered():
    """The toy batcher's programs of the looped trunk, and of the same
    layers run once (sandwich norms, no loop)."""
    once = dataclasses.replace(TOY, loop_steps=1)
    return {
        "looped": _batcher_programs_lowered(PACKAGE, TOY),
        "once": _batcher_programs_lowered(PACKAGE, once),
    }


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_the_step_loop_is_a_loop_in_the_program(looped_lowered, program):
    """The three-layer body appears ONCE in the looped program's text —
    as many matrix products as the same layers run once — inside one more
    ``while`` than that program has; unrolled, four passes would hold four
    times the products."""
    looped = looped_lowered["looped"][program]
    once = looped_lowered["once"][program]
    dots = "stablehlo.dot_general"
    assert looped.count(dots) == once.count(dots) > 7 * 3
    assert looped.count("stablehlo.while") == once.count(
        "stablehlo.while") + 1
    # every pool is donated and comes back: none is copied at the boundary
    assert looped.count("tf.aliasing_output") == 2 * 3


# ---- (c) the four steps' entries are distinct ---------------------------------

def test_a_program_that_shares_one_entry_a_layer_is_far_off(
        params, tokens, wanted, monkeypatch):
    """Every step writes and reads range 0: the prefill still attends the
    rows in flight, so its row is right — and every decode step reads, for
    three of its four passes, the LAST pass's keys and values."""
    monkeypatch.setattr(
        paged, "_step_view",
        lambda cfg, step, n_rows, dest, tables=None, block_size=None: (
            dest, tables))
    got, _ = run_program(TOY, params, tokens, LENGTHS, STEPS)
    err = rel_err(got, wanted)
    assert err[:, 0].max() < TOLERANCE
    assert err[:, 1:].min() > 1000 * TOLERANCE


def test_a_program_that_runs_three_steps_is_far_off(params, tokens, wanted):
    three = dataclasses.replace(TOY, loop_steps=3)
    got, pools = run_program(three, params, tokens, LENGTHS, STEPS)
    assert rel_err(got, wanted).min() > 1000 * TOLERANCE
    assert pools["k0"].shape[0] == 3 * 2 * CAP


def test_every_steps_range_holds_rows_of_its_own(served):
    """After a prefill and four decode steps each of the four ranges of a
    layer's pool holds the lanes' rows — and no two ranges the same ones."""
    _, pools = served
    for name in ("k0", "v2"):
        ranges = np.asarray(pools[name]).reshape(4, 2 * CAP, 4, 16)
        for t in range(4):
            for lane, n in enumerate(LENGTHS):
                rows = ranges[t, lane * CAP:(lane + 1) * CAP]
                assert np.abs(rows[:n + STEPS]).sum(axis=(1, 2)).min() > 0
                assert not rows[n + STEPS:].any()
        for t in range(1, 4):
            assert np.abs(ranges[t] - ranges[0]).max() > 1e-3


def test_the_rotation_takes_the_tokens_position_in_every_step(params, tokens):
    """One token, one decode step at position p: shifting p moves the
    logits (RoPE sees the position), and the step index does not enter the
    angle — a prefill of n + 1 equals a prefill of n and one step, through
    all four passes."""
    n = 40
    whole, _ = run_program(TOY, params, tokens, [n + 1, 20], 0)
    stepped, _ = run_program(TOY, params, tokens, [n, 20], 1)
    assert rel_err(stepped[:1, 1], whole[:1, 0]).max() < TOLERANCE


# ---- (d) holes and retirement ------------------------------------------------

def test_a_hole_stays_a_hole_at_every_steps_offset():
    n_rows, bs = 8 * 16, 16
    dest = jnp.asarray([0, 5, n_rows - 1, n_rows, n_rows + 7, 10 * n_rows])
    tables = jnp.asarray([[0, 7, 8], [3, 8, 100]])
    for step in range(4):
        rows, tabs = paged._step_view(TOY, step, n_rows, dest, tables, bs)
        rows, tabs = np.asarray(rows), np.asarray(tabs)
        assert list(rows[:3]) == [step * n_rows + r for r in (0, 5, 127)]
        assert (rows[3:] >= 4 * n_rows).all()  # dropped: past the WHOLE pool
        assert list(tabs[0, :2]) == [step * 8, step * 8 + 7]
        assert tabs[1, 0] == step * 8 + 3
        # a hole never lands in the next step's range
        assert (tabs[:, 2] >= 4 * 8).all() and tabs[1, 1] >= 4 * 8
    assert paged._step_view(TOY, None, n_rows, dest, tables, bs) == (
        dest, tables)


def test_a_retired_lane_writes_nothing_in_any_range(params, tokens, served):
    _, pools = served
    before = {k: np.asarray(v) for k, v in pools.items()}
    holes = jnp.full((2, CAP // BS), 2 * CAP // BS, jnp.int32)  # the sentinel
    _, after = paged.paged_decode_forward(
        params, TOY, dict(pools), holes, jnp.asarray(tokens[:, :1]),
        jnp.asarray([60, 40]), block_size=BS, rope_len=CAP)
    assert set(after) == set(before)
    for name, value in after.items():
        assert value.shape[0] == 4 * 2 * CAP
        assert (np.asarray(value) == before[name]).all(), name


def test_a_dropped_prefill_row_is_dropped_in_every_range(params, tokens):
    """Padding rows carry the harness's out-of-bounds row (one range's
    size): under the offset they must not land in the next step's range."""
    pools = paged.init_paged_pools(TOY, 2 * CAP // BS, BS)
    t = 128
    ids = jnp.asarray(tokens[0, :t], jnp.int32)
    seg = jnp.where(jnp.arange(t) < 50, 0, -1)
    pos = jnp.where(jnp.arange(t) < 50, jnp.arange(t), 0)
    dest = jnp.where(jnp.arange(t) < 50, jnp.arange(t), 2 * CAP)
    _, pools = paged.ragged_prefill_forward(
        params, TOY, pools, ids, seg, pos, dest, jnp.asarray([49, 0]),
        rope_len=CAP)
    for name, value in pools.items():
        ranges = np.asarray(value).reshape(4, 2 * CAP, 4, 16)
        assert not ranges[:, 50:].any(), name
        assert np.abs(ranges[:, :50]).sum(axis=(2, 3)).min() > 0, name


# ---- (e) types, bytes and counts by hand --------------------------------------

@pytest.fixture(scope="module")
def published():
    conf = arch.load_cell_config(
        os.path.join(BENCH_DIR, "configs", "ouro-2.6b-bf16.json"))
    return conf, load_config(env={}, overrides=program_overrides(conf))


def test_bytes_a_token_pools_and_parameters_at_the_published_sizes(published):
    conf, config = published
    cfg, shapes = config.decoder, arch.load_shapes(conf).shapes
    assert (cfg.loop_steps, cfg.sandwich_norm, cfg.loop_exit_threshold) == (
        4, True, 1.0)
    assert cfg.block == "gqa_swiglu" and not cfg.quantize_weights
    assert kv_entries(cfg) == 4
    assert paged.kv_bytes_per_token(cfg) == shapes.kv_bytes_per_token(
        conf) == 2 * 16 * 128 * 2 * (4 * 48) == 1_572_864
    # a plain model of these layers keeps a quarter of it
    assert paged.kv_bytes_per_token(
        dataclasses.replace(cfg, loop_steps=1)) == 393_216
    tokens = config.generate.kv_pool_tokens
    pools = jax.eval_shape(
        lambda: paged.init_paged_pools(cfg, tokens // 16, 16))
    assert sorted(pools) == sorted(
        f"{kv}{i}" for kv in "kv" for i in range(48))
    assert tokens == 4 * 512
    for value in pools.values():
        assert value.shape == (4 * 2048, 16, 128)
        assert value.dtype == jnp.bfloat16
    assert sum(v.size * 2 for v in pools.values()) == (
        tokens * 1_572_864) == 3_221_225_472
    schema = list(decoder_param_schema(cfg))
    assert sum(int(np.prod(shape)) for _, _, shape, _ in schema) == (
        shapes.parameters(conf)) == 2_667_974_657
    names = {name for name, *_ in schema}
    assert {"l47_attn_post_norm_g", "l0_mlp_post_norm_g", "exit_gate_w",
            "exit_gate_b"} <= names
    assert dict((n, s) for n, _, s, _ in schema)["exit_gate_w"] == (2048, 1)


def test_the_benchmarks_tree_is_the_schemas(params):
    schema = {n: s for n, _, s, _ in decoder_param_schema(TOY)}
    assert {n: tuple(v.shape) for n, v in params.items()} == schema
    drawn = init_decoder_params(jax.random.PRNGKey(0), TOY)
    assert {n: tuple(v.shape) for n, v in drawn.items()} == schema
    # at the defaults the tree is the plain block's: no gate, two norms
    plain = {n for n, *_ in decoder_param_schema(MISTRAL_TOY)}
    assert not [n for n in plain if "post_norm" in n or "exit_gate" in n]
    assert len(plain) == 3 + 9 * 3
    # the new entries stand behind every older one: the draws keep their
    # place in the stream
    order = [n for n, *_ in decoder_param_schema(TOY)]
    assert order[:3 + 9 * 3] == [
        n for n, *_ in decoder_param_schema(MISTRAL_TOY)]


def test_the_exit_gate_is_in_the_tree_and_read_by_nobody(
        params, tokens, served):
    """At ``loop_exit_threshold`` 1 no step exits early: another gate,
    the same logits."""
    other = dict(params, exit_gate_w=params["exit_gate_w"] + 5.0,
                 exit_gate_b=params["exit_gate_b"] - 3.0)
    got, _ = run_program(TOY, other, tokens, LENGTHS, 1)
    assert (got == served[0][:, :2]).all()


def test_loop_close_is_a_scope_and_the_looped_program_carries_it():
    """Beside ``DEVICE_SCOPES`` (the tuple the benchmark's five decode
    metrics partition, pinned in a file of the benchmark's), taken by
    ``scope()`` like them; a plain trunk's program holds no such scope."""
    assert LOOP_SCOPES == ("loop_close",)
    assert "loop_close" not in DEVICE_SCOPES and len(DEVICE_SCOPES) == 11
    with scope("loop_close"):
        pass
    with pytest.raises(ValueError, match="loop_close"):
        scope("loop_open")
    text = jax.jit(lambda p, pools, tok: paged.paged_decode_forward(
        p, TOY, pools, jnp.zeros((2, 16), jnp.int32), tok,
        jnp.asarray([3, 4]), block_size=BS, rope_len=CAP)).lower(
        jax.eval_shape(lambda: PACKAGE.weights.make_decoder_params(TOY, 1)),
        jax.eval_shape(lambda: paged.init_paged_pools(TOY, 32, BS)),
        jax.ShapeDtypeStruct((2, 1), jnp.int32)).as_text(debug_info=True)
    for name in ("loop_close", "proj", "attend", "mlp", "cache_write",
                 "head", "embed"):
        assert f"dq.{name}" in text, name
    import re

    assert set(re.findall(r"dq\.(\w+)", text)) <= set(
        DEVICE_SCOPES + LOOP_SCOPES)
    once = dataclasses.replace(TOY, loop_steps=1)
    plain = jax.jit(lambda p, pools, tok: paged.paged_decode_forward(
        p, once, pools, jnp.zeros((2, 16), jnp.int32), tok,
        jnp.asarray([3, 4]), block_size=BS, rope_len=CAP)).lower(
        jax.eval_shape(lambda: PACKAGE.weights.make_decoder_params(once, 1)),
        jax.eval_shape(lambda: paged.init_paged_pools(once, 32, BS)),
        jax.ShapeDtypeStruct((2, 1), jnp.int32)).as_text(debug_info=True)
    assert "dq.loop_close" not in plain and "dq.head" in plain


# ---- (f) the refusals -----------------------------------------------------------

@pytest.mark.parametrize("change, said", [
    ({"loop_exit_threshold": 0.5}, "loop_exit_threshold"),
    ({"loop_exit_threshold": 1.5}, "loop_exit_threshold"),
    ({"loop_steps": 0}, "loop_steps"),
    ({"quantize_weights": True}, "quantize_weights"),
    ({"quantize_weights": True, "loop_steps": 1}, "quantize_weights"),
    ({"block": "mla_moe"}, "loop_steps / sandwich_norm"),
    ({"block": "sparse_linear", "sandwich_norm": False},
     "loop_steps / sandwich_norm"),
])
def test_a_configuration_the_trunk_cannot_run_is_refused_by_field(
        change, said):
    cfg = dataclasses.replace(TOY, **change)
    with pytest.raises(ValueError, match=said):
        check_loop_config(cfg)
    with pytest.raises(ValueError, match=said):
        GenerateEngine(cfg, gen=GenerateConfig())


def test_the_defaults_and_the_looped_toy_pass_the_check():
    check_loop_config(MISTRAL_TOY)
    check_loop_config(dataclasses.replace(MISTRAL_TOY, quantize_weights=True))
    check_loop_config(TOY)
    check_loop_config(dataclasses.replace(TOY, loop_steps=1))


def _engine(cfg, params, **gen):
    conf = dataclasses.replace(
        GenerateConfig(), decode_chunk=4, max_concurrent=2,
        **{"speculative_k": 0, "prefix_cache": False, **gen})
    return GenerateEngine(cfg, gen=conf, params=params, use_flash=False)


@pytest.mark.parametrize("gen, said", [
    ({"prefix_cache": True}, "generate.prefix_cache"),
    ({"speculative_k": 4}, "generate.speculative_k"),
])
def test_the_batcher_refuses_by_name_what_the_loop_does_not_serve(
        params, gen, said):
    from docqa_tpu.engines.serve import ContinuousBatcher

    with pytest.raises(ValueError, match=said):
        ContinuousBatcher(_engine(TOY, params, **gen), n_slots=2, chunk=4,
                          cache_len=256, kv_block_size=16)


def test_a_warm_prefill_is_refused(params):
    pools = paged.init_paged_pools(TOY, 32, BS)
    z = jnp.zeros((128,), jnp.int32)
    with pytest.raises(NotImplementedError, match="prefix_cache"):
        paged.ragged_prefill_forward(
            params, TOY, pools, z, z, z, z, jnp.zeros((2,), jnp.int32),
            rope_len=CAP, block_tables=jnp.zeros((2, 16), jnp.int32),
            prefix_lens=jnp.zeros((2,), jnp.int32), n_prefix_rows=CAP,
            block_size=BS)


def test_the_solo_engine_refuses_the_loop_by_field(params):
    engine = _engine(TOY, params)
    with pytest.raises(NotImplementedError, match="loop_steps"):
        engine.generate_ids([[5, 6, 7]], max_new_tokens=2)
    with pytest.raises(NotImplementedError, match="loop_steps"):
        decoder.decoder_forward(
            params, TOY, jnp.zeros((1, 4), jnp.int32),
            {"k0": jnp.zeros((1, 8, 4, 16))}, jnp.zeros((1,), jnp.int32))
    # the sandwich norms alone are the shared trunk's: the solo engine
    # runs them
    once = dataclasses.replace(TOY, loop_steps=1)
    solo = _engine(once, PACKAGE.weights.make_decoder_params(once, 3))
    assert len(solo.generate_ids([[5, 6, 7]], max_new_tokens=2)[0]) == 2


# ---- (g) through the batcher -----------------------------------------------------

COUNTERS = ("serve_loop_passes", "serve_loop_lane_steps",
            "serve_decode_kv_rows_read", "serve_decode_kv_rows_live")


def _counters():
    from docqa_tpu.runtime.metrics import DEFAULT_REGISTRY

    return {n: DEFAULT_REGISTRY.counter(n).value for n in COUNTERS}


def _serve(cfg, params, prompts, n_slots=2):
    from docqa_tpu.engines.serve import ContinuousBatcher

    before = _counters()
    b = ContinuousBatcher(_engine(cfg, params), n_slots=n_slots, chunk=4,
                          cache_len=256, kv_block_size=16, prefix_cache=False)
    try:
        occupancy = b.kv_block_occupancy()
        attrs = b._block.span_attrs
        got = [list(h.result(timeout=600)) for h in
               [b.submit_ids(p, max_new_tokens=9) for p in prompts]]
        in_use, free = b._alloc.blocks_in_use, b._alloc.n_free
        n_blocks = b.n_blocks
    finally:
        b.stop()
    gained = {k: v - before[k] for k, v in _counters().items()}
    return got, gained, occupancy, attrs, (in_use, free, n_blocks)


PROMPTS = [[5 + (7 * i + j) % 250 for j in range(90 - 20 * i)]
           for i in range(3)]


def test_the_counters_the_gauge_and_the_attributes_of_a_looped_run():
    served_params = PACKAGE.weights.make_decoder_params(BF16, 3)
    got, gained, occupancy, attrs, (in_use, free, n_blocks) = _serve(
        BF16, served_params, PROMPTS)
    assert all(len(g) == 9 for g in got)
    steps = gained["serve_loop_lane_steps"]
    assert steps >= sum(len(g) - 1 for g in got) > 0
    assert gained["serve_loop_passes"] == 4 * steps  # exactly loop_steps
    # rows are counted per cache ENTRY: the ratio stays a ratio
    assert gained["serve_decode_kv_rows_read"] >= (
        gained["serve_decode_kv_rows_live"]) > 0
    assert occupancy["loop_steps"] == 4  # gauge serve_loop_steps
    assert occupancy["bytes_per_token"] == paged.kv_bytes_per_token(BF16) == (
        2 * 4 * 16 * 2 * (4 * 3))  # gauge serve_kv_bytes_per_token
    assert occupancy["pool_bytes"] == n_blocks * 16 * 3072
    assert attrs == {"loop_steps": 4}
    # retirement frees a block once, for all four ranges
    assert (in_use, free) == (0, n_blocks)
    # three lanes through two slots: the third reuses blocks a retired
    # lane left in all four ranges, and decodes what it decodes alone
    alone, *_ = _serve(BF16, served_params, PROMPTS[2:], n_slots=1)
    assert alone[0] == got[2]


def test_a_plain_trunk_counts_no_pass_and_reports_no_gauge():
    package = arch.load({"architecture": "mistral"})
    cfg = dataclasses.replace(MISTRAL_TOY, dtype="bfloat16")
    got, gained, occupancy, attrs, _ = _serve(
        cfg, package.weights.make_decoder_params(cfg, 3), PROMPTS[:2])
    assert all(len(g) == 9 for g in got)
    assert gained["serve_loop_passes"] == gained["serve_loop_lane_steps"] == 0
    assert "loop_steps" not in occupancy and attrs == {}
    assert gained["serve_decode_kv_rows_live"] > 0


@pytest.mark.parametrize("occupancy, recorded", [
    ({"bytes_per_token": 3072, "loop_steps": 4}, 4.0),
    ({"bytes_per_token": 768}, None),
])
def test_the_telemetry_records_the_gauge_only_under_the_loop(
        occupancy, recorded):
    from docqa_tpu.obs.telemetry import TelemetrySampler

    gauges = {}
    sampler = types.SimpleNamespace(
        batcher=types.SimpleNamespace(
            n_queued=0, n_active=0, kv_block_occupancy=lambda: occupancy),
        store=types.SimpleNamespace(
            record_gauge=lambda name, value, now=None: gauges.update(
                {name: value})))
    TelemetrySampler._scrape_batcher(sampler, 0.0)
    assert gauges.get("serve_loop_steps") == recorded
    assert gauges["serve_kv_bytes_per_token"] == occupancy["bytes_per_token"]


# ---- (h) the audits --------------------------------------------------------------

@pytest.mark.parametrize("mesh_name", ["1x1", "2x4", "1x8"])
@pytest.mark.parametrize("program", ["loop_paged_decode",
                                     "loop_ragged_prefill"])
def test_the_looped_programs_lower_on_the_three_meshes(program, mesh_name):
    """Under the Megatron layout, the pools' kv heads over ``model``: one
    all-reduce a Megatron block in the text (the loop holds each once),
    nothing else — and what the budget file says."""
    import json

    from docqa_tpu.analysis import shard_audit

    counts, meta = shard_audit._AUDITS[program](mesh_name)
    tp = shard_audit.MESH_SHAPES[mesh_name][1] > 1
    assert meta["loop_steps"] == 4 and meta["megatron_blocks"] == 4
    assert counts["all-reduce"] == (4 if tp else 0)
    for op in ("all-gather", "all-to-all", "collective-permute",
               "reduce-scatter"):
        assert counts[op] == 0, op
    with open(os.path.join(ROOT, "shard_budget.json"), encoding="utf-8") as f:
        budget = json.load(f)["programs"][program]
    assert budget["meta"] == {
        k: v for k, v in meta.items() if k != "model_parallel"}
    assert {k: v for k, v in budget["per_mesh"][mesh_name].items()
            if k != "model_parallel"} == counts


def test_the_new_tree_entries_are_replicated_and_the_pools_keep_their_heads():
    from jax.sharding import PartitionSpec as P

    from docqa_tpu.parallel.sharding import decoder_param_pspecs

    specs = decoder_param_pspecs(TOY, "model")
    assert set(specs) == {n for n, *_ in decoder_param_schema(TOY)}
    for name in ("l0_attn_post_norm_g", "l2_mlp_post_norm_g", "exit_gate_b"):
        assert specs[name] == P(None)
    assert specs["exit_gate_w"] == P(None, None)
    assert set(decoder_param_pspecs(MISTRAL_TOY, "model")) == {
        n for n, *_ in decoder_param_schema(MISTRAL_TOY)}


def test_the_compile_budget_holds_the_looped_workload():
    import json

    with open(os.path.join(ROOT, "compile_budget.json"),
              encoding="utf-8") as f:
        workload = json.load(f)["workloads"]["serve_loop"]
    assert set(workload["roots"]) == {
        "serve_loop_decode", "serve_loop_prefill"}  # no warm family
    for root in workload["roots"].values():
        assert root["compiles"] == 1 and root["steady_state_retraces"] == 0
    assert workload["meta"]["prefix_cache"] is False
