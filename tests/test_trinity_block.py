"""ISSUE 48: window and global attention layers as two mixer kinds of the
one stack (``models/hybrid.py``), a window layer's RING of pages a lane
(``engines/paged.py``, ``engines/serve.py``) and the routed feed-forward
of ``models/routed.py`` inside that stack — against the plain reference of
``benchmark/architectures/afmoe`` at toy widths on the CPU.

(a) the program against the reference, prefill then decode through the
    cache past the window, logits and routing record;
(b) the eight shares' parts, the shared expert counted once, sum to the
    uncut layer;
(c) a window layer's pools hold a lane no more than its ring over a lane
    that runs to five windows, and both allocators read zero after 1-4
    lanes were admitted and retired in every order;
(d) the schema's parameter count is ISSUE 48's arithmetic at the published
    widths (shapes only);
and the refusals, the record and the counters' arithmetic.
"""

import dataclasses
import itertools
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

from docqa_tpu.config import DecoderConfig, GenerateConfig, load_config  # noqa: E402
from docqa_tpu.engines import paged  # noqa: E402
from docqa_tpu.engines.generate import GenerateEngine  # noqa: E402
from docqa_tpu.models import hybrid, latent, routed  # noqa: E402
from docqa_tpu.models.decoder import (  # noqa: E402
    block_serving,
    decoder_param_schema,
    init_decoder_params,
    kernel_forms,
)
from docqa_tpu.models.serving import KernelForms  # noqa: E402
from docqa_tpu.ops.attention import ragged_prefill_attention  # noqa: E402
from harness import arch  # noqa: E402
from harness.child import program_overrides  # noqa: E402

PACKAGE = arch.load({"architecture": "afmoe"})
FILE = os.path.join(BENCH_DIR, "configs", "trinity-mini-ep8-bf16.json")
W, BS, CAP = 48, 16, 512  # window, page, positions a lane
# float32 so that program and reference differ by rounding order alone
TOY = DecoderConfig(
    vocab_size=256, hidden_dim=64, num_layers=6, num_heads=4, num_kv_heads=2,
    head_dim=16, mlp_dim=128, max_seq_len=CAP, norm_eps=1e-5,
    block="sparse_linear", dtype="float32",
    mixer_types=("window", "window", "attention") * 2, sliding_window=W,
    qk_norm=True, use_output_gate=True, use_output_norm=False,
    sandwich_norm=True, scale_emb=8.0,
    first_dense_layers=1, num_experts=16, experts_held=4,
    experts_held_start=4, experts_per_token=4, expert_dim=32,
    num_shared_experts=1, routed_scale=2.826, router_score="sigmoid",
    router_bias=True, router_norm=True,
)
RING = hybrid.ring_pages(TOY, BS)
XLA = KernelForms(False, False, False, False, False, False)


@pytest.fixture(scope="module")
def params():
    return PACKAGE.weights.make_decoder_params(TOY, 5)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(1).integers(5, 256, size=(2, CAP))


def run_program(cfg, params, tokens, lengths, steps, rows=256):
    """Prefill ``lengths[b]`` tokens of lane b in ONE packed dispatch of
    ``rows`` packed rows a lane, then ``steps`` teacher-forced decode
    steps: (logits [lanes, 1 + steps, vocab], record [routed layers,
    lanes, positions, k] (-1 where nothing was computed), pools)."""
    lanes = len(lengths)
    n_blocks = lanes * CAP // BS
    pools = paged.init_paged_pools(cfg, n_blocks, BS)
    t = rows * lanes
    ids = np.zeros(t, np.int32)
    seg = np.full(t, -1, np.int32)
    pos = np.zeros(t, np.int32)
    dest = np.full(t, n_blocks * BS, np.int32)
    last = np.zeros(lanes, np.int32)
    for b, n in enumerate(lengths):
        st = rows * b
        ids[st:st + n] = tokens[b, :n]
        seg[st:st + n] = b
        pos[st:st + n] = np.arange(n)
        dest[st:st + n] = b * CAP + np.arange(n)
        last[b] = st + n - 1
    logits, pools, taken = paged.ragged_prefill_forward(
        params, cfg, pools, *map(jnp.asarray, (ids, seg, pos, dest, last)),
        rope_len=CAP, kernels=XLA)
    got = [np.asarray(logits)[:, None]]
    record = np.full((taken.shape[0], lanes, CAP, taken.shape[-1]), -1)
    for b, n in enumerate(lengths):
        record[:, b, :n] = np.asarray(taken)[:, rows * b:rows * b + n]
    tables = jnp.arange(n_blocks, dtype=jnp.int32).reshape(lanes, -1)
    lens = np.asarray(lengths, np.int32)
    step = jax.jit(lambda pl, tok, ln: paged.paged_decode_forward(
        params, cfg, pl, tables, tok, ln, block_size=BS, rope_len=CAP,
        kernels=XLA))
    for _ in range(steps):
        tok = np.stack([tokens[b, lens[b]:lens[b] + 1] for b in range(lanes)])
        logits, pools, taken = step(pools, jnp.asarray(tok), jnp.asarray(lens))
        got.append(np.asarray(logits))
        for b in range(lanes):
            record[:, b, lens[b]] = np.asarray(taken)[:, b, 0]
        lens = lens + 1
    return np.concatenate(got, axis=1), record, pools


def reference(cfg, params, tokens, lengths, n_rows, routing=None):
    rows = np.asarray(lengths)[:, None] - 1 + np.arange(n_rows)[None, :]
    logits, gap, taken = PACKAGE.reference.forward_logits(
        params, cfg, jnp.asarray(tokens), jnp.asarray(rows.astype(np.int32)),
        routing=routing)
    return np.asarray(logits), np.asarray(gap), np.asarray(taken)


def rel_err(got, want):
    centred = want - want.mean(-1, keepdims=True)
    return (np.linalg.norm(got - want, axis=-1)
            / np.linalg.norm(centred, axis=-1)).max()


# ---- (a) the program against the reference -----------------------------------

LENGTHS, STEPS = [150, 97], 9  # past window + 2 pages; 9 steps = 2+ chunks of 4


@pytest.fixture(scope="module")
def served(params, tokens):
    return run_program(TOY, params, tokens, LENGTHS, STEPS)


def test_prefill_then_decode_past_the_window_match_the_reference(
        params, tokens, served):
    got, record, _ = served
    assert min(LENGTHS) > W + 2 * BS
    want, gap, own = reference(TOY, params, tokens, LENGTHS, 1 + STEPS)
    assert rel_err(got, want) < 2e-4
    # the routing record: every computed decision is the reference's own
    held = record[..., 0] >= 0
    assert held.sum() == 5 * (sum(LENGTHS) + 2 * STEPS)
    assert (np.sort(record[held], -1) == np.sort(own[held], -1)).mean() > 0.999
    # replayed, the reference computes with the record and its gap is rounding
    again, gap, taken = reference(
        TOY, params, tokens, LENGTHS, 1 + STEPS, routing=record)
    assert rel_err(got, again) < 2e-4
    assert gap[held].max() < 1e-5
    assert (taken[held] == record[held]).all()


def test_the_window_binds_and_the_global_layers_see_every_row(
        params, tokens, served):
    """A window as long as the lane, or rotation in the global layers, is
    another model: the agreement above is no accident of short prompts."""
    got, _, _ = served
    wide = dataclasses.replace(TOY, sliding_window=CAP)
    assert rel_err(got, reference(wide, params, tokens, LENGTHS, 1 + STEPS)[0]
                   ) > 1e-2
    turned = dataclasses.replace(
        TOY, mixer_types=("window",) * 6, sliding_window=CAP)
    assert rel_err(got, reference(turned, params, tokens, LENGTHS,
                                  1 + STEPS)[0]) > 1e-2


def test_a_decode_step_continues_the_prefill(params, tokens):
    stepped, _, _ = run_program(TOY, params, tokens[:1], [130], 1)
    longer, _, _ = run_program(TOY, params, tokens[:1], [131], 0)
    assert np.abs(stepped[0, 1] - longer[0, 0]).max() < 2e-4


@pytest.mark.parametrize("window, max_segment", [
    (48, None), (None, 256), (48, 256), (None, None), (300, 256)])
def test_the_grouped_prefill_attention_is_the_general_form(
        window, max_segment):
    """K and V at their kv heads, the key blocks out of reach skipped: the
    same numbers as the form that repeats them and multiplies them all."""
    rng = np.random.default_rng(3)
    t, lanes = 1024, 4
    q = jnp.asarray(rng.standard_normal((t, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((t, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((t, 2, 16)), jnp.float32)
    seg = np.full(t, -1, np.int32)
    pos = np.zeros(t, np.int32)
    for b, n in enumerate((256, 130, 7, 201)):
        seg[256 * b:256 * b + n] = b
        pos[256 * b:256 * b + n] = np.arange(n)
    args = (q, k, v, jnp.asarray(seg), jnp.asarray(pos))
    plain = ragged_prefill_attention(*args, sliding_window=window)
    grouped = ragged_prefill_attention(
        *args, sliding_window=window, grouped_heads=True,
        max_segment=max_segment)
    assert np.abs(np.asarray(plain) - np.asarray(grouped)).max() < 1e-5


# ---- (b) the shares' parts sum to the uncut layer ----------------------------

def test_the_shares_parts_sum_to_the_uncut_layer():
    whole = dataclasses.replace(TOY, experts_held=0, experts_held_start=0)
    full = init_decoder_params(jax.random.PRNGKey(2), whole)
    full["l1_router_bias"] = jnp.asarray(
        np.random.default_rng(4).normal(0, 0.1, 16), jnp.float32)
    y = jnp.asarray(np.random.default_rng(5).standard_normal((40, 64)),
                    jnp.float32)
    uncut, taken = routed.routed_mlp(y, full, whole, 1)
    shared = routed._swiglu(y, full, "l1_s_gate", "l1_s_up", "l1_s_down")
    parts = jnp.zeros_like(uncut)
    for lo in range(0, 16, 2):  # eight shares of two experts
        share = dataclasses.replace(
            whole, experts_held=2, experts_held_start=lo)
        held = {**full, **{f"l1_e_{n}": full[f"l1_e_{n}"][lo:lo + 2]
                           for n in ("gate", "up", "down")}}
        part, taken_here = routed.routed_mlp(y, held, share, 1)
        assert (np.asarray(taken_here) == np.asarray(taken)).all()
        parts = parts + (part - shared)  # the shared expert counted once
    assert np.abs(np.asarray(parts + shared - uncut)).max() < 1e-4


def test_the_router_is_the_issues():
    """sigmoid scores, the bias for the choice alone, gates normalised
    over the k taken and scaled."""
    rng = np.random.default_rng(6)
    scores = jax.nn.sigmoid(jnp.asarray(rng.standard_normal((9, 16)),
                                        jnp.float32))
    bias = jnp.asarray(rng.normal(0, 0.5, 16), jnp.float32)
    taken, gates = routed.select_experts(scores, TOY, bias)
    want = np.argsort(-(np.asarray(scores) + np.asarray(bias)), -1)[:, :4]
    assert (np.sort(np.asarray(taken), -1) == np.sort(want, -1)).all()
    assert np.allclose(
        np.asarray(gates),
        np.take_along_axis(np.asarray(scores), np.asarray(taken), -1))
    bare, _ = routed.select_experts(scores, TOY)
    assert (np.sort(np.asarray(bare), -1) != np.sort(want, -1)).any()


def test_both_trunks_import_the_one_routed_module():
    for name in ("routed_mlp", "select_experts", "held_experts_sum",
                 "moe_step_sums", "moe_prefill_sums", "moe_chunk_counts",
                 "MOE_SUMS", "MOE_PREFILL_SUMS", "experts_held"):
        assert getattr(latent, name) is getattr(routed, name), name
    assert hybrid.routed_mlp is routed.routed_mlp


# ---- (c) the ring --------------------------------------------------------------

def test_a_lane_of_five_windows_holds_no_more_than_its_ring(params, tokens):
    """One lane runs from 40 to 5 x the window + in two-lane pools: the
    rows its window layers' pools hold never exceed the ring (window + a
    page), they all lie in the lane's own pages, and the last logits are
    the reference's."""
    n, steps = 40, 5 * W + 20 - 40
    got, _, pools = run_program(TOY, params, tokens, [n, 3], steps)
    assert RING * BS <= W + BS and RING == math.ceil((W - 1) / BS) + 1
    for i in hybrid.window_layers(TOY):
        pool = np.asarray(pools[f"k{i}"])
        assert pool.shape[0] == 2 * RING * BS  # lanes x ring: no more exists
        # both lanes wrapped: every row of each ring, and that is all
        assert (np.abs(pool).sum((1, 2)) > 0).all()
    for i in hybrid.layers_of(TOY, "attention"):
        assert pools[f"k{i}"].shape[0] == 2 * CAP  # a global layer: them all
    want, _, _ = reference(TOY, params, tokens[:1], [n], 1 + steps)
    assert rel_err(got[:1, -3:], want[:, -3:]) < 2e-4


def test_a_prefill_writes_a_window_layer_only_what_a_later_step_sees(
        params, tokens):
    _, _, pools = run_program(TOY, params, tokens[:1], [200], 0)
    i = hybrid.window_layers(TOY)[0]
    written = np.abs(np.asarray(pools[f"k{i}"])).sum((1, 2)) > 0
    assert written.sum() == W - 1  # positions 153..199, where they live
    for p in range(200):
        row = (p // BS) % RING * BS + p % BS
        assert written[row] == (p > 200 - W) or p <= 200 - W


ORDERS = [order for n in range(1, 5)
          for order in itertools.permutations(range(n))]


@pytest.mark.parametrize("order", ORDERS, ids=lambda o: "".join(map(str, o)))
def test_lanes_admitted_and_retired_in_every_order_leave_nothing(order):
    """The allocators' accounting as the batcher drives it: a ring's table
    hangs off the lane's block table, is taken all-or-nothing with it and
    released with it — exactly once, whoever releases."""
    n = len(order)
    blocks = paged.BlockAllocator(n * CAP // BS, BS)
    rings = paged.BlockAllocator(n * RING, BS)
    tables = []
    for lane in range(n):
        table = blocks.new_table()
        table.ring = rings.new_table()
        table.ensure(100 + 30 * lane)
        table.ring.ensure(RING * BS)
        tables.append(table)
    assert rings.blocks_in_use == n * RING and rings.n_free == 0
    late = blocks.new_table()
    late.ring = rings.new_table()
    with pytest.raises(paged.OutOfBlocks):  # no ring left: nothing taken
        late.ring.ensure(RING * BS)
    late.release()
    pages = [set(t.ring.blocks) for t in tables]
    assert all(len(p) == RING for p in pages)
    assert not set.intersection(*pages) or n == 1
    for done, lane in enumerate(order, 1):
        tables[lane].release()
        tables[lane].release()  # idempotent, for both
        assert rings.blocks_in_use == (n - done) * RING
    assert blocks.blocks_in_use == 0 and rings.blocks_in_use == 0
    assert rings.n_free == n * RING


BF16 = dataclasses.replace(TOY, dtype="bfloat16", max_seq_len=256)
COUNTERS = (
    "serve_window_kv_rows_read", "serve_window_kv_rows_held",
    "serve_window_kv_rows_live", "serve_moe_picks", "serve_moe_picks_local",
    "serve_moe_experts_touched", "serve_moe_layer_steps",
    "serve_moe_prefill_picks", "serve_moe_prefill_picks_local",
    "serve_decode_kv_rows_read", "serve_decode_kv_rows_live",
    "serve_prefill_tokens",
)


def _counters():
    from docqa_tpu.runtime.metrics import DEFAULT_REGISTRY

    return {n: DEFAULT_REGISTRY.counter(n).value for n in COUNTERS}


def test_the_batcher_serves_the_stack_and_both_allocators_read_zero():
    """Four prompts through two slots: each gives the tokens it gives alone,
    the rings are held while lanes live and returned when they retire, the
    counters count."""
    from docqa_tpu.engines.serve import ContinuousBatcher

    served_params = PACKAGE.weights.make_decoder_params(BF16, 5)
    gen = dataclasses.replace(
        GenerateConfig(), speculative_k=0, prefix_cache=False, decode_chunk=4,
        max_concurrent=2)
    prompts = [[5 + (7 * i + j) % 250 for j in range(150 - 35 * i)]
               for i in range(4)]

    def batcher():
        engine = GenerateEngine(
            BF16, gen=gen, params=served_params, use_flash=False)
        return ContinuousBatcher(
            engine, n_slots=2, chunk=4, cache_len=256, kv_block_size=16,
            prefix_cache=False)

    before = _counters()
    b = batcher()
    try:
        assert b._block.step_sum_names == routed.MOE_SUMS
        assert b._block.prefill_sum_names == routed.MOE_PREFILL_SUMS
        assert b._ring_pages == RING and b._ring_alloc.n_blocks == 2 * RING
        # positions over lanes, and what a position costs FOR GOOD
        assert b.kv_bytes_per_token == 2 * (2 * 2 * 16) * 2
        handles = [b.submit_ids(p, max_new_tokens=10) for p in prompts]
        got = [h.result(timeout=600) for h in handles]
        occupancy = b.kv_block_occupancy()
        assert occupancy["window_rows_total"] == 2 * RING * 16
        assert occupancy["window_rows_held"] == 0
        assert b._alloc.blocks_in_use == 0
        assert b._ring_alloc.blocks_in_use == 0
    finally:
        b.stop()
    gained = {k: v - before[k] for k, v in _counters().items()}
    assert gained["serve_prefill_tokens"] == sum(map(len, prompts))
    assert gained["serve_moe_prefill_picks"] == 5 * 4 * sum(map(len, prompts))
    assert 0 < gained["serve_moe_prefill_picks_local"] < (
        gained["serve_moe_prefill_picks"])
    assert 0 < gained["serve_moe_picks_local"] < gained["serve_moe_picks"]
    assert gained["serve_moe_layer_steps"] > 0
    live = gained["serve_window_kv_rows_live"]
    assert live == gained["serve_decode_kv_rows_live"] > 0
    # a CPU: the XLA form gathers every table's span, in either kind
    assert gained["serve_window_kv_rows_read"] == (
        gained["serve_decode_kv_rows_read"]) >= live
    assert gained["serve_window_kv_rows_held"] % (RING * 16) == 0
    for prompt, toks in zip(prompts, got):
        alone = batcher()
        try:
            assert alone.submit_ids(prompt, max_new_tokens=10).result(
                timeout=600) == toks
        finally:
            alone.stop()
            assert alone._ring_alloc.blocks_in_use == 0


def test_the_counters_arithmetic_under_the_kernel():
    """What ``window_rows_read`` answers where the paged kernel runs: a
    window layer reads from the first 512-row block its window still sees,
    a global layer the live pages; the pools hold a ring a lane."""
    cfg = dataclasses.replace(TOY, sliding_window=2048, max_seq_len=9728)
    lens = np.asarray([[9200, 9201], [100, 101]])
    paged_forms = KernelForms(True, False, False, True, True, False)
    mean, counts = hybrid.window_rows_read(
        cfg, lens, kernels=paged_forms, block_size=16, table_rows=4 * 9728)
    first = (9200 - 2048) // 512 * 512
    in_window = (9200 - first) + (9216 - first) + 112 + 112
    in_global = 9200 + 9216 + 112 + 112
    assert counts == {
        "serve_window_kv_rows_read": in_window,
        "serve_window_kv_rows_held": 2 * 2 * 129 * 16,
        "serve_window_kv_rows_live": int(lens.sum()),
    }
    assert mean == (4 * in_window + 2 * in_global) // 6
    _, xla = hybrid.window_rows_read(
        cfg, lens, kernels=XLA, block_size=16, table_rows=4 * 9728)
    assert xla["serve_window_kv_rows_read"] == 2 * 4 * 9728


# ---- (d) the arithmetic at the published widths --------------------------------

def _published(**over):
    with open(FILE, encoding="utf-8") as f:
        conf = {**json.load(f), **over}
    return conf, load_config(
        env={}, overrides=program_overrides(conf)).decoder


@pytest.mark.parametrize("over, total", [
    ({}, 4_267_194_112),
    ({"num_experts": 128, "vocab_size": 200_192}, 26_123_974_400),
], ids=["this-chip", "published"])
def test_the_schema_counts_what_the_issue_counted(over, total):
    conf, cfg = _published(**over)
    shapes = {n: s for n, _k, s, _f in decoder_param_schema(cfg)}
    assert sum(math.prod(s) for s in shapes.values()) == total
    assert PACKAGE.shapes.parameters(conf) == total
    held = conf["num_experts"]
    assert shapes["l2_e_gate"] == (held, 2048, 1024)
    assert shapes["l0_w_gate"] == (2048, 6144) and "l0_router" not in shapes
    assert shapes["l2_router"] == (2048, 128)
    assert shapes["l2_router_bias"] == (128,)
    assert shapes["l31_w_ogate"] == (2048, 4096)
    layer = sum(math.prod(s) for n, s in shapes.items()
                if n.startswith("l5_"))
    assert layer == PACKAGE.shapes.routed_layer_params(conf) == (
        839_131_520 if over else 134_488_448)
    assert PACKAGE.shapes.attention_params(conf) == 27_271_424
    assert cfg.mixer_types == ("window", "window", "window", "attention") * 8
    assert (cfg.sliding_window, cfg.router_score, cfg.router_norm,
            cfg.router_bias, cfg.sandwich_norm) == (
        2048, "sigmoid", True, True, True)
    assert cfg.scale_emb == math.sqrt(2048) and cfg.routed_scale == 2.826


def test_the_served_pools_are_under_the_issues_bound():
    _, cfg = _published()
    pools = jax.eval_shape(
        lambda: paged.init_paged_pools(cfg, 4 * 9728 // 16, 16, n_lanes=4))
    total = sum(math.prod(a.shape) * a.dtype.itemsize
                for a in jax.tree.leaves(pools))
    assert total < 1.2e9  # an unfreed pool: 4 x 9,728 x 65,536 B = 2.55 GB
    assert hybrid.ring_pages(cfg, 16) * 16 == 2064 <= 2048 + 16
    assert paged.kv_bytes_per_token(cfg) == 8 * 2048
    assert pools["k0"].shape == (4 * 2064, 4, 128)
    assert pools["k3"].shape == (4 * 9728, 4, 128)
    assert pools[hybrid.WINDOW_PAGES].shape == (4, 129)


# ---- the record and the refusals -----------------------------------------------

def test_the_record_of_the_stack_that_routes_and_windows():
    block = block_serving(TOY)
    assert block.unserved == ("generate.prefix_cache",
                              "generate.speculative_k", "qos.preemption")
    assert block.uses_flash and block.lane_state
    assert block.ring_pages(16) == RING
    assert block.span_attrs == {
        "experts_held": 4, "window_layers": 4, "global_layers": 2}
    assert block.prefill_attrs(200, 2)["window_rows_kept"] == W - 1
    assert block.occupancy["window"] == W
    specs = block.param_pspecs("model")
    assert tuple(specs["l1_e_gate"]) == ("model", None, None)
    assert tuple(specs["l1_router_bias"]) == (None,)
    assert tuple(specs["l0_attn_post_norm_g"]) == (None,)
    assert hybrid.WINDOW_PAGES in block.pool_pspecs()
    forms = kernel_forms(TOY, on_tpu=True, mesh=None, block_size=16)
    assert not forms.paged  # a 16-wide head is not the kernel's
    _, cfg = _published()
    forms = kernel_forms(cfg, on_tpu=True, mesh=None, block_size=16)
    assert forms.paged and forms.grouped and forms.ragged
    # a stack without a window layer or a routed one is what it was
    plain = block_serving(dataclasses.replace(
        TOY, mixer_types=("attention",) * 6, sliding_window=None,
        num_experts=0))
    assert plain.ring_pages is None and plain.span_attrs == {}
    assert plain.step_sum_names == () and plain.kv_rows_read is None


@pytest.mark.parametrize("change, said", [
    (dict(sliding_window=None), "sliding_window"),
    (dict(mixer_types=("attention",) * 6), "sliding_window"),
    (dict(router_score="tanh"), "router_score"),
    (dict(experts_per_token=0), "experts_per_token"),
    (dict(experts_held_start=14), "experts held"),
    (dict(quantize_weights=True), "quantize_weights"),
    (dict(mixer_types=("window", "sparse") * 3), "sparse layer"),
    (dict(head_dim=15), "head_dim"),
])
def test_a_configuration_the_stack_cannot_run_is_refused_by_field(
        change, said):
    with pytest.raises(ValueError, match=said):
        hybrid.check_hybrid_config(dataclasses.replace(TOY, **change))


def test_the_tree_the_package_draws_is_the_schemas(params):
    schema = {n: (s, k) for n, k, s, _f in decoder_param_schema(TOY)}
    assert set(params) == set(schema)
    for name, (shape, kind) in schema.items():
        assert params[name].shape == shape, name
        want = jnp.float32 if kind == "zeros_f32" else jnp.dtype(TOY.dtype)
        assert params[name].dtype == want, name
    # every share holds the same bias values, in an order of its own
    bias = np.sort(np.asarray(params["l1_router_bias"]).reshape(4, 4), -1)
    assert (bias == bias[0]).all() and np.abs(bias.sum(-1)).max() < 1e-5
    assert abs(bias.std() - PACKAGE.weights.EXPERT_BIAS_STD) < 1e-3
    router = np.asarray(params["l3_router"], np.float32)
    assert np.abs(router.sum(-1)).max() < 1e-5  # level
    assert np.abs(np.linalg.norm(router, axis=0) - 1).max() < 0.05
