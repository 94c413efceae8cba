"""The retention mixer's one-pass decode kernel (ISSUE 52):
``ops/retention.power_retention_step_fused`` fetches a tile of an owned
entry's state once, advances it, reads it while it is in fast memory and
writes it back where it came from.

* in interpret mode against the XLA form (``ops/attention.
  power_retention_step``): head widths 8, 16 and the published 128, 2 and
  8 kv heads, 1-5 query heads a kv head, gates from -0.36 to -0.0002; an
  entry's last block short of a whole one;
* the entries no live lane owns keep their bits: one live lane of four,
  none, all, the owned entries in any order;
* the last register column, half full at the published width (8,256 =
  64.5 x 128), by hand;
* who chooses it (``models/decoder.kernel_forms``'s ``retention``) and what
  the batcher counts by that choice.
"""

import dataclasses
import importlib
import types

import jax.numpy as jnp
import numpy as np
import pytest

from docqa_tpu.config import DecoderConfig, GenerateConfig
from docqa_tpu.models import hybrid
from docqa_tpu.models.decoder import block_serving, kernel_forms

# ``docqa_tpu.ops`` re-exports a FUNCTION named ``attention``
A = importlib.import_module("docqa_tpu.ops.attention")
R = importlib.import_module("docqa_tpu.ops.retention")
F32 = jnp.float32


def _draw(entries, heads, kv_heads, d, seed, dtype=F32):
    """Unit-norm q and k (the layer's qk-norm), gates over the range the
    seeded weights give, a state of random entries."""
    rng = np.random.default_rng(seed)

    def unit(*shape):
        x = rng.standard_normal(shape, np.float32)
        return x / np.sqrt((x * x).mean(-1, keepdims=True))

    gates = -np.geomspace(0.0002, 0.36, entries * kv_heads)
    return (
        jnp.asarray(unit(entries, heads, d), dtype),
        jnp.asarray(unit(entries, kv_heads, d), dtype),
        jnp.asarray(rng.standard_normal((entries, kv_heads, d)), dtype),
        jnp.asarray(rng.permutation(gates).reshape(entries, kv_heads), F32),
        jnp.asarray(rng.standard_normal(
            (entries, d + 1, kv_heads, A.power_feature_count(d))), F32),
    )


def _both(inputs, owned, count):
    """(the kernel's (out of the owned entries, pool), the XLA form's over
    the same entries: an unowned one handed zeros and a gate of 1, as the
    decode step hands it), float32, and who is owned."""
    q, k, v, gate, pool = inputs
    entries = q.shape[0]
    mask = np.zeros(entries, bool)
    mask[np.asarray(owned)[:count]] = True

    def of_owned(x):
        return jnp.where(
            jnp.asarray(mask).reshape((entries,) + (1,) * (x.ndim - 1)), x, 0)

    want = A.power_retention_step(
        of_owned(q), of_owned(k), of_owned(v), of_owned(gate), pool)
    got = R.power_retention_step_fused(
        q, k, v, gate, pool, jnp.asarray(owned, jnp.int32), jnp.int32(count),
        interpret=True)
    assert got[0].shape == want[0].shape and got[0].dtype == q.dtype
    assert got[1].shape == pool.shape and got[1].dtype == F32
    # the output rows of the OWNED entries (an unowned one's is whatever
    # the buffer held: the decode step reads its lanes' rows alone), the
    # whole pools
    return ([np.asarray(got[0], np.float32)[mask], np.asarray(got[1])],
            [np.asarray(want[0], np.float32)[mask], np.asarray(want[1])],
            mask)


def _rel(got, want):
    return np.abs(got - want).max() / np.abs(want).max()


@pytest.mark.parametrize("d, kv_heads, per", [
    (8, 2, 1), (8, 2, 4), (8, 8, 3), (16, 2, 5), (16, 8, 2), (128, 2, 5),
    (128, 8, 1),
], ids=lambda x: str(x))
def test_the_kernel_is_the_xla_form(d, kv_heads, per):
    entries = 3 if d < 128 else 2
    inputs = _draw(entries, per * kv_heads, kv_heads, d, seed=d + per)
    got, want, _ = _both(inputs, list(range(entries))[::-1], entries)
    # the update is the same three operations a value (a multiply-add
    # the compiler may fuse apart); the read sums the features in another
    # order
    assert _rel(got[1], want[1]) <= 1e-6
    assert _rel(got[0], want[0]) <= (1e-5 if d < 128 else 1e-4)
    assert np.abs(want[0]).max() > 0.1


def test_a_bfloat16_step_is_the_xla_forms_to_the_bit_of_its_rounding():
    """The served types: q, k, v bfloat16, the output in q's type."""
    inputs = _draw(4, 10, 2, 16, seed=5, dtype=jnp.bfloat16)
    got, want, _ = _both(inputs, [0, 1, 2, 3], 4)
    assert _rel(got[1], want[1]) <= 1e-6
    assert _rel(got[0], want[0]) <= 2.0 ** -7


@pytest.mark.parametrize("block_bytes, blocks", [
    (2 * 3 * 8 * 128 * 4, (3, 2)),  # 9 rows in blocks of 6: 6 + 3
    (4 * 3 * 8 * 128 * 4, (3, 3)),  # one block an entry
    (1, (3, 1)),  # a group a block
], ids=["a-short-last-block", "one-block", "a-group-a-block"])
def test_an_entrys_last_block_may_be_short(monkeypatch, block_bytes, blocks):
    monkeypatch.setattr(R, "RETENTION_BLOCK_BYTES", block_bytes)
    assert R.retention_row_blocks(4, 2, 8) == blocks
    inputs = _draw(4, 4, 2, 8, seed=9)
    got, want, mask = _both(inputs, [2, 0, 3, 1], 3)
    assert _rel(got[1], want[1]) <= 1e-6
    assert _rel(got[0], want[0]) <= 1e-5
    assert (got[1][~mask] == np.asarray(inputs[4])[~mask]).all()


@pytest.mark.parametrize("owned, count", [
    ([2, 0, 1, 3], 1), ([1, 3, 0, 2], 0), ([3, 3, 3, 3], 0),
    ([0, 1, 2, 3], 4), ([3, 1, 2, 0], 4), ([3, 0, 9, 9], 2),
], ids=["one-of-four", "none", "none-and-no-list", "all", "all-out-of-order",
        "two-out-of-order"])
def test_an_entry_no_live_lane_owns_keeps_its_bits(owned, count):
    inputs = _draw(4, 6, 2, 16, seed=11)
    got, want, mask = _both(inputs, owned, count)
    pool = np.asarray(inputs[4])
    assert mask.sum() == count
    # bit for bit what it was — the XLA form rewrites it under a gate of 1
    # (the same bits, another pass)
    assert (got[1][~mask] == pool[~mask]).all()
    assert got[0].shape[0] == count
    if count:
        assert (got[1][mask] != pool[mask]).any()
        assert _rel(got[1][mask], want[1][mask]) <= 1e-6
        assert _rel(got[0], want[0]) <= 1e-5


def test_an_owned_entry_is_stepped_wherever_the_list_names_it():
    """The same step under every order of the list: each entry is handed
    its own token whatever the grid step that reaches it."""
    inputs = _draw(4, 4, 2, 8, seed=13)
    first, _, _ = _both(inputs, [0, 1, 2, 3], 3)
    for owned in ([2, 1, 0, 3], [1, 2, 0, 0], [2, 0, 1, 1]):
        again, _, _ = _both(inputs, owned, 3)
        assert (again[0] == first[0]).all() and (again[1] == first[1]).all()


def test_the_half_full_last_column_adds_nothing_by_hand():
    """At the published width the features end half way through a 128-lane
    register (8,256 = 64 x 128 + 64).  A state that is zero but for ONE
    feature reads that feature's product and nothing else: the last one
    (8,255), the first of the short column (8,192), the last of the whole
    ones (8,191)."""
    d, kv_heads, per = 128, 2, 2
    features = A.power_feature_count(d)
    assert features == 8256 == 64 * 128 + 64
    q, k, v, gate, _ = _draw(1, per * kv_heads, kv_heads, d, seed=17)
    k, v = jnp.zeros_like(k), jnp.zeros_like(v)  # the step adds nothing
    phi_q = np.asarray(A.power_features(q.reshape(1, kv_heads, per, d)))
    decay = np.exp(np.asarray(gate))[0]  # [kv heads]
    for f in (features - 1, 8192, 8191):
        pool = np.zeros((1, d + 1, kv_heads, features), np.float32)
        pool[0, 5, :, f] = 3.0  # value channel 5
        pool[0, d, :, f] = 2.0  # the running sum of weights
        out, new = R.power_retention_step_fused(
            q, k, v, gate, jnp.asarray(pool), jnp.zeros((1,), jnp.int32),
            jnp.int32(1), interpret=True)
        want_state = pool * decay[None, None, :, None]
        # the row of weights also takes the key's own weight, phi(0) = 0
        np.testing.assert_allclose(np.asarray(new), want_state, rtol=1e-6)
        out = np.asarray(out).reshape(kv_heads, per, d)
        weight = decay[:, None] * phi_q[0, :, :, f]  # [kv heads, per]
        want = np.zeros((kv_heads, per, d), np.float32)
        want[:, :, 5] = 3.0 * weight / (2.0 * weight + A.RETENTION_EPS)
        np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-7)
        assert np.abs(want[:, :, 5]).min() > 1e-3, "a feature q does not see"


# ---- who chooses it ---------------------------------------------------------

@pytest.fixture
def fused_calls(monkeypatch):
    """``power_retention_step_fused`` as the forwards call it under
    ``use_flash``, interpreted (the one thing a CPU cannot take from it);
    the list holds the positional arguments of each call."""
    calls = []
    real = R.power_retention_step_fused

    def interpreted(*args, **kw):
        calls.append(len(args))
        return real(*args, **{**kw, "interpret": True})

    monkeypatch.setattr(R, "power_retention_step_fused", interpreted)
    return calls


TOY = DecoderConfig(
    vocab_size=256, hidden_dim=64, num_layers=2, num_heads=4, num_kv_heads=2,
    head_dim=16, mlp_dim=128, max_seq_len=256, norm_eps=1e-6,
    rope_theta=1e6, block="sparse_linear", dtype="bfloat16",
    mixer_types=("retention",) * 2, qk_norm=True, use_output_gate=False,
    use_output_norm=False, tie_embeddings=False,
)


# the published widths: a head of whole 128-lane registers
WIDE = dataclasses.replace(
    TOY, hidden_dim=5120, num_heads=40, num_kv_heads=8, head_dim=128)
MESH = types.SimpleNamespace(n_devices=4, n_model=4)


@pytest.mark.parametrize("change, kw, chosen", [
    ({}, {}, True),
    ({}, {"on_tpu": False}, False),  # a CPU
    ({}, {"mesh": MESH}, False),  # the state is replicated there
    ({"head_dim": 1024}, {}, False),  # a step's blocks outgrow fast memory
    ({"num_heads": 64, "head_dim": 512}, {}, False),
    ({"mixer_types": ("attention",) * 2}, {}, False),  # no such layer
    ({"head_dim": 16}, {}, False),  # no whole register a head
    ({"num_heads": 8, "num_kv_heads": 2}, {}, True),
], ids=["alone", "a-cpu", "a-mesh", "a-head-too-wide", "too-many-query-heads",
        "no-retention-layer", "a-narrow-head", "two-kv-heads"])
def test_kernel_forms_sixth_answer(change, kw, chosen):
    cfg = dataclasses.replace(WIDE, **change)
    args = {"on_tpu": True, "mesh": None, "block_size": 16, **kw}
    assert kernel_forms(cfg, **args).retention is chosen
    # a prefill is asked with no page: the same answer (the step's form
    # asks nothing of the pools' pages)
    assert kernel_forms(cfg, **{**args, "block_size": None}
                        ).retention is chosen


def test_the_support_function_refuses_by_shape():
    ok = R.retention_kernel_supported
    assert ok(40, 8, 128) and ok(16, 8, 256) and ok(8, 2, 128) and ok(4, 1, 128)
    assert not ok(40, 8, 127), "an odd head has no pairs half apart"
    # the kernel's loops slice the features by whole 128-lane registers
    assert not (ok(16, 8, 64) or ok(4, 2, 16) or ok(40, 8, 192))
    assert not ok(40, 7, 128), "query heads in whole groups of a kv head"
    assert not ok(40, 0, 128)
    assert not ok(8, 8, 1024), "a block of one group outgrows fast memory"
    # at the published widths: three value rows share the features'
    # registers (15 running sums of 64), six a grid step (1.6 MB)
    assert R.retention_row_blocks(40, 8, 128) == (3, 2)
    assert R._tile_bytes(8, 8256) == 8 * 65 * 128 * 4
    assert R._vmem_bytes(40, 8, 128) < 16 << 20
    # a geometry refused is refused by the op too, unless interpreted
    q, k, v, gate, _ = _draw(1, 8, 8, 8, seed=1)
    with pytest.raises(ValueError, match="pool"):
        R.power_retention_step_fused(
            q, k, v, gate, jnp.zeros((1, 9, 8, 36), jnp.bfloat16),
            jnp.zeros((1,), jnp.int32), jnp.int32(1), interpret=True)
    with pytest.raises(NotImplementedError, match="retention_kernel_supp"):
        R.power_retention_step_fused(
            jnp.zeros((1, 7, 8)), jnp.zeros((1, 2, 8)), jnp.zeros((1, 2, 8)),
            jnp.zeros((1, 2)), jnp.zeros((1, 9, 2, 36)),
            jnp.zeros((1,), jnp.int32), jnp.int32(1))


def test_the_form_chosen_is_the_form_called(fused_calls):
    """``retention_decode_step`` under ``use_flash`` calls the kernel with
    the list; without, the XLA form, which is never handed it."""
    inputs = _draw(2, 4, 2, 8, seed=3)
    owned, count = jnp.asarray([1, 0], jnp.int32), jnp.int32(2)
    fused = R.retention_decode_step(*inputs, owned, count, use_flash=True)
    assert fused_calls == [7]
    plain = R.retention_decode_step(*inputs, None, None)
    assert fused_calls == [7]
    for a, b in zip(fused, plain):
        assert _rel(np.asarray(a), np.asarray(b)) <= 1e-5


def test_the_counter_counts_a_chunk_under_the_kernel_and_is_absent_without():
    block = block_serving(TOY)
    fused = kernel_forms(WIDE, on_tpu=True, mesh=None, block_size=16)
    xla = kernel_forms(WIDE, on_tpu=False, mesh=None, block_size=16)
    assert fused.retention and not any(xla)
    state = hybrid.lane_state_bytes(TOY)
    base = {"serve_state_lane_steps": 12,
            "serve_state_bytes_rw": 2 * state * 12}
    assert block.chunk_counts(lane_steps=12, row=None, kernels=xla) == (
        base, {})
    assert block.chunk_counts(lane_steps=12, row=None, kernels=fused) == (
        {**base, "serve_retention_fused_chunks": 1}, {})
    # a stack that keeps other states counts nothing of it
    other = dataclasses.replace(WIDE, mixer_types=("attention", "attention"))
    forms = kernel_forms(other, on_tpu=True, mesh=None, block_size=16)
    counts, _ = hybrid.hybrid_chunk_counts(
        other, lane_steps=4, row=None, kernels=forms)
    assert "serve_retention_fused_chunks" not in counts


def test_the_batcher_counts_the_chunks_that_stepped_in_the_kernel(
        fused_calls, monkeypatch):
    """An engine that saw a TPU (``use_flash``): every decode chunk's
    retention layers step in the kernel — interpreted here, so at a head
    of 16 the chip's kernel would refuse —, the counter over
    ``serve_decode_chunks`` reads 1.0; an engine that did not counts
    nothing; the tokens are the same until a near-tie tips."""
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for p in (root, os.path.join(root, "benchmark")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from harness import arch

    from docqa_tpu.engines.generate import GenerateEngine
    from docqa_tpu.engines.serve import ContinuousBatcher
    from docqa_tpu.runtime.metrics import DEFAULT_REGISTRY

    monkeypatch.setattr(
        importlib.import_module("docqa_tpu.models.decoder"),
        "retention_kernel_supported", lambda *geometry: True)
    params = arch.load({"architecture": "brumby"}).weights.make_decoder_params(
        TOY, 3)
    names = ("serve_decode_chunks", "serve_retention_fused_chunks",
             "serve_state_lane_steps")
    prompts = [[5 + (7 * i + j) % 250 for j in range(40 + 30 * i)]
               for i in range(3)]
    gen = dataclasses.replace(
        GenerateConfig(), speculative_k=0, prefix_cache=False, decode_chunk=4,
        max_concurrent=4)
    got, gained = {}, {}
    for flash in (True, False):
        before = {n: DEFAULT_REGISTRY.counter(n).value for n in names}
        engine = GenerateEngine(TOY, gen=gen, params=params, use_flash=flash)
        b = ContinuousBatcher(engine, n_slots=4, chunk=4, cache_len=256,
                              kv_block_size=16, prefix_cache=False)
        try:
            assert b._kernels.retention is flash
            assert not any(b._kernels._replace(retention=False))
            got[flash] = [list(h.result(timeout=600)) for h in
                          [b.submit_ids(p, max_new_tokens=9) for p in prompts]]
        finally:
            b.stop()
        gained[flash] = {
            n: DEFAULT_REGISTRY.counter(n).value - before[n] for n in names}
        chunks = gained[flash]["serve_decode_chunks"]
        assert chunks > 0
        assert gained[flash]["serve_retention_fused_chunks"] == (
            chunks if flash else 0)
    assert (gained[True]["serve_state_lane_steps"]
            == gained[False]["serve_state_lane_steps"] > 0)
    assert [len(t) for t in got[True]] == [9, 9, 9]
    same = [a == b for x, y in zip(got[True], got[False])
            for a, b in zip(x, y)]
    assert all(same[::9]) and sum(same) >= 24, (got, same)
