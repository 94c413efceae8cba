"""The ragged prefill kernel (ISSUE 49): ``ragged_flash_prefill`` attends a
COLD packed dispatch, visiting only the key blocks a query block can see.

* in interpret mode against the form it replaces
  (``ragged_prefill_attention``'s XLA forms): the four served head
  geometries x packings, padding rows exactly zero;
* the block-range arithmetic against the mask itself: every block with a
  live pair is visited, none without one is multiplied;
* who chooses it (``models/decoder.kernel_forms``'s ``ragged``) and what
  the batcher counts by it.

It compiles for a described v5e at the served widths in
``tests/test_paged_kernel.py`` (the one topology fixture).
"""

import dataclasses
import importlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from docqa_tpu.config import DecoderConfig
from docqa_tpu.models.decoder import (
    kernel_forms,
    packed_attention_layers,
    ragged_prefill_counts,
)

# ``docqa_tpu.ops`` re-exports a FUNCTION named ``attention``
A = importlib.import_module("docqa_tpu.ops.attention")

TOL = 2.0 ** -6  # two bf16 roundings of an O(1) output, as kernel_selfcheck
ALIGN = A.RAGGED_ALIGN


def _pack(t, lens):
    """``seg_ids``, ``positions`` of segments at aligned starts, in order."""
    seg, pos, row = np.full(t, -1, np.int32), np.zeros(t, np.int32), 0
    for lane, n in enumerate(lens):
        seg[row: row + n], pos[row: row + n] = lane, np.arange(n)
        row += -(-n // ALIGN) * ALIGN
    assert row <= t
    return seg, pos


# (query heads, kv heads, window): Ouro, Mistral, Trinity's window layers,
# Jamba2 — and Trinity's global layers
GEOMETRIES = {
    "16/16": (16, 16, None),
    "32/8": (32, 8, None),
    "32/4-window": (32, 4, 256),
    "32/4": (32, 4, None),
    "20/1": (20, 1, None),
}
# (packed rows, segment lengths): a window of 256 stands for the served
# 2,048, so "five windows long" is 1,300 rows
PACKINGS = {
    "one-segment": (512, [323]),
    "ragged-four-and-a-tail": (1024, [100, 300, 17, 129]),
    "short-and-five-windows": (2560, [100, 1300, 600]),
    "full": (512, [512]),
}


@pytest.mark.parametrize("packing", PACKINGS)
@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_the_kernel_is_the_xla_form(geometry, packing):
    hq, hkv, window = GEOMETRIES[geometry]
    t, lens = PACKINGS[packing]
    rng = np.random.default_rng(len(geometry) * 131 + t)
    q, k, v = (
        jnp.asarray(rng.standard_normal((t, h, 128), np.float32),
                    jnp.bfloat16) for h in (hq, hkv, hkv))
    seg, pos = _pack(t, lens)
    args = (q, k, v, jnp.asarray(seg), jnp.asarray(pos))
    got = A.ragged_flash_prefill(
        *args, sliding_window=window, max_segment=2048, interpret=True)
    assert got.shape == q.shape and got.dtype == q.dtype
    got = np.asarray(got, np.float32)
    for grouped in (False, True):  # the general form, and the one served
        want = A.ragged_prefill_attention(
            *args, sliding_window=window, grouped_heads=grouped,
            max_segment=2048)
        assert np.max(np.abs(got - np.asarray(want, np.float32))) <= TOL
    assert not got[seg < 0].any()  # exact zeros, not small numbers
    assert np.abs(got[seg >= 0]).max() > 0.1


def test_a_dispatch_of_padding_reads_zero():
    """No query block sees a key block: nothing is multiplied and every
    row is an exact zero (a live row always sees itself, so padding is
    the one way a row has no key)."""
    t = 256
    rng = np.random.default_rng(5)
    q, k, v = (jnp.asarray(rng.standard_normal((t, 2, 128), np.float32),
                           jnp.bfloat16) for _ in range(3))
    seg, pos = _pack(t, [])
    got = A.ragged_flash_prefill(
        q, k, v, jnp.asarray(seg), jnp.asarray(pos), interpret=True)
    assert not np.asarray(got, np.float32).any()


def _live_pairs(seg, pos, window):
    """The general form's mask, whole: [T, T] bool."""
    mask = (seg[:, None] == seg[None, :]) & (seg >= 0)[:, None] & (
        pos[None, :] <= pos[:, None])
    if window is not None:
        mask &= pos[None, :] > pos[:, None] - window
    return mask


@pytest.mark.parametrize("window", [None, 128, 200, 1000])
@pytest.mark.parametrize("t, lens", [
    (512, [323]), (512, [512]), (512, []), (1024, [1, 128, 129, 300]),
    (2560, [100, 1300, 600]), (2560, [2560]), (2560, [700, 1, 1, 1, 900]),
    (1536, [384, 384, 384, 384]),
])
def test_the_block_range_is_the_masks_own(t, lens, window):
    """Exhaustively, for every key block size the kernel can pick: a
    (query block, key block) pair holds a live pair of the mask exactly
    when the key block lies in the query block's range — and the static
    innermost extent holds every range."""
    seg, pos = _pack(t, lens)
    mask = _live_pairs(seg, pos, window)
    longest = max(lens, default=1)
    for bk in A.RAGGED_KEY_BLOCKS:
        if t % bk:
            continue
        first, count = A.ragged_key_blocks(seg, pos, window, bk, np)
        live = mask.reshape(t // ALIGN, ALIGN, t // bk, bk).any(axis=(1, 3))
        blocks = np.arange(t // bk)[None, :]
        visited = (blocks >= first[:, None]) & (
            blocks < (first + count)[:, None])
        assert (visited == live).all(), bk
        steps = A.ragged_key_steps(t, window, longest, bk)
        assert count.max(initial=0) <= steps <= t // bk
    # the same arithmetic in the program
    bk = A.ragged_key_block_rows(t)
    traced = jax.jit(
        lambda s, p: A.ragged_key_blocks(s, p, window, bk))(seg, pos)
    for ours, theirs in zip(
            A.ragged_key_blocks(seg, pos, window, bk, np), traced):
        assert (ours == np.asarray(theirs)).all()


def test_the_blocks_are_read_from_the_shapes():
    assert A.ragged_key_block_rows(9728) == 512
    assert A.ragged_key_block_rows(768) == 256
    assert A.ragged_key_block_rows(384) == 128
    with pytest.raises(ValueError, match="whole"):
        A.ragged_key_block_rows(200)
    # one long prompt in a global layer: the whole axis; a window layer:
    # the window and a block on either side; many lanes on a long axis:
    # no more than the longest segment there can be
    assert A.ragged_key_steps(9728, None, 9856, 512) == 19
    assert A.ragged_key_steps(9728, 2048, 9856, 512) == 6
    assert A.ragged_key_steps(37888, None, 9856, 512) == 21


# ---- who chooses it ---------------------------------------------------------

GQA = DecoderConfig(num_heads=32, num_kv_heads=8, head_dim=128)
STACK = dataclasses.replace(
    GQA, block="sparse_linear", num_layers=4, num_kv_heads=4,
    sliding_window=2048,
    mixer_types=("window", "window", "attention", "mamba"))
MESH = types.SimpleNamespace(n_devices=4, n_model=4)


@pytest.mark.parametrize("cfg, change, kw, chosen", [
    (GQA, {}, {}, True),
    (GQA, {}, dict(on_tpu=False), False),  # a CPU
    (GQA, {}, dict(mesh=MESH), False),  # GSPMD places the XLA forms
    (GQA, dict(head_dim=64), {}, False),  # half a register of lanes
    (GQA, dict(head_dim=256), {}, True),
    (GQA, dict(loop_steps=4), {}, True),  # the looped trunk
    (GQA, dict(sliding_window=4096), {}, True),
    (GQA, {}, dict(block_size=None), True),  # a prefill names no page
    (STACK, {}, {}, True),
    (STACK, {}, dict(mesh=MESH), False),
    (STACK, dict(mixer_types=("mamba",) * 4), {}, False),  # nothing attends
    (STACK, dict(mixer_types=("sparse", "linear") * 2, sparse_block_size=64),
     {}, False),  # a layer that selects attends in its own form
    (GQA, dict(block="mla_moe", num_kv_heads=1, num_experts=8,
               first_dense_layers=1), {}, False),  # latent: 192-wide keys
])
def test_kernel_forms_fifth_answer(cfg, change, kw, chosen):
    cfg = dataclasses.replace(cfg, **change)
    args = {**dict(on_tpu=True, mesh=None, block_size=16), **kw}
    assert kernel_forms(cfg, **args).ragged is chosen


def test_the_layers_that_attend_over_packed_rows():
    assert packed_attention_layers(GQA) == ((None, GQA.num_layers),)
    looped = dataclasses.replace(GQA, loop_steps=4, sliding_window=4096)
    assert packed_attention_layers(looped) == ((4096, 4 * GQA.num_layers),)
    assert packed_attention_layers(STACK) == ((None, 1), (2048, 2))
    assert packed_attention_layers(dataclasses.replace(
        STACK, mixer_types=("mamba",) * 4)) == ()


# ---- what the batcher counts ------------------------------------------------

def test_the_counters_arithmetic():
    """One 9,100-token prompt on a 9,728-row axis: a global layer visits
    about half the packed square, a window layer about a quarter."""
    seg, pos = _pack(9728, [9100])
    # 128-row query blocks x 512-row key blocks: block i sees i // 4 + 1
    glob = sum(i // 4 + 1 for i in range(72))  # 72 live of 76
    assert A.ragged_key_block_counts(seg, pos, None) == (glob, 76 * 19)
    seen, square = A.ragged_key_block_counts(seg, pos, 2048, 9856)
    assert square == 76 * 19 and 0.2 < seen / square < 0.3
    assert 0.45 < glob / square < 0.5
    counts = ragged_prefill_counts(
        STACK, [(seg, pos), (seg, pos)], max_segment=9856)
    assert counts == {
        "serve_prefill_attend_kernel_dispatches": 2,
        "serve_prefill_key_blocks_visited": 2 * (glob + 2 * seen),
        "serve_prefill_key_blocks_packed": 2 * 3 * square,
    }
    # a 512-row dispatch of one 323-token prompt: three of its four query
    # blocks are live, and each sees the one key block there is
    assert A.ragged_key_block_counts(*_pack(512, [323]), None) == (3, 4)
    assert ragged_prefill_counts(GQA, [], max_segment=512) == {
        "serve_prefill_attend_kernel_dispatches": 0,
        "serve_prefill_key_blocks_visited": 0,
        "serve_prefill_key_blocks_packed": 0,
    }


def test_a_warm_dispatch_stays_the_xla_form():
    """``use_flash`` with a cached prefix: the kernel is not entered (it
    would raise on a CPU) and the XLA warm form answers."""
    t, bs = 128, 16
    rng = np.random.default_rng(2)
    q, k, v = (jnp.asarray(rng.standard_normal((t, 2, 128), np.float32),
                           jnp.bfloat16) for _ in range(3))
    pool = jnp.asarray(rng.standard_normal((8 * bs, 2, 128), np.float32),
                       jnp.bfloat16)
    seg, pos = _pack(t, [60])
    kw = dict(
        k_pool=pool, v_pool=pool, block_size=bs, n_prefix_rows=128,
        block_tables=jnp.arange(8, dtype=jnp.int32)[None, :],
        prefix_lens=jnp.asarray([128], jnp.int32))
    args = (q, k, v, jnp.asarray(seg), jnp.asarray(pos + 128))
    got = A.ragged_prefill_attention(*args, use_flash=True, **kw)
    want = A.ragged_prefill_attention(*args, **kw)
    assert (np.asarray(got, np.float32) == np.asarray(want, np.float32)).all()


def test_the_batcher_counts_the_dispatches_that_attended_in_the_kernel(
        monkeypatch):
    """An engine whose choice of kernels holds ``ragged``: every cold
    prefill dispatch attends in the kernel — interpreted here, the one
    thing a CPU cannot take from it — so the counter over
    ``serve_prefill_dispatches`` reads 1.0 and the key blocks are the
    host's arithmetic; the tokens are the XLA form's."""
    from docqa_tpu.config import GenerateConfig
    from docqa_tpu.engines.generate import GenerateEngine
    from docqa_tpu.engines.serve import ContinuousBatcher
    from docqa_tpu.runtime.metrics import DEFAULT_REGISTRY

    real = A.ragged_flash_prefill
    monkeypatch.setattr(
        A, "ragged_flash_prefill",
        lambda *args, **kw: real(*args, **kw, interpret=True))
    names = ("serve_prefill_dispatches",
             "serve_prefill_attend_kernel_dispatches",
             "serve_prefill_key_blocks_visited",
             "serve_prefill_key_blocks_packed")
    cfg = DecoderConfig(
        vocab_size=256, hidden_dim=64, num_layers=2, num_heads=2,
        num_kv_heads=1, head_dim=128, mlp_dim=128, max_seq_len=256,
        dtype="bfloat16")
    gen = dataclasses.replace(
        GenerateConfig(), speculative_k=0, prefix_cache=False, decode_chunk=4,
        max_concurrent=2)
    engine = GenerateEngine(cfg, gen=gen, use_flash=False)
    prompts = [[5 + (11 * i + j) % 250 for j in range(140 - 50 * i)]
               for i in range(2)]
    got = {}
    for ragged in (True, False):
        before = {n: DEFAULT_REGISTRY.counter(n).value for n in names}
        b = ContinuousBatcher(engine, n_slots=2, chunk=4, cache_len=256,
                              kv_block_size=16, prefix_cache=False)
        try:
            assert not b._kernels.ragged  # a CPU
            b._kernels = b._kernels._replace(ragged=ragged)
            got[ragged] = [list(h.result(timeout=600)) for h in
                           [b.submit_ids(p, max_new_tokens=6) for p in prompts]]
        finally:
            b.stop()
        gained = {n: DEFAULT_REGISTRY.counter(n).value - before[n]
                  for n in names}
        assert gained["serve_prefill_dispatches"] > 0
        if not ragged:
            assert not any(gained[n] for n in names[1:])
            continue
        assert (gained["serve_prefill_attend_kernel_dispatches"]
                == gained["serve_prefill_dispatches"])
        assert 0 < gained["serve_prefill_key_blocks_visited"] <= gained[
            "serve_prefill_key_blocks_packed"]
        # two layers a dispatch, whole blocks of the packed square
        assert gained["serve_prefill_key_blocks_packed"] % 2 == 0
    assert got[True] == got[False]
