"""The block-sparse decode step through the paged kernel (ISSUE 46):
``sparse_decode_attention`` reads the blocks a lane took as PAGES, one
call of ``paged_flash_decode`` over a virtual lane a (lane, kv head).

* the kernel path in interpret mode against the XLA form
  (``taken_rows_only`` / ``whole_tables``): a lane that selects, one under
  ``dense_len``, one exactly at it and a retired one side by side; page
  tables shuffled; the query at a block's first and last row; fewer
  blocks than ``topk``.  Outputs within the paged kernel's own tolerance,
  ``taken`` identical;
* the virtual table (``taken_page_tables``) by hand.

Who chooses it: ``models/decoder.kernel_forms`` (tests/test_block_serving.py).
"""

import importlib

import jax.numpy as jnp
import numpy as np
import pytest

# ``docqa_tpu.ops`` re-exports a FUNCTION named ``attention``
A = importlib.import_module("docqa_tpu.ops.attention")

BS = 16  # block_size: a page
N_PAGES = 160
NB = 40  # table entries a lane: 640 positions, 10 blocks of 64
HOLE = N_PAGES
TOL = 2.0 ** -6  # tests/test_paged_kernel.py's: two bf16 roundings
SIZES = dict(kernel_size=32, stride=16, block=64, topk=4, init_blocks=1,
             window=64, dense_len=256)


def _tables(rng, lengths, retired=()):
    """Scattered, out-of-order pages a lane, holes (``>= N_PAGES``, another
    sentinel an entry) behind them; a retired lane's row is all holes."""
    tables = N_PAGES + rng.integers(0, 1000, (len(lengths), NB)).astype(
        np.int32)
    pages = iter(rng.permutation(N_PAGES))
    for lane, n in enumerate(lengths):
        if lane not in retired:
            for i in range(-(-int(n) // BS)):
                tables[lane, i] = next(pages)
    return tables


def _both_forms(lengths, retired=(), seed=0, g=2, per=2, d=128, **sizes):
    sizes = {**SIZES, **sizes}
    rng = np.random.default_rng(seed)
    bf16 = jnp.bfloat16

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape, np.float32), bf16)

    lengths = np.asarray(lengths, np.int32)
    args = (
        draw(len(lengths), g * per, d), draw(N_PAGES * BS, g, d),
        draw(N_PAGES * BS, g, d),
        draw(N_PAGES * BS // sizes["stride"], g, d),
        jnp.asarray(_tables(rng, lengths, retired)), jnp.asarray(lengths),
    )
    want = A.sparse_decode_attention(*args, block_size=BS, **sizes)
    got = A.sparse_decode_attention(
        *args, block_size=BS, **sizes, interpret=True)
    return [(np.asarray(o, np.float32), np.asarray(t)) for o, t in (want, got)]


@pytest.mark.parametrize("case", [
    # selects, under dense_len, exactly AT it, retired: the XLA form runs
    # ``whole_tables`` (a live lane is dense)
    dict(lengths=[600, 200, 256, 300], retired=(3,)),
    # every live lane selects (``taken_rows_only``); t % 64 == 0: the
    # block that holds the query has ONE valid row
    dict(lengths=[577, 321, 257, 449], retired=()),
    # t % 64 == 63: it is full
    dict(lengths=[576, 320, 256, 640], retired=()),
    # more blocks wanted than exist: ``took`` false in places
    dict(lengths=[300, 257, 470, 100], retired=(), topk=8, dense_len=128),
    # one kv head and MQA groups of four
    dict(lengths=[600, 200, 256, 300], retired=(3,), g=1, per=4),
], ids=["select-dense-at-retired", "first-row-of-a-block",
        "last-row-of-a-block", "fewer-blocks-than-topk", "one-kv-head"])
def test_the_kernel_path_reads_what_the_xla_form_reads(case):
    (want, taken_w), (got, taken_g) = _both_forms(**case)
    assert (taken_g == taken_w).all()
    sizes = {**SIZES, **{k: v for k, v in case.items() if k in SIZES}}
    lengths = np.asarray(case["lengths"])
    selecting = lengths >= sizes["dense_len"]
    # the record says what was compared: a selecting lane names blocks,
    # fewer than topk where fewer exist; a dense lane names none
    held = (taken_g >= 0).sum(-1)  # [g, S]
    exist = np.minimum(-(-lengths // sizes["block"]), sizes["topk"])
    assert (held == np.where(selecting, exist, 0)[None]).all()
    for lane in range(len(lengths)):
        if lane in case["retired"]:
            # no page is dereferenced: zeros, where the gather reads a
            # clamped garbage row for a lane nobody reads
            assert not got[lane].any()
        else:
            assert np.abs(got[lane] - want[lane]).max() <= TOL, lane


# ---- the virtual table, by hand ----------------------------------------------

def _virtual(ids, took, lengths, tables, dense_len=256):
    ids = np.asarray(ids, np.int32)
    lengths = np.asarray(lengths, np.int32)
    table, lens = A.taken_page_tables(
        jnp.asarray(ids), jnp.asarray(np.asarray(took, bool)),
        jnp.asarray(lengths >= dense_len), jnp.asarray(tables),
        jnp.asarray(lengths), block_size=BS, block=64, dense_len=dense_len,
        n_blocks=N_PAGES)
    return np.asarray(table), np.asarray(lens)


def _own_table(n_pages):
    """A lane's table: page (11 + 37 i) % 160 at entry i (nothing
    contiguous, nothing twice), holes past ``n_pages``."""
    row = np.full(NB, HOLE + 7, np.int32)
    row[:n_pages] = (11 + 37 * np.arange(n_pages)) % N_PAGES
    return row


@pytest.mark.parametrize("case", [
    # top_k hands the forced blocks first: ascending order is the table's
    dict(ids=[9, 0, 4, 7], took=[1, 1, 1, 1], length=600,
         blocks=[0, 4, 7, 9], rows=3 * 64 + 600 - 576),
    # the query on a block's first row: one valid row in the last block
    dict(ids=[5, 0, 1, 3], took=[1, 1, 1, 1], length=321,
         blocks=[0, 1, 3, 5], rows=3 * 64 + 1),
    # on its last row: every taken block is full
    dict(ids=[4, 0, 2, 3], took=[1, 1, 1, 1], length=320,
         blocks=[0, 2, 3, 4], rows=4 * 64),
    # fewer blocks than topk: what ``took`` leaves out is not read
    dict(ids=[4, 0, 3, 1], took=[1, 1, 0, 0], length=300,
         blocks=[0, 4], rows=64 + 300 - 256),
], ids=["ascending", "partial-last-block", "full-last-block", "took-false"])
def test_a_selecting_lanes_row_is_its_taken_blocks_pages_in_order(case):
    n = case["length"]
    own = _own_table(-(-n // BS))
    table, lens = _virtual(
        [[case["ids"]]], [[case["took"]]], [n], own[None])
    assert table.shape == (1, 16) and lens.tolist() == [case["rows"]]
    # four pages a block, in order; holes behind the last and nowhere else
    want = [own[4 * b + i] for b in case["blocks"] for i in range(4)]
    assert table[0, :len(want)].tolist() == want
    assert (table[0, len(want):] >= N_PAGES).all()
    live = -(-case["rows"] // BS)  # the pages the kernel walks
    assert (table[0, :live] < N_PAGES).all()
    # the kernel's clamp (allocated pages x block_size) leaves the length
    assert (table[0] < N_PAGES).sum() * BS >= case["rows"]


def test_each_kv_head_gets_a_row_of_its_own():
    own = _own_table(38)
    table, lens = _virtual(
        [[[9, 0, 2, 8], [9, 0, 5, 1]]], np.ones((1, 2, 4)), [600], own[None])
    assert table.shape == (2, 16) and lens.tolist() == [216, 216]
    assert table[0].tolist() == [
        own[4 * b + i] for b in (0, 2, 8, 9) for i in range(4)]
    assert table[1].tolist() == [
        own[4 * b + i] for b in (0, 1, 5, 9) for i in range(4)]


@pytest.mark.parametrize("length, pages", [(200, 13), (255, 16), (1, 1)])
def test_a_lane_under_dense_len_keeps_its_own_row_and_length(length, pages):
    own = _own_table(pages)
    # whatever the selection says of such a lane is not read
    table, lens = _virtual(
        [[[3, 0, 1, 2]] * 2], np.ones((1, 2, 4)), [length], own[None])
    assert lens.tolist() == [length, length]
    assert (table == own[None, :16]).all()


def test_a_retired_lane_is_all_holes_and_reads_nothing():
    holes = np.full((2, NB), HOLE, np.int32) + np.arange(NB, dtype=np.int32)
    for length in (600, 30):  # it selected, or it did not, when it left
        table, lens = _virtual(
            [[[9, 0, 4, 7]] * 2] * 2, np.ones((2, 2, 4)), [length] * 2, holes)
        assert (table >= N_PAGES).all()
        # ``_paged_attend_local`` clamps a length to the allocated pages
        assert (table < N_PAGES).sum() * BS == 0
    table, lens = _virtual(
        [[[0, 1, 2, 3]]], np.zeros((1, 1, 4)), [300], _own_table(19)[None])
    assert lens.tolist() == [0] and (table >= N_PAGES).all()


def test_a_table_narrower_than_the_span_is_filled_with_holes():
    """A toy table of 8 entries under ``dense_len`` 256: the virtual table
    is as wide as the taken blocks need, the lane's own row padded."""
    own = _own_table(6)[:8]
    table, lens = _virtual(
        [[[0, 1, 0, 0]]], [[[1, 1, 0, 0]]], [90], own[None])
    assert table.shape == (1, 16) and lens.tolist() == [90]
    assert (table[0, :8] == own).all() and (table[0, 8:] >= N_PAGES).all()
