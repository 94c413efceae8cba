"""The latent block's paged decode kernel (ISSUE 50):
``paged_latent_flash_decode`` reads a lane's live pages out of the ONE pool
in place — a page an operand of the call, the grid the live compute blocks.

* in interpret mode against the XLA gather reference
  (``paged_latent_decode_attention``): a free slot, one row, a page's edge,
  the cell's ~450 rows, a full table, a retired lane, pages out of order,
  lanes of different lengths, one token a step and several;
* the schedule (``latent_page_schedule``) against a plain loop: only live
  pages are named, an operand past a lane's last page keeps what it held.
"""

import importlib

import jax.numpy as jnp
import numpy as np
import pytest

# ``docqa_tpu.ops`` re-exports a FUNCTION named ``attention``
A = importlib.import_module("docqa_tpu.ops.attention")

BS = 16  # block_size
NB = 256  # table entries a lane: 4,096 positions, as the cell's
N_PAGES = 600
HEADS, R, DR = 8, 128, 64  # a row of 192 values: 1.5 registers wide
TOL = 2.0 ** -6  # two bf16 roundings of an O(1) output, as kernel_selfcheck
FULL = NB * BS


def _tables(rng, allocated, in_order=False):
    """A lane's pages, scattered and out of order (or ascending); the tail
    of every row is holes (``>= N_PAGES``, a different sentinel an entry so
    that a dereference could not go unnoticed)."""
    tables = N_PAGES + rng.integers(
        0, 1000, (len(allocated), NB)).astype(np.int32)
    pages = rng.permutation(N_PAGES)
    if in_order:
        pages = np.sort(pages)
    pages = iter(pages)
    for lane, n in enumerate(allocated):
        for i in range(-(-int(n) // BS)):
            tables[lane, i] = next(pages)
    return tables


def _compare(lengths, *, s=1, allocated=None, in_order=False, seed=0,
             block_rows=None):
    rng = np.random.default_rng(seed)
    lengths = np.asarray(lengths, np.int32)
    allocated = lengths if allocated is None else np.asarray(allocated)

    def draw(*shape):
        return jnp.asarray(
            rng.standard_normal(shape, np.float32), jnp.bfloat16)

    pool = draw(N_PAGES * BS, 1, R + DR)
    tables = jnp.asarray(_tables(rng, allocated, in_order))
    q_lat, q_rope = draw(len(lengths), s, HEADS, R), draw(
        len(lengths), s, HEADS, DR)
    kw = dict(block_size=BS, scale=(R + DR) ** -0.5,
              q_offset=jnp.asarray(np.maximum(lengths - s, 0)))
    want = A.paged_latent_decode_attention(
        q_lat, q_rope, pool, tables, jnp.asarray(np.minimum(
            lengths, -(-allocated // BS) * BS)), **kw)
    if block_rows is None:
        got = A.paged_latent_flash_decode(
            q_lat, q_rope, pool, tables, jnp.asarray(lengths),
            interpret=True, **kw)
    else:
        got = A._paged_latent_attend_local(
            q_lat, q_rope, pool, tables, jnp.asarray(lengths),
            kw["q_offset"], block_size=BS, scale=kw["scale"],
            block_rows=block_rows, interpret=True)
    assert got.shape == want.shape and got.dtype == want.dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return np.abs(got - want).max(axis=(1, 2, 3)), got


CASES = {
    # lengths AFTER the step, a lane each
    "a-free-slot": dict(lengths=[0, 40]),
    "one-row": dict(lengths=[1, 1]),
    "a-page-to-its-edge": dict(lengths=[16, 16]),
    "a-page-and-a-row": dict(lengths=[17, 17]),
    "the-cells-rows": dict(lengths=[452, 447, 460, 441]),
    "a-full-table": dict(lengths=[FULL, 449]),
    "a-retired-lane": dict(lengths=[200, 77], allocated=[0, 77]),
    "a-length-past-its-pages": dict(lengths=[200, 77], allocated=[32, 77]),
    "pages-in-order": dict(lengths=[300, 129], in_order=True),
    "pages-out-of-order": dict(lengths=[300, 129]),
    "lanes-of-every-length": dict(
        lengths=[0, 1, 16, 17, 450, 0, 1030, 513, 512, 3]),
    "several-blocks-a-lane": dict(
        lengths=[389, 0, 17, 140, 64, 65], block_rows=64),
    "a-verify-step": dict(lengths=[389, 260, 17, 3], s=3),
    "a-verify-step-over-blocks": dict(
        lengths=[389, 33, 130], s=4, block_rows=128),
}


@pytest.mark.parametrize("case", CASES)
def test_the_kernel_is_the_gather_reference(case):
    case = CASES[case]
    err, got = _compare(**case)
    allocated = case.get("allocated", case["lengths"])
    live = np.asarray(allocated) > 0
    assert (err[live] <= TOL).all(), err
    # a lane of no rows (a free slot, a retired lane) reads nothing and
    # writes exact zeros, where the gather attends to a clamped row
    assert not got[~live].any()
    assert np.abs(got[live]).max() > 0.05


def _plain_schedule(tables, lengths, ppb):
    """``latent_page_schedule`` as a loop: (steps, lane, block) a step, the
    pages a step's operands NAME, and which of them are live."""
    steps, held = [], [0] * ppb
    for lane, n in enumerate(lengths):
        allocated = int((tables[lane] < N_PAGES).sum())
        n_pages = min(-(-int(n) // BS), allocated)
        for j in range(max(-(-n_pages // ppb), 1)):
            live = [j * ppb + k < n_pages for k in range(ppb)]
            held = [int(tables[lane, j * ppb + k]) if live[k] else held[k]
                    for k in range(ppb)]
            steps.append((lane, j, list(held), live))
    return steps


@pytest.mark.parametrize("lengths, ppb", [
    ([452, 447, 460, 441], 32),
    ([0, 0, 0], 32),
    ([0, 1, 16, 17, 450, 0, 1030, 513, 512, 3], 32),
    ([FULL, 449, FULL], 32),
    ([389, 0, 17, 140, 64, 65, 0], 4),
    ([33, 200, 8, 90], 3),  # a table that is no whole number of blocks
], ids=["the-cell", "all-free", "every-length", "full-tables", "rows64",
        "a-ragged-table"])
def test_the_schedule_names_live_pages_once(lengths, ppb):
    rng = np.random.default_rng(7)
    tables = _tables(rng, lengths)
    steps, lane, blk, blocks, kv_len, page = (
        np.asarray(x) for x in A.latent_page_schedule(
            jnp.asarray(tables), jnp.asarray(lengths, jnp.int32),
            block_size=BS, n_blocks=N_PAGES, ppb=ppb))
    want = _plain_schedule(tables, lengths, ppb)
    assert int(steps) == len(want) <= len(lane)
    assert list(kv_len) == list(lengths)
    page = page.reshape(len(lane), ppb)
    fetched = set()
    for i, (ln, j, held, live) in enumerate(want):
        assert (lane[i], blk[i]) == (ln, j)
        assert list(page[i]) == held
        # what is fetched: an operand whose index changed — live pages,
        # each once (page 0 once more, before an operand's first)
        for k in range(ppb):
            if i == 0 or page[i, k] != page[i - 1, k]:
                assert live[k] or (i == 0 and page[i, k] == 0)
                assert (page[i, k], k) not in fetched
                fetched.add((int(page[i, k]), k))
    assert [int(b) for b in blocks] == [
        sum(1 for w in want if w[0] == ln) for ln in range(len(lengths))]
    # no hole is ever named
    assert page.max() < N_PAGES


def test_a_geometry_the_kernel_does_not_read_is_refused():
    """Pages that are no whole tiles of the pool's type, a latent that is
    no whole registers: ``kernel_forms`` keeps those on the gather, and
    the kernel says so if called."""
    assert A.paged_latent_kernel_supported(jnp.bfloat16, 512, 16)
    assert A.paged_latent_kernel_supported(jnp.float32, 512, 8)
    assert not A.paged_latent_kernel_supported(jnp.bfloat16, 512, 8)
    assert not A.paged_latent_kernel_supported(jnp.bfloat16, 32, 16)
    assert not A.paged_latent_kernel_supported(jnp.bfloat16, 512, None)
    assert not A.paged_latent_kernel_supported(jnp.int8, 512, 32)
    q_lat, q_rope = jnp.zeros((1, 1, 4, 32)), jnp.zeros((1, 1, 4, 16))
    pool = jnp.zeros((64, 1, 48))
    args = (jnp.zeros((1, 4), jnp.int32), jnp.ones((1,), jnp.int32))
    kw = dict(block_size=16, q_offset=jnp.zeros((1,), jnp.int32), scale=1.0)
    with pytest.raises(NotImplementedError, match="32-wide latents"):
        A.paged_latent_flash_decode(q_lat, q_rope, pool, *args, **kw)
    with pytest.raises(ValueError, match="a pool of rows"):
        A.paged_latent_flash_decode(
            q_lat, q_rope, pool[:, :, :40], *args, **kw)
