"""The paged decode kernel (ISSUE 29): ``paged_flash_decode`` reads a lane's
live pages out of the pool in place.

* in interpret mode against the plain reference (``gather_paged_kv`` +
  ``attention_reference``): lengths around a page boundary, scattered and
  shared pages, holes, both step widths, a sliding window, GQA and MHA;
  — and (PR 57) what the kernel decides a compute block: full or partial,
  its ids a run or not.  ``interpret=True`` is the generic interpreter: a
  DMA there copies at its start, a wait never blocks, and a DMA semaphore
  is an int16 count of ELEMENTS clamped at 32,767 — so these cases hold
  the data path of each branch (which copies land where), not the byte
  accounting of the ONE wait a full block makes for all its copies; that
  is held on the chip (a wrong count hangs the call or reads a stale
  buffer: the five served geometries agree with the gather there, PERF
  §6 "PR 57");
* structurally: the traced decode attention holds no value with the block
  tables' span of rows (no gather, no transpose of the span);
* the counters ``serve_decode_kv_rows_read`` / ``_live`` as defined, on
  both paths, through ``_process_chunk``.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# ``docqa_tpu.ops`` re-exports a FUNCTION named ``attention``
A = importlib.import_module("docqa_tpu.ops.attention")

BS = 16  # block_size
N_PAGES = 96
NB = 32  # table entries per lane: a full table is 512 positions
TOL = 2.0 ** -6  # two bf16 roundings of an O(1) output, as kernel_selfcheck


def _pool(rng, hkv, d, dtype, n_pages=N_PAGES):
    return jnp.asarray(
        rng.standard_normal((n_pages * BS, hkv, d), np.float32), dtype
    )


def _tables(rng, lengths, shared_pages=0, nb=NB, n_pages=N_PAGES,
            order="scattered"):
    """Scattered, out-of-order pages per lane; the tail of every row is
    holes (``>= n_pages``, a different sentinel per entry so a dereference
    could not go unnoticed).  ``shared_pages``: lanes 0 and 1 share their
    first pages (a prefix hit).  ``order``: "runs" hands each lane
    ascending consecutive ids, lane after lane (what a fresh pool does);
    "broken" the same with two ids swapped inside every fourth page of a
    lane (a run broken inside a compute block)."""
    tables = n_pages + rng.integers(0, 1000, (len(lengths), nb)).astype(np.int32)
    pages = iter(
        rng.permutation(n_pages) if order == "scattered" else range(n_pages))
    for lane, n in enumerate(lengths):
        for i in range(-(-int(n) // BS)):
            tables[lane, i] = next(pages)
        if order == "broken":
            for i in range(1, -(-int(n) // BS) - 1, 4):
                tables[lane, [i, i + 1]] = tables[lane, [i + 1, i]]
    if shared_pages:
        tables[1, :shared_pages] = tables[0, :shared_pages]
    return tables


def _compare(lengths, *, s=1, hq=32, hkv=8, d=128, window=None,
             dtype=jnp.bfloat16, shared_pages=0, seed=0, nb=NB,
             n_pages=N_PAGES, order="scattered"):
    rng = np.random.default_rng(seed)
    lengths = np.asarray(lengths, np.int32)
    k_pool, v_pool = (_pool(rng, hkv, d, dtype, n_pages) for _ in range(2))
    tables = jnp.asarray(
        _tables(rng, lengths, shared_pages, nb, n_pages, order))
    q = jnp.asarray(
        rng.standard_normal((len(lengths), s, hq, d), np.float32), dtype
    )
    q_offset = jnp.asarray(np.maximum(lengths - s, 0))
    args = (q, k_pool, v_pool, tables, jnp.asarray(lengths))
    want = A.paged_decode_attention(
        *args, block_size=BS, q_offset=q_offset, sliding_window=window
    )
    got = A.paged_flash_decode(
        *args, q_offset, block_size=BS, sliding_window=window, interpret=True
    )
    assert got.shape == want.shape and got.dtype == want.dtype
    err = np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32))
    return err.max(axis=(1, 2, 3)), np.asarray(got, np.float32)


# tables of 72 entries (1,152 positions) over 160 pages: a lane of several
# compute blocks at either block size; few heads keep the pools small
LONG = dict(nb=72, n_pages=160, hq=8, hkv=4)

@pytest.mark.parametrize("case", [
    dict(lengths=[0, 1, 15, 16]),
    dict(lengths=[17, 389, 0, NB * BS]),  # a full table
    dict(lengths=[389, 260, 17, 1], s=4),  # the verify width, with q_offset
    dict(lengths=[NB * BS, 16, 5, 0], s=4),
    dict(lengths=[389, 300, 140, 33], window=100),  # window < length
    dict(lengths=[389, 300, 500, 33], s=4, window=270),
    dict(lengths=[200, 260, 40, 0], shared_pages=8),  # a prefix hit
    dict(lengths=[1, 15, 17, 389], hq=8, hkv=8),  # MHA
    dict(lengths=[16, 389, 0, 100], hq=8, hkv=2, s=4),
    dict(lengths=[15, 389, 17, 0], hq=4, hkv=1),  # one kv head a device
    dict(lengths=[15, 389, 17, 0], hq=8, hkv=4, dtype=jnp.float32),
    # what the kernel decides a block (PR 57): full or partial, a run or
    # not.  Lanes of exactly 1, 2, 8 and 16 blocks of 64 rows = a partial,
    # exactly 1, a partial and exactly 2 blocks of 512: every length ends
    # on a block's edge
    dict(lengths=[64, 512, 128, 1024], order="runs", **LONG),
    dict(lengths=[64, 512, 128, 1024], **LONG),  # the same, no block a run
    dict(lengths=[1120, 1000, 8], order="runs", **LONG),  # 17 1/2 blocks of 64
    dict(lengths=[8960, 1120], order="runs", nb=576, n_pages=640, hq=4,
         hkv=2),  # 17 1/2 blocks of 512, as Trinity's lanes
    dict(lengths=[1120, 1000, 300], order="broken", **LONG),
    # the first block the window sees is the lane's last, partial one
    dict(lengths=[70, 300, 1000, 130], window=6, order="runs", **LONG),
    dict(lengths=[1100, 300, 1152], window=200, order="runs", **LONG),
    # the cross-lane prefetch over a lane that has nothing to fetch
    dict(lengths=[1000, 0, 1000], order="runs", **LONG),
    dict(lengths=[1000, 0, 0, 1030], **LONG),
    # a hole inside a block that has live pages (31 of its 32)
    dict(lengths=[490, 1000, 33], order="runs", **LONG),
    dict(lengths=[200, 1030], order="runs", nb=72, n_pages=160, hq=2, hkv=1),
    dict(lengths=[200, 1030], order="runs", nb=72, n_pages=160, hq=4, hkv=2),
    dict(lengths=[200, 1030], order="broken", nb=72, n_pages=160, hq=8,
         hkv=4),
    dict(lengths=[200, 1030], order="runs", nb=72, n_pages=160, hq=16, hkv=8),
    dict(lengths=[200, 1030], order="runs", nb=72, n_pages=160, hq=16,
         hkv=16),
    dict(lengths=[1030, 640, 4, 512], s=4, order="runs", **LONG),
    dict(lengths=[1030, 640, 4, 512], s=4, order="broken", **LONG),
    dict(lengths=[200, 1030], order="runs", dtype=jnp.float32, **LONG),
], ids=["page_edge", "full_table", "verify", "verify_full", "window",
        "verify_window", "shared_pages", "mha", "gqa_pair", "one_kv_head",
        "float32_pool", "whole_blocks_runs", "whole_blocks_scattered",
        "blocks_17_and_a_half", "blocks_17_and_a_half_of_512", "broken_runs",
        "window_first_block_partial", "window_runs", "empty_lane_between",
        "empty_lanes_scattered", "hole_past_the_length", "runs_1_kv_head",
        "runs_2_kv_heads", "broken_4_kv_heads", "runs_8_kv_heads",
        "runs_16_kv_heads", "verify_runs", "verify_broken",
        "float32_pool_runs"])
@pytest.mark.parametrize("block_rows", [64, 512], ids=["rows64", "rows512"])
def test_kernel_matches_the_gather_reference(case, block_rows, monkeypatch):
    # 64 rows: a lane of 389 streams through 7 compute blocks, the window
    # starts past the first; 512 (the served size): a lane is one block
    monkeypatch.setattr(A, "PAGED_BLOCK_ROWS", block_rows)
    err, got = _compare(**case)
    assert (err <= TOL).all(), err
    # a lane of length 0 writes zeros, and fetched nothing to do so
    for lane, n in enumerate(case["lengths"]):
        if n == 0:
            assert not got[lane].any()


def test_a_hole_inside_the_length_is_never_dereferenced():
    """A lane whose length runs past its allocated pages (a retired lane:
    every entry a hole) attends only to what is allocated."""
    rng = np.random.default_rng(3)
    k_pool, v_pool = (_pool(rng, 8, 128, jnp.bfloat16) for _ in range(2))
    lengths = np.array([200, 77], np.int32)
    tables = _tables(rng, [200, 32])
    tables[0] = N_PAGES + 7  # retired: all holes, length left standing
    q = jnp.asarray(rng.standard_normal((2, 1, 32, 128), np.float32),
                    jnp.bfloat16)
    got = A.paged_flash_decode(
        q, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(lengths),
        jnp.asarray(lengths - 1), block_size=BS, interpret=True,
    )
    assert not np.asarray(got[0], np.float32).any()
    # lane 1 holds two pages: exactly the reference at length 32
    want = A.paged_decode_attention(
        q, k_pool, v_pool, jnp.asarray(tables), jnp.asarray([200, 32]),
        block_size=BS, q_offset=jnp.asarray(lengths - 1),
    )
    np.testing.assert_allclose(
        np.asarray(got[1], np.float32), np.asarray(want[1], np.float32),
        atol=TOL,
    )


def test_on_a_virtual_mesh_kv_heads_are_sharded():
    """The 1x4 path end to end on virtual devices: ``shard_map`` over kv
    heads (2 a device), tables and lengths as the batcher holds them."""
    from docqa_tpu.runtime.mesh import host_cpu_mesh

    mesh = host_cpu_mesh(4)
    rng = np.random.default_rng(11)
    lengths = np.array([389, 0, 17, 512], np.int32)
    k_pool, v_pool = (_pool(rng, 8, 128, jnp.bfloat16) for _ in range(2))
    tables = jnp.asarray(_tables(rng, lengths))
    for s in (1, 4):
        q = jnp.asarray(rng.standard_normal((4, s, 32, 128), np.float32),
                        jnp.bfloat16)
        q_offset = jnp.asarray(np.maximum(lengths - s, 0))
        args = (q, k_pool, v_pool, tables, jnp.asarray(lengths))
        want = A.paged_decode_attention(
            *args, block_size=BS, q_offset=q_offset, sliding_window=300
        )
        got = A.paged_flash_decode(
            *args, q_offset, block_size=BS, sliding_window=300,
            interpret=True, mesh=mesh,
        )
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            atol=TOL,
        )


@pytest.mark.parametrize("dtype, hkv, d, reads", [
    ("bfloat16", 8, 128, True), ("bfloat16", 1, 128, True),
    ("float32", 3, 128, True), ("bfloat16", 3, 128, False),
    ("bfloat16", 8, 64, False), ("int8", 8, 128, False),
])
def test_geometries_the_kernel_reads(dtype, hkv, d, reads):
    assert A.paged_kernel_supported(dtype, hkv, d) is reads


def _values(jaxpr):
    """Every value of a jaxpr and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield from eqn.outvars
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (list, tuple)) else [param]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _values(inner)


@pytest.mark.parametrize("use_flash", [True, False], ids=["kernel", "gather"])
def test_no_value_spans_the_block_tables(use_flash):
    """At [4 x 256] tables the gather reference makes [4, 4096, 8, 128]
    copies of the span; the kernel's trace holds nothing with that many
    rows (the parent's gather + flash pairing did, four times a layer)."""
    S, nb, hq, hkv, d = 4, 256, 32, 8, 128
    span = S * nb * BS

    def attend(q, k_pool, v_pool, tables, lengths):
        return A.paged_decode_attention(
            q, k_pool, v_pool, tables, lengths, block_size=BS,
            q_offset=lengths - 1, sliding_window=4096, use_flash=use_flash,
        )

    pool = jax.ShapeDtypeStruct((span, hkv, d), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(attend)(
        jax.ShapeDtypeStruct((S, 1, hq, d), jnp.bfloat16), pool, pool,
        jax.ShapeDtypeStruct((S, nb), jnp.int32),
        jax.ShapeDtypeStruct((S,), jnp.int32),
    )
    spanning = [
        v.aval.shape for v in _values(jaxpr.jaxpr)
        if hasattr(v.aval, "shape") and len(v.aval.shape) >= 3
        and int(np.prod(v.aval.shape[:-2])) >= span
    ]
    if use_flash:
        assert not spanning, spanning
        assert "_paged_decode_kernel" in str(jaxpr)
    else:
        assert (S, nb * BS, hkv, d) in spanning


class TestKvRowCounters:
    """``serve_decode_kv_rows_read`` / ``_live`` per fetched chunk: a fake
    chunk through ``_process_chunk``, under the plain and the speculative
    program, on both attention paths."""

    @pytest.fixture(params=[0, 4], ids=["plain", "spec4"])
    def batcher(self, request):
        from docqa_tpu.config import DecoderConfig, GenerateConfig
        from docqa_tpu.engines.generate import GenerateEngine
        from docqa_tpu.engines.serve import ContinuousBatcher

        cfg = DecoderConfig(
            vocab_size=64, hidden_dim=32, num_layers=1, num_heads=2,
            num_kv_heads=1, head_dim=16, mlp_dim=64, max_seq_len=128,
            dtype="float32",
        )
        gen = GenerateConfig(
            temperature=0.0, prefill_buckets=(16,), eos_id=2,
            speculative_k=request.param,
        )
        b = ContinuousBatcher(
            GenerateEngine(cfg, gen, seed=3), n_slots=4, chunk=4,
            cache_len=128,
        )
        yield b
        b.stop()

    @staticmethod
    def _fake_chunk(batcher, in_place, runs=None):
        """One chunk fetched for two lanes that have since retired: 37
        and 16 positions of K/V at dispatch; the first emitted 4 tokens,
        the second stopped after 1."""
        from docqa_tpu.engines.serve import make_request
        from docqa_tpu.runtime.metrics import DEFAULT_REGISTRY

        assert batcher.block_size == 16
        batcher._kernels = batcher._kernels._replace(paged=in_place)
        lanes = []
        for kv_prompt, delivered in ((30, 8), (14, 3)):
            req = make_request([3] * kv_prompt, 16)
            req.kv_prompt = kv_prompt
            req.tokens.extend([5] * delivered)
            lanes.append(req)
        snap = lanes + [None] * (batcher.n_slots - 2)
        if runs is not None:  # as the worker snapshots a dispatch
            from docqa_tpu.engines.serve import _ChunkSnap

            snap = _ChunkSnap(snap)
            snap.runs = runs
        chunk, k = batcher.chunk, batcher.spec_k
        if k:  # [tokens | emitted count | active]
            packed = np.zeros((batcher.n_slots, chunk + 2 * k + 2), np.int32)
            packed[:2, chunk + 2 * k] = (4, 1)
        else:  # [tokens | valid | active]
            packed = np.zeros((batcher.n_slots, 2 * chunk + 1), np.int32)
            packed[0, chunk: 2 * chunk] = 1
            packed[1, chunk] = 1
        names = ("serve_decode_kv_rows_read", "serve_decode_kv_rows_live")
        before = [DEFAULT_REGISTRY.counter(n).value for n in names]
        assert batcher._process_chunk(packed, snap)
        return [DEFAULT_REGISTRY.counter(n).value - b
                for n, b in zip(names, before)]

    @staticmethod
    def _expected(batcher):
        """(steps, live rows, live pages) of the fake chunk."""
        if batcher.spec_k:
            # one verify forward of 4 positions: 41 and 20 rows attended
            return 1, 41 + 20, 3 + 2
        # 4 steps: lane 0 attends 38, 39, 40, 41 rows (3 pages each),
        # lane 1 attends 17, then 18 where it stopped (2 pages each)
        return 4, 38 + 39 + 40 + 41 + 17 + 18 + 18 + 18, 4 * 3 + 4 * 2

    def test_under_the_kernel_live_pages_are_read(self, batcher):
        _steps, rows, pages = self._expected(batcher)
        read, live = self._fake_chunk(batcher, in_place=True)
        assert (read, live) == (pages * 16, rows)

    def test_under_the_gather_every_table_is_read(self, batcher):
        steps, rows, _pages = self._expected(batcher)
        read, live = self._fake_chunk(batcher, in_place=False)
        # every slot's whole table, each of the chunk's steps
        assert (read, live) == (
            steps * batcher.n_slots * batcher.seq_capacity, rows
        )

    def test_the_path_is_observed_not_set(self, batcher):
        # a CPU run serves the gather reference
        assert batcher._kernels.paged is False
        assert batcher.engine.use_flash is False

    COPIES = ("serve_decode_kv_copies", "serve_decode_kv_blocks",
              "serve_decode_kv_run_blocks")

    def _copies_of_the_fake_chunk(self, batcher, in_place, monkeypatch):
        from docqa_tpu.runtime.metrics import DEFAULT_REGISTRY

        # compute blocks of 2 pages, so that the fake lanes hold whole ones
        monkeypatch.setattr(A, "PAGED_BLOCK_ROWS", 32)
        batcher._block_rows[0, :3] = (4, 5, 9)  # pages 0-1: a run
        batcher._block_rows[1, :2] = (7, 6)  # descending: none
        before = [DEFAULT_REGISTRY.counter(n).value for n in self.COPIES]
        read, _live = self._fake_chunk(batcher, in_place)
        return read, [DEFAULT_REGISTRY.counter(n).value - b
                      for n, b in zip(self.COPIES, before)]

    def test_a_block_that_is_a_run_is_one_copy(self, batcher, monkeypatch):
        """``serve_decode_kv_copies`` per pool and cache entry: a lane's
        step is a copy a live page, but ONE for a compute block whose
        pages are all live and whose ids are an ascending run."""
        read, (copies, blocks, runs) = self._copies_of_the_fake_chunk(
            batcher, True, monkeypatch)
        steps, _rows, pages = self._expected(batcher)
        # lane 0: 3 pages = a full block (ids 4, 5: a run, one copy) and a
        # partial one; lane 1: 2 pages = a full block (ids 7, 6: two copies)
        assert (copies, blocks, runs) == (
            steps * (2 + 2), steps * (2 + 1), steps * 1)
        assert read == pages * 16 and copies == pages - runs

    def test_under_the_gather_no_copy_is_counted(self, batcher, monkeypatch):
        _read, counted = self._copies_of_the_fake_chunk(
            batcher, False, monkeypatch)
        assert counted == [0, 0, 0]

    def test_runs_as_they_stood_at_dispatch(self, batcher, monkeypatch):
        """The worker's snapshot keeps the runs it read when the chunk was
        dispatched: tables that changed since do not count."""
        from docqa_tpu.runtime.metrics import DEFAULT_REGISTRY

        monkeypatch.setattr(A, "PAGED_BLOCK_ROWS", 32)
        batcher._kernels = batcher._kernels._replace(paged=True)
        batcher._block_rows[0, :3] = (4, 5, 9)
        at_dispatch = batcher._table_runs()
        assert at_dispatch[0][0].tolist() == [True, False, False, False]
        batcher._block_rows[0, :3] = batcher.n_blocks  # retired since
        assert not batcher._table_runs()[0].any()
        before = DEFAULT_REGISTRY.counter("serve_decode_kv_run_blocks").value
        self._fake_chunk(batcher, in_place=True, runs=at_dispatch)
        gained = DEFAULT_REGISTRY.counter(
            "serve_decode_kv_run_blocks").value - before
        assert gained == self._expected(batcher)[0]


def test_block_runs_on_the_host_as_in_the_program():
    """``paged_block_runs``: the wrapper's mark of the compute blocks it
    fetches with one copy, and the batcher's count of them, are one
    function of the tables — ascending consecutive ids of allocated pages,
    a whole block of them."""
    n_blocks = 40
    tables = np.array([
        [3, 4, 5, 6, 10, 11, 12, 13, 40, 40],  # two runs, then holes
        [6, 5, 4, 3, 0, 1, 2, 4, 8, 9],  # descending; a jump; a short tail
        [36, 37, 38, 39, 37, 38, 39, 40, 40, 41],  # a hole ends a run
    ], np.int32)
    want = [[True, True, False], [False, False, False], [True, False, False]]
    assert A.paged_block_runs(tables, 4, n_blocks).tolist() == want
    on_device = jax.jit(
        lambda t: A.paged_block_runs(t, 4, n_blocks))(jnp.asarray(tables))
    assert np.asarray(on_device).tolist() == want
    # a pool smaller than a block holds no run of one
    assert not A.paged_block_runs(tables[:, :8], 4, 3).any()


class TestCompilesForTheChip:
    """What interpret mode cannot see: whether Mosaic accepts the kernel
    (strided sublane reads of packed bf16 pairs, page-sized DMAs, VMEM)
    at the widths the deployment runs — compiled for a DESCRIBED v5e, no
    chip attached, nothing executed."""

    @pytest.fixture(scope="class")
    def topo(self):
        from jax.experimental import topologies

        try:
            return topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")

    @staticmethod
    def _decode_args(sharding, S=4, s=1, n_rows=16384):
        """Mistral-7B's decode attention: 32/8 heads x 128, 4 lanes of 256
        table entries over a 16384-row pool."""

        def arg(shape, dtype, *spec):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding(*spec))

        return (
            arg((S, s, 32, 128), jnp.bfloat16, None, None, "model", None),
            arg((n_rows, 8, 128), jnp.bfloat16, None, "model", None),
            arg((n_rows, 8, 128), jnp.bfloat16, None, "model", None),
            arg((S, 256), jnp.int32), arg((S,), jnp.int32),
        )

    @staticmethod
    def _attend(mesh=None):
        def attend(q, k_pool, v_pool, tables, lengths):
            return A.paged_decode_attention(
                q, k_pool, v_pool, tables, lengths, block_size=BS,
                q_offset=lengths - q.shape[1], sliding_window=4096,
                use_flash=True, mesh=mesh,
            )

        return jax.jit(attend)

    @pytest.mark.parametrize("s", [1, 4], ids=["step", "verify"])
    def test_one_chip(self, topo, s):
        from jax.sharding import SingleDeviceSharding

        one_chip = SingleDeviceSharding(topo.devices[0])
        args = self._decode_args(lambda *spec: one_chip, s=s)
        hlo = self._attend().lower(*args).compile().as_text()
        assert "_paged_decode_kernel" in hlo
        # the pool reaches the kernel as it lies: no copy, no gather of it
        assert not [
            line for line in hlo.splitlines()
            if " = bf16[16384,8,128]" in line and " parameter(" not in line
        ]

    def test_a_1x4_mesh_shards_kv_heads(self, topo):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from docqa_tpu.runtime.mesh import MeshContext

        mesh = MeshContext(
            mesh=Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model")),
            data_axis="data", model_axis="model",
        )
        args = self._decode_args(
            lambda *spec: NamedSharding(mesh.mesh, P(*spec))
        )
        hlo = self._attend(mesh).lower(*args).compile().as_text()
        assert "_paged_decode_kernel" in hlo
        # kv heads are independent: no collective, and each chip's quarter
        # of the pool is read in place
        assert "all-gather" not in hlo and "all-reduce" not in hlo
        # (a chip's 16384 rows x 2 kv heads, flat: a page is 32 of them)
        assert "bf16[32768,128]" in hlo

    def test_on_a_mesh_it_is_shard_mapped(self):
        """GSPMD cannot partition a Mosaic call: cross-lowered for TPU on
        the virtual 1x4 mesh, as ``test_flash_on_a_mesh_lowers_for_tpu``."""
        from jax import export
        from jax.sharding import NamedSharding, PartitionSpec as P

        from docqa_tpu.runtime.mesh import host_cpu_mesh

        mesh = host_cpu_mesh(4)
        args = self._decode_args(
            lambda *spec: NamedSharding(mesh.mesh, P(*spec))
        )
        exported = export.export(self._attend(mesh), platforms=["tpu"])(*args)
        assert "tpu_custom_call" in exported.mlir_module()
        with pytest.raises(NotImplementedError, match="shard_map"):
            export.export(self._attend(None), platforms=["tpu"])(*args)

    @pytest.mark.parametrize("rows", [512, 9728, 37888], ids=[
        "warm-up", "one-prompt", "the-comparison"])
    def test_the_state_space_scan_at_jamba2s_widths(self, topo, rows):
        """ISSUE 43: ``ops/ssm._selective_scan_kernel`` — a dynamic lane
        rotate, static lane slices spread over 128 lanes, ``h`` [16, 512]
        in registers through a chunk — at
        5,120 channels x 16 states, for the three row counts the cell's
        process builds (this file holds the one topology fixture)."""
        import functools

        from jax.sharding import SingleDeviceSharding

        from docqa_tpu.ops import ssm

        one_chip = SingleDeviceSharding(topo.devices[0])

        def arg(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        d, n, bf16, i32 = 5120, 16, jnp.bfloat16, jnp.int32
        compiled = jax.jit(functools.partial(
            ssm.selective_scan_prefill, use_flash=True)).lower(
            arg((rows, d), bf16), arg((rows, d), jnp.float32),
            arg((n, d), jnp.float32), arg((rows, n), bf16),
            arg((rows, n), bf16), arg((d,), bf16), arg((rows,), i32),
            arg((rows,), i32), arg((4,), i32)).compile()
        hlo = compiled.as_text()
        assert "_selective_scan_kernel" in hlo
        # nothing of [chunks, rows, n, d] or [rows, n, d] beside the kernel
        assert f"f32[{rows},{n},{d}]" not in hlo
        assert f"f32[{rows // 128},128,{n},{d}]" not in hlo

    @pytest.mark.parametrize("rows, heads, kv_heads, window", [
        (512, 16, 16, None), (512, 32, 8, 4096), (9728, 32, 4, None),
        (9728, 32, 4, 2048), (37888, 32, 4, None), (37888, 32, 4, 2048),
        (9728, 20, 1, None), (37888, 20, 1, None),
    ], ids=["ouro", "mistral", "trinity-global", "trinity-window",
            "trinity-global-comparison", "trinity-window-comparison",
            "jamba2", "jamba2-comparison"])
    def test_the_ragged_prefill_kernel_at_the_served_widths(
            self, topo, rows, heads, kv_heads, window):
        """ISSUE 49: ``ops/attention.ragged_flash_prefill`` — column blocks
        of the ``[rows, heads * 128]`` views, four prefetched ranges, a kv
        head's query heads unrolled in one step — for the four served head
        geometries at the row counts their processes build; K and V enter
        the call as they are (no float32 copy, no repeat over a group)."""
        from jax.sharding import SingleDeviceSharding

        one_chip = SingleDeviceSharding(topo.devices[0])

        def arg(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        bf16, i32 = jnp.bfloat16, jnp.int32
        compiled = jax.jit(
            lambda *args: A.ragged_prefill_attention(
                *args, sliding_window=window, max_segment=9856,
                use_flash=True)).lower(
            arg((rows, heads, 128), bf16), arg((rows, kv_heads, 128), bf16),
            arg((rows, kv_heads, 128), bf16), arg((rows,), i32),
            arg((rows,), i32)).compile()
        hlo = compiled.as_text()
        assert "_ragged_prefill_kernel" in hlo
        assert f"f32[{rows},{kv_heads},128]" not in hlo
        assert f"bf16[{rows},{heads},128]{{" in hlo  # q in, out: not widened
        assert f"f32[{rows},{heads},128]" not in hlo

    @pytest.mark.parametrize("rows", [48, 3072, 18432], ids=[
        "decode-step", "one-dispatch", "the-comparison"])
    @pytest.mark.parametrize("k, n", [(5120, 1536), (1536, 5120)], ids=[
        "gate-up", "down"])
    def test_the_grouped_product_at_deepseek_v2s_widths(
            self, topo, rows, k, n):
        """ISSUE 45: ``ops/grouped.grouped_matmul`` over 40 held experts
        at the published widths — the tiles ``_tile`` picks (the
        contraction whole, 4 MB of weight a tile) fit the kernel's fast
        memory, for a decode step's 8 x 6 picks as ONE row tile, a
        512-row dispatch's 3,072 and the comparison's 18,432."""
        from jax.sharding import SingleDeviceSharding

        from docqa_tpu.ops import grouped

        one_chip = SingleDeviceSharding(topo.devices[0])

        def arg(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        assert rows % grouped.row_tile(rows) == 0
        hlo = jax.jit(
            lambda x, w, sizes: grouped.grouped_matmul(
                x, w, sizes, jnp.float32, use_flash=True)
        ).lower(
            arg((rows, k), jnp.bfloat16), arg((40, k, n), jnp.bfloat16),
            arg((40,), jnp.int32)).compile().as_text()
        assert "tpu_custom_call" in hlo and "gmm" in hlo
        # the stacked weights reach the kernel as they lie
        assert not [
            line for line in hlo.splitlines()
            if f" = bf16[40,{k},{n}]" in line and " parameter(" not in line
        ]

    @pytest.mark.parametrize("lanes, pool_rows", [(4, 38912), (4, 77824)],
                             ids=["served", "the-comparison"])
    def test_the_sparse_step_at_minicpm_salas_widths(
            self, topo, lanes, pool_rows):
        """ISSUE 46: ``sparse_decode_attention`` under ``use_flash`` — the
        paged kernel at 2 kv heads x 16 query rows over 8 virtual lanes of
        a 512-entry table of 8 KB pages — for the pool the cell serves and
        the comparison's; no array of the taken rows is written."""
        from jax.sharding import SingleDeviceSharding

        one_chip = SingleDeviceSharding(topo.devices[0])

        def arg(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        bf16, i32 = jnp.bfloat16, jnp.int32
        hlo = jax.jit(lambda *args: A.sparse_decode_attention(
            *args, block_size=BS, kernel_size=32, stride=16, block=64,
            topk=64, init_blocks=1, window=2048, dense_len=8192,
            use_flash=True,
        )).lower(
            arg((lanes, 32, 128), bf16), arg((pool_rows, 2, 128), bf16),
            arg((pool_rows, 2, 128), bf16),
            arg((pool_rows // 16, 2, 128), bf16), arg((lanes, 608), i32),
            arg((lanes,), i32)).compile().as_text()
        assert "_paged_decode_kernel" in hlo and "conditional" not in hlo
        assert f"bf16[{lanes},2,4096,128]" not in hlo
        assert f"bf16[{lanes * 2 * 4096},128]" not in hlo

    @pytest.mark.parametrize("lanes, pool_rows, s", [
        (8, 32768, 1), (4, 16384, 1), (8, 32768, 4)],
        ids=["served", "the-comparison", "a-verify-step"])
    def test_the_latent_step_at_deepseek_v2s_widths(
            self, topo, lanes, pool_rows, s):
        """ISSUE 50: ``latent_decode_attention`` under ``use_flash`` — 128
        heads as the rows of one product against pages of 16 x 576 values,
        32 page operands a grid step over a 256-entry table, a dynamic grid
        — for the pool the cell serves and the comparison's; nothing with
        the tables' span of rows is gathered or widened to float32."""
        from jax.sharding import SingleDeviceSharding

        one_chip = SingleDeviceSharding(topo.devices[0])

        def arg(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        bf16, i32 = jnp.bfloat16, jnp.int32
        hlo = jax.jit(lambda q_lat, q_rope, pool, tables, lengths: (
            A.latent_decode_attention(
                q_lat, q_rope, pool, tables, lengths, block_size=BS,
                q_offset=lengths - s, scale=0.1147, use_flash=True)
        )).lower(
            arg((lanes, s, 128, 512), bf16), arg((lanes, s, 128, 64), bf16),
            arg((pool_rows, 1, 576), bf16), arg((lanes, 256), i32),
            arg((lanes,), i32)).compile().as_text()
        assert "_paged_latent_kernel" in hlo
        assert f"[{lanes},4096,576]" not in hlo
        assert f"[{lanes},4096,1,576]" not in hlo
        assert f"f32[{pool_rows}," not in hlo

    def test_the_retention_step_at_brumbys_widths(self, topo):
        """ISSUE 52: ``ops/retention.power_retention_step_fused`` inside
        the decode chunk it serves — 16 steps of ``paged_decode_forward``
        over Brumby-14B's first 12 layers at the published widths (40 / 8
        heads x 128, int8 matrices, four lanes' float32 states: 1.65 GB
        of pools), the forms an engine that saw a TPU hands it — beside
        the same chunk in the XLA form.  Every pool the program was given
        is the pool it returns, and the kernel's chunk holds no more
        beside them than the XLA form's: no copy of a 137 MB pool."""
        import os
        import sys

        from jax.experimental.compilation_cache import compilation_cache
        from jax.sharding import SingleDeviceSharding

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        for p in (root, os.path.join(root, "benchmark")):
            if p not in sys.path:
                sys.path.insert(0, p)
        from harness import arch
        from harness.child import program_overrides

        from docqa_tpu.config import load_config
        from docqa_tpu.engines import paged
        from docqa_tpu.models.decoder import kernel_forms
        from docqa_tpu.models.quant import init_quantized_decoder_params

        conf = arch.load_cell_config(os.path.join(
            root, "benchmark", "configs", "brumby-14b-l12-int8.json"))
        served = load_config(env={}, overrides=program_overrides(conf))
        cfg, lanes = served.decoder, served.generate.max_concurrent
        one_chip = SingleDeviceSharding(topo.devices[0])

        def described(tree):
            return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=one_chip), tree)

        n_blocks = lanes * -(-cfg.max_seq_len // BS)
        params = described(jax.eval_shape(
            lambda: init_quantized_decoder_params(jax.random.key(0), cfg)))
        pools = described(jax.eval_shape(
            lambda: paged.init_paged_pools(cfg, n_blocks, BS)))
        assert pools["s11"].shape == (lanes, 129, 8, 8256) == (4, 129, 8, 8256)
        pool_bytes = sum(
            int(np.prod(x.shape)) * x.dtype.itemsize for x in pools.values())
        tables = described(jnp.zeros((lanes, n_blocks // lanes), jnp.int32))
        lane = described(jnp.zeros((lanes,), jnp.int32))

        def chunk(forms):
            def program(weights, held, table, tok, lengths):
                def step(t, carry):
                    held, tok, lengths = carry
                    logits, held = paged.paged_decode_forward(
                        weights, cfg, held, table, tok[:, None], lengths,
                        block_size=BS, rope_len=cfg.max_seq_len,
                        kernels=forms)
                    return (held, jnp.argmax(logits[:, 0], -1).astype(
                        jnp.int32), lengths + 1)

                return jax.lax.fori_loop(
                    0, served.generate.decode_chunk, step,
                    (held, tok, lengths))

            return jax.jit(program, donate_argnums=(1,)).lower(
                params, pools, tables, lane, lane).compile()

        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            fused = kernel_forms(cfg, on_tpu=True, mesh=None, block_size=BS)
            assert fused.retention
            compiled = {form: chunk(forms) for form, forms in (
                ("kernel", fused), ("xla", fused._replace(retention=False)))}
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()
        hlo = compiled["kernel"].as_text()
        assert "_retention_step_kernel" in hlo
        assert "_retention_step_kernel" not in compiled["xla"].as_text()
        # no pool is copied, re-laid or widened on its way to the kernel
        assert not [
            line for line in hlo.splitlines()
            if " = f32[4,129,8,8256]" in line and (
                " copy(" in line or " transpose(" in line)]
        memory = {k: c.memory_analysis() for k, c in compiled.items()}
        for m in memory.values():
            # as the chip rests them: 8,256 features in 65 registers
            assert 1.6e9 < pool_bytes <= m.alias_size_in_bytes
            assert m.alias_size_in_bytes < 1.01 * pool_bytes
        assert (memory["kernel"].temp_size_in_bytes
                <= memory["xla"].temp_size_in_bytes + 50e6)
        assert memory["kernel"].temp_size_in_bytes < 0.6e9

    @pytest.mark.parametrize("heads, kv_heads, head_dim", [
        (8, 8, 128), (8, 2, 128), (4, 1, 128), (32, 16, 128), (16, 8, 256),
    ], ids=lambda x: str(x))
    def test_the_retention_step_at_the_geometries_it_admits(
            self, topo, heads, kv_heads, head_dim):
        """What ``retention_kernel_supported`` answers for beside Brumby's
        40 / 8 x 128 (above, in its program): one query head a kv head,
        kv heads short of a register's eight sublanes and past them, a
        head of two registers (257 value rows, a prime: a row a block,
        and features that end on a whole column) — the op alone, lowered
        by Mosaic for the described chip."""
        from jax.sharding import SingleDeviceSharding

        from docqa_tpu.ops import retention

        assert retention.retention_kernel_supported(heads, kv_heads, head_dim)
        one_chip = SingleDeviceSharding(topo.devices[0])
        features = head_dim * (head_dim + 1) // 2

        def arg(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        entries = 2
        pool = arg((entries, head_dim + 1, kv_heads, features), jnp.float32)
        compiled = jax.jit(
            retention.power_retention_step_fused, donate_argnums=(4,),
        ).lower(
            arg((entries, heads, head_dim), jnp.bfloat16),
            arg((entries, kv_heads, head_dim), jnp.bfloat16),
            arg((entries, kv_heads, head_dim), jnp.bfloat16),
            arg((entries, kv_heads), jnp.float32), pool,
            arg((entries,), jnp.int32), arg((), jnp.int32)).compile()
        assert "_retention_step_kernel" in compiled.as_text()
        # the pool is the pool it returns
        pool_bytes = compiled.memory_analysis().alias_size_in_bytes
        assert pool_bytes >= entries * (head_dim + 1) * kv_heads * features * 4

    @pytest.mark.parametrize("config, lanes, stacked", [
        ("trinity-mini-ep8-bf16", 4, ("16,2048,1024", "16,1024,2048")),
        ("deepseek-v2-ep4-bf16", 8, ("40,5120,1536", "40,1536,5120")),
    ], ids=["trinity", "deepseek-v2"])
    def test_the_routed_step_inside_the_decode_chunk_it_serves(
            self, topo, config, lanes, stacked):
        """ISSUE 54: ``ops/grouped.grouped_swiglu_step`` inside 16 steps of
        ``paged_decode_forward`` at the published widths, the forms an
        engine that saw a TPU hands it: one call of the kernel a routed
        layer and no ``gmm``; the stacked expert tensors reach it as the
        program's parameters lie — no quarter of one is sliced or copied
        into fast memory ahead of the call, which is what the compiler did
        to 51 of the 90 operands of Trinity's ``gmm`` calls (PERF.md
        section 5, PR 53: ``slice-done`` of ``bf16[4,1024,2048]``)."""
        import os
        import re
        import sys

        from jax.experimental.compilation_cache import compilation_cache
        from jax.sharding import SingleDeviceSharding

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        for p in (root, os.path.join(root, "benchmark")):
            if p not in sys.path:
                sys.path.insert(0, p)
        from harness import arch
        from harness.child import program_overrides

        from docqa_tpu.config import load_config
        from docqa_tpu.engines import paged
        from docqa_tpu.models.decoder import init_decoder_params, kernel_forms
        from docqa_tpu.models.routed import routed_layers

        conf = arch.load_cell_config(os.path.join(
            root, "benchmark", "configs", config + ".json"))
        served = load_config(env={}, overrides=program_overrides(conf))
        cfg = served.decoder
        assert served.generate.max_concurrent == lanes
        one_chip = SingleDeviceSharding(topo.devices[0])

        def described(tree, dtype=None):
            return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
                x.shape, dtype if dtype and x.ndim > 1 else x.dtype,
                sharding=one_chip), tree)

        n_blocks = lanes * -(-cfg.max_seq_len // BS)
        params = described(jax.eval_shape(
            lambda: init_decoder_params(jax.random.key(0), cfg)),
            jnp.bfloat16)  # as the benchmark's package draws them
        pools = described(jax.eval_shape(
            lambda: paged.init_paged_pools(cfg, n_blocks, BS)))
        tables = described(jnp.zeros((lanes, n_blocks // lanes), jnp.int32))
        lane = described(jnp.zeros((lanes,), jnp.int32))
        forms = kernel_forms(cfg, on_tpu=True, mesh=None, block_size=BS)
        assert forms.grouped

        def program(weights, held, table, tok, lengths):
            def step(t, carry):
                held, tok, lengths = carry
                logits, held, _record = paged.paged_decode_forward(
                    weights, cfg, held, table, tok[:, None], lengths,
                    block_size=BS, rope_len=cfg.max_seq_len, kernels=forms)
                return (held, jnp.argmax(logits[:, 0], -1).astype(
                    jnp.int32), lengths + 1)

            return jax.lax.fori_loop(
                0, served.generate.decode_chunk, step, (held, tok, lengths))

        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            hlo = jax.jit(program, donate_argnums=(1,)).lower(
                params, pools, tables, lane, lane).compile().as_text()
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()
        calls = [line for line in hlo.splitlines()
                 if " custom-call(" in line and "_swiglu_step_kernel" in line]
        assert len(calls) == routed_layers(cfg) > 0
        assert not re.search(r"%gmm[.\d]* = ", hlo)  # the sorted form's call
        # a stacked expert tensor, whole or a leading part of it, is
        # produced by NOTHING in the program: parameters and the loop's
        # own tuple plumbing alone carry it to the kernel
        part = re.compile(
            r" = bf16\[\d+,(%s)\]" % "|".join(
                s.split(",", 1)[1] for s in stacked))
        carried = (" parameter(", " get-tuple-element(", " bitcast(")
        assert not [
            line for line in hlo.splitlines()
            if part.search(line) and not any(w in line for w in carried)]
        assert any(f"bf16[{s}]" in hlo for s in stacked)

    @pytest.mark.parametrize("held, h, f, lanes, k, tile_mb", [
        (16, 2048, 1024, 4, 8, 4.0), (40, 5120, 1536, 8, 6, 3.75),
    ], ids=["trinity", "deepseek-v2"])
    def test_the_routed_step_alone_at_the_published_widths(
            self, topo, held, h, f, lanes, k, tile_mb):
        """The op alone, lowered by Mosaic for the described chip: the
        three operands' tiles, twice buffered, are what the call asks of
        fast memory (an expert of Trinity whole, a quarter of one of
        DeepSeek-V2's), and the stacked tensors enter it as they lie."""
        from jax.sharding import SingleDeviceSharding

        from docqa_tpu.ops import grouped

        one_chip = SingleDeviceSharding(topo.devices[0])

        def arg(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        tf = grouped._tile(f, max(grouped._WEIGHT_TILE // h, 128))
        assert f % tf == 0 and h * tf * 2 / 2 ** 20 == tile_mb
        bf16 = jnp.bfloat16
        compiled = jax.jit(grouped.grouped_swiglu_step).lower(
            arg((lanes, h), bf16), arg((lanes, k), jnp.int32),
            arg((lanes, k), jnp.float32), arg((held, h, f), bf16),
            arg((held, h, f), bf16), arg((held, f, h), bf16)).compile()
        hlo = compiled.as_text()
        assert "_swiglu_step_kernel" in hlo and " sort(" not in hlo
        assert not [
            line for line in hlo.splitlines()
            if (f" = bf16[{held},{h},{f}]" in line
                or f" = bf16[{held},{f},{h}]" in line)
            and " parameter(" not in line]
        # nothing of the experts' size beside the arguments
        assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
