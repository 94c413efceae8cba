"""Prefill first: the order of dispatch inside one worker iteration.

When a round is being admitted, its prefill is the next device program and
the iteration's one decode chunk follows it, carrying the lanes that were
live and the lanes just admitted.  What has to keep holding:

* token streams of the live and the new lanes equal the solo engine's
  (plain and ``spec_k`` 4, cold and warm prefill groups);
* a lane whose first token retires it (EOS, budget 1) is in the snapshot
  of the chunk dispatched before its first-token fetch and gets nothing
  from that chunk;
* a slot retired and refilled across the drain reads the new prompt's
  rows (the PR-9 re-use guarantee);
* zero leaked blocks after stop.
"""

import dataclasses
import threading

import pytest

from docqa_tpu.config import DecoderConfig, GenerateConfig
from docqa_tpu.engines import serve
from docqa_tpu.engines.generate import GenerateEngine
from docqa_tpu.engines.serve import ContinuousBatcher
from docqa_tpu.runtime.metrics import DEFAULT_REGISTRY

CFG = DecoderConfig(
    vocab_size=128, hidden_dim=64, num_layers=2, num_heads=4,
    num_kv_heads=2, head_dim=16, mlp_dim=128, max_seq_len=512,
    dtype="float32",
)
PLAIN = GenerateConfig(temperature=0.0, eos_id=2)
SPEC = dataclasses.replace(PLAIN, speculative_k=4)
COUNTERS = ("serve_prefill_ahead", "serve_admit_rounds",
            "serve_decode_chunks_stale")


@pytest.fixture(scope="module")
def engines():
    plain = GenerateEngine(CFG, PLAIN, seed=7)
    return {"plain": plain,
            "spec": GenerateEngine(CFG, SPEC, params=plain.params)}


def _ctx(n, seed=3):
    return [(seed + i * 7) % 120 + 3 for i in range(n)]


def _counters():
    return {n: DEFAULT_REGISTRY.counter(n).value for n in COUNTERS}


def _gained(before):
    return {n: v - before[n] for n, v in _counters().items()}


class Worker:
    """The worker's spine stages in order, the snapshot of every chunk it
    fetched, and a gate: the worker stops inside its ``hold``-th
    ``serve_decode`` dispatch until ``release()`` — a lane is live, its
    chunk not yet dispatched, and whatever the test submits meanwhile is
    queued when the next iteration pops."""

    def __init__(self, monkeypatch, batcher, hold=1):
        self.stages, self.snaps, self.tables = [], [], []
        self.batcher = batcher
        self._hold = hold
        self._held = threading.Event()
        self._go = threading.Event()
        run, submit = serve.spine_run, serve.spine_submit
        process = batcher._process_chunk

        def spine_run(stage, fn, *a, **kw):
            self._note(stage)
            return run(stage, fn, *a, **kw)

        def spine_submit(stage, fn, *a, **kw):
            self._note(stage)
            return submit(stage, fn, *a, **kw)

        def process_chunk(packed, snap):
            self.snaps.append(list(snap))
            return process(packed, snap)

        monkeypatch.setattr(serve, "spine_run", spine_run)
        monkeypatch.setattr(serve, "spine_submit", spine_submit)
        monkeypatch.setattr(batcher, "_process_chunk", process_chunk)

    def _note(self, stage):
        if threading.current_thread() is not self.batcher._worker:
            return
        self.stages.append(stage)
        if stage != "serve_decode":
            return
        # block tables of the lanes this chunk advances, slot by slot
        self.tables.append([
            None if t is None else list(t.blocks)
            for t in self.batcher._slot_table
        ])
        if len(self.tables) == self._hold:
            self._held.set()
            assert self._go.wait(120), "the test never released the worker"

    def wait_held(self):
        assert self._held.wait(120), "the worker never reached its chunk"

    def release(self):
        self._go.set()


@pytest.mark.parametrize("kind", ["plain", "spec"])
def test_prefill_goes_ahead_of_the_live_lanes_chunk(
    engines, monkeypatch, kind
):
    solo = engines["plain"]
    p_live, p_new = _ctx(40), _ctx(70, seed=11)
    want = [solo.generate_ids([p], max_new_tokens=n)[0]
            for p, n in ((p_live, 60), (p_new, 24))]
    b = ContinuousBatcher(engines[kind], n_slots=4, chunk=4, cache_len=512)
    try:
        w = Worker(monkeypatch, b)
        before = _counters()
        live = b.submit_ids(p_live, max_new_tokens=60)
        w.wait_held()  # admitted into an idle batcher, first chunk held
        assert _gained(before)["serve_prefill_ahead"] == 0
        new = b.submit_ids(p_new, max_new_tokens=24)
        w.release()
        got = [live.result(timeout=300), new.result(timeout=300)]
    finally:
        b.stop()
    assert got == want
    # iteration 1: prefill, chunk, first-token fetch (admission into an
    # idle batcher); iteration 2 drains that chunk, then the round's
    # prefill goes out AHEAD of the one chunk, the fetch after both
    assert w.stages[:7] == [
        "serve_prefill", "serve_decode", "serve_prefill_fetch",
        "serve_decode_chunk",
        "serve_prefill", "serve_decode", "serve_prefill_fetch",
    ], w.stages[:10]
    # the chunk dispatched behind the second prefill carries both lanes
    first, second = w.snaps[0], w.snaps[1]
    assert [r for r in first if r is not None] == [live._req]
    assert {id(r) for r in second if r is not None} == {
        id(live._req), id(new._req)
    }
    gained = _gained(before)
    assert gained["serve_admit_rounds"] == 2
    assert gained["serve_prefill_ahead"] == 1
    assert b._alloc.blocks_in_use == 0


@pytest.mark.parametrize("kind", ["plain", "spec"])
def test_warm_and_cold_groups_beside_a_live_lane_match_solo(
    engines, monkeypatch, kind
):
    """A round with a warm (prefix-hit) group and a cold one, admitted
    beside a live lane: the chunk is then the third program in flight."""
    solo = engines["plain"]
    ctx = _ctx(260, seed=11)
    p_seed, p_warm = ctx + [10], ctx + [11, 12]
    p_live, p_cold = _ctx(50, seed=5), _ctx(33, seed=17)
    want = [solo.generate_ids([p], max_new_tokens=n)[0]
            for p, n in ((p_live, 48), (p_warm, 16), (p_cold, 16))]
    b = ContinuousBatcher(engines[kind], n_slots=4, chunk=4, cache_len=512)
    try:
        b.submit_ids(p_seed, max_new_tokens=4, prefix_key="s").result(
            timeout=300
        )
        w = Worker(monkeypatch, b)
        before = _counters()
        live = b.submit_ids(p_live, max_new_tokens=48)
        w.wait_held()
        warm = b.submit_ids(p_warm, max_new_tokens=16, prefix_key="s")
        cold = b.submit_ids(p_cold, max_new_tokens=16)
        w.release()
        got = [h.result(timeout=300) for h in (live, warm, cold)]
        assert b._prefix_cache.stats()["hits"] >= 1
    finally:
        b.stop()
    assert got == want
    assert _gained(before)["serve_prefill_ahead"] >= 1
    held = {id(r) for snap in w.snaps[1:3] for r in snap if r is not None}
    assert {id(warm._req), id(cold._req)} <= held
    assert b._alloc.blocks_in_use == 0


@pytest.mark.parametrize("kind", ["plain", "spec"])
@pytest.mark.parametrize("case", ["first_token_eos", "budget_1"])
def test_lane_retired_by_its_first_token_takes_nothing_from_the_chunk(
    engines, monkeypatch, kind, case
):
    base = engines["plain"]
    p_live, p_short, p_next = _ctx(40), _ctx(21, seed=9), _ctx(30, seed=13)
    first = base.generate_ids([p_short], max_new_tokens=1)[0][0]
    gen = PLAIN if kind == "plain" else SPEC
    if case == "first_token_eos":
        gen = dataclasses.replace(gen, eos_id=first)
        solo = GenerateEngine(
            CFG, dataclasses.replace(PLAIN, eos_id=first), params=base.params
        )
        short_new, short_want = 16, []
    else:
        solo = base
        short_new, short_want = 1, [first]
    eng = GenerateEngine(CFG, gen, params=base.params)
    want_live = solo.generate_ids([p_live], max_new_tokens=40)[0]
    want_next = solo.generate_ids([p_next], max_new_tokens=12)[0]
    b = ContinuousBatcher(eng, n_slots=2, chunk=4, cache_len=512)
    try:
        w = Worker(monkeypatch, b)
        live = b.submit_ids(p_live, max_new_tokens=40)
        w.wait_held()
        short = b.submit_ids(p_short, max_new_tokens=short_new)
        w.release()
        assert short.result(timeout=300) == short_want
        # the slot it left is refilled and decodes the new prompt
        nxt = b.submit_ids(p_next, max_new_tokens=12)
        assert nxt.result(timeout=300) == want_next
        assert live.result(timeout=300) == want_live
    finally:
        b.stop()
    # it was in the snapshot of the chunk dispatched before its fetch,
    # and that chunk gave it nothing: no token after (or instead of) the
    # first, no rewind
    assert any(r is short._req for snap in w.snaps for r in snap)
    assert short._req.tokens == short_want
    assert b._alloc.blocks_in_use == 0


def test_slot_refilled_across_the_drain_reads_the_new_prompts_rows(
    engines, monkeypatch
):
    """The PR-9 guarantee: the overshoot chunk of a retired lane (stale
    writes into rows it no longer owns) is fetched before the prefill that
    re-populates those rows is dispatched.  The pool holds one sequence,
    so the next request MUST take the blocks the first one left."""
    solo = engines["plain"]
    p_old, p_new = _ctx(4), _ctx(90, seed=23)
    want = solo.generate_ids([p_new], max_new_tokens=20)[0]
    b = ContinuousBatcher(
        engines["plain"], n_slots=2, chunk=4, cache_len=128,
        kv_pool_tokens=128, prefix_cache=False,
    )
    try:
        w = Worker(monkeypatch, b, hold=0)  # no gate: log only
        before = _counters()
        # 1 + 4 + 4 tokens: retired when its second chunk is processed,
        # its third (dispatched ahead) still pending while the worker idles
        old = b.submit_ids(p_old, max_new_tokens=9)
        old.result(timeout=300)
        old_blocks = {blk for t in w.tables for row in t if row for blk in row}
        n_tables = len(w.tables)
        new = b.submit_ids(p_new, max_new_tokens=20)
        assert new.result(timeout=300) == want
    finally:
        b.stop()
    assert _gained(before)["serve_decode_chunks_stale"] >= 1
    # the drain of the stale chunk comes before the new round's prefill
    i = w.stages.index("serve_prefill", 1)
    assert w.stages[i - 1] == "serve_decode_chunk", w.stages
    new_blocks = {
        blk for t in w.tables[n_tables:] for row in t if row for blk in row
    }
    assert old_blocks & new_blocks
    assert b._alloc.blocks_in_use == 0
