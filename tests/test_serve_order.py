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

And which chunks are dispatched at all (ISSUE 36): one only if some
occupied slot can still be owed a token after the chunk in flight.  A lane
that ends on its budget leaves no overshoot chunk behind; one that ends on
EOS, which the host cannot foresee, still does.
"""

import dataclasses
import threading
import time
from types import SimpleNamespace

import pytest

from docqa_tpu.config import DecoderConfig, GenerateConfig, QoSConfig
from docqa_tpu.engines import serve
from docqa_tpu.engines.generate import GenerateEngine
from docqa_tpu.engines.serve import ContinuousBatcher
from docqa_tpu.runtime.metrics import DEFAULT_REGISTRY

CFG = DecoderConfig(
    vocab_size=128, hidden_dim=64, num_layers=2, num_heads=4,
    num_kv_heads=2, head_dim=16, mlp_dim=128, max_seq_len=512,
    dtype="float32",
)
# speculative_k defaults to 4: the plain chunk program has to be asked for
PLAIN = GenerateConfig(temperature=0.0, eos_id=2, speculative_k=0)
SPEC = dataclasses.replace(PLAIN, speculative_k=4)
COUNTERS = ("serve_prefill_ahead", "serve_admit_rounds",
            "serve_decode_chunks", "serve_decode_chunks_stale",
            "serve_decode_chunks_skipped")
CHUNK = 4


@pytest.fixture(scope="module")
def engines():
    plain = GenerateEngine(CFG, PLAIN, seed=7)
    return {"plain": plain,
            "spec": GenerateEngine(CFG, SPEC, params=plain.params)}


def _ctx(n, seed=3):
    return [(seed + i * 7) % 120 + 3 for i in range(n)]


def _full(solo, n, max_new, seed=3):
    """(prompt of ``n`` tokens, its greedy output): the first seed from
    ``seed`` on whose continuation runs the whole budget — it ends on its
    BUDGET, which is what the host can count on, and not on an EOS."""
    for s in range(seed, seed + 40):
        out = solo.generate_ids([_ctx(n, seed=s)], max_new_tokens=max_new)[0]
        if len(out) == max_new:
            return _ctx(n, seed=s), out
    pytest.fail(f"no prompt of {n} tokens runs {max_new} tokens from {seed}")


def _counters():
    return {n: DEFAULT_REGISTRY.counter(n).value for n in COUNTERS}


def _gained(before):
    return {n: v - before[n] for n, v in _counters().items()}


def _wait(cond, what):
    t0 = time.monotonic()
    while not cond():
        assert time.monotonic() - t0 < 120, f"timed out waiting for {what}"
        time.sleep(0.005)


class Worker:
    """The worker's spine stages in order, the snapshot of every chunk it
    fetched (``snaps``) with the tokens it gave each request of it
    (``gave``, by ``id``, once the fetch is processed), and a gate: the
    worker stops inside its ``hold``-th
    ``serve_decode`` dispatch until ``release()`` — a lane is live, its
    chunk not yet dispatched, and whatever the test submits meanwhile is
    queued when the next iteration pops."""

    def __init__(self, monkeypatch, batcher, hold=1):
        self.stages, self.snaps, self.tables, self.gave = [], [], [], []
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
            had = {id(r): len(r.tokens) for r in snap if r is not None}
            ok = process(packed, snap)
            self.gave.append({
                id(r): len(r.tokens) - had[id(r)]
                for r in snap if r is not None
            })
            return ok

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

    def wait_fetched(self, n):
        """Until ``n`` chunks have been fetched AND processed: what the
        counters gained by then is final."""
        _wait(lambda: len(self.gave) >= n, f"{n} fetched chunks")


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


def _ends_on_eos(solo, prompt, lo, hi):
    """(eos id, tokens before it): a token of ``prompt``'s greedy output
    whose FIRST occurrence is at an index in [lo, hi) — as an engine's
    ``eos_id`` it ends the request there, on nothing the host foresees."""
    out = solo.generate_ids([prompt], max_new_tokens=hi)[0]
    for k in range(lo, min(hi, len(out))):
        if out[k] not in out[:k]:
            return out[k], out[:k]
    pytest.fail(f"no token of {out} first occurs in [{lo}, {hi})")


@pytest.mark.parametrize("ended_by", ["budget", "eos"])
def test_slot_refilled_across_the_drain_reads_the_new_prompts_rows(
    engines, monkeypatch, ended_by
):
    """The pool holds one sequence, so the next request MUST take the
    blocks the first one left, and reads its own rows there.

    ``budget``: 1 + 4 + 4 tokens at chunk 4.  The request is finished
    when its second chunk is fetched, the host knows it when that chunk
    is in flight, and no third is dispatched: the next round meets an
    empty pipeline.  ``eos``: the request ends inside its second chunk on
    a token nobody foresaw, its third chunk (dispatched ahead: stale
    writes into rows it no longer owns) is pending while the worker idles
    — the PR-9 guarantee: it is fetched before the prefill that
    re-populates those rows is dispatched."""
    base = engines["plain"]
    p_old, p_new = _ctx(4), _ctx(90, seed=23)
    if ended_by == "eos":
        eos, want_old = _ends_on_eos(base, p_old, CHUNK + 1, 2 * CHUNK + 1)
        eng = GenerateEngine(
            CFG, dataclasses.replace(PLAIN, eos_id=eos), params=base.params
        )
        old_new = 40
    else:
        eng, old_new = base, 1 + 2 * CHUNK
        want_old = base.generate_ids([p_old], max_new_tokens=old_new)[0]
    want = eng.generate_ids([p_new], max_new_tokens=20)[0]
    assert len(want) == 20  # the second request ends on its budget
    b = ContinuousBatcher(
        eng, n_slots=2, chunk=CHUNK, cache_len=128,
        kv_pool_tokens=128, prefix_cache=False,
    )
    try:
        w = Worker(monkeypatch, b, hold=0)  # no gate: log only
        before = _counters()
        old = b.submit_ids(p_old, max_new_tokens=old_new)
        assert old.result(timeout=300) == want_old
        w.wait_fetched(2)
        after_old = _gained(before)
        old_blocks = {blk for t in w.tables for row in t if row for blk in row}
        n_tables = len(w.tables)
        new = b.submit_ids(p_new, max_new_tokens=20)
        assert new.result(timeout=300) == want
    finally:
        b.stop()
    assert after_old["serve_decode_chunks"] == 2
    assert after_old["serve_decode_chunks_stale"] == 0
    i = w.stages.index("serve_prefill", 1)
    if ended_by == "budget":
        assert n_tables == 2  # dispatched: two chunks, no third
        assert after_old["serve_decode_chunks_skipped"] == 1
        # nothing was in flight: the new round's prefill follows the
        # fetch of the last REAL chunk, and nothing is stale afterwards
        assert w.stages[i - 1] == "serve_decode_chunk", w.stages
        assert w.stages[:i].count("serve_decode_chunk") == 2
        assert _gained(before)["serve_decode_chunks_stale"] == 0
    else:
        assert n_tables == 3  # the third went out ahead of the EOS
        assert after_old["serve_decode_chunks_skipped"] == 0
        # the drain of the stale chunk comes before the new round's prefill
        assert w.stages[i - 1] == "serve_decode_chunk", w.stages
        assert w.stages[:i].count("serve_decode_chunk") == 3
        assert _gained(before)["serve_decode_chunks_stale"] == 1
        assert w.gave[2] == {id(old._req): 0}
    new_blocks = {
        blk for t in w.tables[n_tables:] for row in t if row for blk in row
    }
    assert old_blocks & new_blocks
    assert b._alloc.blocks_in_use == 0


@pytest.mark.parametrize("kind", ["plain", "spec"])
@pytest.mark.parametrize("max_new", [1 + 2 * CHUNK, 1 + 2 * CHUNK + 1])
def test_a_further_chunk_goes_out_only_for_a_token_still_owed(
    engines, monkeypatch, kind, max_new
):
    """A budget of 1 + 2 chunks is met by two chunks, one token more
    needs the third: dispatched in the second case, withheld in the
    first, an overshoot in neither.  The speculative program emits AT
    LEAST a chunk a dispatch, so there the host may learn only from the
    fetch that a lane is done: each drain to empty is then one withheld
    chunk or one stale chunk, never both and never neither."""
    prompt, want = _full(engines["plain"], 12, max_new, seed=5)
    b = ContinuousBatcher(
        engines[kind], n_slots=2, chunk=CHUNK, cache_len=128,
        prefix_cache=False,
    )
    try:
        w = Worker(monkeypatch, b, hold=0)
        before = _counters()
        assert b.submit_ids(prompt, max_new_tokens=max_new).result(
            timeout=300
        ) == want
        # an overshoot chunk is fetched by the next admission's drain
        assert b.submit_ids(prompt, max_new_tokens=2).result(
            timeout=300
        ) == want[:2]
    finally:
        b.stop()
    gained = _gained(before)
    # two drains to empty (the second request: one chunk for its second
    # token, none after)
    assert (gained["serve_decode_chunks_skipped"]
            + gained["serve_decode_chunks_stale"]) == 2
    if kind == "plain":
        assert gained["serve_decode_chunks"] == -(-(max_new - 1) // CHUNK) + 1
        assert gained["serve_decode_chunks_stale"] == 0
        assert all(any(g.values()) for g in w.gave)
    assert b._alloc.blocks_in_use == 0


@pytest.mark.parametrize("kind", ["plain", "spec"])
def test_chunks_go_out_while_any_lane_of_the_round_is_owed(
    engines, monkeypatch, kind
):
    """Three budgets in one round: the lanes that are done ride along in
    the chunks the longest one is owed, and the dispatches stop with it."""
    solo = engines["plain"]
    budgets = (6, 1 + 2 * CHUNK, 19)
    prompts, want = zip(*(
        _full(solo, 10 + 9 * i, n, seed=5 + i) for i, n in enumerate(budgets)
    ))
    # a round waits for its slots to fill: the three are admitted as one
    gen = dataclasses.replace(engines[kind].gen, admit_hold_ms=20000.0)
    b = ContinuousBatcher(
        GenerateEngine(CFG, gen, params=solo.params), n_slots=3, chunk=CHUNK,
        cache_len=128, prefix_cache=False,
    )
    try:
        w = Worker(monkeypatch, b, hold=0)
        before = _counters()
        handles = [b.submit_ids(p, max_new_tokens=n)
                   for p, n in zip(prompts, budgets)]
        assert [h.result(timeout=300) for h in handles] == list(want)
        # an overshoot chunk is fetched by the next admission's drain
        assert b.submit_ids(prompts[0], max_new_tokens=1).result(
            timeout=300
        ) == want[0][:1]
    finally:
        b.stop()
    gained = _gained(before)
    assert gained["serve_admit_rounds"] == 2
    # one drain to empty.  (The one-token request is retired by its
    # prefill: its chunk, dispatched before that fetch, is never fetched.)
    assert (gained["serve_decode_chunks_skipped"]
            + gained["serve_decode_chunks_stale"]) == 1
    if kind == "plain":
        n_chunks = -(-(max(budgets) - 1) // CHUNK)
        assert gained["serve_decode_chunks"] == n_chunks
        assert gained["serve_decode_chunks_stale"] == 0
        # the third chunk goes out for the longest lane alone and the
        # other two, finished inside the second, ride along in it
        assert [sum(r is not None for r in snap) for snap in w.snaps] == [
            3, 3, 3, 1, 1
        ]
        assert all(any(g.values()) for g in w.gave)
    assert b._alloc.blocks_in_use == 0


@pytest.mark.parametrize("kind", ["plain", "spec"])
@pytest.mark.parametrize("n_slots", [1, 2])
def test_arrival_under_the_last_real_chunk_waits_for_its_drain(
    engines, monkeypatch, kind, n_slots
):
    """A request arrives while the resident lane's last chunk is in
    flight and nothing is owed beyond it.  With a free slot it is popped
    at once and sits out the drain of that chunk, which retires the
    lane; with none it waits for that fetch to free the slot, and the
    worker sends no chunk meanwhile.  Either way its prefill follows the
    fetch of the last real chunk and it decodes exactly."""
    solo = engines["plain"]
    old_new = 1 + 2 * CHUNK
    p_old, want_old = _full(solo, 20, old_new, seed=7)
    p_new, want_new = _full(solo, 33, 14, seed=19)
    b = ContinuousBatcher(
        engines[kind], n_slots=n_slots, chunk=CHUNK, cache_len=128,
        prefix_cache=False,
    )
    try:
        w = Worker(monkeypatch, b, hold=2)  # inside the second dispatch
        before = _counters()
        old = b.submit_ids(p_old, max_new_tokens=old_new)
        w.wait_held()
        new = b.submit_ids(p_new, max_new_tokens=14)
        w.release()
        assert old.result(timeout=300) == want_old
        assert new.result(timeout=300) == want_new
    finally:
        b.stop()
    i = w.stages.index("serve_prefill", 1)
    # both of the old lane's chunks were fetched before the new prefill,
    # and no third one was dispatched between them
    assert w.stages[:i] == [
        "serve_prefill", "serve_decode", "serve_prefill_fetch",
        "serve_decode", "serve_decode_chunk", "serve_decode_chunk",
    ], w.stages[:i + 1]
    assert w.gave[1][id(old._req)] > 0
    if kind == "plain":
        # n_slots 1: withheld behind the old lane, then behind the new;
        # n_slots 2: the drain retired the old lane before any decision
        assert _gained(before)["serve_decode_chunks_skipped"] == (
            2 if n_slots == 1 else 1
        )
        assert _gained(before)["serve_decode_chunks_stale"] == 0
    assert b._alloc.blocks_in_use == 0


def test_a_resumed_request_is_judged_on_its_full_budget(engines, monkeypatch):
    """A preempted lane comes back with the tokens it had folded into its
    prompt; its budget counts them, as ``len(req.tokens)`` does.  It gets
    every chunk it is owed, and none past its budget: the last chunk that
    holds it gives it tokens."""
    solo = engines["plain"]
    p_bg, want_bg = _full(solo, 40, 30)
    p_ia = [(5 + i * 3) % 120 + 4 for i in range(64)]
    want_ia = solo.generate_ids([p_ia], max_new_tokens=8)[0]
    # 8 blocks: the background lane at 4 and the 5 of the interactive
    # arrival cannot coexist, so admitting the second evicts the first
    b = ContinuousBatcher(
        solo, n_slots=2, chunk=CHUNK, cache_len=128, kv_block_size=16,
        kv_pool_tokens=128, prefix_cache=False,
        qos=QoSConfig(preemption="on", aging_floor_s=0.0),
    )
    try:
        w = Worker(monkeypatch, b, hold=0)
        preempted = DEFAULT_REGISTRY.counter("qos_preempted").value
        before = _counters()
        bg = b.submit_ids(p_bg, max_new_tokens=30, req_class="background")
        _wait(lambda: b.kv_block_occupancy()["blocks_used"] >= 4,
              "the background lane to hold 4 blocks")
        assert not bg._req.done.is_set()
        ia = b.submit_ids(p_ia, max_new_tokens=8, req_class="interactive")
        assert ia.result(timeout=300) == want_ia
        assert bg.result(timeout=300) == want_bg
    finally:
        b.stop()
    assert DEFAULT_REGISTRY.counter("qos_preempted").value > preempted
    held_bg = [g[id(bg._req)] for g in w.gave if id(bg._req) in g]
    # every chunk gave it a full chunk's tokens but the one in flight at
    # its preemption (dropped) and the last (what its budget still owed)
    assert held_bg[-1] > 0
    assert held_bg.count(0) <= 1
    assert sum(held_bg) == 30 - 2  # two first tokens came from prefills
    assert _gained(before)["serve_decode_chunks_skipped"] >= 1
    assert b._alloc.blocks_in_use == 0


@pytest.fixture(scope="module")
def stopped(engines):
    """A batcher whose worker is gone: its slot state is the test's."""
    b = ContinuousBatcher(
        engines["plain"], n_slots=2, chunk=CHUNK, cache_len=128,
        prefix_cache=False,
    )
    b.stop()
    return b


def _lane(n_tokens):
    return SimpleNamespace(tokens=[5] * n_tokens)


@pytest.mark.parametrize("tokens,budget,in_flight,owed", [
    (1, 2, "none", True),           # just admitted, one more token due
    (0, 9, "none", True),           # admitted, first token not fetched yet
    (9, 9, "none", False),          # nothing pending and nothing due
    (5, 9, "same", False),          # 5 + 4 in flight = the budget
    (5, 10, "same", True),          # ... one short of it
    (5, 9, "other", True),          # the chunk in flight is not this lane's
    (17, 30, "none", True),         # resumed with 17 of 30: short of it
    (25, 30, "same", True),         # resumed, 25 + 4 in flight < 30
    (26, 30, "same", False),        # resumed, 26 + 4 in flight = 30
])
def test_owed_rule(stopped, tokens, budget, in_flight, owed):
    b, lane = stopped, _lane(tokens)
    b._slot_req[1] = lane
    b._slot_budget[1] = budget
    snap = {"none": None, "same": [None, lane],
            "other": [None, _lane(tokens)]}[in_flight]
    try:
        assert b._any_lane_owed(snap) is owed
        # a free slot is owed nothing; a second lane that is owed decides
        b._slot_req[0] = _lane(0)
        b._slot_budget[0] = 3
        assert b._any_lane_owed(snap) is True
    finally:
        b._slot_req[0] = b._slot_req[1] = None
