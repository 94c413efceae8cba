"""Admission gathers for arrivals the caller can see (ISSUE 39).

``ContinuousBatcher.expect_arrival`` is how the HTTP layer says that an ask
is on its way (``service/app.py:_ask_preamble``); ``generate.admit_hold_ms``
stays 0 throughout, except where a case says otherwise.

* four asks counted as expected and trickling in are ONE round, with the
  tokens four rounds give;
* a request with nothing expected is admitted without the gather, and a
  request that is the only one counted waits for nobody else;
* an expected ask that never submits ends the wait when the count drops,
  and one that never leaves ends it on the bound — the only case
  ``serve_admit_expected_expired`` counts;
* lanes that are live never decode later because a round gathers;
* the HTTP layer's count is back to zero whatever an ask ends in.
"""

import asyncio
import threading
import time

import pytest

from docqa_tpu import obs
from docqa_tpu.config import DecoderConfig, GenerateConfig, load_config
from docqa_tpu.engines import serve
from docqa_tpu.runtime.metrics import DEFAULT_REGISTRY

CFG = DecoderConfig(
    vocab_size=64,
    hidden_dim=32,
    num_layers=1,
    num_heads=2,
    num_kv_heads=1,
    head_dim=16,
    mlp_dim=64,
    max_seq_len=512,
    dtype="float32",
)
# eos_id outside the vocabulary: a lane ends on its budget and on nothing else
GEN = GenerateConfig(temperature=0.0, prefill_buckets=(16,), eos_id=1000)
TRICKLE_S = 0.03
PROMPTS = [[3 + j for j in range(5 + 3 * i)] for i in range(4)]
BOUND_S = serve._EXPECTED_ARRIVAL_BOUND_S


@pytest.fixture(scope="module")
def engine():
    from docqa_tpu.engines.generate import GenerateEngine

    return GenerateEngine(CFG, GEN, seed=3)


@pytest.fixture()
def batcher(engine):
    b = serve.ContinuousBatcher(engine, n_slots=4, chunk=4, cache_len=512)
    # every shape a case uses is built before anything is counted or timed
    for h in [b.submit_ids(p, max_new_tokens=6) for p in PROMPTS]:
        h.result(timeout=240)
    wait_idle(b)
    yield b
    b.stop()


def wait_idle(b):
    t_end = time.monotonic() + 60
    while b.n_active or b.n_queued or b.n_admitting:
        assert time.monotonic() < t_end
        time.sleep(0.005)
    time.sleep(0.02)  # the worker is back in its idle wait


def gained(*names):
    """A function that says what the named counters (``*_ms``: the
    histogram's count) gained since this call."""
    def read():
        return [
            DEFAULT_REGISTRY.histogram(n).count if n.endswith("_ms")
            else DEFAULT_REGISTRY.counter(n).value
            for n in names
        ]

    before = read()
    return lambda: [b - a for a, b in zip(before, read())]


def trickle(b, counted):
    """Four requests, one every TRICKLE_S, the way the HTTP layer sends
    them when ``counted``: all four expected before the first is submitted,
    each reported once it is in the queue.  Returns the tokens of each."""
    arrived = [b.expect_arrival() for _ in PROMPTS] if counted else []
    handles = []
    for i, p in enumerate(PROMPTS):
        handles.append(b.submit_ids(p, max_new_tokens=6))
        if counted:
            arrived[i]()
        time.sleep(TRICKLE_S)
    return handles, [h.result(timeout=240) for h in handles]


def test_four_expected_asks_are_one_round_with_the_same_tokens(batcher):
    assert batcher._admit_hold_s == 0.0
    since = gained("serve_admit_rounds", "serve_admitted")
    _handles, want = trickle(batcher, counted=False)
    n_rounds, n_admitted = since()
    assert n_admitted == 4 and n_rounds >= 2  # the control: hold 0, no count
    wait_idle(batcher)
    since = gained("serve_admit_rounds", "serve_admitted",
                   "serve_admit_expected_rounds",
                   "serve_admit_expected_expired")
    _handles, got = trickle(batcher, counted=True)
    assert since() == [1, 4, 1, 0]
    assert got == want  # same answers, request by request


def test_nothing_expected_is_admitted_without_the_gather(batcher):
    since = gained("serve_admit_rounds", "serve_admit_gather_ms",
                   "serve_admit_expected_rounds")
    t0 = time.perf_counter()
    h = batcher.submit_ids(PROMPTS[0], max_new_tokens=2)
    h.result(timeout=240)
    took = time.perf_counter() - t0
    assert since() == [1, 0, 0]  # today's default: the loop is not entered
    assert h._req.t_pop - h._req.t_submit < BOUND_S / 4
    assert took < BOUND_S  # the whole answer, inside one bound


def test_the_only_counted_ask_waits_for_nobody(batcher):
    """The HTTP shape of a request alone: counted, submitted, reported.
    The worker may pop it before the report lands and then waits for the
    report itself, microseconds — never for the bound."""
    since = gained("serve_admit_rounds", "serve_admit_expected_expired")
    ctx = obs.new_trace("alone")
    arrived = batcher.expect_arrival()
    with ctx.activate():
        h = batcher.submit_ids(PROMPTS[0], max_new_tokens=2)
    arrived()
    h.result(timeout=240)
    obs.finish(ctx)
    assert since() == [1, 0]
    spans = {s.name: s for s in ctx.trace.snapshot_spans()}
    hold = spans["serve_admit_hold"]
    assert hold.t_end - hold.t_start < BOUND_S / 4
    if "serve_admit_gather" in spans:  # popped before the report
        assert spans["serve_admit_gather"].attrs["ended_by"] == "quiet"


def test_an_expected_ask_that_never_submits_ends_the_wait(batcher):
    """Routed extractive, shed, failed: it leaves the count without a
    submit, and the round goes when the count drops — not on the bound."""
    since = gained("serve_admit_rounds", "serve_admitted",
                   "serve_admit_expected_rounds",
                   "serve_admit_expected_expired")
    ctx = obs.new_trace("first")
    first, never = batcher.expect_arrival(), batcher.expect_arrival()
    with ctx.activate():
        h = batcher.submit_ids(PROMPTS[0], max_new_tokens=2)
    first()
    time.sleep(TRICKLE_S)
    assert not h.started  # the round is waiting for the second ask
    t0 = time.perf_counter()  # the spans' clock
    never()
    never()  # a second report changes nothing
    h.result(timeout=240)
    obs.finish(ctx)
    assert since() == [1, 1, 1, 0]
    assert batcher._expected == 0
    gather = [s for s in ctx.trace.snapshot_spans()
              if s.name == "serve_admit_gather"]
    assert [s.attrs["ended_by"] for s in gather] == ["quiet"]
    # 2 if the worker looked before the first ask reported, else 1
    assert gather[0].attrs["expected"] in (1, 2)
    # the wait ended because the count dropped: after `never()`, which
    # found the round not started, and not on the bound
    assert gather[0].t_end >= t0
    assert gather[0].t_end - gather[0].t_start < BOUND_S


def test_the_bound_ends_a_wait_whose_ask_never_leaves(batcher):
    since = gained("serve_admit_rounds", "serve_admit_expected_rounds",
                   "serve_admit_expected_expired")
    ctx = obs.new_trace("held")
    first, stuck = batcher.expect_arrival(), batcher.expect_arrival()
    try:
        with ctx.activate():
            h = batcher.submit_ids(PROMPTS[0], max_new_tokens=2)
        first()
        h.result(timeout=240)
        obs.finish(ctx)
    finally:
        stuck()
    assert since() == [1, 1, 1]
    gather = [s for s in ctx.trace.snapshot_spans()
              if s.name == "serve_admit_gather"]
    assert [s.attrs["ended_by"] for s in gather] == ["expired"]
    assert BOUND_S <= gather[0].t_end - gather[0].t_start < 5 * BOUND_S


def test_every_arrival_restarts_the_bound(batcher, monkeypatch):
    """Four asks 0.6 of a bound apart are still one round: the bound is
    an arrival's, not the round's."""
    monkeypatch.setattr(serve, "_EXPECTED_ARRIVAL_BOUND_S", 0.1)
    since = gained("serve_admit_rounds", "serve_admitted",
                   "serve_admit_expected_expired")
    arrived = [batcher.expect_arrival() for _ in PROMPTS]
    handles = []
    for p, fn in zip(PROMPTS, arrived):
        handles.append(batcher.submit_ids(p, max_new_tokens=2))
        fn()
        time.sleep(0.06)
    for h in handles:
        h.result(timeout=240)
    assert since() == [1, 4, 0]


def test_the_order_of_admission_is_the_order_of_submission(batcher):
    handles, _tokens = trickle(batcher, counted=True)
    pops = [h._req.t_pop for h in handles]
    assert pops == sorted(pops)


def test_stop_ends_an_expected_wait(engine, monkeypatch):
    monkeypatch.setattr(serve, "_EXPECTED_ARRIVAL_BOUND_S", 600.0)
    b = serve.ContinuousBatcher(engine, n_slots=4, chunk=4, cache_len=512)
    b.expect_arrival()  # never reported
    h = b.submit_ids(PROMPTS[0], max_new_tokens=2)
    time.sleep(0.05)  # the worker is inside the gather now
    t0 = time.perf_counter()
    stopper = threading.Thread(target=b.stop)
    stopper.start()
    stopper.join(timeout=30)
    assert not stopper.is_alive()
    assert time.perf_counter() - t0 < 4.0
    del h


def test_live_lanes_never_wait_for_an_expected_ask(batcher):
    """With a lane decoding, a round is whatever is queued when the worker
    looks (and what the drain's top-up finds): an expected ask is not
    waited for, so no live lane's next chunk is held back by it."""
    since = gained("serve_admit_expected_rounds",
                   "serve_admit_expected_expired", "serve_admit_gather_ms")
    live = batcher.submit_ids(PROMPTS[0], max_new_tokens=400)
    t_end = time.monotonic() + 60
    while not live.started:
        assert time.monotonic() < t_end
        time.sleep(0.001)
    stuck = batcher.expect_arrival()
    try:
        h = batcher.submit_ids(PROMPTS[1], max_new_tokens=2)
        h.result(timeout=240)
        beside = not live._req.done.is_set()
    finally:
        stuck()
    assert len(live.result(timeout=240)) == 400
    assert beside, "the long lane ended first: the case tested nothing"
    assert since() == [0, 0, 0]


def test_the_hold_timer_keeps_its_meaning_beside_the_count(engine):
    """``admit_hold_ms`` > 0 with one ask counted: the count dropping does
    not end the round before the timer has run out for arrivals nobody
    announced."""
    import dataclasses

    hold_s = 0.15
    eng = object.__new__(type(engine))
    eng.__dict__.update(engine.__dict__)
    eng.gen = dataclasses.replace(engine.gen, admit_hold_ms=hold_s * 1e3)
    b = serve.ContinuousBatcher(eng, n_slots=4, chunk=4, cache_len=512)
    try:
        b.submit_ids(PROMPTS[0], max_new_tokens=2).result(timeout=240)
        wait_idle(b)
        since = gained("serve_admit_rounds", "serve_admitted")
        arrived = b.expect_arrival()
        first = b.submit_ids(PROMPTS[0], max_new_tokens=2)
        arrived()
        time.sleep(hold_s / 3)
        late = b.submit_ids(PROMPTS[0], max_new_tokens=2)  # unannounced
        for h in (first, late):
            h.result(timeout=240)
        assert since() == [1, 2]
    finally:
        b.stop()


# ---- the HTTP layer's count -------------------------------------------------

TINY = {
    "encoder.hidden_dim": 64,
    "encoder.num_layers": 1,
    "encoder.num_heads": 4,
    "encoder.mlp_dim": 128,
    "encoder.embed_dim": 64,
    "store.dim": 64,
    "store.shard_capacity": 256,
    "ner.train_steps": 0,
    "decoder.hidden_dim": 64,
    "decoder.num_layers": 2,
    "decoder.num_heads": 8,
    "decoder.num_kv_heads": 8,
    "decoder.head_dim": 8,
    "decoder.mlp_dim": 128,
    "decoder.vocab_size": 512,
    "decoder.max_seq_len": 512,
    "decoder.dtype": "float32",
    "generate.max_new_tokens": 8,
    "generate.max_concurrent": 4,
    "generate.prefill_buckets": (64, 128, 256),
    "flags.use_fake_encoder": True,
}
NOTE = "Aspirin 100 mg daily after the cardiac event."


@pytest.fixture(scope="module")
def rt():
    from docqa_tpu.service.app import DocQARuntime

    runtime = DocQARuntime(load_config(env={}, overrides=dict(TINY))).start()
    rec = runtime.pipeline.ingest_document("c.txt", NOTE.encode(),
                                           patient_id="p3")
    assert runtime.pipeline.wait_indexed(rec.doc_id, timeout=60)
    yield runtime
    runtime.stop()


def _queue_full(question, deadline=None, **kw):
    raise serve.QueueFull("injected")


def _out_of_time(question, deadline=None, **kw):
    from docqa_tpu.resilience.deadline import DeadlineExceeded

    raise DeadlineExceeded("test_inject")


def _extractive(question, deadline=None, **kw):
    from docqa_tpu.service.qa import PendingAnswer

    # what ask_submit returns for a lookup: answered, nothing submitted
    return PendingAnswer(sources=["c.txt"], answer=NOTE, route="extractive")


@pytest.mark.parametrize("status, body, fake_submit", [
    (422, {"nonsense": 1}, None),
    (503, {"question": "aspirin dose?"}, _queue_full),
    (504, {"question": "aspirin dose?"}, _out_of_time),
    (200, {"question": "aspirin dose?"}, _extractive),
    (200, {"question": "aspirin dose?"}, None),  # through the batcher
], ids=["422", "503_queue_full", "504", "200_extractive", "200_generated"])
def test_the_count_is_back_to_zero_after(rt, monkeypatch, status, body,
                                         fake_submit):
    from aiohttp.test_utils import TestClient, TestServer

    from docqa_tpu.service.app import make_app

    seen = []
    if fake_submit is not None:
        def spy(question, deadline=None, **kw):
            seen.append(counts())  # on the device lane, inside the preamble
            return fake_submit(question, deadline=deadline, **kw)

        monkeypatch.setattr(rt.qa, "ask_submit", spy)

    def counts():
        return [r.batcher._expected for r in rt.batcher._replicas]

    async def drive():
        client = TestClient(TestServer(make_app(rt)))
        await client.start_server()
        try:
            resp = await client.post("/ask/", json=body)
            await resp.read()
            return resp.status
        finally:
            await client.close()

    assert set(counts()) == {0}
    assert asyncio.run(drive()) == status
    assert set(counts()) == {0}
    if fake_submit is not None:
        assert seen == [[1] * len(counts())]  # it WAS counted while inside
