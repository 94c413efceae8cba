"""``generate.admit_hold_ms`` (ROADMAP A1 (a), brought as an option in PR 35).

* off (the default) a round is whatever is queued when the worker looks:
  requests that trickle in are admitted in more rounds than one;
* on, requests that reach the queue a few milliseconds apart are admitted
  as ONE round, in the order they were submitted, with the same tokens;
* the wait ends at once when the slots are full, and after one hold when
  nothing more arrives (a request alone is not held for longer).
"""

import dataclasses
import threading
import time

import pytest

from docqa_tpu.config import DecoderConfig, GenerateConfig
from docqa_tpu.runtime.metrics import DEFAULT_REGISTRY

CFG = DecoderConfig(
    vocab_size=64,
    hidden_dim=32,
    num_layers=1,
    num_heads=2,
    num_kv_heads=1,
    head_dim=16,
    mlp_dim=64,
    max_seq_len=128,
    dtype="float32",
)
GEN = GenerateConfig(temperature=0.0, prefill_buckets=(16,), eos_id=2)
HOLD_MS = 400.0
TRICKLE_S = 0.03
PROMPTS = [[3 + j for j in range(5 + 3 * i)] for i in range(4)]


@pytest.fixture(scope="module")
def engine():
    from docqa_tpu.engines.generate import GenerateEngine

    return GenerateEngine(CFG, GEN, seed=3)


def make_batcher(engine, hold_ms, n_slots=4):
    from docqa_tpu.engines.serve import ContinuousBatcher

    eng = engine
    if hold_ms:
        eng = object.__new__(type(engine))
        eng.__dict__.update(engine.__dict__)
        eng.gen = dataclasses.replace(engine.gen, admit_hold_ms=hold_ms)
    b = ContinuousBatcher(eng, n_slots=n_slots, chunk=4, cache_len=128)
    # every shape the trickle uses is built before anything is counted
    for h in [b.submit_ids(p, max_new_tokens=6) for p in PROMPTS]:
        h.result(timeout=240)
    return b


def rounds():
    return (DEFAULT_REGISTRY.counter("serve_admit_rounds").value,
            DEFAULT_REGISTRY.counter("serve_admitted").value)


def trickle(batcher, n=4):
    """``n`` requests, one every TRICKLE_S: (rounds gained, admitted
    gained, tokens of each, seconds from the first submit to the last
    answer)."""
    r0, a0 = rounds()
    t0 = time.perf_counter()
    handles = []
    for p in PROMPTS[:n]:
        handles.append(batcher.submit_ids(p, max_new_tokens=6))
        time.sleep(TRICKLE_S)
    tokens = [h.result(timeout=240) for h in handles]
    took = time.perf_counter() - t0
    r1, a1 = rounds()
    return r1 - r0, a1 - a0, tokens, took


def test_default_is_off():
    assert GenerateConfig().admit_hold_ms == 0.0


def test_off_a_trickle_is_admitted_in_several_rounds(engine):
    b = make_batcher(engine, 0.0)
    try:
        assert b._admit_hold_s == 0.0
        n_rounds, n_admitted, _tokens, _took = trickle(b)
    finally:
        b.stop()
    assert n_admitted == 4
    assert n_rounds >= 2  # the first request never waits for the others


def test_on_a_trickle_is_one_round_with_the_same_tokens(engine):
    off = make_batcher(engine, 0.0)
    try:
        _r, _a, want, _took = trickle(off)
    finally:
        off.stop()
    on = make_batcher(engine, HOLD_MS)
    try:
        assert on._admit_hold_s == pytest.approx(HOLD_MS / 1e3)
        n_rounds, n_admitted, got, took = trickle(on)
    finally:
        on.stop()
    assert (n_rounds, n_admitted) == (1, 4)
    assert got == want  # same answers, request by request
    # the slots filled with the fourth arrival (3 x TRICKLE_S in): the
    # wait ended there and not a whole hold after it
    assert took < 3 * TRICKLE_S + HOLD_MS / 1e3


def test_on_a_request_alone_waits_one_hold_and_no_longer(engine):
    hold_ms = 150.0
    b = make_batcher(engine, hold_ms, n_slots=4)
    try:
        r0, a0 = rounds()
        t0 = time.perf_counter()
        b.submit_ids(PROMPTS[0], max_new_tokens=2).result(timeout=240)
        took = time.perf_counter() - t0
        r1, a1 = rounds()
    finally:
        b.stop()
    assert (r1 - r0, a1 - a0) == (1, 1)
    assert took >= hold_ms / 1e3
    assert took < 20 * hold_ms / 1e3


def test_on_the_order_of_admission_is_the_order_of_submission(engine):
    b = make_batcher(engine, HOLD_MS)
    try:
        handles = []
        for p in PROMPTS:
            handles.append(b.submit_ids(p, max_new_tokens=3))
            time.sleep(TRICKLE_S / 3)
        for h in handles:
            h.result(timeout=240)
        pops = [h._req.t_pop for h in handles]
    finally:
        b.stop()
    assert pops == sorted(pops)


def test_stop_ends_a_wait(engine):
    b = make_batcher(engine, 5000.0)
    h = b.submit_ids(PROMPTS[0], max_new_tokens=2)
    time.sleep(0.05)  # the worker is inside the hold now
    t0 = time.perf_counter()
    stopper = threading.Thread(target=b.stop)
    stopper.start()
    stopper.join(timeout=30)
    assert not stopper.is_alive()
    assert time.perf_counter() - t0 < 4.0
    del h
