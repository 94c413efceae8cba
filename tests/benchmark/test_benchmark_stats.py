"""Percentile and token-time arithmetic on hand-made samples."""

import math
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(HERE)), "benchmark")
sys.path.insert(0, BENCH_DIR)

from harness import stats, traffic  # noqa: E402
from harness.client import Sample  # noqa: E402
from readers import client_latency, client_token_rate  # noqa: E402
from readers import histogram_mean, polled_mean, request_span, spine_wait  # noqa: E402


@pytest.mark.parametrize(
    "values,q,expected",
    [([1, 2, 3, 4, 5], 50, 3.0), ([1, 2, 3, 4], 50, 2.5),
     ([10], 95, 10.0), (list(range(1, 101)), 95, 95.05),
     ([5, 1, 3], 0, 1.0), ([5, 1, 3], 100, 5.0)],
)
def test_percentile(values, q, expected):
    assert stats.percentile(values, q) == pytest.approx(expected)


def test_percentile_of_nothing_is_nan():
    assert math.isnan(stats.percentile([], 50))


def test_percentile_agrees_with_numpy():
    import numpy as np

    rng = np.random.default_rng(0)
    xs = rng.exponential(size=257).tolist()
    for q in (5, 50, 95, 99):
        assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def chunks(*times, n=16):
    """Deliveries of n tokens at each time, a burst each."""
    return [t + i * 1e-4 for t in times for i in range(n)]


@pytest.mark.parametrize(
    "sent,times,expected",
    [
        # wholly inside: 3 deliveries, all 48 tokens
        (11.0, (12.0, 13.0, 14.0), 48.0),
        # straddles the start: the delivery at 10.5 was produced over
        # (9.5, 10.5], half of it inside; the one at 9.5 not at all
        (8.0, (9.5, 10.5, 11.5), 8.0 + 16.0),
        # straddles the end: produced over (19.5, 20.5], half inside
        (17.0, (18.5, 19.5, 20.5), 16.0 + 16.0 + 8.0),
        # wholly outside
        (24.0, (25.0, 26.0), 0.0),
        # one delivery only: produced since the request was sent
        (9.0, (11.0,), 8.0),
    ],
)
def test_tokens_are_credited_to_the_time_they_were_made(sent, times, expected):
    got = stats.credited_tokens(sent, chunks(*times), 10.0, 20.0)
    assert got == pytest.approx(expected, abs=0.1)
    assert stats.credited_tokens(sent, [], 10.0, 20.0) == 0.0


def test_a_window_edge_no_longer_swings_the_count_by_a_chunk():
    # four requests in lockstep, a delivery every 0.654 s, for ever: the
    # rate is 4 * 16 / 0.654 whatever the window's phase
    times = [i * 0.654 for i in range(1, 200)]
    for phase in (0.0, 0.1, 0.3, 0.6):
        t0, t1 = 20.0 + phase, 50.0 + phase
        n = 4 * stats.credited_tokens(0.0, chunks(*times), t0, t1)
        assert n / 30.0 == pytest.approx(4 * 16 / 0.654, rel=2e-3)


def test_token_rate_reader_counts_ramp_and_draining_requests():
    samples = [Sample("generative", due=0, sent=s, delta_times=chunks(*t))
               for s, t in ((11.0, (12.0, 13.0, 14.0)), (8.0, (9.5, 10.5, 11.5)),
                            (17.0, (18.5, 19.5, 20.5)), (24.0, (25.0, 26.0)))]
    samples.append(Sample("generative", due=0))  # delivered nothing
    ctx = {"samples": samples, "t0": 10.0, "t1": 20.0}
    assert client_token_rate.read(ctx) == pytest.approx((48 + 24 + 40) / 10.0, abs=0.02)
    assert client_token_rate.read({"samples": [], "t0": 0, "t1": 1}) is None


def test_ttft_and_tpot():
    assert stats.ttft_ms(1.0, [1.25, 1.5]) == pytest.approx(250.0)
    assert stats.ttft_ms(1.0, []) is None
    # 4 tokens after the first over 0.8 s, whatever the chunking
    assert stats.tpot_ms([2.0, 2.0, 2.0, 2.8, 2.8]) == pytest.approx(200.0)
    assert stats.tpot_ms([2.0]) is None


def test_latency_reader_skips_failed_requests_and_times_from_due():
    ok = Sample("lookup", due=1.0, sent=1.2, done=1.5)
    late = Sample("lookup", due=1.0, sent=3.0, done=3.1)
    bad = Sample("lookup", due=1.0, sent=1.0, done=9.0, failed="http_503")
    ctx = {"window": [ok, late, bad]}
    assert client_latency.read(ctx, "total", 50) == pytest.approx(1300.0)
    assert client_latency.read({"window": [bad]}, "total", 50) is None
    with pytest.raises(ValueError):
        client_latency.read(ctx, "nonsense", 50)


def test_histogram_deltas():
    before = {"histograms": {"qa_retrieve_ms": {"count": 10, "mean": 5.0}}}
    after = {"histograms": {"qa_retrieve_ms": {"count": 30, "mean": 7.0}}}
    assert stats.histogram_delta(before, after, "qa_retrieve_ms") == (20, 160.0)
    ctx = {"before": {"metrics": before}, "after": {"metrics": after}}
    assert histogram_mean.read(ctx, "qa_retrieve_ms") == pytest.approx(8.0)
    assert histogram_mean.read(ctx, "absent_ms") is None


def test_polled_span_and_spine_readers():
    ctx = {"polled": [{"n_active": 4, "kv_utilization": 0.5},
                      {"n_active": 2, "kv_utilization": None}]}
    assert polled_mean.read(ctx, "n_active") == pytest.approx(3.0)
    assert polled_mean.read(ctx, "kv_utilization", 100) == pytest.approx(50.0)
    assert polled_mean.read({}, "n_active") is None
    traces = {"t1": {"spans": [{"name": "serve_queue_wait", "duration_ms": 2.0},
                               {"name": "other", "duration_ms": 99.0}]},
              "t2": {"spans": [{"name": "serve_queue_wait", "duration_ms": 4.0}]}}
    assert request_span.read({"request_traces": traces}, ["serve_queue_wait"], 50) == 3.0
    assert request_span.read({}, ["serve_queue_wait"], 50) is None

    def status(wait, count):
        return {"dispatch": {"spine": {"stages": {
            "serve_decode_chunk": {"queue_wait_s": wait, "count": count}}}}}

    ctx = {"before": {"status": status(1.0, 100)},
           "after": {"status": status(1.5, 300)}}
    assert spine_wait.read(ctx, ["serve_decode_chunk"]) == pytest.approx(2.5)
    assert spine_wait.read(ctx, ["absent"]) is None


def _timeline(queue, hold, other=99.0):
    spans = [{"name": "serve_first_token", "duration_ms": other}]
    spans += [{"name": "serve_queue_wait", "duration_ms": q} for q in queue]
    spans += [{"name": "serve_admit_hold", "duration_ms": h} for h in hold]
    return {"spans": spans}


@pytest.mark.parametrize("timelines, expected", [
    # rag_closed since PR 26 (PERF.md): the request admitted ahead, two
    # popped before the drain (hold ~670), one after it (queue ~667): each
    # span's own median falls on either population, the sum does not
    ([_timeline([0.3], [3.0]), _timeline([60.0], [670.0]),
      _timeline([55.0], [671.0]), _timeline([667.0], [2.0])], 697.5),
    # a bounced request holds twice: both count towards its wait
    ([_timeline([5.0], [100.0, 20.0])], 125.0),
    # a request that lacks one of the spans is left out, not half counted
    ([_timeline([5.0], [10.0]), _timeline([700.0], [])], 15.0),
    ([_timeline([], [])], None),
], ids=["two_populations", "bounced", "a_span_missing", "nothing_to_read"])
def test_request_span_sums_the_named_spans_per_request(timelines, expected):
    ctx = {"request_traces": {f"t{i}": t for i, t in enumerate(timelines)}}
    got = request_span.read(ctx, ["serve_queue_wait", "serve_admit_hold"], 50)
    assert got == (None if expected is None else pytest.approx(expected))


def test_every_seed_asks_the_same_templates_in_another_order():
    mix = traffic.load(os.path.join(BENCH_DIR, "traffic", "rag_closed.json"))

    def first_period(seed):
        stream = traffic.questions(mix, seed, 64, "client0")
        return [next(stream) for _ in range(8)]

    a, b = first_period(11), first_period(12)
    assert [k for k, _ in a] == ["generative"] * 8
    assert a != b and a == first_period(11)
    # the same eight templates, each once, whatever the seed
    shape = lambda qs: sorted(q[:7] for _, q in qs)  # noqa: E731
    assert shape(a) == shape(b) and len(set(shape(a))) == 8


@pytest.mark.parametrize(
    "mix", [{}, {"loop": "sideways", "clients": 1, "endpoint": "/ask/",
                 "questions": [{"kind": "generative"}]},
            {"loop": "closed", "clients": 0, "endpoint": "/ask/",
             "questions": [{"kind": "generative"}]},
            {"loop": "closed", "clients": 1, "endpoint": "/nowhere",
             "questions": [{"kind": "generative"}]},
            {"loop": "closed", "clients": 1, "endpoint": "/ask/",
             "questions": []}],
)
def test_a_malformed_traffic_file_is_refused(mix, tmp_path):
    import json

    path = tmp_path / "mix.json"
    path.write_text(json.dumps(mix))
    with pytest.raises(ValueError):
        traffic.load(str(path))
