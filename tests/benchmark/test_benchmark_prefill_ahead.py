"""``prefill_ahead_share`` (ISSUE 26): the share of admission rounds whose
prefill was dispatched ahead of a chunk that live lanes were due — the
metric's own file on the ``counter_ratio`` reader, on hand-made counter
snapshots."""

import importlib
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH_DIR = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH_DIR)

NAME = "prefill_ahead_share"
with open(os.path.join(BENCH_DIR, "metrics", NAME + ".json")) as f:
    METRIC = json.load(f)


def read(before, after):
    ctx = {"before": {"metrics": {"counters": before}},
           "after": {"metrics": {"counters": after}}}
    # as run.py finds it: the module the metric's file names
    reader = importlib.import_module("readers." + METRIC["reader"])
    return reader.read(ctx, **METRIC["params"])


@pytest.mark.parametrize("before, after, expected", [
    # the parent commit: rounds moved, the counter does not exist
    ({"serve_admit_rounds": 4}, {"serve_admit_rounds": 10}, 0.0),
    # rag_closed: a round of 1 into an idle batcher, then 3 beside one lane
    ({"serve_admit_rounds": 4, "serve_prefill_ahead": 2},
     {"serve_admit_rounds": 10, "serve_prefill_ahead": 5}, 50.0),
    # the counter first appears inside the window
    ({"serve_admit_rounds": 2}, {"serve_admit_rounds": 4,
                                 "serve_prefill_ahead": 2}, 100.0),
    # no round in the window: nothing to read, not a zero
    ({"serve_admit_rounds": 4, "serve_prefill_ahead": 2},
     {"serve_admit_rounds": 4, "serve_prefill_ahead": 2}, None),
], ids=["counter_absent", "three_of_six", "every_round", "no_round"])
def test_prefill_ahead_share(before, after, expected):
    got = read(before, after)
    assert got == (None if expected is None else pytest.approx(expected))


def test_declared_beside_the_layers_other_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "Admission and batching",
        "moves": "ttft_p50_ms", "workloads": ["rag_closed"],
    }
    assert bench["per_layer"][-1] is entry  # appended, nothing reordered
