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
PARENT_ENTRIES = [
    "window_tok_s", "retrieve_mean_ms.gen", "admit_wait_p50_ms",
    "decode_batch_mean", "kv_pool_used_share", "spine_wait_mean_ms",
    "decode_step_ms", "decode_step_roofline", "device_idle_share.gen",
    "first_token_wait_p50_ms", "admit_drain_mean_ms", "admit_batch_mean",
    "prefill_pad_share", "decode_tokens_per_chunk",
    "decode_stale_chunk_share", NAME,
]
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
    # the sixteen entries PR 26 left are a prefix, in their order: later
    # PRs append (ISSUE 33), nothing is reordered, renamed or taken out
    assert [m["name"] for m in bench["per_layer"][:16]] == PARENT_ENTRIES
