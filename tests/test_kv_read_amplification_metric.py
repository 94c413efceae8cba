"""``decode_kv_read_amplification`` (ISSUE 29): KV rows a decode chunk's
steps fetched per layer over the rows that were live — the metric's own
file on the benchmark's ``counter_ratio`` reader, on hand-made counter
snapshots.

The file is data waiting for its ``per_layer`` entry, under ``tests/data/``
and not under ``benchmark/metrics/`` (where an undeclared file fails the
schema test): appending an entry to ``BENCHMARK.json`` fails
``tests/benchmark/test_benchmark_prefill_ahead.py``, which holds that
``prefill_ahead_share`` is the LAST entry, and a PR that claims a gain
edits no file under the benchmark's ``paths`` (PERF.md section 7).  The
``benchmark`` PR that declares it moves both files beside their kin."""

import importlib
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
BENCH_DIR = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH_DIR)

NAME = "decode_kv_read_amplification"
# where the file waits; its place is benchmark/metrics/ once it is declared
with open(os.path.join(HERE, "data", NAME + ".json")) as f:
    METRIC = json.load(f)

READ, LIVE = "serve_decode_kv_rows_read", "serve_decode_kv_rows_live"


def read(before, after):
    ctx = {"before": {"metrics": {"counters": before}},
           "after": {"metrics": {"counters": after}}}
    # as run.py finds it: the module the metric's file names
    reader = importlib.import_module("readers." + METRIC["reader"])
    return reader.read(ctx, **METRIC["params"])


@pytest.mark.parametrize("before, after, expected", [
    # the parent commit has neither counter: nothing to read, not a zero
    ({"serve_decode_chunks": 4}, {"serve_decode_chunks": 40}, None),
    # the kernel: 4 lanes of 357 live rows read 23 pages of 16 each step
    ({READ: 1000, LIVE: 900},
     {READ: 1000 + 64 * 4 * 368, LIVE: 900 + 64 * 4 * 357}, 368 / 357),
    # the gather reference: every slot's 4096-position table each step
    ({READ: 0, LIVE: 0},
     {READ: 16 * 4 * 4096, LIVE: 16 * 3 * 357}, 4 * 4096 / (3 * 357)),
    # no chunk in the window
    ({READ: 5000, LIVE: 4000}, {READ: 5000, LIVE: 4000}, None),
], ids=["counters_absent", "live_pages", "whole_tables", "no_chunk"])
def test_decode_kv_read_amplification(before, after, expected):
    got = read(before, after)
    assert got == (None if expected is None else pytest.approx(expected))
