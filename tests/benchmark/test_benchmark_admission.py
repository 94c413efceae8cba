"""The admission metrics of ISSUE 25: the ``counter_ratio`` reader on
hand-made snapshots, and one traced rehearsal of ``rag_closed`` that
reports all seven."""

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH_DIR = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH_DIR)

from readers import counter_ratio  # noqa: E402

OVERLAY = os.path.join(HERE, "data", "tiny_overlay.json")
NEW = {"admit_wait_p50_ms", "first_token_wait_p50_ms", "admit_drain_mean_ms",
       "admit_batch_mean", "prefill_pad_share", "decode_tokens_per_chunk",
       "decode_stale_chunk_share"}


def snapshots(before, after):
    return {"before": {"metrics": {"counters": before}},
            "after": {"metrics": {"counters": after}}}


CTX = snapshots(
    {"serve_admitted": 10, "serve_admit_rounds": 6,
     "serve_prefill_tokens": 1000, "serve_prefill_budget_tokens": 5000},
    {"serve_admitted": 18, "serve_admit_rounds": 10,
     "serve_prefill_tokens": 2292, "serve_prefill_budget_tokens": 9608,
     "serve_decode_chunks": 7, "serve_decode_chunks_stale": 1},
)


@pytest.mark.parametrize("params, expected", [
    # what the window gained, not the lifetime totals: 8 requests / 4 rounds
    (dict(numerator=["serve_admitted"], denominator=["serve_admit_rounds"]),
     2.0),
    # the share that is left: 1 - 1292 / 4608 of the rows were padding
    (dict(numerator=["serve_prefill_tokens"],
          denominator=["serve_prefill_budget_tokens"], scale=100.0,
          complement=True), 100.0 * (1 - 1292 / 4608)),
    # a counter the first snapshot lacks started at 0
    (dict(numerator=["serve_decode_chunks_stale"],
          denominator=["serve_decode_chunks"], scale=100.0), 100.0 / 7),
    # several names add up
    (dict(numerator=["serve_admitted", "serve_admit_rounds"],
          denominator=["serve_admit_rounds"]), 3.0),
], ids=["ratio", "complement", "counter_new_in_the_window", "sum_of_names"])
def test_counter_ratio(params, expected):
    assert counter_ratio.read(CTX, **params) == pytest.approx(expected)


@pytest.mark.parametrize("ctx", [
    snapshots({"serve_admit_rounds": 4}, {"serve_admit_rounds": 4,
                                          "serve_admitted": 9}),
    snapshots({}, {}),  # a program without the counters: the parent commit
    {"before": {"metrics": {}}, "after": {"metrics": {}}},
    {"before": {}, "after": {}},
], ids=["unmoved", "no_such_counter", "no_counters", "no_snapshot"])
def test_counter_ratio_reads_nothing_where_the_denominator_did_not_move(ctx):
    assert counter_ratio.read(
        ctx, numerator=["serve_admitted"], denominator=["serve_admit_rounds"]
    ) is None


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A traced rehearsal from a tree of its own (the benchmark copied,
    the rest linked), so that its work directory is not the one the other
    rehearsals of this suite write to at the same time."""
    tree = tmp_path_factory.mktemp("tree")
    for name in os.listdir(ROOT):
        if name.startswith(".") or name in ("benchmark", "chiprun_out"):
            continue
        os.symlink(os.path.join(ROOT, name), tree / name)
    shutil.copytree(BENCH_DIR, tree / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "rag_closed",
         "--seed", "4294967311", "--seconds", "3", "--trace", "1",
         "--rehearsal", OVERLAY],
        cwd=tree, env=env, capture_output=True, text=True, timeout=420,
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


def test_traced_rehearsal_reports_the_seven_admission_metrics(traced):
    result, _out = traced
    assert result["correct"] is True and result["failed"] == 0
    assert NEW <= set(result["metrics"])
    units = {n: result["metrics"][n]["unit"] for n in NEW}
    assert units["prefill_pad_share"] == "%"
    assert units["admit_batch_mean"] == "requests"


def test_rehearsal_values_are_what_the_counters_allow(traced):
    m = {n: v["value"] for n, v in traced[0]["metrics"].items()}
    assert 0 <= m["prefill_pad_share"] < 100
    assert 0 <= m["decode_stale_chunk_share"] <= 100
    assert 1 <= m["admit_batch_mean"] <= 4  # four clients on four slots
    assert m["decode_tokens_per_chunk"] >= 0
    for name in ("admit_wait_p50_ms", "first_token_wait_p50_ms",
                 "admit_drain_mean_ms"):
        assert m[name] >= 0, name


def test_the_four_serve_spans_reach_the_request_timelines(traced):
    """``run.py`` prints the spans it found in ``/api/trace/<id>``."""
    line = next(ln for ln in traced[1].splitlines()
                if ln.startswith("request spans"))
    spans = json.loads(line.split(": ", 1)[1])
    for name in ("serve_queue_wait", "serve_admit_hold", "serve_prefill",
                 "serve_first_token"):
        assert name in spans, name
    # one of each per request
    assert len({spans[n][0] for n in ("serve_queue_wait", "serve_admit_hold",
                                      "serve_prefill", "serve_first_token")}) == 1
