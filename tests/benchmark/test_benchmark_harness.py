"""The harness end to end at tiny widths on the CPU, and what load may
and may not do to the result line."""

import http.server
import json
import os
import shutil
import subprocess
import sys
import threading
import zlib

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH_DIR = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH_DIR)

from harness import client  # noqa: E402

OVERLAY = os.path.join(HERE, "data", "tiny_overlay.json")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "seed", "weights_seed", "affinity", "compared"}


def child_env():
    env = dict(os.environ)
    # one CPU device, as one chip: conftest's eight would make a 1x8 mesh
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env["BENCH_RUN"] = "ignored"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return env


STDERR = {}  # (workload, trace) -> what that rehearsal wrote there


def run_cell(workload, trace, seconds="2", seed="4294967301"):
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"),
         "--workload", workload, "--seed", seed, "--seconds", seconds,
         "--trace", trace, "--rehearsal", OVERLAY],
        cwd=ROOT, env=child_env(), capture_output=True, text=True, timeout=420,
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    STDERR[workload, trace] = proc.stderr
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


@pytest.fixture(scope="module")
def rag_untraced():
    return run_cell("rag_closed", "0")


@pytest.fixture(scope="module")
def rag_traced():
    return run_cell("rag_closed", "1")


def test_last_line_has_the_keys_the_driver_reads(rag_untraced):
    result, out = rag_untraced
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert result["device"]["platform"] == "cpu"
    assert "compared decoder_logit_rel_err" in out
    assert "compared retrieval_score_err" in out
    # which run it was, and which model it served: the run's seed and the
    # one the configuration states for its weights (ISSUE 58)
    assert result["seed"] == 4294967301
    assert result["weights_seed"] == zlib.crc32(b"mistral-7b-int8")
    split = result["affinity"]
    assert split == "left alone" or (
        len(split["parent"]) == 2 and split["child"]
        and not set(split["parent"]) & set(split["child"]))


def test_the_compared_numbers_are_the_last_lines_of_standard_error(rag_untraced):
    _result, out = rag_untraced
    compared = [ln for ln in out.splitlines() if ln.startswith("compared ")]
    assert len(compared) == 3
    assert STDERR["rag_closed", "0"].strip().splitlines()[-3:] == compared


def test_the_compared_numbers_come_last_in_the_result_line(rag_untraced):
    result, out = rag_untraced
    assert list(result)[-1] == "compared"
    assert list(result["compared"]) == [
        "decoder_logit_rel_err", "kv_cache_bits_missing", "retrieval_score_err"]
    for name, n in result["compared"].items():
        assert set(n) == {"value", "limit"} and 0 <= n["value"] <= n["limit"]
        assert f"compared {name}: {n['value']:.6g} (limit {n['limit']})" in out


def test_untraced_run_reports_the_cells_end_to_end_metrics(rag_untraced):
    result, _ = rag_untraced
    assert set(result["metrics"]) == {"ttft_p50_ms", "tpot_p50_ms", "setup_s"}
    for name, m in result["metrics"].items():
        assert m["value"] > 0 and isinstance(m["unit"], str), name


def test_traced_run_reports_per_layer_metrics_but_no_device_number_on_a_cpu(rag_traced):
    result, out = rag_traced
    assert set(result) == RESULT_KEYS  # no breakdown either
    names = set(result["metrics"])
    assert {"window_tok_s", "retrieve_mean_ms.gen", "admit_wait_p50_ms",
            "decode_batch_mean", "kv_pool_used_share",
            "spine_wait_mean_ms"} <= names
    # what only a device trace can say is never printed from a CPU run
    assert not names & {"decode_step_ms", "decode_step_roofline",
                        "device_idle_share.gen"}
    assert "busy_s" not in result["device"]
    assert "request spans" in out and "prompt tokens per request" in out


def test_without_a_tpu_the_child_refuses():
    """Not a rehearsal, CPU backend: the device is not in the peaks table,
    so the child exits non-zero before it builds anything."""
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "harness", "child.py"),
         "--config", os.path.join(BENCH_DIR, "configs", "mistral-7b-int8.json"),
         "--seed", "1", "--port", "1", "--work", os.path.join(ROOT, ".benchmark_work")],
        cwd=ROOT, env=dict(child_env(), JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert "peaks table" in proc.stderr


def test_a_directory_with_only_the_benchmark_prints_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH_DIR, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "rag_closed",
         "--seed", "1", "--seconds", "1", "--trace", "0", "--rehearsal", OVERLAY],
        cwd=tmp_path, env=child_env(), capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert not proc.stdout.strip().endswith("}")


# ---- what load does to a request is `failed`, never `correct` -------------

class Stub(http.server.BaseHTTPRequestHandler):
    answers = {}

    def do_POST(self):
        length = int(self.headers.get("Content-Length", 0))
        question = json.loads(self.rfile.read(length))["question"]
        status, body = self.answers[question]
        raw = body if isinstance(body, bytes) else json.dumps(body).encode()
        self.send_response(status)
        self.send_header("Content-Length", str(len(raw)))
        self.send_header("X-Trace-Id", "t-" + question)
        self.end_headers()
        self.wfile.write(raw)

    def log_message(self, *args):
        pass


@pytest.fixture(scope="module")
def stub():
    Stub.answers = {
        "ok": (200, {"answer": "a", "sources": ["s"], "route": "extractive"}),
        "full": (503, {"detail": "queue full"}),
        "late": (504, {"detail": "deadline"}),
        "degraded": (200, {"answer": "a", "sources": [], "degraded": True,
                           "degrade_reason": "deadline"}),
        "empty": (200, {"answer": "", "sources": []}),
        "stream": (200, b'data: {"delta": "w1"}\n\ndata: {"delta": " w2"}\n\n'
                        b'event: done\ndata: {"sources": []}\n\n'),
        "stream_error": (200, b'data: {"delta": "w1"}\n\n'
                              b'event: error\ndata: {"detail": "x"}\n\n'),
        "stream_cut": (200, b'data: {"delta": "w1"}\n\n'),
    }
    server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Stub)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    yield server.server_address[1]
    server.shutdown()
    thread.join(timeout=5)


@pytest.mark.parametrize(
    "question,failed",
    [("ok", None), ("full", "http_503"), ("late", "http_504"),
     ("degraded", "degraded_deadline"), ("empty", "empty_answer")],
)
def test_ask_outcomes(stub, question, failed):
    conn = client.Connection("127.0.0.1", stub, 5.0)
    s = client.send(conn, "/ask/", "lookup", question, 0.0)
    conn.close()
    assert s.failed == failed and s.trace_id == "t-" + question
    assert s.done >= s.sent > 0
    assert (s.route == "extractive") == (failed is None)


@pytest.mark.parametrize(
    "question,failed,n_deltas",
    [("stream", None, 2), ("stream_error", 'sse_error:{"detail": "x"}', 1),
     ("stream_cut", "stream_cut", 1), ("full", "http_503", 0)],
)
def test_stream_outcomes(stub, question, failed, n_deltas):
    conn = client.Connection("127.0.0.1", stub, 5.0)
    s = client.send(conn, "/ask/stream", "generative", question, 0.0)
    conn.close()
    assert s.failed == failed and len(s.delta_times) == n_deltas


def test_an_unreachable_server_is_a_failed_request_not_an_exception():
    conn = client.Connection("127.0.0.1", 1, 1.0)
    s = client.send(conn, "/ask/", "lookup", "ok", 0.0)
    assert s.failed == "http_None"
    s = client.send(conn, "/ask/stream", "generative", "ok", 0.0)
    assert s.failed == "connection"


def test_the_result_line_takes_correct_from_the_comparisons_alone():
    """run.py builds `correct` from the child's verdict and `failed` from
    the samples; no path leads from one to the other."""
    import ast

    with open(os.path.join(BENCH_DIR, "run.py"), encoding="utf-8") as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.Dict):
            keys = [k.value for k in node.keys if isinstance(k, ast.Constant)]
            if "correct" in keys and "attempted" in keys:
                src = ast.unparse(node.values[keys.index("correct")])
                assert src == "bool(verdict.get('correct'))"
                return
    raise AssertionError("result line not found")


def test_closed_loop_opens_the_window_after_the_ramp(stub):
    def stream():
        while True:
            yield "lookup", "ok"

    opened = []
    samples, t0, t1 = client.closed_loop(
        "127.0.0.1", stub, "/ask/", 5.0, [stream(), stream()], 2, 0.3,
        opened.append,
    )
    assert opened == [t0] and t1 - t0 >= 0.3
    before = [s for s in samples if s.due < t0]
    assert len(before) >= 4  # each client's ramp requests came first
    assert any(t0 <= s.due < t1 for s in samples)


def test_lockstep_clients_send_in_rounds(stub):
    def stream():
        while True:
            yield "lookup", "ok"

    samples, t0, t1 = client.closed_loop(
        "127.0.0.1", stub, "/ask/", 5.0, [stream() for _ in range(3)], 1, 0.3,
        lambda _t: None, lockstep=True,
    )
    assert len(samples) % 3 == 0 and len(samples) >= 6
    ordered = sorted(samples, key=lambda s: s.sent)
    for r in range(0, len(ordered) - 3, 3):
        # nobody of the next round is sent before the whole round is done
        assert min(s.sent for s in ordered[r + 3:r + 6]) >= max(
            s.done for s in ordered[r:r + 3]
        )
