"""Bring-up rules that keep the serving path honest on the chip: where the
compile cache lives, one process per chip, and a smoke that a degraded
server cannot pass.  CPU-only and quick (no server boots here)."""

import copy
import json
import os
import subprocess
import sys
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


class TestCompileCachePlacement:
    def test_env_set_means_code_sets_nothing(self, monkeypatch, tmp_path):
        from docqa_tpu.runtime import compile_cache

        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path))
        assert compile_cache.configure_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    def test_unset_means_one_fixed_dir_inside_the_checkout(self, monkeypatch):
        from docqa_tpu.runtime import compile_cache

        monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
        before = jax.config.jax_compilation_cache_dir
        try:
            got = compile_cache.configure_compile_cache()
            assert got == compile_cache.DEFAULT_CACHE_DIR
            assert jax.config.jax_compilation_cache_dir == got
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
        assert got == os.path.join(REPO, ".jax_compile_cache")
        assert got == compile_cache.compile_cache_dir()  # no pid, no time


class TestOneProcessPerChip:
    def test_load_or_train_never_spawns_a_process(self, monkeypatch, tmp_path):
        """Any backend, any step count: the tagger trains in the calling
        process (a child could not get a chip its parent holds)."""
        import numpy as np

        from docqa_tpu.config import NERConfig
        from docqa_tpu.training import ner

        def no_child(*a, **kw):
            raise AssertionError("load_or_train spawned a process")

        monkeypatch.setattr(subprocess, "Popen", no_child)
        monkeypatch.setattr(subprocess, "run", no_child)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        cfg = NERConfig(
            vocab_size=64, hidden_dim=16, num_layers=1, num_heads=2,
            mlp_dim=32, max_seq_len=32,
        )
        trained = []

        def fake_train(cfg, **kw):
            trained.append(kw)
            return {"w": np.zeros((2, 2), np.float32)}

        monkeypatch.setattr(ner, "train_ner", fake_train)
        path = str(tmp_path / "ner.npz")
        _, seq = ner.load_or_train(cfg, path, steps=1500, seed=0)
        assert trained == [{"steps": 1500, "seed": 0}] and seq == 32
        assert os.path.exists(path)
        with open(ner.__file__) as f:
            assert "subprocess" not in f.read()

    def test_supervise_parent_never_imports_jax(self):
        """scripts/start_all.py --supervise is a parent of the process
        that holds the chip: everything it imports must stay off JAX."""
        code = (
            "import sys; sys.path.insert(0, %r)\n"
            "from docqa_tpu.analysis.race_witness import "
            "maybe_install_from_env\n"
            "maybe_install_from_env()\n"
            "from docqa_tpu.config import load_config\n"
            "load_config()\n"
            "assert 'jax' not in sys.modules, 'parent imported jax'\n"
        ) % REPO
        subprocess.run([sys.executable, "-c", code], check=True, timeout=60)
        with open(os.path.join(REPO, "scripts", "start_all.py")) as f:
            head = f.read().split("def _pool_rolling_restart")[0]
        imports = [
            ln for ln in head.splitlines()
            if ln.startswith(("import ", "from "))
        ]
        assert [ln for ln in imports if "docqa_tpu" in ln] == [
            "from docqa_tpu.analysis.race_witness import "
            "maybe_install_from_env  # noqa: E402"
        ]


class TestMeshSteadyState:
    def test_batcher_compiles_nothing_after_warmup_on_a_mesh(self):
        """jit keys its cache on input shardings.  On a mesh the slot
        state and pools cycle from one dispatch's outputs into the next
        one's inputs, so their shardings are pinned; left to the compiler
        (or fresh and uncommitted, as the warm-up's throwaway state was)
        each new combination recompiled the whole layer stack inside a
        live request — on a 1x4 v5e every concurrent ask then ran into
        its deadline (PR 21)."""
        from docqa_tpu.config import DecoderConfig, GenerateConfig
        from docqa_tpu.engines.generate import GenerateEngine
        from docqa_tpu.engines.serve import ContinuousBatcher
        from docqa_tpu.runtime.mesh import host_cpu_mesh

        cfg = DecoderConfig(
            vocab_size=256, hidden_dim=32, num_layers=1, num_heads=8,
            num_kv_heads=4, head_dim=8, mlp_dim=64, max_seq_len=256,
        )
        gen = GenerateConfig(max_new_tokens=8, max_concurrent=4)
        engine = GenerateEngine(cfg, gen, mesh=host_cpu_mesh(4))
        b = ContinuousBatcher(engine, chunk=4)
        try:
            b.warmup()
            fns = (
                b._get_prefill_fn(), b._get_prefill_warm_fn(),
                b._get_decode_fn(),
            )
            warmed = [f._cache_size() for f in fns]
            assert warmed == [1, 1, 1]
            b.submit_ids([1, 2, 3], max_new_tokens=2).result(timeout=120)
            handles = [
                b.submit_ids(list(range(5 + i, 90 + i)), max_new_tokens=8)
                for i in range(3)
            ]
            assert all(len(h.result(timeout=120)) <= 8 for h in handles)
            assert [f._cache_size() for f in fns] == warmed
        finally:
            b.stop()


# ---------------------------------------------------------------------------
# chip_smoke against a canned server: what it must refuse
# ---------------------------------------------------------------------------

GOOD_STATUS = {
    "device": {
        "platform": "cpu", "device_kind": "cpu", "count": 1, "mesh": None,
        "index_devices": 1,
        "memory": [{"id": 0, "bytes_in_use": None,
                    "peak_bytes_in_use": None, "bytes_limit": None}],
        "compile_cache": {"dir": "x", "entries_at_boot": 0, "entries": 3},
    },
    "boot": {"total": 1.0},
    "warmup": {"state": "ok", "decode_kernel_calls": 0, "seconds": 1.0},
    "breakers": {"decoder": "closed", "index": "closed"},
    "dead_letters": {"raw_documents_queue": 0},
    "dispatch": {"spine": {
        "errors": 0, "n_lanes": 2, "completed": 9, "peak_depth": 1,
        "stages": {"serve_prefill": {"count": 2, "errors": 0}},
    }},
    "pool": {"replicas": [{
        "replica": 0, "state": "healthy", "worker_alive": True,
        "breaker": "closed", "deaths": 0, "n_active": 2,
    }]},
    "slo": [],
}
N_ASKS = len(chip_smoke.SEQUENTIAL_ASKS) + 1 + len(chip_smoke.CONCURRENT_ASKS)
GOOD_COUNTERS = {
    "ask_requests": N_ASKS, "ask_failures": 0, "qa_degraded": 0,
    "qa_routed_generative": N_ASKS - 1, "qa_routed_extractive": 1,
    "cost_decode_tokens_interactive": 40.0 * (N_ASKS - 1),
    "cost_decode_tokens_batch": 64.0,
}


class _Canned(BaseHTTPRequestHandler):
    state: dict = {}

    def log_message(self, *a):
        pass

    def _send(self, payload, code=200):
        body = json.dumps(payload).encode()
        self.send_response(code)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self):
        st = self.state
        if self.path == "/api/status":
            return self._send(st["status"])
        if self.path == "/api/metrics":
            return self._send({"counters": st["counters"]})
        if self.path.startswith("/documents/"):
            return self._send({"doc_id": self.path.rsplit("/", 1)[1],
                               "status": "INDEXED", "n_chunks": 2})
        return self._send({"status": "ok"})

    def do_POST(self):
        st = self.state
        body = json.loads(
            self.rfile.read(int(self.headers["Content-Length"])) or b"{}"
        )
        if self.path.startswith("/ingest/"):
            return self._send({"doc_id": body["filename"], "status": "INDEXED"})
        if self.path == "/ask/":
            answer = {"answer": "w1 w2", "sources": ["a.txt"]}
            if body["question"] == chip_smoke.LOOKUP_ASK:
                answer["route"] = "extractive"
            answer.update(st.get("ask_extra", {}))
            return self._send(answer)
        return self._send({"patient_id": "P-1001", "key_points": [],
                           "sections": [{"title": "t", "content": "w3"}]})


@pytest.fixture
def canned():
    state = {
        "status": copy.deepcopy(GOOD_STATUS),
        "counters": dict(GOOD_COUNTERS),
    }
    _Canned.state = state
    srv = ThreadingHTTPServer(("127.0.0.1", 0), _Canned)
    t = threading.Thread(
        target=srv.serve_forever, kwargs={"poll_interval": 0.02}, daemon=True
    )
    t.start()

    def run(rehearsal=True):
        import time

        obs = chip_smoke.drive(
            f"http://127.0.0.1:{srv.server_port}", time.monotonic() + 30
        )
        return chip_smoke.verdict(obs, rehearsal)

    yield state, run
    srv.shutdown()
    srv.server_close()
    t.join(timeout=5)
    assert not t.is_alive()


class TestSmokeCannotBeFooled:
    def test_healthy_rehearsal_passes(self, canned):
        _, run = canned
        assert run() == []

    def test_cpu_without_the_rehearsal_flag_is_an_error(self, canned):
        _, run = canned
        bad = run(rehearsal=False)
        assert any("platform is 'cpu'" in b for b in bad)
        assert any("Mosaic" in b for b in bad)

    def test_a_degraded_answer_fails_with_http_200(self, canned):
        state, run = canned
        state["ask_extra"] = {"degraded": True, "degrade_reason": "deadline"}
        assert any("degraded (deadline)" in b for b in run())

    def test_zero_decode_tokens_fail(self, canned):
        state, run = canned
        state["counters"]["cost_decode_tokens_interactive"] = 0
        assert any("interactive decode tokens = 0" in b for b in run())

    def test_failed_warmup_fails(self, canned):
        state, run = canned
        state["status"]["warmup"] = {"state": "failed", "error": "OOM"}
        assert any("warm-up failed: OOM" in b for b in run())

    def test_open_breaker_fails(self, canned):
        state, run = canned
        state["status"]["breakers"]["decoder"] = "open"
        assert any("breaker decoder is open" in b for b in run())

    def test_spine_errors_dead_replica_and_counters_fail(self, canned):
        state, run = canned
        state["status"]["dispatch"]["spine"]["errors"] = 1
        state["status"]["pool"]["replicas"][0]["state"] = "dead"
        state["counters"]["qa_degraded"] = 2
        state["counters"]["ask_failures"] = 1
        bad = " | ".join(run())
        for needle in ("spine errors", "replica not healthy",
                       "qa_degraded = 2", "ask_failures = 1"):
            assert needle in bad, bad
