"""docqa-trace (docqa_tpu/obs) tests.

The contracts that matter:

* deterministic ids, span nesting, zero-cost no-op when disabled;
* FlightRecorder retention — ring bounds, always-keep anomalous,
  slow-percentile flagging, open-trace eviction;
* propagation across the REAL thread boundaries: the ContinuousBatcher
  worker (trace ids identical on both sides, no cross-request leakage
  under concurrency) and the pipeline's deid/index consumer threads
  (one linked extract→deid→index timeline per document);
* exporters (timeline coverage, Chrome-trace structure), histogram
  exemplars, the trace-id log filter;
* the jit-purity lint rule fires on a span call leaked into a jit root
  (obs instrumentation must stay jit-exterior).
"""

import threading
import time

import pytest

from docqa_tpu import obs
from docqa_tpu.config import DecoderConfig, GenerateConfig
from docqa_tpu.obs.spans import Trace


@pytest.fixture(autouse=True)
def clean_recorder():
    obs.set_enabled(True)
    obs.DEFAULT_RECORDER.clear()
    yield
    obs.set_enabled(True)
    obs.DEFAULT_RECORDER.clear()


# ---------------------------------------------------------------------------
# ids / context / spans
# ---------------------------------------------------------------------------


class TestContext:
    def test_ids_are_deterministic(self):
        obs.reset_ids(prefix="x", start=9)
        c1 = obs.new_trace("a")
        c2 = obs.new_trace("b")
        assert c1.trace_id == "x-000009"
        assert c2.trace_id == "x-00000a"
        obs.reset_ids()

    def test_span_nesting_parents(self):
        ctx = obs.new_trace("root")
        with ctx.activate():
            with obs.start_span("outer") as outer:
                with obs.start_span("inner") as inner:
                    pass
        assert outer.parent_id == ctx.trace.root.span_id
        assert inner.parent_id == outer.span_id
        assert outer.t_end is not None and inner.t_end is not None

    def test_disabled_is_a_noop(self):
        obs.set_enabled(False)
        assert obs.new_trace("a") is None
        with obs.start_span("x") as sp:
            assert sp is None
        # call_in with None ctx runs plainly
        assert obs.call_in(None, lambda v: v + 1, 2) == 3
        assert obs.headers_of(None) == {}
        obs.finish(None)  # must not raise

    def test_headers_roundtrip_and_adoption(self):
        ctx = obs.new_trace("doc")
        hdrs = obs.headers_of(ctx)
        assert hdrs[obs.TRACE_HEADER] == ctx.trace_id
        # open trace: re-attach to the SAME object
        re = obs.from_headers(hdrs)
        assert re.trace is ctx.trace
        assert re.span_id == ctx.span_id
        # unknown id (post-restart replay): a stub is adopted under it
        stub = obs.from_headers({obs.TRACE_HEADER: "t-dead"})
        assert stub.trace_id == "t-dead"
        assert stub.trace.root.attrs.get("adopted") is True
        # and finish_id completes it, flagged
        obs.finish_id("t-dead", flag="dead_lettered")
        done = obs.DEFAULT_RECORDER.get("t-dead")
        assert done.finished and "dead_lettered" in done.flags

    def test_ensure_reuses_active_context(self):
        with obs.ensure("outer") as outer:
            with obs.ensure("inner") as inner:
                assert inner is outer
        assert obs.current() is None

    def test_cross_thread_handoff_via_run(self):
        ctx = obs.new_trace("xthread")
        seen = []

        def work():
            seen.append(obs.current_trace_id())

        t = threading.Thread(target=ctx.run, args=(work,))
        t.start()
        t.join()
        assert seen == [ctx.trace_id]
        assert obs.current_trace_id() is None  # nothing leaked here


# ---------------------------------------------------------------------------
# recorder retention
# ---------------------------------------------------------------------------


def _mk_done_trace(rec, name="t", duration_s=0.0, flag=None):
    ctx = rec.new_trace(name)
    if duration_s:
        # rewind the start so duration is synthetic, not slept
        ctx.trace.root.t_start -= duration_s
        ctx.trace.t0 -= duration_s
    if flag:
        ctx.trace.flag(flag)
    rec.complete(ctx.trace)
    return ctx.trace


class TestFlightRecorder:
    def test_ring_is_bounded_and_anomalous_always_kept(self):
        rec = obs.FlightRecorder(capacity=4, anomalous_capacity=4)
        bad = _mk_done_trace(rec, "bad", flag="degraded")
        for i in range(10):
            _mk_done_trace(rec, f"ok{i}")
        assert len(rec.recent(100)) == 4  # ring bounded
        # the flagged trace was evicted from the ring but survives in
        # the anomalous ring, and get() still finds it
        assert rec.get(bad.trace_id) is bad
        assert [t.trace_id for t in rec.anomalous(10)] == [bad.trace_id]

    def test_slow_percentile_flagging(self):
        rec = obs.FlightRecorder(min_slow_samples=10, slow_percentile=95.0)
        for i in range(20):
            _mk_done_trace(rec, f"fast{i}", duration_s=0.001)
        slow = _mk_done_trace(rec, "slow", duration_s=1.0)
        assert any(f.startswith("slow_p") for f in slow.flags)
        assert slow in rec.anomalous(10)

    def test_open_traces_are_evicted_bounded(self):
        rec = obs.FlightRecorder(max_open=3)
        first = rec.new_trace("leak0")
        for i in range(1, 5):
            rec.new_trace(f"leak{i}")
        assert len(rec.open_traces()) == 3
        evicted = rec.get(first.trace_id)
        assert evicted.finished and "abandoned" in evicted.flags

    def test_complete_is_idempotent(self):
        rec = obs.FlightRecorder()
        ctx = rec.new_trace("once")
        rec.complete(ctx.trace)
        rec.complete(ctx.trace)  # second completion must not double-add
        assert len(rec.recent(10)) == 1

    def test_summaries_shape(self):
        _mk_done_trace(obs.DEFAULT_RECORDER, "s", flag="degraded")
        rows = obs.DEFAULT_RECORDER.summaries(anomalous=True)
        assert rows and set(rows[0]) >= {
            "trace_id", "name", "flags", "duration_ms", "n_spans",
        }


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------


class TestExport:
    def test_coverage_merges_overlaps(self):
        tr = Trace("t-c", "r")
        t0 = tr.t0
        # two overlapping children over [0,0.9] of a 1.0 s root
        tr.record_span("a", t0, t0 + 0.6)
        tr.record_span("b", t0 + 0.5, t0 + 0.9)
        tr.root.t_end = t0 + 1.0
        tr.status = "ok"
        assert obs.coverage(tr) == pytest.approx(0.9, abs=0.01)

    def test_timeline_dict_is_relative_ms(self):
        ctx = obs.new_trace("tl")
        ctx.trace.record_span("stage", ctx.trace.t0, ctx.trace.t0 + 0.05)
        obs.finish(ctx)
        d = obs.timeline_dict(ctx.trace)
        stage = [s for s in d["spans"] if s["name"] == "stage"][0]
        assert stage["start_ms"] == pytest.approx(0.0, abs=0.5)
        assert stage["duration_ms"] == pytest.approx(50.0, abs=1.0)
        assert 0.0 <= d["coverage"] <= 1.0

    def test_chrome_trace_structure(self):
        ctx = obs.new_trace("web")
        with ctx.activate():
            with obs.start_span("stage"):
                ctx.trace.add_event("tick", span_id=None, k=1)
        obs.finish(ctx)
        out = obs.to_chrome_trace([ctx.trace])
        phs = [e["ph"] for e in out["traceEvents"]]
        assert "M" in phs and "X" in phs and "i" in phs  # meta/span/event
        x = [e for e in out["traceEvents"] if e["ph"] == "X"]
        assert all("ts" in e and "dur" in e and e["pid"] == 1 for e in x)
        assert any(e["args"].get("trace_id") == ctx.trace_id for e in x)


# ---------------------------------------------------------------------------
# metrics integration: span() -> trace span + exemplar; log filter
# ---------------------------------------------------------------------------


class TestMetricsIntegration:
    def test_metrics_span_records_trace_span_and_exemplar(self):
        from docqa_tpu.runtime.metrics import MetricsRegistry, span

        reg = MetricsRegistry()
        ctx = obs.new_trace("m")
        with ctx.activate():
            with span("stagex", reg):
                time.sleep(0.002)
        obs.finish(ctx)
        names = [s.name for s in ctx.trace.snapshot_spans()]
        assert "stagex" in names
        summary = reg.histogram("stagex_ms").summary()
        assert summary["exemplars"][0]["trace_id"] == ctx.trace_id

    def test_exemplars_keep_largest(self):
        from docqa_tpu.runtime.metrics import Histogram

        h = Histogram("h")
        for i in range(20):
            h.observe(float(i), trace_id=f"t{i}")
        h.observe(999.0, trace_id="slowest")
        ex = h.exemplars()
        assert len(ex) == Histogram.MAX_EXEMPLARS
        assert ex[0] == {"value": 999.0, "trace_id": "slowest"}
        # untraced observations never take an exemplar slot
        h2 = Histogram("h2")
        h2.observe(5.0)
        assert "exemplars" not in h2.summary()

    def test_log_filter_prefixes_trace_id(self, caplog):
        from docqa_tpu.runtime.metrics import get_logger

        log = get_logger("docqa.obs_test")
        ctx = obs.new_trace("logged")
        with caplog.at_level("INFO", logger="docqa.obs_test"):
            with ctx.activate():
                log.info("inside %s", "fmt")
            log.info("outside")
        msgs = [r.getMessage() for r in caplog.records]
        assert f"trace_id={ctx.trace_id} inside fmt" in msgs
        assert "outside" in msgs  # untraced lines stay untouched


# ---------------------------------------------------------------------------
# propagation across the batcher worker thread
# ---------------------------------------------------------------------------

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


@pytest.fixture(scope="module")
def engine():
    from docqa_tpu.engines.generate import GenerateEngine

    return GenerateEngine(CFG, GEN, seed=3)


@pytest.fixture()
def batcher(engine):
    from docqa_tpu.engines.serve import ContinuousBatcher

    b = ContinuousBatcher(engine, n_slots=4, chunk=4, cache_len=128)
    yield b
    b.stop()


class TestBatcherPropagation:
    def test_one_linked_timeline_per_request(self, batcher):
        ctx = obs.new_trace("ask")
        with ctx.activate():
            h = batcher.submit_ids([3, 5, 9], max_new_tokens=6)
        h.result(timeout=120)
        obs.finish(ctx)
        names = [s.name for s in ctx.trace.snapshot_spans()]
        # the full submit→admit→prefill→decode→result-wait chain landed
        # on the SUBMITTER's trace even though the worker recorded it
        assert names.count("serve_queue_wait") == 1
        assert names.count("serve_prefill") == 1
        assert names.count("serve_decode_chunk") >= 1
        assert names.count("serve_result_wait") == 1
        # coverage: no unattributed gap > 5% of request wall
        assert obs.coverage(ctx.trace) >= 0.95

    def test_no_cross_request_leakage_under_concurrency(self, batcher):
        n = 8
        ctxs, handles = [], []
        for i in range(n):
            ctx = obs.new_trace(f"ask{i}")
            prompt = [3 + j for j in range(2 + i)]  # distinct lengths
            with ctx.activate():
                handles.append(
                    batcher.submit_ids(prompt, max_new_tokens=4)
                )
            ctxs.append((ctx, len(prompt)))
        for (ctx, _n), h in zip(ctxs, handles):
            h.result(timeout=240)
            obs.finish(ctx)
        seen_span_ids = set()
        for ctx, prompt_len in ctxs:
            spans = ctx.trace.snapshot_spans()
            names = [s.name for s in spans]
            assert names.count("serve_queue_wait") == 1
            assert names.count("serve_result_wait") == 1
            # submit event carries THIS request's prompt length — a
            # crossed wire would show another request's
            submit_evts = [
                e for s in spans for e in s.events
                if e["name"] == "serve_submit"
            ]
            assert len(submit_evts) == 1
            assert submit_evts[0]["prompt_len"] == prompt_len
            ids = {(ctx.trace_id, s.span_id) for s in spans}
            assert not (ids & seen_span_ids)
            seen_span_ids |= ids

    def test_deadline_shed_flags_the_trace(self, batcher):
        from docqa_tpu.resilience.deadline import (
            Deadline,
            DeadlineExceeded,
        )

        ctx = obs.new_trace("shed")
        with ctx.activate():
            with pytest.raises(DeadlineExceeded):
                batcher.submit_ids(
                    [3, 5], max_new_tokens=4,
                    deadline=Deadline.after(-1.0),
                )
        obs.finish(ctx, status="error")
        assert "deadline_exceeded" in ctx.trace.flags
        # flagged traces ride the always-keep ring
        assert ctx.trace in obs.DEFAULT_RECORDER.anomalous(10)


# ---------------------------------------------------------------------------
# propagation across the pipeline consumer threads
# ---------------------------------------------------------------------------


@pytest.fixture()
def pipeline(tmp_path):
    from docqa_tpu.config import load_config
    from docqa_tpu.deid.engine import DeidEngine
    from docqa_tpu.engines.encoder import HashEncoder
    from docqa_tpu.index.store import VectorStore
    from docqa_tpu.service.broker import MemoryBroker
    from docqa_tpu.service.pipeline import DocumentPipeline
    from docqa_tpu.service.registry import DocumentRegistry

    cfg = load_config(env={}, overrides={
        "encoder.embed_dim": 32,
        "store.dim": 32,
        "store.shard_capacity": 256,
        "ner.hidden_dim": 32,
        "ner.num_layers": 1,
        "ner.num_heads": 2,
        "ner.mlp_dim": 64,
        "ner.train_steps": 0,
        "flags.use_fake_encoder": True,
    })
    p = DocumentPipeline(
        cfg,
        MemoryBroker(cfg.broker),
        DocumentRegistry(),
        DeidEngine(cfg.ner),
        HashEncoder(cfg.encoder),
        VectorStore(cfg.store),
    )
    p.start()
    yield p
    p.stop()


class TestPipelinePropagation:
    def test_document_timeline_links_extract_deid_index(self, pipeline):
        rec = pipeline.ingest_text(
            "Patient on aspirin 100 mg daily. BP 120/80.",
            filename="n1.txt",
        )
        assert pipeline.wait_indexed(rec.doc_id, timeout=30)
        # find the doc's completed trace in the recorder
        traces = [
            t for t in obs.DEFAULT_RECORDER.recent(20)
            if t.root.attrs.get("doc_id") == rec.doc_id
        ]
        assert len(traces) == 1
        tr = traces[0]
        assert tr.finished and tr.status == "ok"
        names = [s.name for s in tr.snapshot_spans()]
        # the ingest-thread extract AND both consumer-thread hops landed
        # on ONE trace — the ids crossed the broker via headers
        assert "extract" in names
        assert "deid_batch" in names
        assert "index_batch" in names

    def test_concurrent_documents_get_distinct_timelines(self, pipeline):
        recs = [
            pipeline.ingest_text(f"Note {i}: vitals stable.", filename=f"n{i}.txt")
            for i in range(4)
        ]
        for r in recs:
            assert pipeline.wait_indexed(r.doc_id, timeout=30)
        by_doc = {
            t.root.attrs.get("doc_id"): t
            for t in obs.DEFAULT_RECORDER.recent(20)
        }
        for r in recs:
            tr = by_doc[r.doc_id]
            assert tr.status == "ok"
            # every span of this trace belongs to this doc (no leakage):
            # batch spans carry the doc_id they were attributed to
            for s in tr.snapshot_spans():
                if "doc_id" in s.attrs:
                    assert s.attrs["doc_id"] == r.doc_id


# ---------------------------------------------------------------------------
# lint: obs spans must stay jit-exterior
# ---------------------------------------------------------------------------


@pytest.mark.lint
class TestJitPurityGuard:
    def test_span_inside_jit_root_is_flagged(self, tmp_path):
        import textwrap

        from docqa_tpu.analysis import run

        (tmp_path / "mod.py").write_text(textwrap.dedent(
            """
            import jax
            from docqa_tpu.runtime.metrics import span

            @jax.jit
            def decode_step(x):
                with span("serve_decode_chunk"):
                    return x + 1
            """
        ))
        findings = run(
            str(tmp_path), rules=["jit-purity"], package_name="fixture"
        )
        assert any(
            "span()" in f.message for f in findings
        ), findings