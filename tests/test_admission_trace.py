"""Admission on the record (ISSUE 25).

* the four serve spans of a request (``serve_queue_wait``,
  ``serve_admit_hold``, ``serve_prefill``, ``serve_first_token``) tile its
  time from submit to its first token, end to start;
* the counters of the two dispatch sites stay consistent with each other;
* ``metrics.span`` annotates the profiler's trace exactly while the
  program's own window is open, and keeps the signature the benchmark's
  traced runs call it with.
"""

import inspect

import pytest

from docqa_tpu import obs
from docqa_tpu.config import DecoderConfig, GenerateConfig
from docqa_tpu.runtime import metrics
from docqa_tpu.runtime.metrics import DEFAULT_REGISTRY, MetricsRegistry

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

TILE = ("serve_queue_wait", "serve_admit_hold", "serve_prefill",
        "serve_first_token")
COUNTERS = ("serve_admit_rounds", "serve_admitted", "serve_prefill_tokens",
            "serve_prefill_budget_tokens", "serve_decode_chunks",
            "serve_decode_chunks_stale", "serve_decode_chunks_skipped",
            "serve_prefill_dispatches", "serve_prefill_rounds_split")
GAP_S = 5e-3


@pytest.fixture(autouse=True)
def clean_recorder():
    obs.set_enabled(True)
    obs.DEFAULT_RECORDER.clear()
    yield
    obs.set_enabled(True)
    obs.DEFAULT_RECORDER.clear()


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


def counters():
    return {n: DEFAULT_REGISTRY.counter(n).value for n in COUNTERS}


@pytest.fixture()
def burst(batcher):
    """One request, then three more while the worker is busy with it:
    (trace, request) of each, all finished, and what the counters and the
    tokens-per-chunk histogram gained meanwhile."""
    before = counters()
    chunks = DEFAULT_REGISTRY.histogram("serve_tokens_per_chunk")
    chunks_before = chunks.summary()["count"]
    done = []
    for i in range(4):
        ctx = obs.new_trace(f"ask{i}")
        with ctx.activate():
            h = batcher.submit_ids(
                [3 + j for j in range(5 + 3 * i)], max_new_tokens=6
            )
        done.append((ctx, h))
    for ctx, h in done:
        h.result(timeout=240)
        obs.finish(ctx)
    gained = {n: v - before[n] for n, v in counters().items()}
    gained["chunk_observations"] = chunks.summary()["count"] - chunks_before
    return [(ctx.trace, h._req) for ctx, h in done], gained


class TestServeSpansTile:
    def test_one_hold_and_one_first_token_per_request(self, burst):
        for trace, _req in burst[0]:
            names = [s.name for s in trace.snapshot_spans()]
            for name in TILE:
                assert names.count(name) == 1, (name, names)

    def test_spans_tile_submit_to_first_token(self, burst):
        for trace, req in burst[0]:
            spans = {s.name: s for s in trace.snapshot_spans()}
            first_token = [
                e["t"] for s in trace.snapshot_spans() for e in s.events
                if e["name"] == "first_token"
            ]
            assert len(first_token) == 1
            edges = [req.t_submit]
            for name in TILE:
                edges += [spans[name].t_start, spans[name].t_end]
            edges.append(first_token[0])
            # submit | queue | hold | prefill | first token | the mark:
            # each span starts where the one before ended
            for end, start in zip(edges[0::2], edges[1::2]):
                assert -1e-9 <= start - end <= GAP_S, (edges, trace.trace_id)
            for name in TILE:
                assert spans[name].t_end >= spans[name].t_start

    def test_attributes_name_the_dispatch(self, burst):
        for trace, req in burst[0]:
            spans = {s.name: s for s in trace.snapshot_spans()}
            hold = spans["serve_admit_hold"].attrs
            assert isinstance(hold["drained"], bool)
            assert 1 <= hold["round"] <= 4
            for name in ("serve_prefill", "serve_first_token"):
                attrs = spans[name].attrs
                # rows the prompts take at their aligned starts fit the
                # budget the dispatch ran; this prompt's tokens fit those
                assert len(req.prompt_ids) <= attrs["packed_tokens"]
                assert attrs["packed_tokens"] <= attrs["budget_tokens"]
                assert attrs["batch"] == hold["round"]
            # its dispatch group is one of the groups its round ran
            prefill = spans["serve_prefill"].attrs
            assert 0 <= prefill["dispatch"] < prefill["dispatches"]
            assert prefill["dispatches"] <= prefill["batch"]


class TestDispatchCounters:
    def test_admission_counters(self, burst):
        traces, gained = burst
        assert gained["serve_admitted"] == 4
        assert 1 <= gained["serve_admit_rounds"] <= 4
        assert gained["serve_prefill_tokens"] == sum(
            len(req.prompt_ids) for _t, req in traces
        )
        assert (gained["serve_prefill_tokens"]
                <= gained["serve_prefill_budget_tokens"])
        # a round is at least one dispatch group and a group at least one
        # request; only a round of several groups can count as split
        assert (gained["serve_admit_rounds"]
                <= gained["serve_prefill_dispatches"]
                <= gained["serve_admitted"])
        assert (gained["serve_prefill_rounds_split"]
                <= gained["serve_prefill_dispatches"]
                - gained["serve_admit_rounds"])
        rounds = {s.attrs["round"] for t, _r in traces
                  for s in t.snapshot_spans()
                  if s.name == "serve_admit_hold"}
        # as many rounds as the holds say, when the sizes differ
        assert gained["serve_admit_rounds"] >= len(rounds)

    def test_decode_chunk_counters(self, burst):
        _traces, gained = burst
        assert gained["serve_decode_chunks"] >= 1
        assert (0 <= gained["serve_decode_chunks_stale"]
                <= gained["serve_decode_chunks"])
        # one observation of tokens delivered per fetched chunk, a stale
        # chunk's 0 included
        assert gained["chunk_observations"] == gained["serve_decode_chunks"]

    def test_a_stale_chunk_delivers_nothing(self, batcher):
        """A chunk fetched after every lane of its snapshot retired counts
        as stale and observes 0 tokens."""
        import numpy as np

        from docqa_tpu.engines.serve import make_request

        def delivered(hist):
            return hist.count, (hist.count and hist.count * hist.mean)

        # the packed result of one chunk, as the plain and the
        # speculative decode programs lay it out
        width = (
            batcher.chunk + 2 * batcher.spec_k + 2 if batcher.spec_k
            else 2 * batcher.chunk + 1
        )
        packed = np.ones((batcher.n_slots, width), np.int32)
        # the slot's occupant at dispatch has retired since: slot 0 is free
        snap = [make_request([3, 5], 4)] + [None] * (batcher.n_slots - 1)
        before = counters()
        hist = DEFAULT_REGISTRY.histogram("serve_tokens_per_chunk")
        n0, total0 = delivered(hist)
        assert batcher._process_chunk(packed, snap)
        gained = {n: v - before[n] for n, v in counters().items()}
        assert gained["serve_decode_chunks"] == 1
        assert gained["serve_decode_chunks_stale"] == 1
        n1, total1 = delivered(hist)
        assert n1 == n0 + 1
        assert total1 == pytest.approx(total0)


class TestWorkerPhaseSpans:
    def test_worker_phases_feed_their_histograms(self, burst):
        """The four phases added around the worker's blocking calls are
        ``span()`` sites: each feeds ``<name>_ms``."""
        snap = DEFAULT_REGISTRY.snapshot()["histograms"]
        for name in ("serve_admit_round", "serve_first_token_fetch",
                     "serve_idle_wait", "serve_admit_drain"):
            assert snap[f"{name}_ms"]["count"] >= 1, name

    def test_every_admission_round_records_its_drain(self, batcher):
        """``serve_admit_drain`` is what an admission waited for the
        pipeline: one sample a round, 0 ms where nothing was in flight —
        a lane that ended on its budget left no chunk behind, so the
        round after it meets an empty pipeline and is still counted."""
        drain = DEFAULT_REGISTRY.histogram("serve_admit_drain_ms")
        rounds = DEFAULT_REGISTRY.counter("serve_admit_rounds")
        skipped = DEFAULT_REGISTRY.counter("serve_decode_chunks_skipped")
        n0, r0, s0 = drain.count, rounds.value, skipped.value
        for i in range(2):  # into an idle batcher, then behind a budget end
            ctx = obs.new_trace(f"alone{i}")
            with ctx.activate():
                h = batcher.submit_ids([3, 4, 5, 6, 7], max_new_tokens=5)
            assert len(h.result(timeout=240)) == 5  # ended on its budget
            obs.finish(ctx)
            hold = [s for s in ctx.trace.snapshot_spans()
                    if s.name == "serve_admit_hold"]
            assert [s.attrs["drained"] for s in hold] == [False]
        assert rounds.value - r0 == 2
        assert drain.count - n0 == 2
        assert skipped.value - s0 == 2  # 1 + one chunk: no second one


class TestSpanAnnotatesTheProfilerWindow:
    @pytest.fixture()
    def annotations(self, monkeypatch):
        import contextlib

        import jax.profiler

        opened = []

        @contextlib.contextmanager
        def spy(name):
            opened.append(name)
            yield

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", spy)
        monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
        monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
        return opened

    def test_signature_is_the_one_traced_runs_call(self):
        params = inspect.signature(metrics.span).parameters
        assert list(params) == ["name", "registry", "profile"]
        assert params["registry"].default is None
        assert params["profile"].default is False

    def test_annotation_only_while_the_window_is_open(
        self, annotations, tmp_path
    ):
        reg = MetricsRegistry()
        with metrics.span("stage_shut", reg):
            pass
        assert annotations == []
        obs.DEFAULT_PROFILER.start(str(tmp_path))
        try:
            with metrics.span("stage_open", reg):
                pass
        finally:
            obs.DEFAULT_PROFILER.stop()
        with metrics.span("stage_shut_again", reg):
            pass
        assert annotations == ["stage_open"]
        assert not obs.DEFAULT_PROFILER.active
        # every span fed its histogram, annotated or not
        assert set(reg.snapshot()["histograms"]) == {
            "stage_shut_ms", "stage_open_ms", "stage_shut_again_ms"
        }

    def test_positional_profile_still_annotates(self, annotations):
        """``span(name, registry, True)``: the call the benchmark's
        wrapper makes in every traced run."""
        reg = MetricsRegistry()
        with metrics.span("forced", reg, True):
            pass
        assert annotations == ["forced"]
        assert reg.snapshot()["histograms"]["forced_ms"]["count"] == 1
