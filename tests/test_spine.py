"""docqa-observatory: dispatch spine + cost observatory units.

Covers the ISSUE-11 test satellite: spine ordering / bounded queue /
cancellation / exception propagation, serve-vs-solo token equality with
every dispatch flowing through the spine, live ``dispatch_*`` telemetry
series, dual-dialect /metrics lint with the spine series present, and
the observatory's MFU accounting."""

import threading
import time

import pytest

from docqa_tpu.engines.spine import (
    DispatchSpine,
    SpineCancelled,
    SpineClosed,
    SpineSaturated,
    get_spine,
    set_spine,
)
from docqa_tpu.obs.observatory import Observatory, detect_peak_flops


def _gate():
    """An event-gated work item: runs block until released."""
    ev = threading.Event()

    def fn(tag, log):
        ev.wait(10)
        log.append(tag)
        return tag

    return ev, fn


class TestSpineCore:
    def test_run_returns_result_and_orders_fifo(self):
        s = DispatchSpine(n_lanes=1)
        try:
            log = []
            ev, fn = _gate()
            # occupy the single lane, then queue two more items; FIFO
            # order must hold within the serving class
            t1 = s.submit("a", fn, 1, log)
            for _ in range(100):  # lane picks the gated item up
                if s.stats()["busy_lanes"] == 1:
                    break
                time.sleep(0.01)
            t2 = s.submit("b", log.append, 2)
            t3 = s.submit("c", log.append, 3)
            assert s.queue_depth == 2
            ev.set()
            assert t1.result(timeout=10) == 1
            t2.result(timeout=10)
            t3.result(timeout=10)
            assert log == [1, 2, 3]
        finally:
            s.close()

    def test_bounded_queue_raises_typed(self):
        s = DispatchSpine(n_lanes=1, max_depth=1)
        try:
            ev, fn = _gate()
            s.submit("hold", fn, 0, [])  # occupies the lane
            time.sleep(0.05)  # let the lane pick it up
            s.submit("queued", lambda: None)  # fills the queue
            with pytest.raises(SpineSaturated):
                s.submit("overflow", lambda: None)
            ev.set()
        finally:
            s.close()

    def test_cancellation_before_start(self):
        s = DispatchSpine(n_lanes=1)
        try:
            ev, fn = _gate()
            ran = []
            s.submit("hold", fn, 0, [])
            time.sleep(0.05)
            t = s.submit("victim", ran.append, 1)
            assert t.cancel() is True
            ev.set()
            with pytest.raises(SpineCancelled):
                t.result(timeout=5)
            # a started/completed item refuses cancellation
            t2 = s.submit("done", lambda: 7)
            assert t2.result(timeout=10) == 7
            assert t2.cancel() is False
            assert ran == []
        finally:
            s.close()

    def test_exception_propagates_to_submitter(self):
        s = DispatchSpine(n_lanes=1)
        try:
            with pytest.raises(ValueError, match="boom"):
                s.run("bad", lambda: (_ for _ in ()).throw(ValueError("boom")))
            # the spine survives an item failure
            assert s.run("ok", lambda: 5) == 5
            assert s.stats()["errors"] == 1
        finally:
            s.close()

    def test_background_capped_below_lanes(self):
        s = DispatchSpine(n_lanes=2)
        try:
            running = []
            ev = threading.Event()

            def bg(tag):
                running.append(tag)
                ev.wait(10)
                return tag

            t1 = s.submit("w1", bg, 1, stream="warmup")
            t2 = s.submit("w2", bg, 2, stream="warmup")
            time.sleep(0.2)
            # only n_lanes-1 = 1 background item may occupy a lane; the
            # reserved lane still serves
            assert running == [1]
            assert s.run("serve_probe", lambda: "ok") == "ok"
            ev.set()
            assert t1.result(timeout=10) == 1
            assert t2.result(timeout=10) == 2
        finally:
            s.close()

    def test_lane_reentrancy_runs_inline(self):
        s = DispatchSpine(n_lanes=1)
        try:
            # an item whose closure submits again must not deadlock the
            # single lane: the nested call executes inline on the lane
            out = s.run("outer", lambda: s.run("inner", lambda: 42))
            assert out == 42
        finally:
            s.close()

    def test_inline_mode_executes_on_caller(self):
        s = DispatchSpine(n_lanes=1, inline=True)
        try:
            ident = s.run("x", threading.get_ident)
            assert ident == threading.get_ident()
            assert s.stats()["stages"]["x"]["count"] == 1
        finally:
            s.close()

    def test_deadline_sheds_before_execution(self):
        from docqa_tpu.resilience.deadline import Deadline, DeadlineExceeded

        s = DispatchSpine(n_lanes=1)
        try:
            ran = []
            dl = Deadline.after(0.0)
            time.sleep(0.01)
            with pytest.raises(DeadlineExceeded):
                s.run("late", ran.append, 1, deadline=dl)
            assert ran == []
        finally:
            s.close()

    def test_close_fails_queued_typed_and_rejects_new(self):
        s = DispatchSpine(n_lanes=1)
        ev, fn = _gate()
        s.submit("hold", fn, 0, [])
        time.sleep(0.05)
        t = s.submit("doomed", lambda: 1)
        # close() while the one lane is still held: "doomed" is failed
        # in the queue before close() starts joining lanes — released
        # first, the lane could pick it up and run it
        closer = threading.Thread(target=s.close)
        closer.start()
        with pytest.raises((SpineClosed, RuntimeError)):
            t.result(timeout=5)
        ev.set()
        closer.join(10)
        assert not closer.is_alive()
        with pytest.raises(SpineClosed):
            s.submit("after", lambda: 1)

    def test_stats_shape_and_gauges(self):
        s = DispatchSpine(n_lanes=2)
        try:
            s.run("stage_a", lambda: 1)
            s.run("stage_a", lambda: 2)
            st = s.stats()
            assert st["n_lanes"] == 2
            assert st["completed"] >= 2
            row = st["stages"]["stage_a"]
            assert row["count"] == 2
            assert row["device_s"] >= 0
            g = s.telemetry_gauges()
            assert set(g) >= {
                "dispatch_queue_depth",
                "dispatch_occupancy",
                "dispatch_lanes",
            }
            c = s.telemetry_counters()
            assert c["dispatch_count_stage_a"] == 2.0
            assert "dispatch_device_ms_stage_a" in c
            s.reset_stats()
            assert s.stats()["stages"] == {}
        finally:
            s.close()

    def test_strict_mode_serializes_lanes(self):
        """Strict mode (the multi-device-CPU-client guard): at most ONE
        lane executes at a time even with 2 lanes and concurrent
        submitters — exactly one device program can ever be in flight."""
        s = DispatchSpine(n_lanes=2)
        s.reconfigure(strict_sync=True)
        try:
            peak = []
            running = [0]
            lock = threading.Lock()

            def probe(_i):
                with lock:
                    running[0] += 1
                    peak.append(running[0])
                time.sleep(0.05)
                with lock:
                    running[0] -= 1

            threads = [
                threading.Thread(target=s.run, args=("strict", probe, i))
                for i in range(4)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(10)
            assert max(peak) == 1, peak
        finally:
            s.close()

    def test_strict_mode_syncs_items(self):
        s = DispatchSpine(n_lanes=2)
        s.reconfigure(strict_sync=True)
        try:
            # sync applies even without sync=True at the call site
            import jax.numpy as jnp

            out = s.run("strict_sync", lambda: jnp.ones((4,)) * 2)
            assert float(out.sum()) == 8.0
        finally:
            s.close()

    def test_global_spine_swap(self):
        mine = DispatchSpine(n_lanes=1)
        prev = set_spine(mine)
        try:
            assert get_spine() is mine
        finally:
            set_spine(prev)
            mine.close()


class TestObservatory:
    def test_mfu_and_roofline(self):
        obs = Observatory()
        # 1 GFLOP over 1 ms against a 197 TFLOP/s peak -> mfu ~ 0.005076
        obs.annotate("stage", flops=1e9, bytes_accessed=1e6, key="k")
        obs.record("stage", "k", 1e-3)
        st = obs.stats(
            peak={
                "peak_flops": 197e12,
                "peak_bytes_s": 819e9,
                "peak_flops_source": "test",
            }
        )
        row = st["stages"]["stage"]
        assert row["mfu"] == pytest.approx(1e9 / 1e-3 / 197e12, abs=1e-6)
        # intensity 1000 flops/byte >> ridge (~240) -> compute bound
        assert row["roofline_bound"] == "compute"

    def test_tuple_cost_keys_accumulate(self):
        obs = Observatory()
        obs.annotate("prefill", flops=100.0, key=128)
        obs.annotate("prefill", flops=50.0, key=64)
        obs.record("prefill", (128, 64), 1.0)  # one fetch, two groups
        st = obs.stats()
        assert st["stages"]["prefill"]["flops"] == 150.0

    def test_uncosted_calls_visible(self):
        obs = Observatory()
        obs.record("mystery", None, 0.5)
        row = obs.stats()["stages"]["mystery"]
        assert row["mfu"] is None
        assert row["uncosted_calls"] == 1

    def test_annotate_lowered_fenced(self):
        obs = Observatory()

        class Broken:
            def cost_analysis(self):
                raise RuntimeError("no estimate")

        assert obs.annotate_lowered("s", Broken()) is False

    def test_peaks_table_known_and_unknown_device(self):
        from docqa_tpu.obs.observatory import device_peaks

        row = device_peaks("TPU v5 lite")
        assert row["flops_bf16"] == 197e12 and row["hbm_bytes_s"] == 819e9
        assert row["source"]  # every peak names where it was published
        assert device_peaks("cpu") is None
        assert device_peaks(None) is None
        # this suite runs on the CPU backend: no row, so no MFU and no
        # roofline verdict — only counts (flops, bytes, their ratio)
        assert detect_peak_flops() is None
        obs = Observatory()
        obs.annotate("stage", flops=1e9, bytes_accessed=1e6, key="k")
        obs.record("stage", "k", 1e-3)
        st = obs.stats()
        assert st["peak"] is None
        row = st["stages"]["stage"]
        assert row["mfu"] is None and row["roofline_bound"] is None
        assert "mfu_raw_invalid" not in row
        assert row["intensity_flops_per_byte"] == 1000.0


class TestSpineServing:
    """Device-backed: the batcher + solo engine with every dispatch on
    the spine (the default path now) stay token-exact, feed the
    observatory, and surface dispatch_* telemetry."""

    @pytest.fixture(scope="class")
    def engine(self):
        from docqa_tpu.config import DecoderConfig, GenerateConfig
        from docqa_tpu.engines.generate import GenerateEngine

        return GenerateEngine(
            DecoderConfig(
                vocab_size=64, hidden_dim=32, num_layers=2, num_heads=4,
                num_kv_heads=4, head_dim=8, mlp_dim=64, max_seq_len=128,
                dtype="float32",
            ),
            GenerateConfig(temperature=0.0, prefill_buckets=(16,), eos_id=2),
            seed=0,
        )

    def test_serve_vs_solo_token_equality_through_spine(self, engine):
        from docqa_tpu.engines.serve import ContinuousBatcher

        b = ContinuousBatcher(engine, n_slots=2, chunk=4, cache_len=128)
        try:
            b.warmup()
            spine_before = get_spine().stats()["completed"]
            prompts = [[3, 5, 7], [9, 4, 6, 8]]
            handles = [
                b.submit_ids(p, max_new_tokens=6) for p in prompts
            ]
            served = [h.result(timeout=120) for h in handles]
            solo = engine.generate_ids(prompts, max_new_tokens=6)
            assert served == solo
            # every device phase flowed through the spine
            stats = get_spine().stats()
            assert stats["completed"] > spine_before
            stages = stats["stages"]
            for stage in ("serve_prefill", "serve_decode",
                          "serve_decode_chunk", "generate"):
                assert stage in stages, stages.keys()
        finally:
            b.stop()

    def test_costs_feed_mfu(self, engine):
        from docqa_tpu.engines.serve import ContinuousBatcher
        from docqa_tpu.obs.observatory import DEFAULT_OBSERVATORY

        b = ContinuousBatcher(engine, n_slots=2, chunk=4, cache_len=128)
        try:
            b.warmup()
            assert b.annotate_costs() is True
            DEFAULT_OBSERVATORY.reset()
            b.submit_ids([3, 5, 7], max_new_tokens=6).result(timeout=120)
            st = DEFAULT_OBSERVATORY.stats()
            row = st["stages"]["serve_decode_chunk"]
            assert row["flops"] > 0
            assert row["device_s"] > 0
            # CPU backend: not in the peaks table, so no utilization
            assert st["peak"] is None and row["mfu"] is None
        finally:
            b.stop()

    def test_dispatch_series_on_telemetry_and_metrics(self, engine):
        from docqa_tpu.obs.expo import lint_prometheus_text, prometheus_text
        from docqa_tpu.obs.telemetry import TelemetrySampler, TelemetryStore
        from docqa_tpu.runtime.metrics import MetricsRegistry

        engine.generate_ids([[1, 2, 3]], max_new_tokens=2)
        store = TelemetryStore(interval_s=1.0, points=60)
        sampler = TelemetrySampler(store, spine=get_spine())
        sampler.tick()
        names = store.names()
        assert "dispatch_queue_depth" in names
        assert "dispatch_occupancy" in names
        # per-stage device-time counters (the acceptance series)
        assert any(n.startswith("dispatch_device_ms_") for n in names)
        assert any(
            n == "dispatch_device_ms_generate" for n in names
        ), names
        # /metrics stays dual-dialect lint-clean with the new series
        reg = MetricsRegistry()
        for openmetrics in (False, True):
            text = prometheus_text(reg, store, openmetrics=openmetrics)
            assert lint_prometheus_text(text) == [], text
            assert "dispatch_queue_depth" in text
