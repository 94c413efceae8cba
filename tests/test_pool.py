"""Replicated decode-engine pool (engines/pool.py; docqa-pool).

The contract under test is the zero-lost-requests invariant: whatever
happens to a replica — worker crash, wedge, drain, rebuild — every
submitted request either completes with the tokens a solo engine would
produce, or fails with a TYPED error inside its deadline.  Nothing hangs
to a bare ResultTimeout; that hang is the failure mode the pool exists
to remove (ISSUE 6 / ROADMAP item 5).

Fault-injection tests ride the ``faults`` marker (``pytest -m faults``).
"""

import threading
import time

import pytest

from docqa_tpu.config import DecoderConfig, GenerateConfig
from docqa_tpu.engines.generate import GenerateEngine
from docqa_tpu.engines.pool import EnginePool, FailoverExhausted
from docqa_tpu.engines.serve import (
    ContinuousBatcher,
    Draining,
    QueueFull,
    RequestCancelled,
    WorkerDied,
)
from docqa_tpu.resilience import Deadline, DeadlineExceeded, FaultPlan, FaultRule

CFG = DecoderConfig(
    vocab_size=128,
    hidden_dim=64,
    num_layers=2,
    num_heads=4,
    num_kv_heads=2,
    head_dim=16,
    mlp_dim=128,
    max_seq_len=256,
    dtype="float32",
)
GEN = GenerateConfig(temperature=0.0, prefill_buckets=(16, 32), eos_id=2)


@pytest.fixture(scope="module")
def engine():
    return GenerateEngine(CFG, GEN, seed=7)


def make_pool(engine, **kw):
    kw.setdefault("replicas", 2)
    kw.setdefault("n_slots", 2)
    kw.setdefault("chunk", 4)
    kw.setdefault("cache_len", 128)
    # no canary traffic unless a test asks for it: canaries are their own
    # liveness channel and would add nondeterministic load here
    kw.setdefault("canary_interval_s", 600.0)
    kw.setdefault("health_interval_s", 0.05)
    kw.setdefault("breaker_reset_s", 0.2)
    return EnginePool(engine, **kw)


def _prompts(n, base=3):
    return [[base + i, 5 + i % 7, 9, 4 + i % 3] for i in range(n)]


class TestPoolServing:
    def test_matches_solo_engine_across_replicas(self, engine):
        """Routing through N replicas must be answer-invisible: the same
        greedy tokens a solo engine produces, whichever replica served."""
        prompts = _prompts(6)
        solo = [engine.generate_ids([p], max_new_tokens=8)[0] for p in prompts]
        pool = make_pool(engine)
        try:
            handles = [pool.submit_ids(p, max_new_tokens=8) for p in prompts]
            got = [h.result(timeout=240) for h in handles]
        finally:
            pool.stop()
        assert got == solo

    def test_routes_to_all_replicas(self, engine):
        pool = make_pool(engine)
        try:
            handles = [
                pool.submit_ids(p, max_new_tokens=4) for p in _prompts(8)
            ]
            for h in handles:
                h.result(timeout=240)
            st = pool.status()
        finally:
            pool.stop()
        assert sum(r["routed"] for r in st["replicas"]) == 8
        # least-queued routing over concurrent arrivals spreads the work
        assert all(r["routed"] > 0 for r in st["replicas"])

    def test_status_surface(self, engine):
        pool = make_pool(engine)
        try:
            st = pool.status()
        finally:
            pool.stop()
        assert len(st["replicas"]) == 2
        for r in st["replicas"]:
            assert r["state"] == "healthy"
            assert r["worker_alive"] is True
            assert r["breaker"] == "closed"
        assert st["hedge"]["enabled"] is False

    def test_pool_handle_is_batcher_shaped(self, engine):
        """qa.py/summarize call result/text/iter_tokens/cancel on whatever
        the runtime wired — the pool handle must expose all of them."""
        pool = make_pool(engine, replicas=1)
        try:
            h = pool.submit_ids([3, 5, 9], max_new_tokens=4)
            assert hasattr(h, "text") and hasattr(h, "cancel")
            toks = list(h.iter_tokens(timeout=240))
            assert toks == engine.generate_ids(
                [[3, 5, 9]], max_new_tokens=4
            )[0]
        finally:
            pool.stop()


# ---- single-engine worker death (ISSUE 6 satellite: typed, not hangs) ------


@pytest.mark.faults
class TestWorkerDeathSoloBatcher:
    def test_worker_death_delivers_typed_errors_to_all_waiters(self, engine):
        """A solo batcher (no pool) whose worker loop dies must fail every
        queued AND admitted request with WorkerDied — including streaming
        ``iter_tokens`` waiters — instead of stranding them to their
        result timeouts."""
        b = ContinuousBatcher(engine, n_slots=2, chunk=4, cache_len=128)
        outcomes = {}
        lock = threading.Lock()
        try:
            b.warmup()
            plan = FaultPlan(
                [FaultRule("serve.worker_loop", at_steps=(1,))], seed=3
            )
            with plan:
                handles = [
                    b.submit_ids(p, max_new_tokens=30) for p in _prompts(5)
                ]

                def stream_one(idx, h):
                    try:
                        toks = list(h.iter_tokens(timeout=30))
                        outcome = ("ok", len(toks))
                    except WorkerDied as e:
                        outcome = ("worker_died", repr(e))
                    except Exception as e:  # pragma: no cover - diagnostic
                        outcome = ("other", repr(e))
                    with lock:
                        outcomes[idx] = outcome

                def wait_one(idx, h):
                    try:
                        toks = h.result(timeout=30)
                        outcome = ("ok", len(toks))
                    except WorkerDied as e:
                        outcome = ("worker_died", repr(e))
                    except Exception as e:  # pragma: no cover - diagnostic
                        outcome = ("other", repr(e))
                    with lock:
                        outcomes[idx] = outcome

                threads = [
                    threading.Thread(
                        target=stream_one if i % 2 else wait_one,
                        args=(i, h),
                    )
                    for i, h in enumerate(handles)
                ]
                t0 = time.monotonic()
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=60)
                elapsed = time.monotonic() - t0
            assert len(plan.log) == 1  # the injected crash fired
        finally:
            b.stop()
        assert len(outcomes) == 5, f"waiter(s) hung: {outcomes}"
        # typed failure (or clean completion for work that beat the
        # crash) — never a hang to the 30 s result timeout
        assert elapsed < 25
        kinds = {k for k, _ in outcomes.values()}
        assert kinds <= {"ok", "worker_died"}, outcomes
        assert "worker_died" in kinds  # the crash really failed someone
        assert not b.worker_alive

    def test_submit_after_death_raises_immediately(self, engine):
        b = ContinuousBatcher(engine, n_slots=2, chunk=4, cache_len=128)
        try:
            plan = FaultPlan(
                [FaultRule("serve.worker_loop", at_steps=(0,))], seed=0
            )
            with plan:
                deadline = time.monotonic() + 30
                while b.worker_alive and time.monotonic() < deadline:
                    try:
                        b.submit_ids([3, 5], max_new_tokens=2)
                    except WorkerDied:
                        break
                    time.sleep(0.02)
            assert not b.worker_alive
            with pytest.raises(WorkerDied):
                b.submit_ids([3, 5], max_new_tokens=2)
        finally:
            b.stop()


# ---- pool failover ----------------------------------------------------------


@pytest.mark.faults
class TestPoolFailover:
    def test_replica_crash_zero_lost_requests(self, engine):
        """Kill one replica's worker mid-traffic: queued requests fail
        over to the healthy replica, admitted ones fail typed, and the
        dead replica is rebuilt — zero hangs."""
        pool = make_pool(engine)
        try:
            pool.warmup()
            plan = FaultPlan(
                [FaultRule("serve.worker_loop", at_steps=(2,))], seed=11
            )
            results = {}
            lock = threading.Lock()
            with plan:
                handles = [
                    pool.submit_ids(
                        p, max_new_tokens=12, deadline=Deadline.after(60)
                    )
                    for p in _prompts(10)
                ]

                def wait_one(idx, h):
                    try:
                        out = ("ok", len(h.result(timeout=90)))
                    except (WorkerDied, DeadlineExceeded, QueueFull) as e:
                        out = ("typed", repr(e))
                    except Exception as e:
                        out = ("HUNG_OR_UNTYPED", repr(e))
                    with lock:
                        results[idx] = out

                threads = [
                    threading.Thread(target=wait_one, args=(i, h))
                    for i, h in enumerate(handles)
                ]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=120)
            assert len(plan.log) == 1
        finally:
            st = pool.status()
            pool.stop()
        assert len(results) == 10, "waiter(s) hung"
        kinds = {k for k, _ in results.values()}
        assert "HUNG_OR_UNTYPED" not in kinds, results
        assert sum(r["deaths"] for r in st["replicas"]) >= 1
        # most requests must SUCCEED (failover, not mass shedding): only
        # requests admitted on the dying replica may fail typed
        n_ok = sum(1 for k, _ in results.values() if k == "ok")
        assert n_ok >= 6, results

    def test_wedge_detected_and_replica_rebuilt(self, engine):
        """A wedged (not crashed) worker — heartbeat stale with work
        pending — is declared dead by the monitor, its queued work moves,
        and the replica rebuilds."""
        pool = make_pool(engine, heartbeat_max_age_s=0.6)
        try:
            pool.warmup()  # flip `cold` off so wedge detection engages
            plan = FaultPlan(
                [
                    FaultRule(
                        "serve.worker_loop",
                        at_steps=(2,),
                        delay_s=2.0,
                        raise_error=False,
                    )
                ],
                seed=5,
            )
            results = {}
            lock = threading.Lock()
            with plan:
                handles = [
                    pool.submit_ids(
                        p, max_new_tokens=10, deadline=Deadline.after(60)
                    )
                    for p in _prompts(8)
                ]

                def wait_one(idx, h):
                    try:
                        out = ("ok", len(h.result(timeout=90)))
                    except (WorkerDied, DeadlineExceeded, QueueFull) as e:
                        out = ("typed", repr(e))
                    except Exception as e:
                        out = ("HUNG_OR_UNTYPED", repr(e))
                    with lock:
                        results[idx] = out

                threads = [
                    threading.Thread(target=wait_one, args=(i, h))
                    for i, h in enumerate(handles)
                ]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=120)
            assert plan.log  # the wedge stall fired
        finally:
            st = pool.status()
            pool.stop()
        assert len(results) == 8, "waiter(s) hung"
        assert not any(k == "HUNG_OR_UNTYPED" for k, _ in results.values()), (
            results
        )
        assert sum(1 for k, _ in results.values() if k == "ok") >= 4

    def test_failover_exhausted_is_typed_worker_died(self):
        # the QA layer catches WorkerDied; the hop-budget failure must be
        # a subtype so it degrades the same way
        assert issubclass(FailoverExhausted, WorkerDied)

    def test_wedge_inside_admission_window_fails_typed(self, engine):
        """A worker wedged BETWEEN the queue pop and slot assignment
        (hung host->device transfer inside the admission round) shows 0
        queued AND 0 active — only ``n_admitting`` betrays the pending
        work.  The monitor must still declare the wedge, and every
        request in the window must fail typed instead of hanging to its
        ResultTimeout."""
        pool = make_pool(engine, replicas=1, heartbeat_max_age_s=0.5)
        try:
            pool.warmup()  # flip `cold` off so wedge detection engages
            b = pool._replicas[0].batcher
            release = threading.Event()

            def hung_admit(pairs, drained_at=None):
                # popped, never slot-resident; released only at teardown
                release.wait(30)
                raise WorkerDied("test wedge released")

            b._admit_round = hung_admit
            handles = [
                pool.submit_ids(
                    p, max_new_tokens=8, deadline=Deadline.after(60)
                )
                for p in _prompts(3)
            ]
            t0 = time.monotonic()
            while b.n_admitting == 0 and time.monotonic() - t0 < 10:
                time.sleep(0.01)
            assert b.n_admitting > 0  # the window is populated...
            assert b.n_active == 0  # ...and invisible to the slot count
            outcomes = []
            for h in handles:
                try:
                    outcomes.append(("ok", len(h.result(timeout=30))))
                except (WorkerDied, DeadlineExceeded) as e:
                    outcomes.append(("typed", repr(e)))
            # window requests fail typed (queued stragglers may park and
            # complete after the rebuild) — never a ResultTimeout hang
            assert len(outcomes) == 3, outcomes
            assert any(k == "typed" for k, _ in outcomes), outcomes
            assert pool._replicas[0].deaths >= 1  # wedge was declared
        finally:
            release.set()
            pool.stop()


# ---- hedged dispatch --------------------------------------------------------


@pytest.mark.faults
class TestHedgedDispatch:
    def test_hedge_duplicates_queued_request_first_token_wins(self, engine):
        """Hedging triggers for a request with NO first token after the
        p95 delay — i.e. one stuck queued behind load (prefill emits the
        first token, so an admitted request never hedges).  Occupy both
        replicas' single slots with long decodes, queue a third request:
        the monitor duplicates it onto the other replica, both copies
        race from their queues, the first token wins and the answer is
        solo-identical.

        The slot-holding decodes are pinned slow with an injected
        per-chunk delay: on a warm host 60 tokens of a tiny model decode
        in ~150 ms, which races the monitor's hedge tick — the injected
        delay makes "both slots busy past the hedge delay" a property of
        the test, not of host speed."""
        from docqa_tpu.runtime.metrics import DEFAULT_REGISTRY

        prompt = [3, 5, 9, 4]
        solo = engine.generate_ids([prompt], max_new_tokens=6)[0]
        pool = make_pool(
            engine,
            replicas=2,
            n_slots=1,
            hedge=True,
            hedge_min_delay_s=0.1,
            hedge_warmup=10_000,  # stay on the floor: no p95 yet
        )
        try:
            pool.warmup()
            before = DEFAULT_REGISTRY.snapshot()["counters"].get(
                "pool_hedges", 0
            )
            plan = FaultPlan(
                [
                    FaultRule(
                        "serve.decode_chunk",
                        p=1.0,
                        delay_s=0.15,
                        raise_error=False,
                    )
                ],
                seed=0,
            )
            with plan:
                # one long decode per replica: every slot busy for
                # ≥ (60/chunk)·0.15 s ≫ hedge delay + monitor interval
                long1 = pool.submit_ids([4, 6, 8], max_new_tokens=60)
                long2 = pool.submit_ids([5, 7, 9], max_new_tokens=60)
                deadline = time.monotonic() + 60
                while pool.n_active < 2 and time.monotonic() < deadline:
                    time.sleep(0.01)
                h = pool.submit_ids(
                    prompt, max_new_tokens=6, deadline=Deadline.after(120)
                )
                got = h.result(timeout=240)
                after = DEFAULT_REGISTRY.snapshot()["counters"].get(
                    "pool_hedges", 0
                )
                long1.result(timeout=240)
                long2.result(timeout=240)
        finally:
            pool.stop()
        assert got == solo
        assert after > before  # a hedge twin was actually dispatched


# ---- drain / rolling restart ------------------------------------------------


class TestDrainRestart:
    def test_drain_finishes_inflight_then_resume(self, engine):
        pool = make_pool(engine)
        try:
            handles = [
                pool.submit_ids(p, max_new_tokens=8) for p in _prompts(6)
            ]
            out = pool.drain(0, timeout=120.0)
            assert out["drained"] is True
            assert out["n_active"] == 0 and out["n_queued"] == 0
            # every pre-drain request completed with real tokens
            for h in handles:
                assert h.result(timeout=120)
            st = pool.status()
            assert st["replicas"][0]["state"] == "draining"
            pool.resume(0)
            assert pool.status()["replicas"][0]["state"] == "healthy"
            # replica 0 serves again after resume
            assert pool.submit_ids([3, 5], max_new_tokens=2).result(
                timeout=120
            )
        finally:
            pool.stop()

    def test_draining_batcher_sheds_typed(self, engine):
        b = ContinuousBatcher(engine, n_slots=2, chunk=4, cache_len=128)
        try:
            assert b.drain(timeout=30.0) is True
            with pytest.raises(Draining) as e:
                b.submit_ids([3, 5], max_new_tokens=2)
            assert isinstance(e.value, QueueFull)  # existing 503 mapping
            b.resume()
            assert b.submit_ids([3, 5], max_new_tokens=2).result(timeout=120)
        finally:
            b.stop()

    def test_single_replica_pool_parks_during_drain(self, engine):
        """A 1-replica pool mid-drain PARKS new arrivals (the rolling
        restart window) and flushes them on resume — nothing dropped."""
        pool = make_pool(engine, replicas=1)
        try:
            assert pool.drain(0, timeout=120.0)["drained"]
            h = pool.submit_ids(
                [3, 5, 9], max_new_tokens=4, deadline=Deadline.after(120)
            )
            assert pool.status()["pending"] == 1
            pool.resume(0)
            assert h.result(timeout=120) == engine.generate_ids(
                [[3, 5, 9]], max_new_tokens=4
            )[0]
        finally:
            pool.stop()

    def test_rolling_restart_under_load_drops_nothing(self, engine):
        pool = make_pool(engine)
        results = {}
        lock = threading.Lock()
        stop_feed = threading.Event()

        def feeder():
            i = 0
            while not stop_feed.is_set() and i < 12:
                try:
                    h = pool.submit_ids(
                        _prompts(12)[i],
                        max_new_tokens=6,
                        deadline=Deadline.after(120),
                    )
                except QueueFull as e:
                    with lock:
                        results[i] = ("typed", repr(e))
                    i += 1
                    continue

                def wait_one(idx=i, handle=h):
                    try:
                        out = ("ok", len(handle.result(timeout=180)))
                    except (WorkerDied, DeadlineExceeded, QueueFull) as e:
                        out = ("typed", repr(e))
                    except Exception as e:
                        out = ("HUNG_OR_UNTYPED", repr(e))
                    with lock:
                        results[idx] = out

                threading.Thread(target=wait_one).start()
                i += 1
                time.sleep(0.05)

        try:
            pool.warmup()
            feed = threading.Thread(target=feeder)
            feed.start()
            time.sleep(0.2)  # restarts begin with requests in flight
            out = pool.rolling_restart(timeout_per_replica=120.0)
            feed.join(timeout=60)
            stop_feed.set()
            deadline = time.monotonic() + 180
            while len(results) < 12 and time.monotonic() < deadline:
                time.sleep(0.1)
        finally:
            st = pool.status()
            pool.stop()
        assert out["ok"] is True
        assert len(results) == 12, f"request(s) hung: {len(results)}/12"
        kinds = {k for k, _ in results.values()}
        assert "HUNG_OR_UNTYPED" not in kinds, results
        # zero DROPPED: rolling restart must not shed — drains route
        # around / park, so every request actually completes
        assert all(k == "ok" for k, _ in results.values()), results
        assert all(r["generation"] >= 1 for r in st["replicas"])


# ---- cancellation -----------------------------------------------------------


class TestCancellation:
    def test_cancel_before_admission_is_typed(self, engine):
        b = ContinuousBatcher(engine, n_slots=1, chunk=4, cache_len=128)
        try:
            busy = b.submit_ids([3, 5, 9], max_new_tokens=40)
            queued = b.submit_ids([4, 6], max_new_tokens=40)
            queued.cancel()
            with pytest.raises(RequestCancelled):
                queued.result(timeout=120)
            assert busy.result(timeout=240)  # occupant unaffected
        finally:
            b.stop()

    def test_cancel_mid_decode_retires_lane(self, engine):
        b = ContinuousBatcher(engine, n_slots=2, chunk=4, cache_len=128)
        try:
            b.warmup()
            h = b.submit_ids([3, 5, 9], max_new_tokens=60)
            # wait until it has started producing, then cancel
            deadline = time.monotonic() + 60
            while not h.started and time.monotonic() < deadline:
                time.sleep(0.01)
            h.cancel()
            with pytest.raises(RequestCancelled):
                h.result(timeout=60)
            # the lane is free again: new work completes promptly
            assert b.submit_ids([4, 6], max_new_tokens=4).result(timeout=120)
        finally:
            b.stop()


# ---- liveness surface -------------------------------------------------------


class TestLivenessSurface:
    def test_heartbeat_and_cold_flags(self, engine):
        b = ContinuousBatcher(engine, n_slots=2, chunk=4, cache_len=128)
        try:
            assert b.cold  # nothing compiled yet
            assert b.worker_alive
            assert b.heartbeat_age_s < 5.0  # idle loop re-stamps
            b.submit_ids([3, 5], max_new_tokens=2).result(timeout=120)
            assert not b.cold  # first chunk landed
        finally:
            b.stop()

    def test_dead_replica_state_surfaced(self, engine):
        pool = make_pool(engine, breaker_failure_threshold=100)
        try:
            pool.warmup()
            # kill replica 1's batcher directly (simulates hard death)
            pool._replicas[1].batcher.kill(WorkerDied("test kill"))
            # the monitor notices (counting the death) and rebuilds
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                r1 = pool.status()["replicas"][1]
                if r1["deaths"] >= 1 and r1["generation"] >= 1:
                    break
                time.sleep(0.05)
            assert pool._replicas[1].deaths >= 1
            assert pool._replicas[1].generation >= 1
            # traffic keeps flowing whatever replica 1's state
            assert pool.submit_ids([3, 5], max_new_tokens=2).result(
                timeout=120
            )
        finally:
            pool.stop()
