"""docqa-recallscope: retrieval-quality observatory tests.

Covers the estimator math (Wilson CIs at small n, the recall=1.0
degenerate case, tie-tolerant set comparison), deterministic sampler
reproducibility across restarts, the tiered/fused shadow hooks, the
loud off-mesh fallback, zero-shadow-when-disabled, and the served
end-to-end loop: a fake-mode runtime with shadow sampling on and
nprobe dropped to 1 must fire the recall SLO burn, flag the window's
/ask traces anomalous, show the degraded estimate + frontier on
/api/retrieval, and keep both /metrics dialects lint-clean with the
new series.
"""

import time
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from docqa_tpu import obs
from docqa_tpu.config import EncoderConfig, StoreConfig
from docqa_tpu.index.store import VectorStore
from docqa_tpu.index.tiered import TieredIndex
from docqa_tpu.obs.retrieval_observatory import (
    RetrievalObservatory,
    ShadowJob,
    compare_topk,
    get_retrieval_observatory,
    set_retrieval_observatory,
    wilson_interval,
)
from docqa_tpu.runtime.metrics import DEFAULT_REGISTRY


def _unit_rows(rng, n, d):
    v = rng.standard_normal((n, d)).astype(np.float32)
    return v / np.linalg.norm(v, axis=1, keepdims=True)


def _counter(name):
    return DEFAULT_REGISTRY.counter(name).value


@pytest.fixture()
def observatory():
    """A started observatory installed as the process hook; always
    uninstalled + stopped, so tests cannot leak shadows into each
    other."""
    prev = get_retrieval_observatory()
    robs = RetrievalObservatory(
        sample_every=1,
        seed=0,
        frontier_every=1,
        min_frontier_n=1,
        registry=DEFAULT_REGISTRY,
    ).start()
    set_retrieval_observatory(robs)
    yield robs
    robs.stop()
    set_retrieval_observatory(prev)


@pytest.fixture()
def tiered_small():
    rng = np.random.default_rng(0)
    vecs = _unit_rows(rng, 600, 32)
    store = VectorStore(StoreConfig(dim=32, shard_capacity=1024))
    store.add(vecs, [{"doc_id": f"d{i}"} for i in range(len(vecs))])
    tiered = TieredIndex(store, nprobe=1, min_rows=100,
                         rebuild_tail_rows=100_000)
    assert tiered.rebuild()
    return store, tiered, vecs, rng


# ---------------------------------------------------------------------------
# estimator math
# ---------------------------------------------------------------------------


class TestWilson:
    def test_no_evidence_constrains_nothing(self):
        assert wilson_interval(0, 0) == (0.0, 1.0)

    def test_recall_one_degenerate(self):
        """At recall 1.0 the normal approximation collapses to a
        zero-width interval; Wilson keeps an honest lower bound that
        tightens with n but never claims certainty."""
        lo10, hi10 = wilson_interval(10, 10)
        lo100, hi100 = wilson_interval(100, 100)
        assert hi10 == 1.0 and hi100 == 1.0
        assert lo10 < lo100 < 1.0
        assert lo10 == pytest.approx(0.7225, abs=1e-3)

    def test_small_n(self):
        lo, hi = wilson_interval(1, 2)
        assert 0.0 < lo < 0.5 < hi < 1.0

    def test_known_value(self):
        lo, hi = wilson_interval(95, 100)
        assert lo == pytest.approx(0.8882, abs=1e-3)
        assert hi == pytest.approx(0.9785, abs=1e-3)

    def test_bounds_stay_in_unit_interval(self):
        for total in (1, 2, 5, 17):
            for hits in range(total + 1):
                lo, hi = wilson_interval(hits, total)
                assert 0.0 <= lo <= hits / total <= hi <= 1.0


class TestCompareTopk:
    def test_exact_match(self):
        shadow = [(1, 0.9), (2, 0.8), (3, 0.7)]
        assert compare_topk(shadow, shadow, 3) == (3, 3)

    def test_miss_counts(self):
        served = [(1, 0.9), (9, 0.2), (8, 0.1)]
        shadow = [(1, 0.9), (2, 0.8), (3, 0.7)]
        assert compare_topk(served, shadow, 3) == (1, 3)

    def test_duplicate_score_tie_is_not_a_miss(self):
        """Exact top-k picks an arbitrary representative among
        equal-scored rows; a served row at the shadow's k-th score is
        interchangeable evidence, not a recall miss."""
        served = [(1, 0.9), (7, 0.5)]
        shadow = [(1, 0.9), (2, 0.5)]
        assert compare_topk(served, shadow, 2) == (2, 2)

    def test_expected_truncates_to_shadow(self):
        served = [(1, 0.9), (2, 0.8)]
        shadow = [(1, 0.9)]  # corpus only had one live row
        assert compare_topk(served, shadow, 5) == (1, 1)

    def test_empty_shadow(self):
        assert compare_topk([(1, 0.5)], [], 3) == (0, 0)


class TestSamplerDeterminism:
    def test_reproducible_across_restarts(self):
        """The sampler is a pure hash of (seed, sequence index): a
        restarted process replaying the same workload must shadow the
        exact same request indices."""
        a = RetrievalObservatory(sample_every=8, seed=3)
        b = RetrievalObservatory(sample_every=8, seed=3)
        da = [a._sampled(i) for i in range(256)]
        db = [b._sampled(i) for i in range(256)]
        assert da == db
        # one hashed slot per window of 8: exactly 1-in-8, not
        # approximately
        assert sum(da) == 32

    def test_seed_changes_the_sample_set(self):
        a = RetrievalObservatory(sample_every=8, seed=0)
        b = RetrievalObservatory(sample_every=8, seed=1)
        da = [a._sampled(i) for i in range(256)]
        db = [b._sampled(i) for i in range(256)]
        assert da != db
        assert sum(da) == sum(db) == 32

    def test_exact_one_per_window_at_any_rate(self):
        """Window-exactness must hold for operator-tuned rates too, not
        just powers of two (a raw hash residue mod 30 leaves ~13% of
        windows shadowless)."""
        for n in (3, 7, 30, 32):
            robs = RetrievalObservatory(sample_every=n, seed=5)
            for w in range(40):
                hits = sum(
                    robs._sampled(i) for i in range(w * n, (w + 1) * n)
                )
                assert hits == 1, (n, w)

    def test_not_running_never_samples(self):
        robs = RetrievalObservatory(sample_every=1)
        assert not robs.sample()  # worker not started: zero shadows

    def test_estimate_window_math(self):
        robs = RetrievalObservatory(sample_every=1, registry=None)
        job = ShadowJob(
            tier="t", nprobe=4, k=2,
            served=[[(1, 0.9), (9, 0.1)]],
            shadow_fn=lambda: ([[(1, 0.9), (2, 0.8)]], None),
        )
        robs._process(job)
        est = robs.status()["estimate"]
        assert est["hits"] == 1 and est["expected"] == 2
        assert est["recall"] == 0.5
        lo, hi = wilson_interval(1, 2)
        assert est["ci_lo"] == pytest.approx(round(lo, 4))
        assert est["ci_hi"] == pytest.approx(round(hi, 4))

    def test_comparisons_count_queries_not_jobs(self):
        """One batched shadow job of 3 queries is 3 comparisons —
        min_frontier_n-style evidence floors must not mean 20x
        different evidence at batch 20 than at batch 1."""
        robs = RetrievalObservatory(sample_every=1, registry=None)
        job = ShadowJob(
            tier="t", nprobe=4, k=2,
            served=[[(1, 0.9)], [(2, 0.8)], [(9, 0.1)]],
            shadow_fn=lambda: (
                [[(1, 0.9)], [(2, 0.8)], [(3, 0.7)]], None,
            ),
        )
        robs._process(job)
        est = robs.status()["estimate"]
        assert est["comparisons"] == 3
        assert est["hits"] == 2 and est["expected"] == 3


# ---------------------------------------------------------------------------
# shadow hooks against a real tiered index
# ---------------------------------------------------------------------------


class TestTieredShadow:
    def test_degraded_nprobe_measured_and_frontier_observed(
        self, observatory, tiered_small
    ):
        store, tiered, vecs, rng = tiered_small
        expected0 = _counter("retrieve_shadow_expected")
        q = vecs[:4] + 0.05 * rng.standard_normal((4, 32)).astype(np.float32)
        for _ in range(6):
            tiered.search(q, k=5)
        assert observatory.drain(30)
        st = observatory.status()
        # nprobe=1 over ~24 cells of random vectors: recall collapses,
        # and the estimator must SAY so with a CI excluding the target
        est = st["estimate"]
        assert est is not None and est["recall"] < 0.95
        assert est["ci_hi"] < 0.95
        assert st["current"] == {"tier": "tiered", "nprobe": 1}
        assert _counter("retrieve_shadow_expected") > expected0
        # the frontier observed neighboring nprobes with latency
        # (first-probe compile samples dropped) and monotone-ish recall
        frontier = {row["nprobe"]: row for row in st["frontier"]}
        assert len(frontier) >= 2 and 1 in frontier
        ps = sorted(frontier)
        assert frontier[ps[-1]]["recall"] >= frontier[ps[0]]["recall"] - 0.05
        # per-tier latency split digests recorded for the two-step path
        for name in (
            "retrieve_tier_ms_bulk_ivf",
            "retrieve_tier_ms_tail_exact",
            "retrieve_tier_ms_merge",
        ):
            assert DEFAULT_REGISTRY.histogram(name).summary()["count"] > 0
        gauges = observatory.telemetry_gauges()
        assert gauges["retrieve_recall_estimate"] == est["recall"]
        assert gauges["retrieve_nprobe_current"] == 1.0

    def test_set_nprobe_applies_live_to_both_paths(self, tiered_small):
        _store, tiered, _vecs, _rng = tiered_small
        assert tiered.set_nprobe(4) == 4
        assert tiered.nprobe == 4
        assert tiered._tier[0].nprobe == 4  # the fused path reads this

    def test_auto_apply_moves_nprobe_to_the_measured_frontier(self):
        """Synthetic frontier: the current nprobe misses the target and
        a neighbor meets it — auto-apply (default-OFF config, ON here)
        must call the wired setter with exactly the qualifying
        neighbor, and only once."""
        applied = []
        robs = RetrievalObservatory(
            sample_every=1, frontier_every=1, min_frontier_n=1,
            recall_target=0.9, auto_apply=True,
            apply_nprobe=applied.append, frontier_factors=(1.0, 2.0),
        )
        truth = [[(1, 0.9), (2, 0.8)]]

        def frontier_fn(_qn, p):
            # nprobe=2 finds half the truth, nprobe=4 all of it
            return (truth if p == 4 else [[(1, 0.9), (7, 0.1)]], 0.001)

        job = ShadowJob(
            tier="tiered", nprobe=2, k=2,
            served=[[(1, 0.9), (7, 0.1)]],
            shadow_fn=lambda: (truth, "qn"),
            frontier_fn=frontier_fn,
            covered=100, n_clusters=64,
        )
        robs._process(job)
        assert applied == [4]
        assert robs.status()["applied_nprobe"] == 4
        assert robs.recommended_nprobe() == 4
        # a second identical round must not re-apply the same value
        robs._process(job)
        assert applied == [4]

    def test_recommendation_without_auto_apply_stays_advisory(self):
        calls = []
        robs = RetrievalObservatory(
            sample_every=1, frontier_every=1, min_frontier_n=1,
            recall_target=0.9, auto_apply=False,  # the config default
            apply_nprobe=calls.append, frontier_factors=(1.0, 2.0),
        )
        truth = [[(1, 0.9), (2, 0.8)]]
        job = ShadowJob(
            tier="tiered", nprobe=2, k=2,
            served=[[(1, 0.9), (7, 0.1)]],
            shadow_fn=lambda: (truth, "qn"),
            frontier_fn=lambda _qn, p: (
                truth if p == 4 else [[(1, 0.9), (7, 0.1)]], 0.001,
            ),
            covered=100, n_clusters=64,
        )
        robs._process(job)
        assert robs.recommended_nprobe() == 4
        assert calls == []  # recommendation only, never applied

    def test_frontier_resets_when_the_tier_is_rebuilt(self):
        """A rebuild reclusters, changing what any nprobe MEANS — the
        recommendation must not survive on evidence measured against
        the old clustering (it feeds auto-apply)."""
        robs = RetrievalObservatory(
            sample_every=1, frontier_every=1, min_frontier_n=1,
            recall_target=0.9, frontier_factors=(1.0, 2.0),
        )
        truth = [[(1, 0.9), (2, 0.8)]]
        job = ShadowJob(
            tier="tiered", nprobe=2, k=2, served=[truth[0]],
            shadow_fn=lambda: (truth, "qn"),
            frontier_fn=lambda _qn, p: (truth, 0.001),
            covered=100, n_clusters=64,
        )
        robs._process(job)
        assert robs.recommended_nprobe() == 2
        # same corpus rebuilt at a different clustering: nothing the
        # old windows measured applies; the frontier starts over
        rebuilt = ShadowJob(
            tier="tiered", nprobe=2, k=2, served=[truth[0]],
            shadow_fn=lambda: (truth, "qn"),
            # the new clustering finds nothing at any probed nprobe
            frontier_fn=lambda _qn, p: ([[(7, 0.1), (8, 0.1)]], 0.001),
            covered=500, n_clusters=256,
        )
        robs._process(rebuilt)
        assert robs.recommended_nprobe() is None

    def test_frontier_excludes_reported_compile_samples(self):
        """A frontier_fn that reports per-shape compile freshness (the
        IVFIndex.timed_probe contract) keeps EVERY compile out of the
        latency axis — not just the first sample per nprobe, which
        would record a later compile at a new batch size."""
        robs = RetrievalObservatory(
            sample_every=1, frontier_every=1, min_frontier_n=1,
            frontier_factors=(1.0,),
        )
        truth = [[(1, 0.9), (2, 0.8)]]
        lats = iter([5000.0, 0.001, 7000.0, 0.002])  # compiles are slow
        fresh = iter([True, False, True, False])  # batch-shape changes

        def frontier_fn(_qn, p):
            return truth, next(lats), next(fresh)

        job = ShadowJob(
            tier="tiered", nprobe=2, k=2, served=[truth[0]],
            shadow_fn=lambda: (truth, "qn"), frontier_fn=frontier_fn,
            covered=100, n_clusters=64,
        )
        for _ in range(4):
            robs._process(job)
        lat_ms = list(robs._frontier[2]["lat_ms"])
        # both compile samples excluded, both warm samples kept (the
        # old first-per-nprobe drop would have recorded the second
        # compile's 7000 s)
        assert lat_ms == pytest.approx([1.0, 2.0])
        row = next(
            r for r in robs.status()["frontier"] if r["nprobe"] == 2
        )
        assert row["probe_ms_p50"] < 100, row

    def test_zero_shadow_dispatches_while_disabled(self, tiered_small):
        """The acceptance bullet: sampling off == zero shadow work, not
        merely less — counted at the spine stage AND the counters."""
        from docqa_tpu.engines.spine import get_spine

        store, tiered, vecs, rng = tiered_small
        assert get_retrieval_observatory() is None  # no observatory wired

        def shadow_stage_count():
            row = get_spine().stats()["stages"].get("retrieve_shadow")
            return row["count"] if row else 0

        stage0 = shadow_stage_count()
        total0 = _counter("retrieve_shadow_total")
        served0 = _counter("retrieve_served_total")
        q = vecs[:2]
        tiered.search(q, k=5)
        # an observatory that exists but is NOT running must also stay
        # at zero (the runtime constructs in __init__, starts in start())
        robs = RetrievalObservatory(sample_every=1, registry=DEFAULT_REGISTRY)
        prev = set_retrieval_observatory(robs)
        try:
            tiered.search(q, k=5)
        finally:
            set_retrieval_observatory(prev)
        assert shadow_stage_count() == stage0
        assert _counter("retrieve_shadow_total") == total0
        # the not-running observatory still counts served traffic
        assert _counter("retrieve_served_total") == served0 + 1


TINY_ENC = EncoderConfig(
    vocab_size=512, hidden_dim=64, num_layers=2, num_heads=4,
    mlp_dim=128, max_seq_len=64, embed_dim=64, dtype="float32",
)


class TestFusedTieredShadow:
    @pytest.fixture(scope="class")
    def fused_setup(self):
        from docqa_tpu.engines.encoder import EncoderEngine
        from docqa_tpu.engines.retrieve import FusedTieredRetriever

        enc = EncoderEngine(TINY_ENC)
        store = VectorStore(StoreConfig(dim=64, shard_capacity=512))
        rng = np.random.default_rng(1)
        texts = [
            f"note {i}: drug-{i % 13} for condition-{i % 7}"
            for i in range(300)
        ]
        vecs = enc.encode_texts(texts)
        store.add(
            vecs,
            [
                {"doc_id": f"d{i}", "source": t, "text_content": t}
                for i, t in enumerate(texts)
            ],
        )
        tiered = TieredIndex(store, nprobe=1, min_rows=100,
                             rebuild_tail_rows=100_000)
        assert tiered.rebuild()
        return enc, store, tiered, FusedTieredRetriever(enc, tiered)

    def test_fused_hook_estimates_recall(self, observatory, fused_setup):
        _enc, _store, _tiered, retr = fused_setup
        for i in range(4):
            retr.search_texts([f"drug-{i} for condition-{i % 7}"], k=5)
        assert observatory.drain(30)
        st = observatory.status()
        assert "tiered_fused@nprobe=1" in st["estimates"]
        assert (
            DEFAULT_REGISTRY.histogram(
                "retrieve_tier_ms_fused_probe"
            ).summary()["count"]
            > 0
        )

    def test_queued_shadow_job_holds_no_raw_text(self, fused_setup):
        """PHI regression (docqa-costscope satellite): the fused path's
        pending shadow closure used to hold the sampled request's raw
        query texts until the job ran.  It now holds the served
        dispatch's query EMBEDDINGS plus a salted content hash — no
        string reachable from a queued ShadowJob may contain the query
        text, so a diagnostic that serialized the pending queue could
        not leak one."""
        from docqa_tpu.obs.retrieval_observatory import (
            RetrievalObservatory,
            set_retrieval_observatory,
        )

        _enc, _store, _tiered, retr = fused_setup

        class _Capture(RetrievalObservatory):
            def __init__(self):
                super().__init__(sample_every=1)
                self.jobs = []

            @property
            def running(self):  # sample() gates on a live worker
                return True

            def submit(self, job):
                self.jobs.append(job)
                return True

        cap = _Capture()
        prev = set_retrieval_observatory(cap)
        query = "drug-3 for condition-3 PHI-SENTINEL-TEXT"
        try:
            retr.search_texts([query], k=5)
        finally:
            set_retrieval_observatory(prev)
        assert cap.jobs, "shadow job was not sampled"
        job = cap.jobs[0]

        # walk everything reachable from the job — dataclass fields,
        # closure cells, containers — and collect every string
        strings, seen = [], set()

        def walk(o, depth=0):
            if depth > 6 or id(o) in seen:
                return
            seen.add(id(o))
            if isinstance(o, str):
                strings.append(o)
                return
            # arrays are leaves: they hold numbers, and a device array's
            # __slots__ name descriptors (__weakref__) getattr cannot read
            if isinstance(
                o, (bytes, np.ndarray, jax.Array, int, float, bool)
            ):
                return
            if isinstance(o, dict):
                for k, v in o.items():
                    walk(k, depth + 1)
                    walk(v, depth + 1)
                return
            if isinstance(o, (list, tuple, set, frozenset)):
                for v in o:
                    walk(v, depth + 1)
                return
            if callable(o):
                for cell in getattr(o, "__closure__", None) or ():
                    walk(cell.cell_contents, depth + 1)
                walk(getattr(o, "__defaults__", None), depth + 1)
                return
            slots = getattr(type(o), "__slots__", None)
            if slots:
                for name in slots:
                    walk(getattr(o, name, None), depth + 1)
            d = getattr(o, "__dict__", None)
            if d:
                walk(d, depth + 1)

        walk(job)
        leaked = [
            s for s in strings
            if "PHI-SENTINEL" in s or query in s
        ]
        assert not leaked, f"raw query text reachable from job: {leaked}"
        # the dedup/diagnostic label rides along instead
        assert job.attrs.get("query_hashes"), "salted hash missing"
        assert all(
            "PHI-SENTINEL" not in h for h in job.attrs["query_hashes"]
        )

    def test_no_offmesh_fallback_ever(self, fused_setup):
        """docqa-meshindex: the fused tiered probe is MESH-NATIVE — the
        PR-13 loud fallback (and its two extra host<->device
        round-trips) is structurally gone.  The counter stays on the
        /api/retrieval surface; the sharded-path equivalence (and the
        same zero) is covered by tests/test_ivf_sharded.py on the
        8-device mesh."""
        enc, store, tiered, retr = fused_setup
        fallback0 = _counter("retrieve_offmesh_fallback")
        ctx = obs.new_trace("ask")
        obs.call_in(
            ctx, retr.search_texts, ["drug-1 for condition-1"], k=3
        )
        obs.finish(ctx)
        retr.search_texts(["drug-2 for condition-2"], k=3)
        assert _counter("retrieve_offmesh_fallback") == fallback0
        assert "offmesh_fallback" not in ctx.trace.flags


# ---------------------------------------------------------------------------
# served end-to-end: recall regression -> burn alert -> evidence
# ---------------------------------------------------------------------------


class TestServedRecallBurnE2E:
    @pytest.fixture()
    def rt(self):
        from docqa_tpu.config import load_config
        from docqa_tpu.service.app import DocQARuntime

        obs.DEFAULT_RECORDER.clear()
        cfg = load_config(env={}, overrides={
            "flags.use_fake_llm": True,
            "flags.use_fake_encoder": True,
            "encoder.embed_dim": 64,
            "store.dim": 64,
            "store.shard_capacity": 1024,
            # the induced regression: tiered serving with nprobe
            # dropped to 1 over a clustered corpus
            "store.serving_index": "tiered",
            "store.ivf_nprobe": 1,
            "store.ivf_min_rows": 100,
            "ner.hidden_dim": 32,
            "ner.num_layers": 1,
            "ner.num_heads": 2,
            "ner.mlp_dim": 64,
            "ner.train_steps": 0,
            # sub-second rollups so "within two windows" is test-speed
            "telemetry.interval_s": 0.5,
            "telemetry.sample_every_s": 0.05,
            "telemetry.slo_long_windows": 8,
            "retrieval_quality.sample_every": 1,
            "retrieval_quality.frontier_every": 2,
            "retrieval_quality.min_frontier_n": 1,
            "retrieval_quality.slo_long_windows": 8,
        })
        runtime = DocQARuntime(cfg).start()
        rng = np.random.default_rng(7)
        vecs = _unit_rows(rng, 600, 64)
        runtime.store.add(
            vecs,
            [
                {"doc_id": f"d{i}", "source": f"s{i}",
                 "text_content": f"chunk {i}"}
                for i in range(len(vecs))
            ],
        )
        assert runtime.search_index.rebuild()
        yield runtime
        runtime.stop()

    def test_recall_burn_fires_with_evidence(self, rt):
        import asyncio

        from docqa_tpu.obs.expo import lint_prometheus_text
        from docqa_tpu.service.app import make_app

        async def drive():
            import aiohttp
            from aiohttp import web

            app = make_app(rt)
            runner = web.AppRunner(app)
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            port = site._server.sockets[0].getsockname()[1]
            base = f"http://127.0.0.1:{port}"
            fired = False
            loop = asyncio.get_running_loop()
            try:
                async with aiohttp.ClientSession() as s:
                    for i in range(80):
                        async with s.post(
                            f"{base}/ask/",
                            json={"question": f"chunk {i} drug dose?"},
                        ) as r:
                            assert r.status == 200, await r.text()
                        async with s.get(f"{base}/api/status") as r:
                            slo = (await r.json())["slo"]
                        row = next(
                            x for x in slo if x["name"] == "retrieve_recall"
                        )
                        # keep asking for a few requests even once
                        # firing: the estimate/frontier assertions below
                        # need this runtime's own shadows processed, not
                        # just the counters that fed the burn
                        if row["firing"] and i >= 8:
                            fired = True
                            break
                        await asyncio.sleep(0.05)
                    assert fired, f"recall burn never fired; slo={row}"
                    assert await loop.run_in_executor(
                        None, rt.retrieval_obs.drain, 30
                    ), "shadow worker never drained"
                    async with s.get(
                        f"{base}/api/traces?anomalous=1&limit=100"
                    ) as r:
                        anomalous = await r.json()
                    async with s.get(f"{base}/api/retrieval") as r:
                        assert r.status == 200
                        retrieval = await r.json()
                    async with s.get(f"{base}/metrics") as r:
                        prom_plain = await r.text()
                    async with s.get(
                        f"{base}/metrics",
                        headers={
                            "Accept": "application/openmetrics-text"
                        },
                    ) as r:
                        prom_om = await r.text()
                    async with s.get(f"{base}/api/telemetry") as r:
                        tele = await r.json()
            finally:
                await runner.cleanup()
            return anomalous, retrieval, prom_plain, prom_om, tele

        anomalous, retrieval, prom_plain, prom_om, tele = asyncio.run(
            drive()
        )
        # the firing window's /ask traces are in the always-keep ring,
        # flagged with the recall SLO that burned
        flagged = [
            t for t in anomalous
            if "slo_retrieve_recall_burn" in t["flags"]
        ]
        assert flagged, anomalous
        assert all(t["name"] == "ask" for t in flagged)
        # /api/retrieval shows the degraded estimate and the observed
        # frontier, and names the serving configuration that caused it
        est = retrieval["estimate"]
        assert est is not None and est["recall"] < 0.95
        assert retrieval["current"]["nprobe"] == 1
        assert retrieval["serving"]["serving_index"] == "tiered"
        assert retrieval["frontier"], retrieval
        # both exposition dialects lint clean and carry the new series
        assert lint_prometheus_text(prom_plain) == []
        assert lint_prometheus_text(prom_om) == []
        for text in (prom_plain, prom_om):
            assert "docqa_retrieve_shadow_expected_total" in text
            assert "docqa_retrieve_recall_estimate" in text
        assert "docqa_slo_retrieve_recall_burning 1" in prom_plain.splitlines()
        # rollup series on /api/telemetry
        assert "retrieve_recall_estimate" in tele["series"]
        assert "retrieve_shadow_expected" in tele["series"]
