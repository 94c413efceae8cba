"""Application wiring + HTTP surface.

One process replaces the reference's six (SURVEY §1): the runtime owns the
mesh, engines, store, broker, registry, pipeline and services; the aiohttp
app exposes every endpoint the reference exposed — plus the two it *called*
without providing (patient-snippet search, prompt summarize).

Construction is factory-based, never at import time — the reference built
models/indexes at module import, which its own tests had to undo with
``sys.modules`` surgery (SURVEY §4 lesson 1).

Endpoint parity map (reference → here):
  POST /ingest/                 doc-ingestor/main.py:19-65
  GET  /documents/              doc-ingestor/main.py:67-69
  GET  /health                  doc-ingestor/main.py:72-74, llm-qa/main.py:124-126
  POST /ask/                    llm-qa/main.py:111-122
  GET  /api/status              synthese-comparative/api/routes.py:22-24
  POST /api/synthese/patient    routes.py:27-75
  POST /api/synthese/comparaison routes.py:78-141
  GET  /api/search/patient-snippets   (aspirational: retrieval_client.py:89)
  POST /api/llm/summarize             (aspirational: llm_client.py:51)
  GET  /metrics                 (new: SURVEY §5 — reference had none)
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import json
import os
import time
from typing import Optional

from docqa_tpu import obs
from docqa_tpu.config import Config, load_config
from docqa_tpu.engines.serve import QueueFull
from docqa_tpu.resilience import BreakerBoard, FaultPlan
from docqa_tpu.resilience import faults as _faults
from docqa_tpu.resilience.deadline import Deadline, DeadlineExceeded
from docqa_tpu.runtime.metrics import DEFAULT_REGISTRY, get_logger
from docqa_tpu.service.broker import make_broker
from docqa_tpu.service.pipeline import DocumentPipeline
from docqa_tpu.service.qa import QA_TEMPLATE, QAService
from docqa_tpu.service.registry import DocumentRegistry
from docqa_tpu.service.schemas import (
    PatientComparisonRequest,
    PatientSummaryRequest,
    Query,
    SummarizeRequest,
)
from docqa_tpu.service.synthesis import SynthesisError, SynthesisService
from docqa_tpu.service.wire import to_wire

log = get_logger("docqa.app")


class DocQARuntime:
    """Builds and owns every component; start()/stop() manage the workers."""

    def __init__(
        self,
        cfg: Optional[Config] = None,
        journal_dir: Optional[str] = None,
    ) -> None:
        # With DOCQA_RACE_WITNESS=1 every named lock/cv constructed
        # from here on is instrumented and GET /api/witness serves the
        # witnessed lock-order graph (docs/STATIC_ANALYSIS.md
        # "Concurrency witness"; soak pulls it into its dump).  This is
        # the FALLBACK install point (embedding/test boots): locks built
        # at app.py IMPORT time (obs.DEFAULT_RECORDER,
        # metrics.DEFAULT_REGISTRY) predate it and stay unwrapped here —
        # scripts/start_all.py installs at process entry, before any
        # docqa_tpu import, for full coverage in a served process.
        from docqa_tpu.analysis.race_witness import maybe_install_from_env

        maybe_install_from_env()
        # DOCQA_LEDGER_WITNESS=1 tracks every KV table and cost record
        # from acquire to release/retire; GET /api/ledger serves the
        # live dump (docs/STATIC_ANALYSIS.md "Ledger witness").  Method-
        # level wrapping, so this install point covers embedding/test
        # boots fully — no import-order caveat like the lock witness.
        from docqa_tpu.analysis import ledger_audit

        ledger_audit.maybe_install_from_env()
        import jax

        from docqa_tpu.deid.engine import DeidEngine
        from docqa_tpu.engines.encoder import EncoderEngine, HashEncoder
        from docqa_tpu.engines.generate import GenerateEngine
        from docqa_tpu.engines.summarize import SummarizeEngine
        from docqa_tpu.index.store import VectorStore
        from docqa_tpu.runtime.mesh import make_mesh, multihost_init

        self.cfg = cfg or load_config()
        # set-up split served on /api/status ("boot"): seconds each
        # construction phase took, so a cold start can be read apart
        self.boot_s: dict = {}
        t_boot = time.perf_counter()
        from docqa_tpu.runtime.compile_cache import compile_cache_dir

        self._compile_cache_dir = compile_cache_dir()
        self._compile_cache_entries_at_boot = self._compile_cache_entries()

        def _phase(name: str, t0: float) -> float:
            now = time.perf_counter()
            self.boot_s[name] = round(now - t0, 3)
            return now

        # failure-path plumbing first: every dependency below is wrapped
        # by a breaker from this board (docs/RESILIENCE.md), and a
        # DOCQA_FAULTS env plan makes chaos drills run against the real
        # service with zero code changes
        self.breakers = BreakerBoard(
            failure_threshold=self.cfg.resilience.breaker_failure_threshold,
            reset_timeout_s=self.cfg.resilience.breaker_reset_s,
        )
        from docqa_tpu.models import hf_checkpoint as _hf_checkpoint

        # module-level singleton (checkpoint loads happen before/outside
        # the runtime too) — adopted so /api/status shows its state
        self.breakers.adopt(_hf_checkpoint._LOAD_BREAKER)
        self._fault_plan = FaultPlan.from_env()
        if self._fault_plan is not None:
            _faults.install(self._fault_plan)
            log.warning(
                "fault-injection plan ACTIVE (%d rule(s), seed %d) — "
                "chaos drill mode",
                len(self._fault_plan.rules),
                self._fault_plan.seed,
            )
        multihost_init()
        # dispatch spine FIRST: every component below routes its device
        # work through it (engines/spine.py), so the lane count must be
        # configured before the first lane spins up
        from docqa_tpu.engines import spine as _spine

        self.spine = _spine.configure(
            n_lanes=self.cfg.dispatch.n_lanes,
            max_depth=self.cfg.dispatch.max_depth,
            inline=self.cfg.dispatch.inline,
            strict_sync=self.cfg.dispatch.strict_sync,
        )
        self.mesh = make_mesh(self.cfg.mesh) if jax.device_count() > 1 else None
        t_phase = time.perf_counter()

        if self.cfg.flags.use_fake_encoder:
            if self.cfg.encoder.checkpoint_dir:
                # surface the conflict: the operator configured a real
                # checkpoint but the fake flag wins — silent hash
                # embeddings "from" a real model is the trap
                log.warning(
                    "flags.use_fake_encoder=true shadows "
                    "encoder.checkpoint_dir=%s — serving HASH embeddings",
                    self.cfg.encoder.checkpoint_dir,
                )
            self.encoder = HashEncoder(self.cfg.encoder)
        elif self.cfg.encoder.checkpoint_dir:
            # real-checkpoint serving: the ergonomic the reference gets
            # from SentenceTransformer("all-MiniLM-L6-v2") (indexer.py:21)
            from docqa_tpu.config import EncoderConfig
            from docqa_tpu.models.hf_checkpoint import load_checkpoint_dir

            enc_cfg, enc_params, _ = load_checkpoint_dir(
                self.cfg.encoder.checkpoint_dir,
                expect=EncoderConfig,
                tokenizer_fallback=self.cfg.encoder.tokenizer_path,
            )
            if enc_cfg.embed_dim != self.cfg.store.dim:
                raise ValueError(
                    f"encoder checkpoint embeds {enc_cfg.embed_dim}-d but "
                    f"store.dim is {self.cfg.store.dim} — set "
                    f"DOCQA_STORE__DIM={enc_cfg.embed_dim} (an existing "
                    "index snapshot of the old dim cannot be reused)"
                )
            self.encoder = EncoderEngine(
                enc_cfg, mesh=self.mesh, params=enc_params
            )
        else:
            self.encoder = EncoderEngine(self.cfg.encoder, mesh=self.mesh)

        t_phase = _phase("encoder_init", t_phase)
        # ---- store: restore-from-snapshot on boot (parity with the
        # reference's reload, indexer.py:97-101 — minus its unlocked-file
        # races).  A corrupt/mismatched snapshot logs and serves fresh, the
        # reference's own degrade-don't-die behavior (llm-qa/main.py:61-62).
        self._index_dir = (
            os.path.join(self.cfg.data.work_dir, "index")
            if self.cfg.data.work_dir
            else None
        )
        self._docs_since_snapshot = 0
        self.store = None
        if self._index_dir and os.path.exists(
            os.path.join(self._index_dir, "LATEST")
        ):
            try:
                self.store = VectorStore.restore(
                    self._index_dir, self.cfg.store, mesh=self.mesh
                )
                log.info(
                    "restored index v%d (%d rows) from %s",
                    self.store.version, self.store.count, self._index_dir,
                )
            except Exception:
                log.exception(
                    "index restore failed; starting with an empty store"
                )
        if self.store is None:
            self.store = VectorStore(self.cfg.store, mesh=self.mesh)

        # serving index: exact store, or the tiered IVF+tail composition
        # for beyond-exact-scale corpora (store stays the ingest target and
        # source of truth either way)
        # lexical tier (docqa-lexroute): device-resident inverted index
        # over the SAME corpus, fed by the store's index-sink seam so
        # journal replay / snapshot restore converge both tiers from one
        # ingest path (index/lexical.py).  Registered before any
        # bootstrap indexing so first-boot CSVs land in both tiers.
        self.lexical = None
        if self.cfg.lexical.enabled:
            from docqa_tpu.index.lexical import LexicalIndex

            self.lexical = LexicalIndex(
                vocab_size=self.cfg.lexical.vocab_size,
                tile_width=self.cfg.lexical.tile_width,
                k1=self.cfg.lexical.k1,
                b=self.cfg.lexical.b,
                ref_len=self.cfg.lexical.ref_len,
                mesh=self.mesh,
            )
            self.store.register_index_sink(self.lexical)

        if self.cfg.store.serving_index == "tiered":
            from docqa_tpu.index.tiered import TieredIndex

            self.search_index = TieredIndex(
                self.store,
                nprobe=self.cfg.store.ivf_nprobe,
                min_rows=self.cfg.store.ivf_min_rows,
                rebuild_tail_rows=self.cfg.store.ivf_rebuild_tail,
                storage=self.cfg.store.ivf_storage,
                lexical=self.lexical,
                hybrid_alpha=self.cfg.lexical.hybrid_alpha,
                default_mode=self.cfg.lexical.serving_mode,
            )
        else:
            self.search_index = self.store

        t_phase = _phase("index_init", t_phase)
        if self.cfg.ner.train_steps > 0 or self.cfg.ner.params_path:
            # the cache rides the persistence root so restarts load
            # instead of retrain (the npz fingerprint invalidates it on
            # any architecture change); without a work_dir nothing is
            # cached — the tagger trains at every boot
            params_path = self.cfg.ner.params_path or (
                os.path.join(self.cfg.data.work_dir, "ner.npz")
                if self.cfg.data.work_dir
                else None
            )
            self.deid = DeidEngine.trained(
                self.cfg.ner,
                params_path=params_path,
                steps=self.cfg.ner.train_steps,
                mesh=self.mesh,
            )
        else:  # plumbing mode (tests): random-init tagger
            self.deid = DeidEngine(self.cfg.ner)
        t_phase = _phase("ner_load_or_train", t_phase)
        if self.cfg.decoder.checkpoint_dir and self.cfg.flags.use_fake_llm:
            # the fake path never decodes — don't pay a multi-GB weight
            # load for a generator nothing will invoke, but say so
            log.warning(
                "flags.use_fake_llm=true: decoder.checkpoint_dir=%s is NOT "
                "loaded (fake answers are served)",
                self.cfg.decoder.checkpoint_dir,
            )
        if self.cfg.decoder.checkpoint_dir and not self.cfg.flags.use_fake_llm:
            # real-checkpoint serving: the ergonomic the reference gets
            # from ChatOllama(model="mistral") (llm-qa/main.py:66-69).
            # Architecture + weights + vocabulary come from the directory;
            # the configured quantize_weights/quant_bits still govern the
            # serving precision (quantize-on-load in GenerateEngine).
            import dataclasses as _dc

            from docqa_tpu.config import DecoderConfig
            from docqa_tpu.models.hf_checkpoint import load_checkpoint_dir

            dec_cfg, dec_params, _ = load_checkpoint_dir(
                self.cfg.decoder.checkpoint_dir,
                expect=DecoderConfig,
                keep={
                    "quantize_weights": self.cfg.decoder.quantize_weights,
                    "quant_bits": self.cfg.decoder.quant_bits,
                },
                tokenizer_fallback=self.cfg.decoder.tokenizer_path,
            )
            # cap the context window at the CONFIGURED max_seq_len: the
            # batcher sizes its KV cache from cfg.max_seq_len x n_slots,
            # and a real checkpoint's max_position_embeddings (32k for
            # Mistral, 128k for Llama-3.1) would OOM the 16 GB chip
            dec_cfg = _dc.replace(
                dec_cfg,
                max_seq_len=min(
                    dec_cfg.max_seq_len, self.cfg.decoder.max_seq_len
                ),
            )
            self.generator = GenerateEngine(
                dec_cfg, gen=self.cfg.generate, params=dec_params,
                mesh=self.mesh,
            )
        else:
            self.generator = GenerateEngine(
                self.cfg.decoder, gen=self.cfg.generate, mesh=self.mesh
            )
        t_phase = _phase("decoder_weight_init", t_phase)
        # Decode-engine POOL: the single submit surface for ALL generation
        # (BASELINE config 5, QPS 16 — and ROADMAP item 5's scale-out
        # spine).  The pool owns N ContinuousBatcher replicas with a
        # liveness contract each (worker heartbeat, canary generate,
        # per-replica breaker): a replica that dies or wedges fails over
        # instead of stranding every in-flight and queued request until a
        # process restart — the serving plane's old single point of
        # failure.  replicas=1 (default) keeps one batcher's economics
        # while retaining fail-fast, drain, and /api/pool.
        if self.cfg.flags.use_fake_llm:
            self.batcher = None
        else:
            from docqa_tpu.engines.pool import EnginePool

            self.batcher = EnginePool(
                self.generator, cfg=self.cfg.pool, qos=self.cfg.qos
            )
        t_phase = _phase("kv_pool_alloc", t_phase)
        summarizer_cfg = self.cfg.summarizer
        instruction_prompts = True
        if (
            summarizer_cfg.backend == "seq2seq"
            and not self.cfg.flags.use_fake_llm  # fake path never decodes —
            # don't pay a BART-class param init it would never touch
        ):
            # dedicated BART-class encoder-decoder (its own weights; the
            # decode loop is seq2seq-internal, so no batcher lane).  Its
            # source window bounds the packing budget — otherwise the
            # engine would clip a 3k-token packed prompt to max_src_len
            # and silently drop documents.
            import dataclasses as _dc

            from docqa_tpu.engines.seq2seq import Seq2SeqEngine

            if self.cfg.seq2seq.checkpoint_dir:
                # bart-large-cnn-layout directory: architecture + weights
                # + vocabulary + SHIPPED generation policy come from the
                # checkpoint's config.json; a policy knob the operator SET
                # (non-None — the knobs are Optional exactly for this)
                # overrides it, including setting the engine default
                # (num_beams=1 forces greedy over a checkpoint's 4)
                from docqa_tpu.config import Seq2SeqConfig
                from docqa_tpu.models.hf_checkpoint import (
                    load_checkpoint_dir,
                )

                _policy_knobs = (
                    "num_beams", "length_penalty", "min_length",
                    "no_repeat_ngram",
                )
                keep = {
                    k: getattr(self.cfg.seq2seq, k)
                    for k in _policy_knobs
                    if getattr(self.cfg.seq2seq, k) is not None
                }
                s2s_cfg, s2s_params, _ = load_checkpoint_dir(
                    self.cfg.seq2seq.checkpoint_dir,
                    expect=Seq2SeqConfig,
                    keep=keep,
                    tokenizer_fallback=self.cfg.seq2seq.tokenizer_path,
                )
                summarizer_model = Seq2SeqEngine(s2s_cfg, params=s2s_params)
            else:
                s2s_cfg = self.cfg.seq2seq
                summarizer_model = Seq2SeqEngine(s2s_cfg)
            summarizer_batcher = None
            summarizer_cfg = _dc.replace(
                summarizer_cfg,
                max_input_tokens=min(
                    summarizer_cfg.max_input_tokens,
                    s2s_cfg.max_src_len,
                ),
            )
            instruction_prompts = False  # BART summarizes raw source text
        else:
            summarizer_model = self.generator
            summarizer_batcher = self.batcher
        self.summarizer = SummarizeEngine(
            summarizer_model,
            summarizer_cfg,
            use_fake=self.cfg.flags.use_fake_llm,
            batcher=summarizer_batcher,
            instruction_prompts=instruction_prompts,
        )

        if journal_dir is None and self.cfg.data.work_dir:
            # queue journal rides the persistence root: un-acked pipeline
            # messages replay after a crash (at-least-once across restarts)
            journal_dir = os.path.join(self.cfg.data.work_dir, "journal")
        self.broker = make_broker(self.cfg.broker, journal_dir=journal_dir)
        registry_url = self.cfg.registry.url
        if registry_url == "sqlite://" and self.cfg.data.work_dir:
            # persistence on → document records must survive restarts too
            # (an index that outlives its registry would serve vectors for
            # documents /documents/ no longer lists)
            os.makedirs(self.cfg.data.work_dir, exist_ok=True)
            registry_url = "sqlite:///" + os.path.join(
                self.cfg.data.work_dir, "registry.db"
            )
        self.registry = DocumentRegistry(registry_url)
        http_extractor = None
        if self.cfg.service.extractor_url:
            from docqa_tpu.service.extract import make_http_extractor

            http_extractor = make_http_extractor(self.cfg.service.extractor_url)
        self.pipeline = DocumentPipeline(
            self.cfg,
            self.broker,
            self.registry,
            self.deid,
            self.encoder,
            self.store,
            http_extractor=http_extractor,
            on_indexed=self._on_indexed,
            breakers=self.breakers,
        )

        # ---- registry ↔ index reconciliation: a crash between periodic
        # snapshots can leave durable INDEXED rows whose vectors never made
        # it into the restored snapshot.  The registry must not lie —
        # re-mark those documents ERROR_INDEXING (their raw text is gone;
        # re-upload is the recovery path, and /documents/ now says so).
        if self._index_dir:
            try:
                indexed_ids = {
                    md.get("doc_id") for md in self.store.metadata_rows()
                }
                from docqa_tpu.service import registry as reg

                lost = [
                    rec
                    for rec in self.registry.list_documents()
                    if rec.status == reg.INDEXED
                    and rec.doc_id not in indexed_ids
                ]
                for rec in lost:
                    self.registry.set_status(rec.doc_id, reg.ERROR_INDEXING)
                if lost:
                    log.warning(
                        "reconciled %d registry rows whose vectors predate "
                        "the restored snapshot (re-marked ERROR_INDEXING)",
                        len(lost),
                    )
            except Exception:
                log.exception("registry/index reconciliation failed")

        t_phase = time.perf_counter()
        # ---- first-boot knowledge base (parity: indexer.py:102-107 indexed
        # default_data/*.csv into an otherwise-empty index)
        if self.cfg.data.bootstrap_dir and self.store.count == 0:
            from docqa_tpu.service.bootstrap import bootstrap_csv_dir

            n = bootstrap_csv_dir(
                self.cfg.data.bootstrap_dir, self.encoder, self.store
            )
            if n and self._index_dir:
                self._snapshot()
        _phase("bootstrap_index", t_phase)
        # Fused encode+search retrieval (one dispatch) applies when serving
        # exact search over the plain store with a real device encoder;
        # the hash-encoder fake keeps the generic two-step path; real
        # encoders get the one-dispatch fused program matched to the
        # serving index (exact store or tiered IVF+tail).
        retriever = None
        if not self.cfg.flags.use_fake_encoder:
            if self.search_index is self.store:
                from docqa_tpu.engines.retrieve import FusedRetriever

                retriever = FusedRetriever(self.encoder, self.store)
            else:
                from docqa_tpu.engines.retrieve import FusedTieredRetriever

                retriever = FusedTieredRetriever(
                    self.encoder, self.search_index
                )
        # answer router (docqa-lexroute): extractive/lookup questions are
        # served straight from retrieval — zero decode dispatches, no KV
        # slot.  Disabled = the pre-lexroute generative-only path.
        self.router = None
        if self.cfg.router.enabled:
            from docqa_tpu.engines.router import AnswerRouter

            self.router = AnswerRouter(
                min_confidence=self.cfg.router.min_confidence,
                evidence_min=self.cfg.router.evidence_min,
            )
        self.qa = QAService(
            self.encoder,
            self.search_index,
            self.generator,
            self.summarizer,
            k=self.cfg.store.default_k,
            use_fake_llm=self.cfg.flags.use_fake_llm,
            batcher=self.batcher,
            retriever=retriever,
            breakers=self.breakers,
            resilience=self.cfg.resilience,
            router=self.router,
        )
        if self.cfg.flags.use_fake_retrieval:
            # standalone/dev parity with the reference's USE_FAKE_RETRIEVAL
            # (core/config.py:22-23): synthesis works without any index
            from docqa_tpu.service.synthesis import fake_patient_retrieval

            retrieval = fake_patient_retrieval
        else:
            retrieval = self.qa.patient_snippets
        self.synthesis = SynthesisService(
            retrieval=retrieval, summarizer=self.summarizer
        )

        # ---- retrieval-quality observatory (docqa-recallscope,
        # docs/OBSERVABILITY.md "Retrieval quality"): shadow-sampling
        # online recall estimation + the measured nprobe frontier.
        # Constructed for every runtime the config enables it on and
        # installed as the process hook point (the tiered/fused search
        # paths look it up per retrieval); the worker starts in start().
        # Exact serving produces no shadow jobs (recall is 1.0 by
        # construction there), so the observatory idles at zero cost.
        rq = self.cfg.retrieval_quality
        self.retrieval_obs = None
        if rq.enabled:
            apply_cb = getattr(self.search_index, "set_nprobe", None)
            self.retrieval_obs = obs.RetrievalObservatory(
                sample_every=rq.sample_every,
                seed=rq.seed,
                window=rq.window,
                max_pending=rq.max_pending,
                frontier_every=rq.frontier_every,
                frontier_factors=rq.frontier_factors,
                min_frontier_n=rq.min_frontier_n,
                recall_target=rq.recall_target,
                auto_apply=rq.auto_apply_nprobe,
                apply_nprobe=apply_cb,
                registry=DEFAULT_REGISTRY,
            )
            obs.set_retrieval_observatory(self.retrieval_obs)

        # ---- request cost attribution (docqa-costscope,
        # docs/OBSERVABILITY.md "Cost attribution"): the process ledger
        # gets its pressure probe — the closure shed forensics snapshots
        # (which classes hold KV blocks / lanes / queue slots) — wired
        # over whatever batcher surface this runtime built, plus the
        # spine's queue depth.  /api/costs + /api/costs/sheds serve it.
        self.costs = obs.DEFAULT_COST_LEDGER
        self.costs.set_pressure_probe(self._cost_pressure)

        # ---- telemetry: time-series rollups + SLO burn-rate alerting
        # (docqa-telemetry, docs/OBSERVABILITY.md).  Built last so the
        # sampler scrapes fully-constructed components; started in
        # start() and joined in stop() so it can never outlive the
        # serving plane it observes.
        tcfg = self.cfg.telemetry
        self.telemetry = None
        self.slo = None
        self.sampler = None
        if tcfg.enabled:
            # align every histogram's rollup windows with the store's
            # clock BEFORE serving (re-windowing drops sealed history)
            DEFAULT_REGISTRY.configure_windows(tcfg.interval_s, tcfg.points)
            self.telemetry = obs.TelemetryStore(
                interval_s=tcfg.interval_s, points=tcfg.points
            )
            slos = obs.default_ask_slos(
                p95_objective_ms=tcfg.slo_ask_p95_ms,
                availability=tcfg.slo_ask_availability,
                degraded_budget=tcfg.slo_ask_degraded_budget,
                short_windows=tcfg.slo_short_windows,
                long_windows=tcfg.slo_long_windows,
                burn_threshold=tcfg.slo_burn_threshold,
            )
            if self.retrieval_obs is not None:
                # the recall objective burns exactly like a latency
                # burn: fires, flags the window's /ask traces anomalous
                slos += obs.default_retrieval_slos(
                    recall_target=rq.recall_target,
                    short_windows=rq.slo_short_windows,
                    long_windows=rq.slo_long_windows,
                    burn_threshold=rq.slo_burn_threshold,
                    min_events=rq.slo_min_events,
                )
            self.slo = obs.BurnRateEvaluator(
                self.telemetry,
                slos,
                registry=DEFAULT_REGISTRY,
                recorder=obs.DEFAULT_RECORDER,
            )
            # QoS self-protection closes its loop here: the burn-rate
            # evaluator becomes the admission layer's deferral signal
            # (batch-class sheds while ask_p95/availability burn)
            probe = getattr(self.batcher, "set_slo_probe", None)
            if probe is not None:
                probe(self.slo.firing)
            self.sampler = obs.TelemetrySampler(
                self.telemetry,
                registry=DEFAULT_REGISTRY,
                batcher=self.batcher,
                broker=self.broker,
                queues=(
                    self.cfg.broker.raw_queue,
                    self.cfg.broker.clean_queue,
                ),
                recorder=obs.DEFAULT_RECORDER,
                # HBM/jit-cache probes only make sense when decode is
                # real — the fake-llm path never compiles the programs
                # the probe would measure
                engine=self.generator if self.batcher is not None else None,
                slo_evaluator=self.slo,
                # dispatch_* series: spine queue depth / lane occupancy
                # gauges + per-stage device-time counters
                spine=self.spine,
                # retrieve_recall_* series: the shadow estimator's live
                # recall/CI gauges (counters ride the registry scrape)
                retrieval=self.retrieval_obs,
                sample_every_s=tcfg.sample_every_s,
                hbm_refresh_s=tcfg.hbm_refresh_s,
                # cost_* gauges (bounded); the per-class cost counters
                # ride the registry scrape like every other counter
                extra_probes=(self.costs.telemetry_gauges,),
            )
        _phase("total", t_boot)
        # decode warm-up outcome, served on /api/status ("warmup"):
        # pending → running → ok | failed (+ the error) | skipped
        self.warmup_status: dict = {
            "state": "skipped" if self.batcher is None else "pending"
        }

    def _compile_cache_entries(self) -> int:
        try:
            return len(os.listdir(self._compile_cache_dir))
        except OSError:
            return 0

    def device_status(self) -> dict:
        """What this process runs on, as JAX reports it — the ONE place
        a client that must stay off JAX (chip_smoke.py) learns the
        platform, the mesh and each device's memory in use."""
        import jax

        devices = jax.devices()
        memory = []
        for d in devices:
            stats = d.memory_stats() or {}
            memory.append(
                {
                    "id": d.id,
                    "bytes_in_use": stats.get("bytes_in_use"),
                    "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                    "bytes_limit": stats.get("bytes_limit"),
                }
            )
        return {
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "count": len(devices),
            "mesh": (
                {"data": self.mesh.n_data, "model": self.mesh.n_model}
                if self.mesh is not None
                else None
            ),
            "memory": memory,
            # devices the vector index's rows are sharded over
            "index_devices": self.store.n_devices,
            "compile_cache": {
                "dir": self._compile_cache_dir,
                "entries_at_boot": self._compile_cache_entries_at_boot,
                "entries": self._compile_cache_entries(),
            },
        }

    def _cost_pressure(self):
        """Shed-forensics pressure snapshot (obs/costs.py): per-class
        holdings from the batcher/pool plus the spine's live depth.
        Lock-free end to end — it runs on shedding threads."""
        out = {}
        b = self.batcher
        probe = getattr(b, "pressure_by_class", None)
        if probe is not None:
            out = probe() or {}
        # operator dry-run: what KV preemption WOULD evict for an
        # interactive arrival right now (every mode, including off) —
        # lets /api/costs/sheds forensics show the counterfactual
        cand = getattr(b, "preemption_candidates", None)
        if cand is not None:
            try:
                out["preemption_candidates"] = cand()
            except Exception:
                pass
        try:
            out["spine_queue_depth"] = self.spine.queue_depth
        except Exception:
            pass
        return out

    def start(self) -> "DocQARuntime":
        self.pipeline.start()
        if self.retrieval_obs is not None:
            self.retrieval_obs.start()
        if self.sampler is not None:
            self.sampler.start()
        self._warmup_thread = None
        if self.batcher is not None:
            # warm the decode programs off the request path: the first
            # trace+compile costs tens of seconds on a real chip, and a
            # cold-start /ask would burn its whole request deadline
            # (resilience.request_deadline_s) inside the compiler —
            # showing up as a phantom decoder outage on every deploy.
            # The thread is KEPT and joined in stop(): a live XLA
            # compile on a daemon thread at interpreter exit aborts the
            # process (the hazard engines/pool.py already joins its
            # rebuild warmups for — observed on the short-lived
            # fault-drill drive in PR 7).
            import threading as _threading

            self._warmup_thread = _threading.Thread(
                target=self._warmup_decode, daemon=True, name="warmup"
            )
            self._warmup_thread.start()
        return self

    def _warmup_decode(self) -> None:
        t0 = time.perf_counter()
        status = self.warmup_status
        status["state"] = "running"
        try:
            # compile the ragged-prefill token budgets plus the decode
            # chunk for the configured warm depth
            # (gen.startup_warm_buckets smallest budgets; -1 = all of
            # them — the whole paged matrix is <= 3 programs, so even
            # "all" is cheap now) ahead of the first busy round
            gen = self.batcher.gen
            depth = gen.startup_warm_buckets
            if depth != 0:
                buckets = (
                    None if depth < 0
                    else list(gen.prefill_token_buckets[:depth])
                )
                self.batcher.warmup(buckets=buckets)
            # then one real request end to end: exercises admission,
            # sampling, retirement and the result path on top of the
            # warmed programs (background class: warmups must never
            # read as interactive spend on /api/costs)
            self.batcher.submit_ids(
                [1, 2, 3], max_new_tokens=2, req_class="background"
            ).result(timeout=600)
            # the /ask retrieval program (encode + top-k in one
            # dispatch) at the question shape: it compiles on first use
            # like the decode programs, and a first ask must not spend
            # its deadline inside the compiler either
            if self.store.count:
                self.qa._retrieve("warm-up", k=self.qa.k)
            # register the warmed programs' cost_analysis() FLOPs with
            # the observatory (background probe items): /api/status
            # then reports per-stage MFU instead of wall guesses
            if self.cfg.dispatch.annotate_costs and hasattr(
                self.batcher, "annotate_costs"
            ):
                self.batcher.annotate_costs()
            # which attention ran: Mosaic custom calls in the lowered
            # decode program (0 = the XLA reference path), and — when
            # the kernel is on — its agreement with that reference on
            # THIS device at the decode shapes
            status["decode_kernel_calls"] = getattr(
                self.batcher, "decode_kernel_calls", None
            )
            if self.generator.use_flash:
                status["kernel_check"] = self.generator.kernel_selfcheck()
            status["state"] = "ok"
            log.info(
                "decode programs warm (ragged token budgets, "
                "warm depth %s)", depth,
            )
        except Exception as e:
            # serving continues cold (request-time degradation is
            # product behaviour) but the failure is on /api/status for
            # every operator and smoke test to see
            status["state"] = "failed"
            status["error"] = repr(e)[:500]
            log.exception("decode warmup failed (serving continues cold)")
        finally:
            status["seconds"] = round(time.perf_counter() - t0, 3)

    # ---- persistence hooks ---------------------------------------------------

    def _snapshot(self, keep_previous: bool = True) -> None:
        if not self._index_dir:
            return
        try:
            self.store.snapshot(self._index_dir, keep_previous=keep_previous)
            self._docs_since_snapshot = 0
        except Exception:
            log.exception("index snapshot failed")

    def _on_indexed(self, n_docs: int) -> None:
        """Called by the index worker after each indexed batch — snapshots
        every ``data.snapshot_every`` documents (the reference rewrote the
        full index after EVERY message, ``indexer.py:125``)."""
        if not self._index_dir or self.cfg.data.snapshot_every <= 0:
            return
        self._docs_since_snapshot += n_docs
        if self._docs_since_snapshot >= self.cfg.data.snapshot_every:
            self._snapshot()

    def delete_document(self, doc_id: str, erase: bool = False) -> int:
        """Tombstone a document out of retrieval (clinical right-to-erasure;
        the reference had no deletion at all — its index only ever grew).

        Covers every lifecycle stage: a doc still in the async pipeline is
        suppressed (its queued message gets dropped, not indexed); an
        indexed doc's chunks are tombstoned; ``erase=True`` additionally
        compacts the store — run even when THIS call tombstoned nothing,
        so erasing an already-tombstoned doc still removes its bytes — and
        resets any IVF tier built over the old row numbering.  Returns the
        number of chunks tombstoned by this call."""
        from docqa_tpu.service import registry as reg

        # first, so a racing index-worker batch can't add chunks after we
        # looked: suppression wins regardless of pipeline position
        self.pipeline.suppress_doc(doc_id)
        n = self.store.delete_docs([doc_id])
        threshold = self.cfg.store.compact_threshold
        auto = (
            not erase
            and threshold > 0
            and self.store.count > 0
            and self.store.deleted_count >= threshold * self.store.count
        )
        compacted = 0
        if erase or auto:
            compacted = self.store.compact_deleted()
            if compacted and self.search_index is not self.store and hasattr(
                self.search_index, "reset"
            ):
                self.search_index.reset()
        try:
            self.registry.set_status(doc_id, reg.DELETED)
        except Exception:
            log.exception("status write failed for %s", doc_id)
        if n or compacted:
            # deletions must survive a crash immediately — this is a
            # privacy action, not an indexing optimization.  An erasure
            # also drops the rollback predecessor snapshot: it still holds
            # the erased vectors + de-identified text on disk.
            self._snapshot(keep_previous=not erase)
        return n

    def stop(self) -> None:
        # sampler first: it reads the components torn down below (every
        # probe is fenced, but a clean join beats relying on fences)
        if self.sampler is not None:
            self.sampler.stop()
        # retrieval observatory next: its worker submits spine work
        # against the store/tier — join it before the index plane (and
        # before the spine can close at interpreter exit).  Uninstall
        # the process hook only if it is still OURS (tests boot several
        # runtimes; a later runtime's observatory must survive an
        # earlier one's stop)
        if self.retrieval_obs is not None:
            self.retrieval_obs.stop()
            if obs.get_retrieval_observatory() is self.retrieval_obs:
                obs.set_retrieval_observatory(None)
        self.pipeline.stop()
        if self.batcher is not None:
            self.batcher.stop()
        # a tiered index may have a background ivf-rebuild mid-compile;
        # join it before the interpreter can exit (VectorStore has no
        # close — only the tiered composition owns a thread)
        index_close = getattr(self.search_index, "close", None)
        if index_close is not None:
            index_close()
        warmup = getattr(self, "_warmup_thread", None)
        if warmup is not None and warmup.is_alive():
            # the stopped batcher fails the warmup's submits fast, but a
            # compile already inside XLA should be allowed to finish —
            # abandoning it aborts the interpreter at exit.  The join is
            # SHORT on purpose: a warmup thread can also be wedged in
            # the known CPU-client capacity hazard (engines/pool.py PR 6
            # notes), and a long join would convert that leaked-thread
            # nuisance into a multi-second stall on every stop()
            warmup.join(timeout=5)
            if warmup.is_alive():
                log.warning("decode warmup thread still alive after stop()")
        # final snapshot so a restart resumes exactly here (kill-and-restart
        # loses nothing; the reference lost everything after its last save)
        self._snapshot()
        self.broker.close()
        self.registry.close()
        if self._fault_plan is not None:
            _faults.uninstall(self._fault_plan)


# ---------------------------------------------------------------------------
# HTTP layer (aiohttp).  Three lanes:
#
# * device_pool (1 thread) — encode/search dispatches and generation
#   *submission*.  Retrieval programs stay serialized (latency policy), but
#   a submission only enqueues into the continuous batcher, so the single
#   thread never blocks on decoding.
# * gen_pool (max_concurrent threads) — host-side WAITS on batcher handles.
#   Concurrent /ask requests decode together in the batcher's slot program;
#   each waiter just parks here until its lane finishes.
# * host_pool — extraction/registry IO, so uploads don't block QA.
# ---------------------------------------------------------------------------

def make_app(rt: DocQARuntime):
    from aiohttp import web

    device_pool = concurrent.futures.ThreadPoolExecutor(
        max_workers=1, thread_name_prefix="device"
    )
    gen_pool = concurrent.futures.ThreadPoolExecutor(
        max_workers=max(rt.cfg.generate.max_concurrent, 4),
        thread_name_prefix="genwait",
    )
    host_pool = concurrent.futures.ThreadPoolExecutor(
        max_workers=4, thread_name_prefix="host"
    )

    async def on_device(fn, *args, **kw):
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(
            device_pool, lambda: fn(*args, **kw)
        )

    async def on_gen(fn, *args, **kw):
        """Blocking waits for batcher results (and, with a batcher present,
        synthesis flows — their generation rides the batcher, so they must
        not occupy the single device thread while waiting)."""
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(gen_pool, lambda: fn(*args, **kw))

    async def on_host(fn, *args, **kw):
        """Host-only work (extraction, registry/journal IO) — keeps large
        uploads from head-of-line-blocking /ask and /summarize behind the
        single device executor."""
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(host_pool, lambda: fn(*args, **kw))

    def json_response(payload, **kw):
        """Every JSON body leaves through to_wire() — numpy scalars
        become native, non-finite floats become null with the path
        recorded under ``_nonfinite_fields`` (wire-safety's sanctioned
        boundary; api_contract.json tolerates the flag key)."""
        return web.json_response(to_wire(payload), **kw)

    def json_error(status: int, detail: str, ctx=None):
        resp = json_response({"detail": detail}, status=status)
        if ctx is not None:
            resp.headers["X-Trace-Id"] = ctx.trace_id
        return resp

    def with_trace(resp, ctx):
        """Stamp the request's trace id on the response — the body
        contract stays exactly the reference's (``{"answer","sources"}``
        for /ask); the timeline link rides a header."""
        if ctx is not None:
            resp.headers["X-Trace-Id"] = ctx.trace_id
        return resp

    # ---- health / status ----------------------------------------------------

    async def health(_req):
        return json_response({"status": "ok"})

    async def api_status(_req):
        queues = (rt.cfg.broker.raw_queue, rt.cfg.broker.clean_queue)
        return json_response(
            {
                "service": "docqa-tpu",
                "status": "running",
                # platform / device_kind / count / mesh / per-device
                # memory in use / compile-cache entries, from JAX itself
                "device": rt.device_status(),
                # set-up split (seconds per construction phase) and the
                # decode warm-up's outcome: "failed" here means requests
                # compile on the request path and will degrade
                "boot": rt.boot_s,
                "warmup": rt.warmup_status,
                "indexed_vectors": rt.store.count,
                "index_version": rt.store.version,
                "queue_depths": {q: rt.broker.depth(q) for q in queues},
                # pipeline health at a glance: messages being processed and
                # poison messages parked in the DLQ (the reference DROPPED
                # poison messages, anonymizer.py:83-87)
                "in_flight": {q: rt.broker.in_flight(q) for q in queues},
                "dead_letters": {
                    q: len(rt.broker.dead_letters(q)) for q in queues
                },
                # per-dependency breaker states (closed/half_open/open):
                # an "open" here is WHY /ask answers are degraded right now
                "breakers": rt.breakers.states(),
                # decode-pool summary (full detail on /api/pool): replica
                # health at a glance — a dead/draining replica here is WHY
                # capacity halved or requests briefly parked
                "pool": (
                    rt.batcher.status()
                    if hasattr(rt.batcher, "status")
                    else None
                ),
                # SLO burn-rate state (obs/slo.py): a firing alert here
                # is WHY /api/traces?anomalous=1 just grew — the
                # evaluator flags the firing window's timelines
                "slo": rt.slo.status() if rt.slo is not None else None,
                # multi-tenant QoS policy state (docqa-qos): weights,
                # preemption mode, live deferral flag, queue depths by
                # class — "is the runtime protecting interactive right
                # now, and at whose expense"
                "qos": (
                    rt.batcher.qos_status()
                    if hasattr(rt.batcher, "qos_status")
                    else None
                ),
                # device observatory (engines/spine.py + obs/
                # observatory.py): spine queue/occupancy + per-stage
                # device time with MFU/roofline where a cost model is
                # registered — "where did device time go and what did
                # it buy", not wall-clock guesses
                "dispatch": {
                    "spine": rt.spine.stats(),
                    "observatory": obs.DEFAULT_OBSERVATORY.stats(),
                },
            }
        )

    async def metrics(req):
        """Prometheus text exposition (scraper-facing; ISSUE 7), content
        negotiated: plain 0.0.4 by default (exemplar-free — the legacy
        parser rejects exemplar syntax), OpenMetrics 1.0 with exemplar
        trace-ids when the Accept header asks for it.  The JSON snapshot
        the docs' curl examples used lives on /api/metrics — same
        registry, different serialization."""
        openmetrics = "application/openmetrics-text" in req.headers.get(
            "Accept", ""
        )
        text = obs.prometheus_text(
            DEFAULT_REGISTRY, rt.telemetry, openmetrics=openmetrics
        )
        if openmetrics:
            return web.Response(
                text=text,
                content_type="application/openmetrics-text",
                charset="utf-8",
                headers={"X-Prometheus-Format": "openmetrics-1.0"},
            )
        return web.Response(
            text=text,
            content_type="text/plain",
            charset="utf-8",
            headers={"X-Prometheus-Format": "0.0.4"},
        )

    async def api_metrics(_req):
        return json_response(DEFAULT_REGISTRY.snapshot())

    async def api_telemetry(req):
        """Rollup time series as JSON (?name= for one series) — the
        soak/chaos drivers dump these next to trace timelines so a
        violation carries its ten-minute history, not just the moment."""
        if rt.telemetry is None:
            return json_error(404, "telemetry disabled (telemetry.enabled)")
        return json_response(
            obs.telemetry_json(rt.telemetry, req.query.get("name"))
        )

    async def api_costs(_req):
        """Per-class cost attribution (docqa-costscope): class
        breakdown, top session spenders, share of measured device time
        (vs the spine total) and of KV pool block-seconds —
        docs/OPERATIONS.md "Answer 'who caused the shed'" reads this."""
        spine_dev = sum(
            row.get("device_s", 0.0)
            for row in rt.spine.stats()["stages"].values()
        )
        bs = getattr(rt.batcher, "block_seconds", None)
        pool_bs = None
        if bs is not None:
            try:
                pool_bs = bs()["total"]
            except Exception:
                pool_bs = None
        return json_response(
            rt.costs.snapshot(
                spine_device_s=spine_dev, pool_block_seconds=pool_bs
            )
        )

    async def api_costs_sheds(req):
        """Shed forensics ring: every QueueFull / BlockPoolExhausted /
        SpineSaturated / deadline shed's pressure snapshot — which
        classes held the blocks, lanes, and queue slots at that
        instant."""
        try:
            limit = int(req.query.get("limit", "64"))
        except ValueError:
            return json_error(422, "limit must be an integer")
        if limit < 0:
            return json_error(422, "limit must be >= 0")
        return json_response(rt.costs.sheds(limit))

    async def api_retrieval(_req):
        """Retrieval-quality observatory (docqa-recallscope): live
        recall estimate + Wilson CI per (tier, nprobe), drift digests,
        the measured nprobe recall/latency frontier, and the
        recommended nprobe for the configured target — the evidence
        surface docs/OPERATIONS.md's recall-regression runbook reads."""
        if rt.retrieval_obs is None:
            return json_error(
                404,
                "retrieval observatory disabled (retrieval_quality.enabled)",
            )
        payload = rt.retrieval_obs.status()
        stats_fn = getattr(rt.search_index, "index_stats", None)
        payload["serving"] = {
            "serving_index": rt.cfg.store.serving_index,
            "rows": rt.store.count,
            "nprobe": getattr(rt.search_index, "nprobe", None),
            "covered": getattr(rt.search_index, "covered", None),
            "tail_rows": getattr(rt.search_index, "tail_rows", None),
            # tier layout + per-chunk/per-shard bytes (docqa-meshindex):
            # the capacity surface the "scale past 1M chunks" runbook
            # reads (storage dtype, shard count, bytes_per_chunk)
            "index": stats_fn() if stats_fn is not None else None,
            # structurally zero since the probe went mesh-native — kept
            # on the surface (tests/test_ivf_sharded.py pins it to 0) so
            # any future fallback reappearing is loud
            "offmesh_fallbacks": DEFAULT_REGISTRY.counter(
                "retrieve_offmesh_fallback"
            ).value,
        }
        # docqa-lexroute: answer-router posture + live route split, on
        # the same surface the retrieval runbooks already read (the
        # "Tune the answer router" runbook's evidence source)
        payload["routing"] = {
            "enabled": rt.router is not None,
            "min_confidence": getattr(rt.router, "min_confidence", None),
            "evidence_min": getattr(rt.router, "evidence_min", None),
            "routed_extractive": DEFAULT_REGISTRY.counter(
                "qa_routed_extractive"
            ).value,
            "routed_generative": DEFAULT_REGISTRY.counter(
                "qa_routed_generative"
            ).value,
            "hybrid_alpha": rt.cfg.lexical.hybrid_alpha,
            "serving_mode": rt.cfg.lexical.serving_mode,
        }
        return json_response(payload)

    # ---- decode-engine pool (docs/OPERATIONS.md "Replica pool") -------------

    def _pool_or_none():
        # duck-typed: the pool surface is whatever rt.batcher exposes;
        # fake-llm runtimes have no batcher at all
        b = rt.batcher
        return b if b is not None and hasattr(b, "rolling_restart") else None

    async def api_pool(_req):
        pool = _pool_or_none()
        if pool is None:
            return json_error(404, "no decode pool (fake-llm runtime)")
        return json_response(pool.status())

    async def api_pool_drain(req):
        """Drain one replica (stop admitting → finish in-flight).  Body
        ``{"replica": i, "timeout": s}``; the replica stays drained until
        /api/pool/resume — the hot-restart window."""
        pool = _pool_or_none()
        if pool is None:
            return json_error(404, "no decode pool (fake-llm runtime)")
        body = {}
        if req.can_read_body:
            try:
                body = await req.json()
            except Exception:
                return json_error(422, "body must be JSON")
        replica = body.get("replica", 0)
        try:
            timeout = float(body.get("timeout", 30.0))
        except (TypeError, ValueError):
            return json_error(422, "timeout must be a number")
        if not isinstance(replica, int) or not (
            0 <= replica < pool.n_replicas
        ):
            return json_error(
                422, f"replica must be 0..{pool.n_replicas - 1}"
            )
        return json_response(
            await on_host(pool.drain, replica, timeout)
        )

    async def api_pool_resume(req):
        pool = _pool_or_none()
        if pool is None:
            return json_error(404, "no decode pool (fake-llm runtime)")
        body = {}
        if req.can_read_body:
            try:
                body = await req.json()
            except Exception:
                return json_error(422, "body must be JSON")
        replica = body.get("replica", 0)
        if not isinstance(replica, int) or not (
            0 <= replica < pool.n_replicas
        ):
            return json_error(
                422, f"replica must be 0..{pool.n_replicas - 1}"
            )
        return json_response(
            await on_host(
                pool.resume, replica, bool(body.get("rebuild", False))
            )
        )

    async def api_pool_rolling_restart(req):
        """Drain → rebuild → resume every replica in turn (hot restart /
        weight reload with zero dropped requests).  Used by the
        ``--supervise`` launcher for planned restarts."""
        pool = _pool_or_none()
        if pool is None:
            return json_error(404, "no decode pool (fake-llm runtime)")
        timeout = 30.0
        if req.can_read_body:
            try:
                timeout = float(
                    (await req.json()).get("timeout_per_replica", 30.0)
                )
            except Exception:
                pass
        return json_response(
            await on_host(pool.rolling_restart, timeout)
        )

    # ---- observability (docs/OBSERVABILITY.md) ------------------------------

    async def api_traces(req):
        """Flight-recorder listing: recent completed timelines, or only
        the anomalous ring (?anomalous=1).  Summaries only — fetch one
        timeline via /api/trace/<id>."""
        anomalous = req.query.get("anomalous") in ("1", "true")
        try:
            limit = int(req.query.get("limit", "50"))
        except ValueError:
            return json_error(422, "limit must be an integer")
        return json_response(
            obs.DEFAULT_RECORDER.summaries(n=limit, anomalous=anomalous)
        )

    async def api_witness(_req):
        """The concurrency witness's lock-order graph (locks seen,
        witnessed edges, held-lock blocking events, cycles, and the
        cross-check against the static acquisition graph).  404 unless
        the process booted with DOCQA_RACE_WITNESS=1 — the witness must
        wrap locks at creation, so it cannot be enabled after boot."""
        from docqa_tpu.analysis.race_witness import witness_snapshot

        snap = witness_snapshot()
        if snap is None:
            return json_error(
                404,
                "witness not installed (boot with DOCQA_RACE_WITNESS=1)",
            )
        return json_response(snap)

    async def api_ledger(_req):
        """The resource-ledger witness's live dump (table/record counts,
        currently-live entries, witnessed call sites, and the
        witnessed-⊆-static cross-check).  On a serving process the
        leaked_tables / unretired_records lists show IN-FLIGHT work,
        not leaks — the leak assertion only holds at quiesce
        (chaos/soak run it after stop()).  404 unless booted with
        DOCQA_LEDGER_WITNESS=1."""
        from docqa_tpu.analysis.ledger_audit import ledger_snapshot

        snap = ledger_snapshot()
        if snap is None:
            return json_error(
                404,
                "ledger witness not installed (boot with "
                "DOCQA_LEDGER_WITNESS=1)",
            )
        return json_response(snap)

    async def api_trace_one(req):
        """One request's full timeline — JSON by default, Chrome-trace
        (Perfetto-loadable) with ?format=chrome."""
        trace = obs.DEFAULT_RECORDER.get(req.match_info["trace_id"])
        if trace is None:
            return json_error(404, "trace not found (evicted or unknown)")
        if req.query.get("format") == "chrome":
            return json_response(obs.to_chrome_trace([trace]))
        return json_response(obs.timeline_dict(trace))

    async def profiler_start(req):
        """Open an on-demand ``jax.profiler`` window (jit-exterior by
        construction: this runs on the HTTP surface, never inside a
        compiled program — the jit-purity lint rule enforces the
        general invariant)."""
        logdir = None
        if req.can_read_body:
            try:
                logdir = (await req.json()).get("logdir")
            except Exception:
                pass
        try:
            logdir = await on_host(obs.DEFAULT_PROFILER.start, logdir)
        except RuntimeError as e:  # already active
            return json_error(409, str(e))
        except Exception as e:  # backend without profiler support
            return json_error(500, f"profiler start failed: {e!r}")
        return json_response({"profiling": True, "logdir": logdir})

    async def profiler_stop(_req):
        try:
            logdir = await on_host(obs.DEFAULT_PROFILER.stop)
        except RuntimeError as e:  # no window open
            return json_error(409, str(e))
        except Exception as e:
            return json_error(500, f"profiler stop failed: {e!r}")
        return json_response({"profiling": False, "logdir": logdir})

    # ---- ingestion ----------------------------------------------------------

    async def ingest(req):
        """Multipart (file + form fields, reference contract
        doc-ingestor/main.py:19-24) or JSON {filename, text, ...}."""
        filename, data = None, None
        doc_type = patient_id = doc_date = None
        wait = req.query.get("wait") in ("1", "true")
        if req.content_type and req.content_type.startswith("multipart/"):
            reader = await req.multipart()
            async for part in reader:
                if part.name == "file":
                    filename = part.filename or "upload"
                    data = await part.read(decode=False)
                elif part.name in ("doc_type", "patient_id", "doc_date"):
                    value = (await part.text()).strip() or None
                    if part.name == "doc_type":
                        doc_type = value
                    elif part.name == "patient_id":
                        patient_id = value
                    else:
                        doc_date = value
        else:
            body = await req.json()
            filename = body.get("filename", "inline.txt")
            data = body.get("text", "").encode()
            doc_type = body.get("doc_type")
            patient_id = body.get("patient_id")
            doc_date = body.get("doc_date")
        if not data:
            return json_error(400, "no file/text provided")
        # the DOCUMENT trace: opened here, finished by the pipeline at
        # the doc's terminal status (INDEXED / ERROR_* / dead-letter) —
        # the response may return while deid/index hops are still
        # appending to the same timeline
        ctx = obs.new_trace("ingest")
        obs.cost_open(ctx, "background")
        try:
            record = await on_host(
                obs.call_in,
                ctx,
                rt.pipeline.ingest_document,
                filename,
                data,
                doc_type,
                patient_id,
                doc_date,
            )
        except Exception:
            # an exception ESCAPING the pipeline (before its own terminal
            # paths) would otherwise leak the trace open until the
            # recorder's abandoned-eviction mislabels it
            obs.finish(ctx, status="error")
            raise
        if wait:
            await asyncio.get_running_loop().run_in_executor(
                None, rt.pipeline.wait_indexed, record.doc_id
            )
            record = rt.registry.get(record.doc_id)
        return with_trace(
            json_response(
                {"doc_id": record.doc_id, "status": record.status}
            ),
            ctx,
        )

    async def documents(_req):
        return json_response(
            [r.to_dict() for r in rt.registry.list_documents()]
        )

    async def document_one(req):
        rec = rt.registry.get(req.match_info["doc_id"])
        if rec is None:
            return json_error(404, "document not found")
        return json_response(rec.to_dict())

    async def document_delete(req):
        doc_id = req.match_info["doc_id"]
        rec = rt.registry.get(doc_id)
        if rec is None:
            return json_error(404, "document not found")
        erase = req.query.get("erase") in ("1", "true")
        # device lane: tombstoning races with appends/searches otherwise
        n = await on_device(rt.delete_document, doc_id, erase)
        return json_response(
            {"doc_id": doc_id, "chunks_removed": n, "erased": erase}
        )

    # ---- QA -----------------------------------------------------------------

    async def _ask_preamble(req, ctx):
        """Shared /ask admission: parse → 422, empty index → 503, submit
        on the device lane → QueueFull 503, budget gone → 504.  Returns
        (pending, None) or (None, error-response) so both the blocking and
        streaming handlers admit identically.

        The request's end-to-end :class:`Deadline` is stamped HERE — the
        one admission point — and threaded through retrieval, dispatch and
        the batcher (docs/RESILIENCE.md); every later stage sheds instead
        of queueing past it.  ``ctx`` is the request's trace: retrieval
        and batcher submission run UNDER it (``obs.call_in``), so the
        whole submit→admit→prefill→decode→result-wait is one timeline."""
        try:
            q = Query(**await req.json())
        except Exception as e:
            return None, json_error(422, str(e), ctx)
        if rt.store.count == 0:
            # parity: llm-qa returns 503 when its index is unavailable
            # (main.py:113-114) — ours can only be *empty*, never missing
            return None, json_error(
                503, "index is empty; ingest documents first", ctx
            )
        budget = rt.cfg.resilience.request_deadline_s
        deadline = Deadline.after(budget) if budget > 0 else None
        t_lane = time.perf_counter()
        # This is the one point every ask passes, so it is where the
        # batcher learns how many are on their way: counted from here —
        # waiting for the lane, then inside ask_submit (routing, retrieval,
        # prompt assembly) — until ask_submit has put it in the batcher's
        # queue, answered it without the decoder, or raised.  A round that
        # has its first request gathers for the rest (serve._run_loop).
        expect = getattr(rt.batcher, "expect_arrival", None)
        arrived = expect() if expect is not None else (lambda: None)

        def submit_on_lane():
            # the device lane is ONE thread: until it is free the request
            # has not begun (a retrieval ahead of it may itself be waiting
            # on the device behind a decode chunk) — on the record, so the
            # spans of a timeline add up to the client's time to first token
            if ctx is not None:
                ctx.trace.record_span(
                    "ask_lane_wait", t_lane, time.perf_counter(),
                    parent_id=ctx.span_id,
                )
            try:
                return rt.qa.ask_submit(q.question, deadline=deadline)
            finally:
                arrived()

        try:
            pending = await on_device(obs.call_in, ctx, submit_on_lane)
        except QueueFull as e:
            return None, json_error(503, str(e), ctx)
        except DeadlineExceeded as e:
            # shed before any answer material existed (admission or
            # retrieval) — 504 distinguishes "out of time" from the
            # QueueFull 503 "out of capacity"
            DEFAULT_REGISTRY.counter("qa_deadline_shed").inc()
            return None, json_error(504, str(e), ctx)
        finally:
            # a handler cancelled while it waited for the lane never ran
            # the closure above; a second call does nothing
            arrived()
        return pending, None

    def _ask_outcome(status: int) -> None:
        """SLO event accounting (obs/slo.py): every /ask admission is a
        request; 5xx responses spend the availability budget.  Client
        errors (422) are the caller's problem, not ours — they count as
        requests (the objective is over served traffic) but never as
        failures."""
        DEFAULT_REGISTRY.counter("ask_requests").inc()
        if status >= 500:
            DEFAULT_REGISTRY.counter("ask_failures").inc()

    async def ask(req):
        # retrieval + submission on the device lane; decode wait on the gen
        # lane so N concurrent /ask share batcher slots (≈ solo latency)
        t0 = time.perf_counter()
        ctx = obs.new_trace("ask")
        obs.cost_open(ctx, "interactive")
        try:
            pending, err = await _ask_preamble(req, ctx)
            if err is not None:
                obs.finish(ctx, status="error")
                _ask_outcome(err.status)
                return err
            try:
                result = await on_gen(obs.call_in, ctx, pending.resolve)
            except DeadlineExceeded as e:
                # resolve() degrades whenever it has chunks to degrade to,
                # so reaching here means even the fallback was impossible
                DEFAULT_REGISTRY.counter("qa_deadline_shed").inc()
                obs.finish(ctx, status="error")
                _ask_outcome(504)
                return json_error(504, str(e), ctx)
            DEFAULT_REGISTRY.histogram("qa_e2e_ms").observe(
                (time.perf_counter() - t0) * 1000,
                trace_id=ctx.trace_id if ctx else None,
            )
            obs.finish(ctx)
            _ask_outcome(200)
            return with_trace(json_response(result), ctx)
        except Exception:
            obs.finish(ctx, status="error")
            _ask_outcome(500)
            raise

    async def ask_stream(req):
        """Server-sent-events variant of /ask/: token deltas as they
        decode, then one final event with the sources.  (The reference
        couldn't stream — generation lived in an external Ollama process
        behind a blocking LangChain call.)"""
        import threading as _threading

        t0 = time.perf_counter()
        ctx = obs.new_trace("ask_stream")
        obs.cost_open(ctx, "interactive")
        pending, err = await _ask_preamble(req, ctx)
        if err is not None:
            obs.finish(ctx, status="error")
            _ask_outcome(err.status)
            return err
        # the stream commits to a 200 at prepare(); decode failures
        # surface as SSE error events, so availability accounting for
        # the stream variant happens here at admission
        _ask_outcome(200)
        resp = web.StreamResponse(
            headers={
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-cache",
            }
        )
        with_trace(resp, ctx)
        await resp.prepare(req)
        loop = asyncio.get_running_loop()
        queue: asyncio.Queue = asyncio.Queue()
        gone = _threading.Event()  # client disconnected: stop pumping

        def pump():
            # no ctx activation here: iter_text records its spans on the
            # request's own trace via the batcher Handle (worker-side),
            # and a generator body would outlive any activation scope
            try:
                for delta in pending.iter_text():
                    if gone.is_set():
                        return  # free the gen_pool thread; the batcher
                        # slot retires on its own budget/EOS
                    loop.call_soon_threadsafe(queue.put_nowait, ("d", delta))
                loop.call_soon_threadsafe(queue.put_nowait, ("end", None))
            except BaseException as e:  # surfaced as an SSE error event
                loop.call_soon_threadsafe(queue.put_nowait, ("err", str(e)))

        fut = loop.run_in_executor(gen_pool, pump)
        try:
            while True:
                kind, payload = await queue.get()
                if kind == "d":
                    await resp.write(
                        b"data: " + json.dumps({"delta": payload}).encode()
                        + b"\n\n"
                    )
                elif kind == "err":
                    await resp.write(
                        b"event: error\ndata: "
                        + json.dumps({"detail": payload}).encode() + b"\n\n"
                    )
                    break
                else:
                    await resp.write(
                        b"event: done\ndata: "
                        + json.dumps({"sources": pending.sources}).encode()
                        + b"\n\n"
                    )
                    break
        finally:
            # release the pump on every exit (incl. client disconnect /
            # task cancel): it checks `gone` between deltas and returns,
            # freeing its gen_pool thread within one decode chunk — NOT
            # awaited here, because awaiting from a cancelled task would
            # just re-raise and the pump cleans itself up regardless
            gone.set()
            del fut
            DEFAULT_REGISTRY.histogram("qa_e2e_ms").observe(
                (time.perf_counter() - t0) * 1000,
                trace_id=ctx.trace_id if ctx else None,
            )
            obs.finish(ctx)
        await resp.write_eof()
        return resp

    async def patient_snippets(req):
        pid = req.query.get("patient_id")
        if not pid:
            return json_error(422, "patient_id is required")
        try:
            rows = await on_device(
                rt.qa.patient_snippets,
                pid,
                req.query.get("from_date"),
                req.query.get("to_date"),
                req.query.get("focus"),
            )
        except ValueError as e:  # malformed date bounds reject loudly
            return json_error(422, str(e))
        return json_response(rows)

    async def llm_summarize(req):
        try:
            body = SummarizeRequest(**await req.json())
        except Exception as e:
            return json_error(422, str(e))
        t0 = time.perf_counter()
        ctx = obs.new_trace("summarize")
        obs.cost_open(ctx, "batch")
        try:
            pending = await on_device(
                obs.call_in, ctx, rt.summarizer.submit_prompt,
                body.prompt, body.max_tokens,
            )
        except QueueFull as e:
            obs.finish(ctx, status="error")
            return json_error(503, str(e), ctx)
        try:
            summary = await on_gen(
                obs.call_in, ctx, rt.summarizer.resolve, pending
            )
        except Exception:
            obs.finish(ctx, status="error")
            raise
        if rt.batcher is not None:
            # the batcher path skips the engine's span("summarize"); record
            # the e2e latency here so /metrics keeps the serving histogram
            DEFAULT_REGISTRY.histogram("summarize_ms").observe(
                (time.perf_counter() - t0) * 1000,
                trace_id=ctx.trace_id if ctx else None,
            )
        obs.finish(ctx)
        return with_trace(json_response({"summary": summary}), ctx)

    # ---- synthesis ----------------------------------------------------------

    async def synthese_patient(req):
        try:
            body = PatientSummaryRequest(**await req.json())
        except Exception as e:
            return json_error(422, str(e))
        # retrieval/packing on the device lane; decode wait on the gen lane
        ctx = obs.new_trace("synthese_patient")
        obs.cost_open(ctx, "batch")
        try:
            finish = await on_device(
                obs.call_in,
                ctx,
                rt.synthesis.patient_summary_submit,
                body.patient_id,
                body.from_date,
                body.to_date,
                body.focus,
            )
        except SynthesisError as e:
            obs.finish(ctx, status="error")
            return json_error(e.status, e.detail, ctx)
        except QueueFull as e:
            obs.finish(ctx, status="error")
            return json_error(503, str(e), ctx)
        try:
            resp = await on_gen(obs.call_in, ctx, finish)
        except Exception:
            obs.finish(ctx, status="error")
            raise
        obs.finish(ctx)
        return with_trace(
            json_response(json.loads(resp.model_dump_json())), ctx
        )

    async def synthese_comparaison(req):
        try:
            body = PatientComparisonRequest(**await req.json())
        except Exception as e:
            return json_error(422, str(e))
        ctx = obs.new_trace("synthese_comparaison")
        obs.cost_open(ctx, "batch")
        try:
            finish = await on_device(
                obs.call_in,
                ctx,
                rt.synthesis.patient_comparison_submit,
                body.patient_ids,
                body.focus,
            )
        except SynthesisError as e:
            obs.finish(ctx, status="error")
            return json_error(e.status, e.detail, ctx)
        except QueueFull as e:
            obs.finish(ctx, status="error")
            return json_error(503, str(e), ctx)
        try:
            resp = await on_gen(obs.call_in, ctx, finish)
        except Exception:
            obs.finish(ctx, status="error")
            raise
        obs.finish(ctx)
        return with_trace(
            json_response(json.loads(resp.model_dump_json())), ctx
        )

    async def index_page(_req):
        """The chat/upload UI (replaces the reference's Streamlit app,
        ``clinical-ui/app.py`` — status pings, upload, QA chat — with a real
        pipeline completion signal instead of its 5 s fake progress bar)."""
        path = os.path.join(os.path.dirname(__file__), "ui.html")
        return web.FileResponse(path)

    app = web.Application(client_max_size=64 * 1024 * 1024)
    app.add_routes(
        [
            web.get("/", index_page),
            web.get("/health", health),
            web.get("/api/status", api_status),
            web.get("/metrics", metrics),
            web.get("/api/metrics", api_metrics),
            web.get("/api/telemetry", api_telemetry),
            web.get("/api/costs", api_costs),
            web.get("/api/costs/sheds", api_costs_sheds),
            web.get("/api/retrieval", api_retrieval),
            web.get("/api/traces", api_traces),
            web.get("/api/witness", api_witness),
            web.get("/api/ledger", api_ledger),
            web.get("/api/trace/{trace_id}", api_trace_one),
            web.get("/api/pool", api_pool),
            web.post("/api/pool/drain", api_pool_drain),
            web.post("/api/pool/resume", api_pool_resume),
            web.post("/api/pool/rolling_restart", api_pool_rolling_restart),
            web.post("/api/profiler/start", profiler_start),
            web.post("/api/profiler/stop", profiler_stop),
            web.post("/ingest/", ingest),
            web.get("/documents/", documents),
            web.get("/documents/{doc_id}", document_one),
            web.delete("/documents/{doc_id}", document_delete),
            web.post("/ask/", ask),
            web.post("/ask/stream", ask_stream),
            web.get("/api/search/patient-snippets", patient_snippets),
            web.post("/api/llm/summarize", llm_summarize),
            web.post("/api/synthese/patient", synthese_patient),
            web.post("/api/synthese/comparaison", synthese_comparaison),
        ]
    )
    app["runtime"] = rt
    app["device_pool"] = device_pool
    return app


def serve(cfg: Optional[Config] = None, port: Optional[int] = None) -> None:
    from aiohttp import web

    from docqa_tpu.runtime.compile_cache import configure_compile_cache

    configure_compile_cache()
    rt = DocQARuntime(cfg).start()
    app = make_app(rt)
    try:
        web.run_app(
            app,
            host=rt.cfg.service.host,
            port=port or rt.cfg.service.ingest_port,
        )
    finally:
        rt.stop()


if __name__ == "__main__":
    serve()
