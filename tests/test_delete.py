"""Document deletion: tombstones hide rows from every search surface
immediately, survive snapshot/restore, and compaction erases for real.
(The reference had no deletion at all — its FAISS index only ever grew.)"""

import numpy as np
import pytest

from docqa_tpu.config import EncoderConfig, StoreConfig, load_config
from docqa_tpu.index.store import VectorStore


def _mk_store(n=8, dim=16):
    store = VectorStore(StoreConfig(dim=dim, shard_capacity=64))
    rng = np.random.default_rng(0)
    vecs = rng.standard_normal((n, dim)).astype(np.float32)
    store.add(
        vecs,
        [
            {"doc_id": f"doc{i // 2}", "source": f"s{i}", "patient_id": "p1"}
            for i in range(n)
        ],
    )
    return store, vecs


class TestStoreTombstones:
    def test_deleted_rows_vanish_from_search(self):
        store, vecs = _mk_store()
        before = store.search(vecs[:1], k=8)[0]
        assert any(r.metadata["doc_id"] == "doc0" for r in before)
        n = store.delete_docs(["doc0"])
        assert n == 2
        after = store.search(vecs[:1], k=8)[0]
        assert all(r.metadata["doc_id"] != "doc0" for r in after)
        # filtered search excludes them too
        rows = store.search(vecs[:1], k=8, filters={"patient_id": "p1"})[0]
        assert all(r.metadata["doc_id"] != "doc0" for r in rows)
        # and metadata listings
        assert all(
            md["doc_id"] != "doc0"
            for md in store.metadata_select(patient_id="p1")
        )

    def test_delete_unknown_doc_is_noop(self):
        store, _ = _mk_store()
        assert store.delete_docs(["nope"]) == 0

    def test_double_delete_counts_once(self):
        store, _ = _mk_store()
        assert store.delete_docs(["doc1"]) == 2
        assert store.delete_docs(["doc1"]) == 0

    def test_fused_retriever_excludes_tombstones(self):
        from docqa_tpu.engines.encoder import EncoderEngine
        from docqa_tpu.engines.retrieve import FusedRetriever

        cfg = EncoderConfig(
            vocab_size=512, hidden_dim=32, num_layers=1, num_heads=4,
            mlp_dim=64, max_seq_len=32, embed_dim=32, dtype="float32",
        )
        enc = EncoderEngine(cfg)
        store = VectorStore(StoreConfig(dim=32, shard_capacity=64))
        texts = ["aspirin note", "metformin note", "warfarin note"]
        store.add(
            enc.encode_texts(texts),
            [{"doc_id": f"d{i}", "source": t} for i, t in enumerate(texts)],
        )
        retr = FusedRetriever(enc, store)
        store.delete_docs(["d1"])
        rows = retr.search_texts(["metformin note"], k=3)[0]
        assert all(r.metadata["doc_id"] != "d1" for r in rows)

    def test_tombstoned_chunks_never_reach_the_prompt(self):
        """Under-fill: with fewer live rows than k, top-k pads with masked
        ties whose indices point at tombstoned rows — erased clinical text
        must not be packed into the prompt ``ask_submit`` hands the
        batcher, nor cited as a source."""
        from docqa_tpu.engines.encoder import EncoderEngine
        from docqa_tpu.engines.retrieve import FusedRetriever
        from docqa_tpu.service.qa import QAService

        cfg = EncoderConfig(
            vocab_size=512, hidden_dim=32, num_layers=1, num_heads=4,
            mlp_dim=64, max_seq_len=32, embed_dim=32, dtype="float32",
        )
        enc = EncoderEngine(cfg)
        store = VectorStore(StoreConfig(dim=32, shard_capacity=64))
        texts = [f"secret{i} note about drug{i}" for i in range(4)]
        store.add(
            enc.encode_texts(texts),
            [
                {"doc_id": f"d{i}", "source": f"chunk {i}", "text_content": t}
                for i, t in enumerate(texts)
            ],
        )
        store.delete_docs(["d1", "d2", "d3"])  # one live row, k=3

        prompts = []

        class _Batcher:
            class engine:
                tokenizer = None

            def submit_text(self, prompt, **kw):
                prompts.append(prompt)

        for retriever in (None, FusedRetriever(enc, store)):
            qa = QAService(
                enc, store, None, None, k=3, batcher=_Batcher(),
                retriever=retriever,
            )
            pending = qa.ask_submit("what about drug2?")
            assert pending.sources == ["chunk 0"]
        assert len(prompts) == 2
        for prompt in prompts:
            assert "secret0" in prompt  # the live row's text IS there
            assert not any(f"secret{i}" in prompt for i in (1, 2, 3))

    def test_compaction_erases_and_renumbers(self):
        store, vecs = _mk_store()
        store.delete_docs(["doc0"])
        count_before = store.count
        removed = store.compact_deleted()
        assert removed == 2
        assert store.count == count_before - 2
        assert all(md["doc_id"] != "doc0" for md in store.metadata_rows())
        # the compacted store still searches correctly
        hits = store.search(vecs[2:3], k=1)[0]
        assert hits[0].metadata["source"] == "s2"
        # vectors are really gone from the host copy
        host, meta = store.vectors_snapshot()
        assert len(host) == store.count == len(meta)

    def test_tombstones_survive_snapshot_restore(self, tmp_path):
        store, vecs = _mk_store()
        store.delete_docs(["doc2"])
        store.snapshot(str(tmp_path))
        again = VectorStore.restore(
            str(tmp_path), StoreConfig(dim=16, shard_capacity=64)
        )
        rows = again.search(vecs[4:5], k=8)[0]
        assert all(r.metadata["doc_id"] != "doc2" for r in rows)


class TestTieredTombstones:
    def test_tiered_filters_and_reset(self):
        from docqa_tpu.index.tiered import TieredIndex

        store, vecs = _mk_store(n=32)
        tiered = TieredIndex(store, min_rows=8, n_clusters=4, nprobe=4)
        tiered.rebuild()
        store.delete_docs(["doc0"])
        rows = tiered.search(vecs[:1], k=8)[0]
        assert all(r.metadata["doc_id"] != "doc0" for r in rows)
        store.compact_deleted()
        tiered.reset()
        assert tiered.covered == 0  # tier dropped; exact serves meanwhile
        rows = tiered.search(vecs[4:5], k=4)[0]
        assert rows and all(r.metadata["doc_id"] != "doc0" for r in rows)


class TestErasureEdges:
    def test_erase_after_tombstone_still_compacts(self):
        store, _ = _mk_store()
        assert store.delete_docs(["doc0"]) == 2
        # second call tombstones nothing, but erasure must still remove
        # the earlier tombstones' bytes
        assert store.delete_docs(["doc0"]) == 0
        assert store.compact_deleted() == 2
        assert store.count == 6

    def test_erase_prunes_predecessor_snapshot(self, tmp_path):
        store, _ = _mk_store()
        store.snapshot(str(tmp_path))  # v1 contains doc0
        store.delete_docs(["doc0"])
        store.compact_deleted()
        store.snapshot(str(tmp_path), keep_previous=False)
        import os

        dirs = [d for d in os.listdir(str(tmp_path)) if d.startswith("index_v")]
        assert len(dirs) == 1  # the pre-erasure snapshot is gone from disk
        again = VectorStore.restore(
            str(tmp_path), StoreConfig(dim=16, shard_capacity=64)
        )
        assert all(md["doc_id"] != "doc0" for md in again.metadata_rows())

    def test_suppressed_inflight_doc_never_indexes(self):
        """DELETE racing the async pipeline: the queued message must be
        dropped, not indexed (and not marked INDEXED)."""
        from docqa_tpu.config import load_config
        from docqa_tpu.service.app import DocQARuntime

        cfg = load_config(
            env={},
            overrides={
                "ner.train_steps": 0,
                "flags.use_fake_encoder": True,
                "flags.use_fake_llm": True,
                "decoder.hidden_dim": 32,
                "decoder.num_layers": 1,
                "decoder.num_heads": 4,
                "decoder.num_kv_heads": 4,
                "decoder.head_dim": 8,
                "decoder.mlp_dim": 64,
                "decoder.vocab_size": 256,
                "store.shard_capacity": 128,
                "data.bootstrap_dir": None,
            },
        )
        rt = DocQARuntime(cfg)  # NOT started: messages stay queued
        try:
            rec = rt.pipeline.ingest_document(
                "a.txt", b"Metformin 500mg twice daily.", patient_id="p7"
            )
            count_before = rt.store.count
            assert rt.delete_document(rec.doc_id) == 0  # nothing indexed yet
            rt.pipeline.start()  # now the queued message flows
            import time as _t

            deadline = _t.monotonic() + 30
            while (
                rt.broker.depth(cfg.broker.raw_queue)
                + rt.broker.depth(cfg.broker.clean_queue)
                and _t.monotonic() < deadline
            ):
                _t.sleep(0.05)
            _t.sleep(0.2)
            assert rt.store.count == count_before  # never indexed
            assert rt.registry.get(rec.doc_id).status == "DELETED"
            assert rt.qa.patient_snippets("p7") == []
        finally:
            rt.stop()


class TestDeletionRaces:
    """Regression tests for the advisor's round-2 findings: deletions that
    race the async pipeline or a process restart must stick."""

    def _runtime(self, tmp_path=None):
        from docqa_tpu.service.app import DocQARuntime

        overrides = {
            "ner.train_steps": 0,
            "flags.use_fake_encoder": True,
            "flags.use_fake_llm": True,
            "decoder.hidden_dim": 32,
            "decoder.num_layers": 1,
            "decoder.num_heads": 4,
            "decoder.num_kv_heads": 4,
            "decoder.head_dim": 8,
            "decoder.mlp_dim": 64,
            "decoder.vocab_size": 256,
            "store.shard_capacity": 128,
            "store.compact_threshold": 0.0,  # keep tombstones visible
            "data.bootstrap_dir": None,
        }
        if tmp_path is not None:
            overrides["data.work_dir"] = str(tmp_path)
        cfg = load_config(env={}, overrides=overrides)
        return DocQARuntime(cfg)

    def test_delete_during_encode_cannot_resurrect(self):
        """A DELETE landing while the index worker is inside encode_texts
        (a seconds-long window in production) must still drop the doc's
        chunks: the worker re-checks suppression under the shared lock
        right before store.add."""
        rt = self._runtime()
        try:
            rec = rt.pipeline.ingest_document(
                "a.txt", b"Lisinopril 10mg for hypertension.",
                patient_id="p3",
            )
            count_before = rt.store.count
            orig = rt.pipeline.encoder
            state = {"fired": False}

            class RacingEncoder:
                def encode_texts(self, texts):
                    embs = orig.encode_texts(texts)
                    if not state["fired"]:
                        state["fired"] = True
                        # the DELETE arrives after encode, before store.add
                        rt.delete_document(rec.doc_id)
                    return embs

            rt.pipeline.encoder = RacingEncoder()
            rt.pipeline.start()
            import time as _t

            # queue depth drops while the message is still in flight inside
            # the workers, so wait on the observable outcome instead
            deadline = _t.monotonic() + 60
            while not state["fired"] and _t.monotonic() < deadline:
                _t.sleep(0.05)
            _t.sleep(0.5)  # let the index worker finish its batch
            assert state["fired"]
            assert rt.store.count == count_before  # chunks dropped
            assert rt.registry.get(rec.doc_id).status == "DELETED"
            assert rt.qa.patient_snippets("p3") == []
        finally:
            rt.stop()

    def test_cross_process_delete_cannot_resurrect(self):
        """Multi-process registry mode (Postgres): a DELETE handled by a
        DIFFERENT service process writes DELETED straight to the shared
        registry and can never populate this process's in-memory
        suppression set.  The per-doc INDEXED write must therefore consult
        the registry record too — without that check the in-flight batch
        here would flip DELETED back to INDEXED (ADVICE r3, medium)."""
        from docqa_tpu.service import registry as reg

        rt = self._runtime()
        try:
            rec = rt.pipeline.ingest_document(
                "x.txt", b"Atorvastatin 40mg nightly.", patient_id="p9"
            )
            body = {
                "doc_id": rec.doc_id,
                "original_text_masked": "Atorvastatin 40mg nightly.",
                "metadata": {"patient_id": "p9", "filename": "x.txt"},
                "processed_at": 0.0,
            }
            # the foreign process's delete: registry-only, no suppression
            rt.registry.set_status(rec.doc_id, reg.DELETED)
            assert rec.doc_id not in rt.pipeline._suppressed_doc_ids
            rt.pipeline._index_handler([body])
            assert rt.registry.get(rec.doc_id).status == reg.DELETED
        finally:
            rt.stop()

    def test_erasure_survives_restart_replay(self):
        """The in-memory suppressed set dies with the process; the registry
        DELETED row is the durable record.  A message replayed after a
        restart must be dropped on its account."""
        from docqa_tpu.service import registry as reg

        rt = self._runtime()
        try:
            rec = rt.pipeline.ingest_document(
                "b.txt", b"Warfarin 5mg, INR monitored.", patient_id="p4"
            )
            # delete while the message is still queued, then simulate the
            # restart by clearing the in-memory suppression (a new process
            # starts with an empty set)
            rt.delete_document(rec.doc_id, erase=True)
            rt.pipeline._suppressed_doc_ids.clear()
            body = {
                "doc_id": rec.doc_id,
                "original_text_masked": "Warfarin 5mg, INR monitored.",
                "metadata": {"patient_id": "p4", "filename": "b.txt"},
                "processed_at": 0.0,
            }
            count_before = rt.store.count
            rt.pipeline._index_handler([body])  # the journal replay
            assert rt.store.count == count_before
            assert rt.registry.get(rec.doc_id).status == reg.DELETED
        finally:
            rt.stop()

    def test_deid_stage_drops_deleted_doc(self):
        """A doc deleted while still on the RAW queue must be dropped at
        the deid stage: a DEIDENTIFIED overwrite of DELETED would advertise
        an erased doc as alive, and the clean-queue publish would re-arm
        its resurrection across a restart."""
        from docqa_tpu.service import registry as reg

        rt = self._runtime()
        try:
            rec = rt.pipeline.ingest_document(
                "d.txt", b"Insulin glargine 20 units at bedtime.",
                patient_id="p6",
            )
            rt.delete_document(rec.doc_id, erase=True)
            # simulate the restart: in-memory suppression is gone, only the
            # registry DELETED row survives
            rt.pipeline._suppressed_doc_ids.clear()
            body = {
                "doc_id": rec.doc_id,
                "text": "Insulin glargine 20 units at bedtime.",
                "metadata": {"patient_id": "p6", "filename": "d.txt"},
            }
            rt.pipeline._deid_handler([body])  # the raw-queue replay
            assert rt.registry.get(rec.doc_id).status == reg.DELETED
            assert rt.broker.depth(rt.cfg.broker.clean_queue) == 0
        finally:
            rt.stop()

    def test_replay_does_not_flip_deleted_to_indexed(self):
        """A tombstoned-but-uncompacted doc is still in metadata_rows(), so
        its replayed message lands in the already-indexed path — which must
        NOT overwrite the DELETED status with INDEXED."""
        from docqa_tpu.service import registry as reg
        from docqa_tpu.service.pipeline import DocumentPipeline

        rt = self._runtime()
        rt.pipeline.start()
        try:
            rec = rt.pipeline.ingest_document(
                "c.txt", b"Atorvastatin 20mg nightly.", patient_id="p5"
            )
            assert rt.pipeline.wait_indexed(rec.doc_id, timeout=60)
            rt.delete_document(rec.doc_id)  # tombstone, no compaction
            assert rt.store.deleted_count >= 1
            # a fresh pipeline (as after restart) seeds _indexed_doc_ids
            # from the store, which still physically holds the rows
            fresh = DocumentPipeline(
                rt.cfg, rt.broker, rt.registry, rt.pipeline.deid,
                rt.pipeline.encoder, rt.store,
            )
            assert rec.doc_id in fresh._indexed_doc_ids
            body = {
                "doc_id": rec.doc_id,
                "original_text_masked": "Atorvastatin 20mg nightly.",
                "metadata": {"patient_id": "p5", "filename": "c.txt"},
                "processed_at": 0.0,
            }
            fresh._index_handler([body])
            assert rt.registry.get(rec.doc_id).status == reg.DELETED
        finally:
            rt.stop()


class TestTieredOverfetch:
    def test_k_live_results_despite_tombstones(self):
        """Between rebuilds the IVF tier physically holds tombstoned rows
        and filters them host-side after top-k; the fetch must over-fetch
        by the deleted fraction so k live results still come back."""
        from docqa_tpu.index.tiered import TieredIndex

        dim, n = 16, 32
        q = np.zeros(dim, np.float32)
        q[0] = 1.0
        u = np.zeros(dim, np.float32)
        u[1] = 1.0
        # deterministic ranking: row i scores cos(theta_i), decreasing in i
        thetas = np.linspace(0.05, 1.2, n)
        vecs = (
            np.cos(thetas)[:, None] * q[None] + np.sin(thetas)[:, None] * u[None]
        ).astype(np.float32)
        store = VectorStore(StoreConfig(dim=dim, shard_capacity=64))
        store.add(vecs, [{"doc_id": f"d{i}", "source": f"s{i}"} for i in range(n)])
        tiered = TieredIndex(store, min_rows=8, n_clusters=2, nprobe=2)
        assert tiered.rebuild()
        # tombstone every even-ranked row: half the top-k raw candidates
        store.delete_docs([f"d{i}" for i in range(0, n, 2)])
        rows = tiered.search(q[None], k=8)[0]
        assert len(rows) == 8  # not fewer, despite 50% tombstones
        assert all(not r.metadata.get("deleted") for r in rows)
        assert all(int(r.metadata["doc_id"][1:]) % 2 == 1 for r in rows)

    def test_correlated_deletion_falls_back_to_exact(self):
        """Deleting one document tombstones mutually-similar chunks that
        monopolize the top of the ranking for related queries — no
        fraction-based headroom covers that, so an under-filled query must
        fall back to exact tombstone-masked search."""
        from docqa_tpu.index.tiered import TieredIndex

        dim, n = 16, 64
        q = np.zeros(dim, np.float32)
        q[0] = 1.0
        u = np.zeros(dim, np.float32)
        u[1] = 1.0
        thetas = np.concatenate(
            [np.linspace(0.01, 0.1, 16), np.linspace(0.8, 1.4, n - 16)]
        )
        vecs = (
            np.cos(thetas)[:, None] * q[None] + np.sin(thetas)[:, None] * u[None]
        ).astype(np.float32)
        store = VectorStore(StoreConfig(dim=dim, shard_capacity=128))
        # the first 16 rows (the entire top of the ranking) are ONE doc
        metas = [
            {"doc_id": "hot" if i < 16 else f"d{i}", "source": f"s{i}"}
            for i in range(n)
        ]
        store.add(vecs, metas)
        tiered = TieredIndex(store, min_rows=8, n_clusters=2, nprobe=2)
        assert tiered.rebuild()
        store.delete_docs(["hot"])  # 25% deleted, all of them ranked top
        rows = tiered.search(q[None], k=8)[0]
        assert len(rows) == 8  # exact fallback fills the quota
        assert all(r.metadata["doc_id"] != "hot" for r in rows)


class TestServiceDelete:
    def test_runtime_delete_document(self, tmp_path):
        from docqa_tpu.service.app import DocQARuntime

        cfg = load_config(
            env={},
            overrides={
                "ner.train_steps": 0,
                "flags.use_fake_encoder": True,
                "flags.use_fake_llm": True,
                "decoder.hidden_dim": 32,
                "decoder.num_layers": 1,
                "decoder.num_heads": 4,
                "decoder.num_kv_heads": 4,
                "decoder.head_dim": 8,
                "decoder.mlp_dim": 64,
                "decoder.vocab_size": 256,
                "store.shard_capacity": 128,
                "data.work_dir": str(tmp_path),
                "data.bootstrap_dir": None,
                "data.snapshot_every": 1,
            },
        )
        rt = DocQARuntime(cfg).start()
        try:
            rec = rt.pipeline.ingest_document(
                "a.txt", b"Aspirin 100mg daily for the heart.",
                patient_id="p9",
            )
            assert rt.pipeline.wait_indexed(rec.doc_id, timeout=60)
            assert rt.qa.patient_snippets("p9")
            n = rt.delete_document(rec.doc_id, erase=True)
            assert n >= 1
            assert rt.qa.patient_snippets("p9") == []
            assert rt.registry.get(rec.doc_id).status == "DELETED"
        finally:
            rt.stop()

    def test_auto_compaction_at_threshold(self, tmp_path):
        """Plain (non-erase) deletions compact automatically once
        tombstones reach compact_threshold of the corpus."""
        from docqa_tpu.service.app import DocQARuntime

        cfg = load_config(
            env={},
            overrides={
                "ner.train_steps": 0,
                "flags.use_fake_encoder": True,
                "flags.use_fake_llm": True,
                "decoder.hidden_dim": 32,
                "decoder.num_layers": 1,
                "decoder.num_heads": 4,
                "decoder.num_kv_heads": 4,
                "decoder.head_dim": 8,
                "decoder.mlp_dim": 64,
                "decoder.vocab_size": 256,
                "store.shard_capacity": 128,
                "store.compact_threshold": 0.4,
                "data.bootstrap_dir": None,
            },
        )
        rt = DocQARuntime(cfg).start()
        try:
            recs = [
                rt.pipeline.ingest_document(
                    f"{i}.txt", f"Note {i} stable vitals.".encode(),
                    patient_id=f"q{i}",
                )
                for i in range(4)
            ]
            for r in recs:
                assert rt.pipeline.wait_indexed(r.doc_id, timeout=60)
            rt.delete_document(recs[0].doc_id)  # 1/4 < 0.4: tombstone only
            assert rt.store.deleted_count == 1
            rt.delete_document(recs[1].doc_id)  # 2/4 >= 0.4: auto-compacts
            assert rt.store.deleted_count == 0
            assert rt.store.count == 2
        finally:
            rt.stop()

        # deletion survives restart (the snapshot carried the compaction)
        rt2 = DocQARuntime(cfg).start()
        try:
            assert rt2.qa.patient_snippets("p9") == []
        finally:
            rt2.stop()
