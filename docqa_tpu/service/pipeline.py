"""The async document pipeline: ingest → de-identify → chunk+embed+index.

Re-creates the reference's three-process queue pipeline (SURVEY §3.1) inside
one framework, with the device plane batched:

* ingest (was ``doc-ingestor/main.py:19-65``): registry row PENDING → extract
  → publish to the raw queue → PROCESSED / ERROR_EXTRACTION / ERROR_QUEUE;
* deid worker (was ``deid-service/anonymizer.py:50-87``): batch-consumes the
  raw queue, jit NER + pattern recognizers over the batch, publishes the
  reference's message schema ``{doc_id, original_text_masked, metadata,
  processed_at}`` to the clean queue;
* index worker (was ``semantic-indexer/indexer.py:112-126``): batch-consumes,
  chunks, encodes ALL chunks of the batch in one device call (the reference
  ran one batch-1 encode per chunk) and appends to the HBM store — which is
  immediately searchable, no file handoff, no restart.

Completion is *observable*: the registry reaches INDEXED with a chunk count
(the reference UI guessed with a 5 s sleep, ``clinical-ui/app.py:55-58``).
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from docqa_tpu import obs
from docqa_tpu.config import Config
from docqa_tpu.resilience import faults
from docqa_tpu.resilience.policy import RetryPolicy
from docqa_tpu.service import registry as reg
from docqa_tpu.service.broker import Consumer, MemoryBroker
from docqa_tpu.service.extract import extract_text_ex
from docqa_tpu.service.registry import DocumentRegistry
from docqa_tpu.runtime.metrics import DEFAULT_REGISTRY, get_logger, span
from docqa_tpu.text.chunker import chunk_text

log = get_logger("docqa.pipeline")


class DocumentPipeline:
    """Owns the two queue consumers and the ingest entrypoint."""

    def __init__(
        self,
        cfg: Config,
        broker: MemoryBroker,
        registry: DocumentRegistry,
        deid_engine,  # DeidEngine
        encoder_engine,  # EncoderEngine
        store,  # VectorStore
        http_extractor=None,
        on_indexed=None,  # Callable[[int], None]: docs indexed per batch
        breakers=None,  # resilience.BreakerBoard: broker/deid/index circuits
    ) -> None:
        self.cfg = cfg
        self.broker = broker
        self.registry = registry
        self.deid = deid_engine
        self.encoder = encoder_engine
        self.store = store
        self.http_extractor = http_extractor
        self.on_indexed = on_indexed
        self.breakers = breakers
        res = cfg.resilience
        # in-place publish retries: a transient broker hiccup must not
        # turn into ERROR_QUEUE (ingest) or a redelivery burn (deid) — the
        # pre-resilience behavior had exactly one failure path, the DLQ
        self._retry = RetryPolicy(
            max_attempts=res.retry_attempts,
            base_delay_s=res.retry_base_delay_s,
            max_delay_s=res.retry_max_delay_s,
        )
        # extraction: only IO-class failures retry — a corrupt upload
        # fails identically every attempt, and re-parsing it three times
        # just delays its terminal ERROR_EXTRACTION
        import dataclasses as _dc

        self._io_retry = _dc.replace(
            self._retry, retry_on=(OSError, faults.InjectedFault)
        )
        # consumer handlers: retry transient classes only (IO, device /
        # broker RuntimeErrors — InjectedFault included) so a poison
        # message's deterministic KeyError/TypeError goes straight to the
        # nack path instead of re-running a full NER batch three times
        self._consumer_retry = _dc.replace(
            self._retry, retry_on=(OSError, RuntimeError)
        )
        self._broker_breaker = (
            breakers.get("broker") if breakers is not None else None
        )
        # signaled on every terminal status write (INDEXED / ERROR_*) so
        # wait_indexed() blocks on a Condition instead of polling
        self._done_cv = threading.Condition()
        self._started = False
        self._stopped = False
        # Replay idempotence: a crash between store snapshot and queue ack
        # redelivers an already-indexed message on restart (at-least-once);
        # seeding from the restored store and checking before store.add
        # keeps redelivered docs from duplicating their chunks.  doc_ids are
        # per-upload uuids, so a same-id body always IS the same document.
        self._indexed_doc_ids = {
            md.get("doc_id") for md in store.metadata_rows()
        }
        # docs deleted while still in flight: the index worker must drop
        # their messages instead of indexing a document the user already
        # erased (and must NOT mark them INDEXED).  The lock closes the
        # batch-start-to-store.add window: encode_texts can take seconds,
        # and a DELETE landing inside it would tombstone nothing (rows not
        # yet added) while the worker then adds the chunks anyway.  Both
        # suppress_doc and the worker's add/status critical sections take
        # it, so either the suppression lands before the add (chunks are
        # dropped) or the add completes first (delete_docs tombstones them).
        self._suppressed_doc_ids: set = set()
        self._suppress_lock = threading.Lock()
        def _dead(body, headers, status):
            self.registry.set_status_unless_deleted(body["doc_id"], status)
            # the document's timeline ends here, flagged — dead-lettered
            # docs are exactly what the flight recorder must always keep
            obs.finish_id(
                (headers or {}).get(obs.TRACE_HEADER),
                flag="dead_lettered",
            )
            self._notify_done()

        # per-stage breakers: while a stage's circuit is open its consumer
        # pauses pulling (messages keep their redelivery budget); the
        # retry policy absorbs transient failures before any nack.
        # pass_headers threads each message's trace id (docqa_tpu/obs)
        # through both hops without touching payloads.
        self._consumers = [
            Consumer(
                broker,
                cfg.broker.raw_queue,
                self._deid_handler,
                batch=cfg.broker.prefetch,
                name="deid-worker",
                on_dead=lambda body, headers: _dead(
                    body, headers, reg.ERROR_DEID
                ),
                retry=self._consumer_retry,
                breaker=breakers.get("deid") if breakers else None,
                pass_headers=True,
            ),
            Consumer(
                broker,
                cfg.broker.clean_queue,
                self._index_handler,
                batch=cfg.broker.prefetch,
                name="index-worker",
                on_dead=lambda body, headers: _dead(
                    body, headers, reg.ERROR_INDEXING
                ),
                retry=self._consumer_retry,
                breaker=breakers.get("index") if breakers else None,
                pass_headers=True,
            ),
        ]

    def suppress_doc(self, doc_id: str) -> None:
        """Never index this document, even if its pipeline message is still
        queued or replays later — the deletion path calls this so a DELETE
        racing the async pipeline cannot resurrect the document.  Blocks
        while an index-worker batch is inside its store-add critical
        section: on return, the doc's chunks are either dropped or already
        in the store where the caller's ``delete_docs`` will find them.
        (Registry status writes run OUTSIDE the lock; the DELETED status
        the caller writes afterwards wins either way because the worker's
        writes are conditional at the database.)"""
        with self._suppress_lock:
            self._suppressed_doc_ids.add(doc_id)

    # ---- lifecycle -----------------------------------------------------------

    def start(self) -> None:
        self._started = True
        self._stopped = False
        for c in self._consumers:
            c.start()

    def stop(self) -> None:
        """Idempotent: a double-stop (runtime.stop() + a supervisor's
        shutdown hook) must not try to join consumer threads that already
        exited — Thread.join on a dead thread is safe, but stop() also
        must not block a second caller behind the first's join timeout."""
        if self._stopped:
            return
        self._stopped = True
        for c in self._consumers:
            c.stop()
        self._notify_done()  # release any wait_indexed() blocked at stop

    def _notify_done(self) -> None:
        with self._done_cv:
            self._done_cv.notify_all()

    # ---- ingest (sync stage) -------------------------------------------------

    def ingest_document(
        self,
        filename: str,
        data: bytes,
        doc_type: Optional[str] = None,
        patient_id: Optional[str] = None,
        doc_date: Optional[str] = None,
    ):
        """Reference contract (``doc-ingestor/main.py:19-65``): create the
        metadata row first, then extract, then queue; every failure mode gets
        a distinct terminal status.

        The document's trace starts (or continues — the HTTP layer may
        have opened it) HERE and spans the whole extract→deid→index
        lifecycle: trace headers ride the broker messages, and the trace
        completes at the first terminal status — including a dead-letter
        — so every ingested document leaves exactly one timeline."""
        with obs.ensure("ingest") as ctx:
            return self._ingest_traced(
                ctx, filename, data, doc_type, patient_id, doc_date
            )

    def _ingest_traced(
        self, ctx, filename, data, doc_type, patient_id, doc_date
    ):
        record = self.registry.create(filename, doc_type, patient_id, doc_date)
        if ctx is not None:
            ctx.trace.root.attrs.setdefault("doc_id", record.doc_id)

        def _extract():
            faults.perturb("extract")  # resilience_site: extract
            return extract_text_ex(data, filename, self.http_extractor)

        with span("extract", DEFAULT_REGISTRY):
            try:
                # retried in place: a flaky HTTP extractor (or an injected
                # fault) gets retry_attempts before the terminal status
                text, why = self._io_retry.call(_extract, name="extract")
            except Exception:
                log.exception("extraction failed for %s", filename)
                text, why = None, "extractor_error"
        if text is None or not text.strip():
            # precise, actionable failure (VERDICT r4 item 7): the row says
            # WHY ("pdf_scanned_image_only", "legacy_ole2_document", ...)
            # so the operator knows to enable the extractor service or
            # convert the file — not just that extraction failed
            self.registry.set_status(
                record.doc_id,
                reg.ERROR_EXTRACTION,
                detail=why or "empty_text",
            )
            self._notify_done()
            obs.flag("error_extraction")
            obs.finish(ctx, status="error")
            return self.registry.get(record.doc_id)
        try:
            self._publish(
                self.cfg.broker.raw_queue,
                {
                    "doc_id": record.doc_id,
                    "text": text,
                    "metadata": {
                        "filename": filename,
                        "type": doc_type,
                        "patient_id": patient_id,
                        "doc_date": doc_date,
                    },
                },
                headers=obs.headers_of(ctx),
            )
        except Exception:
            log.exception("queue publish failed")
            self.registry.set_status(record.doc_id, reg.ERROR_QUEUE)
            self._notify_done()
            obs.flag("error_queue")
            obs.finish(ctx, status="error")
            return self.registry.get(record.doc_id)
        self.registry.set_status(record.doc_id, reg.PROCESSED)
        # trace stays OPEN: the async deid/index hops finish it at the
        # document's terminal status (or dead-letter)
        return self.registry.get(record.doc_id)

    def _publish(
        self,
        queue: str,
        body: Dict[str, Any],
        headers: Optional[Dict[str, Any]] = None,
    ) -> None:
        """Broker publish under the retry policy — a transient broker
        failure is retried with backoff instead of immediately becoming a
        terminal ERROR_QUEUE/ERROR_DEID.

        The broker breaker OBSERVES (one outcome per publish, feeding
        /api/status) but does not gate: a publish has no queue to wait
        in — ingest is synchronous HTTP — so failing fast during the
        reset window would turn a recovered broker into 30 s of terminal
        document errors.  Hold-and-retry is strictly better here."""
        br = self._broker_breaker
        try:
            self._retry.call(
                lambda: self.broker.publish(queue, body, headers=headers),
                name="broker_publish",
            )
        except Exception:
            if br is not None:
                br.record_failure()
            raise
        if br is not None:
            br.record_success()

    def ingest_text(self, text: str, **kw):
        """Convenience for pre-extracted text (tests, CSV bootstrap)."""
        return self.ingest_document(kw.pop("filename", "inline.txt"), text.encode(), **kw)

    # ---- workers -------------------------------------------------------------

    def _deid_handler(
        self,
        bodies: List[Dict[str, Any]],
        headers: Optional[List[Dict[str, Any]]] = None,
    ) -> None:
        # Pure phase first — a raise here is side-effect-free, so the
        # Consumer's one-by-one poison isolation (and its in-place retry
        # policy) may safely replay the batch.
        faults.perturb("deid")  # resilience_site: deid (slow-stage/outage)
        headers = headers if headers is not None else [{} for _ in bodies]
        texts = [b["text"] for b in bodies]
        t_batch0 = time.perf_counter()
        with span("deid_batch", DEFAULT_REGISTRY):
            masked = self.deid.deidentify_batch(texts)
        t_batch1 = time.perf_counter()
        # Side-effect phase: per-message failures are terminal here, never
        # re-raised (a raise would make the retry republish the prefix).
        for body, clean, hdrs in zip(bodies, masked, headers):
            # re-link the document's trace (or adopt a stub after a
            # cross-restart replay) and charge it this batch's interval
            ctx = obs.from_headers(hdrs, name="doc")
            if ctx is not None:
                ctx.trace.record_span(
                    "deid_batch", t_batch0, t_batch1,
                    parent_id=ctx.span_id, batch=len(bodies),
                    doc_id=body.get("doc_id"),
                )
            try:
                # deleted docs stop HERE, not just at the index worker: a
                # DEIDENTIFIED overwrite of DELETED would advertise an
                # erased doc as alive.  The suppress lock covers ONLY the
                # set membership read — registry I/O (SQLite/Postgres
                # writes) must not run inside it, or every DELETE blocks
                # behind this worker's database round-trips
                # (docqa-lint: lock-discipline).  Correctness without the
                # wider section: the status write is conditional AT the
                # database (UPDATE ... WHERE status != DELETED), so a
                # DELETE committing first makes this write refuse, and a
                # DELETE committing after overwrites DEIDENTIFIED with
                # DELETED — either order ends DELETED, and the index
                # worker re-checks both the registry and the suppression
                # set before touching the store.
                with self._suppress_lock:
                    suppressed = body["doc_id"] in self._suppressed_doc_ids
                if not suppressed:
                    # status BEFORE publish: once the message is on the
                    # clean queue the index worker may race us to INDEXED,
                    # which must not be overwritten by a late DEIDENTIFIED
                    if not self.registry.set_status_unless_deleted(
                        body["doc_id"], reg.DEIDENTIFIED
                    ):
                        # rowcount 0 is ambiguous: DELETED row, or no
                        # row at all (registry restored from an older
                        # snapshot / out-of-band enqueue).  Only a
                        # DELETED row suppresses; an absent row keeps
                        # the message flowing (prior behavior).
                        record = self.registry.get(body["doc_id"])
                        suppressed = record is not None
                        if record is None:
                            log.warning(
                                "doc %s not in registry; processing "
                                "anyway",
                                body["doc_id"],
                            )
                if suppressed:
                    log.info(
                        "dropping deleted doc %s at deid stage", body["doc_id"]
                    )
                    obs.finish(ctx, status="dropped")
                    continue
                self._publish(
                    self.cfg.broker.clean_queue,
                    {
                        "doc_id": body["doc_id"],
                        "original_text_masked": clean,
                        "metadata": body.get("metadata", {}),
                        "processed_at": time.time(),
                    },
                    headers=obs.headers_of(ctx),
                )
            except Exception:
                log.exception("clean-queue publish failed for %s", body["doc_id"])
                try:
                    self.registry.set_status_unless_deleted(
                        body["doc_id"], reg.ERROR_DEID
                    )
                    self._notify_done()
                except Exception:
                    log.exception("status write failed for %s", body["doc_id"])
                if ctx is not None:
                    ctx.trace.flag("error_deid")
                    obs.finish(ctx, status="error")

    def _index_handler(
        self,
        bodies: List[Dict[str, Any]],
        headers: Optional[List[Dict[str, Any]]] = None,
    ) -> None:
        # before any side effect: an injected raise here replays the whole
        # batch safely (resilience_site: index)
        faults.perturb("index")
        headers = headers if headers is not None else [{} for _ in bodies]
        # per-doc trace contexts (docqa_tpu/obs): re-linked from message
        # headers so the index hop lands on the same timeline as ingest
        # and deid; the terminal status below completes each trace
        ctx_by_doc = {
            body["doc_id"]: obs.from_headers(hdrs, name="doc")
            for body, hdrs in zip(bodies, headers)
        }
        all_chunks: List[str] = []
        all_meta: List[Dict[str, Any]] = []
        per_doc: List[tuple] = []
        replayed: List[str] = []
        for body in bodies:
            # Durable suppression: the in-memory suppressed set dies with
            # the process, but a DELETE writes reg.DELETED to the registry
            # (SQLite/Postgres) — so a message replayed from the broker
            # journal after a restart still cannot resurrect an erased
            # document, and a tombstoned-but-uncompacted doc's replay
            # cannot flip its status back to INDEXED.
            record = self.registry.get(body["doc_id"])
            if record is not None and record.status == reg.DELETED:
                log.info("dropping deleted doc %s (registry)", body["doc_id"])
                obs.finish(ctx_by_doc.get(body["doc_id"]), status="dropped")
                continue
            if body["doc_id"] in self._suppressed_doc_ids:
                log.info("dropping deleted in-flight doc %s", body["doc_id"])
                obs.finish(ctx_by_doc.get(body["doc_id"]), status="dropped")
                continue
            if body["doc_id"] in self._indexed_doc_ids:
                log.info(
                    "skipping replayed already-indexed doc %s", body["doc_id"]
                )
                replayed.append(body["doc_id"])
                continue
            text = body["original_text_masked"]
            md = body.get("metadata", {})
            published_at = body.get("processed_at")
            if published_at is not None:
                DEFAULT_REGISTRY.histogram("clean_queue_lag_s").observe(
                    max(0.0, time.time() - float(published_at))
                )
            chunks = chunk_text(text, self.cfg.chunk)
            per_doc.append((body["doc_id"], len(chunks)))
            for ci, ch in enumerate(chunks):
                all_chunks.append(ch.text)
                all_meta.append(
                    {
                        "doc_id": body["doc_id"],
                        "text_content": ch.text,
                        "source": f"Dossier Patient {body['doc_id']}"
                        if md.get("patient_id")
                        else (md.get("filename") or body["doc_id"]),
                        "type": "patient_file",
                        "patient_id": md.get("patient_id"),
                        "doc_type": md.get("type"),
                        "doc_date": md.get("doc_date"),
                        "chunk_index": ci,
                        "char_start": ch.start,
                        "char_end": ch.end,
                    }
                )
        t_batch0 = time.perf_counter()
        if all_chunks:
            with span("index_batch", DEFAULT_REGISTRY):
                # encode is pure; a raise from it (or from store.add, whose
                # append is all-or-nothing) leaves no partial state, so the
                # Consumer's individual retry cannot duplicate vectors
                embeddings = self.encoder.encode_texts(all_chunks)
                with self._suppress_lock:
                    # a DELETE may have landed during the (seconds-long)
                    # encode; drop those docs' rows now, while suppress_doc
                    # is excluded — past this block, added rows are visible
                    # to the deleter's delete_docs
                    late = {
                        d for d, _n in per_doc if d in self._suppressed_doc_ids
                    }
                    if late:
                        keep = [
                            i
                            for i, md in enumerate(all_meta)
                            if md["doc_id"] not in late
                        ]
                        embeddings = np.asarray(embeddings)[keep]
                        all_meta = [all_meta[i] for i in keep]
                        per_doc = [
                            (d, n) for d, n in per_doc if d not in late
                        ]
                        log.info(
                            "dropped %d doc(s) deleted mid-encode", len(late)
                        )
                        for d in sorted(late):
                            obs.finish(ctx_by_doc.get(d), status="dropped")
                    if all_meta:
                        self.store.add(embeddings, all_meta)
                    self._indexed_doc_ids.update(d for d, _n in per_doc)
            t_batch1 = time.perf_counter()
            for doc_id, n in per_doc:
                ctx = ctx_by_doc.get(doc_id)
                if ctx is not None:
                    ctx.trace.record_span(
                        "index_batch", t_batch0, t_batch1,
                        parent_id=ctx.span_id, batch=len(per_doc),
                        doc_id=doc_id, n_chunks=n,
                    )
        # vectors are committed past this point: never raise (a retry would
        # re-encode and re-append the whole batch)
        if self.on_indexed is not None and per_doc:
            # BEFORE the status writes: with snapshot_every=1 an INDEXED
            # status then implies the vectors are already durable
            try:
                self.on_indexed(len(per_doc))
            except Exception:
                log.exception("on_indexed hook failed")
        for doc_id, n in per_doc:
            try:
                # a DELETE between store.add and here already wrote (or
                # is about to write) DELETED; an INDEXED overwrite would
                # advertise a doc whose vectors are tombstoned.  The
                # suppress lock covers ONLY the set read — the registry
                # write (database I/O) runs outside it so DELETEs never
                # queue behind this worker's commits (docqa-lint:
                # lock-discipline).  Races stay closed without the wider
                # section: the write is conditional AT the database
                # (UPDATE ... WHERE status != DELETED), atomic against
                # both an in-process DELETE (which writes DELETED after
                # its suppress_doc, overwriting any INDEXED that slipped
                # in between) and a foreign process's DELETE committing
                # mid-loop (Postgres multi-process mode).  (Cross-process
                # deletes still cannot drop this process's in-flight
                # vectors; those rows stay tombstone-filtered at query
                # time once the deleter's delete_docs reaches the store
                # snapshot — see docs/OPERATIONS.md.)
                with self._suppress_lock:
                    skip = doc_id in self._suppressed_doc_ids
                if skip:
                    obs.finish(ctx_by_doc.get(doc_id), status="dropped")
                    continue
                self.registry.set_status_unless_deleted(
                    doc_id, reg.INDEXED, n_chunks=n
                )
                # terminal: the document's whole ingest→deid→index
                # timeline completes here
                obs.finish(ctx_by_doc.get(doc_id), status="ok")
            except Exception:
                log.exception("status write failed for %s", doc_id)
        for doc_id in replayed:
            # the crash the replay recovers from may have hit between the
            # snapshot and the status write — make the registry agree with
            # the vectors it already has (idempotent overwrite).  Same
            # guard and same narrow locking as the per_doc loop: a DELETE
            # that landed while this batch was in the encoder must not be
            # overwritten by INDEXED.
            try:
                with self._suppress_lock:
                    skip = doc_id in self._suppressed_doc_ids
                if skip:
                    obs.finish(ctx_by_doc.get(doc_id), status="dropped")
                    continue
                self.registry.set_status_unless_deleted(doc_id, reg.INDEXED)
                obs.finish(ctx_by_doc.get(doc_id), status="ok")
            except Exception:
                log.exception("status write failed for %s", doc_id)
        if per_doc or replayed:  # wake wait_indexed() blockers
            self._notify_done()

    # ---- completion signal ---------------------------------------------------

    _TERMINAL = (
        reg.INDEXED,
        reg.ERROR_EXTRACTION,
        reg.ERROR_QUEUE,
        reg.ERROR_DEID,
        reg.ERROR_INDEXING,
        reg.DELETED,
    )

    def wait_indexed(self, doc_id: str, timeout: float = 30.0) -> bool:
        """Real completion signal (vs the reference's 5 s guess).

        Blocks on a Condition signaled by every terminal status write
        (``_index_handler``, error paths, dead-letter callbacks) — no
        10 ms registry poll per waiting upload.  The wait is still capped
        (1 s) per cycle: in multi-process registry deployments (Postgres)
        a FOREIGN process's status write can't notify this Condition."""
        deadline = time.monotonic() + timeout
        with self._done_cv:
            while True:
                record = self.registry.get(doc_id)
                if record is not None and record.status in self._TERMINAL:
                    return record.status == reg.INDEXED
                remaining = deadline - time.monotonic()
                if remaining <= 0 or self._stopped:
                    return False
                self._done_cv.wait(min(remaining, 1.0))
