"""QA / RAG service core (replaces ``llm-qa/main.py`` end to end).

The reference's ``/ask/`` stack — CPU batch-1 query embed → FAISS exact
search k=3 → prompt stuffing → HTTP round-trip to Ollama (SURVEY §3.2) —
becomes three on-device steps in one process: jit encoder → sharded
HBM top-k → jit decode loop with KV cache.

Also implements, for real, the two endpoints the reference *called* but
never provided (SURVEY §1 "aspirational API layer"):

* patient-snippet retrieval (``core/retrieval_client.py:89``) — backed by
  the store's metadata filter (first-class ``patient_id``/dates, which the
  reference store schema couldn't express);
* prompt summarization (``core/llm_client.py:51``) — backed by the
  summarizer engine.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from docqa_tpu import obs
from docqa_tpu.engines.serve import (
    DEFAULT_RESULT_TIMEOUT,
    DeferredByPolicy,
    QueueFull,
    WorkerDied,
)
from docqa_tpu.resilience import faults
from docqa_tpu.resilience.deadline import Deadline, DeadlineExceeded
from docqa_tpu.runtime.metrics import DEFAULT_REGISTRY, get_logger, span

log = get_logger("docqa.qa")

# Our own QA template; same *shape* as the reference's French TCM-expert
# prompt with score-ranking instructions (``llm-qa/main.py:71-93``) without
# reproducing its wording.
QA_TEMPLATE = (
    "Tu es un expert en médecine traditionnelle chinoise et en analyse de "
    "dossiers cliniques. Appuie-toi uniquement sur le contexte ci-dessous. "
    "Quand plusieurs éléments portent un score, privilégie les scores les "
    "plus élevés et mentionne-les. Si le contexte ne permet pas de répondre, "
    "dis-le explicitement.\n\n"
    "Contexte:\n{context}\n\nQuestion: {question}\n\nRéponse:"
)

# Template half of the prefix-cache key (docqa-prefix): stamped once per
# process so a template edit invalidates every cached prefix by key.
_TEMPLATE_HASH = hashlib.sha1(QA_TEMPLATE.encode("utf-8")).hexdigest()[:12]


def prefix_key_for(chunks: List[str]) -> str:
    """The (template hash, retrieved-chunk-set hash) prefix-cache /
    session-affinity key: consecutive questions against the SAME
    retrieved chunk set — the repeat-heavy clinical pattern — share the
    whole template+context prompt prefix, which is exactly what the
    batcher's KV prefix cache can serve without re-prefilling.  The
    chunk hash is order-sensitive (context order changes the prompt
    tokens, so a reordered set must not key the same entry)."""
    h = hashlib.sha1()
    for c in chunks:
        h.update(c.encode("utf-8", "surrogatepass"))
        h.update(b"\x1f")
    return f"{_TEMPLATE_HASH}:{h.hexdigest()[:16]}"


# Promoted to engines/router.py (docqa-lexroute): ONE implementation now
# serves both the degraded fallback here (behavior pinned unchanged by
# the resilience tests) and the routed-extractive fast path.  Re-exported
# so existing imports of qa.extractive_answer keep working.
from docqa_tpu.engines.router import (  # noqa: E402
    ROUTE_EXTRACTIVE,
    extractive_answer,
)


@dataclass
class PendingAnswer:
    """An in-flight ``/ask`` answer: retrieval is done, generation may still
    be decoding in the continuous batcher.  ``resolve()`` blocks for the
    tokens (host-side wait — the caller must NOT hold the device executor,
    that's the whole point of the split).

    Degraded mode: when generation fails or times out AND the retrieved
    chunks are on hand (``chunks``), ``resolve()`` falls back to the
    extractive answer instead of raising — the response carries
    ``degraded: true`` plus the reason, and ``qa_degraded`` counts it.
    A submit-time degrade (breaker open / budget too small) arrives here
    with ``answer`` already set and ``degraded=True``."""

    sources: List[str]
    answer: Optional[str] = None  # already final (fake mode / inline path)
    handle: Optional[Any] = None  # engines.serve.Handle when batched
    tokenizer: Optional[Any] = None
    chunks: List[str] = field(default_factory=list)  # retrieved texts
    degraded: bool = False
    degrade_reason: Optional[str] = None
    breaker: Optional[Any] = None  # decoder CircuitBreaker (outcome sink)
    degraded_max_chars: int = 600
    # docqa-lexroute: set on routed-extractive answers (the decoder was
    # never dispatched); declared as the optional ``route`` key in
    # api_contract.json (contract version 2)
    route: Optional[str] = None
    route_confidence: Optional[float] = None
    route_reason: Optional[str] = None

    def _result(self, answer: str) -> Dict[str, Any]:
        out: Dict[str, Any] = {"answer": answer, "sources": self.sources}
        if self.degraded:
            # key present ONLY on degraded responses: the normal contract
            # stays exactly {"answer", "sources"} (reference parity)
            out["degraded"] = True
            out["degrade_reason"] = self.degrade_reason
        if self.route is not None:
            # same opt-in shape as the degraded keys: generative answers
            # keep the exact reference contract
            out["route"] = self.route
        return out

    def _degrade(self, reason: str) -> Dict[str, Any]:
        self.degraded = True
        self.degrade_reason = reason
        DEFAULT_REGISTRY.counter("qa_degraded").inc()
        # anomalous by definition: the flight recorder always keeps
        # degraded requests, and the timeline says WHY (the reason event)
        obs.flag("degraded")
        obs.event("degraded", reason=reason)
        return self._result(
            extractive_answer(self.chunks, self.degraded_max_chars)
        )

    def resolve(
        self, timeout: Optional[float] = DEFAULT_RESULT_TIMEOUT
    ) -> Dict[str, Any]:
        if self.answer is not None:
            return self._result(self.answer)
        try:
            answer = self.handle.text(self.tokenizer, timeout)
        except DeadlineExceeded:
            # the batcher shed it (queued or mid-decode) — the budget is
            # (nearly) gone, but the extractive answer is free: serve it.
            # Not a decoder fault: release any reserved probe instead of
            # recording an outcome
            if self.breaker is not None:
                self.breaker.release_probe()
            return self._degrade("deadline")
        except TimeoutError:  # ResultTimeout: slow, possibly hung decode
            if self.breaker is not None:
                self.breaker.record_failure()
            return self._degrade("decode_timeout")
        except WorkerDied as e:
            # a pool replica died/wedged with this request ADMITTED —
            # fail-fast by design (queued requests fail over instead);
            # the reason names it so a trace distinguishes replica loss
            # from a device decode error
            if self.breaker is not None:
                self.breaker.record_failure()
            log.warning(
                "decode replica died; serving degraded answer: %r", e
            )
            return self._degrade("replica_died")
        except Exception as e:  # decode failed on device
            if self.breaker is not None:
                self.breaker.record_failure()
            log.warning("generation failed; serving degraded answer: %r", e)
            return self._degrade("decoder_error")
        if self.breaker is not None:
            self.breaker.record_success()
        return self._result(answer)

    def iter_text(self, timeout: Optional[float] = DEFAULT_RESULT_TIMEOUT):
        """Yield answer text incrementally as decode chunks land (SSE
        backing).  Fake/inline answers yield once; batched answers stream
        text DELTAS of the cumulative detokenization — per-token decoding
        would mis-render wordpiece merges and skipped specials, so the
        concatenated stream must equal ``resolve()``'s answer exactly by
        construction."""
        if self.answer is not None:
            yield self.answer
            return
        ids: list = []
        emitted = 0
        try:
            for tok in self.handle.iter_tokens(timeout):
                ids.append(tok)
                decoded = self.tokenizer.decode_ids(ids)
                if len(decoded) > emitted:
                    yield decoded[emitted:]
                    emitted = len(decoded)
        except (DeadlineExceeded, GeneratorExit):
            # budget shed / client disconnect: neither is a decoder
            # outcome — but the probe slot allow() may have reserved
            # must come back
            if self.breaker is not None:
                self.breaker.release_probe()
            raise
        except Exception:
            if self.breaker is not None:
                self.breaker.record_failure()
            raise
        if self.breaker is not None:
            self.breaker.record_success()


class QAService:
    def __init__(
        self,
        encoder,  # EncoderEngine
        store,  # VectorStore
        generator,  # GenerateEngine
        summarizer,  # SummarizeEngine
        k: int = 3,
        use_fake_llm: bool = False,
        batcher=None,  # ContinuousBatcher: concurrent /ask share decode slots
        retriever=None,  # FusedRetriever: encode+search in one dispatch
        breakers=None,  # resilience.BreakerBoard: "decoder" gates generation
        resilience=None,  # ResilienceConfig: degrade thresholds
        router=None,  # engines.router.AnswerRouter: decoder-skip routing
    ) -> None:
        self.encoder = encoder
        self.store = store
        self.generator = generator
        self.summarizer = summarizer
        self.k = k
        self.use_fake_llm = use_fake_llm
        self.batcher = batcher
        self.retriever = retriever
        self.decoder_breaker = (
            breakers.get("decoder") if breakers is not None else None
        )
        self.min_generate_budget_s = (
            resilience.min_generate_budget_s if resilience is not None else 0.5
        )
        self.degraded_max_chars = (
            resilience.degraded_max_chars if resilience is not None else 600
        )
        self.router = router

    def _retrieve(
        self, text: str, k: int, filters=None, deadline=None, mode=None
    ):
        """One fused dispatch when a retriever is wired (encoder forward +
        store top-k in a single XLA program — half the host round-trips);
        otherwise the classic encode-then-search pair.

        ``mode`` (docqa-lexroute) requests a retrieve tier —
        ``"hybrid"``/``"lexical"`` — and is forwarded only to surfaces
        that declare ``supports_modes`` (TieredIndex and the fused
        tiered retriever); everything else serves dense, which is the
        tier contract's own fallback."""
        if self.retriever is not None:
            kw = {}
            if mode is not None and getattr(
                self.retriever, "supports_modes", False
            ):
                kw["mode"] = mode
            return self.retriever.search_texts(
                [text], k=k, filters=filters, deadline=deadline, **kw
            )[0]
        if deadline is not None:
            deadline.check("retrieve")
        emb = self.encoder.encode_texts([text])
        if mode is not None and getattr(self.store, "supports_modes", False):
            return self.store.search(
                emb, k=k, filters=filters, mode=mode, query_texts=[text]
            )[0]
        return self.store.search(emb, k=k, filters=filters)[0]

    # ---- /ask/ ---------------------------------------------------------------

    def _degraded_pending(
        self, sources: List[str], chunks: List[str], reason: str
    ) -> PendingAnswer:
        DEFAULT_REGISTRY.counter("qa_degraded").inc()
        obs.flag("degraded")
        obs.event("degraded", reason=reason)
        return PendingAnswer(
            sources=sources,
            answer=extractive_answer(chunks, self.degraded_max_chars),
            chunks=chunks,
            degraded=True,
            degrade_reason=reason,
        )

    def ask_submit(
        self,
        question: str,
        k: Optional[int] = None,
        deadline: Optional[Deadline] = None,
        req_class: str = "interactive",
    ) -> PendingAnswer:
        """Retrieval + prompt assembly + generation *submission*.

        With a batcher, returns immediately after enqueueing the decode —
        concurrent questions ride separate slots of one decode program
        (BASELINE config 5) instead of serializing whole-request (the round-1
        flaw: ``make_app``'s 1-worker device executor made QPS-16 impossible).

        Failure policy (docs/RESILIENCE.md): retrieval failures propagate
        (no context, nothing to degrade to); once retrieval has produced
        chunks, a decoder problem — breaker open, too little budget left
        for a decode round, or the submission itself failing — serves the
        *degraded* extractive answer instead of an error.  ``QueueFull``
        still propagates: an overloaded-but-healthy decoder is admission
        control (503 + retry), not an outage."""
        if deadline is not None:
            deadline.check("qa_admission")
        # per-class cost attribution (docqa-costscope): stamp a record
        # on the request's trace BEFORE retrieval, so the retrieve
        # dispatch's device time lands on it via the spine's accounting
        # hook.  The HTTP layer usually attached one already (with its
        # endpoint's class); cost_open reuses it.
        cost = obs.cost_open(obs.current(), req_class)
        # docqa-lexroute stage 1: text-only route decision, taken BEFORE
        # retrieval because it picks the retrieve tier — extractive
        # candidates retrieve hybrid (dense + lexical fusion) so the
        # exact-token evidence an MRN/phone lookup needs is actually in
        # the candidate set.  Stamped on the trace either way.
        decision = None
        if self.router is not None and self.router.enabled:
            decision = self.router.decide(question)
            obs.event(
                "route_decision",
                route=decision.route,
                confidence=round(decision.confidence, 3),
                reason=decision.reason,
            )
        mode = (
            "hybrid"
            if decision is not None and decision.route == ROUTE_EXTRACTIVE
            else None
        )
        with span("qa_retrieve", DEFAULT_REGISTRY):
            hits = self._retrieve(
                question, k=k or self.k, deadline=deadline, mode=mode
            )
        chunks = [
            h.metadata.get("text_content", h.metadata.get("source", ""))
            for h in hits
        ]
        context = "\n\n".join(chunks)
        prompt = QA_TEMPLATE.format(context=context, question=question)
        sources = [h.metadata.get("source", "") for h in hits]
        if decision is not None:
            # stage 2: the evidence gate — a routed answer must actually
            # be IN the retrieved context.  A demotion is the generative
            # path with a reason, never a failure (ISSUE contract).
            decision, ev = self.router.evidence_gate(
                decision, question, chunks
            )
            if decision.route == ROUTE_EXTRACTIVE:
                # decoder-skip fast path: the answer is served straight
                # from retrieval — no prompt, no batcher lane, no KV
                # allocation, no decode dispatch (routing_smoke asserts
                # the spine's decode stage counters stay flat here)
                DEFAULT_REGISTRY.counter("qa_routed_extractive").inc()
                obs.event(
                    "routed_extractive",
                    reason=decision.reason,
                    evidence=round(ev, 3),
                )
                if cost is not None:
                    cost.add("routed_extractive", 1.0)
                return PendingAnswer(
                    sources=sources,
                    answer=extractive_answer(
                        chunks, self.degraded_max_chars
                    ),
                    chunks=chunks,
                    route=ROUTE_EXTRACTIVE,
                    route_confidence=decision.confidence,
                    route_reason=decision.reason,
                )
            DEFAULT_REGISTRY.counter("qa_routed_generative").inc()
        if self.use_fake_llm:
            answer = context[:500] if context else "Aucun contexte trouvé."
            return PendingAnswer(sources=sources, answer=answer)
        if (
            deadline is not None
            and deadline.remaining() < self.min_generate_budget_s
        ):
            # a decode round it cannot finish would only waste a lane —
            # checked BEFORE the breaker so a budget shed never consumes
            # a half-open probe slot
            return self._degraded_pending(
                sources, chunks, "insufficient_budget"
            )
        breaker = self.decoder_breaker
        if breaker is not None and not breaker.allow():
            return self._degraded_pending(
                sources, chunks, "decoder_breaker_open"
            )
        try:
            faults.perturb("decoder")  # resilience_site: decoder
            if self.batcher is not None:
                # deadline passed only when set: batcher stand-ins (tests,
                # alternative schedulers) need not know the kwarg.  Same
                # opt-in for the prefix key: only a batcher that
                # advertises a prefix cache receives it (it doubles as
                # the pool's session-affinity key).
                kw = {} if deadline is None else {"deadline": deadline}
                if getattr(self.batcher, "prefix_cache_enabled", False):
                    kw["prefix_key"] = prefix_key_for(chunks)
                    if cost is not None:
                        # session = prefix key: the ledger's top-spender
                        # table groups a patient session's questions
                        cost.set_session(kw["prefix_key"])
                return PendingAnswer(
                    sources=sources,
                    handle=self.batcher.submit_text(prompt, **kw),
                    tokenizer=self.batcher.engine.tokenizer,
                    chunks=chunks,
                    breaker=breaker,
                    degraded_max_chars=self.degraded_max_chars,
                )
            answer = self.generator.generate_texts([prompt])[0]
            if breaker is not None:
                breaker.record_success()
            return PendingAnswer(
                sources=sources, answer=answer, chunks=chunks
            )
        except QueueFull as e:
            # overload ≠ outage: the 503 + client retry is correct.  The
            # shed never reached the decoder — hand back any half-open
            # probe slot allow() reserved, or the breaker wedges.  The
            # cost record retires typed here (idempotent — the batcher/
            # pool shed path usually retired it already): a 503'd
            # request must not leak an open record.  Policy deferrals
            # (DeferredByPolicy, a QueueFull subclass) retire under
            # their own outcome so operators can split "we were full"
            # from "we chose to protect interactive".
            if breaker is not None:
                breaker.release_probe()
            outcome = (
                "shed_deferred"
                if isinstance(e, DeferredByPolicy)
                else "shed_queue"
            )
            obs.DEFAULT_COST_LEDGER.retire(cost, outcome)
            raise
        except DeadlineExceeded:
            if breaker is not None:
                breaker.release_probe()
            return self._degraded_pending(sources, chunks, "deadline")
        except Exception as e:
            if breaker is not None:
                breaker.record_failure()
            log.warning(
                "generation submission failed; serving degraded answer: %r", e
            )
            return self._degraded_pending(sources, chunks, "decoder_error")

    def ask(
        self,
        question: str,
        k: Optional[int] = None,
        deadline: Optional[Deadline] = None,
    ) -> Dict[str, Any]:
        """Returns the reference's response contract
        ``{"answer": ..., "sources": [...]}`` (``llm-qa/main.py:119-122``):
        ``ask_submit`` resolved in place, the path ``/ask/stream`` takes."""
        if deadline is not None:
            deadline.check("qa_admission")
        with span("qa_e2e", DEFAULT_REGISTRY):
            return self.ask_submit(question, k, deadline=deadline).resolve()

    # ---- /api/search/patient-snippets ---------------------------------------

    def patient_snippets(
        self,
        patient_id: str,
        from_date: Optional[str] = None,
        to_date: Optional[str] = None,
        focus: Optional[str] = None,
        limit: int = 20,
    ) -> List[Dict[str, str]]:
        """The retrieval contract synthese expected: ``[{doc_id, text}]``
        (``core/retrieval_client.py:81-91``).

        ``focus`` ranks the patient's chunks by semantic similarity; without
        focus, chunks come back in document order.  Both paths filter via
        the store's columnar metadata (vectorized mask — not a per-row
        Python predicate, which was O(corpus) at the 1M-chunk target)."""
        filters = {
            "patient_id": patient_id,
            "date_from": from_date,
            "date_to": to_date,
        }
        if focus:
            hits = self._retrieve(focus, k=limit, filters=filters)
            rows = [h.metadata for h in hits]
        else:
            rows = self.store.metadata_select(limit=limit, **filters)
        return [
            {"doc_id": md["doc_id"], "text": md.get("text_content", "")}
            for md in rows
        ]

    # ---- /api/llm/summarize --------------------------------------------------

    def summarize(self, prompt: str, max_tokens: Optional[int] = None) -> str:
        return self.summarizer.summarize_prompt(prompt, max_tokens)
