"""Answer routing (docqa-lexroute, ``engines/router.py``).

The router's contract has four load-bearing edges:

* the text-stage ``decide()`` must hold the precision floor on the
  checked-in labeled mix (``data/routing_mix.jsonl`` — authored like the
  deid HELDOUT split, never tuned against), with generative cues taking
  precedence over digit runs ("why is patient 12345678 ..." is a
  generative question ABOUT an MRN);
* the evidence gate demotes — never fails — an extractive decision the
  retrieved context can't actually answer;
* ``extractive_answer`` is ONE implementation with two call sites: the
  PR 1 degraded-mode fallback (behavior pinned here byte-for-byte) and
  the routed fast path;
* the wire shape: ``route`` is an opt-in key on routed-extractive
  answers only — generative and degraded responses keep their exact
  pre-lexroute contracts.
"""

import json
import os

import numpy as np
import pytest

from docqa_tpu.engines.router import (
    ROUTE_EXTRACTIVE,
    ROUTE_GENERATIVE,
    AnswerRouter,
    RouteDecision,
    extractive_answer,
    extractive_confidence,
    fuse_scores,
)

MIX_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "data",
    "routing_mix.jsonl",
)


def _load_mix():
    with open(MIX_PATH, encoding="utf-8") as f:
        return [json.loads(ln) for ln in f if ln.strip()]


# ---------------------------------------------------------------------------
# Text-stage decisions
# ---------------------------------------------------------------------------


class TestDecide:
    def test_mix_precision_floor(self):
        # precision >= 0.95, recall >= 0.90 on the labeled mix: a wrong
        # extractive route ships a wrong-shaped answer, a missed one
        # only costs a decode
        router = AnswerRouter()
        tp = fp = fn = 0
        for ex in _load_mix():
            want = ex["label"] == "extractive"
            got = router.decide(ex["question"]).route == ROUTE_EXTRACTIVE
            tp += want and got
            fp += got and not want
            fn += want and not got
        assert tp / max(tp + fp, 1) >= 0.95, (tp, fp)
        assert tp / max(tp + fn, 1) >= 0.90, (tp, fn)

    def test_generative_cue_beats_digit_run(self):
        # precedence: an MRN inside a why-question stays generative
        d = AnswerRouter().decide("Why is patient 12345678 on dialysis?")
        assert d.route == ROUTE_GENERATIVE
        assert d.reason.startswith("generative_cue")

    def test_digit_run_routes_extractive(self):
        d = AnswerRouter().decide("Look up the record 77120034")
        assert d.route == ROUTE_EXTRACTIVE
        assert d.reason == "digit_run"
        # dotted phone groups count as one run
        d2 = AnswerRouter().decide("Whose chart lists 450.555.0142?")
        assert d2.route == ROUTE_EXTRACTIVE

    def test_quoted_exact_routes_extractive(self):
        d = AnswerRouter().decide('Find the note containing "chest pain"')
        assert d.route == ROUTE_EXTRACTIVE
        assert d.reason == "quoted_exact"

    def test_fr_lookup_cue_with_diacritics(self):
        d = AnswerRouter().decide(
            "Quel est le numéro de dossier du patient Tremblay ?"
        )
        assert d.route == ROUTE_EXTRACTIVE
        assert d.reason.startswith("lookup_cue")

    def test_empty_and_default_generative(self):
        r = AnswerRouter()
        assert r.decide("").route == ROUTE_GENERATIVE
        assert r.decide("").reason == "empty_question"
        # no cue at all: conservative default is the generative path
        d = r.decide("patient status overnight")
        assert d.route == ROUTE_GENERATIVE
        assert d.reason == "default_generative"

    def test_disabled_router_is_pre_lexroute_behavior(self):
        d = AnswerRouter(enabled=False).decide("What is the MRN of Okafor?")
        assert d.route == ROUTE_GENERATIVE
        assert d.reason == "router_disabled"


# ---------------------------------------------------------------------------
# Evidence gate (stage 2)
# ---------------------------------------------------------------------------

_EX = RouteDecision(ROUTE_EXTRACTIVE, 0.9, "digit_run")


class TestEvidenceGate:
    def test_no_chunks_demotes(self):
        d, ev = AnswerRouter().evidence_gate(_EX, "MRN 40081223?", [])
        assert d.route == ROUTE_GENERATIVE
        assert d.reason == "low_evidence"
        assert ev == 0.0

    def test_missing_identifier_demotes(self):
        # context covers the words but NOT the asked-for MRN: a lookup
        # the context can't answer must fall through to the decoder
        chunks = ["admission note for the patient, MRN redacted"]
        d, ev = AnswerRouter().evidence_gate(
            _EX, "What is MRN 40081223?", chunks
        )
        assert d.route == ROUTE_GENERATIVE
        assert ev <= 0.25

    def test_full_evidence_keeps_route(self):
        chunks = ["patient okafor mrn 40081223 admitted to ward b"]
        d, ev = AnswerRouter().evidence_gate(
            _EX, "What is the MRN of patient Okafor?", chunks
        )
        assert d.route == ROUTE_EXTRACTIVE
        assert ev >= 0.5

    def test_below_min_confidence_demotes(self):
        weak = RouteDecision(ROUTE_EXTRACTIVE, 0.6, "lookup_cue:dose of")
        d, _ = AnswerRouter(min_confidence=0.7).evidence_gate(
            weak, "dose of metformin?", ["metformin 850 mg dose"]
        )
        assert d.route == ROUTE_GENERATIVE
        assert d.reason == "below_min_confidence"

    def test_generative_decision_passes_through(self):
        gen = RouteDecision(ROUTE_GENERATIVE, 0.9, "generative_cue:why")
        d, _ = AnswerRouter().evidence_gate(gen, "why?", ["context"])
        assert d is gen


class TestExtractiveConfidence:
    def test_monotone_in_coverage(self):
        q = "metformin dose for patient silva"
        none = extractive_confidence(q, ["unrelated cardiology note"])
        part = extractive_confidence(q, ["metformin dose reviewed"])
        full = extractive_confidence(
            q, ["metformin 850 mg dose for patient silva"]
        )
        assert none < part < full == 1.0

    def test_empty_inputs(self):
        assert extractive_confidence("q", []) == 0.0
        # stopword-only question carries no checkable content
        assert extractive_confidence("what is the", ["anything"]) == 0.0

    def test_digit_term_gate_caps_confidence(self):
        # everything matches EXCEPT the identifier: capped hard
        q = "record 77120034 discharge summary"
        ev = extractive_confidence(q, ["record discharge summary"])
        assert ev <= 0.25


# ---------------------------------------------------------------------------
# Score fusion
# ---------------------------------------------------------------------------


class TestFuseScores:
    def test_union_minmax_and_tiebreak(self):
        dense = [(0.9, 1), (0.5, 2)]
        lexical = [(10.0, 2), (4.0, 3)]
        fused = fuse_scores(dense, lexical, alpha=0.5)
        # rows 1 and 2 both fuse to 0.5; deterministic tie-break on id
        assert [rid for _, rid in fused] == [1, 2, 3]
        assert fused[0][0] == pytest.approx(fused[1][0])

    def test_alpha_extremes(self):
        dense = [(0.9, 1), (0.5, 2)]
        lexical = [(10.0, 2), (4.0, 3)]
        # pure dense: dense order leads; absent row 3 ties at 0 with
        # row 2's normalized min — id tie-break keeps it deterministic
        assert [r for _, r in fuse_scores(dense, lexical, 1.0)] == [1, 2, 3]
        # pure lexical: row 2 leads; rows 1 and 3 tie at 0, id order
        assert [r for _, r in fuse_scores(dense, lexical, 0.0)] == [2, 1, 3]

    def test_k_truncation_and_empty_tiers(self):
        dense = [(0.9, 1), (0.5, 2)]
        assert len(fuse_scores(dense, [], 0.5, k=1)) == 1
        # one-sided fusion still ranks the populated tier
        assert [r for _, r in fuse_scores(dense, [], 0.5)] == [1, 2]
        assert fuse_scores([], [], 0.5) == []

    def test_degenerate_single_candidate(self):
        # min==max: normalization must not divide by zero
        fused = fuse_scores([(0.7, 5)], [(3.0, 5)], 0.6)
        assert fused == [(pytest.approx(1.0), 5)]


# ---------------------------------------------------------------------------
# Promoted extractive answerer (PR 1 degraded behavior pinned)
# ---------------------------------------------------------------------------


class TestPromotedAnswerer:
    def test_one_implementation_two_call_sites(self):
        # qa.py re-exports the SAME function object — not a copy that
        # could drift from the degraded-mode behavior the tests pin
        from docqa_tpu.service import qa as qa_mod

        assert qa_mod.extractive_answer is extractive_answer

    def test_degraded_behavior_pinned(self):
        # byte-for-byte the PR 1 fallback: join, truncate, FR empty-case
        assert extractive_answer(["a", "", "b"]) == "a\n\nb"
        assert extractive_answer(["x" * 1000], max_chars=600) == "x" * 600
        assert extractive_answer([]) == "Aucun contexte trouvé."
        # whitespace-only chunks strip to nothing -> same FR empty case
        assert extractive_answer(["", "  "]) == "Aucun contexte trouvé."


# ---------------------------------------------------------------------------
# QA-service wiring: route wire key, mode forwarding, degraded contract
# ---------------------------------------------------------------------------


class _Hit:
    def __init__(self, text, source):
        self.metadata = {"text_content": text, "source": source}


class _Enc:
    def encode_texts(self, texts):
        return np.zeros((len(texts), 4), np.float32)


class _Store:
    """Mode-aware fake store recording the forwarded retrieve kwargs."""

    count = 2
    supports_modes = True

    def __init__(self, chunks):
        self.chunks = chunks
        self.calls = []

    def search(self, emb, k=3, filters=None, mode=None, query_texts=None):
        self.calls.append({"mode": mode, "query_texts": query_texts})
        return [[_Hit(c, f"s{i}") for i, c in enumerate(self.chunks)]]


def _qa(store, router=AnswerRouter):
    from docqa_tpu.service.qa import QAService

    return QAService(
        _Enc(), store, None, None, use_fake_llm=True,
        router=router() if router else None,
    )


class TestRoutedWireShape:
    def test_routed_extractive_wire_shape(self):
        store = _Store(["patient okafor mrn 40081223 admitted ward b"])
        out = _qa(store).ask("What is the MRN of patient Okafor?")
        assert {"answer", "sources"} <= set(out)
        assert out["route"] == "extractive"
        assert "degraded" not in out
        # the answer IS the retrieved evidence (extractive_answer)
        assert "40081223" in out["answer"]
        # stage 1 picked the hybrid tier for the extractive candidate
        assert store.calls[0]["mode"] == "hybrid"
        assert store.calls[0]["query_texts"] == [
            "What is the MRN of patient Okafor?"
        ]

    def test_generative_keeps_reference_contract(self):
        store = _Store(["observation note for the overnight admission"])
        out = _qa(store).ask("Why was the patient admitted overnight?")
        assert {"answer", "sources"} <= set(out)
        assert "route" not in out  # opt-in key, extractive-routed only
        # generative questions retrieve on the serving default (dense)
        assert store.calls[0]["mode"] is None

    def test_evidence_demotion_serves_generative(self):
        # extractive text decision, but retrieval misses the identifier:
        # demote to the generative path — an answer, never an error
        store = _Store(["unrelated cardiology consult"])
        out = _qa(store).ask("What is the MRN of patient Okafor?")
        assert {"answer", "sources"} <= set(out)
        assert "route" not in out
        assert store.calls[0]["mode"] == "hybrid"  # stage 1 still tried

    def test_no_router_is_pre_lexroute_behavior(self):
        store = _Store(["patient okafor mrn 40081223"])
        out = _qa(store, router=None).ask("What is the MRN of Okafor?")
        assert "route" not in out
        assert store.calls[0]["mode"] is None

    def test_mode_not_forwarded_without_support(self):
        # a store that never declared supports_modes gets the exact
        # pre-lexroute call signature (no mode kwarg to choke on)
        class _Legacy:
            count = 1

            def __init__(self):
                self.kwargs = None

            def search(self, emb, k=3, filters=None):
                self.kwargs = {"k": k, "filters": filters}
                return [[_Hit("mrn 40081223 patient okafor chart", "s0")]]

        store = _Legacy()
        from docqa_tpu.service.qa import QAService

        qa = QAService(
            _Enc(), store, None, None, use_fake_llm=True,
            router=AnswerRouter(),
        )
        out = qa.ask("What is the MRN of patient Okafor?")
        assert out["route"] == "extractive"  # routing works on dense too
        assert store.kwargs == {"k": 3, "filters": None}

    def test_degraded_response_contract_unchanged(self):
        # generation fails AFTER retrieval: the degraded answer keeps the
        # PR 1 contract — degraded keys present, no route key
        class _DeadBatcher:
            prefix_cache_enabled = False

            class engine:
                tokenizer = None

            def submit_text(self, prompt, **kw):
                raise RuntimeError("decoder down")

        from docqa_tpu.service.qa import QAService

        store = _Store(["observation note for the admission"])
        qa = QAService(
            _Enc(), store, None, None, use_fake_llm=False,
            batcher=_DeadBatcher(), router=AnswerRouter(),
        )
        out = qa.ask("Why was the patient admitted?")
        assert out["degraded"] is True
        assert out["degrade_reason"] == "decoder_error"
        assert "route" not in out
        assert out["answer"]  # the extractive fallback served
