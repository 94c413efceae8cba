"""Confidence-gated answer routing + dense/lexical score fusion (docqa-lexroute).

Two serving decisions live here, both pure host logic (no device code):

* **Score fusion** (:func:`fuse_scores`): min-max-normalized mix of the
  dense tier's cosine scores and the lexical tier's impact scores over
  the candidate union — the ``mode="hybrid"`` merge used by
  ``TieredIndex``/``FusedTieredRetriever``.  The mix weight is a config
  knob (``RetrieveConfig.hybrid_alpha``); whether hybrid is the serving
  DEFAULT is a measured decision (the recallscope CI-low on the labeled
  exact-token mix must beat dense-only — PR 13's advisory-first rule),
  not an assumption.
* **Answer routing** (:class:`AnswerRouter`): classifies each /ask as
  *extractive/lookup* (the answer is a span the index already holds —
  MRN/phone lookups, quoted exact strings, "what is the dose of X"
  shapes in EN/FR) vs *generative* (why/how/explain/summarize needs the
  decoder).  Routed-extractive requests are served straight from
  retrieval via :func:`extractive_answer` — the decoder is never
  touched and no KV slot is allocated.
  The gate is two-stage and conservative by design: a query-text
  decision first, then an evidence check
  (:func:`extractive_confidence`) after retrieval — low confidence at
  EITHER stage falls through to the generative path, so a wrong route
  can cost latency, never correctness (``tests/test_router.py`` holds
  the text stage's precision to >=0.95 on the checked-in labeled mix,
  authored like the deid HELDOUT split and never tuned against).

:func:`extractive_answer` is PR 1's degraded-mode answerer *promoted*:
one implementation, two call sites (degraded fallback in
``service/qa.py`` — behavior pinned unchanged by tests — and the routed
extractive path here).
"""

from __future__ import annotations

import re
import unicodedata
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

from docqa_tpu.index.lexical import clinical_tokens
from docqa_tpu.runtime.metrics import get_logger

log = get_logger("docqa.router")

ROUTE_EXTRACTIVE = "extractive"
ROUTE_GENERATIVE = "generative"


# ---------------------------------------------------------------------------
# Promoted extractive answerer (PR 1 degraded mode -> shared implementation)
# ---------------------------------------------------------------------------


def extractive_answer(chunks: List[str], max_chars: int = 600) -> str:
    """The extractive answer: the top-k retrieved chunks verbatim.

    Promoted from the degraded-mode fallback (retrieval stays up when
    generation is down — serving the evidence beats serving a 500) to
    ALSO serve routed lookup requests at full health.  Deterministic and
    model-free by construction; byte-identical to the PR 1 behavior the
    degraded-mode tests pin."""
    text = "\n\n".join(c for c in chunks if c).strip()
    if not text:
        return "Aucun contexte trouvé."
    return text[:max_chars]


# EN + FR function words excluded from the evidence-overlap signal: a
# chunk matching only "the"/"de la" is not evidence
_STOPWORDS = frozenset(
    """a an and are as at be by for from in is it of on or that the to was
    what when where which who with
    au aux ce cette dans de des du en est et il elle la le les ou par pour
    que quel quelle qui sur un une""".split()
)


def extractive_confidence(question: str, chunks: Sequence[str]) -> float:
    """Evidence confidence in [0, 1]: how much of the question's
    informative vocabulary the retrieved context actually contains.

    Calibration (fit once on the labeled routing mix — data/
    routing_mix.jsonl — and frozen): full coverage of the question's
    content tokens, including any digit runs, is what separates servable
    lookups from spans the context only grazes; the piecewise scale
    below maps coverage so the router threshold 0.5 sits at ~80%
    coverage.  Shared with degraded-mode telemetry so operators read one
    number on both paths."""
    if not chunks:
        return 0.0
    q_toks = [t for t in clinical_tokens(question) if t not in _STOPWORDS]
    if not q_toks:
        return 0.0
    ctx = set(clinical_tokens(" ".join(c for c in chunks if c)))
    need = set(q_toks)
    coverage = len(need & ctx) / len(need)
    # digit runs (MRNs, phones) are the whole point of a lookup — a
    # context missing the asked-for identifier cannot answer it
    digit_terms = {t for t in need if len(t) >= 5 and t.isdigit()}
    if digit_terms and not digit_terms <= ctx:
        return min(coverage, 0.25)
    # piecewise calibration: <=40% coverage ~ noise, >=95% ~ certainty
    if coverage >= 0.95:
        return 1.0
    if coverage <= 0.4:
        return coverage * 0.5
    return 0.2 + (coverage - 0.4) / 0.55 * 0.75


# ---------------------------------------------------------------------------
# Dense + lexical score fusion
# ---------------------------------------------------------------------------


def _minmax(pairs: Sequence[Tuple[float, int]]) -> Dict[int, float]:
    if not pairs:
        return {}
    scores = [s for s, _ in pairs]
    lo, hi = min(scores), max(scores)
    if hi - lo < 1e-12:
        return {rid: 1.0 for _, rid in pairs}
    return {rid: (s - lo) / (hi - lo) for s, rid in pairs}


def fuse_scores(
    dense: Sequence[Tuple[float, int]],
    lexical: Sequence[Tuple[float, int]],
    alpha: float,
    k: Optional[int] = None,
) -> List[Tuple[float, int]]:
    """Hybrid merge: ``alpha * norm(dense) + (1-alpha) * norm(lexical)``
    over the candidate union, each tier min-max normalized over its OWN
    candidate list (cosine and BM25-impact scales are incomparable raw).
    A row only one tier surfaced scores 0 on the other — present but
    un-boosted.  Deterministic tie-break on row id."""
    nd = _minmax(dense)
    nl = _minmax(lexical)
    fused = [
        (alpha * nd.get(rid, 0.0) + (1.0 - alpha) * nl.get(rid, 0.0), rid)
        for rid in nd.keys() | nl.keys()
    ]
    fused.sort(key=lambda p: (-p[0], p[1]))
    return fused[:k] if k is not None else fused


# ---------------------------------------------------------------------------
# Answer router
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class RouteDecision:
    """Stamped on the request trace and cost record (class stays
    ``interactive`` — routing is a serving decision, not a tenant)."""

    route: str  # ROUTE_EXTRACTIVE | ROUTE_GENERATIVE
    confidence: float
    reason: str


def _fold(text: str) -> str:
    t = unicodedata.normalize("NFKD", text.casefold())
    return "".join(ch for ch in t if not unicodedata.combining(ch))


# reasoning/synthesis cues: the decoder earns its keep here.  Checked
# FIRST — "why was patient 12345678 readmitted" contains an MRN but is a
# generative question about it.
_GENERATIVE_CUES = (
    "why", "how ", "how?", "explain", "summar", "compare", "interpret",
    "recommend", "should ", "describe", "what would", "what could",
    "assess", "evaluate", "discuss", "implication", "differen", "risk",
    "likely", "opinion", "advise", "suggest",
    "pourquoi", "comment ", "expliqu", "resum", "compar", "interpret",
    "recommand", "devrait", "faut-il", "analyse", "decri", "justifi",
    "synthese", "synthet", "evalu", "consequence", "avis", "conseil",
)

# lookup cues: the answer is a stored span (EN + diacritic-folded FR)
_LOOKUP_CUES = (
    "mrn", "medical record", "record number", "phone", "telephone",
    "date of birth", "dob", "room number", "dosage", "dose of",
    "what is the dose", "blood type", "allergies", "allergy",
    "admission date", "discharge date", "lookup", "look up",
    "id of", "number of the patient", "contact number",
    "numero de dossier", "numero de telephone", "quel est le numero",
    "quelle est la dose", "posologie", "groupe sanguin",
    "date de naissance", "date d'admission", "date de sortie",
    "chambre", "identifiant",
)

_DIGIT_RUN = re.compile(r"\d[\d.\-\s]{4,}\d")
_QUOTED = re.compile(r"[\"«'']([^\"»'']{3,})[\"»'']")


class AnswerRouter:
    """Per-request extractive-vs-generative classification from query
    text alone (stage 1; the post-retrieval evidence gate is stage 2,
    applied by the QA service).  ``min_confidence`` is the operator knob
    (docs/OPERATIONS.md "Tune the answer router"): decisions below it
    always take the generative path."""

    def __init__(
        self,
        min_confidence: float = 0.7,
        evidence_min: float = 0.5,
        enabled: bool = True,
    ) -> None:
        self.min_confidence = float(min_confidence)
        self.evidence_min = float(evidence_min)
        self.enabled = bool(enabled)

    def decide(self, question: str) -> RouteDecision:
        """Text-stage decision.  Conservative by precedence: any
        reasoning cue forces generative (a wrong generative route costs
        latency; a wrong extractive route would cost answer quality, so
        that side carries the precision floor)."""
        if not self.enabled:
            return RouteDecision(ROUTE_GENERATIVE, 1.0, "router_disabled")
        q = _fold(question or "").strip()
        if not q:
            return RouteDecision(ROUTE_GENERATIVE, 1.0, "empty_question")
        for cue in _GENERATIVE_CUES:
            if cue in q:
                return RouteDecision(
                    ROUTE_GENERATIVE, 0.9, f"generative_cue:{cue.strip()}"
                )
        if _DIGIT_RUN.search(q):
            # an identifier-bearing lookup (MRN, phone, dotted groups)
            return RouteDecision(ROUTE_EXTRACTIVE, 0.9, "digit_run")
        if _QUOTED.search(q):
            return RouteDecision(ROUTE_EXTRACTIVE, 0.85, "quoted_exact")
        hits = [cue for cue in _LOOKUP_CUES if cue in q]
        if hits:
            conf = min(0.95, 0.75 + 0.05 * (len(hits) - 1))
            return RouteDecision(
                ROUTE_EXTRACTIVE, conf, f"lookup_cue:{hits[0]}"
            )
        return RouteDecision(ROUTE_GENERATIVE, 0.6, "default_generative")

    def evidence_gate(
        self, decision: RouteDecision, question: str, chunks: Sequence[str]
    ) -> Tuple[RouteDecision, float]:
        """Stage 2: re-check an extractive decision against what
        retrieval actually found.  Returns the (possibly demoted)
        decision plus the evidence confidence — a demotion is never a
        failure, just the generative path with a reason the trace keeps."""
        ev = extractive_confidence(question, chunks)
        if decision.route != ROUTE_EXTRACTIVE:
            return decision, ev
        if decision.confidence < self.min_confidence:
            return (
                RouteDecision(
                    ROUTE_GENERATIVE, decision.confidence,
                    "below_min_confidence",
                ),
                ev,
            )
        if ev < self.evidence_min:
            return (
                RouteDecision(ROUTE_GENERATIVE, ev, "low_evidence"),
                ev,
            )
        return decision, ev
