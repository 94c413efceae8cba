"""NER training: contextual PHI detection on held-out surface forms.

This is the capability test the reference gets from Presidio's pretrained
spaCy model (``deid-service/anonymizer.py:29-48``): names/locations/groups
the system has NEVER seen must be masked from context + orthographic shape.
The probe words (John, Smith, Boston, ...) are deliberately absent from the
training lexicons (``deid/datagen.py`` EVAL_* vs TRAIN_*).
"""

import numpy as np
import pytest

from docqa_tpu.config import NERConfig
from docqa_tpu.deid.datagen import (
    EVAL_LEXICONS,
    TRAIN_LEXICONS,
    encode_example,
    generate_example,
    ner_tokenizer,
    word_bio_labels,
)
from docqa_tpu.deid.engine import DeidEngine
from docqa_tpu.models.ner import label_ids
from docqa_tpu.text.tokenizer import ShapeHashTokenizer

CFG = NERConfig(
    vocab_size=30522, hidden_dim=64, num_layers=2, num_heads=4,
    mlp_dim=128, max_seq_len=128, dtype="float32",
)


@pytest.fixture(scope="session")
def trained_params():
    from docqa_tpu.training.ner import train_ner

    # 550 steps: the round-3 datagen widening (narrative/letter/French/NRP
    # registers, deid/datagen.py) enlarged the template space, and 350
    # steps under-fit it (template-eval F1 0.72; 550 restores 0.94 and
    # lifts the handwritten-eval entity F1 to 0.76)
    return train_ner(
        CFG, steps=550, batch_size=32, seq=96, lr=2e-3, seed=0, log_every=0
    )


@pytest.fixture(scope="session")
def engine(trained_params):
    return DeidEngine(
        CFG,
        tokenizer=ner_tokenizer(CFG),
        params=trained_params,
        ner_threshold=0.5,
    )


class TestShapeHashTokenizer:
    def test_markers(self):
        tok = ShapeHashTokenizer(1024)
        assert tok.word_to_ids("Boston")[0] == ShapeHashTokenizer.SHAPE_TITLE
        assert tok.word_to_ids("MRI")[0] == ShapeHashTokenizer.SHAPE_UPPER
        assert tok.word_to_ids("b12")[0] == ShapeHashTokenizer.SHAPE_DIGIT
        assert len(tok.word_to_ids("fever")) == 1

    def test_bucket_case_insensitive(self):
        tok = ShapeHashTokenizer(1024)
        assert tok.word_to_ids("Boston")[-1] == tok.word_to_ids("boston")[-1]

    def test_not_lowercasing(self):
        assert ShapeHashTokenizer(1024).lowercase is False


class TestDatagen:
    def test_spans_match_text(self):
        rng = np.random.default_rng(0)
        for _ in range(50):
            text, spans = generate_example(rng)
            for a, b, ent in spans:
                frag = text[a:b]
                assert frag and frag[0].isupper(), (text, frag, ent)

    def test_word_bio_labels(self):
        L = label_ids(CFG)
        text = "Ava Moreau lives in Lyon."
        spans = [(0, 10, "PERSON"), (20, 24, "LOCATION")]
        words, _, labels = word_bio_labels(text, spans, CFG)
        assert words[:2] == ["Ava", "Moreau"]
        assert labels[0] == L["B-PERSON"] and labels[1] == L["I-PERSON"]
        assert labels[words.index("Lyon")] == L["B-LOCATION"]
        assert labels[words.index("lives")] == L["O"]

    def test_encode_supervises_first_token(self):
        tok = ner_tokenizer(CFG)
        text = "Ava lives here."
        ids, length, labels, mask = encode_example(tok, CFG, text, [(0, 3, "PERSON")], 64)
        # CLS at 0; first word "Ava" starts at token 1 (its shape marker)
        assert mask[1] == 1.0 and labels[1] == label_ids(CFG)["B-PERSON"]
        # non-first tokens of a word are unsupervised
        assert mask[2] == 0.0
        assert length == int((ids != 0).sum())

    def test_lexicons_disjoint(self):
        for key in TRAIN_LEXICONS:
            overlap = set(w.lower() for w in TRAIN_LEXICONS[key]) & set(
                w.lower() for w in EVAL_LEXICONS[key]
            )
            assert not overlap, (key, overlap)


@pytest.mark.slow
class TestContextualPHI:
    """VERDICT round-1 item 2's acceptance criteria.

    Marked ``slow``: the shared ``engine`` fixture trains a real tagger
    (~2 min on the CPU test mesh), which alone blows most of the tier-1
    870 s budget now that the whole suite actually runs (these tests were
    collection errors before the jax shard_map compat shim).  Full deid
    quality still runs via ``pytest -m slow`` / an unfiltered run."""

    def test_unseen_person_location_no_title_cue(self, engine):
        assert engine.anonymize("John Smith from Boston") == "<PERSON> from <LOCATION>"

    def test_unseen_person_comma_variant(self, engine):
        out = engine.anonymize("John Smith, lives in Boston")
        assert out == "<PERSON>, lives in <LOCATION>"

    def test_composed_clause_regression(self, engine):
        # Round-2 service drive caught this exact composition slipping
        # through a tagger trained on fixed whole-sentence templates:
        # subject decoration ("Patient ... from ...") + admission predicate.
        out = engine.anonymize(
            "Patient John Smith from Boston was admitted on 2024-03-12 "
            "with chest pain."
        )
        assert "John" not in out and "Smith" not in out, out
        assert "Boston" not in out, out
        assert "<PERSON>" in out and "<LOCATION>" in out, out

    def test_unseen_nrp(self, engine):
        out = engine.anonymize(
            "The patient identifies as Buddhist and requests an interpreter."
        )
        assert "<NRP>" in out and "Buddhist" not in out

    def test_negatives_untouched(self, engine):
        for text in (
            "Patient presents with abdominal pain and nausea.",
            "Started on Lisinopril 10 mg daily.",
            "The MRI of the chest was unremarkable.",
        ):
            assert engine.anonymize(text) == text

    def test_heldout_span_f1(self, trained_params):
        from docqa_tpu.training.ner import evaluate_ner

        metrics = evaluate_ner(trained_params, CFG, n_examples=48)
        assert metrics["f1"] >= 0.8, metrics

    def test_handwritten_evalset_floors(self, engine):
        """Round-3 quality gate (VERDICT item 6): the tagger must clear
        fixed floors on the HAND-WRITTEN eval set (deid/evalset.py),
        whose sentences are written in registers the training generator
        does not emit — this measures generalization, not memorization.
        Floors sit under the measured values (entity F1 0.76, char F1
        0.91, span recall 0.95 at this test size) with slack for
        platform-to-platform training drift.  Typed precision trails
        recall by design: for a privacy masker the safe failure direction
        is over-masking, never leaking."""
        from docqa_tpu.deid.evalset import evaluate_deid

        ev = evaluate_deid(engine)
        assert ev["span_recall_any"] >= 0.85, ev
        assert ev["char_f1"] >= 0.75, ev
        assert ev["entity_f1"] >= 0.50, ev
        # the two pattern-backed entities must be near-perfect regardless
        # of tagger quality
        assert ev["per_entity"]["EMAIL_ADDRESS"]["f1"] >= 0.99, ev
        assert ev["per_entity"]["DATE_TIME"]["recall"] >= 0.99, ev

    def test_pattern_precision_on_clinical_register(self):
        """The broadened date/person/NRP patterns must NOT corrupt
        common clinical constructions (verb+number, 'Pt. Denies',
        sentence-boundary initials, dotted organisms, French etiology)
        while still catching the shapes they were added for."""
        eng = DeidEngine(CFG, use_ner_model=False)
        untouched = (
            "dose decreased 3 mg this week.",
            "seen on 2 separate occasions.",
            "patient marched 5 km daily.",
            "Pt. Denies chest pain.",
            "Pt Tolerating PO intake.",
            "Plan B. Follow up next week.",
            "Culture grew E. Coli positive.",
            "I.V. Fluids started overnight.",
            "Embolie d'origine cardiaque suspectée.",
            "Fièvre d'origine inconnue depuis trois jours.",
            "AVC d'origine ischémique confirmé.",
            "pt reported severe dizziness overnight.",
            "pt verbalized understanding of the plan.",
            "The dose of 3 may be reduced.",
            "Increase to 10 may help symptoms.",
        )
        for text in untouched:
            assert eng.anonymize(text) == text, eng.anonymize(text)
        caught = (
            ("0800 rounds: pt J. Castellano resting.", "<PERSON>"),
            ("Dr. LEE on call tonight per signature block.", "<PERSON>"),
            ("Seen by Dr. Smith on 3 May 2026.", "<DATE_TIME>"),
            ("Consent witnessed by Beatrice Lindqvist, RN.", "<PERSON>"),
            ("Patient d'origine kabyle, suivi à Toulouse.", "<NRP>"),
            ("follow-up scheduled for May 21st.", "<DATE_TIME>"),
            ("records transfer by the end of August.", "<DATE_TIME>"),
            ("call me back before Friday.", "<DATE_TIME>"),
            ("revu le 3 juin 2026 en consultation.", "<DATE_TIME>"),
        )
        for text, token in caught:
            assert token in eng.anonymize(text), (text, eng.anonymize(text))
        # both endpoints of a transfer are cued
        spans = eng.analyze(
            "transferred from Mercy General to Oakdale Manor today."
        )
        locs = {
            "transferred from Mercy General to Oakdale Manor today."[
                s.start : s.end
            ]
            for s in spans
            if s.entity_type == "LOCATION"
        }
        assert {"Mercy General", "Oakdale Manor"} <= locs, locs

    def test_dev_test_split_evaluation(self, engine):
        """VERDICT r4 item 5: the reported deid quality must come from
        spans never used to pick the served threshold.  The split scorer
        returns dev (threshold-selection) and test (held-out) metrics
        with a bootstrap CI; floors here are calibrated on the in-test
        550-step tagger + pattern/cue recognizers (measured: test
        span_recall 0.97, char F1 0.90, entity F1 0.93) with slack for
        training drift."""
        from docqa_tpu.deid.evalset import evaluate_deid_split

        ev = evaluate_deid_split(engine, n_boot=100)
        assert ev["dev"]["gold_spans"] + ev["test"]["gold_spans"] >= 100
        assert ev["test"]["gold_spans"] >= 60
        assert ev["test"]["span_recall_any"] >= 0.85, ev["test"]
        assert ev["test"]["char_f1"] >= 0.75, ev["test"]
        assert ev["test"]["entity_f1"] >= 0.70, ev["test"]
        lo, hi = ev["test"]["entity_f1_ci95"]
        assert lo <= ev["test"]["entity_f1"] <= hi
        # pattern-backed entities stay near-perfect on the held-out
        # split too (no training involved)
        assert ev["test"]["per_entity"]["EMAIL_ADDRESS"]["f1"] >= 0.99
        assert ev["test"]["per_entity"]["PHONE_NUMBER"]["recall"] >= 0.99

    def test_six_entity_contract_end_to_end(self, engine):
        # model entities + pattern entities in one document
        text = (
            "John Smith of Boston, reachable at j.smith@mail.org or "
            "555-123-4567, was seen on 2024-03-05."
        )
        out = engine.anonymize(text)
        for token in ("<PERSON>", "<LOCATION>", "<EMAIL_ADDRESS>",
                      "<PHONE_NUMBER>", "<DATE_TIME>"):
            assert token in out, out
        for leak in ("John", "Smith", "Boston", "mail.org", "555-123"):
            assert leak not in out, out


@pytest.mark.slow  # shares TestContextualPHI's trained_params fixture —
# see that class's note; any one of these triggers the ~2 min training
class TestPersistence:
    def test_save_load_roundtrip(self, trained_params, tmp_path):
        from docqa_tpu.training.ner import load_ner_params, save_ner_params

        path = str(tmp_path / "ner.npz")
        save_ner_params(path, trained_params, CFG)
        loaded = load_ner_params(path, CFG)
        assert loaded is not None
        for k, v in trained_params.items():
            np.testing.assert_array_equal(np.asarray(v), np.asarray(loaded[k]))

    def test_fingerprint_mismatch_retrains(self, trained_params, tmp_path):
        from docqa_tpu.training.ner import load_ner_params, save_ner_params

        path = str(tmp_path / "ner.npz")
        save_ner_params(path, trained_params, CFG)
        import dataclasses

        other = dataclasses.replace(CFG, hidden_dim=32)
        assert load_ner_params(path, other) is None

    def test_trained_classmethod_caches(self, tmp_path):
        import os

        path = str(tmp_path / "cache.npz")
        tiny = NERConfig(
            vocab_size=512, hidden_dim=16, num_layers=1, num_heads=2,
            mlp_dim=32, max_seq_len=64, dtype="float32",
        )
        eng1 = DeidEngine.trained(tiny, params_path=path, steps=2)
        assert os.path.exists(path)
        eng2 = DeidEngine.trained(tiny, params_path=path, steps=2)
        for k in eng1.params:
            np.testing.assert_array_equal(
                np.asarray(eng1.params[k]), np.asarray(eng2.params[k])
            )
