"""Hand-written PHI evaluation set + span/char metrics.

The tagger trains on ``deid/datagen.py``'s synthetic generator; every
earlier quality signal was drawn from the SAME template distribution, so
it measured memorization as much as generalization.  This module is the
disjoint check: the sentences below were written by hand in registers the
generator does not produce (narrative discharge prose, referral letters,
nursing shorthand, French clinical snippets mirroring the service's
prompt language, intake forms); the test suite scores against it.

Reference capability being measured: Presidio's pretrained 6-entity
detection (``deid-service/anonymizer.py:41-48``).

Span markup: ``[TYPE:text]`` inline markers; ``_parse`` strips them and
records the character spans against the clean text.

Metric definitions (privacy-first):

* ``char_*`` — precision/recall/F1 over *characters* inside gold PHI
  spans vs characters inside predicted spans, type-agnostic: masking a
  name as LOCATION still hides it, so char metrics measure leak risk.
* ``span_recall_any`` — fraction of gold spans overlapped by ANY
  prediction (a partially masked identifier may still leak; this counts
  any-contact coverage).
* ``entity_f1`` + per-entity breakdown — type-aware span matching
  (overlap with the same entity_type), the classic NER view.
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

_MARK = re.compile(r"\[([A-Z_]+):([^\]]*)\]")


@dataclass(frozen=True)
class GoldSpan:
    entity_type: str
    start: int
    end: int


def _parse(marked: str) -> Tuple[str, List[GoldSpan]]:
    out: List[str] = []
    spans: List[GoldSpan] = []
    pos = 0
    plain_len = 0
    for m in _MARK.finditer(marked):
        out.append(marked[pos : m.start()])
        plain_len += m.start() - pos
        text = m.group(2)
        spans.append(
            GoldSpan(m.group(1), plain_len, plain_len + len(text))
        )
        out.append(text)
        plain_len += len(text)
        pos = m.end()
    out.append(marked[pos:])
    return "".join(out), spans


# Registers deliberately absent from datagen.py's templates: flowing
# multi-clause narrative, letters with salutations, nursing shorthand,
# French prose, form fields with colons, possessives, mid-sentence dates.
_MARKED: Sequence[str] = (
    # narrative discharge prose
    "The patient, [PERSON:Margaret O'Leary], tolerated the procedure "
    "well and was discharged to her daughter's home in "
    "[LOCATION:Worcester] with follow-up scheduled for "
    "[DATE_TIME:April 12, 2026].",
    "On examination [PERSON:Henry Whitfield] appeared comfortable; he "
    "moved from [LOCATION:Portland] last winter and works nights.",
    "We saw [PERSON:Amara Okafor] in clinic today; her sister drove "
    "her from [LOCATION:Springfield] after the fall on "
    "[DATE_TIME:2026-02-19].",
    # referral-letter register
    "Dear colleague, thank you for referring [PERSON:Tomasz Nowak] "
    "regarding refractory hypertension; please fax results to "
    "[PHONE_NUMBER:617-555-0182] or write to "
    "[EMAIL_ADDRESS:cardiology.referrals@mercyhealth.org].",
    "I reviewed the imaging with [PERSON:Dr. Elena Vasquez] by phone "
    "([PHONE_NUMBER:+1 415 555 0101]) before the family meeting on "
    "[DATE_TIME:March 3, 2026].",
    # nursing shorthand
    "0800 rounds: pt [PERSON:J. Castellano] resting, wife at bedside, "
    "transfer from [LOCATION:Mount Auburn] pending bed.",
    "Night shift note - [PERSON:Priya Raghunathan] c/o nausea, called "
    "covering MD at [PHONE_NUMBER:(508) 555-0147], orders received.",
    # intake-form fields (colon-delimited, sentence-initial entities)
    "Next of kin: [PERSON:Robert Ashford]. Residence: "
    "[LOCATION:New Bedford]. Contact: [PHONE_NUMBER:774-555-0133]. "
    "Email: [EMAIL_ADDRESS:r.ashford@example.net].",
    "Emergency contact [PERSON:Linda Zhao] can be reached after "
    "[DATE_TIME:6:30 pm] at [PHONE_NUMBER:857-555-0190].",
    # religious / community affiliation (NRP)
    "The patient is a practicing [NRP:Buddhist] and requests a "
    "vegetarian diet during admission.",
    "Family identifies as [NRP:Jehovah's Witnesses]; blood products "
    "declined, documented with [PERSON:Samuel Ferreira] present.",
    "As an observant [NRP:Muslim] patient he fasts during daylight "
    "hours; medication times adjusted accordingly.",
    # French clinical prose (the service's prompt language)
    "La patiente [PERSON:Camille Rousseau] de [LOCATION:Lyon] est "
    "suivie depuis le [DATE_TIME:12/01/2026] pour un diabète de type 2.",
    "Monsieur [PERSON:Olivier Mercier] sera revu en consultation le "
    "[DATE_TIME:2026-03-28]; joindre le secrétariat au "
    "[PHONE_NUMBER:01 44 55 01 22].",
    # possessives and appositions
    "[PERSON:Katherine Bell]'s INR remains labile; her pharmacist in "
    "[LOCATION:Quincy] will supervise dosing.",
    "The surgeon, [PERSON:Prof. Nathaniel Greene], operated on "
    "[DATE_TIME:February 2, 2026] without complication.",
    # mid-sentence machine-style identifiers
    "Labs drawn [DATE_TIME:2026-02-20] at [DATE_TIME:07:45] show "
    "improving renal function; repeat in ten days.",
    "Telehealth visit recorded; patient joined from [LOCATION:Fall "
    "River] and verified identity via "
    "[EMAIL_ADDRESS:m.santos1958@webmail.com].",
    # clean sentences (false-positive pressure — no PHI at all)
    "Continue metformin 500 mg twice daily with meals and recheck the "
    "hemoglobin A1c in three months.",
    "Ambulating independently, pain controlled, diet advanced as "
    "tolerated, wound edges clean and dry.",
    "Echocardiogram shows preserved ejection fraction without "
    "regional wall motion abnormality.",
)

# ---- SECOND DEV split (VERDICT r4 item 5, relabeled honestly) --------------
# Written AFTER the served threshold (0.8) was frozen from the dev curve —
# but round 5 then tuned the deny-word list and person-position cues
# (deid/engine.py) directly against THESE spans, so they are a second dev
# set, not a held-out test: an F1 scored on them carries
# tuning optimism from that step and must be labeled accordingly wherever
# it is quoted.  A genuinely held-out split would have to be written
# fresh and never scored until a release gate.  Registers avoid datagen's
# templates
# and go beyond the dev split's: ED triage, operative notes, medication
# reconciliation, transcribed voicemail, social-work and hospice notes,
# billing correspondence, more French prose, and harder shapes (initials,
# hyphenated and particle surnames, spelled-out dates, international and
# extension phone formats, plus-addressed emails, multi-entity sentences).
_MARKED_TEST: Sequence[str] = (
    # ED triage register
    "Triage 0312: [PERSON:Dmitri Volkov], walked in with his neighbor "
    "from [LOCATION:Chelsea], chest tightness since "
    "[DATE_TIME:around midnight].",
    "EMS handoff - pt [PERSON:Rosa Delgado-Marin] found at home in "
    "[LOCATION:East Boston]; daughter en route, cell "
    "[PHONE_NUMBER:617-555-0246].",
    "Triage nurse reached the on-call interpreter at "
    "[PHONE_NUMBER:800-555-0109 ext 4412] for a Portuguese speaker.",
    # operative / procedure notes
    "Operative note: [PERSON:Dr. Yusuf al-Rashid] performed the "
    "laparoscopic cholecystectomy on [DATE_TIME:June 9, 2026] with "
    "[PERSON:Dr. M. Kowalczyk] assisting.",
    "Consent witnessed by [PERSON:Beatrice Lindqvist], RN, and faxed "
    "to the surgical coordinator at [PHONE_NUMBER:(781) 555-0168].",
    "Specimen labeled and sent; pathology will call "
    "[PHONE_NUMBER:508 555 0177] with preliminary results "
    "[DATE_TIME:tomorrow morning].",
    # medication reconciliation / pharmacy
    "Pharmacy flagged an interaction; [PERSON:Theodore Vance] confirmed "
    "he stopped the amiodarone on [DATE_TIME:May 21st] per his "
    "cardiologist in [LOCATION:Providence].",
    "Refill request forwarded to the mail-order pharmacy; confirmation "
    "sent to [EMAIL_ADDRESS:ted.vance+rx@inboxmail.com].",
    # transcribed voicemail
    "Voicemail transcription: 'Hi, this is [PERSON:Janice Thibodeaux] "
    "calling about my mother, please call me back at "
    "[PHONE_NUMBER:985-555-0123] before [DATE_TIME:Friday].'",
    "Second voicemail from [PERSON:Mr. O'Donnell] on "
    "[DATE_TIME:03/14/2026]; prefers email at "
    "[EMAIL_ADDRESS:sean.odonnell@postbox.ie].",
    # social work / hospice
    "Social work met with [PERSON:Grace Nakamura] and her son; family "
    "relocating to [LOCATION:Sacramento] and requests records transfer "
    "by [DATE_TIME:the end of August].",
    "Hospice intake notes the patient is a devout [NRP:Catholic] and "
    "has asked for chaplain visits on Sundays.",
    "The family, practicing [NRP:Sikhs], request that the turban "
    "remain in place during any procedure; noted by "
    "[PERSON:Chaplain Andrea Foss].",
    "Interpreter services booked for a [NRP:Hmong] family meeting on "
    "[DATE_TIME:July 2, 2026] in [LOCATION:Fresno].",
    # billing / administrative correspondence
    "Billing dispute: statement mailed to [PERSON:Viktor Petrov] at "
    "his [LOCATION:Brookline] address returned undeliverable; updated "
    "email [EMAIL_ADDRESS:vpetrov1947@corremail.ru] on file.",
    "Prior authorization approved [DATE_TIME:2026-06-30]; reference "
    "faxed to [PHONE_NUMBER:+44 20 7946 0958] for the overseas insurer.",
    # French clinical prose (service language), new shapes
    "Compte rendu: Madame [PERSON:Anne-Sophie Lefebvre] demeurant à "
    "[LOCATION:Marseille] a été hospitalisée du [DATE_TIME:3 juin 2026] "
    "au [DATE_TIME:9 juin 2026].",
    "Le docteur [PERSON:Jean-Luc Moreau] transmettra le dossier; "
    "courriel [EMAIL_ADDRESS:jl.moreau@chu-exemple.fr], téléphone "
    "[PHONE_NUMBER:04 91 55 01 33].",
    "Patient d'origine [NRP:kabyle], suivi à [LOCATION:Toulouse], "
    "prochain rendez-vous le [DATE_TIME:15/09/2026].",
    # harder name shapes: initials, particles, hyphens
    "Path report countersigned by [PERSON:A. J. Vandenberg] and "
    "uploaded [DATE_TIME:April 30, 2026].",
    "Dialysis schedule confirmed for [PERSON:Maria de la Cruz]; "
    "transport from [LOCATION:New Rochelle] arranged on "
    "[DATE_TIME:Tuesdays and Thursdays].",
    "Guardian [PERSON:Liesel von Trapp-Hughes] signed; copy to the "
    "school nurse in [LOCATION:White Plains].",
    # multi-entity dense lines
    "Transfer summary: [PERSON:Ibrahim Diallo], from "
    "[LOCATION:Hartford] to [LOCATION:New Haven], accepted by "
    "[PERSON:Dr. Felicity Ahmed] on [DATE_TIME:June 17, 2026] — unit "
    "desk [PHONE_NUMBER:203-555-0144].",
    "Records release: [PERSON:Hannah Abramowitz] authorizes sending "
    "imaging to [EMAIL_ADDRESS:h.abramowitz@medrecords.example] and to "
    "her attorney in [LOCATION:Albany] before [DATE_TIME:12 August].",
    # clean sentences (false-positive pressure — no PHI at all)
    "Start lisinopril 10 mg daily; titrate to blood pressure below "
    "140 over 90 and repeat the basic metabolic panel in two weeks.",
    "Wound care performed; granulation tissue healthy, no odor or "
    "discharge, dressing changed per protocol.",
    "Colonoscopy normal to the cecum; recommend repeat screening per "
    "guideline intervals.",
    "Physical therapy to continue twice weekly focusing on gait "
    "stability and fall prevention.",
)

# ---- HELD-OUT split (ISSUE 7 satellite / ROADMAP carry-forward) ------------
# Written fresh for PR 7 and NEVER scored during any tuning round: no
# threshold, deny-word, cue, or recognizer change may be made against
# these spans — the moment one is, this block must be renamed a dev set
# and a new held-out split written (the fate that befell _MARKED_TEST in
# round 5).  Registers and shapes beyond both earlier splits: radiology
# and endoscopy reports, psychiatric/behavioral notes, discharge
# instructions addressed to the patient in second person, lab-callback
# and after-hours triage phone logs, school/work clearance forms,
# dietitian and wound-care consults, French appointment-reminder prose,
# diacritic and particle-heavy names, dotted/spaced phone formats,
# quoted-speech attributions, and sentence-initial dates.
_MARKED_HELDOUT: Sequence[str] = (
    # radiology / procedure reports
    "CT abdomen read by [PERSON:Dr. Søren Østergaard] on "
    "[DATE_TIME:2026-07-14]; wet read phoned to the floor at "
    "[PHONE_NUMBER:617.555.0155].",
    "Endoscopy: [PERSON:Marguerite Beauchamp-Laurent] tolerated the "
    "procedure; biopsies labeled and couriered to [LOCATION:Burlington] "
    "for processing.",
    "Comparison film from [DATE_TIME:November 2025] requested from the "
    "imaging center in [LOCATION:Nashua]; release signed by "
    "[PERSON:Mr. Takeshi Yamamoto].",
    # psychiatric / behavioral health
    # (the 988 crisis line is a public hotline, not PHI — deliberately
    # unmarked; masking it would not reduce leak risk)
    "Patient [PERSON:Caleb Wojciechowski] presents with low mood since "
    "[DATE_TIME:early June]; safety plan reviewed, partner aware, "
    "crisis line 988 provided.",
    "Group session attended; [PERSON:Yolanda Mbeki] reports improved "
    "sleep since relocating from [LOCATION:Dorchester] to her "
    "cousin's place.",
    # discharge instructions, second person
    "You should call [PERSON:Dr. Anaïs Dupont-Rivière] at "
    "[PHONE_NUMBER:413 555 0162] if the swelling returns before "
    "[DATE_TIME:your visit on August 4].",
    "Your follow-up is scheduled for [DATE_TIME:September 1, 2026] at "
    "the clinic in [LOCATION:Pawtucket]; bring this sheet with you.",
    # lab callback / after-hours phone log
    "After-hours log: spoke with [PERSON:Mrs. Eun-Ji Park] regarding "
    "the potassium result; she will recheck at the "
    "[LOCATION:Woonsocket] lab [DATE_TIME:tomorrow at 8:15].",
    "Critical value called to the covering resident, read back "
    "confirmed; patient's spouse [PERSON:Gerald Okonkwo-Hughes] "
    "notified at [PHONE_NUMBER:+1 (401) 555-0170].",
    # school / work clearance
    "Clearance form completed for [PERSON:Milo Castellanos Jr.]; may "
    "return to school in [LOCATION:Cranston] on [DATE_TIME:May 5th] "
    "with no gym for two weeks.",
    "Work note faxed to the employer; [PERSON:Ingrid Svensson] is "
    "restricted to light duty until [DATE_TIME:the 18th of July].",
    # dietitian / wound care consults
    "Dietitian consult: [PERSON:Fatima el-Amin] follows a [NRP:halal] "
    "diet; menu adjusted and education materials sent to "
    "[EMAIL_ADDRESS:f.elamin82@courriel.example].",
    "Wound care: undermining at 3 o'clock reduced; photos uploaded by "
    "[PERSON:Nurse Practitioner Dana Whitehorse] on "
    "[DATE_TIME:07/22/2026].",
    # French appointment-reminder prose (service language)
    "Rappel: votre rendez-vous avec le [PERSON:Dr Pham Nguyen] est "
    "fixé au [DATE_TIME:22 août 2026] à la clinique de "
    "[LOCATION:Nantes]; en cas d'empêchement appelez le "
    "[PHONE_NUMBER:02 40 55 01 44].",
    "La famille de [PERSON:Mme Aïcha Benkirane] demande un interprète "
    "arabe pour la consultation du [DATE_TIME:30/09/2026].",
    "Patient pratiquant [NRP:orthodoxe], demande un régime sans viande "
    "le vendredi; noté au dossier par l'infirmière [PERSON:Claire "
    "Vasseur].",
    # quoted speech / attribution shapes
    "Per the patient: 'my daughter [PERSON:Renata]' manages the pillbox "
    "and drives her from [LOCATION:Central Falls] every Thursday.",
    "Sister states the patient 'has not been himself since "
    "[DATE_TIME:the Fourth of July weekend]' and sleeps most days.",
    # sentence-initial dates, machine identifiers
    "[DATE_TIME:2026-08-02 06:40] vitals stable; overnight events none; "
    "awaiting placement coordination with [LOCATION:Attleboro] rehab.",
    "[DATE_TIME:March 1] labs reviewed with [PERSON:Dr. B. Okafor-"
    "Smith]; repeat lipid panel in twelve weeks, results to "
    "[EMAIL_ADDRESS:b.okaforsmith+labs@clinicmail.example].",
    # clean sentences (false-positive pressure — no PHI at all)
    "Increase the evening insulin by two units if fasting glucose "
    "exceeds one-eighty on three consecutive mornings.",
    "Gait steady with the rolling walker; stairs supervised only, "
    "home PT to continue twice weekly.",
    "No acute distress; lungs clear bilaterally; plan unchanged "
    "pending the culture results.",
    "Take the antibiotic with food and finish the full course even "
    "if you feel better sooner.",
)

EXAMPLES: List[Tuple[str, List[GoldSpan]]] = [_parse(m) for m in _MARKED]
DEV_EXAMPLES = EXAMPLES  # threshold-selection split
TEST_EXAMPLES: List[Tuple[str, List[GoldSpan]]] = [
    _parse(m) for m in _MARKED_TEST
]
HELDOUT_EXAMPLES: List[Tuple[str, List[GoldSpan]]] = [
    _parse(m) for m in _MARKED_HELDOUT
]


def _char_set(spans) -> set:
    chars: set = set()
    for s in spans:
        chars.update(range(s.start, s.end))
    return chars


def _prf(tp: int, fp: int, fn: int) -> Tuple[float, float, float]:
    p = tp / (tp + fp) if tp + fp else 0.0
    r = tp / (tp + fn) if tp + fn else 0.0
    f = 2 * p * r / (p + r) if p + r else 0.0
    return p, r, f


def _predict(engine, examples) -> List[list]:
    """``analyze_batch`` + overlap resolution — the spans the system
    actually MASKS (anonymize_text resolves overlapping recognizer
    results, highest score wins, before replacing; raw analyze output
    would double-count pattern collisions as typed FPs)."""
    from docqa_tpu.deid.engine import _resolve_overlaps

    texts = [t for t, _ in examples]
    return [_resolve_overlaps(rs) for rs in engine.analyze_batch(texts)]


def _score(examples, preds) -> Dict[str, object]:
    c_tp = c_fp = c_fn = 0
    gold_total = gold_hit = 0
    ent_tp: Dict[str, int] = {}
    ent_fp: Dict[str, int] = {}
    ent_fn: Dict[str, int] = {}
    for (_, gold), pred in zip(examples, preds):
        gchars = _char_set(gold)
        pchars = _char_set(pred)
        c_tp += len(gchars & pchars)
        c_fp += len(pchars - gchars)
        c_fn += len(gchars - pchars)
        gold_total += len(gold)
        for g in gold:
            if any(p.start < g.end and g.start < p.end for p in pred):
                gold_hit += 1
            matched = any(
                p.entity_type == g.entity_type
                and p.start < g.end
                and g.start < p.end
                for p in pred
            )
            key = g.entity_type
            if matched:
                ent_tp[key] = ent_tp.get(key, 0) + 1
            else:
                ent_fn[key] = ent_fn.get(key, 0) + 1
        for p in pred:
            if not any(
                p.entity_type == g.entity_type
                and p.start < g.end
                and g.start < p.end
                for g in gold
            ):
                ent_fp[p.entity_type] = ent_fp.get(p.entity_type, 0) + 1

    cp, cr, cf = _prf(c_tp, c_fp, c_fn)
    tp = sum(ent_tp.values())
    fp = sum(ent_fp.values())
    fn = sum(ent_fn.values())
    ep, er, ef = _prf(tp, fp, fn)
    per_entity = {}
    for e in sorted(set(ent_tp) | set(ent_fp) | set(ent_fn)):
        p, r, f = _prf(ent_tp.get(e, 0), ent_fp.get(e, 0), ent_fn.get(e, 0))
        per_entity[e] = {
            "precision": round(p, 3),
            "recall": round(r, 3),
            "f1": round(f, 3),
        }
    return {
        "examples": len(examples),
        "gold_spans": gold_total,
        "char_precision": round(cp, 3),
        "char_recall": round(cr, 3),
        "char_f1": round(cf, 3),
        "span_recall_any": round(gold_hit / max(gold_total, 1), 3),
        "entity_precision": round(ep, 3),
        "entity_recall": round(er, 3),
        "entity_f1": round(ef, 3),
        "per_entity": per_entity,
    }


def evaluate_deid(engine, examples=None) -> Dict[str, object]:
    """Run ``engine.analyze_batch`` over the (dev) eval set and score it.

    Works with any object exposing the Presidio-shaped ``analyze_batch``
    (``deid/engine.py``).  Returns a JSON-ready dict; see module docstring
    for metric semantics.
    """
    examples = examples if examples is not None else EXAMPLES
    return _score(examples, _predict(engine, examples))


def _bootstrap_f1_ci(
    examples, preds, n_boot: int = 1000, seed: int = 0
) -> Tuple[float, float]:
    """95% percentile bootstrap interval on entity F1, resampling
    EXAMPLES (the natural exchangeable unit — spans within a sentence
    are correlated).  Predictions are reused, so the engine runs once."""
    import numpy as _np

    rng = _np.random.default_rng(seed)
    n = len(examples)
    f1s = []
    for _ in range(n_boot):
        idx = rng.integers(0, n, n)
        f1s.append(
            _score(
                [examples[i] for i in idx], [preds[i] for i in idx]
            )["entity_f1"]
        )
    return (
        round(float(_np.percentile(f1s, 2.5)), 3),
        round(float(_np.percentile(f1s, 97.5)), 3),
    )


def evaluate_deid_split(
    engine, n_boot: int = 1000, seed: int = 0
) -> Dict[str, object]:
    """Three-split evaluation (VERDICT r4 item 5 → closed by ISSUE 7).

    * ``dev`` — the original 21-example split; the served acceptance
      threshold (``DEFAULT_NER_THRESHOLD``) was selected on its operating
      curve, so its numbers carry metric-selection optimism.
    * ``test`` — the SECOND dev split (key kept for report
      compatibility): written after the threshold froze, but round 5
      tuned deny-words and person-position cues against these spans, so
      ``test.entity_f1`` also carries tuning optimism — report it as a
      second dev number, never as held-out.
    * ``heldout`` — written fresh for PR 7 and never used in any tuning
      decision; THIS is the number to quote as generalization.  Both
      are reported side by side so the tuning-optimism gap is itself
      measured instead of hidden.
    """
    dev_preds = _predict(engine, DEV_EXAMPLES)
    test_preds = _predict(engine, TEST_EXAMPLES)
    test = _score(TEST_EXAMPLES, test_preds)
    lo, hi = _bootstrap_f1_ci(TEST_EXAMPLES, test_preds, n_boot, seed)
    test["entity_f1_ci95"] = [lo, hi]
    held_preds = _predict(engine, HELDOUT_EXAMPLES)
    heldout = _score(HELDOUT_EXAMPLES, held_preds)
    lo_h, hi_h = _bootstrap_f1_ci(HELDOUT_EXAMPLES, held_preds, n_boot, seed)
    heldout["entity_f1_ci95"] = [lo_h, hi_h]
    return {
        "dev": _score(DEV_EXAMPLES, dev_preds),
        "test": test,
        "heldout": heldout,
        "note": (
            "threshold selected on dev; 'test' is a SECOND dev set (r5 "
            "tuned deny-words/cues against its spans) and carries tuning "
            "optimism; 'heldout' was written for PR 7 and never scored "
            "during tuning — quote heldout.entity_f1 as the "
            "generalization number, and if any tuning decision is ever "
            "made against it, relabel it dev and write a fresh one"
        ),
    }
