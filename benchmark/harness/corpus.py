"""Seeded clinical corpus and the questions asked over it (stdlib only).

One generator serves both sides of the benchmark: the child that holds the
chip builds the index from :func:`patient_chunks`, the parent that drives
HTTP draws questions over the same patients from :func:`question`.  Both
derive everything from ``(seed, patient index)``, so the two processes
agree without exchanging a byte.

Shapes follow the repo's own material (``data/routing_mix.jsonl`` lookup
questions with their one-line documents, ``chip_smoke.py`` consultation
notes); the wording is this file's.

Invariants a lookup cell will rest on (PERF.md §7; those of the corpus
are tested in ``tests/benchmark/test_benchmark_traffic.py``):

* a patient's name (two tokens) is unique in the corpus and occurs in
  that patient's identity chunk alone (the notes say "le patient" and carry the patient
  id as metadata), so no other row can outscore the planted one in the
  lexical tier — with the name in the notes too, a long French note that
  shares the question's function words won 1 lookup in 202 (chip run,
  PR 24);
* the *identity* chunk of a patient holds every non-stopword token of every
  lookup question about that patient, in at most 32 distinct tokens (the
  lexical tier keeps the 32 strongest terms of a row);
* every generative question (``benchmark/questions/generative.json``)
  carries a reasoning cue, so the router's text stage sends it to the
  decoder.
"""

from __future__ import annotations

import random
from typing import Dict, List, Tuple

# 16 syllables, none of which can spell one of the router's reasoning or
# lookup cues across a join
_SYL = (
    "ka", "lo", "mi", "nu", "be", "do", "fi", "gu",
    "ta", "ro", "so", "pi", "zu", "pe", "jo", "ne",
)
_DRUGS = (
    ("amlodipine", (5, 10)), ("metformin", (500, 850, 1000)),
    ("ramipril", (2, 5, 10)), ("bisoprolol", (2, 5, 10)),
    ("atorvastatin", (10, 20, 40)), ("levothyroxine", (50, 75, 100)),
    ("amoxicillin", (500, 1000)), ("furosemide", (20, 40)),
    ("omeprazole", (10, 20, 40)), ("sertraline", (25, 50, 100)),
    ("warfarin", (1, 3, 5)), ("prednisone", (5, 20, 40)),
)
_CONDITIONS_FR = (
    "une hypertension artérielle", "un diabète de type 2",
    "une insuffisance cardiaque", "une bronchopneumopathie chronique",
    "une hypothyroïdie", "une fibrillation auriculaire",
    "une insuffisance rénale modérée", "un asthme ancien",
)
_CONDITIONS_EN = (
    "arterial hypertension", "type 2 diabetes", "heart failure",
    "chronic obstructive lung disease", "hypothyroidism",
    "atrial fibrillation", "moderate renal impairment",
    "long-standing asthma",
)
_SYMPTOMS_FR = (
    "céphalées intermittentes", "fatigue modérée en fin de journée",
    "dyspnée à l'effort", "toux productive", "œdèmes des membres inférieurs",
    "vertiges au lever", "douleurs lombaires", "palpitations nocturnes",
)
_SYMPTOMS_EN = (
    "intermittent headaches", "moderate fatigue late in the day",
    "shortness of breath on exertion", "a productive cough",
    "swelling of the lower limbs", "dizziness on standing",
    "lower back pain", "palpitations at night",
)
_WARDS = ("cardiologie", "pneumologie", "médecine interne", "endocrinologie",
          "néphrologie", "gériatrie")

def _word(code: int) -> str:
    a, b, c = code >> 8, (code >> 4) & 15, code & 15
    return (_SYL[a] + _SYL[b] + _SYL[c]).capitalize()


def patient_name(index: int) -> str:
    """Unique pronounceable "Given Surname" for patient ``index``
    (< 16**3): two fixed permutations of the index (odd multipliers:
    bijections) each pick three syllables.

    Two tokens, not one: the lexical tier hashes terms into 131,072 slots,
    so among 2048 single-token names some share a slot with another row's
    name or number; that row then ties with the planted one, and 1 lookup
    in ~1000 was demoted to the decoder (chip runs, PR 24).  No single row
    can collide on both tokens."""
    if not 0 <= index < 4096:
        raise ValueError("patient index out of range (0..4095)")
    return (_word((index * 40503 + 1234) % 4096) + " "
            + _word((index * 2654435761 + 977) % 4096))


def patient(seed: int, index: int) -> Dict[str, object]:
    """The seeded facts of one patient."""
    rng = random.Random(f"patient/{seed}/{index}")
    drug, doses = _DRUGS[rng.randrange(len(_DRUGS))]
    cond = rng.randrange(len(_CONDITIONS_FR))
    sym = rng.randrange(len(_SYMPTOMS_FR))
    return {
        "index": index,
        "patient_id": f"P-{index:05d}",
        "name": patient_name(index),
        "mrn": f"{rng.randrange(10**7, 10**8)}",
        "phone": f"{rng.randrange(200, 990)}-555-{rng.randrange(0, 10**4):04d}",
        "drug": drug,
        "dose": doses[rng.randrange(len(doses))],
        "condition": cond,
        "symptom": sym,
        "systolic": rng.randrange(118, 165),
        "diastolic": rng.randrange(70, 98),
        "hba1c": f"{rng.randrange(55, 89) / 10:.1f}".replace(".", ","),
        "ldl": f"{rng.randrange(7, 19) / 10:.1f}".replace(".", ","),
        "spo2": rng.randrange(88, 95),
        "days": rng.randrange(3, 9),
        "weeks": rng.randrange(2, 7),
        "months": rng.randrange(1, 7),
        "ward": _WARDS[rng.randrange(len(_WARDS))],
        "year": rng.randrange(2022, 2026),
        "month": rng.randrange(1, 13),
        "day": rng.randrange(1, 28),
    }


def identity_chunk(p: Dict[str, object]) -> str:
    """The chunk the lookup facts are planted in: 24 distinct tokens, in
    the words an MRN / phone / dosage question uses, EN and FR.

    The facts are stated twice, so that the chunk is as long as a note (84
    tokens of the program's tokenizer; the notes have 77 to 87).  A prompt
    is the template, the question and three retrieved chunks, and WHICH
    three is drawn with the seed (the encoder's weights are): with a
    43-token identity chunk a prompt that retrieved two of them had under
    257 tokens, took 256 packed rows and not 384, and shared a 512-row
    prefill dispatch with another such prompt — so the seed set how many
    dispatches a round ran (2 or 3, ~53 ms apart) and which small programs
    the warm phase built (PERF.md section 6, PR 33).  With chunks of one
    size every prompt takes 384 rows whatever was retrieved."""
    return (
        f"Registration: patient {p['name']}, MRN {p['mrn']}, numéro de "
        f"dossier {p['mrn']}. Phone number on file {p['phone']}, numéro de "
        f"téléphone {p['phone']}. Dosage / posologie: {p['drug']} "
        f"{p['dose']} mg. Fiche d'identité du patient {p['name']} : numéro "
        f"de dossier {p['mrn']} (MRN {p['mrn']}), téléphone {p['phone']} "
        f"(phone {p['phone']}), posologie {p['drug']} {p['dose']} mg, dosage "
        f"{p['drug']} {p['dose']} mg."
    )


def note_chunks(p: Dict[str, object]) -> List[Tuple[str, str]]:
    """Three clinical notes of one patient, each (doc_type, text) and at
    most 500 characters (``chunk.chunk_chars``)."""
    cf, ce = _CONDITIONS_FR[p["condition"]], _CONDITIONS_EN[p["condition"]]
    sf, se = _SYMPTOMS_FR[p["symptom"]], _SYMPTOMS_EN[p["symptom"]]
    consultation = (
        f"Compte rendu de consultation de {p['ward']}. Le patient "
        f"est suivi pour {cf} connue depuis {p['months']} ans. "
        f"Tension artérielle mesurée à {p['systolic']}/{p['diastolic']} mmHg "
        f"au cabinet, {sf} depuis {p['weeks']} semaines, pas de douleur "
        f"thoracique. Un traitement par {p['drug']} a été instauré, "
        f"{p['dose']} mg par jour le matin. Surveillance à domicile matin "
        f"et soir pendant {p['weeks']} semaines. Prochain contrôle dans "
        f"{p['months']} mois avec bilan rénal et ionogramme."
    )
    followup = (
        f"Follow-up visit, patient with known {ce}. Laboratory "
        f"results: HbA1c {p['hba1c']} %, LDL cholesterol {p['ldl']} g/L, "
        f"creatinine within range. Blood pressure came down to "
        f"{p['systolic'] - 12}/{p['diastolic'] - 6} mmHg on {p['drug']}, "
        f"well tolerated, no swelling. The patient reports {se}. Treatment "
        f"continued unchanged; clinical and laboratory review in "
        f"{p['months']} months, yearly eye examination to be scheduled."
    )
    stay = (
        f"Compte rendu d'hospitalisation en {p['ward']}. Patient "
        f"admis le {p['year']}-{p['month']:02d}-{p['day']:02d} "
        f"pour décompensation sur {cf}, saturation à {p['spo2']} % en air "
        f"ambiant, {sf}. Oxygénothérapie à 2 L/min et adaptation de "
        f"{p['drug']}. Évolution favorable en {p['days']} jours, saturation "
        f"à 97 %. Sortie à domicile, contrôle à {p['weeks']} semaines. "
        f"Points de vigilance : terrain fragile, observance, allergie "
        f"signalée aux macrolides."
    )
    return [("consultation", consultation), ("suivi", followup),
            ("hospitalisation", stay)]


def patient_chunks(seed: int, index: int) -> List[Dict[str, str]]:
    """Store metadata rows (with ``text_content``) for one patient."""
    p = patient(seed, index)
    rows = [("identite", identity_chunk(p))] + note_chunks(p)
    date = f"{p['year']}-{p['month']:02d}-{p['day']:02d}"
    return [
        {
            "doc_id": f"{p['patient_id']}-{i}",
            "patient_id": p["patient_id"],
            "doc_type": doc_type,
            "doc_date": date,
            "source": f"{doc_type}_{p['patient_id']}.txt",
            "text_content": text,
        }
        for i, (doc_type, text) in enumerate(rows)
    ]


def question(seed: int, template: str, patient_index: int) -> str:
    """``template`` (a line of ``benchmark/questions/<kind>.json``) asked
    about one patient."""
    p = patient(seed, patient_index)
    return template.format(name=p["name"], drug=p["drug"])
