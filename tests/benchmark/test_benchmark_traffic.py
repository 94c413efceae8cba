"""The corpus and the questions, held against the PROGRAM's own router
and lexical tokenizer."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "benchmark"))

from harness import corpus, traffic  # noqa: E402

GENERATIVE = traffic.load(
    os.path.join(os.path.dirname(os.path.dirname(HERE)), "benchmark",
                 "traffic", "rag_closed.json")
)["templates"]["generative"]

N_PATIENTS = 2048  # what the configurations hold


def test_names_are_unique_over_the_whole_index_range():
    assert len({corpus.patient_name(i) for i in range(4096)}) == 4096
    with pytest.raises(ValueError):
        corpus.patient_name(4096)


def test_the_same_seed_gives_the_same_corpus_and_another_seed_another():
    assert corpus.patient_chunks(5, 17) == corpus.patient_chunks(5, 17)
    assert corpus.patient_chunks(5, 17) != corpus.patient_chunks(6, 17)
    assert corpus.patient_chunks(2**31 + 9, 3)  # seeds wider than 32 bits


@pytest.mark.parametrize("index", [0, 1, 777, 2047])
def test_chunks_fit_the_chunker_and_only_the_identity_chunk_names_the_patient(index):
    rows = corpus.patient_chunks(3, index)
    name = corpus.patient_name(index).lower()
    other = corpus.patient_name((index + 1) % N_PATIENTS).lower()
    assert len(rows) == 4
    # the name lives in the identity chunk alone: no other row can
    # outscore the planted one in the lexical tier
    assert name in rows[0]["text_content"].lower()
    for row in rows:
        assert len(row["text_content"]) <= 500
        assert other not in row["text_content"].lower()
        assert row["patient_id"] == f"P-{index:05d}"
    for row in rows[1:]:
        assert name not in row["text_content"].lower()


def test_the_identity_chunk_fits_the_lexical_tier_and_holds_the_planted_facts():
    """What a lookup cell will rest on (PERF.md §7): the planted facts and
    the words a lookup uses sit in one row of at most 32 distinct terms,
    the number the lexical tier keeps of a row."""
    from docqa_tpu.index.lexical import clinical_tokens

    for index in range(0, N_PATIENTS, 41):
        p = corpus.patient(9, index)
        tokens = set(clinical_tokens(corpus.identity_chunk(p)))
        assert len(tokens) <= 32
        wanted = clinical_tokens(
            f"{p['name']} MRN {p['mrn']} phone {p['phone']} dosage "
            f"posologie {p['drug']} numéro dossier téléphone"
        )
        assert set(wanted) <= tokens


@pytest.mark.parametrize("template", GENERATIVE)
def test_every_generative_question_reaches_the_decoder(template):
    from docqa_tpu.engines.router import ROUTE_GENERATIVE, AnswerRouter

    router = AnswerRouter()
    for index in range(0, N_PATIENTS, 41):
        q = corpus.question(9, template, index)
        assert router.decide(q).route == ROUTE_GENERATIVE, q


def test_every_prompt_of_the_generative_mix_takes_the_same_packed_rows():
    """ISSUE 33: which three chunks a question retrieves is drawn with the
    seed (the encoder's weights are), so the chunks are of one size: the
    template, any question and ANY three chunks of the corpus make a prompt
    that packs into the same number of ``RAGGED_ALIGN`` rows — three such
    prompts are three 512-row prefill dispatches on every seed, never two."""
    from docqa_tpu.ops.attention import RAGGED_ALIGN
    from docqa_tpu.service.qa import QA_TEMPLATE
    from docqa_tpu.text.tokenizer import default_tokenizer

    tok = default_tokenizer(32000, vocab_path=None)  # the cells' tokenizer
    chunks = [row["text_content"] for index in range(0, N_PATIENTS, 13)
              for row in corpus.patient_chunks(9, index)]
    def size(text):
        return len(tok.encode(text, add_specials=False))

    rows = set()
    for three in ([min(chunks, key=size)] * 3, [max(chunks, key=size)] * 3):
        for template in GENERATIVE:
            for index in (0, 777, 2047):
                prompt = QA_TEMPLATE.format(
                    context="\n\n".join(three),
                    question=corpus.question(9, template, index))
                rows.add(-(-len(tok.encode(prompt)) // RAGGED_ALIGN))
    assert rows == {3}  # 257..384 tokens: 384 rows, two never share 512
