"""Real-vocabulary tokenizers (VERDICT round-3 item 3).

The reference delegates tokenization to Ollama (``llm-qa/main.py:66-69``)
and sentence-transformers (``semantic-indexer/indexer.py:21``); this
framework loads the checkpoint's own vocabulary files.  Zero-egress, so the
fixtures are built in-test (the ``test_hf_import.py`` pattern):

* byte-level + metaspace ``tokenizer.json`` fixtures are TRAINED with the
  independent ``tokenizers`` wheel, then every encode/decode is
  cross-validated token-for-token against that wheel — two implementations,
  one spec.
* the SentencePiece ``tokenizer.model`` fixture is serialized with a
  minimal protobuf writer (the ``sentencepiece`` wheel is not in the
  image) and checked for exact round-trips and Llama-convention specials.
"""

import json
import struct

import pytest

from docqa_tpu.config import DecoderConfig, GenerateConfig
from docqa_tpu.text.bpe import (
    BPETokenizer,
    SentencePieceTokenizer,
    gpt2_pre_tokenize,
    load_tokenizer,
)

tokenizers = pytest.importorskip("tokenizers")

CORPUS = [
    "Patient presents with hypertension and type 2 diabetes mellitus.",
    "Prescribed metformin 500mg twice daily; follow-up in 3 months.",
    "ECG shows normal sinus rhythm. Blood pressure 140/90 mmHg.",
    "The patient's history includes myocardial infarction in 2019.",
    "Lisinopril 10mg daily was added for blood pressure control.",
] * 20

TEXTS = [
    "Patient presents with hypertension.",
    "metformin 500mg twice daily",
    "  weird   spacing\tand\nnewlines  ",
    "unicode: café, naïve, 温度 40.1°C",
    "don't can't we'll they've",
    "BP 140/90; HR 72bpm!!!",
    "",
    " ",
    "a\n\n\nb",
]


@pytest.fixture(scope="module")
def bytelevel_json(tmp_path_factory):
    """Mini BART-style byte-level BPE trained by the independent wheel."""
    from tokenizers import Tokenizer, decoders, models, pre_tokenizers, trainers

    path = str(tmp_path_factory.mktemp("tok") / "bytelevel.json")
    tok = Tokenizer(models.BPE(unk_token="<unk>"))
    tok.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=False)
    tok.decoder = decoders.ByteLevel()
    trainer = trainers.BpeTrainer(
        vocab_size=400,
        special_tokens=["<s>", "<pad>", "</s>", "<unk>"],
        initial_alphabet=pre_tokenizers.ByteLevel.alphabet(),
        show_progress=False,
    )
    tok.train_from_iterator(CORPUS, trainer)
    tok.save(path)
    return path


@pytest.fixture(scope="module")
def metaspace_json(tmp_path_factory):
    """Mini Llama/Mistral-style export: no pre-tokenizer, ``" "→"▁"``
    normalizer, byte-fallback pieces."""
    from tokenizers import Tokenizer, decoders, models, normalizers, trainers

    path = str(tmp_path_factory.mktemp("tok") / "metaspace.json")
    tok = Tokenizer(models.BPE(unk_token="<unk>", byte_fallback=True))
    tok.normalizer = normalizers.Sequence(
        [normalizers.Prepend("▁"), normalizers.Replace(" ", "▁")]
    )
    byte_toks = [f"<0x{b:02X}>" for b in range(256)]
    trainer = trainers.BpeTrainer(
        vocab_size=700,
        special_tokens=["<unk>", "<s>", "</s>"] + byte_toks,
        show_progress=False,
    )
    tok.train_from_iterator(CORPUS, trainer)
    tok.save(path)
    # the trainer can only inject byte pieces as "special" tokens; real
    # Llama exports mark them BYTE (decodable) — flip the flag back
    blob = json.load(open(path))
    for t in blob["added_tokens"]:
        if t["content"].startswith("<0x"):
            t["special"] = False
    json.dump(blob, open(path, "w"))
    return path


def _their_metaspace(path):
    from tokenizers import Tokenizer, decoders

    tok = Tokenizer.from_file(path)
    tok.decoder = decoders.Sequence(
        [
            decoders.Replace("▁", " "),
            decoders.ByteFallback(),
            decoders.Fuse(),
            decoders.Strip(" ", 1, 0),
        ]
    )
    return tok


class TestByteLevel:
    def test_matches_independent_implementation(self, bytelevel_json):
        from tokenizers import Tokenizer

        theirs = Tokenizer.from_file(bytelevel_json)
        mine = BPETokenizer.from_tokenizer_json(bytelevel_json)
        assert mine.mode == "byte_level"
        for text in TEXTS:
            t_ids = theirs.encode(text).ids
            m_ids = mine.encode(text, add_specials=False)
            assert m_ids == t_ids, text
            assert mine.decode_ids(m_ids) == theirs.decode(t_ids), text

    def test_round_trip_exact(self, bytelevel_json):
        mine = BPETokenizer.from_tokenizer_json(bytelevel_json)
        for text in TEXTS:
            ids = mine.encode(text, add_specials=False)
            assert mine.decode_ids(ids) == text

    def test_specials_and_truncation(self, bytelevel_json):
        mine = BPETokenizer.from_tokenizer_json(bytelevel_json)
        ids = mine.encode("blood pressure control", add_specials=True)
        # trained with <s>/<pad>/</s>/<unk> at 0/1/2/3
        assert ids[0] == mine.bos_id and ids[-1] == mine.eos_id
        short = mine.encode("blood pressure control", max_len=4)
        assert len(short) == 4
        batch, lengths = mine.batch(["one", "two longer text"], max_len=8)
        assert batch.shape == (2, 8)
        assert lengths[1] >= lengths[0]

    def test_pre_tokenizer_scanner_grammar(self):
        # the documented GPT-2 grammar cases the scanner hand-implements
        assert gpt2_pre_tokenize("don't") == ["don", "'t"]
        assert gpt2_pre_tokenize("a  b") == ["a", " ", " b"]
        assert gpt2_pre_tokenize(" x") == [" x"]
        assert gpt2_pre_tokenize("ab 12!?") == ["ab", " 12", "!?"]
        assert gpt2_pre_tokenize("tail  ") == ["tail", "  "]


class TestMetaspace:
    def test_matches_independent_implementation(self, metaspace_json):
        theirs = _their_metaspace(metaspace_json)
        mine = BPETokenizer.from_tokenizer_json(metaspace_json)
        assert mine.mode == "metaspace"
        for text in [t for t in TEXTS if "\t" not in t and "\n" not in t]:
            t_ids = theirs.encode(text).ids
            m_ids = mine.encode(text, add_specials=False)
            assert m_ids == t_ids, text
            assert mine.decode_ids(m_ids) == theirs.decode(t_ids), text

    def test_byte_fallback_round_trip(self, metaspace_json):
        mine = BPETokenizer.from_tokenizer_json(metaspace_json)
        text = "température 39.5°C — naïve café 温度"
        ids = mine.encode(text, add_specials=False)
        assert mine.decode_ids(ids) == text

    def test_llama_convention_bos_only(self, metaspace_json):
        mine = BPETokenizer.from_tokenizer_json(metaspace_json)
        ids = mine.encode("hello", add_specials=True)
        assert ids[0] == mine.bos_id
        assert ids[-1] != mine.eos_id  # no eos appended by default


    def test_untemplated_prompt_specials_match_the_vocab(self, metaspace_json):
        """What a prompt built as plain text (no chat template) gets at
        its ends: BOS at the head, nothing at the tail — and a vocabulary
        without a BOS/EOS piece gets neither, whatever the flags say."""
        from docqa_tpu.service.qa import QA_TEMPLATE

        mine = BPETokenizer.from_tokenizer_json(metaspace_json)
        assert mine.add_eos is False  # sentencepiece lineage
        prompt = QA_TEMPLATE.format(
            context="metformin 500 mg twice daily", question="which dose?"
        )
        ids = mine.encode(prompt, add_specials=True)
        assert ids[0] == mine.bos_id and ids[-1] != mine.eos_id
        assert ids[1:] == mine.encode(prompt, add_specials=False)

        bare = BPETokenizer(
            {c: i for i, c in enumerate("abcdefgh?▁")},
            [],
            mode="metaspace",
            add_bos=False,
            add_eos=True,
        )
        assert bare.eos_id is None
        assert bare.encode("abc def?", add_specials=True) == bare.encode(
            "abc def?", add_specials=False
        )


def _sp_varint(n: int) -> bytes:
    out = b""
    while True:
        b = n & 0x7F
        n >>= 7
        out += bytes([b | (0x80 if n else 0)])
        if not n:
            return out


def _sp_field(no: int, wire: int, payload: bytes) -> bytes:
    return _sp_varint(no << 3 | wire) + payload


def _sp_piece(piece: str, score: float, ptype: int) -> bytes:
    raw = piece.encode()
    body = _sp_field(1, 2, _sp_varint(len(raw)) + raw)
    body += _sp_field(2, 5, struct.pack("<f", score))
    body += _sp_field(3, 0, _sp_varint(ptype))
    return _sp_field(1, 2, _sp_varint(len(body)) + body)


@pytest.fixture(scope="module")
def sp_model(tmp_path_factory):
    """Llama-convention mini ``tokenizer.model``: <unk>/<s>/</s> at 0/1/2,
    256 byte pieces, char + merged pieces with BPE-rank scores."""
    path = str(tmp_path_factory.mktemp("sp") / "tokenizer.model")
    pieces = [("<unk>", 0.0, 2), ("<s>", 0.0, 3), ("</s>", 0.0, 3)]
    pieces += [(f"<0x{b:02X}>", 0.0, 6) for b in range(256)]
    chars = list("▁theainsordlmpcugf.05")
    merged = [
        "▁t", "he", "▁the", "in", "en", "ti", "on", "▁pa", "ent",
        "▁pati", "▁patient", "▁m", "et", "for", "min", "▁metformin",
        "▁5", "00", "mg", "▁500mg",
    ]
    pieces += [(s, -1.0, 1) for s in chars]
    pieces += [(s, -2.0 - r, 1) for r, s in enumerate(merged)]
    blob = b"".join(_sp_piece(*p) for p in pieces)
    trainer_spec = _sp_field(3, 0, _sp_varint(2))  # model_type = BPE
    blob += _sp_field(2, 2, _sp_varint(len(trainer_spec)) + trainer_spec)
    with open(path, "wb") as f:
        f.write(blob)
    return path


class TestSentencePiece:
    def test_loads_and_identifies_specials(self, sp_model):
        sp = load_tokenizer(sp_model)
        assert isinstance(sp, SentencePieceTokenizer)
        assert (sp.unk_id, sp.bos_id, sp.eos_id) == (0, 1, 2)
        assert sp.model_type == 2  # BPE per the serialized TrainerSpec

    def test_known_segmentation(self, sp_model):
        sp = load_tokenizer(sp_model)
        ids = sp.encode("the patient", add_specials=False)
        assert [sp._inv[i] for i in ids] == [
            "▁the", "▁", "p", "a", "ti", "ent",
        ]

    def test_round_trip_with_byte_fallback(self, sp_model):
        sp = load_tokenizer(sp_model)
        for text in ["the patient", "metformin 500mg", "café x", "zq!?"]:
            ids = sp.encode(text, add_specials=False)
            assert sp.decode_ids(ids) == text, text

    def test_bos_prepended(self, sp_model):
        sp = load_tokenizer(sp_model)
        ids = sp.encode("the", add_specials=True)
        assert ids[0] == sp.bos_id


class TestEngineWiring:
    def test_generate_engine_adopts_real_vocab_ids(self, metaspace_json):
        """A decoder configured with a tokenizer file must stop decoding on
        the CHECKPOINT's eos id, not the hash-fallback default."""
        mine = BPETokenizer.from_tokenizer_json(metaspace_json)
        cfg = DecoderConfig(
            vocab_size=mine.vocab_size,
            hidden_dim=32,
            num_layers=1,
            num_heads=4,
            num_kv_heads=4,
            head_dim=8,
            mlp_dim=64,
            max_seq_len=64,
            dtype="float32",
            tokenizer_path=metaspace_json,
        )
        from docqa_tpu.engines.generate import GenerateEngine

        eng = GenerateEngine(cfg, gen=GenerateConfig(max_new_tokens=4))
        assert isinstance(eng.tokenizer, BPETokenizer)
        assert eng.gen.eos_id == eng.tokenizer.eos_id
        out = eng.generate_texts(["the patient"])
        assert len(out) == 1 and isinstance(out[0], str)

    def test_seq2seq_engine_loads_tokenizer_file(self, bytelevel_json):
        from docqa_tpu.config import Seq2SeqConfig
        from docqa_tpu.engines.seq2seq import Seq2SeqEngine

        mine = BPETokenizer.from_tokenizer_json(bytelevel_json)
        cfg = Seq2SeqConfig(
            vocab_size=mine.vocab_size,
            d_model=32,
            enc_layers=1,
            dec_layers=1,
            num_heads=4,
            mlp_dim=64,
            max_src_len=64,
            max_tgt_len=16,
            dtype="float32",
            tokenizer_path=bytelevel_json,
        )
        eng = Seq2SeqEngine(cfg)
        assert isinstance(eng.tokenizer, BPETokenizer)
        out = eng.generate_texts(["blood pressure was controlled"], max_new_tokens=4)
        assert len(out) == 1 and isinstance(out[0], str)
