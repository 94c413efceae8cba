"""Typed configuration tree for the whole framework.

The reference scatters ``os.getenv`` calls with inline defaults across every
service (``deid-service/anonymizer.py:20-24``, ``doc-ingestor/database.py:7-8``,
``llm-qa/main.py:66``) and centralizes config in only one service
(``synthese-comparative/core/config.py:5-23``).  Here the whole framework has a
single typed tree of frozen dataclasses with one env overlay, and fake-mode
flags are *injectable* (constructor arguments) rather than read-at-import —
the reference's read-at-import flags made its own tests awkward
(``synthese-comparative/tests/test_llm_client.py:45-47``).

Env overlay convention: ``DOCQA_<SECTION>__<FIELD>`` (double underscore), e.g.
``DOCQA_STORE__SHARD_CAPACITY=65536``, ``DOCQA_FLAGS__USE_FAKE_LLM=false``.
"""

from __future__ import annotations

import dataclasses
import os
from dataclasses import dataclass, field, fields
from typing import Any, Mapping, Optional, Tuple


def _env_bool(value: str) -> bool:
    return value.strip().lower() in ("1", "true", "yes", "on")


@dataclass(frozen=True)
class MeshConfig:
    """TPU mesh topology.  Axis names follow the scaling-book convention:
    ``data`` (batch/DP), ``model`` (TP over ICI).  A v5e-8 slice defaults to
    (data=1, model=8) for serving and (data=2, model=4) for training."""

    data_axis: str = "data"
    model_axis: str = "model"
    # -1 means "use all available devices on that axis product".
    data_parallel: int = 1
    model_parallel: int = -1
    # Force a platform for tests ("cpu") or leave None for auto.
    platform: Optional[str] = None


@dataclass(frozen=True)
class EncoderConfig:
    """MiniLM-class sentence encoder (replaces ``indexer.py:21-22`` and
    ``llm-qa/main.py:25`` — all-MiniLM-L6-v2, 384-d)."""

    vocab_size: int = 30522
    hidden_dim: int = 384
    num_layers: int = 6
    num_heads: int = 12
    mlp_dim: int = 1536
    max_seq_len: int = 512
    embed_dim: int = 384  # pooled output dim
    dtype: str = "bfloat16"
    normalize: bool = True  # cosine == L2 on normalized vectors (SURVEY appendix)
    # real-vocabulary file for imported checkpoints: vocab.txt (WordPiece,
    # MiniLM/BERT) / tokenizer.json / tokenizer.model.  None → hash fallback.
    tokenizer_path: Optional[str] = None
    # HF checkpoint DIRECTORY (config.json + safetensors + tokenizer) for
    # the serving runtime — the ergonomic the reference gets from a model
    # name (``indexer.py:21``: all-MiniLM-L6-v2).  When set, DocQARuntime
    # loads architecture + weights + vocabulary from here and this
    # config's architecture fields are ignored (models/hf_checkpoint.py).
    checkpoint_dir: Optional[str] = None


@dataclass(frozen=True)
class NERConfig:
    """Token-classification PHI tagger (replaces Presidio/spaCy,
    ``anonymizer.py:29-35``).  Labels follow the reference's 6-entity contract
    (``anonymizer.py:43``) in BIO scheme."""

    vocab_size: int = 30522
    hidden_dim: int = 256
    num_layers: int = 4
    num_heads: int = 8
    mlp_dim: int = 1024
    max_seq_len: int = 512
    entities: Tuple[str, ...] = (
        "PERSON",
        "PHONE_NUMBER",
        "EMAIL_ADDRESS",
        "DATE_TIME",
        "NRP",
        "LOCATION",
    )
    dtype: str = "bfloat16"
    # Serving-runtime tagger provenance: load cached params from params_path
    # if present/compatible, else train train_steps on the synthetic PHI
    # generator (training/ner.py) and cache.  train_steps=0 keeps random-init
    # weights — pipeline-plumbing mode only, never masks contextual PHI.
    params_path: Optional[str] = None
    train_steps: int = 1500
    # Document-register language for the PATTERN recognizers (the NER
    # tagger is model-bound and language-blind).  "fr" — the reference's
    # actual data language (NLP_LANG, deid-service/anonymizer.py:24) —
    # keeps the combined French+English register (French clinical prose
    # quotes English drug labels); "en" drops the French-only date and
    # d'origine cues whose lowercase forms would be dead weight on
    # English text.  Threaded end-to-end: pipeline → DeidEngine →
    # analyze/deidentify (VERDICT item 8).
    language: str = "fr"
    # cross-entropy weight on entity (non-O) labels: O is ~82 % of
    # supervised positions and a fresh tagger otherwise sits in the
    # all-O collapse for hundreds of steps (observed: 500 steps of the
    # unweighted loss served all-O)
    entity_loss_weight: float = 4.0

    @property
    def num_labels(self) -> int:
        return 1 + 2 * len(self.entities)  # O + B-/I- per entity


@dataclass(frozen=True)
class DecoderConfig:
    """Decoder-only generator (replaces Ollama/Mistral, ``llm-qa/main.py:66-69``).
    Defaults are a small smoke-size model; ``mistral_7b()`` gives the
    target-scale config."""

    vocab_size: int = 32000
    hidden_dim: int = 512
    num_layers: int = 4
    num_heads: int = 8
    num_kv_heads: int = 2  # GQA
    head_dim: int = 64
    mlp_dim: int = 1408
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    sliding_window: Optional[int] = None
    # weight-only quantization (models/quant.py): shrinks the weight tree
    # AND the bytes read per decode step — the configuration that fits a
    # Mistral-7B-class decoder on one 16 GB v5e chip.  quant_bits: 8 =
    # per-channel int8 (w8a16, ~7.2 GB at 7B); 4 = grouped int4 (w4a16,
    # ~3.6 GB at 7B — the q4 class the reference's Ollama runtime served)
    quantize_weights: bool = False
    quant_bits: int = 8
    # real-vocabulary file for imported checkpoints: tokenizer.json
    # (byte-level or metaspace BPE) or tokenizer.model (SentencePiece) —
    # text/bpe.py.  None → hash fallback (zero-egress default).
    tokenizer_path: Optional[str] = None
    # HF checkpoint DIRECTORY for the serving runtime — the ergonomic the
    # reference gets from ``ChatOllama(model="mistral")``
    # (``llm-qa/main.py:66-69``).  When set, DocQARuntime loads
    # architecture + weights + vocabulary from here; this config's
    # architecture fields are ignored but quantize_weights/quant_bits
    # still govern the serving precision (quantize-on-load).
    checkpoint_dir: Optional[str] = None
    # Instruction-format wrapper for text prompts (the reference's Ollama
    # applied Mistral's chat template internally, so its /ask prompts were
    # instruct-formatted).  A named alias ("mistral-inst") or any format
    # string containing "{prompt}".  None = raw prompts (base models, the
    # zero-egress default).  Applied by GenerateEngine.format_prompt on
    # every TEXT entry point (generate_texts, batcher submit_text) — id
    # entry points are never wrapped.
    chat_template: Optional[str] = None
    # ---- the block (models/latent.py) -----------------------------------
    # "gqa_swiglu": the Mistral / Llama block above.  "mla_moe": the
    # DeepSeek-V2 block — multi-head LATENT attention (the cache holds one
    # normed latent + one rotated key per token and layer, shared by every
    # head) and, past ``first_dense_layers``, a group-limited routed expert
    # MLP with shared experts.  Chosen at trace time; every field below is
    # read by that block alone.  There ``head_dim`` is the query/key width
    # (``qk_nope_head_dim + qk_rope_head_dim``) and ``num_kv_heads`` is 1.
    block: str = "gqa_swiglu"
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN RoPE scaling (ops/rope.yarn_inv_freq); factor 1 = plain RoPE
    rope_scaling_factor: float = 1.0
    rope_original_max_len: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    # routed experts: the router scores all ``num_experts``; this process
    # HOLDS the contiguous range [experts_held_start, + experts_held)
    # (expert parallelism: its chip's share) and computes their part of
    # the sum plus the shared experts; what absent experts would add is
    # left out.  experts_held 0 = all of them.
    first_dense_layers: int = 0
    num_experts: int = 0
    experts_per_token: int = 0
    expert_dim: int = 0
    num_shared_experts: int = 0
    expert_groups: int = 1
    expert_groups_per_token: int = 1
    routed_scale: float = 1.0
    experts_held_start: int = 0
    experts_held: int = 0
    # the router (models/routed.py, both blocks that route): how it scores
    # ("softmax" over the experts | "sigmoid" of each), a float32 bias a
    # routed layer adds for the CHOICE alone (``l{i}_router_bias``: never
    # to a weight), and whether the taken scores are normalised over the k
    # taken (+ 1e-20) before ``routed_scale``
    router_score: str = "softmax"
    router_bias: bool = False
    router_norm: bool = False
    # ---- "sparse_linear" (models/hybrid.py) ------------------------------
    # A stack of mixer kinds, one name per layer in ``mixer_types``
    # (``len == num_layers``): "linear" — decayed linear attention whose
    # whole past is one [heads, d, d] float32 state a LANE (no row a
    # token), RoPE, per-head output norm — and "sparse" — GQA softmax
    # attention (``num_heads`` / ``num_kv_heads`` / ``head_dim`` above, no
    # RoPE) that, once a sequence holds ``sparse_dense_len`` tokens, reads
    # only the ``sparse_topk`` blocks of ``sparse_block_size`` tokens a row
    # selects by its scores against mean-pooled keys (windows of
    # ``sparse_kernel_size`` every ``sparse_kernel_stride`` tokens); the
    # first ``sparse_init_blocks`` blocks and those over the last
    # ``sparse_window_size`` tokens are always taken.  Both mixers norm
    # q and k per head and gate their output (sigmoid).  muP scalings:
    # the embedding x ``scale_emb``, every residual add x ``scale_depth /
    # sqrt(num_layers)`` (0: plain adds), logits / (hidden_dim /
    # ``dim_model_base``) (0: unscaled).  Read by that block alone.
    mixer_types: Tuple[str, ...] = ()
    linear_heads: int = 0
    linear_head_dim: int = 0
    scale_emb: float = 1.0
    scale_depth: float = 0.0
    dim_model_base: int = 0
    sparse_kernel_size: int = 32
    sparse_kernel_stride: int = 16
    sparse_block_size: int = 64
    sparse_topk: int = 64
    sparse_init_blocks: int = 1
    sparse_window_size: int = 2048
    sparse_dense_len: int = 8192
    # Two more mixer kinds of the same stack.  "attention": plain causal
    # GQA / MQA softmax attention over every row (no RoPE, no selection),
    # read at decode by the paged kernel.  "mamba": a Mamba-1 state-space
    # mixer (arXiv:2312.00752) with its own projections — in
    # (``hidden -> 2 x ssm_expand x hidden``), a depthwise causal conv of
    # ``ssm_conv_width`` taps, ``x`` (``-> ssm_dt_rank + 2 x
    # ssm_state_dim``, each part RMS-normed), ``dt`` and out — whose past
    # is, a LANE, the last ``ssm_conv_width - 1`` conv inputs and one
    # [ssm_state_dim, inner] float32 state (no row a token).  What the
    # attention kinds do around the softmax is what the file says:
    # per-head q / k norms (``qk_norm``), a sigmoid output gate
    # (``use_output_gate``), the linear mixer's per-head output norm
    # (``use_output_norm``).  ``tie_embeddings``: the tree holds no
    # ``lm_head``, logits are taken against ``tok_emb``.
    # "window": GQA softmax attention with RoPE over the last
    # ``sliding_window`` rows alone (a stack that names the kind sets the
    # field; none else may): its pools hold a RING of pages a lane — the
    # window plus one page — whatever the lane's length
    # (``engines/paged.py``).  ``num_experts`` > 1: the layers past
    # ``first_dense_layers`` route (the fields above, ``models/routed.py``)
    # and the leading ones keep the dense SwiGLU of ``mlp_dim``;
    # ``sandwich_norm`` (below) is read by this stack too.
    # "retention": gated power retention of degree 2 (arXiv:2507.04239) —
    # GQA with RoPE whose weights are squared scores under a decay the
    # token computes (a projection to the kv heads, ``l{i}_w_decay``),
    # divided by their sum; its whole past is one float32 state of
    # ``head_dim + 1`` by ``head_dim (head_dim + 1) / 2`` a kv head and
    # LANE, and no row a token.  It has no field of its own: heads, kv
    # heads, head width, ``rope_theta`` and ``qk_norm`` are the trunk's.
    qk_norm: bool = True
    use_output_gate: bool = True
    use_output_norm: bool = True
    tie_embeddings: bool = False
    ssm_state_dim: int = 16
    ssm_conv_width: int = 4
    ssm_dt_rank: int = 0
    ssm_expand: int = 2
    ssm_conv_bias: bool = True
    ssm_proj_bias: bool = False
    # ---- a looped trunk ("gqa_swiglu" alone reads these) -----------------
    # ``loop_steps`` T > 1: the whole stack of ``num_layers`` layers runs
    # T times over the SAME parameters (arXiv:2510.25741); the final norm
    # closes EVERY step and its output enters the next; a token keeps K
    # and V per (step, layer) — ``models/decoder.kv_entries`` — and the
    # tree holds an exit gate (``exit_gate_w`` [hidden, 1], ``exit_gate_b``
    # [1]) that ``loop_exit_threshold`` 1.0 never evaluates (no step exits
    # early; a value under 1 is refused: a depth that differs by lane
    # needs a scheduler).  ``sandwich_norm``: a norm on each sublayer's
    # OUTPUT before its residual add, beside the two pre-norms (the stack
    # of mixer kinds reads it too).  At the defaults the block's programs
    # are what they were.
    loop_steps: int = 1
    sandwich_norm: bool = False
    loop_exit_threshold: float = 1.0

    @staticmethod
    def mistral_7b() -> "DecoderConfig":
        return DecoderConfig(
            vocab_size=32000,
            hidden_dim=4096,
            num_layers=32,
            num_heads=32,
            num_kv_heads=8,
            head_dim=128,
            mlp_dim=14336,
            max_seq_len=4096,
            rope_theta=1000000.0,
            sliding_window=4096,
        )

    @staticmethod
    def llama3_8b() -> "DecoderConfig":
        return DecoderConfig(
            vocab_size=128256,
            hidden_dim=4096,
            num_layers=32,
            num_heads=32,
            num_kv_heads=8,
            head_dim=128,
            mlp_dim=14336,
            max_seq_len=8192,
            rope_theta=500000.0,
        )


@dataclass(frozen=True)
class Seq2SeqConfig:
    """BART-class encoder-decoder (the architecture BASELINE config 4
    names for summarization: bart-large-cnn).  Layout is faithful to HF
    ``BartForConditionalGeneration`` — post-LN residuals, learned positions
    with the +2 padding offset, GELU, tied lm_head + final_logits_bias —
    so real safetensors import 1:1 (``models/seq2seq.py``).  Defaults are a
    smoke size; ``bart_large_cnn()`` is the target checkpoint's shape."""

    vocab_size: int = 1024
    d_model: int = 128
    enc_layers: int = 2
    dec_layers: int = 2
    num_heads: int = 4
    mlp_dim: int = 256
    max_src_len: int = 256
    max_tgt_len: int = 128
    pos_offset: int = 2  # BART's learned-position padding offset
    pad_id: int = 1  # BART convention: pad=1, bos=0, eos=2
    bos_id: int = 0
    eos_id: int = 2
    decoder_start_id: int = 2  # HF bart: decoding starts from eos
    # HF BART generation forces BOS as the first decoded token
    forced_bos_id: Optional[int] = None
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    # Generation policy; None = UNSET (engine decodes greedy/unconstrained,
    # and a checkpoint_dir's shipped policy is free to take effect) — a
    # set value always wins, including explicitly setting the engine
    # default (num_beams=1 forces greedy over a checkpoint that ships 4).
    # (Of bart-large-cnn's shipped generation config this implements
    # num_beams / length_penalty / forced_bos_token_id / min_length /
    # no_repeat_ngram_size; early_stopping is not — the loop runs to
    # EOS-or-horizon, which can only find better hypotheses than stopping
    # early.)
    num_beams: Optional[int] = None  # effective default 1 (greedy)
    length_penalty: Optional[float] = None  # effective default 1.0
    min_length: Optional[int] = None  # EOS masked below this; default 0
    no_repeat_ngram: Optional[int] = None  # n bans repeat n-grams; default 0
    # real-vocabulary file (tokenizer.json — bart-large-cnn ships byte-level
    # BPE).  None → hash fallback.
    tokenizer_path: Optional[str] = None
    # HF checkpoint DIRECTORY (bart-large-cnn layout) for the serving
    # runtime; when set, DocQARuntime's seq2seq summarizer loads
    # architecture + weights + vocabulary from here.
    checkpoint_dir: Optional[str] = None

    @staticmethod
    def bart_large_cnn() -> "Seq2SeqConfig":
        return Seq2SeqConfig(
            vocab_size=50264,
            d_model=1024,
            enc_layers=12,
            dec_layers=12,
            num_heads=16,
            mlp_dim=4096,
            max_src_len=1024,
            max_tgt_len=1024,
            forced_bos_id=0,
            num_beams=4,
            length_penalty=2.0,
            min_length=56,
            no_repeat_ngram=3,
        )


@dataclass(frozen=True)
class SummarizerConfig:
    """Clinical summarizer (BART-class role per BASELINE.json config 4).
    Implemented as instruction-prompted decoding on the generator; this config
    bounds the prompt/summary budget (the reference truncated instead:
    ``llm_client.py:26-30``)."""

    max_input_tokens: int = 3072
    max_summary_tokens: int = 512
    max_chunks: int = 5
    # "decoder": instruction-prompted decoding on the causal LM, sharing
    # its weights and the continuous batcher (default).  "seq2seq": a
    # dedicated BART-class encoder-decoder (Seq2SeqConfig) — the
    # architecture BASELINE config 4 names.
    backend: str = "decoder"


@dataclass(frozen=True)
class StoreConfig:
    """HBM-resident sharded vector store (replaces FAISS IndexFlatL2 +
    on-disk handoff, ``indexer.py:17-18,39`` / ``llm-qa/main.py:35-38``)."""

    dim: int = 384
    # Rows per device shard bucket.  Append buffer is shape-bucketed so adds
    # never trigger recompilation (SURVEY §7 hard part (a)).
    shard_capacity: int = 16384
    dtype: str = "bfloat16"
    score: str = "cosine"  # normalized dot == cosine == L2 ranking
    default_k: int = 3  # reference fan-in, llm-qa/main.py:101
    # Serving index tier: "exact" (one MXU matmul, optimal to ~1M rows) or
    # "tiered" (IVF over the compacted bulk + exact over the append tail,
    # index/tiered.py — the beyond-1M path).
    serving_index: str = "exact"
    # Serving nprobe: frontier-tuned against the measured recall target
    # (>= 0.95, not 1.0): recall CI lower bound >= 0.961 at nprobe=8
    # from 1M to 10M chunks on the int8 sharded tier (PR 15's sweep), and
    # PR 13's online frontier on a d=384 corpus recommended the same 8.
    # The old blind 48 probed ~6x the cells the target needs.  Re-tune
    # live via /api/retrieval's measured frontier +
    # TieredIndex.set_nprobe.
    ivf_nprobe: int = 8
    ivf_min_rows: int = 50_000  # below this the IVF tier stays off
    ivf_rebuild_tail: int = 100_000  # rebuild when the tail outgrows this
    # Bulk-tier cell storage: "int8" (per-row-scaled tiles — ~4x fewer
    # index bytes per chunk than the f32 build buffer, mesh-shardable,
    # the 10M-chunk HBM-resident layout) or "float" (store dtype cells,
    # exact scores, single-device only).  Quantization recall cost is
    # MEASURED, not assumed: the recallscope shadow scans the
    # full-precision store (obs/retrieval_observatory.py).
    ivf_storage: str = "int8"
    # auto-compaction: once this fraction of live+dead rows is tombstoned,
    # deletions trigger a compaction (tombstones cost a mask upload per
    # search and dilute IVF cells); 0 disables
    compact_threshold: float = 0.25


@dataclass(frozen=True)
class ChunkConfig:
    """Chunking policy.  Reference: fixed 500 chars, no overlap
    (``indexer.py:120``).  We keep that default and add overlap support."""

    chunk_chars: int = 500
    overlap_chars: int = 0


@dataclass(frozen=True)
class BrokerConfig:
    """Service-plane bus (replaces RabbitMQ queues ``raw_documents_queue`` /
    ``clean_documents_queue``, ``processing.py:8``, ``anonymizer.py:21-22``)."""

    backend: str = "memory"  # "memory" | "amqp"
    raw_queue: str = "raw_documents_queue"
    clean_queue: str = "clean_documents_queue"
    prefetch: int = 8  # reference forced 1 (anonymizer.py:97); we batch
    max_redelivery: int = 3  # reference dropped poison messages; we DLQ
    retry_backoff_s: float = 0.5  # base redelivery delay (doubles per attempt)
    amqp_host: str = "localhost"
    amqp_port: int = 5672


@dataclass(frozen=True)
class RegistryConfig:
    """Document-metadata registry (replaces Postgres ``documents`` table,
    ``doc-ingestor/models.py:5-12``).  SQLite default, URL override for
    Postgres.  No credentials in code (reference committed them,
    ``database.py:10``)."""

    url: str = "sqlite://"  # in-memory default; "sqlite:///path.db" for disk
    table: str = "documents"


@dataclass(frozen=True)
class DataConfig:
    """Startup data lifecycle — the reference's indexer reloaded its saved
    index on boot and bootstrapped ``default_data/*.csv`` on first start
    (``semantic-indexer/indexer.py:26-30,97-107``).  Here:

    * ``work_dir`` — persistence root.  The store snapshots under
      ``<work_dir>/index`` (atomic, versioned) and restores from it on boot;
      the trained NER params cache also defaults here.  None disables
      persistence (tests).
    * ``bootstrap_dir`` — CSV knowledge-base directory, indexed on first
      boot (only when the restored/fresh store is empty).
    * ``snapshot_every`` — snapshot after this many indexed documents
      (the reference rewrote the whole index after EVERY message,
      ``indexer.py:125``); 0 disables periodic snapshots (shutdown still
      snapshots when ``work_dir`` is set).
    """

    work_dir: Optional[str] = None
    bootstrap_dir: Optional[str] = None
    snapshot_every: int = 64


@dataclass(frozen=True)
class ServiceConfig:
    """HTTP surface.  Ports mirror the reference deployment
    (``start_all.bat:18-35``) with the synthese port fixed to match reality
    (the reference's default pointed at :8004 while llm-qa served :8001 —
    ``core/config.py:16-19`` vs ``start_all.bat:31``)."""

    ingest_port: int = 8000
    qa_port: int = 8001
    synthesis_port: int = 8005
    host: str = "0.0.0.0"
    request_timeout_s: float = 60.0
    # Tika-protocol extractor server for formats the in-process extractors
    # cannot read (scanned PDFs, legacy .doc, RTF...).  None = disabled;
    # the compose "extractor" profile provisions one and sets
    # DOCQA_SERVICE__EXTRACTOR_URL (reference: docker-compose.yml:34-38,
    # processing.py:15).
    extractor_url: Optional[str] = None


@dataclass(frozen=True)
class FlagsConfig:
    """Fake-mode flags (kept from ``core/config.py:22-23`` but injectable)."""

    use_fake_llm: bool = False
    use_fake_retrieval: bool = False
    use_fake_encoder: bool = False


@dataclass(frozen=True)
class ResilienceConfig:
    """Failure-path policy (docqa_tpu/resilience/, docs/RESILIENCE.md).

    The reference had none of this — services died on a missed call and
    requests queued without bound."""

    # end-to-end /ask budget, stamped at admission and threaded through
    # retrieval → dispatch → the continuous batcher; stages shed
    # (504/degrade) instead of queueing past it.  0 disables deadlines.
    request_deadline_s: float = 8.0
    # below this remaining budget the QA path skips generation entirely
    # and serves the degraded extractive answer (a decode round it cannot
    # finish in time would only waste a batcher lane)
    min_generate_budget_s: float = 0.5
    # in-place retry policy (resilience/policy.py) wrapping broker
    # publishes, checkpoint shard reads, and pipeline handlers
    retry_attempts: int = 3
    retry_base_delay_s: float = 0.05
    retry_max_delay_s: float = 2.0
    # per-dependency circuit breakers (resilience/breaker.py): trip after
    # this many consecutive failures; probe again after the reset timeout
    breaker_failure_threshold: int = 5
    breaker_reset_s: float = 30.0
    # cap on the degraded extractive answer built from retrieved chunks
    degraded_max_chars: int = 600


@dataclass(frozen=True)
class PoolConfig:
    """Replicated decode-engine pool (``engines/pool.py``; docqa-pool,
    docs/OPERATIONS.md "Replica pool").

    The pool wraps N continuous batchers behind one submit surface with
    a liveness contract per replica (heartbeat, canary, breaker),
    failover for queued requests, fail-fast for admitted ones, graceful
    drain for hot restarts, and optional hedged dispatch.  ``replicas=1``
    (the default) keeps single-batcher economics while still providing
    worker-death fail-fast, drain, and the /api/pool surface."""

    replicas: int = 1
    # per-replica batcher knobs; None = the batcher's own defaults
    # (gen.max_concurrent slots)
    n_slots: Optional[int] = None
    max_queue: int = 256
    # a worker iteration can legitimately contain a first-shape XLA
    # compile (tens of seconds on a real chip) — pre-warmed deployments
    # (generate.startup_warm_buckets=-1) can drop this for faster wedge
    # detection
    heartbeat_max_age_s: float = 60.0
    # synthetic 2-token canary generate per replica; its outcome feeds
    # the replica breaker so a slow/stuck replica stops receiving
    # traffic before real requests pile onto it
    canary_interval_s: float = 20.0
    canary_timeout_s: float = 30.0
    health_interval_s: float = 0.5
    # failover budget: how many replica hops a queued request may make
    # before failing typed (at-most-one by default)
    requeue_max_hops: int = 1
    # hedged dispatch: duplicate a request with no first token after a
    # p95-based delay onto a second replica; first token wins, the loser
    # is cancelled at its next admit round
    hedge: bool = False
    hedge_min_delay_s: float = 0.75
    hedge_warmup: int = 20
    # session-affine routing (docqa-prefix): a request carrying a
    # prefix_key prefers the replica hash(key) names, so one patient's
    # warm KV prefix blocks stay on the replica serving their session;
    # falls back to least-queued whenever the preferred replica is more
    # than affinity_max_queue_delta requests deeper than the shallowest
    # (affinity must never amplify a hotspot)
    session_affinity: bool = True
    affinity_max_queue_delta: int = 4


@dataclass(frozen=True)
class DispatchConfig:
    """Bounded async dispatch spine (``engines/spine.py``;
    docs/OBSERVABILITY.md "Device observatory").

    Every device dispatch in the process flows through one spine of
    ``n_lanes`` executor lanes — the number of threads concurrently
    inside jax dispatch/compile is bounded by construction, retiring
    the >= 3-concurrent-stream CPU-client deadlock class the
    ``dispatch_streams.json`` budget used to gate statically."""

    # concurrent device-dispatch lanes.  2 is the count
    # scripts/serve_cluster_loop.py measured clean on the CPU client, and
    # the count a 1x4 v5e mesh served chip_smoke.py's concurrent asks
    # with (no stall, zero spine errors — PR 21); raising it needs fresh
    # evidence from either.
    n_lanes: int = 2
    # bounded work-item queue: submitters are synchronous, so depth
    # tracks live submitting threads — saturation means a runaway
    # producer and fails typed (SpineSaturated)
    max_depth: int = 256
    # inline mode runs work items on the submitting thread (no lanes) —
    # the OFF arm of a dispatch-overhead A/B; never serve with it
    inline: bool = False
    # strict mode FULLY SERIALIZES device work: one lane runs at a time
    # and every item block_until_ready()s on it, so exactly one device
    # program is ever in flight.  None = auto: ON for the multi-device
    # CPU client — whose collective scheduler parks even at 2 concurrent
    # sharded dispatches (PR-6 notes: 1-in-4 pre-spine; reproduced
    # deterministically by serve_cluster_loop under load) — OFF for
    # single-device and real TPU runtimes, which keep n_lanes-bounded
    # concurrency and the async decode pipeline (observed clean on one
    # v5e chip and on a 1x4 v5e mesh with two lanes plus the warm-up
    # thread dispatching sharded programs at once: chip_smoke.py, PR 21).
    strict_sync: Optional[bool] = None
    # register compiled-program cost_analysis() FLOPs/bytes at boot so
    # /api/status reports per-stage MFU (a few background
    # lowerings; disable on hosts where tracing at boot is too dear)
    annotate_costs: bool = True


@dataclass(frozen=True)
class TelemetryConfig:
    """Time-series telemetry + SLO burn-rate policy (``obs/telemetry.py``
    / ``obs/slo.py``; docqa-telemetry, docs/OBSERVABILITY.md "Time
    series, SLOs, and /metrics").

    The sampler scrapes the live serving plane every ``sample_every_s``
    into ``interval_s × points`` rollup windows (default 10 s × 360 =
    one hour), serves them on ``GET /api/telemetry`` and as Prometheus
    text on ``GET /metrics``, and evaluates the /ask SLOs once per
    tick — a firing burn-rate alert flags the window's traces anomalous
    in the flight recorder (the "SLO burning → exact timelines" loop)."""

    enabled: bool = True
    interval_s: float = 10.0
    points: int = 360
    sample_every_s: float = 2.0
    # HBM working-set probe (GenerateEngine.decode_memory_analysis)
    # re-lowers and re-compiles per call: refresh rarely (first probe
    # one period after boot — never inside the warmup compile storm);
    # 0 disables
    hbm_refresh_s: float = 600.0
    # /ask objectives: p95 latency threshold, availability (non-5xx)
    # target, degraded-answer budget.  The p95 default tracks the
    # resilience deadline economics: well under request_deadline_s (8 s)
    # so the alert fires while requests still SUCCEED slowly, not only
    # once they shed.
    slo_ask_p95_ms: float = 2500.0
    slo_ask_availability: float = 0.99
    slo_ask_degraded_budget: float = 0.05
    # burn-rate evaluation: both windows (in rollup-window units) must
    # exceed burn_threshold to fire; short clears it after clear_windows
    # calm windows
    slo_short_windows: int = 2
    slo_long_windows: int = 30
    slo_burn_threshold: float = 4.0


@dataclass(frozen=True)
class RetrievalQualityConfig:
    """Retrieval-quality observatory (``obs/retrieval_observatory.py``;
    docqa-recallscope, docs/OBSERVABILITY.md "Retrieval quality").

    A deterministic 1-in-``sample_every`` fraction of tiered retrievals
    gets an asynchronous exact-scan shadow query on the spine's
    background stream; served-vs-exact comparisons yield windowed
    recall@k estimates with Wilson CIs (``/api/retrieval``, the
    ``retrieve_recall_*`` telemetry series), a recall SLO burn alert,
    and a measured nprobe recall/latency frontier with a recommendation
    for ``recall_target``."""

    enabled: bool = True
    # 1-in-N shadow sampling of tiered retrievals (deterministic seeded
    # hash — replayed workloads sample identical request indices).  The
    # overhead budget is 2% of qa_e2e p50 at this default.
    sample_every: int = 32
    seed: int = 0
    # per-QUERY comparisons retained per (tier, nprobe) estimate window
    window: int = 512
    # bounded shadow-job queue; a backlogged worker DROPS (counted) —
    # shadow evidence is sampled anyway, so dropping beats queueing
    max_pending: int = 8
    # every Nth sampled shadow also probes neighboring nprobe values
    # (frontier_factors x current nprobe, clamped to [1, n_clusters])
    frontier_every: int = 4
    frontier_factors: Tuple[float, ...] = (0.25, 0.5, 1.0, 2.0, 4.0)
    # minimum frontier comparisons (per-query, not per shadow job)
    # before a row can back a recommendation
    min_frontier_n: int = 5
    # the measured recall objective (ROADMAP item 2: ">= 0.95, not
    # 1.0"): drives the recommended nprobe AND the recall SLO objective
    recall_target: float = 0.95
    # apply the recommended nprobe live via TieredIndex.set_nprobe.
    # DEFAULT OFF: recommendation-only — an operator reads
    # /api/retrieval and decides (docs/OPERATIONS.md runbook)
    auto_apply_nprobe: bool = False
    # recall SLO burn policy (obs/slo.py default_retrieval_slos), in
    # telemetry rollup windows like the /ask SLOs
    slo_short_windows: int = 2
    slo_long_windows: int = 30
    slo_burn_threshold: float = 4.0
    slo_min_events: int = 6


@dataclass(frozen=True)
class GenerateConfig:
    """Decode-loop policy."""

    max_new_tokens: int = 256
    temperature: float = 0.0  # reference used temperature=0 (llm-qa/main.py:69)
    top_k: int = 0
    top_p: float = 1.0
    eos_id: int = 2
    pad_id: int = 0
    # SOLO-engine prefill bucketing (GenerateEngine): prompt lengths pad
    # to these buckets so a handful of compiled programs cover all
    # requests.  The continuous batcher no longer buckets prompts — its
    # ragged prefill packs mixed lengths into prefill_token_buckets below.
    prefill_buckets: Tuple[int, ...] = (128, 256, 512, 1024, 2048, 4096)
    # Ragged-prefill token budgets for the continuous batcher (engines/
    # serve.py + engines/paged.py): an admission round packs its prompts
    # (starts 128-aligned) into dispatch groups, each run at the
    # smallest budget that holds its LARGEST prompt and filled only up
    # to that budget (serve.partition_prefill_round) — a program's cost
    # grows faster than its rows, so several short prompts go as several
    # small dispatches and never sum their way into the full-capacity
    # program; short prompts ride along with a long one that needs the
    # large budget anyway.  The batcher always ADDS the
    # full packed cache capacity to this set (a maximal prompt must fit
    # one dispatch), so the WHOLE batcher prefill compile surface is
    # this-set-plus-full — one program per budget, regardless of how
    # prompt lengths mix — versus the old (2 shape families x
    # prefill_buckets) matrix.  The default single trickle budget keeps
    # the total at prefill<=2 + decode = <=3 programs at ANY cache
    # length (compile_budget.json gates the collapse and the <=3 total).
    prefill_token_buckets: Tuple[int, ...] = (512,)
    # startup warm depth: how many of the SMALLEST ragged token budgets
    # the runtime pre-compiles (plus the decode chunk) in the background
    # at boot via ContinuousBatcher.warmup().  -1 = every budget (a
    # deployment that wants zero compile surprises pays the full compile
    # bill up front); 0 = none.  The default keeps dev/CPU boots cheap;
    # the compile audit proves the full-set mechanism retrace-free
    # regardless (compile_budget.json).
    startup_warm_buckets: int = 1
    max_concurrent: int = 16  # continuous batching lanes (QPS 16 target)
    # tokens per batcher decode dispatch: larger chunks amortize the
    # per-dispatch host round-trip at the cost of coarser slot-retirement
    # granularity
    decode_chunk: int = 16
    # coalesced admission BY TIMER (ROADMAP A1 (a)), for arrivals the
    # service cannot see coming: once a round has popped its first request
    # and slots are still free, the batcher's worker waits this long for
    # the next arrival before it dispatches the round's prefill; every
    # arrival restarts the wait, and it ends at once when the slots are
    # full.  Asks that come through the HTTP layer need none of it: the
    # service counts them from the moment it takes them in
    # (app._ask_preamble -> ContinuousBatcher.expect_arrival), and a round
    # into an idle batcher gathers while that count is above zero — a
    # ward's clients asking together prefill as ONE round, and a request
    # with nobody behind it waits for nobody.  What the timer is still
    # for: callers that submit to the pool directly, or that reach the
    # service one after the other (each sent when the one before was
    # acknowledged).  Its price: a request that arrives alone starts this
    # much later, and live lanes decode this much later.  0 = off: with
    # nothing expected either, a round is whatever is queued when the
    # worker looks, bit for bit the behaviour before the option existed.
    admit_hold_ms: float = 0.0
    # paged KV cache (engines/paged.py; docs/OPERATIONS.md "Paged KV
    # cache"): tokens per KV block.  Smaller blocks waste less on the
    # last partial block per request but grow the block-table/alloc
    # churn; 16 matches the RPA paper's sweet spot.
    kv_block_size: int = 16
    # total KV tokens the shared HBM block pool holds.  None = worst-case
    # provisioning (n_slots x cache capacity — no request mix can ever
    # exhaust the pool, matching the old per-slot reservation byte for
    # byte).  Set BELOW that to overcommit: mixed real-world lengths
    # rarely sum to worst case, so the same HBM sustains more slots;
    # exhaustion then sheds typed (serve.BlockPoolExhausted) instead of
    # admitting work the pool cannot hold.
    kv_pool_tokens: Optional[int] = None
    # copy-on-write KV prefix cache (engines/paged.PrefixCache;
    # docs/OPERATIONS.md "Prefix cache"): admission maps a cached,
    # token-verified prompt prefix — keyed by the submitter's prefix_key,
    # e.g. /ask's (template hash, retrieved-chunk-set hash) — into the
    # new request's block table at refcount+1 and prefills only the
    # novel suffix.  Shared runs are full blocks and 128-aligned, so
    # warm output is bitwise-identical to a cold prefill (gated by
    # tests/test_prefix.py); the cache LRU-evicts under block-pool
    # pressure before any live work is shed.
    prefix_cache: bool = True
    # max cached prefixes per batcher replica (each entry pins its
    # blocks until evicted; at 1024 B/token and 128-token granularity
    # one align-unit costs 128 KB of pool HBM)
    prefix_cache_entries: int = 32
    # prompt-lookup speculative decoding (greedy only): verify width per
    # step; 0/1 disables.  Decode is HBM-bound, so a K-token verify costs
    # one weight read like a single step but emits the matched draft
    # prefix + 1 — RAG answers that quote retrieved context draft well
    # from the prompt's own bigrams.  Output-exact vs plain greedy by
    # construction (tests/test_speculative.py gates the equality), so it
    # ships on; what k buys on the chip is not measured yet (ROADMAP A8).
    speculative_k: int = 4


@dataclass(frozen=True)
class QoSConfig:
    """Multi-tenant QoS (docqa-qos; docs/OPERATIONS.md "Protect
    interactive traffic under overload"): weighted-fair admission by
    request class, KV preemption under block-pool pressure, and
    SLO-burn-driven batch deferral.  Policy state is served on
    /api/status; per-class preemption/deferral counters reach
    /api/telemetry and both /metrics dialects."""

    # master switch: False reverts every batcher to plain FIFO admission
    # with no preemption and no deferral (the pre-QoS behavior, bit for
    # bit)
    enabled: bool = True
    # admission weights: over a contended drain, classes are served in
    # this ratio (deficit WFQ in engines/qos.ClassQueue).  Weights shape
    # throughput SHARING; they are not the eviction ranks.
    weight_interactive: float = 8.0
    weight_batch: float = 2.0
    weight_background: float = 1.0
    # starvation-aging floor: a queue head older than this wins the next
    # admission slot outright regardless of weight (bounded starvation
    # for the 1-weight classes under an interactive burst); 0 disables
    aging_floor_s: float = 5.0
    # KV preemption under BlockPoolExhausted pressure: "off" never
    # evicts, "advisory" computes and counts would-be victims (the
    # preemption_candidates dry-run on /api/costs/sheds) without
    # evicting, "on" evicts lower-ranked holders' KV blocks and
    # requeues them (generated-so-far tokens preserved for re-prefill)
    preemption: str = "off"
    # a preemption victim whose deadline has less than this left cannot
    # survive a second prefill: it degrades typed instead of requeueing
    preempt_min_resume_s: float = 0.5
    # self-protection: while the /ask p95 or availability SLO burns,
    # defer batch-class admission (typed serve.DeferredByPolicy; relaxes
    # as the burn clears).  Background is never deferred — it carries
    # the pool's canaries.
    defer_batch_on_burn: bool = True


@dataclass(frozen=True)
class LexicalConfig:
    """Device-resident lexical (BM25-impact) tier + hybrid fusion
    (``index/lexical.py``, docqa-lexroute; docs/SHARDING.md "Lexical
    tier").  Exact-token recall — MRNs, phone numbers, drug names —
    that the dense encoder's semantic neighborhood misses."""

    # master switch: False skips building the tier entirely (no sink
    # registration, hybrid/lexical retrieve modes fall back to dense)
    enabled: bool = True
    # hashed term vocabulary (crc32 mod vocab_size; collisions are
    # counted, not resolved — at 128k slots a clinical corpus stays
    # sparse).  Power of two keeps the modulo cheap on host.
    vocab_size: int = 131072
    # impact-ordered terms kept per document tile row; terms beyond the
    # top tile_width by impact are dropped (counted in stats)
    tile_width: int = 32
    # BM25 shape parameters; ref_len replaces the corpus-average doc
    # length so incremental adds never rescale existing impacts
    k1: float = 1.5
    b: float = 0.75
    ref_len: int = 64
    # hybrid fusion mix: alpha * norm(dense) + (1-alpha) * norm(lexical)
    hybrid_alpha: float = 0.6
    # serving retrieve mode: "dense" | "lexical" | "hybrid".  Dense stays
    # the default per the advisory-first rule (PR 13): hybrid is promoted
    # only when the measured recall CI-low on the labeled mix beats
    # dense-only.
    serving_mode: str = "dense"


@dataclass(frozen=True)
class RouterConfig:
    """Confidence-gated answer routing (``engines/router.py``,
    docqa-lexroute; docs/OPERATIONS.md "Tune the answer router").
    Extractive/lookup questions are served straight from the index —
    the decoder is never dispatched and no KV slot is allocated."""

    # master switch: False sends every /ask down the generative path
    # (the pre-lexroute behavior, bit for bit)
    enabled: bool = True
    # text-stage decisions below this confidence take the generative
    # path; raise toward 1.0 to make extractive routing rarer/safer
    min_confidence: float = 0.7
    # post-retrieval evidence floor: routed-extractive demotes to
    # generative when the retrieved context covers less of the
    # question's content vocabulary than this
    evidence_min: float = 0.5


@dataclass(frozen=True)
class Config:
    mesh: MeshConfig = field(default_factory=MeshConfig)
    encoder: EncoderConfig = field(default_factory=EncoderConfig)
    ner: NERConfig = field(default_factory=NERConfig)
    decoder: DecoderConfig = field(default_factory=DecoderConfig)
    summarizer: SummarizerConfig = field(default_factory=SummarizerConfig)
    seq2seq: Seq2SeqConfig = field(default_factory=Seq2SeqConfig)
    store: StoreConfig = field(default_factory=StoreConfig)
    chunk: ChunkConfig = field(default_factory=ChunkConfig)
    broker: BrokerConfig = field(default_factory=BrokerConfig)
    registry: RegistryConfig = field(default_factory=RegistryConfig)
    data: DataConfig = field(default_factory=DataConfig)
    service: ServiceConfig = field(default_factory=ServiceConfig)
    flags: FlagsConfig = field(default_factory=FlagsConfig)
    generate: GenerateConfig = field(default_factory=GenerateConfig)
    resilience: ResilienceConfig = field(default_factory=ResilienceConfig)
    pool: PoolConfig = field(default_factory=PoolConfig)
    telemetry: TelemetryConfig = field(default_factory=TelemetryConfig)
    dispatch: DispatchConfig = field(default_factory=DispatchConfig)
    retrieval_quality: RetrievalQualityConfig = field(
        default_factory=RetrievalQualityConfig
    )
    qos: QoSConfig = field(default_factory=QoSConfig)
    lexical: LexicalConfig = field(default_factory=LexicalConfig)
    router: RouterConfig = field(default_factory=RouterConfig)


_SECTIONS = {f.name: f.type for f in fields(Config)}


def _coerce(raw: str, target_type: Any) -> Any:
    if target_type is bool:
        return _env_bool(raw)
    if target_type is int:
        return int(raw)
    if target_type is float:
        return float(raw)
    if target_type in (str,):
        return raw
    # Optional[...] / tuple — try int, float, then raw string.
    for cast in (int, float):
        try:
            return cast(raw)
        except ValueError:
            continue
    if raw.lower() in ("none", "null", ""):
        return None
    if raw.lower() in ("true", "false"):
        return _env_bool(raw)
    return raw


def load_config(
    env: Optional[Mapping[str, str]] = None,
    overrides: Optional[Mapping[str, Any]] = None,
) -> Config:
    """Build a Config from defaults + env overlay + explicit overrides.

    ``overrides`` maps dotted paths to values, e.g.
    ``{"store.shard_capacity": 1024, "flags.use_fake_llm": True}``.
    """
    env = os.environ if env is None else env
    cfg = Config()
    sections = {name: getattr(cfg, name) for name in _SECTIONS}

    prefix = "DOCQA_"
    for key, raw in env.items():
        if not key.startswith(prefix) or "__" not in key:
            continue
        section_name, _, field_name = key[len(prefix):].partition("__")
        section_name = section_name.lower()
        field_name = field_name.lower()
        section = sections.get(section_name)
        if section is None:
            continue
        by_name = {f.name: f for f in fields(section)}
        if field_name not in by_name:
            continue
        current = getattr(section, field_name)
        # None-default (Optional) fields carry no type to coerce to: use
        # the generic fallback (int → float → none/bool → raw string) so
        # DOCQA_SEQ2SEQ__NUM_BEAMS=4 arrives as 4, not "4" (str would
        # silently break every numeric Optional knob)
        target_type = type(current) if current is not None else object
        sections[section_name] = dataclasses.replace(
            section, **{field_name: _coerce(raw, target_type)}
        )

    if overrides:
        for path, value in overrides.items():
            section_name, _, field_name = path.partition(".")
            section = sections[section_name]
            sections[section_name] = dataclasses.replace(
                section, **{field_name: value}
            )

    return Config(**sections)
