"""NER fine-tuning: masked token-classification cross-entropy.

The reference never trains anything — contextual PHI detection comes from
Presidio's pretrained spaCy model (``deid-service/anonymizer.py:29-35``).
Zero-egress here means no pretrained weights, so the tagger is trained
in-framework on the synthetic generator (``deid/datagen.py``): one
jit-compiled step (DP over the ``data`` mesh axis when a mesh is given),
the same shape as the causal-LM step in ``training/train.py``.

The trained parameters are cached as an ``.npz`` so serving restarts load
instead of retrain (``load_or_train``); ``DeidEngine.trained`` is the
one-call consumer.
"""

from __future__ import annotations

import os
from typing import Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from docqa_tpu.config import NERConfig
from docqa_tpu.models.ner import init_ner_params, ner_forward
from docqa_tpu.runtime.mesh import MeshContext
from docqa_tpu.runtime.metrics import get_logger

log = get_logger("docqa.train.ner")

Params = Dict[str, jax.Array]


def ner_loss(
    params: Params,
    cfg: NERConfig,
    ids: jax.Array,  # [b, s]
    lengths: jax.Array,  # [b]
    labels: jax.Array,  # [b, s] BIO label ids
    mask: jax.Array,  # [b, s] 1.0 on supervised positions (first word token)
) -> jax.Array:
    logits = ner_forward(params, cfg, ids, lengths)  # [b, s, L] f32
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    # entity positions are ~18 % of the supervision; weighting them keeps
    # the optimizer out of the all-O collapse (NERConfig docstring)
    w = jnp.where(labels > 0, cfg.entity_loss_weight, 1.0) * mask
    return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)


def default_ner_optimizer(
    lr: float = 1e-3, steps: Optional[int] = None, warmup: int = 100
) -> optax.GradientTransformation:
    """AdamW with global-norm clipping; when ``steps`` is given the lr
    follows linear-warmup + cosine-decay (constant lr measured unstable:
    single-batch loss oscillated 0.37→0.73 over 500 steps)."""
    if steps:
        schedule = optax.warmup_cosine_decay_schedule(
            0.0, lr, min(warmup, max(steps // 10, 1)), steps, lr * 0.05
        )
    else:
        schedule = lr
    return optax.chain(
        optax.clip_by_global_norm(1.0),
        optax.adamw(schedule, b1=0.9, b2=0.95, weight_decay=0.01),
    )


def make_ner_train_step(
    cfg: NERConfig,
    optimizer: optax.GradientTransformation,
    mesh: Optional[MeshContext] = None,
):
    """(params, opt_state, batch) → (params, opt_state, loss), jit with
    donated state; batch is DP-sharded over ``data`` when a mesh is given
    (params replicated — the tagger is small, BASELINE config 2 is a
    batch-throughput workload, not a model-size one)."""

    def step(params, opt_state, ids, lengths, labels, mask):
        if mesh is not None:
            row = NamedSharding(mesh.mesh, P(mesh.data_axis, None))
            vec = NamedSharding(mesh.mesh, P(mesh.data_axis))
            ids = jax.lax.with_sharding_constraint(ids, row)
            lengths = jax.lax.with_sharding_constraint(lengths, vec)
            labels = jax.lax.with_sharding_constraint(labels, row)
            mask = jax.lax.with_sharding_constraint(mask, row)
        loss, grads = jax.value_and_grad(ner_loss)(
            params, cfg, ids, lengths, labels, mask
        )
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return jax.jit(step, donate_argnums=(0, 1))


def train_ner(
    cfg: NERConfig,
    *,
    steps: Optional[int] = None,
    batch_size: int = 32,
    seq: int = 128,
    lr: float = 2e-3,
    seed: int = 0,
    mesh: Optional[MeshContext] = None,
    log_every: int = 100,
) -> Params:
    """Fit the tagger on the synthetic PHI generator; returns params.

    Serving must window documents at the ``seq`` used here — position
    embeddings beyond it never receive gradient (``DeidEngine.trained``
    wires this through ``max_window``).
    """
    from docqa_tpu.deid.datagen import ner_tokenizer, sample_batch

    if steps is None:
        steps = cfg.train_steps
    if steps < 1:
        raise ValueError(
            f"train_ner needs steps >= 1, got {steps}; a 0-step 'trained' "
            "tagger would serve random weights (contextual-PHI leak)"
        )
    tokenizer = ner_tokenizer(cfg)
    seq = min(seq, cfg.max_seq_len)
    if mesh is not None and batch_size % mesh.n_data:
        batch_size += mesh.n_data - batch_size % mesh.n_data
    params = init_ner_params(jax.random.PRNGKey(seed), cfg)
    optimizer = default_ner_optimizer(lr, steps=steps)
    opt_state = optimizer.init(params)
    step_fn = make_ner_train_step(cfg, optimizer, mesh=mesh)
    rng = np.random.default_rng(seed)
    for i in range(steps):
        ids, lengths, labels, mask = sample_batch(
            rng, tokenizer, cfg, batch_size, seq
        )
        params, opt_state, loss = step_fn(
            params, opt_state, ids, lengths, labels, mask
        )
        if log_every and (i + 1) % log_every == 0:
            log.info("ner step %d/%d loss %.4f", i + 1, steps, float(loss))
    return params


# ---------------------------------------------------------------------------
# Span-level evaluation on the HELD-OUT lexicons (generalization, not recall
# of memorized surface forms).
# ---------------------------------------------------------------------------

def evaluate_ner(
    params: Params,
    cfg: NERConfig,
    *,
    n_examples: int = 64,
    seed: int = 1234,
    threshold: Optional[float] = None,
) -> Dict[str, float]:
    """Exact-span precision / recall / F1 against gold spans of synthetic
    notes filled from EVAL_LEXICONS (disjoint from training).

    Scores the TAGGER ALONE (``engine._ner_results`` with the deny-list
    veto off, not the merged analyze output): the cue regexes in
    ``deid/engine.py`` literally match several datagen templates, and the
    deny-list was built from past tagger false positives — including
    either would credit a collapsed or regressed model — this metric
    gates the training recipe and must not be maskable.  The threshold
    defaults to the SERVED operating point (engine.DEFAULT_NER_THRESHOLD)
    so the gate measures what production drops."""
    from docqa_tpu.deid.datagen import (
        EVAL_LEXICONS,
        generate_example,
        ner_tokenizer,
    )
    from docqa_tpu.deid.engine import DEFAULT_NER_THRESHOLD, DeidEngine

    engine = DeidEngine(
        cfg,
        tokenizer=ner_tokenizer(cfg),
        params=params,
        use_ner_model=True,
        ner_threshold=(
            DEFAULT_NER_THRESHOLD if threshold is None else threshold
        ),
        ner_deny_list=False,
    )
    rng = np.random.default_rng(seed)
    texts, golds = [], []
    for _ in range(n_examples):
        text, spans = generate_example(rng, EVAL_LEXICONS, gibberish_frac=0.0)
        texts.append(text)
        golds.append({(a, b, e) for a, b, e in spans})
    results = engine._ner_results(texts)
    tp = fp = fn = 0
    for rs, gold in zip(results, golds):
        pred = {
            (r.start, r.end, r.entity_type)
            for r in rs
            if r.entity_type in ("PERSON", "LOCATION", "NRP")
        }
        gold = {g for g in gold if g[2] in ("PERSON", "LOCATION", "NRP")}
        tp += len(pred & gold)
        fp += len(pred - gold)
        fn += len(gold - pred)
    precision = tp / max(tp + fp, 1)
    recall = tp / max(tp + fn, 1)
    f1 = 2 * precision * recall / max(precision + recall, 1e-9)
    return {"precision": precision, "recall": recall, "f1": f1}


# ---------------------------------------------------------------------------
# Persistence: flat .npz cache so serving restarts load instead of retrain.
# ---------------------------------------------------------------------------

def save_ner_params(
    path: str,
    params: Params,
    cfg: NERConfig,
    train_seq: int = 128,
    train_steps: Optional[int] = None,
) -> None:
    """``train_steps`` must be the steps ACTUALLY trained (a smoke run
    saving a 2-step tagger under a 1500-step fingerprint would later be
    served silently — the exact leak the fingerprint exists to stop)."""
    arrays = {k: np.asarray(v) for k, v in params.items()}
    arrays["__fingerprint__"] = np.asarray(
        _fingerprint(cfg, train_steps if train_steps is not None else cfg.train_steps)
    )
    # serving must window at the trained length — longer positions have
    # untrained position embeddings (see train_ner docstring)
    arrays["__train_seq__"] = np.asarray(train_seq)
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    tmp = f"{path}.tmp"
    with open(tmp, "wb") as f:
        np.savez(f, **arrays)
    os.replace(tmp, path)


def load_ner_params(
    path: str, cfg: NERConfig, steps: Optional[int] = None
) -> Optional[Params]:
    """None if missing or trained under a different architecture/recipe.
    ``steps``: the steps the CALLER would train with (defaults to
    ``cfg.train_steps``) — a cache trained with fewer is not a match."""
    if not os.path.exists(path):
        return None
    with np.load(path) as z:
        arrays = {k: z[k] for k in z.files}
    fp = arrays.pop("__fingerprint__", None)
    arrays.pop("__train_seq__", None)
    want = _fingerprint(cfg, steps if steps is not None else cfg.train_steps)
    if fp is None or fp.tolist() != want:
        log.warning("ner params at %s do not match config; retraining", path)
        return None
    return {k: jnp.asarray(v) for k, v in arrays.items()}


def load_ner_train_seq(path: str) -> Optional[int]:
    if not os.path.exists(path):
        return None
    with np.load(path) as z:
        if "__train_seq__" not in z.files:
            return None
        return int(z["__train_seq__"])


def _fingerprint(cfg: NERConfig, steps: int) -> list:
    from docqa_tpu.deid.datagen import DATA_VERSION

    return [
        cfg.vocab_size, cfg.hidden_dim, cfg.num_layers, cfg.num_heads,
        cfg.mlp_dim, cfg.max_seq_len, cfg.num_labels,
        # training-recipe fields: a cache trained under the collapsed
        # unweighted-loss recipe (or with fewer steps) must invalidate,
        # not serve an under-fit tagger — and a cache trained on an older
        # synthetic-data distribution (DATA_VERSION) likewise
        steps, int(cfg.entity_loss_weight * 100), DATA_VERSION,
    ]


def load_or_train(
    cfg: NERConfig,
    path: Optional[str] = None,
    **train_kw,
) -> Tuple[Params, int]:
    """(params, train_seq).  ``train_seq`` is the serving window bound.

    Loads the cache at ``path`` when it matches the config, else trains
    IN THIS PROCESS and writes the cache: an accelerator belongs to one
    process at a time, so the process that will serve is the only one
    that may train."""
    steps = train_kw.get("steps")
    if steps is None:
        steps = cfg.train_steps
    if path:
        params = load_ner_params(path, cfg, steps=steps)
        if params is not None:
            log.info("loaded ner params from %s", path)
            return params, load_ner_train_seq(path) or 128
    seq = min(train_kw.get("seq", 128), cfg.max_seq_len)
    params = train_ner(cfg, **train_kw)
    if path:
        save_ner_params(path, params, cfg, train_seq=seq, train_steps=steps)
        log.info("saved ner params to %s", path)
    return params, seq
