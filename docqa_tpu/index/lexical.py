"""Lexical (inverted-impact) tier: exact-token recall beside the dense store.

Dense-only retrieval misses exact-token clinical queries — MRNs, dotted
phone numbers, hyphenated drug names, French jargon whose embedding
neighborhood is generic (ROADMAP item 3; NAIL, arXiv 2305.14499).  This
module adds a device-resident lexical tier the dense tiers' own mesh
discipline applies to:

* **Clinical tokenizer** (:func:`clinical_tokens`): case-fold, NFKD
  diacritic fold (French "résumé" == "resume"), digit-run joining so
  MRNs/phones survive punctuation ("01.42.34.56" and "01-42-34-56" both
  tokenize to ``0142345678``-style runs), hyphenated drug names emit the
  parts AND the joined form ("co-amoxiclav" -> co, amoxiclav,
  coamoxiclav).
* **Hashed vocabulary**: terms map to ``crc32(token) % vocab_size``
  slots (NEVER the builtin ``hash`` — PYTHONHASHSEED would make the
  index non-replayable, the determinism contract PR 19 audits).
  Collisions are *accounted* (:meth:`LexicalIndex.stats`), not resolved:
  at the default 128k-slot vocab a clinical corpus's few collisions cost
  recall the recallscope shadow scan can measure, which is cheaper than
  chasing pointers on the MXU.
* **Impact tiles**: each row packs its top ``tile_width`` terms as
  ``(term_id int32, impact int8)`` pairs — BM25-style impacts
  ``tf*(k1+1) / (tf + k1*(1-b+b*len/ref_len))`` quantized to int8 at a
  fixed ``(k1+1)/127`` scale.  ``ref_len`` is a config constant, not the
  live average doc length, so :meth:`add` is incremental and replay-
  deterministic (an avgdl-dependent impact would re-score the whole
  corpus on every append).  IDF is applied **query-side** from host
  document frequencies, folded into the f32 query weights together with
  the int8 descale — the device never needs a re-upload when N grows.
* **Mesh sharding**: tiles row-shard over the model axis under
  ``shard_map`` exactly like the int8 IVF tier (``index/ivf.py``), and
  the per-shard top-k merges through the SAME 2-gather budget
  (``ops/topk.py:sharded_topk``) — audited as program family
  ``retrieve_lexical_sharded`` in shard_budget.json: 1x1 collective-free,
  multi-device owes exactly the merge gather pair.
* **Scoring** accumulates in f32 via ``preferred_element_type`` on every
  matmul with an int8 operand (the dtype-flow lint contract).

The tier ingests through the ``VectorStore.register_index_sink`` seam,
so adds/deletes/compactions ride the same journal-replayed path as the
dense store and a crash replay converges both tiers (tests/test_lexical.py).
"""

from __future__ import annotations

import functools
import re
import threading
import unicodedata
import zlib
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from docqa_tpu.engines.spine import spine_run
from docqa_tpu.ops.topk import sharded_topk
from docqa_tpu.runtime.metrics import DEFAULT_REGISTRY, get_logger, span

log = get_logger("docqa.lexical")

NEG_INF = -1e30

# tile pad sentinel (-1) and query pad sentinel (-2) are DISTINCT: a
# padded query slot must never equality-match a padded tile slot, or
# every pad row would score tile_width phantom hits
_TILE_PAD = -1
_QUERY_PAD = -2

# row-count upload bucket (per shard): tiles re-upload on a version
# bump, so quantizing the padded row count keeps the jit shape stable
# while the corpus grows within a bucket
_ROW_BUCKET = 64

# query-term padding ladder (compiled-program reuse across query lengths)
_QUERY_TERM_BUCKETS = (8, 16, 32, 64)
_QUERY_BATCH_BUCKETS = (1, 4, 16)


# ---------------------------------------------------------------------------
# Clinical tokenizer
# ---------------------------------------------------------------------------

# join punctuation/whitespace BETWEEN digits: "01.42.34" / "01-42-34" /
# "01 42 34" -> "014234" (MRNs, FR phone groups); a letter boundary
# still splits, so "10mg" -> 10, mg stays two tokens
_DIGIT_JOIN = re.compile(r"(?<=\d)[.\-\s](?=\d)")
_TOKEN = re.compile(r"[a-z0-9]+")
_HYPHEN_WORD = re.compile(r"[a-z0-9]+(?:-[a-z0-9]+)+")


def clinical_tokens(text: str) -> List[str]:
    """Normalize + tokenize one document or query (EN/FR clinical text).

    case-fold -> NFKD + combining-mark strip (diacritic fold) -> digit-run
    join -> ``[a-z0-9]+`` split, plus one joined token per hyphenated
    compound.  Pure function of the text — no corpus state — so document
    and query tokenization can never drift."""
    if not text:
        return []
    t = unicodedata.normalize("NFKD", text.casefold())
    t = "".join(ch for ch in t if not unicodedata.combining(ch))
    t = _DIGIT_JOIN.sub("", t)
    toks = _TOKEN.findall(t)
    for m in _HYPHEN_WORD.finditer(t):
        toks.append(m.group(0).replace("-", ""))
    return toks


def term_slot(token: str, vocab_size: int) -> int:
    """Deterministic hashed vocab slot (crc32, not builtin ``hash`` —
    the replay witness runs under two PYTHONHASHSEEDs)."""
    return zlib.crc32(token.encode("utf-8")) % vocab_size


# ---------------------------------------------------------------------------
# Device kernels
# ---------------------------------------------------------------------------


def _score_lexical(term_ids, impacts, row_live, q_terms, q_weights):
    """Impact-tile scoring for a batch of term-encoded queries.

    term_ids [R, W] int32 (pad -1), impacts [R, W] int8, row_live [R]
    bool, q_terms [Q, T] int32 (pad -2), q_weights [Q, T] f32 (idf *
    query-tf * int8 descale; pad 0).  Returns scores [Q, R] f32 with
    dead/pad rows at -inf.

    Per query: an equality match ``q_terms == term_ids`` selects each
    row's matching impact slots; contracting the tile axis with int8
    ones and the term axis with the f32 weights are both MXU matmuls
    accumulating in f32 (``preferred_element_type`` — the dtype-flow
    contract)."""
    ones_w = jnp.ones((impacts.shape[1],), jnp.int8)

    def one_query(qt, qw):
        eq = qt[:, None, None] == term_ids[None, :, :]  # [T, R, W]
        masked = jnp.where(eq, impacts[None, :, :], jnp.int8(0))
        per_term = jax.lax.dot_general(
            masked, ones_w, (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [T, R]
        return jax.lax.dot_general(
            qw, per_term, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [R]

    scores = jax.vmap(one_query)(q_terms, q_weights)  # [Q, R]
    return jnp.where(row_live[None, :], scores, NEG_INF)


def _lexical_kernel(term_ids, impacts, row_live, q_terms, q_weights, *, k: int):
    """Single-device lexical top-k: score -> ``lax.top_k``.  Collective-
    free (shard_budget.json family ``retrieve_lexical_sharded`` @ 1x1)."""
    scores = _score_lexical(term_ids, impacts, row_live, q_terms, q_weights)
    return jax.lax.top_k(scores, min(k, scores.shape[-1]))


def _lexical_kernel_sharded(
    term_ids, impacts, row_live, q_terms, q_weights, *, k: int, axis: str
):
    """``shard_map`` body: each shard scores only the tile rows it owns,
    then the per-shard candidates (global row ids via the shard offset)
    merge through ``sharded_topk`` — exactly the 2-gather (vals + ids)
    budget the dense tiers pay, nothing else."""
    r_local = term_ids.shape[0]
    shard = jax.lax.axis_index(axis)
    scores = _score_lexical(term_ids, impacts, row_live, q_terms, q_weights)
    return sharded_topk(scores, shard * r_local, k, axis)


def lexical_specs(model_axis: str) -> Tuple[P, ...]:
    """``shard_map`` in_specs for the lexical kernel's five operands:
    tiles/impacts/liveness row-sharded over the model axis, the term-
    encoded queries replicated.  Shared by ``LexicalIndex._get_fn``, the
    hybrid fused program (``engines/retrieve.py``) and the shard audit
    (``analysis/shard_audit.py:retrieve_lexical_sharded``) so the
    audited layout IS the serving layout."""
    return (
        P(model_axis, None),  # term_ids [R, W]
        P(model_axis, None),  # impacts [R, W]
        P(model_axis),  # row_live [R]
        P(),  # q_terms (replicated)
        P(),  # q_weights (replicated)
    )


def build_lexical_search_program(mesh, k: int):
    """The lexical search program: impact-tile scoring -> exact top-k
    (sharded merge kernel when the mesh has model parallelism).  Returns
    the un-jitted callable with arity (term_ids, impacts, row_live,
    q_terms, q_weights) so both :class:`LexicalIndex` (which jits it per
    k) and the sharding audit (``analysis/shard_audit.py`` program
    ``retrieve_lexical_sharded``, which lowers it on virtual meshes to
    count its collectives against ``shard_budget.json``) build the exact
    same program."""
    sharded = mesh is not None and mesh.n_model > 1
    if not sharded:
        return functools.partial(_lexical_kernel, k=k)
    kernel = functools.partial(
        _lexical_kernel_sharded, k=k, axis=mesh.model_axis
    )

    def lexical_body(term_ids, impacts, row_live, q_terms, q_weights):
        return kernel(term_ids, impacts, row_live, q_terms, q_weights)

    return shard_map(
        lexical_body,
        mesh=mesh.mesh,
        in_specs=lexical_specs(mesh.model_axis),
        out_specs=(P(), P()),
        check_vma=False,
    )


def _bucket(n: int, ladder: Sequence[int]) -> int:
    for b in ladder:
        if n <= b:
            return b
    return ladder[-1]


# ---------------------------------------------------------------------------
# LexicalIndex
# ---------------------------------------------------------------------------


class LexicalIndex:
    """Incremental device-resident lexical tier over hashed impact tiles.

    Host master copy (int32 term ids, int8 impacts, f32 unquantized
    impacts for the exact shadow reference, bool liveness) grows under a
    lock exactly like ``VectorStore``; the device copy is a version-
    checked padded snapshot uploaded lazily on the ``lexical_search``
    spine stage.  Rows are addressed by the **dense store's row ids** —
    the tier ingests through ``VectorStore.register_index_sink``, so
    adds, tombstones and compaction renumbering stay in lockstep with
    the dense tier by construction (journal replay converges both).
    """

    def __init__(
        self,
        *,
        vocab_size: int = 1 << 17,
        tile_width: int = 32,
        k1: float = 1.5,
        b: float = 0.75,
        ref_len: int = 64,
        mesh=None,  # runtime.mesh MeshContext: shard tiles over model
    ) -> None:
        if vocab_size < 2:
            raise ValueError("vocab_size must be >= 2")
        if tile_width < 1:
            raise ValueError("tile_width must be >= 1")
        self.vocab_size = int(vocab_size)
        self.tile_width = int(tile_width)
        self.k1 = float(k1)
        self.b = float(b)
        self.ref_len = max(1, int(ref_len))
        self.mesh = mesh
        self._sharded = mesh is not None and mesh.n_model > 1
        self._lock = threading.RLock()
        cap = 0
        self._term_ids = np.full((cap, tile_width), _TILE_PAD, np.int32)
        self._impacts = np.zeros((cap, tile_width), np.int8)
        self._impacts_f = np.zeros((cap, tile_width), np.float32)
        self._live = np.zeros((cap,), bool)
        self._count = 0
        self._df = np.zeros((self.vocab_size,), np.int64)
        self._n_docs = 0  # docs that contributed df (includes deleted)
        self._slot_owner: Dict[int, str] = {}
        self._collided_slots: set = set()
        self._n_truncated_terms = 0
        self._n_empty_docs = 0
        self._version = 0
        # device snapshot: (version, r_pad, term_ids, impacts, row_live)
        self._dev: Optional[Tuple[Any, ...]] = None
        self._fns: Dict[int, Any] = {}

    # -- ingest (VectorStore index-sink protocol) ---------------------------

    def on_add(self, row_ids: Sequence[int], metadata: Sequence[Dict[str, Any]]):
        """Index-sink add hook: rows appended to the dense store arrive
        here with their store row ids and metadata (text under
        ``text_content``, the pipeline's chunk payload key)."""
        texts = [
            str((md or {}).get("text_content", "") or "") for md in metadata
        ]
        self.add(row_ids, texts)
        # snapshot-restore replays tombstoned rows through add() with
        # ``deleted`` set in their metadata — mirror the dense mask
        dead = [
            rid
            for rid, md in zip(row_ids, metadata)
            if (md or {}).get("deleted")
        ]
        if dead:
            self.on_delete(dead)

    def on_delete(self, row_ids: Sequence[int]) -> None:
        """Index-sink tombstone hook (mirrors the dense ``_deleted`` mask)."""
        with self._lock:
            for rid in row_ids:
                if 0 <= rid < self._count:
                    self._live[rid] = False
            self._version += 1

    def on_compact(self, keep: np.ndarray) -> None:
        """Index-sink compaction hook: ``keep`` is the dense store's
        boolean keep-mask over its pre-compaction rows; surviving rows
        renumber to ``np.nonzero(keep)`` order — the same renumbering
        the store applies, so row ids stay aligned."""
        keep = np.asarray(keep, bool)
        with self._lock:
            k = keep[: self._count]
            self._term_ids = self._term_ids[: self._count][k].copy()
            self._impacts = self._impacts[: self._count][k].copy()
            self._impacts_f = self._impacts_f[: self._count][k].copy()
            self._live = self._live[: self._count][k].copy()
            self._count = int(k.sum())
            self._version += 1

    def add(self, row_ids: Sequence[int], texts: Sequence[str]) -> None:
        """Incremental add: tokenize, accumulate per-slot tf, keep the
        top ``tile_width`` impacts per row.  Impacts use the FIXED
        ``ref_len`` (not live avgdl) so an append never re-scores
        existing rows — the replay-determinism requirement."""
        if len(row_ids) != len(texts):
            raise ValueError("row_ids and texts must align")
        if not row_ids:
            return
        with self._lock, span("lexical_add", DEFAULT_REGISTRY):
            top = max(max(row_ids) + 1, self._count)
            self._ensure_capacity(top)
            for rid, text in zip(row_ids, texts):
                self._add_one_locked(int(rid), text)
            self._count = max(self._count, top)
            self._version += 1

    def _ensure_capacity(self, n: int) -> None:
        cap = len(self._live)
        if n <= cap:
            return
        new_cap = max(64, cap * 2, n)
        w = self.tile_width

        def grow(arr, fill, dtype):
            out = np.full((new_cap, w), fill, dtype) if arr.ndim == 2 else (
                np.zeros((new_cap,), dtype)
            )
            out[: len(arr)] = arr
            return out

        self._term_ids = grow(self._term_ids, _TILE_PAD, np.int32)
        self._impacts = grow(self._impacts, 0, np.int8)
        self._impacts_f = grow(self._impacts_f, 0, np.float32)
        self._live = grow(self._live, False, bool)

    def _add_one_locked(self, rid: int, text: str) -> None:
        toks = clinical_tokens(text)
        self._live[rid] = True
        self._term_ids[rid, :] = _TILE_PAD
        self._impacts[rid, :] = 0
        self._impacts_f[rid, :] = 0.0
        if not toks:
            self._n_empty_docs += 1
            return
        tf: Dict[int, int] = {}
        for tok in toks:
            s = term_slot(tok, self.vocab_size)
            tf[s] = tf.get(s, 0) + 1
            owner = self._slot_owner.get(s)
            if owner is None:
                self._slot_owner[s] = tok
            elif owner != tok:
                self._collided_slots.add(s)
        dl = len(toks)
        k1, b = self.k1, self.b
        norm = k1 * (1.0 - b + b * dl / self.ref_len)
        pairs = []  # (impact f32, slot)
        for s, f in tf.items():
            pairs.append((f * (k1 + 1.0) / (f + norm), s))
        # deterministic tie-break on the slot id (dict order is insertion
        # order, itself deterministic, but be explicit)
        pairs.sort(key=lambda p: (-p[0], p[1]))
        if len(pairs) > self.tile_width:
            self._n_truncated_terms += len(pairs) - self.tile_width
            pairs = pairs[: self.tile_width]
        for j, (imp, s) in enumerate(pairs):
            self._term_ids[rid, j] = s
            self._impacts_f[rid, j] = imp
            q = int(round(127.0 * imp / (k1 + 1.0)))
            self._impacts[rid, j] = max(1, min(127, q))
            self._df[s] += 1
        self._n_docs += 1

    # -- query encoding -----------------------------------------------------

    def _descale(self) -> float:
        """Folds the int8 impact quantization back out on the query side."""
        return (self.k1 + 1.0) / 127.0

    def _encode_query_locked(self, text: str) -> List[Tuple[int, float]]:
        """(slot, weight) pairs for one query: weight = query-tf * idf *
        int8-descale.  Slots no live document ever emitted are dropped
        (they can only score 0)."""
        tf: Dict[int, int] = {}
        for tok in clinical_tokens(text):
            s = term_slot(tok, self.vocab_size)
            tf[s] = tf.get(s, 0) + 1
        n = max(self._n_docs, 1)
        descale = self._descale()
        out = []
        for s, f in tf.items():
            df = int(self._df[s])
            if df == 0:
                continue
            idf = float(np.log(1.0 + (n - df + 0.5) / (df + 0.5)))
            out.append((s, f * idf * descale))
        # widest-impact terms first so the bucket truncation (rare: >64
        # distinct query terms) drops the least informative ones
        out.sort(key=lambda p: (-p[1], p[0]))
        return out[: _QUERY_TERM_BUCKETS[-1]]

    def encode_queries(
        self, texts: Sequence[str]
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Term-encode a query batch to padded device operands
        ``(q_terms [Q, T] int32, q_weights [Q, T] f32)`` — also the
        operands the hybrid fused program takes (engines/retrieve.py)."""
        with self._lock:
            enc = [self._encode_query_locked(t) for t in texts]
        t_pad = _bucket(max((len(e) for e in enc), default=1) or 1,
                        _QUERY_TERM_BUCKETS)
        # batch axis: same overflow convention as the dense marshaller
        # (engines/encoder.py marshal_texts) — bucket inside the ladder,
        # exact size beyond it, never a silent truncation
        n_q = max(len(texts), 1)
        q_pad = (
            _bucket(n_q, _QUERY_BATCH_BUCKETS)
            if n_q <= _QUERY_BATCH_BUCKETS[-1]
            else n_q
        )
        q_terms = np.full((q_pad, t_pad), _QUERY_PAD, np.int32)
        q_weights = np.zeros((q_pad, t_pad), np.float32)
        for i, pairs in enumerate(enc):
            for j, (s, w) in enumerate(pairs):
                q_terms[i, j] = s
                q_weights[i, j] = w
        return q_terms, q_weights

    # -- device snapshot ----------------------------------------------------

    def _padded_rows(self, count: int) -> int:
        n_shards = self.mesh.n_model if self._sharded else 1
        chunk = _ROW_BUCKET * n_shards
        return max(chunk, -(-count // chunk) * chunk)

    def device_tiles(self):
        """Version-checked device snapshot ``(term_ids, impacts,
        row_live, count)`` — uploads (bounded, on the background rebuild
        stream) only when the host copy moved.  Returns None while the
        tier is empty."""
        with self._lock:
            count = self._count
            version = self._version
            if count == 0:
                return None
            dev = self._dev
            if dev is not None and dev[0] == version:
                return dev[1:]
            r_pad = self._padded_rows(count)
            w = self.tile_width
            term_ids = np.full((r_pad, w), _TILE_PAD, np.int32)
            impacts = np.zeros((r_pad, w), np.int8)
            live = np.zeros((r_pad,), bool)
            term_ids[:count] = self._term_ids[:count]
            impacts[:count] = self._impacts[:count]
            live[:count] = self._live[:count]

        def _upload_on_lane():
            # returns the uploaded arrays: strict mode must sync every
            # transfer before the lane frees (index/ivf.py discipline)
            if self._sharded:
                m = self.mesh
                specs = lexical_specs(m.model_axis)

                def put(arr, spec):
                    return jax.device_put(arr, NamedSharding(m.mesh, spec))

                return (
                    put(term_ids, specs[0]),
                    put(impacts, specs[1]),
                    put(live, specs[2]),
                )
            return (
                jnp.asarray(term_ids),
                jnp.asarray(impacts),
                jnp.asarray(live),
            )

        dev_arrays = spine_run(
            "lexical_search", _upload_on_lane, stream="rebuild"
        )
        snapshot = (version, *dev_arrays, count)
        with self._lock:
            # publish only if nothing moved during the upload; a racing
            # add re-uploads on its next search, and THIS search still
            # serves the consistent snapshot it just built
            if self._version == version:
                self._dev = snapshot
        return snapshot[1:]

    def _get_fn(self, k: int):
        fn = self._fns.get(k)
        if fn is None:
            fn = jax.jit(build_lexical_search_program(
                self.mesh if self._sharded else None, k
            ))
            self._fns[k] = fn
        return fn

    # -- search -------------------------------------------------------------

    def search(
        self, texts: Sequence[str], k: int = 10
    ) -> List[List[Tuple[float, int]]]:
        """Per query, ``(score, row_id)`` pairs ranked by lexical impact
        score; rows with no term overlap (score <= 0) are dropped —
        lexical evidence is exact-match evidence, an all-miss row is not
        a result.  One device dispatch on the ``lexical_search`` stage."""
        if not len(texts):
            return []
        tiles = self.device_tiles()
        if tiles is None:
            return [[] for _ in texts]
        term_ids, impacts, row_live, count = tiles
        q_terms, q_weights = self.encode_queries(texts)
        if not (q_terms != _QUERY_PAD).any():
            # no query term exists in the corpus: skip the dispatch
            return [[] for _ in texts]
        k_eff = min(k, count)
        fn = self._get_fn(k_eff)

        def _lexical_on_lane():
            v, i = fn(
                term_ids, impacts, row_live,
                jnp.asarray(q_terms), jnp.asarray(q_weights),
            )
            return np.asarray(v, np.float32), np.asarray(i)

        with span("lexical_search", DEFAULT_REGISTRY):
            vals, ids = spine_run("lexical_search", _lexical_on_lane)
        out: List[List[Tuple[float, int]]] = []
        for qi in range(len(texts)):
            row = []
            for score, rid in zip(vals[qi], ids[qi]):
                if score <= 0.0 or rid < 0 or rid >= count:
                    continue
                row.append((float(score), int(rid)))
            out.append(row)
        return out

    def host_topk(
        self,
        texts: Sequence[str],
        k: int,
        count_cap: Optional[int] = None,
    ) -> List[List[Tuple[int, float]]]:
        """Exact host-side reference scoring (full-precision f32
        impacts, no int8 quantization, no tile-width device layout
        shortcuts beyond the per-row truncation that defines the tier):
        the recallscope shadow ground truth for the ``lexical`` tier.
        ``count_cap`` freezes the row horizon at what the served
        dispatch saw."""
        with self._lock:
            count = self._count if count_cap is None else min(
                count_cap, self._count
            )
            term_ids = self._term_ids[:count].copy()
            impacts_f = self._impacts_f[:count].copy()
            live = self._live[:count].copy()
            enc = [self._encode_query_locked(t) for t in texts]
        out: List[List[Tuple[int, float]]] = []
        descale = self._descale()
        for pairs in enc:
            if count == 0 or not pairs:
                out.append([])
                continue
            scores = np.zeros((count,), np.float32)
            for s, w in pairs:
                # w folds the int8 descale in; the f32 reference undoes
                # it so ground truth scores full-precision impacts
                hit = term_ids == s  # [count, W]
                scores += (w / descale) * (impacts_f * hit).sum(axis=1)
            scores[~live] = NEG_INF
            order = np.argsort(-scores, kind="stable")[:k]
            out.append(
                [(int(r), float(scores[r])) for r in order if scores[r] > 0.0]
            )
        return out

    # -- accounting ---------------------------------------------------------

    def index_bytes(self) -> Dict[str, Any]:
        """Device-resident byte accounting (``/api/retrieval`` surface)."""
        count = self._count
        r_pad = self._padded_rows(count) if count else 0
        w = self.tile_width
        per_row = w * (4 + 1) + 1  # int32 ids + int8 impacts + bool live
        total = r_pad * per_row
        n_shards = self.mesh.n_model if self._sharded else 1
        return {
            "total_bytes": total,
            "bytes_per_chunk": round(total / max(count, 1), 2),
            "per_shard_bytes": total // n_shards,
            "shards": n_shards,
            "storage": "lexical_int8",
        }

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            live = int(self._live[: self._count].sum())
            return {
                "rows": self._count,
                "live_rows": live,
                "vocab_size": self.vocab_size,
                "tile_width": self.tile_width,
                "hash_collisions": len(self._collided_slots),
                "truncated_terms": self._n_truncated_terms,
                "empty_docs": self._n_empty_docs,
                "version": self._version,
                **self.index_bytes(),
            }
