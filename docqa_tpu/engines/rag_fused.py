"""Single-sync RAG: retrieval → prompt assembly → decode, chained on device.

The classic `/ask` path costs two synchronization points: fetch the top-k
rows (the host needs the chunk TEXTS to build the prompt string), then
fetch the generated tokens, and the intermediate fetch serializes host
work into the device timeline (what one sync costs on the attached chip:
PERF.md).  The reference could not pose the question: its retrieval
(FAISS), prompt assembly (LangChain), and generation (Ollama) were three
separate host processes (``llm-qa/main.py:25,101,66-69``).

This module removes the intermediate sync.  The store keeps a *token
sidecar* (``StoreConfig.token_width``): each row's chunk pre-tokenized
with the GENERATOR's tokenizer at index time.  One program then runs

    encode(question) → top-k over the store → gather the hit rows'
    tokens → pack the prompt token stream (template prefix + chunks +
    separators + question tail) → (prompt_ids, prompt_len, hits)

and the existing prefill+decode program consumes ``prompt_ids`` directly
from device memory — a pure data dependency, no host round-trip.  The
host fetches the top-k ids (for the answer's ``sources``) WHILE decode
runs, then fetches the packed tokens: one sync on the critical path.

Prompt packing uses a gather, not scattered writes: output position ``j``
maps to (segment, offset) via searchsorted over the segments' cumulative
lengths, so chunks of different true lengths concatenate with no pad
tokens inside the prompt (mid-prompt pads would be attended as real
tokens).

Equality caveat: the packed stream equals ``tokenize(template.format(...))``
exactly for whitespace-pretokenized tokenizers (hash/WordPiece — segment
boundaries sit on whitespace).  A byte-level BPE can merge across the
"\\n\\n" boundaries, so with ``tokenizer.json`` vocabularies the fused
prompt may differ by a few boundary tokens from the text path (same
content, same budget; not token-identical).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from docqa_tpu.engines.dispatch import dispatch_with_donation_retry
from docqa_tpu.engines.generate import greedy_dummy_key
from docqa_tpu.engines.spine import spine_run
from docqa_tpu.engines.encoder import marshal_texts
from docqa_tpu.index.store import NEG_INF, SearchResult, _search_single
from docqa_tpu.models.encoder import encode_batch
from docqa_tpu.runtime.metrics import DEFAULT_REGISTRY, span
from docqa_tpu.utils import pick_bucket, round_up


class EmptyStoreError(RuntimeError):
    """Nothing indexed yet — the caller's fallback path owns the reply."""


def _seg_tokens(tokenizer, text: str) -> List[int]:
    """Tokenize one template segment (no CLS/SEP — the stream is one
    sequence, not a batch of sentences)."""
    return [int(t) for t in tokenizer.encode(text, add_specials=False)]


class FusedAnswer:
    """In-flight fused ask: device handles for the answer + hit rows.

    ``sources``/``resolve`` fetch in the overlap-friendly order: row ids
    first (available as soon as the retrieval step of the chain finishes,
    long before decode), tokens last."""

    def __init__(self, rag: "FusedRAG", row_ids_dev, vals_dev, out_dev,
                 n_emitted_dev, prompt_dev=None, prompt_len_dev=None):
        self._rag = rag
        self._row_ids_dev = row_ids_dev
        self._vals_dev = vals_dev
        self._out_dev = out_dev
        self._n_dev = n_emitted_dev
        # the packed prompt stays on device; kept for inspection/tests
        # (fetching it is an extra sync — never done on the serving path)
        self._prompt_dev = prompt_dev
        self._prompt_len_dev = prompt_len_dev
        self._hits: Optional[List[SearchResult]] = None

    def prompt_tokens(self) -> List[int]:
        """Fetch the packed prompt (costs a sync; tests/debugging only)."""
        toks, n = spine_run(
            "fused_rag_fetch",
            lambda: (
                np.asarray(self._prompt_dev)[0],
                int(np.asarray(self._prompt_len_dev)[0]),
            ),
        )
        return [int(t) for t in toks[:n]]

    def hits(self) -> List[SearchResult]:
        if self._hits is None:
            vals, row_ids = spine_run(
                "fused_rag_fetch",
                lambda: (
                    np.asarray(self._vals_dev)[:1],
                    np.asarray(self._row_ids_dev)[:1],
                ),
            )
            self._hits = self._rag.store.assemble_results(vals, row_ids)[0]
        return self._hits

    def resolve(self) -> Dict[str, Any]:
        hits = self.hits()  # fetch hits first: overlaps decode
        out, n = spine_run(
            "fused_rag_fetch",
            lambda: (
                np.asarray(self._out_dev)[0],
                int(np.asarray(self._n_dev)[0]),
            ),
        )
        answer = self._rag.generator.tokenizer.decode_ids(
            [int(t) for t in out[:n]]
        )
        return {
            "answer": answer,
            "sources": [h.metadata.get("source", "") for h in hits],
        }


class FusedRAG:
    """Single-sync ask over (EncoderEngine, VectorStore+sidecar,
    GenerateEngine).

    Composes with a row-sharded store: the search + sidecar gather run
    under ``shard_map`` (per-shard top-k, owned-row token gather, psum
    merge) and the packed prompt feeds the TP-sharded prefill+decode —
    a v5e-8 keeps the one-sync path.  The template is the caller's QA
    template split at ``{context}``/``{question}``, with the generator's
    chat template wrapped around the whole prompt when configured."""

    def __init__(self, encoder, store, generator, template: str,
                 k: int = 3, joiner: str = "\n\n"):
        if not store.cfg.token_width:
            raise ValueError("FusedRAG needs StoreConfig.token_width > 0")
        self.encoder = encoder
        self.store = store
        self.generator = generator
        self.k = k
        tok = generator.tokenizer
        before, after = template.split("{context}", 1)
        mid, suffix = after.split("{question}", 1)
        # mirror encode_prompt EXACTLY so fused output == text-path output
        # (for whitespace-pretokenized tokenizers): untemplated prompts are
        # tokenizer.encode(prompt) = [CLS] words [SEP]; templated prompts
        # are encode(pre) + raw + encode(post, no specials)
        chat = getattr(generator, "_chat_template", None)
        if chat is None:
            # Mirror encode()'s special-token behavior EXACTLY on both
            # ends — the classic text path is tokenizer.encode(prompt),
            # so any special the fused stream adds that encode() would
            # not (or vice versa) silently diverges the two paths:
            # * hash tokenizer (no add_bos/add_eos attrs): encode always
            #   wraps [CLS] ... [SEP];
            # * BPE/SentencePiece: leading BOS only when ``add_bos`` AND
            #   ``bos_id is not None``; trailing EOS only when
            #   ``add_eos`` AND ``eos_id is not None`` (False for
            #   sentencepiece-lineage vocabs, absent-id for vocabs
            #   without the control piece — sep_id would alias 0, a real
            #   token, in that case).
            if not hasattr(tok, "add_bos"):
                head = [tok.cls_id]
            elif tok.add_bos and tok.bos_id is not None:
                head = [tok.bos_id]
            else:
                head = []
            if not hasattr(tok, "add_eos"):
                self._tail_extra: List[int] = [tok.sep_id]
            elif tok.add_eos and tok.eos_id is not None:
                self._tail_extra = [tok.eos_id]
            else:
                self._tail_extra = []
            self._prefix = head + _seg_tokens(tok, before)
        else:
            pre, _, post = chat.partition("{prompt}")
            self._prefix = list(
                int(t) for t in tok.encode(pre)
            ) + _seg_tokens(tok, before)
            self._tail_extra = _seg_tokens(tok, post)
        self._sep = _seg_tokens(tok, joiner)
        self._mid = mid  # tokenized with the question at ask time
        self._suffix = suffix
        self._fns: Dict[Any, Any] = {}

    def _get_fn(self, k: int, t_bucket: int, l_bucket: int, masked: bool):
        key = (k, t_bucket, l_bucket, masked)
        fn = self._fns.get(key)
        if fn is None:
            enc_cfg = self.encoder.cfg
            W = self.store.cfg.token_width
            prefix = jnp.asarray(self._prefix, jnp.int32)
            sep = jnp.asarray(self._sep, jnp.int32)
            pad_id = self.generator.gen.pad_id
            n_seg = 1 + 2 * k  # prefix, (chunk, sep)*(k-1), chunk, tail
            w_seg = max(W, len(self._prefix), len(self._sep), t_bucket, 1)
            mesh = self.store.mesh
            sharded = mesh is not None and mesh.n_model > 1
            # static per-chunk token budget: everything except the chunks
            # is non-negotiable (template + question), chunks absorb the
            # squeeze when l_bucket is clamped by max_seq_len - max_new
            chunk_cap = max(
                0,
                (
                    l_bucket
                    - len(self._prefix)
                    - (k - 1) * len(self._sep)
                    - t_bucket
                )
                // k,
            )

            def _search_gather(buf, q, count, tok_dev, tok_len_dev, mask):
                """(vals, row_ids, chunk_toks [k,W], chunk_lens [k]).

                Sharded: the store's shard_map search kernel, then each
                shard gathers the hit rows IT owns from its sidecar slice
                and a psum merges the contributions (non-owners add
                zeros) — the packed prompt never leaves the device and no
                shard materializes another shard's sidecar."""
                if not sharded:
                    vals, row_ids = _search_single(buf, q, count, mask, k)
                    rows = jnp.clip(row_ids[0], 0, tok_dev.shape[0] - 1)
                    return vals, row_ids, tok_dev[rows], tok_len_dev[rows]
                axis = mesh.model_axis
                from docqa_tpu.index.store import _search_kernel

                def body(buf_s, q_r, cnt, tok_s, tok_len_s, m):
                    vals, ids = _search_kernel(buf_s, q_r, cnt, m, k, axis)
                    n_local = tok_s.shape[0]
                    off = jax.lax.axis_index(axis) * n_local
                    local = ids[0] - off                      # [k]
                    owned = (local >= 0) & (local < n_local)
                    safe = jnp.clip(local, 0, n_local - 1)
                    toks = jnp.where(owned[:, None], tok_s[safe], 0)
                    lens = jnp.where(owned, tok_len_s[safe], 0)
                    return (
                        vals,
                        ids,
                        jax.lax.psum(toks, axis),
                        jax.lax.psum(lens, axis),
                    )

                in_specs = [
                    P(axis, None),  # vector rows sharded
                    P(),            # query embedding replicated
                    P(),            # count
                    P(axis, None),  # sidecar tokens row-sharded
                    P(axis),        # sidecar lengths row-sharded
                ]
                args = [buf, q, count, tok_dev, tok_len_dev]
                if mask is not None:
                    in_specs.append(P())
                    args.append(mask)
                    wrapped = body
                else:
                    def wrapped(buf_s, q_r, cnt, tok_s, tok_len_s):
                        return body(buf_s, q_r, cnt, tok_s, tok_len_s, None)

                return shard_map(
                    wrapped,
                    mesh=mesh.mesh,
                    in_specs=tuple(in_specs),
                    out_specs=(P(), P(), P(), P()),
                    check_vma=False,
                )(*args)

            def program(enc_params, q_ids, q_len, buf, count, tok_dev,
                        tok_len_dev, tail_ids, tail_len, mask):
                emb = encode_batch(enc_params, enc_cfg, q_ids, q_len)
                emb = emb / jnp.maximum(
                    jnp.linalg.norm(emb, axis=-1, keepdims=True), 1e-9
                )
                vals, row_ids, chunk_toks, chunk_lens = _search_gather(
                    buf, emb.astype(buf.dtype), count, tok_dev,
                    tok_len_dev, mask,
                )
                # Under-fill guard: with fewer than k LIVE rows, top_k
                # pads with NEG_INF ties whose indices point at masked
                # (tombstoned) rows — zero their lengths so erased
                # content can never be packed into a prompt (the sources
                # list already drops them via assemble_results).
                live_hit = vals[0] > NEG_INF / 2
                chunk_lens = jnp.where(live_hit, chunk_lens, 0)
                # Budget guard: cap the per-chunk length so the prompt's
                # tail (question + closing template tokens) always fits —
                # tail-truncating the stream would cut the question off,
                # the exact failure encode_prompt exists to avoid.
                chunk_lens = jnp.minimum(chunk_lens, chunk_cap)
                # segment matrix: rows padded to w_seg
                def pad_to(x):
                    return jnp.zeros((w_seg,), jnp.int32).at[: x.shape[0]].set(x)

                seg_rows = [pad_to(prefix)]
                seg_lens = [jnp.int32(prefix.shape[0])]
                for i in range(k):
                    seg_rows.append(
                        jnp.zeros((w_seg,), jnp.int32)
                        .at[:W]
                        .set(chunk_toks[i])
                    )
                    seg_lens.append(chunk_lens[i])
                    if i < k - 1:
                        seg_rows.append(pad_to(sep))
                        seg_lens.append(jnp.int32(sep.shape[0]))
                seg_rows.append(pad_to(tail_ids))
                seg_lens.append(tail_len)
                seg_toks = jnp.stack(seg_rows)        # [n_seg, w_seg]
                lens = jnp.stack(seg_lens).astype(jnp.int32)
                bounds = jnp.cumsum(lens)             # [n_seg]
                starts = bounds - lens
                total = jnp.minimum(bounds[-1], l_bucket)
                j = jnp.arange(l_bucket)
                seg_idx = jnp.clip(
                    jnp.searchsorted(bounds, j, side="right"), 0, n_seg - 1
                )
                within = jnp.clip(j - starts[seg_idx], 0, w_seg - 1)
                toks = seg_toks[seg_idx, within]
                prompt = jnp.where(j < total, toks, pad_id)[None, :]
                return prompt, total[None].astype(jnp.int32), vals, row_ids

            if masked:
                fn = jax.jit(program)
            else:
                fn = jax.jit(
                    lambda p, qi, ql, b, c, td, tl, ti, tn: program(
                        p, qi, ql, b, c, td, tl, ti, tn, None
                    )
                )
            self._fns[key] = fn
        return fn

    def ask_submit(self, question: str, max_new_tokens: Optional[int] = None
                   ) -> FusedAnswer:
        gen = self.generator
        store = self.store
        max_new = max_new_tokens or gen.gen.max_new_tokens
        q_ids, q_len = marshal_texts(
            self.encoder.tokenizer, self.encoder.cfg, [question],
            batch_buckets=(1,),
        )
        tail = (
            _seg_tokens(gen.tokenizer, self._mid + question + self._suffix)
            + self._tail_extra
        )
        t_bucket = pick_bucket(max(len(tail), 1), (64, 128, 256))
        tail_ids = np.zeros((t_bucket,), np.int32)
        tail_ids[: len(tail)] = tail[:t_bucket]
        W = store.cfg.token_width
        usable = gen.cfg.max_seq_len - max_new
        l_need = (
            len(self._prefix)
            + self.k * W
            + (self.k - 1) * len(self._sep)
            + t_bucket
        )
        l_bucket = min(
            pick_bucket(l_need, gen.gen.prefill_buckets)
            if l_need <= gen.gen.prefill_buckets[-1]
            else round_up(l_need, 128),
            usable,
        )
        def snapshot_and_build():
            """Consistent (fn, args) from ONE lock acquisition; the
            dispatch discipline (compile outside the lock, donation-race
            retry under it — ADVICE r4) lives in ``engines.dispatch``."""
            with store._lock:
                count = store._count
                if count == 0:
                    raise EmptyStoreError("empty store: nothing to retrieve")
                sidecar = store.token_sidecar()
                k_eff = min(self.k, count)
                # tombstoned rows must stay unretrievable through this
                # path too
                mask = store._compose_live_locked(None, already_live=False)
                fn = self._get_fn(
                    k_eff, t_bucket, l_bucket, masked=mask is not None
                )
                args = [
                    self.encoder.params,
                    jnp.asarray(q_ids),
                    jnp.asarray(q_len),
                    store._dev,
                    jnp.int32(count),
                    sidecar[0],
                    sidecar[1],
                    jnp.asarray(tail_ids),
                    jnp.int32(min(len(tail), t_bucket)),
                ]
                if mask is not None:
                    args.append(jnp.asarray(mask))
            return fn, args

        with span("fused_rag_pack", DEFAULT_REGISTRY):
            prompt, total, vals, row_ids = dispatch_with_donation_retry(
                store._lock, snapshot_and_build
            )
        # prefill+decode chained on the device-side prompt — no sync between
        gfn = gen._get_fn(
            1, l_bucket, max_new, greedy=gen.gen.temperature == 0.0
        )
        # minted OUTSIDE the lane closure: a donation/spine retry must
        # replay the SAME key, and each request gets its own — a shared
        # literal key would make every fused answer sample identically
        # at temperature>0.  Greedy programs take the argmax branch and
        # never consume the key, so the marked dummy is sufficient there.
        rng_key = (
            greedy_dummy_key()
            if gen.gen.temperature == 0.0
            else gen.next_request_key()
        )

        def _generate_on_lane():
            return gfn(
                gen.params, prompt, total, rng_key,
                jnp.float32(gen.gen.temperature),
            )

        with span("fused_rag_generate", DEFAULT_REGISTRY):
            out, n_emitted = spine_run(
                "fused_rag_generate", _generate_on_lane
            )
        return FusedAnswer(
            self, row_ids, vals, out, n_emitted,
            prompt_dev=prompt, prompt_len_dev=total,
        )

    def ask(self, question: str, max_new_tokens: Optional[int] = None
            ) -> Dict[str, Any]:
        with span("qa_e2e_fused", DEFAULT_REGISTRY):
            return self.ask_submit(question, max_new_tokens).resolve()
