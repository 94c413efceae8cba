"""BART-class encoder-decoder for summarization (BASELINE config 4).

The reference's summarization was a fake that returned the prompt's last
1200 chars (``synthese-comparative/core/llm_client.py:18-30``) — its
requirements file even pinned transformers/safetensors for a local HF
summarizer that never landed (SURVEY appendix).  This module lands it:
a jit-compiled encoder-decoder whose layout mirrors HF
``BartForConditionalGeneration`` exactly —

* post-LN residuals (``x = LN(x + sublayer(x))``), GELU MLP;
* learned positional embeddings with BART's ``+2`` padding offset;
* ``layernorm_embedding`` after the (token + position) sum;
* tied lm_head (shared embedding transposed) + ``final_logits_bias`` —

so a real ``bart-large-cnn`` safetensors file imports 1:1 via
:func:`load_hf_bart_weights` (zero-egress here: seeded init stands in).

Inference shape (TPU-first): the source encodes ONCE; every decoder
layer's cross-attention K/V over the source is precomputed ONCE; the
greedy loop is a ``lax.while_loop`` with a self-attention KV cache and no
host round-trip per token — same discipline as ``engines/generate.py``.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from docqa_tpu.config import Seq2SeqConfig
from docqa_tpu.ops.attention import attention_reference

Params = Dict[str, jax.Array]


# ---------------------------------------------------------------------------
# Schema / init
# ---------------------------------------------------------------------------

def seq2seq_param_schema(cfg: Seq2SeqConfig):
    """(name, kind, shape) with kind in {normal, zeros, ones}; the single
    source of truth shared by init and the HF import mapping."""
    d, m = cfg.d_model, cfg.mlp_dim
    yield ("shared_emb", "normal", (cfg.vocab_size, d))
    yield ("enc_pos", "normal", (cfg.max_src_len + cfg.pos_offset, d))
    yield ("dec_pos", "normal", (cfg.max_tgt_len + cfg.pos_offset, d))
    yield ("enc_ln_emb_g", "ones", (d,))
    yield ("enc_ln_emb_b", "zeros", (d,))
    yield ("dec_ln_emb_g", "ones", (d,))
    yield ("dec_ln_emb_b", "zeros", (d,))
    yield ("final_logits_bias", "zeros", (cfg.vocab_size,))
    for side, n_layers in (("e", cfg.enc_layers), ("d", cfg.dec_layers)):
        for i in range(n_layers):
            p = f"{side}{i}_"
            attns = ("self", "cross") if side == "d" else ("self",)
            for a in attns:
                ap = p + ("x" if a == "cross" else "")
                for w in ("q", "k", "v", "o"):
                    yield (ap + w + "w", "normal", (d, d))
                    yield (ap + w + "b", "zeros", (d,))
                yield (ap + "ln_g", "ones", (d,))
                yield (ap + "ln_b", "zeros", (d,))
            yield (p + "fc1_w", "normal", (d, m))
            yield (p + "fc1_b", "zeros", (m,))
            yield (p + "fc2_w", "normal", (m, d))
            yield (p + "fc2_b", "zeros", (d,))
            yield (p + "lnf_g", "ones", (d,))
            yield (p + "lnf_b", "zeros", (d,))


def init_seq2seq_params(
    rng: jax.Array, cfg: Seq2SeqConfig, param_dtype=None,
    host_init: bool = False, host_seed: Optional[int] = None,
) -> Params:
    """``host_init``: draw on the host and ``device_put`` per tensor — the
    transfer path real checkpoints take (see models/decoder.py); serving
    engines default to it and pass ``host_seed`` so the seed is not
    derived via a ``key_data`` fetch."""
    import numpy as _np

    from docqa_tpu.utils import host_seed_from_rng

    param_dtype = jnp.dtype(param_dtype or cfg.dtype)
    schema = list(seq2seq_param_schema(cfg))
    p: Params = {}
    if host_init:
        host_rng = _np.random.default_rng(host_seed_from_rng(rng, host_seed))
        for name, kind, shape in schema:
            if kind == "ones":
                p[name] = jax.device_put(_np.ones(shape, param_dtype))
            elif kind == "zeros":
                p[name] = jax.device_put(_np.zeros(shape, param_dtype))
            else:
                p[name] = jax.device_put(
                    (host_rng.standard_normal(shape, _np.float32) * 0.02)
                    .astype(param_dtype)
                )
        return p
    n_normal = sum(1 for _, kind, _ in schema if kind == "normal")
    keys = iter(jax.random.split(rng, n_normal))
    for name, kind, shape in schema:
        if kind == "ones":
            p[name] = jnp.ones(shape, param_dtype)
        elif kind == "zeros":
            p[name] = jnp.zeros(shape, param_dtype)
        else:
            p[name] = (
                jax.random.normal(next(keys), shape, jnp.float32) * 0.02
            ).astype(param_dtype)
    return p


# ---------------------------------------------------------------------------
# Forward pieces
# ---------------------------------------------------------------------------

def _ln(x, g, b, eps):
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * g.astype(jnp.float32)
            + b.astype(jnp.float32)).astype(x.dtype)


def _proj(x, w, b, dtype):
    return x @ w.astype(dtype) + b.astype(dtype)


def _heads(x, n_heads):
    b, s, d = x.shape
    return x.reshape(b, s, n_heads, d // n_heads)


def _attn_block(params, prefix, x, kv, cfg, lengths, causal, q_offset, dtype):
    """One (post-LN) attention sublayer.  ``kv``: the K/V source sequence
    (== x for self-attention on the encoder side)."""
    q = _heads(_proj(x, params[prefix + "qw"], params[prefix + "qb"], dtype),
               cfg.num_heads)
    k = _heads(_proj(kv, params[prefix + "kw"], params[prefix + "kb"], dtype),
               cfg.num_heads)
    v = _heads(_proj(kv, params[prefix + "vw"], params[prefix + "vb"], dtype),
               cfg.num_heads)
    out = attention_reference(
        q, k, v, causal=causal, lengths=lengths, q_offset=q_offset
    )
    out = out.reshape(x.shape)
    out = _proj(out, params[prefix + "ow"], params[prefix + "ob"], dtype)
    return _ln(x + out, params[prefix + "ln_g"], params[prefix + "ln_b"],
               cfg.norm_eps)


def _ffn_block(params, prefix, x, cfg, dtype):
    h = jax.nn.gelu(
        _proj(x, params[prefix + "fc1_w"], params[prefix + "fc1_b"], dtype)
        .astype(jnp.float32)
    ).astype(dtype)
    h = _proj(h, params[prefix + "fc2_w"], params[prefix + "fc2_b"], dtype)
    return _ln(x + h, params[prefix + "lnf_g"], params[prefix + "lnf_b"],
               cfg.norm_eps)


def encode_source(
    params: Params, cfg: Seq2SeqConfig, ids: jax.Array, lengths: jax.Array
) -> jax.Array:
    """[b, s] source ids -> [b, s, d] encoder states (padding positions are
    masked out of every attention by ``lengths``)."""
    b, s = ids.shape
    dtype = jnp.dtype(cfg.dtype)
    pos = jnp.arange(s) + cfg.pos_offset
    x = (params["shared_emb"][ids] + params["enc_pos"][pos][None]).astype(dtype)
    x = _ln(x, params["enc_ln_emb_g"], params["enc_ln_emb_b"], cfg.norm_eps)
    for i in range(cfg.enc_layers):
        x = _attn_block(
            params, f"e{i}_", x, x, cfg, lengths, False, None, dtype
        )
        x = _ffn_block(params, f"e{i}_", x, cfg, dtype)
    return x


def precompute_cross_kv(
    params: Params, cfg: Seq2SeqConfig, enc_h: jax.Array
) -> Dict[str, jax.Array]:
    """Per-decoder-layer cross-attention K/V over the encoded source —
    computed ONCE per request instead of once per decode step."""
    dtype = jnp.dtype(cfg.dtype)
    out: Dict[str, jax.Array] = {}
    for i in range(cfg.dec_layers):
        p = f"d{i}_x"
        out[f"xk{i}"] = _heads(
            _proj(enc_h, params[p + "kw"], params[p + "kb"], dtype),
            cfg.num_heads,
        )
        out[f"xv{i}"] = _heads(
            _proj(enc_h, params[p + "vw"], params[p + "vb"], dtype),
            cfg.num_heads,
        )
    return out


def init_self_cache(cfg: Seq2SeqConfig, batch: int, max_len: int):
    shape = (batch, max_len, cfg.num_heads, cfg.d_model // cfg.num_heads)
    dtype = jnp.dtype(cfg.dtype)
    return {
        key: jnp.zeros(shape, dtype)
        for i in range(cfg.dec_layers)
        for key in (f"sk{i}", f"sv{i}")
    }


def decoder_forward(
    params: Params,
    cfg: Seq2SeqConfig,
    ids: jax.Array,  # [b, s] target ids (new tokens)
    cache,  # self-attn KV cache dict
    cache_lengths: jax.Array,  # [b] tokens already in cache
    cross_kv,  # precomputed xk/xv per layer
    src_lengths: jax.Array,  # [b]
) -> Tuple[jax.Array, dict]:
    """Run s new target tokens; returns (logits [b, s, vocab] f32, cache)."""
    b, s = ids.shape
    dtype = jnp.dtype(cfg.dtype)
    max_len = cache["sk0"].shape[1]
    pos = jnp.minimum(
        cache_lengths[:, None] + jnp.arange(s)[None, :], max_len - 1
    ) + cfg.pos_offset
    x = (params["shared_emb"][ids] + params["dec_pos"][pos]).astype(dtype)
    x = _ln(x, params["dec_ln_emb_g"], params["dec_ln_emb_b"], cfg.norm_eps)
    new_lengths = cache_lengths + s
    for i in range(cfg.dec_layers):
        p = f"d{i}_"
        # causal self-attention over the cache
        q = _heads(_proj(x, params[p + "qw"], params[p + "qb"], dtype),
                   cfg.num_heads)
        k = _heads(_proj(x, params[p + "kw"], params[p + "kb"], dtype),
                   cfg.num_heads)
        v = _heads(_proj(x, params[p + "vw"], params[p + "vb"], dtype),
                   cfg.num_heads)

        def write(c, new, off):
            return jax.lax.dynamic_update_slice_in_dim(c, new, off, axis=0)

        cache[f"sk{i}"] = jax.vmap(write)(cache[f"sk{i}"], k, cache_lengths)
        cache[f"sv{i}"] = jax.vmap(write)(cache[f"sv{i}"], v, cache_lengths)
        attn = attention_reference(
            q, cache[f"sk{i}"], cache[f"sv{i}"], causal=True,
            lengths=new_lengths, q_offset=cache_lengths,
        ).reshape(b, s, cfg.d_model)
        attn = _proj(attn, params[p + "ow"], params[p + "ob"], dtype)
        x = _ln(x + attn, params[p + "ln_g"], params[p + "ln_b"], cfg.norm_eps)
        # cross-attention over the precomputed source K/V
        xq = _heads(_proj(x, params[p + "xqw"], params[p + "xqb"], dtype),
                    cfg.num_heads)
        xattn = attention_reference(
            xq, cross_kv[f"xk{i}"], cross_kv[f"xv{i}"], causal=False,
            lengths=src_lengths,
        ).reshape(b, s, cfg.d_model)
        xattn = _proj(xattn, params[p + "xow"], params[p + "xob"], dtype)
        x = _ln(x + xattn, params[p + "xln_g"], params[p + "xln_b"],
                cfg.norm_eps)
        x = _ffn_block(params, p, x, cfg, dtype)
    logits = (
        x @ params["shared_emb"].T.astype(dtype)
    ).astype(jnp.float32) + params["final_logits_bias"].astype(jnp.float32)
    return logits, cache


def greedy_summarize_fn(
    params: Params,
    cfg: Seq2SeqConfig,
    src_ids: jax.Array,  # [b, s]
    src_lengths: jax.Array,  # [b]
    *,
    max_new: int,
):
    """The whole request as ONE program: encode -> cross K/V -> greedy
    ``while_loop`` decode with early exit when every lane hit EOS."""
    b = src_ids.shape[0]
    enc_h = encode_source(params, cfg, src_ids, src_lengths)
    cross_kv = precompute_cross_kv(params, cfg, enc_h)
    cache = init_self_cache(cfg, b, max_new + 1)

    start = jnp.full((b, 1), cfg.decoder_start_id, jnp.int32)
    logits, cache = decoder_forward(
        params, cfg, start, cache, jnp.zeros((b,), jnp.int32),
        cross_kv, src_lengths,
    )
    first = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    if cfg.forced_bos_id is not None:  # HF BART: first decoded token is BOS
        first = jnp.full((b,), cfg.forced_bos_id, jnp.int32)
    out = jnp.full((b, max_new), cfg.pad_id, jnp.int32)
    out = out.at[:, 0].set(first)
    done = first == cfg.eos_id
    n_emitted = jnp.where(done, 0, 1).astype(jnp.int32)

    def cond(st):
        step, _, _, done, _ = st
        return jnp.logical_and(step < max_new, ~jnp.all(done))

    def body(st):
        step, cache, out, done, n_emitted = st
        tok = out[:, step - 1]
        logits, cache = decoder_forward(
            params, cfg, tok[:, None], cache,
            jnp.full((b,), step, jnp.int32), cross_kv, src_lengths,
        )
        nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
        nxt = jnp.where(done, cfg.pad_id, nxt)
        out = out.at[:, step].set(nxt)
        is_eos = nxt == cfg.eos_id
        n_emitted = n_emitted + jnp.where(done | is_eos, 0, 1)
        done = done | is_eos
        return step + 1, cache, out, done, n_emitted

    _, _, out, _, n_emitted = jax.lax.while_loop(
        cond, body, (jnp.int32(1), cache, out, done, n_emitted)
    )
    return out, n_emitted


NEG_INF = -1e30


def beam_summarize_fn(
    params: Params,
    cfg: Seq2SeqConfig,
    src_ids: jax.Array,  # [b, s]
    src_lengths: jax.Array,  # [b]
    *,
    max_new: int,
    n_beams: int,
    length_penalty: float = 1.0,
    min_length: int = 0,
    no_repeat_ngram: int = 0,
):
    """Beam-search decode as ONE program (bart-large-cnn ships with beam 4;
    greedy under-serves it).  Beams ride the batch axis ([b*B] lanes): the
    per-step reorder gathers the self-attention cache rows by winning beam,
    while the (tiled, never-mutated) cross K/V needs no reorder.  A
    finished beam exposes exactly one continuation (pad at logp 0) so its
    score freezes but it stays selectable; final ranking divides by
    emitted length ** ``length_penalty`` (GNMT-style).

    ``n_beams=1`` reduces to exactly the greedy trajectory (tested).
    Returns (tokens [b, max_new], n_emitted [b]) like the greedy fn.
    """
    b = src_ids.shape[0]
    B, V = n_beams, cfg.vocab_size
    eos, pad = cfg.eos_id, cfg.pad_id
    alpha = jnp.float32(length_penalty)

    def penalize(score, n):
        return score / jnp.maximum(n, 1).astype(jnp.float32) ** alpha

    enc_h = encode_source(params, cfg, src_ids, src_lengths)
    cross_kv = {
        k: jnp.repeat(v, B, axis=0)
        for k, v in precompute_cross_kv(params, cfg, enc_h).items()
    }
    srcl = jnp.repeat(src_lengths, B, axis=0)
    cache = init_self_cache(cfg, b * B, max_new + 1)

    start = jnp.full((b * B, 1), cfg.decoder_start_id, jnp.int32)
    logits, cache = decoder_forward(
        params, cfg, start, cache, jnp.zeros((b * B,), jnp.int32),
        cross_kv, srcl,
    )
    logp = jax.nn.log_softmax(logits[:, -1].astype(jnp.float32), axis=-1)
    if min_length > 1:  # zero emitted + the start token: ban EOS while
        # 0 + 1 < min_length (mirrors the in-loop HF-parity condition)
        logp = jnp.where(
            (jnp.arange(V) == eos)[None, :], NEG_INF, logp
        )
    if cfg.forced_bos_id is not None:
        # HF BART generation forces BOS as the first decoded token; all
        # beams share that prefix, so only beam 0 carries weight until the
        # first real branching step
        tok0 = jnp.full((b, B), cfg.forced_bos_id, jnp.int32)
        scores = jnp.where(
            jnp.arange(B)[None, :] == 0,
            logp.reshape(b, B, V)[:, 0, cfg.forced_bos_id][:, None],
            NEG_INF,
        )
    else:
        # all beams of a row are identical at step 0 — branch from beam 0
        scores, tok0 = jax.lax.top_k(logp.reshape(b, B, V)[:, 0], B)
    out = jnp.full((b, B, max_new), pad, jnp.int32)
    out = out.at[:, :, 0].set(tok0)
    done = tok0 == eos
    emit_len = jnp.where(done, 0, 1).astype(jnp.int32)
    pad_only = jnp.where(  # a finished beam's single allowed continuation
        jax.nn.one_hot(pad, V, dtype=jnp.float32) > 0, 0.0, NEG_INF
    )
    # the finished-hypothesis pool: a beam that hits EOS is banked here
    # immediately, so later eviction from the live beam (higher-scoring
    # prefixes whose completions end up worse) cannot lose it
    fin_score = jnp.where(done, penalize(scores, emit_len), NEG_INF)
    best0 = jnp.argmax(fin_score, axis=1)
    fin_best = jnp.max(fin_score, axis=1)  # [b] penalized
    fin_tokens = jnp.take_along_axis(out, best0[:, None, None], 1)[:, 0]
    fin_len = jnp.take_along_axis(emit_len, best0[:, None], 1)[:, 0]

    def cond(st):
        t, _, _, _, done, _, _, _, _ = st
        return jnp.logical_and(t < max_new, ~jnp.all(done))

    def body(st):
        (t, cache, out, scores, done, emit_len,
         fin_best, fin_tokens, fin_len) = st
        prev = out[:, :, t - 1].reshape(b * B)
        logits, cache = decoder_forward(
            params, cfg, prev[:, None], cache,
            jnp.full((b * B,), t, jnp.int32), cross_kv, srcl,
        )
        logp = jax.nn.log_softmax(
            logits[:, 0].astype(jnp.float32), axis=-1
        ).reshape(b, B, V)
        if min_length > 0:
            # HF parity: MinLengthLogitsProcessor counts the decoder-start
            # token in cur_len, so EOS unlocks once emit_len + 1 reaches
            # min_length (a min_length=56 summary may end at 55 emissions)
            logp = jnp.where(
                (emit_len + 1 < min_length)[:, :, None]
                & (jnp.arange(V) == eos)[None, None, :],
                NEG_INF,
                logp,
            )
        if no_repeat_ngram >= 1 and max_new >= no_repeat_ngram:
            # (max_new < n can't repeat an n-gram; skipping also keeps the
            # m-1 history slice within the out axis at trace time)
            m = no_repeat_ngram
            if m == 1:  # each token at most once
                complete = jnp.arange(max_new)[None, None, :] < t
                ban = jnp.where(complete, out, V)
            else:
                W = max_new - m + 1
                # the m-1 tokens ending at position t-1, per beam
                last = jax.lax.dynamic_slice_in_dim(
                    out, jnp.maximum(t - (m - 1), 0), m - 1, axis=2
                )  # [b, B, m-1]
                # every historical m-gram window: prefix + follower token
                win = jnp.stack(
                    [out[:, :, j : j + W] for j in range(m - 1)], axis=-1
                )
                follower = out[:, :, m - 1 : m - 1 + W]
                match = jnp.all(win == last[:, :, None, :], axis=-1)
                complete = (jnp.arange(W) + m - 1)[None, None, :] < t
                ban = jnp.where(
                    match & complete & (t >= (m - 1)), follower, V
                )  # V = out of bounds, dropped
            bb = jnp.broadcast_to(jnp.arange(b)[:, None, None], ban.shape)
            kk = jnp.broadcast_to(jnp.arange(B)[None, :, None], ban.shape)
            logp = logp.at[bb, kk, ban].set(NEG_INF, mode="drop")
        cont = jnp.where(done[:, :, None], pad_only[None, None, :], logp)
        total = scores[:, :, None] + cont  # [b, B, V]
        scores_new, idx = jax.lax.top_k(total.reshape(b, B * V), B)
        beam_idx = idx // V  # [b, B]
        tok = (idx % V).astype(jnp.int32)
        # reorder beam-carried state by the winning parent beam
        rows = (jnp.arange(b)[:, None] * B + beam_idx).reshape(-1)
        cache = {k: v[rows] for k, v in cache.items()}
        out = jnp.take_along_axis(out, beam_idx[:, :, None], axis=1)
        done_g = jnp.take_along_axis(done, beam_idx, axis=1)
        emit_g = jnp.take_along_axis(emit_len, beam_idx, axis=1)
        out = out.at[:, :, t].set(jnp.where(done_g, pad, tok))
        is_eos = (~done_g) & (tok == eos)
        emit_len_new = emit_g + jnp.where(done_g | is_eos, 0, 1)
        done_new = done_g | is_eos
        # bank newly finished hypotheses into the pool
        cand = jnp.where(is_eos, penalize(scores_new, emit_len_new), NEG_INF)
        cand_best = jnp.argmax(cand, axis=1)
        cand_score = jnp.max(cand, axis=1)
        better = cand_score > fin_best
        fin_best = jnp.where(better, cand_score, fin_best)
        fin_tokens = jnp.where(
            better[:, None],
            jnp.take_along_axis(out, cand_best[:, None, None], 1)[:, 0],
            fin_tokens,
        )
        fin_len = jnp.where(
            better,
            jnp.take_along_axis(emit_len_new, cand_best[:, None], 1)[:, 0],
            fin_len,
        )
        return (t + 1, cache, out, scores_new, done_new, emit_len_new,
                fin_best, fin_tokens, fin_len)

    (_, _, out, scores, done, emit_len, fin_best, fin_tokens, fin_len) = (
        jax.lax.while_loop(
            cond, body,
            (jnp.int32(1), cache, out, scores, done, emit_len,
             fin_best, fin_tokens, fin_len),
        )
    )
    # final ranking: best banked hypothesis vs best still-live beam
    live_pen = jnp.where(done, NEG_INF, penalize(scores, emit_len))
    live_best = jnp.argmax(live_pen, axis=1)
    live_score = jnp.max(live_pen, axis=1)
    use_fin = fin_best >= live_score
    tokens = jnp.where(
        use_fin[:, None],
        fin_tokens,
        jnp.take_along_axis(out, live_best[:, None, None], axis=1)[:, 0],
    )
    n_emitted = jnp.where(
        use_fin,
        fin_len,
        jnp.take_along_axis(emit_len, live_best[:, None], axis=1)[:, 0],
    )
    return tokens, n_emitted


# ---------------------------------------------------------------------------
# HF weight import (facebook/bart-large-cnn layout, offline-gated)
# ---------------------------------------------------------------------------

_HF_ATTN = {"q": "q_proj", "k": "k_proj", "v": "v_proj", "o": "out_proj"}


def load_hf_bart_weights(path: str, cfg: Seq2SeqConfig) -> Params:
    """Map HF ``model.safetensors`` (BartForConditionalGeneration) into the
    flat param tree.  Torch Linear stores [out, in] -> transpose."""
    from safetensors.numpy import load_file

    raw = {k.replace("model.", "", 1): v for k, v in load_file(path).items()}

    def t(name):
        return jnp.asarray(raw[name].T)

    def a(name):
        return jnp.asarray(raw[name])

    p: Params = {
        "shared_emb": a("shared.weight"),
        "enc_pos": a("encoder.embed_positions.weight"),
        "dec_pos": a("decoder.embed_positions.weight"),
        "enc_ln_emb_g": a("encoder.layernorm_embedding.weight"),
        "enc_ln_emb_b": a("encoder.layernorm_embedding.bias"),
        "dec_ln_emb_g": a("decoder.layernorm_embedding.weight"),
        "dec_ln_emb_b": a("decoder.layernorm_embedding.bias"),
        "final_logits_bias": (
            a("final_logits_bias").reshape(-1)
            if "final_logits_bias" in raw
            else jnp.zeros((cfg.vocab_size,), jnp.float32)
        ),
    }
    for side, hf_side, n_layers in (
        ("e", "encoder", cfg.enc_layers),
        ("d", "decoder", cfg.dec_layers),
    ):
        for i in range(n_layers):
            pre = f"{hf_side}.layers.{i}."
            attns = [("", "self_attn", "ln")]
            if side == "d":
                attns.append(("x", "encoder_attn", "xln"))
            for mark, hf_attn, ln_mark in attns:
                for ours, theirs in _HF_ATTN.items():
                    p[f"{side}{i}_{mark}{ours}w"] = t(
                        pre + f"{hf_attn}.{theirs}.weight"
                    )
                    p[f"{side}{i}_{mark}{ours}b"] = a(
                        pre + f"{hf_attn}.{theirs}.bias"
                    )
                p[f"{side}{i}_{ln_mark}_g"] = a(
                    pre + f"{hf_attn}_layer_norm.weight"
                )
                p[f"{side}{i}_{ln_mark}_b"] = a(
                    pre + f"{hf_attn}_layer_norm.bias"
                )
            p[f"{side}{i}_fc1_w"] = t(pre + "fc1.weight")
            p[f"{side}{i}_fc1_b"] = a(pre + "fc1.bias")
            p[f"{side}{i}_fc2_w"] = t(pre + "fc2.weight")
            p[f"{side}{i}_fc2_b"] = a(pre + "fc2.bias")
            p[f"{side}{i}_lnf_g"] = a(pre + "final_layer_norm.weight")
            p[f"{side}{i}_lnf_b"] = a(pre + "final_layer_norm.bias")
    return p
