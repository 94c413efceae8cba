"""Plain float32 reference of the MiniCPM-SALA decoder
(``https://huggingface.co/openbmb/MiniCPM-SALA``: lightning attention,
Lightning Attention-2 arXiv:2401.04658; InfLLM-V2 sparse attention,
arXiv:2509.24663).

Straightforward ``jax.numpy``: no kernel, no cache, no paging, nothing
imported from the program.  Every matmul runs under
``jax.default_matmul_precision("highest")``.  The weights are the tensors
the benchmark made from the seed (``weights.py``), dequantized to float32
one layer at a time; a layer runs one lane at a time and its MLP a block
of rows at a time, so that 4 x 9.4k rows fit beside the served tree.

``L`` layers, ``r = scale_depth / sqrt(L)``, RMSNorm eps from the file:

    h_0 = scale_emb * E[ids]
    y = rmsnorm(h) g;  q, k, v = y Wq, y Wk, y Wv
    q, k = rmsnorm_head(q) g_q, rmsnorm_head(k) g_k       (qk_norm)
    lightning-attn (32 heads = 32 kv heads):  q, k = rope(q), rope(k);
        S_t = lambda_h S_{t-1} + k_t^T v_t  (THE RECURRENCE, a scan over
        tokens; lambda_h = exp(-s_h));  o_t = (q_t / sqrt(d)) S_t;
        o = rmsnorm_head(o) g_o
    minicpm4 (32 / 2 heads, no RoPE): causal softmax attention over all
        tokens, or over the tokens of the blocks a row takes (below)
    h = h + r * (o * sigmoid(y Wg)) Wo
    h = h + r * (silu(y' Wgate) * (y' Wup)) Wdown,   y' = rmsnorm(h) g'
    logits = (rmsnorm(h) g_f) Whead / (hidden / dim_model_base)

Selection, by explicit masks (a row at position t, kv head g): compressed
keys ``Kbar_j = mean(k_{16 j .. 16 j + 31})`` of the windows that end at or
before t; ``p = softmax_j(q . Kbar_j / sqrt(d))`` per query head, summed
over g's 16 query heads; a block's score is the maximum of ``p_j`` over
the windows that overlap it; block 0 and the blocks over the last 2048
tokens are always taken; the 64 best in all.  A row selects iff the
forward it belongs to holds ``dense_len`` tokens or more: one full pass
over ``ids`` holds ``s`` of them for every row; with ``prompt_lengths``
(tests: a prefill of that many tokens, then decode steps) a row past the
prompt belongs to a forward of ``t + 1`` tokens.

Assumed (the row gives no more; the configuration file lists each):
``s_h = 2^(-8 (h+1) / H) (1 - l / (L - 1) + 1e-5)``; no activation on q, k,
v; the output norm over each head's values; the sparse sizes of
``keys.SPARSE_DEFAULTS``.

A block that routes (benchmark/README.md): ``routing`` int32 [sparse
layers x kv heads, b, s, topk] replays the block sets of the rows whose
first id is >= 0; the choice gap is taken over BLOCK SCORES."""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from harness.weights import Control

from . import weights as _weights

SPARSE, LINEAR = _weights.SPARSE, _weights.LINEAR
FORCED = 1e4  # the selection score of a block that is always taken
Q_ROWS = 256  # query rows a block of the sparse attention holds
MLP_ROWS = 2048
HEAD_BLOCKS = 4
_MATS = ("wq", "wk", "wv", "w_ogate", "wo", "w_gate", "w_up", "w_down")


def _same(x):
    return x


def _same_kv(x, _what):
    return x


def _published(window_scores, overlaps):
    """A block's score: the best of the windows that overlap it."""
    return jnp.where(overlaps, window_scores[..., None], 0.0).max(axis=-2)


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x [s, heads, d], positions 0..s-1, split-halves convention."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def slopes(cfg, layer: int):
    heads = cfg.linear_heads
    base = 2.0 ** (-8.0 * (jnp.arange(heads, dtype=jnp.float32) + 1) / heads)
    return base * (1.0 - layer / max(cfg.num_layers - 1, 1) + 1e-5)


def _in_row_blocks(fn, x, rows: int):
    """``fn`` over ``x`` [s, ...] a block of ``rows`` rows at a time."""
    s = x.shape[0]
    if s <= rows:
        return fn(x)
    n = -(-s // rows)
    padded = jnp.pad(x, ((0, n * rows - s),) + ((0, 0),) * (x.ndim - 1))
    out = jax.lax.map(fn, padded.reshape(n, rows, *x.shape[1:]))
    return out.reshape(n * rows, *out.shape[2:])[:s]


def lightning(q, k, v, slope, kv):
    """The recurrence, one token a step: q, k, v [s, heads, d] ->
    [s, heads, d].  ``kv(S, "state")`` rounds the state as a pool would
    hold it."""
    heads, d = q.shape[1:]
    decay = jnp.exp(-slope)[:, None, None]

    def step(state, qkv):
        qt, kt, vt = qkv
        state = kv(decay * state + kt[:, :, None] * vt[:, None, :], "state")
        return state, jnp.einsum("hd,hde->he", qt / math.sqrt(d), state)

    _, out = jax.lax.scan(
        step, jnp.zeros((heads, d, d), jnp.float32), (q, k, v), unroll=8)
    return out


def _membership(ids, n: int):
    """ids [..., k] (-1: none) -> bool [..., n]."""
    return jnp.any(ids[..., None] == jnp.arange(n), axis=-2)


def sparse_attention(q, k, v, record, prompt_len, cfg, kv, select):
    """One lane of the sparse mixer: q [s, heads, d]; k, v [s, kv heads,
    d]; ``record`` int32 [kv heads, s, topk]; ``prompt_len`` scalar.
    Returns (out [s, heads, d], choice gap [kv heads, s], sets taken
    [kv heads, s, topk])."""
    s, hq, d = q.shape
    g = k.shape[1]
    per = hq // g
    ks, st = cfg.sparse_kernel_size, cfg.sparse_kernel_stride
    bs, topk = cfg.sparse_block_size, cfg.sparse_topk
    n_win = max((s - ks) // st + 1, 0)
    nb = -(-s // bs)
    kk = min(topk, nb)
    k, v = kv(k, "k"), kv(v, "v")
    starts = jnp.arange(n_win) * st
    if n_win:
        ck = kv(k[starts[:, None] + jnp.arange(ks)[None, :]].mean(axis=1),
                "ck")  # [W, g, d]
    else:
        ck = jnp.zeros((0, g, d), jnp.float32)
    w_end = starts + ks - 1
    b_lo = jnp.arange(nb) * bs
    overlaps = (starts[:, None] <= b_lo[None, :] + bs - 1) & (
        w_end[:, None] >= b_lo[None, :])  # [W, nb]
    scale = 1.0 / math.sqrt(d)
    key_pos = jnp.arange(s)

    def rows(t):  # t [bq] positions
        qb = q[jnp.minimum(t, s - 1)].reshape(-1, g, per, d)
        ok_w = w_end[None, :] <= t[:, None]  # [bq, W]
        s_sel = jnp.einsum("qgpd,wgd->gpqw", qb, ck) * scale
        p = jax.nn.softmax(jnp.where(ok_w, s_sel, -jnp.inf), axis=-1)
        p = jnp.where(ok_w, p, 0.0).sum(axis=1)  # [g, bq, W]
        score = select(p, overlaps)  # [g, bq, nb]
        exists = b_lo[None, :] <= t[:, None]
        forced = (b_lo[None, :] < cfg.sparse_init_blocks * bs) | (
            b_lo[None, :] + bs - 1 >= t[:, None] - cfg.sparse_window_size + 1)
        sel = jnp.where(exists, jnp.where(forced, FORCED, score), -1.0)
        vals, own = jax.lax.top_k(sel, kk)
        own = jnp.where(vals >= 0, own, -1)
        rec = record[:, jnp.minimum(t, s - 1), :kk]
        replay = rec[..., :1] >= 0
        taken = jnp.where(replay, rec, own)
        in_own, in_taken = _membership(own, nb), _membership(taken, nb)
        left_out = jnp.where(in_own & ~in_taken, sel, -jnp.inf).max(-1)
        instead = jnp.where(in_taken & ~in_own, sel, jnp.inf).min(-1)
        gap = jnp.where(
            jnp.isfinite(left_out) & jnp.isfinite(instead),
            jnp.maximum(left_out - instead, 0.0), 0.0)
        sparse_row = jnp.maximum(t + 1, prompt_len) >= cfg.sparse_dense_len
        blk = in_taken | ~sparse_row[None, :, None]
        mask = jnp.repeat(blk, bs, axis=-1)[..., :s] & (
            key_pos[None, :] <= t[:, None])[None]
        scores = jnp.einsum("qgpd,kgd->gpqk", qb, k) * scale
        probs = jax.nn.softmax(
            jnp.where(mask[:, None], scores, -jnp.inf), axis=-1)
        out = jnp.einsum("gpqk,kgd->qgpd", probs, v).reshape(-1, hq, d)
        decided = sparse_row[None, :]
        return (out, jnp.where(decided, gap, 0.0),
                jnp.where(decided[..., None], taken, -1))

    n = -(-s // Q_ROWS)
    t_all = jnp.arange(n * Q_ROWS).reshape(n, Q_ROWS)
    out, gap, taken = jax.lax.map(rows, t_all)
    out = out.reshape(n * Q_ROWS, hq, d)[:s]
    gap = jnp.moveaxis(gap, 1, 0).reshape(g, n * Q_ROWS)[:, :s]
    taken = jnp.moveaxis(taken, 1, 0).reshape(g, n * Q_ROWS, kk)[:, :s]
    if kk < topk:
        taken = jnp.pad(taken, ((0, 0), (0, 0), (0, topk - kk)),
                        constant_values=-1)
    return out, gap, taken


@functools.lru_cache(maxsize=32)
def _programs(cfg, control):
    """The jitted pieces of one (configuration, control) pair: a layer of
    each kind, the head; every layer of a kind shares a trace."""
    control = control or Control()
    prep = control.weights or _same
    act, kv = control.act or _same, control.kv or _same_kv
    select = control.router or _published
    r = cfg.scale_depth / math.sqrt(cfg.num_layers) if cfg.scale_depth else 1.0
    eps = cfg.norm_eps

    def make_layer(kind):
        heads, kv_heads, d = _weights.geometry(cfg, kind)

        def layer(x, w, gains, slope, record, prompt_len):
            w32 = {n: prep(_weights.dequantized(w, n)) for n in _MATS}
            g32 = {n: v.astype(jnp.float32) for n, v in gains.items()}

            def mlp(rows):
                y = act(_rmsnorm(rows, g32["mlp_norm_g"], eps))
                return act(jax.nn.silu(y @ w32["w_gate"]) * (
                    y @ w32["w_up"])) @ w32["w_down"]

            def lane(args):
                x, record, prompt_len = args
                s = x.shape[0]
                y = act(_rmsnorm(x, g32["attn_norm_g"], eps))
                q = (y @ w32["wq"]).reshape(s, heads, d)
                k = (y @ w32["wk"]).reshape(s, kv_heads, d)
                v = (y @ w32["wv"]).reshape(s, kv_heads, d)
                q = _rmsnorm(q, g32["q_norm_g"], eps)
                k = _rmsnorm(k, g32["k_norm_g"], eps)
                if kind == LINEAR:
                    q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)
                    o = lightning(q, k, v, slope, kv)
                    o = _rmsnorm(o, g32["o_norm_g"].reshape(heads, d), eps)
                    gap = jnp.zeros((0, s), jnp.float32)
                    taken = jnp.zeros((0, s, cfg.sparse_topk), jnp.int32)
                else:
                    o, gap, taken = sparse_attention(
                        q, k, v, record, prompt_len, cfg, kv, select)
                gate = jax.nn.sigmoid(y @ w32["w_ogate"])
                x = x + r * (act(o.reshape(s, heads * d) * gate) @ w32["wo"])
                x = x + r * _in_row_blocks(mlp, x, MLP_ROWS)
                return x, gap, taken

            x, gap, taken = jax.lax.map(lane, (x, record, prompt_len))
            # [b, g, s(, k)] -> [g, b, s(, k)]
            return x, jnp.moveaxis(gap, 0, 1), jnp.moveaxis(taken, 0, 1)

        return jax.jit(layer)

    @jax.jit
    def head(x, rows, g_final, w):
        picked = jnp.take_along_axis(x, rows[:, :, None], axis=1)
        y = act(_rmsnorm(picked, g_final.astype(jnp.float32), eps))
        w_head = w["lm_head"]
        scale = w.get("lm_head" + _weights.SCALE)
        step = -(-w_head.shape[1] // HEAD_BLOCKS)
        out = []
        for a in range(0, w_head.shape[1], step):
            part = {"lm_head": w_head[:, a:a + step]}
            if scale is not None:
                part["lm_head" + _weights.SCALE] = scale[a:a + step]
            out.append(y @ prep(_weights.dequantized(part, "lm_head")))
        logits = jnp.concatenate(out, axis=-1)
        if cfg.dim_model_base:
            logits = logits / (cfg.hidden_dim / cfg.dim_model_base)
        return logits

    return {SPARSE: make_layer(SPARSE), LINEAR: make_layer(LINEAR)}, head


def _layer_tensors(params, i):
    prefix = f"l{i}_"
    own = {k[len(prefix):]: v for k, v in params.items()
           if k.startswith(prefix)}
    gains = {k: v for k, v in own.items() if k.endswith("_norm_g")}
    return {k: v for k, v in own.items() if k not in gains}, gains


def n_decisions(cfg) -> int:
    """Rows of the record: sparse layers x kv heads."""
    return sum(m == SPARSE for m in cfg.mixer_types) * cfg.num_kv_heads


def _record(routing, cfg, ids) -> np.ndarray:
    """The record as this block wants it: int32 [sparse layers x kv heads,
    b, s, topk]; a row's set either starts with an id >= 0 or is all -1;
    every id a block of the sequence, none twice."""
    shape = (n_decisions(cfg), *ids.shape, cfg.sparse_topk)
    if routing is None:
        return np.full(shape, -1, np.int32)
    record = np.asarray(routing)
    if record.shape != shape or not np.issubdtype(record.dtype, np.integer):
        raise ValueError(
            f"selection record {record.dtype}{list(record.shape)}: this "
            f"block wants int32{list(shape)}"
        )
    held = record[record[..., 0] >= 0]
    ordered = np.sort(held, axis=-1)
    n_blocks = -(-ids.shape[1] // cfg.sparse_block_size)
    twice = (ordered[:, 1:] == ordered[:, :-1]) & (ordered[:, 1:] >= 0)
    if (held < -1).any() or (held >= n_blocks).any() or twice.any():
        raise ValueError(
            "selection record: a row names a block that is none of "
            f"0..{n_blocks - 1}, or one twice"
        )
    return record.astype(np.int32)


def forward_logits(params, cfg, ids, rows,
                   control: Optional[Control] = None, routing=None,
                   prompt_lengths=None):
    """(logits float32 [b, n_rows, vocab] of one full forward pass over
    ``ids`` [b, s] at the positions ``rows`` [b, n_rows]; the choice gap
    of every decision float32 [sparse layers x kv heads, b, s]; the block
    sets the pass computed with int32 [.., b, s, topk], -1 on rows that
    ran dense).

    ``routing``: the program's record, replayed where a row's first id is
    >= 0 (elsewhere: this pass's own choice); ``None``: own choices
    throughout.  ``control``: one of ``weights.controls_for(cfg)``.
    ``prompt_lengths`` [b] (tests): the rows before it belong to a forward
    of that many tokens, each later row to one of ``t + 1``; default: one
    forward of ``s`` tokens."""
    layers, head = _programs(cfg, control)
    ids = jnp.asarray(ids)
    b, s = ids.shape
    record = _record(routing, cfg, ids)
    if prompt_lengths is None:
        prompt_lengths = np.full((b,), s, np.int32)
    prompt_lengths = jnp.asarray(prompt_lengths, jnp.int32)
    g = cfg.num_kv_heads
    none = jnp.zeros((b, 0, s, cfg.sparse_topk), jnp.int32)
    gaps, sets, at = [], [], 0
    with jax.default_matmul_precision("highest"):
        x = params["tok_emb"][ids].astype(jnp.float32) * cfg.scale_emb
        for i, kind in enumerate(cfg.mixer_types):
            w, gains = _layer_tensors(params, i)
            if kind == LINEAR:
                x, _, _ = layers[kind](
                    x, w, gains, slopes(cfg, i), none, prompt_lengths)
                continue
            rec = jnp.asarray(
                np.moveaxis(record[at:at + g], 0, 1))  # [b, g, s, topk]
            at += g
            x, gap, taken = layers[kind](
                x, w, gains, jnp.zeros((0,), jnp.float32), rec,
                prompt_lengths)
            gaps.append(gap)
            sets.append(taken)
        logits = head(
            x, jnp.asarray(rows), params["final_norm_g"],
            {k: v for k, v in params.items() if k.startswith("lm_head")})
    return logits, jnp.concatenate(gaps), jnp.concatenate(sets)
