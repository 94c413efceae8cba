"""What decides ``correct``: arithmetic against the plain references,
outside the timed window.  Nothing a request's status, route or timing
could change enters here.

Decoder: a seeded batch of prompts goes through the program's
``ragged_prefill_forward`` and then ``paged_decode_forward`` steps (the
speculative verify width) through a paged pool of the served shape, with
the flash kernel where the engine uses it.  The LOGITS at the last prompt
position and at every decoded position are compared with the one full
forward pass over prompt + forced tokens of the plain reference in the
configuration's architecture package (``arch.load``).  The compared sizes
are the configuration's ``check`` block: ``prompt_lengths`` (base lengths,
taken in turn by the lanes, each plus a seeded 0..``LENGTH_JITTER``-1) and
``lane_rows`` (packed rows reserved per prompt); ``DECODE_STEPS`` decode
steps follow the prefill.  The number compared is
the worst row's relative error ``|l_prog - l_ref| / |l_ref - mean(l_ref)|``
(2-norms over the vocabulary).

Retrieval: the store's top-k for seeded queries against a numpy float32
scan of the same rows, by SCORE: the worst of |returned score - true score
of that row| and (reference k-th score - true score of a returned row).
A tie or a batch-dependent rounding cannot flip it; a wrong row or a
coarser row type can.

The limits live in the configuration file (``correct``), with the readings
they were set from in PERF.md.
"""

from __future__ import annotations

from typing import Callable, Dict

import numpy as np

RAGGED_ALIGN = 128  # ops/attention.py packs each prompt from a 128-row start
LENGTH_JITTER = 40  # a seeded 0..39 on top of each base length
DECODE_STEPS = 2  # decode steps compared after the prefill


def sample_prompts(seed: int, vocab: int, n_lanes: int, n_decode: int,
                   spec: dict):
    """Seeded token ids: (ids [n_lanes, lane_rows], lengths), at the sizes
    of ``spec`` (the configuration's ``check`` block).  The array's shape
    is the same for every seed, so every seed runs the same programs."""
    rng = np.random.default_rng([seed % (2**31), 7])
    base = spec["prompt_lengths"]
    lengths = [base[i % len(base)] + int(rng.integers(0, LENGTH_JITTER))
               for i in range(n_lanes)]
    total = int(spec["lane_rows"])
    if total % RAGGED_ALIGN:
        raise ValueError(f"check.lane_rows is no multiple of {RAGGED_ALIGN}")
    if max(lengths) + n_decode > total:
        raise ValueError("a checked prompt does not fit check.lane_rows")
    ids = rng.integers(5, vocab, size=(n_lanes, total), dtype=np.int32)
    return ids, np.asarray(lengths, np.int32)


_PROGRAMS: Dict[tuple, tuple] = {}


def _paged_programs(cfg, block_size, seq_capacity, use_flash, mesh):
    """The program's two paged forwards under ``jax.jit``, one pair per
    (configuration, pool geometry, mesh) for the life of the process."""
    import jax

    from docqa_tpu.engines.paged import (
        paged_decode_forward,
        ragged_prefill_forward,
    )

    key = (cfg, block_size, seq_capacity, use_flash, id(mesh))
    if key not in _PROGRAMS:

        @jax.jit
        def prefill(params, pools, packed, seg, pos, dest, last):
            return ragged_prefill_forward(
                params, cfg, pools, packed, seg, pos, dest, last,
                rope_len=seq_capacity,
            )

        @jax.jit
        def decode(params, pools, tables, tok, lens):
            return paged_decode_forward(
                params, cfg, pools, tables, tok, lens,
                block_size=block_size, rope_len=seq_capacity,
                use_flash=use_flash, mesh=mesh,
            )

        _PROGRAMS[key] = (prefill, decode)
    return _PROGRAMS[key]


def program_logits(engine, ids, lengths, n_steps: int, step_width: int,
                   n_blocks: int, block_size: int, seq_capacity: int,
                   mesh=None):
    """(logits [lanes, 1 + n_steps * step_width, vocab] from the program's
    own paged path, teacher-forced with ``ids``; the bits per element of
    the narrowest array of the KV pools the program wrote)."""
    import jax.numpy as jnp

    from docqa_tpu.engines.paged import init_paged_pools

    cfg, params = engine.cfg, engine.params
    lanes, lane_rows = ids.shape
    blocks_per_seq = seq_capacity // block_size
    if lanes * blocks_per_seq > n_blocks:
        raise ValueError("the check's lanes do not fit the pool")
    sharding = None
    if mesh is not None:
        from docqa_tpu.parallel.sharding import paged_pool_sharding

        sharding = paged_pool_sharding(mesh)
    pools = init_paged_pools(cfg, n_blocks, block_size, sharding=sharding)
    n_rows = n_blocks * block_size

    # pack: lane b occupies rows [start_b, start_b + L_b), starts aligned
    starts = [b * lane_rows for b in range(lanes)]
    budget = max(lanes * lane_rows, 2 * RAGGED_ALIGN)
    packed = np.zeros((budget,), np.int32)
    seg = np.full((budget,), -1, np.int32)
    pos = np.zeros((budget,), np.int32)
    dest = np.full((budget,), n_rows, np.int32)  # out of bounds: dropped
    last = np.zeros((lanes,), np.int32)
    for b, (start, length) in enumerate(zip(starts, lengths)):
        length = int(length)
        packed[start:start + length] = ids[b, :length]
        seg[start:start + length] = b
        pos[start:start + length] = np.arange(length)
        dest[start:start + length] = b * seq_capacity + np.arange(length)
        last[b] = start + length - 1

    prefill, decode = _paged_programs(
        cfg, block_size, seq_capacity, bool(engine.use_flash), mesh
    )
    first, pools = prefill(
        params, pools, jnp.asarray(packed), jnp.asarray(seg),
        jnp.asarray(pos), jnp.asarray(dest), jnp.asarray(last),
    )
    out = [np.asarray(first, np.float32)[:, None, :]]
    tables = jnp.asarray(
        np.arange(lanes * blocks_per_seq, dtype=np.int32).reshape(
            lanes, blocks_per_seq
        )
    )
    lens = np.asarray(lengths, np.int32).copy()
    for _ in range(n_steps):
        tok = np.stack(
            [ids[b, lens[b]:lens[b] + step_width] for b in range(lanes)]
        )
        logits, pools = decode(
            params, pools, tables, jnp.asarray(tok), jnp.asarray(lens)
        )
        out.append(np.asarray(logits, np.float32))
        lens = lens + step_width
    import jax

    kv_bits = min(8 * leaf.dtype.itemsize for leaf in jax.tree.leaves(pools))
    del pools
    return np.concatenate(out, axis=1), kv_bits


def kv_bits_missing(stated_bits: int, found_bits: int) -> int:
    """Bits by which the program's KV pool falls short of the type the
    configuration states (``kv_cache_bits``): 0 for a sound run, and the
    limit is 0.  An exact comparison, because the logits cannot make it:
    keys and values rounded to int8 per token and head move them less
    than bfloat16 arithmetic does."""
    return max(0, int(stated_bits) - int(found_bits))


def reference_logits(arch, params, cfg, ids, lengths, n_rows: int,
                     control=None):
    """The logits of ``arch``'s plain reference at the same positions: rows
    L-1 .. L-1+n_rows-1 of one full forward pass per lane."""
    import jax.numpy as jnp

    rows = np.asarray(lengths)[:, None] - 1 + np.arange(n_rows)[None, :]
    out = arch.reference.forward_logits(
        params, cfg, jnp.asarray(ids), jnp.asarray(rows.astype(np.int32)),
        control=control,
    )
    return np.asarray(out, np.float32)


def logit_error(got: np.ndarray, want: np.ndarray) -> Dict[str, float]:
    """Relative error per compared row; the worst row decides."""
    centred = want - want.mean(axis=-1, keepdims=True)
    err = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(centred, axis=-1)
    return {
        "worst_row": float(err.max()),
        "mean_row": float(err.mean()),
        "prefill_rows": float(err[:, 0].max()),
        "decode_rows": float(err[:, 1:].max()) if err.shape[1] > 1 else 0.0,
    }


def decoder_check(arch, spec: dict, engine, seed: int, n_blocks: int,
                  block_size: int, seq_capacity: int, n_lanes: int,
                  step_width: int, mesh=None,
                  control: bool = False) -> Dict[str, float]:
    """The decoder comparison of one seed, against the reference of the
    architecture package ``arch`` at the sizes of ``spec`` (the
    configuration's ``check`` block).  ``control``: ALSO put the package's
    lower-precision references in the program's place."""
    n_steps = DECODE_STEPS
    ids, lengths = sample_prompts(
        seed, engine.cfg.vocab_size, n_lanes, n_steps * step_width, spec
    )
    n_rows = 1 + n_steps * step_width
    want = reference_logits(
        arch, engine.params, engine.cfg, ids, lengths, n_rows
    )
    got, kv_bits = program_logits(
        engine, ids, lengths, n_steps, step_width, n_blocks, block_size,
        seq_capacity, mesh=mesh,
    )
    out = {"program": logit_error(got, want), "kv_bits": kv_bits}
    if control:
        def reading(c):
            return logit_error(
                reference_logits(arch, engine.params, engine.cfg, ids,
                                 lengths, n_rows, control=c),
                want,
            )

        # every control of the configuration; "control" is the one that
        # reads smallest, the upper end of any limit
        out["controls"] = {
            name: reading(c)
            for name, c in arch.weights.controls_for(engine.cfg).items()
        }
        out["control"] = min(
            out["controls"].values(), key=lambda e: e["worst_row"]
        )
        out["kv_only"] = {
            name: reading(c)
            for name, c in arch.weights.kv_only_controls().items()
        }
    return out


# ---- retrieval ------------------------------------------------------------

def to_bf16(x: np.ndarray) -> np.ndarray:
    """float32 -> bfloat16 (round to nearest even), the row type the
    configuration states for the store."""
    import ml_dtypes

    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16)


def unit_rows(x: np.ndarray) -> np.ndarray:
    """Rows scaled to unit length in float32, as ``VectorStore.add`` does
    before it stores them."""
    x = np.asarray(x, np.float32)
    return x / np.maximum(np.linalg.norm(x, axis=1, keepdims=True), 1e-9)


def to_int8_rows(x: np.ndarray) -> np.ndarray:
    """Per-row absmax int8 and back: the control's coarser row type."""
    x = np.asarray(x, np.float32)
    scale = np.maximum(np.abs(x).max(axis=1, keepdims=True) / 127.0, 1e-12)
    return np.clip(np.round(x / scale), -127, 127) * scale


def _scan(q32: np.ndarray, stored, coarse: bool = False,
          chunk: int = 131072) -> np.ndarray:
    """float32 scores [q, n] of bfloat16 queries against the stored rows,
    a chunk of rows at a time (the rows stay in their 2-byte type)."""
    qb = to_bf16(q32).astype(np.float32)
    out = np.empty((len(qb), len(stored)), np.float32)
    for a in range(0, len(stored), chunk):
        rows = stored[a:a + chunk].astype(np.float32)
        if coarse:
            rows = to_int8_rows(rows)
        out[:, a:a + chunk] = qb @ rows.T
    return out


def retrieval_queries(stored, seed: int, n: int = 8) -> np.ndarray:
    """Seeded queries: half random directions, half sitting next to stored
    rows, so that near-ties occur."""
    rng = np.random.default_rng([seed % (2**31), 11])
    q = rng.standard_normal((n, stored.shape[1])).astype(np.float32)
    picks = rng.integers(0, len(stored), size=n // 2)
    q[: n // 2] = stored[picks].astype(np.float32) + 0.05 * q[: n // 2]
    return q


def store_search(store) -> Callable:
    """``VectorStore.search`` as (scores [q, k], row ids [q, k])."""

    def search(queries, k):
        hits = store.search(queries, k=k)
        return (
            np.asarray([[h.score for h in row] for row in hits], np.float32),
            np.asarray([[h.row_id for h in row] for row in hits]),
        )

    return search


def retrieval_error(stored, queries: np.ndarray, k: int,
                    search: Callable) -> float:
    """``stored``: the unit rows the benchmark handed the store, rounded to
    bfloat16.  ``search(queries, k)`` -> (scores [q, k], row ids [q, k]).
    Returns the worst of |returned score - true score| and the shortfall
    of a returned row's true score against the reference's k-th best.

    ``search`` is handed the RAW queries: the store scales them to unit
    length itself, exactly once, as the reference does.  Scaling an
    already scaled query again moves a coordinate across a bfloat16
    rounding boundary in ~2 % of seeds and the scores by up to 1e-4 (a
    chip run read 3.8e-5 that way, PR 24)."""
    truth = _scan(unit_rows(queries), stored)
    kth = np.partition(truth, -k, axis=1)[:, -k]
    scores, ids = search(queries, k)
    worst = 0.0
    for i in range(len(queries)):
        true_of_returned = truth[i, ids[i]]
        worst = max(
            worst,
            float(np.abs(scores[i] - true_of_returned).max()),
            float((kth[i] - true_of_returned).max()),
        )
    return worst


def control_search(stored):
    """The retrieval control: the reference scan over int8 rows."""

    def search(queries, k):
        s = _scan(unit_rows(queries), stored, coarse=True)
        ids = np.argsort(-s, axis=1)[:, :k]
        return np.take_along_axis(s, ids, axis=1), ids

    return search
