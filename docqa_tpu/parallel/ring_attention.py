"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The reference *avoids* long context entirely — 500-char chunks
(``semantic-indexer/indexer.py:120``), k=3 retrieval (``llm-qa/main.py:101``),
tail-truncation summaries (``synthese-comparative/core/llm_client.py:26-30``)
— because its generation is delegated to an external llama.cpp process that
cannot scale context.  Here long clinical dossiers are first-class: the
sequence axis shards over the ICI ring and attention runs blockwise, so the
context budget grows linearly with the number of devices instead of being
truncated.

Two interchangeable strategies, both pure-JAX collectives (no NCCL/MPI —
SURVEY §2c):

* :func:`ring_attention` — the KV shard rotates around the ring via
  ``lax.ppermute`` while each device keeps its Q shard; partial results merge
  with the same online-softmax (m, l) accumulation as the Pallas flash kernel
  in ``ops/attention.py``.  Communication is overlap-friendly and per-step
  memory is O(local_kv); works for any head count.
* :func:`ulysses_attention` — two ``lax.all_to_all`` reshuffles (seq-sharded
  -> head-sharded and back), full-context attention locally.  Cheaper compute
  (one dense local attention, no n-step loop) but requires
  ``num_heads % ring_size == 0`` and O(full_seq) local memory.

Both compose with the (data, model) mesh: shard the sequence over the
``model`` axis for serving (the TP weights are already there) or over a
dedicated ``seq`` axis on bigger meshes.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from docqa_tpu.runtime.mesh import MeshContext

NEG_INF = -1e30


# --------------------------------------------------------------------------
# Ring attention (shard_map-local implementation)
# --------------------------------------------------------------------------

def ring_attention_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    *,
    causal: bool = False,
    lengths: Optional[jax.Array] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Ring attention over sequence shards — call INSIDE ``shard_map``.

    Args:
      q, k, v: local shards ``[batch, s_local, heads, head_dim]``; device i of
        the ring holds global positions ``[i*s_local, (i+1)*s_local)``.
      axis_name: mesh axis the sequence is sharded over.
      lengths: global ``[batch]`` int32 valid-prefix lengths (padding mask).
      causal: standard causal masking in *global* positions.

    Returns the local output shard ``[batch, s_local, heads, head_dim]``.
    """
    b, s_loc, hq, d = q.shape
    _, skv_loc, hkv, _ = k.shape
    if hq % hkv:
        raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
    groups = hq // hkv
    scale = scale if scale is not None else d ** -0.5

    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)

    qf = q.astype(jnp.float32) * scale
    q_abs = idx * s_loc + jnp.arange(s_loc)  # [s_loc] global q positions

    # ring: each step, kv blocks move to the next device, so after t steps
    # device i holds the block that originated on device (i - t) mod n
    perm = [(i, (i + 1) % n) for i in range(n)]

    def merge(t, kc, vc, acc, m, l):
        """Online-softmax merge of the kv block held at ring step ``t``."""
        # GQA kv shards circulate at their native head count; expansion to q
        # heads happens transiently inside the step so the ppermute (ICI
        # bytes) and the loop carry stay O(hkv), not O(hq)
        ke = jnp.repeat(kc, groups, axis=2) if groups > 1 else kc
        ve = jnp.repeat(vc, groups, axis=2) if groups > 1 else vc
        src = (idx - t) % n
        kv_abs = src * skv_loc + jnp.arange(skv_loc)  # [skv_loc]

        mask = jnp.ones((b, 1, s_loc, skv_loc), bool)
        if lengths is not None:
            mask &= kv_abs[None, None, None, :] < lengths[:, None, None, None]
        if causal:
            mask &= kv_abs[None, None, None, :] <= q_abs[None, None, :, None]

        s = jnp.einsum(
            "bqhd,bkhd->bhqk",
            qf,
            ke.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        s = jnp.where(mask, s, NEG_INF)

        m_cur = jnp.max(s, axis=-1, keepdims=True)  # [b,h,sq,1]
        m_new = jnp.maximum(m, m_cur)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.einsum(
            "bhqk,bkhd->bqhd",
            p,
            ve.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        acc = acc * alpha.transpose(0, 2, 1, 3) + pv
        return acc, m_new, l

    def step(t, carry):
        kc, vc, acc, m, l = carry
        acc, m, l = merge(t, kc, vc, acc, m, l)
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        return kc, vc, acc, m, l

    acc0 = jnp.zeros((b, s_loc, hq, d), jnp.float32)
    m0 = jnp.full((b, hq, s_loc, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hq, s_loc, 1), jnp.float32)
    # n-1 rotation rounds, not n: after round n-2 every device holds the
    # block it still needs for the final merge, and the n-th ppermute would
    # only return shards to their origin — pure wasted ICI bytes.  The
    # collective budget (shard_budget.json, scripts/shard_audit.py) pins
    # this: ring rounds == ring_size - 1.
    kc, vc, acc, m, l = jax.lax.fori_loop(
        0, n - 1, step, (k, v, acc0, m0, l0)
    )
    acc, _, l = merge(n - 1, kc, vc, acc, m, l)

    denom = jnp.maximum(l.transpose(0, 2, 1, 3), 1e-30)  # [b,sq,h,1]
    out = acc / denom
    # rows with no live kv position (fully padded / pre-causal) output zeros
    out = jnp.where(l.transpose(0, 2, 1, 3) > 0.0, out, 0.0)
    return out.astype(q.dtype)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: MeshContext,
    *,
    seq_axis: Optional[str] = None,
    causal: bool = False,
    lengths: Optional[jax.Array] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Global-view ring attention: shards the sequence axis of ``[b, s, h, d]``
    tensors over ``seq_axis`` (default: the mesh's model axis) and runs
    :func:`ring_attention_local` under ``shard_map``."""
    ax = seq_axis or mesh.model_axis
    n = mesh.mesh.shape[ax]
    if q.shape[1] % n:
        raise ValueError(f"seq len {q.shape[1]} not divisible by ring size {n}")
    seq_spec = P(None, ax, None, None)
    in_specs = [seq_spec, seq_spec, seq_spec]
    args = [q, k, v]
    if lengths is not None:
        in_specs.append(P(None))
        args.append(lengths.astype(jnp.int32))

    fn = functools.partial(
        ring_attention_local, axis_name=ax, causal=causal, scale=scale
    )

    def wrapped(*xs):
        if lengths is not None:
            return fn(xs[0], xs[1], xs[2], lengths=xs[3])
        return fn(xs[0], xs[1], xs[2])

    return shard_map(
        wrapped,
        mesh=mesh.mesh,
        in_specs=tuple(in_specs),
        out_specs=seq_spec,
        check_vma=False,
    )(*args)


# --------------------------------------------------------------------------
# Ulysses (all-to-all) sequence parallelism
# --------------------------------------------------------------------------

def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: MeshContext,
    *,
    seq_axis: Optional[str] = None,
    causal: bool = False,
    lengths: Optional[jax.Array] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """All-to-all sequence parallelism: reshuffle seq-sharded -> head-sharded,
    run one dense full-context attention per head group, reshuffle back.

    Requires ``num_q_heads % ring_size == 0`` and, for GQA, the kv heads to
    divide as well (kv is expanded to q heads first when they don't).
    """
    from docqa_tpu.ops.attention import attention_reference

    ax = seq_axis or mesh.model_axis
    n = mesh.mesh.shape[ax]
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    if s % n:
        raise ValueError(f"seq len {s} not divisible by group size {n}")
    if hq % n:
        raise ValueError(f"{hq} heads not divisible by group size {n}")
    if hkv != hq and hkv % n:
        k = jnp.repeat(k, hq // hkv, axis=2)
        v = jnp.repeat(v, hq // hkv, axis=2)

    seq_spec = P(None, ax, None, None)
    in_specs = [seq_spec, seq_spec, seq_spec]
    args = [q, k, v]
    if lengths is not None:
        in_specs.append(P(None))
        args.append(lengths.astype(jnp.int32))

    def local(*xs):
        ql, kl, vl = xs[:3]
        lens = xs[3] if lengths is not None else None
        # seq-sharded [b, s/n, h, d] -> head-sharded [b, s, h/n, d]
        qh = jax.lax.all_to_all(ql, ax, split_axis=2, concat_axis=1, tiled=True)
        kh = jax.lax.all_to_all(kl, ax, split_axis=2, concat_axis=1, tiled=True)
        vh = jax.lax.all_to_all(vl, ax, split_axis=2, concat_axis=1, tiled=True)
        qo = jnp.zeros((b,), jnp.int32) if causal else None
        out = attention_reference(
            qh, kh, vh, causal=causal, lengths=lens, q_offset=qo, scale=scale
        )
        # head-sharded -> seq-sharded
        return jax.lax.all_to_all(out, ax, split_axis=1, concat_axis=2, tiled=True)

    return shard_map(
        local,
        mesh=mesh.mesh,
        in_specs=tuple(in_specs),
        out_specs=seq_spec,
        check_vma=False,
    )(*args)
