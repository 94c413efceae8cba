"""The retention mixer's decode step as ONE pass over a lane's state
(``models/hybrid.py``, the ``retention`` kind; the recurrence and its XLA
form are ``ops/attention.power_retention_step``).

The XLA form is two statements the compiler emits as two fusions over a
34 MB-a-lane array — the update reads and writes it, the read reads it
again — and it runs over EVERY entry of the pool.  The Pallas kernel here
fetches a tile of an entry once, advances it, sums the read from the
advanced tile while it is in fast memory and writes it back once, in
place, float32 throughout; its grid walks the entries a live lane owns,
so an entry no lane owns is neither read nor written.

A module of its own: a Mosaic payload carries source locations, and no
line of an older kernel may move.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from docqa_tpu.ops.attention import (
    RETENTION_EPS,
    power_feature_count,
    power_retention_step,
)
from docqa_tpu.utils import round_up

# what one block of the pool may take of fast memory (the pipeline holds
# four: two in, two out), and the running registers a group of value rows
# may keep (one a (row, query head) — 64 in all on the chip)
RETENTION_BLOCK_BYTES = 2 << 20
RETENTION_GROUP_SUMS = 16
# register columns a pass of the kernel's loop over a group's features
# (PR 52, the bare op on the chip, four lanes: 0.465-0.469 ms a layer-step
# at 4, 0.468 at 8, 0.462-0.465 at 16, 0.460 at 32, 0.465 with all 64 in
# one pass — one reading's noise; the program's equations 650, 1,080 at 16,
# 2,810 at 64)
RETENTION_LOOP_COLUMNS = 4
# what a grid step may take of fast memory in all (``_vmem_bytes``): the
# call asks for it by name, of the chip's 128 MiB, where it is over the
# 16 MiB scoped default
RETENTION_VMEM_BYTES = 40 << 20
_LANES = 128


def _tile_bytes(kv_heads: int, features: int) -> int:
    """A float32 [kv heads, features] slab as fast memory holds it: whole
    (8, 128) tiles."""
    return round_up(kv_heads, 8) * round_up(features, _LANES) * 4


def retention_row_blocks(num_heads: int, kv_heads: int,
                         head_dim: int) -> tuple:
    """(value rows a GROUP, groups a BLOCK) of a state's ``head_dim + 1``
    rows (the channels and the running sum of weights; the dims above the
    last two carry no tiling, so any count is a block).  A group is what
    the kernel advances and reads together, the features loaded once for
    all its rows: the largest divisor of the rows whose running sums (one
    a row and query head of a kv head) stay registers — 3 of 129 at five
    query heads.  A block is what one grid step fetches: as many groups
    as ``RETENTION_BLOCK_BYTES`` holds (6 rows, 1.6 MB, at 8 kv heads of
    128: a step's fixed cost is paid 22 times an entry, not 129); the
    last block of an entry may be short."""
    rows = head_dim + 1
    per = max(1, num_heads // kv_heads)
    group = max(n for n in range(1, rows + 1) if rows % n == 0 and (
        n == 1 or n * per <= RETENTION_GROUP_SUMS))
    tile = _tile_bytes(kv_heads, power_feature_count(head_dim))
    groups = min(rows // group,
                 max(1, RETENTION_BLOCK_BYTES // (group * tile)))
    return group, groups


def _vmem_bytes(num_heads: int, kv_heads: int, head_dim: int) -> int:
    """What a grid step takes of fast memory: the pool's block in and
    out, twice buffered, and the features of the key and of each query
    head of a kv head, built there once an entry (the block's value rows
    and sums are a register or two)."""
    group, groups = retention_row_blocks(num_heads, kv_heads, head_dim)
    tile = _tile_bytes(kv_heads, power_feature_count(head_dim))
    return (4 * group * groups + num_heads // kv_heads + 1) * tile


def retention_kernel_supported(num_heads: int, kv_heads: int,
                               head_dim: int) -> bool:
    """Whether :func:`power_retention_step_fused` steps a state of this
    geometry: the heads are whole groups of the kv heads, the head is
    whole 128-lane registers (the kernel's loops slice the features at
    multiples of it, and Mosaic slices by whole registers), and a grid
    step's blocks fit ``RETENTION_VMEM_BYTES``.  Anything else stays on
    the XLA form."""
    if kv_heads <= 0 or num_heads % kv_heads or head_dim % _LANES:
        return False
    return _vmem_bytes(num_heads, kv_heads, head_dim) <= RETENTION_VMEM_BYTES


def _retention_step_kernel(
    # scalar prefetch
    owned_ref,  # [E] int32: the entries a live lane owns, first
    count_ref,  # [1] int32: how many of them count
    # blocks of the entry (and of its block of value rows) the grid step
    # works on
    decay_ref,  # [1, kv heads, 1] f32: e^gate
    vv_ref,  # [1, 1, kv heads, rows] f32: (v, 1) / d, a column a value row
    kq_ref,  # [1, 1 + per, kv heads, d] f32: the key, then the kv head's
    # query heads
    pool_ref,  # [1, rows, kv heads, F] f32: the step's value rows
    out_ref,  # the same block of the same pool (aliased): as advanced
    num_ref,  # [1, 1, kv heads, rows * per] f32: sum_f phi(q) * advanced,
    # a column a (value row, query head)
    phi_ref,  # scratch [1 + per, kv heads, F] f32: phi(k), then phi(q)
    *,
    group: int,
    channels: int,
):
    """One grid step = a block of value rows of one owned entry, a GROUP
    of them at a time, a 128-lane register of every kv head at a time: the
    key's and the queries' features are loaded once a register column and
    serve every row of the group, a state register is loaded, advanced,
    stored and multiplied into one running register a (row, query head) —
    reduced across lanes once, at the group's end.  The whole columns are
    a LOOP (a body of one column's operations however many there are: an
    unrolled one was ~4,400 equations lowered again with every program
    that holds the kernel); ``F`` is no whole number of registers (8,256
    = 64.5 x 128): the last column is read as wide as it is, after the
    loop, so what fast memory holds past it is never summed.

    An entry's first step builds the features where they are read
    (``ops/attention.power_features``, to the bit: block ``s`` of ``phi``
    is ``x * roll(x, -s)``, the last one half as wide, a pair of different
    channels weighed 2 on the key's side — the key doubled before the
    product, which rounds nothing), the key's and the queries' in one
    operation a block: 1.6 MB an entry that never cross the memory's
    wires.  Its rolls are static: a loop's roll by a traced amount waits
    out the unit's latency 63 times an entry (measured: +7 us an entry)."""
    _, rows, kv_heads, features = pool_ref.shape
    per, d = kq_ref.shape[1] - 1, kq_ref.shape[3]
    live = count_ref[0] > 0
    first = pl.program_id(1) == 0
    # an entry's last block may be short: the groups of rows it holds
    here = jnp.minimum(rows, channels - pl.program_id(1) * rows) // group
    width = min(_LANES, features)
    row_of = jax.lax.broadcasted_iota(jnp.int32, (kv_heads, rows), 1)
    sum_of = jax.lax.broadcasted_iota(jnp.int32, (kv_heads, rows * per), 1)

    @pl.when(live & first)
    def _features():
        half = d // 2
        x = kq_ref[0]  # [1 + per, kv heads, d]: the key, then the queries
        twice = jnp.where(
            jax.lax.broadcasted_iota(jnp.int32, x.shape, 0) == 0, 2.0 * x, x)
        for s in range(half + 1):
            pair = x * x if s == 0 else twice * pltpu.roll(x, d - s, axis=2)
            w = d if s < half else half
            phi_ref[:, :, s * d:s * d + w] = pair[:, :, :w]

    def step_group(g, sums):
        row = g * group  # within the block
        decay = jnp.broadcast_to(decay_ref[0], (kv_heads, width))
        values = vv_ref[0, 0]
        add = [jnp.broadcast_to(jnp.sum(
            jnp.where(row_of == row + e, values, 0.0), axis=-1,
            keepdims=True), (kv_heads, width)) for e in range(group)]

        def column(lo, w):
            """The group's rows over ``w`` lanes from ``lo``: advanced,
            stored, and a row's products with the features of the kv
            head's query heads ([per, kv heads, w], one operation)."""
            cols = pl.ds(lo, w)
            pk, pq = phi_ref[0, :, cols], phi_ref[1:, :, cols]
            terms = []
            for e in range(group):
                new = (decay[:, :w] * pool_ref[0, row + e, :, cols]
                       + add[e][:, :w] * pk)
                out_ref[0, row + e, :, cols] = new
                terms.append(new[None] * pq)
            return terms

        def plus(acc, lo, w):
            return [a + t for a, t in zip(acc, column(lo, w))]

        # the whole register columns in a loop, RETENTION_LOOP_COLUMNS of
        # them a pass; what the loop leaves, then the short last one
        whole, step = features // width, RETENTION_LOOP_COLUMNS

        def columns(c, acc):
            for u in range(step):
                acc = plus(acc, pl.multiple_of(
                    (c * step + u) * width, width), width)
            return acc

        acc = jax.lax.fori_loop(
            0, whole // step, columns,
            [jnp.zeros((per, kv_heads, width), jnp.float32)] * group)
        for c in range(whole - whole % step, whole):
            acc = plus(acc, c * width, width)
        totals = [jnp.sum(a, axis=-1, keepdims=True) for a in acc]
        if features > whole * width:
            totals = [total + jnp.sum(t, axis=-1, keepdims=True)
                      for total, t in zip(totals, column(
                          whole * width, features - whole * width))]
        for e, total in enumerate(totals):  # [per, kv heads, 1]
            for p in range(per):
                sums = jnp.where(
                    sum_of == (row + e) * per + p, total[p], sums)
        return sums

    @pl.when(live)
    def _step():
        num_ref[0, 0] = jax.lax.fori_loop(
            0, here, step_group,
            jnp.zeros((kv_heads, rows * per), jnp.float32))

    @pl.when(jnp.logical_not(live))
    def _keep():
        # no lane is live: the one entry the grid walks is handed back
        # as it came (the aliased output is written whatever a step did)
        out_ref[...] = pool_ref[...]


@functools.partial(jax.jit, static_argnames=("group", "interpret"))
def _retention_step_local(owned, count, decay, vv, kq, pool, *, group,
                          interpret):
    """Jitted, as ``ops/attention._paged_latent_attend_local``: a decode
    program traces and lowers the kernel once and calls it from each of
    its retention layers.  ``vv``'s shape says how many value rows a grid
    step takes, ``group`` how many of them the kernel steps together."""
    n_entries, channels, kv_heads, features = pool.shape
    per, rows = kq.shape[1] - 1, vv.shape[-1]

    def entry(i, owned_ref):
        return jnp.minimum(owned_ref[i], n_entries - 1)

    def of_entry(*block):
        zeros = (0,) * (len(block) - 1)
        return pl.BlockSpec(
            block, lambda i, j, owned_ref, _: (entry(i, owned_ref), *zeros))

    def of_rows(*block):
        return pl.BlockSpec(
            block, lambda i, j, owned_ref, _: (entry(i, owned_ref), j, 0, 0))

    return pl.pallas_call(
        functools.partial(
            _retention_step_kernel, group=group, channels=channels),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            # DYNAMIC: the owned entries (one where there is none), by
            # the blocks of value rows
            grid=(jnp.maximum(count[0], 1), vv.shape[1]),
            in_specs=[
                of_entry(1, kv_heads, 1), of_rows(1, 1, kv_heads, rows),
                of_entry(1, 1 + per, kv_heads, channels - 1),
                of_rows(1, rows, kv_heads, features),
            ],
            out_specs=[of_rows(1, rows, kv_heads, features),
                       of_rows(1, 1, kv_heads, rows * per)],
            scratch_shapes=[
                pltpu.VMEM((1 + per, kv_heads, features), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(pool.shape, pool.dtype),
            jax.ShapeDtypeStruct(
                (n_entries, vv.shape[1], kv_heads, rows * per), jnp.float32),
        ],
        # the pool the program donated is the pool it returns: an entry
        # the grid never visits keeps what it holds
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_bytes(
                per * kv_heads, kv_heads, channels - 1) + (8 << 20)),
        interpret=interpret, name="_retention_step_kernel",
    )(owned, count, decay, vv, kq, pool)


def power_retention_step_fused(q, k, v, log_gate, state_pool, owned, count,
                               *, interpret: bool = False):
    """:func:`ops/attention.power_retention_step` over the ENTRIES of a
    pool as a Pallas kernel that passes over each owned entry once — the
    same arguments an entry, the same outputs; plus ``owned`` [E] int32,
    the entries a live lane owns (first, in any order; what lies past
    ``count`` is not read) and ``count`` [] int32.

    Each tile of an owned entry crosses the memory's wires once in each
    direction: fetched, advanced (``e^gate S + [v, 1] phi(k)^T / d``),
    read (``sum_f phi(q) S``) while it is there, written back where it
    came from — the pool is aliased to the output, so an entry no lane
    owns is neither read nor written and keeps what it holds (its output
    row is whatever the buffer held: the decode step reads the rows of
    the entries its lanes own).  The degree-2 features are
    built in the kernel, once an entry, from q and k as float32; the
    division by the running sum of weights and the transposes stay in
    XLA; state, update and read are float32."""
    n_entries, heads, d = q.shape
    kv_heads = k.shape[1]
    per = heads // kv_heads
    f32 = jnp.float32
    if state_pool.dtype != f32 or state_pool.shape != (
            n_entries, d + 1, kv_heads, power_feature_count(d)):
        raise ValueError(
            f"power_retention_step_fused: a {state_pool.dtype} pool of "
            f"{state_pool.shape} for {n_entries} entries of {kv_heads} kv "
            f"heads of {d}")
    if not (interpret or retention_kernel_supported(heads, kv_heads, d)):
        raise NotImplementedError(
            f"power_retention_step_fused does not step a state of {heads} "
            f"heads over {kv_heads} kv heads of {d} "
            "(retention_kernel_supported)")
    group, groups = retention_row_blocks(heads, kv_heads, d)
    rows = group * groups  # value rows a grid step
    blocks = -(-(d + 1) // rows)
    # the value rows a block of the grid at a time, a column each
    vv = jnp.concatenate(
        [v.astype(f32), jnp.ones((n_entries, kv_heads, 1), f32)], axis=-1)
    vv = jnp.pad(vv / d, ((0, 0), (0, 0), (0, blocks * rows - d - 1)))
    vv = jnp.swapaxes(vv.reshape(n_entries, kv_heads, blocks, rows), 1, 2)
    count = jnp.reshape(count, (1,)).astype(jnp.int32)
    state_pool, num = _retention_step_local(
        owned.astype(jnp.int32), count,
        jnp.exp(log_gate.astype(f32))[..., None], vv,
        jnp.concatenate([k[:, None], jnp.swapaxes(
            q.reshape(n_entries, kv_heads, per, d), 1, 2)], 1).astype(f32),
        state_pool, group=group, interpret=interpret)
    # [E, blocks, g, rows * per] -> [E, g, p, d + 1]
    num = jnp.transpose(
        num.reshape(n_entries, blocks, kv_heads, rows, per), (0, 2, 4, 1, 3)
    ).reshape(n_entries, kv_heads, per, blocks * rows)
    out = num[..., :d] / (num[..., d:d + 1] + RETENTION_EPS)
    return out.reshape(n_entries, heads, d).astype(q.dtype), state_pool


def retention_decode_step(q, k, v, log_gate, state_pool, owned, count, *,
                          use_flash: bool = False):
    """The retention mixer's decode step over a pool's entries in the form
    chosen for it: under ``use_flash`` (``models/decoder.kernel_forms``'s
    ``retention``: a TPU, no mesh, a geometry
    :func:`retention_kernel_supported` steps) the kernel
    :func:`power_retention_step_fused`; otherwise (every CPU run, a mesh)
    the XLA form ``ops/attention.power_retention_step``, which passes over
    every entry (an unowned one under a gate of 1 with nothing added)."""
    if use_flash:
        return power_retention_step_fused(
            q, k, v, log_gate, state_pool, owned, count)
    return power_retention_step(q, k, v, log_gate, state_pool)
