"""The grouped products of a routed layer (``models/routed.py``, the module
both trunks that route import): each pick of a row against the slice of
the stacked weights that belongs to the expert it fell on, and nothing
for a pick on an expert held elsewhere.  Three forms, chosen where every
kernel is (``models/decoder.kernel_forms``'s ``grouped``), handed down as
``use_flash``, and — between the two Pallas forms — by the dispatch's
picks (``models/routed.held_experts_sum``):

* a TPU with no mesh, a dispatch of MANY rows (a prefill's):
  :func:`grouped_matmul` over the rows sorted by the expert they picked,

      out[start_e : start_e + sizes[e]] = lhs[start_e : ...] @ rhs[e]

  and nothing for a row past ``sum(sizes)`` — what lies there in the
  result is UNDEFINED (the kernel never writes it), so a caller masks
  those rows.  ``megablox.gmm``, the Pallas kernel that ships with JAX:
  its grid walks only the (row tile, group) pairs that hold rows, so a
  held expert's weights stream once under the few rows that took it
  (twice where its rows straddle a row tile).  The row tile is 128: at
  256 a tile's matmul takes as long as its expert's bytes and at 512 —
  what ``jax.lax.ragged_dot`` lowers to on a v5e — longer, which made that
  form no faster than a dense pass an expert (PERF.md section 6, PR 45);
* a TPU with no mesh, a dispatch whose picks fit ONE row tile (a decode
  step's few lanes): :func:`grouped_swiglu_step`, the whole held-expert
  sum as one kernel a layer.  ``gmm``'s grid skips the experts no row
  took, but its stacked operand is a blocked one, and where it fits fast
  memory (67 MB of a v5e's 128 MiB) the compiler copies ALL of it there
  ahead of the call: 3.4 GB a step in Trinity for ~2 touched experts of
  16 a layer (PERF.md section 5, PR 53).  The step kernel's three
  stacked operands stay in HBM (``memory_space=pl.ANY``) and it copies
  the tiles of the touched experts alone, so "an expert no row took is
  never read" holds for the program too;
* anywhere else (a CPU, a mesh, the tests' oracle):
  ``jax.lax.ragged_dot`` through :func:`grouped_matmul`, which GSPMD
  partitions like any dot.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

from docqa_tpu.utils import round_up

ROW_TILE = 128
# elements of one weight tile in fast memory: 4 MB of bfloat16, twice
# (the pipeline's two buffers) beside the row, result and accumulator
# tiles inside the 16 MB a kernel may take
_WEIGHT_TILE = 2 * 1024 * 1024
_LANE = 128
# the contraction whole in one tile where it fits: no partial sum then
# waits in the accumulator between two visits of a weight
_CONTRACTION = 8192


def _tile(size: int, most: int) -> int:
    """The largest multiple of a lane that divides ``size`` and is at most
    ``most``; ``size`` itself where it is small or has none."""
    if size <= most:
        return size
    fits = [t for t in range(_LANE, most + 1, _LANE) if size % t == 0]
    return fits[-1] if fits else size


def row_tile(m: int) -> int:
    """The row tile of ``m`` sorted rows: ``ROW_TILE``, or all of a
    handful of rows (a decode step's) as one tile of whole sublanes."""
    return min(ROW_TILE, round_up(m, 16))


def grouped_matmul(lhs, rhs, group_sizes, out_dtype, *,
                   use_flash: bool = False, interpret: bool = False):
    """``lhs`` [m, k] (rows sorted by group; ``m`` a multiple of
    :func:`row_tile`), ``rhs`` [groups, k, n], ``group_sizes`` [groups]
    int32 -> [m, n] in ``out_dtype``, accumulated in float32.  A group
    of size zero is not read; rows past the groups are undefined."""
    if not (use_flash or interpret):
        return jax.lax.ragged_dot(
            lhs, rhs, group_sizes, preferred_element_type=out_dtype
        )
    m, k = lhs.shape
    tk = _tile(k, _CONTRACTION)
    tn = _tile(rhs.shape[2], max(_WEIGHT_TILE // tk, _LANE))
    return gmm(
        lhs, rhs, group_sizes, preferred_element_type=jnp.dtype(out_dtype),
        tiling=(row_tile(m), tk, tn), interpret=interpret,
    )


def step_form(rows: int, picks_a_row: int) -> bool:
    """Whether a dispatch of ``rows`` rows of ``picks_a_row`` picks each is
    a decode STEP to the grouped products: its picks fit one ``ROW_TILE``
    — the constant by which :func:`row_tile` already tells a handful of
    rows from a prefill's.  By picks and not by rows: the step kernel
    passes every row through every touched expert, and the picks bound
    how many experts that can be (Trinity's 4 lanes x 8 and DeepSeek-V2's
    8 x 6 are steps; a prefill tile of 2,048 x 8 or 512 x 6 is not)."""
    return rows * picks_a_row <= ROW_TILE


def _swiglu_step_kernel(
    # scalar prefetch
    picks_ref,  # [rows, k] int32: the held expert a pick fell on (outside
    # 0 .. E - 1: none)
    # blocks
    y_ref,  # [rows, h]: the step's rows (whole sublane tiles of them)
    local_ref,  # [rows, k] int32: the same picks, where vectors read them
    gates_ref,  # [rows, k] f32: the gate of each pick
    wg_hbm,  # [E, h, f] — the layer's stacked experts, left in HBM
    wu_hbm,  # [E, h, f]
    wd_hbm,  # [E, f, h]
    o_ref,  # [rows, h] f32
    # scratch
    ids_ref,  # [E] int32 (SMEM): the held experts a pick fell on, first
    wg_buf,  # [2, h, tf] double-buffered tiles of a touched expert
    wu_buf,  # [2, h, tf]
    wd_buf,  # [2, tf, h]
    sems,  # DMA semaphores [gate | up | down, buffer]
    part_ref,  # [rows, h] f32: one expert's down product, over its tiles
    *,
    picks: int,
):
    """The held-expert sum of a decode step: every row through every
    TOUCHED expert, a tile of the expert's inner width at a time — gate
    and up products (float32 sums, rounded to the rows' type), ``silu(g)
    * u`` (rounded), the down product summed in float32 over the tiles —
    and the expert's product added to the result under the gate each row
    gave it.  While a tile is computed the next one (of this expert or of
    the next touched one) is in flight; ``g``, ``u`` and ``act`` never
    leave fast memory, and an expert no row took costs nothing.

    Which experts were touched is read off the picks HERE, on the scalar
    unit (the first ``picks`` of them, row-major: the rows that fill the
    last sublane tile hold none): a list in the order the experts are
    held, a hundred-odd scalar steps — where the program around the call
    would spend a handful of small fusions a layer on it."""
    held, f, tf = wg_hbm.shape[0], wg_hbm.shape[2], wg_buf.shape[2]
    k = picks_ref.shape[1]
    tiles = f // tf
    dtype = y_ref.dtype

    def unmark(e, carry):
        ids_ref[e] = 0
        return carry

    def mark(p, carry):
        e = picks_ref[p // k, p % k]

        @pl.when((e >= 0) & (e < held))
        def _():
            ids_ref[e] = 1

        return carry

    def gather(e, count):
        took = ids_ref[e]  # read before slot ``count`` <= e is written

        @pl.when(took == 1)
        def _():
            ids_ref[count] = e

        return count + took

    jax.lax.fori_loop(0, held, unmark, 0)
    jax.lax.fori_loop(0, picks, mark, 0)
    steps = jax.lax.fori_loop(0, held, gather, 0) * tiles

    def tile_copies(s, buf):
        e = ids_ref[s // tiles]
        if tiles == 1:
            src = wg_hbm.at[e], wu_hbm.at[e], wd_hbm.at[e]
        else:
            cols = pl.ds(pl.multiple_of((s % tiles) * tf, tf), tf)
            src = (wg_hbm.at[e, :, cols], wu_hbm.at[e, :, cols],
                   wd_hbm.at[e, cols, :])
        return [
            pltpu.make_async_copy(hbm, dst.at[buf], sems.at[i, buf])
            for i, (hbm, dst) in enumerate(
                zip(src, (wg_buf, wu_buf, wd_buf)))]

    @pl.when(steps > 0)
    def _():
        for copy in tile_copies(0, 0):
            copy.start()

    o_ref[...] = jnp.zeros_like(o_ref)

    def one_tile(s, carry):
        buf = s % 2

        @pl.when(s + 1 < steps)
        def _():
            for copy in tile_copies(s + 1, 1 - buf):
                copy.start()

        gate, up, down = tile_copies(s, buf)
        y = y_ref[...]
        gate.wait()
        g = jnp.dot(
            y, wg_buf[buf], preferred_element_type=jnp.float32).astype(dtype)
        up.wait()
        u = jnp.dot(
            y, wu_buf[buf], preferred_element_type=jnp.float32).astype(dtype)
        act = jax.nn.silu(g.astype(jnp.float32)).astype(dtype) * u
        down.wait()
        out = jnp.dot(act, wd_buf[buf], preferred_element_type=jnp.float32)

        def add_expert(product):
            e = ids_ref[s // tiles]
            gates = jnp.sum(
                jnp.where(local_ref[...] == e, gates_ref[...], 0.0),
                axis=1, keepdims=True)  # [rows, 1]: 0 where not picked
            o_ref[...] += gates * product

        if tiles == 1:
            add_expert(out)
        else:
            t = s % tiles

            @pl.when(t == 0)
            def _():
                part_ref[...] = out

            @pl.when((t > 0) & (t < tiles - 1))
            def _():
                part_ref[...] += out

            @pl.when(t == tiles - 1)
            def _():
                add_expert(part_ref[...] + out)

        return carry

    jax.lax.fori_loop(0, steps, one_tile, 0)


@functools.partial(jax.jit, static_argnames=("picks", "tf", "interpret"))
def _swiglu_step_local(local, y, gates, w_gate, w_up, w_down, *, picks, tf,
                       interpret):
    """Jitted, as ``ops/attention._paged_attend_local``: a decode program
    traces and lowers the kernel once and calls it from each of its
    routed layers.  ``picks``: how many of ``local``'s entries, row-major,
    are a row's (the rest pads the rows to whole tiles); ``tf``: the
    columns of an expert's inner width a tile holds."""
    rows, h = y.shape
    held = w_gate.shape[0]
    whole = lambda *shape: pl.BlockSpec(  # noqa: E731
        shape, lambda i, *_: (0,) * len(shape))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    tile_bytes = h * tf * w_gate.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_swiglu_step_kernel, picks=picks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[
                whole(rows, h), whole(*local.shape), whole(*gates.shape),
                in_hbm, in_hbm, in_hbm,
            ],
            out_specs=whole(rows, h),
            scratch_shapes=[
                pltpu.SMEM((held,), jnp.int32),
                pltpu.VMEM((2, h, tf), w_gate.dtype),
                pltpu.VMEM((2, h, tf), w_up.dtype),
                pltpu.VMEM((2, tf, h), w_down.dtype),
                pltpu.SemaphoreType.DMA((3, 2)),
                pltpu.VMEM((rows, h), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((rows, h), jnp.float32),
        # three operands' tiles, twice buffered, beside the rows, the
        # result and a tile's products: asked for by name where it is
        # over the 16 MiB scoped default, of the chip's 128 MiB
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=6 * tile_bytes + (8 << 20)),
        interpret=interpret, name="_swiglu_step_kernel",
    )(local, y, local, gates, w_gate, w_up, w_down)


def grouped_swiglu_step(y, local, gates, w_gate, w_up, w_down, *,
                        interpret: bool = False):
    """``sum_j gates[:, j] . swiglu_{local[:, j]}(y)`` of a decode step's
    few rows as ONE Pallas kernel: ``y`` [n, h], ``local`` [n, k] int32
    (the held expert a pick fell on; anything outside ``0 .. E - 1`` — an
    expert held elsewhere, a retired lane's ``-1`` — adds nothing),
    ``gates`` [n, k] float32, ``w_gate`` / ``w_up`` [E, h, f], ``w_down``
    [E, f, h] -> float32 [n, h].

    The stacked weights stay in HBM; the kernel copies, twice buffered,
    the tiles of the experts a pick fell on (``_WEIGHT_TILE`` elements of
    an operand: Trinity's 2048 x 1024 expert whole, DeepSeek-V2's 5120 x
    1536 in four) and multiplies ALL ``n`` rows by each, weighing a row by
    the gate it gave that expert — zero where it did not pick it.  So
    there is no sort, gather, pad or un-sort of the picks, and the
    arithmetic of a pick is :func:`grouped_matmul`'s caller's: weights
    and rows in ``y``'s type, every product summed in float32, ``g``,
    ``u`` and ``act`` rounded to ``y``'s type, the gated sum float32 —
    summed over a row's experts in the order the experts are held, not
    the order the row picked them."""
    n, h = y.shape
    rows = round_up(n, 16)  # whole sublane tiles of a 16-bit type
    fill = ((0, rows - n), (0, 0))
    # a pick outside the held experts needs no mask: the kernel lists the
    # experts it finds in range and weighs a row by equality with those
    out = _swiglu_step_local(
        jnp.pad(local.astype(jnp.int32), fill, constant_values=-1),
        jnp.pad(y, fill), jnp.pad(gates.astype(jnp.float32), fill),
        w_gate, w_up, w_down, picks=n * local.shape[1],
        tf=_tile(w_gate.shape[2], max(_WEIGHT_TILE // h, _LANE)),
        interpret=interpret)
    return out[:n]
