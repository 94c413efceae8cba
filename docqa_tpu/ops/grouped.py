"""The grouped matmul of a routed layer (models/latent.py): rows sorted by
the expert they picked, each run of rows against ITS expert's slice of
the stacked weights,

    out[start_e : start_e + sizes[e]] = lhs[start_e : ...] @ rhs[e]

and nothing for a row past ``sum(sizes)`` — what lies there in the result
is UNDEFINED (the kernel never writes it), so a caller masks those rows.
Two forms, chosen where every kernel is (``models/decoder.kernel_forms``'s
``grouped``) and handed down as ``use_flash``:

* a TPU with no mesh: ``megablox.gmm``, the Pallas kernel that ships
  with JAX.  Its grid walks only the (row tile, group) pairs that hold
  rows, so an expert no row took is never read and a held expert's
  weights stream once under the few rows that took it (twice where its
  rows straddle a row tile).  The row tile is 128: at 256 a tile's
  matmul takes as long as its expert's bytes and at 512 — what
  ``jax.lax.ragged_dot`` lowers to on a v5e — longer, which made that
  form no faster than a dense pass an expert (PERF.md section 6, PR 45);
* anywhere else (a CPU, a mesh, the tests' oracle):
  ``jax.lax.ragged_dot``, which GSPMD partitions like any dot.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
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
