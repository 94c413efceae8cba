"""The device scopes: what a device trace calls the work INSIDE a program.

A decode chunk is one ``while`` op and a prefill is hundreds of anonymous
fusions; a trace says which fusion is attention and which the MLP only
if the program said so when it was traced.  ``with scope("attend"):``
opens ``jax.named_scope("dq.attend")`` around the ops built inside it:
every such op's ``op_name`` in the compiled program (and, in a profiler
trace of a TPU, the ``tf_op`` stat of the op's event metadata) then
carries ``…/dq.attend/…``.  ``benchmark/harness/xplane_scopes.py`` sums a
program's device time by these names.

Metadata only: no op, shape, donation or program name changes
(``Lowered.as_text()`` is byte-identical with and without), and nothing
runs when no trace is open.  JAX's persistent compile cache leaves
metadata out of its key, so an executable cached BEFORE a scope was
opened is found again without it: empty that cache to see a new scope.

A scope names a phase; the layer's KIND is the second axis
(:func:`layer_kind`, below), never its index or pass (the trunks are
unrolled in Python: the 32 copies of a phase share its scope, and so do
the passes of a looped trunk).  The vocabulary is closed — a
new trunk or kernel opens one of these, or adds its own to the tuple,
to the table in ``docs/OBSERVABILITY.md`` and to PERF.md §3 in the same
PR:

    embed        token embedding (and its scale)
    proj         the mixer's input and output projections with their norm,
                 RoPE / YaRN, qk-norm, output gate and residual add; the
                 state-space mixer's in / x / dt / out projections, its
                 three inner norms and its gate
    cache_write  what a token leaves in the pools: K / V rows, the latent
                 row, the compressed key, ``state_slot``
    attend       softmax attention over cached or in-flight rows
    select       block scoring and top-k of the sparse mixer
    state        the linear mixer's scan / step, the retention mixer's
                 (its degree-2 features, chunk attention, state read and
                 update: one scope) and the state-space mixer's conv and
                 selective scan / step, the state's (and the conv
                 window's) gather and scatter included
    mlp          the dense SwiGLU with its norm and residual add; shared
                 experts
    route        router scores and the choice of experts
    experts      the held experts' grouped sum: the picks' sort, the
                 rows' gather, the grouped matmuls, the gate, the un-sort
    head         final norm and ``lm_head``
    loop_close   the looped trunk's norm that closes every step (and its
                 exit gate, once a threshold under 1 evaluates it)
    sample       sampling, token and length bookkeeping, the chunk's
                 result array with its MoE / sparse sums

The second axis, the LAYER KIND (PR 53): the stack of several mixer kinds
(``models/hybrid.py``) opens ``jax.named_scope("dk.<kind>")`` around each
half of a layer, OUTSIDE the phase scopes — an op's name stack reads
``…/dk.window/dq.attend/…`` —, so the 24 ``window`` and the 8 global
``attention`` layers of one stack no longer share one ``attend``.
Another prefix than ``dq.`` on purpose: ``xplane_scopes.py`` takes the
innermost ``dq.<name>`` of an op, and an op under a kind and under no
phase (a layout copy) must keep reading ``-`` there.
``benchmark/harness/xplane_kinds.py`` sums a program's device time by
(kind, scope, op).  Closed too: the keys of ``hybrid.MIXERS`` (the one
list of mixer kinds; not repeated here) for a layer's mixer half —
``attn_norm``, the kind's branch with the engine's ``mix``, post-norm and
residual add — and ``dense`` | ``routed`` for its feed-forward half.  The
rope tables, ``embed``, ``head`` and ``sample`` lie outside any kind; the
GQA and the latent trunk have one mixer kind each and open none.
"""

from __future__ import annotations

import jax

PREFIX = "dq."
DEVICE_SCOPES = (
    "embed", "proj", "cache_write", "attend", "select", "state", "mlp",
    "route", "experts", "head", "sample",
)
# A scope of the looped trunk alone (PR 44).  Beside the tuple, not in
# it: ``tests/benchmark/test_benchmark_scopes.py`` holds the benchmark's
# five decode metrics to partition ``DEVICE_SCOPES`` exactly, and only a
# ``benchmark`` PR may give ``loop_close`` a metric to be read by
# (PERF.md section 7); until then the by-scope table shows it and no
# declared metric sums it.  ``scope()`` takes a name of either.
LOOP_SCOPES = ("loop_close",)
KIND_PREFIX = "dk."
FFN_KINDS = ("dense", "routed")


def scope(name: str):
    """``jax.named_scope("dq." + name)``; a name outside
    ``DEVICE_SCOPES`` is refused."""
    if name not in DEVICE_SCOPES + LOOP_SCOPES:
        raise ValueError(
            f"{name!r} is no device scope: one of "
            f"{DEVICE_SCOPES + LOOP_SCOPES} (docqa_tpu/ops/scopes.py)"
        )
    return jax.named_scope(PREFIX + name)


def layer_kind(name: str):
    """``jax.named_scope("dk." + name)``; a name that is neither a key
    of ``hybrid.MIXERS`` nor one of ``FFN_KINDS`` is refused."""
    # at the call (trace time only): that module imports this one
    from docqa_tpu.models.hybrid import MIXERS

    if name not in MIXERS and name not in FFN_KINDS:
        raise ValueError(
            f"{name!r} is no layer kind: one of "
            f"{tuple(MIXERS) + FFN_KINDS} (docqa_tpu/ops/scopes.py)"
        )
    return jax.named_scope(KIND_PREFIX + name)
