"""What a decoder block's SURROUNDINGS ask of its kind: one record a kind
(:class:`BlockServing`), kept by the model module that owns the kind
(``models/decoder.block_serving`` picks it) and READ by the batcher, the
solo engine, the sharding rules and the two audits — none of which
branches on a kind.  This module imports nothing of the package.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Mapping, NamedTuple, Optional, Tuple


class KernelForms(NamedTuple):
    """Which ops of the paged forwards run their Pallas form — decided
    once (``models/decoder.kernel_forms``), handed to the ops as plain
    static booleans, counted by the batcher as they stand."""

    paged: bool  # the decode attention reads a lane's live pages in place
    sparse_paged: bool  # a sparse layer's decode reads its blocks as pages
    scan: bool  # a state-space layer's prefill scan keeps ``h`` on the chip
    # a routed layer's products are Pallas: ``megablox.gmm`` over a
    # prefill's rows, one step kernel a layer over a decode step's lanes
    grouped: bool
    ragged: bool  # the cold packed prefill attends in the flash kernel
    # a retention layer's decode step is one pass over the owned entries,
    # in place
    retention: bool


def _no_counts(**_) -> Tuple[Dict[str, int], Dict[str, float]]:
    return {}, {}


def _nothing(*_, **__) -> dict:
    return {}


@dataclasses.dataclass(frozen=True)
class BlockServing:
    """One block kind's answers, in the order a new kind fills them in.
    The defaults are the plain GQA block's: nothing refused, no sums, no
    counters, the GQA sharding rules.

    What it is NOT served with: ``label`` names the kind in a refusal,
    ``unserved`` the settings the batcher refuses it with, by dotted name
    (``generate.prefix_cache``, ``generate.speculative_k``,
    ``qos.preemption``), ``advice`` what to set instead; ``solo`` why the
    dense-cache engine and forward do not run it (``None``: they do);
    ``uses_flash`` whether the engine KEEPS ``use_flash`` for the kind —
    its dense-cache forwards and the warm-up's ``kernel_selfcheck`` go by
    the flag; the forms of the paged forwards are ``kernel_forms``'s,
    asked with what the engine observed either way.  (A configuration the
    kind cannot run is refused by field where the record is built.)

    The sums a program carries to the host in the fetch the worker makes
    anyway, summed inside the jitted programs under the names of the
    counters they feed: ``step_sums(record, lengths, active)`` of one
    decode step (the record its forward hands back, the lanes' lengths
    BEFORE the step, the lanes live in it), ``prefill_sums(record, seg)``
    of one dispatch (``seg`` < 0: padding).

    What the host counts, pure arithmetic (the batcher increments):
    ``chunk_counts(lane_steps=, row=, kernels=, n_lanes=)`` of a fetched
    chunk (``n_lanes``: the rows a decode step holds, the slots) ->
    ({counter: amount}, {histogram: sample}); ``prefill_counts(lanes=,
    tokens=, dispatches=, kernels=)`` of an admission round; ``kv_rows_read
    (lens, kernels=, block_size=, table_rows=) -> (rows read per cache
    entry, {counter: amount})`` where the kind reads KV by a rule of its
    own (a layer that selects; layer kinds that read differently, which
    count per kind); ``paged_reads``: ((layers, sliding window or None),
    ...) of the layer kinds whose decode step reads a lane's OWN table
    through ``ops/attention.paged_flash_decode`` — a windowed kind its
    ring — which is where the host can say how many copies the kernel
    issued (``serve_decode_kv_copies``); none for a kind whose tables the
    program builds (a layer that selects) or that reads through a kernel
    of its own (the latent block); ``span_attrs`` on a request's ``serve_prefill``
    and ``serve_decode_chunk`` spans, ``prefill_attrs(n_ids, n_lanes)`` on
    the first; ``occupancy`` beside the block-pool gauges; ``lane_state``:
    a lane keeps state beside its rows, found through the slot map in the
    pools (``engines/paged.STATE_SLOT``) whose host copy the batcher writes;
    ``ring_pages(block_size)``: the pages of the RING a lane holds in the
    pools of its window layers, whatever its length — a second extent with
    an allocator and a table a lane of its own, taken and released with
    the lane's block table (``engines/paged.WINDOW_PAGES``: the host copy
    of the tables travels in the pools like the slot map).

    The sharding beyond the GQA rules, ``PartitionSpec``s by name:
    ``param_pspecs(model_axis)`` of every per-layer parameter of a kind
    with layers of its own, ``pool_pspecs()`` of every pool.
    """

    label: str
    unserved: Tuple[str, ...] = ()
    advice: str = ""
    solo: Optional[str] = None
    uses_flash: bool = True
    step_sum_names: Tuple[str, ...] = ()
    step_sums: Optional[Callable] = None
    prefill_sum_names: Tuple[str, ...] = ()
    prefill_sums: Optional[Callable] = None
    chunk_counts: Callable = _no_counts
    prefill_counts: Callable = _nothing
    kv_rows_read: Optional[Callable] = None
    paged_reads: Tuple[Tuple[int, Optional[int]], ...] = ((1, None),)
    span_attrs: Mapping = dataclasses.field(default_factory=dict)
    prefill_attrs: Callable = _nothing
    occupancy: Mapping = dataclasses.field(default_factory=dict)
    lane_state: bool = False
    ring_pages: Optional[Callable] = None
    param_pspecs: Optional[Callable] = None
    pool_pspecs: Optional[Callable] = None
