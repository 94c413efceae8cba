"""Shared dispatch discipline for fused programs over donated buffers.

The vector store's ``add()`` donates its device buffers, so any program
that reads them must either dispatch under ``store._lock`` or hold a
consistent snapshot and handle the (rare) donation race.  Dispatching
under the lock is wrong for FIRST calls: XLA tracing+compile of a fused
program (which embeds the encoder forward) takes seconds and would stall
every concurrent index/search (ADVICE r4).  This module holds the
snapshot-outside/retry-under-lock discipline the fused retrievers
(``engines/retrieve.py``) dispatch through.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

from docqa_tpu import obs
from docqa_tpu.engines.spine import spine_run
from docqa_tpu.resilience.deadline import Deadline
from docqa_tpu.runtime.metrics import get_logger

log = get_logger("docqa.dispatch")

# jax's actual use-after-donation phrasings, both layers of the stack:
# jaxlib PjRt raises "Buffer has been deleted or donated", and jax's own
# lifecycle guard raises "Array has been deleted".  Matching bare
# "deleted"/"donated" (the old test) also swallowed unrelated
# RuntimeErrors — e.g. an XLA "resource deleted by peer" transport error
# — and retried them 3x with a fresh multi-second compile each time.
_DELETED_BUFFER_MARKERS = (
    "buffer has been deleted or donated",
    "deleted or donated buffer",
    "array has been deleted",
)


def _is_deleted_buffer_error(e: Exception) -> bool:
    """True only for the use-after-donation failure mode.  Anything else —
    compile failure, device OOM, transport errors — must propagate:
    retrying it under the lock would repeat a multi-second compile while
    holding up every concurrent store caller, the exact stall this module
    exists to avoid."""
    msg = str(e).lower()
    return any(marker in msg for marker in _DELETED_BUFFER_MARKERS)


def dispatch_with_donation_retry(
    lock,
    snapshot_and_build: Callable[[], Tuple[Optional[Callable], Any]],
    deadline: Optional[Deadline] = None,
    stage: str = "retrieve",
    stream: str = "serve",
):
    """Run ``fn(*args)`` from a consistent snapshot, compiling OUTSIDE the
    lock.

    ``snapshot_and_build`` must acquire ``lock`` internally, read a
    consistent view of the store, and return ``(fn, args)`` — or
    ``(None, None)`` when there is nothing to search (caller maps that to
    its empty result).  Dispatches run unlocked: the snapshot's Python
    refs keep the buffers alive, and if an ``add()`` donates them
    mid-compile the dispatch raises immediately (deleted-buffer check)
    and we re-snapshot.  The SECOND attempt is also unlocked — the
    racing add may have changed the program's shape key (count crossing
    ``k``, a capacity double), and a fresh compile must never run under
    the lock.  Only the final attempt dispatches under the lock, which
    excludes adds entirely; reaching it twice through fresh donation
    races is vanishingly rare, and by then every shape in play has a
    warm program.  ``lock`` must be re-entrant (the store's RLock).

    ``deadline`` (resilience/deadline.py) is checked before every
    attempt: a request whose end-to-end budget is gone sheds HERE —
    before a possibly multi-second trace+compile — instead of paying for
    a dispatch whose answer nobody can use.

    ``stage``/``stream`` relabel the spine work item: the retrieval
    observatory's shadow queries run this exact discipline but under the
    ``retrieve_shadow`` stage on the background ``probe`` stream, so
    their cost is attributable and they can never occupy the last
    serving lane."""
    for unlocked_try in range(2):
        if deadline is not None:
            deadline.check("dispatch")
        fn, args = snapshot_and_build()
        if fn is None:
            return None
        try:
            # spine work item, ASYNC like the pre-spine call: the lane
            # covers the hazard window (trace/compile + enqueue) and
            # returns device arrays immediately, so a lane is never
            # held for the program's device time.
            # A donation race surfaces at dispatch (tracing re-reads the
            # donated buffers) exactly as it did pre-spine.
            return spine_run(
                stage, fn, *args, stream=stream, deadline=deadline
            )
        except RuntimeError as e:
            if not _is_deleted_buffer_error(e):
                raise
            # visible, not silent: a donation race per dispatch is
            # expected noise, a STREAK of them is an ingest/serve
            # contention signal an operator should see — and the
            # request's timeline shows the retry it paid for
            obs.event("donation_race", attempt=unlocked_try + 1)
            log.warning(
                "donation race on unlocked dispatch attempt %d/2; "
                "re-snapshotting (%r)", unlocked_try + 1, e,
            )
    with lock:
        if deadline is not None:
            deadline.check("dispatch")
        # reaching the locked fallback is itself diagnostic: two fresh
        # donation races in one request
        obs.event("dispatch_locked_fallback")
        fn, args = snapshot_and_build()
        if fn is None:
            return None
        # still a spine item even under the lock: the submitter holds
        # the store lock while BLOCKED on the ticket; the lane runs the
        # closure without acquiring anything, so no lock-order edge
        return spine_run(stage, fn, *args, stream=stream, deadline=deadline)
