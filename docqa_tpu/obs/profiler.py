"""On-demand ``jax.profiler`` window.

:class:`ProfilerWindow` starts and stops a ``jax.profiler`` trace from the
HTTP surface (``POST /api/profiler/start|stop``) for the deep-dive that
needs XLA-level detail.  Strictly **jit-exterior**: it is only ever
invoked from the HTTP layer / scripts, never from traced code (the
jit-purity rule flags any profiler/span call that leaks into a jit
root), and one window at a time (starting twice is an error, not a
nested trace).

While the window is open every ``runtime.metrics.span`` site also opens a
``jax.profiler.TraceAnnotation``, so the host stages (the batcher's
``serve_admit_drain`` / ``serve_admit_round`` / ``serve_first_token_fetch``
/ ``serve_idle_wait``, the spine's dispatch spans, ``qa_retrieve`` …) sit
on the same clock as the device's programs: a gap in the device timeline
reads as what the host was doing in it.  What ran on the device comes from
the trace itself, never from a host clock around a blocking fetch — and
what ran INSIDE a program from the device scopes of ``ops/scopes.py``:
``python3 benchmark/harness/xplane_scopes.py <logdir>`` reduces a window.
"""

from __future__ import annotations

import threading
from typing import Optional


class ProfilerWindow:
    """One guarded ``jax.profiler`` start/stop window (HTTP-surfaced).

    jax is imported inside the methods so the obs package stays
    importable on hosts without an accelerator stack, and so importing
    obs never pays a jax import."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._logdir: Optional[str] = None
        # read, unlocked, by every ``metrics.span`` call: True exactly
        # while a trace is being written (a stale read costs one
        # annotation more or less at the window's edge)
        self.annotate = False

    @property
    def active(self) -> bool:
        with self._lock:
            return self._logdir is not None

    @property
    def logdir(self) -> Optional[str]:
        with self._lock:
            return self._logdir

    def start(self, logdir: Optional[str] = None) -> str:
        import tempfile

        import jax.profiler

        with self._lock:
            if self._logdir is not None:
                raise RuntimeError(
                    f"profiler window already active ({self._logdir})"
                )
            if logdir is None:
                logdir = tempfile.mkdtemp(prefix="docqa_profile_")
            jax.profiler.start_trace(logdir)
            self._logdir = logdir
            self.annotate = True
            return logdir

    def stop(self) -> str:
        import jax.profiler

        with self._lock:
            if self._logdir is None:
                raise RuntimeError("no profiler window active")
            self.annotate = False
            jax.profiler.stop_trace()
            logdir, self._logdir = self._logdir, None
            return logdir


DEFAULT_PROFILER = ProfilerWindow()
