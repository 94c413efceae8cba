"""Failure-path engineering for the serving stack.

The reference system has no fault handling at all — services die on a
missed HTTP call and poison messages are silently dropped (PAPER.md
"What the reference is NOT").  The happy path alone has a cost too: an
open-loop run above capacity collapses when requests queue with no
deadline, no shedding, and no fallback.  This package supplies the four primitives every stage of the
pipeline leans on:

* :mod:`deadline` — an end-to-end request budget created at admission
  and threaded through retrieval, dispatch, and the continuous batcher;
  every stage *sheds* instead of queueing past its deadline.
* :mod:`policy` — jittered exponential-backoff retries with a
  deterministic (seeded) jitter so failure tests replay exactly.
* :mod:`breaker` — per-dependency circuit breakers (broker, deid,
  index, decoder, checkpoint loads) that stop hammering a failing
  dependency and give it a recovery window.
* :mod:`faults` — a deterministic seeded fault-injection plan; every
  resilience behavior above is exercised by injecting broker drops,
  slow stages, handler exceptions, and decoder failures at chosen steps
  (``pytest -m faults``, ``scripts/chaos_smoke.py``).

See ``docs/RESILIENCE.md`` for the operator-facing story.
"""

from docqa_tpu.resilience.breaker import (  # noqa: F401
    BreakerBoard,
    BreakerOpen,
    CircuitBreaker,
)
from docqa_tpu.resilience.deadline import (  # noqa: F401
    Deadline,
    DeadlineExceeded,
)
from docqa_tpu.resilience.faults import (  # noqa: F401
    FaultPlan,
    FaultRule,
    InjectedFault,
    active_plan,
    perturb,
)
from docqa_tpu.resilience.policy import RetryPolicy  # noqa: F401
