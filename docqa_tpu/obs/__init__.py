"""docqa-trace: request-scoped tracing, flight recorder, and profiling.

The observability subsystem (docs/OBSERVABILITY.md).  One import site
for the rest of the framework:

* identity + propagation: :func:`new_trace`, :func:`current`,
  :func:`call_in`, :func:`headers_of`, :func:`from_headers`;
* recording: :func:`start_span` (context-var style), explicit
  ``Trace.record_span`` (worker threads), :func:`event`, :func:`flag`;
* retention: :data:`DEFAULT_RECORDER` (ring + always-keep anomalous),
  :func:`finish` / :func:`finish_id`;
* export: :func:`timeline_dict`, :func:`to_chrome_trace`,
  :func:`coverage`;
* profiling: :data:`DEFAULT_PROFILER` (on-demand ``jax.profiler``
  window; while open, every ``metrics.span`` site annotates the trace);
* telemetry (ISSUE 7): :class:`TelemetryStore` / :class:`WindowedDigest`
  / :class:`TelemetrySampler` (time-series rollups of the serving
  plane), :class:`BurnRateEvaluator` + :func:`default_ask_slos` (SLO
  burn-rate alerting), :func:`prometheus_text` / :func:`telemetry_json`
  / :func:`lint_prometheus_text` (exposition);
* cost attribution (docqa-costscope): :class:`RequestCostLedger` /
  :class:`CostRecord` / :data:`DEFAULT_COST_LEDGER` / :func:`cost_open`
  (per-class request cost vectors, KV block-second accounting, shed
  forensics — ``GET /api/costs``);
* retrieval quality (ISSUE 13): :class:`RetrievalObservatory` +
  :func:`get_retrieval_observatory` / :func:`set_retrieval_observatory`
  (shadow-sampling online recall estimation, the measured nprobe
  frontier), :func:`wilson_interval` / :func:`compare_topk` (estimator
  math), :func:`default_retrieval_slos` (the recall burn objective).

Depends only on the stdlib (jax is imported lazily inside the profiler
window), so ``runtime/metrics.py`` can import it without cycles.
"""

from docqa_tpu.obs.context import (  # noqa: F401
    SPAN_HEADER,
    TRACE_HEADER,
    TraceContext,
    call_in,
    current,
    current_trace_id,
    event,
    flag,
    headers_of,
    next_trace_id,
    reset_ids,
)
from docqa_tpu.obs.costs import (  # noqa: F401
    DEFAULT_COST_LEDGER,
    REQUEST_CLASSES,
    CostRecord,
    RequestCostLedger,
    cost_open,
    cost_record_of,
)
from docqa_tpu.obs.export import (  # noqa: F401
    coverage,
    timeline_dict,
    to_chrome_trace,
)
from docqa_tpu.obs.profiler import (  # noqa: F401
    DEFAULT_PROFILER,
    ProfilerWindow,
)
from docqa_tpu.obs.expo import (  # noqa: F401
    lint_prometheus_text,
    prometheus_text,
    telemetry_json,
)
from docqa_tpu.obs.observatory import (  # noqa: F401
    DEFAULT_OBSERVATORY,
    Observatory,
    detect_peak_flops,
)
from docqa_tpu.obs.recorder import (  # noqa: F401
    DEFAULT_RECORDER,
    FlightRecorder,
    enabled,
    ensure,
    finish,
    finish_id,
    from_headers,
    new_trace,
    set_enabled,
)
from docqa_tpu.obs.retrieval_observatory import (  # noqa: F401
    RetrievalObservatory,
    ShadowJob,
    compare_topk,
    get_retrieval_observatory,
    set_retrieval_observatory,
    wilson_interval,
)
from docqa_tpu.obs.slo import (  # noqa: F401
    BurnRateEvaluator,
    SLODef,
    default_ask_slos,
    default_retrieval_slos,
)
from docqa_tpu.obs.spans import Span, Trace, start_span  # noqa: F401
from docqa_tpu.obs.telemetry import (  # noqa: F401
    TelemetrySampler,
    TelemetryStore,
    WindowedDigest,
)
