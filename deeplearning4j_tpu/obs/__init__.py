"""Unified observability plane (ISSUE-8).

One measurement substrate for the whole system — "what is this system
doing right now, and where did this one slow request spend its time":

- `registry` — thread-safe Counter/Gauge/Histogram + `MetricsRegistry`
  with Prometheus text exposition (served at ``GET /metrics`` by the
  UI server, the fleet front, and `MetricsServer`);
- `trace` — request ids, the bounded `TraceRecorder` ring behind
  ``GET /trace/recent``, and Chrome trace-event (Perfetto-loadable)
  export; request ids propagate across the fleet via ``X-Request-Id``;
  `PhaseClock` / `annotate` put a loop's phases on the profiler's host
  plane and in counters at once;
- `compilewatch` — the build account, fed by ``jax.monitoring``: every
  program the process builds by key (the active `compile_scope`, else
  ``fn:<function>``) and by stage (Python tracing, lowering, compile or
  cache load, with the persistent cache's hits and misses):
  ``compiles_total{program_key}``, ``compile_seconds_total{program_key,
  stage}``, ``compile_cache_total{program_key, result}``, plus the
  recent-event ring the tracer uses to attach ``xla_compile`` spans to
  the request that paid for an off-ladder recompile;
- `telemetry` — `TrainingTelemetry`, the listener-slot feed for step
  time, examples/sec, grad norm, loss-scale grow/backoff events and
  supervisor interventions (``dl4j train -metrics-port``);
- `http` — `MetricsServer`, the standalone training-plane endpoint.

See docs/observability.md for the metric catalog, the trace span
taxonomy and a scrape quickstart.
"""

from deeplearning4j_tpu.obs.compilewatch import (
    COMPILE_EVENT,
    CompileWatcher,
    compile_scope,
    compile_watcher,
)
from deeplearning4j_tpu.obs.http import MetricsServer
from deeplearning4j_tpu.obs.registry import (
    EXPOSITION_CONTENT_TYPE,
    Counter,
    Gauge,
    Histogram,
    LATENCY_BUCKETS,
    MetricsRegistry,
    STEP_TIME_BUCKETS,
)
from deeplearning4j_tpu.obs.telemetry import TrainingTelemetry
from deeplearning4j_tpu.obs.trace import (
    PhaseClock,
    TraceRecorder,
    annotate,
    chrome_trace,
    new_request_id,
    span,
    trace,
)

__all__ = [
    "COMPILE_EVENT",
    "CompileWatcher",
    "Counter",
    "EXPOSITION_CONTENT_TYPE",
    "Gauge",
    "Histogram",
    "LATENCY_BUCKETS",
    "MetricsRegistry",
    "MetricsServer",
    "PhaseClock",
    "STEP_TIME_BUCKETS",
    "TraceRecorder",
    "TrainingTelemetry",
    "annotate",
    "chrome_trace",
    "compile_scope",
    "compile_watcher",
    "new_request_id",
    "span",
    "trace",
]
