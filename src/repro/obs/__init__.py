"""Unified observability: metrics registry, trace spans, logs, profiling.

One subsystem, three pillars, shared by core / serving / streaming /
distributed (and the benchmark drivers):

* **metrics** (:mod:`repro.obs.metrics`) — process-local
  :class:`MetricsRegistry` of labeled :class:`Counter` / :class:`Gauge` /
  :class:`Histogram` families with a typed, round-trippable ``snapshot()``
  schema and Prometheus text exposition
  (:func:`start_metrics_server`, ``repro.launch.serve --metrics-port``).
  :class:`StreamingHistogram` (formerly ``repro.serving.scheduler``) is the
  shared percentile structure.
* **traces** (:mod:`repro.obs.trace`) — per-request span trees. Library
  code calls :func:`span` unconditionally; with no tracer installed and no
  ``jax.profiler`` session recording it returns a no-op singleton (one
  thread-local read and one ``is_enabled()`` call, zero allocation), so
  instrumentation-off is the fast path. While a profiler session records,
  every span also writes a ``repro.<name>`` ``TraceAnnotation`` carrying
  its ``set`` values, on the same clock as the device trace. ``SearchRequest(trace=True)``
  (or ``EngineConfig(trace_sample=...)``) rides a finished :class:`Trace`
  back on ``SearchResult.trace`` — export Chrome-trace JSON with
  ``.save()`` or print ``result.explain()``; ``with obs.capture() as tr:``
  scopes a trace around arbitrary code (serving steps, flush/compact).
* **logs + profiling** (:mod:`repro.obs.log`, :mod:`repro.obs.profile`) —
  rate-limited structured progress logging (:func:`get_logger`), an opt-in
  ``jax.profiler`` capture wrapper (:func:`profiler_capture`), and the
  per-``device_kind`` peak table (:data:`PEAKS`).
"""
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry, REGISTRY,
                      StreamingHistogram, get_registry, start_metrics_server)
from .trace import (NULL_SPAN, PROFILE_PREFIX, Span, Trace, Tracer,
                    active_tracer, begin_request_trace, capture,
                    end_request_trace, span, tracing)
from .log import StructuredLogger, get_logger
from .profile import PEAKS, V5E, peaks, profiler_capture

__all__ = [
    # metrics
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "REGISTRY",
    "StreamingHistogram", "get_registry", "start_metrics_server",
    # traces
    "NULL_SPAN", "PROFILE_PREFIX", "Span", "Trace", "Tracer", "active_tracer",
    "begin_request_trace", "capture", "end_request_trace", "span", "tracing",
    # logs
    "StructuredLogger", "get_logger",
    # profiling
    "PEAKS", "V5E", "peaks", "profiler_capture",
]
