"""``repro.obs`` — unified tracing, metrics, and profiling.

One observability substrate for every layer: the planner, the
event-driven runtime, the compiled exec path and the serving tier all
emit the same span vocabulary (:data:`~repro.obs.trace.SPAN_NAMES`)
and publish into the same metrics registry, so a simulated run and a
real run can be diffed signal-for-signal.

* :mod:`~repro.obs.trace` — :class:`Tracer` (explicit spans, Chrome
  trace / Perfetto JSON export with one process-row per device actor)
  and the zero-alloc :data:`NULL_TRACER` default;
* :mod:`~repro.obs.metrics` — :class:`MetricsRegistry` (counters,
  gauges, windowed histograms with nearest-rank p50/p95/p99) and the
  versioned JSON snapshot codec shared with the bench gate;
* :mod:`~repro.obs.compiles` — XLA compiles and compile-cache loads
  counted from JAX's monitoring events (``xla.compiles``,
  ``xla.compile_s``, ``compile`` spans).

Summarize/validate traces from the shell with
``python -m repro.tools.trace``.
"""

from .trace import (HOST_TRACK, NULL_TRACER, NullTracer, REQUEST_SPANS,
                    RING_SPANS, SPAN_NAMES, Span, Tracer, activate, current,
                    from_chrome_trace, scoped, span_tree,
                    validate_chrome_trace)
from .metrics import (Counter, DEFAULT_WINDOW, Gauge, Histogram,
                      METRICS_SCHEMA_VERSION, MetricsRegistry, NULL_REGISTRY,
                      NullRegistry, default_registry, flatten, open_snapshot,
                      percentiles, quantile, registry_from_values)

__all__ = [
    "HOST_TRACK", "NULL_TRACER", "NullTracer", "REQUEST_SPANS",
    "RING_SPANS", "SPAN_NAMES", "Span",
    "Tracer", "activate", "current", "from_chrome_trace", "scoped",
    "span_tree", "validate_chrome_trace",
    "Counter", "DEFAULT_WINDOW", "Gauge", "Histogram",
    "METRICS_SCHEMA_VERSION", "MetricsRegistry", "NULL_REGISTRY",
    "NullRegistry", "default_registry", "flatten", "open_snapshot",
    "percentiles", "quantile", "registry_from_values",
]
