"""Observability layer: request tracing, model-drift profiling, metrics.

Three pillars, deliberately decoupled from the serving hot path:

  * ``obs.trace``   — ``Tracer``/``Span``: host-clock spans at the
    scheduler's boundaries (admit, request, queue, form, dispatch with
    its h2d copy and launch, device with its readback, finalize; listed
    in ``obs.trace``) with Chrome/Perfetto JSON export.  No jax import,
    no device sync.  Device time comes from the profiler trace that the
    benchmark reduces (``bench/benchlib/xtrace.py``), not from here.
  * ``obs.profile`` — opt-in per-site profiled execution reconciling
    host wall clock against the FPGA cycle model (``DriftReport``).
    Synchronizes per site; never on by default, and not a device time.
  * ``obs.metrics`` — ``MetricsRegistry``: Prometheus-text / JSON export
    facade over ``serving.telemetry`` plus standalone instruments.

``obs.ledger`` standardizes benchmark output (``BENCH_*.json``).
"""
from repro.obs.trace import (TRACE_SCHEMA, Span, Tracer,
                             validate_chrome_trace, request_chains)
from repro.obs.ledger import (BENCH_SCHEMA, bench_result, validate_result,
                              write_result, load_result, flag_value)

# obs.metrics renders serving telemetry, and importing repro.serving
# pulls the jax-backed executor stack — lazy-load those names (PEP 562)
# so `import repro.obs` keeps the tracer's no-jax guarantee.
_METRICS_NAMES = ("MetricsRegistry", "MetricFamily", "Counter", "Gauge",
                  "Histogram", "escape_label")


def __getattr__(name):
    if name in _METRICS_NAMES:
        from repro.obs import metrics
        return getattr(metrics, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

__all__ = [
    "TRACE_SCHEMA", "Span", "Tracer", "validate_chrome_trace",
    "request_chains",
    "MetricsRegistry", "MetricFamily", "Counter", "Gauge", "Histogram",
    "escape_label",
    "BENCH_SCHEMA", "bench_result", "validate_result", "write_result",
    "load_result", "flag_value",
]
