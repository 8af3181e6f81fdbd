"""Observability: span tracing, the unified metrics registry, and the
helpers behind EXPLAIN ANALYZE.

The standard library plus `jax.profiler` (the phase annotations) — the
engine and the serving tier import this unconditionally, so it must cost
nothing when tracing is off (every hook is guarded by `trace is not
None`, and `trace.phase` without a tracer is a shared no-op).
"""
from repro.obs.metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    log_buckets,
    parse_prometheus,
    quantile_from_samples,
)
from repro.obs.trace import (  # noqa: F401
    Span,
    Trace,
    Tracer,
    phase_totals,
    validate_chrome_events,
)
