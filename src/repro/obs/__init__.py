"""Observability subsystem: request tracing, metrics exposition, search
profiling.

Three layers, each usable on its own:

  * :mod:`repro.obs.trace` — the serving path's spans. The same-thread
    phases (``http.decode``, ``engine.assemble``, ``engine.dispatch``,
    ``engine.demux``, ``http.encode``, and ``fetch.page_fetch`` on the
    host callback's thread) are ``jax.profiler.TraceAnnotation`` spans, so
    they land in any ``jax.profiler`` trace beside the device's
    operations, with nothing to switch on. An injected
    :class:`~repro.obs.trace.Tracer` (bounded ring buffer, injected
    monotonic clock, ~zero cost when disabled) records them as well,
    plus the cross-thread ``submit``, ``queue_wait`` and ``request``
    spans and child spans for semantic-cache lookups and mutable-index
    writes, and exports Chrome ``trace_event`` JSON viewable in Perfetto
    (https://ui.perfetto.dev).
  * :mod:`repro.obs.metrics` — a registry of named counters / gauges /
    histograms wrapping the existing ``EngineMetrics`` / ``CacheStats`` /
    compile-cache / fetch counters as sources, rendered as Prometheus
    text exposition; :mod:`repro.obs.server` serves it over a tiny stdlib
    ``http.server`` sidecar (``/metrics``, ``/healthz``, ``/stats``).
  * per-hop search profiling — ``PageANNIndex.profile(queries)``
    (``core.search.profile_search``) captures the beam's per-hop trail
    without touching the compiled fast path; ``python -m
    repro.obs.report`` renders a trace or profile into a human-readable
    phase breakdown.

The serving layer imports :func:`~repro.obs.trace.phase` on its hot path;
ring-buffer tracers and metric registries stay injected (duck-typed).
"""
from repro.obs.metrics import (
    MetricsRegistry,
    parse_prometheus_text,
    sample_value,
    serve_registry,
)
from repro.obs.server import MetricsServer
from repro.obs.trace import NULL_TRACER, Span, Tracer, phase

__all__ = [
    "MetricsRegistry",
    "MetricsServer",
    "NULL_TRACER",
    "Span",
    "Tracer",
    "phase",
    "parse_prometheus_text",
    "sample_value",
    "serve_registry",
]
