"""Structured query profiler: span tree, typed events, machine-readable
QueryProfile artifacts, and a process-level metrics registry.

Four pieces (README "Profiling"):

- ``spans``    per-query :class:`Profiler` — op spans with phase
  sub-timings, cross-thread attribution via capture()/activate(), typed
  events, bounded buffers. Disarmed by default (zero-allocation no-op).
- ``timeline`` where a query is armed (``arm_for_query``), the same spans
  as ``TraceAnnotation``s while a ``jax.profiler`` session is live
  (``device_trace_live``), and the always-on stage / dispatch / wait /
  gather / compile counters of each device attempt.
- ``export``   :class:`QueryProfile` — the stable JSON artifact
  (``df.collect(profile=...)`` / ``daft_tpu.last_profile()``), per-op
  rollups, critical path, schema validation.
- ``metrics``  process-wide counters/gauges/histograms with a
  Prometheus-text dump for the future serving layer.

The chrome-trace output (``daft_tpu.tracing``) is rendered from the same
span tree — one consolidated writer, re-armed per query.
"""

from .export import (SCHEMA_VERSION, QueryProfile, build_profile,
                     validate_profile)
from .metrics import (METRICS, Counter, Gauge, Histogram, MetricsRegistry,
                      record_query_metrics)
from .spans import DISARMED, Profiler, Span
from .timeline import arm_for_query, device_trace_live

__all__ = [
    "SCHEMA_VERSION", "QueryProfile", "build_profile", "validate_profile",
    "METRICS", "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "record_query_metrics", "DISARMED", "Profiler", "Span",
    "arm_for_query", "device_trace_live",
]
