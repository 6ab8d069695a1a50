"""Dependency-free observability: metrics registry, span tracing, export.

The subsystem the reference farm never had (SURVEY §5.1: "no timing
histograms, no flamegraphs") — fine-grained timing of compute vs.
communication is what lets a distributed stack find overlap opportunities
and diagnose concurrency ceilings (PAPERS.md: T3, arxiv 2401.16677;
TPU-concurrency limits, arxiv 2011.03641).

Five modules, stdlib-only by contract (``device_scopes`` imports JAX
inside its one function, when a program is traced):

- ``registry``  — process-global, thread/async-safe Counter / Gauge /
  Histogram with frozen label tuples and a per-metric cardinality cap;
- ``spans``     — nesting span context managers over a ``contextvars``
  context, stitched across HTTP by the ``X-CDT-Trace`` header;
- ``export``    — Prometheus text exposition + structured JSON, both
  rendered from one ``snapshot()``;
- ``device_scopes`` — the registry of device layers and
  ``device_scope(layer)``, the ``cdt.<layer>`` name a traced operation
  carries into the profiler's trace (the device-side twin of ``span``);
- ``build``     — the set-up ledger: boot phases, a model's weights, and
  each program's trace / lower / cache read / compile / first run — its
  seconds, who asked for it and when — fed by the three ``jax.monitoring``
  listeners it holds (imported where used).

``metrics`` declares the framework's standard families; instrumentation
sites import those objects and guard every record with ``enabled()`` —
``CDT_TELEMETRY=0`` turns the whole subsystem into one boolean read per
site. Served by ``GET /distributed/metrics`` (Prometheus),
``GET /distributed/metrics.json``, and ``GET /distributed/trace/{job_id}``
(assembled span tree). See ``docs/telemetry.md``.
"""

from .registry import (BYTES_BUCKETS, COMPILE_BUCKETS, DURATION_BUCKETS,
                       Counter, Gauge, Histogram, MetricRegistry, REGISTRY,
                       enabled, set_enabled)
from .device_scopes import DEVICE_LAYERS, device_scope
from .spans import (STORE as SPAN_STORE, TRACE_HEADER, current_span_id,
                    current_trace_id, new_trace_id, parse_trace_header,
                    record_span, set_annotator, span, timed_span,
                    trace_headers, use_trace)
from . import metrics  # noqa: F401  — declares the standard families

counter = REGISTRY.counter
gauge = REGISTRY.gauge
histogram = REGISTRY.histogram

__all__ = [
    "BYTES_BUCKETS", "COMPILE_BUCKETS", "DEVICE_LAYERS", "DURATION_BUCKETS",
    "Counter", "Gauge", "Histogram", "MetricRegistry", "REGISTRY",
    "SPAN_STORE", "TRACE_HEADER", "counter", "current_span_id",
    "current_trace_id", "device_scope", "enabled", "gauge", "histogram", "metrics",
    "new_trace_id", "parse_trace_header", "record_span", "set_annotator",
    "set_enabled", "span", "timed_span", "trace_headers", "use_trace",
]
