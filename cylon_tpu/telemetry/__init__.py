"""Structured tracing + metrics for the TPU dataframe engine.

The reference's observability is per-phase wall-clock logging at every
operator (cpp/src/cylon/table.cpp:320-335 shuffle timers, join/join.cpp
per-phase logs). This package keeps that discipline — every label the
old flat telemetry module emitted is still emitted, byte-identical —
and grows it into a measurement layer:

* ``spans``   — hierarchical, contextvar-nested spans with typed
  attributes (rows/bytes/world/mode/error); ``phase``/``collect_phases``
  are thin back-compat wrappers over it, so every pre-existing call
  site participates in the span tree unchanged.
* ``metrics`` — process-local counters (shuffle bytes, rows exchanged,
  collective launches, kernel-factory builds = jit recompiles),
  per-phase latency histograms, and HBM gauges sampled from
  ``memory.MemoryPool`` (duck-typed; telemetry stays a base-layer
  leaf).
* ``export``  — JSONL span sink and Prometheus text dump; the
  ``jax.profiler.TraceAnnotation`` carrier stays inside ``span`` so
  Perfetto labels work with no exporter configured.
* ``skew``    — key-distribution skew stats reduced from the exchange
  count matrices the host already fetches (zero extra syncs): per-shard
  send/recv rows+bytes histograms, imbalance factor, EXPLAIN ANALYZE
  warning threshold.
* ``profiler`` — always-on ``jax.monitoring`` listener: trace, lower,
  compile and cache-load seconds of every jitted program, by stage and
  by the span they were spent under
  (``cylon_jit_seconds_total{stage=,phase=}``).
* ``ledger``  — buffer lifetime ledger: materializing ops register
  alloc/free events with owner labels
  (``cylon_live_table_bytes{owner=...}``), per-span HBM deltas ride
  every span as ``hbm_delta``/``hbm_peak`` attrs, and the plan
  executor renders an end-of-query leak report.
* ``flight``  — query flight recorder: a bounded ring of recent root
  span trees plus, on any exception crossing a root span, a JSON
  crash dump (span stack, metrics snapshot, pool watermarks, ledger
  outstanding set) written to ``CYLON_FLIGHT_DIR``.
* ``querylog`` — structured query log: one digest per completed root
  query span (id, tenant, plan fingerprint, outcome, shuffle/retry/
  HBM aggregates) in an in-memory ring + optional rotating JSONL
  file — the join key between traces, metrics and crash dumps.
* ``slo``     — per-tenant latency objectives: fixed-bucket latency
  histograms with p50/p95/p99 estimation, error-budget accounting
  (``CYLON_SLO_P95_MS`` / ``CYLON_SLO_TARGET``), burn events into the
  flight admission ring.
* ``stats``   — the query statistics warehouse: per-fingerprint
  measured EWMAs fed by the querylog hook, per-node-kind q-error
  histograms (estimate accuracy), drift detection with plan-cache
  eviction, stats-informed admission estimates
  (``min(static, ewma x CYLON_STATS_SAFETY)``), JSONL warm-start
  persistence (``CYLON_STATS_PATH``).
* ``sampling`` — overhead-bounded head sampling for root query spans
  (``CYLON_TRACE_SAMPLE_RATE``, deterministic on the query-id hash):
  sampled-out queries keep counters/histograms/querylog but skip
  trace-sink writes; errored queries always promote to fully
  recorded.

The plan executor builds per-query EXPLAIN ANALYZE reports
(plan/report.py) on this layer; docs/telemetry.md documents the span
model, the attribute catalog and both exporter formats.

Layering: this package is a BASE-LAYER LEAF (analysis/layering.py
``telemetry-leaf`` contract) — it imports nothing from the package but
its own submodules, and its underscore names are module-private
(``layering/private-internals``).
"""
from __future__ import annotations

from .spans import (Span, annotate, collect_phases, current_span,
                    host_fetch, log_to_stderr, logger, phase,
                    span, open_span, close_span, attach, add_sink,
                    remove_sink, add_root_hook, remove_root_hook)
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      REGISTRY, counted_cache, counter, gauge, histogram,
                      metrics_snapshot, reset_metrics, sample_memory,
                      set_memory_pool, get_memory_pool)
from .export import JsonlSpanSink, prometheus_text, span_to_json
from . import knobs, ledger, profiler, sampling, skew
from . import flight
from . import stats
from . import querylog, slo
from .skew import SkewStats

__all__ = [
    # spans
    "Span", "annotate", "collect_phases", "current_span", "host_fetch",
    "log_to_stderr", "logger", "phase", "span",
    "open_span", "close_span", "attach", "add_sink", "remove_sink",
    "add_root_hook", "remove_root_hook",
    # metrics
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "REGISTRY",
    "counted_cache", "counter", "gauge", "histogram", "metrics_snapshot",
    "reset_metrics", "sample_memory", "set_memory_pool", "get_memory_pool",
    # exporters
    "JsonlSpanSink", "prometheus_text", "span_to_json",
    # skew + compile-cost + memory-lifetime + failure observability
    "profiler", "skew", "SkewStats", "ledger", "flight",
    # live-service observability: query digests, per-tenant SLOs,
    # overhead-bounded trace sampling
    "querylog", "slo", "sampling",
    # the query statistics warehouse: measured per-fingerprint stats,
    # q-error observatory, drift detection, stats-informed admission
    "stats",
    # the declared CYLON_* environment-knob registry
    "knobs",
]
