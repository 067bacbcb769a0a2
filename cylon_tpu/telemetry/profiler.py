"""Trace, lower, compile and cache-load seconds of every jitted program,
always on: a ``jax.monitoring`` listener registered once at import.

JAX reports what it spends before a program first runs: tracing the
Python function to a jaxpr, lowering the jaxpr to an MLIR module
(Mosaic kernels are built here), the backend compile, and, on a
persistent-cache hit, the retrieval of the executable. This module adds
each to

* ``cylon_jit_seconds_total{stage=trace|lower|compile|cache_load,
  phase=<innermost open span's name, or none>}`` and
* ``cylon_jit_events_total{stage=}`` (one per event, nested ones too),

so that a slow first query says which stage and which operator's span
the time went to (``summary()`` reads them back). The seconds are SELF
time: a jnp helper traced inside an outer trace, or a constant folded
eagerly inside one, reports an event of its own within the outer one's
interval, and is taken out of the outer one's seconds; the retrieval of
a cached executable happens inside the backend-compile event and is
taken out of ``stage="compile"``. The four stages therefore add up to
the wall time this thread spent in JAX's compile pipeline, counted
once. What a program's FIRST RUN costs beyond these (executable load
onto the device, the run itself) is not a JAX event; it is the rest of
the enclosing span's time.

The listeners are never cleared (``jax.monitoring`` has only a
clear-all, and callers such as the benchmark harness register
listeners of their own).
"""
from __future__ import annotations

import threading

import jax.monitoring as _monitoring

from . import metrics as _metrics
from . import spans as _spans

# jax/_src/dispatch.py and compiler.py: the three stages that report a
# time span (start and end), and the retrieval that reports a duration
_STAGE_OF = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
STAGES = ("trace", "lower", "compile", "cache_load")

_KEEP = 1024


class _Thread(threading.local):
    """Per thread (each thread's compile pipeline nests on its own)."""

    def __init__(self):
        # completed intervals that no enclosing event has absorbed
        # yet. Events complete innermost first, so the intervals an
        # arriving event encloses are exactly the tail of this list. A
        # top-level interval is never absorbed: the list is cut back
        # to _KEEP once it passes twice that (an outer trace holds a
        # few hundred direct children at most).
        self.done = []
        # cache-retrieval seconds not yet taken out of a compile event
        self.loaded = 0.0


_local = _Thread()


def _add(stage: str, seconds: float) -> None:
    s = _spans.current_span()
    _metrics.REGISTRY.counter(
        "cylon_jit_seconds_total",
        {"stage": stage, "phase": s.name if s is not None else "none"}
    ).inc(seconds)
    _metrics.REGISTRY.counter("cylon_jit_events_total",
                              {"stage": stage}).inc()


def _on_time_span(event: str, start: float, end: float, **_kw) -> None:
    stage = _STAGE_OF.get(event)
    if stage is None:
        return
    done = _local.done
    inside = 0.0
    while done and done[-1][0] >= start:
        s, e = done.pop()
        inside += e - s
    done.append((start, end))
    if len(done) > 2 * _KEEP:
        del done[:-_KEEP]
    if stage == "compile":
        inside += _local.loaded
        _local.loaded = 0.0
    _add(stage, max(end - start - inside, 0.0))


def _on_duration(event: str, seconds: float, **_kw) -> None:
    if event == _CACHE_LOAD_EVENT:
        _local.loaded += seconds
        _add("cache_load", seconds)


_monitoring.register_event_time_span_listener(_on_time_span)
_monitoring.register_event_duration_secs_listener(_on_duration)


def summary() -> dict:
    """``{stage: {"events": n, "seconds": s, "by_phase": {phase: s}}}``
    read back from the two counters: the process's totals so far."""
    out = {st: {"events": 0, "seconds": 0.0, "by_phase": {}}
           for st in STAGES}
    for name, labels, m in _metrics.REGISTRY.series():
        lab = dict(labels)
        if name == "cylon_jit_events_total":
            out[lab["stage"]]["events"] = m.value
        elif name == "cylon_jit_seconds_total":
            agg = out[lab["stage"]]
            agg["seconds"] += m.value
            agg["by_phase"][lab["phase"]] = m.value
    return out
