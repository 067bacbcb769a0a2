"""Hierarchical spans + the phase-timer back-compat surface.

The reference's observability is pervasive manual wall-clock timing with
glog at every operator phase (reference: cpp/src/cylon/table.cpp:320-335
shuffle timing; join/join.cpp:101-253 per-phase logs; arrow_hash_kernels.hpp
:120,163 build/probe timers). Here the same discipline rides three carriers:

* a ``logging`` logger named ``cylon_tpu`` — every span logs its
  host-side elapsed time at INFO on exit. JAX dispatch is async: unless
  a span ends in a host sync (the count→materialize scalar fetches do),
  the time logged is dispatch+trace cost, not device time. That is
  exactly what the phase discipline is for — spotting recompiles and
  host round-trips, the things the host can see.
* ``jax.profiler.TraceAnnotation`` — the same label appears in
  TensorBoard / Perfetto traces captured with ``jax.profiler.trace``,
  where the DEVICE time lives. ``seq`` carries the context's op
  sequence number, the moral heir of the reference's MPI edge/tag id
  (ctx/cylon_context.cpp:94-99).
* a contextvar-scoped `Span` TREE — spans opened inside another span
  become its children, carry typed attributes (``rows_in``/``rows_out``,
  ``bytes_moved``, ``world``, ``mode``, error flag), and feed the
  registered sinks (export.JsonlSpanSink) and the per-phase latency
  histogram (metrics) on completion. The plan executor's per-query
  EXPLAIN ANALYZE report (plan/report.py) is built on this tree.

Every span is stamped ``start_s`` / ``end_s`` on ``time.perf_counter``
(one clock for every thread) and closes in ONE place, ``close_span``.
``span()`` is ``open_span`` + the block + ``close_span``; the two are
public for a span that is no ``with`` block on one thread, and
``attach`` makes an open span the current one of another thread, so a
tree can cross threads (the service's ``service.query`` root opens in
``submit()`` on the caller's thread and closes on the completion
thread: service/scheduler.py).

``phase(name, seq)`` is the original module's API, now a thin wrapper
over ``span`` — all pre-package call sites keep their exact semantics
(label format ``name#seq``, one INFO line per span, collect_phases
label counting). New in the package: the body is wrapped in
try/finally, so a raising phase still records its elapsed time, gains
an ``error=True`` attribute, logs, and re-raises (the old module
silently dropped the measurement on the floor).

Enable host-side logs with ``logging.getLogger("cylon_tpu").setLevel(
logging.INFO)`` plus a handler, or ``cylon_tpu.telemetry.log_to_stderr()``.
"""
from __future__ import annotations

import itertools
import logging
import time
from contextlib import contextmanager, nullcontext
from contextvars import ContextVar
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterator, List, Optional

import jax

from . import knobs as _knobs
from . import metrics as _metrics
from . import sampling as _sampling

logger = logging.getLogger("cylon_tpu")

# active phase collectors (collect_phases contexts) — every entered
# span appends its label AND the Span object to each, so callers can
# COUNT events (e.g. a query plan's shuffles) without wiring a logging
# handler, and the plan recorder can read back typed attributes (the
# exchange skew stats) by the same indices
_collectors: list = []

# completed-span sinks (add_sink/remove_sink); each is called with every
# Span as it CLOSES — the JSONL exporter registers here
_sinks: List[Callable] = []

# root-span close hooks: called with every span that closes with NO
# parent (a whole query tree / top-level eager op). The flight recorder
# (telemetry/flight.py) registers here to keep its completed-query ring
# and to write crash dumps when a root span closes errored. Exceptions
# are logged, never raised.
_root_hooks: List[Callable] = []

_span_ids = itertools.count(1)

# per-span HBM sampling (hbm_delta/hbm_peak attrs): two pool snapshots
# per span — a refcounted-counter read on ledger-backed pools, one
# memory_stats runtime call per local device on stats-bearing
# backends. CYLON_HBM_SPAN_ATTRS=0 turns it off for latency-critical
# runs (read live through the knob registry, so it can be flipped at
# any time); the flight recorder's crash-time watermarks are
# unaffected (sampled at dump time).


def _hbm_attrs_on() -> bool:
    return _knobs.get("CYLON_HBM_SPAN_ATTRS")


# innermost open span of the current (async/thread) context, or None
_current: ContextVar[Optional["Span"]] = ContextVar(
    "cylon_tpu_current_span", default=None)

@dataclass
class Span:
    """One timed operation with typed attributes and child spans.

    ``elapsed_ms`` is None while the span is open; ``attrs`` holds the
    attribute catalog documented in docs/telemetry.md (``rows_in``,
    ``rows_out``, ``bytes_moved``, ``world``, ``mode``, ``error``...).
    """

    name: str
    seq: Optional[int] = None
    attrs: Dict[str, object] = field(default_factory=dict)
    children: List["Span"] = field(default_factory=list)
    span_id: int = 0
    parent_id: int = 0
    root_id: int = 0               # the enclosing tree's root span_id
    elapsed_ms: Optional[float] = None
    error: bool = False
    # head-sampling decision (telemetry/sampling.py): decided at the
    # ROOT from the query_id hash, inherited by every child. False =
    # this span skips trace sinks + device-trace annotation; the tree
    # itself is still built (crash dumps / error promotion need it)
    sampled: bool = True
    # stamps on ``time.perf_counter``'s clock: ``start_s`` when the span
    # opened, ``end_s`` None while it is open. A body that knows the
    # true edge better than its ``with`` block does may stamp either
    # (the service's ``service.submit`` ends where the job is enqueued,
    # inside the lock); ``close_span`` keeps a stamped end and computes
    # ``elapsed_ms`` from the two
    start_s: float = 0.0
    end_s: Optional[float] = None
    _hbm0: Optional[int] = None    # pool bytes_in_use at span enter

    @property
    def label(self) -> str:
        return f"{self.name}#{self.seq}" if self.seq is not None \
            else self.name

    def set(self, **attrs) -> "Span":
        """Attach/overwrite attributes on this span."""
        self.attrs.update(attrs)
        return self

    def walk(self) -> Iterator["Span"]:
        yield self
        for c in self.children:
            yield from c.walk()

    def walk_postorder(self) -> Iterator["Span"]:
        """Children before parents — the order spans CLOSE in, and the
        order the JSONL exporter promises its lines (error promotion
        replays a sampled-out tree through the sinks in this order)."""
        for c in self.children:
            yield from c.walk_postorder()
        yield self

    def to_dict(self, nested: bool = False) -> dict:
        """Flat JSON-able record (parent_id links the tree); pass
        ``nested=True`` to embed children instead."""
        d = {"span_id": self.span_id, "parent_id": self.parent_id,
             "root_id": self.root_id, "name": self.name, "seq": self.seq,
             "start_s": self.start_s, "end_s": self.end_s,
             "elapsed_ms": self.elapsed_ms, "error": self.error,
             "attrs": dict(self.attrs)}
        if nested:
            d["children"] = [c.to_dict(nested=True) for c in self.children]
        return d


def current_span() -> Optional[Span]:
    """The innermost open span of this context, or None."""
    return _current.get()  # cylint: disable=concurrency/unstamped-contextvar — None on a thread with no open span IS the answer (the jit listener bills such seconds to phase="none")


def annotate(**attrs) -> None:
    """Attach attributes to the innermost open span (no-op outside any
    span) — lets deep helpers report ``rows``/``bytes`` without
    threading the Span object through every signature."""
    s = _current.get()
    if s is not None:
        s.attrs.update(attrs)


def add_sink(sink: Callable) -> None:
    """Register a completed-span sink: ``sink(span)`` runs as each span
    closes (innermost first). Exceptions are logged, never raised."""
    _sinks.append(sink)


def add_root_hook(hook: Callable) -> None:
    """Register a root-span close hook: ``hook(span)`` runs when a span
    with no parent closes — the whole tree is complete at that point
    (children closed first). The flight recorder lives here."""
    _root_hooks.append(hook)


def remove_root_hook(hook: Callable) -> None:
    for i, h in enumerate(_root_hooks):
        if h is hook:
            del _root_hooks[i]
            break


def remove_sink(sink: Callable) -> None:
    for i, s in enumerate(_sinks):
        if s is sink:
            del _sinks[i]
            break


def _emit_to_sinks(s: "Span") -> None:
    for sink in list(_sinks):
        try:
            sink(s)
        except Exception:  # pragma: no cover - defensive
            logger.exception("span sink failed")


class collect_phases:
    """Collect every span label entered inside the context — the
    programmatic mirror of the INFO log stream. ``count(prefix)``
    answers questions like "how many shuffles did this plan run?"
    (prefix="plan.shuffle"); labels keep their ``name#seq`` form.
    ``spans[i]`` is the Span whose label is ``labels[i]`` — attributes
    set later in the span body (skew stats, rows_out) are visible
    after it closes, which is how the EXPLAIN ANALYZE recorder reads
    per-exchange skew without re-threading the objects."""

    def __init__(self):
        self.labels: list = []
        self.spans: list = []

    def __enter__(self) -> "collect_phases":
        _collectors.append(self)
        return self

    def __exit__(self, *exc):
        # remove by IDENTITY: list.remove compares by ==, and two nested
        # collectors with equal contents would remove each other
        for i, c in enumerate(_collectors):
            if c is self:
                del _collectors[i]
                break
        return False

    def count(self, prefix: str) -> int:
        return sum(1 for l in self.labels if l.startswith(prefix))


def log_to_stderr(level: int = logging.INFO) -> None:
    """Convenience: route cylon_tpu phase logs to stderr (idempotent)."""
    if not any(getattr(h, "_cylon_tpu", False) for h in logger.handlers):
        handler = logging.StreamHandler()
        handler.setFormatter(
            logging.Formatter("%(asctime)s %(name)s %(message)s"))
        handler._cylon_tpu = True
        logger.addHandler(handler)
    logger.setLevel(level)


def open_span(name: str, seq: Optional[int] = None, *,
              parent: Optional[Span] = None, **attrs) -> Span:
    """Open one span under ``parent`` (None: a ROOT, which takes the
    head-sampling decision) and stamp its start. The head of
    ``span()``; called alone for a span that does not live in one
    ``with`` block on one thread (the service's
    ``service.query`` root opens in ``submit()`` and closes on the
    completion thread): such a span is NOT the current one anywhere
    until ``attach`` makes it so, carries no device-trace annotation,
    and stays open until ``close_span``."""
    sid = next(_span_ids)
    if parent is None:
        # head sampling decided HERE, once per tree: deterministic on
        # the stamped query_id (the service scheduler's monotonic id;
        # this root's span_id outside the service — replayable either
        # way, never an RNG)
        sampled = _sampling.decide(attrs.get("query_id", sid))
        _sampling.record_decision(sampled)
        if not sampled:
            attrs = {**attrs, "sampled": False}
    else:
        sampled = parent.sampled
    s = Span(name, seq, dict(attrs), span_id=sid,
             parent_id=parent.span_id if parent is not None else 0,
             sampled=sampled)
    s.root_id = parent.root_id if parent is not None else s.span_id
    label = s.label
    for c in _collectors:
        c.labels.append(label)
        c.spans.append(s)
    if parent is not None:
        parent.children.append(s)
    # per-span HBM accounting: snapshot the registered pool (duck-typed
    # — metrics.set_memory_pool) at enter and exit so every span carries
    # hbm_delta/hbm_peak attrs. On backends that hide memory_stats the
    # pool reads the ledger's tracked bytes, so the attrs stay live
    # there too (the CPU test mesh).
    pool = _metrics.get_memory_pool() if _hbm_attrs_on() else None
    if pool is not None:
        try:
            s._hbm0 = int(pool.snapshot()[0])
        except Exception:  # pragma: no cover - defensive  # cylint: disable=errors/broad-swallow — pool snapshot failure disables hbm attrs
            s._hbm0 = None
    s.start_s = time.perf_counter()
    return s


def close_span(s: Span) -> None:
    """Close ``s``: THE one place a span ends, on whichever thread.
    Stamps ``end_s`` (unless the body already has), computes
    ``elapsed_ms``, takes the HBM attrs, feeds the per-phase histogram
    and the sinks, and for a root promotes an errored sampled-out tree
    and runs the root hooks. ``span()`` calls it from its ``finally``;
    a span opened with ``open_span`` is closed by calling it directly,
    from any thread (its children have to be closed by then)."""
    if s.end_s is None:
        s.end_s = time.perf_counter()
    s.elapsed_ms = (s.end_s - s.start_s) * 1e3
    pool = _metrics.get_memory_pool() if s._hbm0 is not None else None
    if pool is not None:
        try:
            used, peak, _limit = pool.snapshot()
            s.attrs["hbm_delta"] = int(used) - s._hbm0
            s.attrs["hbm_peak"] = int(peak)
        except Exception:  # pragma: no cover - defensive  # cylint: disable=errors/broad-swallow — pool snapshot failure drops hbm attrs
            pass
    _metrics.observe_phase(s.name, s.elapsed_ms, error=s.error)
    if s.sampled:
        _emit_to_sinks(s)
    if not s.parent_id:
        if s.error and not s.sampled:
            # error promotion: the whole tree is complete (children
            # closed first) and still in memory — record it to the
            # sinks post-hoc, children before parents, so the JSONL
            # trace AND the crash dump read like a fully sampled
            # query. Forensics never degrade under sampling.
            s.sampled = True
            # the sampled attr means "a full trace was exported":
            # after promotion that is TRUE — the query log's
            # digest must not tell an operator that the one class
            # of query GUARANTEED to have a trace has none
            s.attrs["sampled"] = True
            s.attrs["sampled_promoted"] = True
            _sampling.record_promotion()
            for node in s.walk_postorder():
                node.sampled = True
                _emit_to_sinks(node)
        for hook in list(_root_hooks):
            try:
                hook(s)
            except Exception:  # pragma: no cover - defensive
                logger.exception("root-span hook failed")
    if logger.isEnabledFor(logging.INFO):
        logger.info("%s %.3f ms%s", s.label, s.elapsed_ms,
                    " error=True" if s.error else "")


@contextmanager
def attach(s: Span) -> Iterator[Span]:
    """Make the open span ``s`` the current one of THIS thread's context
    for the block: spans opened inside nest under it, ``annotate`` and
    ``current_span`` find it. How a tree crosses threads (a contextvar's
    value does not): the service's worker and completion thread attach
    the root that ``submit()`` opened on the caller's."""
    token = _current.set(s)
    try:
        yield s
    finally:
        _current.reset(token)


@contextmanager
def span(name: str, seq: Optional[int] = None, **attrs) -> Iterator[Span]:
    """Open one span: time it, nest it under the current span, annotate
    device traces with the same label, feed sinks and the per-phase
    latency histogram on close. Yields the Span so the body can
    ``s.set(rows_out=...)``. Exceptions re-raise after the span records
    ``error=True`` and its elapsed time (the fixed phase() bug)."""
    s = open_span(name, seq, parent=_current.get(), **attrs)
    token = _current.set(s)
    try:
        # sampled-out trees skip the device-trace annotation too — the
        # Perfetto label volume is part of the per-span cost the head
        # decision bounds
        with jax.profiler.TraceAnnotation(f"cylon:{s.label}") \
                if s.sampled else nullcontext():
            yield s
    except BaseException:
        s.error = True
        s.attrs["error"] = True
        raise
    finally:
        _current.reset(token)
        close_span(s)


def host_fetch(site: str, x):
    """``jax.device_get(x)`` at a named choke point — THE way the host
    reads a value that decides what it dispatches next (a count, a
    capacity, a flag, splitters; ``x`` may be a pytree). The blocking
    call runs inside a ``sync.<site>`` span, so a profiler trace shows
    ``cylon:sync.<site>`` nested in the operator's span on the device
    planes' clock: the chip's idle time INSIDE it is what the round
    trip costs. Each call adds 1 to ``cylon_host_syncs_total{site=}``
    (before it blocks: what comes after the fetch returns is on the
    critical path of the next dispatch).
    ``site`` is a static string at the call site (label cardinality is
    the fixed set of choke points, never data). Bulk export of a
    finished result (``to_numpy``, csv, output gathers) decides nothing
    and stays a plain ``device_get``."""
    with span("sync." + site):
        _metrics.REGISTRY.counter("cylon_host_syncs_total",
                                  {"site": site}).inc()
        return jax.device_get(x)


def phase(name: str, seq: Optional[int] = None):
    """Time one operator phase; annotate device traces with the same
    label. The original telemetry.py API — now a span with no
    attributes, so every pre-package call site participates in the
    span tree unchanged."""
    return span(name, seq)
