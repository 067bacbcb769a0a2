"""Kernel compile-cost profiler: what the ~24 jitted factories cost.

The engine's eager discipline (host-picked pow2 capacities, counted
``@counted_cache`` factories) bounds the number of distinct XLA
programs — but each one still pays a compile, and on a TPU
backend a recompile storm is the classic way a "fast" pipeline goes
slow. ``cylon_kernel_factory_builds_total`` counts the builds; this
module, when enabled, measures what each build's programs actually
COST:

* **compile wall time** — the program is lowered and compiled
  explicitly (``jitted.lower(*args).compile()``), the wall clock around
  ``compile()`` feeding ``cylon_kernel_compile_seconds{factory=...}``;
* **XLA cost analysis** — ``compiled.cost_analysis()`` FLOPs and bytes
  accessed, when the backend reports them (TPU does; CPU may not —
  every probe degrades gracefully to ``None``, never an error).

Mechanics: ``enable()`` installs a build hook into
``metrics.counted_cache``; every factory built afterwards returns a
``_ProfiledProgram`` proxy instead of the bare jit callable. The proxy
keeps its own (shape, dtype)-keyed executable cache: the FIRST call
with a new signature lowers + compiles + measures, then runs the
compiled executable; repeat signatures dispatch the cached executable
directly, so profiling never compiles the same program twice. Anything
unexpected (non-lowerable callable, aval mismatch, exotic backend)
falls back to calling the original jit object — profiling is strictly
additive, never a correctness risk.

Factories already memoized before ``enable()`` keep their unwrapped
programs (the lru_cache holds them); enable the profiler before first
use — bench.py does, so BENCH artifacts embed ``summary()`` under
``detail.compile_profile``.
"""
from __future__ import annotations

import threading
import time
from typing import List, Optional

from . import metrics as _metrics

# compile wall-time buckets, seconds (an elementwise program to a
# many-minute Mosaic build)
COMPILE_SECONDS_BUCKETS = (0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 10.0, 30.0,
                           60.0, 300.0)

_enabled = False
_records: List[dict] = []
_lock = threading.Lock()


def _cost_analysis(compiled):
    """(flops, bytes_accessed) from an XLA Compiled, or (None, None)
    when the backend hides them — cost_analysis may raise, return a
    list, or return a dict missing either key depending on backend and
    jax version."""
    try:
        ca = compiled.cost_analysis()
    except Exception:  # cylint: disable=errors/broad-swallow — cost_analysis is best-effort
        return None, None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None, None

    def _num(key):
        v = ca.get(key)
        return float(v) if isinstance(v, (int, float)) else None

    return _num("flops"), _num("bytes accessed")


def _signature(args):
    """Hashable (treedef, leaf aval) key for one call's inputs."""
    import jax

    leaves, treedef = jax.tree.flatten(args)
    return (str(treedef),
            tuple((getattr(x, "shape", None), str(getattr(x, "dtype", type(x))))
                  for x in leaves))


class _ProfiledProgram:
    """Proxy over one factory's jitted program: compile-on-first-call
    per input signature, with measurement. Falls back to the wrapped
    callable whenever the explicit lower/compile path cannot apply."""

    def __init__(self, factory: str, fn):
        self._factory = factory
        self._fn = fn
        self._compiled = {}

    def __call__(self, *args, **kwargs):
        if kwargs:  # factories here are positional; don't guess
            return self._fn(*args, **kwargs)
        try:
            import jax

            leaves = jax.tree.leaves(args)
            if any(isinstance(x, jax.core.Tracer) for x in leaves):
                # being traced (make_jaxpr, an enclosing jit): the
                # proxy must be transparent, not AOT-compile
                return self._fn(*args)
            key = _signature(args)
        except Exception:  # cylint: disable=errors/broad-swallow — non-lowerable program falls back to bare jit
            return self._fn(*args)
        hit = self._compiled.get(key)
        if hit is not None:
            try:
                return hit(*args)
            except Exception:  # cylint: disable=errors/broad-swallow — cost_analysis is best-effort
                # evict: a signature whose executable rejects dispatch
                # (sharding/commitment drift) must not pay a failed
                # AOT call on every subsequent exchange
                del self._compiled[key]
                return self._fn(*args)
        if not _enabled:
            return self._fn(*args)
        try:
            lowered = self._fn.lower(*args)
            t0 = time.perf_counter()
            compiled = lowered.compile()
            dt = time.perf_counter() - t0
        except Exception:  # cylint: disable=errors/broad-swallow — compile() unsupported: bare jit fallback
            # tracers (make_jaxpr/abstract eval), non-jit callables,
            # backends without AOT support: profiling bows out
            return self._fn(*args)
        flops, nbytes = _cost_analysis(compiled)
        _record(self._factory, dt, flops, nbytes)
        self._compiled[key] = compiled
        try:
            return compiled(*args)
        except Exception:  # cylint: disable=errors/broad-swallow — cost dict shape varies by backend
            # aval/sharding subtleties the signature key missed: the
            # jit object remains the source of truth
            del self._compiled[key]
            return self._fn(*args)


def _record(factory: str, seconds: float, flops, nbytes) -> None:
    _metrics.REGISTRY.histogram(
        "cylon_kernel_compile_seconds", {"factory": factory},
        buckets=COMPILE_SECONDS_BUCKETS).observe(seconds)
    if flops is not None:
        _metrics.REGISTRY.counter(
            "cylon_kernel_compile_flops_total",
            {"factory": factory}).inc(int(flops))
    if nbytes is not None:
        _metrics.REGISTRY.counter(
            "cylon_kernel_compile_bytes_accessed_total",
            {"factory": factory}).inc(int(nbytes))
    with _lock:
        _records.append({"factory": factory,
                         "compile_s": round(seconds, 6),
                         "flops": flops, "bytes_accessed": nbytes})


def _build_hook(factory: str, built):
    if not callable(built):
        return built
    return _ProfiledProgram(factory, built)


def enable() -> None:
    """Install the counted_cache build hook; factories built from now
    on capture compile cost. Idempotent."""
    global _enabled
    _enabled = True
    _metrics.set_factory_build_hook(_build_hook)


def disable() -> None:
    """Stop profiling NEW programs. Already-wrapped factories keep
    dispatching their cached executables (no re-measurement)."""
    global _enabled
    _enabled = False
    _metrics.set_factory_build_hook(None)


def enabled() -> bool:
    return _enabled


def records() -> List[dict]:
    """Every measured compile, in order: {factory, compile_s, flops,
    bytes_accessed} (cost fields None where the backend hides them)."""
    with _lock:
        return [dict(r) for r in _records]


def reset() -> None:
    with _lock:
        _records.clear()


def summary() -> dict:
    """Per-factory aggregate — the BENCH artifact form:
    {factory: {programs, compile_s, flops, bytes_accessed}} with cost
    totals None when no program reported them."""
    out: dict = {}
    for r in records():
        agg = out.setdefault(r["factory"], {
            "programs": 0, "compile_s": 0.0,
            "flops": None, "bytes_accessed": None})
        agg["programs"] += 1
        agg["compile_s"] = round(agg["compile_s"] + r["compile_s"], 6)
        for k in ("flops", "bytes_accessed"):
            if r[k] is not None:
                agg[k] = (agg[k] or 0.0) + r[k]
    return out
