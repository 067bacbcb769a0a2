"""cylon_tpu.analysis — pluggable static-analysis suite.

Ten checker families guard the invariants the paper's *local kernel +
shuffle + local kernel* decomposition rests on (SURVEY §1), each
registered in `core.CHECKERS` and runnable from one entry point:

* ``layering``      — declarative per-subsystem import contracts;
* ``hostsync``      — AST detector for host transfers inside traced
                      (`jit`/`shard_map`/Pallas) code;
* ``collectives``   — jaxpr-level checks over the `parallel/` kernel
                      factories on a virtual mesh: collective axis
                      names, all_to_all split/concat discipline, no
                      implicit float64 promotion;
* ``witness``       — optimizer-independent re-derivation of
                      partitioning witnesses over optimized plans
                      (wraps plan/verify.py): every shuffle elision
                      must be justified or the plan is rejected;
* ``span-coverage`` — every public ``distributed_*`` op and every
                      executor lowering must run under a telemetry
                      span (the observability layer's coverage
                      contract — an unspanned operator is invisible
                      to shuffle counting and EXPLAIN ANALYZE);
* ``ledger-coverage`` — the memory analog: every materializing
                      ``distributed_*`` op and executor lowering must
                      register its output with the telemetry ledger,
                      or its HBM is unattributable to gauges, leak
                      reports and crash dumps;
* ``errors``        — no silent swallowing: bare ``except:`` and
                      broad ``except Exception`` handlers that
                      neither re-raise nor report (log call /
                      ``error=True`` span attr) are findings — a
                      fault dying in one never reaches the
                      resilience layer's retry or flight recorder;
* ``concurrency``   — thread-domain race detector over the service
                      tier: shared state written across the worker/
                      submitter/finalizer/hook domains must follow
                      the per-attribute lock discipline, no blocking
                      call may hold a lock, thread-entry code must
                      re-stamp the contextvars it reads, and GC
                      finalizers must never touch non-reentrant
                      locks or jax;
* ``envknobs``      — every ``CYLON_*`` environment read routes
                      through the declared knob registry
                      (telemetry/knobs.py) and every declared knob
                      appears in the generated docs table;
* ``specialization`` — kernel-specialization auditor: every
                      ``counted_cache`` factory cache-key argument is
                      classified (structural / schema-bound / bucketed
                      / data-dependent / unbounded) by tracing it from
                      the call site through the call graph; a runtime
                      count reaching a cache key without a recognized
                      bucketing helper is a finding — recompile
                      cardinality stays bounded by construction.

Run ``python -m cylon_tpu.analysis`` (see ``--help``); wired into
``scripts/check.sh`` ahead of tier-1. Rule catalog, suppression syntax
and extension guide: docs/analysis.md.
"""
from __future__ import annotations

from .core import (AnalysisContext, CHECKERS, Finding, RunResult,
                   SARIF_VERSION, SCHEMA_VERSION, register, run_checkers,
                   to_json_text, to_sarif, to_sarif_text)

# importing the checker modules registers them
from . import layering as _layering          # noqa: F401,E402
from . import hostsync as _hostsync          # noqa: F401,E402
from . import collectives as _collectives    # noqa: F401,E402
from . import witness as _witness            # noqa: F401,E402
from . import spancov as _spancov            # noqa: F401,E402
from . import ledgercov as _ledgercov        # noqa: F401,E402
from . import errors as _errors              # noqa: F401,E402
from . import concurrency as _concurrency    # noqa: F401,E402
from . import envknobs as _envknobs          # noqa: F401,E402
from . import specialization as _specialization  # noqa: F401,E402

__all__ = ["AnalysisContext", "CHECKERS", "Finding", "RunResult",
           "SARIF_VERSION", "SCHEMA_VERSION", "register", "run_checkers",
           "to_json_text", "to_sarif", "to_sarif_text"]
