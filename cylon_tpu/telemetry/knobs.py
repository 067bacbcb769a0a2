"""The declared ``CYLON_*`` environment-knob registry.

Every tunable the engine reads from the environment is declared HERE —
name, type, default, floor, one-line doc — and read through
:func:`get`. That single chokepoint buys three things the old ad-hoc
``os.environ.get`` sprawl could not:

* **one parse policy** — unset or malformed values read as the declared
  default, ``lo`` floors numeric knobs (absorbing the old
  ``metrics.env_number``); a future policy change (logging malformed
  values, say) lands everywhere at once;
* **a generated reference** — :func:`render_table` emits the
  docs/telemetry.md knob table (``python -m cylon_tpu.telemetry.knobs``
  regenerates it), so the docs can never silently drift from the code;
* **lintability** — the ``envknobs`` analysis family rejects any
  ``CYLON_*`` read of ``os.environ``/``os.getenv`` outside this module
  and any :func:`get` of an undeclared name, so a new knob cannot ship
  undeclared or undocumented.

Reads are LIVE (each :func:`get` consults ``os.environ``), so tests and
operators can flip a knob at any time — nothing is latched at import.

Layering: this module is the leaf of the telemetry leaf — it imports
nothing but the stdlib, so even the base-layer modules (``memory.py``)
may read their knobs through it (the ``base-leaf`` contract carves out
exactly ``telemetry.knobs``).
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Dict, Optional

_TRUTHY = ("1", "true", "yes", "on")
_FALSY = ("0", "false", "no", "off")


def parse_number(raw: Optional[str], default, lo=None,
                 as_int: bool = False):
    """Pure numeric parse — THE policy behind every numeric knob:
    ``None`` or malformed reads as ``default``, ``lo`` floors the
    result."""
    if raw is None:
        return default
    try:
        v = int(raw) if as_int else float(raw)
    except ValueError:
        return default
    return max(v, lo) if lo is not None else v


def env_number(name: str, default, lo=None, as_int: bool = False):
    """:func:`parse_number` over a live ``os.environ`` read. Exposed
    for the rare caller that needs the raw policy; everything in-tree
    goes through a declared :class:`Knob` and :func:`get`."""
    return parse_number(os.environ.get(name), default, lo=lo,
                        as_int=as_int)


@dataclass(frozen=True)
class Knob:
    """One declared environment knob.

    ``kind`` is ``int`` / ``float`` / ``bool`` / ``str``; ``default``
    is returned when the variable is unset or malformed; ``lo`` floors
    numeric values; ``doc`` is the one-line description the generated
    docs table renders."""

    name: str
    default: object
    kind: str
    doc: str
    lo: Optional[float] = None

    def parse(self, raw: Optional[str]):
        if raw is None:
            return self.default
        if self.kind == "str":
            return raw
        if self.kind == "bool":
            v = raw.strip().lower()
            if v in _TRUTHY:
                return True
            if v in _FALSY:
                return False
            return self.default
        return parse_number(raw, self.default, lo=self.lo,
                            as_int=self.kind == "int")

    def get(self):
        return self.parse(os.environ.get(self.name))

    def default_str(self) -> str:
        if self.default is None:
            return "unset"
        if self.kind == "bool":
            return "1" if self.default else "0"
        return str(self.default)


# name -> Knob, in declaration order (the docs-table order)
KNOBS: "Dict[str, Knob]" = {}


def declare(name: str, default, kind: str, doc: str,
            lo: Optional[float] = None) -> Knob:
    """Register one knob; re-declaring a name is a programming error
    (two owners would disagree about defaults)."""
    if kind not in ("int", "float", "bool", "str"):
        raise ValueError(f"knob {name!r}: unknown kind {kind!r}")
    if name in KNOBS:
        raise ValueError(f"knob {name!r} already declared")
    k = Knob(name, default, kind, doc, lo)
    KNOBS[name] = k
    return k


def _require(name: str) -> Knob:
    k = KNOBS.get(name)
    if k is None:
        raise KeyError(
            f"{name!r} is not a declared knob (telemetry/knobs.py); "
            f"declared: {sorted(KNOBS)}")
    return k


def get(name: str):
    """The current value of a declared knob (live ``os.environ``
    read; unset/malformed -> the declared default)."""
    return _require(name).get()


def default(name: str):
    """A declared knob's default — the single source the per-module
    ``DEFAULT_*`` re-exports bind to."""
    return _require(name).default


def render_table() -> str:
    """The markdown knob-reference table embedded in docs/telemetry.md
    (``python -m cylon_tpu.telemetry.knobs`` regenerates it; the
    ``envknobs`` analysis family checks every declared name appears)."""
    lines = ["| knob | type | default | description |",
             "|---|---|---|---|"]
    for k in KNOBS.values():
        lines.append(f"| `{k.name}` | {k.kind} | `{k.default_str()}` "
                     f"| {k.doc} |")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# the catalog — every CYLON_* tunable in the package, one row each.
# Grouped by owner; the owning module re-exports its DEFAULT_* via
# default() so there is exactly one copy of each value.
# ---------------------------------------------------------------------------

# memory.py
declare("CYLON_HBM_BYTES", None, "int",
        "per-chip HBM limit for a TPU whose runtime hides memory_stats "
        "(unset: the published size of its device_kind, memory."
        "TPU_HBM_BYTES); sizes the >HBM routing guards and the shuffle "
        "comm budget", lo=1)

# telemetry/
declare("CYLON_TRACE_SAMPLE_RATE", 1.0, "float",
        "head-sampling rate for root query spans (0..1), decided "
        "deterministically from the query_id hash; sampled-out queries "
        "keep counters/histograms/querylog but skip trace-sink writes, "
        "and errored queries are always promoted to fully recorded",
        lo=0.0)
declare("CYLON_SPAN_LOG_MAX_BYTES", 0, "int",
        "size bound for file-backed JSONL sinks (span trace and query "
        "log): past it the file rotates (keep-3 .1/.2/.3 suffixes); "
        "0 = unbounded", lo=0)
declare("CYLON_HBM_SPAN_ATTRS", True, "bool",
        "sample the registered MemoryPool at span enter/exit for "
        "hbm_delta/hbm_peak attrs; 0 skips the two per-span snapshots "
        "on latency-critical runs")
declare("CYLON_SKEW_WARN_FACTOR", 2.0, "float",
        "exchange imbalance factor (max/mean destination rows) beyond "
        "which spans gain skew_warn and EXPLAIN ANALYZE marks [SKEW]",
        lo=1.0)
declare("CYLON_FLIGHT_RING", 16, "int",
        "completed root-span trees (and admission decisions) the "
        "flight recorder keeps in memory", lo=1)
declare("CYLON_FLIGHT_DIR", None, "str",
        "directory for crash dumps when a root span closes errored; "
        "unset disables dumps (the ring stays on)")
declare("CYLON_FLIGHT_MAX_DUMPS", 32, "int",
        "crash-dump files kept in CYLON_FLIGHT_DIR before oldest-first "
        "rotation", lo=1)

# plan/
declare("CYLON_TPU_VERIFY_PLANS", False, "bool",
        "debug assert: re-derive partitioning witnesses over every "
        "optimized (and cache-hit) plan via plan/verify.py, raising on "
        "unjustified elisions (tests/conftest.py enables it)")

# resilience/
declare("CYLON_RETRY_MAX", 3, "int",
        "total attempts per retryable stage (exchange dispatch, "
        "ingest reads)", lo=1)
declare("CYLON_RETRY_BACKOFF_S", 0.05, "float",
        "base backoff before attempt 2, doubling per retry — "
        "deterministic, no jitter", lo=0.0)
declare("CYLON_QUERY_DEADLINE_S", None, "float",
        "per-query wall-clock budget; expiry raises CylonTimeoutError "
        "at the next node/retry boundary")
declare("CYLON_SHED_FACTOR", 8.0, "float",
        "admission controller sheds when the worst node estimate "
        "exceeds this multiple of the byte budget", lo=1.0)
declare("CYLON_FAULT_PLAN", None, "str",
        "armed chaos fault plan (site:trigger:kind[,...]) — see "
        "docs/resilience.md for the grammar")

# service/
declare("CYLON_SERVICE_QUEUE_MAX", 256, "int",
        "total service queue bound; beyond it submit() raises typed "
        "backpressure before enqueue", lo=1)
declare("CYLON_SERVICE_QUANTUM_BYTES", 1 << 20, "int",
        "deficit-round-robin quantum added per sweep visit (the "
        "fair-share byte unit)", lo=1)
declare("CYLON_PLAN_CACHE_MAX", 64, "int",
        "plan/fingerprint cache entries (0 disables the cache)", lo=0)
declare("CYLON_OBS_PORT", 0, "int",
        "TCP port for the observability HTTP endpoint (/metrics, "
        "/healthz, /queries, /slo, /stats) the QueryService starts "
        "on a daemon thread; 0 disables it", lo=0)

# telemetry/slo.py (per-tenant service-level objectives)
declare("CYLON_SLO_P95_MS", None, "float",
        "declared per-tenant latency objective: the p95 query latency "
        "(ms) the service promises; unset = no objective, SLO "
        "evaluation reports latency quantiles only", lo=0.0)
declare("CYLON_SLO_TARGET", 0.99, "float",
        "fraction of queries that must meet the latency objective "
        "(the SLO target); the error budget is the allowed 1-target "
        "violation share, and burn events land in the flight "
        "admission ring", lo=0.0)

# telemetry/stats.py (the query statistics warehouse)
declare("CYLON_STATS_MIN_OBS", 3, "int",
        "successful observations a fingerprint needs before its "
        "measured EWMA informs admission estimates (below it the "
        "static upper bound rules); also the drift-detection floor",
        lo=1)
declare("CYLON_STATS_SAFETY", 1.5, "float",
        "headroom multiplier on the measured EWMA when it replaces a "
        "static estimate: effective = min(static, ewma x safety) — "
        "never above the static bound", lo=1.0)
declare("CYLON_STATS_DRIFT_FACTOR", 4.0, "float",
        "a new measurement deviating beyond this ratio from the EWMA "
        "(either direction) fires cylon_stats_drift_total, records a "
        "flight-ring event, evicts the plan-cache entry and resets "
        "the learned stats to re-learn from the new regime", lo=1.0)
declare("CYLON_STATS_PATH", None, "str",
        "JSONL persistence path for the statistics warehouse: saved "
        "on QueryService.close(), loaded on start() so a fresh "
        "replica warm-starts its estimates; a corrupt file is "
        "quarantined (renamed aside), never fatal")

# plan/optimizer.py (adaptive join execution — stats-driven rewrites)
declare("CYLON_BROADCAST_MAX_BYTES", 1 << 22, "int",
        "broadcast-hash-join budget: a join side whose MEASURED size "
        "(EWMA x CYLON_STATS_SAFETY) fits under this many bytes may "
        "be replicated to every shard instead of hash-exchanged "
        "(requires CYLON_STATS_MIN_OBS successful observations and a "
        "probe side measured at least BROADCAST_MIN_RATIO x larger); "
        "0 disables the rewrite", lo=0)
declare("CYLON_SALT_FACTOR", 4, "int",
        "hot-key salting spread: a standalone exchange whose measured "
        "skew crossed CYLON_SKEW_WARN_FACTOR splits each hot "
        "destination's rows across this many sub-buckets (consecutive "
        "shards; pow2-floored — the factor keys one compiled program "
        "per octave), bounding the max shard under Zipfian keys; 0 or "
        "1 disables salting", lo=0)


if __name__ == "__main__":  # pragma: no cover - doc regeneration
    print(render_table())
