#!/bin/sh
# One-stop verification gate: static analysis + telemetry smoke +
# tier-1 tests (ROADMAP.md). Usage: sh scripts/check.sh
set -e
cd "$(dirname "$0")/.."

echo "== static analysis: ten families + wall-clock budget =="
# all ten checker families (layering, hostsync, collectives, witness,
# span-coverage, ledger-coverage, errors, concurrency, envknobs,
# specialization); any unsuppressed finding fails the gate before
# tests. The call-graph families (hostsync/concurrency/envknobs/
# specialization) share ONE ModuleIndex per invocation, and this
# budget assertion makes sure the full ten-family gate never silently
# turns unusably slow (measured ~30s: jax import + collectives kernel
# builds dominate; the budget leaves 3x headroom)
python - <<'EOF'
import json, subprocess, sys, time
t0 = time.monotonic()
proc = subprocess.run(
    [sys.executable, "-m", "cylon_tpu.analysis", "--json"],
    capture_output=True, text=True)
wall = time.monotonic() - t0
if proc.returncode != 0:
    sys.exit("analysis gate: real tree not clean (exit %d)\n%s"
             % (proc.returncode, proc.stdout + proc.stderr))
doc = json.loads(proc.stdout)
assert doc["version"] == 1, doc["version"]
assert len(doc["checkers"]) == 10, doc["checkers"]
for fam in ("concurrency", "specialization"):
    assert fam in doc["checkers"], doc["checkers"]
assert doc["ok"] and not doc["findings"], doc["findings"]
if wall >= 90.0:
    sys.exit("analysis gate: %.1fs wall, budget is 90s — the "
             "call-graph closure or kernel-build sweep has regressed"
             % wall)
print("analysis gate ok: ten families clean in %.1fs (budget 90s)"
      % wall)
EOF

echo "== concurrency smoke: --families concurrency --json under 30s =="
# single-family contract pin: the race detector alone must stay usable
# for inner-loop runs, and the JSON envelope CI consumes stays stable
python - <<'EOF'
import json, subprocess, sys, time
t0 = time.monotonic()
proc = subprocess.run(
    [sys.executable, "-m", "cylon_tpu.analysis",
     "--families", "concurrency", "--json"],
    capture_output=True, text=True)
wall = time.monotonic() - t0
if proc.returncode != 0:
    sys.exit("concurrency smoke: real tree not clean (exit %d)\n%s"
             % (proc.returncode, proc.stdout + proc.stderr))
doc = json.loads(proc.stdout)
assert doc["version"] == 1, doc["version"]
assert "concurrency" in doc["checkers"], doc["checkers"]
assert doc["ok"] and not doc["findings"], doc["findings"]
if wall >= 30.0:
    sys.exit("concurrency smoke: %.1fs wall, budget is 30s — "
             "call-graph closure has regressed" % wall)
print("concurrency smoke ok: clean in %.1fs (budget 30s)" % wall)
EOF

echo "== telemetry smoke: scripts/smoke_telemetry.py =="
# a two-shuffle pipeline must produce a parseable JSONL trace (with
# per-exchange skew attributes AND per-span hbm_delta/hbm_peak attrs),
# a Prometheus dump with nonzero shuffle_bytes_total + per-shard
# shuffle histograms + kernel compile-seconds + live-table-bytes
# gauges, an EXPLAIN ANALYZE report whose shuffle count matches the
# phase labels with skew + pre-flight est= columns and zero leaks; a
# deliberately failing query must leave a parseable crash dump (span
# stack, metrics, nonzero pool watermark, ledger outstanding set)
python scripts/smoke_telemetry.py

echo "== service smoke: scripts/smoke_service.py =="
# the concurrent query service: 8 equal-shape queries over two tenants
# must return results bit-identical to sequential execution with >= 7
# plan-cache hits, zero kernel-factory builds after the first query
# (cached plans re-verified by plan/verify.py on every hit), per-tenant
# cylon_queries_total/queue-depth series in the Prometheus dump, and
# zero ledger leaks
python scripts/smoke_service.py

echo "== observability smoke: scripts/smoke_obs.py =="
# the live service observatory: a multi-tenant service with the HTTP
# endpoint armed must serve valid /metrics (incl. per-tenant
# cylon_slo_latency_p95_ms series), /healthz, /queries and /slo while
# running; the structured query log must carry exactly one parseable
# JSONL line per completed query; at CYLON_TRACE_SAMPLE_RATE=0.5 the
# span-sink line count must DROP while counters/querylog stay complete
# and the per-query sampling decisions replay from the query_id hash;
# close() must leave no obs thread and zero ledger leaks
python scripts/smoke_obs.py

echo "== stats smoke: scripts/smoke_stats.py =="
# the estimate-accuracy closed loop: a repeat-shape workload must
# populate per-kind cylon_estimate_qerror series and the /stats
# route; a query whose stat-free estimate sheds at first sight under
# a clamped budget must be ADMITTED on repeat (est_source=measured in
# digest + admission ring) once the shape is learned, while a fresh
# shape still sheds on its static estimate; zero leaks, clean close
python scripts/smoke_stats.py

echo "== chaos drill: scripts/chaos.py --seeds 3 =="
# seeded fault plans through a join→groupby pipeline: transient faults must
# retry to success ([RETRY] in EXPLAIN ANALYZE) — including a fault
# MID-CHUNK-STREAM of the overlapped (chunked) exchange pipeline, whose
# retried result must bit-match the single-shot baseline with zero new
# ledger leaks (the overlap scenario) — persistent faults must
# fail TYPED with a parseable crash dump naming the fault site, an
# over-budget query must be shed or degraded by the admission
# controller, a zero deadline must time out typed, a corrupt stats
# snapshot must be quarantined and an injected 10x-rows drift must
# evict the cached plan + revert admission to static estimates with
# bit-identical results (stats scenario), and the CONCURRENT
# service drill (queries across two tenants with an injected exchange
# fault + one over-budget query) must retry/shed without disturbing the
# other queries' results — all deterministic per seed, zero ledger
# leaks on every path; failures print the fault plan + seed for
# one-command replay
python scripts/chaos.py --seeds 3

echo "== tier-1 tests =="
JAX_PLATFORMS=cpu exec python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider -p no:xdist \
    -p no:randomly
