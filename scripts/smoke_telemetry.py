#!/usr/bin/env python
"""Telemetry exporter smoke gate (wired into scripts/check.sh).

Runs a two-shuffle pipeline (join on k → groupby on a DIFFERENT key,
so the groupby cannot aggregate in place) on the virtual CPU mesh and
verifies the observability layer end to end:

* the JSONL span sink produced a trace where EVERY line parses, the
  tree links up (parent_id resolves), both ``plan.shuffle*`` exchange
  stages appear, and the ``shuffle.exchange*`` spans carry the skew
  attributes (``skew_imbalance`` + shard-row min/med/max) computed
  from the count matrices;
* the Prometheus dump renders and carries a NONZERO
  ``cylon_shuffle_bytes_total`` (the exchange counters are wired, not
  decorative), the per-shard shuffle histograms
  (``cylon_shuffle_shard_rows`` / ``_shard_bytes``), host-sync
  counters, and ``cylon_jit_seconds_total`` from the always-on
  ``jax.monitoring`` listener (telemetry/profiler.py);
* ``explain(analyze=True)`` renders per-node measured rows, its
  reported shuffle count equals ``collect_phases.count("plan.shuffle")``,
  its exchange-bearing nodes render ``skew(...)`` columns, and every
  node carries the planner's pre-flight ``est=...`` bytes beside the
  measured bytes;
* the MEMORY half of the observatory is live: spans carry
  ``hbm_delta``/``hbm_peak`` attrs (ledger-backed pool on the CPU
  mesh), ``cylon_live_table_bytes`` gauges render, and the query leaks
  nothing;
* the FLIGHT RECORDER works under fire: a deliberately failing query
  (injected exchange failure) writes a single-file JSON crash dump to
  ``CYLON_FLIGHT_DIR`` that parses, carries the in-flight
  ``plan.shuffle*`` span in its error path, a NONZERO pool watermark,
  the metrics snapshot, and the ledger's outstanding set.

Exit 0 on success; any failure prints the offending artifact and exits
non-zero, failing the gate.
"""
import json
import os
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "--xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
        " --xla_force_host_platform_device_count=8"

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def fail(msg: str) -> None:
    print(f"telemetry smoke: FAIL — {msg}", file=sys.stderr)
    sys.exit(1)


def main() -> None:
    import numpy as np

    import cylon_tpu as ct
    from cylon_tpu import plan, telemetry
    from cylon_tpu.telemetry import profiler

    ctx = ct.CylonContext.InitDistributed(ct.TPUConfig(world_size=4))
    rng = np.random.default_rng(0)
    n = 4096
    left = ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, n // 4, n).astype(np.int32),
        "v": rng.normal(size=n).astype(np.float32),
        "z": rng.integers(0, 50, n).astype(np.int32)})
    right = ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, n // 4, n).astype(np.int32),
        "w": rng.normal(size=n).astype(np.float32)})

    # join on k, group by z: TWO exchange stages even optimized
    pipe = plan.scan(left).join(plan.scan(right), on="k") \
        .groupby("lt-2", ["rt-4"], ["sum"])

    trace_path = os.path.join(tempfile.mkdtemp(), "trace.jsonl")
    with telemetry.JsonlSpanSink(trace_path) as sink:
        with telemetry.collect_phases() as cp:
            txt = pipe.explain(analyze=True)

    # -- JSONL trace: parseable, linked, carrying both exchanges ------
    lines = open(trace_path, encoding="utf-8").read().splitlines()
    if not lines:
        fail("empty JSONL trace")
    try:
        recs = [json.loads(l) for l in lines]
    except json.JSONDecodeError as e:
        fail(f"unparseable JSONL line: {e}")
    if len(recs) != sink.spans_written:
        fail(f"sink wrote {sink.spans_written} spans, file has "
             f"{len(recs)} lines")
    ids = {r["span_id"] for r in recs}
    dangling = [r for r in recs
                if r["parent_id"] and r["parent_id"] not in ids]
    if dangling:
        fail(f"dangling parent_id in trace: {dangling[:3]}")
    shuffle_spans = [r for r in recs
                     if r["name"].startswith("plan.shuffle")]
    if len(shuffle_spans) != 2:
        fail(f"expected 2 plan.shuffle* spans in the trace, got "
             f"{[r['name'] for r in shuffle_spans]}")
    # every exchange span must carry the skew attributes (reduced from
    # the already-fetched count matrix — the zero-extra-sync contract)
    ex_spans = [r for r in recs
                if r["name"].startswith("shuffle.exchange")]
    if not ex_spans:
        fail("no shuffle.exchange* spans in the trace")
    for r in ex_spans:
        missing = [k for k in ("skew_imbalance", "shard_rows_min",
                               "shard_rows_med", "shard_rows_max")
                   if k not in r["attrs"]]
        if missing:
            fail(f"exchange span {r['name']} lacks skew attrs "
                 f"{missing}: {r['attrs']}")

    # -- EXPLAIN ANALYZE: measured + label-consistent -----------------
    rep = pipe.last_report
    if "rows=" not in txt or "actual time=" not in txt:
        fail(f"explain(analyze=True) missing measurements:\n{txt}")
    if "skew(imb=" not in txt:
        fail(f"explain(analyze=True) missing skew columns:\n{txt}")
    if "est=" not in txt:
        fail(f"explain(analyze=True) missing pre-flight est= bytes:\n"
             f"{txt}")
    if rep.shuffle_count != cp.count("plan.shuffle"):
        fail(f"report shuffle_count {rep.shuffle_count} != "
             f"collect_phases {cp.count('plan.shuffle')}")
    if rep.shuffle_count != 2:
        fail(f"two-shuffle pipeline reported {rep.shuffle_count} "
             f"exchanges:\n{txt}")
    if rep.leaks:
        fail(f"clean pipeline reported ledger leaks: {rep.leaks}")

    # -- memory observatory: per-span HBM attrs ride the trace --------
    hbm_spans = [r for r in recs if "hbm_delta" in r["attrs"]
                 and "hbm_peak" in r["attrs"]]
    if not hbm_spans:
        fail("no span in the trace carries hbm_delta/hbm_peak attrs "
             "(pool not registered, or ledger fallback dead)")
    if max(r["attrs"]["hbm_peak"] for r in hbm_spans) <= 0:
        fail("hbm_peak is zero across the whole trace — the ledger-"
             "backed pool fallback is not accounting")

    # -- Prometheus dump: renders, counters wired ---------------------
    prom = telemetry.prometheus_text()
    bytes_lines = [l for l in prom.splitlines()
                   if l.startswith("cylon_shuffle_bytes_total ")]
    if not bytes_lines:
        fail("cylon_shuffle_bytes_total missing from Prometheus dump")
    if not float(bytes_lines[0].split()[1]) > 0:
        fail(f"cylon_shuffle_bytes_total is zero: {bytes_lines[0]}")
    if "cylon_phase_latency_ms_bucket" not in prom:
        fail("phase latency histogram missing from Prometheus dump")
    for series in ("cylon_shuffle_shard_rows_bucket",
                   "cylon_shuffle_shard_bytes_bucket",
                   "cylon_shuffle_imbalance_factor_bucket",
                   "cylon_jit_seconds_total",
                   "cylon_host_syncs_total",
                   "cylon_live_table_bytes"):
        if series not in prom:
            fail(f"{series} missing from Prometheus dump")
    n_compiles = profiler.summary()["compile"]["events"]
    if n_compiles == 0:
        fail("the jax.monitoring listener counted no compile")

    # -- flight recorder: a failing query leaves a crash dump ---------
    dump = crash_dump_smoke(ct, plan, left)

    print(f"telemetry smoke: OK — {len(recs)} spans traced, "
          f"{rep.shuffle_count} exchanges measured, "
          f"{bytes_lines[0].split()[1]} shuffle bytes counted, "
          f"{len(ex_spans)} exchange span(s) with skew attrs, "
          f"{len(hbm_spans)} span(s) with hbm attrs, "
          f"{n_compiles} kernel compile(s) profiled, "
          f"crash dump at {dump}")


def crash_dump_smoke(ct, plan, left) -> str:
    """Force a failing query under the flight recorder: inject an
    exchange failure into an explicit Shuffle plan, assert the crash
    dump is written to CYLON_FLIGHT_DIR, parses as JSON, and carries
    the in-flight plan.shuffle span, a nonzero pool watermark, the
    metrics snapshot and the ledger outstanding set."""
    from cylon_tpu.parallel import dist_ops

    flight_dir = tempfile.mkdtemp()
    os.environ["CYLON_FLIGHT_DIR"] = flight_dir

    orig = dist_ops.shuffle

    def boom(*a, **kw):
        raise RuntimeError("injected exchange failure (smoke)")

    dist_ops.shuffle = boom
    try:
        try:
            plan.scan(left).shuffle("k").execute(analyze=True)
        except RuntimeError:
            pass
        else:
            fail("injected exchange failure did not raise")
    finally:
        dist_ops.shuffle = orig
        os.environ.pop("CYLON_FLIGHT_DIR", None)

    dumps = [f for f in os.listdir(flight_dir) if f.endswith(".json")]
    if len(dumps) != 1:
        fail(f"expected exactly one crash dump in {flight_dir}, "
             f"found {dumps}")
    path = os.path.join(flight_dir, dumps[0])
    try:
        doc = json.load(open(path, encoding="utf-8"))
    except json.JSONDecodeError as e:
        fail(f"crash dump does not parse as JSON: {e}")
    for key in ("query", "error_path", "metrics", "pool",
                "ledger_outstanding", "environment"):
        if key not in doc:
            fail(f"crash dump lacks {key!r}: {sorted(doc)}")
    names = [s["name"] for s in doc["error_path"]]
    if not any(n.startswith("plan.shuffle") for n in names):
        fail(f"crash dump error path lacks the in-flight plan.shuffle "
             f"span: {names}")
    if not doc["pool"].get("bytes_in_use", 0) > 0:
        fail(f"crash dump pool watermark is zero (ledger fallback "
             f"dead): {doc['pool']}")
    if not doc["ledger_outstanding"]:
        fail("crash dump has an empty ledger outstanding set — the "
             "in-flight scan inputs should be live")
    return path


if __name__ == "__main__":
    main()
