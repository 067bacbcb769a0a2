"""Statistics-warehouse tests: EWMA store math, q-error observatory,
drift detection with plan-cache eviction, JSONL persistence with
corrupt-file quarantine, stats-informed admission (the pinned
closed-loop acceptance scenarios), and cross-process warm-start."""
import json
import os
import subprocess
import sys
import textwrap
import urllib.request

import numpy as np
import pytest

import cylon_tpu as ct
from cylon_tpu import plan, telemetry
from cylon_tpu.plan.fingerprint import fingerprint, node_fingerprint
from cylon_tpu.plan.report import (calibrate_estimates,
                                   preflight_estimates)
from cylon_tpu.resilience import inject
from cylon_tpu.service import ObsServer, plancache
from cylon_tpu.service.scheduler import QueryService
from cylon_tpu.telemetry import flight, ledger, querylog
from cylon_tpu.telemetry import stats as stats_mod
from cylon_tpu.telemetry.stats import MetricStats, StatsStore, qerror


@pytest.fixture(autouse=True)
def _clean():
    stats_mod.reset()
    yield
    inject.disarm()
    plancache.global_cache().clear()
    querylog.reset()
    stats_mod.reset()


def _tables(ctx, n=512, seed=0, key_space=None):
    rng = np.random.default_rng(seed)
    ks = key_space or max(n // 4, 1)
    left = ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, ks, n).astype(np.int32),
        "v": rng.normal(size=n).astype(np.float32)})
    right = ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, ks, n).astype(np.int32),
        "w": rng.normal(size=n).astype(np.float32)})
    return left, right


def _pipe(left, right):
    return plan.scan(left).join(plan.scan(right), on="k") \
        .groupby("lt-1", ["rt-2"], ["sum"])


def _counter(name):
    return telemetry.metrics_snapshot().get(name, 0)


def _rows(table):
    d = table.to_pydict()
    ks = sorted(d)
    return ks, sorted(zip(*(np.asarray(d[k]).tolist() for k in ks)))


# ---------------------------------------------------------------------------
# store math
# ---------------------------------------------------------------------------


def test_metric_stats_ewma_min_max_count():
    m = MetricStats()
    m.observe(100.0)
    assert (m.ewma, m.min, m.max, m.count) == (100.0, 100.0, 100.0, 1)
    m.observe(200.0)
    # alpha 0.3: 0.3*200 + 0.7*100
    assert m.ewma == pytest.approx(130.0)
    assert (m.min, m.max, m.count) == (100.0, 200.0, 2)
    rt = MetricStats.from_dict(m.to_dict())
    assert rt.to_dict() == m.to_dict()


def test_qerror_symmetric_and_guarded():
    assert qerror(200, 100) == pytest.approx(2.0)
    assert qerror(100, 200) == pytest.approx(2.0)
    assert qerror(100, 100) == pytest.approx(1.0)
    assert qerror(0, 100) is None
    assert qerror(None, 100) is None
    assert qerror(100, None) is None


def test_effective_bytes_gating_and_soundness(monkeypatch):
    monkeypatch.setenv("CYLON_STATS_MIN_OBS", "3")
    monkeypatch.setenv("CYLON_STATS_SAFETY", "1.5")
    s = StatsStore()

    def feed(n_obs, v=1000.0):
        for _ in range(n_obs):
            s._observe_node("pfp", "nfp", "join",
                            {"bytes": v, "rows": 10},
                            ("bytes", "rows"), None, 0.0)

    feed(2)
    # below the observation floor: the static bound rules
    assert s.effective_bytes("nfp", 50_000) == (50_000, "static")
    feed(1)
    eff, src = s.effective_bytes("nfp", 50_000)
    assert src == "measured"
    assert eff == int(1000.0 * 1.5) + 1
    # SOUNDNESS: never above the static bound, even when the measured
    # EWMA exceeds it (joins can out-multiply the width x row bound)
    eff, src = s.effective_bytes("nfp", 800)
    assert src == "measured" and eff == 800
    # unknown fingerprints and missing statics pass through untouched
    assert s.effective_bytes("zzz", 123) == (123, "static")
    assert s.effective_bytes(None, 123) == (123, "static")
    assert s.effective_bytes("nfp", None) == (None, "static")


def test_node_fingerprint_capacity_blind_and_shape_sharp(dist_ctx):
    l0, r0 = _tables(dist_ctx, n=256, seed=1)
    l1, r1 = _tables(dist_ctx, n=2048, seed=2)

    def join_node(p):
        root, _ = p.optimized()
        return next(n for n in plan.ir.walk(root) if n.kind == "join")

    with plancache.disabled():
        a = node_fingerprint(join_node(_pipe(l0, r0)), 4)
        b = node_fingerprint(join_node(_pipe(l1, r1)), 4)
        # capacity-blind: a growing table keeps its fingerprint — the
        # drift detector, not a key change, notices the shift
        assert a == b
        # shape-sharp: a different filter literal reshapes the subtree
        c = node_fingerprint(join_node(
            plan.scan(l0).filter(plan.col("v") > 1.0)
            .join(plan.scan(r0), on="k")), 4)
        assert c != a
        # ...and the node key space never collides with the plan one
        assert fingerprint(join_node(_pipe(l0, r0)), 4) != a


# ---------------------------------------------------------------------------
# the feed: executed queries observe, failed ones do not
# ---------------------------------------------------------------------------


def test_execute_feeds_warehouse_and_qerror(dist_ctx):
    left, right = _tables(dist_ctx, n=1024, seed=3)
    q0 = {k: v.get("count", 0)
          for k, v in telemetry.metrics_snapshot().items()
          if k.startswith("cylon_estimate_qerror")
          and isinstance(v, dict)}
    _pipe(left, right).execute()
    st = stats_mod.state()
    assert st["plan_count"] == 1
    # join + groupby sub-fingerprints observed (folded shuffles never
    # execute standalone, so they contribute no node entries), plus
    # the join's algorithm-invariant DECISION entry carrying both
    # sides' measured input sizes (the broadcast rewrite's evidence)
    assert st["node_count"] == 3
    kinds = {e["kind"] for e in st["nodes"]}
    assert kinds == {"join", "groupby", "join_input"}
    for e in st["nodes"]:
        if e["kind"] == "join_input":
            assert e["metrics"]["left_bytes"]["count"] == 1
            assert e["metrics"]["left_bytes"]["ewma"] > 0
            assert e["metrics"]["right_bytes"]["count"] == 1
            continue
        assert e["metrics"]["bytes"]["count"] == 1
        assert e["metrics"]["bytes"]["ewma"] > 0
        assert e["metrics"]["rows"]["count"] == 1
    pe = st["plans"][0]
    assert pe["metrics"]["exec_ms"]["count"] == 1
    assert pe["metrics"]["shuffle_bytes"]["ewma"] > 0
    # q-error observed per node kind
    snap = telemetry.metrics_snapshot()
    for kind in ("join", "groupby"):
        key = f'cylon_estimate_qerror{{kind="{kind}"}}'
        assert snap[key]["count"] == q0.get(key, 0) + 1
    # the digest carries the warehouse's join keys
    d = querylog.recent()[-1]
    assert d["plan_fp"] == st["plans"][0]["fp"]
    assert "est_bytes" in d and "est_source" in d


def test_failed_query_observes_nothing(dist_ctx):
    left, right = _tables(dist_ctx, n=1024, seed=4)
    inject.arm("exchange:1+:transient")
    try:
        with pytest.raises(ct.CylonTransientError):
            _pipe(left, right).execute()
    finally:
        inject.disarm()
    assert stats_mod.state()["plan_count"] == 0
    assert stats_mod.state()["node_count"] == 0


def test_explicit_shuffle_node_observes(dist_ctx):
    left, _right = _tables(dist_ctx, n=1024, seed=5)
    plan.scan(left).shuffle(["v"]).execute()
    st = stats_mod.state()
    assert any(e["kind"] == "shuffle" and
               e["metrics"]["bytes"]["count"] == 1
               for e in st["nodes"])


# ---------------------------------------------------------------------------
# calibration: EXPLAIN ANALYZE column + report plumbing
# ---------------------------------------------------------------------------


def test_calibrated_column_in_explain_analyze(dist_ctx, monkeypatch):
    monkeypatch.setenv("CYLON_STATS_MIN_OBS", "2")
    left, right = _tables(dist_ctx, n=1024, seed=6)
    p0 = _pipe(left, right)
    txt_cold = p0.explain(analyze=True)
    assert "calibrated=" not in txt_cold      # nothing qualified yet
    _pipe(left, right).execute()
    p = _pipe(left, right)
    txt = p.explain(analyze=True)
    assert "calibrated=" in txt
    doc = p.last_report.to_dict()

    def walk(m):
        yield m
        for c in m.get("children", []):
            yield from walk(c)

    join = next(m for m in walk(doc["plan"]) if m["kind"] == "join")
    assert join["est_source"] == "measured"
    assert join["calibrated_bytes"] is not None
    assert join["calibrated_bytes"] <= join["est_bytes"]


def test_calibrate_estimates_is_idempotent(dist_ctx, monkeypatch):
    monkeypatch.setenv("CYLON_STATS_MIN_OBS", "1")
    left, right = _tables(dist_ctx, n=1024, seed=7)
    _pipe(left, right).execute()
    root, _ = _pipe(left, right).optimized()
    est = preflight_estimates(root)
    calibrate_estimates(root, est, 4)
    first = {k: dict(v) for k, v in est.items()}
    calibrate_estimates(root, est, 4)    # second pass: no-op
    assert {k: dict(v) for k, v in est.items()} == first
    join = next(n for n in plan.ir.walk(root) if n.kind == "join")
    e = est[id(join)]
    assert e["est_source"] == "measured"
    assert e["calibrated_bytes"] <= e["bytes"]
    assert e["node_fp"] == node_fingerprint(join, 4)


def test_a_row_keeping_node_takes_its_measured_childs_estimate(
        local_ctx, monkeypatch):
    """A Compute / Sort over a join the warehouse has watched is the
    join's rows at its own width: its estimate follows the join's, never
    above its static bound, and `plan.preflight` stops warning of it."""
    from cylon_tpu.plan import col

    monkeypatch.setenv("CYLON_STATS_MIN_OBS", "1")
    left, right = _lowmatch_tables(local_ctx)

    def pipe():
        return (plan.scan(left).join(plan.scan(right), on="k")
                .with_columns({"s": col("lt-0") + col("rt-2")})
                .sort("s"))

    root, _ = pipe().optimized()
    cold = calibrate_estimates(root, preflight_estimates(root), 1)
    assert all("calibrated_bytes" not in cold[id(n)]
               for n in plan.ir.walk(root))
    pipe().execute()
    root, _ = pipe().optimized()
    est = calibrate_estimates(root, preflight_estimates(root), 1)
    by_kind = {n.kind: est[id(n)] for n in plan.ir.walk(root)}
    join, comp, top = by_kind["join"], by_kind["compute"], by_kind["sort"]
    assert join["calibrated_bytes"] < join["bytes"] / 16
    for e in (comp, top):
        assert e["est_source"] == "measured"
        assert join["calibrated_bytes"] <= e["calibrated_bytes"] \
            < e["bytes"] / 16
    assert "calibrated_bytes" not in by_kind["scan"]
    again = {k: dict(v) for k, v in est.items()}
    calibrate_estimates(root, est, 1)
    assert {k: dict(v) for k, v in est.items()} == again


# ---------------------------------------------------------------------------
# the pinned closed loop: shed/degrade on first sight, measured
# admission on repeat — sound in both directions
# ---------------------------------------------------------------------------


def _lowmatch_tables(ctx, n=8192, overlap=64, seed=8):
    """A join whose static estimate is a planning disaster: near-
    disjoint key ranges, so the width x row bound (left+right rows)
    over-estimates the measured output by ~30x — the classic
    cardinality-estimation q-error the warehouse exists to retire."""
    rng = np.random.default_rng(seed)
    left = ct.Table.from_pydict(ctx, {
        "k": np.arange(n, dtype=np.int32),
        "v": rng.normal(size=n).astype(np.float32)})
    right = ct.Table.from_pydict(ctx, {
        "k": (np.arange(n, dtype=np.int32) + n - overlap),
        "w": rng.normal(size=n).astype(np.float32)})
    return left, right


def test_closed_loop_shed_first_measured_admit_on_repeat(
        local_ctx, monkeypatch):
    """The acceptance pin, world=1 (no folded-shuffle markers, so the
    worst allocating node is the join the warehouse calibrates):

    * under a clamped budget, a FIRST-SIGHT query (no measurements)
      sheds on its static estimate;
    * the same-shaped query, learned while unclamped, is ADMITTED
      under the same clamp with est_source=measured in the admission
      ring AND the querylog digest;
    * soundness both ways: the measured estimate never exceeds the
      static bound, and a clamp below even the measured estimate
      still sheds — with measured provenance."""
    monkeypatch.setenv("CYLON_STATS_MIN_OBS", "2")
    left, right = _lowmatch_tables(local_ctx)
    pipe = lambda: plan.scan(left).join(plan.scan(right), on="k")  # noqa: E731
    # learn the shape unclamped
    for _ in range(2):
        pipe().execute()
    p = pipe()
    p.execute(analyze=True)
    rep = p.last_report.to_dict()

    def walk(m):
        yield m
        for c in m.get("children", []):
            yield from walk(c)

    join = next(m for m in walk(rep["plan"]) if m["kind"] == "join")
    static_b, meas_b = join["est_bytes"], join["calibrated_bytes"]
    assert meas_b is not None and meas_b < static_b / 16, \
        f"workload not selective enough: {meas_b} vs {static_b}"
    clamp = meas_b * 2
    assert static_b / clamp > 8          # static estimate MUST shed
    inject.arm(f"pool:{clamp}:oom")
    try:
        # first sight under the clamp: a fresh SHAPE (identity project
        # changes the structural fingerprints, not the work) has only
        # its static estimate — shed before any device work
        with pytest.raises(ct.CylonResourceExhausted):
            plan.scan(left).project([0, 1]) \
                .join(plan.scan(right), on="k").execute()
        shed = [a for a in flight.admissions()
                if a.get("action") == "shed"][-1]
        assert shed["est_source"] == "static"
        # the learned shape under the SAME clamp: admitted on its
        # measured EWMA
        out = pipe().execute()
        assert out.capacity > 0
        adm = [a for a in flight.admissions()
               if a.get("action") == "admit"][-1]
        assert adm["est_source"] == "measured"
        assert adm["est_bytes"] <= static_b
        d = querylog.recent()[-1]
        assert d["admission"] == "admit"
        assert d["est_source"] == "measured"
        assert d["est_bytes"] == adm["est_bytes"]
    finally:
        inject.disarm()
    # soundness: a budget below even the measured estimate still
    # sheds — measured statistics relax false alarms, never real ones
    inject.arm(f"pool:{max(meas_b // 32, 64)}:oom")
    try:
        with pytest.raises(ct.CylonResourceExhausted):
            pipe().execute()
        shed = [a for a in flight.admissions()
                if a.get("action") == "shed"][-1]
        assert shed["est_source"] == "measured"
    finally:
        inject.disarm()


def test_closed_loop_degrade_first_undegraded_repeat(
        local_ctx, monkeypatch):
    """The degrade arm of the pin: a clamp that forces the blocked/
    chunked join on first execution is lifted to a clean admit on
    repeat — the measured output fit all along — with bit-identical
    results throughout."""
    monkeypatch.setenv("CYLON_STATS_MIN_OBS", "1")
    left, right = _lowmatch_tables(local_ctx, n=4096, seed=9)
    pipe = lambda: plan.scan(left).join(plan.scan(right), on="k")  # noqa: E731
    p0 = pipe()
    clean = p0.execute(analyze=True)
    static_b = next(
        m["est_bytes"] for m in [p0.last_report.root.to_dict()]
        if m["kind"] == "join")
    stats_mod.reset()                     # forget: first sight again
    clamp = static_b // 2                 # 2x over static -> degrade
    inject.arm(f"pool:{clamp}:oom")
    try:
        p = pipe()
        degraded = p.execute(analyze=True)
        rep1 = p.last_report
        assert rep1.admission["action"] == "degrade"
        assert rep1.admission["est_source"] == "static"
        assert _rows(degraded) == _rows(clean)
        # repeat: one successful observation qualified the fingerprint
        p2 = pipe()
        repeat = p2.execute(analyze=True)
        rep2 = p2.last_report
        assert rep2.admission["action"] == "admit"
        assert rep2.admission["est_source"] == "measured"
        assert _rows(repeat) == _rows(clean)
    finally:
        inject.disarm()


# ---------------------------------------------------------------------------
# drift: detection, plan-cache eviction, fallback to static
# ---------------------------------------------------------------------------


def test_drift_fires_evicts_and_reverts_to_static(
        dist_ctx, monkeypatch):
    monkeypatch.setenv("CYLON_STATS_MIN_OBS", "2")
    monkeypatch.setenv("CYLON_STATS_DRIFT_FACTOR", "4.0")
    left, right = _tables(dist_ctx, n=1024, seed=10, key_space=256)
    for _ in range(2):
        _pipe(left, right).execute()
    fp = _pipe(left, right).plan_fingerprint()
    root, _ = _pipe(left, right).optimized()
    join_fp = node_fingerprint(
        next(n for n in plan.ir.walk(root) if n.kind == "join"), 4)
    # qualified before the drift
    assert stats_mod.effective_bytes(join_fp, 1 << 40)[1] == "measured"
    d0 = _counter("cylon_stats_drift_total")
    m0 = _counter("cylon_plan_cache_misses_total")
    # same shape, 10x the rows: same fingerprints, wildly different
    # measured bytes
    L, R = _tables(dist_ctx, n=10240, seed=11, key_space=256)
    assert _pipe(L, R).plan_fingerprint() == fp
    big = _pipe(L, R).execute()
    assert _counter("cylon_stats_drift_total") > d0
    ev = [a for a in flight.admissions()
          if a.get("action") == "stats_drift"]
    assert ev and ev[-1]["plan_fp"] == fp
    assert stats_mod.recent_drift()[-1]["factor"] > 4.0
    # the learned entry reset below the observation floor: admission
    # falls back to the static bound until the new regime re-learns
    # (checked BEFORE any further execution — every successful query
    # observes, and two observations of the new regime re-qualify it,
    # which is the re-learning working, not a bug)
    assert stats_mod.effective_bytes(join_fp, 1 << 40)[1] == "static"
    # the cached plan template was evicted: the next optimize of this
    # shape is a MISS
    _pipe(left, right).optimized()
    assert _counter("cylon_plan_cache_misses_total") == m0 + 1
    # drift never perturbs data: the drifted run's result bit-matches
    # an uncached fresh execution
    with plancache.disabled():
        baseline = _pipe(L, R).execute()
    assert _rows(big) == _rows(baseline)


# ---------------------------------------------------------------------------
# persistence: round trip, quarantine, warm start
# ---------------------------------------------------------------------------


def _seed_store(s, n_obs=3):
    for i in range(n_obs):
        s._observe_node("pfp", "nfp", "join",
                        {"bytes": 1000.0 + i, "rows": 10 + i},
                        ("bytes", "rows"), 2000.0, float(i))
    return s


def test_persistence_round_trip(tmp_path):
    s = _seed_store(StatsStore())
    path = str(tmp_path / "stats.jsonl")
    assert s.save(path) == path
    s2 = StatsStore()
    assert s2.load(path) == 1
    assert s2.state()["nodes"] == s.state()["nodes"]
    assert s2.effective_bytes("nfp", 1 << 30) == \
        s.effective_bytes("nfp", 1 << 30)


def test_save_without_path_is_noop(monkeypatch):
    monkeypatch.delenv("CYLON_STATS_PATH", raising=False)
    assert StatsStore().save() is None
    assert StatsStore().load() == 0


@pytest.mark.parametrize("corruption", [
    "garbage{{{",                                     # unparseable
    '{"rec": "header", "v": 999}',                    # bad version
    '{"rec": "nope"}',                                # bad kind
    "123",                                            # valid JSON,
    #                                                   not an object
])
def test_corrupt_snapshot_quarantined(tmp_path, corruption):
    path = str(tmp_path / "stats.jsonl")
    with open(path, "w") as f:
        f.write(corruption + "\n")
    q0 = _counter("cylon_stats_quarantine_total")
    s = StatsStore()
    assert s.load(path) == 0              # never raises, never blocks
    assert s.state()["node_count"] == 0
    assert os.path.exists(path + ".quarantine")
    assert not os.path.exists(path)
    assert _counter("cylon_stats_quarantine_total") == q0 + 1
    ev = [a for a in flight.admissions()
          if a.get("action") == "stats_quarantine"][-1]
    assert "CylonDataError" in ev["error"]


def test_truncated_entry_line_quarantined(tmp_path):
    s = _seed_store(StatsStore())
    path = str(tmp_path / "stats.jsonl")
    s.save(path)
    raw = open(path).read()
    with open(path, "w") as f:
        f.write(raw[:-20])                # torn mid-line
    s2 = StatsStore()
    assert s2.load(path) == 0
    assert os.path.exists(path + ".quarantine")


def test_snapshot_survives_tiny_span_log_bound(tmp_path, monkeypatch):
    """A snapshot is rotated BEFORE writing and written unbounded: a
    small CYLON_SPAN_LOG_MAX_BYTES (the streaming sinks' cap) must
    never split a snapshot mid-write into a truncated — and therefore
    quarantined — file. Re-saving keeps the previous generation."""
    monkeypatch.setenv("CYLON_SPAN_LOG_MAX_BYTES", "64")
    s = _seed_store(StatsStore())
    path = str(tmp_path / "stats.jsonl")
    s.save(path)
    s.save(path)                          # second snapshot rotates
    assert os.path.exists(path + ".1")    # previous generation kept
    s2 = StatsStore()
    assert s2.load(path) == 1             # intact despite the 64 B cap
    assert not os.path.exists(path + ".quarantine")


def test_load_never_clobbers_live_entries(tmp_path):
    s = _seed_store(StatsStore())
    path = str(tmp_path / "stats.jsonl")
    s.save(path)
    live = StatsStore()
    live._observe_node("pfp", "nfp", "join",
                       {"bytes": 7777.0, "rows": 1},
                       ("bytes", "rows"), None, 0.0)
    live.load(path)
    # the in-process measurement wins; the snapshot fills gaps only
    e = next(e for e in live.state()["nodes"] if e["fp"] == "nfp")
    assert e["metrics"]["bytes"]["ewma"] == 7777.0


def test_never_started_close_preserves_snapshot(tmp_path, monkeypatch):
    """A service closed without ever starting never start()-loaded the
    snapshot, so its close() must not rotate a learned warm-start file
    aside and replace it with a near-empty store (and a double-close
    must not rotate again)."""
    path = str(tmp_path / "stats.jsonl")
    _seed_store(stats_mod.STORE)
    stats_mod.save(path)
    learned = open(path).read()
    stats_mod.reset()
    monkeypatch.setenv("CYLON_STATS_PATH", path)
    svc = QueryService(name="never-started", start=False)
    svc.close()
    svc.close()
    assert open(path).read() == learned
    assert not os.path.exists(path + ".1")
    # a STARTED service still saves (merged through start()'s load)
    svc2 = QueryService(name="started")
    svc2.close()
    s2 = StatsStore()
    assert s2.load(path) == 1             # learned entry survived


def test_cross_process_warm_start(dist_ctx, tmp_path, monkeypatch):
    """The replica warm-start pin: a fresh subprocess (hash seed
    varied) loads the snapshot through QueryService.start(), joins on
    the IDENTICAL fingerprints, and admits its very first query with
    est_source=measured."""
    monkeypatch.setenv("CYLON_STATS_MIN_OBS", "2")
    left, right = _tables(dist_ctx, n=1024, seed=12, key_space=256)
    for _ in range(3):
        _pipe(left, right).execute()
    here_fp = _pipe(left, right).plan_fingerprint()
    path = str(tmp_path / "stats.jsonl")
    assert stats_mod.save(path) == path
    prog = textwrap.dedent("""
        import json
        import numpy as np
        import cylon_tpu as ct
        from cylon_tpu import plan
        from cylon_tpu.service import QueryService
        from cylon_tpu.telemetry import querylog
        ctx = ct.CylonContext.InitDistributed(
            ct.TPUConfig(world_size=4))
        rng = np.random.default_rng(777)   # different CONTENT
        n = 1024
        left = ct.Table.from_pydict(ctx, {
            "k": rng.integers(0, 256, n).astype(np.int32),
            "v": rng.normal(size=n).astype(np.float32)})
        right = ct.Table.from_pydict(ctx, {
            "k": rng.integers(0, 256, n).astype(np.int32),
            "w": rng.normal(size=n).astype(np.float32)})
        p = plan.scan(left).join(plan.scan(right), on="k") \\
            .groupby("lt-1", ["rt-2"], ["sum"])
        svc = QueryService(name="replica")   # start() loads the stats
        tk = svc.submit(p, tenant="warm")
        svc.drain(timeout=600)
        tk.result(timeout=60)
        svc.close()
        d = querylog.recent()[-1]
        print(json.dumps({"fp": d["plan_fp"],
                          "est_source": d["est_source"],
                          "outcome": d["outcome"]}))
    """)
    for seed in ("0", "1"):
        env = dict(os.environ, PYTHONHASHSEED=seed,
                   JAX_PLATFORMS="cpu", CYLON_STATS_PATH=path,
                   CYLON_STATS_MIN_OBS="2")
        env["XLA_FLAGS"] = env.get("XLA_FLAGS", "") + \
            " --xla_force_host_platform_device_count=8"
        r = subprocess.run([sys.executable, "-c", prog],
                           capture_output=True, text=True, timeout=600,
                           env=env)
        assert r.returncode == 0, r.stderr
        doc = json.loads(r.stdout.strip().splitlines()[-1])
        # identical fingerprint space across processes AND hash seeds,
        # and measured-calibrated admission from query 1
        assert doc["fp"] == here_fp
        assert doc["outcome"] == "ok"
        assert doc["est_source"] == "measured"


# ---------------------------------------------------------------------------
# /stats route + offline joinability
# ---------------------------------------------------------------------------


def test_stats_route_served(dist_ctx):
    left, right = _tables(dist_ctx, n=1024, seed=13)
    _pipe(left, right).execute()
    obs = ObsServer(service=None, port=0).start()
    try:
        with urllib.request.urlopen(obs.url("/stats"), timeout=30) as r:
            assert r.status == 200
            doc = json.loads(r.read().decode("utf-8"))
    finally:
        obs.close()
    assert doc["plan_count"] >= 1
    assert {e["kind"] for e in doc["nodes"]} >= {"join", "groupby"}
    assert "join" in doc["qerror"] and "p95" in doc["qerror"]["join"]
    assert doc["config"]["min_obs"] >= 1
    assert doc["drift_events"] == []


def test_digest_jsonl_joinable_offline(dist_ctx, tmp_path):
    """Satellite pin: measured-vs-estimated is joinable from the
    querylog JSONL alone — est_bytes, est_source AND the admission
    decision ride every line."""
    qlog = str(tmp_path / "q.jsonl")
    querylog.enable(qlog)
    try:
        left, right = _tables(dist_ctx, n=1024, seed=14)
        _pipe(left, right).execute()
    finally:
        querylog.disable()
    line = json.loads(open(qlog).read().splitlines()[-1])
    for field in ("est_bytes", "est_source", "admission", "plan_fp",
                  "shuffle_bytes", "exec_ms"):
        assert field in line, field
    assert line["plan_fp"] is not None


def test_zero_leaks_through_the_warehouse(dist_ctx):
    import gc

    left, right = _tables(dist_ctx, n=1024, seed=15)
    held = ledger.leak_count()
    for _ in range(3):
        _pipe(left, right).execute()
    gc.collect()
    assert ledger.leak_count() == held
