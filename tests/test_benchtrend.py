"""scripts/benchtrend.py unit tests over synthetic BENCH trajectories:
metric extraction across heterogeneous artifact shapes, same-backend
reference selection, the regression predicate (incl. an injected >20%
drop), table rendering, and the CLI exit codes check.sh gates on."""
import importlib.util
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
SCRIPT = os.path.join(REPO, "scripts", "benchtrend.py")

spec = importlib.util.spec_from_file_location("benchtrend", SCRIPT)
benchtrend = importlib.util.module_from_spec(spec)
spec.loader.exec_module(benchtrend)


def _artifact(value, backend="tpu", suite=None, shuffle_gbps=None,
              local=None, signatures=None):
    detail = {"backend": backend}
    if suite is not None:
        detail["suite"] = suite
    if shuffle_gbps is not None:
        detail["shuffle_gbps"] = shuffle_gbps
    if local is not None:
        detail["local_inner_join"] = {"rows_per_s_per_chip": local}
    if signatures is not None:
        detail["distinct_kernel_signatures"] = signatures
    return {"metric": "dist_inner_join_rows_per_sec_per_chip",
            "value": value, "unit": "rows/s/chip", "detail": detail}


def _write_rounds(tmp_path, parsed_by_round):
    for n, parsed in parsed_by_round.items():
        path = tmp_path / f"BENCH_r{n:02d}.json"
        path.write_text(json.dumps({"n": n, "rc": 0 if parsed else 1,
                                    "parsed": parsed}))
    return str(tmp_path)


def test_flatten_metrics_shapes():
    flat = benchtrend.flatten_metrics(_artifact(
        1e6, suite={"groupby_agg": {"rows_per_s_per_chip": 5e5},
                    "shuffle_wide": {"gbps_per_chip": 1.5},
                    "plan_pipeline": {"speedup": 1.4},
                    "broken": {"error": "ValueError: x"}},
        shuffle_gbps=0.4, local=2e6))
    assert flat["dist_inner_join.rows_per_s"] == 1e6
    assert flat["groupby_agg.rows_per_s"] == 5e5
    assert flat["shuffle_wide.gbps"] == 1.5
    assert flat["plan_pipeline.speedup"] == 1.4
    assert flat["shuffle.gbps"] == 0.4
    assert flat["local_inner_join.rows_per_s"] == 2e6
    assert not any(k.startswith("broken") for k in flat)
    assert benchtrend.flatten_metrics(None) == {}
    assert benchtrend.flatten_metrics({"value": 0}) == {}
    flat = benchtrend.flatten_metrics(_artifact(1e6, signatures=37))
    assert flat["compile.distinct_kernel_signatures"] == 37


def test_shuffle_pipeline_metrics_flatten_and_gate_lower(tmp_path):
    """The overlapped-exchange metrics flatten (wall + launch count)
    and gate LOWER_IS_BETTER: a round that halves the exchange wall
    passes, one that rebloats the launch count past the threshold
    fails."""
    flat = benchtrend.flatten_metrics(_artifact(
        1e6, suite={"shuffle_pipeline": {"exchange_wall_s": 0.8,
                                         "partition_wall_s": 0.3,
                                         "collective_launches": 4,
                                         "gbps_per_chip": 2.0}}))
    assert flat["shuffle_pipeline.exchange_wall_s"] == 0.8
    assert flat["shuffle_pipeline.partition_wall_s"] == 0.3
    assert flat["shuffle_pipeline.collective_launches"] == 4
    assert flat["shuffle_pipeline.gbps"] == 2.0
    assert "shuffle_pipeline.exchange_wall_s" in \
        benchtrend.LOWER_IS_BETTER
    assert "shuffle_pipeline.partition_wall_s" in \
        benchtrend.LOWER_IS_BETTER
    assert "shuffle_pipeline.collective_launches" in \
        benchtrend.LOWER_IS_BETTER
    win = _write_rounds(tmp_path, {
        1: _artifact(1e6, suite={"shuffle_pipeline": {
            "exchange_wall_s": 0.8, "partition_wall_s": 0.4,
            "collective_launches": 8}}),
        2: _artifact(1e6, suite={"shuffle_pipeline": {
            "exchange_wall_s": 0.4, "partition_wall_s": 0.1,
            "collective_launches": 4}})})
    assert benchtrend.find_regressions(benchtrend.load_rounds(win)) == []
    lose = _write_rounds(tmp_path, {
        1: _artifact(1e6, suite={"shuffle_pipeline": {
            "exchange_wall_s": 0.4, "partition_wall_s": 0.1,
            "collective_launches": 4}}),
        2: _artifact(1e6, suite={"shuffle_pipeline": {
            "exchange_wall_s": 0.8, "partition_wall_s": 0.4,
            "collective_launches": 8}})})
    regs = {m for m, *_ in benchtrend.find_regressions(
        benchtrend.load_rounds(lose))}
    assert "shuffle_pipeline.exchange_wall_s" in regs
    assert "shuffle_pipeline.partition_wall_s" in regs
    assert "shuffle_pipeline.collective_launches" in regs


def test_adaptive_join_metrics_flatten_and_gate(tmp_path):
    """The adaptive-join metrics flatten — broadcast_speedup judged by
    drop (higher is better), salted_imbalance LOWER_IS_BETTER (a rise
    means hot-key salting got worse at bounding the max shard)."""
    flat = benchtrend.flatten_metrics(_artifact(
        1e6, suite={"adaptive_join": {"broadcast_speedup": 2.5,
                                      "salted_imbalance": 1.1}}))
    assert flat["adaptive_join.broadcast_speedup"] == 2.5
    assert flat["adaptive_join.salted_imbalance"] == 1.1
    assert "adaptive_join.salted_imbalance" in \
        benchtrend.LOWER_IS_BETTER
    assert "adaptive_join.broadcast_speedup" not in \
        benchtrend.LOWER_IS_BETTER
    lose = _write_rounds(tmp_path, {
        1: _artifact(1e6, suite={"adaptive_join": {
            "broadcast_speedup": 2.5, "salted_imbalance": 1.1}}),
        2: _artifact(1e6, suite={"adaptive_join": {
            "broadcast_speedup": 1.2, "salted_imbalance": 2.4}})})
    regs = {m for m, *_ in benchtrend.find_regressions(
        benchtrend.load_rounds(lose))}
    assert "adaptive_join.broadcast_speedup" in regs
    assert "adaptive_join.salted_imbalance" in regs


def test_signature_count_is_judged_lower_is_better(tmp_path):
    """The recompile-cardinality metric inverts the gate: a round that
    HALVES distinct signatures (the bucketing win) passes, a round
    that rebloats them past the threshold fails."""
    win = _write_rounds(tmp_path, {
        1: _artifact(1e6, signatures=40),
        2: _artifact(1e6, signatures=18)})
    assert benchtrend.find_regressions(benchtrend.load_rounds(win)) == []
    bloat = _write_rounds(tmp_path, {
        1: _artifact(1e6, signatures=18),
        2: _artifact(1e6, signatures=40)})
    regs = benchtrend.find_regressions(benchtrend.load_rounds(bloat))
    assert [r[0] for r in regs] == ["compile.distinct_kernel_signatures"]


def test_no_regression_on_stable_trajectory(tmp_path):
    d = _write_rounds(tmp_path, {
        1: _artifact(1.00e6), 2: _artifact(1.05e6), 3: _artifact(0.95e6)})
    rounds = benchtrend.load_rounds(d)
    assert [r["round"] for r in rounds] == [1, 2, 3]
    # r03 vs r02: -9.5%, below the 20% threshold
    assert benchtrend.find_regressions(rounds) == []
    table = benchtrend.render_table(rounds)
    assert "dist_inner_join.rows_per_s" in table
    assert "-9.5%" in table


def test_injected_regression_detected(tmp_path):
    d = _write_rounds(tmp_path, {
        1: _artifact(1e6, suite={"groupby_agg":
                                 {"rows_per_s_per_chip": 4e5}}),
        2: _artifact(1e6, suite={"groupby_agg":
                                 {"rows_per_s_per_chip": 3e5}})})
    rounds = benchtrend.load_rounds(d)
    regs = benchtrend.find_regressions(rounds, threshold=0.2)
    assert [r[0] for r in regs] == ["groupby_agg.rows_per_s"]
    metric, new_v, ref_v, drop = regs[0]
    assert new_v == 3e5 and ref_v == 4e5
    assert abs(drop - 0.25) < 1e-9
    # a looser threshold lets the same trajectory pass
    assert benchtrend.find_regressions(rounds, threshold=0.3) == []


def test_backend_change_is_not_a_regression(tmp_path):
    """An outage round (cpu-fallback) must never be judged against a
    TPU round — that 100x 'drop' is the outage, not a code change."""
    d = _write_rounds(tmp_path, {
        1: _artifact(60e6, backend="tpu"),
        2: _artifact(1e5, backend="cpu-fallback")})
    rounds = benchtrend.load_rounds(d)
    assert benchtrend.reference_round(rounds) is None
    assert benchtrend.find_regressions(rounds) == []
    assert "no earlier same-backend round" in \
        benchtrend.render_table(rounds)


def test_reference_skips_unparsed_and_other_backends(tmp_path):
    d = _write_rounds(tmp_path, {
        1: _artifact(50e6, backend="tpu"),
        2: _artifact(2e5, backend="cpu-fallback"),
        3: None,                                  # rc=1, parsed null
        4: _artifact(40e6, backend="tpu")})
    rounds = benchtrend.load_rounds(d)
    latest = benchtrend.latest_parsed(rounds)
    ref = benchtrend.reference_round(rounds)
    assert latest["round"] == 4 and ref["round"] == 1
    regs = benchtrend.find_regressions(rounds)  # 50M -> 40M = -20%, not >
    assert regs == []
    table = benchtrend.render_table(rounds)
    assert "r03 has no parsed artifact" in table


def test_new_and_removed_metrics_never_fail(tmp_path):
    d = _write_rounds(tmp_path, {
        1: _artifact(1e6, suite={"old_only":
                                 {"rows_per_s_per_chip": 1e5}}),
        2: _artifact(1e6, suite={"new_only":
                                 {"rows_per_s_per_chip": 1e5}})})
    rounds = benchtrend.load_rounds(d)
    assert benchtrend.find_regressions(rounds) == []


def test_cli_check_exit_codes(tmp_path):
    d = _write_rounds(tmp_path, {
        1: _artifact(1e6), 2: _artifact(0.5e6)})  # -50%: regression
    bad = subprocess.run(
        [sys.executable, SCRIPT, "--dir", d, "--check"],
        capture_output=True, text=True, timeout=60)
    assert bad.returncode == 1, bad.stdout + bad.stderr
    assert "REGRESSION dist_inner_join.rows_per_s" in bad.stderr
    ok = subprocess.run(
        [sys.executable, SCRIPT, "--dir", d, "--check",
         "--threshold", "0.6"],
        capture_output=True, text=True, timeout=60)
    assert ok.returncode == 0, ok.stdout + ok.stderr
    js = subprocess.run(
        [sys.executable, SCRIPT, "--dir", d, "--json"],
        capture_output=True, text=True, timeout=60)
    doc = json.loads(js.stdout)
    assert doc["regressions"][0]["metric"] == "dist_inner_join.rows_per_s"
    assert [r["round"] for r in doc["rounds"]] == [1, 2]


def test_empty_trajectory_is_no_baseline_not_a_crash(tmp_path):
    """A fresh repo / an external trend state of "[]": load_rounds must
    tolerate non-dict JSON and --check must exit 0 with an explicit
    'no baseline yet' note instead of crashing."""
    # non-dict JSON documents (the observed external state) and garbage
    (tmp_path / "BENCH_r01.json").write_text("[]")
    (tmp_path / "BENCH_r02.json").write_text("not json at all {{{")
    rounds = benchtrend.load_rounds(str(tmp_path))
    assert [r["round"] for r in rounds] == [1, 2]
    assert all(r["parsed"] is None for r in rounds)
    assert benchtrend.latest_parsed(rounds) is None
    assert benchtrend.find_regressions(rounds) == []
    r = subprocess.run(
        [sys.executable, SCRIPT, "--dir", str(tmp_path), "--check"],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "no baseline yet" in r.stdout
    # the per-round listing survives: an operator can still see WHICH
    # rounds stopped parsing
    assert "r01" in r.stdout and "r02" in r.stdout
    js = subprocess.run(
        [sys.executable, SCRIPT, "--dir", str(tmp_path), "--json"],
        capture_output=True, text=True, timeout=60)
    doc = json.loads(js.stdout)
    assert doc["note"] == "no baseline yet"
    assert [r["round"] for r in doc["rounds"]] == [1, 2]


def test_empty_directory_check_passes(tmp_path):
    """No BENCH artifacts at all — the gate passes vacuously, in both
    text and JSON form."""
    r = subprocess.run(
        [sys.executable, SCRIPT, "--dir", str(tmp_path), "--check"],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "no baseline yet" in r.stdout
    js = subprocess.run(
        [sys.executable, SCRIPT, "--dir", str(tmp_path), "--json"],
        capture_output=True, text=True, timeout=60)
    doc = json.loads(js.stdout)
    assert doc == {"rounds": [], "threshold": 0.2, "regressions": [],
                   "note": "no baseline yet"}


def test_cli_over_a_five_round_trajectory(tmp_path):
    """A trajectory shaped like the repo's first five rounds — three
    TPU rounds, one lost (rc=1, nothing parsed), one on another backend
    with no same-backend reference — renders and passes the gate."""
    d = _write_rounds(tmp_path, {
        1: _artifact(1.0e7), 2: _artifact(1.2e7), 3: _artifact(1.3e7),
        4: None, 5: _artifact(2.0e5, backend="cpu-fallback")})
    r = subprocess.run(
        [sys.executable, SCRIPT, "--dir", d, "--check"],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stdout + r.stderr
    for rnd in ("r01", "r02", "r03", "r04", "r05"):
        assert rnd in r.stdout
    assert "dist_inner_join.rows_per_s" in r.stdout
