"""bench.py is the driver-facing artifact producer — its code paths are
gated here so a refactor can't silently sink a round's evidence."""
import json

import numpy as np
import pytest

import bench


def test_main_refuses_a_platform_other_than_tpu():
    """`python bench.py` measures the chip: on the CPU it is an error
    (no fallback artifact, no child, no retry); run() below stays
    callable anywhere."""
    with pytest.raises(SystemExit) as e:
        bench.main(["--rows", "1024", "--join-only"])
    assert "'cpu'" in str(e.value.code)  # a message: exit status 1


def test_run_join_only_small(local_ctx):
    """The primary metric path end-to-end at tiny scale: valid artifact
    shape, real numbers, never parsed-null material."""
    res = bench.run(1 << 10, iters=1, full=False)
    assert res["metric"] == "dist_inner_join_rows_per_sec_per_chip"
    assert res["value"] > 0
    assert res["unit"] == "rows/s/chip"
    assert isinstance(res["vs_baseline"], float)
    d = res["detail"]
    assert d["out_rows"] > 0
    assert d["local_inner_join"]["rows_per_s_per_chip"] > 0
    assert d["shuffle"]["rows_per_s_per_chip"] > 0
    json.dumps(res)  # one-line artifact must be serializable


@pytest.mark.slow
def test_full_suite_small(local_ctx):
    """Every suite config produces a number (no error keys) at small
    scale — the round-4 'one failing config sinks the artifact' guard
    plus the round-5 configs (dist_string_join, dist_sort,
    pandas_reference)."""
    res = bench.run(1 << 12, iters=1, full=True)
    suite = res["detail"]["suite"]
    for name in ("groupby_agg", "global_sort", "set_union", "q5_pipeline",
                 "string_join", "dist_string_join", "dist_sort", "dist_union",
                 "shuffle_wide", "shuffle_pipeline", "hbm_blocked_join",
                 "pandas_reference", "service_pipeline"):
        assert name in suite, f"missing config {name}"
        assert "error" not in suite[name], (name, suite[name])
    # the overlapped-exchange config must demonstrate the fusion win
    # (strictly fewer collective launches with the fused partition+
    # chunk-0 program) and record the pipeline geometry
    sp = suite["shuffle_pipeline"]
    assert sp["chunks"] > 1
    assert sp["collective_launches"] < sp["collective_launches_nofuse"]
    assert 0.0 < sp["overlap_ratio"] < 1.0
    assert sp["exchange_wall_s"] > 0
    json.dumps(res)


def test_service_pipeline_records_cache_amortization(local_ctx):
    """The service_pipeline config proves the plan cache live in the
    artifact: >= 7 of 8 equal-shape submissions hit, zero kernel
    builds after the first query, and the mean wait rides along for
    the benchtrend trajectory."""
    ctx = bench._mk_ctx()
    res = bench.bench_service_pipeline(ctx, 1 << 10, iters=1)
    assert res["queries"] == 8
    assert res["cache_hits"] >= 7
    assert res["builds_after_first_query"] == 0
    assert res["mean_wait_s"] is not None and res["mean_wait_s"] >= 0
    # the bucket-interpolated p95 wait rides the artifact too (the
    # benchtrend gate judges it lower-is-better)
    assert res["wait_p95_s"] is not None and res["wait_p95_s"] >= 0
    assert res["service_wall_s"] > 0 and res["sequential_wall_s"] > 0
    json.dumps(res)


def test_plan_pipeline_emits_reports_and_metrics(local_ctx):
    """The plan_pipeline config carries the measurement layer's own
    outputs: per-query EXPLAIN ANALYZE reports and the metrics delta —
    not hand-rolled dicts."""
    ctx = bench._mk_ctx()
    res = bench.bench_plan_pipeline(ctx, 1 << 10, iters=1)
    for key in ("plan_report", "eager_report", "metrics"):
        assert key in res, res.keys()
    assert res["plan_report"]["plan"]["kind"] == "groupby"
    assert res["plan_report"]["plan"]["rows"] is not None
    assert res["plan_report"]["total_ms"] > 0
    assert res["plan_report"]["optimizer"]["groupbys_localized"] == 1
    # shuffle counts in the report are the executed plan.shuffle labels
    assert res["eager_report"]["shuffle_count"] >= \
        res["plan_report"]["shuffle_count"]
    for section in ("eager", "planned"):
        m = res["metrics"][section]
        assert m["cylon_shuffle_bytes_total"] >= 0
        assert m["cylon_collective_launches_total"] >= 0
    json.dumps(res)  # artifact stays one-line serializable
