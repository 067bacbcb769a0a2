"""The low-cardinality groupby cell's own pieces (`h2o-groupby-1e8-f32-q4`,
PR 34): the reference passes on exact answers and on a sound float32 path,
fails on the answer in the next lower precision (the control) by the v3
number ALONE, on a dropped group, on an integer mean off by 2^-15, on a
float64 result column; the cell's entries of BENCHMARK.json, found by
NAME, never by position. Needs nothing of `cylon_tpu`; tier-1 runs this
file too (tests/test_cell_groupby_q4.py).
"""
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(HERE)]   # test_references; xplane

from test_references import code, data, failed  # noqa: E402

SCALE = 0.001               # 100,000 rows: 100 groups of ~1,000
NEW_METRICS = ["groupby_lowcard_device_ms_per_query",
               "groupby_dense_device_ms_per_query",
               "groupby_dense_roofline",
               "groupby_dense_reduces_per_query",
               "groupby_dense_slots_per_query"]


@pytest.fixture(scope="module")
def q4_case():
    config = data("configs", "h2o-groupby-1e8-f32-q4")
    traffic = data("traffic", "q4")
    tables = code("generators", config["generator"]).generate(
        config, traffic, 1, SCALE, 2147483659)["tables"]
    ref_mod = code("references", config["reference"])
    return ref_mod, ref_mod.reference(tables, config, traffic), tables, \
        config, traffic


def means(tables, traffic, how):
    """The result a program would hand back: id4 and three float32 means,
    each group's mean formed by ``how(values of the group)``."""
    t = tables["x"]
    keys = np.unique(t["id4"])
    cols = [keys.astype(np.int32)]
    for name in traffic["values"]:
        cols.append(np.array([how(t[name][t["id4"] == k]) for k in keys],
                             np.float32))
    return {"names": ["id4"] + list(traffic["values"]), "columns": cols,
            "nulls": 0}


def pairwise_f32(x):
    """numpy's own float32 sum (pairwise) over a float32 count: what a
    sound float32 path may do."""
    return np.float32(x.astype(np.float32).sum(dtype=np.float32)) \
        / np.float32(len(x))


def test_q4_is_100_groups_of_the_same_rows_as_q5(q4_case):
    _m, ref, tables, config, traffic = q4_case
    t = tables["x"]
    assert len(t["id4"]) == 100000 and t["id4"].dtype == np.int32
    assert t["id4"].min() == 1 and t["id4"].max() == config["K"] == 100
    assert len(ref["keys"]) == 100 and ref["count"].sum() == 100000
    # a column is the same whichever question touches it
    q5 = code("generators", "h2o_g1").generate(
        data("configs", "h2o-groupby-1e8-f32"), data("traffic", "q5"), 1,
        SCALE, 2147483659)["tables"]["x"]
    for name in traffic["values"]:
        assert (q5[name] == t[name]).all()


def test_exact_and_sound_float32_answers_pass(q4_case):
    ref_mod, ref, tables, _c, traffic = q4_case
    exact = means(tables, traffic, lambda x: x.astype(np.float64).mean())
    numbers = ref_mod.compare(exact, ref)
    assert failed(numbers) == []
    assert [n["name"] for n in numbers] == [
        "schema_diff", "nulls", "groups_diff", "int_mean_err_over_bound.v1",
        "int_mean_err_over_bound.v2", "f32_mean_err_over_bound.v3"]
    # rounding the exact mean to float32 is half an ulp: 1 unit of 2^-24
    # at most, an eighth of the float bound and a quarter of the integer
    assert max(n["value"] for n in numbers[3:]) <= 0.25
    sound = ref_mod.compare(means(tables, traffic, pairwise_f32), ref)
    assert failed(sound) == []
    assert max(n["value"] for n in sound[3:]) < 1.0 / 3


def test_the_bfloat16_control_fails_by_v3_alone(q4_case):
    ref_mod, ref, tables, config, traffic = q4_case
    control = ref_mod.control(tables, config, traffic)
    assert [c.dtype for c in control["columns"]] == ref["dtypes"]
    numbers = ref_mod.compare(control, ref)
    assert failed(numbers) == ["f32_mean_err_over_bound.v3"]
    assert [n for n in numbers if not n["value"] <= n["limit"]][0]["value"] > 3


def test_a_naive_float32_sum_of_a_long_group_fails(q4_case):
    """What the bound is for: one float32 accumulator fed row after row.
    At a rehearsal's 1,000 rows a group it still passes; at 2^20 rows in a
    group it is tens of units off."""
    ref_mod, _ref, _tables, config, traffic = q4_case
    r = np.random.default_rng(5)
    n = 1 << 20
    t = {"x": {"id4": np.ones(n, np.int32),
               "v1": r.integers(1, 6, n).astype(np.int32),
               "v2": r.integers(1, 16, n).astype(np.int32),
               "v3": np.round(r.uniform(0, 100, n), 6).astype(np.float32)}}
    ref = ref_mod.reference(t, config, traffic)
    naive = means(t, traffic, lambda x: np.cumsum(
        x.astype(np.float32), dtype=np.float32)[-1] / np.float32(len(x)))
    assert failed(ref_mod.compare(naive, ref)) == \
        ["f32_mean_err_over_bound.v3"]
    assert failed(ref_mod.compare(means(t, traffic, pairwise_f32), ref)) == []


def test_wrong_groups_integer_means_and_schema_fail(q4_case):
    ref_mod, ref, tables, _c, traffic = q4_case
    good = means(tables, traffic, lambda x: x.astype(np.float64).mean())
    missing = dict(good, columns=[c[1:] for c in good["columns"]])
    assert "groups_diff" in failed(ref_mod.compare(missing, ref))
    twice = dict(good, columns=[np.concatenate([c, c[:1]])
                                for c in good["columns"]])
    assert "groups_diff" in failed(ref_mod.compare(twice, ref))
    off = dict(good, columns=[c.copy() for c in good["columns"]])
    off["columns"][1][3] *= np.float32(1 + 2.0 ** -15)
    assert failed(ref_mod.compare(off, ref)) == ["int_mean_err_over_bound.v1"]
    wide = dict(good, columns=good["columns"][:3]
                + [good["columns"][3].astype(np.float64)])
    assert failed(ref_mod.compare(wide, ref)) == ["schema_diff"]
    assert failed(ref_mod.compare(dict(good, nulls=1), ref)) == ["nulls"]


def test_benchmark_lists_the_cell_and_its_metrics_by_name():
    bench = data("..", "BENCHMARK")
    config = [c for c in bench["configs"]
              if c["name"] == "h2o-groupby-1e8-f32-q4"][0]
    spec = data("configs", config["name"])
    assert config["source"] == spec["source"] and len(config["source"]) <= 200
    assert config["reduced"] == spec["reduced"] == list(spec["reduced_why"])
    assert config["file"] == "benchmarks/configs/h2o-groupby-1e8-f32-q4.json"
    assert spec["N"] == 100000000 and spec["K"] == 100
    sibling = data("configs", "h2o-groupby-1e8-f32")
    assert spec["columns"] == sibling["columns"]       # the same data set
    assert spec["generator"] == sibling["generator"]
    cell = [w for w in bench["workloads"] if w["name"] == "groupby-q4"][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (config["name"], "q4", 1)
    listed = {m["name"]: m for m in bench["per_layer"]
              if m["name"] in NEW_METRICS}
    assert sorted(listed) == sorted(NEW_METRICS)
    for name, m in listed.items():
        assert m["workloads"] == ["groupby-q4"] \
            and m["moves"] == "query_p50_s"
        mspec = data("metrics", name)
        assert (mspec["unit"], mspec["layer"], mspec["source"]) \
            == (m["unit"], m["layer"], m["source"])
    # no metric the sort path alone feeds lists this cell
    for m in bench["per_layer"]:
        if "groupby-q4" in m.get("workloads", ()):
            assert m["name"] in NEW_METRICS
