"""The TPC-H Q1 cell's own pieces (`tpch-sf100-q1`, PR 42): the generator's
population (value ranges, the four groups, the share of rows under the
filter, every seed the same work); the plain reference passes on the exact
report whether its sums arrive as word planes or as int64, fails on the
control (sums accumulated in float32) by the sums, on a dropped, a doubled
and a misplaced group, on a sum off by ONE unit, on a count off by one, on
an average off by 2^-20, on a narrowed schema and on a null; the reference
imports nothing of the engine; the cell's entries of BENCHMARK.json, found
by NAME. Needs nothing of `cylon_tpu`; tier-1 runs this file too
(tests/test_cell_tpch_q1.py).
"""
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(HERE)]   # test_references; xplane

from test_references import BENCH, code, data, failed  # noqa: E402

SCALE = 0.002               # 150,009 rows
NEW_METRICS = ["scanagg_device_ms_per_query",
               "scanagg_expr_device_ms_per_query",
               "scanagg_dense_device_ms_per_query",
               "scanagg_dense_roofline",
               "scanagg_dense_keys_per_query",
               "scanagg_expr_materialized_bytes_per_query"]


@pytest.fixture(scope="module")
def q1_case():
    config = data("configs", "tpch-sf100-q1")
    traffic = data("traffic", "tpch-q1")
    tables = code("generators", config["generator"]).generate(
        config, traffic, 1, SCALE, 2147483659)["tables"]
    ref_mod = code("references", config["reference"])
    return ref_mod, ref_mod.reference(tables, config, traffic), tables, \
        config, traffic


def report(ref_mod, ref, planes=True):
    """The result an exact engine hands back, from the reference's own
    numbers: code columns, four sums (word planes or int64), three
    float32 averages, the int32 count."""
    count = np.asarray(ref["count"], np.int64)
    cols = [np.array([g[0] for g in ref["groups"]], np.int32),
            np.array([g[1] for g in ref["groups"]], np.int32)]
    cols += [ref_mod._planes(ref["sums"][j]) if planes
             else np.asarray(ref["sums"][j], np.int64) for j in range(4)]
    cols += [(np.asarray(ref["sums"][j], np.float64) / count)
             .astype(np.float32) for j in (0, 1, 4)]
    cols.append(count.astype(np.int32))
    return {"names": list(ref_mod.NAMES), "columns": cols, "nulls": 0}


def test_the_population_is_tpch_lineitems(q1_case):
    _m, ref, tables, config, traffic = q1_case
    t = tables["lineitem"]
    n = len(t["l_quantity"])
    assert n == int(config["rows"] * SCALE) == 150009
    assert list(t) == traffic["columns"]
    for name in ("l_quantity", "l_extendedprice", "l_discount", "l_tax"):
        assert t[name].dtype == np.int64
    assert t["l_shipdate"].dtype == np.int32
    assert t["l_returnflag"].dtype == t["l_linestatus"].dtype == "<U1"
    assert sum(a.nbytes for a in t.values()) == config["row_bytes_placed"] * n
    assert t["l_quantity"].min() == 100 and t["l_quantity"].max() == 5000
    assert (t["l_quantity"] % 100 == 0).all()
    assert t["l_discount"].min() == 0 and t["l_discount"].max() == 10
    assert t["l_tax"].min() == 0 and t["l_tax"].max() == 8
    unit = t["l_extendedprice"] // (t["l_quantity"] // 100)
    assert (unit * (t["l_quantity"] // 100) == t["l_extendedprice"]).all()
    assert 90000 <= unit.min() and unit.max() <= 209900
    assert 8035 + 1 <= t["l_shipdate"].min() \
        and t["l_shipdate"].max() <= 10440 + 121
    assert set(t["l_returnflag"]) == {"A", "N", "R"}
    assert set(t["l_linestatus"]) == {"F", "O"}
    # the four groups of the specification's answer, N/F the small one
    names = [(str(ref["flags"][f]), str(ref["status"][s]))
             for f, s in ref["groups"]]
    assert names == [("A", "F"), ("N", "F"), ("N", "O"), ("R", "F")]
    assert 0.004 * n < ref["count"][1] < 0.01 * n
    assert 0.98 < ref["rows_kept"] / n < 0.99
    assert sum(ref["count"]) == ref["rows_kept"]


def test_every_seed_is_the_same_work_and_a_seed_repeats(q1_case):
    _m, ref, tables, config, traffic = q1_case
    gen = code("generators", config["generator"])
    again = gen.generate(config, traffic, 1, SCALE, 2147483659)["tables"]
    other = gen.generate(config, traffic, 1, SCALE, 4294967311)["tables"]
    for name, col in tables["lineitem"].items():
        assert (again["lineitem"][name] == col).all()
        assert len(other["lineitem"][name]) == len(col)
    assert (other["lineitem"]["l_extendedprice"]
            != tables["lineitem"]["l_extendedprice"]).any()
    ref_mod = code("references", config["reference"])
    ref2 = ref_mod.reference(other, config, traffic)
    assert ref2["groups"] == ref["groups"]


def test_the_exact_report_passes_as_planes_and_as_int64(q1_case):
    ref_mod, ref, _t, _c, _tr = q1_case
    for planes in (True, False):
        numbers = ref_mod.compare(report(ref_mod, ref, planes), ref)
        assert failed(numbers) == []
        assert [n["name"] for n in numbers] == [
            "schema_diff", "nulls", "groups_diff", "count_diff.count_order",
            "sum_diff.sum_qty", "sum_diff.sum_base_price",
            "sum_diff.sum_disc_price", "sum_diff.sum_charge",
            "avg_err_over_bound.avg_qty", "avg_err_over_bound.avg_price",
            "avg_err_over_bound.avg_disc"]
        # the exact mean rounded once to float32: a quarter of the bound
        assert max(n["value"] for n in numbers[8:]) <= 0.25


def test_the_sums_are_python_integers_of_the_blocks(q1_case):
    """The reference against a second route: Python integers row by row
    over a slice of the table."""
    ref_mod, _ref, tables, config, traffic = q1_case
    t = {k: v[:5000] for k, v in tables["lineitem"].items()}
    ref = ref_mod.reference({"lineitem": t}, config, traffic)
    sums, count = {}, {}
    for q, p, d, x, f, s, day in zip(*(t[c].tolist()
                                       for c in traffic["columns"])):
        if day > traffic["shipdate_max"]:
            continue
        dp = p * (100 - d)
        acc = sums.setdefault((f, s), [0] * 5)
        for j, v in enumerate((q, p, dp, dp * (100 + x), d)):
            acc[j] += v
        count[(f, s)] = count.get((f, s), 0) + 1
    keys = sorted(sums)
    assert [(str(ref["flags"][f]), str(ref["status"][g]))
            for f, g in ref["groups"]] == keys
    assert ref["count"] == [count[k] for k in keys]
    assert ref["sums"] == [[sums[k][j] for k in keys] for j in range(5)]


def test_the_float32_control_fails_by_its_sums(q1_case):
    ref_mod, ref, tables, config, traffic = q1_case
    control = ref_mod.control(tables, config, traffic)
    good = report(ref_mod, ref)
    assert [(c.dtype, c.shape) for c in control["columns"]] \
        == [(c.dtype, c.shape) for c in good["columns"]]
    numbers = ref_mod.compare(control, ref)
    bad = failed(numbers)
    assert set(bad) >= {"sum_diff.sum_base_price", "sum_diff.sum_disc_price",
                        "sum_diff.sum_charge"}
    assert all(name.startswith("sum_diff.") for name in bad)
    by = {n["name"]: n["value"] for n in numbers}
    assert by["sum_diff.sum_charge"] > 1e6      # limit 0: orders of magnitude


def test_wrong_groups_sums_counts_averages_and_schema_fail(q1_case):
    ref_mod, ref, _t, _c, _tr = q1_case
    good = report(ref_mod, ref)

    def edit(fn):
        cols = [c.copy() for c in good["columns"]]
        fn(cols)
        return dict(good, columns=cols)

    def cut(c, keep):
        return c[..., keep]

    missing = dict(good, columns=[cut(c, slice(1, None))
                                  for c in good["columns"]])
    assert failed(ref_mod.compare(missing, ref)) == ["groups_diff"]
    twice = dict(good, columns=[np.concatenate([c, cut(c, slice(0, 1))],
                                               axis=-1)
                                for c in good["columns"]])
    assert failed(ref_mod.compare(twice, ref)) == ["groups_diff"]
    swapped = dict(good, columns=[cut(c, [1, 0, 2, 3])
                                  for c in good["columns"]])
    assert failed(ref_mod.compare(swapped, ref)) == ["groups_diff"]

    def one_unit(cols):
        cols[5][1, 2] += np.uint32(1)       # sum_charge's low plane
    assert failed(ref_mod.compare(edit(one_unit), ref)) \
        == ["sum_diff.sum_charge"]

    def high_word(cols):
        cols[2][0, 0] += np.uint32(1)       # sum_qty's HIGH plane: 2^32
    assert failed(ref_mod.compare(edit(high_word), ref)) \
        == ["sum_diff.sum_qty"]

    def one_row(cols):
        cols[9][3] -= 1
    assert failed(ref_mod.compare(edit(one_row), ref)) \
        == ["count_diff.count_order"]

    def avg(cols):
        cols[7][0] *= np.float32(1 + 2.0 ** -20)
    assert failed(ref_mod.compare(edit(avg), ref)) \
        == ["avg_err_over_bound.avg_price"]
    for narrow in (lambda c: c.__setitem__(9, c[9].astype(np.int64)),
                   lambda c: c.__setitem__(6, c[6].astype(np.float64)),
                   lambda c: c.__setitem__(3, c[3][1].astype(np.int32)),
                   lambda c: c.pop()):
        assert failed(ref_mod.compare(edit(narrow), ref)) == ["schema_diff"]
    assert failed(ref_mod.compare(dict(good, nulls=1), ref)) == ["nulls"]


def test_the_reference_imports_nothing_of_the_engine():
    with open(os.path.join(BENCH, "references", "tpch_q1_exact.py")) as f:
        text = f.read()
    assert "cylon" not in text and "import jax" not in text


def test_benchmark_lists_the_cell_and_its_metrics_by_name():
    bench = data("..", "BENCHMARK")
    config = [c for c in bench["configs"] if c["name"] == "tpch-sf100-q1"][0]
    spec = data("configs", config["name"])
    assert config["source"] == spec["source"] and len(config["source"]) <= 200
    assert config["reduced"] == spec["reduced"] == list(spec["reduced_why"]) \
        == ["rows", "columns_placed"]
    assert config["file"] == "benchmarks/configs/tpch-sf100-q1.json"
    assert spec["rows"] == 75004738 \
        == -(-spec["source_rows"] // spec["chips_in_deployment"])
    placed = [c for c, d in spec["columns"].items()
              if not d["placed"].startswith("no")]
    assert sorted(placed) == sorted(data("traffic", "tpch-q1")["columns"])
    assert len(spec["columns"]) == 16 and len(placed) == 7
    cell = [w for w in bench["workloads"] if w["name"] == "tpch-q1"][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (config["name"], "tpch-q1", 1)
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 3 \
        and len(bench["workloads"]) == 8
    listed = {m["name"]: m for m in bench["per_layer"]
              if m["name"] in NEW_METRICS}
    assert sorted(listed) == sorted(NEW_METRICS)
    for name, m in listed.items():
        assert m["workloads"] == ["tpch-q1"] and m["moves"] == "query_p50_s"
        mspec = data("metrics", name)
        assert (mspec["unit"], mspec["layer"], mspec["source"]) \
            == (m["unit"], m["layer"], m["source"])
    # no metric of another cell's lists this one
    for m in bench["per_layer"]:
        if "tpch-q1" in m.get("workloads", ()):
            assert m["name"] in NEW_METRICS
