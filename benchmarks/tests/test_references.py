"""The comparisons that decide ``correct`` pass on the exact answer and fail
on the answer computed in the next lower precision (the control), on a
dropped row, on a doubled row and on a payload paired with the wrong key."""
import importlib.util
import json
import os

import ml_dtypes
import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def code(kind, name):
    path = os.path.join(BENCH, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"t_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def data(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def failed(numbers):
    return [n["name"] for n in numbers if not n["value"] <= n["limit"]]


@pytest.fixture(scope="module")
def join_case():
    config, traffic = data("configs", "hashjoin-workload-b"), \
        data("traffic", "inner-1chip")
    tables = code("generators", "pk_fk_pair").generate(
        config, traffic, 1, 0.002, 7)["tables"]
    ref_mod = code("references", "inner_join_fingerprint")
    ref = ref_mod.reference(tables, config, traffic)
    # the exact join by another route: a dictionary of the right side
    by_key = {}
    for j, k in enumerate(tables["right"]["k"]):
        by_key.setdefault(int(k), []).append(j)
    li, ri = [], []
    for i, k in enumerate(tables["left"]["k"]):
        for j in by_key.get(int(k), ()):
            li.append(i)
            ri.append(j)
    li, ri = np.array(li), np.array(ri)
    exact = [tables["left"]["k"][li], tables["left"]["v"][li],
             tables["right"]["k"][ri], tables["right"]["w"][ri]]
    return ref_mod, ref, tables, config, traffic, exact


def as_result(cols):
    return {"names": ["lt-0", "lt-1", "rt-2", "rt-3"], "columns": cols,
            "nulls": 0}


def test_join_exact_passes_in_any_order(join_case):
    ref_mod, ref, *_rest, exact = join_case
    assert failed(ref_mod.compare(as_result(exact), ref)) == []
    perm = np.random.default_rng(0).permutation(len(exact[0]))
    assert failed(ref_mod.compare(
        as_result([c[perm] for c in exact]), ref)) == []


def test_join_bfloat16_payload_fails(join_case):
    ref_mod, ref, tables, config, traffic, exact = join_case
    rounded = list(exact)
    rounded[1] = exact[1].astype(ml_dtypes.bfloat16).astype(np.float32)
    assert failed(ref_mod.compare(as_result(rounded), ref)) \
        == ["fingerprint_diff"]
    control = ref_mod.control(tables, config, traffic)
    assert failed(ref_mod.compare(control, ref)) == ["fingerprint_diff"]


def test_join_dropped_doubled_and_mispaired_rows_fail(join_case):
    ref_mod, ref, *_rest, exact = join_case
    dropped = [c[1:] for c in exact]
    assert "rows_diff" in failed(ref_mod.compare(as_result(dropped), ref))
    doubled = [np.concatenate([c[1:], c[1:2]]) for c in exact]
    assert failed(ref_mod.compare(as_result(doubled), ref)) \
        == ["fingerprint_diff"]
    mispaired = [c.copy() for c in exact]
    mispaired[3][[0, 1]] = mispaired[3][[1, 0]]
    assert failed(ref_mod.compare(as_result(mispaired), ref)) \
        == ["fingerprint_diff"]
    one_bit = [c.copy() for c in exact]
    one_bit[1].view(np.uint32)[5] ^= 1
    assert failed(ref_mod.compare(as_result(one_bit), ref)) \
        == ["fingerprint_diff"]


@pytest.fixture(scope="module")
def groupby_case():
    config, traffic = data("configs", "h2o-groupby-1e8-f32"), data("traffic", "q5")
    tables = code("generators", "h2o_g1").generate(
        config, traffic, 1, 0.001, 7)["tables"]
    ref_mod = code("references", "groupby_sum_f64")
    return ref_mod, ref_mod.reference(tables, config, traffic), tables, \
        config, traffic


def f32_sums(tables, traffic, cast=None):
    """Group sums accumulated row by row in float32 (or a lower type):
    what a sound program may do."""
    t = tables["x"]
    keys = np.unique(t["id6"])
    pos = np.searchsorted(keys, t["id6"])
    cols = [keys.astype(np.int32)]
    for name in traffic["values"]:
        x = t[name]
        acc = np.zeros(len(keys), x.dtype if cast is None
                       or x.dtype.kind != "f" else cast)
        np.add.at(acc, pos, x.astype(acc.dtype))
        cols.append(acc.astype(x.dtype))
    return {"names": ["id6"] + list(traffic["values"]), "columns": cols,
            "nulls": 0}


def test_h2o_ranges(groupby_case):
    _m, _ref, tables, config, _t = groupby_case
    t, n = tables["x"], len(tables["x"]["id6"])
    assert n == 100000
    assert t["id6"].min() >= 1 and t["id6"].max() <= n // config["K"]
    assert set(np.unique(t["v1"])) == set(range(1, 6))
    assert set(np.unique(t["v2"])) == set(range(1, 16))
    assert 0 <= t["v3"].min() and t["v3"].max() <= 100
    assert t["v3"].dtype == np.float32 and t["id6"].dtype == np.int32


def test_groupby_float32_accumulation_passes(groupby_case):
    ref_mod, ref, tables, _c, traffic = groupby_case
    numbers = ref_mod.compare(f32_sums(tables, traffic), ref)
    assert failed(numbers) == []
    worst = [n for n in numbers if n["name"].startswith("f32_sum_err")][0]
    assert 0 < worst["value"] < 1.0 / 3


def test_groupby_bfloat16_fails(groupby_case):
    ref_mod, ref, tables, config, traffic = groupby_case
    got = f32_sums(tables, traffic, cast=ml_dtypes.bfloat16)
    assert failed(ref_mod.compare(got, ref)) == ["f32_sum_err_over_bound.v3"]
    control = ref_mod.control(tables, config, traffic)
    numbers = ref_mod.compare(control, ref)
    assert failed(numbers) == ["f32_sum_err_over_bound.v3"]
    assert [n for n in numbers if not n["value"] <= n["limit"]][0]["value"] > 3


def test_groupby_wrong_groups_and_integer_sums_fail(groupby_case):
    ref_mod, ref, tables, _c, traffic = groupby_case
    good = f32_sums(tables, traffic)
    missing = dict(good, columns=[c[1:] for c in good["columns"]])
    assert "groups_diff" in failed(ref_mod.compare(missing, ref))
    twice = dict(good, columns=[np.concatenate([c, c[:1]])
                                for c in good["columns"]])
    assert "groups_diff" in failed(ref_mod.compare(twice, ref))
    off = dict(good, columns=[c.copy() for c in good["columns"]])
    off["columns"][1][3] += 1
    assert failed(ref_mod.compare(off, ref)) == ["int_sum_mismatches.v1"]
