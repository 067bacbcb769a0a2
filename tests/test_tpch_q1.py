"""TPC-H Q1 through the plan, and the three mechanisms it is made of (PR 42).

- Value expressions (``plan/ir.Value`` -> ``Compute`` -> ``Table.
  with_columns`` -> ``ops/expr.py`` over ``ops/wideint.py``): ``add`` /
  ``sub`` / ``mul`` over int32, plane-held int64 and mixed operands against
  Python integers, at the edges; a step that cannot be shown to fit raises
  by name and never wraps.
- The dense table over several keys and over plane-held int64 value
  columns (``ops/groupby.dense_aggregate_keys``): against the sort path on
  32-bit data, and exact 64-bit sums against Python integers.
- The optimizer through the ``Compute`` node: the filter goes below it, the
  pruning through it, the fingerprint is stable.

The chip runs with x64 off; this suite runs with it on (conftest.py), so a
case that is about word planes runs under ``jax.enable_x64(False)`` as a
whole, as ``test_join_i64.py`` does. The dense kernel runs on the Pallas
interpreter here (2-5 s to compile a distinct program, then fast).
"""
import importlib.util
import json
import os

import jax
import numpy as np
import pytest

import cylon_tpu as ct
from cylon_tpu import dtypes, plan, telemetry
from cylon_tpu.data.column import Column
from cylon_tpu.ops import groupby as G
from cylon_tpu.ops import wideint as W
from cylon_tpu.plan import col
from cylon_tpu.status import CylonError

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmarks")
I32, I64 = np.int32, np.int64


def _code(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"q1_{kind}_{name}", os.path.join(BENCH, kind, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _data(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def _ints(column):
    """A result column as Python integers (planes put back together)."""
    return [int(x) for x in column._host_data().tolist()]


def _delta(before, prefix):
    now = telemetry.metrics_snapshot()
    return sum(v - before.get(k, 0) for k, v in now.items()
               if k.startswith(prefix) and isinstance(v, (int, float)))


# ---------------------------------------------------------------------------
# the whole query against the plain reference
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def q1():
    config, traffic = _data("configs", "tpch-sf100-q1"), \
        _data("traffic", "tpch-q1")
    tables = _code("generators", "tpch_lineitem").generate(
        config, traffic, 1, 0.0015, 2147483659)["tables"]     # 112,507 rows
    ref_mod = _code("references", "tpch_q1_exact")
    return (ref_mod, ref_mod.reference(tables, config, traffic), tables,
            traffic, _code("queries", "tpch_q1"))


def _host_result(table):
    assert table.row_mask is None
    return {"names": list(table.column_names),
            "columns": [np.asarray(c.data) for c in table.columns()],
            "nulls": sum(c.null_count() for c in table.columns())}


def test_q1_through_the_plan_matches_the_reference_exactly(local_ctx, q1):
    """The cell's own query over word planes (x64 off, as on the chip):
    counts and the four sums equal as integers, the averages inside the
    cell's bound, the groups in key order with no row mask; the dense
    table ran over both keys, nothing was sorted, two host fetches."""
    ref_mod, ref, tables, traffic, query = q1
    with jax.enable_x64(False):
        t = ct.Table.from_pydict(local_ctx, tables["lineitem"])
        assert t.get_column(1).is_planes and t.get_column(4).is_string
        pipe = query.build(plan, {"lineitem": t}, traffic)
        text = pipe.explain()
        before = telemetry.metrics_snapshot()
        with telemetry.collect_phases() as cp:
            out = pipe.execute()
        numbers = ref_mod.compare(_host_result(out), ref)
        dtypes_out = [c.dtype.type.name for c in out.columns()]
    assert [n["name"] for n in numbers if not n["value"] <= n["limit"]] == []
    assert max(n["value"] for n in numbers[-3:]) < 0.5
    # the filter below the computed columns, a groupby on two keys
    lines = [ln.strip() for ln in text.splitlines()]
    assert lines[0].startswith("Sort(") and lines[1].startswith(
        "GroupBy(keys=[4, 5]")
    assert lines[2].startswith("Compute(disc_price=") \
        and lines[3].startswith("Filter(")
    assert _delta(before, "cylon_groupby_dense_keys_total") == 2
    assert _delta(before, "cylon_expr_columns_total") == 2
    assert _delta(before, "cylon_expr_materialized_bytes_total") \
        == 2 * 8 * t.capacity
    assert _delta(before, 'cylon_groupby_reduce_path_total{path="dense"') == 1
    assert _delta(before, "cylon_groupby_sort_operands_total") == 0
    for site in ("expr.range", "groupby.densegroups"):
        assert cp.count("sync." + site) == 1
    # two dictionary keys: their codes' ranges are known without a probe
    assert cp.count("sync.groupby.keyrange") == 0
    assert cp.count("plan.compute") == 1 and cp.count("plan.sort") == 1
    assert cp.count("sync.groupby.groups") == 0      # the sort path's fetch
    # labelled as what they are (x64 off)
    assert dtypes_out == ["STRING", "STRING"] + ["INT64"] * 4 \
        + ["FLOAT"] * 3 + ["INT32"]
    assert out.to_pandas()["l_returnflag"].tolist() == ["A", "N", "N", "R"]


def test_q1_with_native_int64_columns_sorts_and_agrees(local_ctx, q1):
    """The same query with x64 on: native int64 steps, and a groupby whose
    8-byte accumulators take the sort path; the same integers."""
    ref_mod, ref, tables, traffic, query = q1
    t = ct.Table.from_pydict(local_ctx, tables["lineitem"])
    out = query.build(plan, {"lineitem": t}, traffic).execute()
    live = out.to_pandas()
    assert live.iloc[:, 9].tolist() == ref["count"]
    for j in range(4):
        assert [int(x) for x in live.iloc[:, 2 + j]] == ref["sums"][j]
    for j, k in zip((6, 7, 8), (0, 1, 4)):
        want = np.array(ref["sums"][k], float) / np.array(ref["count"])
        assert np.allclose(live.iloc[:, j], want, rtol=1e-12)


# ---------------------------------------------------------------------------
# expressions on their own
# ---------------------------------------------------------------------------

def _edge(lo, hi, n, seed, dtype):
    """Values over [lo, hi] with both ends, zero and +-1 where they fit."""
    r = np.random.default_rng(seed)
    x = r.integers(lo, hi, n, dtype=np.int64, endpoint=True)
    for i, v in enumerate(v for v in (lo, hi, 0, 1, -1, lo + 1, hi - 1)
                          if lo <= v <= hi):
        x[i] = v
    return x.astype(dtype)


B31, B62 = 1 << 31, 1 << 62

# name -> (columns, expression, the same over Python integers)
EXPRESSIONS = {
    "i32*i32_fits": (
        {"a": _edge(-46340, 46340, 300, 1, I32),
         "b": _edge(-46340, 46340, 300, 2, I32)},
        lambda: col("a") * col("b"), lambda a, b: a * b),
    "i32+i32_at_the_ends": (
        {"a": _edge(-B31 // 2, B31 // 2 - 1, 300, 3, I32),
         "b": _edge(-B31 // 2, B31 // 2 - 1, 300, 4, I32)},
        lambda: col("a") + col("b") - 1 + 1, lambda a, b: a + b),
    "i64*i32_near_2^62": (
        {"a": _edge(-(B31 - 1), B31 - 1, 300, 5, I64),
         "b": _edge(-(B31 - 1), B31 - 1, 300, 6, I32)},
        lambda: col("a") * col("b"), lambda a, b: a * b),
    "i64*i64_mixed_signs": (
        {"a": _edge(-(1 << 40), 1 << 40, 300, 7, I64),
         "b": _edge(-(1 << 22), 1 << 22, 300, 8, I64)},
        lambda: col("a") * col("b"), lambda a, b: a * b),
    "i64_sub_to_a_small_difference": (
        {"a": _edge(B62, B62 + 1000, 300, 9, I64),
         "b": _edge(B62 - 1000, B62, 300, 10, I64)},
        lambda: (col("a") - col("b")) * 1000 - 7,
        lambda a, b: (a - b) * 1000 - 7),
    "i64+i64_to_the_ends": (
        {"a": _edge(-B62, B62 - 1, 300, 11, I64),
         "b": _edge(-B62, B62, 300, 12, I64)},
        lambda: col("a") + col("b"), lambda a, b: a + b),
    "literals_on_the_left": (
        {"a": _edge(0, 10, 300, 13, I64),
         "b": _edge(-B31, B31 - 1, 300, 14, I32)},
        lambda: (100 - col("a")) * col("b") + (5_000_000_000 - col("a")),
        lambda a, b: (100 - a) * b + (5_000_000_000 - a)),
    "q1_charge": (
        {"a": _edge(90000, 10_495_000, 300, 15, I64),
         "b": _edge(0, 10, 300, 16, I64)},
        lambda: col("a") * (100 - col("b")) * (100 + col("b")),
        lambda a, b: a * (100 - b) * (100 + b)),
    "uint32_and_int8": (
        {"a": _edge(0, 2 ** 32 - 1, 300, 17, np.uint32),
         "b": _edge(-128, 127, 300, 18, np.int8)},
        lambda: col("a") * col("b") - col("a"), lambda a, b: a * b - a),
}


@pytest.mark.parametrize("x64", [False, True], ids=["planes", "native"])
@pytest.mark.parametrize("name", list(EXPRESSIONS))
def test_expression_is_exact(local_ctx, name, x64):
    data, build, py = EXPRESSIONS[name]
    want = [py(int(a), int(b)) for a, b in zip(data["a"], data["b"])]
    with jax.enable_x64(x64):
        t = ct.Table.from_pydict(local_ctx, data)
        out = plan.scan(t).with_columns({"e": build()}).execute()
        c = out.get_column(2)
        assert c.is_planes == (not x64 and c.dtype.type.name == "INT64")
        got = _ints(c)
    assert got == want
    assert out.column_names == ["a", "b", "e"] and out.row_mask is None


def test_a_later_expression_reads_an_earlier_one_and_nulls_carry(local_ctx):
    a = np.arange(-50, 50, dtype=I64) * (1 << 33)
    valid = np.arange(100) % 7 != 0
    with jax.enable_x64(False):
        t = ct.Table([Column.from_numpy(a, "a", valid),
                      Column.from_numpy(np.arange(100, dtype=I32), "b")],
                     local_ctx)
        out = plan.scan(t).with_columns({
            "x": col("a") + col("b"), "y": col("x") * 2 - col("b"),
            "z": col("b") * 3}).execute()
        x, y, z = out.get_column(2), out.get_column(3), out.get_column(4)
        assert np.array_equal(np.asarray(x.validity), valid)
        assert np.array_equal(np.asarray(y.validity), valid)
        assert z.validity is None and z.dtype.type.name == "INT32"
        got = [v for v, ok in zip(_ints(y), valid) if ok]
    assert got == [2 * (int(p) + q) - q
                   for p, q, ok in zip(a, range(100), valid) if ok]


OVERFLOWS = {
    "product_past_int64": (
        {"a": np.array([1, B62], I64), "b": np.array([1, 2], I64)},
        lambda: col("a") * col("b"), r"'e'.*\(a \* b\).*int64"),
    "inner_step_past_int64": (
        {"a": np.array([B62, 5], I64), "b": np.array([1, 4], I32)},
        lambda: (col("a") * 4) - col("a") * 3, r"'e'.*\(a \* 4\)"),
    "sum_past_int64": (
        {"a": np.array([B62, B62], I64), "b": np.array([B62, 1], I64)},
        lambda: col("a") + col("b"), r"'e'.*\(a \+ b\)"),
    "int32_column_past_int32": (
        {"a": np.array([46341, 2], I32), "b": np.array([46341, 3], I32)},
        lambda: col("a") * col("b"), r"'e'.*int32"),
}


@pytest.mark.parametrize("x64", [False, True], ids=["planes", "native"])
@pytest.mark.parametrize("name", list(OVERFLOWS))
def test_a_step_that_may_not_fit_raises_by_name(local_ctx, name, x64):
    data, build, pattern = OVERFLOWS[name]
    with jax.enable_x64(x64):
        t = ct.Table.from_pydict(local_ctx, data)
        before = telemetry.metrics_snapshot()
        with pytest.raises(CylonError, match=pattern):
            plan.scan(t).with_columns({"e": build()}).execute()
        # the probe ran, no column was made
        assert _delta(before, "cylon_expr_columns_total") == 0


def test_the_builder_refuses_what_is_not_an_integer_expression(local_ctx):
    t = ct.Table.from_pydict(local_ctx, {
        "a": np.arange(4, dtype=I32), "f": np.ones(4, np.float32),
        "s": np.array(list("abcd"))})
    lt = plan.scan(t)
    with pytest.raises(CylonError, match="integer"):
        lt.with_columns({"e": col("a") * 1.5})
    with pytest.raises(CylonError, match="float32"):
        lt.with_columns({"e": col("f") + 1})
    with pytest.raises(CylonError, match="str"):
        lt.with_columns({"e": col("s") + 1})
    with pytest.raises(CylonError, match="already"):
        lt.with_columns({"a": col("a") + 1})
    with pytest.raises(CylonError):
        lt.with_columns({"e": col("nope") + 1})
    assert lt.with_columns({"e": col("a") + 1, "g": col("e") * col(0)}
                           ).schema == ["a", "f", "s", "e", "g"]


def test_wideint_against_python_integers():
    """The word arithmetic itself, at the carries."""
    vals = [0, 1, -1, (1 << 32) - 1, 1 << 32, -(1 << 32), (1 << 63) - 1,
            -(1 << 63), 0x0000FFFF_FFFF0000, -0x0000FFFF_FFFF0001,
            123456789012345678, -98765432109876543]
    a = np.array([x for x in vals for _ in vals], I64)
    b = np.array([y for _ in vals for y in vals], I64)
    m64 = (1 << 64) - 1

    def pair(x):
        u = x.view(np.uint64)
        return ((u >> np.uint64(32)).astype(np.uint32),
                (u & np.uint64(0xFFFFFFFF)).astype(np.uint32))

    def back(p):
        hi, lo = (np.asarray(w).astype(object) for w in p)
        return [int(v) for v in (hi << 32) | lo]

    with jax.enable_x64(False):
        pa, pb = pair(a), pair(b)
        for fn, py in ((W.add, lambda x, y: x + y),
                       (W.sub, lambda x, y: x - y),
                       (W.mul, lambda x, y: x * y)):
            assert back(fn(pa, pb)) == [py(int(x), int(y)) & m64
                                        for x, y in zip(a, b)]
        for k in (0, 1, 21, 31, 32, 42, 63):
            assert back(W.wide_shl(pa, k)) == [(int(x) << k) & m64 for x in a]
            assert back(W.shr(pa, k)) == [(int(x) & m64) >> k for x in a]
        x32 = np.array([0, 1, -1, -(1 << 31), (1 << 31) - 1, 65536, -65537],
                       I32)
        i, j = np.repeat(x32, len(x32)), np.tile(x32, len(x32))
        assert back(W.mul_i32(i, j)) == [(int(p) * int(q)) & m64
                                         for p, q in zip(i, j)]
        assert W.range_of(np.asarray(W.minmax(pa))) == (min(vals), max(vals))
        for small in ([7, 1 << 31, (1 << 32) - 1, 12], [-7, -(1 << 32), 3],
                      [-5, -9], [(1 << 40) + 5, -2]):
            assert W.range_of(np.asarray(W.minmax(pair(
                np.array(small, I64))))) == (min(small), max(small))
        # the exact sum of many int64s takes three words
        for k in (0, 1, 22, 44, 63, 64, 95):
            got = W.wide_shl(W.wide_from_int32(np.array([-5, 7], I32), 3), k)
            assert [(int(a) << 64) | (int(b) << 32) | int(c)
                    for a, b, c in zip(*map(np.asarray, got))] \
                == [(v << k) & ((1 << 96) - 1) for v in (-5, 7)]
        big = W.wide_add(W.wide_const((1 << 95) - 1, 3),
                         W.wide_neg(W.wide_const(1 << 64, 3)))
        assert [int(w) for w in big] == [0x7FFFFFFE, 0xFFFFFFFF, 0xFFFFFFFF]
        fits = W.fits_int64(tuple(np.array(w, np.uint32) for w in zip(
            *(W.wide_const(v, 3) for v in (
                (1 << 63) - 1, 1 << 63, -(1 << 63), -(1 << 63) - 1, 0)))))
        assert np.asarray(fits).tolist() == [True, False, True, False, True]
        # a 64-bit value over a count in one float32, past 24 bits
        num = np.array([3, 10 ** 18 + 7, -(10 ** 17) - 1, 7 * 10 ** 10], I64)
        den = np.array([7, 75_004_737, 33_554_433, 3], I64)
        q = np.asarray(W.divide_float32(W.to_float32_pair(pair(num)),
                                        W.to_float32_pair(pair(den))))
    want = num.astype(object) / den.astype(object)
    assert q.dtype == np.float32
    assert np.all(np.abs(q.astype(float) - want.astype(float))
                  <= 1.01 * 2.0 ** -24 * np.abs(want.astype(float)))


# ---------------------------------------------------------------------------
# the dense table over several keys, and over 64-bit values
# ---------------------------------------------------------------------------

DENSE_CASES = {
    "two_keys": (2, False), "three_keys": (3, False),
    "nullable_keys": (2, True),
}


@pytest.mark.parametrize("name", list(DENSE_CASES))
def test_dense_groupby_over_several_keys_matches_the_sort_path(
        local_ctx, monkeypatch, name):
    nkeys, nullable = DENSE_CASES[name]
    r = np.random.default_rng(nkeys + 10 * nullable)
    n = 20_000
    with jax.enable_x64(False):
        cols = []
        for i, (lo, hi) in enumerate([(-3, 3), (100, 104), (0, 1)][:nkeys]):
            k = r.integers(lo, hi, n, endpoint=True).astype(
                [I32, np.int16, np.uint8][i])
            cols.append(Column.from_numpy(
                k, f"k{i}", r.random(n) > 0.1 if nullable else None))
        cols.append(Column.from_numpy(r.integers(-1000, 1000, n).astype(I32),
                                      "v", r.random(n) > 0.2))
        cols.append(Column.from_numpy(r.normal(size=n).astype(np.float32),
                                      "w"))
        t = ct.Table(cols, local_ctx).filter_mask(r.random(n) > 0.3)
        by, vals = list(range(nkeys)), [nkeys, nkeys, nkeys + 1, nkeys + 1]
        ops = ["sum", "count", "mean", "sum"]
        before = telemetry.metrics_snapshot()
        dense = t.groupby(by, vals, ops)
        assert _delta(before, "cylon_groupby_dense_keys_total") == nkeys
        assert dense.ordered_by(by) and dense.row_mask is not None
        # slot order is key order, nulls last: what a sort leaves
        compact = dense.compact()
        assert repr(compact.to_pandas().values.tolist()) \
            == repr(compact.sort(by).to_pandas().values.tolist())
        monkeypatch.setattr(G, "group_path", lambda *a, **k: "sort")
        sort = t.groupby(by, vals, ops)
        assert _delta(before, "cylon_groupby_dense_keys_total") == nkeys
        d, s = dense.to_pandas(), sort.to_pandas()
    assert len(d) == len(s) > 2 * nkeys
    s = s.sort_values(list(s.columns[:nkeys]), na_position="last")
    for j in range(nkeys):
        assert d.iloc[:, j].astype(object).where(d.iloc[:, j].notna(), None)\
            .tolist() == s.iloc[:, j].astype(object).where(
                s.iloc[:, j].notna(), None).tolist()
    for j in (nkeys, nkeys + 1):        # int32 sum, count: exact
        assert d.iloc[:, j].fillna(-1).tolist() \
            == s.iloc[:, j].fillna(-1).tolist()
    for j in (nkeys + 2, nkeys + 3):    # float32 mean and sum
        assert np.allclose(d.iloc[:, j], s.iloc[:, j], rtol=2e-5, atol=1e-4)


def test_keys_whose_values_are_known_are_not_probed(local_ctx):
    """Dictionary codes lie in [0, len(dictionary)) and a bool is 0 or 1:
    a groupby by such keys alone dispatches no probe and fetches nothing
    (here: 32-bit sums, so not even the second fetch); one integer key
    among them, or vocabularies past the slots, and the probe is back."""
    r = np.random.default_rng(9)
    n = 5000
    with jax.enable_x64(False):
        cols = {"s": np.array(list("xyz"))[r.integers(0, 3, n)],
                "b": r.random(n) > 0.5,
                "i": r.integers(5, 9, n).astype(I32),
                "v": r.integers(-50, 50, n).astype(I32)}
        t = ct.Table.from_pydict(local_ctx, cols)
        with telemetry.collect_phases() as cp:
            out = t.groupby(["s", "b"], ["v"], ["sum"]).to_pandas()
        assert cp.count("sync.groupby.keyrange") == 0 \
            and cp.count("sync.groupby.densegroups") == 0
        want = {}
        for s_, b, v in zip(cols["s"], cols["b"], cols["v"]):
            want[(s_, bool(b))] = want.get((s_, bool(b)), 0) + int(v)
        assert {(a, bool(b)): int(v) for a, b, v in out.values.tolist()} \
            == want
        assert out.values.tolist() == sorted(out.values.tolist())
        with telemetry.collect_phases() as cp:
            t.groupby(["s", "i"], ["v"], ["sum"])
        assert cp.count("sync.groupby.keyrange") == 1
        u = r.integers(0, 40, n)
        wide = ct.Table.from_pydict(local_ctx, {
            "s": np.array([f"k{i:03d}" for i in range(40)])[
                r.integers(0, 40, n)],
            "u": np.array([f"u{i:03d}" for i in range(40)])[u],
            "v": cols["v"]}).filter_mask(u < 3)
        with telemetry.collect_phases() as cp:      # 40 x 40 vocabularies
            out = wide.groupby(["s", "u"], ["v"], ["sum"])
        assert cp.count("sync.groupby.keyrange") == 1 \
            and out.ordered_by(["s", "u"])          # observed: 40 x 3


def test_group_path_takes_several_keys_by_the_product_of_their_slots():
    i32, S = np.dtype(I32), G.AggregationOp.SUM
    two = ([i32, np.dtype(np.int8)], [False, True], [i32], [S], 1000)
    assert G.group_path(*two) == "dense"                      # probe it
    assert G.group_path(*two, [32, 31]) == "dense"            # 32 x 32
    assert G.group_path(*two, [32, 32]) == "sort"             # 32 x 33
    assert G.group_path([i32, None], [False, False], [i32], [S], 1000) \
        == "sort"                                             # varbytes key
    assert G.group_path([i32, np.dtype(I64)], [False] * 2, [i32], [S],
                        1000) == "sort"
    with jax.enable_x64(False):
        wide = ([i32] * 2, [False] * 2, [G.PLANES_INT64] * 3,
                [S, G.AggregationOp.MEAN, G.AggregationOp.COUNT], 1000)
        assert G.group_path(*wide, [2, 3]) == "dense"
        assert G.group_path([i32], [False], [G.PLANES_INT64],
                            [G.AggregationOp.MIN], 1000) == "sort"


def test_dense_sums_of_int64_planes_are_exact_and_signed(local_ctx):
    """One key, 64-bit values of both signs over 40,000 rows with nulls
    and a row mask: SUM, MEAN and COUNT against Python integers."""
    r = np.random.default_rng(42)
    n = 40_000
    k = r.integers(0, 5, n).astype(I32)
    v = r.integers(-(1 << 44), 1 << 45, n).astype(I64)
    valid, live = r.random(n) > 0.25, r.random(n) > 0.2
    with jax.enable_x64(False):
        t = ct.Table([Column.from_numpy(k, "k"),
                      Column.from_numpy(v, "v", valid)],
                     local_ctx).filter_mask(live)
        with telemetry.collect_phases() as cp:
            out = t.groupby(0, [1, 1, 1], ["sum", "mean", "count"])
        assert out.row_mask is None and out.ordered_by([0])
        assert cp.count("sync.groupby.densegroups") == 1
        keys = _ints(out.get_column(0))
        sums, counts = _ints(out.get_column(1)), _ints(out.get_column(3))
        means = np.asarray(out.get_column(2).data)
        assert out.get_column(1).is_planes and means.dtype == np.float32
    assert keys == [0, 1, 2, 3, 4]
    for g in keys:
        rows = (k == g) & valid & live
        total = sum(int(x) for x in v[rows])
        assert sums[g] == total and counts[g] == int(rows.sum())
        assert abs(float(means[g]) - total / counts[g]) \
            <= 1.01 * 2.0 ** -24 * abs(total / counts[g])


def test_a_sum_that_may_not_fit_raises_by_name_and_never_wraps(local_ctx):
    v = np.full(64, (1 << 62) // 16, I64)       # 32 a group: 2^63 exactly
    k = (np.arange(64) % 2).astype(I32)
    with jax.enable_x64(False):
        t = ct.Table.from_pydict(local_ctx, {"k": k, "v": v})
        with pytest.raises(CylonError, match=r"sum of column 'v'.*int64"):
            t.groupby(0, [1], ["sum"])
        # the count alone reads no value: nothing to overflow
        out = t.groupby(0, [1], ["count"])
        assert _ints(out.get_column(1)) == [32, 32]
        # -2^63 IS an int64, and so is 2^63 - 32: exact, to the last unit
        for w in (-v, v - 1):
            out = ct.Table.from_pydict(local_ctx, {"k": k, "v": w}) \
                .groupby(0, [1, 1], ["sum", "mean"])
            assert _ints(out.get_column(1)) == [32 * int(w[0])] * 2
            assert np.asarray(out.get_column(2).data).tolist() \
                == [float(np.float32(int(w[0])))] * 2


def _programs(fn):
    """Dispatches of each named program while ``fn`` runs."""
    before = telemetry.metrics_snapshot()
    out = fn()
    now = telemetry.metrics_snapshot()
    return out, {k: v - before.get(k, 0) for k, v in now.items()
                 if isinstance(v, (int, float)) and v != before.get(k, 0)}


@pytest.mark.parametrize("x64", [False, True], ids=["planes", "native"])
def test_computed_columns_are_dispatched_on_a_guess_and_held_to_the_ranges(
        local_ctx, monkeypatch, x64):
    """The compute program goes out BEFORE the ranges are down, with the
    forms the last table of the shape proved. A table whose ranges prove
    other forms is computed again with those (exact: the guess's columns
    are dropped), one that proves nothing raises, and a table that
    proves the same forms is computed once."""
    from cylon_tpu.data import table as T

    order = []
    fetch = T._telemetry.host_fetch
    compute = T._expr_compute_program_fn

    def fetching(site, x):
        order.append("fetch")
        return fetch(site, x)

    def computing(*key):
        order.append(("compute", key[1]))
        return compute(*key)

    monkeypatch.setattr(T._telemetry, "host_fetch", fetching)
    monkeypatch.setattr(T, "_expr_compute_program_fn", computing)
    monkeypatch.setattr(T, "_last_forms", {})
    small = {"a": np.array([3, -4, 5], I64), "b": np.array([7, 8, -9], I64)}
    big = {"a": np.array([1 << 40, -5, 1], I64),
           "b": np.array([3, 1 << 20, -2], I64)}
    huge = {"a": np.array([B62, 1, 1], I64), "b": np.array([2, 1, 1], I64)}
    with jax.enable_x64(x64):
        def run(data):
            del order[:]
            t = ct.Table.from_pydict(local_ctx, data)
            out = t.with_columns(["e"], [("mul", ("col", 0), ("col", 1))])
            got = _ints(out.get_column(2))
            assert got == [int(p) * int(q)
                           for p, q in zip(data["a"], data["b"])]
            return [o if o == "fetch" else "compute" for o in order]

        if x64:     # no forms to choose: always before the fetch, once
            assert run(small) == run(big) == ["compute", "fetch"]
        else:
            assert run(small) == ["fetch", "compute"]       # nothing known
            narrow = order[-1][1]
            assert run(small) == ["compute", "fetch"]       # the same forms
            assert order[0][1] == narrow
            assert run(big) == ["compute", "fetch", "compute"]
            assert order[0][1] == narrow != order[-1][1]    # guessed, redone
            assert run(big) == ["compute", "fetch"]
            assert run(small) == ["compute", "fetch", "compute"]
        del order[:]
        with pytest.raises(CylonError, match=r"'e'.*\(a \* b\).*int64"):
            ct.Table.from_pydict(local_ctx, huge).with_columns(
                ["e"], [("mul", ("col", 0), ("col", 1))])
        assert order[-1] == "fetch"     # nothing follows a failed proof


def test_the_cut_is_dispatched_on_a_guess_and_held_to_the_count(
        local_ctx, monkeypatch):
    """A dense result with plane-held sums is cut to its live groups for
    the count the last groupby of the shape had, before its own count is
    down; where that is another it is cut again, and what comes back has
    this table's groups, no more and no fewer."""
    from cylon_tpu.data import table as T

    cuts = []
    cut = T._dense_cut_program
    monkeypatch.setattr(T, "_dense_cut_program",
                        lambda *a, n: cuts.append(n) or cut(*a, n=n))
    monkeypatch.setattr(T, "_last_live", {})
    v = (np.arange(1, 41, dtype=I64) << 36)

    def run(keys):
        del cuts[:]
        k = np.resize(np.array(keys, I32), 40)
        # the same slots every time: the observed range is [0, 7]
        k[:2] = (0, 7)
        out = ct.Table.from_pydict(local_ctx, {"k": k, "v": v}) \
            .groupby(0, [1, 1], ["sum", "count"])
        present = sorted(set(k.tolist()))
        assert out.row_mask is None and _ints(out.get_column(0)) == present
        assert _ints(out.get_column(1)) \
            == [sum(int(x) for x in v[k == g]) for g in present]
        assert _ints(out.get_column(2)) \
            == [int((k == g).sum()) for g in present]
        return list(cuts)

    with jax.enable_x64(False):
        assert run([0, 7, 3]) == [3]            # nothing known: after
        assert run([0, 7, 5]) == [3]            # three again: once, before
        assert run([0, 7, 1, 2, 4]) == [3, 5]   # five: cut again
        assert run([0, 7, 1, 2, 6]) == [5]
        assert run([0, 7]) == [5, 2]


def test_planes_the_dense_table_cannot_take_are_refused_by_name(local_ctx):
    r = np.random.default_rng(1)
    with jax.enable_x64(False):
        t = ct.Table.from_pydict(local_ctx, {
            "k": r.integers(0, 5000, 300).astype(I32),      # > 1,024 slots
            "v": r.integers(0, 9, 300).astype(I64),
            "f": r.normal(size=300)})                       # float64 planes
        for by, val, op in ((0, 1, "sum"), (0, 1, "min"), (0, 2, "sum"),
                            (1, 0, "sum")):
            with pytest.raises(CylonError, match="word planes"):
                t.groupby(by, [val], [op])


# ---------------------------------------------------------------------------
# the optimizer through the Compute node
# ---------------------------------------------------------------------------

def _pipeline(t):
    return (plan.scan(t)
            .with_columns({"x": col("a") * col("b"), "y": col("x") + col("c"),
                           "unused": col("d") * 2})
            .filter((col("c") > 2) & (col("b") <= 40))
            .groupby("k", ["y", "a"], ["sum", "sum"]))


def test_filter_goes_below_and_pruning_through_the_compute_node(local_ctx):
    r = np.random.default_rng(8)
    n = 3000
    data = {"k": r.integers(0, 7, n).astype(I32),
            "a": r.integers(-9, 9, n).astype(I32),
            "b": r.integers(0, 50, n).astype(I32),
            "c": r.integers(0, 9, n).astype(I32),
            "d": r.integers(0, 9, n).astype(I32),
            "e": r.integers(0, 9, n).astype(I32)}
    t = ct.Table.from_pydict(local_ctx, data)
    pipe = _pipeline(t)
    root, stats = pipe.optimized()
    text = plan.ir.format_plan(root)
    lines = [ln.strip() for ln in text.splitlines()]
    assert [ln.split("(")[0] for ln in lines] \
        == ["GroupBy", "Compute", "Filter", "Project", "Scan"]
    # d, e and the unused expression are gone; x stays for y
    assert "unused" not in lines[1] and "x=" in lines[1] and "y=" in lines[1]
    assert lines[3] == "Project(cols=[0, 1, 2, 3])"
    assert stats.filters_below_compute == 1 and stats.columns_pruned == 3
    assert "filters pushed below computed columns: 1" in stats.summary()
    fast = pipe.execute().to_pandas()
    slow = pipe.execute(optimize=False).to_pandas()
    keep = (data["c"] > 2) & (data["b"] <= 40)
    want = {}
    for g, a, b, c in zip(data["k"][keep], data["a"][keep], data["b"][keep],
                          data["c"][keep]):
        acc = want.setdefault(int(g), [0, 0])
        acc[0] += int(a) * int(b) + int(c)
        acc[1] += int(a)
    for df in (fast, slow):
        got = {int(g): [int(y), int(a)] for g, y, a in df.values.tolist()}
        assert got == want
    # a filter that reads a computed column stays above it
    above = plan.scan(t).with_columns({"x": col("a") * col("b")}) \
        .filter(col("x") > 0)
    root, stats = above.optimized()
    assert [type(nd).__name__ for nd in plan.ir.walk(root)] \
        == ["Filter", "Compute", "Scan"]
    assert stats.filters_below_compute == 0
    assert (above.execute().to_pandas()["x"] > 0).all()


def test_fingerprint_reads_the_expressions_and_is_stable(local_ctx):
    t = ct.Table.from_pydict(local_ctx, {
        c: np.arange(8, dtype=I32) for c in "kabcde"})
    fp = _pipeline(t).plan_fingerprint()
    assert fp == _pipeline(t).plan_fingerprint()
    other = ct.Table.from_pydict(local_ctx, {
        c: np.arange(50, dtype=I32) for c in "kabcde"})
    assert fp == _pipeline(other).plan_fingerprint()    # capacity-blind
    base = plan.scan(t)
    fps = {base.with_columns({"x": e}).plan_fingerprint()
           for e in (col("a") * col("b"), col("a") * col("c"),
                     col("a") + col("b"), col("a") * 3, col("a") * 4,
                     3 - col("a"), col("a") - 3)}
    assert len(fps) == 7
    assert base.with_columns({"x": col("a") * 3}).plan_fingerprint() \
        != base.with_columns({"y": col("a") * 3}).plan_fingerprint()
    from cylon_tpu.plan.verify import verify_plan

    root, _stats = _pipeline(t).optimized()
    assert verify_plan(root, 1) == []


def test_computed_columns_on_a_sharded_table_and_64_bit_sums_across_chips(
        dist_ctx):
    """Elementwise on a sharded table (x64 on: native int64 shards); a
    plane-held column cannot be distributed at all, so the distributed
    groupby never meets one - and says so by name."""
    from cylon_tpu.parallel import shard

    r = np.random.default_rng(3)
    n = 4096
    data = {"k": r.integers(0, 5, n).astype(I32),
            "a": r.integers(-(1 << 40), 1 << 40, n).astype(I64),
            "b": r.integers(0, 100, n).astype(I32)}
    t = shard.distribute(ct.Table.from_pydict(dist_ctx, data), dist_ctx)
    out = plan.scan(t).with_columns({"x": col("a") * (100 - col("b"))}) \
        .groupby("k", ["x"], ["sum"]).execute().to_pandas()
    want = {}
    for g, a, b in zip(data["k"], data["a"], data["b"]):
        want[int(g)] = want.get(int(g), 0) + int(a) * (100 - int(b))
    assert {int(g): int(x) for g, x in out.values.tolist()} == want
    with jax.enable_x64(False):
        planes = ct.Table.from_pydict(dist_ctx, {"k": data["k"],
                                                 "a": data["a"]})
        with pytest.raises(CylonError, match="word planes"):
            shard.distribute(planes, dist_ctx)


# ---------------------------------------------------------------------------
# satellites: string encoding, dates, truthful aggregate labels
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", ["U1", "U5", "one_value", "empty",
                                  "many_distinct"])
def test_fixed_width_strings_encode_as_the_general_path_does(case):
    r = np.random.default_rng(4)
    arr = {
        "U1": np.array(list("ANR"))[r.integers(0, 3, 5000)],
        "U5": np.array(["", "a", "ab", "abcde", "b", "é", "z"])[
            r.integers(0, 7, 5000)],
        "one_value": np.array(["x"] * 10),
        "empty": np.array([], dtype="<U3"),
        "many_distinct": np.array([f"row{i:06d}" for i in range(3000)]),
    }[case]
    fast = Column._encode_fixed_width(arr, "s")
    slow = Column._encode_strings(arr.astype(object), "s", None)
    if case == "many_distinct":        # past the dictionary's threshold
        assert fast is None and slow.is_varbytes
        assert Column.from_numpy(arr, "s").is_varbytes
        return
    assert Column.from_numpy(arr, "s").dictionary.tolist() \
        == fast.dictionary.tolist() == slow.dictionary.tolist()
    assert np.array_equal(np.asarray(fast.data), np.asarray(slow.data))
    assert fast.data.dtype == slow.data.dtype and fast.validity is None
    assert fast.to_numpy().tolist() == arr.tolist()


def test_a_date_is_32_bits(local_ctx):
    days = np.array(["1998-09-02", "1970-01-01", "1969-12-31"],
                    "datetime64[D]")
    with jax.enable_x64(False):
        c = Column.from_numpy(days, "d")
        assert c.dtype == dtypes.Date32() and not c.is_planes
        assert np.asarray(c.data).tolist() == [10471, 0, -1]
        assert c.to_numpy().tolist() == days.tolist()
        t = ct.Table([c], local_ctx)
        assert plan.scan(t).filter(col("d") <= 10471).execute().row_count == 3
    us = Column.from_numpy(days.astype("datetime64[us]"), "t")
    assert us.dtype.type.name == "TIMESTAMP"


@pytest.mark.parametrize("x64", [False, True], ids=["x32", "x64"])
def test_count_and_mean_are_labelled_as_the_arrays_they_are(local_ctx, x64):
    with jax.enable_x64(x64):
        t = ct.Table.from_pydict(local_ctx, {
            "k": (np.arange(3000) % 2000).astype(I32),   # past the dense table
            "d": (np.arange(3000) % 3).astype(I32),
            "v": np.arange(3000, dtype=I32)})
        for by in ("k", "d"):
            out = t.groupby(by, ["v", "v", "v"], ["count", "mean", "sum"])
            for c in out.columns():
                assert c.dtype.np_dtype == np.dtype(c.data.dtype), c.name
            labels = [c.dtype.type.name for c in out.columns()]
            assert labels == (["INT32", "INT64", "DOUBLE", "INT32"] if x64
                              else ["INT32", "INT32", "FLOAT", "INT32"])
