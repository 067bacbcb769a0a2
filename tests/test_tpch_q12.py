"""TPC-H Q12 through the plan, and the mechanisms it is made of (PR 46).

- A predicate over two columns (``plan/ir.ColCmp`` -> ``data/table.
  compare_columns``) and a predicate as a value (``case_when`` ->
  ``("case", predicate tokens)`` -> ``ops/expr.predicate``).
- Predicates pushed through a join (``plan/optimizer.pushdown_filters``)
  and the side's own columns pruned before the join carries them.
- A filtered table compacted on the device before the join sorts its slots
  (``data/table.compact_live``, ``plan.compact``).

The chip runs with x64 off; this suite runs with it on (conftest.py), so
the cases that are about what the chip runs take both.
"""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

import cylon_tpu as ct
from cylon_tpu import plan, telemetry, util
from cylon_tpu.data import table as T
from cylon_tpu.data.column import Column
from cylon_tpu.ops import groupby as G
from cylon_tpu.parallel import shard
from cylon_tpu.plan import case_when, col
from cylon_tpu.status import CylonError, CylonPlanError

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmarks")
I32, F32 = np.int32, np.float32
OPS = {"eq": lambda a, b: a == b, "ne": lambda a, b: a != b,
       "lt": lambda a, b: a < b, "gt": lambda a, b: a > b,
       "le": lambda a, b: a <= b, "ge": lambda a, b: a >= b}


def _code(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"q12_{kind}_{name}", os.path.join(BENCH, kind, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _data(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def _delta(before, prefix):
    now = telemetry.metrics_snapshot()
    return sum(v - before.get(k, 0) for k, v in now.items()
               if k.startswith(prefix) and isinstance(v, (int, float)))


def _host_result(table):
    mask = None if table.row_mask is None else np.asarray(table.row_mask)
    cols = [np.asarray(c.data) if mask is None else np.asarray(c.data)[mask]
            for c in table.columns()]
    return {"names": list(table.column_names), "columns": cols,
            "nulls": sum(int((~np.asarray(c.validity))[
                slice(None) if mask is None else mask].sum())
                for c in table.columns() if c.validity is not None)}


def _live(table):
    """The live rows of a table as one list of tuples, in row order."""
    return list(zip(*[c.tolist() for c in _host_result(table)["columns"]]))


# ---------------------------------------------------------------------------
# the whole query against the plain reference
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def q12():
    config, traffic = _data("configs", "tpch-sf100-q12"), \
        _data("traffic", "tpch-q12")
    tables = _code("generators", config["generator"]).generate(
        config, traffic, 1, 0.002, 2147483659)["tables"]   # 150,000 lines
    ref_mod = _code("references", config["reference"])
    return (ref_mod, ref_mod.reference(tables, config, traffic), tables,
            traffic, _code("queries", "tpch_q12"))


def _below_by_hand(tables, traffic):
    """Q12 with the five predicates placed under the join by hand."""
    line = plan.scan(tables["lineitem"])
    first, second = traffic["shipmodes"]
    kept = line.filter(
        ((col("l_shipmode") == first) | (col("l_shipmode") == second))
        & (col("l_commitdate") < col("l_receiptdate"))
        & (col("l_shipdate") < col("l_commitdate"))
        & (col("l_receiptdate") >= int(traffic["receiptdate_min"]))
        & (col("l_receiptdate") < int(traffic["receiptdate_max"])))
    joined = plan.scan(tables["orders"]).join(
        kept, left_on="o_orderkey", right_on="l_orderkey")
    priority, shipmode = joined.schema[1], joined.schema[6]
    return (joined.with_columns({
        "high_line_count": case_when((col(priority) == "1-URGENT")
                                     | (col(priority) == "2-HIGH")),
        "low_line_count": case_when((col(priority) != "1-URGENT")
                                    & (col(priority) != "2-HIGH"))})
        .groupby(shipmode, ["high_line_count", "low_line_count"],
                 ["sum", "sum"]).sort(shipmode))


@pytest.mark.parametrize("x64", [False, True], ids=["x32", "x64"])
def test_q12_through_the_plan_matches_the_reference_exactly(local_ctx, q12,
                                                            x64):
    """The cell's own query, written in the specification's order: both
    counts exact; the five conjuncts under the join on LINEITEM's side,
    the three dates pruned above them, the filtered table counted and
    compacted on the device, so the join's sort is handed the orders and
    the compacted capacity; no range probe for the case_when columns, the
    dense table, the sort elided. Written with the predicates placed under
    the join by hand it gives the same table bit for bit."""
    ref_mod, ref, host, traffic, query = q12
    with jax.enable_x64(x64):
        tables = {n: ct.Table.from_pydict(local_ctx, t)
                  for n, t in host.items()}
        pipe = query.build(plan, tables, traffic)
        text = pipe.explain()
        before = telemetry.metrics_snapshot()
        with telemetry.collect_phases() as cp:
            out = pipe.execute()
        got = _host_result(out)
        counted = {name: _delta(before, name) for name in (
            "cylon_plan_filters_below_join_total",
            "cylon_compact_rows_in_total", "cylon_compact_rows_out_total",
            "cylon_compact_streams_total", "cylon_join_plan_sort_rows_total",
            "cylon_expr_columns_total",
            'cylon_groupby_reduce_path_total{path="dense"')}
        by_hand = _below_by_hand(tables, traffic)
        hand_text = by_hand.explain()
        again = _host_result(by_hand.execute())
    numbers = ref_mod.compare(got, ref)
    assert [n["name"] for n in numbers if not n["value"] <= n["limit"]] == []
    assert len(numbers) == 5 and ref_mod.rows_out(ref) == 2
    for a, b in zip(got["columns"], again["columns"]):
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes()
    lines = [ln.strip() for ln in text.splitlines()]
    assert [ln.split("(")[0] for ln in lines[:8]] == [
        "Sort", "GroupBy", "Compute", "Join", "Scan", "Project", "Filter",
        "Scan"]
    assert lines[5] == "Project(cols=[0, 4])"
    assert "5 conjunct(s) pushed below a join" in lines[6]
    assert "conjuncts pushed below a join: 5" in text
    # the plan the optimizer makes IS the hand-placed one
    assert [ln.split("(")[0] for ln in hand_text.splitlines()[:8]] \
        == [ln.split("(")[0] for ln in text.splitlines()[:8]]
    n_orders, n_lines = (t.capacity for t in tables.values())
    cap = util.capacity(ref["rows_kept"])
    assert list(counted.values()) == [
        5, n_lines, ref["rows_kept"], 2, n_orders + cap, 2, 1], counted
    for span in ("plan.compact", "sync.compact.count", "sync.join.count",
                 "plan.compute", "plan.sort"):
        assert cp.count(span) == 1, span
    assert cp.count("sync.expr.range") == 0
    assert cp.count("sync.groupby.groups") == 0


def test_q12_example_runs(local_ctx):
    path = os.path.join(os.path.dirname(BENCH), "examples",
                        "tpch_q12_example.py")
    spec = importlib.util.spec_from_file_location("q12_example", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main(scale=0.0005) == 0


# ---------------------------------------------------------------------------
# a predicate over two columns
# ---------------------------------------------------------------------------

def _pair(kind, n=600, nulls=True):
    r = np.random.default_rng(11)
    if kind == "float32":
        a = r.integers(-4, 5, n).astype(F32) / 2
        b = r.integers(-4, 5, n).astype(F32) / 2
    else:
        a = r.integers(-5, 6, n).astype(I32)
        b = r.integers(-5, 6, n).astype(I32)
    va = r.random(n) > 0.2 if nulls else np.ones(n, bool)
    vb = r.random(n) > 0.2 if nulls else np.ones(n, bool)
    return a, b, va, vb


def _table(ctx, kind, a, b, va, vb):
    if kind == "date32":
        a, b = (x.astype("datetime64[D]") for x in (a, b))
    return ct.Table([Column.from_numpy(a, "a", va), Column.from_numpy(
        b, "b", vb), Column.from_numpy(np.arange(len(a), dtype=I32), "i")],
        ctx)


@pytest.mark.parametrize("kind", ["int32", "float32", "date32"])
@pytest.mark.parametrize("op", list(OPS))
def test_two_columns_compare_and_a_null_on_either_side_is_false(local_ctx,
                                                                 op, kind):
    a, b, va, vb = _pair(kind)
    with jax.enable_x64(False):
        t = _table(local_ctx, kind, a, b, va, vb)
        assert not t.get_column(0).is_planes
        pred = getattr(col("a"), f"__{op}__")(col("b"))
        out = plan.scan(t).filter(pred).execute()
        kept = np.asarray(out.get_column(2).data)[np.asarray(out.row_mask)]
    want = np.flatnonzero(OPS[op](a, b) & va & vb)
    assert kept.tolist() == want.tolist() and len(want) > 20
    # and under NOT, as a compare with a literal has it: a plain complement
    with jax.enable_x64(False):
        out = plan.scan(t).filter(~pred).execute()
        kept = np.asarray(out.get_column(2).data)[np.asarray(out.row_mask)]
    assert kept.tolist() == np.flatnonzero(~(OPS[op](a, b) & va & vb)).tolist()


def test_what_two_columns_cannot_compare_is_refused_by_name(local_ctx):
    n = 64
    r = np.random.default_rng(2)
    t = ct.Table.from_pydict(local_ctx, {
        "d1": np.array(["x", "y"])[r.integers(0, 2, n)],
        "d2": np.array(["x", "z"])[r.integers(0, 2, n)],
        "v1": np.array([f"row{i:04d}" for i in range(n)]),
        "w1": r.integers(0, 9, n).astype(np.int64),
        "w2": r.integers(0, 9, n).astype(np.int64),
        "i": r.integers(0, 9, n).astype(I32),
        "f": r.integers(0, 9, n).astype(F32)})
    assert t.get_column(2).is_varbytes and t.get_column(0).is_string
    lt = plan.scan(t)
    for a, b, what in (("d1", "d2", "string"), ("v1", "d1", "string"),
                       ("w1", "w2", "64-bit"), ("i", "f", "two types"),
                       ("i", "w1", "two types")):
        with pytest.raises(CylonPlanError) as e:
            lt.filter(col(a) < col(b))
        assert repr(a) in str(e.value) and repr(b) in str(e.value) \
            and what in str(e.value), str(e.value)
        with pytest.raises(CylonPlanError):     # inside a case_when too
            lt.with_columns({"c": case_when(col(a) == col(b))})
    with pytest.raises(CylonPlanError):
        col("i") < (col("f") + 1)
    with pytest.raises(CylonPlanError):
        case_when(col("i"))
    # the table's own gate, for a plan built by hand
    with pytest.raises(CylonError, match="'d1' and 'd2'.*dictionary"):
        T.compare_columns(t, 0, "lt", 1)
    with pytest.raises(CylonError, match="varbytes"):
        T.compare_columns(t, 2, "eq", 0)
    with jax.enable_x64(False):
        planes = ct.Table.from_pydict(local_ctx, {
            "w1": np.arange(8, dtype=np.int64),
            "w2": np.arange(8, dtype=np.int64)})
        with pytest.raises(CylonError, match="'w1'.*word planes"):
            T.compare_columns(planes, 0, "lt", 1)


# ---------------------------------------------------------------------------
# a predicate as a value
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("x64", [False, True], ids=["x32", "x64"])
def test_case_when_is_one_zero_and_zero_for_a_null(local_ctx, x64):
    a, b, va, vb = _pair("int32")
    words = np.array(["MAIL", "SHIP", "AIR"])
    s = words[np.random.default_rng(3).integers(0, 3, len(a))]
    with jax.enable_x64(x64):
        t = ct.Table([Column.from_numpy(a, "a", va),
                      Column.from_numpy(b, "b", vb),
                      Column.from_numpy(s, "s")], local_ctx)
        before = telemetry.metrics_snapshot()
        with telemetry.collect_phases() as cp:
            out = plan.scan(t).with_columns({
                "lt": case_when(col("a") < col("b")),
                "in": case_when((col("s") == "MAIL") | (col("s") == "SHIP")),
                "out": case_when((col("s") != "MAIL") & (col("s") != "SHIP")),
                "none": case_when(col("s") == "FOB"),
                "not_none": case_when(col("s") != "FOB"),
                "three": case_when(col("a") > 3),
                "mixed": 10 * case_when(col("a") >= col("b")) - col("a")
                + case_when(~(col("lt") == 1)) * 100,
            }).execute()
        got = {c.name: (np.asarray(c.data), c.validity)
               for c in out.columns()}
    lt = (a < b) & va & vb
    assert got["lt"][0].tolist() == lt.astype(int).tolist()
    assert got["lt"][0].dtype == I32 and got["lt"][1] is None
    assert got["in"][0].tolist() == np.isin(s, ["MAIL", "SHIP"]).astype(
        int).tolist()
    assert (got["in"][0] + got["out"][0] == 1).all()
    assert not got["none"][0].any() and got["not_none"][0].all()
    assert got["three"][0].tolist() == ((a > 3) & va).astype(int).tolist()
    # arithmetic around it: null where the VALUE column a is null, not
    # where only a predicate read a null
    mixed, valid = got["mixed"]
    want = 10 * ((a >= b) & va & vb).astype(int) - a + 100 * (~lt)
    assert np.asarray(valid).tolist() == va.tolist()
    assert mixed[va].tolist() == want[va].tolist()
    # only `a` is probed (the one column a value reads): one fetch
    assert cp.count("sync.expr.range") == 1
    assert _delta(before, "cylon_expr_columns_total") == 7


def test_case_when_alone_is_neither_probed_nor_fetched(local_ctx):
    a, b, va, vb = _pair("int32", nulls=False)
    with jax.enable_x64(False):
        t = ct.Table.from_pydict(local_ctx, {"a": a, "b": b})
        with telemetry.collect_phases() as cp:
            out = plan.scan(t).with_columns(
                {"c": case_when(col("a") <= col("b"))}).execute()
        assert np.asarray(out.get_column(2).data).tolist() \
            == (a <= b).astype(int).tolist()
    assert not [label for label in cp.labels if label.startswith("sync.")]
    with pytest.raises(CylonError, match="'v'.*varbytes|varbytes"):
        ct.Table.from_pydict(local_ctx, {
            "v": np.array([f"row{i:04d}" for i in range(64)])}
        ).with_columns(["c"], [("case", ("cmp", 0, "eq", "row0001"))])
    with pytest.raises(CylonError, match="with a number"):
        t.with_columns(["c"], [("case", ("cmp", 0, "eq", "x"))])


@pytest.mark.parametrize("path", ["dense", "sort"])
def test_case_when_summed_counts_rows_on_both_groupby_paths(
        local_ctx, monkeypatch, path):
    if path == "sort":
        monkeypatch.setattr(G, "group_path", lambda *a, **k: "sort")
    r = np.random.default_rng(5)
    n = 4000
    k = r.integers(0, 6, n).astype(I32)
    s = np.array(["1-URGENT", "2-HIGH", "3-MEDIUM", "5-LOW"])[
        r.integers(0, 4, n)]
    with jax.enable_x64(False):
        t = ct.Table.from_pydict(local_ctx, {"k": k, "s": s})
        before = telemetry.metrics_snapshot()
        out = plan.scan(t).with_columns({
            "high": case_when((col("s") == "1-URGENT")
                              | (col("s") == "2-HIGH")),
            "low": case_when((col("s") != "1-URGENT")
                             & (col("s") != "2-HIGH"))}
        ).groupby("k", ["high", "low"], ["sum", "sum"]).sort("k").execute()
        rows = _live(out)
    assert _delta(before, 'cylon_groupby_reduce_path_total{path="dense"') \
        == (path == "dense")
    high = np.isin(s, ["1-URGENT", "2-HIGH"])
    assert rows == [(g, int((high & (k == g)).sum()),
                     int((~high & (k == g)).sum())) for g in range(6)]


def test_fingerprints_tell_column_compares_and_cases_apart(local_ctx):
    t = ct.Table.from_pydict(local_ctx, {
        "a": np.arange(8, dtype=I32), "b": np.arange(8, dtype=I32),
        "c": np.arange(8, dtype=I32)})
    lt = plan.scan(t)
    shapes = [lt.filter(col("a") < col("b")), lt.filter(col("a") < col("c")),
              lt.filter(col("b") < col("a")), lt.filter(col("a") <= col("b")),
              lt.filter(col("a") < 1),
              lt.with_columns({"x": case_when(col("a") < col("b"))}),
              lt.with_columns({"x": case_when(col("a") < col("c"))}),
              lt.with_columns({"x": case_when(col("a") < 1)}),
              lt.with_columns({"x": case_when(col("a") < 2)}),
              lt.with_columns({"x": case_when(~(col("a") < 2))}),
              lt.with_columns({"x": case_when(col("a") < 2) + 0})]
    prints = [q.plan_fingerprint() for q in shapes]
    assert len(set(prints)) == len(prints)
    assert lt.filter(col("a") < col("b")).plan_fingerprint() == prints[0]
    other = plan.scan(ct.Table.from_pydict(local_ctx, {
        "a": np.arange(9, dtype=I32), "b": np.arange(9, dtype=I32),
        "c": np.arange(9, dtype=I32)}))
    assert other.with_columns({"x": case_when(col("a") < col("b"))}
                              ).plan_fingerprint() == prints[5]
    assert "case_when(c0 < c1)" in shapes[5].explain()
    assert "c0 lt c1" in shapes[0].explain()


# ---------------------------------------------------------------------------
# predicates pushed through a join
# ---------------------------------------------------------------------------

def _sides(ctx, distribute=False):
    r = np.random.default_rng(9)
    left = ct.Table.from_pydict(ctx, {
        "k": r.integers(0, 40, 160).astype(I32),
        "x": r.integers(0, 10, 160).astype(I32),
        "y": r.integers(0, 10, 160).astype(I32)})
    right = ct.Table.from_pydict(ctx, {
        "k": r.integers(20, 60, 120).astype(I32),
        "u": r.integers(0, 10, 120).astype(I32),
        "w": r.integers(0, 10, 120).astype(I32)})
    if distribute:
        left, right = shard.distribute(left, ctx), shard.distribute(right,
                                                                    ctx)
    return left, right


def _rows(table):
    df = table.to_pandas()
    return sorted(tuple(None if pd.isna(v) else int(v) for v in row)
                  for row in df.itertuples(index=False))


# per join type: conjuncts pushed (of the four one-sided ones written; the
# fifth reads both sides and always stays above)
PUSHED = {"inner": 4, "left": 2, "right": 2, "full_outer": 0}


@pytest.mark.parametrize("world", [1, 4])
@pytest.mark.parametrize("how", list(PUSHED))
def test_conjuncts_go_below_a_join_only_where_the_answer_keeps(
        local_ctx, dist_ctx, how, world):
    ctx = local_ctx if world == 1 else dist_ctx
    left, right = _sides(ctx, distribute=world > 1)
    q = plan.scan(left).join(plan.scan(right), how, on="k").filter(
        (col("lt-1") < 7) & (col("lt-1") <= col("lt-2"))
        & (col("rt-4") > 1) & (col("rt-5") != col("rt-4"))
        & (col("lt-2") < col("rt-5")))
    root, stats = q.optimized()
    text = q.explain()
    assert stats.filters_below_join == PUSHED[how], text
    top = root.children[0] if isinstance(root, plan.Project) else root
    assert isinstance(top, plan.Filter) and isinstance(
        top.children[0], plan.Join)      # what reads both sides stays
    assert len(text.split("pushed below a join")) - 1 \
        == {0: 0, 2: 2, 4: 3}[PUSHED[how]]   # a side each, and the summary
    if world > 1 and PUSHED[how]:
        # the pushed filter went on below the side's Shuffle marker
        assert stats.filters_pushed >= 1
        join = top.children[0]
        sides = [c for c in join.children if isinstance(c, plan.Shuffle)]
        assert sides and all(not isinstance(c, plan.Filter)
                             for c in join.children)
    pushed = q.execute()
    plain = q.execute(optimize=False)
    assert _rows(pushed) == _rows(plain) and len(_rows(plain)) > 3
    # against pandas, for the inner join
    if how == "inner":
        m = left.to_pandas().merge(right.to_pandas(), on="k")
        m = m[(m.x < 7) & (m.x <= m.y) & (m.u > 1) & (m.w != m.u)
              & (m.y < m.w)]
        assert len(m) == len(_rows(pushed))


def test_a_filter_goes_through_a_compute_and_then_a_join(local_ctx):
    left, right = _sides(local_ctx)
    q = (plan.scan(left).join(plan.scan(right), on="k")
         .with_columns({"s": col("lt-1") + col("rt-4")})
         .filter((col("rt-5") >= 3) & (col("lt-2") < 5))
         .project(["lt-0", "s"]))
    root, stats = q.optimized()
    assert stats.filters_below_compute == 1 and stats.filters_below_join == 2
    text = q.explain()
    # each side keeps its key and what the result needs, the predicate's
    # own column goes before the join
    assert text.count("Project(cols=[0, 1])") == 2, text
    assert _rows(q.execute()) == _rows(q.execute(optimize=False))
    # an OR over both sides is one conjunct that reads both: it stays
    q2 = plan.scan(left).join(plan.scan(right), on="k").filter(
        (col("lt-1") < 3) | (col("rt-4") < 3))
    assert q2.optimized()[1].filters_below_join == 0


@pytest.mark.parametrize("shape,world", [("union", 1), ("join", 1),
                                         ("union", 4)])
def test_a_join_under_two_parents_is_filtered_under_one_alone(
        local_ctx, dist_ctx, shape, world):
    """`j.filter(p).union(j)` holds the ONE `Join` node twice: the conjuncts
    pushed for the filtered branch may not reach the other."""
    ctx = local_ctx if world == 1 else dist_ctx
    left, right = _sides(ctx, distribute=world > 1)
    j = plan.scan(left).join(plan.scan(right), on="k")
    f = j.filter((col("lt-1") < 3) & (col("rt-4") > 6))
    q = f.union(j) if shape == "union" else f.join(j, on="lt-0")
    root, stats = q.optimized()
    assert stats.filters_below_join == 2

    def joins(n):
        return [n] * isinstance(n, plan.Join) + [
            x for c in n.children for x in joins(c)]

    # the unfiltered branch's join reads no filter on either side
    def filtered(n):
        return isinstance(n, plan.Filter) or (
            not isinstance(n, plan.Join)
            and any(filtered(c) for c in n.children))

    bare = [n for n in joins(root)
            if not any(filtered(c) for c in n.children)
            and not any(isinstance(c, plan.Join) for c in n.children)]
    assert len(bare) == 1, q.explain()
    pushed, plain = _rows(q.execute()), _rows(q.execute(optimize=False))
    assert pushed == plain
    m = left.to_pandas().merge(right.to_pandas(), on="k")
    kept = m[(m.x < 3) & (m.u > 6)]
    assert 0 < len(kept) < len(m)
    if shape == "union":
        assert len(plain) == len(m.drop_duplicates())
    else:
        assert len(plain) == len(kept.merge(m, on="k"))


# ---------------------------------------------------------------------------
# device compaction
# ---------------------------------------------------------------------------

def _masked(ctx, n, live, seed=0, nullable=True, wide=False):
    r = np.random.default_rng(seed)
    mask = np.zeros(n, bool)
    mask[r.permutation(n)[:live]] = True
    cols = [Column.from_numpy(r.integers(-9, 9, n).astype(I32), "i",
                              r.random(n) > 0.3 if nullable else None),
            Column.from_numpy(r.standard_normal(n).astype(F32), "f"),
            Column.from_numpy(np.array(["MAIL", "SHIP", "AIR"])[
                r.integers(0, 3, n)], "s"),
            Column.from_numpy(r.integers(0, 2, n).astype(bool), "b",
                              r.random(n) > 0.5 if nullable else None),
            Column.from_numpy(r.integers(-300, 300, n).astype(np.int16),
                              "h")]
    if wide:
        cols.append(Column.from_numpy(
            r.integers(-2 ** 40, 2 ** 40, n).astype(np.int64), "w"))
    return ct.Table(cols, ctx, jnp.asarray(mask)), mask


def _same_live_rows(a, b):
    da, db = a.to_pandas(), b.to_pandas()
    assert list(da.columns) == list(db.columns) and len(da) == len(db)
    for name in da.columns:
        x, y = da[name].to_numpy(), db[name].to_numpy()
        assert x.dtype == y.dtype, name
        assert pd.isna(x).tolist() == pd.isna(y).tolist(), name
        keep = ~pd.isna(x)
        assert x[keep].tolist() == y[keep].tolist(), name


@pytest.mark.parametrize("x64", [False, True], ids=["x32", "x64"])
@pytest.mark.parametrize("n,live", [
    (4096, 37), (4096, 0), (4096, 512), (4096, 513), (4096, 1024),
    (5000, 1), (3000, 700),
    # both sides of the line (PR 50): 63% alive is cut, 90% is not; 3456
    # rows are 27 x 128 slots, the last capacity under 7/8 of 4096, and
    # one row more takes the next (28 x 128: an eighth exactly, left)
    (4096, 2580), (4096, 3686), (4096, 3456), (4096, 3457)])
def test_compact_live_is_table_compact_without_a_host_index(local_ctx, n,
                                                             live, x64):
    """Against `Table.compact()` (the host's `flatnonzero`): the same live
    rows in the same order, validity and all; ONE fetch (the count), no
    index array from or to the host; a plane-held column as two streams.
    Cut wherever at least an eighth of the slots go, to the 16-an-octave
    grid's capacity and not the octave's."""
    with jax.enable_x64(x64):
        t, mask = _masked(local_ctx, n, live, seed=n + live, wide=True)
        assert t.get_column(5).is_planes == (not x64)
        before = telemetry.metrics_snapshot()
        with telemetry.collect_phases() as cp:
            out, info = T.compact_live(t)
        cap = util.capacity(live)
        assert live <= cap <= max(live + live // 16, 1)
        assert info["rows_out"] == live and info["capacity"] == cap
        assert info["compacted"] == (8 * cap < 7 * n)
        assert info["compacted"] == (live not in (3686, 3457))
        assert cp.labels == ["sync.compact.count"]
        assert _delta(before, "cylon_host_syncs_total") == 1
        if not info["compacted"]:
            assert out is t
            return
        assert out.capacity == cap and out.row_count == live
        assert info["streams"] == 7 + 1    # w: two words; two masks: one
        assert _delta(before, "cylon_compact_streams_total") == info["streams"]
        assert _delta(before, "cylon_compact_rows_in_total") == n
        assert _delta(before, "cylon_compact_rows_out_total") == live
        assert _delta(before, "cylon_compact_slots_out_total") == cap
        assert np.asarray(out.row_mask).tolist() \
            == (np.arange(cap) < live).tolist()
        assert [c.name for c in out.columns()] == list("ifsbh") + ["w"]
        assert out.get_column(2).dictionary.tolist() \
            == t.get_column(2).dictionary.tolist()
        assert out.get_column(5).is_planes == (not x64)
        _same_live_rows(out, t.compact())
        # the count is memoised on the mask's buffer: no second fetch
        with telemetry.collect_phases() as cp:
            T.compact_live(t)
        assert cp.labels == []


@pytest.mark.parametrize("cap", [320, 512], ids=["grid", "octave"])
def test_the_stream_kernel_compacts_as_the_xla_path_does(local_ctx,
                                                          monkeypatch, cap):
    """On a TPU the program holds the Pallas pass `stream_compact`; here
    under the interpreter, against the CPU's own path: at the capacity
    `compact_live` gives 311 rows (20 x 16 slots: no power of two, no
    whole (8, 128) tile) and at the octave's."""
    with jax.enable_x64(False):
        t, _mask = _masked(local_ctx, 40_000, 311, seed=4, wide=True)
        plain, _info = T.compact_live(t)
        arrays = [c.data for c in t.columns()]
        valids = [c.validity for c in t.columns() if c.validity is not None]
        monkeypatch.setattr(T, "COMPACT_BLOCK_ROWS", 16)
        got, gv, live = T._compact_program_fn(cap, "stream", True)(
            t.row_mask, arrays, valids)
        want, wv, wlive = T._compact_program_fn(cap, "xla")(
            t.row_mask, arrays, valids)
    assert np.asarray(live).tolist() == np.asarray(wlive).tolist()
    for a, b in zip(list(got) + list(gv), list(want) + list(wv)):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.asarray(a)[..., :311].tobytes() \
            == np.asarray(b)[..., :311].tobytes()
    assert plain.capacity == 320 == util.capacity(311)


def test_what_is_not_compacted_stays_as_it_is(local_ctx, dist_ctx):
    t, _m = _masked(local_ctx, 4096, 3700, nullable=False)    # 29 x 128
    before = telemetry.metrics_snapshot()
    out, info = T.compact_live(t)
    assert out is t and not info["compacted"] and info["capacity"] == 3712
    assert _delta(before, "cylon_compact_") == 0
    bare = ct.Table.from_pydict(local_ctx, {"a": np.arange(64, dtype=I32)})
    with telemetry.collect_phases() as cp:
        assert T.compact_live(bare)[0] is bare
    assert cp.labels == []
    v = ct.Table.from_pydict(local_ctx, {
        "v": np.array([f"row{i:05d}" for i in range(4096)])})
    v = v.filter_mask(jnp.arange(4096) < 3)
    assert v.get_column(0).is_varbytes and T.compact_live(v)[0] is v
    sharded = shard.distribute(ct.Table.from_pydict(dist_ctx, {
        "a": np.arange(4096, dtype=I32)}), dist_ctx)
    sharded = sharded.filter_mask(jnp.arange(sharded.capacity) < 3)
    with telemetry.collect_phases() as cp:
        assert T.compact_live(sharded)[0] is sharded
    assert cp.labels == []


def test_a_join_compacts_a_filtered_side_and_only_on_one_chip(local_ctx,
                                                               dist_ctx):
    r = np.random.default_rng(1)
    n = 6000
    host = {"k": r.integers(0, 500, n).astype(I32),
            "v": r.integers(0, 100, n).astype(I32)}
    dim = {"k": np.arange(500, dtype=I32),
           "d": r.integers(0, 9, 500).astype(I32)}

    def query(ctx, distribute, bound):
        fact, small = (ct.Table.from_pydict(ctx, h) for h in (host, dim))
        if distribute:
            fact, small = (shard.distribute(t, ctx) for t in (fact, small))
        return plan.scan(small).join(plan.scan(fact), on="k").filter(
            col("rt-3") < bound)

    q = query(local_ctx, False, 3)
    before = telemetry.metrics_snapshot()
    with telemetry.collect_phases() as cp:
        out = q.execute()
    live = int((host["v"] < 3).sum())
    assert cp.count("plan.compact") == 1 \
        and cp.count("sync.compact.count") == 1
    assert _delta(before, "cylon_compact_rows_out_total") == live
    cap = util.capacity(live)
    assert live <= cap < 512
    assert _delta(before, "cylon_compact_slots_out_total") == cap
    assert _delta(before, "cylon_join_plan_sort_rows_total") == 500 + cap
    assert _rows(out) == _rows(q.execute(optimize=False))
    assert f"compacted={n}->{live} rows in {cap} slots" \
        in q.explain(analyze=True)
    # over the line (95% alive: under an eighth of the slots would go):
    # counted, not compacted, the join sorts every slot
    q = query(local_ctx, False, 95)
    before = telemetry.metrics_snapshot()
    with telemetry.collect_phases() as cp:
        out = q.execute()
    assert cp.count("plan.compact") == 1 \
        and cp.count("sync.compact.count") == 1
    assert _delta(before, "cylon_compact_rows_in_total") == 0
    assert _delta(before, "cylon_join_plan_sort_rows_total") == 500 + n
    assert _rows(out) == _rows(q.execute(optimize=False))
    # across chips the exchange drops the dead rows: nothing is compacted
    q = query(dist_ctx, True, 3)
    with telemetry.collect_phases() as cp:
        out = q.execute()
    assert cp.count("plan.compact") == 0
    assert len(_rows(out)) == live


@pytest.mark.parametrize("how", ["semi", "inner"])
def test_a_side_63_percent_alive_is_cut_and_the_join_says_the_same(
        local_ctx, monkeypatch, how):
    """`tpch-q4`'s shape (PR 50): the large side keeps 63% of its rows,
    over half, so the octave rule sorted every slot of it. Cut to the
    grid's capacity the join gives the same rows, and its plan sort is
    handed fewer slots by the dead ones less the grid's overshoot."""
    r = np.random.default_rng(50)
    n, m = 8000, 600
    fact = {"k": r.integers(0, 900, n).astype(I32),
            "v": r.integers(0, 100, n).astype(I32)}
    dim = {"k": r.permutation(900)[:m].astype(I32),
           "d": r.integers(0, 9, m).astype(I32)}
    live = int((fact["v"] < 63).sum())
    cap = util.capacity(live)
    assert 2 * util.bucket_cap(live) >= n and 8 * cap < 7 * n   # PR 50's rule

    def run():
        small = plan.scan(ct.Table.from_pydict(local_ctx, dim))
        large = plan.scan(ct.Table.from_pydict(local_ctx, fact)).filter(
            col("v") < 63)
        q = small.join(large, how, on="k") if how == "semi" \
            else large.join(small, how, on="k")
        before = telemetry.metrics_snapshot()
        with telemetry.collect_phases() as cp:
            out = q.execute()
        assert cp.count("sync.compact.count") == 1
        return _rows(out), {name: _delta(before, name) for name in (
            "cylon_join_plan_sort_rows_total", "cylon_compact_rows_in_total",
            "cylon_compact_rows_out_total", "cylon_compact_slots_out_total",
            "cylon_host_syncs_total")}

    cut_rows, cut = run()
    monkeypatch.setattr(T, "compaction_pays", lambda cap, slots: False)
    whole_rows, whole = run()
    assert cut_rows == whole_rows and len(cut_rows) > 0
    assert (whole["cylon_compact_rows_in_total"],
            cut["cylon_compact_rows_in_total"]) == (0, n)
    assert cut["cylon_compact_rows_out_total"] == live
    assert cut["cylon_compact_slots_out_total"] == cap
    assert whole["cylon_join_plan_sort_rows_total"] == m + n
    assert cut["cylon_join_plan_sort_rows_total"] == m + cap \
        == m + n - ((n - live) - (cap - live))
    # the count that decides is the one fetched before: no new fetch
    assert cut["cylon_host_syncs_total"] == whole["cylon_host_syncs_total"]
