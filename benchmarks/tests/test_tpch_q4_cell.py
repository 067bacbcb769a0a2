"""The TPC-H Q4 cell's own pieces (`tpch-sf100-q4`, PR 49): the
generator's population on two seeds (every seed the same orders, keys and
number of lines) and its equality, column for column, with
`tpch_orders_lineitem` on the columns both place; the three reckoned
selectivities (the quarter's share of the orders, the late lines, the
quarter's orders with a late line); the plain reference against a
brute-force loop; it passes on the exact report and fails on the control
(both key columns through float32) by its count alone, on a dropped, a
doubled and a misplaced group, on a count off by one, on a narrowed schema
and on a null; it imports nothing of the engine; the roofline's bytes
function; the cell's entries of BENCHMARK.json, found by NAME. Needs
nothing of `cylon_tpu`; tier-1 runs this file too
(tests/test_cell_tpch_q4.py).
"""
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(HERE)]   # test_references; xplane

from test_references import BENCH, code, data, failed  # noqa: E402

SCALE = 0.004               # 75,000 orders, 300,000 lines
SEEDS = (2147483659, 3000000019)
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
NEW_METRICS = ["semijoin_device_ms_per_query",
               "semijoin_join_device_ms_per_query",
               "semijoin_sort_rows_per_query",
               "semijoin_joins_per_query",
               "semijoin_filters_below_join_per_query",
               "semijoin_compact_device_ms_per_query",
               "semijoin_roofline"]


def made(scale, seed):
    config = data("configs", "tpch-sf100-q4")
    traffic = data("traffic", "tpch-q4")
    tables = code("generators", config["generator"]).generate(
        config, traffic, 1, scale, seed)["tables"]
    return tables, config, traffic


@pytest.fixture(scope="module")
def q4_case():
    tables, config, traffic = made(SCALE, SEEDS[0])
    ref_mod = code("references", config["reference"])
    return ref_mod, ref_mod.reference(tables, config, traffic), tables, \
        config, traffic


def report(ref):
    """The result an exact engine hands back, from the reference's own
    numbers."""
    return {"names": ["o_orderpriority", "order_count"],
            "columns": [np.asarray(ref["groups"], np.int32),
                        np.asarray(ref["counts"], np.int32)],
            "nulls": 0}


@pytest.mark.parametrize("seed", SEEDS)
def test_the_population_is_the_accepted_generators_to_the_row(seed):
    """The columns both cells place are equal row for row, seed for seed:
    Q4's cell runs on Q12's data, with the order date besides and no ship
    mode."""
    tables, config, traffic = made(SCALE, seed)
    q12_config = data("configs", "tpch-sf100-q12")
    q12_traffic = data("traffic", "tpch-q12")
    theirs = code("generators", q12_config["generator"]).generate(
        q12_config, q12_traffic, 1, SCALE, seed)["tables"]
    shared = {"orders": ["o_orderkey", "o_orderpriority"],
              "lineitem": ["l_orderkey", "l_commitdate", "l_receiptdate"]}
    for table, columns in shared.items():
        for c in columns:
            assert tables[table][c].dtype == theirs[table][c].dtype
            assert (tables[table][c] == theirs[table][c]).all(), (table, c)
    assert list(tables["orders"]) == traffic["tables"]["orders"] \
        == ["o_orderkey", "o_orderdate", "o_orderpriority"]
    assert list(tables["lineitem"]) == traffic["tables"]["lineitem"] \
        == ["l_orderkey", "l_commitdate", "l_receiptdate"]
    assert "l_shipmode" not in tables["lineitem"]
    o, line = tables["orders"], tables["lineitem"]
    n = len(o["o_orderkey"])
    assert n == int(config["rows"]["orders"] * SCALE) == 75000
    assert len(line["l_orderkey"]) == 4 * n
    assert o["o_orderdate"].dtype == np.int32
    assert o["o_orderdate"].min() >= 8035 and o["o_orderdate"].max() <= 10440
    # the order's date is the one its lines' dates were made from
    first = np.concatenate([[0], np.cumsum(np.unique(
        line["l_orderkey"], return_counts=True)[1])[:-1]])
    commit_lo = np.minimum.reduceat(line["l_commitdate"], first)
    commit_hi = np.maximum.reduceat(line["l_commitdate"], first)
    assert (commit_lo - o["o_orderdate"] >= 30).all()
    assert (commit_hi - o["o_orderdate"] <= 90).all()
    gap = line["l_receiptdate"] - np.repeat(
        o["o_orderdate"], np.diff(np.append(first, len(line["l_orderkey"]))))
    assert gap.min() >= 2 and gap.max() <= 151
    placed = sum(4 * len(next(iter(t.values()))) * len(t)
                 for t in tables.values())
    assert placed == n * config["row_bytes_placed"]["orders"] \
        + 4 * n * config["row_bytes_placed"]["lineitem"]


def test_every_seed_is_the_same_work():
    a, _c, _t = made(SCALE, SEEDS[0])
    b, _c, _t = made(SCALE, SEEDS[1])
    assert (a["orders"]["o_orderkey"] == b["orders"]["o_orderkey"]).all()
    assert len(a["lineitem"]["l_orderkey"]) == len(b["lineitem"]["l_orderkey"])
    assert (a["orders"]["o_orderdate"] != b["orders"]["o_orderdate"]).any()
    again, _c, _t = made(SCALE, SEEDS[0])
    for name, table in a.items():
        for c, arr in table.items():
            assert (arr == again[name][c]).all(), (name, c)


@pytest.mark.parametrize("seed", SEEDS)
def test_the_three_selectivities(seed):
    """What the configuration states: 92 / 2,406 of the orders fall in the
    quarter, 63.22% of the lines are late, 91.70% of the quarter's orders
    have a late line (at 75,000 orders: within a few standard errors)."""
    tables, config, traffic = made(SCALE, seed)
    ref = code("references", config["reference"]).reference(
        tables, config, traffic)
    assert traffic["orderdate_max"] - traffic["orderdate_min"] == 92
    assert 0.034 <= ref["orders_in_quarter"] / ref["orders"] <= 0.0425
    assert abs(ref["orders_in_quarter"] / ref["orders"] - 92 / 2406) < 0.003
    assert abs(ref["lines_late"] / ref["lines"] - 139995 / 221430) < 0.005
    assert abs(ref["orders_counted"] / ref["orders_in_quarter"]
               - 0.91698) < 0.025
    assert [ref["priorities"][g] for g in ref["groups"]] == PRIORITIES
    assert sum(ref["counts"]) == ref["orders_counted"]


@pytest.mark.parametrize("quarter", [(8582, 8674), (8035, 10441)],
                         ids=["1993Q3", "every_day"])
def test_the_reference_is_the_brute_force_loop(quarter):
    """At 1,024 orders and 4,096 lines (the generator's least), against a
    loop over the orders with a set of the late lines' keys."""
    tables, config, traffic = made(1e-9, 7)
    traffic = dict(traffic, orderdate_min=quarter[0],
                   orderdate_max=quarter[1])
    ref_mod = code("references", config["reference"])
    ref = ref_mod.reference(tables, config, traffic)
    o, line = tables["orders"], tables["lineitem"]
    assert len(o["o_orderkey"]) == 1024 and len(line["l_orderkey"]) == 4096
    late = set()
    for i in range(4096):
        if int(line["l_commitdate"][i]) < int(line["l_receiptdate"][i]):
            late.add(int(line["l_orderkey"][i]))
    want = {}
    for i in range(1024):
        if quarter[0] <= int(o["o_orderdate"][i]) < quarter[1] \
                and int(o["o_orderkey"][i]) in late:
            name = str(o["o_orderpriority"][i])
            want[name] = want.get(name, 0) + 1
    got = {ref["priorities"][g]: n for g, n in
           zip(ref["groups"], ref["counts"])}
    assert got == want and want
    assert ref["priorities"] == PRIORITIES
    assert failed(ref_mod.compare(report(ref), ref)) == []


def test_exact_passes_and_the_control_fails_by_its_count(q4_case):
    ref_mod, ref, tables, config, traffic = q4_case
    assert failed(ref_mod.compare(report(ref), ref)) == []
    wide = report(ref)
    wide["columns"][1] = wide["columns"][1].astype(np.int64)
    assert failed(ref_mod.compare(wide, ref)) == []
    assert ref_mod.rows_out(ref) == 5 and "1-URGENT=" in ref_mod.describe(ref)
    control = ref_mod.control(tables, config, traffic)
    assert failed(ref_mod.compare(control, ref)) == ["order_count_diff"]
    # float32 keys past 2^29 merge with a neighbour's: only ever MORE
    assert sum(control["columns"][1].tolist()) > ref["orders_counted"]


def test_wrong_groups_counts_and_schema_fail(q4_case):
    ref_mod, ref, _t, _c, _tr = q4_case
    good = report(ref)

    def edit(fn):
        cols = [c.copy() for c in good["columns"]]
        fn(cols)
        return dict(good, columns=cols)

    missing = dict(good, columns=[c[1:] for c in good["columns"]])
    assert failed(ref_mod.compare(missing, ref)) == ["groups_diff"]
    twice = dict(good, columns=[np.concatenate([c, c[:1]])
                                for c in good["columns"]])
    assert failed(ref_mod.compare(twice, ref)) == ["groups_diff"]
    swapped = dict(good, columns=[c[::-1] for c in good["columns"]])
    assert failed(ref_mod.compare(swapped, ref)) == ["groups_diff"]
    other = edit(lambda c: c[0].__setitem__(0, 7))     # no such priority
    assert failed(ref_mod.compare(other, ref)) == ["groups_diff"]
    one_more = edit(lambda c: c[1].__setitem__(0, c[1][0] + 1))
    assert failed(ref_mod.compare(one_more, ref)) == ["order_count_diff"]
    one_less = edit(lambda c: c[1].__setitem__(4, c[1][4] - 1))
    assert failed(ref_mod.compare(one_less, ref)) == ["order_count_diff"]
    for narrow in (lambda c: c.__setitem__(0, c[0].astype(np.int64)),
                   lambda c: c.__setitem__(1, c[1].astype(np.float32)),
                   lambda c: c.__setitem__(1, c[1].astype(np.int16)),
                   lambda c: c.pop(),
                   lambda c: c.append(c[1])):
        assert failed(ref_mod.compare(edit(narrow), ref)) == ["schema_diff"]
    assert failed(ref_mod.compare(dict(good, nulls=1), ref)) == ["nulls"]


def test_the_reference_imports_nothing_of_the_engine():
    with open(os.path.join(BENCH, "references", "tpch_q4_exact.py")) as f:
        text = f.read()
    assert "cylon" not in text and "import jax" not in text


def test_the_query_ends_at_load_without_a_semi_join():
    """The query file says at load, by name, that a program whose joins
    stop at the full outer join cannot run it (a non-zero exit, before any
    data is made), and writes the semi join and ONE filter above it."""
    with open(os.path.join(BENCH, "queries", "tpch_q4.py")) as f:
        text = f.read()
    load = text[:text.index("def build")]
    assert 'hasattr(_ct.JoinType, "SEMI")' in load
    assert "raise SystemExit(" in load
    assert text.count(".filter(") == 2 and 'join_type="semi"' in text
    assert text.index('join_type="semi"') < text.index("orderdate_min")


class _Trace:
    n_queries = 3

    def __init__(self, seconds):
        self.seconds = seconds

    def seconds_matching(self, line, patterns):
        assert line == "XLA Modules" and patterns
        return self.seconds


def test_the_semi_join_roofline_counts_the_cells_shapes():
    reducer = code("reducers", "semijoin_roofline")
    spec = data("metrics", "semijoin_roofline")
    run = {"input_rows": 93_750_000, "chips": 1, "trace": None,
           "peaks": {"hbm_gbytes_per_s": 819}}
    # 75,000,000 lines x (4 B + 1 B) + 1,048,576 slots x (9 B + 1 B)
    assert reducer.semijoin_bytes(run, spec) \
        == 75_000_000 * 5 + 1_048_576 * 10 == 385_485_760
    # the probe side's capacity follows the rows: 716,958 live -> 2^20
    assert -(-18_750_000 * 92 // 2406) == 716_958
    assert reducer.semijoin_bytes(dict(run, input_rows=375_000), spec) \
        == 300_000 * 5 + 4_096 * 10
    assert reducer.reduce(run, spec) is None              # no trace
    floor_s = 385_485_760 / 819e9
    run["trace"] = _Trace(3 * 200 * floor_s)       # 200 floors a query
    assert reducer.reduce(run, spec) == pytest.approx(0.5)
    run["trace"] = _Trace(0.0)                     # no such program ran
    assert reducer.reduce(run, spec) is None
    run["peaks"] = None
    assert reducer.reduce(run, spec) is None
    # the join's programs by name, on the modules line
    import re
    names = ["jit__plan_program_stream_impl(123)", "jit_semi_plan_program",
             "jit__materialize_program_stream_impl(9)"]
    assert all(any(re.search(p, n) for p in spec["patterns"])
               for n in names)
    assert not any(re.search(p, n) for p in spec["patterns"]
                   for n in ("jit_compact_program(1)", "jit_groupby_dense",
                             "jit_copy", "jit_less"))


def test_benchmark_lists_the_cell_and_its_metrics_by_name():
    bench = data("..", "BENCHMARK")
    config = [c for c in bench["configs"]
              if c["name"] == "tpch-sf100-q4"][0]
    spec = data("configs", config["name"])
    assert config["source"] == spec["source"] and len(config["source"]) <= 200
    assert config["reduced"] == spec["reduced"] == list(spec["reduced_why"]) \
        == ["rows", "columns_placed"]
    assert config["file"] == "benchmarks/configs/tpch-sf100-q4.json"
    assert spec["chips_in_deployment"] == 8
    assert spec["rows"]["orders"] * 8 == spec["source_rows"]["orders"]
    assert spec["rows"]["lineitem"] == 4 * spec["rows"]["orders"]
    assert spec["row_bytes_placed"] == {"orders": 12, "lineitem": 12}
    traffic = data("traffic", "tpch-q4")
    for table, columns in spec["columns"].items():
        placed = [c for c, d in columns.items()
                  if not d["placed"].startswith("no")]
        assert sorted(placed) == sorted(traffic["tables"][table])
    assert len(spec["columns"]["orders"]) == 9 \
        and len(spec["columns"]["lineitem"]) == 16
    assert (traffic["query"], traffic["orderdate_min"],
            traffic["orderdate_max"], traffic["traced_queries"]) \
        == ("tpch_q4", 8582, 8674, 3)
    assert len(spec["guarantees"]) == 3 and "exists" in spec["query"]
    # the deployment tpch-q1 and tpch-q12 state: a chip's eighth of SF100
    q12 = data("configs", "tpch-sf100-q12")
    assert (spec["rows"], spec["source_rows"], spec["scale_factor"]) \
        == (q12["rows"], q12["source_rows"], q12["scale_factor"])
    cell = [w for w in bench["workloads"] if w["name"] == "tpch-q4"][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (config["name"], "tpch-q4", 1) and len(cell["why"]) <= 200
    names = [w["name"] for w in bench["workloads"]]
    assert names.index("tpch-q4") > names.index("tpch-q12") == 8
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 3
    listed = {m["name"]: m for m in bench["per_layer"]
              if m["name"] in NEW_METRICS}
    assert sorted(listed) == sorted(NEW_METRICS)
    for name, m in listed.items():
        assert m["workloads"] == ["tpch-q4"] and m["moves"] == "query_p50_s"
        mspec = data("metrics", name)
        assert (mspec["unit"], mspec["layer"], mspec["source"],
                mspec["better"]) \
            == (m["unit"], m["layer"], m["source"], m["better"])
    assert listed["semijoin_joins_per_query"]["better"] == "higher"
    assert listed["semijoin_roofline"]["unit"] == "%"
    # no metric of another cell's lists this one
    for m in bench["per_layer"]:
        if "tpch-q4" in m.get("workloads", ()):
            assert m["name"] in NEW_METRICS
