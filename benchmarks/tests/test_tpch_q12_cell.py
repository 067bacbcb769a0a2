"""The TPC-H Q12 cell's own pieces (`tpch-sf100-q12`, PR 46): the
generator's population (every seed the same orders, the same keys and the
same number of lines; every line's order present; 1-7 lines an order; the
three date rules; the share of lines under the five predicates); the plain
reference against a brute-force loop; it passes on the exact report and
fails on the control (both key columns through float32) by the two counts
alone, on a dropped, a doubled and a misplaced group, on a count off by
one, on a narrowed schema and on a null; it imports nothing of the engine;
the compaction's bytes function; the cell's entries of BENCHMARK.json,
found by NAME. Needs nothing of `cylon_tpu`; tier-1 runs this file too
(tests/test_cell_tpch_q12.py).
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
NEW_METRICS = ["joinagg_device_ms_per_query",
               "joinagg_filter_device_ms_per_query",
               "joinagg_compact_device_ms_per_query",
               "joinagg_compact_roofline",
               "joinagg_join_device_ms_per_query",
               "joinagg_join_sort_rows_per_query",
               "joinagg_filters_below_join_per_query",
               "joinagg_dense_device_ms_per_query"]


def made(scale, seed):
    config = data("configs", "tpch-sf100-q12")
    traffic = data("traffic", "tpch-q12")
    tables = code("generators", config["generator"]).generate(
        config, traffic, 1, scale, seed)["tables"]
    return tables, config, traffic


@pytest.fixture(scope="module")
def q12_case():
    tables, config, traffic = made(SCALE, SEEDS[0])
    ref_mod = code("references", config["reference"])
    return ref_mod, ref_mod.reference(tables, config, traffic), tables, \
        config, traffic


def report(ref):
    """The result an exact engine hands back, from the reference's own
    numbers."""
    return {"names": ["l_shipmode", "high_line_count", "low_line_count"],
            "columns": [np.asarray(ref["groups"], np.int32),
                        np.asarray(ref["high"], np.int32),
                        np.asarray(ref["low"], np.int32)],
            "nulls": 0}


def test_the_population_is_tpch_orders_and_their_lines(q12_case):
    _m, ref, tables, config, traffic = q12_case
    o, line = tables["orders"], tables["lineitem"]
    assert list(o) == traffic["tables"]["orders"]
    assert list(line) == traffic["tables"]["lineitem"]
    n = len(o["o_orderkey"])
    assert n == int(config["rows"]["orders"] * SCALE) == 75000
    assert len(line["l_orderkey"]) == 4 * n == 300000
    assert o["o_orderkey"].dtype == line["l_orderkey"].dtype == np.int32
    assert o["o_orderpriority"].dtype == "<U15"
    assert line["l_shipmode"].dtype == "<U7"
    for c in ("l_shipdate", "l_commitdate", "l_receiptdate"):
        assert line[c].dtype == np.int32
    # the specification's sparse keys, the chip's member of every 32, in
    # key order, over the whole of SF100's range: past 2^29 too
    key = o["o_orderkey"]
    assert (key % 32 == 1).all() and (np.diff(key) > 0).all()
    assert key.min() == 1 and 2 ** 29 < key.max() <= 600_000_000
    assert (np.diff(key) == 32).mean() > 0.99      # runs of neighbours
    # every line's order is there; 1-7 lines an order, the fixed multiset
    assert (np.diff(line["l_orderkey"]) >= 0).all()
    keys, counts = np.unique(line["l_orderkey"], return_counts=True)
    assert (keys == key).all()
    per = np.bincount(counts, minlength=8)
    assert per[0] == 0 and len(per) == 8
    assert per[1:].tolist() == [n // 7] * 3 + [n // 7 + n % 7] + [n // 7] * 3
    assert set(o["o_orderpriority"]) == {
        "1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"}
    assert set(line["l_shipmode"]) == {
        "REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"}
    # the three date rules of Clause 4.2.3, off the order's date
    first = np.concatenate([[0], np.cumsum(counts)[:-1]])

    def of_order(fn, column):
        return fn.reduceat(line[column], first)

    # an order date that gives every line of the order its ship date
    # (+ [1..121]) and its commit date (+ [30..90]) exists, in the range
    lo = np.maximum(of_order(np.maximum, "l_shipdate") - 121,
                    of_order(np.maximum, "l_commitdate") - 90)
    hi = np.minimum(of_order(np.minimum, "l_shipdate") - 1,
                    of_order(np.minimum, "l_commitdate") - 30)
    assert (lo <= hi).all() and 8035 <= hi.min() and lo.max() <= 10440
    gap = line["l_receiptdate"] - line["l_shipdate"]
    assert gap.min() == 1 and gap.max() == 30
    d = line["l_commitdate"] - line["l_shipdate"]
    assert d.min() == 30 - 121 and d.max() == 90 - 1
    # about 0.52% of the lines pass the five predicates
    assert 0.0045 <= ref["rows_kept"] / ref["rows_in"] <= 0.0060
    assert [ref["modes"][g] for g in ref["groups"]] == ["MAIL", "SHIP"]
    placed = sum(4 * len(next(iter(t.values()))) * len(t)
                 for t in tables.values())
    assert placed == n * config["row_bytes_placed"]["orders"] \
        + 4 * n * config["row_bytes_placed"]["lineitem"]


def test_order_keys_are_unique_at_every_size():
    """Each order once, in key order, inside SF100's range, at the cell's
    size (every member of the share) and at sizes near it and far from it
    (a key twice would join a line to two orders)."""
    gen = code("generators", "tpch_orders_lineitem")
    members = 18_750_000
    for n in (members, members - 1, members - 255, members // 2 + 7,
              75_000, 1024):
        key = gen.order_keys(n, members).astype(np.int64)
        assert len(key) == n and (np.diff(key) >= 32).all(), n
        assert key[0] == 1 and key[-1] <= 600_000_000 - 31
        assert (key % 32 == 1).all()
    assert (np.diff(gen.order_keys(members, members)) == 32).all()


def test_every_seed_is_the_same_work():
    a, _c, _t = made(SCALE, SEEDS[0])
    b, _c, _t = made(SCALE, SEEDS[1])
    assert (a["orders"]["o_orderkey"] == b["orders"]["o_orderkey"]).all()
    assert len(a["lineitem"]["l_orderkey"]) == len(b["lineitem"]["l_orderkey"])
    ca = np.unique(a["lineitem"]["l_orderkey"], return_counts=True)[1]
    cb = np.unique(b["lineitem"]["l_orderkey"], return_counts=True)[1]
    assert (np.sort(ca) == np.sort(cb)).all() and (ca != cb).any()
    assert (a["lineitem"]["l_shipmode"] != b["lineitem"]["l_shipmode"]).any()
    again, _c, _t = made(SCALE, SEEDS[0])
    for name, table in a.items():
        for c, arr in table.items():
            assert (arr == again[name][c]).all(), (name, c)


@pytest.mark.parametrize("years", [(8766, 9131), (8036, 10592)],
                         ids=["1994", "every_year"])
def test_the_reference_is_the_brute_force_loop(years):
    """At 1,024 orders and 4,096 lines (the generator's least), against a
    loop over the lines with a dictionary of the orders."""
    tables, config, traffic = made(1e-9, 7)
    traffic = dict(traffic, receiptdate_min=years[0],
                   receiptdate_max=years[1])
    ref_mod = code("references", config["reference"])
    ref = ref_mod.reference(tables, config, traffic)
    o, line = tables["orders"], tables["lineitem"]
    assert len(o["o_orderkey"]) == 1024 and len(line["l_orderkey"]) == 4096
    priority = dict(zip(o["o_orderkey"].tolist(),
                        o["o_orderpriority"].tolist()))
    want = {}
    for i in range(4096):
        mode = str(line["l_shipmode"][i])
        ship, commit, receipt = (int(line[c][i]) for c in
                                 ("l_shipdate", "l_commitdate",
                                  "l_receiptdate"))
        if mode in ("MAIL", "SHIP") and commit < receipt and ship < commit \
                and years[0] <= receipt < years[1]:
            high = priority[int(line["l_orderkey"][i])] \
                in ("1-URGENT", "2-HIGH")
            counts = want.setdefault(mode, [0, 0])
            counts[0 if high else 1] += 1
    got = {ref["modes"][g]: [h, low] for g, h, low in
           zip(ref["groups"], ref["high"], ref["low"])}
    assert got == want and want
    assert ref["modes"] == sorted(set(line["l_shipmode"].tolist()))
    assert failed(ref_mod.compare(report(ref), ref)) == []


def test_exact_passes_and_the_control_fails_by_its_counts(q12_case):
    ref_mod, ref, tables, config, traffic = q12_case
    assert failed(ref_mod.compare(report(ref), ref)) == []
    wide = report(ref)
    wide["columns"][1:] = [c.astype(np.int64) for c in wide["columns"][1:]]
    assert failed(ref_mod.compare(wide, ref)) == []
    assert ref_mod.rows_out(ref) == 2 and "MAIL=" in ref_mod.describe(ref)
    control = ref_mod.control(tables, config, traffic)
    assert sorted(failed(ref_mod.compare(control, ref))) \
        == ["high_count_diff", "low_count_diff"]


def test_wrong_groups_counts_and_schema_fail(q12_case):
    ref_mod, ref, _t, _c, _tr = q12_case
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
    other = edit(lambda c: c[0].__setitem__(0, 0))     # AIR, not MAIL
    assert failed(ref_mod.compare(other, ref)) == ["groups_diff"]
    one_high = edit(lambda c: c[1].__setitem__(0, c[1][0] + 1))
    assert failed(ref_mod.compare(one_high, ref)) == ["high_count_diff"]
    one_low = edit(lambda c: c[2].__setitem__(1, c[2][1] - 1))
    assert failed(ref_mod.compare(one_low, ref)) == ["low_count_diff"]
    for narrow in (lambda c: c.__setitem__(0, c[0].astype(np.int64)),
                   lambda c: c.__setitem__(1, c[1].astype(np.float32)),
                   lambda c: c.__setitem__(2, c[2].astype(np.int16)),
                   lambda c: c.pop()):
        assert failed(ref_mod.compare(edit(narrow), ref)) == ["schema_diff"]
    assert failed(ref_mod.compare(dict(good, nulls=1), ref)) == ["nulls"]


def test_the_reference_imports_nothing_of_the_engine():
    with open(os.path.join(BENCH, "references", "tpch_q12_exact.py")) as f:
        text = f.read()
    assert "cylon" not in text and "import jax" not in text


class _Trace:
    n_queries = 3

    def __init__(self, seconds):
        self.seconds = seconds

    def seconds_matching(self, line, patterns):
        assert line == "XLA Modules" and patterns
        return self.seconds


def test_the_compaction_roofline_counts_the_cells_shapes():
    reducer = code("reducers", "compact_roofline")
    spec = data("metrics", "joinagg_compact_roofline")
    run = {"input_rows": 93_750_000, "chips": 1, "trace": None,
           "peaks": {"hbm_gbytes_per_s": 819}}
    # 75,000,000 lines x (1 B of mask + 2 x 4 B) + 390,000 live x 8 B
    assert reducer.compact_bytes(run, spec) \
        == 75_000_000 * 9 + 390_000 * 8 == 678_120_000
    assert reducer.reduce(run, spec) is None              # no trace
    floor_s = 678_120_000 / 819e9
    run["trace"] = _Trace(3 * 10 * floor_s)               # ten floors a query
    assert reducer.reduce(run, spec) == pytest.approx(10.0)
    run["trace"] = _Trace(0.0)                            # never compacted
    assert reducer.reduce(run, spec) is None
    run["peaks"] = None
    assert reducer.reduce(run, spec) is None


def test_benchmark_lists_the_cell_and_its_metrics_by_name():
    bench = data("..", "BENCHMARK")
    config = [c for c in bench["configs"]
              if c["name"] == "tpch-sf100-q12"][0]
    spec = data("configs", config["name"])
    assert config["source"] == spec["source"] and len(config["source"]) <= 200
    assert config["reduced"] == spec["reduced"] == list(spec["reduced_why"]) \
        == ["rows", "columns_placed"]
    assert config["file"] == "benchmarks/configs/tpch-sf100-q12.json"
    assert spec["chips_in_deployment"] == 8
    assert spec["rows"]["orders"] * 8 == spec["source_rows"]["orders"]
    assert spec["rows"]["lineitem"] == 4 * spec["rows"]["orders"]
    traffic = data("traffic", "tpch-q12")
    for table, columns in spec["columns"].items():
        placed = [c for c, d in columns.items()
                  if not d["placed"].startswith("no")]
        assert sorted(placed) == sorted(traffic["tables"][table])
    assert len(spec["columns"]["orders"]) == 9 \
        and len(spec["columns"]["lineitem"]) == 16
    cell = [w for w in bench["workloads"] if w["name"] == "tpch-q12"][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (config["name"], "tpch-q12", 1) and len(cell["why"]) <= 200
    at = bench["workloads"].index(cell)
    assert at == 8 and sum(w["chips"] == 4
                           for w in bench["workloads"][:9]) == 3
    listed = {m["name"]: m for m in bench["per_layer"]
              if m["name"] in NEW_METRICS}
    assert sorted(listed) == sorted(NEW_METRICS)
    for name, m in listed.items():
        assert m["workloads"] == ["tpch-q12"] and m["moves"] == "query_p50_s"
        mspec = data("metrics", name)
        assert (mspec["unit"], mspec["layer"], mspec["source"]) \
            == (m["unit"], m["layer"], m["source"])
    # no metric of another cell's lists this one
    for m in bench["per_layer"]:
        if "tpch-q12" in m.get("workloads", ()):
            assert m["name"] in NEW_METRICS
