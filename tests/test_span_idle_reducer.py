"""The reducer that lays the chips' idle time over the program's host
spans by overlap (``benchmarks/reducers/span_idle.py``), on a small
hand-built trace of two chips and two queries (times in microseconds).

Query 1, ``bench:query`` 0-10000, ``plan.query`` 500-9500 with ``A``
1000-4000 (children ``B`` 1500-2500 and ``C`` 2500-3500, adjacent) and,
adjacent to it, ``D`` 4000-6000:

    chip 0 busy  0-1200, 3800-4500, 5000-9800
    chip 1 busy  0-1200, 3000-4500, 5000-9400

so chip 0's gap 1200-3800 crosses A, B, C and A again, its midpoint (2500)
lies in C, chip 0 alone is idle 3000-3800 and chip 1 alone 9400-9800.
Query 2, 20000-30000, ``plan.query`` 20500-29500, both chips busy
20000-29000. Between the queries the harness's copies run.
"""
import importlib.util
import json
import os
import sys

import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmarks")
sys.path.insert(0, BENCH)

import xplane  # noqa: E402


def _load(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}", os.path.join(BENCH, kind, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


span_idle = _load("reducers", "span_idle")


def metric(name):
    with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
        return json.load(f)


def _events(rows):
    """(name, start us, end us) -> the trace's [name, start ns, dur ns]."""
    return [[n, s * 1000, (e - s) * 1000] for n, s, e in rows]


HOST = [("bench:query", 0, 10000), ("cylon:plan.query", 500, 9500),
        ("cylon:A#12", 1000, 4000), ("cylon:B", 1500, 2500),
        ("cylon:C#12", 2500, 3500), ("cylon:D#13", 4000, 6000),
        ("bench:query", 20000, 30000), ("cylon:plan.query", 20500, 29500)]
OPS = {0: [("sort.1 sort", 0, 1200), ("fusion.2 fusion", 3800, 4500),
           ("sort.3 sort", 5000, 9800), ("copy.4 copy", 12000, 13000),
           ("sort.1 sort", 20000, 29000)],
       1: [("sort.1 sort", 0, 1200), ("fusion.2 fusion", 3000, 4500),
           ("sort.3 sort", 5000, 9400), ("copy.4 copy", 12000, 13000),
           ("sort.1 sort", 20000, 29000)]}
MODULES = {0: [("jit_join_plan_stream(11)", 0, 1200),
               ("jit_bitwise_xor(7)", 3800, 3900),
               ("jit_bitwise_xor(7)", 3900, 4000),
               ("jit_bitwise_xor(8)", 4000, 4100),
               ("jit_multiply(9)", 4100, 4500),
               ("jit__multi_slice(3)", 5000, 9800),
               ("jit_copy(5)", 12000, 12500), ("jit_copy(5)", 12500, 13000),
               # starts before the query: not one of its launches
               ("jit_copy(5)", 19900, 20100),
               ("jit_join_plan_stream(11)", 20100, 28000),
               ("jit_bitwise_xor(7)", 28000, 29000)],
           1: [("jit_join_plan_stream(11)", 0, 9400),
               ("jit_join_plan_stream(11)", 20000, 29000)]}


def make_trace(chips=(0, 1), host=HOST):
    planes = [{"name": f"/device:TPU:{c}", "lines": [
        {"name": "XLA Modules", "events": _events(MODULES[c])},
        {"name": "XLA Ops", "events": _events(OPS[c])}]} for c in chips]
    planes.append({"name": "/host:CPU", "lines": [
        {"name": "python", "events": _events(host)}]})
    return xplane.Trace({"planes": planes})


@pytest.fixture(scope="module")
def run():
    return {"trace": make_trace()}


def read(run, **spec):
    return span_idle.reduce(run, spec)


def us(ms_per_query, queries=2):
    """ms a query -> us over the traced queries, the docstring's unit."""
    return ms_per_query * 1000 * queries


def test_the_midpoint_books_the_crossing_gap_to_one_span(run):
    """What the ledger's ``idle_gaps`` does with chip 0's 2600 us gap:
    all of it to C, nothing to A or B."""
    gaps = dict(run["trace"].breakdown()["idle_gaps"])
    assert gaps["cylon:C"] == pytest.approx(2600e-6)
    assert "cylon:A" not in gaps and "cylon:B" not in gaps


@pytest.mark.parametrize("span,chips,want_us", [
    ("cylon:A", "worst", 600),     # 1200-1500 and 3500-3800 on chip 0
    ("cylon:B", "worst", 1000),
    ("cylon:C", "worst", 1000),
    ("cylon:A", "all", 300),       # chip 1 works from 3000 on
    ("cylon:B", "all", 1000),
    ("cylon:C", "all", 500),
    ("cylon:D", "all", 500),       # 4500-5000, both chips
    ("cylon:plan.query", "all", 500),        # query 2's 29000-29500
    ("cylon:plan.query", "worst", 600),      # + chip 1's 9400-9500
    (["cylon:B", "cylon:C", "cylon:D"], "all", 2000),
])
def test_leaf_idle_splits_a_gap_by_overlap(run, span, chips, want_us):
    got = read(run, read="leaf_idle_ms", span=span, chips=chips)
    assert us(got) == pytest.approx(want_us)


@pytest.mark.parametrize("chips,want_us", [
    ("all", 3500),     # 1800 + 500 + 200, and 1000 in query 2
    ("worst", 4300),   # chip 0: 2600 + 500 + 200 + 1000 (chip 1: 3900)
])
def test_idle_of_all_chips_against_the_worst(run, chips, want_us):
    assert us(read(run, read="idle_ms", chips=chips)) \
        == pytest.approx(want_us)


def test_all_is_the_default_and_imbalance_is_the_difference(run):
    assert read(run, read="idle_ms") == read(run, read="idle_ms",
                                             chips="all")
    assert us(read(run, read="imbalance_ms")) == pytest.approx(800)


@pytest.mark.parametrize("chips,want_us", [
    ("all", 700),      # 9800-10000 and 29500-30000
    ("worst", 1000),   # chip 1: 9500-10000 and 29500-30000
])
def test_unspanned_idle(run, chips, want_us):
    assert us(read(run, read="unspanned_idle_ms", chips=chips)) \
        == pytest.approx(want_us)


@pytest.mark.parametrize("chips", ["all", "worst"])
def test_the_books_balance(run, chips):
    """Every span's leaf idle time and the unspanned rest add up to the
    whole, and ``leaves`` lists them."""
    table = span_idle.leaves(run["trace"], chips)
    whole = table.pop("(idle)")
    assert whole == pytest.approx(read(run, read="idle_ms", chips=chips))
    assert sum(table.values()) == pytest.approx(whole)
    if chips == "all":
        assert {k: round(us(v)) for k, v in table.items()} == {
            "cylon:A": 300, "cylon:B": 1000, "cylon:C": 500,
            "cylon:D": 500, "cylon:plan.query": 500, "(unspanned)": 700}


def test_one_chip_reads_the_same_either_way():
    run = {"trace": make_trace(chips=(0,))}
    assert read(run, read="idle_ms", chips="all") \
        == read(run, read="idle_ms", chips="worst")
    assert read(run, read="imbalance_ms") == 0


def test_programs_count_the_launches_inside_the_queries(run):
    # chip 0 launches 6 + 2 inside the queries, chip 1 one a query
    assert read(run, read="programs") == 4.0
    # by name: [runs, device ms] a query
    assert span_idle.programs(run["trace"]) == {
        "jit_join_plan_stream": [1.0, pytest.approx(4.55)],
        "jit_bitwise_xor": [2.0, pytest.approx(0.65)],
        "jit_multiply": [0.5, pytest.approx(0.2)],
        "jit__multi_slice": [0.5, pytest.approx(2.4)]}


def test_anonymous_programs_are_those_the_engine_did_not_name(run):
    """The metric's own list of names: the counted factories' programs
    and the misnamed plan program go, the eager ones stay."""
    spec = metric("anonymous_programs_per_query")
    assert span_idle.reduce(run, spec) == 2.5
    assert set(span_idle.programs(run["trace"], spec["not_matching"])) \
        == {"jit_bitwise_xor", "jit_multiply"}
    assert span_idle.reduce(run, metric("device_programs_per_query")) == 4.0


def test_a_count_over_three_queries_is_a_whole_number():
    """`join-w4`'s 95 programs a query over 3 traced queries: whole runs
    are summed before they are divided (thirds summed by name read
    94.99999999999997)."""
    runs = {"jit_bitwise_xor": 26, "jit_convert_element_type": 22,
            "jit_right_shift": 18, "jit_multiply": 12,
            "jit_broadcast_in_dim": 4, "jit__multi_slice": 4,
            "jit_remainder": 2, "jit_exchange_chunk_first": 2,
            "jit_exchange_chunk": 2, "jit_count2": 1,
            "jit_join_plan_stream": 1, "jit_join_mat_stream": 1}
    host, modules = [], []
    for q in range(3):
        host.append(("bench:query", q * 1000, q * 1000 + 900))
        at = q * 1000
        for name, n in runs.items():
            for _ in range(n):
                modules.append((f"{name}({n})", at, at + 1))
                at += 1
    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": _events(modules)},
        {"name": "XLA Ops", "events": _events(
            [("sort.1 sort", q * 1000, q * 1000 + 500) for q in range(3)])}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": _events(host)}]}]
    run = {"trace": xplane.Trace({"planes": planes})}
    assert span_idle.reduce(run, {"read": "programs"}) == 95.0
    assert span_idle.reduce(
        run, metric("anonymous_programs_per_query")) == 84.0
    assert span_idle.programs(run["trace"])["jit_bitwise_xor"][0] == 26.0


@pytest.mark.parametrize("name", [
    "jit_exchange_chunk_first(123)", "jit_count2(9)", "jit_count(9)",
    "jit__plan_program_stream_impl(5)", "jit__join_prefix_program(2)",
    "jit__materialize_program_stream_impl(1)", "jit_groupby_dense(4)",
    "jit_presort_groups(8)", "jit_sorted_segment_aggregate(8)",
    "jit__multi_slice(77)", "jit_partition_targets_program(3)",
    "jit_key_bits_program(3)"])
def test_named_programs_match_the_metric_s_list(name):
    import re

    spec = metric("anonymous_programs_per_query")
    assert any(re.search(p, name) for p in spec["not_matching"])


@pytest.mark.parametrize("name", [
    "jit_bitwise_xor(7)", "jit_multiply(1)", "jit_copy(5)",
    "jit_shift_right_logical(3)", "jit_remainder(2)", "jit__where(6)",
    "jit_convert_element_type(4)", "jit_count_nonzero(1)"])
def test_eager_programs_do_not(name):
    import re

    spec = metric("anonymous_programs_per_query")
    assert not any(re.search(p, name) for p in spec["not_matching"])


@pytest.mark.parametrize("spec", [
    {"read": "idle_ms"}, {"read": "imbalance_ms"}, {"read": "programs"},
    {"read": "unspanned_idle_ms"},
    {"read": "leaf_idle_ms", "span": "cylon:A"}])
def test_nothing_to_read_is_none(spec):
    assert span_idle.reduce({"trace": None}, spec) is None
    # no device plane: nothing ran on a chip
    assert span_idle.reduce({"trace": make_trace(chips=())}, spec) is None
    # no traced query
    no_query = [h for h in HOST if h[0] != "bench:query"]
    assert span_idle.reduce({"trace": make_trace(host=no_query)}, spec) \
        is None


def test_a_span_the_program_does_not_open_is_none(run):
    assert read(run, read="leaf_idle_ms", span="cylon:shuffle.route") is None
    assert read(run, read="leaf_idle_ms",
                span=["cylon:shuffle.route", "cylon:shuffle.unpack"]) is None
    # a parent without the new leaves: the four-chip metrics fall silent
    for name in ("join_targets_idle_ms_per_query",
                 "exchange_prepare_idle_ms_per_query",
                 "join_keybits_idle_ms_per_query"):
        assert span_idle.reduce(run, metric(name)) is None
    assert span_idle.leaves(None) is None


def test_unknown_readings_raise(run):
    with pytest.raises(ValueError):
        read(run, read="idle_ms", chips="some")
    with pytest.raises(ValueError):
        read(run, read="busy_ms")


NEW_METRICS = {
    "host_idle_ms_per_query": None,
    "imbalance_idle_ms_per_query": ["join-w4", "join-w4-zipf"],
    "unspanned_idle_ms_per_query": None,
    "join_shuffle_residue_idle_ms_per_query": ["join-w4", "join-w4-zipf"],
    "join_targets_idle_ms_per_query": ["join-w4", "join-w4-zipf"],
    "exchange_prepare_idle_ms_per_query": ["join-w4", "join-w4-zipf"],
    "join_keybits_idle_ms_per_query": ["join-w4", "join-w4-zipf"],
    "join_targets_host_ms_per_query": ["join-w4", "join-w4-zipf"],
    "device_programs_per_query": None,
    "anonymous_programs_per_query": None,
}


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_benchmark_lists_the_metric(name):
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = [m for m in bench["per_layer"] if m["name"] == name]
    assert len(entry) == 1
    entry, spec = entry[0], metric(name)
    assert entry.get("workloads") == NEW_METRICS[name]
    assert (entry["moves"], entry["better"], entry["source"]) \
        == ("query_p50_s", "lower", "device_trace")
    assert (entry["unit"], entry["layer"]) == (spec["unit"], spec["layer"])
    assert os.path.isfile(os.path.join(BENCH, "reducers",
                                       spec["reducer"] + ".py"))


def test_the_whole_list_by_hand(run, tmp_path, monkeypatch, capsys):
    """`span_idle.py <dir>`: what a builder prints for CHANGES.md."""
    (tmp_path / "x.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(xplane, "load", lambda path: run["trace"].data)
    assert span_idle.main(["span_idle.py", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "chips=all: idle 1.750 ms a query" in out
    assert "(sum 1.750)" in out and "0.500  cylon:B" in out
    assert "chips=worst: idle 2.150 ms a query" in out
    assert "programs a query on the chip that runs most: 4.00" in out
    (tmp_path / "empty").mkdir()
    assert span_idle.main(["span_idle.py", str(tmp_path / "empty")]) == 1
