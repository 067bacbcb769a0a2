"""The 64-bit join cell's own pieces (`cylon-join-scaling-i64`, PR 32):
the generator gives every seed the same work; the reference passes on the
exact answer, in either form a 64-bit column may come in, and fails on the
answer in the next lower precision (the control), on keys that lost their
high word, on a dropped and on a doubled row; the six per-layer metrics
the cell brings read what they say. Needs nothing of `cylon_tpu` but
`format_series`; tier-1 runs this file too (tests/test_cell_join_i64.py).
"""
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(HERE)]   # test_references; xplane

from test_references import as_result, code, data, failed  # noqa: E402

SCALE = 0.0005              # 15,625 rows a side, as tier-1's rehearsal
SEEDS = (7, 2147483659, 4000000007)


def planes(a):
    """An 8-byte array as the uint32[2, n] word planes an engine without
    a 64-bit type holds it in."""
    u = np.ascontiguousarray(a).view(np.uint64)
    return np.stack([(u >> np.uint64(32)).astype(np.uint32),
                     u.astype(np.uint32)])


@pytest.fixture(scope="module")
def i64_case():
    config = data("configs", "cylon-join-scaling-i64")
    traffic = data("traffic", "inner-1chip")
    gen = code("generators", config["generator"])
    ref_mod = code("references", config["reference"])
    runs = [gen.generate(config, traffic, 1, SCALE, s)["tables"]
            for s in SEEDS]
    tables = runs[0]
    # the exact join by another route: a dictionary of the right side
    by_key = {}
    for j, k in enumerate(tables["right"]["k"].tolist()):
        by_key.setdefault(k, []).append(j)
    li, ri = [], []
    for i, k in enumerate(tables["left"]["k"].tolist()):
        for j in by_key.get(k, ()):
            li.append(i)
            ri.append(j)
    li, ri = np.array(li), np.array(ri)
    exact = [tables["left"]["k"][li], tables["left"]["v"][li],
             tables["right"]["k"][ri], tables["right"]["w"][ri]]
    ref = ref_mod.reference(tables, config, traffic)
    return gen, ref_mod, ref, runs, config, traffic, exact


def test_every_seed_is_the_same_work(i64_case):
    gen, ref_mod, ref, runs, config, traffic, _exact = i64_case
    n = gen.rows_of(config, 1, SCALE)
    rows = gen.output_rows(config, 1, SCALE)
    assert n == 15625 and ref["rows"] == rows
    assert abs(rows - n) < 0.01 * n           # about a row out a row in
    for side in ("left", "right"):
        keys = [np.sort(t[side]["k"]) for t in runs]
        assert all(len(k) == n and (k == keys[0]).all() for k in keys)
        order = [t[side]["k"] for t in runs]   # the seed orders the rows
        assert not (order[0] == order[1]).all() \
            and not (order[1] == order[2]).all()
    for t in runs[1:]:
        assert ref_mod.reference(t, config, traffic)["rows"] == rows
    # the published shape: int64 keys over all 64 bits, float64 values
    k = runs[0]["left"]["k"]
    assert k.dtype == np.int64 and runs[0]["left"]["v"].dtype == np.float64
    assert 0.4 < (k < 0).mean() < 0.6
    assert len(np.unique(k >> 32)) > 0.6 * len(np.unique(k))
    # many-to-many: a key occurs up to 7 times a table at this size, and
    # the two tables do not give a key the same count
    _u, lc = np.unique(k, return_counts=True)
    assert lc.max() == 7 and (lc > 1).mean() > 0.3
    common, li, ri = np.intersect1d(
        *(np.unique(runs[0][s]["k"]) for s in ("left", "right")),
        return_indices=True)
    rc = np.unique(runs[0]["right"]["k"], return_counts=True)[1]
    assert (lc[li] != rc[ri]).mean() > 0.3


def test_the_configuration_states_its_output_rows():
    """What the file says the join gives at full size is what the
    generator's rule gives (no table is made: ~7 s of numpy)."""
    config = data("configs", "cylon-join-scaling-i64")
    gen = code("generators", config["generator"])
    assert gen.output_rows(config, 1, 1.0) == config["output_rows"] \
        < 1 << 25
    bench = data("..", "BENCHMARK")
    entry = [c for c in bench["configs"]
             if c["name"] == config["name"]][0]
    assert entry["reduced"] == config["reduced"] == ["rows"]
    assert entry["source"] == config["source"]


def test_exact_answer_passes_in_either_form_and_any_order(i64_case):
    _g, ref_mod, ref, *_rest, exact = i64_case
    assert ref["dtypes"] == [np.int64, np.float64, np.int64, np.float64]
    assert failed(ref_mod.compare(as_result(exact), ref)) == []
    perm = np.random.default_rng(0).permutation(len(exact[0]))
    held = as_result([planes(c[perm]) for c in exact])
    assert failed(ref_mod.compare(held, ref)) == []
    assert sum(c.nbytes for c in held["columns"]) \
        == sum(c.nbytes for c in exact)       # 8 bytes a value either way


def test_float32_payload_fails(i64_case):
    _g, ref_mod, ref, runs, config, traffic, exact = i64_case
    rounded = list(exact)
    rounded[3] = exact[3].astype(np.float32).astype(np.float64)
    assert failed(ref_mod.compare(as_result(rounded), ref)) \
        == ["fingerprint_diff"]
    control = ref_mod.control(runs[0], config, traffic)
    assert failed(ref_mod.compare(control, ref)) == ["fingerprint_diff"]


def test_keys_without_their_high_word_fail(i64_case):
    """What the engine answered before it held 64-bit columns exactly:
    the columns narrowed to 32 bits (a schema the comparison refuses), or
    the low words alone under a 64-bit type."""
    _g, ref_mod, ref, *_rest, exact = i64_case
    narrowed = [exact[0].astype(np.int32), exact[1].astype(np.float32),
                exact[2].astype(np.int32), exact[3].astype(np.float32)]
    assert set(failed(ref_mod.compare(as_result(narrowed), ref))) \
        == {"schema_diff", "fingerprint_diff"}
    low = list(exact)
    low[0] = exact[0] & 0xFFFFFFFF
    low[2] = exact[2] & 0xFFFFFFFF
    assert failed(ref_mod.compare(as_result(low), ref)) \
        == ["fingerprint_diff"]
    low_planes = [planes(c) for c in exact]
    low_planes[0][0] = 0
    assert failed(ref_mod.compare(as_result(low_planes), ref)) \
        == ["fingerprint_diff"]


def test_dropped_doubled_and_one_bit_fail(i64_case):
    _g, ref_mod, ref, *_rest, exact = i64_case
    dropped = [c[1:] for c in exact]
    assert "rows_diff" in failed(ref_mod.compare(as_result(dropped), ref))
    doubled = [np.concatenate([c[1:], c[1:2]]) for c in exact]
    assert failed(ref_mod.compare(as_result(doubled), ref)) \
        == ["fingerprint_diff"]
    one_bit = [planes(c) for c in exact]
    one_bit[1][1, 5] ^= 1                      # the last bit of a mantissa
    assert failed(ref_mod.compare(as_result(one_bit), ref)) \
        == ["fingerprint_diff"]


# -- the cell's per-layer metrics, each through its file and its reducer ----

NEW_METRICS = ["join64_device_ms_per_query", "join64_sort_device_ms_per_query",
               "join64_sort_operands_per_query", "join64_key_lanes_per_query",
               "join64_gathered_columns_per_query", "join_expand_roofline"]


def _ms(name, start_ms, dur_ms):
    return [name, int(start_ms * 1e6), int(dur_ms * 1e6)]


def _run():
    """Two traced queries of 0.8 s: a sort of 400 ms and a plan kernel of
    60 in the plan program, an expand kernel of 250 in the materialize
    program, the cut of the result in its own."""
    import xplane

    modules, ops, host = [], [], []
    for q in (0.0, 900.0):
        host.append(_ms("bench:query", q, 800.0))
        modules += [_ms("jit__plan_program_stream_impl(1)", q + 2, 470.0),
                    _ms("jit__materialize_program_stream_impl(2)", q + 480,
                        260.0),
                    _ms("jit__join_prefix_program(3)", q + 745, 4.0),
                    _ms("jit_copy(4)", q + 750, 3.0)]
        ops += [_ms("sort.26 sort", q + 5, 400.0),
                _ms("join_stream_plan.1 custom-call", q + 406, 60.0),
                _ms("join_stream_expand.1 custom-call", q + 481, 250.0),
                _ms("sorted_fusion.2 fusion", q + 745, 4.0)]
    trace = xplane.Trace({"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": modules},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": host}]}]})
    from cylon_tpu.telemetry.metrics import format_series

    return {"trace": trace, "traced_queries": 2, "chips": 1,
            "peaks": {"hbm_gbytes_per_s": 819},
            "input_bytes": 1_000_000_000, "result_bytes": 999_994_592,
            "counters": {
                format_series("cylon_join_sort_operands_total", ()): 10,
                format_series("cylon_join_key_lanes_total", ()): 4,
                format_series("cylon_join_gathered_columns_total", ()): 0,
                format_series("cylon_join_algorithm_total",
                              (("algo", "local"),)): 2}}


def _read(name, run):
    spec = data("metrics", name)
    return code("reducers", spec["reducer"]).reduce(run, spec)


def test_new_metrics_read_what_they_say():
    run = _run()
    assert _read("join64_device_ms_per_query", run) \
        == pytest.approx(470.0 + 260.0 + 4.0)  # not the harness's copy
    assert _read("join64_sort_device_ms_per_query", run) \
        == pytest.approx(400.0)
    assert _read("join64_sort_operands_per_query", run) == 5.0
    assert _read("join64_key_lanes_per_query", run) == 2.0
    assert _read("join64_gathered_columns_per_query", run) == 0.0
    floor_ms = 1e3 * (1_000_000_000 + 999_994_592) / 819e9
    share = _read("join_expand_roofline", run)
    assert share == pytest.approx(100.0 * floor_ms / 250.0)
    assert 0.0 < share < 100.0


def test_new_metrics_find_nothing_at_a_program_without_them():
    """The parent has neither counter; a cell that never runs the kernel,
    or an untraced run, gives nothing to read: None, no error."""
    run = _run()
    run["counters"] = {k: v for k, v in run["counters"].items()
                       if "key_lanes" not in k and "gathered" not in k}
    assert _read("join64_key_lanes_per_query", run) is None
    assert _read("join64_gathered_columns_per_query", run) is None
    for name in NEW_METRICS:
        assert _read(name, dict(run, trace=None, counters=None,
                                traced_queries=0)) is None
    import xplane

    bare = xplane.Trace({"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [_ms("sort.1 sort", 1, 5.0)]}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            _ms("bench:query", 0, 10.0)]}]}]})
    assert _read("join_expand_roofline", dict(run, trace=bare)) is None
    assert _read("join_expand_roofline", dict(run, peaks=None)) is None


def test_benchmark_lists_the_cell_and_its_metrics():
    bench = data("..", "BENCHMARK")
    cell = [w for w in bench["workloads"] if w["name"] == "join-i64-w1"][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("cylon-join-scaling-i64", "inner-1chip", 1)
    assert [m["name"] for m in bench["per_layer"]][-6:] == NEW_METRICS
    for m in bench["per_layer"][-6:]:
        assert m["workloads"] == ["join-i64-w1"] \
            and m["moves"] == "query_p50_s"
        spec = data("metrics", m["name"])
        assert (spec["unit"], spec["layer"], spec["source"]) \
            == (m["unit"], m["layer"], m["source"])
