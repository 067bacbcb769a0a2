"""The two metrics of the groupby's sort step (PR 29), each through its own
metric file and the reducer it names: the `sort` op's device time on the
ops line, read off the recorded join trace (`trace_small.json`: three
queries, one sort each) and off a hand-built groupby trace where it must
sit inside the sort's program; and the operands that sort carried, on
counters keyed as the program renders them, absent at a parent without
the counter."""
import json
import os

import pytest

import xplane
from test_query_spans import HERE, metric, reducer   # the same two loaders


def _ms(name, start_ms, dur_ms):
    return [name, int(start_ms * 1e6), int(dur_ms * 1e6)]


def test_sort_ms_reads_the_sort_op_alone():
    spec = metric("groupby_sort_device_ms_per_query")
    red = reducer(spec["reducer"])
    with open(os.path.join(HERE, "trace_small.json")) as f:
        recorded = xplane.Trace(json.load(f))
    # 177.127711, 177.125613, 177.138455 ms: the three `sort.16 sort`
    assert recorded.n_queries == 3
    assert red.reduce({"trace": recorded}, spec) == pytest.approx(
        (177.127711 + 177.125613 + 177.138455) / 3)
    modules, ops, host = [], [], []
    for q in (0.0, 700.0):   # a sort of 540 ms in a program of 541
        host.append(_ms("bench:query", q, 600.0))
        modules += [_ms("jit_presort_groups(7)", q + 2, 541.0),
                    _ms("jit_sorted_segment_aggregate(8)", q + 545, 38.0)]
        ops += [_ms("sort.3 sort", q + 2, 540.0),
                _ms("sorted_fusion.1 fusion", q + 542, 1.0),   # not a sort
                _ms("groupby_run_reduce.1 custom-call", q + 546, 36.0)]
    run = {"trace": xplane.Trace({"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": modules},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": host}]}]})}
    sort_ms = red.reduce(run, spec)
    assert sort_ms == pytest.approx(540.0)
    whole = red.reduce(run, metric("groupby_device_ms_per_query"))
    reduce_ms = red.reduce(run, metric("groupby_reduce_device_ms_per_query"))
    assert whole - reduce_ms - sort_ms == pytest.approx(1.0)
    assert red.reduce({"trace": None}, spec) is None


def test_sort_operands_counts_what_the_program_counted():
    from cylon_tpu.telemetry.metrics import format_series

    name = "cylon_groupby_sort_operands_total"
    spec = metric("groupby_sort_operands_per_query")
    red = reducer(spec["reducer"])
    run = {"traced_queries": 3, "counters": {
        format_series(name, ()): 12,     # key, v1, v2, v3: 4 a query
        format_series("cylon_groupby_reduce_path_total",
                      (("path", "stream"),)): 3}}
    assert red.reduce(run, spec) == 4.0
    # a join cell, or the parent (no such counter): nothing to read
    del run["counters"][format_series(name, ())]
    assert red.reduce(run, spec) is None
    assert red.reduce({"traced_queries": 3, "counters": None}, spec) is None
