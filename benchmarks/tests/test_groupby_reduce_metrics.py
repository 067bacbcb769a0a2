"""The two metrics of the groupby's reduce step (PR 26), each through its
own metric file and the reducer it names: the reduce program's device time
apart from the sort's, on a hand-built trace of two groupby queries (times
in whole microseconds), and the count of reduce steps that took the
streaming path, on counters keyed as the program renders them."""
import pytest

import xplane
from test_query_spans import metric, reducer   # the same two loaders


def _ms(name, start_ms, dur_ms):
    return [name, int(start_ms * 1e6), int(dur_ms * 1e6)]


@pytest.fixture(scope="module")
def run():
    modules, ops, host = [], [], []
    for q in (0.0, 1100.0):   # a sort of 940 ms, then the reduce step
        host.append(_ms("bench:query", q, 1000.0))
        modules += [_ms("jit_presort_groups(7)", q + 2, 941.0),
                    _ms("jit_sorted_segment_aggregate(8)", q + 945, 40.0),
                    _ms("jit_copy(9)", q + 1050, 5.0)]
        ops += [_ms("sort.3 sort", q + 2, 940.0),
                _ms("groupby_run_reduce.1 custom-call", q + 946, 38.0)]
    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": modules},
        {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": host}]}]
    return {"trace": xplane.Trace({"planes": planes})}


def test_reduce_ms_is_the_aggregate_program_alone(run):
    red = reducer("trace_line_ms")
    assert run["trace"].n_queries == 2
    reduce_ms = red.reduce(run, metric("groupby_reduce_device_ms_per_query"))
    assert reduce_ms == pytest.approx(40.0)
    # with the sort's program it is the whole groupby: nothing of the
    # groupby runs in a program the older metric's patterns miss
    whole = red.reduce(run, metric("groupby_device_ms_per_query"))
    assert whole == pytest.approx(941.0 + reduce_ms)
    assert red.reduce({"trace": None},
                      metric("groupby_reduce_device_ms_per_query")) is None


def test_stream_reduces_counts_the_series_the_program_renders():
    from cylon_tpu.telemetry.metrics import format_series

    name = "cylon_groupby_reduce_path_total"
    spec = metric("groupby_stream_reduces_per_query")
    red = reducer(spec["reducer"])
    run = {"traced_queries": 3, "counters": {
        format_series(name, (("path", "stream"),)): 3,
        format_series(name, (("path", "segment"),)): 6,
        "cylon_host_syncs_total{site=\"groupby.groups\"}": 3}}
    assert red.reduce(run, spec) == 1.0
    # a join cell, or a program from before the counter: nothing to read
    del run["counters"][format_series(name, (("path", "stream"),))]
    assert red.reduce(run, spec) is None
