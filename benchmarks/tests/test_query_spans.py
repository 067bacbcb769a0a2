"""The reducers that read the program's host spans (``query_spans``) and
its counters' totals (``counter_total``), on a small hand-built trace of
two chips and two queries (``trace_sync_small.json``, times in whole
microseconds): in each query the count fetch's span opens while the sort
still runs and closes after the chip has gone idle, so it straddles a
busy/idle edge; a third sync span lies between the queries."""
import importlib.util
import json
import os

import pytest

import xplane

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def reducer(name):
    spec = importlib.util.spec_from_file_location(
        f"reducer_{name}", os.path.join(BENCH, "reducers", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric(name):
    with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def run():
    with open(os.path.join(HERE, "trace_sync_small.json")) as f:
        return {"trace": xplane.Trace(json.load(f))}


def test_the_trace_is_what_the_docstring_says(run):
    trace = run["trace"]
    assert sorted(trace.devices) == [0, 1] and trace.n_queries == 2
    # the fetch's idle gap is labelled by the sync span, the innermost
    gaps = dict(trace.breakdown()["idle_gaps"])
    assert max(gaps, key=gaps.get) == "cylon:sync.join.count"


def test_syncs_per_query_counts_starts_inside_queries(run):
    # three sync spans in the trace, the one between the queries is out
    assert reducer("query_spans").reduce(
        run, metric("host_syncs_per_query")) == 1.0


def test_sync_idle_is_the_open_span_less_busy_on_the_worst_chip(run):
    # chip 0: the sort and the plan kernel run until 3.5 ms, the span is
    # open 3.0-5.0: 1.5 ms idle; second query busy until 14.6 of
    # 14.0-16.0: 1.4 ms. Chip 1 ends its first sort 0.2 ms sooner: 1.7 +
    # 1.4 = 3.1 ms over two queries, the longer of the two chips.
    got = reducer("query_spans").reduce(run, metric("sync_idle_ms_per_query"))
    assert got == pytest.approx(3.1 / 2)


def test_host_dispatch_is_plan_query_less_the_syncs(run):
    # plan.query covers 5 ms a query, of which the fetch 2 ms
    got = reducer("query_spans").reduce(
        run, metric("host_dispatch_ms_per_query"))
    assert got == pytest.approx(3.0)


def test_a_program_without_sync_spans_reads_nothing(run):
    """The parent commit opens no ``cylon:sync.*`` span: every reading is
    left out, none raises (``trace_small.json`` is such a trace)."""
    with open(os.path.join(HERE, "trace_small.json")) as f:
        old = {"trace": xplane.Trace(json.load(f))}
    red = reducer("query_spans")
    for name in ("host_syncs_per_query", "sync_idle_ms_per_query",
                 "host_dispatch_ms_per_query"):
        assert red.reduce(old, metric(name)) is None
        assert red.reduce({"trace": None}, metric(name)) is None
    with pytest.raises(ValueError):
        red.reduce(run, {"read": "no_such", "spans": "cylon:sync."})
    # a trace with host spans and no device plane (a CPU rehearsal): the
    # host readings stand, the chip's idle time is not there to read
    host_only = {"trace": xplane.Trace({"planes": [
        p for p in run["trace"].data["planes"]
        if not p["name"].startswith("/device:")]})}
    assert red.reduce(host_only, metric("host_syncs_per_query")) == 1.0
    assert red.reduce(host_only, metric("sync_idle_ms_per_query")) is None


def test_counter_total_sums_the_named_series(monkeypatch):
    from cylon_tpu import telemetry

    snap = {
        'cylon_jit_seconds_total{phase="join.plan",stage="trace"}': 1.5,
        'cylon_jit_seconds_total{phase="join.plan",stage="lower"}': 2.0,
        'cylon_jit_seconds_total{phase="none",stage="trace"}': 0.25,
        'cylon_jit_seconds_total{phase="join.plan",stage="compile"}': 64.0,
        'cylon_jit_events_total{stage="trace"}': 7,
        'cylon_phase_latency_ms{phase="x"}': {"count": 1, "sum": 2.0},
    }
    monkeypatch.setattr(telemetry, "metrics_snapshot", lambda: dict(snap))
    red = reducer("counter_total")
    assert red.reduce({}, metric("setup_trace_lower_s")) == 3.75
    assert red.reduce({}, {"prefix": "cylon_jit_seconds_total"}) == 67.75
    # a program that has no such series (the parent commit): nothing
    assert red.reduce({}, {"prefix": "cylon_no_such_total"}) is None
    monkeypatch.setattr(telemetry, "metrics_snapshot", lambda: {})
    assert red.reduce({}, metric("setup_trace_lower_s")) is None
