"""The window's accounting on a stub query and a stub clock."""
import pytest

from loop import closed_loop


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_closes_at_first_completion_past_seconds():
    clock = Clock()

    def query(i):
        clock.t += 3.0
        return i

    records, window = closed_loop(query, 10.0, clock=clock)
    # completions at 3, 6, 9, 12: the fourth is the first at or after 10
    assert [r.index for r in records] == [0, 1, 2, 3]
    assert window == pytest.approx(12.0)
    assert [r.started for r in records] == pytest.approx([0, 3, 6, 9])
    assert all(r.seconds == pytest.approx(3.0) and r.ok for r in records)


def test_completion_exactly_at_seconds_closes():
    clock = Clock()

    def query(i):
        clock.t += 5.0

    records, window = closed_loop(query, 10.0, clock=clock)
    assert len(records) == 2 and window == pytest.approx(10.0)


def test_failed_queries_are_counted_and_the_loop_goes_on():
    clock = Clock()

    def query(i):
        clock.t += 2.0
        if i == 1:
            raise RuntimeError("boom")
        return i

    def accept(i, started, result):
        return result != 2          # a wrong row count, say

    records, _ = closed_loop(query, 7.0, accept=accept, clock=clock)
    assert [r.ok for r in records] == [True, False, False, True]
    assert "boom" in records[1].error and records[2].error is None


def test_time_between_queries_is_in_the_window_not_in_a_query():
    clock = Clock()

    def query(i):
        clock.t += 1.0

    def between(i, elapsed):
        clock.t += 0.5

    records, window = closed_loop(query, 4.0, between=between, clock=clock)
    assert all(r.seconds == pytest.approx(1.0) for r in records)
    # 0.5 + 1.0 a round: completions at 1.5, 3.0, 4.5
    assert len(records) == 3 and window == pytest.approx(4.5)


def reducer(name):
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "reducers", name + ".py")
    spec = importlib.util.spec_from_file_location(f"t_reducers_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("q,want", [(0.5, 2.5), (0.9, 7.9), (0.0, 1.0)])
def test_quantile_is_over_all_ok_queries_and_interpolates(q, want):
    from loop import QueryRecord

    secs = [3.0, 1.0, 10.0, 2.0]            # sorted: 1 2 3 10
    run = {"records": [QueryRecord(i, 0.0, s, True)
                       for i, s in enumerate(secs)]
           + [QueryRecord(9, 0.0, 99.0, False)]}   # a failed query: left out
    assert reducer("loop_quantile").reduce(run, {"q": q}) \
        == pytest.approx(want)
    assert reducer("loop_quantile").reduce({"records": []}, {"q": q}) is None


def test_counter_growth_is_per_traced_query_and_none_without_a_counter():
    red = reducer("counter_delta")
    run = {"counters": {"cylon_host_syncs_total{site=a}": 4,
                        "cylon_host_syncs_total{site=b}": 2,
                        "cylon_shuffle_bytes_total": 30},
           "traced_queries": 3}
    assert red.reduce(run, {"prefix": "cylon_host_syncs_total"}) == 2.0
    assert red.reduce(run, {"prefix": "cylon_none"}) is None
    assert red.reduce({"counters": None, "traced_queries": 0},
                      {"prefix": "cylon_host_syncs_total"}) is None
