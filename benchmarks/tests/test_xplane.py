"""The reduction from a trace to busy time, idle share, module time and the
breakdown, on a small trace recorded on a v5e (three ``join-w1`` queries,
my chip run, PR 24; names shortened as ``xplane.load`` does) and on a
synthetic one for the cases that trace lacks."""
import json
import os

import pytest

import xplane

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "trace_small.json")) as f:
        return xplane.Trace(json.load(f))


def test_short_name():
    hlo = ("%sort.16 = (u32[32000000]{0:T(1024)}, s32[32000000]{0:T(1024)})"
           " sort(u32[32000000]{0:T(1024)} %pad_add_fusion, s32[32000000]"
           "{0:T(1024)} %iota), dimensions={0}, is_stable=true")
    assert xplane.short_name(hlo) == "sort.16 sort"
    kernel = ("%_plan_program_stream_impl.1 = (u32[125072,128]{1,0:T(8,128)"
              "S(1)}) custom-call(u32[250048,128]{1,0:T(8,128)} %x), "
              "custom_call_target=\"tpu_custom_call\"")
    assert xplane.short_name(kernel) == \
        "_plan_program_stream_impl.1 custom-call"
    assert xplane.short_name("jit_f(42)") == "jit_f(42)"
    assert xplane.base_name("sort.16 sort") == "sort"
    assert xplane.base_name("_plan_program_stream_impl.1 custom-call") \
        == "_plan_program_stream_impl (custom-call)"
    assert xplane.base_name("jit__reduce_sum(5162432036475046947)") \
        == "jit__reduce_sum"


def test_intervals():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (7, 8)]) \
        == [[0, 3], [5, 8]]
    assert xplane.total([[0, 3], [5, 8]]) == 6
    assert xplane.clip([(0, 3), (5, 8), (9, 12)], 2, 10) \
        == [(2, 3), (5, 8), (9, 10)]
    assert xplane.gaps([[0, 3], [5, 8]], 0, 10) == [(3, 5), (8, 10)]
    assert xplane.gaps([[2, 3]], 0, 4) == [(0, 2), (3, 4)]
    assert xplane.gaps([], 0, 4) == [(0, 4)]


def test_recorded_planes_and_window(recorded):
    assert sorted(recorded.devices) == [0]
    assert set(recorded.devices[0]) == {"XLA Modules", "XLA Ops",
                                        "Async XLA Ops"}
    assert recorded.n_queries == 3
    assert recorded.window_s() == pytest.approx(0.962573528)


def test_recorded_busy_and_idle(recorded):
    # XLA Ops only: the overlapping Async XLA Ops spans are not busy time
    assert recorded.busy_s() == pytest.approx(0.952239658)
    assert recorded.idle_share() == pytest.approx(1.0735668, rel=1e-6)
    assert 0 < recorded.busy_s() < recorded.window_s()


def test_recorded_module_matching(recorded):
    join = recorded.seconds_matching(
        "XLA Modules", ["join", "_plan_program", "_materialize_program"])
    assert join == pytest.approx(0.952169543)
    only_plan = recorded.seconds_matching("XLA Modules", ["_plan_program"])
    assert only_plan == pytest.approx(0.611141, rel=1e-4)
    assert recorded.seconds_matching("XLA Modules", ["groupby"]) == 0
    assert recorded.seconds_matching("No Such Line", ["join"]) is None
    assert recorded.seconds_matching("XLA Ops", [" all-to-all$"]) == 0


def test_recorded_breakdown(recorded):
    b = recorded.breakdown()
    assert [n for n, _ in b["device_ops"][:3]] == [
        "sort", "_materialize_program_stream_impl (custom-call)",
        "_plan_program_stream_impl (custom-call)"]
    assert b["device_ops"][0][1] == pytest.approx(0.531391779)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    gaps = dict(b["idle_gaps"])
    # the host has dispatched everything after ~8 ms and then waits in
    # block_until_ready, outside every cylon span
    assert gaps["bench:query (no cylon span)"] > 0.004
    assert gaps["between queries"] > 0.004
    assert sum(gaps.values()) == pytest.approx(
        recorded.window_s() - recorded.busy_s(), rel=1e-6)


def synthetic(chips=2):
    planes = []
    for c in range(chips):
        ops = [["fusion.1 fusion", 100, 100], ["all-to-all.2 all-to-all",
                                               250, 50 + 100 * c],
               ["sort.3 sort", 600, 300]]
        planes.append({"name": f"/device:TPU:{c}", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": [["jit_f(7)", 100, 800]]}]})
    planes.append({"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ["bench:query", 0, 1000], ["cylon:plan.query", 10, 900],
        ["cylon:join.plan#5", 190, 70], ["cylon:shuffle.count#6", 400, 150]]}]})
    return xplane.Trace({"planes": planes})


def test_synthetic_gaps_are_named_by_the_innermost_span():
    t = synthetic()
    assert t.window_s() == pytest.approx(1000e-9)
    # chip 0 is busy 100+50+300, chip 1 100+150+300
    assert t.busy_s() == pytest.approx((450 + 550) / 2 * 1e-9)
    assert t.idle_share() == pytest.approx(55.0)      # the worst chip: 0
    gaps = dict(t.breakdown()["idle_gaps"])
    assert gaps["cylon:join.plan"] == pytest.approx(50e-9)      # 200..250
    assert gaps["cylon:shuffle.count"] == pytest.approx(300e-9)  # 300..600
    assert gaps["cylon:plan.query"] == pytest.approx(100e-9)     # 0..100
    assert gaps["bench:query (no cylon span)"] == pytest.approx(100e-9)
    # chip 0: 50 ns, chip 1: 150 ns of all-to-all; the mean over chips
    assert t.seconds_matching("XLA Ops", [" all-to-all(-start|-done)?$"]) \
        == pytest.approx(100e-9)


def test_no_device_plane_means_nothing_to_read():
    t = xplane.Trace({"planes": [{"name": "/host:CPU", "lines": [
        {"name": "python", "events": [["bench:query", 0, 10]]}]}]})
    assert t.busy_s() is None and t.idle_share() is None
    assert t.breakdown() == {"device_ops": [], "idle_gaps": []}
