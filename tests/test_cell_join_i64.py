"""Tier-1's run of the 64-bit join cell's own tests: the generator (every
seed the same work), the plain reference (passes on the exact answer,
fails on float32 payloads, on keys without their high word, on a dropped
and a doubled row) and the six per-layer metrics it brings. The tests
live with the benchmark, in ``benchmarks/tests/test_join_i64_cell.py``
(run by hand with the rest of that suite); this file takes them as they
are, fixtures included, so that there is one copy, but for one: the
benchmark's file pins the cell's six metrics as the LAST six of
``BENCHMARK.json`` and only a `benchmark` PR may edit it, while every
later PR appends its metrics after them (PR 33 did), so the listing is
checked here by name. The cell itself is rehearsed through
``benchmarks/run.py`` by ``test_cells_rehearsal.py``.
"""
import importlib.util
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmarks", "tests", "test_join_i64_cell.py")
_spec = importlib.util.spec_from_file_location("bench_test_join_i64_cell",
                                               _PATH)
_mod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_mod)
globals().update({name: obj for name, obj in vars(_mod).items()
                  if name.startswith("test_") or name == "i64_case"})


def test_benchmark_lists_the_cell_and_its_metrics():
    """The benchmark file's test of the same name, the six metrics found
    by name and in their order, wherever later PRs appended theirs."""
    data, names = _mod.data, _mod.NEW_METRICS
    bench = data("..", "BENCHMARK")
    cell = [w for w in bench["workloads"] if w["name"] == "join-i64-w1"][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("cylon-join-scaling-i64", "inner-1chip", 1)
    listed = [m for m in bench["per_layer"] if m["name"] in names]
    assert [m["name"] for m in listed] == names
    for m in listed:
        assert m["workloads"] == ["join-i64-w1"] \
            and m["moves"] == "query_p50_s"
        spec = data("metrics", m["name"])
        assert (spec["unit"], spec["layer"], spec["source"]) \
            == (m["unit"], m["layer"], m["source"])
