"""Tier-1's run of the TPC-H Q1 cell's own tests: the generator's
population, the plain reference `tpch_q1_exact` on the exact report (word
planes and int64), on the float32 control, on a dropped, doubled and
misplaced group, a sum off by one unit, a count off by one, an average off
by 2^-20, a narrowed schema, a null, and the cell's entries of
``BENCHMARK.json`` found by name. The tests live with the benchmark, in
``benchmarks/tests/test_tpch_q1_cell.py`` (run by hand with the rest of
that suite); this file takes them as they are, fixtures included, so that
there is one copy. The cell itself is rehearsed through
``benchmarks/run.py`` by ``test_cells_rehearsal.py``.
"""
import importlib.util
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmarks", "tests", "test_tpch_q1_cell.py")
_spec = importlib.util.spec_from_file_location("bench_test_tpch_q1_cell",
                                               _PATH)
_mod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_mod)
globals().update({name: obj for name, obj in vars(_mod).items()
                  if name.startswith("test_") or name == "q1_case"})


def test_benchmark_lists_the_cell_and_its_metrics_by_name(monkeypatch):
    """The benchmark's own test pins the EIGHT cells the benchmark had when
    PR 42 wrote it (a file of the benchmark is a `benchmark` PR's to
    edit). Cells are only ever appended, so it is held to the first
    eight: everything it says of `tpch-q1` stands, and what follows the
    eighth is what later PRs appended: a list that starts with `tpch-q12`,
    each cell once and each over a configuration found by name (held to
    names, not to a count: the next cell appended does not fail it)."""
    whole = _mod.data
    bench = whole("..", "BENCHMARK")
    appended = bench["workloads"][8:]
    assert appended and appended[0]["name"] == "tpch-q12"
    names = [w["name"] for w in bench["workloads"]]
    assert len(set(names)) == len(names)
    configs = {c["name"] for c in bench["configs"]}
    for w in appended:
        assert w["config"] in configs, w["name"]
        assert whole("configs", w["config"])["name"] == w["config"]

    def first_eight(kind, name):
        found = whole(kind, name)
        if name == "BENCHMARK":
            found = dict(found, workloads=found["workloads"][:8])
        return found

    monkeypatch.setattr(_mod, "data", first_eight)
    _mod.test_benchmark_lists_the_cell_and_its_metrics_by_name()
