"""Tier-1's run of the low-cardinality groupby cell's own tests: the plain
reference `groupby_mean_f64` on exact answers, on a sound float32 path, on
the bfloat16 control (fails by v3 alone), on a naive float32 accumulator
over 2^20 rows, on a dropped group, an integer mean off by 2^-15 and a
float64 result column, and the cell's entries of ``BENCHMARK.json`` found
by name. The tests live with the benchmark, in
``benchmarks/tests/test_groupby_q4_cell.py`` (run by hand with the rest of
that suite); this file takes them as they are, fixtures included, so that
there is one copy. The cell itself is rehearsed through
``benchmarks/run.py`` by ``test_cells_rehearsal.py``.
"""
import importlib.util
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmarks", "tests", "test_groupby_q4_cell.py")
_spec = importlib.util.spec_from_file_location("bench_test_groupby_q4_cell",
                                               _PATH)
_mod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_mod)
globals().update({name: obj for name, obj in vars(_mod).items()
                  if name.startswith("test_") or name == "q4_case"})
