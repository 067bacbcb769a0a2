"""Tier-1's run of the 64-bit join cell's own tests: the generator (every
seed the same work), the plain reference (passes on the exact answer,
fails on float32 payloads, on keys without their high word, on a dropped
and a doubled row) and the six per-layer metrics it brings. The tests
live with the benchmark, in ``benchmarks/tests/test_join_i64_cell.py``
(run by hand with the rest of that suite); this file takes them as they
are, fixtures included, so that there is one copy. The cell itself is
rehearsed through ``benchmarks/run.py`` by ``test_cells_rehearsal.py``.
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
