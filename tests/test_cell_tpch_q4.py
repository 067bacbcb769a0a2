"""Tier-1's run of the TPC-H Q4 cell's own tests: the generator's
population on two seeds and its equality, column for column, with
`tpch_orders_lineitem` on the columns both place; the three selectivities;
the plain reference `tpch_q4_exact` against a brute-force loop, on the
exact report, on the float32-key control, on a dropped, doubled and
misplaced group, a count off by one, a narrowed schema, a null; that the
query file ends at load without a semi join; the roofline's bytes
function, and the cell's entries of ``BENCHMARK.json`` found by name. The
tests live with the benchmark, in
``benchmarks/tests/test_tpch_q4_cell.py`` (run by hand with the rest of
that suite); this file takes them as they are, fixtures included, so that
there is one copy. The cell itself is rehearsed through
``benchmarks/run.py`` by ``test_cells_rehearsal.py``.
"""
import importlib.util
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmarks", "tests", "test_tpch_q4_cell.py")
_spec = importlib.util.spec_from_file_location("bench_test_tpch_q4_cell",
                                               _PATH)
_mod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_mod)
globals().update({name: obj for name, obj in vars(_mod).items()
                  if name.startswith("test_") or name == "q4_case"})
