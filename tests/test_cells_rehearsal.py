"""Every cell of BENCHMARK.json, rehearsed on the CPU at a test size.

The benchmark under ``benchmarks/`` is the one yardstick a number may
come from, and the driver runs it only after a PR is handed in. So that a
PR whose program no longer runs a cell, or no longer emits a counter a
metric file reads, learns it from tier-1 and not as a ``null`` in the
ledger, each cell runs here through every phase of ``benchmarks/run.py``
(``--scale``: off a TPU the run ends with exit 1 and no result line),
sound and as the control, and every metric file that names a counter of
the program finds that counter's family after the run, every metric file
that names a span of the program the span, closed.

Each run is a child process, as on the chip: x64 off and four virtual CPU
devices instead of this suite's x64 on and eight, its counters its own,
and a time limit of its own.
"""
import functools
import glob
import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]

SCALE = 0.0005        # 8,000 rows a side a chip; 50,000 for the groupby
# groupby-q5's key has N/K = rows/100 values: at 50,000 rows its range
# (500) would fit the dense table (ops/groupby.DENSE_MAX_SLOTS, 1,024),
# which the cell at 1e8 rows never takes; 250,000 rows keep it on the
# sort path its metrics read
SCALES = {"groupby-q5": 0.0025,
          # groupby-q5-w4 (PR 40): 128,000 rows, 32,000 a chip. At the
          # default scale a chip's 31,250 rows are no multiple of the row
          # quantum (8), so `shard.distribute` pads and the table arrives
          # WITH a row mask, which the cell's 6.25e7 rows a chip never
          # have: no dead flag rides its first sort (PR 43). (id6's
          # range is the literal 1e7 at every scale, so here nearly every
          # row is a group of its own and the exchange is one program, not
          # the cell's eight chunks: the merge phase's path is the same.)
          "groupby-q5-w4": 0.000512,
          # tpch-q12 (PR 46): 75,000 orders and 300,000 lines, of which
          # some 1,560 pass the five predicates; at the default scale the
          # few dozen lines whose order key lies past 2^29 (where the
          # control's float32 keys collide) could miscount to a net of 0
          "tpch-q12": 0.004,
          # tpch-q4 (PR 49): tpch-q12's population: 75,000 orders, of
          # which some 2,870 fall in the quarter and some 2,630 are
          # counted; the control overcounts by the few orders without a
          # late line whose float32 key is a neighbour's with one
          "tpch-q4": 0.004}
LIMIT_S = 120         # a run takes 4-8 s

_CHILD = """
import argparse, importlib, json, os, sys
script, cell, scale, control = sys.argv[1:5]
sys.path.insert(0, os.path.dirname(os.path.abspath(script)))
run = importlib.import_module(os.path.splitext(os.path.basename(script))[0])
# the sort's packing (PR 35) is looked for from SORT_PACK_MIN_ROWS rows
# on, which no rehearsal has: groupby-q5 packs at 1e8 rows, so here too
from cylon_tpu.ops import groupby
groupby.SORT_PACK_MIN_ROWS = 0
result, code = run.run(argparse.Namespace(
    workload=cell, seed=2147483659, seconds=0.5, trace=0,
    scale=float(scale), control=int(control)))
from cylon_tpu import telemetry
snap = telemetry.metrics_snapshot()
print("REHEARSED " + json.dumps({
    "code": code, "correct": result["correct"],
    "attempted": result["attempted"], "failed": result["failed"],
    "series": sorted(k for k, v in snap.items()
                     if isinstance(v, (int, float))),
    "phases": sorted(k.split('"')[1] for k in snap
                     if k.startswith("cylon_phase_latency_ms{phase=")),
    "counts": {name: sum(v for k, v in snap.items()
                         if k.split("{")[0] == name
                         and isinstance(v, (int, float)))
               for name in ("cylon_compact_rows_in_total",
                            "cylon_compact_rows_out_total",
                            "cylon_compact_slots_out_total",
                            "cylon_compact_streams_total",
                            "cylon_join_plan_sort_rows_total",
                            "cylon_join_semi_total")},
    "packed": snap.get("cylon_groupby_sort_packed_columns_total", 0),
    "sorted": snap.get("cylon_groupby_sort_operands_total", 0)}))
"""


@functools.lru_cache(maxsize=None)
def rehearse(cell, control):
    """(what the run said of itself, its whole output)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_ENABLE_X64="0",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("CYLON_TPU_VERIFY_PLANS", None)   # the chip's run has none
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD, BENCH["command"][-1], cell,
         str(SCALES.get(cell, SCALE)), str(control)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=LIMIT_S)
    said = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("REHEARSED ")]
    assert said, (f"{cell}: the run did not reach its end (exit "
                  f"{proc.returncode})\n{proc.stdout[-3000:]}\n"
                  f"{proc.stderr[-3000:]}")
    return json.loads(said[-1][len("REHEARSED "):]), proc.stdout


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_correct(cell):
    said, out = rehearse(cell, 0)
    assert said["code"] == 1 and "every phase ran" in out   # not a TPU
    assert said["correct"] is True, out[-3000:]
    assert said["attempted"] >= 1 and said["failed"] == 0
    window = re.search(r"window: \d+ queries .* (\d+) compile\(s\)", out)
    assert window and int(window.group(1)) == 0, out[-3000:]
    assert "MISMATCH" not in out


@pytest.mark.parametrize("cell", CELLS)
def test_cell_control_is_not_correct(cell):
    """The reference in the next lower precision, put in the program's
    place, has to come out wrong: the comparison can tell."""
    said, out = rehearse(cell, 1)
    assert said["correct"] is False
    assert said["failed"] == 0 and "MISMATCH" in out


def test_groupby_q5_rehearses_packed():
    """The cell's sort carries v1 and v2 inside the key's word in every
    query, as at 1e8 rows: two operands a query (the word and v3; off a
    TPU the index besides), and `groupby-q4`, which never sorts, packs
    nothing."""
    said, _out = rehearse("groupby-q5", 0)
    queries = said["packed"] // 2
    assert queries >= 3 and said["packed"] == 2 * queries
    assert said["sorted"] == 3 * queries
    said, _out = rehearse("groupby-q4", 0)
    assert said["packed"] == 0 and said["sorted"] == 0


def test_groupby_q5_w4_rehearses_probed_and_packed():
    """The cell across chips probes its table before the first per-shard
    sort, as at 6.25e7 rows a chip (PR 43): ONE fetch of the ranges a
    query under a sync span of its own. Off a TPU the sort carries the
    row index and the key as two lanes (bits and mask), so the key is
    not observed and v1 and v2 share a word of their own: 5 + 7 operands
    a query where the chip's lanes path has 2 + 5 (PR 44: the partial
    sums carry no mask, 5 + 10 and 2 + 8 before); no row mask, no dead
    flag."""
    said, _out = rehearse("groupby-q5-w4", 0)
    queries = said["packed"]
    assert queries >= 3 and said["sorted"] == (5 + 7) * queries
    assert "sync.groupby.valuerange" in said["phases"]
    assert "sync.groupby.packranges" not in said["phases"]


def test_tpch_q1_rehearses_on_the_dense_table_with_its_spans():
    """The cell runs as at 7.5e7 rows: the dense table over both keys and
    nothing sorted, the computed columns through `plan.compute`, its two
    host fetches each under a span of its own, four groups, the control
    refused by its sums."""
    said, out = rehearse("tpch-q1", 0)
    assert said["sorted"] == 0
    for family in ("cylon_groupby_dense_keys_total", "cylon_expr_columns_total",
                   "cylon_expr_materialized_bytes_total"):
        assert any(s.split("{")[0] == family for s in said["series"]), family
    for span in ("plan.compute", "plan.filter", "plan.groupby", "plan.sort",
                 "sync.expr.range", "sync.groupby.densegroups"):
        assert span in said["phases"], (span, said["phases"])
    # nothing sorted; dictionary keys' ranges are known without a probe
    assert "sync.groupby.groups" not in said["phases"] \
        and "sync.groupby.keyrange" not in said["phases"]
    assert "4 groups (A/F N/F N/O R/F)" in out
    assert "Compute(disc_price=" in out and "GroupBy(keys=[4, 5]" in out
    _said, control = rehearse("tpch-q1", 1)
    bad = re.findall(r"compare first query: (\S+) = .* MISMATCH", control)
    assert bad and all(name.startswith("sum_diff.") for name in bad)


def test_tpch_q12_rehearses_pushed_compacted_and_dense():
    """The cell runs as at 75,000,000 lines: the five conjuncts below the
    join, the filtered LINEITEM counted and compacted on the device before
    the join sorts it (so the sort is handed the orders and the compacted
    capacity, not both tables' slots), the two case_when columns without a
    range probe, the dense table, the sort elided; the control refused by
    its two counts alone."""
    said, out = rehearse("tpch-q12", 0)
    for family in ("cylon_plan_filters_below_join_total",
                   "cylon_compact_rows_in_total",
                   "cylon_compact_rows_out_total",
                   "cylon_compact_streams_total",
                   "cylon_join_plan_sort_rows_total"):
        assert any(s.split("{")[0] == family for s in said["series"]), family
    for span in ("plan.filter", "plan.project", "plan.compact",
                 "sync.compact.count", "plan.join", "sync.join.count",
                 "plan.compute", "plan.groupby", "plan.sort"):
        assert span in said["phases"], (span, said["phases"])
    assert "sync.expr.range" not in said["phases"]
    assert "sync.groupby.groups" not in said["phases"]
    assert "5 conjunct(s) pushed below a join" in out
    assert "2 groups (high/low MAIL=" in out
    # the lines are cut to the 16-an-octave grid (PR 50): under 6.25% of
    # the compacted slots are dead; the orders have no mask and ride the
    # sort whole beside them
    c = said["counts"]
    assert 0 < c["cylon_compact_rows_out_total"] \
        <= c["cylon_compact_slots_out_total"] \
        <= 1.0625 * c["cylon_compact_rows_out_total"] + 16
    assert c["cylon_join_plan_sort_rows_total"] \
        > c["cylon_compact_slots_out_total"]
    _said, control = rehearse("tpch-q12", 1)
    bad = re.findall(r"compare first query: (\S+) = .* MISMATCH", control)
    assert sorted(bad) == ["high_count_diff", "low_count_diff"]


def test_tpch_q4_rehearses_as_a_semi_join():
    """The cell runs as at 18,750,000 orders: ONE semi join a query and no
    inner join under it (no count fetch, no materialise phase), the two
    date conjuncts written above it run below it on ORDERS, the filtered
    ORDERS counted and compacted before the join sorts them (the SMALL
    side), LINEITEM under its column-column compare (63% alive) counted
    and compacted too since PR 50, so `plan.compact` cuts BOTH sides and
    the sort is handed the two compacted capacities and not one slot
    more; the dense table, the sort elided; the control refused by its
    count alone."""
    said, out = rehearse("tpch-q4", 0)
    for family in ("cylon_join_semi_total",
                   "cylon_plan_filters_below_join_total",
                   "cylon_compact_rows_in_total",
                   "cylon_compact_rows_out_total",
                   "cylon_compact_slots_out_total",
                   "cylon_join_plan_sort_rows_total"):
        assert any(s.split("{")[0] == family for s in said["series"]), family
    c = said["counts"]
    queries = c["cylon_join_semi_total"]
    assert queries >= 3
    # both sides: every slot the plan sort is handed came out of a
    # compaction, two streams for the orders and one for the lines
    assert c["cylon_join_plan_sort_rows_total"] \
        == c["cylon_compact_slots_out_total"]
    assert c["cylon_compact_streams_total"] == (2 + 1) * queries
    assert 0.5 < c["cylon_compact_rows_out_total"] \
        / c["cylon_compact_rows_in_total"] < 0.55     # (0.038 x 1 + 0.63 x 4) / 5
    assert c["cylon_compact_rows_out_total"] \
        <= c["cylon_compact_slots_out_total"] \
        <= 1.0625 * c["cylon_compact_rows_out_total"] + 16 * queries
    assert 'cylon_join_semi_total{kind="semi"}' in said["series"]
    for span in ("plan.filter", "plan.project", "plan.compact",
                 "sync.compact.count", "plan.join", "join.semi",
                 "plan.groupby", "plan.sort"):
        assert span in said["phases"], (span, said["phases"])
    for span in ("sync.join.count", "join.plan", "join.materialize",
                 "sync.groupby.groups"):
        assert span not in said["phases"], (span, said["phases"])
    assert "Join(semi, l[0]=r[0])" in out
    assert "2 conjunct(s) pushed below a join" in out
    assert re.search(r"5 groups \(1-URGENT=\d+ 2-HIGH=\d+ 3-MEDIUM=\d+ "
                     r"4-NOT SPECIFIED=\d+ 5-LOW=\d+\)", out)
    _said, control = rehearse("tpch-q4", 1)
    bad = re.findall(r"compare first query: (\S+) = .* MISMATCH", control)
    assert bad == ["order_count_diff"]


def _metric_files():
    """(metric, its file's content) for every metric of the benchmark."""
    for path in sorted(glob.glob(os.path.join(
            ROOT, BENCH["paths"][0], "metrics", "*.json"))):
        with open(path) as f:
            yield os.path.splitext(os.path.basename(path))[0], json.load(f)


def _counter_metrics():
    """(metric, the counter families its file names) for every metric file
    whose reducer reads the program's counters."""
    found = []
    for metric, spec in _metric_files():
        names = [spec[k] for k in ("prefix", "numerator", "denominator")
                 if k in spec]
        if names:
            found.append((metric,
                          sorted({n.split("{")[0] for n in names})))
    return found


COUNTER_METRICS = _counter_metrics()


@pytest.mark.parametrize("metric,families", COUNTER_METRICS,
                         ids=[m for m, _ in COUNTER_METRICS])
def test_metric_finds_its_counter(metric, families):
    """After the rehearsal of one cell that lists the metric, the program
    has emitted every counter family the metric's file names (the label a
    reducer then picks, ``path="stream"``, may belong to the chip)."""
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    cell = entry.get("workloads", CELLS)[0]
    said, _out = rehearse(cell, 0)
    for family in families:
        assert any(s.split("{")[0] == family for s in said["series"]), \
            f"{metric}: no series of {family} after {cell}"


def _span_metrics():
    """(metric, the span names its file gives) for every metric file whose
    reducer reads the program's host spans: ``span`` (one name or a list),
    ``spans``, ``less``."""
    found = []
    for metric, spec in _metric_files():
        names = []
        for key in ("span", "spans", "less"):
            value = spec.get(key, [])
            names += [value] if isinstance(value, str) else value
        if names:
            found.append((metric, sorted(set(names))))
    return found


SPAN_METRICS = _span_metrics()


@pytest.mark.parametrize("metric,spans", SPAN_METRICS,
                         ids=[m for m, _ in SPAN_METRICS])
def test_metric_finds_its_span(metric, spans):
    """After the rehearsal of the first cell that lists the metric, the
    program has closed every span the metric's file names: each fed
    ``cylon_phase_latency_ms{phase=}`` under its name without the trace's
    ``cylon:``. A name that ends in a dot is the start of a family
    (``cylon:sync.``: any host fetch)."""
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    cell = entry.get("workloads", CELLS)[0]
    said, _out = rehearse(cell, 0)
    for name in spans:
        assert name.startswith("cylon:"), f"{metric}: {name}"
        name = name[len("cylon:"):]
        assert any(p.startswith(name) if name.endswith(".") else p == name
                   for p in said["phases"]), \
            f"{metric}: no span {name} closed in {cell}: {said['phases']}"
