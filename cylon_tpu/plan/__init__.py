"""Lazy query-plan subsystem: logical IR, optimizer, executor.

The reference shipped its task-graph layer as an unfinished overlay
(`LogicalTaskPlan` + `ArrowTaskAllToAll`, arrow_task_all_to_all.h:9-57);
here the layer is completed the way the paper's own cost model demands:
every distributed op is *local kernel + all-to-all + local kernel*
(PAPER.md §1, docs/arch.md), so the dominant optimization is running
FEWER all-to-alls. A `LazyTable` builds a logical plan (`Scan`,
`Project`, `Filter`, `Compute`, `Join`, `GroupBy`, `SetOp`, `Sort`, `Shuffle`
nodes) over the `table_api` registry; the optimizer propagates
partitioning metadata and (1) deletes `Shuffle` nodes whose input is
already hash-placed on the same keys, (2) prunes unreferenced columns
below the exchanges, and (3) pushes filters below shuffles so dead rows
drop in transit, and a filter's conjuncts through a join to the side
whose columns they read; the executor lowers the optimized plan onto the
existing `dist_ops`/`table_api` primitives (never `ops/` kernels — the
analysis suite's `layering/plan-no-ops` rule) and stamps per-node
`telemetry.span` spans, so a plan's shuffle count is directly observable in logs and
Perfetto traces as ``plan.shuffle.*`` labels. `LazyTable.explain(
analyze=True)` executes the query under a recorder and renders the
plan annotated with measured rows/bytes/ms per node (EXPLAIN ANALYZE
— see `plan.report.PlanReport` and docs/telemetry.md).

The task-routing overlay is `plan.tasks` (`LogicalTaskPlan`,
`task_exchange`).
"""
from . import ir, optimizer, executor, report, tasks
from .ir import (Compute, Filter, GroupBy, Join, PlanNode, Project, Scan,
                 SetOp, Shuffle, Sort, case_when, col)
from .lazy import LazyTable, scan
from .optimizer import PlanStats, optimize
from .executor import execute, execute_analyzed
from .report import NodeMeasure, PlanReport
from .tasks import LogicalTaskPlan, task_exchange

__all__ = [
    "Compute", "Filter", "GroupBy", "Join", "LazyTable", "LogicalTaskPlan",
    "NodeMeasure", "PlanNode", "PlanReport", "PlanStats", "Project",
    "Scan", "SetOp", "Shuffle", "Sort", "case_when", "col", "execute",
    "execute_analyzed", "executor", "ir", "optimize", "optimizer",
    "report", "scan", "task_exchange", "tasks",
]
