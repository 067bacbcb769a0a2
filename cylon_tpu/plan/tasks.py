"""Task-routed all-to-all — ArrowTaskAllToAll parity (the reference
shipped this overlay next to its LogicalTaskPlan,
arrow_task_all_to_all.h:9-57).

Reference: cpp/src/cylon/arrow/arrow_task_all_to_all.h:9-57 (.cpp) — a
task-graph overlay the reference never finished: `LogicalTaskPlan` holds
task→worker maps and `ArrowTaskAllToAll` inserts tables BY TASK ID,
delivering each to the worker that owns the task (mutex-guarded, spun
via WaitForCompletion).

The TPU-native form maps logical tasks onto MESH SHARDS: the plan
assigns each task id to a shard; ``task_exchange`` routes every row of a
batch to the shard owning its task in ONE collective exchange (the same
two-phase count+exchange the joins use — no mutexes, no spin loops;
program completion is the delivery guarantee). Receivers read their
tasks' rows off their own shard. This is deliberately minimal — the
reference's overlay was infrastructure for a task runtime that was
never built; this covers the same insert-by-task / deliver-to-owner
capability on the mesh."""
from __future__ import annotations

from typing import Dict, List

import jax.numpy as jnp
import numpy as np

from ..context import CylonContext
from ..data.table import Table
from ..status import Code, CylonError
from ..parallel import shard
from ..parallel.dist_ops import _exchange_table


class LogicalTaskPlan:
    """task id → owning shard (reference: LogicalTaskPlan's
    task_to_worker / worker_to_task maps, arrow_task_all_to_all.h:9-37).
    Workers ARE mesh shards here."""

    def __init__(self, task_to_worker: Dict[int, int], world: int):
        for t, w in task_to_worker.items():
            if not (0 <= w < world):
                raise CylonError(Code.Invalid,
                                 f"task {t} mapped to worker {w} "
                                 f"outside world {world}")
        self.task_to_worker = dict(task_to_worker)
        self.world = world

    def worker_of(self, task_id: int) -> int:
        w = self.task_to_worker.get(int(task_id))
        if w is None:
            raise CylonError(Code.KeyError, f"unknown task {task_id}")
        return w

    def tasks_of(self, worker: int) -> List[int]:
        return sorted(t for t, w in self.task_to_worker.items()
                      if w == worker)


def task_exchange(table: Table, task_ids, plan: LogicalTaskPlan,
                  ctx: CylonContext = None) -> Table:
    """Deliver each row to the shard owning its task: the insert(+task
    header) / receive-callback protocol of ArrowTaskAllToAll collapses
    into one routed exchange. ``task_ids``: per-row int array. Returns
    the routed table with the task-id column appended as
    ``__task__`` (receivers filter their own tasks locally)."""
    import jax

    ctx = ctx or table._ctx
    t = shard.distribute(table, ctx)
    host_ids = np.asarray(task_ids).astype(np.int32)
    # validate LIVE rows only — dead (masked) slots may carry filler
    # ids and never route
    live = host_ids
    if t.row_mask is not None and host_ids.shape[0] == t.capacity:
        mask = np.asarray(jax.device_get(t.row_mask))
        live = host_ids[mask[: host_ids.shape[0]]]
    unknown = set(np.unique(live).tolist()) - set(plan.task_to_worker)
    if unknown:
        raise CylonError(Code.KeyError,
                         f"task ids not in plan: {sorted(unknown)[:8]}")
    ids = jnp.asarray(host_ids)
    if ids.shape[0] != t.capacity:
        # pad to the distributed capacity (dead rows never route)
        pad = t.capacity - ids.shape[0]
        if pad < 0:
            raise CylonError(Code.Invalid, "task_ids longer than table")
        ids = jnp.concatenate([ids, jnp.zeros(pad, jnp.int32)])
    # task → worker lookup as a device table (tasks are small)
    max_task = max(plan.task_to_worker) if plan.task_to_worker else 0
    lut = np.zeros(max_task + 1, np.int32)
    for task, w in plan.task_to_worker.items():
        lut[task] = w
    targets = shard.pin(jnp.take(jnp.asarray(lut),
                                 jnp.clip(ids, 0, max_task)), ctx)
    ids = shard.pin(ids, ctx)
    emit = shard.pin(t.emit_mask(), ctx)
    cols, new_emit, xout = _exchange_table(t, targets, emit, ctx,
                                           {"__task__": ids})
    from ..data.column import Column
    from .. import dtypes

    out_cols = cols + [Column(xout["__task__"], dtypes.Int32(), None,
                              None, "__task__")]
    return Table(out_cols, ctx, new_emit)
