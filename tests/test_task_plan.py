"""LogicalTaskPlan + task-routed exchange (ArrowTaskAllToAll analog;
reference: arrow_task_all_to_all.h:9-57)."""
import numpy as np
import pytest

import cylon_tpu as ct
from cylon_tpu.plan.tasks import LogicalTaskPlan, task_exchange


@pytest.fixture(scope="module")
def dctx():
    return ct.CylonContext.InitDistributed(ct.TPUConfig(world_size=4))


def test_task_plan_maps(dctx):
    plan = LogicalTaskPlan({0: 0, 1: 2, 2: 2, 3: 1}, 4)
    assert plan.worker_of(1) == 2
    assert plan.tasks_of(2) == [1, 2]
    with pytest.raises(Exception):
        plan.worker_of(9)
    with pytest.raises(Exception):
        LogicalTaskPlan({0: 7}, 4)


def test_task_exchange_delivers_to_owner(dctx):
    import jax

    world = dctx.get_world_size()
    rng = np.random.default_rng(5)
    n = 4000
    tasks = rng.integers(0, 6, n)
    plan = LogicalTaskPlan({t: t % world for t in range(6)}, world)
    t = ct.Table.from_pydict(dctx, {"v": np.arange(n), "z": rng.normal(size=n)})
    routed = task_exchange(t, tasks, plan, dctx)
    assert routed.row_count == n
    # every row landed on the shard owning its task
    cap = routed.capacity // world
    task_col = np.asarray(jax.device_get(
        routed.get_column(routed.column_count - 1).data))
    emit = np.asarray(jax.device_get(routed.emit_mask()))
    for s in range(world):
        sl = slice(s * cap, (s + 1) * cap)
        owned = {tid for tid in range(6) if tid % world == s}
        got = set(task_col[sl][emit[sl]].tolist())
        assert got <= owned, (s, got, owned)
    # payload preserved as a multiset
    v = np.asarray(jax.device_get(routed.get_column(0).data))[emit]
    assert sorted(v.tolist()) == list(range(n))
