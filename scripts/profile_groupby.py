"""Phase-timed breakdown of the groupby-aggregate (VERDICT r04 #9 —
slowest tracked config). Round-5 reworked the op to ONE fused presort
(values/validity/iota ride the sort, dead rows last) + sorted-id
segment reductions with deduped sub-reductions; this profile attributes
what remains: the sort, the n_groups host sync, the segment scatters,
and key materialization.

Usage: python scripts/profile_groupby.py [n_rows_log2=24] [groups_log2=20]
Writes PROFILE_groupby.json at the repo root.
"""
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def main(log2n: int = 24, log2g: int = 20) -> dict:
    import cylon_tpu as ct
    from cylon_tpu.ops import groupby as _groupby
    from cylon_tpu.ops import order as _order
    from cylon_tpu.util import pow2 as _pow2

    ctx = ct.CylonContext.Init()
    n, g = 1 << log2n, 1 << log2g
    rng = np.random.default_rng(1)
    t = ct.Table.from_pydict(ctx, {
        "g": rng.integers(0, g, n).astype(np.int32),
        "x": rng.normal(size=n).astype(np.float32),
        "y": rng.integers(0, 100, n).astype(np.int32)})

    def sync(x):
        jax.device_get(jax.tree.leaves(x)[0].reshape(-1)[:1])

    def best_of(f, iters=3):
        f()
        b = 1e9
        for _ in range(iters):
            t0 = time.perf_counter()
            f()
            b = min(b, time.perf_counter() - t0)
        return b

    res = {"n_rows": n, "n_groups": g,
           "backend": jax.devices()[0].platform}

    keys = tuple(_order.sort_keys([t._columns[0]]))
    emit = t.emit_mask()
    values = (t._columns[1].data, t._columns[2].data, t._columns[1].data)
    valids = (None, None, None)  # all-valid: masks never ride the sort
    ops = (_groupby.AggregationOp.SUM, _groupby.AggregationOp.COUNT,
           _groupby.AggregationOp.MEAN)

    # phase 1: the fused presort alone
    def presort():
        sync(_groupby.presort_groups_jit(keys, emit, values, valids))
    res["presort_s"] = best_of(presort)

    # phase 1b: the n_groups scalar fetch (the op's single host sync)
    state = _groupby.presort_groups_jit(keys, emit, values, valids)
    vs, vm, emit_s, iota_s, new_grp, ng = state

    def ngroups_fetch():
        int(jax.device_get(ng))
    res["ngroups_fetch_s"] = best_of(ngroups_fetch)
    cap = _pow2(max(int(jax.device_get(ng)), 1))

    # phase 2: the sorted segment reductions alone
    def aggregate():
        rep, gv, results = _groupby.sorted_segment_aggregate_jit(
            new_grp, emit_s, iota_s, vs, vm, cap, ops, (1, 2, 1),
            (True, True, True))
        sync(results[0][0])
    res["segment_agg_s"] = best_of(aggregate)

    # end to end through the Table surface (adds key materialization)
    def full():
        out = t.groupby(0, [1, 2, 1], ["sum", "count", "mean"])
        sync(out._columns[0].data)
    res["end_to_end_s"] = best_of(full)

    res["rows_per_s"] = n / res["end_to_end_s"]
    for k, v in res.items():
        if isinstance(v, float):
            res[k] = round(v, 5)
    return res


if __name__ == "__main__":
    log2n = int(sys.argv[1]) if len(sys.argv) > 1 else 24
    log2g = int(sys.argv[2]) if len(sys.argv) > 2 else 20
    out = main(log2n, log2g)
    print(json.dumps(out))
    with open(os.path.join(os.path.dirname(__file__), "..",
                           "PROFILE_groupby.json"), "w") as f:
        json.dump(out, f, indent=1)
