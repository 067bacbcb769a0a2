"""Scaling-shape sweep (VERDICT r03 #8, r04 #5; reference analog:
cpp/src/experiments/run_dist_scaling.py:1-60, which sweeps MPI world
sizes 1-160 with weak/strong scaling vs Dask/Spark).

Here the mesh is W virtual CPU devices in one process (the same
simulation the test matrix uses), swept over world sizes {1,2,4,8} in
BOTH scaling modes:

* strong: global rows fixed, per-shard rows shrink with W;
* weak:   per-shard rows fixed, global rows grow with W — the r4 ask.

Wall-clock on the CPU backend is NOT TPU performance — and, critically,
all W "devices" share one host's cores, so per-shard compute SERIALIZES:
a W-wide sweep cannot show real speedup here by construction (every
compiled program runs W shard-programs back-to-back on the same
silicon). What the artifact captures is the SCALING SHAPE — how the
per-world FIXED costs (count sync, splitter agreement, per-shard
program count) grow with W — plus a phase attribution so device-side
growth is separable from virtual-mesh artifact. See the "diagnosis"
key of SCALING.json for the committed read of the numbers.

Usage: python scripts/scaling_sweep.py [rows_log2=20]
Writes SCALING.json at the repo root.
"""
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import jax.numpy as jnp  # noqa: E402


def probe(x):
    jax.device_get(jax.tree.leaves(x)[0].reshape(-1)[:1])


def best_of(f, iters=3):
    f()
    b = 1e9
    for _ in range(iters):
        t0 = time.perf_counter()
        f()
        b = min(b, time.perf_counter() - t0)
    return b


def sweep_world(world: int, n: int) -> dict:
    import cylon_tpu as ct
    from cylon_tpu.ops import hash as _hash
    from cylon_tpu.parallel import shard as _shard
    from cylon_tpu.parallel import shuffle as _shuffle
    from cylon_tpu.parallel import dist_ops as D

    ctx = ct.CylonContext.InitDistributed(ct.TPUConfig(world_size=world))
    rng = np.random.default_rng(world)
    left = _shard.distribute(ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, n, n).astype(np.int64),
        "v": rng.normal(size=n).astype(np.float32)}), ctx)
    right = _shard.distribute(ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, n, n).astype(np.int64),
        "w": rng.normal(size=n).astype(np.float32)}), ctx)

    targets = _shard.pin(
        _hash.partition_targets([left.get_column(0)], world), ctx)
    emit = _shard.pin(left.emit_mask(), ctx)
    payload = {"k": _shard.pin(left.get_column(0).data, ctx),
               "v": _shard.pin(left.get_column(1).data, ctx)}

    # phase attribution: the COUNT phase alone (program + host fetch) —
    # the per-exchange fixed cost that scales with the W compare-sum
    # passes (shuffle.py _target_counts). world 1 reports 0: the fused
    # padded body computes counts in-program (round-5) and never syncs.
    if world > 1:
        def count_phase():
            np.asarray(jax.device_get(
                _shuffle._count_fn(ctx.mesh)(targets, emit)))
        t_count = best_of(count_phase)
    else:
        t_count = 0.0

    # splitter agreement (distributed_sort's fixed cost): one batched
    # sample fetch + host quantiles (round-5: was one fetch per lane)
    lanes = [_shard.pin(left.get_column(0).data.astype(jnp.uint64), ctx)]

    def splitters():
        D._range_splitters(ctx, lanes, emit)
    t_split = best_of(splitters)

    def ex():
        out, _e, _c, _m = _shuffle.exchange(payload, targets, emit, ctx,
                                            dense=left.row_mask is None)
        probe(out)

    t_ex = best_of(ex)
    row_bytes = sum(int(np.dtype(np.asarray(v).dtype).itemsize)
                    for v in payload.values())

    cfg = left._make_join_config(right, "inner", "sort", {"on": ["k"]})

    def dj():
        out = D.distributed_join(left, right, cfg, force_exchange=True)
        probe(out.get_column(0).data)

    t_join = best_of(dj, iters=2)

    return {
        "world": world,
        "global_rows": n,
        "rows_per_shard": n // world,
        "count_phase_s": round(t_count, 4),
        "splitter_phase_s": round(t_split, 4),
        "exchange_s": round(t_ex, 4),
        "exchange_gb_per_s": round(n * row_bytes / t_ex / 1e9, 4),
        "dist_join_s": round(t_join, 4),
        "dist_join_rows_per_s": round(2 * n / t_join, 1),
    }


DIAGNOSIS = (
    "Anti-scaling on this artifact is dominated by the virtual mesh: all W "
    "'devices' are one host CPU, so per-shard compute serializes and strong-"
    "scaling speedup is structurally impossible (W programs x (N/W rows) = "
    "constant work, plus per-world overhead). The separable DEVICE-SIDE "
    "per-world costs, measured in count_phase_s/splitter_phase_s: (1) the "
    "count phase runs W compare-sum passes per shard (W^2 total vector "
    "passes, shuffle.py _target_counts) plus one ~100ms-class host fetch — "
    "round-5 removed it entirely at W=1 (fused in-program counts) and added "
    "a repeat-shuffle count cache; (2) splitter agreement is one batched "
    "device_get (round-5: was per-lane) + O(W*samples) host quantiles; "
    "(3) the padded exchange moves W slices per leaf — W-linear program "
    "size, constant per-byte volume. On a real ICI mesh (1) and (2) are "
    "fixed ~100ms-class syncs amortized by per-shard work, and the weak-"
    "scaling rows below are the honest predictor: efficiency = t(W1)/t(W) "
    "at fixed per-shard rows, with the virtual-mesh serialization caveat "
    "that t(W) here includes W serialized shard-programs. NOTE on the W=1 "
    "baseline: round-5's fused world-1 exchange (identity when all rows "
    "live — no bucket sort, no count sync) makes W=1 nearly free, so "
    "vs-W1 ratios now conflate that optimization with scaling shape; read "
    "the W>=2 rows against each other instead — weak-mode exchange_s/"
    "dist_join_s growing ~linearly in W at fixed per-shard rows is "
    "exactly the serialized-shard-programs artifact, while count_phase_s "
    "and splitter_phase_s (the real per-world fixed costs) stay in the "
    "low-millisecond range on CPU; on the TPU they are not measured."
)


def main(log2n: int) -> dict:
    n = 1 << log2n
    res = {"backend": "cpu-virtual-mesh",
           "modes": {}, "diagnosis": DIAGNOSIS}

    strong = {"mode": "strong-scaling", "global_rows": n, "worlds": []}
    for w in (1, 2, 4, 8):
        r = sweep_world(w, n)
        strong["worlds"].append(r)
        print(json.dumps(r), flush=True)
    base = strong["worlds"][0]["dist_join_s"]
    for r in strong["worlds"]:
        r["join_speedup_vs_w1"] = round(base / r["dist_join_s"], 3)
    res["modes"]["strong"] = strong

    per_shard = n // 8
    weak = {"mode": "weak-scaling", "rows_per_shard": per_shard,
            "worlds": []}
    for w in (1, 2, 4, 8):
        r = sweep_world(w, per_shard * w)
        weak["worlds"].append(r)
        print(json.dumps(r), flush=True)
    base = weak["worlds"][0]["dist_join_s"]
    for r in weak["worlds"]:
        # ideal weak scaling: time stays flat as W and global rows grow
        r["weak_efficiency"] = round(base / r["dist_join_s"], 3)
    res["modes"]["weak"] = weak
    return res


if __name__ == "__main__":
    out = main(int(sys.argv[1]) if len(sys.argv) > 1 else 20)
    print(json.dumps(out))
    with open(os.path.join(os.path.dirname(__file__), "..",
                           "SCALING.json"), "w") as f:
        json.dump(out, f, indent=1)
