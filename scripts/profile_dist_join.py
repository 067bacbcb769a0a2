"""Phase-timed breakdown of the distributed-join composition at bench
shape (VERDICT r03 #2/#3 follow-up). Every phase is forced with a
one-element device_get probe;
subtract host_round_trip_s from each phase for pure device time.

Usage: python scripts/profile_dist_join.py [n_rows_log2=24]
Writes PROFILE_dist_join.json at the repo root.
"""
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402
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


def main(log2n: int = 24) -> dict:
    import cylon_tpu as ct
    from cylon_tpu.ops import join as _join
    from cylon_tpu.parallel import dist_ops as D
    from cylon_tpu.parallel import shard as _shard
    from cylon_tpu.parallel.shuffle import count_pair

    ctx = ct.CylonContext.InitDistributed(ct.TPUConfig())
    world = ctx.get_world_size()
    n = 1 << log2n
    rng = np.random.default_rng(1)
    left = _shard.distribute(ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, n, n),
        "v": rng.normal(size=n).astype(np.float32)}), ctx)
    right = _shard.distribute(ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, n, n),
        "w": rng.normal(size=n).astype(np.float32)}), ctx)

    res = {"n_rows": n, "world": world,
           "backend": jax.devices()[0].platform}
    z = jnp.zeros(1, jnp.int32)
    res["host_round_trip_s"] = best_of(lambda: jax.device_get(z[0]))

    lcols = [left._columns[0]]
    rcols = [right._columns[0]]

    def keybits_targets(t, cols, other):
        bits, kv, h1s = D._dist_key_bits(ctx, cols, other)
        targets = _shard.pin(D._targets_from_hashes(ctx, h1s), ctx)
        probe((bits, targets))
        return bits, kv, targets

    res["keybits_targets_both_s"] = best_of(
        lambda: (keybits_targets(left, lcols, rcols),
                 keybits_targets(right, rcols, lcols)))

    lb, lkv, lt_ = keybits_targets(left, lcols, rcols)
    rb, rkv, rt_ = keybits_targets(right, rcols, lcols)
    lemit = _shard.pin(left.emit_mask(), ctx)
    remit = _shard.pin(right.emit_mask(), ctx)

    res["count_pair_s"] = best_of(
        lambda: count_pair(lt_, lemit, rt_, remit, ctx))
    cl, cr = count_pair(lt_, lemit, rt_, remit, ctx)

    def exch(t, bits, kv, targets, emit, counts):
        extra = {f"k{j}": b for j, b in enumerate(bits)}
        extra["kv"] = kv
        cols, emit_s, xout = D._exchange_table(t, targets, emit, ctx,
                                               extra, counts=counts)
        probe(xout["k0"])
        return cols, emit_s, xout

    res["exchange_left_s"] = best_of(
        lambda: exch(left, lb, lkv, lt_, lemit, cl))
    res["exchange_right_s"] = best_of(
        lambda: exch(right, rb, rkv, rt_, remit, cr))
    lcols_s, lemit_s, lx = exch(left, lb, lkv, lt_, lemit, cl)
    rcols_s, remit_s, rx = exch(right, rb, rkv, rt_, remit, cr)
    lkb = tuple(lx[f"k{j}"] for j in range(len(lb)))
    rkb = tuple(rx[f"k{j}"] for j in range(len(rb)))

    jt = _join.JoinType.INNER
    mode = D._dist_stream_mode(lkb, rkb, jt, world)
    ldat = tuple(_shard.pin(c.data, ctx) for c in lcols_s)
    lval = tuple(_shard.pin(c.valid_mask(), ctx) for c in lcols_s)
    rdat = tuple(_shard.pin(c.data, ctx) for c in rcols_s)
    rval = tuple(_shard.pin(c.valid_mask(), ctx) for c in rcols_s)
    if mode is not None:
        hash_mode, br = mode
        a_desc, b_desc = _join.plan_lane_descs(ldat, lval, rdat, rval, jt)

        def plan():
            rep, cd, a_s, b_s = D._join_plan_stream_fn(
                ctx.mesh, jt, len(lkb), a_desc, b_desc, br, hash_mode)(
                lkb, lx["kv"], lemit_s, rkb, rx["kv"], remit_s,
                ldat, lval, rdat, rval)
            cm = np.asarray(jax.device_get(rep)).reshape(world, -1)
            return cm, cd, a_s, b_s

        res["plan_plus_sync_s"] = best_of(plan)
        cm, counts_dev, a_streams, b_streams = plan()
        cap_e = _join.stream_expand_capacity(int(cm[:, 0].max()), br)

        def mat():
            out = D._join_mat_stream_fn(ctx.mesh, jt, cap_e, a_desc,
                                        b_desc, br)(
                counts_dev, a_streams, b_streams, ldat, lval, rdat, rval)
            probe(out[0])

        res["materialize_s"] = best_of(mat)
    else:
        # stream plan is TPU-only — profile the XLA plan path instead
        # (the CPU-mesh shape of the same phases)
        res["stream_mode"] = "unavailable (xla plan profiled)"

        def plan():
            counts2, lo, m, bperm, un_mask = D._join_plan_fn(
                ctx.mesh, jt)(lkb, lx["kv"], lemit_s, rkb, rx["kv"],
                              remit_s)
            cm = np.asarray(jax.device_get(counts2)).reshape(world, 2)
            return cm, (lo, m, bperm, un_mask)

        res["plan_plus_sync_s"] = best_of(plan)
        cm, (lo, m, bperm, un_mask) = plan()
        from cylon_tpu.util import pow2 as _pow2

        cap_p = _pow2(int(cm[:, 0].max()))

        def mat():
            out = D._join_mat_fn(ctx.mesh, jt, cap_p, 0)(
                lo, m, bperm, un_mask, lemit_s, ldat, lval, rdat, rval)
            probe(out[0])

        res["materialize_s"] = best_of(mat)

    total = (res["keybits_targets_both_s"] + res["count_pair_s"]
             + res["exchange_left_s"] + res["exchange_right_s"]
             + res["plan_plus_sync_s"] + res["materialize_s"])
    res["sum_phases_s"] = total

    # the adaptive alternative (PR 15): the whole broadcast-hash-join
    # composition against a 1000:1 build side — zero all-to-all, so
    # broadcast_s beside the shuffle walls above quantifies exactly
    # what eliding the exchange buys at this scale on this backend
    n_build = max(n // 1000, 64)
    small = _shard.distribute(ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, n, n_build),
        "w": rng.normal(size=n_build).astype(np.float32)}), ctx)
    cfg = _join.JoinConfig(_join.JoinType.INNER, [0], [0],
                           _join.JoinAlgorithm.AUTO)
    res["broadcast_build_rows"] = n_build

    def bcast():
        probe(D.broadcast_hash_join(left, small, cfg, build_side=1)
              ._columns[0].data)

    res["broadcast_s"] = best_of(bcast)
    for k, v in res.items():
        if isinstance(v, float):
            res[k] = round(v, 4)
    return res


if __name__ == "__main__":
    out = main(int(sys.argv[1]) if len(sys.argv) > 1 else 24)
    print(json.dumps(out))
    with open(os.path.join(os.path.dirname(__file__), "..",
                           "PROFILE_dist_join.json"), "w") as f:
        json.dump(out, f, indent=1)
