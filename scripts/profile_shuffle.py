"""Phase-timed breakdown of the two-phase exchange (VERDICT r03 #3).

Times each phase of parallel/shuffle.exchange on the attached backend
with honest syncs (every phase is forced with a one-element
device_get probe) and writes a JSON
breakdown next to the repo's bench artifacts.

Usage: python scripts/profile_shuffle.py [n_rows_log2=24]
"""
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def main(log2n: int = 24) -> dict:
    import cylon_tpu as ct
    from cylon_tpu.ops import hash as _hash
    from cylon_tpu.parallel import shard as _shard
    from cylon_tpu.parallel import shuffle as _shuffle

    ctx = ct.CylonContext.InitDistributed(ct.TPUConfig())
    world = ctx.get_world_size()
    n = 1 << log2n
    rng = np.random.default_rng(2)
    t = ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, n, n),
        "v": rng.normal(size=n).astype(np.float32)})
    t = _shard.distribute(t, ctx)
    targets = _shard.pin(_hash.partition_targets([t.get_column(0)], world),
                         ctx)
    emit = _shard.pin(t.emit_mask(), ctx)
    payload = {"k": _shard.pin(t.get_column(0).data, ctx),
               "v": _shard.pin(t.get_column(1).data, ctx)}

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

    res = {"n_rows": n, "world": world,
           "backend": jax.devices()[0].platform}

    # phase 0: bare host round trip (every sync below includes one of
    # these)
    probe = jnp.zeros(1, jnp.int32)
    res["host_round_trip_s"] = best_of(lambda: jax.device_get(probe[0]))

    # phase 1: count program (compiled compute, forced via device_get)
    cf = _shuffle._count_fn(ctx.mesh)

    def count_only():
        sync(cf(targets, emit))
    res["count_program_s"] = best_of(count_only)

    # phase 2: the count HOST SYNC as exchange() actually pays it
    # (full [W,W] matrix device_get)
    def count_sync():
        np.asarray(jax.device_get(cf(targets, emit)))
    res["count_plus_fetch_s"] = best_of(count_sync)

    # phase 3: exchange program alone with precomputed counts — at W=1
    # this routes through the COUNTED (bucket-sort) path, i.e. the
    # pre-round-5 behavior; kept as the floor comparison
    counts = np.asarray(jax.device_get(cf(targets, emit)))

    def exchange_only():
        out, new_emit, _cap, _meta = _shuffle.exchange(
            payload, targets, emit, ctx, counts=counts)
        sync(out)
    res["exchange_program_s"] = best_of(exchange_only)

    # phase 3b: the bucket-sort FLOOR — one stable multi-operand sort of
    # the same operand set, nothing else. If exchange_program_s ≈
    # sort_floor_s, the counted exchange is sort-bound and the fused
    # world-1 identity path (below) is the only way past it
    tkey = jnp.where(emit, targets.astype(jnp.int32), world)
    iota = jnp.arange(n, dtype=jnp.int32)
    sort_fn = jax.jit(lambda tk, ops: jax.lax.sort(
        (tk,) + tuple(ops) + (iota,), num_keys=1, is_stable=True))

    def sort_floor():
        sync(sort_fn(tkey, tuple(payload.values())))
    res["sort_floor_s"] = best_of(sort_floor)

    # phase 3c: raw-copy HBM bandwidth floor — one jitted read+write
    # pass over the payload (x+0 defeats aliasing), the wall a
    # bandwidth-bound partition cannot beat. partition walls land
    # between this and sort_floor_s; the Pallas kernel's win is
    # (partition_sort_s − partition_pallas_s) once TPU rounds resume.
    copy_fn = jax.jit(lambda p: jax.tree.map(lambda x: x + 0, p))

    def copy_floor():
        sync(copy_fn(payload))
    res["copy_floor_s"] = best_of(copy_floor)

    # phase 3d: the partition wall per path — the unfused partition
    # program (bucket sort | fused Pallas hash+bucket+scatter kernel),
    # isolated from the chunk stream. The pallas leg runs only where
    # the kernel compiles (TPU); the interpreter path would measure the
    # interpreter, not the chip.
    on_tpu = jax.devices()[0].platform == "tpu"
    p_ok0, blk0, _ = _shuffle._padded_route(counts, payload, world,
                                            ctx.memory_pool
                                            .comm_budget_bytes())
    routed_part = _shuffle._partition_path(ctx.mesh, world, payload)
    # artifact carries the PUBLIC label (pallas|sort) — "interp" is an
    # internal spelling no other surface exposes
    res["partition_path"] = _shuffle.partition_path_label(routed_part)
    if p_ok0 and blk0 >= 16 and world >= 2:
        cb0 = _shuffle._pow2_floor(max(blk0 // 8, 1))

        def time_partition(part):
            fn = _shuffle._exchange_partition_fn(ctx.mesh, blk0, cb0,
                                                 part)

            def run():
                sync(fn(payload, targets, emit)[0])
            return best_of(run)

        res["partition_sort_s"] = time_partition("sort")
        res["partition_pallas_s"] = time_partition("pallas") \
            if on_tpu else None
    else:
        res["partition_sort_s"] = None
        res["partition_pallas_s"] = None

    # end to end, default routing (round-5: at W=1 this is the FUSED
    # count+exchange — in-program counts, device-side all-live identity)
    def full():
        out, new_emit, _cap, _meta = _shuffle.exchange(
            payload, targets, emit, ctx, dense=True)
        sync(out)
    res["end_to_end_s"] = best_of(full)

    # phase 4: the overlapped (chunked, double-buffered) pipeline —
    # per-phase chunk timings. Geometry comes from the real chunk plan;
    # when the default CYLON_EXCHANGE_CHUNK_BYTES would not chunk at
    # this scale, an 8-chunk split is forced (recorded as chunks) so
    # the phases are measurable at any n. overlap_ratio compares the
    # pipelined chunk stream against the same chunks dispatched with a
    # sync barrier after each — the wall-clock the overlap actually
    # removes.
    budget = ctx.memory_pool.comm_budget_bytes()
    row_bytes_p = _shuffle._payload_row_bytes(payload)
    p_ok, block, _mb = _shuffle._padded_route(counts, payload, world,
                                              budget)
    if p_ok and block >= 16:
        cb, chunks = _shuffle._chunk_plan(block, world, row_bytes_p)
        if chunks == 1:
            cb, chunks = block // 8, 8
        part_fn = _shuffle._exchange_partition_fn(
            ctx.mesh, block, cb, routed_part)
        step_fn = _shuffle._exchange_chunk_fn(ctx.mesh, block, cb)

        def partition_only():
            sync(part_fn(payload, targets, emit)[0])
        res["partition_s"] = best_of(partition_only)

        def chunk_stream(serialize):
            # fresh partition outputs per run: the chunk program
            # donates its accumulator on TPU, so a timed closure must
            # never reuse a consumed buffer
            padded, start, _ci, _em, outs = part_fn(payload, targets,
                                                    emit)
            for k in range(chunks):
                outs = step_fn(padded, start, outs, np.int32(k))
                if serialize:
                    sync(outs)
            sync(outs)

        pipelined = best_of(lambda: chunk_stream(False))
        serial = best_of(lambda: chunk_stream(True))
        res["exchange_s"] = round(
            max(pipelined - res["partition_s"], 0.0), 5)
        res["exchange_serial_s"] = serial
        res["overlap_ratio"] = round(max(0.0, 1.0 - pipelined / serial)
                                     if serial > 0 else 0.0, 4)
        res["chunks"] = chunks
        res["chunk_block"] = cb
    else:
        res["partition_s"] = None
        res["exchange_s"] = None
        res["overlap_ratio"] = None
        res["chunks"] = 0
        res["chunk_block"] = 0

    bytes_moved = n * 12  # k int64? int32+float32+mask-ish; report both
    row_bytes = sum(int(np.dtype(np.asarray(v).dtype).itemsize)
                    for v in payload.values())
    res["row_bytes"] = row_bytes
    res["gbps_end_to_end"] = n * row_bytes / res["end_to_end_s"] / 1e9
    res["gbps_exchange_only"] = (n * row_bytes
                                 / res["exchange_program_s"] / 1e9)
    del bytes_moved
    for k, v in res.items():
        if isinstance(v, float):
            res[k] = round(v, 5)
    return res


if __name__ == "__main__":
    log2n = int(sys.argv[1]) if len(sys.argv) > 1 else 24
    out = main(log2n)
    print(json.dumps(out))
    with open(os.path.join(os.path.dirname(__file__), "..",
                           f"PROFILE_shuffle.json"), "w") as f:
        json.dump(out, f, indent=1)
