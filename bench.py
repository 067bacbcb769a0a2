"""Benchmark driver — the BASELINE.md tracked configs on the attached
chip(s).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "detail"}.
The primary metric is the DISTRIBUTED inner-join throughput — the honest
shuffle+join composition the baseline measures: even on one chip the
exchange executes on a 1-wide mesh (``force_exchange``), so the count
phase, blockwise all_to_all rounds and compaction are all in the timed
path. The local join is reported separately (detail.local_inner_join),
as is the raw shuffle bandwidth (detail.shuffle_gbps — a BASELINE.md
tracked metric). The rest of the matrix (groupby-aggregate, global sort,
set ops, TPC-H-Q5-style pipeline) rides in detail.suite.

Timing discipline: JAX returns before the device finishes, so every
timed closure ends with ``jax.block_until_ready`` on its result's
terminal buffers — real execution, not dispatch, is on the clock.

``python bench.py`` runs in this ONE process and needs a TPU: any other
platform is an error, and so is a config that raised (the others still
run and are printed; the exit code is non-zero).

Baseline: the reference's published single-worker distributed inner join
— 200M rows in 141.5 s ≈ 1.414M rows/s/worker (reference:
docs/docs/arch.md:152, arXiv:2007.09589; see BASELINE.md). vs_baseline is
our rows/sec/chip over that per-worker rate. The other configs have no
published reference numbers (BASELINE.md:26-28).
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np

from cylon_tpu.benchutils import round_sig as _sig

# Cylon-MPI, 1 worker: 200M-row inner join in 141.5 s (BASELINE.md)
_BASELINE_ROWS_PER_S = 200e6 / 141.5


def _sync(t):
    """End a timed closure: wait for every terminal buffer of the
    result and the row mask — varbytes columns own a separate WORD
    buffer (the lane-interleave is a separate chained program from the
    lengths)."""
    import jax

    bufs = [c.data for c in t._columns]
    bufs += [c.varbytes.words for c in t._columns if c.is_varbytes]
    if t.row_mask is not None:
        bufs.append(t.row_mask)
    jax.block_until_ready(bufs)


def _time(fn, iters):
    fn()  # warmup/compile
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return min(times)


def _mk_ctx():
    import cylon_tpu as ct

    # a distributed context even at world 1: the bench times the real
    # exchange path on whatever mesh is attached
    return ct.CylonContext.InitDistributed(ct.TPUConfig())


def _join_tables(ctx, n_rows):
    import cylon_tpu as ct

    rng = np.random.default_rng(0)
    left = ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, n_rows, n_rows).astype(np.int32),
        "v": rng.normal(size=n_rows).astype(np.float32),
    })
    right = ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, n_rows, n_rows).astype(np.int32),
        "w": rng.normal(size=n_rows).astype(np.float32),
    })
    return left, right


def bench_local_join(ctx, n_rows: int, iters: int) -> dict:
    """Per-chip local join (no shuffle) — the kernel-only number."""
    left, right = _join_tables(ctx, n_rows)
    out = {}

    def one():
        t = left.join(right, "inner", on="k")
        _sync(t)
        out["t"] = t

    best = _time(one, iters)
    total_rows = 2 * n_rows
    return {
        "rows_per_s_per_chip": total_rows / best,
        "wall_s_best": _sig(best),
        "out_rows": out["t"].row_count,
    }


def bench_dist_join(ctx, n_rows: int, iters: int) -> dict:
    """The honest distributed composition: hash-partition + count
    exchange + blockwise all_to_all + per-shard join — forced even on a
    1-wide mesh so the collective machinery is always on the clock."""
    from cylon_tpu.ops.join import JoinConfig
    from cylon_tpu.parallel import dist_ops

    left, right = _join_tables(ctx, n_rows)
    cfg = JoinConfig.InnerJoin([0], [0])
    out = {}

    def one():
        t = dist_ops.distributed_join(left, right, cfg,
                                      force_exchange=True)
        _sync(t)
        out["t"] = t

    best = _time(one, iters)
    world = max(ctx.get_world_size(), 1)
    return {
        "rows_per_s_per_chip": 2 * n_rows / best / world,
        "wall_s_best": _sig(best),
        "out_rows": out["t"].row_count,
    }


def bench_shuffle(ctx, n_rows: int, iters: int) -> dict:
    """Raw shuffle bandwidth (BASELINE.md tracked metric): bytes of
    payload delivered through the two-phase count+blockwise exchange per
    second per chip."""
    import jax
    import jax.numpy as jnp

    from cylon_tpu.parallel import shard as _shard
    from cylon_tpu.parallel.shuffle import exchange

    rng = np.random.default_rng(7)
    world = max(ctx.get_world_size(), 1)
    payload = {
        "a": _shard.pin(jnp.asarray(
            rng.integers(0, 1 << 31, n_rows).astype(np.int32)), ctx),
        "b": _shard.pin(jnp.asarray(
            rng.normal(size=n_rows).astype(np.float32)), ctx),
        "c": _shard.pin(jnp.asarray(
            rng.integers(0, 1 << 31, n_rows).astype(np.int64)), ctx),
    }
    targets = _shard.pin(jnp.asarray(
        rng.integers(0, world, n_rows).astype(np.int32)), ctx)
    emit = _shard.pin(jnp.ones(n_rows, dtype=bool), ctx)
    bytes_per_row = 4 + 4 + 8

    def one():
        out, new_emit, _cap, _meta = exchange(payload, targets, emit, ctx,
                                              dense=True)
        jax.device_get(out["a"][:1])

    best = _time(one, iters)
    gbps = n_rows * bytes_per_row / best / 1e9 / world
    return {"gbps_per_chip": _sig(gbps, 4),
            "rows_per_s_per_chip": n_rows / best / world,
            "wall_s_best": _sig(best)}


def bench_shuffle_wide(ctx, n_rows: int, iters: int) -> dict:
    """Bandwidth-oriented shuffle config: 8 payload leaves (40 B/row —
    a realistic wide table). The narrow config's GB/s is dominated by
    the per-exchange fixed cost (bucket sort of the key + the count
    host sync, see PROFILE_shuffle.json); payload leaves ride the sort at
    near-memcpy cost, so bandwidth scales with row width."""
    import jax
    import jax.numpy as jnp

    from cylon_tpu.parallel import shard as _shard
    from cylon_tpu.parallel.shuffle import exchange

    rng = np.random.default_rng(8)
    world = max(ctx.get_world_size(), 1)
    payload = {}
    bytes_per_row = 0
    for i in range(6):
        payload[f"f{i}"] = _shard.pin(jnp.asarray(
            rng.normal(size=n_rows).astype(np.float32)), ctx)
        bytes_per_row += 4
    for i in range(2):
        payload[f"i{i}"] = _shard.pin(jnp.asarray(
            rng.integers(0, 1 << 31, n_rows).astype(np.int64)), ctx)
        bytes_per_row += 8
    targets = _shard.pin(jnp.asarray(
        rng.integers(0, world, n_rows).astype(np.int32)), ctx)
    emit = _shard.pin(jnp.ones(n_rows, dtype=bool), ctx)

    def one():
        out, new_emit, _cap, _meta = exchange(payload, targets, emit, ctx,
                                              dense=True)
        jax.device_get(out["f0"][:1])

    best = _time(one, iters)
    gbps = n_rows * bytes_per_row / best / 1e9 / world
    return {"gbps_per_chip": _sig(gbps, 4),
            "bytes_per_row": bytes_per_row,
            "rows_per_s_per_chip": n_rows / best / world,
            "wall_s_best": _sig(best)}


def bench_shuffle_pipeline(ctx, n_rows: int, iters: int) -> dict:
    """The overlapped (chunked, double-buffered) exchange pipeline vs
    the single-shot monolithic program, on the COUNTED padded route
    (the distributed-op composition's shape — the count matrix is
    fetched once, outside the timed region, exactly as the join/setop/
    groupby consumers pay it). Records, per benchtrend's
    LOWER_IS_BETTER gate: ``exchange_wall_s`` (the chunked pipeline's
    best wall) and ``collective_launches`` (program dispatches per
    chunked exchange with the fused partition+chunk-0 program — the
    artifact also carries ``collective_launches_nofuse`` to show the
    fusion win, strictly one launch fewer per exchange)."""
    import os

    import jax
    import jax.numpy as jnp

    from cylon_tpu import telemetry
    from cylon_tpu.parallel import shard as _shard
    from cylon_tpu.parallel import shuffle as _shuffle

    rng = np.random.default_rng(12)
    world = max(ctx.get_world_size(), 1)
    payload = {}
    bytes_per_row = 0
    for i in range(4):
        payload[f"f{i}"] = _shard.pin(jnp.asarray(
            rng.normal(size=n_rows).astype(np.float32)), ctx)
        bytes_per_row += 4
    payload["i0"] = _shard.pin(jnp.asarray(
        rng.integers(0, 1 << 31, n_rows).astype(np.int64)), ctx)
    bytes_per_row += 8
    targets = _shard.pin(jnp.asarray(
        rng.integers(0, world, n_rows).astype(np.int32)), ctx)
    emit = _shard.pin(jnp.ones(n_rows, dtype=bool), ctx)
    counts = np.asarray(jax.device_get(
        _shuffle._count_fn(ctx.mesh)(targets, emit)))
    # pick a chunk size that yields a >=4-deep pipeline at this scale
    # (the default 64 MiB knob only chunks at production payloads)
    _ok, block, _mb = _shuffle._padded_route(
        counts, payload, world, ctx.memory_pool.comm_budget_bytes())
    cbytes = max((world * bytes_per_row * block) // 4, 1 << 12)

    def launches():
        return telemetry.metrics_snapshot().get(
            "cylon_collective_launches_total", 0)

    def one(**kw):
        out, _e, _cap, meta = _shuffle.exchange(
            payload, targets, emit, ctx, counts=counts, **kw)
        jax.device_get(out["f0"][:1])
        return meta

    old = {k: os.environ.get(k) for k in
           ("CYLON_EXCHANGE_CHUNK_BYTES", "CYLON_EXCHANGE_OVERLAP")}
    os.environ["CYLON_EXCHANGE_CHUNK_BYTES"] = str(cbytes)
    os.environ["CYLON_EXCHANGE_OVERLAP"] = "1"
    try:
        meta = one()  # warmup + geometry
        chunks = meta.get("chunks", 1)
        l0 = launches()
        one()
        fused_launches = launches() - l0
        l0 = launches()
        one(fuse=False)
        nofuse_launches = launches() - l0
        chunked_s = _time(one, iters)
        # partition wall in isolation, on the ROUTED path (pallas on
        # TPU, sort elsewhere) — the number the fused Pallas kernel
        # exists to shrink; benchtrend gates it LOWER_IS_BETTER
        part = _shuffle._partition_path(ctx.mesh, world, payload)
        cb_p, _ = _shuffle._chunk_plan(block, world, bytes_per_row)
        pfn = _shuffle._exchange_partition_fn(ctx.mesh, block, cb_p,
                                              part)

        def partition_only():
            jax.device_get(jax.tree.leaves(
                pfn(payload, targets, emit)[0])[0][:1])

        partition_s = _time(partition_only, iters)
        os.environ["CYLON_EXCHANGE_OVERLAP"] = "0"
        single_s = _time(one, iters)
    finally:
        # restore BOTH knobs to their pre-config values: knobs read
        # live, so a popped override would silently re-enable the
        # default for every later suite config in this process
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    gbps = n_rows * bytes_per_row / chunked_s / 1e9 / world
    return {
        "exchange_wall_s": _sig(chunked_s),
        "partition_wall_s": _sig(partition_s),
        "partition_path": _shuffle.partition_path_label(part),
        "single_shot_wall_s": _sig(single_s),
        "speedup_vs_single_shot": _sig(single_s / chunked_s, 4)
        if chunked_s else 0.0,
        "chunks": int(chunks),
        "overlap_ratio": _sig((fused_launches - 1) / fused_launches, 4)
        if fused_launches else 0.0,
        "collective_launches": int(fused_launches),
        "collective_launches_nofuse": int(nofuse_launches),
        "gbps_per_chip": _sig(gbps, 4),
        "rows_per_s_per_chip": n_rows / chunked_s / world,
        "bytes_per_row": bytes_per_row,
    }


def bench_adaptive_join(ctx, n_rows: int, iters: int) -> dict:
    """Adaptive join execution (PR 15): the cold (exploratory shuffle)
    join vs the warm (learned broadcast) join on a 1000:1 size ratio,
    plus the Zipfian-keyed salted vs unsalted exchange. Gated metrics
    (scripts/benchtrend.py): ``broadcast_speedup`` (HIGHER — warm wall
    over cold wall) and ``salted_imbalance`` (LOWER_IS_BETTER — the
    salted exchange's max/mean shard-row imbalance; unsalted rides
    beside it as ``unsalted_imbalance`` for the delta). The warm run
    must dispatch strictly fewer collective launches than the cold run
    AND move zero payload-exchange bytes — both pinned in the
    artifact."""
    import os

    import jax

    import cylon_tpu as ct
    from cylon_tpu import plan, telemetry
    from cylon_tpu.parallel import dist_ops
    from cylon_tpu.telemetry import stats as stats_mod

    rng = np.random.default_rng(21)
    world = max(ctx.get_world_size(), 1)
    n_build = max(n_rows // 1000, 64)
    keys = max(n_build // 2, 1)
    left = ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, keys, n_rows).astype(np.int32),
        "v": rng.normal(size=n_rows).astype(np.float32)})
    right = ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, keys, n_build).astype(np.int32),
        "w": rng.normal(size=n_build).astype(np.float32)})

    def pipe():
        return plan.scan(left).join(plan.scan(right), on="k")

    def snap(name):
        return telemetry.metrics_snapshot().get(name, 0)

    def one():
        _sync(pipe().execute())

    stats_mod.reset()
    old = {k: os.environ.get(k)
           for k in ("CYLON_JOIN_ALGORITHM", "CYLON_STATS_MIN_OBS")}
    os.environ["CYLON_STATS_MIN_OBS"] = "2"
    try:
        # cold leg: the forced-shuffle program (the exact pre-adaptive
        # plan) — its executions double as the learning runs
        os.environ["CYLON_JOIN_ALGORITHM"] = "shuffle"
        cold_s = _time(one, iters)
        l0, b0 = snap("cylon_collective_launches_total"), \
            snap("cylon_shuffle_bytes_total")
        one()
        cold_launches = snap("cylon_collective_launches_total") - l0
        cold_bytes = snap("cylon_shuffle_bytes_total") - b0
        # warm leg: the learned statistics rewrite the shape
        os.environ["CYLON_JOIN_ALGORITHM"] = "auto"
        went_broadcast = "algo=broadcast" in pipe().explain()
        warm_s = _time(one, iters)
        l0, b0 = snap("cylon_collective_launches_total"), \
            snap("cylon_shuffle_bytes_total")
        one()
        warm_launches = snap("cylon_collective_launches_total") - l0
        warm_bytes = snap("cylon_shuffle_bytes_total") - b0
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    # salted vs unsalted exchange under a Zipfian key (70% hot)
    zk = np.where(rng.random(n_rows) < 0.7, 7,
                  rng.integers(0, 1 << 20, n_rows)).astype(np.int32)

    def zipf():
        return ct.Table.from_pydict(ctx, {
            "k": zk, "v": np.arange(n_rows, dtype=np.float32)})

    def imbalance(t):
        em = np.asarray(jax.device_get(t.emit_mask()))
        per = em.shape[0] // world
        rows = [int(em[i * per:(i + 1) * per].sum())
                for i in range(world)]
        return max(rows) / max(sum(rows) / world, 1.0)

    plain = dist_ops.shuffle(zipf(), ["k"])
    unsalted_imb = imbalance(plain)
    unsalted_s = _time(lambda: _sync(dist_ops.shuffle(zipf(), ["k"])),
                       iters)
    salted = dist_ops.shuffle(zipf(), ["k"], salted=True)
    salted_imb = imbalance(salted)
    salted_s = _time(
        lambda: _sync(dist_ops.shuffle(zipf(), ["k"], salted=True)),
        iters)
    return {
        "cold_shuffle_wall_s": _sig(cold_s),
        "warm_broadcast_wall_s": _sig(warm_s),
        "broadcast_speedup": _sig(cold_s / warm_s, 4) if warm_s else 0.0,
        "went_broadcast": bool(went_broadcast),
        "cold_collective_launches": int(cold_launches),
        "warm_collective_launches": int(warm_launches),
        "fewer_launches_warm": bool(warm_launches < cold_launches),
        "cold_exchange_bytes": int(cold_bytes),
        "warm_exchange_bytes": int(warm_bytes),
        "build_rows": int(n_build),
        "unsalted_wall_s": _sig(unsalted_s),
        "salted_wall_s": _sig(salted_s),
        "unsalted_imbalance": _sig(unsalted_imb, 4),
        "salted_imbalance": _sig(salted_imb, 4),
    }


def bench_groupby(ctx, n_rows: int, iters: int) -> dict:
    import cylon_tpu as ct

    rng = np.random.default_rng(1)
    t = ct.Table.from_pydict(ctx, {
        "g": rng.integers(0, 1 << 20, n_rows).astype(np.int32),
        "x": rng.normal(size=n_rows).astype(np.float32),
        "y": rng.integers(0, 100, n_rows).astype(np.int32),
    })

    def one():
        g = t.groupby(0, [1, 2, 1], ["sum", "count", "mean"])
        _sync(g)

    best = _time(one, iters)
    world = max(ctx.get_world_size(), 1)
    return {"rows_per_s_per_chip": n_rows / best / world,
            "wall_s_best": _sig(best)}


def bench_sort(ctx, n_rows: int, iters: int) -> dict:
    import cylon_tpu as ct

    rng = np.random.default_rng(2)
    t = ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, 1 << 31, n_rows).astype(np.int32),
        "v": rng.normal(size=n_rows).astype(np.float32),
    })
    dist = ctx.is_distributed() and ctx.get_world_size() > 1

    def one():
        s = ct.distributed_sort(t, "k") if dist else t.sort("k")
        _sync(s)

    best = _time(one, iters)
    world = max(ctx.get_world_size(), 1)
    return {"rows_per_s_per_chip": n_rows / best / world,
            "wall_s_best": _sig(best)}


def bench_setops(ctx, n_rows: int, iters: int) -> dict:
    import cylon_tpu as ct

    rng = np.random.default_rng(3)
    a = ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, n_rows, n_rows).astype(np.int32),
        "g": rng.integers(0, 1 << 20, n_rows).astype(np.int32),
    })
    b = ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, n_rows, n_rows).astype(np.int32),
        "g": rng.integers(0, 1 << 20, n_rows).astype(np.int32),
    })
    dist = ctx.is_distributed() and ctx.get_world_size() > 1

    def one():
        u = a.distributed_union(b) if dist else a.union(b)
        _sync(u)

    best = _time(one, iters)
    world = max(ctx.get_world_size(), 1)
    return {"rows_per_s_per_chip": 2 * n_rows / best / world,
            "wall_s_best": _sig(best)}


def bench_dist_union(ctx, n_rows: int, iters: int) -> dict:
    """The honest DISTRIBUTED set-op composition, forced even on a
    1-wide mesh: shuffle-two-tables on all columns + per-shard union
    (the reference's DistributedUnion shape, table.cpp:948-1010)."""
    import cylon_tpu as ct
    from cylon_tpu.ops.setops import SetOp
    from cylon_tpu.parallel import dist_ops

    rng = np.random.default_rng(6)
    a = ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, n_rows, n_rows).astype(np.int32),
        "g": rng.integers(0, 1 << 20, n_rows).astype(np.int32),
    })
    b = ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, n_rows, n_rows).astype(np.int32),
        "g": rng.integers(0, 1 << 20, n_rows).astype(np.int32),
    })

    def one():
        u = dist_ops.distributed_set_op(a, b, SetOp.UNION,
                                        force_exchange=True)
        _sync(u)

    best = _time(one, iters)
    world = max(ctx.get_world_size(), 1)
    return {"rows_per_s_per_chip": 2 * n_rows / best / world,
            "wall_s_best": _sig(best)}


def bench_string_join(ctx, n_rows: int, iters: int) -> dict:
    """Varbytes string-key join: device content-hash identity, no host
    vocabulary (the high-cardinality ETL case)."""
    import cylon_tpu as ct
    from cylon_tpu.data.strings import VarBytes
    from cylon_tpu.data.column import Column
    from cylon_tpu.data.table import Table

    rng = np.random.default_rng(5)
    n_keys = max(n_rows // 4, 1)

    def make(n, seed):
        r = np.random.default_rng(seed)
        ks = r.integers(0, n_keys, n)
        # synthesize key strings without a python loop: "u" + 8 hex chars
        hexd = np.frombuffer(b"0123456789abcdef", np.uint8)
        b = np.empty((n, 12), np.uint8)
        b[:, 0] = ord("u")
        for j in range(8):
            b[:, 1 + j] = hexd[(ks >> (28 - 4 * j)) & 0xF]
        b[:, 9:] = ord("x")
        lengths = np.full(n, 12, np.int32)
        vb = VarBytes._from_packed(b.tobytes(), lengths)
        cols = [Column.from_varbytes(vb, None, "k"),
                Column.from_numpy(r.normal(size=n).astype(np.float32), "v")]
        return Table(cols, ctx)

    left = make(n_rows, 10)
    right = make(n_rows, 11)

    def one():
        t = left.join(right, "inner", on="k")
        _sync(t)

    best = _time(one, iters)
    return {"rows_per_s_per_chip": 2 * n_rows / best,
            "wall_s_best": _sig(best)}


def bench_dist_sort(ctx, n_rows: int, iters: int) -> dict:
    """The honest DISTRIBUTED sort composition, forced even on a 1-wide
    mesh: splitter sampling (one batched device_get), range partition
    through the exchange, per-shard fused sort — the same machinery a
    multi-chip global sort runs (round-4 gap: sort only ever timed the
    local kernel on the 1-chip bench)."""
    import cylon_tpu as ct
    from cylon_tpu.parallel import dist_ops

    rng = np.random.default_rng(2)
    t = ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, 1 << 31, n_rows).astype(np.int32),
        "v": rng.normal(size=n_rows).astype(np.float32),
    })

    def one():
        s = dist_ops.distributed_sort(t, "k", force_exchange=True)
        _sync(s)

    best = _time(one, iters)
    world = max(ctx.get_world_size(), 1)
    return {"rows_per_s_per_chip": n_rows / best / world,
            "wall_s_best": _sig(best)}


def bench_dist_string_join(ctx, n_rows: int, iters: int) -> dict:
    """DISTRIBUTED varbytes string-key join, forced exchange: the
    round-4 word-lane machinery (string words riding the row exchange as
    payload lanes) on the clock — bench_string_join times only the local
    kernel."""
    from cylon_tpu.ops.join import JoinConfig
    from cylon_tpu.parallel import dist_ops
    from cylon_tpu.data.strings import VarBytes
    from cylon_tpu.data.column import Column
    from cylon_tpu.data.table import Table

    n_keys = max(n_rows // 4, 1)

    def make(n, seed):
        r = np.random.default_rng(seed)
        ks = r.integers(0, n_keys, n)
        hexd = np.frombuffer(b"0123456789abcdef", np.uint8)
        b = np.empty((n, 12), np.uint8)
        b[:, 0] = ord("u")
        for j in range(8):
            b[:, 1 + j] = hexd[(ks >> (28 - 4 * j)) & 0xF]
        b[:, 9:] = ord("x")
        lengths = np.full(n, 12, np.int32)
        vb = VarBytes._from_packed(b.tobytes(), lengths)
        cols = [Column.from_varbytes(vb, None, "k"),
                Column.from_numpy(r.normal(size=n).astype(np.float32), "v")]
        return Table(cols, ctx)

    left = make(n_rows, 20)
    right = make(n_rows, 21)
    cfg = JoinConfig.InnerJoin([0], [0])
    out = {}

    def one():
        t = dist_ops.distributed_join(left, right, cfg,
                                      force_exchange=True)
        _sync(t)
        out["t"] = t

    best = _time(one, iters)
    world = max(ctx.get_world_size(), 1)
    return {"rows_per_s_per_chip": 2 * n_rows / best / world,
            "wall_s_best": _sig(best),
            "out_rows": out["t"].row_count}


def bench_plan_pipeline(ctx, n_rows: int, iters: int) -> dict:
    """Eager vs PLANNED execution of the canonical analytics pipeline
    join(on=k) → groupby(on=k): the eager composition pays one exchange
    per operator; the lazy plan's optimizer propagates partitioning
    metadata, aggregates the join output in place, and prunes unused
    payload columns before the exchange. Shuffle counts come from
    telemetry phase spans (every `shuffle.exchange*` program on the
    clock), so the elision is recorded, not inferred — and the
    artifact carries the MEASUREMENT LAYER's own outputs instead of
    hand-rolled dicts: the per-query EXPLAIN ANALYZE PlanReport
    (per-node rows/bytes/ms, machine-comparable across rounds) and the
    metrics-registry delta for the timed section (shuffle bytes, rows
    exchanged, collective launches, jit factory builds)."""
    import cylon_tpu as ct
    from cylon_tpu import plan, telemetry
    from cylon_tpu.parallel import dist_ops

    rng = np.random.default_rng(9)
    left = ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, n_rows // 4, n_rows).astype(np.int32),
        "v": rng.normal(size=n_rows).astype(np.float32),
        "z": rng.integers(0, 50, n_rows).astype(np.int32),
    })
    right = ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, n_rows // 4, n_rows).astype(np.int32),
        "w": rng.normal(size=n_rows).astype(np.float32),
    })
    agg = ct.AggregationOp.SUM

    def eager():
        j = dist_ops.distributed_join(
            left, right, ct.JoinConfig.InnerJoin([0], [0]))
        g = dist_ops.distributed_groupby(j, [0], [4], [agg])
        _sync(g)

    pipe = plan.scan(left).join(plan.scan(right), on="k") \
        .groupby("lt-0", ["rt-4"], ["sum"])

    def planned():
        _sync(pipe.execute())

    def counters_now():
        snap = telemetry.metrics_snapshot()
        keep = ("cylon_shuffle_bytes_total", "cylon_rows_exchanged_total",
                "cylon_collective_launches_total")
        out = {k: snap.get(k, 0) for k in keep}
        out["kernel_factory_builds"] = sum(
            v for k, v in snap.items()
            if k.startswith("cylon_kernel_factory_builds_total") and
            isinstance(v, int))
        return out

    c0 = counters_now()
    with telemetry.collect_phases() as ce:
        eager_s = _time(eager, iters)
        eager_shuffles = ce.count("shuffle.exchange") // (iters + 1)
    c1 = counters_now()
    with telemetry.collect_phases() as cp:
        plan_s = _time(planned, iters)
        plan_shuffles = cp.count("shuffle.exchange") // (iters + 1)
    c2 = counters_now()

    # one analyzed run per shape: the per-node EXPLAIN ANALYZE records
    # (rows/bytes/ms + optimizer stats + global shuffle count)
    pipe.execute(analyze=True)
    plan_report = pipe.last_report.to_dict()
    pipe.execute(optimize=False, analyze=True)
    eager_report = pipe.last_report.to_dict()

    world = max(ctx.get_world_size(), 1)
    total = 2 * n_rows
    return {
        "world": world,
        "eager_wall_s_best": _sig(eager_s),
        "plan_wall_s_best": _sig(plan_s),
        "eager_shuffles": int(eager_shuffles),
        "plan_shuffles": int(plan_shuffles),
        "speedup": _sig(eager_s / plan_s, 4) if plan_s else 0.0,
        "eager_rows_per_s_per_chip": total / eager_s / world,
        "plan_rows_per_s_per_chip": total / plan_s / world,
        "plan_report": plan_report,
        "eager_report": eager_report,
        "metrics": {
            "eager": {k: c1[k] - c0[k] for k in c0},
            "planned": {k: c2[k] - c1[k] for k in c1},
        },
    }


def bench_service_pipeline(ctx, n_rows: int, iters: int = 3) -> dict:
    """The SAME query shape submitted 8× — sequential-eager (the plan
    cache bypassed, so every run pays host-side optimization) vs
    submitted through the :class:`QueryService` with a warm plan/
    fingerprint cache. The artifact records the cache hit count, the
    total ``cylon_jit_seconds_total`` (the compile cost the warm
    cache amortizes — zero NEW factory builds across the whole warmed
    service phase), and the
    mean submit→dispatch wait, so scripts/benchtrend.py tracks the
    service tier round over round (``service_pipeline.cache_hits`` /
    ``.speedup``)."""
    import cylon_tpu as ct
    from cylon_tpu import plan, telemetry
    from cylon_tpu.service import QueryService, plancache

    rng = np.random.default_rng(11)
    left = ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, n_rows // 4, n_rows).astype(np.int32),
        "v": rng.normal(size=n_rows).astype(np.float32),
        "z": rng.integers(0, 50, n_rows).astype(np.int32),
    })
    right = ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, n_rows // 4, n_rows).astype(np.int32),
        "w": rng.normal(size=n_rows).astype(np.float32),
    })

    def mk_pipe():
        return plan.scan(left).join(plan.scan(right), on="k") \
            .groupby("lt-0", ["rt-4"], ["sum"])

    def snap(prefix):
        return sum(v for k, v in telemetry.metrics_snapshot().items()
                   if k.startswith(prefix) and isinstance(v, int))

    def compile_seconds():
        return sum(
            v for k, v in telemetry.metrics_snapshot().items()
            if k.startswith("cylon_jit_seconds_total"))

    N = 8
    # warm the kernel memos once so BOTH sides measure steady state
    _sync(mk_pipe().execute())

    with plancache.disabled():
        t0 = time.perf_counter_ns()
        for _ in range(N):
            _sync(mk_pipe().execute())
        seq_s = (time.perf_counter_ns() - t0) / 1e9

    def qerror_buckets():
        # per-kind cumulative bucket counts of the q-error histograms
        # (registry accumulates process-wide; the service-phase p95 is
        # computed over the BEFORE/AFTER delta so earlier bench
        # phases' estimates cannot leak into this config's gate)
        out = {}
        for name, labels, m in telemetry.REGISTRY.series():
            if name == "cylon_estimate_qerror" and \
                    m.kind == "histogram":
                st = m.stats()
                out[dict(labels).get("kind", "")] = \
                    (m.buckets, list(st["counts"]))
        return out

    def delta_qerror_p95(before, after):
        worst = None
        for kind, (buckets, counts1) in after.items():
            counts0 = before.get(kind, (buckets, [0] * len(counts1)))[1]
            counts = [a - b for a, b in zip(counts1, counts0)]
            total = sum(counts)
            if total <= 0:
                continue
            rank = 0.95 * total
            cum, lo = 0, 1.0            # q-error floor: 1.0
            p95 = float(buckets[-1])    # +Inf bucket: report last edge
            for bound, c in zip(buckets, counts):
                if cum + c >= rank and c > 0:
                    p95 = lo + (bound - lo) * (rank - cum) / c
                    break
                cum += c
                lo = bound
            worst = p95 if worst is None else max(worst, p95)
        return worst

    h0 = snap("cylon_plan_cache_hits_total")
    m0 = snap("cylon_plan_cache_misses_total")
    c0 = compile_seconds()
    q0 = qerror_buckets()
    sa0 = telemetry.metrics_snapshot().get(
        'cylon_admission_est_source_total{source="measured"}', 0)
    # builds baseline BEFORE the service runs: the warm-up execute
    # already built every factory this shape needs, so a correct warm
    # cache shows zero builds across the WHOLE service phase — and the
    # snapshot races with nothing (vs. snapshotting "after query 1"
    # while the worker is already executing query 2)
    b0 = snap("cylon_kernel_factory_builds_total")
    svc = QueryService(start=False)
    t0 = time.perf_counter_ns()
    tickets = [svc.submit(mk_pipe(), tenant=f"t{i % 2}")
               for i in range(N)]
    svc.start()
    svc.drain(timeout=600)
    for tk in tickets:
        _sync(tk.result(timeout=600))
    svc_s = (time.perf_counter_ns() - t0) / 1e9
    svc.close()

    builds_delta = snap("cylon_kernel_factory_builds_total") - b0
    waits = [tk.wait_s for tk in tickets if tk.wait_s is not None]
    # the p95 queue wait via Histogram.quantile over the service wait
    # histogram (bucket-interpolated — the same estimator the SLO
    # tracker uses); the registry accumulates process-wide, but this
    # is the only service phase of the bench run
    wait_p95 = telemetry.REGISTRY.histogram(
        "cylon_service_wait_seconds").quantile(0.95)
    # estimate-accuracy observatory rollups (telemetry/stats.py): the
    # worst per-kind q-error p95 OF THIS PHASE (bucket-delta
    # interpolation — 1.0 = perfect; LOWER is better in benchtrend)
    # and how many admissions this phase ran on measured statistics
    # instead of static bounds
    qerror_p95 = delta_qerror_p95(q0, qerror_buckets())
    stats_admits = telemetry.metrics_snapshot().get(
        'cylon_admission_est_source_total{source="measured"}', 0) - sa0
    world = max(ctx.get_world_size(), 1)
    return {
        "world": world,
        "queries": N,
        "sequential_wall_s": _sig(seq_s),
        "service_wall_s": _sig(svc_s),
        "speedup": _sig(seq_s / svc_s, 4) if svc_s else 0.0,
        "cache_hits": snap("cylon_plan_cache_hits_total") - h0,
        "cache_misses": snap("cylon_plan_cache_misses_total") - m0,
        "builds_after_first_query": builds_delta,
        "compile_seconds_total": _sig(compile_seconds(), 4),
        "compile_seconds_during_service": _sig(
            compile_seconds() - c0, 4),
        "mean_wait_s": _sig(sum(waits) / len(waits)) if waits else None,
        "wait_p95_s": _sig(wait_p95, 4) if wait_p95 is not None
        else None,
        "queries_per_s": _sig(N / svc_s, 4) if svc_s else 0.0,
        "qerror_p95": _sig(qerror_p95, 4) if qerror_p95 is not None
        else None,
        "stats_informed_admits": stats_admits,
    }


def bench_pandas_reference(n_rows: int, iters: int = 1) -> dict:
    """Same workload, same host, pandas (the reference's Dask-comparison
    discipline, cpp/src/experiments/dask_run.py — a competitor number
    measured beside ours, not quoted from a paper). The full
    engine-matrix harness is scripts/compare_competitors.py; this folds
    the pandas join/groupby rows into the driver-verified artifact."""
    import pandas as pd

    rng = np.random.default_rng(0)
    ldf = pd.DataFrame({"k": rng.integers(0, n_rows, n_rows).astype(np.int32),
                        "v": rng.normal(size=n_rows).astype(np.float32)})
    rdf = pd.DataFrame({"k": rng.integers(0, n_rows, n_rows).astype(np.int32),
                        "w": rng.normal(size=n_rows).astype(np.float32)})
    gdf = pd.DataFrame({"g": rng.integers(0, 1 << 20, n_rows).astype(np.int32),
                        "x": rng.normal(size=n_rows).astype(np.float32)})
    join_s = _time(lambda: ldf.merge(rdf, on="k"), iters)
    group_s = _time(lambda: gdf.groupby("g").agg(
        s=("x", "sum"), c=("x", "count"), m=("x", "mean")), iters)
    return {"join_rows_per_s": 2 * n_rows / join_s,
            "join_s": _sig(join_s),
            "groupby_rows_per_s": n_rows / group_s,
            "groupby_s": _sig(group_s)}


def run(n_rows: int = 1 << 24, iters: int = 3, full: bool = True) -> dict:
    import jax

    from cylon_tpu.telemetry import profiler as _profiler

    ctx = _mk_ctx()
    dist_res = bench_dist_join(ctx, n_rows, iters)
    local_res = bench_local_join(ctx, n_rows, iters)
    shuffle_res = bench_shuffle(ctx, n_rows, iters)
    suite = {}
    if full:
        # one failing config reports its error in detail and lets the
        # others run; main() then exits non-zero
        configs = [
            ("groupby_agg", lambda: bench_groupby(ctx, n_rows, iters)),
            ("global_sort", lambda: bench_sort(ctx, n_rows, iters)),
            ("set_union", lambda: bench_setops(ctx, n_rows // 2, iters)),
            ("dist_union",
             lambda: bench_dist_union(ctx, n_rows // 2, iters)),
            ("q5_pipeline",
             lambda: bench_q5_pipeline(ctx, n_rows // 2, iters)),
            ("plan_pipeline",
             lambda: bench_plan_pipeline(ctx, n_rows // 2, iters)),
            ("service_pipeline",
             lambda: bench_service_pipeline(ctx, n_rows // 4, iters)),
            ("string_join",
             lambda: bench_string_join(ctx, n_rows // 4, iters)),
            ("dist_string_join",
             lambda: bench_dist_string_join(ctx, n_rows // 4, iters)),
            ("dist_sort",
             lambda: bench_dist_sort(ctx, n_rows, iters)),
            ("shuffle_wide",
             lambda: bench_shuffle_wide(ctx, n_rows, iters)),
            ("shuffle_pipeline",
             lambda: bench_shuffle_pipeline(ctx, n_rows, iters)),
            ("adaptive_join",
             lambda: bench_adaptive_join(ctx, n_rows // 4, iters)),
            ("hbm_blocked_join",
             lambda: bench_hbm_blocked_join(ctx, n_rows * 12,
                                            n_rows * 3)),
            ("pandas_reference",
             lambda: bench_pandas_reference(n_rows // 4, iters)),
        ]
        for name, fn in configs:
            try:
                suite[name] = fn()
            except Exception as e:  # pragma: no cover - defensive
                suite[name] = {"error": f"{type(e).__name__}: {e}"[:300]}
    rps = dist_res["rows_per_s_per_chip"]
    # the full registry snapshot (counters + per-phase latency
    # histograms + HBM gauges) rides the artifact — the machine-
    # comparable perf trajectory across BENCH rounds
    from cylon_tpu import telemetry as _telemetry

    _telemetry.sample_memory(ctx.memory_pool)
    # memory trajectory for future benchtrend rounds: the run's HBM
    # high-water mark (ledger-backed on stats-hidden backends) and the
    # ledger's end-of-run leak count — a growing leak count across
    # rounds is a regression even when throughput holds
    _hbm_used, _hbm_peak, _hbm_limit = ctx.memory_pool.snapshot()
    # recompile-cardinality trajectory: every backend-compile event of
    # the process (the always-on jax.monitoring listener,
    # cylon_jit_events_total{stage="compile"}) is one compiled XLA
    # program. Capacity bucketing (benchutils.bucket_cap, enforced
    # statically by the specialization analysis family) bounds this
    # per factory by the BUCKET count, not the distinct-value count —
    # benchtrend tracks it lower-is-better across rounds
    _compile_profile = _profiler.summary()
    return {
        "metric": "dist_inner_join_rows_per_sec_per_chip",
        "value": round(rps, 1),
        "unit": "rows/s/chip",
        "vs_baseline": round(rps / _BASELINE_ROWS_PER_S, 3),
        "telemetry": _telemetry.metrics_snapshot(),
        "detail": {
            "n_rows_per_side": n_rows,
            "world": ctx.get_world_size(),
            "peak_hbm_bytes": int(_hbm_peak),
            "ledger_leaks": int(_telemetry.ledger.leak_count()),
            "wall_s_best": dist_res["wall_s_best"],
            "out_rows": dist_res["out_rows"],
            "backend": jax.devices()[0].platform,
            "local_inner_join": {
                k: (_sig(v) if isinstance(v, float) else v)
                for k, v in local_res.items()},
            "shuffle_gbps": shuffle_res["gbps_per_chip"],
            "shuffle": shuffle_res,
            "compile_profile": _compile_profile,
            "distinct_kernel_signatures":
                _compile_profile["compile"]["events"],
            "suite": {k: {kk: (_sig(vv) if isinstance(vv, float) else vv)
                          for kk, vv in v.items()}
                      for k, v in suite.items()},
        },
    }


def bench_hbm_blocked_join(ctx, n_probe: int, n_build: int) -> dict:
    """>HBM working-set join (VERDICT r03 #6): the probe side is big
    enough that the plan estimate exceeds the HBM headroom and
    join_blocked auto-engages (table.py join() routing). Data generates
    ON DEVICE (a host transfer of GBs would dominate the wall
    clock)."""
    import jax
    import jax.numpy as jnp

    from cylon_tpu import dtypes
    from cylon_tpu.data.column import Column
    from cylon_tpu.data.table import Table
    from cylon_tpu.data import table as table_mod

    def dev_table(n, seed, name):
        k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
        k = jax.random.randint(k1, (n,), 0, n_probe, dtype=jnp.int32)
        v = jax.random.normal(k2, (n,), dtype=jnp.float32)
        return Table([Column(k, dtypes.Int32(), None, None, "k"),
                      Column(v, dtypes.Float(), None, None, name)], ctx)

    left = dev_table(n_probe, 1, "v")
    right = dev_table(n_build, 2, "w")
    engaged = {}
    orig = table_mod.join_blocked

    def spy(*a, **kw):
        engaged["blocked"] = True
        return orig(*a, **kw)

    # backends that hide memory stats AND aren't TPUs (the CPU test
    # mesh) can never auto-engage the >HBM router — force the blocked
    # path there so the artifact still measures it, honestly flagged
    forced = ctx.memory_pool.available_bytes() is None
    blk = {"probe_block_rows": max(n_probe // 8, 1)} if forced else {}
    table_mod.join_blocked = spy
    try:
        out = {}

        def one():
            t = left.join(right, "inner", on="k", **blk)
            _sync(t)
            out["t"] = t

        wall = _time(one, 1)  # warmup (compile) + one timed run
        rows = out["t"].row_count
    finally:
        table_mod.join_blocked = orig
    total = n_probe + n_build
    blocked = bool(engaged.get("blocked", False))
    return {
        # a rows/s number for the blocked path only counts if the
        # blocked path actually ran — otherwise report the miss loudly
        "rows_per_s_per_chip": round(total / wall, 1) if blocked else 0.0,
        "wall_s": _sig(wall), "out_rows": int(rows),
        "probe_rows": n_probe, "build_rows": n_build,
        "blocked_engaged": blocked, "forced": forced,
        "working_set_gb": round((n_probe + n_build) * 8 * 8 / 1e9, 2)}


def bench_q5_pipeline(ctx, n_rows: int, iters: int) -> dict:
    """TPC-H Q5 shape: 3-table star join + filter + grouped aggregate
    (customer ⋈ orders ⋈ lineitem-ish, then revenue by group)."""
    import cylon_tpu as ct

    rng = np.random.default_rng(4)
    n_cust = n_rows // 16
    cust = ct.Table.from_pydict(ctx, {
        "ck": np.arange(n_cust, dtype=np.int32),
        "region": rng.integers(0, 5, n_cust).astype(np.int32),
    })
    orders = ct.Table.from_pydict(ctx, {
        "ok": np.arange(n_rows // 4, dtype=np.int32),
        "ck": rng.integers(0, n_cust, n_rows // 4).astype(np.int32),
    })
    items = ct.Table.from_pydict(ctx, {
        "ok": rng.integers(0, n_rows // 4, n_rows).astype(np.int32),
        "price": rng.exponential(100.0, n_rows).astype(np.float32),
    })

    dist = ctx.is_distributed() and ctx.get_world_size() > 1

    def one():
        co = cust.distributed_join(orders, "inner", left_on=["ck"],
                                   right_on=["ck"]) if dist else \
            cust.join(orders, "inner", left_on=["ck"], right_on=["ck"])
        # co columns: [ck, region, ok, ck]; region filter: region < 2
        full = co.filter_mask(co._columns[1].data < 2)
        coi = full.distributed_join(items, "inner", left_on=[2],
                                    right_on=[0]) if dist else \
            full.join(items, "inner", left_on=[2], right_on=[0])
        # group revenue by region (col 1), summing price (last col)
        g = coi.groupby(1, [coi.column_count - 1], ["sum"])
        _sync(g)

    best = _time(one, iters)
    world = max(ctx.get_world_size(), 1)
    # rows ingested across the pipeline
    total = n_cust + n_rows // 4 + n_rows
    return {"rows_per_s_per_chip": total / best / world,
            "wall_s_best": _sig(best)}


def main(argv=None) -> int:
    """Run the suite in THIS process on the attached TPU and print the
    one JSON line. No child, no retry, no fallback: a platform other
    than ``tpu`` is an error, and a config that raised makes the exit
    code non-zero after the others have run."""
    import argparse

    import jax

    p = argparse.ArgumentParser()
    p.add_argument("--rows", type=int, default=1 << 24)
    p.add_argument("--iters", type=int, default=3)
    p.add_argument("--join-only", action="store_true")
    a = p.parse_args(argv)
    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise SystemExit(f"bench.py measures the TPU: platform is "
                         f"{platform!r} (run() stays callable anywhere)")
    res = run(a.rows, a.iters, full=not a.join_only)
    print(json.dumps(res))
    failed = sorted(k for k, v in res["detail"]["suite"].items()
                    if "error" in v)
    if failed:
        print(f"bench.py: config(s) raised: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
