"""The overlapped (chunked, double-buffered) exchange pipeline.

Core contract: the chunked path is BIT-IDENTICAL to the single-shot
padded program on every live row — same emit mask, same counts_in, same
capacity — across chunk counts (1, 2, deep, odd remainder, chunk >
payload), under per-chunk transient faults, and end to end through the
distributed-op compositions. A retry that finds its donated accumulator
consumed rebuilds the pipeline through the partition program without
chunk 0 and lands on the same bits.
"""

import numpy as np
import pytest

import cylon_tpu as ct
import forced_paths
from cylon_tpu import telemetry
from cylon_tpu.parallel import shard as _shard
from cylon_tpu.parallel import shuffle as _shuffle
from cylon_tpu.resilience import inject as _inject


def _mk_exchange_inputs(ctx, n, seed=0, live=0.85):
    import jax.numpy as jnp

    world = ctx.get_world_size()
    rng = np.random.default_rng(seed)
    payload = {
        "a": _shard.pin(jnp.asarray(
            rng.integers(0, 1 << 30, n).astype(np.int32)), ctx),
        "b": _shard.pin(jnp.asarray(
            rng.normal(size=n).astype(np.float32)), ctx),
    }
    targets = _shard.pin(jnp.asarray(
        rng.integers(0, world, n).astype(np.int32)), ctx)
    emit = _shard.pin(jnp.asarray(rng.random(n) < live), ctx)
    return payload, targets, emit


def _counts(ctx, targets, emit):
    import jax

    return np.asarray(jax.device_get(
        _shuffle._count_fn(ctx.mesh)(targets, emit)))


def _run(ctx, payload, targets, emit, counts, **kw):
    return _shuffle.exchange(payload, targets, emit, ctx, counts=counts,
                             **kw)


def _assert_bit_identical(base, out):
    o0, e0, c0, m0 = base
    o1, e1, c1, m1 = out
    assert c0 == c1
    e0h, e1h = np.asarray(e0), np.asarray(e1)
    assert np.array_equal(e0h, e1h)
    assert np.array_equal(np.asarray(m0["counts_in"]),
                          np.asarray(m1["counts_in"]))
    assert m0["mode"] == m1["mode"] == "padded"
    assert m0["block"] == m1["block"]
    for k in o0:
        assert np.array_equal(np.asarray(o0[k])[e0h],
                              np.asarray(o1[k])[e1h]), k


@pytest.mark.parametrize("n,cbytes,want_chunks", [
    (4096, 1 << 26, 1),    # chunk >= payload: single-shot
    (4096, 4096, 2),       # two-chunk pipeline
    (16384, 4096, 8),      # deep pipeline
])
def test_chunked_bit_identical_across_chunk_counts(dist_ctx, monkeypatch,
                                                   n, cbytes,
                                                   want_chunks):
    """Every chunk count reproduces the single-shot result bit for
    bit: same live rows, emit mask, counts_in and capacity."""
    payload, targets, emit = _mk_exchange_inputs(dist_ctx, n)
    counts = _counts(dist_ctx, targets, emit)
    forced_paths.single_shot(monkeypatch)
    base = _run(dist_ctx, payload, targets, emit, counts)
    forced_paths.chunked(monkeypatch, cbytes)
    c0 = telemetry.metrics_snapshot().get(
        "cylon_exchange_chunks_total", 0)
    out = _run(dist_ctx, payload, targets, emit, counts)
    _assert_bit_identical(base, out)
    assert out[3].get("chunks", 1) == want_chunks
    moved = telemetry.metrics_snapshot().get(
        "cylon_exchange_chunks_total", 0) - c0
    assert moved == (want_chunks if want_chunks > 1 else 0)


def test_chunked_bit_identical_odd_remainder(dist_ctx, monkeypatch):
    """A non-pow2 chunk block (forced plan) exercises the dropping-
    scatter remainder path; the last partial chunk must neither wrap
    nor clobber earlier rows."""
    payload, targets, emit = _mk_exchange_inputs(dist_ctx, 4096, seed=3)
    counts = _counts(dist_ctx, targets, emit)
    forced_paths.single_shot(monkeypatch)
    base = _run(dist_ctx, payload, targets, emit, counts)
    forced_paths.single_shot(monkeypatch, False)
    monkeypatch.setattr(
        _shuffle, "_chunk_plan",
        lambda block, *_: (3, -(-block // 3)) if block > 3
        else (block, 1))
    out = _run(dist_ctx, payload, targets, emit, counts)
    _assert_bit_identical(base, out)
    assert out[3]["chunks"] == -(-base[3]["block"] // 3)


def test_chunked_skew_attrs_match_single_shot(dist_ctx, monkeypatch):
    """Skew span attributes ride the ONE host count matrix, so a
    chunked exchange reports exactly the single-shot combined matrix —
    plus the chunk-pipeline attrs."""
    payload, targets, emit = _mk_exchange_inputs(dist_ctx, 4096, seed=7)
    counts = _counts(dist_ctx, targets, emit)
    spans = []

    def sink(span):
        if span.name.startswith("shuffle.exchange"):
            spans.append(dict(span.attrs))

    telemetry.add_sink(sink)
    try:
        forced_paths.single_shot(monkeypatch)
        _run(dist_ctx, payload, targets, emit, counts)
        forced_paths.chunked(monkeypatch, 4096)
        _run(dist_ctx, payload, targets, emit, counts)
    finally:
        telemetry.remove_sink(sink)
    assert len(spans) == 2
    single, chunked = spans
    skew_keys = [k for k in single
                 if k.startswith(("skew_", "shard_"))]
    assert skew_keys, single
    for k in skew_keys:
        assert single[k] == chunked[k], k
    assert chunked["chunks"] > 1
    assert chunked["chunk_block"] > 0
    assert 0.0 < chunked["overlap_ratio"] < 1.0
    assert "chunks" not in single


def test_chunked_per_chunk_retry_bit_identical(dist_ctx, monkeypatch):
    """A transient fault on a mid-stream chunk dispatch retries that
    chunk idempotently; the recovered result is bit-identical."""
    monkeypatch.setenv("CYLON_RETRY_BACKOFF_S", "0.001")
    payload, targets, emit = _mk_exchange_inputs(dist_ctx, 4096, seed=9)
    counts = _counts(dist_ctx, targets, emit)
    forced_paths.single_shot(monkeypatch)
    base = _run(dist_ctx, payload, targets, emit, counts)
    forced_paths.chunked(monkeypatch, 4096)

    def retries():
        return sum(v for k, v in telemetry.metrics_snapshot().items()
                   if k.startswith("cylon_retries_total"))

    r0 = retries()
    _inject.arm("exchange:2:transient")
    try:
        out = _run(dist_ctx, payload, targets, emit, counts)
    finally:
        _inject.disarm()
    assert retries() > r0
    assert out[3]["chunks"] > 1
    _assert_bit_identical(base, out)


class _Consumed:
    """What a donated accumulator looks like after the dispatch that
    took it (donation is a no-op on the CPU, so a real one never is)."""

    def is_deleted(self):
        return True


@pytest.mark.parametrize("k", [1, 2])
def test_rebuild_after_consumed_donation_is_bit_identical(dist_ctx,
                                                          monkeypatch, k):
    """A retried chunk ``k`` that finds its accumulator consumed (after
    the fused first program for k = 1, after a chunk program for k = 2)
    rebuilds the pipeline from the never-donated payload: the partition
    program WITHOUT chunk 0 runs once, chunks 0..k-1 replay, and the
    result is bit-identical to the undisturbed run (C launches counted)."""
    import jax

    payload, targets, emit = _mk_exchange_inputs(dist_ctx, 16384, seed=11)
    counts = _counts(dist_ctx, targets, emit)
    forced_paths.chunked(monkeypatch, 4096)

    def launches():
        return telemetry.metrics_snapshot().get(
            "cylon_collective_launches_total", 0)

    l0 = launches()
    base = _run(dist_ctx, payload, targets, emit, counts)
    chunks = base[3]["chunks"]
    assert chunks > 2
    assert launches() - l0 == chunks

    real = {n: getattr(_shuffle, n) for n in (
        "_exchange_chunk_first_fn", "_exchange_chunk_fn",
        "_exchange_partition_fn")}
    steps, rebuilds = [], []

    def consumed(tree):
        return jax.tree.map(lambda _: _Consumed(), tree)

    def first_fn(*key):
        def first(*args):
            *state, outs = real["_exchange_chunk_first_fn"](*key)(*args)
            return (*state, consumed(outs) if k == 1 else outs)
        return first

    def step_fn(*key):
        def step(padded, start, outs, i):
            steps.append(int(i))
            out = real["_exchange_chunk_fn"](*key)(padded, start, outs, i)
            # chunk k-1 lands, then its buffers go
            return consumed(out) if len(steps) == k - 1 else out
        return step

    def part_fn(*key):
        def part(*args):
            rebuilds.append(key)
            return real["_exchange_partition_fn"](*key)(*args)
        return part

    monkeypatch.setattr(_shuffle, "_exchange_chunk_first_fn", first_fn)
    monkeypatch.setattr(_shuffle, "_exchange_chunk_fn", step_fn)
    monkeypatch.setattr(_shuffle, "_exchange_partition_fn", part_fn)
    out = _run(dist_ctx, payload, targets, emit, counts)
    assert len(rebuilds) == 1
    # chunks up to k-1, then before chunk k the replay of 0..k-1, the rest
    assert steps == list(range(1, k)) + list(range(k)) \
        + list(range(k, chunks))
    _assert_bit_identical(base, out)


def _one_wide_repartition(t):
    from cylon_tpu.parallel import dist_ops

    return dist_ops.repartition(t, t.context)


def _one_wide_task_exchange(t):
    from cylon_tpu.plan import LogicalTaskPlan, task_exchange

    ids = np.arange(t.capacity) % 3
    return task_exchange(t, ids, LogicalTaskPlan({0: 0, 1: 0, 2: 0}, 1))


@pytest.mark.parametrize("op", [_one_wide_repartition,
                                _one_wide_task_exchange],
                         ids=["repartition", "task_exchange"])
def test_one_wide_context_keeps_live_rows_in_order(op):
    """On a 1-wide distributed context there is one target: what comes
    back is the input's live rows, in their order (task_exchange takes
    the general counted route, repartition returns its input)."""
    ctx = ct.CylonContext.InitDistributed(ct.TPUConfig(world_size=1))
    rng = np.random.default_rng(5)
    n = 1000
    k = rng.integers(0, 1 << 30, n).astype(np.int32)
    v = rng.normal(size=n).astype(np.float32)
    t = ct.Table.from_pydict(ctx, {"k": k, "v": v})
    t = t.filter_mask(t.get_column(0).data % 3 != 0)
    live = k % 3 != 0
    got = op(t).to_pandas()
    assert np.array_equal(got["k"].to_numpy(), k[live])
    assert np.array_equal(got["v"].to_numpy(), v[live])


def test_exchange_pair_routes_through_chunked(dist_ctx, monkeypatch):
    """When a side is big enough to chunk, exchange_pair falls through
    to two chunked exchanges; results match the monolithic pair
    program bit for bit."""
    import jax.numpy as jnp

    world = dist_ctx.get_world_size()
    rng = np.random.default_rng(13)
    n1, n2 = 4096, 2048

    def side(n, seed):
        r = np.random.default_rng(seed)
        p = {"a": _shard.pin(jnp.asarray(
                 r.integers(0, 1 << 30, n).astype(np.int32)), dist_ctx),
             "b": _shard.pin(jnp.asarray(
                 r.normal(size=n).astype(np.float32)), dist_ctx)}
        t = _shard.pin(jnp.asarray(
            r.integers(0, world, n).astype(np.int32)), dist_ctx)
        e = _shard.pin(jnp.asarray(r.random(n) < 0.9), dist_ctx)
        return p, t, e

    p1, t1, e1 = side(n1, 13)
    p2, t2, e2 = side(n2, 14)
    c1, c2 = _shuffle.count_pair(t1, e1, t2, e2, dist_ctx)
    forced_paths.single_shot(monkeypatch)
    b1, b2 = _shuffle.exchange_pair(p1, t1, e1, c1, p2, t2, e2, c2,
                                    dist_ctx)
    forced_paths.chunked(monkeypatch, 4096)
    o1, o2 = _shuffle.exchange_pair(p1, t1, e1, c1, p2, t2, e2, c2,
                                    dist_ctx)
    _assert_bit_identical(b1, o1)
    _assert_bit_identical(b2, o2)
    assert o1[3].get("chunks", 1) > 1 or o2[3].get("chunks", 1) > 1


# a quarter (`MemoryPool.comm_fraction`) of 10 GB: what a v5e's 15.75 GB
# leave free beside the live tables of the benchmark's largest cell
V5E_BUDGET = 10_000_000_000 // 4


@pytest.mark.parametrize("block,row_bytes,budget,factor,want", [
    # the three four-chip cells (world 4): every exchange is one program.
    # `join-w4`: 2^22 slots a pair, 8 bytes a row, two tables a program
    (2 ** 22, 8, V5E_BUDGET, 8, (2 ** 22, 1)),
    # `join-w4-zipf`: S's block doubles beside R's 2^22
    (2 ** 23, 8, V5E_BUDGET, 8, (2 ** 23, 1)),
    # `groupby-q5-w4`: the partial table, 16 bytes a row, one table
    (2 ** 22, 16, V5E_BUDGET, 4, (2 ** 22, 1)),
    # a budget of a quarter of the stacks: 4 chunks of a power of two
    (2 ** 22, 8, 4 * 4 * 2 ** 22 * 8 // 4, 4, (2 ** 20, 4)),
    # ... and what fits to the byte is not chunked
    (2 ** 22, 8, 4 * 4 * 2 ** 22 * 8, 4, (2 ** 22, 1)),
    # no budget is known (the CPU): one program whatever the bytes
    (2 ** 30, 64, None, 4, (2 ** 30, 1)),
    # the chunk block is floored so that no pipeline passes MAX_CHUNKS
    (2 ** 30, 64, 1 << 20, 4, (2 ** 24, 64)),
    # `_padded_route`'s floor: a block of 1,024 rows is never cut
    (2 ** 10, 8, 1, 4, (2 ** 10, 1)),
], ids=["join-w4", "join-w4-zipf", "groupby-q5-w4", "quarter-budget",
        "exact-fit", "no-budget", "max-chunks", "route-floor"])
def test_chunk_plan_is_decided_by_the_budget(block, row_bytes, budget,
                                             factor, want):
    """`_chunk_plan` reads the geometry and the pool's comm budget, no
    knob and no constant of bytes: single-shot wherever the stacks
    `_padded_route` checked fit, and by the same arithmetic."""
    assert _shuffle._chunk_plan(block, 4, row_bytes, budget,
                                buffer_factor=factor) == want
    assert want[1] <= _shuffle.MAX_CHUNKS
    # what the route admits on a budget, the plan leaves whole
    payload = {"x": np.zeros((8, row_bytes), np.uint8)}
    ok, block_p, _mb = _shuffle._padded_route(
        np.full((4, 4), block), payload, 4, budget, buffer_factor=factor)
    if ok and budget:
        assert block_p == block and want[1] == 1


@pytest.mark.parametrize("s_pair_rows", [4_000_000, 8_000_000],
                         ids=["join-w4", "join-w4-zipf"])
def test_exchange_pair_is_one_program_at_the_cells_geometry(
        dist_ctx, monkeypatch, s_pair_rows):
    """16M rows of 8 bytes a side a chip on four chips, on a v5e's
    budget: `exchange_pair` takes its fused branch (PR 48; the 64 MiB
    chunk target sent each side through 2-4 chunk programs), one
    dispatch and one collective launch for the two tables. Routed over
    shapes alone: the pair program is stood in for."""
    import jax

    rows = 4 * 16_000_000
    leaf = jax.ShapeDtypeStruct((rows,), np.int32)
    side = {"k": leaf, "v": leaf}
    counts = [np.full((4, 4), n) for n in (4_000_000, s_pair_rows)]
    built = []

    def pair_fn(mesh, b1, b2, part1, part2):
        built.append((b1, b2))
        return lambda *operands: ("o1", "e1", "c1", "o2", "e2", "c2")

    monkeypatch.setattr(_shuffle, "_exchange_padded_pair_fn", pair_fn)
    monkeypatch.setattr(dist_ctx.memory_pool, "comm_budget_bytes",
                        lambda: V5E_BUDGET)

    def launches():
        return telemetry.metrics_snapshot().get(
            "cylon_collective_launches_total", 0)

    l0 = launches()
    with telemetry.collect_phases() as cp:
        r1, r2 = _shuffle.exchange_pair(side, None, None, counts[0],
                                        side, None, None, counts[1],
                                        dist_ctx)
    assert launches() - l0 == 1
    assert built == [(2 ** 22, _shuffle._pow2(s_pair_rows))]
    route, = [s for s in cp.spans if s.name == "shuffle.route"]
    assert (route.attrs["mode"], route.attrs["chunks"],
            route.attrs["tables"]) == ("pair", 1, 2)
    assert [s.name for s in cp.spans if s.name.startswith(
        "shuffle.exchange")] == ["shuffle.exchange_pair"]
    assert (r1[0], r1[2], r2[0], r2[2]) \
        == ("o1", 4 * 2 ** 22, "o2", 4 * _shuffle._pow2(s_pair_rows))
    assert "chunks" not in r1[3] and "chunks" not in r2[3]


@pytest.mark.parametrize("overlap", ["0", "1"])
def test_distributed_join_identical_under_overlap(dist_ctx, monkeypatch,
                                                  overlap):
    """End to end through the dist_ops composition: the distributed
    join's rows are the same chunked and single-shot."""
    if overlap == "0":
        forced_paths.single_shot(monkeypatch)
    else:
        forced_paths.chunked(monkeypatch, 4096)
    rng = np.random.default_rng(17)
    n = 4096
    left = ct.Table.from_pydict(dist_ctx, {
        "k": rng.integers(0, n // 4, n).astype(np.int32),
        "v": rng.normal(size=n).astype(np.float32)})
    right = ct.Table.from_pydict(dist_ctx, {
        "k": rng.integers(0, n // 4, n).astype(np.int32),
        "w": rng.normal(size=n).astype(np.float32)})
    got = left.distributed_join(right, "inner", on="k").to_pandas()
    lctx = ct.CylonContext.Init()
    want = ct.Table.from_pydict(lctx, {
        "k": np.asarray(left.to_pydict()["k"]),
        "v": np.asarray(left.to_pydict()["v"])}).join(
        ct.Table.from_pydict(lctx, {
            "k": np.asarray(right.to_pydict()["k"]),
            "w": np.asarray(right.to_pydict()["w"])}),
        "inner", on="k").to_pandas()

    def canon(df):
        df = df.copy()
        df.columns = range(df.shape[1])
        return df.sort_values(list(df.columns)).reset_index(drop=True)

    import pandas as pd

    pd.testing.assert_frame_equal(canon(got), canon(want),
                                  check_dtype=False, atol=1e-6)


def test_padded_route_block_cap_comes_from_a_known_budget():
    """16M rows a chip on 4 chips: every (src, dst) pair moves ~4.2M
    rows, so the padded block is 8M — past MAX_BLOCK, which caps the
    block only where the budget is unknown. With live HBM numbers the
    budget alone decides (chip run, PR 22: capped at MAX_BLOCK the main
    path fell to the blockwise sort rounds and never met the partition
    kernel)."""
    counts = np.full((4, 4), 4_200_000)
    payload = {"k": np.zeros(8, np.int32), "w": np.zeros(8, np.float32)}
    assert _shuffle._padded_route(counts, payload, 4, None) \
        == (False, 1 << 23, _shuffle.MAX_BLOCK)
    ok, block, mb = _shuffle._padded_route(counts, payload, 4, 4 << 30)
    assert ok and block == 1 << 23 and mb >= block
    # a budget that cannot hold 4 * world * block * 8 B still refuses
    assert not _shuffle._padded_route(counts, payload, 4, 512 << 20)[0]
    # an explicit max_block binds whatever the budget
    assert not _shuffle._padded_route(counts, payload, 4, 4 << 30,
                                     max_block=1 << 20)[0]
