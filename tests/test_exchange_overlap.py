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
from cylon_tpu import telemetry, util
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
    (4096, 4096, 2),       # two-chunk pipeline: 240 slots in 128s
    (16384, 4096, 7),      # deep pipeline: a block of 896 = 7 * 128
    (16384, 8192, 4),      # 896 in 256s: the last chunk is moved back
])
def test_chunked_bit_identical_across_chunk_counts(dist_ctx, monkeypatch,
                                                   n, cbytes,
                                                   want_chunks):
    """Every chunk count reproduces the single-shot result bit for
    bit: same live rows, emit mask, counts_in and capacity."""
    payload, targets, emit = _mk_exchange_inputs(dist_ctx, n)
    counts = _counts(dist_ctx, targets, emit)
    # the forced chunk block: cbytes over 8 bytes a row on 4 chips
    assert want_chunks == 1 or -(-util.capacity(int(counts.max()))
                                 // (cbytes // 32)) == want_chunks
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


def _exact_pairs(ctx, pair, seed=0):
    """Exchange inputs whose EVERY (source, target) pair moves ``pair``
    live rows, in a drawn order among dead ones: the block is
    `util.capacity(pair)` by construction."""
    import jax.numpy as jnp

    world = ctx.get_world_size()
    per = util.pow2(2 * world * pair)
    rng = np.random.default_rng(seed)
    t = np.zeros((world, per), np.int32)
    e = np.zeros((world, per), bool)
    for s in range(world):
        where = rng.permutation(per)[:world * pair]
        t[s, where] = np.repeat(np.arange(world), pair)
        e[s, where] = True
    n = world * per
    payload = {
        "a": _shard.pin(jnp.asarray(
            rng.integers(0, 1 << 30, n).astype(np.int32)), ctx),
        "b": _shard.pin(jnp.asarray(
            rng.normal(size=n).astype(np.float32)), ctx)}
    return (payload, _shard.pin(jnp.asarray(t.reshape(-1)), ctx),
            _shard.pin(jnp.asarray(e.reshape(-1)), ctx))


@pytest.mark.parametrize("pair,cbytes,want", [
    (600, 2048, (64, 10)),      # 19 * 32 in 64s: 9 whole chunks and a half
    (600, 8192, (256, 3)),
    (600, 16384, (512, 2)),     # the second chunk re-lands 416 slots
    (1200, 8192, (256, 5)),     # 19 * 64
    (330, 4096, (128, 3)),      # 21 * 16
])
def test_chunked_bit_identical_at_a_grid_block(dist_ctx, monkeypatch,
                                               pair, cbytes, want):
    """A block on `util.capacity`'s grid (19 * 2^k, 21 * 2^k: what the
    route sizes since PR 52) is no multiple of a power-of-two chunk
    block above 2^k: the last chunk starts early and lands some slots a
    second time, the same rows, and the result is the single-shot
    program's bit for bit. No chunk program scatters."""
    import jax

    payload, targets, emit = _exact_pairs(dist_ctx, pair, seed=pair)
    counts = _counts(dist_ctx, targets, emit)
    assert counts.min() == counts.max() == pair
    block = util.capacity(pair)
    assert block != util.pow2(block) and block % want[0]
    forced_paths.single_shot(monkeypatch)
    base = _run(dist_ctx, payload, targets, emit, counts)
    assert base[3]["block"] == block and base[2] == 4 * block
    forced_paths.chunked(monkeypatch, cbytes)
    with telemetry.collect_phases() as cp:
        out = _run(dist_ctx, payload, targets, emit, counts)
    _assert_bit_identical(base, out)
    # every slot, not only the live ones: a slot landed twice holds what
    # it held
    for k in base[0]:
        assert np.array_equal(np.asarray(base[0][k]),
                              np.asarray(out[0][k])), k
    sp, = [s for s in cp.spans if s.name == "shuffle.exchange"]
    assert (sp.attrs["chunk_block"], sp.attrs["chunks"]) == want
    assert out[3]["chunks"] == want[1]
    # the chunk program at this geometry: a dynamic-update-slice a leaf
    # and no scatter
    world = dist_ctx.get_world_size()
    rows = jax.tree.leaves(payload)[0].shape[0] + world * want[0]
    leaf = lambda n, dt: jax.ShapeDtypeStruct((n,), dt)  # noqa: E731
    hlo = _shuffle._exchange_chunk_fn(dist_ctx.mesh, block, want[0]).lower(
        {"a": leaf(rows, np.int32), "b": leaf(rows, np.float32)},
        leaf(world * world, np.int32),
        {"a": leaf(world * world * block, np.int32),
         "b": leaf(world * world * block, np.float32)},
        jax.ShapeDtypeStruct((), np.int32)).as_text()
    assert "scatter" not in hlo
    assert hlo.count("dynamic_update_slice") == 2


@pytest.mark.parametrize("block", [608, 1216, 336, 2 ** 9, 5, 2_621_440])
def test_padded_emit_is_the_modulo_form(block):
    """`_padded_emit` (a compare against a broadcast) against the form
    it replaced, `pos % block < counts_in[pos // block]`, at blocks that
    are no power of two."""
    import jax.numpy as jnp

    world = 4
    rng = np.random.default_rng(block)
    counts_in = rng.integers(0, block + 1, world).astype(np.int32)
    counts_in[1], counts_in[2] = 0, block
    pos = np.arange(world * block)
    want = (pos % block) < counts_in[pos // block]
    got = np.asarray(_shuffle._padded_emit(jnp.asarray(counts_in), block))
    assert got.shape == want.shape and np.array_equal(got, want)


def test_chunked_bit_identical_odd_remainder(dist_ctx, monkeypatch):
    """A chunk block that is no power of two and divides nothing (forced
    plan): the last chunk, moved back to end with the block, must
    neither wrap nor clobber earlier rows."""
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
    # the blocks the cells cross in since PR 52, `util.capacity` of the
    # worst pair (4.00M, 4.81M and 2.4955M rows): a block that fits comes
    # back whole, never floored to the octave under it
    (4_063_232, 8, V5E_BUDGET, 8, (4_063_232, 1)),
    (4_980_736, 8, V5E_BUDGET, 8, (4_980_736, 1)),
    (2_621_440, 16, V5E_BUDGET, 4, (2_621_440, 1)),
    # a grid block (19 * 2^18) over a budget of 5/8 of its stacks: the
    # chunk block is the power of two that fits, the second chunk ends
    # with the block
    (4_980_736, 8, 4 * 4 * 4_980_736 * 8 * 5 // 8, 4, (2 ** 21, 3)),
    (4_980_736, 8, 4 * 4 * 4_980_736 * 8 - 1, 4, (2 ** 22, 2)),
], ids=["join-w4-octave", "join-w4-zipf-octave", "groupby-q5-w4-octave",
        "quarter-budget", "exact-fit", "no-budget", "max-chunks",
        "route-floor", "join-w4", "join-w4-zipf", "groupby-q5-w4",
        "grid-block-cut", "grid-block-a-byte-over"])
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
    ok, block_p, mb = _shuffle._padded_route(
        np.full((4, 4), block), payload, 4, budget, buffer_factor=factor)
    if ok and budget:
        assert block_p == block <= mb and want[1] == 1


@pytest.mark.parametrize("s_pair_rows,s_block", [
    (4_003_000, 4_063_232), (4_810_000, 4_980_736),
    (8_000_000, 8_126_464)],
    ids=["join-w4", "join-w4-zipf", "twice-the-pair"])
def test_exchange_pair_is_one_program_at_the_cells_geometry(
        dist_ctx, monkeypatch, s_pair_rows, s_block):
    """16M rows of 8 bytes a side a chip on four chips, on a v5e's
    budget: `exchange_pair` takes its fused branch (PR 48; the 64 MiB
    chunk target sent each side through 2-4 chunk programs), one
    dispatch and one collective launch for the two tables, in blocks on
    `util.capacity`'s grid (PR 52: S's 4.81M rows a pair crossed in
    2^23 slots). Routed over shapes alone: the pair program is stood in
    for."""
    import jax

    rows = 4 * 16_000_000
    leaf = jax.ShapeDtypeStruct((rows,), np.int32)
    side = {"k": leaf, "v": leaf}
    counts = [np.full((4, 4), n) for n in (4_003_000, s_pair_rows)]
    assert util.capacity(s_pair_rows) == s_block
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
    assert built == [(4_063_232, s_block)]
    route, = [s for s in cp.spans if s.name == "shuffle.route"]
    assert (route.attrs["mode"], route.attrs["chunks"],
            route.attrs["tables"]) == ("pair", 1, 2)
    assert [s.name for s in cp.spans if s.name.startswith(
        "shuffle.exchange")] == ["shuffle.exchange_pair"]
    assert (r1[0], r1[2], r2[0], r2[2]) \
        == ("o1", 4 * 4_063_232, "o2", 4 * s_block)
    assert (r1[3]["block"], r2[3]["block"]) == (4_063_232, s_block)
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
    rows, so the padded block is 4.46M (`util.capacity`; 8M on the
    octave before PR 52) — past MAX_BLOCK, which caps the block only
    where the budget is unknown. With live HBM numbers the budget alone
    decides (chip run, PR 22: capped at MAX_BLOCK the main path fell to
    the blockwise sort rounds and never met the partition kernel)."""
    counts = np.full((4, 4), 4_200_000)
    payload = {"k": np.zeros(8, np.int32), "w": np.zeros(8, np.float32)}
    grid = util.capacity(4_200_000)
    assert grid == 4_456_448 == 17 * 2 ** 18
    assert _shuffle._padded_route(counts, payload, 4, None) \
        == (False, grid, _shuffle.MAX_BLOCK)
    ok, block, mb = _shuffle._padded_route(counts, payload, 4, 4 << 30)
    # the cap is the block itself, whole: floored to 2^22 it would
    # refuse the block it was sized for
    assert ok and block == grid == mb
    # a budget that cannot hold 4 * world * block * 8 B still refuses
    assert not _shuffle._padded_route(counts, payload, 4, 512 << 20)[0]
    # an explicit max_block binds whatever the budget
    assert not _shuffle._padded_route(counts, payload, 4, 4 << 30,
                                     max_block=1 << 20)[0]
