"""The Pallas fused hash+bucket+scatter partition kernel.

Core contract: `shuffle._partition_path` routes the padded exchange's
partition through either the XLA stable sort or the fused Pallas
histogram+scatter kernel, from platform, world and payload alone (no
knob: a test reaches the other path, the kernel under the interpreter
off a TPU, by patching the function, `forced_paths.partition`), and the
two paths are BIT-IDENTICAL on every live row — leaves, counts, start offsets, emit
mask — across dtypes (varbytes word legs included), chunk geometry
(single-shot / deep / odd remainder), empty buckets, all-dead emit
masks, and end to end through distributed_join /
distributed_groupby. The path string keys every factory cache.

Interpreter-cost guard: sizes here stay <= 4096 rows and world <= 4
(one pallas block, <= 5 grid buckets). The PR-1-era lesson holds: an
interpreted Pallas graph compiles through XLA:CPU at real cost, and
each distinct (block, part) geometry is one compile — keep geometries
few and tiny.
"""
from types import SimpleNamespace

import numpy as np
import pytest

import cylon_tpu as ct
import forced_paths
from cylon_tpu import telemetry
from cylon_tpu.ops import tpu_kernels as tk
from cylon_tpu.parallel import shard as _shard
from cylon_tpu.parallel import shuffle as _shuffle


def _mk_inputs(ctx, n, seed=0, live=0.85, extra_dtypes=()):
    import jax.numpy as jnp

    world = ctx.get_world_size()
    rng = np.random.default_rng(seed)
    payload = {
        "a": _shard.pin(jnp.asarray(
            rng.integers(0, 1 << 30, n).astype(np.int32)), ctx),
        "b": _shard.pin(jnp.asarray(
            rng.normal(size=n).astype(np.float32)), ctx),
        "m": _shard.pin(jnp.asarray(rng.random(n) < 0.5), ctx),
    }
    for i, dt in enumerate(extra_dtypes):
        payload[f"x{i}"] = _shard.pin(jnp.asarray(
            rng.integers(-100, 100, n).astype(dt)), ctx)
    targets = _shard.pin(jnp.asarray(
        rng.integers(0, world, n).astype(np.int32)), ctx)
    if live >= 1.0:
        emit = _shard.pin(jnp.ones(n, dtype=bool), ctx)
    elif live <= 0.0:
        emit = _shard.pin(jnp.zeros(n, dtype=bool), ctx)
    else:
        emit = _shard.pin(jnp.asarray(rng.random(n) < live), ctx)
    return payload, targets, emit


def _counts(ctx, targets, emit):
    import jax

    return np.asarray(jax.device_get(
        _shuffle._count_fn(ctx.mesh)(targets, emit)))


def _both_paths(ctx, payload, targets, emit, monkeypatch, **kw):
    counts = _counts(ctx, targets, emit)
    forced_paths.partition(monkeypatch, "sort")
    base = _shuffle.exchange(payload, targets, emit, ctx, counts=counts,
                             **kw)
    forced_paths.partition(monkeypatch, "pallas")
    out = _shuffle.exchange(payload, targets, emit, ctx, counts=counts,
                            **kw)
    return base, out


def _assert_bit_identical(base, out):
    o0, e0, c0, m0 = base
    o1, e1, c1, m1 = out
    assert c0 == c1
    e0h, e1h = np.asarray(e0), np.asarray(e1)
    assert np.array_equal(e0h, e1h)
    assert np.array_equal(np.asarray(m0["counts_in"]),
                          np.asarray(m1["counts_in"]))
    assert m0["block"] == m1["block"]
    for k in o0:
        assert np.array_equal(np.asarray(o0[k])[e0h],
                              np.asarray(o1[k])[e1h]), k


# ---------------------------------------------------------------------------
# kernel units (eager interpreter, outside any jit)
# ---------------------------------------------------------------------------


def test_partition_hist_matches_reference():
    import jax.numpy as jnp

    rng = np.random.default_rng(1)
    for n, w in [(1000, 5), (4096, 2), (9000, 9), (17, 1)]:
        t = rng.integers(0, w, n).astype(np.int32)
        hist = np.asarray(tk.partition_hist(jnp.asarray(t), w,
                                            interpret=True))
        blocks = max(-(-n // (32 * 128)), 1)
        assert hist.shape == (blocks, w)
        ref = np.zeros((blocks, w), np.int32)
        for b in range(blocks):
            seg = t[b * 4096:(b + 1) * 4096]
            for k in range(w):
                ref[b, k] = (seg == k).sum()
        assert np.array_equal(hist, ref), (n, w)


def _targets(kind, n, w, rng):
    """Bucket ids for one scatter case; the LAST bucket is the dead
    rows' (`_kernel_partition` keys them ``world``)."""
    if kind == "uniform":
        return rng.integers(0, w, n)
    if kind == "empty_bucket":        # no row of bucket 2
        t = rng.integers(0, w - 1, n)
        return np.where(t >= 2, t + 1, t)
    if kind == "all_dead":
        return np.full(n, w - 1)
    if kind == "one_live":
        t = np.full(n, w - 1)
        t[n // 3] = 1
        return t
    if kind == "clustered":           # whole blocks hold one bucket
        return np.sort(rng.integers(0, w, n))[::-1]
    if kind == "dead_tail":           # groupby-q5-w4: a live prefix
        return np.where(np.arange(n) < n // 6,
                        rng.integers(0, w - 1, n), w - 1)
    raise AssertionError(kind)


# a block is `partition_block_rows(legs)` x 128 rows: 32,768 up to 10
# legs, 8,192 at 21; block_rows 8 is a test's height, for many blocks
SCATTER_CASES = [
    # (n, buckets, legs, targets, block_rows)
    (1000, 5, 3, "uniform", None),          # under one block
    (17, 1, 1, "uniform", None),
    (32767, 5, 2, "uniform", None),         # one row short of a block
    (32768, 4, 2, "uniform", None),         # a block exactly
    (32769, 5, 2, "uniform", None),         # one row over
    (4095, 3, 1, "uniform", 32),            # around the old block
    (4097, 3, 1, "uniform", 32),
    (70000, 5, 1, "empty_bucket", None),
    (40000, 5, 2, "all_dead", None),
    (40000, 5, 2, "one_live", None),
    (100000, 5, 2, "clustered", None),      # the skip path
    (100000, 5, 4, "dead_tail", None),
    (9000, 17, 2, "uniform", None),         # world 16
    (5000, 5, 8, "uniform", None),
    (9000, 3, 21, "uniform", None),         # 64 rows a block
    (9000, 9, 3, "uniform", 8),             # nine blocks
]


@pytest.mark.parametrize(
    "n,w,nlegs,kind,block_rows", SCATTER_CASES,
    ids=[f"{n}-b{w}-l{l}-{k}" + (f"-r{r}" if r else "")
         for n, w, l, k, r in SCATTER_CASES])
def test_partition_scatter_is_the_stable_sort_permutation(
        n, w, nlegs, kind, block_rows):
    import jax.numpy as jnp

    rng = np.random.default_rng(2)
    t = _targets(kind, n, w, rng).astype(np.int32)
    legs = [rng.integers(0, 1 << 32, n, dtype=np.uint64)
            .astype(np.uint32) for _ in range(nlegs)]
    outs = tk.partition_scatter(jnp.asarray(t),
                                [jnp.asarray(x) for x in legs], w,
                                block_rows=block_rows, interpret=True)
    perm = np.argsort(t, kind="stable")
    for o, x in zip(outs, legs):
        assert np.array_equal(np.asarray(o), x[perm])


@pytest.mark.parametrize("legs,rows", [
    (1, 256), (2, 256), (4, 256), (10, 256), (11, 128), (20, 128),
    (21, 64), (32, 64), (42, 64), (43, 32), (128, 32)])
def test_partition_block_rows_fit_the_vmem_budget(legs, rows):
    """The block is as tall as the legs allow, and no taller than pays:
    id block and leg blocks double-buffered plus a write window a leg
    stay inside the budget wherever the floor of 32 rows is not what
    holds the height."""
    assert tk.partition_block_rows(legs) == rows
    held = (2 * (1 + legs) + legs) * rows * tk.LANES * 4
    assert held <= tk._PARTITION_VMEM_BYTES or rows == 32


@pytest.mark.parametrize("n,buckets,legs,steps", [
    (16_000_000, 5, 2, 2445),       # join-w4, a side: 489 blocks
    (62_500_000, 5, 4, 9540),       # groupby-q5-w4's partial table
    (1, 2, 1, 2), (0, 5, 2, 5),     # one block at the least
    (32768, 17, 2, 17), (32769, 17, 2, 34),
    (8192, 5, 21, 5), (8193, 5, 21, 10)])
def test_partition_scatter_steps_are_the_grid(n, buckets, legs, steps):
    assert tk.partition_scatter_steps(n, buckets, legs) == steps


@pytest.mark.parametrize("dtypes", [
    (np.int32, np.float32, np.uint32),
    (np.int16, np.int8, np.bool_),
])
def test_kernel_partition_bit_identical_to_bucket_sort(dtypes):
    """`_kernel_partition` reproduces `_bucket_sort` EXACTLY — sorted
    leaves including the dead-row tail, counts_out and start — across
    4/2/1-byte dtypes and bool (the scatter IS the stable sort)."""
    import jax.numpy as jnp

    rng = np.random.default_rng(3)
    n, world = 3000, 4
    payload = {}
    for i, dt in enumerate(dtypes):
        if dt is np.bool_:
            payload[f"c{i}"] = jnp.asarray(rng.random(n) < 0.5)
        else:
            payload[f"c{i}"] = jnp.asarray(
                rng.integers(-100, 100, n).astype(dt))
    targets = jnp.asarray(rng.integers(0, world, n).astype(np.int32))
    emit = jnp.asarray(rng.random(n) < 0.8)
    ref_leaves, ref_counts, ref_start = _shuffle._bucket_sort(
        dict(payload), targets, emit, world)
    got_leaves, got_counts, got_start = _shuffle._kernel_partition(
        dict(payload), targets, emit, world, interpret=True)
    assert np.array_equal(np.asarray(ref_counts), np.asarray(got_counts))
    assert np.array_equal(np.asarray(ref_start), np.asarray(got_start))
    for k in ref_leaves:
        assert ref_leaves[k].dtype == got_leaves[k].dtype, k
        assert np.array_equal(np.asarray(ref_leaves[k]),
                              np.asarray(got_leaves[k])), k


def test_leg_split_round_trips_2d_leaf():
    import jax.numpy as jnp

    x = jnp.asarray(np.arange(24, dtype=np.int32).reshape(12, 2))
    legs, join = _shuffle._leg_split(x)
    assert len(legs) == 2 and all(leg.dtype == jnp.uint32
                                  for leg in legs)
    assert np.array_equal(np.asarray(join(list(legs))), np.asarray(x))


# ---------------------------------------------------------------------------
# exchange-level bit-identity (pallas-interpret vs sort path)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("live", [1.0, 0.85])
def test_exchange_bit_identical_single_shot(dist_ctx, monkeypatch, live):
    forced_paths.single_shot(monkeypatch)
    payload, targets, emit = _mk_inputs(dist_ctx, 2048, seed=5,
                                        live=live)
    base, out = _both_paths(dist_ctx, payload, targets, emit,
                            monkeypatch)
    _assert_bit_identical(base, out)


def test_exchange_bit_identical_narrow_dtypes(dist_ctx, monkeypatch):
    """2-byte and 1-byte leaves ride as widened u32 legs and come back
    bit-exact."""
    forced_paths.single_shot(monkeypatch)
    payload, targets, emit = _mk_inputs(
        dist_ctx, 2048, seed=6, extra_dtypes=(np.int16, np.int8))
    base, out = _both_paths(dist_ctx, payload, targets, emit,
                            monkeypatch)
    _assert_bit_identical(base, out)


def test_exchange_bit_identical_chunked_and_odd_geometry(dist_ctx,
                                                         monkeypatch):
    """The chunked pipeline feeds from the same `_padded_partition`:
    the kernel path must be bit-identical through a deep pipeline AND a
    forced non-pow2 chunk block (the dropping-scatter remainder)."""
    payload, targets, emit = _mk_inputs(dist_ctx, 4096, seed=7)
    counts = _counts(dist_ctx, targets, emit)
    forced_paths.single_shot(monkeypatch)
    forced_paths.partition(monkeypatch, "sort")
    base = _shuffle.exchange(payload, targets, emit, dist_ctx,
                             counts=counts)
    forced_paths.chunked(monkeypatch, 4096)
    forced_paths.partition(monkeypatch, "pallas")
    deep = _shuffle.exchange(payload, targets, emit, dist_ctx,
                             counts=counts)
    assert deep[3].get("chunks", 1) > 1
    _assert_bit_identical(base, deep)
    monkeypatch.setattr(
        _shuffle, "_chunk_plan",
        lambda block, *_: (3, -(-block // 3)) if block > 3
        else (block, 1))
    odd = _shuffle.exchange(payload, targets, emit, dist_ctx,
                            counts=counts)
    assert odd[3]["chunks"] == -(-base[3]["block"] // 3)
    _assert_bit_identical(base, odd)


def test_exchange_bit_identical_empty_buckets(dist_ctx, monkeypatch):
    """Every row targets shard 0: the other buckets are empty, the
    scatter must still land counts/offsets exactly."""
    import jax.numpy as jnp

    forced_paths.single_shot(monkeypatch)
    payload, _targets, emit = _mk_inputs(dist_ctx, 2048, seed=8)
    targets = _shard.pin(jnp.zeros(2048, jnp.int32), dist_ctx)
    base, out = _both_paths(dist_ctx, payload, targets, emit,
                            monkeypatch)
    _assert_bit_identical(base, out)


def test_exchange_bit_identical_all_dead(dist_ctx, monkeypatch):
    """An all-False emit mask sends every row to the dead bucket: both
    paths must report zero live rows everywhere."""
    forced_paths.single_shot(monkeypatch)
    payload, targets, emit = _mk_inputs(dist_ctx, 2048, seed=9,
                                        live=0.0)
    base, out = _both_paths(dist_ctx, payload, targets, emit,
                            monkeypatch)
    assert not np.asarray(base[1]).any()
    _assert_bit_identical(base, out)


# ---------------------------------------------------------------------------
# routing, observability, and a program a path
# ---------------------------------------------------------------------------


def _stub_mesh(platform):
    devs = np.empty(1, object)
    devs[0] = SimpleNamespace(platform=platform)
    return SimpleNamespace(devices=devs)


_NARROW = {"a": np.zeros(8, np.int32)}


@pytest.mark.parametrize("platform,world,payload,want", [
    # off a TPU: the XLA sort (the kernel only wins on the chip)
    ("cpu", 4, _NARROW, "sort"),
    ("tpu", 4, _NARROW, "pallas"),
    # past _PARTITION_MAX_WORLD the scatter's passes lose to the sort
    ("tpu", 17, _NARROW, "sort"),
    # one shard: nothing to partition
    ("tpu", 1, _NARROW, "sort"),
    # a 3-D leaf does not split into u32 legs: ineligible
    ("tpu", 4, {"a": np.zeros((8, 2, 2), np.int32)}, "sort"),
    # world+1 buckets must fit one histogram lane row
    ("tpu", 127, _NARROW, "sort"),
], ids=["cpu", "tpu-w4", "tpu-w17", "tpu-w1", "tpu-3d-leaf", "tpu-w127"])
def test_partition_path_is_a_pure_function(platform, world, payload,
                                           want):
    """Platform, world and payload decide, and nothing else: no knob
    and no environment variable is read (PR 45), and the interpreter's
    spelling is never returned."""
    assert _shuffle._partition_path(_stub_mesh(platform), world,
                                    payload) == want


def test_exchange_pair_mixed_partition_paths(dist_ctx, monkeypatch):
    """A fused pair whose sides route differently (side 1 ineligible →
    sort, side 2 → kernel) must still build the unchecked shard_map
    program (any pallas side forbids the replication check) and stay
    bit-identical to the all-sort pair."""
    import jax.numpy as jnp

    world = dist_ctx.get_world_size()
    forced_paths.single_shot(monkeypatch)

    def side(n, seed, extra_3d=False):
        r = np.random.default_rng(seed)
        p = {"a": _shard.pin(jnp.asarray(
            r.integers(0, 1 << 30, n).astype(np.int32)), dist_ctx)}
        if extra_3d:
            # 3-D leaf: ineligible for the kernel → this side is sort
            p["z"] = _shard.pin(jnp.asarray(
                r.integers(0, 9, (n, 2, 2)).astype(np.int32)),
                dist_ctx)
        t = _shard.pin(jnp.asarray(
            r.integers(0, world, n).astype(np.int32)), dist_ctx)
        e = _shard.pin(jnp.asarray(r.random(n) < 0.9), dist_ctx)
        return p, t, e

    p1, t1, e1 = side(1024, 31, extra_3d=True)
    p2, t2, e2 = side(512, 32)
    c1, c2 = _shuffle.count_pair(t1, e1, t2, e2, dist_ctx)
    forced_paths.partition(monkeypatch, "sort")
    b1, b2 = _shuffle.exchange_pair(p1, t1, e1, c1, p2, t2, e2, c2,
                                    dist_ctx)
    forced_paths.partition(monkeypatch, "pallas")
    assert _shuffle._partition_path(dist_ctx.mesh, world, p1) == "sort"
    assert _shuffle._partition_path(dist_ctx.mesh, world, p2) == "interp"
    spans = []

    def sink(span):
        if span.name.startswith("shuffle.exchange_pair"):
            spans.append(dict(span.attrs))

    telemetry.add_sink(sink)
    try:
        o1, o2 = _shuffle.exchange_pair(p1, t1, e1, c1, p2, t2, e2, c2,
                                        dist_ctx)
    finally:
        telemetry.remove_sink(sink)
    _assert_bit_identical(b1, o1)
    _assert_bit_identical(b2, o2)
    assert spans[-1]["partition_path"] == "mixed"


def test_partition_path_counter_and_span_attr(dist_ctx, monkeypatch):
    forced_paths.single_shot(monkeypatch)
    payload, targets, emit = _mk_inputs(dist_ctx, 2048, seed=11)
    counts = _counts(dist_ctx, targets, emit)
    spans = []

    def sink(span):
        if span.name.startswith("shuffle.exchange"):
            spans.append(dict(span.attrs))

    telemetry.add_sink(sink)
    try:
        def total(path):
            return telemetry.metrics_snapshot().get(
                f'cylon_partition_path_total{{path="{path}"}}', 0)

        s0, p0 = total("sort"), total("pallas")
        forced_paths.partition(monkeypatch, "sort")
        _shuffle.exchange(payload, targets, emit, dist_ctx,
                          counts=counts)
        assert total("sort") == s0 + 1
        forced_paths.partition(monkeypatch, "pallas")
        _shuffle.exchange(payload, targets, emit, dist_ctx,
                          counts=counts)
        assert total("pallas") == p0 + 1
    finally:
        telemetry.remove_sink(sink)
    assert [s["partition_path"] for s in spans] == ["sort", "pallas"]


def test_partition_steps_counted_by_the_kernels_grid(dist_ctx,
                                                     monkeypatch):
    """`cylon_partition_steps_total` grows once a dispatch on the kernel
    path by the grid ONE shard's `partition_scatter` takes over the
    payload, a pure function of the shapes, and by nothing on the sort
    path (where the family still exists: the benchmark's
    `partition_steps_per_query` reads 0 there, not "no counter")."""
    forced_paths.single_shot(monkeypatch)
    world = dist_ctx.get_world_size()
    n = 2048
    payload, targets, emit = _mk_inputs(dist_ctx, n, seed=13,
                                        extra_dtypes=(np.int64,))
    counts = _counts(dist_ctx, targets, emit)
    legs = _shuffle._payload_legs(payload)
    assert legs == 5      # a, b, m one each; the int64 column two

    def total():
        return telemetry.metrics_snapshot().get(
            "cylon_partition_steps_total")

    forced_paths.partition(monkeypatch, "sort")
    _shuffle.exchange(payload, targets, emit, dist_ctx, counts=counts)
    t0 = total()
    assert t0 is not None
    _shuffle.exchange(payload, targets, emit, dist_ctx, counts=counts)
    assert total() == t0
    forced_paths.partition(monkeypatch, "pallas")
    _shuffle.exchange(payload, targets, emit, dist_ctx, counts=counts)
    assert total() - t0 == tk.partition_scatter_steps(
        n // world, world + 1, legs) == world + 1


def test_payload_legs_are_what_leg_split_cuts():
    import jax.numpy as jnp

    payload = {"a": jnp.zeros(8, jnp.int8), "b": jnp.zeros(8, jnp.int16),
               "c": jnp.zeros(8, jnp.float32), "d": jnp.zeros(8, bool),
               "e": jnp.zeros(8, jnp.int64), "f": jnp.zeros(8, jnp.float64),
               "g": jnp.zeros((8, 3), jnp.uint32),
               "h": jnp.zeros((8, 2), jnp.int64)}
    for name, x in payload.items():
        assert _shuffle._payload_legs({name: x}) \
            == len(_shuffle._leg_split(x)[0]), name
    assert _shuffle._payload_legs(payload) == 15


def test_the_sort_path_keys_a_program_of_its_own(dist_ctx, monkeypatch):
    """The path string keys the factory cache: repeated sort-path
    exchanges build the padded program once, and a pallas-path exchange
    in between builds a DIFFERENT program without evicting it."""
    forced_paths.single_shot(monkeypatch)
    payload, targets, emit = _mk_inputs(dist_ctx, 2048, seed=12)
    counts = _counts(dist_ctx, targets, emit)

    def builds():
        return telemetry.metrics_snapshot().get(
            'cylon_kernel_factory_builds_total'
            '{factory="_exchange_padded_fn"}', 0)

    forced_paths.partition(monkeypatch, "sort")
    _shuffle.exchange(payload, targets, emit, dist_ctx, counts=counts)
    b0 = builds()
    forced_paths.partition(monkeypatch, "pallas")
    _shuffle.exchange(payload, targets, emit, dist_ctx, counts=counts)
    forced_paths.partition(monkeypatch, "sort")
    _shuffle.exchange(payload, targets, emit, dist_ctx, counts=counts)
    # the second sort-path exchange re-used the first program; only
    # the pallas variant could have added a build
    assert builds() - b0 <= 1


# ---------------------------------------------------------------------------
# end to end through the distributed ops and EXPLAIN ANALYZE
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("knob", ["sort", "pallas"])
def test_distributed_join_and_groupby_end_to_end(dist_ctx, monkeypatch,
                                                 knob):
    forced_paths.partition(monkeypatch, knob)
    rng = np.random.default_rng(17)
    n = 2048
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

    gg = ct.distributed_groupby(
        left, 0, [1], [ct.AggregationOp.SUM]).to_pandas()
    gl = ct.Table.from_pydict(lctx, {
        "k": np.asarray(left.to_pydict()["k"]),
        "v": np.asarray(left.to_pydict()["v"])}).groupby(
        0, [1], ["sum"]).to_pandas()
    a = gg.sort_values(gg.columns[0]).reset_index(drop=True)
    b = gl.sort_values(gl.columns[0]).reset_index(drop=True)
    np.testing.assert_allclose(a.iloc[:, 1].astype(float),
                               b.iloc[:, 1].astype(float), rtol=1e-4)


@pytest.mark.parametrize("knob", ["sort", "pallas"])
def test_varbytes_word_legs_end_to_end(dist_ctx, monkeypatch, knob):
    """Forced-varbytes string keys route their word legs through the
    same partition — the strings must survive both paths."""
    from cylon_tpu.data import strings as _strings

    forced_paths.partition(monkeypatch, knob)
    monkeypatch.setattr(_strings, "DICT_MAX_VOCAB", 0)
    rng = np.random.default_rng(19)
    n = 512
    keys = np.array([f"key{int(x):04d}" for x in
                     rng.integers(0, 50, n)], object)
    left = ct.Table.from_pydict(dist_ctx, {
        "k": keys, "v": rng.normal(size=n).astype(np.float32)})
    right = ct.Table.from_pydict(dist_ctx, {
        "k": keys[rng.permutation(n)][:n // 2],
        "w": rng.normal(size=n // 2).astype(np.float32)})
    got = left.distributed_join(right, "inner", on="k").to_pandas()
    lctx = ct.CylonContext.Init()
    want = ct.Table.from_pydict(lctx, {
        "k": keys, "v": np.asarray(left.to_pydict()["v"])}).join(
        ct.Table.from_pydict(lctx, {
            "k": np.asarray(right.to_pydict()["k"]),
            "w": np.asarray(right.to_pydict()["w"])}),
        "inner", on="k").to_pandas()
    assert sorted(map(tuple, got.astype(str).values.tolist())) \
        == sorted(map(tuple, want.astype(str).values.tolist()))


def test_explain_analyze_renders_partition_path(dist_ctx8, monkeypatch):
    from cylon_tpu import plan

    forced_paths.partition(monkeypatch, "sort")
    rng = np.random.default_rng(23)
    n = 2048
    left = ct.Table.from_pydict(dist_ctx8, {
        "k": rng.integers(0, n, n).astype(np.int32),
        "v": rng.normal(size=n).astype(np.float32)})
    right = ct.Table.from_pydict(dist_ctx8, {
        "k": rng.integers(0, n, n).astype(np.int32),
        "w": rng.normal(size=n).astype(np.float32)})
    pipe = plan.scan(left).join(plan.scan(right), on="k")
    txt = pipe.explain(analyze=True)
    assert "part=sort" in txt, txt
    d = pipe.last_report.to_dict()

    def paths(node):
        yield node.get("partition_path")
        for c in node.get("children", ()):
            yield from paths(c)

    assert "sort" in set(paths(d["plan"]))
