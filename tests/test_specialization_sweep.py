"""Dynamic corroboration of the `specialization` analysis family: a
varied-cardinality distributed-op sweep, run once with the OLD
mantissa-rounded capacities (util.capacity, 16 buckets per octave) and
once with the shipped bucket_cap routing, pinning
``cylon_kernel_factory_builds_total{factory=_setop_mat_fn}`` for both.

The static checker (analysis/specialization.py) proves every
capacity-keyed factory call site routes through a recognized bucketing
helper; this test proves the routing WORKS: on the same data the
bucketed path compiles at most one program per capacity BUCKET (not
per distinct capacity value), at least 2x fewer than the unbucketed
baseline — and every op result is identical row-for-row, because the
padding rows past the true count are masked by the kernels' emit
discipline.
"""
import numpy as np
import pandas as pd
import pytest

import cylon_tpu as ct
from cylon_tpu import telemetry, util
from cylon_tpu.util import bucket_cap
from cylon_tpu.parallel import dist_ops, distribute

# per-side row counts chosen so the union's per-shard materialize
# totals straddle pow2 boundaries: ~6 distinct mantissa capacities
# collapse into ~2-3 pow2 buckets (and everything under 512 shares the
# floor bucket)
SWEEP_SIZES = (700, 930, 1150, 1520, 2100, 2650)


def _builds(factory: str) -> int:
    return telemetry.counter("cylon_kernel_factory_builds_total",
                             {"factory": factory}).value


def _make_sides(ctx, n: int, seed: int):
    rng = np.random.default_rng(seed)
    # wide value range: near-zero dedup, so the union total tracks n
    # and each sweep size lands a distinct per-shard materialize count
    lo, hi = 1_000_000, 900_000_000
    tl = ct.Table.from_pydict(ctx, {
        "k": rng.integers(lo, hi, n).astype(np.int64),
        "v": rng.integers(0, 1000, n).astype(np.int64)})
    tr = ct.Table.from_pydict(ctx, {
        "k": rng.integers(lo, hi, n).astype(np.int64),
        "v": rng.integers(0, 1000, n).astype(np.int64)})
    return distribute(tl, ctx), distribute(tr, ctx)


def _run_sweep(ctx, cap_fn, caps_seen):
    """Run the union sweep with dist_ops' capacity routing replaced by
    ``cap_fn`` (recording each produced capacity), returning sorted
    result frames and the _setop_mat_fn builds delta."""
    orig = dist_ops._bucket_cap

    def recording(n):
        cap = cap_fn(n)
        caps_seen.append(int(cap))
        return cap

    before = _builds("_setop_mat_fn")
    results = []
    dist_ops._bucket_cap = recording
    try:
        for i, n in enumerate(SWEEP_SIZES):
            tl, tr = _make_sides(ctx, n, seed=1000 + i)
            got = tl.distributed_union(tr).to_pandas()
            got.columns = range(got.shape[1])
            results.append(got.sort_values(list(got.columns))
                           .reset_index(drop=True))
    finally:
        dist_ops._bucket_cap = orig
    return results, _builds("_setop_mat_fn") - before


def test_varied_sweep_builds_bounded_by_bucket_count(dist_ctx):
    """Per-factory builds <= bucket count (not distinct-value count),
    >=2x fewer distinct compiles than the unbucketed baseline, results
    identical row-for-row."""
    # baseline FIRST: its mantissa capacities (s in [17,32] << e) are
    # not pow2 for these sizes, so earlier tests' warm bucket keys
    # cannot have pre-built them
    base_caps, buck_caps = [], []
    base_results, base_builds = _run_sweep(
        dist_ctx, lambda n: util.capacity(max(int(n), 1)), base_caps)
    buck_results, buck_builds = _run_sweep(dist_ctx, bucket_cap,
                                           buck_caps)

    # the sweep actually varied: the unbucketed path saw one distinct
    # capacity per sweep size...
    assert len(set(base_caps)) >= 4, sorted(set(base_caps))
    # ...which the bucketing collapses at least 2x
    assert len(set(base_caps)) >= 2 * len(set(buck_caps)), (
        sorted(set(base_caps)), sorted(set(buck_caps)))
    # every bucketed capacity is what bucket_cap says (pow2, floored)
    assert all(c == bucket_cap(c) for c in buck_caps), buck_caps

    # builds are bounded by the BUCKET count (warm lru entries from
    # earlier tests can only lower the delta, never raise it) and the
    # unbucketed baseline pays >=2x more distinct compiles
    assert buck_builds <= len(set(buck_caps)), (buck_builds, buck_caps)
    assert base_builds >= 4, base_builds
    assert base_builds >= 2 * max(buck_builds, 1), (base_builds,
                                                    buck_builds)

    # bit-identical op results: bucketing only pads the capacity, the
    # emit mask hides the padding — int64 frames compare exactly
    for n, a, b in zip(SWEEP_SIZES, base_results, buck_results):
        pd.testing.assert_frame_equal(a, b, check_exact=True,
                                      obj=f"union n={n}")


def test_bucket_cap_policy():
    """The ONE bucketing policy: next pow2 with a 512 floor — octave
    cardinality above the floor, a single shared bucket below it."""
    assert bucket_cap(1) == 512
    assert bucket_cap(511) == 512
    assert bucket_cap(512) == 512
    assert bucket_cap(513) == 1024
    assert bucket_cap(1024) == 1024
    assert bucket_cap(1025) == 2048
    assert bucket_cap(0) == 512  # degenerate counts share the floor
    # idempotent: a bucketed capacity re-buckets to itself
    for n in (3, 700, 5000, 1 << 20):
        assert bucket_cap(bucket_cap(n)) == bucket_cap(n)
    # custom floor
    assert bucket_cap(3, floor=16) == 16
    assert bucket_cap(100, floor=16) == 128


def test_pow2_floor_rounds_down():
    assert util.pow2_floor(1) == 1
    assert util.pow2_floor(1023) == 512
    assert util.pow2_floor(1024) == 1024
    assert util.pow2_floor(0) == 1  # degenerate: never zero


def test_capacity_grid_policy():
    """`util.capacity`: 17..32 x 2^e, at most 6.25% over, 16 shapes an
    octave; the capacities PR 50's compaction gives `tpch-q4`'s and
    `tpch-q12`'s filtered sides, beside the octave's."""
    for live, grid, octave in ((47_400_000, 48_234_496, 67_108_864),
                               (716_958, 720_896, 1_048_576),
                               (390_367, 393_216, 524_288)):
        assert util.capacity(live) == grid and bucket_cap(live) == octave
    octave = {util.capacity(n) for n in range((1 << 16) + 1, 1 << 17, 97)}
    assert len(octave) == 16
    for n in (1, 16, 17, 33, 311, 5000, 123_457, 75_000_000):
        cap = util.capacity(n)
        assert n <= cap <= n + max(n // 16, 1)
        assert util.capacity(cap) == cap           # idempotent


def test_compaction_builds_are_bounded_by_the_grid(local_ctx):
    """The ONE factory whose capacity key comes through `util.capacity`
    (analysis/specialization.FINE_KEYED_FACTORY_PARAMS): over an octave
    of live counts `_compact_program_fn` builds at most 16 programs, one
    a grid capacity and not one a count, the cut tables hold under 6.25%
    of dead slots where the octave's rounding held up to half, and the
    rows are `Table.compact()`'s."""
    from cylon_tpu.data import table as T

    n = 4096
    counts = list(range(1030, 2040, 37))
    caps = {util.capacity(c) for c in counts}
    assert len(counts) == 28 and len(caps) <= 16
    rng = np.random.default_rng(50)
    vals = rng.integers(0, 1 << 20, n).astype(np.int32)
    before = _builds("_compact_program_fn")
    slots = 0
    for c in counts:
        mask = np.zeros(n, bool)
        mask[rng.permutation(n)[:c]] = True
        t = ct.Table.from_pydict(local_ctx, {"v": vals}).filter_mask(mask)
        out, info = T.compact_live(t)
        assert info["compacted"] and out.capacity == util.capacity(c)
        assert out.to_pandas()["v"].tolist() == vals[mask].tolist()
        slots += out.capacity
    assert _builds("_compact_program_fn") - before <= len(caps)
    assert slots <= 1.0625 * sum(counts) < 0.8 * len(counts) * bucket_cap(1030)


def test_exchange_builds_are_bounded_by_the_grid(dist_ctx):
    """The padded exchange's block comes through `util.capacity` (PR 52;
    analysis/specialization.FINE_KEYED_FACTORY_PARAMS): over an octave
    of worst pairs `_exchange_padded_fn` builds at most 16 programs, one
    a grid block and not one a count, each chip's slots stay within
    6.25% of world x the worst pair where the octave's rounding held up
    to twice that, and every live row arrives."""
    import jax.numpy as jnp

    from cylon_tpu.parallel import shard as _shard
    from cylon_tpu.parallel import shuffle

    world = dist_ctx.get_world_size()
    per = 2048                      # rows a source
    pairs = list(range(260, 512, 9))
    blocks = {util.capacity(p) for p in pairs}
    assert len(pairs) == 28 and len(blocks) <= 16
    rng = np.random.default_rng(52)
    vals = rng.integers(0, 1 << 30, world * per).astype(np.int32)
    payload = {"v": _shard.pin(jnp.asarray(vals), dist_ctx)}
    before = _builds("_exchange_padded_fn")
    slots = 0
    for p in pairs:
        # every source sends every target exactly p live rows
        t = np.tile(np.repeat(np.arange(world), per // world), world)
        e = np.tile(np.tile(np.arange(per // world) < p, world), world)
        counts = np.full((world, world), p)
        out, emit, cap, meta = shuffle.exchange(
            payload, _shard.pin(jnp.asarray(t.astype(np.int32)), dist_ctx),
            _shard.pin(jnp.asarray(e), dist_ctx), dist_ctx, counts=counts)
        assert meta["mode"] == "padded"
        assert meta["block"] == util.capacity(p)
        assert cap == world * meta["block"]
        got = np.asarray(out["v"])[np.asarray(emit)]
        assert sorted(got.tolist()) == sorted(vals[e].tolist())
        slots += cap
    assert _builds("_exchange_padded_fn") - before <= len(blocks)
    assert slots <= 1.0625 * world * sum(pairs) \
        < 0.8 * world * sum(util.pow2(p) for p in pairs)
