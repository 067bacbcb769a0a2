"""The join's expand kernel at the block size the benchmark's cells run.

Every tier-1 join is under 2^20 rows, so `ops/join.stream_block_rows`
hands the kernels `block_rows` 8 and the 64-row blocks of a 16M-row join
were never run off a chip. Here `tpu_kernels.join_expand_stream` is
called directly on the Pallas interpreter at `block_rows` 64, 16 and 8,
on plans laid out by hand in numpy in the layout `join_plan_stream`
writes (group A: probe row, packed delta, output start, lanes; group B:
build row, lanes; `BR + 8` slack rows; garbage past the counts), and
compared element for element with a numpy expansion of the same plan.
Every case of one block size and lane count has the same shapes (a
capacity of four blocks), so the interpreted kernel is compiled once for
them all: ~6 s a variant, which is all the time the file takes.
The cases pin what the kernel answers, not how: they pass on a kernel
that sweeps the whole block window for every output vreg as on one that
sweeps a slab's own 16 rows.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cylon_tpu.ops import tpu_kernels as tk

LANES = 128
SLAB = 8 * LANES
CAP_BLOCKS = 4   # every case's output capacity, in blocks


def _rows(br):
    """Rows of every plan stream: three capacities (the widest build
    side below) and the `BR + 8` slack rows."""
    return 3 * CAP_BLOCKS * br + br + 8


@functools.lru_cache(maxsize=None)
def _expand(br):
    return jax.jit(functools.partial(
        tk.join_expand_stream, cap_e=CAP_BLOCKS * br * LANES, block_rows=br,
        interpret=True))


def _pk_fk(rng, n):
    """Every probe row matches exactly one build row; the build side has
    rows that no probe row wants between the wanted ones."""
    lo = np.sort(rng.choice(3 * n, n, replace=False))
    return np.ones(n, np.int64), lo, 3 * n


def _many_to_many(rng, n):
    """Probe rows in runs of up to 10 equal keys against up to 10 build
    rows: the rows of one probe run all start at the same build row."""
    m, lo, at = [], [], 0
    while len(m) < n:
        dup, cnt = rng.integers(1, 11, 2)
        m += [cnt] * dup
        lo += [at] * dup
        at += cnt + rng.integers(0, 3)
    return np.array(m[:n]), np.array(lo[:n]), at


def _one_hot_row(rng, n, mult):
    """ONE probe row emits `mult` outputs between rows that emit one."""
    m = np.ones(n, np.int64)
    m[n // 2] = mult
    return m, np.cumsum(m) - m, int(m.sum())


def _left_unmatched(rng, n):
    """A LEFT join's plan: a third of the probe rows have no build row
    and emit one output with no match, between matched rows."""
    m = rng.integers(0, 3, n)
    lo = np.cumsum(m) - m + 5
    return m, lo, int(m.sum()) + 5


def _sized(n_out):
    """A one-to-one plan of exactly `n_out` outputs."""
    return lambda rng, n: (np.ones(n_out, np.int64), np.arange(n_out) + 1,
                           n_out + 1)


def _lay(x, br, rng):
    """One plan stream: `x` as uint32 bit patterns, garbage after it."""
    rows = _rows(br)
    assert len(x) <= (rows - br - 8) * LANES
    out = rng.integers(0, 1 << 32, rows * LANES, dtype=np.uint64)
    out = out.astype(np.uint32)
    out[:len(x)] = np.asarray(x).astype(np.int64).astype(np.uint32)
    return jnp.asarray(out.reshape(rows, LANES))


def _plan(m, lo, n_b, la, lb, br, rng):
    """(counts, a_streams, b_streams, expected) for emitting probe rows
    with `m` matches each from build position `lo`."""
    n_emit = len(m)
    mult = np.maximum(m, 1)           # an unmatched row emits itself
    start = np.cumsum(mult) - mult
    n_out = int(mult.sum())
    elist = rng.permutation(n_emit + 7)[:n_emit]
    blist = rng.permutation(n_b + 3)[:n_b]
    delta2 = (lo - start) * 2 + (m > 0)
    a_lanes = rng.integers(1, 1 << 32, (la, n_emit), dtype=np.uint64)
    b_lanes = rng.integers(1, 1 << 32, (lb, n_b), dtype=np.uint64)
    a_streams = tuple(_lay(x, br, rng)
                      for x in (elist, delta2, start, *a_lanes))
    b_streams = tuple(_lay(x, br, rng) for x in (blist, *b_lanes))
    counts = jnp.asarray([n_out, n_emit, n_b, 0], jnp.int32)

    run = np.repeat(np.arange(n_emit), mult)      # output -> probe run
    has = m[run] > 0
    bpos = np.where(has, np.arange(n_out) + lo[run] - start[run], 0)
    want = dict(
        aidx=elist[run], bidx=np.where(has, blist[bpos], -1),
        a_lanes=[x[run].astype(np.uint32) for x in a_lanes],
        b_lanes=[np.where(has, x[bpos], 0).astype(np.uint32)
                 for x in b_lanes])
    return counts, a_streams, b_streams, n_out, want


CASES = [
    # name, plan, probe rows, a lanes, b lanes, block_rows
    ("pk_fk_64", _pk_fk, 28000, 2, 2, 64),
    ("pk_fk_16", _pk_fk, 7000, 2, 2, 16),
    ("pk_fk_8", _pk_fk, 3500, 2, 2, 8),
    ("many_to_many_64", _many_to_many, 5000, 2, 2, 64),
    ("many_to_many_8lanes_64", _many_to_many, 4000, 4, 4, 64),
    ("many_to_many_8lanes_16", _many_to_many, 1200, 4, 4, 16),
    ("one_row_over_two_blocks_64",
     lambda rng, n: _one_hot_row(rng, n, 2 * 64 * LANES + 777), 3000, 2, 2,
     64),
    ("one_row_over_two_blocks_8",
     lambda rng, n: _one_hot_row(rng, n, 2 * 8 * LANES + 77), 900, 2, 2, 8),
    ("left_unmatched_64", _left_unmatched, 18000, 2, 2, 64),
    ("left_unmatched_16", _left_unmatched, 5000, 2, 2, 16),
    ("n_out_on_a_slab_edge_64", _sized(64 * LANES + 3 * SLAB), 0, 2, 2, 64),
    ("n_out_on_a_block_edge_64", _sized(2 * 64 * LANES), 0, 2, 2, 64),
    ("n_out_on_a_block_edge_16", _sized(3 * 16 * LANES), 0, 2, 2, 16),
    ("n_out_fills_the_capacity_16", _sized(CAP_BLOCKS * 16 * LANES), 0, 2,
     2, 16),
    ("n_out_zero_64", _sized(0), 0, 2, 2, 64),
    ("n_out_zero_8", _sized(0), 0, 2, 2, 8),
    # dead slabs and dead blocks: three quarters of the capacity
    ("capacity_4x_n_out_64", _pk_fk, 64 * LANES - 100, 2, 2, 64),
    ("capacity_4x_n_out_8lanes_16", _many_to_many, 300, 4, 4, 16),
]


@pytest.mark.parametrize("name,make,n,la,lb,br", CASES,
                         ids=[c[0] for c in CASES])
def test_expand_matches_numpy(name, make, n, la, lb, br):
    rng = np.random.default_rng(len(name) * 1000 + n + br)
    m, lo, n_b = make(rng, n)
    counts, a_streams, b_streams, n_out, want = _plan(
        np.asarray(m, np.int64), np.asarray(lo, np.int64), n_b, la, lb, br,
        rng)
    cap_e = CAP_BLOCKS * br * LANES
    assert cap_e >= n_out
    if name.startswith("capacity_4x"):
        assert 4 * n_out <= cap_e
    with jax.enable_x64(False):
        aidx, bidx, a_out, b_out = _expand(br)(counts, a_streams, b_streams)
    assert len(a_out) == la and len(b_out) == lb
    got = [np.asarray(aidx), np.asarray(bidx)] \
        + [np.asarray(x) for x in (*a_out, *b_out)]
    exp = [want["aidx"], want["bidx"], *want["a_lanes"], *want["b_lanes"]]
    fills = [-1, -1] + [0] * (la + lb)
    for g, e, fill in zip(got, exp, fills):
        assert g.shape == (cap_e,)
        np.testing.assert_array_equal(g[:n_out], e.astype(g.dtype))
        # dead slabs and dead blocks: the fills and nothing else
        assert (g[n_out:] == fill).all()
