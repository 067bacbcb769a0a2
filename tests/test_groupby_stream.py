"""The groupby's streaming reduce step (`tpu_kernels.groupby_run_reduce`
behind `groupby.sorted_segment_aggregate`) against the `segment_*` path,
which stays the oracle: eagerly under the Pallas interpreter, through
``interpret=True``, at sizes of a few blocks.

The interpreter costs about half a second a stream and a call whatever
the rows, so each SHAPE is reduced once (both paths, module cache) and
the parametrised cases read their (op, column) out of it: the whole
op x column matrix on one general shape; an int32 and a float32 sum (each
carried across blocks as bits) on every shape that stresses the scan, the
carry or the compaction; a masked min with its any-valid tally besides,
where rows are dead or null. Every shape also runs without a row mask
(no dead flag rides the sort) and without the index (the sorted key lane
rides out in its place and the keys are read back off it), on the lighter
of its case lists.
"""
import contextlib
import functools
import zlib

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import cylon_tpu as ct
from cylon_tpu import telemetry
from cylon_tpu.ops import groupby as G
from cylon_tpu.ops import order
from cylon_tpu.ops import tpu_kernels as tk

Op = G.AggregationOp
BR = 8                 # rows a block in these tests
SPAN = BR * tk.LANES   # 1024 elements


def _keys_of_counts(counts, rng):
    """Rows of key k repeated counts[k] times, shuffled: sorted, the runs
    end at the running sums of ``counts``."""
    keys = np.repeat(np.arange(len(counts), dtype=np.int32), counts)
    return rng.permutation(keys)


def _shape(name):
    """(keys, emit, num_segments) of a named shape."""
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    if name in ("mixed", "mixed_x64"):
        # n a multiple of neither the block nor 128, dead rows, one run
        # longer than two blocks among ~300 short ones
        keys = rng.integers(0, 300, 3500).astype(np.int32)
        keys[rng.random(3500) < 0.6] = 150
        return keys, rng.random(3500) < 0.9, 512
    if name == "one_run":      # every block inside one run; the grid's
        n = 4 * SPAN           # last element is live: the open-end flush
        return np.full(n, 7, np.int32), np.ones(n, bool), 16
    if name == "own_runs":     # every row its own run, num_segments = n
        n = 2 * SPAN + 37
        return rng.permutation(n).astype(np.int32), np.ones(n, bool), n
    if name == "block_edges":  # runs end ON 1024 and 2048, one PAST 3072,
        counts = [SPAN, SPAN - 24, 24, SPAN + 1, SPAN - 1]   # and on n
        keys = _keys_of_counts(counts, rng)
        return keys, np.ones(len(keys), bool), 8
    if name == "dead_rows":
        keys = rng.integers(0, 40, 3000).astype(np.int32)
        return keys, rng.random(3000) < 0.4, 64
    if name == "all_dead":
        return (rng.integers(0, 9, 1500).astype(np.int32),
                np.zeros(1500, bool), 16)
    if name == "nulls":        # groups 0..9 hold no valid masked value
        keys = rng.integers(0, 60, 2500).astype(np.int32)
        return keys, rng.random(2500) < 0.95, 64
    if name == "n1":
        return np.array([5], np.int32), np.ones(1, bool), 1
    raise KeyError(name)


COLUMNS = ("int32", "float32", "masked")   # masked: int32 + a bool mask
FULL = [(op, col) for op in Op for col in COLUMNS]
SUMS = [(Op.SUM, "int32"), (Op.SUM, "float32")]
MASKED = SUMS + [(Op.MIN, "masked")]
SHAPES = {"mixed": FULL, "mixed_x64": MASKED, "one_run": SUMS,
          "own_runs": SUMS, "block_edges": SUMS, "dead_rows": MASKED,
          "all_dead": SUMS, "nulls": MASKED, "n1": SUMS}
# what the sort carries: (a row mask and its dead flag, the row index);
# without the mask every row of the shape is live
CARRIES = {"mask+index": (True, True), "index": (False, True),
           "mask+keys": (True, False), "keys": (False, False)}


def _pairs(shape, carries):
    full = SHAPES[shape]
    return full if carries == "mask+index" or full is not FULL else MASKED


CASES = [(shape, carries, op, col) for shape in SHAPES
         for carries in CARRIES for op, col in _pairs(shape, carries)]


@contextlib.contextmanager
def small_blocks():
    """The kernel at BR rows a block, so that a few thousand rows are
    several blocks: steered here, in the test, not through an option of
    the program."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(tk, "groupby_run_reduce", functools.partial(
            tk.groupby_run_reduce, block_rows=BR))
        yield


@functools.lru_cache(maxsize=None)
def _reduced(shape, carries):
    """Both paths over one shape: (inputs, segment result, stream
    result), results as host arrays. The segment path's sort always
    carries the index (it is the oracle); the stream path's sort what
    ``carries`` says. x64 off as on the chip (COUNT and MEAN accumulate
    in 32 bits there), but for ``mixed_x64``, which runs as tier-1
    does."""
    masked, index = CARRIES[carries]
    keys, emit, num_segments = _shape(shape)
    n = len(keys)
    if not masked:
        emit = np.ones(n, bool)
    rng = np.random.default_rng(n)
    cols = {"int32": rng.integers(-1000, 1000, n).astype(np.int32),
            "float32": (rng.normal(size=n) * 100).astype(np.float32),
            "masked": rng.integers(-1000, 1000, n).astype(np.int32)}
    mask = rng.random(n) < 0.5
    if shape == "nulls":
        mask &= keys >= 10
    pairs = _pairs(shape, carries)
    ops = tuple(op for op, _ in pairs)
    cids = tuple(COLUMNS.index(c) for _, c in pairs)
    av = tuple(c != "masked" for _, c in pairs)
    with jax.enable_x64(shape == "mixed_x64"), small_blocks():
        presort = functools.partial(
            G.presort_groups, (order.ordered_bits_raw(jnp.asarray(keys)),),
            jnp.asarray(emit) if masked else None,
            tuple(jnp.asarray(cols[c]) for _, c in pairs),
            tuple(jnp.asarray(mask) if c == "masked" else None
                  for _, c in pairs))

        def reduce(state, **kw):
            vs, vm, emit_s, first_s, new_grp, _ng = state
            assert (emit_s is None) == (not masked)
            assert G.reduce_path([v.dtype for v in vs], ops, n,
                                 True) == "stream"
            return jax.tree.map(np.asarray, G.sorted_segment_aggregate(
                new_grp, emit_s, first_s, vs, vm, num_segments, ops, cids,
                av, **kw))

        seg = reduce(presort())
        run = reduce(presort(index=index), interpret=True,
                     key_spec=None if index else (
                         (np.dtype(np.int32), False, False),))
    return (keys, emit, cols, mask), seg, run


def _f64_groups(keys, use, x):
    """Per group, in key order: float64 sum, sum of magnitudes, count."""
    ks = np.unique(keys[use])
    pos = np.searchsorted(ks, keys[use])
    x = x[use].astype(np.float64)
    return (np.bincount(pos, x, len(ks)), np.bincount(pos, np.abs(x), len(ks)),
            np.bincount(pos, minlength=len(ks)))


@pytest.mark.parametrize("shape,carries,op,col", CASES,
                         ids=[f"{s}-{w}-{o.name}-{c}" for s, w, o, c in CASES])
def test_stream_path_matches_segment_path(shape, carries, op, col):
    (keys, emit, cols, mask), seg, run = _reduced(shape, carries)
    i = _pairs(shape, carries).index((op, col))
    groups = len(np.unique(keys[emit]))
    # the slots: group_valid, bit-equal, and rep (n past the group
    # count) or, where the index did not ride, the keys themselves
    np.testing.assert_array_equal(run[1], seg[1])
    assert run[1].sum() == groups and (seg[0][groups:] == len(keys)).all()
    if CARRIES[carries][1]:
        np.testing.assert_array_equal(run[0], seg[0])
    else:
        [(got_keys, key_validity)] = run[0]
        assert key_validity is None and got_keys.dtype == np.int32
        np.testing.assert_array_equal(got_keys[:groups],
                                      keys[seg[0][:groups]])
    (got, got_valid), (want, want_valid) = run[2][i], seg[2][i]
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(got_valid, want_valid)
    if not (op == Op.MEAN or (op == Op.SUM and col == "float32")):
        np.testing.assert_array_equal(got, want)   # empty slots too
        return
    # a float32 sum: within the configuration's bound of the float64 sum
    # (count_g * 2^-24 * sum|x|_g, any association of the group's own
    # values), for both paths; a mean adds its division's rounding
    use = emit & mask if col == "masked" else emit
    s64, sabs, cnt = _f64_groups(keys, use, cols[col])
    bound = cnt * 2.0 ** -24 * sabs
    live = got_valid[:groups]
    assert live.sum() == len(s64)
    if op == Op.MEAN:
        s64, bound = s64 / cnt, bound / cnt + 2.0 ** -23 * np.abs(s64 / cnt)
    for res in (got, want):
        err = np.abs(res[:groups][live].astype(np.float64) - s64)
        assert (err <= bound).all(), (err / np.maximum(bound, 1e-300)).max()
    np.testing.assert_array_equal(got[groups:], want[groups:])


def test_small_group_between_huge_neighbours_keeps_its_own_error():
    """A two-row group of ~1e-3 between groups of ~1e30 in ONE block: any
    shortcut through a prefix that runs over the neighbours (a difference
    of block prefix sums) loses it entirely; a running sum that restarts
    at the run start meets the bound, which is relative to the group's
    own sum of magnitudes."""
    x = np.concatenate([np.full(50, 1e30), [1.25e-3, 2.5e-3],
                        np.full(50, -3e30)]).astype(np.float32)
    keys = np.repeat(np.arange(3, dtype=np.int32), [50, 2, 50])
    state = G.presort_groups((jnp.asarray(keys),), jnp.ones(102, bool),
                             (jnp.asarray(x),), (None,))
    vs, vm, emit_s, iota_s, new_grp, _ng = state
    with small_blocks():
        _rep, valid, [(got, _v)] = G.sorted_segment_aggregate(
            new_grp, emit_s, iota_s, vs, vm, 4, (Op.SUM,), (0,), (True,),
            interpret=True)
    got = np.asarray(got)
    assert np.asarray(valid).tolist() == [True, True, True, False]
    s64, sabs, cnt = _f64_groups(keys, np.ones(102, bool), x)
    assert (np.abs(got[:3] - s64) <= cnt * 2.0 ** -24 * sabs).all(), got
    assert got[1] == np.float32(1.25e-3) + np.float32(2.5e-3)


def test_path_is_chosen_from_backend_and_widths(monkeypatch):
    i32, f32 = np.dtype("int32"), np.dtype("float32")
    sums = (Op.SUM, Op.SUM)
    # tier-1 runs on the CPU: the portable path, whatever the widths
    assert G.reduce_path([i32, f32], sums, 1000) == "segment"
    # on a TPU backend (here: its stand-in, the interpreter) 4-byte
    # accumulators stream ...
    assert G.reduce_path([i32, f32], sums, 1000, interpret=True) == "stream"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert G.reduce_path([i32, f32], sums, 1000) == "stream"
    assert G.reduce_path([i32], (Op.MIN,), 1000) == "stream"
    # ... and anything else does not: x64's COUNT and MEAN accumulators
    # (tier-1 has x64 on), 8-byte, narrow, unsigned and bool columns,
    # an empty table
    assert jax.config.jax_enable_x64
    for op in (Op.COUNT, Op.MEAN):
        assert G.reduce_path([i32], (op,), 1000) == "segment"
        with jax.enable_x64(False):
            assert G.reduce_path([i32], (op,), 1000) == "stream"
    for wide in ("int64", "float64", "int16", "uint32", "bool"):
        assert G.reduce_path([np.dtype(wide)], (Op.SUM,), 1000) == "segment"
    assert G.reduce_path([np.dtype("int64")], (Op.COUNT,), 1000) == "segment"
    assert G.reduce_path([i32], (Op.SUM,), 0) == "segment"


def _paths():
    snap = telemetry.metrics_snapshot()
    counted = {p: snap.get('cylon_groupby_reduce_path_total{path="%s"}' % p, 0)
               for p in ("stream", "segment")}
    counted["operands"] = snap.get("cylon_groupby_sort_operands_total", 0)
    counted["packed"] = snap.get("cylon_groupby_sort_packed_columns_total", 0)
    return counted


def test_every_reduce_step_is_counted_by_its_path(local_ctx, dist_ctx,
                                                  monkeypatch):
    # keys 5,000 apart: a range past DENSE_MAX_SLOTS, so the rows are
    # sorted (five keys side by side fill a dense table, which counts
    # `dense` and no sort operand: tests/test_groupby_dense.py)
    data = {"k": np.arange(64, dtype=np.int32) % 5 * 5000,
            "v": np.arange(64, dtype=np.int32)}
    # the sort's packing as a table of SORT_PACK_MIN_ROWS rows meets it
    monkeypatch.setattr(G, "SORT_PACK_MIN_ROWS", 0)
    before = _paths()
    out = ct.Table.from_pydict(local_ctx, data).groupby(0, [1], ["sum"])
    assert out.row_count == 5
    after = _paths()
    assert after["segment"] == before["segment"] + 1     # the CPU's path
    assert after["stream"] == before["stream"]
    # its sort carried the key's word, the value inside it (the key's
    # observed range takes 15 bits, the value's 6), and, for the CPU's
    # path, the index; no row mask, so no dead flag
    assert after["operands"] == before["operands"] + 2
    assert after["packed"] == before["packed"] + 1
    # distributed: the partial aggregation and the merge are a reduce
    # step each, and each one's sort carries the key's bits and validity
    # lane, the value and the index; the merge's also the dead flag (an
    # exchanged table always has a mask; the 64 rows spread without
    # padding have none); its value, the partial sum of a column without
    # nulls, has no validity mask to carry (PR 44: 4 + 6 before).
    # On the CPU's gather path the key rides as two lanes and is not
    # observed, and one integer column has nothing to share a word with:
    # no probe, nothing packed (tests/test_groupby_sort_pack.py has the
    # sharded tables that do pack)
    t = ct.Table.from_pydict(dist_ctx, data)
    assert t.groupby(0, [1], ["sum"]).row_count == 5
    assert _paths()["segment"] == after["segment"] + 2
    assert _paths()["operands"] == after["operands"] + 4 + 5
    assert _paths()["packed"] == after["packed"]
