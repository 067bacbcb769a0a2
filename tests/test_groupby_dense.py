"""The groupby over few groups, with no sort (PR 34): `ops/groupby.
group_path` decides from what the code can observe (static conditions,
then the OBSERVED key range), `tpu_kernels.groupby_dense_reduce` (here
under the Pallas interpreter, as every backend but a TPU runs it) fills a
table of `_pow2(range)` slots in one pass, and `data/table.groupby_local`
pays one fetch (`sync.groupby.keyrange`) where the sort path now pays two.

Tier-1 runs with x64 ON, under which COUNT and MEAN accumulate 8 bytes and
sort; the cases that need them run the chip's way, with
``jax.enable_x64(False)`` around the whole of the case.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cylon_tpu as ct
from cylon_tpu import telemetry
from cylon_tpu.data import table as table_mod
from cylon_tpu.ops import groupby as G
from cylon_tpu.ops import tpu_kernels as tk
from cylon_tpu.ops.groupby import AggregationOp as Op

U24 = 2.0 ** -24
I32, F32 = np.dtype(np.int32), np.dtype(np.float32)


def _col(data, name, valid=None):
    return ct.Column.from_numpy(np.asarray(data), name,
                                None if valid is None else np.asarray(valid))


def _counted():
    snap = telemetry.metrics_snapshot()
    out = {p: snap.get('cylon_groupby_reduce_path_total{path="%s"}' % p, 0)
           for p in ("dense", "stream", "segment")}
    out["slots"] = snap.get("cylon_groupby_dense_slots_total", 0)
    out["operands"] = snap.get("cylon_groupby_sort_operands_total", 0)
    out["packed"] = snap.get("cylon_groupby_sort_packed_columns_total", 0)
    for site in ("groupby.keyrange", "groupby.valuerange", "groupby.groups"):
        out[site] = snap.get('cylon_host_syncs_total{site="%s"}' % site, 0)
    out["probes"] = snap.get(
        'cylon_kernel_factory_builds_total{factory="_groupby_key_range_fn"}',
        0)
    return out


def _delta(before):
    after = _counted()
    return {k: after[k] - before[k] for k in after if after[k] != before[k]}


def _reference(key, kvalid, live, cols, ops):
    """{key or None: [one aggregate an op, or None for a null result]} in
    float64 / exact integers, Arrow's count semantics."""
    out = {}
    groups = {}
    for i in np.flatnonzero(live):
        k = None if kvalid is not None and not kvalid[i] \
            else getattr(key[i], "item", lambda: key[i])()
        groups.setdefault(k, []).append(i)
    for k, rows in groups.items():
        rows = np.array(rows)
        res = []
        for (x, valid), op in zip(cols, ops):
            ok = rows if valid is None else rows[valid[rows]]
            if op == "count":
                res.append(len(ok))
            elif len(ok) == 0:
                res.append(None)
            elif op == "sum":
                res.append(x[ok].astype(np.float64).sum() if x.dtype.kind
                           == "f" else int(x[ok].astype(np.int64).sum()))
            else:
                res.append(x[ok].astype(np.float64).mean())
        out[k] = res
    return out


def _check(out, want, ops, dtypes):
    """A dense result against the reference: the same groups once each,
    integer results exact, float results within 4 units of 2^-24 of
    sum|x| (a compensated sum's rounding and, for MEAN, the division's)."""
    mask = np.asarray(out.row_mask)
    cols = out._columns
    kdata = np.asarray(cols[0].data)[mask]
    kval = None if cols[0].validity is None \
        else np.asarray(cols[0].validity)[mask]
    if cols[0].dictionary is not None:
        kdata = np.asarray(cols[0].dictionary)[kdata]
    keys = [None if kval is not None and not kval[i]
            else getattr(kdata[i], "item", lambda: kdata[i])()
            for i in range(len(kdata))]
    assert sorted(keys, key=repr) == sorted(want, key=repr)
    for j, (op, dt) in enumerate(zip(ops, dtypes)):
        c = cols[1 + j]
        data = np.asarray(c.data)[mask]
        valid = np.ones(len(data), bool) if c.validity is None \
            else np.asarray(c.validity)[mask]
        for i, k in enumerate(keys):
            w = want[k][j]
            assert valid[i] == (w is not None), (k, op)
            if w is None:
                continue
            if op == "count" or (op == "sum" and dt.kind == "i"):
                assert data[i] == w, (k, op)
            else:
                assert abs(float(data[i]) - w) <= 4 * U24 * max(
                    abs(w), 1e-30) + 1e-30 or dt.kind == "f" and \
                    abs(float(data[i]) - w) <= 4 * U24 * want[k][-1], (k, op)


def _table(ctx, key, vals, key_valid=None, mask=None):
    cols = [_col(key, "k", key_valid)] + [
        _col(x, f"v{i}", valid) for i, (x, valid) in enumerate(vals)]
    return ct.Table(cols, ctx, None if mask is None else jnp.asarray(mask))


N = 5000


def _values(rng, nullable=False):
    a = rng.integers(-1000, 1000, N).astype(np.int32)
    b = (rng.normal(size=N) * 100).astype(np.float32)
    va = rng.random(N) < 0.8 if nullable else None
    return [(a, va), (b, None)]


KEYS = {
    "int32": lambda r: r.integers(1, 101, N).astype(np.int32),
    "negative": lambda r: r.integers(-70, -3, N).astype(np.int32),
    "far_from_0": lambda r: (2_000_000_000 + r.integers(0, 40, N)
                             ).astype(np.int32),
    "int8": lambda r: r.integers(-128, 128, N).astype(np.int8),
    "uint16": lambda r: r.integers(65100, 65536, N).astype(np.uint16),
    "uint32_high": lambda r: (np.uint32(4294967000) + r.integers(
        0, 200, N).astype(np.uint32)),
    "bool": lambda r: r.random(N) < 0.3,
    "dictionary": lambda r: np.array(["pear", "apple", "fig", "kiwi"],
                                     object)[r.integers(0, 4, N)],
    "one_group": lambda r: np.full(N, -7, np.int32),
    "two_far_groups": lambda r: np.where(r.random(N) < 0.5, 0, 499
                                         ).astype(np.int32),
}
OPS = ["sum", "sum", "count", "mean", "mean"]    # of v0, v1, v0, v0, v1


@pytest.mark.parametrize("shape", ["plain", "row_mask", "null_values",
                                   "null_key"])
@pytest.mark.parametrize("kind", list(KEYS))
def test_dense_groupby_matches_numpy(local_ctx, kind, shape):
    rng = np.random.default_rng(len(kind) * 7 + len(shape))
    key = KEYS[kind](rng)
    vals = _values(rng, nullable=shape == "null_values")
    mask = rng.random(N) < 0.6 if shape == "row_mask" else None
    kvalid = rng.random(N) < 0.9 if shape == "null_key" else None
    with jax.enable_x64(False):
        t = _table(local_ctx, key, vals, kvalid, mask)
        before = _counted()
        out = t.groupby(0, [1, 2, 1, 1, 2], OPS)
        moved = _delta(before)
        code = np.asarray(t._columns[0].data)    # dictionary codes
    live = np.ones(N, bool) if mask is None else mask
    seen = code[live if kvalid is None else live & kvalid].astype(np.int64)
    slots = table_mod._pow2(int(seen.max() - seen.min() + 1)
                            + (kvalid is not None))
    assert moved == {"dense": 1, "slots": slots, "groupby.keyrange": 1} or \
        moved == {"dense": 1, "slots": slots, "groupby.keyrange": 1,
                  "probes": 1}
    assert out.capacity == slots
    assert [c.data.dtype for c in out._columns] == [
        t._columns[0].data.dtype, I32, F32, I32, F32, F32]
    cols = [vals[0], vals[1], vals[0], vals[0], vals[1]]
    ref_key = key if kind != "dictionary" else np.asarray(key, object)
    want = _reference(ref_key, kvalid, live, cols, OPS)
    # the float column's sum|x| a group, for the tolerance
    for k, res in want.items():
        res.append(_reference(ref_key, kvalid, live,
                              [(np.abs(vals[1][0]), None)], ["sum"])[k][0])
    _check(out, want, OPS, [I32, F32, I32, I32, F32])


@pytest.mark.parametrize("key_range,path", [
    (G.DENSE_MAX_SLOTS, "dense"), (G.DENSE_MAX_SLOTS + 1, "sort")])
def test_both_sides_of_dense_max_slots(local_ctx, monkeypatch, key_range,
                                       path):
    """Range exactly DENSE_MAX_SLOTS: dense, ONE fetch, and nothing of
    the sort's packing (PR 35) is dispatched. One more: the sort path
    after the probe, which keeps the probe's range: the integer column
    rides in the key's spare bits (a third fetch, its range's; one
    operand fewer). Integer results are bit-equal to the plain sort's on
    the same table either way."""
    monkeypatch.setattr(G, "SORT_PACK_MIN_ROWS", 0)
    rng = np.random.default_rng(key_range)
    key = rng.integers(0, key_range, N).astype(np.int32) - 17
    key[:2] = (-17, key_range - 18)              # the range is exact
    a = rng.integers(-1000, 1000, N).astype(np.int32)
    b = rng.normal(size=N).astype(np.float32)
    t = _table(local_ctx, key, [(a, None), (b, None)])
    before = _counted()
    out = t.groupby(0, [1, 2], ["sum", "sum"])
    moved = _delta(before)
    moved.pop("probes", None)
    if path == "dense":
        assert moved == {"dense": 1, "slots": G.DENSE_MAX_SLOTS,
                         "groupby.keyrange": 1}
    else:       # tier-1's CPU: the segment reduce step, the index rides
        assert moved == {"segment": 1, "operands": 3, "packed": 1,
                         "groupby.keyrange": 1, "groupby.valuerange": 1,
                         "groupby.groups": 1}
    # steered to the sort with no probe: no key range and ONE integer
    # column, so no word to save and no value probe either
    monkeypatch.setattr(G, "group_path", lambda *a, **k: "sort")
    before = _counted()
    sort = t.groupby(0, [1, 2], ["sum", "sum"])
    assert _delta(before) == {"segment": 1, "operands": 4,
                              "groupby.groups": 1}
    got, want = out.compact(), sort.compact()
    order_g = np.argsort(got._columns[0].to_numpy(), kind="stable")
    order_w = np.argsort(want._columns[0].to_numpy(), kind="stable")
    for j in (0, 1):
        np.testing.assert_array_equal(got._columns[j].to_numpy()[order_g],
                                      want._columns[j].to_numpy()[order_w])
    np.testing.assert_allclose(got._columns[2].to_numpy()[order_g],
                               want._columns[2].to_numpy()[order_w],
                               rtol=1e-5, atol=1e-5)


def test_an_empty_table_is_one_empty_slot(local_ctx):
    t = _table(local_ctx, np.arange(64, dtype=np.int32),
               [(np.arange(64, dtype=np.int32), None)],
               mask=np.zeros(64, bool))
    out = t.groupby(0, [1], ["sum"])
    assert out.capacity == 1 and out.row_count == 0


PLANES = None   # a varbytes key or a word-plane column: no dtype to give
DECISIONS = [
    # key dtypes, nullable, value dtypes, ops, n, range -> path
    ([I32], [False], [I32, F32], [Op.SUM, Op.SUM], 1000, None, "dense"),
    ([I32], [False], [I32, F32], [Op.SUM, Op.SUM], 1000, 100, "dense"),
    ([np.dtype(np.int8)], [False], [I32], [Op.SUM], 1000, 256, "dense"),
    ([np.dtype(np.uint32)], [False], [I32], [Op.SUM], 1000, 7, "dense"),
    ([np.dtype(bool)], [False], [F32], [Op.SUM], 1000, 2, "dense"),
    ([I32], [True], [I32], [Op.SUM], 1000, G.DENSE_MAX_SLOTS - 1, "dense"),
    ([I32], [True], [I32], [Op.SUM], 1000, G.DENSE_MAX_SLOTS, "sort"),
    ([I32], [False], [I32], [Op.SUM], 1000, G.DENSE_MAX_SLOTS + 1, "sort"),
    ([F32], [False], [I32], [Op.SUM], 1000, None, "sort"),
    ([I32, I32], [False, False], [I32], [Op.SUM], 1000, None, "dense"),
    ([I32, I32], [False, True], [I32], [Op.SUM], 1000, [32, 31], "dense"),
    ([I32, I32], [False, True], [I32], [Op.SUM], 1000, [32, 32], "sort"),
    ([I32, F32], [False, False], [I32], [Op.SUM], 1000, None, "sort"),
    ([PLANES], [False], [I32], [Op.SUM], 1000, None, "sort"),
    ([np.dtype(np.int64)], [False], [I32], [Op.SUM], 1000, None, "sort"),
    ([I32], [False], [I32], [Op.MIN], 1000, None, "sort"),
    ([I32], [False], [I32, I32], [Op.SUM, Op.MAX], 1000, None, "sort"),
    ([I32], [False], [np.dtype(np.int16)], [Op.SUM], 1000, None, "sort"),
    ([I32], [False], [np.dtype(np.float64)], [Op.SUM], 1000, None, "sort"),
    ([I32], [False], [PLANES], [Op.SUM], 1000, None, "sort"),
    ([I32], [False], [PLANES], [Op.COUNT], 1000, None, "count"),
    ([I32], [False], [F32], [Op.MEAN], 1000, None, "count"),
    ([I32], [False], [I32], [Op.SUM], 0, None, "sort"),
    ([I32], [False], [I32], [Op.SUM], 1 << 30, None, "sort"),
]


@pytest.mark.parametrize("case", DECISIONS, ids=[str(i) for i in
                                                 range(len(DECISIONS))])
def test_group_path_cases(case):
    kd, kn, vd, ops, n, key_range, want = case
    if want == "count":     # 8-byte accumulators under x64: the sort
        assert G.group_path(kd, kn, vd, ops, n, key_range) == "sort"
        with jax.enable_x64(False):
            assert G.group_path(kd, kn, vd, ops, n, key_range) == "dense"
    else:
        assert G.group_path(kd, kn, vd, ops, n, key_range) == want


EXCLUDED = {
    "float_key": lambda r: ([_col(r.normal(size=64).astype(np.float32), "k"),
                             _col(np.arange(64, dtype=np.int32), "a")],
                            [0], ["sum"]),
    "two_keys_one_float": lambda r: (
        [_col(np.arange(64, dtype=np.int32) % 3, "k"),
         _col((np.arange(64) % 2).astype(np.float32), "k2"),
         _col(np.arange(64, dtype=np.int32), "a")], [0, 1], ["sum"]),
    "varbytes_key": lambda r: ([_col(np.array(
        [f"row{i % 300:03d}" for i in range(400)], object), "k"),
        _col(np.arange(400, dtype=np.int32), "a")], [0], ["sum"]),
    "int64_key": lambda r: ([_col(np.arange(64, dtype=np.int64) % 5, "k"),
                             _col(np.arange(64, dtype=np.int32), "a")],
                            [0], ["sum"]),
    "min": lambda r: ([_col(np.arange(64, dtype=np.int32) % 5, "k"),
                       _col(np.arange(64, dtype=np.int32), "a")],
                      [0], ["min"]),
    "count_under_x64": lambda r: ([_col(np.arange(64, dtype=np.int32) % 5,
                                        "k"),
                                   _col(np.arange(64, dtype=np.int32), "a")],
                                  [0], ["count"]),
}


@pytest.mark.parametrize("kind", list(EXCLUDED))
def test_a_table_the_static_conditions_exclude_dispatches_no_probe(
        local_ctx, kind):
    cols, by, ops = EXCLUDED[kind](np.random.default_rng(3))
    if kind == "varbytes_key":
        assert cols[0].is_varbytes
    t = ct.Table(cols, local_ctx)
    with telemetry.collect_phases() as cp:
        before = _counted()
        out = t.groupby(by, [len(cols) - 1], ops)
        moved = _delta(before)
    assert out.row_count >= 2
    assert "groupby.keyrange" not in moved and "probes" not in moved \
        and "dense" not in moved
    assert moved["groupby.groups"] == 1
    assert cp.count("sync.groupby.keyrange") == 0


def test_a_word_plane_column_is_refused_before_any_probe(local_ctx):
    with jax.enable_x64(False):
        t = ct.Table.from_pydict(local_ctx, {
            "k": np.arange(64, dtype=np.int64) % 5,
            "a": np.arange(64, dtype=np.int32)})
        assert t._columns[0].is_planes
        before = _counted()
        with pytest.raises(ct.CylonError):
            t.groupby(0, [1], ["sum"])
        assert _delta(before) == {}


def test_a_million_floats_in_one_group_need_the_compensation():
    """2^20 float32 rows in ONE group: the kernel's mean is within 8 units
    of 2^-24 of the float64 mean (the cell's limit; it reads under 2),
    where one float32 accumulator fed row after row is tens of units off."""
    n = 1 << 20
    rng = np.random.default_rng(11)
    x = np.round(rng.uniform(0, 100, n), 6).astype(np.float32)
    exact = x.astype(np.float64).mean()
    count, (total,) = tk.groupby_dense_reduce(
        jnp.zeros(n, jnp.int32), np.int32(0), np.int32(1), [jnp.asarray(x)],
        ["float"], 1, interpret=True)
    assert int(count[0]) == n
    mean = np.float32(np.asarray(total)[0]) / np.float32(n)
    assert abs(float(mean) - exact) <= 2 * U24 * exact
    naive = np.cumsum(x, dtype=np.float32)[-1] / np.float32(n)
    assert abs(float(naive) - exact) > 8 * U24 * exact


def test_kernel_sweeps_only_the_live_slots_and_keeps_dead_rows_out():
    """Rows whose key lies outside [lo, lo + live_slots) reach no slot; a
    ragged last block's padding reaches none; int sums wrap as int32."""
    n = 3 * 1024 * 128 + 77              # ragged: not a multiple of 128
    rng = np.random.default_rng(2)
    key = rng.integers(-3, 30, n).astype(np.int32)
    big = rng.integers(2 ** 30, 2 ** 31 - 1, n).astype(np.int32)
    f = rng.normal(size=n).astype(np.float32)
    count, (si, sf, sif) = tk.groupby_dense_reduce(
        jnp.asarray(key), np.int32(5), np.int32(20),
        [jnp.asarray(big), jnp.asarray(f), jnp.asarray(big)],
        ["int", "float", "float"], 32, interpret=True)
    inside = (key >= 5) & (key < 25)
    rel = key[inside] - 5
    np.testing.assert_array_equal(np.asarray(count),
                                  np.bincount(rel, minlength=32))
    wrapped = np.zeros(32, np.int64)
    np.add.at(wrapped, rel, big[inside].astype(np.int64))
    np.testing.assert_array_equal(
        np.asarray(si), wrapped.astype(np.uint64).astype(np.uint32)
        .view(np.int32))
    for got, src in ((sf, f), (sif, big)):
        want = np.bincount(rel, weights=src[inside].astype(np.float64),
                           minlength=32)
        mag = np.bincount(rel, weights=np.abs(src[inside]).astype(
            np.float64), minlength=32)
        assert (np.abs(np.asarray(got) - want) <= 2 * U24 * mag).all()
