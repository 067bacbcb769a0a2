"""64-bit columns on a backend without x64: held exactly, as two 32-bit
word planes, from the host and back, and through the planned inner join.

The chip runs with x64 off and has no 64-bit type; this suite runs with it
on (conftest.py), so the plane-held path is forced here with
``jax.enable_x64(False)`` around the whole of a case, and the same case
is run again with x64 on through the native arrays. Before this path a
table of int64 / float64 columns built with x64 off was narrowed to int32
/ float32 under its 64-bit logical type, and a join on it matched keys
that differ in the high word: nothing said so.

The join's answer is compared with a plain nested-loop reference over the
host arrays, as multisets of rows of 64-bit patterns. The stream path
runs on the Pallas interpreter (the same kernels compile to Mosaic on a
TPU; ~10 s a join whatever the rows), the portable XLA plan is what a CPU
takes by itself.
"""
from collections import Counter

import jax
import numpy as np
import pytest

import cylon_tpu as ct
from cylon_tpu import dtypes, plan, telemetry
from cylon_tpu.ops import join as _join
from cylon_tpu.parallel import dist_ops, shard
from cylon_tpu.status import CylonError

U64 = np.uint64


def _bits(a):
    return np.ascontiguousarray(a).view(U64)


# keys that only a comparison of all 64 bits tells apart, negatives, the
# ends of the range
_EDGE_KEYS = np.array([
    (5 << 32) | 7, (6 << 32) | 7,        # equal low words, other high
    (5 << 32) | 8,                       # equal high words, other low
    7, -7, -1, 0, 1,
    -(1 << 40) + 3, (1 << 40) + 3, (1 << 40) + 5,
    np.iinfo(np.int64).min, np.iinfo(np.int64).max,
    -(1 << 32), (1 << 32), (1 << 31), -(1 << 31),
], dtype=np.int64)

# payloads whose 64 bits a float32 (or a careless float op) would change
_EDGE_VALUES = np.array([
    0x7FF8000000000123,                  # a quiet NaN with payload bits
    0xFFF0000000000001,                  # a signalling NaN, sign set
    0x8000000000000000,                  # -0.0
    0x0000000000000001,                  # the smallest subnormal
    0x000FFFFFFFFFFFFF,                  # the largest subnormal
    0x7FF0000000000000,                  # +inf
], dtype=U64).view(np.float64)


def _case(n_left, n_right, seed):
    """Seeded tables: keys over the whole int64 range plus the edge keys,
    duplicates on both sides; float64 payloads with the edge values."""
    rng = np.random.default_rng(seed)
    pool = np.concatenate([
        _EDGE_KEYS,
        rng.integers(np.iinfo(np.int64).min, np.iinfo(np.int64).max,
                     max(n_left, n_right) // 3 + 1, dtype=np.int64)])
    lk, rk = pool[rng.integers(0, len(pool), n_left)], \
        pool[rng.integers(0, len(pool), n_right)]
    lv, rw = rng.standard_normal(n_left), rng.standard_normal(n_right)
    lv[:len(_EDGE_VALUES)] = _EDGE_VALUES
    rw[-len(_EDGE_VALUES):] = _EDGE_VALUES
    lv[len(_EDGE_VALUES)] = 1e300
    return lk, lv, rk, rw


def _table(ctx, cols):
    """A table of columns without nulls (a NaN stays a value: its payload
    bits are compared; `from_numpy` alone reads a NaN as a null)."""
    return ct.Table([ct.Column.from_numpy(a, name,
                                          validity=np.ones(len(a), bool))
                     for name, a in cols.items()], ctx)


def _reference(lk, lv, rk, rw):
    by_key = {}
    for j, k in enumerate(rk.tolist()):
        by_key.setdefault(k, []).append(j)
    lvb, rwb = _bits(lv).tolist(), _bits(rw).tolist()
    ukey = _bits(lk).tolist()
    rows = Counter()
    for i, k in enumerate(lk.tolist()):
        for j in by_key.get(k, ()):
            rows[(ukey[i], lvb[i], ukey[i], rwb[j])] += 1
    return rows


def _rows(table):
    cols = [_bits(a).tolist() for a in table.to_pydict().values()]
    return Counter(zip(*cols))


def _planned_join(ctx, lk, lv, rk, rw):
    left = _table(ctx, {"k": lk, "v": lv})
    right = _table(ctx, {"k": rk, "w": rw})
    out = plan.scan(left).join(plan.scan(right), "inner",
                               on="k").execute()
    return left, out


# sizes that cross a block edge of the stream kernels (block_rows 8: 1024
# rows a block) on the sorted stream, on each side and on the output
SIZES = [(90, 70), (700, 500), (1500, 1100)]


@pytest.mark.parametrize("n_left,n_right", SIZES)
@pytest.mark.parametrize("path", ["xla", "stream"])
def test_planned_inner_join_of_word_planes(local_ctx, monkeypatch, path,
                                           n_left, n_right):
    lk, lv, rk, rw = _case(n_left, n_right, seed=n_left)
    want = _reference(lk, lv, rk, rw)
    assert sum(want.values()) > max(n_left, n_right)   # runs multiply
    if path == "stream":
        monkeypatch.setattr(_join, "STREAM_PLAN", True)
    snap = telemetry.metrics_snapshot
    before = {k: snap().get(k, 0) for k in (
        "cylon_join_sort_operands_total", "cylon_join_key_lanes_total",
        "cylon_join_gathered_columns_total")}
    with jax.enable_x64(False):
        left, out = _planned_join(local_ctx, lk, lv, rk, rw)
        assert all(c.is_planes and c.data.dtype == np.uint32
                   and c.data.shape == (2, n_left) for c in left._columns)
        assert all(c.is_planes for c in out._columns)
        got = _rows(out)
    grew = {k: snap()[k] - v for k, v in before.items()}
    assert [c.dtype for c in out._columns] == [
        dtypes.Int64(), dtypes.Double(), dtypes.Int64(), dtypes.Double()]
    assert out.row_mask is None and out.row_count == sum(want.values())
    assert got == want
    # the stream path: (hi, lo, tag) + the payload's two planes, no gather;
    # the XLA plan: (hi, lo, tag), every column gathered by index
    assert grew == {
        "cylon_join_sort_operands_total": 5 if path == "stream" else 3,
        "cylon_join_key_lanes_total": 2,
        "cylon_join_gathered_columns_total": 0 if path == "stream" else 4}


@pytest.mark.parametrize("n_left,n_right", SIZES)
def test_planned_inner_join_native_x64(local_ctx, n_left, n_right):
    """The same cases through the native 64-bit arrays: the same answer."""
    lk, lv, rk, rw = _case(n_left, n_right, seed=n_left)
    left, out = _planned_join(local_ctx, lk, lv, rk, rw)
    assert not any(c.is_planes for c in left._columns + out._columns)
    assert left._columns[0].data.dtype == np.int64
    assert _rows(out) == _reference(lk, lv, rk, rw)


@pytest.mark.parametrize("how", ["left", "right"])
def test_outer_side_joins_of_word_planes(local_ctx, how):
    """LEFT / RIGHT work on the portable plan: a row without a match
    carries nulls on the other side."""
    lk, lv, rk, rw = _case(90, 70, seed=3)
    with jax.enable_x64(False):
        got = _table(local_ctx, {"k": lk, "v": lv}).join(
            _table(local_ctx, {"k": rk, "w": rw}), how, on=["k"]).to_pandas()
    import pandas as pd

    want = pd.DataFrame({"k": lk, "v": lv}).merge(
        pd.DataFrame({"k": rk, "w": rw}), how=how, on="k")
    assert len(got) == len(want)
    side = "rt-3" if how == "left" else "lt-1"
    other = "w" if how == "left" else "v"
    assert got[side].isna().sum() == want[other].isna().sum() > 0
    kept = "lt-0" if how == "left" else "rt-2"
    assert sorted(got[kept].astype(np.int64)) == sorted(want["k"])


HOST_ARRAYS = {
    "int64": np.array([(1 << 40) + 5, -3, 7, np.iinfo(np.int64).min]),
    "uint64": np.array([(1 << 63) + 9, 3, (1 << 32), 0], dtype=U64),
    "float64": np.array([0.1, 1e300, -0.0, 5e-324]),
    "datetime64": np.array(["2026-09-28T17:05:04.123456789", "1969-01-01",
                            "2262-01-01", "1970-01-01"], "datetime64[ns]"),
}


@pytest.mark.parametrize("kind", sorted(HOST_ARRAYS))
def test_from_host_never_narrows(local_ctx, kind):
    arr = HOST_ARRAYS[kind]
    with jax.enable_x64(False):
        col = ct.Column.from_numpy(arr, "c")
        table = ct.Table.from_pydict(local_ctx, {"c": arr})
        assert col.is_planes and len(col) == len(arr)
        assert col.data.dtype == np.uint32 and col.data.shape == (2, 4)
        back = col.to_numpy()
        assert back.dtype == arr.dtype and (_bits(back) == _bits(arr)).all()
        assert (_bits(table.to_pydict()["c"]) == _bits(arr)).all()
        assert (_bits(table.to_pandas()["c"].to_numpy())
                == _bits(arr)).all()
        assert (_bits(col.to_pyarrow().to_numpy()) == _bits(arr)).all()
        # rows are the last axis: the gathers and slices of the exporters
        assert (_bits(col.take(np.array([3, 0])).to_numpy())
                == _bits(arr[[3, 0]])).all()
        assert (_bits(col.slice(1, 3).to_numpy()) == _bits(arr[1:3])).all()
    native = ct.Column.from_numpy(arr, "c")    # x64 on: as before
    assert not native.is_planes and native.data.dtype.itemsize == 8


CSV_LAYOUTS = {
    # the native writer alone, beside a narrow column, after a filter
    # (a row mask: compacted first), and pandas' writer (a string beside)
    "alone": lambda arr: ({"c": arr}, None),
    "beside_int32": lambda arr: (
        {"n": np.arange(len(arr), dtype=np.int32), "c": arr}, None),
    "masked": lambda arr: ({"c": arr, "d": arr[::-1].copy()},
                           np.array([True, False, True, True])),
    "beside_string": lambda arr: (
        {"s": np.array(["a", "b", "c", "d"], dtype=object), "c": arr}, None),
}


@pytest.mark.parametrize("layout", sorted(CSV_LAYOUTS))
@pytest.mark.parametrize("kind", ["float64", "int64", "uint64"])
def test_csv_round_trip_of_word_planes(local_ctx, tmp_path, kind, layout):
    """`to_csv` writes one 64-bit value a row, by whichever writer: the
    native one was handed the raw ``uint32[2, n]`` planes and wrote two
    rows of high words, or n of them beside a narrow column, silently."""
    data, keep = CSV_LAYOUTS[layout](HOST_ARRAYS[kind])
    path = str(tmp_path / "t.csv")
    with jax.enable_x64(False):
        t = ct.Table.from_pydict(local_ctx, data)
        assert t._columns[-1].is_planes
        if keep is not None:
            t = t.filter_mask(keep)
        t.to_csv(path)
        back = ct.read_csv(local_ctx, path).to_pydict()
    keep = slice(None) if keep is None else keep
    assert list(back) == list(data)
    header, *lines = open(path).read().split()
    cells = dict(zip(header.split(","), zip(*(ln.split(",") for ln in lines))))
    for name, arr in data.items():
        if arr.dtype.kind not in "iuf" or arr.dtype.itemsize != 8:
            continue
        parse = float if arr.dtype.kind == "f" else int
        wrote = np.array([parse(x) for x in cells[name]], arr.dtype)
        assert (_bits(wrote) == _bits(arr[keep])).all(), (name, cells[name])
        if kind != "uint64":   # arrow's reader takes 2^63 + 9 for a double
            got = back[name].astype(arr.dtype)
            assert (_bits(got) == _bits(arr[keep])).all(), (name, got)
    # x64 on, the native arrays: the same file
    native = str(tmp_path / "native.csv")
    t = ct.Table.from_pydict(local_ctx, data)
    (t if layout != "masked" else t.filter_mask(keep)).to_csv(native)
    assert open(native).read() == open(path).read()


def test_process_local_export_of_word_planes(local_ctx):
    arr = HOST_ARRAYS["int64"]
    with jax.enable_x64(False):
        t = ct.Table.from_pydict(local_ctx, {
            "c": arr, "n": np.arange(4, dtype=np.int32)})
        got = t.filter_mask(np.array([True, True, False, True])
                            ).to_pydict_local()
    assert got["c"].dtype == np.int64
    assert got["c"].tolist() == arr[[0, 1, 3]].tolist()
    assert got["n"].tolist() == [0, 1, 3]


def test_dense_result_compiles_once_a_distinct_row_count(local_ctx):
    """A join result that holds word planes is cut to its live prefix
    (`table._join_prefix_program`, static ``n``): one small program a
    DISTINCT result row count, none for a count seen before. PERF.md
    section 7 carries what that costs on the chip and why it is there."""
    from cylon_tpu.data import table as table_mod

    def joined(n_right):
        k = np.arange(64, dtype=np.int64) << 33
        left = ct.Table.from_pydict(local_ctx, {"k": k, "v": k * 0.5})
        right = ct.Table.from_pydict(local_ctx, {"k": k[:n_right]})
        out = left.join(right, "inner", on=["k"])
        assert out.row_mask is None and out.capacity == n_right
        return out

    size = table_mod._join_prefix_program._cache_size
    with jax.enable_x64(False):
        joined(40)
        first = size()
        joined(24)
        joined(40)
        joined(24)
    assert size() - first == 1


REFUSED = {
    "distribute_by_key": lambda t, u: shard.distribute_by_key(
        t, t.context, ["k"]),
    "hash_partition": lambda t, u: dist_ops.hash_partition(t, ["k"], 2),
    "groupby": lambda t, u: t.groupby(0, [1], ["sum"]),
    "sort": lambda t, u: t.sort(0),
    "union": lambda t, u: t.union(u),
    "merge": lambda t, u: t.merge(u),
    "sum": lambda t, u: t.sum(1),
    "full outer join": lambda t, u: t.join(u, "outer", on=["k"]),
}


@pytest.mark.parametrize("operator", sorted(REFUSED))
def test_operator_that_cannot_take_planes_says_so(local_ctx, operator):
    """Until its own PR an operator raises, naming the column and itself:
    it does not narrow and it does not answer."""
    with jax.enable_x64(False):
        t = ct.Table.from_pydict(local_ctx, {
            "k": np.array([3, 1, 2], np.int64), "v": np.array([.5, 1., 2.])})
        with pytest.raises(CylonError) as err:
            REFUSED[operator](t, t)
    said = str(err.value)
    assert f"] {operator}: column" in said and "word planes" in said
    assert "'k'" in said or "'v'" in said


def test_distributing_planes_says_so(dist_ctx):
    with jax.enable_x64(False):
        t = ct.Table.from_pydict(dist_ctx, {
            "k": np.arange(8, dtype=np.int64), "v": np.arange(8.0)})
        with pytest.raises(CylonError, match="distribute over 4 chips: "
                                             "column 'k' is INT64"):
            t.distributed_join(t, "inner", on=["k"])


def test_plane_keys_of_unlike_types_are_not_promoted(local_ctx):
    with jax.enable_x64(False):
        a = ct.Table.from_pydict(local_ctx, {"k": np.arange(4, dtype=np.int64)})
        b = ct.Table.from_pydict(local_ctx, {"k": np.arange(4, dtype=np.int32)})
        with pytest.raises(CylonError, match="not promoted"):
            a.join(b, "inner", on=["k"])
