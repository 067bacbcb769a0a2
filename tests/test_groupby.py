"""Group-by + scalar aggregate tests.

Parity model: cpp/test/groupby_test.cpp, aggregate_test.cpp,
python/test/test_table_compute (world=1).
"""
import functools

import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

import cylon_tpu as ct
from cylon_tpu import dtypes, telemetry
from cylon_tpu.ops import groupby as G
from cylon_tpu.ops import order


def df(seed=0, n=80, keys=9):
    rng = np.random.default_rng(seed)
    return pd.DataFrame({"k": rng.integers(0, keys, n).astype(np.int64),
                         "a": rng.random(n),
                         "b": rng.integers(-100, 100, n).astype(np.int64)})


@pytest.mark.parametrize("op,pd_op", [("sum", "sum"), ("min", "min"),
                                      ("max", "max"), ("count", "count"),
                                      ("mean", "mean")])
def test_groupby_single_agg(local_ctx, op, pd_op):
    d = df()
    t = ct.Table.from_pandas(local_ctx, d)
    got = t.groupby(0, [1], [op]).to_pandas().sort_values("k").reset_index(drop=True)
    exp = d.groupby("k")["a"].agg(pd_op).reset_index()
    np.testing.assert_array_equal(got["k"].values, exp["k"].values)
    np.testing.assert_allclose(got["a"].values.astype(float),
                               exp["a"].values.astype(float), rtol=1e-9)


def test_groupby_multi_agg(local_ctx):
    d = df(3)
    t = ct.Table.from_pandas(local_ctx, d)
    got = t.groupby(0, [1, 2], ["sum", "max"]).to_pandas() \
        .sort_values("k").reset_index(drop=True)
    exp = d.groupby("k").agg(a=("a", "sum"), b=("b", "max")).reset_index()
    np.testing.assert_allclose(got["a"].values, exp["a"].values)
    np.testing.assert_array_equal(got["b"].values, exp["b"].values)


def test_groupby_string_keys(local_ctx):
    d = pd.DataFrame({"k": ["x", "y", "x", "z", "y", "x"],
                      "v": [1, 2, 3, 4, 5, 6]})
    t = ct.Table.from_pandas(local_ctx, d)
    got = t.groupby(0, [1], ["sum"]).to_pandas().sort_values("k") \
        .reset_index(drop=True)
    exp = d.groupby("k")["v"].sum().reset_index()
    assert list(got["k"]) == list(exp["k"])
    np.testing.assert_array_equal(got["v"].values, exp["v"].values)


def test_groupby_enum_ops(local_ctx):
    d = df(4)
    t = ct.Table.from_pandas(local_ctx, d)
    got = t.groupby(0, [2], [ct.AggregationOp.MIN])
    exp = d.groupby("k")["b"].min()
    assert got.row_count == len(exp)


def _col(arr, name, validity=None):
    """A column as it is: a NaN stays a value (from_numpy reads a host
    NaN as a null), a string array is dictionary-encoded."""
    arr = np.asarray(arr)
    if arr.dtype.kind in "UO":
        return ct.Column.from_numpy(arr, name, validity)
    return ct.Column(jnp.asarray(arr), dtypes.from_np_dtype(arr.dtype),
                     None if validity is None else jnp.asarray(validity),
                     None, name)


N_KEYED = 400


def _key_columns(kind):
    """(key columns, key lanes) of a named kind of key over N_KEYED
    rows, a dozen-odd groups each."""
    rng = np.random.default_rng(len(kind))
    pick = lambda vals, dt: np.asarray(vals, dt)[
        rng.integers(0, len(vals), N_KEYED)]
    i32 = pick([-2 ** 31, -7, -1, 0, 1, 5, 99, 2 ** 31 - 1], np.int32)
    f32 = pick([-np.inf, -1.5, -0.0, 0.0, 1e-30, 2.5, np.inf, np.nan],
               np.float32)
    some = rng.random(N_KEYED) < 0.8
    made = {
        "int32": ([_col(i32, "k")], 1),
        "int32+row_mask": ([_col(i32, "k")], 1),
        "uint32": ([_col(pick([0, 3, 2 ** 31, 2 ** 32 - 1], np.uint32),
                         "k")], 1),
        "int16": ([_col(pick([-2 ** 15, -3, 0, 2 ** 15 - 1], np.int16),
                        "k")], 1),
        "float32": ([_col(f32, "k")], 1),
        "bool": ([_col(rng.random(N_KEYED) < 0.5, "k")], 1),
        "dictionary": ([_col(pick(["pear", "apple", "fig", "kiwi"], object),
                             "k")], 1),
        "nullable": ([_col(i32, "k", some)], 2),     # + its validity lane
        "two_columns": ([_col(i32, "k"), _col(f32, "k2", some)], 3),
        # no way back from the lanes to the key: the index rides
        "varbytes": ([_col(np.array([f"row{i % 300:03d}" for i in
                                     range(N_KEYED)], object), "k")], None),
        "int64": ([_col(i32.astype(np.int64) * 3, "k")], 1),
    }
    return made[kind]


KEY_KINDS = ["int32", "int32+row_mask", "uint32", "int16", "float32", "bool",
             "dictionary", "nullable", "two_columns", "varbytes", "int64"]


@pytest.mark.parametrize("kind", KEY_KINDS)
def test_groupby_on_the_stream_path_reads_its_keys_off_the_sort(
        local_ctx, monkeypatch, kind):
    """groupby_local as a TPU backend runs it (the stream path, here
    under the Pallas interpreter) against the same call as the CPU runs
    it (``segment_*``, the index rides, the keys gathered): same groups
    in the same order, equal keys, validity and integer sums; and the
    sort's operands are counted as the host sees them."""
    keys, lanes = _key_columns(kind)
    # the SORT path is what this test is about: a bool or a dictionary
    # key's few values would go to the dense table (tests/
    # test_groupby_dense.py) before any sort
    monkeypatch.setattr(G, "group_path", lambda *a, **k: "sort")
    # no probe, so no key range: with ONE integer value column beside a
    # float there is no word to save, and the sort's packing (PR 35),
    # looked for here as for a table of SORT_PACK_MIN_ROWS rows, packs
    # nothing (tests/test_groupby_sort_pack.py has the cases that do)
    monkeypatch.setattr(G, "SORT_PACK_MIN_ROWS", 0)
    rng = np.random.default_rng(7)
    cols = keys + [_col(rng.integers(-50, 50, N_KEYED).astype(np.int32), "a"),
                   _col(rng.normal(size=N_KEYED).astype(np.float32), "b")]
    mask = jnp.asarray(rng.random(N_KEYED) < 0.7) \
        if kind.endswith("row_mask") else None
    table = ct.Table(cols, local_ctx, mask)
    by, vals = list(range(len(keys))), [len(keys), len(keys) + 1]
    rides_index = kind in ("varbytes", "int64")
    assert keys[0].is_varbytes == (kind == "varbytes")
    assert keys[0].is_string == (kind in ("dictionary", "varbytes"))

    def run():
        name = "cylon_groupby_sort_operands_total"
        before = telemetry.metrics_snapshot().get(name, 0)
        out = table.groupby(by, vals, ["sum", "sum"])
        return out, telemetry.metrics_snapshot()[name] - before

    want, carried = run()
    if lanes is not None:   # the CPU's path: the index always rides
        assert carried == (mask is not None) + lanes + 2 + 1
    real_path, real_agg = G.reduce_path, G.sorted_segment_aggregate
    monkeypatch.setattr(G, "reduce_path", lambda dts, ops, n, interpret=False:
                        real_path(dts, ops, n, True))
    monkeypatch.setattr(G, "sorted_segment_aggregate_jit",
                        functools.partial(real_agg, interpret=True))
    got, carried_on_tpu = run()
    assert carried_on_tpu == carried - (not rides_index)
    assert got.row_count == want.row_count >= 2
    live = np.asarray(want.row_mask)
    np.testing.assert_array_equal(np.asarray(got.row_mask), live)
    for g, w in zip(got._columns, want._columns):
        assert (g.name, g.dtype, g.data.dtype, g.is_varbytes) == \
            (w.name, w.dtype, w.data.dtype, w.is_varbytes)
        # no validity at all where the gathered one holds no null
        ok = live if w.validity is None else np.asarray(w.validity) & live
        np.testing.assert_array_equal(
            live if g.validity is None else np.asarray(g.validity) & live,
            ok)
    got, want = got.compact(), want.compact()
    for g, w in zip(got._columns, want._columns):
        gv, wv = g.to_numpy(), w.to_numpy()
        if w.name == "b":   # float sums differ by association only
            np.testing.assert_allclose(gv, wv, rtol=1e-4, atol=1e-4)
        else:               # NaN == NaN, -0.0 == +0.0, None == None
            np.testing.assert_array_equal(gv, wv)
    if kind in ("float32", "two_columns"):
        k = got._columns[len(keys) - 1].to_numpy()
        assert not np.signbit(k[k == 0]).any()   # -0.0 comes back as +0.0
        assert np.isnan(np.asarray(got._columns[len(keys) - 1].data)).any()


ORDERABLE = ["bool", "uint8", "uint16", "uint32", "int8", "int16", "int32",
             "float16", "float32", "uint64", "int64", "float64"]


@pytest.mark.parametrize("dtype", ORDERABLE)
def test_ordered_bits_round_trip(dtype):
    """inverse(ordered_bits_raw(x)) == x bit for bit over each dtype's
    edges (but -0.0, which the ordered bits fold into +0.0), and the bits
    order as the values do."""
    dt = np.dtype(dtype)
    if dt.kind == "b":
        x = np.array([False, True, True, False])
    elif dt.kind in "iu":
        info = np.iinfo(dt)
        x = np.array([info.min, info.min + 1, info.min // 2, 0, 1, 7,
                      info.max - 1, info.max], dt)
    else:
        info = np.finfo(dt)
        x = np.array([-np.inf, info.min, -1.5, -info.tiny, -0.0, 0.0,
                      info.tiny, 2.5, info.max,
                      np.inf, np.nan], dt)
        x = np.concatenate([x, -x[-1:]])       # a NaN with its sign set
    bits = order.ordered_bits_raw(jnp.asarray(x))
    back = np.asarray(order.from_ordered_bits_raw(bits, dt))
    assert back.dtype == dt and bits.dtype.itemsize == dt.itemsize
    want = np.where(x == 0, np.zeros((), dt), x) if dt.kind == "f" else x
    np.testing.assert_array_equal(back.view(f"u{dt.itemsize}"),
                                  want.view(f"u{dt.itemsize}"))
    finite = x[x == x]
    np.testing.assert_array_equal(
        np.argsort(np.asarray(bits)[x == x], kind="stable"),
        np.argsort(finite, kind="stable"))
    # dictionary codes are their own bits
    codes = jnp.asarray(np.array([3, 0, 2], np.int32))
    np.testing.assert_array_equal(np.asarray(order.from_ordered_bits_raw(
        order.ordered_bits_raw(codes, is_string=True), np.int32, True)),
        np.asarray(codes))


def test_groupby_null_values_skipped(local_ctx):
    d = pd.DataFrame({"k": [1, 1, 2, 2], "v": [1.0, np.nan, np.nan, np.nan]})
    t = ct.Table.from_pandas(local_ctx, d)
    got = t.groupby(0, [1], ["count"]).to_pandas().sort_values("k")
    np.testing.assert_array_equal(got["v"].values, [1, 0])


@pytest.mark.parametrize("op", ["sum", "count", "min", "max", "mean"])
def test_scalar_aggregates(local_ctx, op):
    d = df(5)
    t = ct.Table.from_pandas(local_ctx, d)
    got = getattr(t, op)("a").to_pandas().iloc[0, 0]
    exp = getattr(d["a"], op)()
    np.testing.assert_allclose(float(got), float(exp), rtol=1e-9)


def test_aggregate_with_nulls(local_ctx):
    d = pd.DataFrame({"a": [1.0, np.nan, 3.0]})
    t = ct.Table.from_pandas(local_ctx, d)
    assert float(t.sum("a").to_pandas().iloc[0, 0]) == 4.0
    assert int(t.count("a").to_pandas().iloc[0, 0]) == 2
    assert float(t.min("a").to_pandas().iloc[0, 0]) == 1.0


def test_distributed_groupby_preagg_equivalence(dist_ctx):
    """Pre-aggregated (partials shuffled) vs direct (rows shuffled)
    distributed groupby agree, including MEAN (sum,count pairs) and
    COUNT (partials SUMmed — the reference's bug, fixed here)."""
    from cylon_tpu.parallel import dist_ops

    rng = np.random.default_rng(8)
    n = 4000
    d = pd.DataFrame({
        "k": rng.integers(0, 57, n).astype(np.int64),
        "v": rng.normal(size=n).astype(np.float32),
        "w": rng.integers(-40, 40, n).astype(np.int32),
    })
    d.loc[rng.random(n) < 0.15, "v"] = np.nan
    t = ct.Table.from_pandas(dist_ctx, d)
    ops = [ct.AggregationOp.SUM, ct.AggregationOp.COUNT,
           ct.AggregationOp.MEAN, ct.AggregationOp.MIN,
           ct.AggregationOp.MAX]
    cols = [1, 1, 1, 2, 2]
    a = dist_ops.distributed_groupby(t, 0, cols, ops,
                                     pre_aggregate=True).to_pandas()
    b = dist_ops.distributed_groupby(t, 0, cols, ops,
                                     pre_aggregate=False).to_pandas()
    a.columns = b.columns = range(a.shape[1])
    a = a.sort_values(0).reset_index(drop=True)
    b = b.sort_values(0).reset_index(drop=True)
    pd.testing.assert_frame_equal(a, b, check_dtype=False, atol=1e-4)
    # vs pandas ground truth
    exp = d.groupby("k").agg(s=("v", "sum"), c=("v", "count"),
                             m=("v", "mean"), lo=("w", "min"),
                             hi=("w", "max")).reset_index()
    exp = exp.sort_values("k").reset_index(drop=True)
    assert a.shape[0] == exp.shape[0]
    np.testing.assert_allclose(a[1].to_numpy(),
                               exp["s"].to_numpy(), atol=1e-3)
    np.testing.assert_array_equal(a[2].to_numpy(), exp["c"].to_numpy())
    np.testing.assert_allclose(a[3].to_numpy(),
                               exp["m"].to_numpy(), atol=1e-4)


def test_distributed_groupby_preagg_reduces_shuffle_rows(dist_ctx):
    """The exchanged row count drops ~rows/groups-fold: assert via the
    count matrix the shuffle computes (low group cardinality)."""
    from unittest import mock

    from cylon_tpu.parallel import dist_ops, shuffle as _shuffle

    rng = np.random.default_rng(9)
    n = 8000
    t = ct.Table.from_pandas(dist_ctx, pd.DataFrame({
        "k": rng.integers(0, 16, n).astype(np.int32),
        "v": rng.integers(0, 100, n).astype(np.int32)}))
    seen = []
    orig = _shuffle.exchange

    def spy(payload, targets, emit, ctx, max_block=None, counts=None):
        out = orig(payload, targets, emit, ctx, max_block, counts=counts)
        import jax
        seen.append(int(np.asarray(jax.device_get(emit)).sum()))
        return out

    with mock.patch.object(dist_ops, "exchange", side_effect=spy):
        dist_ops.distributed_groupby(
            t, 0, [1], [ct.AggregationOp.SUM], pre_aggregate=True)
    # the (single) row exchange moved only partial rows: <= groups*world
    assert seen, "exchange never called"
    assert max(seen) <= 16 * dist_ctx.get_world_size()
