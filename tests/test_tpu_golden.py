"""Real-TPU correctness tests (VERDICT r03 #7): the golden relational
ops run with COMPILED (non-interpreted) Pallas kernels on the attached
chip — closing the interpreter-vs-Mosaic semantics gap the CPU matrix
leaves open (tests/conftest.py pins JAX_PLATFORMS=cpu and runs kernels
under the Pallas interpreter).

Run: CYLON_TPU_TESTS=1 python -m pytest tests/test_tpu_golden.py -m tpu
(scripts/run_tpu_tests.sh wraps this; its exit code is pytest's).
Reference bar: the reference's tests run the real transport
(cpp/test/CMakeLists.txt:36-76).
"""
import numpy as np
import pandas as pd
import pytest

import jax

import cylon_tpu as ct

pytestmark = [
    pytest.mark.tpu,
    pytest.mark.skipif(jax.default_backend() != "tpu",
                       reason="needs the real TPU backend "
                              "(CYLON_TPU_TESTS=1)"),
]


@pytest.fixture(scope="module")
def ctx():
    return ct.CylonContext.Init()


def _sorted(df):
    df = df.copy()
    df.columns = range(df.shape[1])
    return df.sort_values(list(df.columns)).reset_index(drop=True)


def _cmp(got, exp, name):
    g, e = _sorted(got), _sorted(exp)
    assert g.shape == e.shape, f"{name}: {g.shape} != {e.shape}"
    pd.testing.assert_frame_equal(g, e, check_dtype=False, atol=1e-4,
                                  obj=name)


N = 60_000  # big enough to engage the stream (Pallas) paths, small
            # enough that compiles stay in seconds


def _pair(seed, nkeys=997):
    rng = np.random.default_rng(seed)
    a = pd.DataFrame({"k": rng.integers(0, nkeys, N).astype(np.int32),
                      "v": rng.normal(size=N).astype(np.float32)})
    b = pd.DataFrame({"k": rng.integers(0, nkeys, N).astype(np.int32),
                      "w": rng.normal(size=N).astype(np.float32)})
    return a, b


@pytest.mark.parametrize("jt", ["inner", "left", "right"])
def test_tpu_stream_join(ctx, jt):
    a, b = _pair(1)
    lt = ct.Table.from_pandas(ctx, a)
    rt = ct.Table.from_pandas(ctx, b)
    got = lt.join(rt, jt, on="k").to_pandas()
    # engine emits both key columns; keep the non-null-carrying one and
    # compare (k, v, w) multisets against pandas
    c = list(got.columns)
    got = got[[c[2], c[1], c[3]]] if jt == "right" \
        else got[[c[0], c[1], c[3]]]
    exp = a.merge(b, on="k", how=jt)
    _cmp(got, exp, f"tpu join {jt}")


def test_tpu_hash_join_multikey(ctx):
    rng = np.random.default_rng(7)
    a = pd.DataFrame({"k1": rng.integers(0, 60, N).astype(np.int32),
                      "k2": rng.integers(0, 60, N).astype(np.int64),
                      "v": np.arange(N, dtype=np.int32)})
    b = pd.DataFrame({"k1": rng.integers(0, 60, N).astype(np.int32),
                      "k2": rng.integers(0, 60, N).astype(np.int64),
                      "w": np.arange(N, dtype=np.int32)})
    # shrink to keep the product bounded
    a, b = a.iloc[: N // 8], b.iloc[: N // 8]
    lt = ct.Table.from_pandas(ctx, a)
    rt = ct.Table.from_pandas(ctx, b)
    got = lt.join(rt, "inner", algorithm="hash",
                  on=["k1", "k2"]).to_pandas()
    exp = a.merge(b, on=["k1", "k2"])
    assert len(got) == len(exp)


def test_tpu_string_join_word_lanes(ctx):
    rng = np.random.default_rng(3)
    keys = np.array([f"u{rng.integers(0, 4000):05d}x" for _ in range(N)],
                    object)
    from cylon_tpu.data import strings as _s

    old = _s.DICT_MAX_VOCAB
    _s.DICT_MAX_VOCAB = 0
    try:
        a = pd.DataFrame({"k": keys, "v": np.arange(N, dtype=np.int32)})
        rkeys = np.array([f"u{rng.integers(0, 5000):05d}x"
                          for _ in range(N)], object)
        b = pd.DataFrame({"k": rkeys, "w": np.arange(N, dtype=np.int32)})
        lt = ct.Table.from_pandas(ctx, a)
        rt = ct.Table.from_pandas(ctx, b)
        assert lt.get_column(0).is_varbytes
        got = lt.join(rt, "inner", on="k").to_pandas()
        exp = a.merge(b, on="k")
        assert len(got) == len(exp)
        assert sorted(got.iloc[:, 0]) == sorted(exp["k"])
    finally:
        _s.DICT_MAX_VOCAB = old


def test_tpu_groupby(ctx):
    rng = np.random.default_rng(11)
    d = pd.DataFrame({"k": rng.integers(0, 500, N).astype(np.int32),
                      "v": rng.normal(size=N).astype(np.float32)})
    t = ct.Table.from_pandas(ctx, d)
    got = t.groupby(0, [1, 1], ["sum", "count"]).to_pandas()
    exp = d.groupby("k").agg(s=("v", "sum"), c=("v", "count")) \
        .reset_index()
    got = got.sort_values(got.columns[0]).reset_index(drop=True)
    np.testing.assert_allclose(got.iloc[:, 1].to_numpy(),
                               exp["s"].to_numpy(), rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(got.iloc[:, 2].to_numpy(),
                                  exp["c"].to_numpy())


def test_tpu_set_ops(ctx):
    rng = np.random.default_rng(13)
    a = pd.DataFrame({"x": rng.integers(0, 5000, N).astype(np.int32)})
    b = pd.DataFrame({"x": rng.integers(0, 5000, N).astype(np.int32)})
    lt, rt = ct.Table.from_pandas(ctx, a), ct.Table.from_pandas(ctx, b)
    u = lt.union(rt)
    i = lt.intersect(rt)
    s = lt.subtract(rt)
    ua = set(a["x"]) | set(b["x"])
    ia = set(a["x"]) & set(b["x"])
    sa = set(a["x"]) - set(b["x"])
    assert u.row_count == len(ua)
    assert i.row_count == len(ia)
    assert s.row_count == len(sa)


def test_tpu_sort(ctx):
    rng = np.random.default_rng(17)
    d = pd.DataFrame({"k": rng.normal(size=N).astype(np.float32),
                      "v": np.arange(N, dtype=np.int32)})
    t = ct.Table.from_pandas(ctx, d)
    got = t.sort("k").to_pandas()
    exp = d.sort_values("k", kind="stable")
    np.testing.assert_allclose(got["k"].to_numpy(), exp["k"].to_numpy())
    np.testing.assert_array_equal(got["v"].to_numpy(), exp["v"].to_numpy())
