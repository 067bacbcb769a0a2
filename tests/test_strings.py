"""Device-native varlen strings (data/strings.py VarBytes) through the
local op surface: ingest policy, join, groupby, set ops, sort, filter,
export. Reference behavior being matched: string/binary columns flow
through every kernel (join/join.cpp:648-799, arrow_kernels.hpp:101,
arrow_partition_kernels.hpp:94) — here with no host-side vocabulary."""
import numpy as np
import pandas as pd
import pytest

import cylon_tpu as ct
from cylon_tpu.data import strings as _strings
from cylon_tpu.data.column import Column, as_varbytes
from cylon_tpu.data.strings import VarBytes


@pytest.fixture
def ctx():
    return ct.CylonContext.Init()


def _rand_strings(rng, n, lo=1, hi=18, alpha=26):
    lens = rng.integers(lo, hi, n)
    chars = rng.integers(97, 97 + alpha, int(lens.sum())).astype(np.uint8)
    offs = np.concatenate([[0], np.cumsum(lens)])
    return np.array([chars[offs[i]:offs[i + 1]].tobytes().decode()
                     for i in range(n)], dtype=object)


def _force_varbytes(monkeypatch):
    """Drop the dictionary threshold so every string ingest is varbytes."""
    monkeypatch.setattr(_strings, "DICT_MAX_VOCAB", 0)


def test_ingest_policy(ctx, monkeypatch):
    # low cardinality → dictionary; high cardinality → varbytes
    rng = np.random.default_rng(0)
    low = ct.Table.from_pydict(ctx, {
        "s": np.array(["a", "b", "a", "c"] * 50, dtype=object)})
    assert low.get_column(0).dictionary is not None
    hi_vals = _rand_strings(rng, 500, 8, 20)
    monkeypatch.setattr(_strings, "DICT_MAX_VOCAB", 16)
    hi = ct.Table.from_pydict(ctx, {"s": hi_vals})
    assert hi.get_column(0).is_varbytes
    assert list(hi.to_pydict()["s"]) == list(hi_vals)


def test_varbytes_roundtrip_with_nulls(ctx, monkeypatch):
    _force_varbytes(monkeypatch)
    vals = np.array(["alpha", None, "", "beta", None], dtype=object)
    t = ct.Table.from_pandas(ctx, pd.DataFrame({"s": vals}))
    assert t.get_column(0).is_varbytes
    out = t.to_pydict()["s"]
    assert list(out) == ["alpha", None, "", "beta", None]


@pytest.mark.parametrize("how", ["inner", "left", "right", "outer"])
def test_join_on_varbytes_keys(ctx, monkeypatch, how):
    _force_varbytes(monkeypatch)
    rng = np.random.default_rng(1)
    keys = _rand_strings(rng, 60, 1, 6, 4)  # heavy duplication
    lk = keys[rng.integers(0, 60, 300)]
    rk = keys[rng.integers(0, 60, 200)]
    ldf = pd.DataFrame({"k": lk, "x": np.arange(300, dtype=np.int64)})
    rdf = pd.DataFrame({"k": rk, "y": np.arange(200, dtype=np.int64)})
    left = ct.Table.from_pandas(ctx, ldf)
    right = ct.Table.from_pandas(ctx, rdf)
    assert left.get_column(0).is_varbytes
    got = left.join(right, how, "sort", on=["k"]).to_pandas()
    exp = ldf.merge(rdf, how=how, on="k")
    assert got.shape[0] == exp.shape[0]
    g = got.sort_values(["lt-0", "lt-1", "rt-3"], na_position="last") \
        .reset_index(drop=True)
    # key column contents round-tripped: multiset of (k, x, y)
    gset = sorted(map(tuple, got.fillna(-1).itertuples(index=False)))
    # align column order: got is [lt-0(k), lt-1(x), rt-2(k), rt-3(y)]
    eset = sorted((k if isinstance(k, str) else -1, x,
                   k if isinstance(k, str) else -1, y)
                  for k, x, y in exp.fillna(-1).itertuples(index=False))
    # outer joins null one side's key; compare loosely on counts per key
    if how == "inner":
        assert gset == [(k, x, k2, y) for (k, x, k2, y) in gset]
        assert sorted((r[0], r[1], r[3]) for r in gset) == \
            sorted((k, x, y) for (k, x, _k2, y) in eset)
    del g


def test_join_varbytes_vs_dictionary_equivalence(ctx, monkeypatch):
    """Same data, both storages, identical multiset results."""
    rng = np.random.default_rng(2)
    keys = _rand_strings(rng, 40, 2, 8)
    lk = keys[rng.integers(0, 40, 250)]
    rk = keys[rng.integers(0, 40, 150)]
    ldf = pd.DataFrame({"k": lk, "x": np.arange(250)})
    rdf = pd.DataFrame({"k": rk, "y": np.arange(150)})
    l_dict = ct.Table.from_pandas(ctx, ldf)
    r_dict = ct.Table.from_pandas(ctx, rdf)
    assert not l_dict.get_column(0).is_varbytes
    _force_varbytes(monkeypatch)
    l_vb = ct.Table.from_pandas(ctx, ldf)
    r_vb = ct.Table.from_pandas(ctx, rdf)
    assert l_vb.get_column(0).is_varbytes
    a = l_dict.join(r_dict, "inner", "sort", on=["k"]).to_pandas()
    b = l_vb.join(r_vb, "inner", "sort", on=["k"]).to_pandas()
    key = lambda df: sorted(map(tuple, df.itertuples(index=False)))
    assert key(a) == key(b)
    # mixed storages align too (dictionary side is lifted)
    c = l_dict.join(r_vb, "inner", "sort", on=["k"]).to_pandas()
    assert key(a) == key(c)


def test_join_hash_algorithm_varbytes(ctx, monkeypatch):
    _force_varbytes(monkeypatch)
    rng = np.random.default_rng(3)
    keys = _rand_strings(rng, 30, 2, 10)
    lk = keys[rng.integers(0, 30, 200)]
    rk = keys[rng.integers(0, 30, 100)]
    ldf = pd.DataFrame({"k": lk, "x": np.arange(200)})
    rdf = pd.DataFrame({"k": rk, "y": np.arange(100)})
    left = ct.Table.from_pandas(ctx, ldf)
    right = ct.Table.from_pandas(ctx, rdf)
    got = left.join(right, "inner", "hash", on=["k"]).to_pandas()
    exp = ldf.merge(rdf, how="inner", on="k")
    assert got.shape[0] == exp.shape[0]
    assert sorted(zip(got["lt-0"], got["lt-1"], got["rt-3"])) == \
        sorted(zip(exp["k"], exp["x"], exp["y"]))


def test_groupby_varbytes_keys(ctx, monkeypatch):
    _force_varbytes(monkeypatch)
    rng = np.random.default_rng(4)
    keys = _rand_strings(rng, 25, 3, 9)
    k = keys[rng.integers(0, 25, 400)]
    v = rng.integers(0, 100, 400).astype(np.int64)
    w = rng.integers(0, 100, 400).astype(np.int64)
    df = pd.DataFrame({"k": k, "v": v, "w": w})
    t = ct.Table.from_pandas(ctx, df)
    assert t.get_column(0).is_varbytes
    got = t.groupby(0, [1, 2], ["sum", "count"]).to_pandas()
    exp = df.groupby("k").agg(sum=("v", "sum"),
                              count=("w", "count")).reset_index()
    got = got.sort_values(got.columns[0]).reset_index(drop=True)
    exp = exp.sort_values("k").reset_index(drop=True)
    assert list(got.iloc[:, 0]) == list(exp["k"])
    assert list(got.iloc[:, 1]) == list(exp["sum"])
    assert list(got.iloc[:, 2]) == list(exp["count"])


def test_setops_varbytes(ctx, monkeypatch):
    _force_varbytes(monkeypatch)
    rng = np.random.default_rng(5)
    keys = _rand_strings(rng, 30, 2, 7)
    a = pd.DataFrame({"s": keys[rng.integers(0, 30, 120)],
                      "i": rng.integers(0, 3, 120).astype(np.int64)})
    b = pd.DataFrame({"s": keys[rng.integers(0, 30, 90)],
                      "i": rng.integers(0, 3, 90).astype(np.int64)})
    ta = ct.Table.from_pandas(ctx, a)
    tb = ct.Table.from_pandas(ctx, b)
    for name, fn in (("union", lambda x, y: pd.concat([x, y])),
                     ("subtract", None), ("intersect", None)):
        got = getattr(ta, name)(tb).to_pandas()
        arows = set(map(tuple, a.itertuples(index=False)))
        brows = set(map(tuple, b.itertuples(index=False)))
        if name == "union":
            exp = arows | brows
        elif name == "subtract":
            exp = arows - brows
        else:
            exp = arows & brows
        assert set(map(tuple, got.itertuples(index=False))) == exp
        assert got.shape[0] == len(exp)


def test_sort_varbytes(ctx, monkeypatch):
    _force_varbytes(monkeypatch)
    rng = np.random.default_rng(6)
    vals = _rand_strings(rng, 300, 0 + 1, 14, 5)
    t = ct.Table.from_pydict(ctx, {"s": vals,
                                   "i": np.arange(300, dtype=np.int64)})
    got = t.sort("s").to_pydict()["s"]
    assert list(got) == sorted(vals)
    got_d = t.sort("s", ascending=False).to_pydict()["s"]
    assert list(got_d) == sorted(vals, reverse=True)


def test_sort_varbytes_long_rows_host_fallback(ctx, monkeypatch):
    _force_varbytes(monkeypatch)
    rng = np.random.default_rng(7)
    vals = np.array([("x" * int(n)) + s for n, s in
                     zip(rng.integers(60, 90, 50),
                         _rand_strings(rng, 50, 1, 5))], dtype=object)
    t = ct.Table.from_pydict(ctx, {"s": vals})
    assert not t.get_column(0).varbytes.sortable_on_device
    assert list(t.sort("s").to_pydict()["s"]) == sorted(vals)


def test_filter_and_literal_compare(ctx, monkeypatch):
    _force_varbytes(monkeypatch)
    vals = np.array(["apple", "pear", "apple", "fig", "pear", "apple"],
                    dtype=object)
    t = ct.Table.from_pydict(ctx, {"s": vals,
                                   "i": np.arange(6, dtype=np.int64)})
    f = t[t["s"] == "apple"]
    assert list(f.to_pydict()["i"]) == [0, 2, 5]
    f2 = t[t["s"] != "apple"]
    assert list(f2.to_pydict()["i"]) == [1, 3, 4]


def test_scalar_min_max_varbytes(ctx, monkeypatch):
    _force_varbytes(monkeypatch)
    vals = np.array(["mango", "apple", "zebra", "kiwi"], dtype=object)
    t = ct.Table.from_pydict(ctx, {"s": vals})
    assert t.min("s").to_pydict()["s"][0] == "apple"
    assert t.max("s").to_pydict()["s"][0] == "zebra"


def test_concat_mixed_storage(ctx, monkeypatch):
    low = ct.Table.from_pydict(ctx, {
        "s": np.array(["a", "b", "a"] * 20, dtype=object)})
    _force_varbytes(monkeypatch)
    hi = ct.Table.from_pydict(ctx, {
        "s": _rand_strings(np.random.default_rng(8), 40, 5, 12)})
    m = low.merge(hi)
    assert m.row_count == 100
    assert m.get_column(0).is_varbytes
    exp = list(low.to_pydict()["s"]) + list(hi.to_pydict()["s"])
    assert list(m.to_pydict()["s"]) == exp


def test_csv_roundtrip_varbytes(ctx, monkeypatch, tmp_path):
    _force_varbytes(monkeypatch)
    rng = np.random.default_rng(9)
    df = pd.DataFrame({"s": _rand_strings(rng, 80, 3, 10),
                       "v": rng.integers(0, 50, 80).astype(np.int64)})
    t = ct.Table.from_pandas(ctx, df)
    p = tmp_path / "s.csv"
    t.to_csv(str(p))
    back = pd.read_csv(p)
    pd.testing.assert_frame_equal(back, df, check_dtype=False)


def test_nulls_join_never_match(ctx, monkeypatch):
    _force_varbytes(monkeypatch)
    ldf = pd.DataFrame({"k": np.array(["a", None, "b", None], dtype=object),
                        "x": np.arange(4)})
    rdf = pd.DataFrame({"k": np.array([None, "a", "c"], dtype=object),
                        "y": np.arange(3)})
    left = ct.Table.from_pandas(ctx, ldf)
    right = ct.Table.from_pandas(ctx, rdf)
    got = left.join(right, "inner", "sort", on=["k"]).to_pandas()
    assert got.shape[0] == 1
    assert got.iloc[0]["lt-0"] == "a" and got.iloc[0]["rt-2"] == "a"


def test_groupby_nulls_group_together(ctx, monkeypatch):
    _force_varbytes(monkeypatch)
    df = pd.DataFrame({"k": np.array(["a", None, "a", None, "b"],
                                     dtype=object),
                       "v": np.array([1, 2, 3, 4, 5], dtype=np.int64)})
    t = ct.Table.from_pandas(ctx, df)
    got = t.groupby(0, [1], ["sum"]).to_pandas()
    by_key = {k if isinstance(k, str) else None: v
              for k, v in zip(got.iloc[:, 0], got.iloc[:, 1])}
    assert by_key["a"] == 4 and by_key["b"] == 5 and by_key[None] == 6


# ---------------------------------------------------------------------------
# distributed: varbytes through shuffle / join / groupby / set ops on the
# virtual 8-device mesh (reference composition: DistributedJoin
# table.cpp:656-696 with BinaryHashPartitionKernel string placement)
# ---------------------------------------------------------------------------


def test_dist_join_varbytes(dist_ctx, monkeypatch):
    _force_varbytes(monkeypatch)
    rng = np.random.default_rng(11)
    keys = _rand_strings(rng, 50, 2, 10)
    lk = keys[rng.integers(0, 50, 400)]
    rk = keys[rng.integers(0, 50, 300)]
    ldf = pd.DataFrame({"k": lk, "x": np.arange(400, dtype=np.int64)})
    rdf = pd.DataFrame({"k": rk, "y": np.arange(300, dtype=np.int64)})
    left = ct.Table.from_pandas(dist_ctx, ldf)
    right = ct.Table.from_pandas(dist_ctx, rdf)
    assert left.get_column(0).is_varbytes
    got = left.distributed_join(right, "inner", "sort", on=["k"]).to_pandas()
    exp = ldf.merge(rdf, how="inner", on="k")
    assert got.shape[0] == exp.shape[0]
    assert sorted(zip(got["lt-0"], got["lt-1"], got["rt-3"])) == \
        sorted(zip(exp["k"], exp["x"], exp["y"]))
    # key columns round-tripped exactly on both sides
    assert (got["lt-0"] == got["rt-2"]).all()


def test_dist_join_mixed_storage(dist_ctx, monkeypatch):
    rng = np.random.default_rng(12)
    keys = _rand_strings(rng, 30, 2, 8)
    ldf = pd.DataFrame({"k": keys[rng.integers(0, 30, 200)],
                        "x": np.arange(200, dtype=np.int64)})
    rdf = pd.DataFrame({"k": keys[rng.integers(0, 30, 150)],
                        "y": np.arange(150, dtype=np.int64)})
    left = ct.Table.from_pandas(dist_ctx, ldf)   # dictionary
    assert not left.get_column(0).is_varbytes
    _force_varbytes(monkeypatch)
    right = ct.Table.from_pandas(dist_ctx, rdf)  # varbytes
    assert right.get_column(0).is_varbytes
    got = left.distributed_join(right, "inner", "sort", on=["k"]).to_pandas()
    exp = ldf.merge(rdf, how="inner", on="k")
    assert got.shape[0] == exp.shape[0]
    assert sorted(zip(got["lt-0"], got["lt-1"], got["rt-3"])) == \
        sorted(zip(exp["k"], exp["x"], exp["y"]))


def test_dist_groupby_varbytes(dist_ctx, monkeypatch):
    _force_varbytes(monkeypatch)
    rng = np.random.default_rng(13)
    keys = _rand_strings(rng, 40, 3, 9)
    k = keys[rng.integers(0, 40, 500)]
    v = rng.integers(0, 100, 500).astype(np.int64)
    df = pd.DataFrame({"k": k, "v": v})
    t = ct.Table.from_pandas(dist_ctx, df)
    got = t.groupby(0, [1], ["sum"]).to_pandas()
    exp = df.groupby("k")["v"].sum().reset_index()
    got = got.sort_values(got.columns[0]).reset_index(drop=True)
    exp = exp.sort_values("k").reset_index(drop=True)
    assert list(got.iloc[:, 0]) == list(exp["k"])
    assert list(got.iloc[:, 1]) == list(exp["v"])


def test_dist_setops_varbytes(dist_ctx, monkeypatch):
    _force_varbytes(monkeypatch)
    rng = np.random.default_rng(14)
    keys = _rand_strings(rng, 25, 2, 7)
    a = pd.DataFrame({"s": keys[rng.integers(0, 25, 160)],
                      "i": rng.integers(0, 3, 160).astype(np.int64)})
    b = pd.DataFrame({"s": keys[rng.integers(0, 25, 120)],
                      "i": rng.integers(0, 3, 120).astype(np.int64)})
    ta = ct.Table.from_pandas(dist_ctx, a)
    tb = ct.Table.from_pandas(dist_ctx, b)
    arows = set(map(tuple, a.itertuples(index=False)))
    brows = set(map(tuple, b.itertuples(index=False)))
    for name, exp in (("distributed_union", arows | brows),
                      ("distributed_subtract", arows - brows),
                      ("distributed_intersect", arows & brows)):
        got = getattr(ta, name)(tb).to_pandas()
        assert set(map(tuple, got.itertuples(index=False))) == exp
        assert got.shape[0] == len(exp)


def test_dist_shuffle_varbytes_preserves_rows(dist_ctx, monkeypatch):
    _force_varbytes(monkeypatch)
    from cylon_tpu.parallel import dist_ops

    rng = np.random.default_rng(15)
    vals = _rand_strings(rng, 300, 1, 15)
    df = pd.DataFrame({"s": vals, "i": np.arange(300, dtype=np.int64)})
    t = ct.Table.from_pandas(dist_ctx, df)
    sh = dist_ops.shuffle(t, ["s"])
    got = sh.to_pandas().sort_values("i").reset_index(drop=True)
    pd.testing.assert_frame_equal(
        got, df.sort_values("i").reset_index(drop=True), check_dtype=False)


def test_multihost_ingest_strings(dist_ctx, monkeypatch):
    """assemble_process_local now accepts string columns (varbytes — no
    global vocabulary needed)."""
    from cylon_tpu.parallel import shard as _shard

    _force_varbytes(monkeypatch)
    rng = np.random.default_rng(16)
    world = dist_ctx.get_world_size()
    per = []
    all_rows = []
    for s in range(world):
        n = 20 + s * 3
        vals = _rand_strings(rng, n, 1, 12)
        iv = rng.integers(0, 100, n).astype(np.int64)
        per.append(ct.Table.from_pydict(dist_ctx, {"s": vals, "i": iv}))
        all_rows += list(zip(vals, iv))
    local = ct.CylonContext.Init()
    # single-controller: this process owns every shard
    t = _shard.assemble_process_local(per, dist_ctx)
    got = t.to_pandas()
    assert sorted(map(tuple, got.itertuples(index=False))) == \
        sorted(all_rows)
    del local


def test_empty_take_and_slice(ctx, monkeypatch):
    _force_varbytes(monkeypatch)
    t = ct.Table.from_pydict(ctx, {
        "s": _rand_strings(np.random.default_rng(20), 40, 2, 8),
        "i": np.arange(40, dtype=np.int64)})
    # empty slice
    e = t.slice(3, 3)
    assert e.row_count == 0
    # over-long slice clamps like fixed-width columns
    s = t.slice(2, 1000)
    assert s.row_count == 38
    c = s.get_column(0)
    assert c.data.shape[0] == 38
    assert c.varbytes.lengths.shape[0] == 38
    # single row
    one = t[5]
    assert one.row_count == 1


def test_binary_roundtrip(ctx):
    import pyarrow as pa

    vals = [b"\xff\x00\x01", b"plain", b"", b"\x80\x81" * 9, None]
    # binary always takes the varbytes path (no sorted-str vocab)
    arr = pa.table({"b": pa.array(vals, type=pa.binary())})
    t = ct.Table.from_arrow(ctx, arr)
    c = t.get_column(0)
    assert c.is_varbytes
    back = t.to_arrow()["b"].to_pylist()
    assert back == vals


# ---------------------------------------------------------------------------
# round 4: word-lane fast paths (strided layout, exact short-string keys)
# ---------------------------------------------------------------------------


def test_strided_take_roundtrip(ctx):
    """Short-row takes produce the strided layout; content, chained
    takes, hashes, and mixed-layout concat all agree with packed."""
    import jax
    import jax.numpy as jnp

    from cylon_tpu.data.strings import concat_varbytes

    vals = ["", "a", "abcd", "abcde", "hello world!", "x" * 20, "yy"]
    vb = VarBytes.from_host(vals)
    idx = jnp.asarray(np.array([3, -1, 0, 6, 2, 2, 5], np.int32))
    t = vb.take(idx)
    assert t.stride is not None
    exp = ["abcde", "", "", "yy", "abcd", "abcd", "x" * 20]
    assert list(t.to_host()) == exp
    packed = VarBytes.from_host(exp)
    for a, b in zip(t.hash_keys(), packed.hash_keys()):
        np.testing.assert_array_equal(np.asarray(jax.device_get(a)),
                                      np.asarray(jax.device_get(b)))
    t2 = t.take(jnp.asarray(np.array([0, 2, 4], np.int32)))
    assert list(t2.to_host()) == ["abcde", "", "abcd"]
    c = concat_varbytes([t, vb])
    assert list(c.to_host()) == exp + vals
    cp = VarBytes.from_host(exp + vals)
    for a, b in zip(c.hash_keys(), cp.hash_keys()):
        np.testing.assert_array_equal(np.asarray(jax.device_get(a)),
                                      np.asarray(jax.device_get(b)))
    # long rows keep the packed take path
    vb_long = VarBytes.from_host(["z" * 50, "q" * 40, "w"])
    tl = vb_long.take(jnp.asarray(np.array([2, 0, 1], np.int32)))
    assert tl.stride is None
    assert list(tl.to_host()) == ["w", "z" * 50, "q" * 40]


def test_short_string_join_is_exact_not_hashed(ctx, monkeypatch):
    """VERDICT #4: short varbytes keys (≤ EXACT_KEY_WORDS words) join on
    raw word lanes — byte-exact like the reference
    (join/join.cpp:648-799). Force every content hash to COLLIDE; the
    short-key join must still distinguish distinct keys (it never
    consults the hashes), proving there is no 96-bit-collision failure
    mode for keys up to 20 bytes."""
    _force_varbytes(monkeypatch)

    def colliding_hash(words, starts, lengths, max_words):
        n = starts.shape[0]
        import jax.numpy as jnp
        h = jnp.full(n, jnp.uint32(0xDEADBEEF))
        return h, h, h

    monkeypatch.setattr(_strings, "_hash_rows", colliding_hash)
    n = 300
    lk = np.array([f"key_{i % 40:04d}" for i in range(n)], object)
    rk = np.array([f"key_{i % 55:04d}" for i in range(n)], object)
    lt = ct.Table.from_pydict(ctx, {"k": lk, "v": np.arange(n)})
    rt = ct.Table.from_pydict(ctx, {"k": rk, "w": np.arange(n) * 2})
    assert lt.get_column(0).is_varbytes
    got = lt.join(rt, "inner", on="k").to_pandas()
    exp = pd.DataFrame({"k": lk, "v": np.arange(n)}).merge(
        pd.DataFrame({"k": rk, "w": np.arange(n) * 2}), on="k")
    assert len(got) == len(exp)
    assert sorted(got.iloc[:, 0]) == sorted(exp["k"])
    # the same collision WOULD merge long keys (documented hash identity)
    # — so the guarantee boundary is exactly EXACT_KEY_WORDS
    g = lt.groupby(0, [1], [ct.AggregationOp.COUNT]).to_pandas()
    assert len(g) == 40


def test_inner_join_right_key_aliases_left(ctx, monkeypatch):
    """INNER joins on byte-exact string keys emit one shared varbytes
    buffer for both key columns (left/right bytes are provably equal)."""
    _force_varbytes(monkeypatch)
    n = 120
    k = np.array([f"id{i % 17:03d}" for i in range(n)], object)
    lt = ct.Table.from_pydict(ctx, {"k": k, "v": np.arange(n)})
    rt = ct.Table.from_pydict(ctx, {"k": k, "w": np.arange(n)})
    out = lt.join(rt, "inner", on="k")
    ck_l, ck_r = out.get_column(0), out.get_column(2)
    assert ck_r.varbytes is ck_l.varbytes
    df = out.to_pandas()
    assert (df.iloc[:, 0] == df.iloc[:, 2]).all()


def test_left_join_unmatched_string_rows_are_empty(ctx, monkeypatch):
    _force_varbytes(monkeypatch)
    lk = np.array(["aa", "bb", "cc", "dd"], object)
    rk = np.array(["bb", "dd"], object)
    lt = ct.Table.from_pydict(ctx, {"k": lk, "v": np.arange(4)})
    rt = ct.Table.from_pydict(ctx, {"k": rk, "w": np.arange(2)})
    got = lt.join(rt, "left", on="k").to_pandas()
    assert len(got) == 4
    m = dict(zip(got.iloc[:, 0], got.iloc[:, 2]))
    assert m["bb"] == "bb" and m["dd"] == "dd"
    assert m["aa"] is None or m["aa"] != m["aa"] or m["aa"] == ""


def test_full_outer_join_mixed_max_words(ctx, monkeypatch):
    """Regression (round-4 review): FULL_OUTER's unmatched-right
    membership pass must pair lane counts — left max_words != right
    max_words used to zip misaligned key arrays and misclassify
    matched rows as unmatched."""
    _force_varbytes(monkeypatch)
    lk = np.array(["ab", "cd", "ef"], object)              # 1 word
    rk = np.array(["ab", "longerkey0", "cd", "zz"], object)  # up to 3 words
    lt = ct.Table.from_pydict(ctx, {"k": lk, "v": np.arange(3)})
    rt = ct.Table.from_pydict(ctx, {"k": rk, "w": np.arange(4)})
    assert lt.get_column(0).varbytes.max_words != \
        rt.get_column(0).varbytes.max_words
    got = lt.join(rt, "outer", on="k").to_pandas()
    exp = pd.DataFrame({"k": lk, "v": np.arange(3)}).merge(
        pd.DataFrame({"k": rk, "w": np.arange(4)}), on="k", how="outer")
    assert len(got) == len(exp)
    keys = [a if isinstance(a, str) else b
            for a, b in zip(got.iloc[:, 0], got.iloc[:, 2])]
    assert sorted(keys) == sorted(exp["k"])


def test_binary_min_max_returns_bytes(ctx):
    """Round-3 advisor (low): BINARY min/max must return bytes — a str()
    decode corrupts non-UTF-8 payloads."""
    import pyarrow as pa

    vals = [b"\xff\x00\x01", b"\x80\x81zz", b"aa", None]
    t = ct.Table.from_arrow(ctx, pa.table(
        {"b": pa.array(vals, type=pa.binary())}))
    assert t.max(0).to_pydict()["b"][0] == b"\xff\x00\x01"
    assert t.min(0).to_pydict()["b"][0] == b"aa"


def test_exact_join_survives_forced_hash_collision(ctx, monkeypatch):
    """VERDICT #4: exact=True re-checks true bytes for LONG keys (>
    EXACT_KEY_WORDS words, which join on the 96-bit content hash).
    Force every hash to collide: the default join merges distinct keys
    (documented identity), exact=True filters the false matches."""
    _force_varbytes(monkeypatch)

    def colliding_hash(words, starts, lengths, max_words):
        import jax.numpy as jnp
        n = starts.shape[0]
        h = jnp.full(n, jnp.uint32(0xC0FFEE))
        return h, h, h

    monkeypatch.setattr(_strings, "_hash_rows", colliding_hash)
    # 30-byte keys -> 8 words > EXACT_KEY_WORDS -> hash identity
    lk = np.array([f"{'L' * 26}{i:04d}" for i in range(40)], object)
    rk = np.array([f"{'L' * 26}{i:04d}" for i in range(0, 80, 2)], object)
    lt = ct.Table.from_pydict(ctx, {"k": lk, "v": np.arange(40)})
    rt = ct.Table.from_pydict(ctx, {"k": rk, "w": np.arange(40)})
    assert lt.get_column(0).varbytes.max_words > _strings.EXACT_KEY_WORDS
    # same length + colliding hashes: the hash identity merges ALL keys
    loose = lt.join(rt, "inner", on="k")
    assert loose.row_count == 40 * 40
    exact = lt.join(rt, "inner", on="k", exact=True)
    got = exact.to_pandas()
    exp = pd.DataFrame({"k": lk, "v": np.arange(40)}).merge(
        pd.DataFrame({"k": rk, "w": np.arange(40)}), on="k")
    assert len(got) == len(exp) == 20
    assert sorted(got.iloc[:, 0]) == sorted(exp["k"])
    # outer joins reclassify false matches as unmatched via the
    # shared-vocabulary dictionary fallback (round-5: VERDICT r04 #8 —
    # the old behavior raised)
    ldf = pd.DataFrame({"k": lk, "v": np.arange(40)})
    rdf = pd.DataFrame({"k": rk, "w": np.arange(40)})
    for jt, how in (("left", "left"), ("right", "right"),
                    ("outer", "outer")):
        g = lt.join(rt, jt, on="k", exact=True).to_pandas()
        e = ldf.merge(rdf, on="k", how=how)
        assert len(g) == len(e), (jt, len(g), len(e))
        # matched-row multiset is exact: (k, v, w) for rows present on
        # both sides
        gm = g.dropna(subset=[g.columns[1], g.columns[-1]])
        gset = sorted(zip(gm[g.columns[0]], gm[g.columns[1]].astype(int),
                          gm[g.columns[-1]].astype(int)))
        em = e.dropna()
        eset = sorted(zip(em["k"], em["v"].astype(int),
                          em["w"].astype(int)))
        assert gset == eset, jt


@pytest.mark.parametrize("how", ["inner", "left", "right", "outer"])
def test_exact_distributed_join_long_keys(how, dist_ctx, monkeypatch):
    """exact=True on DISTRIBUTED long-key joins byte-verifies after the
    exchange instead of rejecting. With every content hash forced to
    collide, INNER filters the false matches on device and the outer
    joins redo on shared-vocabulary dictionary codes."""
    from cylon_tpu.ops.join import JoinConfig, JoinType
    from cylon_tpu.parallel import dist_ops

    _force_varbytes(monkeypatch)

    def colliding_hash(words, starts, lengths, max_words):
        import jax.numpy as jnp
        n = starts.shape[0]
        h = jnp.full(n, jnp.uint32(0xC0FFEE))
        return h, h, h

    monkeypatch.setattr(_strings, "_hash_rows", colliding_hash)
    lk = np.array([f"{'L' * 26}{i:04d}" for i in range(40)], object)
    rk = np.array([f"{'L' * 26}{i:04d}" for i in range(0, 80, 2)], object)
    lt = ct.Table.from_pydict(dist_ctx, {"k": lk,
                                         "v": np.arange(40, dtype=np.int32)})
    rt = ct.Table.from_pydict(dist_ctx, {"k": rk,
                                         "w": np.arange(40, dtype=np.int32)})
    assert lt.get_column(0).varbytes.max_words > _strings.EXACT_KEY_WORDS

    ldf = pd.DataFrame({"k": lk, "v": np.arange(40)})
    rdf = pd.DataFrame({"k": rk, "w": np.arange(40)})
    jt = {"inner": JoinType.INNER, "left": JoinType.LEFT,
          "right": JoinType.RIGHT, "outer": JoinType.FULL_OUTER}[how]
    cfg = JoinConfig(jt, [0], [0], exact=True)
    j = dist_ops.distributed_join(lt, rt, cfg).to_pandas()
    e = ldf.merge(rdf, on="k", how=how)
    assert len(j) == len(e) == {"inner": 20, "left": 40, "right": 40,
                                "outer": 60}[how]
    gm = j.dropna(subset=[j.columns[1], j.columns[-1]])
    em = e.dropna()
    assert len(gm) == len(em) == 20
    # matched rows byte-correct, not just counted: (k, v, w) triples
    gset = sorted(zip(gm.iloc[:, 0], gm.iloc[:, 1].astype(int),
                      gm.iloc[:, -1].astype(int)))
    eset = sorted(zip(em["k"], em["v"].astype(int),
                      em["w"].astype(int)))
    assert gset == eset


def test_lane_paths_edge_shapes(ctx, monkeypatch):
    """Empty/one-row/all-empty-string tables through the word-lane
    machinery (join outputs, takes, round trips)."""
    _force_varbytes(monkeypatch)
    # one row
    t1 = ct.Table.from_pydict(ctx, {"k": np.array(["solo"], object),
                                    "v": np.array([1])})
    j1 = t1.join(t1, "inner", on="k")
    assert j1.row_count == 1
    assert j1.to_pandas().iloc[0, 0] == "solo"
    # empty join result
    t2 = ct.Table.from_pydict(ctx, {"k": np.array(["other"], object),
                                    "w": np.array([2])})
    j0 = t1.join(t2, "inner", on="k")
    assert j0.row_count == 0
    assert list(j0.to_pandas().columns) == ["lt-0", "lt-1", "rt-2", "rt-3"]
    # all-empty-string keys (zero-word rows)
    ke = np.array(["", "", "x"], object)
    t3 = ct.Table.from_pydict(ctx, {"k": ke, "v": np.arange(3)})
    j3 = t3.join(t3, "inner", on="k")
    assert j3.row_count == 2 * 2 + 1
    # strided output round-trips through arrow + csv
    out = j1.to_arrow()
    assert out.column("lt-0").to_pylist() == ["solo"]


def test_strided_varbytes_filter_and_concat_chain(ctx, monkeypatch):
    """Strided outputs flow through filter -> concat -> groupby (the
    post-join pipeline shape)."""
    _force_varbytes(monkeypatch)
    n = 300
    k = np.array([f"g{i % 7}" for i in range(n)], object)
    t = ct.Table.from_pydict(ctx, {"k": k, "v": np.arange(n)})
    j = t.join(t, "inner", on="k")          # strided varbytes output
    f = j[j["lt-1"] > 100]                   # mask view
    m = f.merge(f)                           # concat path
    g = m.groupby(0, [1], [ct.AggregationOp.COUNT])
    assert g.row_count <= 7
    df = m.to_pandas()
    assert (df.iloc[:, 0] == df.iloc[:, 2]).all()
