"""The two key programs of a distributed operator (PR 39): what it computes
elementwise from its key columns on the host's side of the exchange is ONE
named program a call — partition targets (and the emit mask) before it
(`_partition_targets_program_fn`), key bits and key validity after it
(`_key_bits_program_fn`) — where it was one eager XLA program an arithmetic
operation. The same rows must go to the same chips: the targets and the key
bits are compared bit for bit with the chain as it stood, written out
eagerly here from ops/hash and ops/order, and the targets with the host's
partitioner (native.hash_partition, what `distribute_by_key` and
`_hash_partition_host` place rows by) wherever that vouches for a
placement."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import cylon_tpu as ct
from cylon_tpu import native, telemetry
from cylon_tpu.data.column import Column
from cylon_tpu.data.strings import EXACT_KEY_WORDS, VarBytes, pair_k_words
from cylon_tpu.data.table import Table
from cylon_tpu.ops import hash as H
from cylon_tpu.ops import order as O
from cylon_tpu.parallel import dist_ops as D
from cylon_tpu.parallel import shard
from cylon_tpu.status import Code, CylonError

ROWS = 96  # 48 and 24 a shard: shard.distribute pads nothing


@pytest.fixture(scope="module")
def ctx_by_world(dist_ctx):
    return {2: ct.CylonContext.InitDistributed(ct.TPUConfig(world_size=2)),
            4: dist_ctx}


# ---------------------------------------------------------------------------
# key columns of every storage a distributed operator takes
# ---------------------------------------------------------------------------

def _words(rng, n, lo, hi):
    """n byte strings of lo..hi bytes from a small alphabet, with repeats."""
    pool = [bytes(rng.integers(97, 101, rng.integers(lo, hi + 1),
                               dtype=np.uint8)).decode() for _ in range(24)]
    return np.array([pool[i] for i in rng.integers(0, len(pool), n)],
                    dtype=object)


def _column(kind, rng, masked, name="k", n=ROWS):
    validity = (rng.random(n) < 0.8) if masked else None
    dev_validity = None if validity is None else jnp.asarray(validity)
    if kind == "int32":
        data = rng.integers(-2 ** 31, 2 ** 31, n).astype(np.int32)
    elif kind == "uint32":
        data = rng.integers(0, 2 ** 32, n).astype(np.uint32)
    elif kind == "int64":   # tier-1 runs with x64 on: a native 8-byte lane
        data = rng.integers(-2 ** 62, 2 ** 62, n).astype(np.int64)
    elif kind == "float32":
        data = rng.normal(size=n).astype(np.float32)
        data[:6] = [0.0, -0.0, np.nan, -np.nan, np.inf, -np.inf]
    elif kind == "bool":
        data = rng.random(n) < 0.5
    elif kind == "dictionary":
        col = Column.from_numpy(_words(rng, n, 1, 6), name)
        assert col.dictionary is not None
        return Column(col.data, col.dtype, dev_validity, col.dictionary,
                      name)
    elif kind in ("short_varbytes", "long_varbytes"):
        lo, hi = (1, 4 * EXACT_KEY_WORDS) if kind == "short_varbytes" \
            else (4 * EXACT_KEY_WORDS + 1, 4 * EXACT_KEY_WORDS + 12)
        vb = VarBytes.from_host(_words(rng, n, lo, hi))
        assert (vb.max_words <= EXACT_KEY_WORDS) == (kind == "short_varbytes")
        return Column.from_varbytes(vb, dev_validity, name)
    else:
        raise AssertionError(kind)
    col = Column.from_numpy(data, name)
    return Column(col.data, col.dtype, dev_validity, None, name)


KINDS = ["int32", "uint32", "int64", "float32", "bool", "dictionary",
         "short_varbytes", "long_varbytes"]
THREE = [("int32", "float32", "dictionary"),
         ("uint32", "short_varbytes", "long_varbytes")]
CASES = [(k,) for k in KINDS] + THREE


def _case_id(kinds):
    return "+".join(kinds)


def _key_columns(ctx, kinds, masked, seed=7):
    """The distributed key columns of one case, and the host table they
    came from (whole, in row order)."""
    rng = np.random.default_rng(seed)
    host = Table([_column(k, rng, masked, f"k{j}")
                  for j, k in enumerate(kinds)], ctx)
    return shard.distribute(host, ctx)._columns, host


# ---------------------------------------------------------------------------
# the chain as it stood: one eager operation after another
# ---------------------------------------------------------------------------

def _eager_col_keys(ctx, c, k_words=None):
    if c.is_varbytes:
        vb = c.varbytes
        k = vb.max_words if k_words is None else max(int(k_words),
                                                     vb.max_words)
        if k <= EXACT_KEY_WORDS:
            lanes = D._dist_word_lanes(ctx, c, k)
            ln = vb.lengths.astype(jnp.uint32)
            h = ln * np.uint32(0x9E3779B1)
            for lane in lanes:
                h = h * np.uint32(31) + H.fmix32(lane)
            h1 = H.fmix32(h)
            bits = lanes + [ln]
        else:
            q = D._dist_string_keys(ctx, c)
            h1, bits = q[0], list(q)
        if c.validity is not None:
            h1 = jnp.where(c.validity, h1, jnp.uint32(0x9E3779B9))
        return bits, h1
    return [O.sort_keys([c])[0]], H.hash_column(c)


def _eager_targets(ctx, cols, paired=None):
    h = None
    for j, c in enumerate(cols):
        kw = pair_k_words(c, paired[j]) if paired is not None else None
        hc = _eager_col_keys(ctx, c, kw)[1]
        h = hc if h is None else h * np.uint32(31) + hc
    h = H.fmix32(h)
    return (h % np.uint32(ctx.get_world_size())).astype(jnp.int32)


def _eager_key_bits(ctx, cols, paired=None):
    bits, kv = [], None
    for j, c in enumerate(cols):
        kw = pair_k_words(c, paired[j]) if paired is not None else None
        bits.extend(_eager_col_keys(ctx, c, kw)[0])
        kv = c.valid_mask() if kv is None else (kv & c.valid_mask())
    return bits, kv


def _same(a, b):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape, (a.dtype, b.dtype,
                                                       a.shape, b.shape)
    np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# (a) targets
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("world", [2, 4])
@pytest.mark.parametrize("masked", [False, True], ids=["dense", "masked"])
@pytest.mark.parametrize("kinds", CASES, ids=_case_id)
def test_targets_are_the_eager_chain_s_bit_for_bit(ctx_by_world, kinds,
                                                   masked, world):
    ctx = ctx_by_world[world]
    cols, _host = _key_columns(ctx, kinds, masked)
    got = D._partition_targets_dist(ctx, cols)
    assert got.dtype == jnp.int32
    _same(got, _eager_targets(ctx, cols))
    assert 0 <= int(got.min()) and int(got.max()) < world


def _host_twin_targets(host, world):
    """What the host's partitioner says of the same rows: raw values (the
    dictionary's codes for a dictionary column, flagged), a varbytes
    column's content hash of its bytes."""
    keys, valids, strings, pre = [], [], [], []
    for c in host._columns:
        if c.is_varbytes:
            keys.append(native.np_varbytes_hash(c.varbytes.to_host()))
        else:
            keys.append(np.asarray(c.data))
        pre.append(c.is_varbytes)
        strings.append(c.is_string and not c.is_varbytes)
        valids.append(None if c.validity is None else np.asarray(c.validity))
    return native.hash_partition(keys, valids, world, is_string=strings,
                                 prehashed=pre)[0]


# short varbytes hash their word lanes on the device (byte-exact keys) and
# their content hash on the host: no witness vouches for a string placement
# (shard.partition_signature is None), so the two need not agree there
TWIN_CASES = [k for k in CASES if "short_varbytes" not in k]


@pytest.mark.parametrize("world", [2, 4])
@pytest.mark.parametrize("masked", [False, True], ids=["dense", "masked"])
@pytest.mark.parametrize("kinds", TWIN_CASES, ids=_case_id)
def test_targets_equal_the_host_partitioner_s(ctx_by_world, kinds, masked,
                                              world):
    ctx = ctx_by_world[world]
    cols, host = _key_columns(ctx, kinds, masked)
    got = np.asarray(D._partition_targets_dist(ctx, cols))
    assert got.shape == (ROWS,)   # no padding: shard s holds rows s*n/world..
    np.testing.assert_array_equal(got, _host_twin_targets(host, world))


def test_paired_varbytes_take_the_pair_s_lane_count(dist_ctx):
    """Both sides of a join hash with matching lane counts: a side whose
    own rows are 1 word long takes the other side's 3."""
    rng = np.random.default_rng(11)
    a = Table([Column.from_varbytes(
        VarBytes.from_host(_words(rng, ROWS, 1, 4)), None, "k")], dist_ctx)
    b = Table([Column.from_varbytes(
        VarBytes.from_host(_words(rng, ROWS, 9, 12)), None, "k")], dist_ctx)
    ac = shard.distribute(a, dist_ctx)._columns
    bc = shard.distribute(b, dist_ctx)._columns
    assert pair_k_words(ac[0], bc[0]) == 3
    _same(D._partition_targets_dist(dist_ctx, ac, bc),
          _eager_targets(dist_ctx, ac, bc))
    bits, kv = D._dist_key_bits(dist_ctx, ac, bc)
    want, want_kv = _eager_key_bits(dist_ctx, ac, bc)
    assert len(bits) == len(want) == 4   # three lanes and the length
    for g, w in zip(bits, want):
        _same(g, w)
    _same(kv, want_kv)


def test_a_column_held_as_word_planes_is_refused(dist_ctx):
    """A 64-bit column with x64 off is two word planes: the exchange moves
    1-D leaves, so the key helpers refuse it by name, as shard.distribute
    does before them."""
    planes = Column(jnp.zeros((2, 32), jnp.uint32), ct.dtypes.Int64(), None,
                    None, "k64")
    with pytest.raises(CylonError, match="k64"):
        D._partition_targets_dist(dist_ctx, [planes])
    with pytest.raises(CylonError, match="k64"):
        D._dist_key_bits(dist_ctx, [planes])


# ---------------------------------------------------------------------------
# (b) key bits and key validity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("world", [2, 4])
@pytest.mark.parametrize("masked", [False, True], ids=["dense", "masked"])
@pytest.mark.parametrize("kinds", CASES, ids=_case_id)
def test_key_bits_are_the_eager_chain_s_bit_for_bit(ctx_by_world, kinds,
                                                    masked, world):
    ctx = ctx_by_world[world]
    cols, _host = _key_columns(ctx, kinds, masked)
    bits, kv = D._dist_key_bits(ctx, cols)
    want, want_kv = _eager_key_bits(ctx, cols)
    assert len(bits) == len(want)
    for g, w in zip(bits, want):
        _same(g, w)
    _same(kv, want_kv)


@pytest.mark.parametrize("kinds", CASES, ids=_case_id)
def test_key_bits_program_computes_no_partition_hash(dist_ctx, kinds):
    """No caller past the exchange uses the partition hashes, so the
    program has no such output: only the bits that have to be computed (a
    plain column's ordered bits, short varbytes' length lane; the word
    lanes and the quad are key bits as they stand) and ONE validity."""
    cols, _host = _key_columns(dist_ctx, kinds, masked=True)
    forms, operands = D._key_inputs(dist_ctx, cols)
    fn = D._key_bits_program_fn(dist_ctx.mesh, forms, (False,) * len(forms))
    computed, kv = jax.eval_shape(fn, operands)
    want = {"plain": 1, "lanes": 1, "quad": 0}
    assert [len(c) for c in computed] == [want[f[0]] for f in forms]
    assert kv.dtype == jnp.bool_
    n_out = len(jax.tree_util.tree_leaves((computed, kv)))
    assert n_out == sum(want[f[0]] for f in forms) + 1
    # and the targets program's only outputs are the targets and the mask
    tfn = D._partition_targets_program_fn(dist_ctx.mesh, forms, True)
    targets, emit = jax.eval_shape(tfn, operands)
    assert (targets.dtype, emit.dtype) == (jnp.int32, jnp.bool_)
    assert len(jax.eval_shape(D._partition_targets_program_fn(
        dist_ctx.mesh, forms, False), operands)) == 1


@pytest.mark.parametrize("masked", [False, True], ids=["dense", "masked"])
def test_null_lanes_ride_after_their_column_s_bits(dist_ctx, masked):
    """A set operation's row key holds a column's validity as a uint8
    lane (nulls compare equal), by the PAIR's flag: a column without a
    mask whose partner has one rides all-ones."""
    cols, _host = _key_columns(dist_ctx, ("int32", "short_varbytes",
                                          "float32"), masked)
    flags = (True, False, True)
    bits, _kv = D._dist_key_bits(dist_ctx, cols, None, flags)
    want = []
    for c, flag in zip(cols, flags):
        want.extend(_eager_col_keys(dist_ctx, c)[0])
        if flag:
            want.append(c.valid_mask().astype(jnp.uint8))
    assert len(bits) == len(want)
    for g, w in zip(bits, want):
        _same(g, w)


# ---------------------------------------------------------------------------
# (c) the outputs carry the row sharding: a pin after them launches nothing
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kinds", [("int32",), ("short_varbytes",),
                                   ("long_varbytes",), THREE[0], THREE[1]],
                         ids=_case_id)
def test_outputs_carry_the_row_sharding(dist_ctx, kinds):
    cols, _host = _key_columns(dist_ctx, kinds, masked=False)
    view = Table(list(cols), dist_ctx, None)
    targets, emit = D._targets_and_emit(dist_ctx, view, cols)
    bits, kv = D._dist_key_bits(dist_ctx, cols)
    for x in (targets, emit, kv) + tuple(bits):
        assert shard.is_row_sharded(x, dist_ctx)
        assert shard.pin(x, dist_ctx) is x
    assert emit.dtype == jnp.bool_ and bool(emit.all())
    assert targets.shape == emit.shape == kv.shape


def test_a_row_mask_is_the_emit_mask(dist_ctx):
    """A table with a row mask keeps it: the program makes no mask."""
    cols, _host = _key_columns(dist_ctx, ("int32",), masked=False)
    mask = shard.pin(jnp.arange(cols[0].data.shape[0]) % 3 != 0, dist_ctx)
    view = Table(list(cols), dist_ctx, mask)
    targets, emit = D._targets_and_emit(dist_ctx, view, cols)
    assert emit is mask
    _same(targets, D._partition_targets_dist(dist_ctx, cols))


# ---------------------------------------------------------------------------
# (d) the counter that says the mechanism engaged, and no recompile
# ---------------------------------------------------------------------------

def _counter(name, **labels):
    snap = telemetry.metrics_snapshot()
    want = name + ("{" + ",".join(f'{k}="{v}"' for k, v in sorted(
        labels.items())) + "}" if labels else "")
    return snap.get(want, 0)


def _join_tables(ctx, seed, n=512):
    rng = np.random.default_rng(seed)
    left = ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, 97, n).astype(np.int32),
        "v": rng.normal(size=n).astype(np.float32)})
    right = ct.Table.from_pydict(ctx, {
        "k": rng.integers(0, 97, n).astype(np.int32),
        "w": rng.normal(size=n).astype(np.float32)})
    return left, right


def test_two_joins_build_each_program_once_and_count_four_each(dist_ctx):
    D._partition_targets_program_fn.cache_clear()
    D._key_bits_program_fn.cache_clear()
    builds = [_counter("cylon_kernel_factory_builds_total", factory=f)
              for f in ("_partition_targets_program_fn",
                        "_key_bits_program_fn")]
    for seed in (21, 22):   # fresh buffers of one shape
        before = {s: _counter("cylon_key_programs_total", stage=s)
                  for s in ("targets", "keybits")}
        left, right = _join_tables(dist_ctx, seed)
        out = left.distributed_join(right, "inner", on="k")
        ref = left.to_pandas().merge(right.to_pandas(), on="k")
        assert out.row_count == len(ref)
        after = {s: _counter("cylon_key_programs_total", stage=s)
                 for s in ("targets", "keybits")}
        # two sides, two stages
        assert {s: after[s] - before[s] for s in after} \
            == {"targets": 2, "keybits": 2}
    # both sides have one form (an int32 key, no mask, no row mask): one
    # program a factory serves the four dispatches of both joins
    assert [_counter("cylon_kernel_factory_builds_total", factory=f)
            for f in ("_partition_targets_program_fn",
                      "_key_bits_program_fn")] == [b + 1 for b in builds]


# ---------------------------------------------------------------------------
# (e) the other operators that share the two helpers give what they gave
# ---------------------------------------------------------------------------

def _frame(ctx, seed, n=400, strings=False):
    rng = np.random.default_rng(seed)
    d = {"k": rng.integers(0, 40, n).astype(np.int32),
         "v": rng.integers(0, 9, n).astype(np.int32)}
    if strings:
        d["s"] = _words(rng, n, 1, 6)
    return ct.Table.from_pydict(ctx, d)


def _sorted_rows(df):
    return sorted(map(tuple, df.itertuples(index=False)))


def test_shuffle_places_every_key_on_one_shard_and_counts_one(dist_ctx):
    t = _frame(dist_ctx, 31)
    before = _counter("cylon_key_programs_total", stage="targets")
    out = D.shuffle(t, ["k"])
    assert _counter("cylon_key_programs_total", stage="targets") \
        == before + 1
    assert _sorted_rows(out.to_pandas()) == _sorted_rows(t.to_pandas())
    keys = np.asarray(out._columns[0].data)
    live = np.asarray(out.emit_mask())
    per = keys.shape[0] // 4
    home = {}
    for s in range(4):
        for k in set(keys[s * per:(s + 1) * per][live[s * per:(s + 1)
                                                      * per]].tolist()):
            assert home.setdefault(k, s) == s
    # the same rows to the same chips as the host's partitioner says
    twin = native.hash_partition([np.asarray(t._columns[0].data)], [None],
                                 4)[0]
    want = dict(zip(np.asarray(t._columns[0].data).tolist(), twin.tolist()))
    assert home == {k: want[k] for k in home}


@pytest.mark.parametrize("op", ["union", "intersect", "subtract"])
def test_set_operation_over_masked_and_string_columns(dist_ctx, op):
    a, b = _frame(dist_ctx, 41, strings=True), _frame(dist_ctx, 42,
                                                      strings=True)
    got = getattr(a, f"distributed_{op}")(b).to_pandas()
    la, lb = a.to_pandas().drop_duplicates(), b.to_pandas().drop_duplicates()
    if op == "union":
        import pandas as pd
        want = pd.concat([la, lb]).drop_duplicates()
    elif op == "intersect":
        want = la.merge(lb)
    else:
        want = la.merge(lb, how="left", indicator=True)
        want = want[want["_merge"] == "left_only"].drop(columns="_merge")
    assert _sorted_rows(got) == _sorted_rows(want)


def test_distributed_groupby_sums_by_key(dist_ctx):
    t = _frame(dist_ctx, 51)
    got = t.groupby(0, [1], ["sum"]).to_pandas()
    want = t.to_pandas().groupby("k", as_index=False)["v"].sum()
    assert _sorted_rows(got) == _sorted_rows(want)


def test_no_ring_join_is_left_to_ask_for(dist_ctx):
    """The ring join went after its one run on the chip (PR 45): asking
    for it is an invalid argument, and the error names what is left."""
    left, right = _join_tables(dist_ctx, 61)
    with pytest.raises(CylonError) as e:
        left.distributed_join(right, "inner", on="k", comm="ring")
    assert e.value.code == Code.Invalid
    assert "'shuffle'" in str(e.value) and "'broadcast'" in str(e.value)
    assert "'ring'" in str(e.value) and "or 'ring'" not in str(e.value)
    assert not hasattr(ct, "distributed_join_ring")


def _spy_key_bits(monkeypatch):
    """Every (forms, null_lanes) `_key_bits_program_fn` is asked for."""
    asked, real = [], D._key_bits_program_fn
    monkeypatch.setattr(
        D, "_key_bits_program_fn",
        lambda mesh, forms, lanes: asked.append((forms, lanes))
        or real(mesh, forms, lanes))
    return asked


@pytest.mark.parametrize("masked", [False, True], ids=["plain", "nullable"])
def test_join_asks_for_key_bits_without_null_lanes(dist_ctx, monkeypatch,
                                                   masked):
    """The groupby hands `_dist_key_bits` ``null_lanes`` since PR 41; a
    join hands none, so its key-bits programs keep their cache keys: the
    forms and all-False lanes, a nullable key too."""
    left, right = _join_tables(dist_ctx, 71)
    if masked:
        rng = np.random.default_rng(72)
        left, right = (Table(
            [Column(t._columns[0].data, t._columns[0].dtype,
                    jnp.asarray(rng.random(t.capacity) < 0.9), None, "k"),
             t._columns[1]], dist_ctx) for t in (left, right))
    asked = _spy_key_bits(monkeypatch)
    out = left.distributed_join(right, "inner", on="k")
    form = ("plain", "int32", False, masked)
    assert asked == [((form,), (False,))] * 2
    ref = left.to_pandas().dropna().merge(right.to_pandas().dropna(), on="k")
    assert out.row_count == len(ref)


def test_groupby_lanes_ask_for_a_null_lane_a_nullable_key(dist_ctx,
                                                          monkeypatch):
    """`_group_key_spec` / `_group_key_operands` where the keys can be
    read off the lanes (steered: on the CPU `sort_carries_index` says
    "index"): a column's bits, then its validity as a uint8 lane only
    where the column is nullable, out of the one key-bits program; no
    data or mask to gather from. Where the index rides: the bits alone,
    the data and every column's mask."""
    from cylon_tpu.ops import groupby as G

    t = shard.distribute(_frame(dist_ctx, 81), dist_ctx)
    k, v = t._columns[0], t._columns[1]
    valid = jnp.asarray(np.random.default_rng(82).random(t.capacity) < 0.8)
    cols = [k, Column(v.data, v.dtype, shard.pin(valid, dist_ctx), None, "v")]
    asked = _spy_key_bits(monkeypatch)
    args = (cols, [jnp.float32], (G.AggregationOp.SUM,), 24)
    spec = D._group_key_spec(*args)
    kbits, kdat, kval = D._group_key_operands(dist_ctx, cols, spec)
    assert spec is None and asked[-1][1] == (False, False)
    assert (len(kbits), len(kdat), len(kval)) == (2, 2, 2)
    real = G.sort_carries_index
    monkeypatch.setattr(G, "sort_carries_index",
                        lambda *a, **kw: real(*a, **kw, interpret=True))
    spec = D._group_key_spec(*args)
    kbits, kdat, kval = D._group_key_operands(dist_ctx, cols, spec)
    assert spec == ((np.dtype(np.int32), False, False),
                    (np.dtype(np.int32), False, True))
    assert asked[-1][1] == (False, True) and (kdat, kval) == ((), ())
    assert [str(b.dtype) for b in kbits] == ["uint32", "uint32", "uint8"]
    assert np.array_equal(np.asarray(kbits[2]), np.asarray(valid))
    # the second column's lane holds the nulls' extreme, as `sort_keys` does
    assert (np.asarray(kbits[1])[~np.asarray(valid)] == 0xFFFFFFFF).all()
