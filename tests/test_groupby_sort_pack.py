"""The groupby's fused sort carries narrow integer columns inside the
key's spare bits (PR 35): `ops/groupby.sort_pack_plan` decides which
column rides in which word from the OBSERVED ranges (the key's from the
dense check's probe, the value columns' from one more, fetched at
`sync.groupby.valuerange`), `presort_groups` packs, sorts the fewer
operands and unpacks exactly, inside one program whose only static part
is the plan's structure.

The path is a function of ranges, not of the platform: every case here
runs on the CPU. `SORT_PACK_MIN_ROWS` (under it no packing is looked for)
is set to 0 for the packed side of a comparison and past every table for
the plain one.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cylon_tpu as ct
from cylon_tpu import telemetry
from cylon_tpu.ops import groupby as G
from cylon_tpu.ops import order

N = 3000
I32_MIN, I32_MAX = np.iinfo(np.int32).min, np.iinfo(np.int32).max
K = -1      # the key, in a plan's word

PLANS = [
    # key bits, value bits -> plan
    ("q5: key 20 + 3 + 4 in one word, the float alone",
     20, [3, 4, None], ((K, 0, 1), (2,))),
    ("key 31: two 8-bit columns share a word of their own",
     31, [8, 8], ((K,), (0, 1))),
    ("a 32-bit range rides alone, the narrow ones behind it share",
     None, [32, 5, 3], ((0,), (1, 2))),
    ("a float never packs", None, [None, 3, 4], ((0,), (1, 2))),
    ("no observed key: values only", None, [3, 4], ((0, 1),)),
    ("no observed key, one integer column: nothing to share", None,
     [3, None], None),
    ("a key that fills its word: nothing rides with it", 32, [3], None),
    ("a key that fills its word and two columns", 32, [3, 4],
     ((K,), (0, 1))),
    ("exactly the spare bits", 24, [8], ((K, 0),)),
    ("one bit more than the spare bits", 24, [9], None),
    ("first fit: the third column goes back to the key's word",
     20, [10, 16, 2], ((K, 0, 2), (1,))),
    ("a constant column takes no bits: it rides a full word", 32, [0],
     ((K, 0),)),
    ("three words", None, [20, 20, 12, 12, None, 30],
     ((0, 2), (1, 3), (4,), (5,))),
    ("only floats", 20, [None, None], None),
    ("no values", 20, [], None),
]


@pytest.mark.parametrize("case", PLANS, ids=[c[0] for c in PLANS])
def test_sort_pack_plan_cases(case):
    _name, key_bits, value_bits, want = case
    plan = G.sort_pack_plan(key_bits, value_bits)
    assert plan == want
    n_keys, n_values = 1, len(value_bits)
    keys, values = (None,) * n_keys, (None,) * n_values
    plain = G.sort_operand_count(keys, None, values, (None,) * n_values,
                                 False)
    assert plain == n_keys + n_values
    packed = G.sort_operand_count(keys, None, values, (None,) * n_values,
                                  False, plan)
    assert packed == plain - G.packed_members(plan)
    if plan is not None:
        members = sorted(m for word in plan for m in word if m != K)
        assert members == list(range(n_values))     # every column once


def test_a_column_packs_by_its_array_dtype():
    for dt in ("bool", "int8", "uint8", "int16", "uint16", "int32", "uint32"):
        assert G.packs(np.dtype(dt))
    for dt in ("int64", "uint64", "float16", "float32", "float64"):
        assert not G.packs(np.dtype(dt))


@pytest.mark.parametrize("lo,hi,bits", [
    (7, 7, 0), (0, 1, 1), (-4, 3, 3), (-4, 4, 4), (0, 255, 8), (0, 256, 9),
    (1, 1_000_000, 20), (0, 2 ** 32 - 1, 32)])
def test_range_bits(lo, hi, bits):
    assert G.range_bits(lo, hi) == bits
    assert hi - lo < 1 << bits and (bits == 0 or hi - lo >= 1 << (bits - 1))


def test_params_put_the_key_on_top_and_stack_the_rest_from_bit_0():
    plan = ((K, 0, 2), (1,), (3, 4))
    got = G.sort_pack_params(plan, 1000, 20, [5, None, 9, 70, 80],
                             [3, None, 4, 32, 0])
    np.testing.assert_array_equal(got, np.array([
        [1000, 12, 2 ** 20 - 1], [5, 0, 7], [9, 3, 15],
        [70, 0, 2 ** 32 - 1], [80, 31, 0]], np.uint32))
    assert got.dtype == np.uint32


@pytest.mark.parametrize("dtype,lo,is_string,lane", [
    ("int32", -5, False, 2 ** 31 - 5), ("int8", -128, False, 0),
    ("int16", 7, False, 2 ** 15 + 7), ("uint32", 9, False, 9),
    ("uint16", 65100, False, 65100), ("int32", 3, True, 3),
    ("bool", 1, False, 1)])
def test_key_lane_lo_is_the_ordered_lane_of_lo(dtype, lo, is_string, lane):
    assert G.key_lane_lo(lo, np.dtype(dtype), is_string) == lane
    x = jnp.asarray(np.array([lo]).astype(dtype))
    assert int(order.ordered_bits_raw(x, is_string)[0]) == lane


# --------------------------------------------------------------------------
# presort_groups itself: the packed sort hands on what the plain one does
# --------------------------------------------------------------------------

def _rows(ks, values_s, valids_s, emit_s):
    """The live sorted rows as one sortable array of bit patterns."""
    cols = [np.asarray(k).astype(np.int64) for k in ks]
    for v in values_s:
        v = np.asarray(v)
        cols.append(v.view(np.int32).astype(np.int64) if v.dtype.kind == "f"
                    else v.astype(np.int64))
    cols += [np.asarray(v).astype(np.int64) for v in valids_s
             if v is not None]
    rows = np.stack(cols, axis=1)
    if emit_s is not None:
        rows = rows[np.asarray(emit_s)]
    return rows[np.lexsort(rows.T[::-1])]


@pytest.mark.parametrize("masked", [False, True])
def test_presort_groups_packed_hands_on_what_the_plain_sort_does(masked):
    rng = np.random.default_rng(5)
    key = rng.integers(-3000, 3000, N).astype(np.int32)
    emit = jnp.asarray(rng.random(N) < 0.7) if masked else None
    if masked:      # dead rows' keys lie outside the live rows' range
        key = np.where(np.asarray(emit), key, rng.choice(
            [I32_MIN, I32_MAX, 40_000, -40_000], N)).astype(np.int32)
    live = key if emit is None else key[np.asarray(emit)]
    values = (jnp.asarray(rng.integers(-5, 6, N).astype(np.int8)),
              jnp.asarray(rng.normal(size=N).astype(np.float32)),
              jnp.asarray(rng.integers(100, 116, N).astype(np.int32)))
    valids = (None, jnp.asarray(rng.random(N) < 0.8), None)
    lanes = (order.ordered_bits_raw(jnp.asarray(key)),)
    lo, hi = int(live.min()), int(live.max())
    key_bits = G.range_bits(lo, hi)
    ranges = np.asarray(G.value_range_probe((values[0], values[2]))).tolist()
    bits = [G.range_bits(*ranges[0]), None, G.range_bits(*ranges[1])]
    assert bits == [4, None, 4]
    plan = G.sort_pack_plan(key_bits, bits)
    assert plan == ((K, 0, 2), (1,))
    params = jnp.asarray(G.sort_pack_params(
        plan, G.key_lane_lo(lo, np.int32, False), key_bits,
        [ranges[0][0], None, ranges[1][0]], bits))
    plain = G.presort_groups(lanes, emit, values, valids, index=False)
    packed = G.presort_groups(lanes, emit, values, valids, index=False,
                              plan=plan, params=params)
    for a, b in zip(plain[0] + plain[3], packed[0] + packed[3]):
        assert a.dtype == b.dtype and a.shape == b.shape
    assert int(plain[5]) == int(packed[5])
    np.testing.assert_array_equal(
        _rows(plain[3], plain[0], plain[1], plain[2]),
        _rows(packed[3], packed[0], packed[1], packed[2]))
    # the same run starts: the live keys come out in the same order
    for got, want in zip(packed[3], plain[3]):
        n_live = N if emit is None else int(np.asarray(packed[2]).sum())
        np.testing.assert_array_equal(np.asarray(got)[:n_live],
                                      np.asarray(want)[:n_live])
    np.testing.assert_array_equal(np.asarray(packed[4]),
                                  np.asarray(plain[4]))


# --------------------------------------------------------------------------
# Table.groupby: packed against plain, bit for bit
# --------------------------------------------------------------------------

def _col(data, name, valid=None):
    return ct.Column.from_numpy(np.asarray(data), name,
                                None if valid is None else np.asarray(valid))


def _counted():
    snap = telemetry.metrics_snapshot()
    out = {"operands": snap.get("cylon_groupby_sort_operands_total", 0),
           "packed": snap.get("cylon_groupby_sort_packed_columns_total", 0)}
    for site in ("groupby.keyrange", "groupby.valuerange", "groupby.groups"):
        out[site] = snap.get('cylon_host_syncs_total{site="%s"}' % site, 0)
    return out


def _delta(before):
    after = _counted()
    return {k: after[k] - before[k] for k in after if after[k] != before[k]}


def _wide_key(rng, lo=1, hi=3000, dtype=np.int32):
    key = rng.integers(lo, hi + 1, N).astype(dtype)
    key[:2] = (lo, hi)      # the range is exact
    return key


def _ints(rng, lo, hi, dtype=np.int32):
    x = rng.integers(lo, hi + 1, N).astype(dtype)
    x[:2] = (lo, hi)
    return x


def _whole_floats(rng):
    """float32 values whose sums are exact in any order."""
    return rng.integers(-64, 64, N).astype(np.float32)


def _q5(rng):
    return dict(key=_wide_key(rng),
                vals=[(_ints(rng, 1, 5), None), (_ints(rng, 1, 15), None),
                      (_whole_floats(rng), None)],
                ops=["sum", "sum", "sum"], operands=(4, 2), packed=2,
                probes_key=True)


def _negative_lo(rng):
    return dict(key=_wide_key(rng, -9000, -4000),
                vals=[(_ints(rng, -1000, -990), None),
                      (_ints(rng, I32_MIN, I32_MIN + 3), None)],
                ops=["sum", "sum"], operands=(3, 1), packed=2,
                probes_key=True)


def _lo_is_hi(rng):
    return dict(key=_wide_key(rng),
                vals=[(np.full(N, -7, np.int32), None),
                      (_whole_floats(rng), None),
                      (np.full(N, I32_MAX, np.int32), None)],
                ops=["sum", "sum", "max"], operands=(4, 3), packed=1,
                probes_key=False)      # MAX: the key is not observed


def _pow2(rng):
    # the key's range is exactly 2^24 values and the column's 2^8: 32 bits
    return dict(key=_wide_key(rng, -5, 2 ** 24 - 6),
                vals=[(_ints(rng, 10, 265), None)],
                ops=["sum"], operands=(2, 1), packed=1, probes_key=True)


def _pow2_plus_1(rng):
    # one value more: 9 bits do not fit the key's 8 spare ones, and the
    # column rides alone, whole
    return dict(key=_wide_key(rng, -5, 2 ** 24 - 6),
                vals=[(_ints(rng, 10, 266), None)],
                ops=["sum"], operands=(2, 2), packed=0, probes_key=True)


def _key_pow2_plus_1(rng):
    # 2^24 + 1 keys: 25 bits, and the 8-bit column finds 7 spare ones and
    # rides alone; the 3-bit column behind it still fits the key's word
    return dict(key=_wide_key(rng, -5, 2 ** 24 - 5),
                vals=[(_ints(rng, 10, 265), None),
                      (_ints(rng, -3, 3), None)],
                ops=["sum", "sum"], operands=(3, 2), packed=1,
                probes_key=True)


def _row_mask(rng):
    mask = rng.random(N) < 0.6
    key = _wide_key(rng)
    mask[:2] = True
    # dead rows hold keys far outside the live rows' range
    key = np.where(mask, key, rng.choice([I32_MIN, I32_MAX, -1, 70_000], N)
                   ).astype(np.int32)
    return dict(key=key, mask=mask,
                vals=[(_ints(rng, 1, 5), None), (_ints(rng, -8, 7), None),
                      (_whole_floats(rng), None)],
                ops=["sum", "sum", "sum"], operands=(5, 3), packed=2,
                probes_key=True)


def _null_garbage(rng):
    # the null slots of v0 hold what no valid row does, the extremes of
    # int32: the probe ignores masks, the range is 32 bits, v0 rides alone
    # and whole; v1 still rides with the key
    valid = rng.random(N) < 0.7
    v0 = np.where(valid, _ints(rng, 0, 9),
                  rng.choice([I32_MIN, I32_MAX], N)).astype(np.int32)
    return dict(key=_wide_key(rng),
                vals=[(v0, valid), (_ints(rng, 1, 15), None)],
                ops=["sum", "sum"], operands=(4, 3), packed=1,
                probes_key=True)


def _null_garbage_narrow(rng):
    # garbage that still fits: the null slots' values ride and come back
    valid = rng.random(N) < 0.7
    v0 = np.where(valid, _ints(rng, 0, 9), 200).astype(np.int32)
    v1valid = rng.random(N) < 0.5
    return dict(key=_wide_key(rng),
                vals=[(v0, valid), (_ints(rng, 1, 15), v1valid)],
                ops=["sum", "sum"], operands=(5, 3), packed=2,
                probes_key=True)


def _narrow_dtypes(rng):
    # int8 / uint16 / bool columns: the static conditions send them to
    # the sort with no key probe, and they share a word among themselves
    return dict(key=_wide_key(rng),
                vals=[(_ints(rng, -128, 127, np.int8), None),
                      (_ints(rng, 65000, 65535, np.uint16), None),
                      (rng.random(N) < 0.4, None),
                      (_ints(rng, -128, 127, np.int8), None)],
                ops=["min", "max", "max", "sum"], operands=(5, 2), packed=3,
                probes_key=False)


def _all_ops(rng):
    v = _ints(rng, -40, 40)
    w = _ints(rng, 0, 1000)
    valid = rng.random(N) < 0.8
    return dict(key=_wide_key(rng),
                vals=[(v, None), (w, valid), (v, None), (w, valid),
                      (v, None), (w, valid)],
                cols=[1, 2, 1, 2, 1, 2],
                ops=["sum", "count", "mean", "min", "max", "mean"],
                # key, v, w, v, w, v, w, w's mask x3 (x64 on: no key probe)
                operands=None, packed=None, probes_key=None)


def _full_range(rng):
    # a column over all of int32 is never truncated: it rides alone
    return dict(key=_wide_key(rng),
                vals=[(_ints(rng, I32_MIN, I32_MAX), None),
                      (_ints(rng, 0, 3), None)],
                ops=["sum", "sum"], operands=(3, 2), packed=1,
                probes_key=True)


def _uint32_high(rng):
    hi = np.uint32(2 ** 32 - 1)
    return dict(key=_wide_key(rng),
                vals=[((hi - rng.integers(0, 50, N).astype(np.uint32)), None),
                      (_ints(rng, 0, 3, np.uint32), None)],
                ops=["max", "min"], operands=(3, 2), packed=1,
                probes_key=False)


def _dictionary_value(rng):
    words = np.array(["pear", "apple", "fig", "kiwi"], object)
    return dict(key=_wide_key(rng),
                vals=[(words[rng.integers(0, 4, N)], None),
                      (_ints(rng, 0, 3), None)],
                ops=["min", "max"], operands=(3, 2), packed=1,
                probes_key=False)


def _int16_key(rng):
    return dict(key=_wide_key(rng, -2000, 2000, np.int16),
                vals=[(_ints(rng, 1, 5), None), (_ints(rng, 1, 15), None)],
                ops=["sum", "sum"], operands=(3, 1), packed=2,
                probes_key=True)


def _uint32_key(rng):
    key = (np.uint32(2 ** 32 - 5000) + rng.integers(0, 4000, N)
           ).astype(np.uint32)
    return dict(key=key,
                vals=[(_ints(rng, 1, 5), None), (_whole_floats(rng), None)],
                ops=["sum", "sum"], operands=(3, 2), packed=1,
                probes_key=True)


def _dictionary_key(rng):
    # few enough for a dictionary (half the rows), past DENSE_MAX_SLOTS
    words = np.array([f"w{i:04d}" for i in range(1450)], object)
    return dict(key=words[rng.integers(0, 1450, N)],
                vals=[(_ints(rng, 1, 5), None), (_ints(rng, 1, 15), None)],
                ops=["sum", "sum"], operands=(3, 1), packed=2,
                probes_key=True)


def _nullable_key(rng):
    # a nullable key's range is not kept: its null rows hold the lane's
    # extreme. The value columns still share a word
    return dict(key=_wide_key(rng), key_valid=rng.random(N) < 0.9,
                vals=[(_ints(rng, 1, 5), None), (_ints(rng, 1, 15), None)],
                ops=["sum", "sum"], operands=(4, 3), packed=1,
                probes_key=True)


def _two_keys(rng):
    return dict(key=_wide_key(rng), key2=_ints(rng, 0, 3),
                vals=[(_ints(rng, 1, 5), None), (_ints(rng, 1, 15), None)],
                ops=["sum", "sum"], operands=(4, 3), packed=1,
                probes_key=True)    # several keys are probed too (PR 42):
    #                                 3,000 x 4 slots say "sort"


CASES = {f.__name__[1:]: f for f in (
    _q5, _negative_lo, _lo_is_hi, _pow2, _pow2_plus_1, _key_pow2_plus_1,
    _row_mask, _null_garbage, _null_garbage_narrow, _narrow_dtypes,
    _all_ops, _full_range, _uint32_high, _dictionary_value, _int16_key,
    _uint32_key, _dictionary_key, _nullable_key, _two_keys)}


def _build(ctx, case):
    cols = [_col(case["key"], "k", case.get("key_valid"))]
    if "key2" in case:
        cols.append(_col(case["key2"], "k2"))
    by = list(range(len(cols)))
    first = len(cols)
    cols += [_col(x, f"v{i}", valid)
             for i, (x, valid) in enumerate(case["vals"])]
    mask = case.get("mask")
    table = ct.Table(cols, ctx, None if mask is None else jnp.asarray(mask))
    # "cols": the aggregated columns by their place in "vals", from 1
    vals = [first - 1 + c for c in case["cols"]] if "cols" in case \
        else list(range(first, len(cols)))
    return table, by, vals


def _same_table(got, want):
    assert got.capacity == want.capacity
    np.testing.assert_array_equal(np.asarray(got.row_mask),
                                  np.asarray(want.row_mask))
    live = np.asarray(want.row_mask)
    for g, w in zip(got._columns, want._columns):
        assert (g.name, g.dtype, g.data.dtype) == (w.name, w.dtype,
                                                   w.data.dtype)
        assert (g.validity is None) == (w.validity is None)
        ok = live if w.validity is None else live & np.asarray(w.validity)
        if g.validity is not None:
            np.testing.assert_array_equal(
                np.asarray(g.validity) & live, ok)
        # bit for bit wherever a value is live and valid
        np.testing.assert_array_equal(np.asarray(g.data)[ok],
                                      np.asarray(w.data)[ok])
        if g.dictionary is not None:
            np.testing.assert_array_equal(g.dictionary, w.dictionary)


def _both(monkeypatch, table, by, vals, ops):
    monkeypatch.setattr(G, "SORT_PACK_MIN_ROWS", 1 << 40)
    before = _counted()
    plain = table.groupby(by, vals, ops)
    plain_moved = _delta(before)
    monkeypatch.setattr(G, "SORT_PACK_MIN_ROWS", 0)
    before = _counted()
    packed = table.groupby(by, vals, ops)
    return plain, plain_moved, packed, _delta(before)


@pytest.mark.parametrize("x64", [True, False], ids=["x64", "x32"])
@pytest.mark.parametrize("name", list(CASES))
def test_packed_groupby_equals_the_plain_one_bit_for_bit(
        local_ctx, monkeypatch, name, x64):
    """The same table through the plain sort and the packed one: the same
    slots, masks and dtypes, every live value equal bit for bit (the
    float columns here hold whole numbers, whose sums do not feel the
    order of a group's rows). And what the host counted: operands, packed
    columns, the fetches."""
    case = CASES[name](np.random.default_rng(len(name)))
    with jax.enable_x64(x64):
        table, by, vals = _build(local_ctx, case)
        plain, plain_moved, packed, moved = _both(
            monkeypatch, table, by, vals, case["ops"])
        _same_table(packed, plain)
    assert plain.row_count >= 2
    assert "groupby.valuerange" not in plain_moved \
        and "packed" not in plain_moved
    assert moved.get("packed", 0) == \
        plain_moved["operands"] - moved["operands"]
    if case["operands"] is None:        # the ops decide what is probed
        assert moved["packed"] >= 1
        return
    # without the index, which the CPU's segment path always carries
    assert (plain_moved["operands"] - 1, moved["operands"] - 1) == \
        case["operands"]
    assert moved.get("packed", 0) == case["packed"]
    assert moved.get("groupby.keyrange", 0) == int(case["probes_key"])
    assert moved["groupby.valuerange"] == 1 and moved["groupby.groups"] == 1


def test_under_the_row_gate_nothing_is_probed(local_ctx):
    """A table of fewer than SORT_PACK_MIN_ROWS rows sorts as it always
    did: no probe of the value columns, no packed column."""
    assert N < G.SORT_PACK_MIN_ROWS
    table, by, vals = _build(local_ctx, _q5(np.random.default_rng(1)))
    before = _counted()
    table.groupby(by, vals, ["sum"] * 3)
    assert _delta(before) == {"operands": 5, "groupby.keyrange": 1,
                              "groupby.groups": 1}


@pytest.mark.parametrize("name", ["only_floats", "one_integer_no_key_range",
                                  "key_fills_its_word"])
def test_no_probe_where_no_word_could_be_saved(local_ctx, monkeypatch, name):
    """The value probe is paid only where the static side of the plan
    says a word could be saved."""
    rng = np.random.default_rng(2)
    monkeypatch.setattr(G, "SORT_PACK_MIN_ROWS", 0)
    if name == "only_floats":
        key, vals, ops = _wide_key(rng), [_whole_floats(rng)] * 2, ["sum"] * 2
    elif name == "one_integer_no_key_range":    # MIN: no key probe
        key, vals, ops = _wide_key(rng), [_ints(rng, 1, 5)], ["min"]
    else:
        key = _wide_key(rng, I32_MIN, I32_MAX)
        vals, ops = [_ints(rng, 1, 5)], ["sum"]
    table = ct.Table([_col(key, "k")] + [_col(v, f"v{i}")
                                         for i, v in enumerate(vals)],
                     local_ctx)
    before = _counted()
    out = table.groupby(0, list(range(1, 1 + len(vals))), ops)
    moved = _delta(before)
    assert out.row_count >= 2
    assert "groupby.valuerange" not in moved and "packed" not in moved
    assert moved["operands"] == 1 + len(vals) + 1


def test_a_sharded_table_packs_by_a_probe_of_the_whole_table(
        dist_ctx, monkeypatch):
    """Across chips the first per-shard sort is packed by the same
    functions, from ONE probe of the whole sharded table before the
    `shard_map` program is dispatched (PR 43; before, its sorts carried
    what they always did). Here the CPU's gather path: the key rides as
    two lanes and is not observed, the two value columns share a word,
    the merge is not looked at. The cell's shape and the other cases are
    in tests/test_cell_groupby_q5_w4.py."""
    monkeypatch.setattr(G, "SORT_PACK_MIN_ROWS", 0)
    rng = np.random.default_rng(3)
    cols = {"k": _wide_key(rng), "a": _ints(rng, 1, 5),
            "b": _ints(rng, 1, 15)}
    t = ct.Table.from_pydict(dist_ctx, cols)
    before = _counted()
    out = t.groupby(0, [1, 2], ["sum", "sum"])
    moved = _delta(before)
    assert moved["groupby.valuerange"] == 1 and moved["packed"] == 1
    got = out.to_pandas()
    assert len(got) == len(np.unique(cols["k"]))
    assert int(got["a"].sum()) == int(cols["a"].sum())
    assert int(got["b"].sum()) == int(cols["b"].sum())


def test_one_plan_structure_is_one_program_whatever_the_ranges(
        local_ctx, monkeypatch):
    """Two tables of different ranges, offsets and widths whose columns
    ride in the same words share ONE compiled sort; a table whose plan
    differs compiles its own."""
    monkeypatch.setattr(G, "SORT_PACK_MIN_ROWS", 0)
    rng = np.random.default_rng(4)

    def table(key_lo, key_hi, a, b):
        return ct.Table([_col(_wide_key(rng, key_lo, key_hi), "k"),
                         _col(_ints(rng, *a), "a"), _col(_ints(rng, *b), "b"),
                         _col(_whole_floats(rng), "c")], local_ctx)

    first = table(1, 3000, (1, 5), (1, 15))
    first.groupby(0, [1, 2, 3], ["sum"] * 3)
    programs = G.presort_groups_jit._cache_size()
    before = _counted()
    # another key range, other offsets, other widths: the same words
    drifted = table(-70_000, 900_000, (-300, 200), (1000, 1003))
    out = drifted.groupby(0, [1, 2, 3], ["sum"] * 3)
    assert _delta(before)["packed"] == 2 and out.row_count >= 2
    assert G.presort_groups_jit._cache_size() == programs
    want = {}
    key = np.asarray(drifted._columns[0].data)
    for j in (1, 2):
        sums = {}
        for k, v in zip(key, np.asarray(drifted._columns[j].data)):
            sums[int(k)] = sums.get(int(k), 0) + int(v)
        want[j] = sums
    live = np.asarray(out.row_mask)
    got_key = np.asarray(out._columns[0].data)[live]
    for j in (1, 2):
        got = np.asarray(out._columns[j].data)[live]
        assert dict(zip(got_key.tolist(), got.tolist())) == want[j]
    # b no longer fits beside the key and a: another structure
    other = table(1, 2 ** 24, (1, 5), (0, 2 ** 20))
    before = _counted()
    other.groupby(0, [1, 2, 3], ["sum"] * 3)
    assert _delta(before)["packed"] == 1
    assert G.presort_groups_jit._cache_size() == programs + 1


@pytest.mark.parametrize("name", ["q5", "row_mask", "int16_key",
                                  "dictionary_key", "uint32_key"])
def test_stream_path_reads_its_keys_off_the_unpacked_lanes(
        local_ctx, monkeypatch, name):
    """As a TPU backend runs it (the stream reduce step, here under the
    Pallas interpreter; the sort carries no index and the key columns are
    read back off the sorted key lanes): with the key packed into a word
    with its riders, the lanes that go out are the unpacked ones, and the
    result equals the plain sort's."""
    case = CASES[name](np.random.default_rng(len(name)))
    real_path, real_agg = G.reduce_path, G.sorted_segment_aggregate
    monkeypatch.setattr(G, "reduce_path", lambda dts, ops, n, interpret=False:
                        real_path(dts, ops, n, True))
    monkeypatch.setattr(G, "sorted_segment_aggregate_jit",
                        functools.partial(real_agg, interpret=True))
    with jax.enable_x64(False):
        table, by, vals = _build(local_ctx, case)
        plain, plain_moved, packed, moved = _both(
            monkeypatch, table, by, vals, case["ops"])
        _same_table(packed, plain)
    assert (plain_moved["operands"], moved["operands"]) == case["operands"]
    assert moved["packed"] == case["packed"]
    snap = telemetry.metrics_snapshot()
    assert snap['cylon_groupby_reduce_path_total{path="stream"}'] >= 2
