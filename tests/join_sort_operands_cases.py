"""The cases of `test_stream_join_answer_bit_for_bit` and what they
share: the stream join's answer, key columns included, compared cell by
cell, bit patterns too, with a nested-loop reference over the host
arrays, on the Pallas interpreter. The cases (~25 s each: every one
compiles its programs anew) are spread over two test files by the
join's `how`, so that `--dist loadfile` hands them to two workers:
`test_join_sort_operands.py` (inner, left) and
`test_join_sort_operands_outer.py` (right, outer).
"""
from collections import Counter

import jax.numpy as jnp
import numpy as np

import cylon_tpu as ct
from cylon_tpu import dtypes, telemetry
from cylon_tpu.ops import join as _join

COUNTER = "cylon_join_sort_operands_total"
KINDS = ["int32", "uint32", "float32_negzero", "int32_nulls", "promoted",
         "dictionary", "hash"]

def _col(arr, name, validity=None):
    arr = np.asarray(arr)
    if arr.dtype.kind in "UO":
        return ct.Column.from_numpy(arr, name, validity)
    return ct.Column(jnp.asarray(arr), dtypes.from_np_dtype(arr.dtype),
                     None if validity is None else jnp.asarray(validity),
                     None, name)


def _cells(arr, valid):
    """One hashable value a row: None for a null, a string, or the bit
    pattern of a number (so -0.0 and +0.0 are different answers)."""
    arr = np.asarray(arr)
    if arr.dtype.kind in "UO":
        vals = [None if v is None else str(v) for v in arr]
    else:
        vals = [int(v) for v in arr.view(f"u{arr.dtype.itemsize}")]
    if valid is None:
        return vals
    return [v if ok else None for v, ok in zip(vals, np.asarray(valid))]


def _reference(lk, lkv, lv, rk, rkv, rw, how):
    """Nested-loop equi-join over host arrays: rows (lk, lv, rk, rw);
    keys compare by value (-0.0 == +0.0), a null key matches nothing."""
    ok_l = np.ones(len(lk), bool) if lkv is None else lkv
    ok_r = np.ones(len(rk), bool) if rkv is None else rkv
    cl = list(zip(_cells(lk, lkv), _cells(lv, None)))
    cr = list(zip(_cells(rk, rkv), _cells(rw, None)))
    rows, hit_r = [], np.zeros(len(rk), bool)
    for i in range(len(lk)):
        m = ok_r & (rk == lk[i]) if ok_l[i] else np.zeros(len(rk), bool)
        hit_r |= m
        rows += [cl[i] + cr[j] for j in np.flatnonzero(m)]
        if not m.any() and how in ("left", "outer"):
            rows.append(cl[i] + (None, None))
    if how in ("right", "outer"):
        rows += [(None, None) + cr[j] for j in np.flatnonzero(~hit_r)]
    return Counter(rows)


def _table_rows(t):
    live = np.ones(t.capacity, bool) if t.row_mask is None \
        else np.asarray(t.row_mask)
    cols = []
    for c in t._columns:
        host = c.to_numpy() if c.is_string else np.asarray(c.data)
        valid = None if c.validity is None else np.asarray(c.validity)
        cols.append(_cells(host, None if c.is_string else valid))
    return Counter(row for row, ok in zip(zip(*cols), live) if ok)


NL, NR = 90, 70


def _inputs(kind):
    """(left key, its validity, right key, its validity, algorithm,
    operands the plan sort carries)."""
    rng = np.random.default_rng(len(kind))
    pick = lambda vals, dt, n: np.asarray(vals, dt)[
        rng.integers(0, len(vals), n)]
    i32 = [-2 ** 31, -7, -1, 0, 1, 5, 99, 2 ** 31 - 1]
    if kind == "int32":
        return pick(i32, np.int32, NL), None, pick(i32, np.int32, NR), \
            None, "sort", 3
    if kind == "uint32":
        u32 = [0, 3, 2 ** 31, 2 ** 32 - 1, 17]
        return pick(u32, np.uint32, NL), None, pick(u32, np.uint32, NR), \
            None, "sort", 3
    if kind == "float32_negzero":     # a float key keeps its lane
        f32 = [-np.inf, -1.5, -0.0, 0.0, 1e-30, 2.5, np.inf]
        return pick(f32, np.float32, NL), None, pick(f32, np.float32, NR), \
            None, "sort", 4
    if kind == "int32_nulls":         # key d + v, payload d
        return pick(i32, np.int32, NL), rng.random(NL) < 0.8, \
            pick(i32, np.int32, NR), rng.random(NR) < 0.8, "sort", 5
    if kind == "promoted":            # int16 joins int32 as int32 copies:
        # no key map; the int16 column itself comes back by the index
        return pick([-7, -1, 0, 1, 5, 99], np.int16, NL), None, \
            pick(i32, np.int32, NR), None, "sort", 4
    if kind == "dictionary":
        fruit = ["pear", "apple", "fig", "kiwi", "plum"]
        return pick(fruit[:4], object, NL), None, \
            pick(fruit[1:], object, NR), None, "sort", 4
    assert kind == "hash"             # h1, h2, tag, verify, two slots
    return pick(i32, np.int32, NL), None, pick(i32, np.int32, NR), None, \
        "hash", 6


def check_stream_join_answer(local_ctx, monkeypatch, kind, how):
    """One case of `test_stream_join_answer_bit_for_bit`."""
    lk, lkv, rk, rkv, algo, operands = _inputs(kind)
    rng = np.random.default_rng(5)
    lv = rng.normal(size=NL).astype(np.float32)
    rw = rng.normal(size=NR).astype(np.float32)
    left = ct.Table([_col(lk, "k", lkv), _col(lv, "v")], local_ctx)
    right = ct.Table([_col(rk, "k", rkv), _col(rw, "w")], local_ctx)
    monkeypatch.setattr(_join, "STREAM_PLAN", True)
    before = telemetry.metrics_snapshot().get(COUNTER, 0)
    got = left.join(right, how, algo, on=["k"])
    assert telemetry.metrics_snapshot()[COUNTER] - before == operands
    for c, src in zip(got._columns, left._columns + right._columns):
        assert (c.dtype, c.data.dtype, c.is_string) == \
            (src.dtype, src.data.dtype, src.is_string)
    want = _reference(lk, lkv, lv, rk, rkv, rw, how)
    assert _table_rows(got) == want
    assert sum(want.values()) > max(NL, NR)   # duplicates on both sides
