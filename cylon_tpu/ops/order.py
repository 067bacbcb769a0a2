"""Ordering primitives: order-preserving bit normalization, multi-key
lexicographic argsort, and dense group-rank computation.

This replaces the reference's comparator/sort-kernel layer (reference:
cpp/src/cylon/arrow/arrow_comparator.hpp/.cpp `ArrowComparator`/
`TableRowComparator`; arrow_kernels.hpp:132-275 sort kernels;
util/sort.hpp quicksort) with a TPU-idiomatic design: every comparable
column is mapped to an unsigned integer array whose natural ordering equals
the column's value ordering ("ordered bits"), so ALL multi-column
comparisons become vectorized integer sorts — no per-row callbacks, no
branching, everything XLA-fusible.

Dense ranks are the workhorse: two tables' key columns are concatenated,
lexsorted once, and each distinct key row gets a dense integer id. Joins,
set ops and group-bys then operate on these int32 ids — one representation
for numeric, string (dictionary codes), temporal and multi-column keys.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from ..data.column import Column, refuse_planes, split_planes
from ..status import Code, CylonError

_WIDTH_UINT = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32, 8: jnp.uint64}


def ordered_bits(col: Column, descending: bool = False) -> jnp.ndarray:
    """Column wrapper over `ordered_bits_raw`."""
    if col.is_varbytes:
        # loud guard: a varbytes column has no single ordered-bits array —
        # order needs sort_prefix_keys, equality needs hash_keys
        raise CylonError(Code.TypeError,
                         "varbytes columns need sort_prefix_keys/hash_keys, "
                         "not ordered_bits")
    refuse_planes([col], "ordered_bits")  # two lanes: ordered_bits_planes
    return ordered_bits_raw(col.data, col.is_string, descending)


def ordered_bits_raw(x: jnp.ndarray, is_string: bool = False,
                     descending: bool = False) -> jnp.ndarray:
    """Map values to unsigned ints preserving value order (traceable —
    usable inside jit/shard_map programs).

    * unsigned ints: identity
    * signed ints: flip the sign bit
    * floats: IEEE total-order trick (flip all bits for negatives, sign bit
      for positives); -0.0 is normalized to +0.0 first so equality matches
      IEEE semantics
    * bool: widen to uint8
    * strings: dictionary codes are already rank-preserving (sorted vocab)

    Nulls are NOT handled here — callers combine with ``valid_mask``.
    """
    if is_string:
        out = x.astype(jnp.uint32)
    else:
        dt = x.dtype
        if dt == jnp.bool_:
            out = x.astype(jnp.uint8)
        elif jnp.issubdtype(dt, jnp.unsignedinteger):
            out = x
        elif jnp.issubdtype(dt, jnp.signedinteger):
            w = dt.itemsize
            u = _WIDTH_UINT[w]
            out = x.astype(u) ^ jnp.asarray(np.uint64(1) << (8 * w - 1), u)
        elif jnp.issubdtype(dt, jnp.floating):
            w = dt.itemsize
            u = _WIDTH_UINT[w]
            xz = jnp.where(x == 0, jnp.zeros((), dt), x)  # -0.0 -> +0.0
            bits = xz.view(u)
            sign = (bits >> (8 * w - 1)).astype(bool)
            allones = jnp.asarray(~np.uint64(0) >> (64 - 8 * w), u)
            signbit = jnp.asarray(np.uint64(1) << (8 * w - 1), u)
            out = jnp.where(sign, ~bits & allones, bits ^ signbit)
        else:
            raise CylonError(Code.TypeError, f"unorderable dtype {dt}")
    if descending:
        allones = jnp.asarray(~np.uint64(0) >> (64 - 8 * out.dtype.itemsize),
                              out.dtype)
        out = out ^ allones
    return out


def from_ordered_bits_raw(bits: jnp.ndarray, dtype,
                          is_string: bool = False) -> jnp.ndarray:
    """The inverse of `ordered_bits_raw` (ascending): the ``dtype``
    values whose ordered bits are ``bits`` (traceable). Exact but for a
    float's -0.0, which `ordered_bits_raw` folded into +0.0 (one key);
    NaNs keep their payload bits. A group-by whose sort carries the key
    as ordered bits reads its output key column off them with this
    (ops/groupby.sorted_segment_aggregate)."""
    dt = np.dtype(dtype)
    if is_string or dt == np.bool_:
        return bits.astype(dt)
    if jnp.issubdtype(dt, jnp.unsignedinteger):
        return bits
    w = dt.itemsize
    u = _WIDTH_UINT[w]
    signbit = jnp.asarray(np.uint64(1) << (8 * w - 1), u)
    if jnp.issubdtype(dt, jnp.signedinteger):
        return (bits ^ signbit).view(dt)
    if jnp.issubdtype(dt, jnp.floating):
        # a set top bit marks a value that was not negative
        return jnp.where((bits & signbit) != 0, bits ^ signbit,
                         ~bits).view(dt)
    raise CylonError(Code.TypeError, f"unorderable dtype {dt}")


def ordered_bits_planes(planes: jnp.ndarray, dtype
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """`ordered_bits_raw`'s twin for a 64-bit column held as word planes
    (data/column.py): the two ``uint32`` lanes ``(hi, lo)`` whose
    lexicographic order is the order of the ``dtype`` values, with no
    ``uint64`` (the chip has none). Signed: the sign bit of the high word
    flipped; float64: the IEEE total-order trick over both words, -0.0
    folded into +0.0 first; unsigned: the planes themselves."""
    dt = np.dtype(dtype)
    hi, lo = split_planes(planes)
    signbit = jnp.uint32(1 << 31)
    if jnp.issubdtype(dt, jnp.unsignedinteger):
        return hi, lo
    if jnp.issubdtype(dt, jnp.signedinteger):
        return hi ^ signbit, lo
    if jnp.issubdtype(dt, jnp.floating):
        hi = jnp.where((hi == signbit) & (lo == 0), jnp.uint32(0), hi)
        neg = (hi & signbit) != 0
        return (jnp.where(neg, ~hi, hi ^ signbit), jnp.where(neg, ~lo, lo))
    raise CylonError(Code.TypeError, f"unorderable dtype {dt}")


def from_ordered_bits_planes(hi: jnp.ndarray, lo: jnp.ndarray, dtype
                             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The inverse of `ordered_bits_planes`: the word planes ``(hi, lo)``
    of the ``dtype`` values whose ordered bits are ``(hi, lo)``; exact but
    for a float's -0.0, as `from_ordered_bits_raw`."""
    dt = np.dtype(dtype)
    signbit = jnp.uint32(1 << 31)
    if jnp.issubdtype(dt, jnp.unsignedinteger):
        return hi, lo
    if jnp.issubdtype(dt, jnp.signedinteger):
        return hi ^ signbit, lo
    if jnp.issubdtype(dt, jnp.floating):
        pos = (hi & signbit) != 0   # a set top bit: the value was not negative
        return (jnp.where(pos, hi ^ signbit, ~hi), jnp.where(pos, lo, ~lo))
    raise CylonError(Code.TypeError, f"unorderable dtype {dt}")


def sort_keys(cols: Sequence[Column],
              ascending: Optional[Sequence[bool]] = None,
              nulls_last: bool = True) -> List[jnp.ndarray]:
    """Per-column ordered-bit arrays with nulls pushed to one end.

    Null placement: each column's keys are widened by nothing — instead the
    null rows get the extreme value of the column's bit domain, and ties are
    broken by later keys, matching "nulls last/first" sort semantics.
    """
    out = []
    for i, c in enumerate(cols):
        desc = bool(ascending is not None and not ascending[i])
        k = ordered_bits(c, descending=desc)
        if c.validity is not None:
            w = k.dtype.itemsize
            extreme = jnp.asarray(~np.uint64(0) >> (64 - 8 * w), k.dtype) \
                if nulls_last else jnp.zeros((), k.dtype)
            k = jnp.where(c.validity, k, extreme)
        out.append(k)
    return out


def lexsort_indices(keys: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """Stable argsort by keys[0] (primary) then keys[1], ... (numpy lexsort
    convention reversed). Single fused `lax.sort` call — XLA sorts all
    operands together, so this is one O(n log n) device sort regardless of
    key count."""
    n = keys[0].shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    import jax.lax as lax

    res = lax.sort(tuple(keys) + (iota,), num_keys=len(keys))
    return res[-1]


def row_neq_sorted(sorted_keys: Sequence[jnp.ndarray],
                   sorted_valid: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Boolean array: row i differs from row i-1 (row 0 = True)."""
    n = sorted_keys[0].shape[0]
    neq = jnp.zeros(n, dtype=bool).at[0].set(True)
    for k in sorted_keys:
        d = jnp.zeros(n, dtype=bool).at[1:].set(k[1:] != k[:-1])
        neq = neq | d
    if sorted_valid is not None:
        d = jnp.zeros(n, dtype=bool).at[1:].set(
            sorted_valid[1:] != sorted_valid[:-1])
        neq = neq | d
    return neq


def dense_ranks(keys: Sequence[jnp.ndarray]) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dense group ids for each row (0-based, ordered by key order).

    Returns (gid, perm) where gid[i] is the rank of row i's key among the
    distinct keys and perm is the stable lexsort permutation.
    """
    perm = lexsort_indices(keys)
    sk = [k[perm] for k in keys]
    neq = row_neq_sorted(sk)
    gid_sorted = jnp.cumsum(neq.astype(jnp.int32)) - 1
    gid = jnp.zeros_like(gid_sorted).at[perm].set(gid_sorted)
    return gid, perm


def dense_ranks_two(keys_l: Sequence[jnp.ndarray],
                    keys_r: Sequence[jnp.ndarray]
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dense ranks over the UNION of two key sets: returns (gid_l, gid_r)
    on a shared id space, so cross-table equality is integer equality.

    This is the TPU replacement for the reference's hash-multimap build/
    probe (arrow_hash_kernels.hpp:48-225): instead of pointer-chasing a
    multimap, one fused sort of the concatenated keys yields ids that both
    sides share.
    """
    nl = keys_l[0].shape[0]
    cat = [jnp.concatenate([a, b]) for a, b in zip(keys_l, keys_r)]
    gid, _ = dense_ranks(cat)
    return gid[:nl], gid[nl:]
