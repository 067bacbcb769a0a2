"""The exact inner join of two tables on their first columns where keys
and payloads are 8 bytes wide, in numpy alone: a sort-merge of the 64-bit
keys (both sides sorted, every left row's run of equal right keys found by
binary search), summarised as the row count and an order-independent
64-bit fingerprint: the sum mod 2^64, over the result's rows, of a mix of
the 64-BIT PATTERNS of (left key, left payload, right key, right payload).
Two keys are equal only if all 64 bits are; a payload that went through
float32, a key that lost its high word, a dropped or doubled row, or a
payload paired with another row's key changes the sum.

How a result column is read (``compare``): a 1-D array of the column's
own 8-byte dtype, or ONE ``uint32[2, n]`` array of word planes, plane 0
the high words and plane 1 the low: the form in which an engine without a
64-bit type on its device holds such a column exactly. Its ``nbytes`` is
8 a value either way. The harness hands over arrays and no logical type,
so the planes of an int64 and of a float64 column are told apart by their
bits alone, which is what the fingerprint compares."""
import numpy as np

_C = [np.uint64(c) for c in (0x9E3779B97F4A7C15, 0xC2B2AE3D27D4EB4F,
                             0x165667B19E3779F9, 0xBF58476D1CE4E5B9,
                             0x94D049BB133111EB, 0xD6E8FEB86659FD93)]


def _bits(a):
    """The 64-bit patterns of a column, or None if it is no 8-byte
    column in either form."""
    a = np.asarray(a)
    if a.ndim == 2 and a.shape[0] == 2 and a.dtype == np.uint32:
        return (a[0].astype(np.uint64) << np.uint64(32)) \
            | a[1].astype(np.uint64)
    if a.ndim == 1 and a.dtype.itemsize == 8 and a.dtype.kind in "iuf":
        return np.ascontiguousarray(a).view(np.uint64)
    return None


def fingerprint(kl, v, kr, w):
    """Over four arrays of 64-bit patterns (``_bits``)."""
    with np.errstate(over="ignore"):
        h = kl * _C[0]
        h ^= kr * _C[1]
        h += v * _C[2]
        h ^= h >> np.uint64(29)
        h *= _C[3]
        h += w * _C[4]
        h ^= h >> np.uint64(32)
        h *= _C[5]
        h ^= h >> np.uint64(29)
        return int(h.sum(dtype=np.uint64))


def _joined(tables, cast=None):
    (lkn, lvn), (rkn, rvn) = (list(tables[s]) for s in ("left", "right"))
    left, right = tables["left"], tables["right"]
    lorder, rorder = np.argsort(left[lkn]), np.argsort(right[rkn])
    lk, rk = left[lkn][lorder], right[rkn][rorder]
    lo = np.searchsorted(rk, lk, "left")
    cnt = np.searchsorted(rk, lk, "right") - lo   # matches of a left row
    rows = int(cnt.sum())
    out_start = np.cumsum(cnt) - cnt
    ri = np.repeat(lo - out_start, cnt) + np.arange(rows)
    v, w = left[lvn][lorder], right[rvn][rorder]
    if cast is not None:
        v, w = (x.astype(cast).astype(x.dtype) for x in (v, w))
    return [np.repeat(lk, cnt), np.repeat(v, cnt), rk[ri], w[ri]]


def reference(tables, config, traffic):
    cols = _joined(tables)
    return {"rows": len(cols[0]),
            "fingerprint": fingerprint(*(_bits(c) for c in cols)),
            "dtypes": [c.dtype for c in cols]}


def control(tables, config, traffic):
    """The same join with both payloads carried in float32: the nearest
    precision below the float64 the configuration states."""
    return {"names": ["lt-0", "lt-1", "rt-2", "rt-3"],
            "columns": _joined(tables, cast=np.float32), "nulls": 0}


def describe(ref):
    return f"{ref['rows']} joined rows, fingerprint {ref['fingerprint']:#018x}"


def rows_out(ref):
    return ref["rows"]


def compare(got, ref):
    """Numbers compared, each with its limit: all exact, so all 0. A
    column counts as of its type if it is the reference's 8-byte dtype or
    a ``uint32[2, n]`` array of word planes (module docstring)."""
    cols = [np.asarray(c) for c in got["columns"]]
    bits = [_bits(c) for c in cols]
    schema = int(len(cols) != 4) + sum(
        b is None or (c.ndim == 1 and c.dtype != d)
        for c, b, d in zip(cols, bits, ref["dtypes"]))
    rows = cols[0].shape[-1] if cols else 0
    same = (schema == 0 and rows == ref["rows"]
            and fingerprint(*bits) == ref["fingerprint"])
    return [
        {"name": "schema_diff", "value": schema, "limit": 0},
        {"name": "rows_diff", "value": abs(rows - ref["rows"]), "limit": 0},
        {"name": "nulls", "value": got["nulls"], "limit": 0},
        {"name": "fingerprint_diff", "value": int(not same), "limit": 0},
    ]
