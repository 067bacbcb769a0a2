"""The exact inner join of two tables on their first columns, in numpy
alone (one sort of the right side, counts by key), summarised as the row count and an
order-independent 64-bit fingerprint: the sum mod 2^64, over the result's
rows, of a mix of the BIT PATTERNS of (left key, left payload, right key,
right payload). Every pair once, no duplicate, payload bits unchanged: a
payload that went through bfloat16, a dropped or doubled row, or a payload
paired with another row's key changes the sum."""
import numpy as np

_C = [np.uint64(c) for c in (0x9E3779B97F4A7C15, 0xC2B2AE3D27D4EB4F,
                             0x165667B19E3779F9, 0xBF58476D1CE4E5B9,
                             0x94D049BB133111EB, 0xD6E8FEB86659FD93)]


def _bits(a):
    return np.ascontiguousarray(a).view(np.uint32).astype(np.uint64)


def fingerprint(kl, v, kr, w):
    with np.errstate(over="ignore"):
        h = _bits(kl) * _C[0]
        h ^= _bits(kr) * _C[1]
        h += _bits(v) * _C[2]
        h ^= h >> np.uint64(29)
        h *= _C[3]
        h += _bits(w) * _C[4]
        h ^= h >> np.uint64(32)
        h *= _C[5]
        h ^= h >> np.uint64(29)
        return int(h.sum(dtype=np.uint64))


def _order(keys):
    """argsort(keys, stable) by one sort of (key << 32 | row): numpy sorts
    64-bit words several times faster than it argsorts."""
    packed = (keys.astype(np.uint64) << np.uint64(32)) \
        | np.arange(len(keys), dtype=np.uint64)
    packed.sort()
    return (packed & np.uint64(0xFFFFFFFF)).astype(np.int64)


def _pairs(left_key, right_key):
    """(left row, right row) of every matching pair: the right rows in key
    order, and for every left row the run of right rows with its key,
    found by counting (the keys are integers over a range of about the
    row count)."""
    base = min(int(left_key.min()), int(right_key.min()))
    span = max(int(left_key.max()), int(right_key.max())) - base + 1
    if span > 1 << 31 or len(right_key) >= 1 << 32:
        raise ValueError(f"key range {span} too wide to count")
    lk = left_key.astype(np.int64) - base
    rk = right_key.astype(np.int64) - base
    rorder = _order(rk)
    per_key = np.bincount(rk, minlength=span)
    run_start = np.cumsum(per_key) - per_key
    cnt = per_key[lk]                       # matches of every left row
    rows = int(cnt.sum())
    out_start = np.cumsum(cnt) - cnt
    li = np.repeat(np.arange(len(lk)), cnt)
    ri = rorder[np.repeat(run_start[lk] - out_start, cnt) + np.arange(rows)]
    return li, ri


def _joined(tables, cast=None):
    (lkn, lvn), (rkn, rvn) = (list(tables[s]) for s in ("left", "right"))
    left, right = tables["left"], tables["right"]
    li, ri = _pairs(left[lkn], right[rkn])
    v, w = left[lvn][li], right[rvn][ri]
    if cast is not None:
        v, w = (x.astype(cast).astype(x.dtype) for x in (v, w))
    return [left[lkn][li], v, right[rkn][ri], w]


def reference(tables, config, traffic):
    cols = _joined(tables)
    return {"rows": len(cols[0]), "fingerprint": fingerprint(*cols),
            "dtypes": [c.dtype for c in cols]}


def control(tables, config, traffic):
    """The same join with both payloads carried in bfloat16: the nearest
    precision below the float32 the configuration states."""
    import ml_dtypes

    cols = _joined(tables, cast=ml_dtypes.bfloat16)
    return {"names": ["lt-0", "lt-1", "rt-2", "rt-3"], "columns": cols,
            "nulls": 0}


def describe(ref):
    return f"{ref['rows']} joined rows, fingerprint {ref['fingerprint']:#018x}"


def rows_out(ref):
    return ref["rows"]


def compare(got, ref):
    """Numbers compared, each with its limit: all exact, so all 0."""
    cols = got["columns"]
    schema = int(len(cols) != 4) + sum(
        c.dtype != d for c, d in zip(cols, ref["dtypes"]))
    rows = len(cols[0]) if cols else 0
    same = (schema == 0 and rows == ref["rows"]
            and fingerprint(*cols) == ref["fingerprint"])
    return [
        {"name": "schema_diff", "value": schema, "limit": 0},
        {"name": "rows_diff", "value": abs(rows - ref["rows"]), "limit": 0},
        {"name": "nulls", "value": got["nulls"], "limit": 0},
        {"name": "fingerprint_diff", "value": int(not same), "limit": 0},
    ]
