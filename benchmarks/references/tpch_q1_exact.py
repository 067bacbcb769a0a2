"""TPC-H Query 1 (Clause 2.4.1) in numpy int64 and Python integers: the
plain reference of configuration ``tpch-sf100-q1``. It imports nothing of
the engine.

Decimals are int64 counts of hundredths. Per block of rows: the filter
``l_shipdate <= shipdate_max``, ``disc_price = l_extendedprice * (100 -
l_discount)`` (scale 4), ``charge = disc_price * (100 + l_tax)`` (scale
6), and per (l_returnflag, l_linestatus) group the count and the five
int64 sums, added up over the blocks as Python integers. No sum can pass
2^63 at a size a cell runs (asserted: rows x the largest row value).

What `compare` holds a result to (the configuration's guarantees): exactly
the groups present, each once, in (l_returnflag, l_linestatus) order - the
two key columns arrive as dictionary codes, the rank of the letter among
the column's sorted distinct letters; no nulls; ``count_order`` exact; the
four sums exact as integers at scales 2, 2, 4, 6, whether they arrive as
``uint32[2, g]`` word planes or as int64; the three averages within 4 x
2^-24 relative of sum / count taken exactly. `control` is the same report
with its sums accumulated in float32: wrong by many orders of magnitude of
the limit (0)."""
import numpy as np

BLOCK = 1 << 22
AVG_UNITS = 4.0
U32 = 2.0 ** -24
SUMS = ("sum_qty", "sum_base_price", "sum_disc_price", "sum_charge")
AVGS = ("avg_qty", "avg_price", "avg_disc")
NAMES = ("l_returnflag", "l_linestatus") + SUMS + AVGS + ("count_order",)


def _codes(letters):
    """(sorted distinct letters, each row's rank among them)."""
    vocab, codes = np.unique(letters.view(np.uint32), return_inverse=True)
    return vocab.view("U1"), codes.astype(np.int64)


def _report(table, traffic, accumulate):
    flags, fcode = _codes(table["l_returnflag"])
    status, scode = _codes(table["l_linestatus"])
    gid = fcode * len(status) + scode
    ngroups = len(flags) * len(status)
    n = len(gid)
    keep = table["l_shipdate"] <= int(traffic["shipdate_max"])
    qty, price = table["l_quantity"], table["l_extendedprice"]
    disc, tax = table["l_discount"], table["l_tax"]
    worst = int(price.max()) * 100 * (100 + int(tax.max()))
    assert n * worst < 2 ** 63, "a sum of this table may pass 2^63"
    count = [0] * ngroups
    sums = [[0] * 5 for _ in range(ngroups)]
    for lo in range(0, n, BLOCK):
        at = slice(lo, lo + BLOCK)
        g = np.where(keep[at], gid[at], -1)
        disc_price = price[at] * (100 - disc[at])
        cols = (qty[at], price[at], disc_price,
                disc_price * (100 + tax[at]), disc[at])
        for group in range(ngroups):
            rows = g == group
            c = int(rows.sum())
            if not c:
                continue
            count[group] += c
            for j, x in enumerate(cols):
                sums[group][j] = accumulate(sums[group][j], x[rows])
    present = [group for group in range(ngroups) if count[group]]
    return {
        "flags": flags, "status": status,
        "groups": [(group // len(status), group % len(status))
                   for group in present],
        "count": [count[group] for group in present],
        # sum_qty, sum_base_price, sum_disc_price, sum_charge, sum_disc
        "sums": [[int(sums[group][j]) for group in present]
                 for j in range(5)],
        "rows_in": n, "rows_kept": int(keep.sum()),
    }


def _exact(total, x):
    return total + int(x.sum(dtype=np.int64))


def _float32(total, x):
    return np.float32(total) + x.astype(np.float32).sum(dtype=np.float32)


def reference(tables, config, traffic):
    return _report(tables[traffic["table"]], traffic, _exact)


def _planes(values):
    v = np.array([x & ((1 << 64) - 1) for x in values], dtype=np.uint64)
    return np.stack([(v >> np.uint64(32)).astype(np.uint32),
                     (v & np.uint64(0xFFFFFFFF)).astype(np.uint32)])


def control(tables, config, traffic):
    """The same report with every sum accumulated in float32, block by
    block: the nearest precision below the exact 64-bit sums the
    configuration states."""
    r = _report(tables[traffic["table"]], traffic, _float32)
    count = np.asarray(r["count"], np.int64)
    cols = [np.array([g[0] for g in r["groups"]], np.int32),
            np.array([g[1] for g in r["groups"]], np.int32)]
    cols += [_planes(r["sums"][j]) for j in range(4)]
    cols += [(np.asarray(r["sums"][j], np.float64) / count)
             .astype(np.float32) for j in (0, 1, 4)]
    cols.append(count.astype(np.int32))
    return {"names": list(NAMES), "columns": cols, "nulls": 0}


def describe(ref):
    groups = " ".join(f"{ref['flags'][f]}/{ref['status'][s]}"
                      for f, s in ref["groups"])
    return (f"{len(ref['groups'])} groups ({groups}) over "
            f"{ref['rows_kept']} of {ref['rows_in']} rows, 4 sums, 3 "
            f"averages, 1 count")


def rows_out(ref):
    return len(ref["groups"])


def _integers(col):
    """A 64-bit column as Python integers: ``uint32[2, g]`` word planes
    (hi, lo) of an int64, or an int64 array; None for anything else."""
    if col.dtype == np.uint32 and col.ndim == 2 and col.shape[0] == 2:
        out = []
        for hi, lo in zip(col[0].tolist(), col[1].tolist()):
            v = (hi << 32) | lo
            out.append(v - (1 << 64) if v >> 63 else v)
        return out
    if col.dtype == np.int64 and col.ndim == 1:
        return col.tolist()
    return None


def compare(got, ref):
    cols = got["columns"]
    sums = [_integers(c) for c in cols[2:6]] if len(cols) == 10 else []
    schema = int(len(cols) != 10)
    if not schema:
        schema = sum(c.dtype != np.int32 or c.ndim != 1
                     for c in (cols[0], cols[1], cols[9])) \
            + sum(s is None for s in sums) \
            + sum(c.dtype != np.float32 or c.ndim != 1 for c in cols[6:9])
    numbers = [{"name": "schema_diff", "value": schema, "limit": 0},
               {"name": "nulls", "value": got["nulls"], "limit": 0}]
    if schema:
        return numbers
    groups = list(zip(cols[0].tolist(), cols[1].tolist()))
    want = ref["groups"]
    # every group present once, none invented, in key order
    groups_diff = len(set(groups) ^ set(want)) \
        + len(groups) - len(set(groups)) + int(groups != sorted(groups))
    numbers.append({"name": "groups_diff", "value": groups_diff,
                    "limit": 0})
    if groups_diff:
        return numbers
    count = ref["count"]
    numbers.append({"name": "count_diff.count_order",
                    "value": max(abs(a - b) for a, b in
                                 zip(cols[9].tolist(), count)),
                    "limit": 0})
    for name, mine, theirs in zip(SUMS, sums, ref["sums"]):
        numbers.append({"name": f"sum_diff.{name}",
                        "value": max(abs(a - b)
                                     for a, b in zip(mine, theirs)),
                        "limit": 0})
    for name, col, j in zip(AVGS, cols[6:9], (0, 1, 4)):
        err = 0.0
        for x, s, c in zip(col.tolist(), ref["sums"][j], count):
            mean = s / c       # Python's int / int: correctly rounded
            bound = max(AVG_UNITS * U32 * abs(mean), np.finfo(float).tiny)
            e = abs(x - mean) / bound
            err = max(err, e if e == e else np.inf)
        numbers.append({"name": f"avg_err_over_bound.{name}",
                        "value": float(err), "limit": 1.0})
    return numbers
