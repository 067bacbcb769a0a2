"""TPC-H Query 12 (Clause 2.4.12) in numpy on the host: the plain
reference of configuration ``tpch-sf100-q12``. It imports nothing of the
engine.

Straightforward: mask LINEITEM by the five predicates (two ship modes, the
two date orders, the receipt year), look each surviving line's order up by
key (a binary search in the sorted order keys; every line's order has to
be there), and count by ship mode and priority class (1-URGENT and 2-HIGH
are "high", the other three "low").

What `compare` holds a result to (the configuration's guarantees): exactly
the ship modes with a surviving line, each once, in l_shipmode order - the
key column arrives as dictionary codes, the rank of the mode among the
column's sorted distinct modes; no nulls; both counts exact. `control` is
the same with both key columns rounded through float32 before the match
(the nearest precision below the configuration's 32-bit integers: keys
past 2^24 collide and a line takes a neighbour's priority)."""
import numpy as np

HIGH = ("1-URGENT", "2-HIGH")
NAMES = ("l_shipmode", "high_line_count", "low_line_count")
CHUNK = 1 << 22


def _vocabulary(strings):
    """The sorted distinct strings of a column, chunk by chunk."""
    seen = set()
    for lo in range(0, len(strings), CHUNK):
        seen.update(np.unique(strings[lo:lo + CHUNK]).tolist())
    return sorted(seen)


def _report(tables, traffic, key_dtype):
    orders = tables[traffic["orders"]]
    line = tables[traffic["lineitem"]]
    mode = line["l_shipmode"]
    receipt = line["l_receiptdate"]
    keep = np.zeros(len(mode), bool)
    for wanted in traffic["shipmodes"]:
        keep |= mode == wanted
    keep &= line["l_commitdate"] < receipt
    keep &= line["l_shipdate"] < line["l_commitdate"]
    keep &= receipt >= int(traffic["receiptdate_min"])
    keep &= receipt < int(traffic["receiptdate_max"])
    rows = np.flatnonzero(keep)
    okey = orders["o_orderkey"].astype(key_dtype)
    by_key = np.argsort(okey, kind="stable")
    in_order = okey[by_key]
    lkey = line["l_orderkey"][rows].astype(key_dtype)
    at = np.searchsorted(in_order, lkey)
    found = at < len(okey)
    found[found] = in_order[at[found]] == lkey[found]
    assert found.all(), "a line's order is not in ORDERS"
    high = np.isin(orders["o_orderpriority"][by_key[at]], HIGH)
    modes = _vocabulary(mode)
    groups, n_high, n_low = [], [], []
    for code, name in enumerate(modes):
        of = mode[rows] == name
        if of.any():
            groups.append(code)
            n_high.append(int((of & high).sum()))
            n_low.append(int((of & ~high).sum()))
    return {"modes": modes, "groups": groups, "high": n_high, "low": n_low,
            "rows_in": len(mode), "rows_kept": len(rows),
            "orders": len(okey)}


def reference(tables, config, traffic):
    return _report(tables, traffic, np.int64)


def control(tables, config, traffic):
    r = _report(tables, traffic, np.float32)
    return {"names": list(NAMES),
            "columns": [np.asarray(r["groups"], np.int32),
                        np.asarray(r["high"], np.int32),
                        np.asarray(r["low"], np.int32)],
            "nulls": 0}


def describe(ref):
    groups = " ".join(f"{ref['modes'][g]}={h}/{low}" for g, h, low in
                      zip(ref["groups"], ref["high"], ref["low"]))
    return (f"{len(ref['groups'])} groups (high/low {groups}) over "
            f"{ref['rows_kept']} of {ref['rows_in']} lines joined to "
            f"{ref['orders']} orders")


def rows_out(ref):
    return len(ref["groups"])


def compare(got, ref):
    cols = got["columns"]
    schema = int(len(cols) != 3)
    if not schema:
        schema = int(cols[0].dtype != np.int32 or cols[0].ndim != 1) \
            + sum(c.dtype not in (np.int32, np.int64) or c.ndim != 1
                  for c in cols[1:])
    numbers = [{"name": "schema_diff", "value": schema, "limit": 0},
               {"name": "nulls", "value": got["nulls"], "limit": 0}]
    if schema:
        return numbers
    groups, want = cols[0].tolist(), ref["groups"]
    # every group present once, none invented, in key order
    groups_diff = len(set(groups) ^ set(want)) \
        + len(groups) - len(set(groups)) + int(groups != sorted(groups))
    numbers.append({"name": "groups_diff", "value": groups_diff,
                    "limit": 0})
    if groups_diff:
        return numbers
    for name, col, theirs in (("high_count_diff", cols[1], ref["high"]),
                              ("low_count_diff", cols[2], ref["low"])):
        numbers.append({"name": name, "limit": 0,
                        "value": max((abs(int(a) - b) for a, b in
                                      zip(col.tolist(), theirs)),
                                     default=0)})
    return numbers
