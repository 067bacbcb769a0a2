"""TPC-H Query 4, Order Priority Checking (Clause 2.4.4), in numpy on the
host: the plain reference of configuration ``tpch-sf100-q4``. It imports
nothing of the engine.

Straightforward: the orders of the quarter (``orderdate_min <= o_orderdate
< orderdate_max``), the lines received after their commit date
(``l_commitdate < l_receiptdate``), the sorted distinct order keys of
those lines, and for each order of the quarter whether ITS key is among
them (a binary search); the orders that are, counted by priority.

What `compare` holds a result to (the configuration's guarantees): exactly
the priorities with a counted order, each once, in o_orderpriority order -
the key column arrives as dictionary codes, the rank of the priority among
the column's sorted distinct priorities; no nulls; ``order_count`` exact.
`control` is the same with both key columns rounded through float32 before
the membership test (the nearest precision below the configuration's
32-bit integers: order keys 32 apart past 2^29 round to one float32, and
an order is counted for its neighbour's late line)."""
import numpy as np

NAMES = ("o_orderpriority", "order_count")


def _report(tables, traffic, key_dtype):
    orders = tables[traffic["orders"]]
    line = tables[traffic["lineitem"]]
    date = orders["o_orderdate"]
    quarter = np.flatnonzero((date >= int(traffic["orderdate_min"]))
                             & (date < int(traffic["orderdate_max"])))
    late = line["l_commitdate"] < line["l_receiptdate"]
    late_keys = np.unique(line["l_orderkey"][late].astype(key_dtype))
    okey = orders["o_orderkey"][quarter].astype(key_dtype)
    at = np.searchsorted(late_keys, okey)
    counted = at < len(late_keys)
    counted[counted] = late_keys[at[counted]] == okey[counted]
    priority = orders["o_orderpriority"]
    priorities = sorted(np.unique(priority).tolist())
    of_counted = priority[quarter[counted]]
    groups, counts = [], []
    for code, name in enumerate(priorities):
        n = int((of_counted == name).sum())
        if n:
            groups.append(code)
            counts.append(n)
    return {"priorities": priorities, "groups": groups, "counts": counts,
            "orders": len(date), "lines": len(late),
            "orders_in_quarter": len(quarter),
            "lines_late": int(late.sum()),
            "orders_counted": int(counted.sum())}


def reference(tables, config, traffic):
    return _report(tables, traffic, np.int64)


def control(tables, config, traffic):
    r = _report(tables, traffic, np.float32)
    return {"names": list(NAMES),
            "columns": [np.asarray(r["groups"], np.int32),
                        np.asarray(r["counts"], np.int32)],
            "nulls": 0}


def describe(ref):
    groups = " ".join(f"{ref['priorities'][g]}={n}" for g, n in
                      zip(ref["groups"], ref["counts"]))
    return (f"{len(ref['groups'])} groups ({groups}): "
            f"{ref['orders_counted']} of {ref['orders_in_quarter']} orders "
            f"of the quarter (of {ref['orders']}) have a late line; "
            f"{ref['lines_late']} of {ref['lines']} lines are late")


def rows_out(ref):
    return len(ref["groups"])


def compare(got, ref):
    cols = got["columns"]
    schema = int(len(cols) != 2)
    if not schema:
        schema = int(cols[0].dtype != np.int32 or cols[0].ndim != 1) \
            + int(cols[1].dtype not in (np.int32, np.int64)
                  or cols[1].ndim != 1)
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
    numbers.append({"name": "order_count_diff", "limit": 0,
                    "value": max((abs(int(a) - b) for a, b in
                                  zip(cols[1].tolist(), ref["counts"])),
                                 default=0)})
    return numbers
