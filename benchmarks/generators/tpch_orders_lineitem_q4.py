"""TPC-H's ORDERS and LINEITEM (Standard Specification rev 3.0.1, Clauses
1.4.1 and 4.2.3) for the columns Query 4 reads: `tpch_orders_lineitem`'s
population (loaded by path, as that file loads `tpch_lineitem`): the same
share of SF100's orders (`order_keys`), the same fixed multiset of
lines-an-order counts (`line_counts`), and THE SAME STREAMS from
``--seed``, one a drawn quantity by its position in that file's
``STREAMS``. So every column both generators make is equal to the row for
a seed and a size: ``o_orderkey``, ``o_orderpriority``, ``l_orderkey``,
``l_commitdate``, ``l_receiptdate``. This one returns ``o_orderdate``
too (the accepted generator draws it and places only the line dates made
from it), and draws no ship mode: Query 4 never reads it (2.1 GB of ``U7``
at the cell's size).

``l_shipdate = o_orderdate + [1..121]`` is drawn, for ``l_receiptdate =
l_shipdate + [1..30]``, and not returned. Every seed is the same work:
the same order keys, as many orders and as many lines."""
import importlib.util
import os

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "bench_generators_tpch_orders_lineitem_shared",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "tpch_orders_lineitem.py"))
_shared = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_shared)
ORDERDATE = _shared.ORDERDATE
PRIORITIES = _shared.PRIORITIES
STREAMS = _shared.STREAMS
ORDERS_A_SF = _shared.ORDERS_A_SF
order_keys = _shared.order_keys
line_counts = _shared.line_counts


def generate(config, traffic, chips, scale, seed):
    members = int(config["scale_factor"]) * ORDERS_A_SF \
        // int(config["chips_in_deployment"]) * chips
    n = min(max(int(config["rows"]["orders"] * chips * scale), 1024),
            members)

    def stream(col):
        return np.random.default_rng([seed, STREAMS.index(col)])

    key = order_keys(n, members)
    counts = line_counts(n, stream("lines"))
    orderdate = stream("o_orderdate").integers(
        ORDERDATE[0], ORDERDATE[1] + 1, n, dtype=np.int32)
    priority = np.array(PRIORITIES, "U15")[
        stream("o_orderpriority").integers(0, len(PRIORITIES), n)]
    lines = int(counts.sum(dtype=np.int64))
    l_date = np.repeat(orderdate, counts)
    receipt = l_date + stream("l_shipdate").integers(1, 122, lines,
                                                     dtype=np.int32)
    commit = l_date + stream("l_commitdate").integers(30, 91, lines,
                                                      dtype=np.int32)
    del l_date
    receipt += stream("l_receiptdate").integers(1, 31, lines,
                                                dtype=np.int32)
    made = {
        "orders": {"o_orderkey": key, "o_orderdate": orderdate,
                   "o_orderpriority": priority},
        "lineitem": {"l_orderkey": np.repeat(key, counts),
                     "l_commitdate": commit, "l_receiptdate": receipt},
    }
    return {"tables": {name: {c: made[name][c] for c in cols}
                       for name, cols in traffic["tables"].items()}}
