"""TPC-H's ORDERS and LINEITEM (Standard Specification rev 3.0.1, Clauses
1.4.1 and 4.2.3) for the columns Query 12 reads, one chip's share of a
deployment that hash-partitions both tables by order key, from ``--seed``
with numpy: NOT dbgen's generator (its random streams are not
reproduced), the same value ranges and the same dependencies between
columns.

WHICH ORDERS. The specification numbers its orders i = 1 .. SF * 1,500,000
and gives order i the sparse key ``(i >> 3) << 5 | (i & 7)`` (8 keys used
of every 32; SF100's largest is 600,000,000). The chip holds the orders
with ``i mod 8 == 1``, whose keys are 32 m + 1: an eighth of them, placed
by ``(o_orderkey mod 32) mod 8``. A run at ``--scale`` < 1 holds fewer of
those members, in runs of up to 256 consecutive ones spread evenly over
the whole of SF100's key range: a rehearsal has keys past 2^29 too, 32
apart, where a float32 cannot tell neighbours apart (the control).

EVERY SEED IS THE SAME WORK: the same order keys, the same number of
orders and of lines (a fixed multiset of lines-an-order counts, as many of
each of 1..7 as go and the rest 4, which the seed only orders), every
line's order present; order dates, priorities, the three line dates and
the ship modes are drawn from the seed, one stream a quantity.

``o_orderdate`` uniform in `tpch_lineitem.ORDERDATE`; ``l_shipdate =
o_orderdate + [1..121]``, ``l_commitdate = o_orderdate + [30..90]``,
``l_receiptdate = l_shipdate + [1..30]``; ``o_orderpriority`` one of the
five priorities and ``l_shipmode`` one of the seven modes, uniform, as the
strings the specification publishes (numpy ``U15`` / ``U7``: the engine
dictionary-encodes them). Keys are int32 (the INTEGER of the
specification's dss.ddl), dates int32 days since 1970-01-01. Rows in key
order, as dbgen emits them."""
import importlib.util
import os

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "bench_generators_tpch_lineitem_constants",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "tpch_lineitem.py"))
_lineitem = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_lineitem)
ORDERDATE = _lineitem.ORDERDATE

PRIORITIES = ("1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW")
SHIPMODES = ("REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB")
# one random stream a drawn quantity, by its position here
STREAMS = ("lines", "o_orderdate", "o_orderpriority", "l_shipdate",
           "l_commitdate", "l_receiptdate", "l_shipmode")
ORDERS_A_SF = 1_500_000
RUN = 256         # consecutive members of the share in a rehearsal's run
MEMBER = 1        # the chip holds the orders with i mod 8 == MEMBER


def order_keys(n, members):
    """The sparse keys of ``n`` of the share's ``members`` orders, in key
    order and each once: run r of ``RUN`` neighbours starts at member
    ``r * RUN * members // n``, so the runs never meet (members >= n),
    span the whole range, and are all of the members where n == members."""
    k = np.arange(n, dtype=np.int64)
    m = (k - k % RUN) * members // n + k % RUN
    i = 8 * m + MEMBER
    return ((i >> 3) << 5 | (i & 7)).astype(np.int32)


def line_counts(n, rng):
    """Lines an order: as many orders of each count 1..7 as go into ``n``
    and the rest with 4, in an order drawn from ``rng``."""
    counts = np.repeat(np.arange(1, 8, dtype=np.int8), n // 7)
    counts = np.concatenate([counts, np.full(n - len(counts), 4, np.int8)])
    return rng.permutation(counts)


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
    ship = l_date + stream("l_shipdate").integers(1, 122, lines,
                                                  dtype=np.int32)
    commit = l_date + stream("l_commitdate").integers(30, 91, lines,
                                                      dtype=np.int32)
    del l_date
    receipt = ship + stream("l_receiptdate").integers(1, 31, lines,
                                                      dtype=np.int32)
    made = {
        "orders": {"o_orderkey": key, "o_orderpriority": priority},
        "lineitem": {
            "l_orderkey": np.repeat(key, counts),
            "l_shipdate": ship, "l_commitdate": commit,
            "l_receiptdate": receipt,
            "l_shipmode": np.array(SHIPMODES, "U7")[
                stream("l_shipmode").integers(0, len(SHIPMODES), lines)],
        },
    }
    return {"tables": {name: {c: made[name][c] for c in cols}
                       for name, cols in traffic["tables"].items()}}
