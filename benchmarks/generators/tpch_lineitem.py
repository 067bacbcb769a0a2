"""TPC-H's LINEITEM (Standard Specification rev 3.0.1, Clause 1.4.1) as
Clause 4.2.3 populates it, for the columns a query of this benchmark
touches, from ``--seed`` with numpy: NOT dbgen's generator (its random
streams are not reproduced), the same value ranges and the same
dependencies between columns.

``rows * chips * scale`` rows, in order order: orders of 1-7 lines (uniform)
share an ``o_orderdate`` uniform in [1992-01-01, 1998-08-02];
``l_shipdate = o_orderdate + [1..121]``, ``l_receiptdate = l_shipdate +
[1..30]`` (made, not placed); ``l_quantity`` in [1..50]; ``l_discount`` in
[0.00..0.10]; ``l_tax`` in [0.00..0.08]; ``l_partkey`` uniform in
[1..SF * 200,000] and ``l_extendedprice = l_quantity * retailprice(
l_partkey)``, ``retailprice = (90000 + (partkey / 10 mod 20001) + 100 *
(partkey mod 1000)) / 100``; ``l_returnflag`` R or A at random when
``l_receiptdate <= 1995-06-17``, else N; ``l_linestatus`` O when
``l_shipdate > 1995-06-17``, else F.

The four DECIMAL(15,2) columns are int64 counts of hundredths, dates int32
days since 1970-01-01, the two char(1) columns numpy ``U1`` (the engine
dictionary-encodes them). Every column is drawn from a stream of its own
(seed, position in ``STREAMS``), and every seed is
the same work: the same rows, the same four groups, value ranges that
reach their ends at any size a cell runs."""
import numpy as np

CURRENT_DATE = 9298      # 1995-06-17, days since 1970-01-01
# one random stream a drawn quantity, by its position here
STREAMS = ("l_orderkey", "o_orderdate", "l_shipdate", "l_receiptdate",
           "l_quantity", "l_partkey", "l_returnflag", "l_discount", "l_tax")
ORDERDATE = (8035, 10440)   # 1992-01-01 .. 1998-08-02 (ENDDATE - 151 days)


def generate(config, traffic, chips, scale, seed):
    n = max(int(config["rows"] * chips * scale), 1024)

    def stream(col):
        return np.random.default_rng([seed, STREAMS.index(col)])

    # orders of 1..7 lines: enough of them for n rows, cut at n
    m = n // 3 + 8
    order = np.repeat(np.arange(m, dtype=np.int32),
                      stream("l_orderkey").integers(1, 8, m))[:n]
    assert len(order) == n
    orderdate = stream("o_orderdate").integers(
        ORDERDATE[0], ORDERDATE[1] + 1, m, dtype=np.int32)
    ship = orderdate[order] + stream("l_shipdate").integers(
        1, 122, n, dtype=np.int32)
    receipt = ship + stream("l_receiptdate").integers(1, 31, n,
                                                      dtype=np.int32)
    del order, orderdate
    quantity = stream("l_quantity").integers(1, 51, n, dtype=np.int64)
    partkey = stream("l_partkey").integers(
        1, int(config["scale_factor"]) * 200_000 + 1, n, dtype=np.int64)
    retail = 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)
    del partkey
    ra = stream("l_returnflag").integers(0, 2, n, dtype=np.int8)
    letters = np.array([ord("A"), ord("R"), ord("N"), ord("F"), ord("O")],
                       np.uint32)
    made = {
        "l_quantity": quantity * 100,
        "l_extendedprice": quantity * retail,
        "l_discount": stream("l_discount").integers(0, 11, n,
                                                    dtype=np.int64),
        "l_tax": stream("l_tax").integers(0, 9, n, dtype=np.int64),
        "l_returnflag": letters[np.where(receipt <= CURRENT_DATE, ra, 2)]
        .view("U1"),
        "l_linestatus": letters[3 + (ship > CURRENT_DATE)].view("U1"),
        "l_shipdate": ship,
    }
    return {"tables": {traffic["table"]: {c: made[c]
                                          for c in traffic["columns"]}}}
