"""TPC-H Query 1, the Pricing Summary Report (Clause 2.4.1), through the
plan: scan, WHERE on the ship date, two computed columns, GROUP BY two
keys with eight aggregates, ORDER BY the keys. Decimals are int64 counts
of hundredths and the QUERY carries the scales: ``1 - l_discount`` is
``100 - l_discount`` at scale 2, so ``disc_price`` is at scale 4 and
``charge`` at scale 6.

A program from before the plan's computed columns cannot run this query:
that is said here, when the file is loaded (before any data is made), and
the run ends at once with a non-zero exit code."""
from cylon_tpu.plan import LazyTable

if not hasattr(LazyTable, "with_columns"):
    raise SystemExit(
        "benchmarks/queries/tpch_q1.py: this program has no computed "
        "columns in its plan (LazyTable.with_columns): it cannot run "
        "TPC-H Q1")


def build(plan, tables, traffic):
    col = plan.col
    t = plan.scan(tables[traffic["table"]])
    return (t.filter(col("l_shipdate") <= int(traffic["shipdate_max"]))
            .with_columns({
                "disc_price": col("l_extendedprice")
                * (100 - col("l_discount")),
                "charge": col("disc_price") * (100 + col("l_tax"))})
            .groupby(["l_returnflag", "l_linestatus"],
                     ["l_quantity", "l_extendedprice", "disc_price",
                      "charge", "l_quantity", "l_extendedprice",
                      "l_discount", "l_quantity"],
                     ["sum", "sum", "sum", "sum", "mean", "mean", "mean",
                      "count"])
            .sort(["l_returnflag", "l_linestatus"]))
