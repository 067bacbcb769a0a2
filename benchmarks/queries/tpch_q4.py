"""TPC-H Query 4, Order Priority Checking (Clause 2.4.4), through the plan
IN THE SPECIFICATION'S ORDER: ORDERS under ``EXISTS (select * from
lineitem where l_orderkey = o_orderkey and l_commitdate < l_receiptdate)``
- a SEMI join of ORDERS with the filtered LINEITEM on the order key - THEN
one filter of the two date predicates, ABOVE the join, THEN ``count(*)``
by ``o_orderpriority``, ORDER BY the priority. Nothing is placed under
the join by hand: where a predicate runs is the planner's work.

A semi join's result holds its LEFT side's columns alone, named by
position (``lt-<i>``); `named` gives a column of ORDERS its name there.
``count(*)`` counts the order key, which is never null.

A program whose joins stop at the full outer join cannot run this query:
that is said here, when the file is loaded (before any data is made), and
the run ends at once with a non-zero exit code."""
import cylon_tpu as _ct

if not hasattr(_ct.JoinType, "SEMI"):
    raise SystemExit(
        "benchmarks/queries/tpch_q4.py: this program has no semi join "
        "(cylon_tpu.JoinType stops at "
        f"{list(_ct.JoinType)[-1].name}): it cannot run TPC-H Q4")


def build(plan, tables, traffic):
    col = plan.col
    orders = tables[traffic["orders"]]
    lineitem = tables[traffic["lineitem"]]
    late = plan.scan(lineitem).filter(
        col("l_commitdate") < col("l_receiptdate"))
    kept = plan.scan(orders).join(late, join_type="semi",
                                  left_on="o_orderkey",
                                  right_on="l_orderkey")

    def named(name):
        return col(kept.schema[orders.column_names.index(name)])

    date, priority = named("o_orderdate"), named("o_orderpriority")
    return (kept
            .filter((date >= int(traffic["orderdate_min"]))
                    & (date < int(traffic["orderdate_max"])))
            .groupby(priority.ref, [named("o_orderkey").ref], ["count"])
            .sort(priority.ref))
