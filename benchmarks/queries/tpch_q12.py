"""TPC-H Query 12, Shipping Modes and Order Priority (Clause 2.4.12),
through the plan IN THE SPECIFICATION'S ORDER: ORDERS joined to LINEITEM
on the order key, THEN one filter of the five predicates, THEN the two
``CASE WHEN ... THEN 1 ELSE 0`` columns, GROUP BY and ORDER BY the ship
mode. Nothing is placed under the join by hand: where a predicate runs is
the planner's work.

A join's result names its columns by position (``lt-<i>``, ``rt-<j>``);
`named` gives a column of either table its name there.

A program that cannot compare two columns or has no `case_when` cannot run
this query: that is said here, when the file is loaded (before any data is
made), and the run ends at once with a non-zero exit code."""
from cylon_tpu import plan as _plan

if not hasattr(_plan, "case_when"):
    raise SystemExit(
        "benchmarks/queries/tpch_q12.py: this program's plan has no "
        "column-against-column predicate and no case_when "
        "(cylon_tpu.plan.case_when): it cannot run TPC-H Q12")


def build(plan, tables, traffic):
    col, case_when = plan.col, plan.case_when
    orders = tables[traffic["orders"]]
    lineitem = tables[traffic["lineitem"]]
    joined = plan.scan(orders).join(
        plan.scan(lineitem), left_on="o_orderkey", right_on="l_orderkey")
    names = orders.column_names + lineitem.column_names

    def named(name):
        return col(joined.schema[names.index(name)])

    first, second = traffic["shipmodes"]
    priority, shipmode = named("o_orderpriority"), named("l_shipmode")
    urgent, high = "1-URGENT", "2-HIGH"
    return (joined
            .filter(((shipmode == first) | (shipmode == second))
                    & (named("l_commitdate") < named("l_receiptdate"))
                    & (named("l_shipdate") < named("l_commitdate"))
                    & (named("l_receiptdate")
                       >= int(traffic["receiptdate_min"]))
                    & (named("l_receiptdate")
                       < int(traffic["receiptdate_max"])))
            .with_columns({
                "high_line_count": case_when((priority == urgent)
                                             | (priority == high)),
                "low_line_count": case_when((priority != urgent)
                                            & (priority != high))})
            .groupby(shipmode.ref, ["high_line_count", "low_line_count"],
                     ["sum", "sum"])
            .sort(shipmode.ref))
