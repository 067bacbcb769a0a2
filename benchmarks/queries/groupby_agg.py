"""scan(table) GROUP BY the traffic's key, one aggregate a value column."""


def build(plan, tables, traffic):
    return plan.scan(tables[traffic["table"]]).groupby(
        traffic["by"], list(traffic["values"]), list(traffic["ops"]))
