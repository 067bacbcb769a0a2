"""scan(left) JOIN scan(right) ON the traffic's key column, inner: all
columns of both sides materialised."""


def build(plan, tables, traffic):
    return plan.scan(tables["left"]).join(plan.scan(tables["right"]),
                                          "inner", on=traffic["on"])
