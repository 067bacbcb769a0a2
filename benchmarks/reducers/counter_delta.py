"""The growth of the program's counters whose name starts with ``prefix``
(every label summed) over the traced queries, per query. None when the
program has no such counter in this cell: nothing to read."""


def reduce(run, spec):
    if run["counters"] is None or not run["traced_queries"]:
        return None
    hit = [v for k, v in run["counters"].items()
           if k.startswith(spec["prefix"])]
    if not hit:
        return None
    return sum(hit) / run["traced_queries"]
