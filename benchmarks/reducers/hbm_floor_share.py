"""A whole query's share of the memory roofline: the seconds the chip
would need to read the query's input columns once and write its result
once at the peak HBM rate, over the seconds an operation ran on the device
per query (each chip moves its share of the bytes). The bytes are the
algorithm's need, computed from the cell's shapes by the harness
(input_bytes: the host arrays placed; result_bytes: live result rows x
their width), not the compiler's estimate."""


def query_bytes(run):
    return run["input_bytes"] + run["result_bytes"]


def reduce(run, spec):
    trace = run["trace"]
    if trace is None or not trace.n_queries or run["peaks"] is None:
        return None
    busy = trace.busy_s()
    if not busy:
        return None
    floor_s = query_bytes(run) / run["chips"] \
        / (run["peaks"]["hbm_gbytes_per_s"] * 1e9)
    return 100.0 * floor_s / (busy / trace.n_queries)
