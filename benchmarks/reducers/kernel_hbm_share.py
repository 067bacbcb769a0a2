"""One kernel's share of the memory roofline: the seconds a chip would
need to move the kernel's bytes at the peak HBM rate, over the seconds the
kernel's events (``patterns`` on the device line ``line``) ran per traced
query, mean over the chips, in %. The bytes are the algorithm's need,
computed from the cell's shapes by the function that ``bytes`` names here,
not the compiler's estimate and not what the kernel happens to re-read.
None when there is no trace, no peak, or no such event (a cell whose
queries never run the kernel)."""


def partition_pass(run):
    """A stable partition of a table by bucket reads every payload stream
    once and writes it once. The exchange partitions every placed column
    of every input table once a query, each chip its share of the rows:
    twice the placed bytes, over all chips. (The bucket ids, the
    histogram pass and the kernel's own re-reads of the streams, once a
    bucket, are not the algorithm's need and are not counted.)"""
    return 2 * run["input_bytes"]


BYTES = {"partition_pass": partition_pass}


def reduce(run, spec):
    trace = run["trace"]
    if trace is None or not trace.n_queries or run["peaks"] is None:
        return None
    kernel_s = trace.seconds_matching(spec["line"], spec["patterns"])
    if not kernel_s:
        return None
    floor_s = BYTES[spec["bytes"]](run) / run["chips"] \
        / (run["peaks"]["hbm_gbytes_per_s"] * 1e9)
    return 100.0 * floor_s / (kernel_s / trace.n_queries)
