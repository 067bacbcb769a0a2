"""``<kernel>_roofline``: one kernel's share of its roofline, in %. The
least seconds a chip could take for the kernel's work (here the memory
bound alone: the kernels read so far move bytes and do no arithmetic to
speak of, so bytes over the peak HBM rate is the larger of the two bounds)
over the seconds the kernel's events (``patterns`` on the device line
``line``) ran per traced query, mean over the chips. The bytes are the
algorithm's need, computed from the cell's shapes by the function that
``bytes`` names here, not the compiler's estimate and not what the kernel
happens to re-read. None when there is no trace, no peak, or no such
event (a cell whose queries never run the kernel, a program without it)."""


def join_expand(run):
    """The join's materialisation turns the input rows into the result
    rows: at the least it reads every input column once and writes every
    result column once (the harness's input_bytes and result_bytes: the
    placed host arrays, and the live result rows times their width). The
    plan streams it also reads (indices, offsets) and the slots of its
    capacity beyond the live rows are not the algorithm's need and are
    not counted."""
    return run["input_bytes"] + run["result_bytes"]


BYTES = {"join_expand": join_expand}


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
