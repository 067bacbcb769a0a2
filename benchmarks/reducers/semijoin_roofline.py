"""``semijoin_roofline``: the share of its memory roofline that a semi
join reaches, in %. The least seconds a chip could take, bytes over the
peak HBM rate, over the seconds the join's programs (``patterns`` on the
device line ``line``) ran per traced query.

The bytes are the algorithm's need, computed from the cell's shapes by
`semijoin_bytes` below and the same whatever implements the join (a sort
and a pass, a hash table, a bitmap): the build side's rows x (its key +
its row mask) read once, the probe side's slots x (its key + the columns
the result keeps + its row mask) read once, and the result's row mask
written once. Never the program's counters. A semi join reads both its
inputs at least once, so the share cannot pass 100%. None when there is
no trace, no peak, or no such program."""


def _pow2(n):
    return 1 << max(int(n) - 1, 0).bit_length()


def semijoin_bytes(run, spec):
    num, den = spec["build_rows_of_input_rows"]
    build = run["input_rows"] * num // den
    num, den = spec["probe_rows_of_input_rows"]
    live, of = spec["probe_live_share"]
    # the probe side arrives compacted: its live rows' power-of-two capacity
    probe = _pow2(-(-run["input_rows"] * num * live // (den * of)))
    return build * spec["build_row_bytes"] \
        + probe * (spec["probe_row_bytes"] + spec["result_row_bytes"])


def reduce(run, spec):
    trace = run["trace"]
    if trace is None or not trace.n_queries or run["peaks"] is None:
        return None
    seconds = trace.seconds_matching(spec["line"], spec["patterns"])
    if not seconds:
        return None
    floor_s = semijoin_bytes(run, spec) / run["chips"] \
        / (run["peaks"]["hbm_gbytes_per_s"] * 1e9)
    return 100.0 * floor_s / (seconds / trace.n_queries)
