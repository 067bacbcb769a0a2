"""``joinagg_compact_roofline``: the share of its memory roofline that the
compaction of a filtered table reaches, in %. The least seconds a chip
could take, bytes over the peak HBM rate, over the seconds the
compaction's events (``patterns`` on the device line ``line``) ran per
traced query.

The bytes are the algorithm's need, computed from the cell's shapes by
`compact_bytes` below and the same whatever implements the compaction
(a Pallas pass, a scatter, a sort): the filtered table's rows x (the row
mask's byte + the bytes of every stream the plan still needs above the
filter) read once, and the live rows x those streams written once. Never
the program's counters, and not `kernel_roofline.join_expand`'s input
bytes: the filter's own inputs (Q12's three dates) are pruned before the
compaction, which never reads them. A compaction reads at least its mask
and every stream it moves once, so the share cannot pass 100%. None when
there is no trace, no peak, or no such event (no table was compacted)."""


def compact_bytes(run, spec):
    num, den = spec["table_rows_of_input_rows"]
    rows = run["input_rows"] * num // den
    stream_bytes = spec["stream_bytes"] * spec["streams"]
    return rows * (spec["mask_bytes"] + stream_bytes) \
        + int(rows * spec["live_share"]) * stream_bytes


def reduce(run, spec):
    trace = run["trace"]
    if trace is None or not trace.n_queries or run["peaks"] is None:
        return None
    seconds = trace.seconds_matching(spec["line"], spec["patterns"])
    if not seconds:
        return None
    floor_s = compact_bytes(run, spec) / run["chips"] \
        / (run["peaks"]["hbm_gbytes_per_s"] * 1e9)
    return 100.0 * floor_s / (seconds / trace.n_queries)
