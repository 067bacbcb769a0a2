"""Small shared helpers."""
from __future__ import annotations


def pow2(n: int) -> int:
    """Round up to a power of two (≥1). All data-dependent capacities are
    pow2-rounded so the count→materialize discipline compiles O(log n)
    distinct programs instead of one per size."""
    return 1 if n <= 1 else 1 << (int(n) - 1).bit_length()


def pow2_floor(n: int) -> int:
    """Round DOWN to a power of two (≥1) — the budget-shrink direction:
    a comm-buffer cap halved to fit stays a pow2, so the chunk and round
    block sizes it feeds into kernel-factory cache keys keep
    1-per-octave cardinality (the specialization analysis recognizes
    this helper)."""
    return 1 << (max(int(n), 1).bit_length() - 1)


# bucket_cap's small-value floor: every capacity below it shares ONE
# bucket (and one compiled program). 512 rows/words is well under a
# single shard's working set, so the extra padding on tiny shapes costs
# noise while the merged buckets cut a long tail of small-capacity
# recompiles.
BUCKET_FLOOR = 512


def bucket_cap(n: int, floor: int = BUCKET_FLOOR) -> int:
    """Next-power-of-two capacity with a small-value floor — the ONE
    bucketing policy for data-dependent kernel-factory cache keys.

    Every ``counted_cache`` factory keyed on a runtime count (join
    materialize cap, set-op cap, varlen word cap, ring slab steps)
    routes the count through this helper, so the key's cardinality is
    bounded by OCTAVES of the data size (1 bucket per octave above the
    floor, 1 below) instead of one compiled XLA program per distinct
    value. Padding rows/words past the true count are masked by the
    kernels' emit discipline, so results are bit-identical to an exact
    capacity — only compile cardinality changes. The ``specialization``
    analysis family (docs/analysis.md) statically enforces that
    capacity-keyed call sites use this helper (or ``pow2`` /
    ``pow2_floor`` for a block the comm budget cut), but for the pairs
    it lists that take ``capacity``'s grid: an operator behind them pays
    for every padded slot."""
    return max(pow2(max(int(n), 1)), int(floor))


def capacity(n: int) -> int:
    """Static-capacity rounding with a 4-bit mantissa: the smallest
    s * 2^e ≥ n with s ∈ [17, 32]. Overshoot ≤ 6.25% (vs up to 100% for
    pow2) while still bounding distinct compiled programs to 16 per
    octave. Used for OUTPUT capacities on the hot path, where every
    padded row costs real gather/scan work, for the compaction before a
    join's sort (PR 50) and for the padded exchange's block (PR 52),
    where every padded slot is sorted."""
    n = max(int(n), 1)
    if n <= 16:
        return pow2(n)
    e = max((n - 1).bit_length() - 5, 0)
    s = -(-n // (1 << e))
    return s << e
