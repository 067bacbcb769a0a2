"""Two tables R(k, v) and S(k, w) of int64 keys and float64 values whose
keys are drawn from the same ``n`` values, as upstream Cylon's join
benchmark draws them (both sides uniform over as many values as there are
rows: a key occurs c times in a table with c about Poisson(1), and the
join is many-to-many), made so that EVERY SEED IS THE SAME WORK
(PERF_LEDGER.jsonl, PR 27): nothing is sampled but the order of the rows
and the payloads.

``n = rows_per_chip * chips`` rows a side, as ``pk_fk_pair``.

- A FIXED multiset of multiplicities, the same for both tables: of the n
  ranks, ``floor(n F(c)) - floor(n F(c-1))`` occur c times, F the CDF of
  Poisson(``multiplicity.mean``) in float64 (the floor-of-cumulative rule
  of ``pk_fk_zipf``), up to the last c the law gives a whole rank; then
  as many ranks as it takes are moved between "once" and "never" for the
  rows to sum to n exactly. Position p of the
  multiset (most frequent first) is a rank of each table through a FIXED
  bijection, another for R than for S: ``rank = (p * rank_multiplier +
  rank_offset) mod n``, the multipliers primes larger than any n. So
  which key meets which is the same on every seed, and the join's row
  count, ``sum over ranks of c_R * c_S``, is one number (`output_rows`).
- A FIXED map from rank to key that uses all 64 bits: ``key = (rank *
  key_multiplier) mod 2^64`` read as int64, the multiplier odd (a
  bijection), so high words differ and half the keys are negative. (Keys
  in ``[0, n)`` would have a high word of zero, and a 32-bit path could
  answer for them.)
- The seed orders each table's rows and draws both payloads (float64,
  standard normal)."""
import math

import numpy as np


def class_counts(n, mean):
    """How many of the n ranks occur c times, c = 0, 1, ...: int64, sums
    to n, and ``sum(c * counts[c]) == n``. The classes end with the last
    one that the law gives a whole rank (``n p_c >= 1``)."""
    cdf, term, upto = 0.0, math.exp(-mean), []
    while term * n >= 1.0 or len(upto) < 2:
        cdf += term
        upto.append(math.floor(n * cdf))
        term *= mean / len(upto)
    counts = np.diff(np.array(upto, dtype=np.int64), prepend=0)
    counts[0] += n - counts.sum()   # the law's tail: never
    extra = int((np.arange(len(counts)) * counts).sum()) - n
    counts[1] -= extra      # too many rows: so many ranks once -> never
    counts[0] += extra      # too few: the other way
    assert counts.min() >= 0 and counts.sum() == n
    return counts


def table_ranks(n, counts, multiplier, offset):
    """(the ranks that occur in a table, how often each): position p of
    the multiset, most frequent first, is rank ``(p * multiplier + offset)
    mod n``."""
    if math.gcd(int(multiplier), n) != 1:
        raise ValueError(f"rank multiplier {multiplier} shares a factor "
                         f"with n = {n}: no bijection")
    live = n - int(counts[0])
    times = np.repeat(np.arange(len(counts) - 1, 0, -1), counts[:0:-1])
    p = np.arange(live, dtype=np.uint64)
    ranks = (p * np.uint64(multiplier) + np.uint64(offset)) % np.uint64(n)
    return ranks.astype(np.int64), times


def _sides(config, n):
    m = config["multiplicity"]
    counts = class_counts(n, m["mean"])
    return [table_ranks(n, counts, m[side + "_rank_multiplier"],
                        m[side + "_rank_offset"])
            for side in ("left", "right")]


def rows_of(config, chips, scale):
    return max(int(config["rows_per_chip"] * scale), 256) * chips


def output_rows(config, chips, scale):
    """The inner join's row count: the same on every seed."""
    n = rows_of(config, chips, scale)
    (lr, lt), (rr, rt) = _sides(config, n)
    right_times = np.zeros(n, dtype=np.int64)
    right_times[rr] = rt
    return int((lt * right_times[lr]).sum())


def generate(config, traffic, chips, scale, seed):
    n = rows_of(config, chips, scale)
    r = np.random.default_rng(seed)
    key = np.dtype(config["schema"]["key_dtype"])
    val = np.dtype(config["schema"]["value_dtype"])
    if key != np.int64 or val != np.float64:
        raise ValueError(f"this generator makes int64 keys and float64 "
                         f"values, the configuration asks for {key}, {val}")
    to_key = np.uint64(config["multiplicity"]["key_multiplier"])
    tables = {}
    for side, (ranks, times) in zip(("left", "right"), _sides(config, n)):
        keys = np.repeat((ranks.astype(np.uint64) * to_key).view(key), times)
        assert len(keys) == n
        kn, vn = config["schema"][side]
        tables[side] = {kn: r.permutation(keys),
                        vn: r.standard_normal(n).astype(val)}
    return {"tables": tables}
