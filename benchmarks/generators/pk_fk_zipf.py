"""A primary-key table R(k, v) and a foreign-key table S(k, w) of the same
size whose foreign keys follow a Zipf law, made so that EVERY SEED IS THE
SAME WORK: the seed orders the rows and draws the payloads, and nothing
else. (A generator that samples S's keys lets the seed decide which keys
are hot and so which chip they hash to: the worst chip's rows, its output
and its block sizes then move from run to run, and runs of one cell stop
being comparable: PERF_LEDGER.jsonl, PR 27.)

``n = rows_per_chip * chips`` rows a side, as ``pk_fk_pair``.

- R: the dense keys [0, n), each once, in an order drawn from the seed.
- S: a FIXED multiset. Rank r (1..n) occurs
  ``floor(n F(r)) - floor(n F(r-1))`` times, F the Zipf CDF over n ranks
  with the configuration's ``zipf.exponent``, in float64: the counts sum
  to n exactly and nothing is sampled. Exponent 0 gives every key once.
- A FIXED map from rank to key, a constant of the configuration:
  ``key = ((r - 1) * zipf.rank_to_key_multiplier) mod n``, a bijection
  because the multiplier is a prime larger than any n. Which chip a hot key
  hashes to is therefore the same on every seed.
- The seed permutes S's rows and draws both payloads (4 bytes of float32,
  standard normal: the source fixes only their width).

So on any two seeds the per-key counts are equal, the rows each chip
receives under the program's key hash are equal, and the join's output is
n rows; only how a chip's rows split over the chips that send them varies,
by the sampling of the row order."""
import math

import numpy as np


def rank_upto(n, exponent):
    """``floor(n F(r))`` for the ranks r = 1..n: how many of the n foreign
    keys have a rank of at most r. Float64 holding whole numbers,
    non-decreasing, the last one n whatever the last division rounds to.
    (``H_r n / H_n`` and not ``(H_r / H_n) n``: at exponent 0 it is ``r n
    / n``, exact, and every key occurs once.) One buffer worked on in
    place: at 64M ranks a fresh array costs more than the arithmetic."""
    upto = np.arange(1, n + 1, dtype=np.float64)
    np.power(upto, -float(exponent), out=upto)
    np.cumsum(upto, out=upto)
    harmonic_n = upto[-1]
    upto *= n
    upto /= harmonic_n
    np.floor(upto, out=upto)
    upto[-1] = n
    return upto


def rank_counts(n, exponent):
    """How often each rank 1..n occurs: int64, every count >= 0, sums to
    n."""
    return np.diff(rank_upto(n, exponent), prepend=0.0).astype(np.int64)


def rank_keys(n, multiplier, ranks=None):
    """The key of each rank 1..n, or of ``ranks`` alone: int64, over all
    ranks a permutation of [0, n)."""
    if math.gcd(int(multiplier), n) != 1:
        raise ValueError(f"rank_to_key_multiplier {multiplier} shares a "
                         f"factor with n = {n}: no bijection")
    r0 = np.arange(n, dtype=np.uint64) if ranks is None \
        else (np.asarray(ranks) - 1).astype(np.uint64)
    return (r0 * np.uint64(multiplier) % np.uint64(n)).astype(np.int64)


def foreign_keys(n, exponent, multiplier):
    """S's keys before the seed orders them: rank by rank, hottest first.
    Most ranks of a Zipf law do not occur at all (54M of 64M at 1.05), so
    only those at which ``rank_upto`` steps get a count and a key; rank 1
    always does (n / H_n >= 1)."""
    upto = rank_upto(n, exponent)
    ranks = np.flatnonzero(
        np.concatenate(([True], upto[1:] > upto[:-1]))) + 1
    counts = np.diff(upto[ranks - 1], prepend=0.0).astype(np.int64)
    return np.repeat(rank_keys(n, multiplier, ranks), counts)


def generate(config, traffic, chips, scale, seed):
    n = max(int(config["rows_per_chip"] * scale), 256) * chips
    r = np.random.default_rng(seed)
    key = np.dtype(config["schema"]["key_dtype"])
    val = np.dtype(config["schema"]["value_dtype"])
    lk, lv = config["schema"]["left"]
    rk, rv = config["schema"]["right"]
    zipf = config["zipf"]
    fk = foreign_keys(n, zipf["exponent"],
                      zipf["rank_to_key_multiplier"]).astype(key)
    return {"tables": {
        "left": {lk: r.permutation(n).astype(key),
                 lv: r.standard_normal(n, dtype=val)},
        "right": {rk: r.permutation(fk),
                  rv: r.standard_normal(n, dtype=val)},
    }}
