"""Benchmark helpers — pycylon.util parity surface.

Reference: python/pycylon/util/benchutils.py:33-46
(`benchmark_with_repitions`) and python/pycylon/util/data/generator.py
(numeric CSV generation backing the demo pipelines). Re-designed for the
TPU execution model: JAX dispatch is asynchronous, so the timer
forces results with a one-element ``jax.device_get`` probe instead of
trusting the wall clock around a dispatch.
"""
from __future__ import annotations

import math
import time
from typing import Sequence

import numpy as np

_DIV = {"ns": 1.0, "us": 1e3, "ms": 1e6, "s": 1e9}

# bucket_cap's small-value floor: every capacity below it shares ONE
# bucket (and one compiled program). 512 rows/words is well under a
# single shard's working set at bench scale, so the extra padding on
# tiny shapes costs noise while the merged buckets cut a long tail of
# small-capacity recompiles.
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
    capacity-keyed call sites use this helper (or ``util.pow2`` /
    ``util.pow2_floor`` for exchange blocks)."""
    from .util import pow2

    return max(pow2(max(int(n), 1)), int(floor))


def round_sig(x: float, sig: int = 6) -> float:
    """Round to ``sig`` SIGNIFICANT digits (not decimal places).

    Fixed-decimal rounding destroyed sub-millisecond bench walls —
    BENCH_r05 reported ``local_inner_join.wall_s_best: 0.0`` beside a
    2.8M rows/s rate because a 23 ms wall was rounded to 1 decimal.
    Significant-digit rounding keeps any nonzero measurement nonzero
    and self-consistent with the rates computed from the unrounded
    value, at any scale."""
    if not isinstance(x, float) or x == 0.0 or not math.isfinite(x):
        return x
    return round(x, sig - 1 - int(math.floor(math.log10(abs(x)))))


def _force(value) -> None:
    """Force async JAX results: device_get one element of every array
    leaf (tables force every column's terminal buffers)."""
    import jax

    from .data.table import Table

    if isinstance(value, Table):
        for c in value._columns:
            jax.device_get(c.data[:1])
            if c.is_varbytes:
                jax.device_get(c.varbytes.words[:1])
        return
    try:
        leaves = jax.tree.leaves(value)
    except Exception:  # cylint: disable=errors/broad-swallow — bench probe: absence is the answer
        return
    for leaf in leaves:
        if hasattr(leaf, "device"):
            jax.device_get(leaf.reshape(-1)[:1])


def benchmark_with_repetitions(repetitions: int = 10, time_type: str = "ms"):
    """Decorator: run ``f`` ``repetitions`` times, return
    (mean_time_in_time_type, last_result). API-compatible with the
    reference's ``benchmark_with_repitions`` [sic] decorator
    (benchutils.py:33-46), plus async-safe result forcing."""
    div = _DIV.get(time_type, 1e6)

    def wrap(f):
        def wrapped_f(*args, **kwargs):
            # perf_counter_ns: monotonic, full resolution — a wall-clock
            # (time_ns) step mid-run would corrupt the measurement, and
            # rates must derive from the unrounded integer-ns wall
            t1 = time.perf_counter_ns()
            for _ in range(repetitions):
                rets = f(*args, **kwargs)
                _force(rets)
            t2 = time.perf_counter_ns()
            return (t2 - t1) / div / float(repetitions), rets

        return wrapped_f

    return wrap


# reference spells it "repitions" — keep an alias so ported user code runs
benchmark_with_repitions = benchmark_with_repetitions


def generate_numeric_csv(rows: int, columns: int, file_path: str,
                         seed: int = 0) -> None:
    """Write a random numeric CSV (reference:
    util/data/generator.py:20-30)."""
    rng = np.random.default_rng(seed)
    a = rng.random((rows, columns))
    np.savetxt(file_path, a, delimiter=",")


def generate_keyed_csv(rows: int, n_keys: int, file_path: str,
                       seed: int = 0,
                       header: Sequence[str] = ("key", "value")) -> None:
    """Write a (key, value) CSV for join/groupby demos."""
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, max(n_keys, 1), rows)
    vals = rng.random(rows)
    with open(file_path, "w") as f:
        f.write(",".join(header) + "\n")
        for k, v in zip(keys, vals):
            f.write(f"{k},{v:.9f}\n")
