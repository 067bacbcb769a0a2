"""Benchmark helpers — pycylon.util parity surface.

Reference: python/pycylon/util/benchutils.py:33-46
(`benchmark_with_repitions`) and python/pycylon/util/data/generator.py
(numeric CSV generation backing the demo pipelines). Re-designed for the
TPU execution model: JAX dispatch is asynchronous, so the timer
forces results with a one-element ``jax.device_get`` probe instead of
trusting the wall clock around a dispatch.
"""
from __future__ import annotations

import time
from typing import Sequence

import numpy as np

_DIV = {"ns": 1.0, "us": 1e3, "ms": 1e6, "s": 1e9}

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
