"""Fixture kernel factories for the specialization auditor: a bucketed
clean control, a raw data-dependent cache key, an unprovable key, a
closure-capture in a non-factory builder (with the counted_cache
closure kept legal as a control), and a suppressed site."""
import os

import jax
import numpy as np

from .telemetry import counted_cache


def bucket_cap(n):
    """Recognized bucketing helper (name-level for fixture trees)."""
    return max(1 << (int(n) - 1).bit_length(), 512)


def _capacity(n):
    """Fine-grained mantissa rounding — NOT a recognized bucket."""
    return n


@counted_cache
def _clean_mat_fn(mesh, cap: int):
    def kernel(x):
        return x

    return jax.jit(kernel)


@counted_cache
def _raw_mat_fn(mesh, cap: int):
    def kernel(x):
        return x

    return jax.jit(kernel)


@counted_cache
def _mystery_fn(mesh, cap):
    def kernel(x):
        return x

    return jax.jit(kernel)


@counted_cache
def _closes_over_key_fn(mesh, width: int):
    lanes = width + 1  # derived from the cache key: legal to close over

    def kernel(x):
        return x + lanes

    return jax.jit(kernel)


def make_scaled(mesh, scale):
    def kernel(x):
        return x * scale  # SEEDED: closure-capture (no cache key)

    return jax.jit(kernel)


def run_ops(mesh, counts, opaque):
    cap = int(np.asarray(jax.device_get(counts)).max())
    _clean_mat_fn(mesh, bucket_cap(cap))            # clean: bucketed
    _raw_mat_fn(mesh, cap)                          # SEEDED: unbucketed
    _raw_mat_fn(mesh, _capacity(cap))               # SEEDED: mantissa
    _mystery_fn(mesh, opaque())                     # SEEDED: unbounded
    _closes_over_key_fn(mesh, 4)
    n = int(os.environ.get("FIXTURE_ROWS", "64"))
    _raw_mat_fn(mesh, n)  # cylint: disable=specialization/unbounded-key — suppression-count control (env-read source)


def pow2_floor(n):
    """Recognized bucketing helper (name-level for fixture trees)."""
    return 1 << (max(int(n), 1).bit_length() - 1)


@counted_cache
def _chunk_exchange_fn(mesh, block: int, chunk_block: int):
    """Chunked-exchange-shaped factory: BOTH capacity params key
    compiled programs, so both must arrive bucketed."""
    def kernel(x):
        return x

    return jax.jit(kernel)


def run_chunked(mesh, counts):
    block = bucket_cap(int(np.asarray(jax.device_get(counts)).max()))
    _chunk_exchange_fn(mesh, block, pow2_floor(block // 4))  # clean
    cb = int(np.asarray(jax.device_get(counts)).sum())
    _chunk_exchange_fn(mesh, block, cb)     # SEEDED: unbucketed chunk block


@counted_cache
def _partition_exchange_fn(mesh, block: int, part: str):
    """Partition-path-shaped factory: the capacity must arrive bucketed
    and the path string is structural (finite literal set)."""
    def kernel(x):
        return x

    return jax.jit(kernel)


def run_partitioned(mesh, counts):
    block = bucket_cap(int(np.asarray(jax.device_get(counts)).max()))
    _partition_exchange_fn(mesh, block, "pallas")   # clean: bucketed+path
    raw = int(np.asarray(jax.device_get(counts)).max())
    _partition_exchange_fn(mesh, raw, "sort")  # SEEDED: raw capacity key


@counted_cache
def _salted_exchange_fn(mesh, salt: int):
    """Salted-exchange-shaped factory: the salt factor keys compiled
    programs, so it must arrive structural (the declared knob), never
    a data-dependent count."""
    def kernel(x):
        return x

    return jax.jit(kernel)


def run_salted(mesh, counts):
    _salted_exchange_fn(mesh, 4)            # clean: structural literal
    raw = int(np.asarray(jax.device_get(counts)).max())
    _salted_exchange_fn(mesh, raw)   # SEEDED: raw capacity as salt key


@counted_cache
def _compact_program_fn(cap: int, path: str):
    """Compaction-shaped factory: THE (factory, parameter) pair whose
    capacity may arrive on util.capacity's 16-an-octave grid (every slot
    past the live rows is sorted by the join behind it)."""
    def kernel(x):
        return x

    return jax.jit(kernel)


@counted_cache
def _setop_program_fn(cap: int, path: str):
    """The same shape under another name: the grid is accepted for the
    compaction alone, so here it is a finding as it is at line 68."""
    def kernel(x):
        return x

    return jax.jit(kernel)


def run_compacted(mask):
    count = int(np.asarray(jax.device_get(mask)).sum())
    _compact_program_fn(_capacity(count), "xla")    # clean: the one pair
    _compact_program_fn(bucket_cap(count), "xla")   # clean: bucketed
    _compact_program_fn(count, "xla")       # SEEDED: raw count, even here
    _setop_program_fn(_capacity(count), "xla")  # SEEDED: mantissa elsewhere
