"""memory.MemoryPool accounting: the stats-backed path, the
hidden-memory_stats TPU fallback (published size by device_kind, the
CYLON_HBM_BYTES override), and telemetry gauge sampling — the satellite coverage for
the paths the >HBM routing guards and the shuffle comm budget depend
on (none of which the CPU test matrix exercised before)."""
import numpy as np

import pytest

from cylon_tpu.memory import TPU_HBM_BYTES, MemoryPool

V5E = "TPU v5 lite"
V5E_HBM_BYTES = TPU_HBM_BYTES[V5E]


class _StatsDev:
    """Fake device exposing memory_stats (the real-TPU shape)."""

    platform = "tpu"

    def __init__(self, limit, used, peak):
        self._stats = {"bytes_limit": limit, "bytes_in_use": used,
                       "peak_bytes_in_use": peak}

    def memory_stats(self):
        return self._stats


class _HiddenDev:
    """Fake device whose runtime hides its stats: memory_stats raises
    (the fallback-limit branch)."""

    def __init__(self, platform, device_kind=None):
        self.platform = platform
        self.device_kind = device_kind

    def memory_stats(self):
        raise NotImplementedError


def test_stats_backed_accounting():
    pool = MemoryPool([_StatsDev(1000, 300, 500),
                       _StatsDev(1000, 100, 200)])
    assert pool.bytes_allocated() == 400
    assert pool.peak_bytes() == 700
    assert pool.bytes_limit() == 2000
    # tightest device bounds the headroom
    assert pool.available_bytes() == 700
    assert pool.comm_budget_bytes() == int(700 * 0.25)


def test_hidden_stats_tpu_fallback_default():
    """A v5e that hides memory_stats falls back to its published chip
    size — without it the >HBM routing guards silently disarm."""
    pool = MemoryPool([_HiddenDev("tpu", V5E)])
    assert pool.bytes_allocated() == 0
    assert pool.peak_bytes() == 0
    assert pool.available_bytes() == V5E_HBM_BYTES
    assert pool.comm_budget_bytes() == int(V5E_HBM_BYTES * 0.25)


def test_hidden_stats_env_override(monkeypatch):
    monkeypatch.setenv("CYLON_HBM_BYTES", str(1 << 20))
    pool = MemoryPool([_HiddenDev("tpu", "TPU v9")], comm_fraction=0.5)
    assert pool.available_bytes() == 1 << 20
    assert pool.comm_budget_bytes() == 1 << 19


def test_hidden_stats_unknown_tpu_kind_is_an_error():
    """A TPU kind the table does not name, hiding its stats, with no
    override: an error, not a made-up 16 GiB."""
    with pytest.raises(RuntimeError, match="TPU v9"):
        MemoryPool([_HiddenDev("tpu", "TPU v9")])


def test_non_tpu_hidden_stats_no_fallback():
    """A non-TPU backend without stats reports None (not a made-up
    16 GiB): the routing guards must know they are blind, not armed."""
    pool = MemoryPool([_HiddenDev("cpu")])
    assert pool.available_bytes() is None
    assert pool.comm_budget_bytes() is None


def test_gauge_sampling_fake_devices():
    from cylon_tpu.telemetry import MetricsRegistry, sample_memory

    reg = MetricsRegistry()
    pool = MemoryPool([_StatsDev(1 << 30, 1 << 20, 1 << 21)])
    vals = sample_memory(pool, registry=reg)
    snap = reg.snapshot()
    assert snap["cylon_hbm_live_bytes"] == 1 << 20 == vals["hbm_live_bytes"]
    assert snap["cylon_hbm_peak_bytes"] == 1 << 21
    assert snap["cylon_hbm_limit_bytes"] == 1 << 30
    assert snap["cylon_hbm_available_bytes"] == (1 << 30) - (1 << 20)
    assert snap["cylon_hbm_stats_available"] == 1
    assert snap["cylon_comm_budget_bytes"] == vals["comm_budget_bytes"]


def test_gauge_sampling_real_ctx(local_ctx):
    """On the CPU test platform sampling must return sane (>= 0 or
    None) values and never throw — live/peak are whatever the backend
    reports, headroom may be unknowable."""
    from cylon_tpu.telemetry import MetricsRegistry, sample_memory

    reg = MetricsRegistry()
    vals = sample_memory(local_ctx.memory_pool, registry=reg)
    assert vals["hbm_live_bytes"] >= 0
    assert vals["hbm_peak_bytes"] >= 0
    for key in ("hbm_available_bytes", "comm_budget_bytes"):
        assert vals[key] is None or vals[key] >= 0
    snap = reg.snapshot()
    assert snap["cylon_hbm_stats_available"] in (0, 1)
    # gauges for None values stay unset (absent), never fabricated
    if vals["comm_budget_bytes"] is None:
        assert "cylon_comm_budget_bytes" not in snap


def test_snapshot_aggregates_in_one_call():
    """snapshot() returns (bytes_in_use, peak, limit) with ONE
    memory_stats call per device (the old trio paid three)."""

    class _CountingDev(_StatsDev):
        calls = 0

        def memory_stats(self):
            _CountingDev.calls += 1
            return self._stats

    pool = MemoryPool([_CountingDev(1000, 300, 500),
                       _CountingDev(1000, 100, 200)])
    _CountingDev.calls = 0   # constructor probes don't count
    assert pool.snapshot() == (400, 700, 2000)
    assert _CountingDev.calls == 2


def test_snapshot_hidden_backend_monotonic_peak_via_external():
    """The fallback (CYLON_HBM_BYTES) path: live bytes come from the
    external (ledger) source and peak is the pool's monotonic
    high-water mark — previously both read 0 on stats-hidden
    backends, silently blanking span hbm_peak attrs."""
    pool = MemoryPool([_HiddenDev("tpu", V5E)])
    live = {"v": 0}
    pool.set_external_source(lambda: live["v"])
    assert pool.snapshot() == (0, 0, V5E_HBM_BYTES)
    live["v"] = 500
    assert pool.snapshot()[:2] == (500, 500)
    live["v"] = 100
    used, peak, limit = pool.snapshot()
    assert (used, peak) == (100, 500)   # peak is monotonic
    assert limit == V5E_HBM_BYTES
    # the method trio reads the same ledger-backed numbers
    assert pool.bytes_allocated() == 100
    assert pool.peak_bytes() == 500


def test_snapshot_cpu_hidden_backend_external_source():
    """Even off-TPU (no CYLON_HBM_BYTES fallback limit), a hidden-stats
    backend self-accounts through the external source — the CPU test
    mesh's crash dumps carry real watermarks."""
    pool = MemoryPool([_HiddenDev("cpu")])
    pool.set_external_source(lambda: 42)
    assert pool.snapshot() == (42, 42, 0)
    # headroom stays unknowable (None), as before
    assert pool.available_bytes() is None


def test_snapshot_external_source_errors_read_as_zero():
    pool = MemoryPool([_HiddenDev("tpu", V5E)])

    def explode():
        raise RuntimeError("ledger gone")

    pool.set_external_source(explode)
    assert pool.snapshot()[0] == 0


def test_pool_prefers_stats_over_fallback(monkeypatch):
    """A mesh mixing stats-backed and hidden devices uses the real
    stats (the fallback only arms when NO device reports)."""
    monkeypatch.setenv("CYLON_HBM_BYTES", str(1 << 10))
    pool = MemoryPool([_StatsDev(2000, 500, 600),
                       _HiddenDev("tpu", "TPU v9")])
    assert pool.available_bytes() == 1500
