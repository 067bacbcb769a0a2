"""HBM accounting + comm-buffer budget.

The reference's memory layer (reference: cpp/src/cylon/ctx/
memory_pool.hpp:25-66 `MemoryPool`, arrow_memory_pool_utils.hpp:25-63
`ProxyMemoryPool`/`ToArrowPool`) adapts a user pool into Arrow allocations.
On TPU the allocator is the XLA runtime's HBM arena, so the pool's role
becomes *accounting and budgeting*: report live/peak HBM per device and
hand the shuffle a comm-buffer budget so blockwise exchange sizes its
rounds to fit (the reference's analog: ArrowAllocator feeding receive
buffers from the pool, arrow_all_to_all.cpp:234-247).
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Tuple

# the knob registry is the one sanctioned telemetry import for base
# leaves: knobs.py itself imports only the stdlib, and nothing in
# telemetry imports memory, so no cycle. Note the carve-out is a
# DEPENDENCY statement, not an import-cost one — binding the submodule
# still executes the telemetry package __init__ (spans/metrics/etc.),
# which is fine because cylon_tpu/__init__ pulls all of that on any
# entry into the package anyway.
from .telemetry.knobs import get as _knob_get

# HBM per chip by ``device_kind``, for a TPU runtime that hides
# memory_stats (Google Cloud documentation, "TPU v5e": 16 GiB a chip).
# CYLON_HBM_BYTES overrides it. Without a limit the >HBM routing guards
# (join_blocked auto-engage, shuffle comm budget) silently disarm and a
# beyond-memory join OOMs instead of chunking — so a TPU kind that is
# not named here and hides its stats is an error, never a guess.
TPU_HBM_BYTES = {"TPU v5 lite": 16 * (1 << 30)}


class MemoryPool:
    """Per-context HBM accounting over the mesh's local devices.

    ``comm_fraction`` bounds the portion of free HBM the shuffle may spend
    on in-flight exchange buffers (see parallel/shuffle.exchange)."""

    def __init__(self, devices, comm_fraction: float = 0.25):
        self._devices = [d for d in devices
                         if _stats(d) is not None]
        self.comm_fraction = comm_fraction
        self._fallback_limit = None
        # monotonic high-water mark over snapshot() observations — the
        # only peak signal on backends that hide memory_stats (the CPU
        # test platform): without it, span hbm_peak attrs and
        # crash-dump watermarks silently read 0 there
        self._peak_seen = 0
        # external live-bytes source (duck-typed zero-arg callable —
        # the telemetry ledger's tracked-table total; memory.py stays a
        # base-layer leaf and never imports telemetry). Consulted only
        # when no local device exposes memory_stats.
        self._external_live: Optional[Callable[[], int]] = None
        hidden_tpus = [d for d in devices
                       if getattr(d, "platform", "") == "tpu"]
        if not self._devices and hidden_tpus:
            self._fallback_limit = _static_hbm_bytes(hidden_tpus)

    def set_external_source(self, fn: Optional[Callable[[], int]]) -> None:
        """Register a fallback live-bytes provider (the telemetry
        ledger's ``live_bytes``) used when the runtime hides per-device
        memory stats — self-accounting instead of blindness."""
        self._external_live = fn

    def snapshot(self) -> Tuple[int, int, int]:
        """``(bytes_in_use, peak_bytes, bytes_limit)`` summed over local
        devices, with ONE ``memory_stats`` call per device (the
        bytes_allocated/peak_bytes/bytes_limit trio used to pay three).
        When every device hides its stats, ``bytes_in_use`` falls back
        to the external (ledger) source and ``peak_bytes`` to the
        pool's own monotonic high-water mark over those observations —
        the fix for hbm_peak reading 0 on stats-hidden backends."""
        used = peak = limit = 0
        seen = False
        for d in self._devices:
            s = _stats(d)
            if s is None:
                continue
            seen = True
            used += s.get("bytes_in_use", 0) or 0
            peak += s.get("peak_bytes_in_use", 0) or 0
            limit += s.get("bytes_limit", 0) or 0
        if not seen:
            if self._external_live is not None:
                try:
                    used = int(self._external_live())
                except Exception:  # pragma: no cover - defensive  # cylint: disable=errors/broad-swallow — broken external source reads as 0 live bytes
                    used = 0
            limit = self._fallback_limit or 0
        self._peak_seen = max(self._peak_seen, used)
        return used, max(peak, self._peak_seen), limit

    def bytes_allocated(self) -> int:
        """Live HBM across local mesh devices; ledger-tracked bytes when
        the backend hides memory_stats (0 with no external source)."""
        return self.snapshot()[0]

    def peak_bytes(self) -> int:
        return self.snapshot()[1]

    def bytes_limit(self) -> int:
        return self.snapshot()[2]

    def available_bytes(self) -> Optional[int]:
        """Free HBM on the tightest local device; the static chip limit
        when the backend hides stats (live usage unknowable there, so
        routing guards compare against the full chip); None when not a
        TPU at all."""
        per = []
        for d in self._devices:
            s = _stats(d)
            if s is None:
                continue
            limit, used = s.get("bytes_limit"), s.get("bytes_in_use")
            if limit:
                per.append(limit - (used or 0))
        if per:
            return min(per)
        return self._fallback_limit

    def comm_budget_bytes(self) -> Optional[int]:
        """Per-device byte budget for in-flight shuffle buffers."""
        avail = self.available_bytes()
        return None if avail is None else int(avail * self.comm_fraction)


def _static_hbm_bytes(tpus) -> int:
    """The per-chip HBM limit of TPUs that hide memory_stats: the
    CYLON_HBM_BYTES override, else the published size of their
    ``device_kind``."""
    override = _knob_get("CYLON_HBM_BYTES")
    if override is not None:
        return int(override)
    kinds = {getattr(d, "device_kind", None) for d in tpus}
    unknown = sorted(str(k) for k in kinds if k not in TPU_HBM_BYTES)
    if unknown:
        raise RuntimeError(
            f"TPU device_kind {unknown} hides memory_stats and has no "
            f"HBM size in memory.TPU_HBM_BYTES "
            f"({sorted(TPU_HBM_BYTES)}); set CYLON_HBM_BYTES")
    return min(TPU_HBM_BYTES[k] for k in kinds)


def _stats(device) -> Optional[Dict]:
    try:
        return device.memory_stats()
    except Exception:  # cylint: disable=errors/broad-swallow — stats-hidden device: None IS the answer
        return None
