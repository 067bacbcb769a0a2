"""CylonContext — the entry point object.

Mirrors the reference's CylonContext (reference: cpp/src/cylon/ctx/
cylon_context.hpp:29-146 — Init/InitDistributed, GetRank/GetWorldSize,
GetNextSequence, Barrier, string config map) re-designed for the TPU
execution model:

* an MPI *world of W processes* becomes a *1-D device mesh of W chips*
  driven by one controller process per host (SPMD via shard_map/pjit);
* ``rank``/``world_size`` become mesh coordinates; on multi-host meshes the
  controller's ``jax.process_index()`` plays the reference's node-rank role
  for file IO placement;
* ``Barrier`` becomes a device synchronization (block_until_ready on a tiny
  psum) — program order inside XLA replaces MPI tag ordering;
* ``GetNextSequence`` survives as the op-sequence counter used to key
  shuffle "edges" for tracing/profiling (the reference used it as the MPI
  tag: cylon_context.cpp:94-99).
"""
from __future__ import annotations

import os
import threading
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .config import CommConfig, CommType, LocalConfig, TPUConfig, MultiHostConfig
from .status import Code, CylonError

_AXIS = "p"  # the canonical 1-D mesh axis name for row partitioning


def _distributed_initialized() -> bool:
    """True when jax.distributed.initialize has already run (idempotence
    guard that — unlike jax.process_count() — does not itself initialise
    the XLA backend)."""
    try:
        from jax._src import distributed as _jd

        return getattr(_jd.global_state, "client", None) is not None
    except Exception:  # pragma: no cover - jax internals moved  # cylint: disable=errors/broad-swallow — jax internals moved: treat as uninitialized
        return False


# fixed, because the directory is part of the cache key: a path that
# moves (temp name, pid, time) never hits
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def _use_compile_cache(devices) -> None:
    """Persistent compile cache for TPU contexts — THE one place the
    program sets it (the stream join plan alone compiles for ~2 min).
    Where JAX_COMPILATION_CACHE_DIR is set JAX already reads it; on the
    CPU nothing is set, so a test run writes no cache into the tree."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR") \
            or devices[0].platform != "tpu" \
            or jax.config.jax_compilation_cache_dir == COMPILE_CACHE_DIR:
        return
    from jax.experimental.compilation_cache import compilation_cache

    compilation_cache.set_cache_dir(COMPILE_CACHE_DIR)
    # if something compiled before the first context, JAX latched "no
    # directory" then, and only a reset makes it look again
    compilation_cache.reset_cache()


class CylonContext:
    """Holds the device mesh, distributed flag and op sequence counter."""

    def __init__(self, config: Optional[CommConfig] = None, distributed: bool = False):
        # pycylon parity: CylonContext(config=MPIConfig(), distributed=True)
        # (python/pycylon/ctx/context.pyx:29-75)
        self._config_map: Dict[str, str] = {}
        self._sequence = 0
        self._lock = threading.Lock()
        self._finalized = False

        if config is None and not distributed:
            config = LocalConfig()
        elif config is None:
            config = TPUConfig()

        self.comm_config = config
        ct = config.comm_type()
        self.distributed = distributed and ct != CommType.LOCAL

        if ct == CommType.MULTIHOST:
            cfg: MultiHostConfig = config  # type: ignore[assignment]
            if cfg.num_processes not in (None, 1) \
                    and not _distributed_initialized():
                # must run before ANY backend-initialising jax call
                # (jax.process_count() itself would initialise it)
                jax.distributed.initialize(
                    coordinator_address=cfg.coordinator_address,
                    num_processes=cfg.num_processes,
                    process_id=cfg.process_id,
                )
            devices = jax.devices()
        elif ct == CommType.TPU:
            cfg2: TPUConfig = config  # type: ignore[assignment]
            devices = list(cfg2.devices) if cfg2.devices is not None else jax.devices()
            if cfg2.world_size is not None:
                if cfg2.world_size > len(devices):
                    raise CylonError(
                        Code.Invalid,
                        f"world_size {cfg2.world_size} > available devices {len(devices)}")
                devices = devices[: cfg2.world_size]
        else:
            devices = [jax.devices()[0]]

        if not self.distributed:
            devices = devices[:1]

        self.devices: List = devices
        self.mesh = jax.sharding.Mesh(np.array(devices), (_AXIS,))
        _use_compile_cache(devices)

        from .memory import MemoryPool
        from . import telemetry as _telemetry

        self.memory_pool = MemoryPool(
            [d for d in devices
             if d.process_index == jax.process_index()])
        # observability wiring: on backends that hide memory_stats the
        # pool falls back to the ledger's tracked-table bytes (self-
        # accounting instead of blindness), and the span layer samples
        # this pool for per-span hbm_delta/hbm_peak attrs + the flight
        # recorder's crash-dump watermarks
        self.memory_pool.set_external_source(_telemetry.ledger.live_bytes)
        _telemetry.set_memory_pool(self.memory_pool)

    # -- reference API (cylon_context.hpp) --

    @staticmethod
    def Init() -> "CylonContext":
        """Local (single-device) context. Reference: CylonContext::Init."""
        return CylonContext(LocalConfig(), distributed=False)

    @staticmethod
    def InitDistributed(config: Optional[CommConfig] = None) -> "CylonContext":
        """Distributed context over the device mesh.

        Reference: CylonContext::InitDistributed (cylon_context.cpp:32-43).
        """
        return CylonContext(config or TPUConfig(), distributed=True)

    def get_world_size(self) -> int:
        """Number of mesh devices (reference: GetWorldSize = MPI world size).

        An MPI rank maps to a mesh SHARD here, so world = shard count, not
        process count (one controller process drives many chips)."""
        return len(self.devices)

    def get_rank(self) -> int:
        """This controller's first shard index in the mesh (shard space —
        consistent with `get_neighbours`). Single-controller meshes always
        return 0; on multi-host meshes each process owns a contiguous run
        of shards and `get_rank` is the first of them. For file placement
        use `get_process_rank`/`local_shard_indices`."""
        local = self.local_shard_indices()
        return local[0] if local else 0

    def get_process_rank(self) -> int:
        """Controller process index (the reference's node-rank role for
        per-rank file IO; reference: cpp/test/join_test.cpp:22-24)."""
        return jax.process_index()

    def get_process_count(self) -> int:
        return jax.process_count()

    def local_shard_indices(self) -> List[int]:
        """Shard indices whose device is addressable from this process."""
        me = jax.process_index()
        return [i for i, d in enumerate(self.devices)
                if d.process_index == me]

    def get_neighbours(self, include_self: bool = False) -> List[int]:
        """All other shard indices, optionally including this controller's
        own (reference: GetNeighbours, cylon_context.cpp:77-86)."""
        w = self.get_world_size()
        me = self.get_rank()
        return [i for i in range(w) if include_self or i != me]

    def get_next_sequence(self) -> int:
        """Monotonic op id — the reference used it as the MPI comm tag
        (cylon_context.cpp:94-99); we key profiler annotations with it."""
        with self._lock:
            self._sequence += 1
            return self._sequence

    def barrier(self) -> None:
        """Synchronize all devices (reference: MPI_Barrier). Runs one tiny
        SPMD program over the whole mesh — multi-host safe (a per-device
        device_put would fail on non-addressable devices)."""
        if self._finalized:
            return
        from jax.sharding import NamedSharding, PartitionSpec as P

        out = jax.jit(lambda: jnp.zeros((), jnp.int32) + 1,
                      out_shardings=NamedSharding(self.mesh, P()))()
        jax.block_until_ready(out)

    def finalize(self) -> None:
        self._finalized = True

    def is_distributed(self) -> bool:
        return self.distributed

    # string config map (cylon_context.hpp:31)
    def add_config(self, key: str, value: str) -> None:
        self._config_map[key] = value

    def get_config(self, key: str, default: str = "") -> str:
        return self._config_map.get(key, default)

    # -- TPU-native additions --

    @property
    def axis(self) -> str:
        return _AXIS

    # PascalCase aliases for reference-style call sites
    GetRank = get_rank
    GetWorldSize = get_world_size
    GetNextSequence = get_next_sequence
    Barrier = barrier
    Finalize = finalize
