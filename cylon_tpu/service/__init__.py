"""The concurrent query service tier — the TOP of the cylon_tpu stack.

Turns the one-blocking-``collect()``-at-a-time library into a service
(ROADMAP item 2): many LazyTable queries submitted at once, per-tenant
fair-share queueing (deficit round-robin), dispatch-time admission
against the ledger-tracked live HBM, typed backpressure before
enqueue, and a plan/fingerprint cache so repeated query shapes skip
optimization and re-hit the compiled-kernel memos.

* ``scheduler`` — :class:`QueryService` / :class:`QueryTicket`: the
  async submission surface, the single executor worker (device
  execution stays serialized; host-side optimize/preflight pipelines
  on the submitters' threads) and the completion thread (a ticket
  resolves when its result is ready on the DEVICE, without holding
  the worker).
* ``plancache`` — the structural plan fingerprint and the bounded LRU
  of optimized plans, shared between the service and library mode.
* ``obs_http`` — the live operational surface: a stdlib HTTP endpoint
  (``CYLON_OBS_PORT``) serving /metrics (Prometheus scrape), /healthz
  (worker liveness + queue depths + pool watermarks), /queries (the
  structured query-log ring) and /slo (per-tenant SLO state).

Importing this package wires the plan cache into ``plan.lazy``'s
late-bound optimize memo (the hook keeps plan/ from importing
service/ — the ``below-service`` layering contract), so even plain
``LazyTable.collect()`` loops skip re-optimizing repeated shapes.

Layering (analysis/layering.py ``service-top``): this package imports
only plan/, resilience/, telemetry/ and status — never device
machinery (ops/parallel/data/io); execution goes through plan/'s
executor seam. Nothing below service may import it back.

Full semantics: docs/service.md.
"""
from __future__ import annotations

from . import obs_http, plancache, scheduler
from .obs_http import ObsServer
from .plancache import PlanCache, fingerprint, global_cache
from .scheduler import QueryService, QueryTicket

# library-mode wiring: LazyTable.optimized()/execute() memoize through
# the global fingerprint cache from the moment the package imports
plancache.install()

__all__ = [
    "ObsServer", "PlanCache", "QueryService", "QueryTicket",
    "fingerprint", "global_cache", "obs_http", "plancache",
    "scheduler",
]
