"""Fleet serving: the layer above one engine.

:mod:`torchgpipe_tpu.serving` ends at ONE continuous-batching engine on
one set of params.  This package is the horizontal story on top of it —
the "millions of users" tier (docs/serving.md, fleet section):

* :mod:`~torchgpipe_tpu.fleet.router` — N replicas behind one
  ``submit()``: session affinity, power-of-two-choices balancing on the
  shared :class:`~torchgpipe_tpu.obs.MetricsRegistry` occupancy/TPOT
  series, and drain-aware failover riding the existing
  ``CheckpointManager`` / ``Engine.restore_requests`` path — a replica
  dying mid-generation resumes its in-flight requests on a SURVIVOR,
  greedy outputs bitwise-equal to an undisturbed run.
* :mod:`~torchgpipe_tpu.fleet.prefix_cache` — a radix trie over
  :class:`~torchgpipe_tpu.serving.cache_pool.CachePool`: requests
  sharing a system prompt reuse KV slots through refcounted donor pins
  and one fixed-shape copy program; reuse is bitwise vs cold prefill.
* :mod:`~torchgpipe_tpu.fleet.speculative` — a draft model through the
  same pipelined decode path, target-verified in one chunked
  ``decode_slots`` step: the engine's ``g > 1`` chunk body jitted once
  pool-wide (the engine's own prefill programs are compact), so the
  steady-state program count stays fixed
  (``analysis.serving.certify_speculative``).
* :mod:`~torchgpipe_tpu.fleet.trace` — a deterministic synthetic
  million-request trace generator (ragged, bursty, shared-prefix
  tenants) that the fleet tests and ``tools/*_verify.py`` replay.
* :mod:`~torchgpipe_tpu.fleet.autoscaler` — :class:`Autoscaler`:
  replica count as a control loop — Little's-law pricing off the
  measured ``CostModel`` + MMPP arrival rates, SLO-burn override,
  hysteresis/cooldown damping; scale-down reuses the router's drain
  path (no in-flight request dropped), scale-up re-opens a parked
  replica's admissions.
* :mod:`~torchgpipe_tpu.fleet.migration` — phase-disaggregated
  serving's handoff: a prefill replica's finished prompt (KV rows +
  first token) ships to a decode replica through one fixed-shape
  ``migrate_ingest`` program; the continued greedy stream is bitwise
  what a unified replica would have produced.  The router drives it
  when its replicas declare ``role="prefill"`` / ``role="decode"``.

    from torchgpipe_tpu import fleet, serving
    shared = obs.MetricsRegistry()
    router = fleet.Router({
        name: serving.Engine(cfg, flat, num_slots=4, max_len=64,
                             registry=shared.labeled(replica=name))
        for name in ("r0", "r1")
    }, registry=shared)
    rid = router.submit(prompt, 32, session="user-1")
    router.run()
    tokens = router.result(rid)
"""

from __future__ import annotations

from torchgpipe_tpu.fleet.autoscaler import Autoscaler
from torchgpipe_tpu.fleet.migration import (
    MigrationError,
    migrate,
    stage_rows,
    validate_pools,
)
from torchgpipe_tpu.fleet.prefix_cache import RadixPrefixCache
from torchgpipe_tpu.fleet.rollout import RolloutController
from torchgpipe_tpu.fleet.router import (
    Replica,
    ReplicaDied,
    Router,
    RouterRecord,
)
from torchgpipe_tpu.fleet.speculative import SpeculativeEngine
from torchgpipe_tpu.fleet.trace import (
    TraceConfig,
    TraceRequest,
    TraceStats,
    prefill_heavy_config,
    synthetic_trace,
    tenant_prefixes,
    trace_summary,
)

__all__ = [
    "Autoscaler",
    "MigrationError",
    "RadixPrefixCache",
    "Replica",
    "ReplicaDied",
    "RolloutController",
    "Router",
    "RouterRecord",
    "SpeculativeEngine",
    "TraceConfig",
    "TraceRequest",
    "TraceStats",
    "migrate",
    "prefill_heavy_config",
    "stage_rows",
    "synthetic_trace",
    "tenant_prefixes",
    "trace_summary",
    "validate_pools",
]
