"""sml_tpu.fleet — the multi-replica serving fleet.

PR 4's `ServingEndpoint` is ONE replica: one micro-batcher, one warm
scorer, one admission queue. The ROADMAP's "million-user scale" story
needs a TIER of them, and every coordination failure mode the
distributed-training literature catalogues for a mesh of chips
(stragglers, unattributed queueing, silent partial failure) applies to
a tier of replicas just the same. This package is that tier:

- `Replica` / `ReplicaPool` (`_replica`, `_pool`): N warm
  `ServingEndpoint` replicas of one registry model+stage. Each replica
  owns a private `parallel.dispatch.QueuePressure(parent=DEVICE_QUEUE)`
  so the router sees PER-REPLICA standing rows while the process-wide
  dispatcher signal still aggregates, and replica start rides the
  per-(manifest, mesh) prewarm guard (`parallel/prewarm.py`) — the
  first replica replays the manifest, later ones land on already-warm
  program caches (counted `prewarm.replica_skip`), so no replica pays
  a fresh compile. An evicted replica dumps a per-replica black-box
  bundle (`obs.dump_blackbox`) before teardown.
- `Router` (`_router`): picks a replica per request from the
  per-replica queue-pressure signal and the audit-calibrated batch
  wall (`dispatch.device_ms`, fed by the dispatch audit's attach
  path), with PRIORITY ADMISSION: `sml.fleet.priorities` classes shed
  lowest-first under pressure (each class admits up to a shrinking
  fraction of every replica's queue bound; the SLO burn-rate past 1.0
  halves the non-top classes' share), and the top class preempts the
  shed order — when every class bound is exhausted it still lands on
  the least-loaded replica's own degradation ladder instead of
  shedding. A request whose replica dies under it is RE-ROUTED (or
  shed) — never a hung `ScoreFuture`.
- `Autoscaler` (`_pool`): adds/retires warm replicas from occupancy
  and burn-rate bands (`sml.fleet.minReplicas` / `maxReplicas` /
  `scaleUpOccupancy` / `scaleDownOccupancy`), and backfills a pool
  that fell below its floor (a killed replica).
- `ReplicaPool.promote` (`_rollout`): fleet-level canary promotion —
  a Staging candidate rolls out replica-by-replica, each stage judged
  by the PR-14 `CanaryGate` (mirror quorum, zero errors, divergence,
  quality) on a replica still serving the incumbent; any failed stage
  auto-rolls-back every pinned replica, archives the candidate, and
  evicts the diverging replica with its black-box bundle. A promotion
  that lands mid-rollout (the stage alias moved underneath) aborts
  the rollout the same way. `ct.ContinuousTrainer(fleet=pool)`
  promotes refits through this path instead of a single endpoint.

Observability: `fleet.*` counters/events/gauges (obs/taxonomy.py),
`fleet.route` events carrying each request's trace id through the
router fan-in, and the `fleet` block of `obs.engine_health()`
(`fleet_report()`). See docs/FLEET.md.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional

from ..conf import _register

_register("sml.fleet.minReplicas", 1, int,
          "Fleet floor: the autoscaler never retires below this many "
          "replicas, and backfills a pool that fell under it (a killed "
          "replica). Also ReplicaPool's default initial size")
_register("sml.fleet.maxReplicas", 4, int,
          "Fleet ceiling: the autoscaler never adds past this many "
          "replicas — each replica pins a warm scorer and a standing "
          "queue, and the device lane is shared no matter how many "
          "batchers feed it")
_register("sml.fleet.scaleUpOccupancy", 0.75, float,
          "Autoscaler scale-up band: mean fleet queue occupancy "
          "(standing rows / admission bound, averaged over the router's "
          "observations since the last step) at or above this adds one "
          "warm replica; an SLO burn-rate past 1.0 scales up regardless "
          "of occupancy")
_register("sml.fleet.scaleDownOccupancy", 0.2, float,
          "Autoscaler scale-down band: mean fleet occupancy at or below "
          "this (with the SLO burn-rate at or under 1.0) gracefully "
          "retires the least-loaded replica (its queue drains; nothing "
          "sheds)")
_register("sml.fleet.priorities", "high,normal,low", str,
          "Priority classes for fleet admission, highest first. Class i "
          "of n admits onto a replica only while its standing rows stay "
          "under (n-i)/n of the queue bound, so the LOWEST class sheds "
          "first as pressure rises and the top class preempts the shed "
          "order (it degrades through the endpoint's own host-fallback "
          "ladder instead of shedding). An SLO burn-rate past 1.0 "
          "halves every non-top class's share")
_register("sml.fleet.burstSlopeWindowSec", 10.0, float,
          "Burst-anticipating admission: the router fits a least-squares "
          "slope to the SLO burn-rate samples inside this window. The "
          "slope is the burst's LEADING edge — the windowed burn level "
          "still averages a fresh burst away while the slope already "
          "points at it")
_register("sml.fleet.burstSlopeHorizonSec", 0.0, float,
          "Burst-anticipating admission horizon: when the current burn "
          "level plus its slope extrapolated this many seconds forward "
          "crosses 1.0, non-top classes pre-tighten (counted "
          "fleet.burst_tighten) BEFORE the budget is actually spent. "
          "0 disables the predictor (admission reacts to the level only)")
_register("sml.fleet.burstSlopeTighten", 0.5, float,
          "Multiplier applied to every non-top class's admission share "
          "while the burn-rate slope predicts a breach within "
          "sml.fleet.burstSlopeHorizonSec (the pre-breach analogue of "
          "the burn>1 halving)")
_register("sml.fleet.autoscalePollSec", 2.0, float,
          "Interval of Autoscaler.start()'s background band evaluation "
          "(Autoscaler.step() is the same evaluation on demand)")

from ._pool import Autoscaler, ReplicaPool  # noqa: E402
from ._replica import Replica, ReplicaGone  # noqa: E402
from ._router import FleetFuture, Router, priority_classes  # noqa: E402

__all__ = ["Replica", "ReplicaGone", "ReplicaPool", "Autoscaler",
           "Router", "FleetFuture", "fleet_report", "priority_classes"]

# ------------------------------------------------------------ registry
# live pools, for the `fleet` block of obs.engine_health() (read lazily
# off sys.modules, so a health poll never imports this package)
_pools_lock = threading.Lock()
_POOLS: List["ReplicaPool"] = []


def _register_pool(pool: "ReplicaPool") -> None:
    with _pools_lock:
        if pool not in _POOLS:
            _POOLS.append(pool)


def _unregister_pool(pool: "ReplicaPool") -> None:
    with _pools_lock:
        if pool in _POOLS:
            _POOLS.remove(pool)


def fleet_report() -> Optional[Dict[str, object]]:
    """The fleet block of `obs.engine_health()`: every live pool's
    replica table (per-replica standing rows, occupancy, resolved/
    pinned version, liveness) next to the shed-by-class counters and
    rollout state. None until a pool exists — like the straggler and
    infer_kernel blocks, absence means the subsystem never ran."""
    with _pools_lock:
        pools = list(_POOLS)
    if not pools:
        return None
    # counters come from whichever stream is live: the recorder's totals
    # (engine_metrics' source, independent of sml.profiler.enabled) and
    # the profiler's — both see the same increments when both are on,
    # so max() never double-counts
    from ..obs._recorder import RECORDER
    from ..utils.profiler import PROFILER
    counters = dict(PROFILER.counters())
    for k, v in RECORDER.counters().items():
        counters[k] = max(counters.get(k, 0.0), v)
    shed = {c: counters.get(f"fleet.shed.{c}", 0.0)
            for c in priority_classes()}
    return {
        "pools": [p.report() for p in pools],
        "shed_by_class": shed,
        "requests": counters.get("fleet.requests", 0.0),
        "reroutes": counters.get("fleet.reroutes", 0.0),
        "scale_up": counters.get("fleet.scale_up", 0.0),
        "scale_down": counters.get("fleet.scale_down", 0.0),
        "rollout_promotions": counters.get("fleet.rollout_promotions",
                                           0.0),
        "rollout_rollbacks": counters.get("fleet.rollout_rollbacks", 0.0),
    }
