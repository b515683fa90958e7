"""One fleet replica: a ServingEndpoint with attributable pressure.

A replica is the unit the router routes to, the autoscaler adds and
retires, and the rollout pins — so it must be individually OBSERVABLE
(its own standing queue rows, not a share of one global number) and
individually KILLABLE (a dead replica's in-flight batches must fail
fast so the router can re-route them, instead of serving from a scorer
the fleet already declared gone).

Both properties are one wrapper deep:

- pressure: the replica owns a `QueuePressure(parent=DEVICE_QUEUE)`
  and hands it to its endpoint's `MicroBatcher`, so admissions feed
  BOTH the per-replica signal the router reads and the process-wide
  dispatcher signal (`parallel/dispatch.py` — the device lane is
  shared no matter how many batchers feed it);
- killability: `_ReplicaEndpoint` checks the replica's poison flag on
  every device/host scoring call. `poison()` (a simulated crash — the
  chaos tests' entry point, and `ReplicaPool.kill`'s first step) makes
  every in-flight batch raise `ReplicaGone`, which the batcher lands
  on each request's future — nothing hangs, and the router-level
  `FleetFuture` re-routes on exactly this shape.
"""

from __future__ import annotations

import threading
from typing import Dict, Optional

import numpy as np

from ..parallel import dispatch
from ..serving._endpoint import ServingEndpoint


class ReplicaGone(RuntimeError):
    """The replica this work was queued on was killed/evicted; the
    router re-routes (or sheds) the request — callers only see this if
    they bypassed the router and held a replica-level future."""


class _ReplicaEndpoint(ServingEndpoint):
    """The replica's endpoint: same resolution/batching/canary
    machinery, plus the poison check that makes a killed replica fail
    fast instead of serving stale results."""

    def __init__(self, replica: "Replica", *args, **kwargs):
        # bound before super().__init__ wires the batcher: a scoring
        # call can only arrive once the batcher exists
        self._replica_ref = replica
        super().__init__(*args, **kwargs)

    def _score_device(self, X: np.ndarray) -> np.ndarray:
        self._replica_ref._check_poisoned()
        return super()._score_device(X)

    def _score_host(self, X: np.ndarray) -> np.ndarray:
        self._replica_ref._check_poisoned()
        return super()._score_host(X)

    def _drift_key(self) -> str:
        # N replicas of one model+stage must not share one drift
        # registry slot: same-keyed endpoints clobber each other's
        # registration, and the last-registrant's eviction would
        # silently remove drift coverage the survivors still feed
        return (f"serve.{self._name}/{self._stage}"
                f"/r{self._replica_ref.rid}")


class Replica:
    """One warm serving replica of `models:/<name>/<stage>`."""

    def __init__(self, rid: int, name: str, stage: str = "Production",
                 **endpoint_kwargs):
        self.rid = int(rid)
        self._lock = threading.Lock()
        self._alive = True
        self._poisoned = False
        #: this replica's standing-rows signal; chained into the
        #: process-wide DEVICE_QUEUE so the dispatcher still sees the
        #: aggregate while the router sees THIS replica
        self.queue = dispatch.QueuePressure(parent=dispatch.DEVICE_QUEUE)
        self.endpoint = _ReplicaEndpoint(self, name, stage,
                                         queue=self.queue,
                                         **endpoint_kwargs)
        #: the admission bound the router's class ladder scales
        self.queue_bound = int(self.endpoint._batcher.queue_rows)

    # -------------------------------------------------------------- state
    @property
    def alive(self) -> bool:
        with self._lock:
            return self._alive

    def _check_poisoned(self) -> None:
        with self._lock:
            poisoned = self._poisoned
        if poisoned:
            raise ReplicaGone(f"replica {self.rid} was killed")

    def poison(self) -> None:
        """Simulate a crash: every in-flight and future scoring call on
        this replica raises ReplicaGone (landed on each request's
        future by the batcher — nothing hangs)."""
        with self._lock:
            self._poisoned = True
            self._alive = False

    def retire(self) -> None:
        """Graceful removal: stop receiving router traffic; the queue
        drains normally (close() still serves everything queued)."""
        with self._lock:
            self._alive = False

    # ------------------------------------------------------------ signals
    def pressure(self) -> int:
        """Standing rows queued toward the device on THIS replica."""
        return self.queue.rows()

    def occupancy(self) -> float:
        """pressure / admission bound — the autoscaler's band signal."""
        return self.queue.rows() / max(self.queue_bound, 1)

    def report(self) -> Dict[str, object]:
        return {
            "rid": self.rid,
            "alive": self.alive,
            "queue_rows": self.pressure(),
            "queue_bound": self.queue_bound,
            "occupancy": round(self.occupancy(), 4),
            "version": self.endpoint.current_version(),
            "pinned": self.endpoint.pinned_version(),
        }

    # ---------------------------------------------------------- lifecycle
    def close(self) -> None:
        self.endpoint.close()

    def __repr__(self) -> str:  # pragma: no cover — debugging aid
        return (f"Replica(rid={self.rid}, alive={self.alive}, "
                f"rows={self.pressure()})")
