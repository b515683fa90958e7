"""Registry-backed serving endpoint: stage aliases, hot-swap, canary.

`ServingEndpoint("model", "Production")` is the engine-side shape of the
course's registry-staged REST scorer (`ML 05`'s stage transitions feeding
the real-time-deployment elective): the endpoint binds a NAME + STAGE
ALIAS, not a version. Resolution goes through
`tracking._store.resolve_stage`; the store's `on_stage_transition` hook
fires on every `transition_model_version_stage` commit, so a promotion
hot-swaps the serving scorer in-process — in-flight batches finish on the
old version, the next batch scores on the new one, and nothing polls.

Warm scorers come from the multi-model `ModelCache` (compile once, serve
many); requests ride the `MicroBatcher` (coalescing + admission control +
host-route degradation). Canary mode (`sml.serve.canaryFraction` > 0)
mirrors a deterministic fraction of traffic to the Staging version OFF
the request path (host route, one shadow worker) and accumulates
prediction-divergence stats — the promote-with-confidence loop.
"""

from __future__ import annotations

import os
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Optional

import numpy as np

from ..conf import GLOBAL_CONF
from ..obs import _context as _trace
from ..obs import drift as _drift
from ..obs._metrics import METRICS as _METRICS
from ..obs._recorder import RECORDER as _OBS
from ..tracking import _store
from ..utils.profiler import PROFILER
from ._batcher import MicroBatcher, ScoreFuture
from ._cache import MODEL_CACHE, ModelCache


def _load_scorer(name: str, version) -> object:
    """DeviceScorer over a registry version's native (spark-flavor) model
    payload — the load the cache amortizes."""
    from ..ml.base import Saveable
    from ..ml.inference import DeviceScorer
    native = os.path.join(_store.model_dir(name), "versions", str(version),
                          "model", "native")
    if not os.path.isdir(native):
        raise ValueError(
            f"registered model {name!r} version {version} has no native "
            f"model payload (log it with tracking.spark.log_model)")
    return DeviceScorer(Saveable.load(native))


class ServingEndpoint:
    """Online scorer for `models:/<name>/<stage>`.

    `score(X)` blocks for the prediction; `submit(X)` returns a
    `ScoreFuture` (the closed-loop client shape). Batcher knobs
    (`max_batch_rows`, `flush_micros`, `queue_rows`, `timeout_millis`,
    `host_fallback`, `start`) pass through to `MicroBatcher`; defaults
    come from the `sml.serve.*` conf keys."""

    def __init__(self, name: str, stage: str = "Production", *,
                 model_cache: Optional[ModelCache] = None,
                 auto_update: bool = True,
                 canary_fraction: Optional[float] = None,
                 **batcher_kwargs):
        self._name = name
        self._stage = stage
        self._cache = model_cache or MODEL_CACHE
        self._swap_lock = threading.RLock()
        self._scorer = None
        self._version: Optional[int] = None
        self._pinned: Optional[int] = None
        self._staging_scorer = None
        self._staging_version: Optional[int] = None
        self._canary_fraction = canary_fraction
        self._canary_lock = threading.Lock()
        self._canary_acc = 0.0
        self._shadow_inflight = 0
        self._canary = {"mirrored": 0, "rows": 0, "sum_abs_diff": 0.0,
                        "max_abs_diff": 0.0, "errors": 0}
        self._drift: Optional[_drift.DriftMonitor] = None
        self._shadow_pool: Optional[ThreadPoolExecutor] = None
        self._closed = False
        # opt-in manifest replay (sml.prewarm.enabled), once per process,
        # in the background: a later hot-swap finds its scorer programs
        # (forest/linear forwards over the serving shape buckets) already
        # first-dispatched instead of paying that first dispatch mid-traffic
        from ..parallel import prewarm as _prewarm
        _prewarm.maybe_prewarm()
        self._refresh(initial=True)
        self._listener = self._on_transition if auto_update else None
        if self._listener is not None:
            _store.on_stage_transition(self._listener)
        self._batcher = MicroBatcher(self._score_device,
                                     host_score=self._score_host,
                                     observer=self._observe_scores,
                                     **batcher_kwargs)

    # ----------------------------------------------------------- resolution
    def _refresh(self, initial: bool = False) -> None:
        """Re-resolve the stage alias (and the Staging canary target) and
        swap the warm scorer if the resolved version changed."""
        meta = _store.resolve_stage(self._name, self._stage)
        if meta is None:
            if initial:
                raise ValueError(
                    f"no READY version of {self._name!r} holds stage "
                    f"{self._stage!r} — promote one with "
                    f"transition_model_version_stage first")
            return  # keep serving the last good version (alias emptied)
        version = meta["version"]
        with self._swap_lock:
            if self._pinned is None and version != self._version:
                self._scorer = self._cache.get(
                    self._name, version,
                    lambda: _load_scorer(self._name, version))
                old, self._version = self._version, version
                if not initial:
                    PROFILER.count("serve.hot_swap")
                    if _OBS.enabled:
                        _OBS.emit("serve", "serve.swap", args={
                            "name": self._name, "stage": self._stage,
                            "from": old, "to": version})
        if self._stage != "Staging":
            smeta = _store.resolve_stage(self._name, "Staging")
            with self._swap_lock:
                changed = False
                if smeta is None:
                    changed = self._staging_version is not None
                    self._staging_scorer = self._staging_version = None
                elif smeta["version"] != self._staging_version:
                    v = smeta["version"]
                    self._staging_scorer = self._cache.get(
                        self._name, v, lambda: _load_scorer(self._name, v))
                    self._staging_version = v
                    changed = True
            if changed:
                # the divergence stats describe the CURRENT canary
                # target: a new candidate entering Staging starts from
                # zero — a past candidate's running max must not poison
                # every later gate on this endpoint (the max is folded
                # monotonically and can never come back down)
                with self._canary_lock:
                    self._canary = {"mirrored": 0, "rows": 0,
                                    "sum_abs_diff": 0.0,
                                    "max_abs_diff": 0.0, "errors": 0}
        self._install_drift()

    def _drift_key(self) -> str:
        # stage is part of the identity: a Production and a Staging
        # endpoint of the same model must not clobber each other's
        # monitor registration
        return f"serve.{self._name}/{self._stage}"

    def _install_drift(self) -> None:
        """(Re)bind the drift monitor to the CURRENT scorer's training
        baseline (obs/drift.py): tree models carry one in their
        persisted spec, so a registry version resolves WITH the
        distribution it was trained on. Models without a baseline
        (linear, pre-drift artifacts) serve unmonitored."""
        key = self._drift_key()
        # `_drift` is written from the stage-transition listener thread
        # (via _refresh) AND from close(): every rebind holds _swap_lock
        # so a close racing a hot-swap cannot leave a monitor registered
        # with no owner (readers snapshot — `_observe_scores`)
        with self._swap_lock:
            if self._closed:
                # a close() that already swept `_drift` must not have a
                # straggling listener re-register a monitor on a dead
                # endpoint (close sets _closed before taking this lock)
                return
            spec = getattr(getattr(self._scorer, "_model", None),
                           "_spec", None)
            baseline = getattr(spec, "baseline", None)
            old = self._drift
            if baseline is None:
                self._drift = None
                if old is not None:
                    _drift.DRIFT.unregister(key, old)
            elif old is not None and old.baseline is baseline:
                # same version: re-assert the registration (self-heals if
                # a same-keyed endpoint's close ever raced it away)
                _drift.DRIFT.register(key, old)
            else:
                # a hot-swap re-baselines: the new version's training
                # distribution is the comparison target from here on
                mon = _drift.DriftMonitor(baseline, name=key)
                self._drift = mon
                _drift.DRIFT.register(key, mon)

    def _observe_scores(self, X, preds, traces) -> None:
        """MicroBatcher observer: feed the scored block into the live
        drift window (no-op without a baseline-carrying model)."""
        mon = self._drift
        if mon is not None:
            mon.observe_block(X, preds, traces)

    def _on_transition(self, name, version, stage, archived) -> None:
        if name != self._name or self._closed:
            return
        self._refresh()
        # an archived version holds no stage: no endpoint resolves to it
        # anymore, so its warm scorer must not sit in the cache until LRU
        # pressure happens to evict it
        for v in archived:
            self._cache.invalidate(self._name, v)

    def current_version(self) -> Optional[int]:
        return self._version

    # ----------------------------------------------------------- pinning
    def pin_version(self, version: int) -> None:
        """Pin the PRIMARY scorer to an explicit registry version — the
        per-replica switch a staged fleet rollout makes while the stage
        alias still points at the incumbent. Stage-transition listeners
        keep firing (the Staging canary target still tracks) but the
        primary no longer follows the alias until `unpin()`; a pinned
        swap emits the same `serve.swap` receipt as a hot-swap, tagged
        pinned=True."""
        version = int(version)
        with self._swap_lock:
            self._pinned = version
            if version != self._version:
                self._scorer = self._cache.get(
                    self._name, version,
                    lambda: _load_scorer(self._name, version))
                old, self._version = self._version, version
                PROFILER.count("serve.hot_swap")
                if _OBS.enabled:
                    _OBS.emit("serve", "serve.swap", args={
                        "name": self._name, "stage": self._stage,
                        "from": old, "to": version, "pinned": True})
        self._install_drift()

    def unpin(self) -> None:
        """Drop the pin and fall back to stage-alias resolution (the
        rollout's rollback edge: the replica re-resolves the incumbent
        the alias still names)."""
        with self._swap_lock:
            if self._pinned is None:
                return
            self._pinned = None
        self._refresh()

    def pinned_version(self) -> Optional[int]:
        with self._swap_lock:
            return self._pinned

    # -------------------------------------------------------------- scoring
    def _score_device(self, X: np.ndarray) -> np.ndarray:
        return self._scorer.score_block(X)

    def _score_host(self, X: np.ndarray) -> np.ndarray:
        return self._scorer.score_block_host(X)

    def submit(self, X: np.ndarray) -> ScoreFuture:
        fut = self._batcher.submit(X)
        f = self._canary_fraction
        if f is None:
            f = float(GLOBAL_CONF.get("sml.serve.canaryFraction"))
        if f > 0.0 and self._staging_scorer is not None:
            with self._canary_lock:
                self._canary_acc += min(f, 1.0)
                mirror = self._canary_acc >= 1.0
                if mirror:
                    self._canary_acc -= 1.0
            if mirror:
                self._shadow(np.asarray(X), fut)
        return fut

    def score(self, X: np.ndarray,
              timeout: Optional[float] = None) -> np.ndarray:
        return self.submit(X).result(timeout)

    # --------------------------------------------------------------- canary
    _SHADOW_MAX_INFLIGHT = 8  # beyond this the shadow sheds, never queues

    def _shadow(self, X: np.ndarray, fut: ScoreFuture) -> None:
        with self._canary_lock:
            # bounded mirror backlog: the shadow is best-effort sampling —
            # when the single host-route worker falls behind the arrival
            # rate, DROP the mirror (each queued entry would pin a copy of
            # X until scored; an unbounded backlog is a slow OOM)
            if self._shadow_inflight >= self._SHADOW_MAX_INFLIGHT:
                return
            self._shadow_inflight += 1
            if self._shadow_pool is None:
                self._shadow_pool = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="sml-serve-shadow")
            pool = self._shadow_pool
        pool.submit(self._mirror, X, fut)

    def _mirror(self, X: np.ndarray, fut: ScoreFuture) -> None:
        """Score the mirrored request on the Staging version's HOST route
        (the shadow must not contend for the production device queue) and
        fold the divergence into the canary stats — both the running
        sums AND the `serve.canary_abs_diff` metrics histogram (PR-7
        core), with the request's trace id as the observation's exemplar
        so `canary_stats()` can name the literal worst-diverging
        request. Never raises into the serving path — but a failed
        shadow COUNTS (`serve.canary_error` + the stats' `errors`
        field): a dead canary reporting zero divergence forever is
        exactly the silent failure this layer exists to name."""
        try:
            primary = np.asarray(fut.result(timeout=60.0), dtype=np.float64)
            scorer = self._staging_scorer
            if scorer is None:
                return
            shadow = np.asarray(scorer.score_block_host(X),
                                dtype=np.float64)
            diff = np.abs(shadow - primary)
            PROFILER.count("serve.canary_mirrored")
            _METRICS.observe("serve.canary_abs_diff", float(diff.max()),
                             exemplar=fut.trace_id)
            with self._canary_lock:
                self._canary["mirrored"] += 1
                self._canary["rows"] += int(diff.size)
                self._canary["sum_abs_diff"] += float(diff.sum())
                self._canary["max_abs_diff"] = max(
                    self._canary["max_abs_diff"], float(diff.max()))
        except BaseException:  # noqa: BLE001 — shadow must never serve 500s
            PROFILER.count("serve.canary_error")
            with self._canary_lock:
                self._canary["errors"] += 1
        finally:
            with self._canary_lock:
                self._shadow_inflight -= 1

    def canary_stats(self) -> Dict[str, float]:
        with self._canary_lock:
            out = dict(self._canary)
        out["staging_version"] = self._staging_version
        out["mean_abs_diff"] = (out["sum_abs_diff"] / out["rows"]
                                if out["rows"] else 0.0)
        # windowed divergence quantiles + the literal worst-diverging
        # request, from the serve.canary_abs_diff histogram (all-time
        # sums above survive recorder-off phases; these fields need the
        # recorder on while mirroring)
        hist = _METRICS.histogram("serve.canary_abs_diff")
        if hist is not None:
            window = float(GLOBAL_CONF.getInt("sml.obs.metricsWindowSec"))
            out["abs_diff_p50"] = hist.quantile(0.50, window)
            out["abs_diff_p99"] = hist.quantile(0.99, window)
            worst, tid = hist.worst()
            out["worst_abs_diff"] = float(worst)
            out["worst_trace"] = _trace.hex_id(tid)
        return out

    # ---------------------------------------------------------------- health
    def health_report(self, window_s: Optional[float] = None
                      ) -> Dict[str, object]:
        """The live health surface for THIS endpoint: the engine-wide
        `obs.engine_health()` snapshot (streaming-metric quantiles incl.
        `serve.request_ms`, dispatch audit, HBM ledger, SLO burn-rate)
        plus the endpoint's own state — resolved version, queue depth,
        and canary divergence. Everything reads bounded in-memory state,
        so a liveness probe can poll it."""
        from .. import obs
        health = obs.engine_health(window_s)
        scorer = self._scorer
        health["endpoint"] = {
            "name": self._name,
            "stage": self._stage,
            "version": self._version,
            "pinned": self._pinned,
            "staging_version": self._staging_version,
            "queued_rows": self._batcher.queued_rows(),
            "max_batch_rows": self._batcher.max_batch_rows,
            "closed": self._closed,
            "canary": self.canary_stats(),
            # THIS replica's resolved traversal spec (None until a
            # device-routed forest dispatch) — next to the engine-wide
            # `infer_kernel` block, so a replica silently off the
            # compiled kernel is attributable to the endpoint
            "kernel": (scorer.kernel_spec()
                       if hasattr(scorer, "kernel_spec") else None),
        }
        return health

    # ------------------------------------------------------------ lifecycle
    def close(self) -> None:
        self._closed = True
        if self._listener is not None:
            _store.remove_stage_listener(self._listener)
            self._listener = None
        self._batcher.close()
        # take the monitor under the same lock _install_drift rebinds it
        # under; unregister outside the lock (registry has its own)
        with self._swap_lock:
            mon, self._drift = self._drift, None
        if mon is not None:
            _drift.DRIFT.unregister(self._drift_key(), mon)
        with self._canary_lock:
            pool, self._shadow_pool = self._shadow_pool, None
        if pool is not None:
            pool.shutdown(wait=True)

    def __enter__(self) -> "ServingEndpoint":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
