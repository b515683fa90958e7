"""Multi-replica serving fleet (sml_tpu/fleet — ISSUE 15).

Acceptance pins:
- per-replica queue attribution: each replica's admissions land on ITS
  `QueuePressure`, chained into the process-wide DEVICE_QUEUE;
- priority admission: the class ladder sheds lowest-first under
  pressure, the top class preempts the shed order (degrades through
  the endpoint ladder instead of shedding at the router);
- chaos: a replica killed mid-load drains its in-flight requests
  (re-route or shed — never a hung future), dumps a per-replica
  black-box bundle, and the autoscaler backfills;
- staged rollout: a clean candidate promotes replica-by-replica; an
  injected-divergence candidate auto-rolls-back, archives, and evicts
  the diverging replica with its bundle; a promotion landing
  mid-rollout aborts the rollout cleanly (the race test);
- the ContinuousTrainer promotes through the fleet rollout when
  constructed with `fleet=`.
"""

import os
import threading

import numpy as np
import pandas as pd
import pytest

import sml_tpu.tracking as mlflow
from sml_tpu.conf import GLOBAL_CONF
from sml_tpu.ct import CanaryGate
from sml_tpu.fleet import Autoscaler, ReplicaPool, Router
from sml_tpu.ml import DeviceScorer, Pipeline
from sml_tpu.ml.feature import VectorAssembler
from sml_tpu.ml.regression import LinearRegression
from sml_tpu.serving import RequestShed
from sml_tpu.tracking import _store
from sml_tpu.utils.profiler import PROFILER


@pytest.fixture(autouse=True)
def tracking_dir(tmp_path):
    mlflow.set_tracking_uri(str(tmp_path / "runs"))
    mlflow.set_experiment("Default")
    yield
    while mlflow.active_run():
        mlflow.end_run()


@pytest.fixture(autouse=True)
def profiler_on():
    old = GLOBAL_CONF.get("sml.profiler.enabled")
    GLOBAL_CONF.set("sml.profiler.enabled", True)
    yield
    GLOBAL_CONF.set("sml.profiler.enabled", old)


@pytest.fixture()
def obs_on(tmp_path):
    import sml_tpu.obs as obs
    old = GLOBAL_CONF.get("sml.obs.enabled")
    old_bb = GLOBAL_CONF.get("sml.obs.blackboxDir")
    GLOBAL_CONF.set("sml.obs.enabled", True)
    GLOBAL_CONF.set("sml.obs.blackboxDir", str(tmp_path / "blackbox"))
    obs.reset()
    yield
    GLOBAL_CONF.set("sml.obs.enabled", old)
    GLOBAL_CONF.set("sml.obs.blackboxDir", old_bb)
    obs.reset()


def _counter(name):
    return PROFILER.counters().get(name, 0.0)


def _fit_linear(spark, seed=0, slope=2.0):
    rng = np.random.default_rng(seed)
    pdf = pd.DataFrame({"a": rng.normal(size=500),
                        "b": rng.normal(size=500)})
    pdf["y"] = slope * pdf["a"] - pdf["b"] + 1.0 \
        + rng.normal(0, 0.1, len(pdf))
    va = VectorAssembler(inputCols=["a", "b"], outputCol="features")
    return Pipeline(stages=[va, LinearRegression(labelCol="y")]) \
        .fit(spark.createDataFrame(pdf))


def _register(name, *models):
    for m in models:
        with mlflow.start_run():
            mlflow.spark.log_model(m, "model", registered_model_name=name)
    _store.set_version_stage(name, 1, "Production")


def _probe(seed=7, rows=8):
    return np.random.default_rng(seed).normal(size=(rows, 2)) \
        .astype(np.float32)


# --------------------------------------------------- queue attribution
def test_per_replica_queue_attribution(spark):
    """Each replica's admissions land on ITS QueuePressure; the
    process-wide DEVICE_QUEUE still sees the aggregate."""
    from sml_tpu.parallel import dispatch
    _register("fleet-attr", _fit_linear(spark))
    with ReplicaPool("fleet-attr", replicas=2, start=False,
                     timeout_millis=0) as pool:
        r0, r1 = pool.replicas()
        base = dispatch.DEVICE_QUEUE.rows()
        f = r0.endpoint.submit(_probe(rows=5))
        assert r0.pressure() == 5 and r1.pressure() == 0
        assert dispatch.DEVICE_QUEUE.rows() == base + 5
        g = r1.endpoint.submit(_probe(rows=3))
        assert r0.pressure() == 5 and r1.pressure() == 3
        assert dispatch.DEVICE_QUEUE.rows() == base + 8
        for r in (r0, r1):
            r.endpoint._batcher.start()
        f.result(30), g.result(30)
        assert r0.pressure() == 0 and r1.pressure() == 0
        assert dispatch.DEVICE_QUEUE.rows() == base


# --------------------------------------------------- priority admission
def test_priority_shed_ladder_low_sheds_first(spark):
    """Class i of n admits to (n-i)/n of the queue bound: low sheds
    first, normal next, and high preempts the shed order — past every
    bound it lands on the endpoint's own ladder (host fallback off →
    reason-tagged overflow shed)."""
    _register("fleet-ladder", _fit_linear(spark))
    with ReplicaPool("fleet-ladder", replicas=1, start=False,
                     queue_rows=30, host_fallback=False,
                     timeout_millis=0) as pool:
        router = Router(pool, priorities=["high", "normal", "low"])
        X = _probe(rows=5)
        ok = []
        # low admits to 10 rows, then sheds
        ok += [router.submit(X, "low") for _ in range(2)]
        shed_low = router.submit(X, "low")
        with pytest.raises(RequestShed):
            shed_low.result(1)
        # normal still admits (to 20 rows), then sheds
        ok += [router.submit(X, "normal") for _ in range(2)]
        with pytest.raises(RequestShed):
            router.submit(X, "normal").result(1)
        # high still admits (to 30 rows)
        ok += [router.submit(X, "high") for _ in range(2)]
        # ...and past the full bound it PREEMPTS: the endpoint's ladder
        # decides (host fallback off → batcher overflow shed)
        over0 = _counter("serve.shed.overflow")
        with pytest.raises(RequestShed):
            router.submit(X, "high").result(1)
        assert _counter("serve.shed.overflow") == over0 + 1
        assert _counter("fleet.shed.low") >= 1
        assert _counter("fleet.shed.normal") >= 1
        assert _counter("fleet.shed.high") == 0
        pool.replicas()[0].endpoint._batcher.start()
        for f in ok:
            assert f.result(30).shape == (5,)  # admitted traffic served


# --------------------------------------------------------------- chaos
def test_kill_replica_mid_load_reroutes_never_hangs(spark, obs_on,
                                                    tmp_path):
    """Kill a replica with requests in flight: every future resolves
    (re-routed onto the live replica — never a hung ScoreFuture), the
    evicted replica's black-box bundle is on disk, and the autoscaler
    backfills the pool to its floor."""
    m = _fit_linear(spark)
    _register("fleet-kill", m)
    expected = DeviceScorer(m).score_block(_probe(rows=2))
    bb_dir = str(tmp_path / "fleet-bb")
    with ReplicaPool("fleet-kill", replicas=2, start=False,
                     timeout_millis=0, blackbox_dir=bb_dir) as pool:
        router = Router(pool)
        futs = [router.submit(_probe(rows=2)) for _ in range(6)]
        on_dead = [f for f in futs if f.replica_id == 0]
        assert on_dead, "router never routed to replica 0"
        reroutes0 = _counter("fleet.reroutes")
        bundle = pool.kill(0)
        assert bundle is not None and os.path.isdir(bundle)
        assert os.path.isfile(os.path.join(bundle, "MANIFEST.json"))
        # the survivor's worker comes up; every future must resolve
        pool.get(1).endpoint._batcher.start()
        for f in futs:
            np.testing.assert_allclose(f.result(30), expected, rtol=1e-5)
        assert _counter("fleet.reroutes") - reroutes0 == len(on_dead)
        for f in on_dead:
            assert f.replica_id == 1  # re-routed onto the survivor
        # the pool fell under its floor: the autoscaler backfills
        assert pool.size() == 1
        asc = Autoscaler(pool, router, min_replicas=2, max_replicas=3)
        assert asc.step()["action"] == "backfill"
        assert pool.size() == 2


def test_autoscaler_occupancy_bands(spark):
    """Router-observed occupancy above the up-band adds a replica;
    an idle fleet at the down-band retires one (never below the
    floor)."""
    _register("fleet-bands", _fit_linear(spark))
    with ReplicaPool("fleet-bands", replicas=1, start=False,
                     queue_rows=20, timeout_millis=0) as pool:
        router = Router(pool)
        asc = Autoscaler(pool, router, min_replicas=1, max_replicas=2,
                         scale_up_occupancy=0.5,
                         scale_down_occupancy=0.2)
        futs = [router.submit(_probe(rows=4), "high") for _ in range(4)]
        up = asc.step()   # mean observed occupancy crossed 0.5
        assert up["action"] == "up" and pool.size() == 2
        for r in pool.replicas():
            r.endpoint._batcher.start()
        for f in futs:
            f.result(30)
        down = asc.step()  # no admissions since: instantaneous idle
        assert down["action"] == "down" and pool.size() == 1
        assert asc.step()["action"] == "hold"  # never below the floor


# ------------------------------------------------------- staged rollout
def test_staged_rollout_promotes_clean_candidate(spark, obs_on):
    """A near-identical candidate passes every per-replica gate stage,
    the alias commits once, and every replica converges unpinned."""
    m1 = _fit_linear(spark, seed=0, slope=2.0)
    m2 = _fit_linear(spark, seed=0, slope=2.0)  # same data: ~0 diff
    _register("fleet-clean", m1, m2)
    _store.set_version_stage("fleet-clean", 2, "Staging")
    with ReplicaPool("fleet-clean", replicas=2, canary_fraction=1.0,
                     flush_micros=500) as pool:
        gate = CanaryGate(min_mirrored=2, timeout_s=20.0,
                          max_abs_diff=0.2, batch_rows=2)
        v = pool.promote(2, gate=gate, X=_probe(rows=6))
        assert v["passed"] and v["action"] == "promoted"
        assert [s["passed"] for s in v["stages"]] == [True, True]
        assert _counter("fleet.rollout_promotions") >= 1
        for r in pool.replicas():
            assert r.endpoint.current_version() == 2
            assert r.endpoint.pinned_version() is None
    assert _store.resolve_stage("fleet-clean", "Production")["version"] \
        == 2
    assert _store.get_model_version("fleet-clean", 1)["current_stage"] \
        == "Archived"


def test_staged_rollout_rolls_back_on_divergence_and_evicts(
        spark, obs_on, tmp_path):
    """Injected divergence (a candidate trained on a flipped target)
    fails the first gate stage: the rollout rolls back, archives the
    candidate, and evicts the diverging replica with its per-replica
    black-box bundle — Production never moves."""
    m1 = _fit_linear(spark, seed=0, slope=2.0)
    m2 = _fit_linear(spark, seed=1, slope=-3.0)  # diverges hard
    _register("fleet-diverge", m1, m2)
    _store.set_version_stage("fleet-diverge", 2, "Staging")
    bb_dir = str(tmp_path / "rollout-bb")
    with ReplicaPool("fleet-diverge", replicas=2, canary_fraction=1.0,
                     flush_micros=500, blackbox_dir=bb_dir) as pool:
        gate = CanaryGate(min_mirrored=2, timeout_s=20.0,
                          max_abs_diff=0.05, batch_rows=2)
        v = pool.promote(2, gate=gate, X=_probe(rows=6))
        assert v["passed"] is False and v["action"] == "rolled_back"
        assert v["checks"]["divergence"] is False
        assert v["aborted_by_transition"] is False
        assert v["evicted"] == 0  # the replica whose gate failed
        assert v["blackbox"] and os.path.isdir(v["blackbox"])
        assert pool.get(0) is None and pool.size() == 1
        for r in pool.replicas():
            assert r.endpoint.current_version() == 1
            assert r.endpoint.pinned_version() is None
        assert _counter("fleet.rollout_rollbacks") >= 1
    assert _store.resolve_stage("fleet-diverge", "Production")["version"] \
        == 1
    assert _store.get_model_version("fleet-diverge", 2)["current_stage"] \
        == "Archived"


def test_promote_during_rollout_race_aborts_cleanly(spark, obs_on):
    """A promotion landing mid-rollout (the Production alias moves
    underneath) aborts the rollout down the rollback edge WITHOUT an
    eviction (nothing diverged): the fleet converges to whatever the
    alias now names, and the candidate archives only because it still
    held Staging."""
    m = _fit_linear(spark, seed=0, slope=2.0)
    _register("fleet-race", m, _fit_linear(spark, seed=0, slope=2.0),
              _fit_linear(spark, seed=0, slope=2.0))
    _store.set_version_stage("fleet-race", 2, "Staging")

    class RaceGate(CanaryGate):
        """Passes, but lands a competing v3 promotion right after the
        first stage's gate traffic — the alias check must catch it."""

        def run(self, endpoint, X, y, cand, inc):
            verdict = super().run(endpoint, X, y, cand, inc)
            _store.set_version_stage("fleet-race", 3, "Production",
                                     archive_existing_versions=True)
            return verdict

    with ReplicaPool("fleet-race", replicas=2, canary_fraction=1.0,
                     flush_micros=500) as pool:
        gate = RaceGate(min_mirrored=2, timeout_s=20.0, batch_rows=2)
        v = pool.promote(2, gate=gate, X=_probe(rows=6))
        assert v["passed"] is False and v["action"] == "rolled_back"
        assert v["aborted_by_transition"] is True
        assert v["evicted"] is None and v["blackbox"] is None
        assert pool.size() == 2  # nothing evicted
        for r in pool.replicas():
            assert r.endpoint.current_version() == 3  # the race's winner
            assert r.endpoint.pinned_version() is None
    assert _store.resolve_stage("fleet-race", "Production")["version"] == 3
    assert _store.get_model_version("fleet-race", 2)["current_stage"] \
        == "Archived"


def test_concurrent_promotes_serialize_on_the_rollout_lock(spark, obs_on):
    """Two threads promoting different Staging candidates through the
    same pool SERIALIZE on the rollout lock: each rollout runs whole
    (stages never interleave), the fleet converges to the later
    winner's alias, nothing stays pinned, and exactly one version holds
    Production — never a torn fleet."""
    m = _fit_linear(spark, seed=0, slope=2.0)
    _register("fleet-dual", m, _fit_linear(spark, seed=0, slope=2.0),
              _fit_linear(spark, seed=0, slope=2.0))
    _store.set_version_stage("fleet-dual", 2, "Staging")
    _store.set_version_stage("fleet-dual", 3, "Staging")
    results, errors = {}, {}
    with ReplicaPool("fleet-dual", replicas=2, canary_fraction=1.0,
                     flush_micros=500) as pool:
        gate = CanaryGate(min_mirrored=1, timeout_s=20.0, batch_rows=2)

        def promote(version):
            try:
                results[version] = pool.promote(version, gate=gate,
                                                X=_probe(rows=4))
            except ValueError as e:  # candidate left Staging meanwhile
                errors[version] = e

        threads = [threading.Thread(target=promote, args=(v,))
                   for v in (2, 3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not errors and len(results) == 2
        # serialized rollouts both complete whole; the fleet converges
        # on whichever committed LAST, and the other is archived
        final = _store.resolve_stage("fleet-dual", "Production")["version"]
        assert final in (2, 3)
        other = 2 if final == 3 else 3
        assert _store.get_model_version("fleet-dual", other)[
            "current_stage"] == "Archived"
        stages = {_store.get_model_version("fleet-dual", v)[
            "current_stage"] for v in (1, 2, 3)}
        assert sorted(stages) == ["Archived", "Production"]
        for r in pool.replicas():
            assert r.endpoint.current_version() == final
            assert r.endpoint.pinned_version() is None


# ------------------------------------------------------- health surface
def test_engine_health_fleet_block_and_shed_reasons(spark, obs_on):
    """engine_health() grows a `fleet` block (per-replica table,
    shed-by-class) and a `shed` block (reason-tagged serve.shed)."""
    from sml_tpu import obs
    _register("fleet-health", _fit_linear(spark))
    with ReplicaPool("fleet-health", replicas=2, start=False,
                     queue_rows=12, host_fallback=False,
                     timeout_millis=0) as pool:
        router = Router(pool, priorities=["high", "low"])
        # low admits to 1/2 of each replica's 12-row bound: one request
        # per replica fits, the third finds every class bound exhausted
        router.submit(_probe(rows=5), "low")
        router.submit(_probe(rows=5), "low")
        with pytest.raises(RequestShed):
            router.submit(_probe(rows=5), "low").result(1)
        health = obs.engine_health()
        fl = health["fleet"]
        assert fl is not None and fl["shed_by_class"]["low"] >= 1
        p = [b for b in fl["pools"] if b["name"] == "fleet-health"][0]
        assert p["size"] == 2 and len(p["replicas"]) == 2
        assert p["replicas"][0]["queue_rows"] == 5
        assert health["shed"]["total"] >= 0.0
        for r in pool.replicas():
            r.endpoint._batcher.start()
    # after the pool closes its report leaves the registry
    from sml_tpu.fleet import fleet_report
    rep = fleet_report()
    assert rep is None or all(b["name"] != "fleet-health"
                              for b in rep["pools"])


def test_replica_start_shares_warm_caches(spark, tmp_path):
    """Replica 2 lands on replica 1's warm program caches: the prewarm
    guard is claimed once per (manifest, mesh) and the shared-cache
    skip is counted."""
    from sml_tpu.parallel import prewarm
    prev_dir = GLOBAL_CONF.get("sml.compile.cacheDir")
    GLOBAL_CONF.set("sml.compile.cacheDir", str(tmp_path / "cache"))
    GLOBAL_CONF.set("sml.prewarm.enabled", True)
    ran = dict(prewarm._ran)
    prewarm._ran.clear()
    try:
        _register("fleet-warm", _fit_linear(spark))
        skip0 = _counter("prewarm.replica_skip")
        with ReplicaPool("fleet-warm", replicas=2,
                         flush_micros=500) as pool:
            assert pool.size() == 2
            assert prewarm._ran.get(prewarm._guard_key()) is True
            assert _counter("prewarm.replica_skip") == skip0 + 1
    finally:
        GLOBAL_CONF.unset("sml.prewarm.enabled")
        GLOBAL_CONF.set("sml.compile.cacheDir", prev_dir or "")
        prewarm._ran.clear()
        prewarm._ran.update(ran)


# ------------------------------------------------- continuous training
def test_ct_trainer_promotes_through_fleet(spark, tmp_path, obs_on):
    """ContinuousTrainer(fleet=pool): a drifted window's warm refit
    promotes through the STAGED FLEET ROLLOUT — every replica gated,
    pinned, then converged on the committed alias."""
    from sml_tpu.ct import ContinuousTrainer, DeltaChunkSource
    from sml_tpu.frame._chunks import ArrayChunkSource
    from sml_tpu.ml._chunked import fit_ensemble_chunked
    from sml_tpu.ml.regression import GBTRegressionModel

    F = 6
    cols = [f"f{i}" for i in range(F)]

    def data(n, seed, shift=False):
        rng = np.random.default_rng(seed)
        X = rng.normal(size=(n, F))
        if shift:
            X[:, 0] += 1.5
            X[:, 2] *= 2.0
        y = (2.0 * X[:, 0] + 0.5 * X[:, 2] - X[:, 1] ** 2
             + rng.normal(0, 0.2, n)).astype(np.float32)
        return X, y

    Xt, yt = data(2400, seed=11)
    spec = fit_ensemble_chunked(
        ArrayChunkSource(Xt, yt, chunk_rows=700), categorical={},
        max_depth=3, max_bins=16, n_trees=6, seed=7, loss="squared",
        step_size=0.3, boosting=True)
    assert spec.baseline is not None
    with mlflow.start_run():
        mlflow.spark.log_model(GBTRegressionModel(spec), "model",
                               registered_model_name="fleet-ct")
    _store.set_version_stage("fleet-ct", 1, "Production")

    dpath = str(tmp_path / "stream")
    Xs, ys = data(900, seed=22, shift=True)
    pdf = pd.DataFrame({c: Xs[:, i] for i, c in enumerate(cols)})
    pdf["y"] = ys.astype(float)
    spark.createDataFrame(pdf).write.format("delta") \
        .mode("errorifexists").save(dpath)

    with ReplicaPool("fleet-ct", replicas=2, canary_fraction=1.0,
                     flush_micros=500) as pool:
        trainer = ContinuousTrainer(
            "fleet-ct", DeltaChunkSource(dpath, cols, "y"),
            fleet=pool,
            gate=CanaryGate(min_mirrored=3, timeout_s=20.0,
                            quality_tol=1.2, batch_rows=64),
            fit_params={"seed": 7, "rounds_per_dispatch": 2},
            warm_rounds=3, min_rows=512, full_severity=1e9)
        rep = trainer.step()
        assert rep["action"] == "promoted", rep
        assert rep["refit"] == "warm"
        gate = rep["gate"]
        assert gate["passed"] and gate["action"] == "promoted"
        assert len(gate["stages"]) == 2
        assert all(s["passed"] for s in gate["stages"])
        for r in pool.replicas():
            assert r.endpoint.current_version() == 2
            assert r.endpoint.pinned_version() is None
    assert _store.resolve_stage("fleet-ct", "Production")["version"] == 2
    assert _store.get_model_version("fleet-ct", 1)["current_stage"] \
        == "Archived"
    assert trainer.stats()["promotions"] == 1


# --------------------------------------------------- shed reason tags
def test_deadline_shed_counts_reason(spark):
    """The deadline shed path is reason-tagged next to the total."""
    import time

    from sml_tpu.serving import MicroBatcher
    m = _fit_linear(spark)
    scorer = DeviceScorer(m)
    b = MicroBatcher(scorer.score_block, max_batch_rows=16,
                     timeout_millis=30, flush_micros=1000, start=False)
    futs = [b.submit(_probe(rows=1)) for _ in range(3)]
    time.sleep(0.1)
    d0 = _counter("serve.shed.deadline")
    b.start()
    for f in futs:
        with pytest.raises(RequestShed):
            f.result(30)
    b.close()
    assert _counter("serve.shed.deadline") == d0 + 3
