"""Model & data drift layer (obs/drift.py) — ISSUE 11.

Acceptance: sketch serialization round-trips bit-compatibly (exact AND
compressed modes, merge-compatible after load); distances agree between
exact and compressed sketches; an iid holdout split never false-positives
while an injected covariate shift flags exactly the moved features;
fitted tree models carry their training baseline through `_save_to`/load
and `tracking.log_model` (reloaded-vs-self distance exactly zero); the
serving micro-batch path populates `engine_health()["drift"]` /
`health_report()` with worst-request trace exemplars; the chunked ingest
judges per-chunk drift (the refit-trigger signal); every drift
observation site honors the disabled-overhead contract; and a dead
canary shadow is counted instead of silently reporting zero divergence.
"""

import json
import time

import numpy as np
import pandas as pd
import pytest

import sml_tpu.tracking as mlflow
from sml_tpu import obs
from sml_tpu.conf import GLOBAL_CONF
from sml_tpu.frame._chunks import (ArrayChunkSource, DatasetSketch,
                                   FeatureSketch)
from sml_tpu.ml import DeviceScorer, Pipeline
from sml_tpu.ml.base import Saveable
from sml_tpu.ml.feature import VectorAssembler
from sml_tpu.ml.regression import LinearRegression, RandomForestRegressor
from sml_tpu.obs import drift
from sml_tpu.serving import ServingEndpoint
from sml_tpu.utils.profiler import PROFILER


@pytest.fixture()
def obs_on():
    prev = GLOBAL_CONF.get("sml.obs.enabled")
    GLOBAL_CONF.set("sml.obs.enabled", True)
    obs.reset()
    yield
    GLOBAL_CONF.set("sml.obs.enabled", bool(prev))


@pytest.fixture(autouse=True)
def _clean_registry():
    yield
    drift.DRIFT.unregister("ingest")


F = 5
CAT = {4: 4}  # slot 4 is categorical, cardinality 4


def make_xy(n, seed, shift=False):
    """4 continuous features + 1 categorical slot; `shift` moves f0
    (location), f2 (scale), and the categorical frequency table —
    everything else stays iid with the training draw."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, F)).astype(np.float64)
    p = np.asarray([0.4, 0.3, 0.2, 0.1])
    if shift:
        X[:, 0] += 1.5
        X[:, 2] *= 2.0
        p = p[::-1].copy()
    X[:, 4] = rng.choice(4, size=n, p=p)
    y = (2.0 * X[:, 0] - X[:, 1] + rng.normal(0, 0.2, n)).astype(np.float32)
    return X, y


def make_baseline(n=8000, seed=3):
    X, y = make_xy(n, seed)
    ds = DatasetSketch(F, CAT)
    ds.update(X, y)
    lab = FeatureSketch()
    lab.update(y)
    return drift.DriftBaseline(ds, label=lab, n_rows=n, sampled_rows=n)


# ------------------------------------------------------------ serialization
def test_feature_sketch_roundtrip_exact_bit_identical():
    rng = np.random.default_rng(0)
    sk = FeatureSketch()
    sk.update(rng.normal(size=3000).astype(np.float32))
    sk.update(rng.normal(size=1000).astype(np.float32))
    d = json.loads(json.dumps(sk.to_dict()))
    back = FeatureSketch.from_dict(d)
    assert back.exact and back.n_seen == sk.n_seen
    qs = np.linspace(0, 1, 65)[1:-1]
    assert np.array_equal(sk.quantiles(qs), back.quantiles(qs))
    probes = np.linspace(-3, 3, 41)
    assert np.array_equal(sk.cdf(probes), back.cdf(probes))
    # merge-compatible after load: folding the same extra chunk into
    # the live and the reloaded sketch lands on identical quantiles
    extra = rng.normal(size=500).astype(np.float32)
    more = FeatureSketch()
    more.update(extra)
    sk.merge(more)
    more2 = FeatureSketch()
    more2.update(extra)
    back.merge(more2)
    assert np.array_equal(sk.quantiles(qs), back.quantiles(qs))


def test_feature_sketch_roundtrip_compressed():
    rng = np.random.default_rng(1)
    sk = FeatureSketch(buckets=64, exact_cap=500)
    sk.update(rng.normal(size=2000))
    assert not sk.exact and sk.compressions >= 1
    # pending post-compression values exercise the consolidate-on-
    # serialize path
    sk.update(rng.normal(size=100))
    d = json.loads(json.dumps(sk.to_dict()))
    back = FeatureSketch.from_dict(d)
    assert not back.exact
    qs = np.linspace(0, 1, 33)[1:-1]
    assert np.array_equal(sk.quantiles(qs), back.quantiles(qs))
    # still merge-compatible: merging past the cap re-compresses
    more = FeatureSketch(buckets=64, exact_cap=500)
    more.update(rng.normal(size=800))
    back.merge(more)
    assert back.n_seen == sk.n_seen + 800


def test_dataset_sketch_roundtrip_with_categoricals():
    X, y = make_xy(4000, seed=5)
    ds = DatasetSketch(F, CAT)
    ds.update(X, y)
    back = DatasetSketch.from_dict(json.loads(json.dumps(ds.to_dict())))
    assert back.n_rows == ds.n_rows and back.categorical == CAT
    np.testing.assert_array_equal(ds._cat_cnt[4], back._cat_cnt[4])
    np.testing.assert_array_equal(ds._cat_sum[4], back._cat_sum[4])
    qs = np.linspace(0, 1, 33)[1:-1]
    for f, sk in ds.features.items():
        assert np.array_equal(sk.quantiles(qs), back.features[f].quantiles(qs))


# ----------------------------------------------------------------- distances
def test_distance_parity_exact_vs_compressed():
    """The same (baseline, live) pair measured through exact sketches
    and through compressed sketches lands on the same verdict and
    nearby distances (compressed quantiles are within one centroid
    weight)."""
    rng = np.random.default_rng(7)
    base_v = rng.normal(size=20000)
    live_v = rng.normal(size=8000) + 0.8  # a real shift

    def pair(exact_cap):
        b = FeatureSketch(buckets=1024, exact_cap=exact_cap)
        b.update(base_v)
        l = FeatureSketch(buckets=1024, exact_cap=exact_cap)
        l.update(live_v)
        return b, l

    be, le = pair(10 ** 9)
    bc, lc = pair(4096)
    assert be.exact and le.exact and not bc.exact and not lc.exact
    psi_e, psi_c = drift.psi_distance(be, le), drift.psi_distance(bc, lc)
    sh_e, sh_c = drift.quantile_shift(be, le), drift.quantile_shift(bc, lc)
    assert psi_e > 0.25 and psi_c > 0.25          # both see the shift
    assert abs(psi_e - psi_c) < 0.1 * max(psi_e, psi_c)
    assert abs(sh_e - sh_c) < 0.1 * max(sh_e, sh_c)
    # and an UNdrifted pair stays near zero through both modes
    lv2 = rng.normal(size=8000)
    le2 = FeatureSketch(buckets=1024, exact_cap=10 ** 9)
    le2.update(lv2)
    lc2 = FeatureSketch(buckets=1024, exact_cap=4096)
    lc2.update(lv2)
    assert drift.psi_distance(be, le2) < 0.02
    assert drift.psi_distance(bc, lc2) < 0.02


def test_iid_split_no_false_positive(obs_on):
    base = make_baseline()
    Xi, _ = make_xy(3000, seed=77)
    rep = drift.evaluate_block(base, Xi)
    assert rep["ready"]
    assert rep["n_flagged"] == 0 and rep["flagged"] == []
    assert rep["max_severity"] < 1.0


def test_injected_shift_flags_the_right_features(obs_on):
    base = make_baseline()
    Xs, _ = make_xy(3000, seed=78, shift=True)
    rep = drift.evaluate_block(base, Xs)
    assert set(rep["flagged"]) == {"f0", "f2", "f4"}
    # severity ordering surfaces the movers first
    assert set(rep["top"][:3]) == {"f0", "f2", "f4"}
    kinds = {e["feature"]: e["kind"] for e in rep["features"]}
    assert kinds["f4"] == "categorical"


def test_reloaded_baseline_self_distance_exactly_zero():
    base = make_baseline()
    back = drift.DriftBaseline.from_dict(
        json.loads(json.dumps(base.to_dict())))
    for f, sk in base.features.features.items():
        assert drift.psi_distance(sk, back.features.features[f]) == 0.0
        assert drift.quantile_shift(sk, back.features.features[f]) == 0.0
    assert drift.categorical_psi(base.features._cat_cnt[4],
                                 back.features._cat_cnt[4]) == 0.0


# ----------------------------------------------------- fit-time capture
def _tree_frame(spark, n=1200, seed=0, shift=False):
    X, y = make_xy(n, seed, shift)
    pdf = pd.DataFrame({f"x{i}": X[:, i] for i in range(F)})
    pdf["y"] = y.astype(np.float64)
    return spark.createDataFrame(pdf), X


def _fit_tree_pipeline(spark, n=1200, seed=0):
    df, X = _tree_frame(spark, n, seed)
    va = VectorAssembler(inputCols=[f"x{i}" for i in range(F)],
                         outputCol="features")
    model = Pipeline(stages=[
        va, RandomForestRegressor(labelCol="y", numTrees=3, maxDepth=4,
                                  seed=11)]).fit(df)
    return model, X


def test_fit_stamps_baseline_and_save_load_roundtrip(spark, tmp_path,
                                                     obs_on):
    model, _X = _fit_tree_pipeline(spark)
    spec = model.stages[-1]._spec
    base = spec.baseline
    assert base is not None
    assert base.n_rows == 1200
    assert base.label is not None and base.prediction is not None
    assert base.prediction.n_seen > 0
    cap = GLOBAL_CONF.getInt("sml.obs.driftBaselineRows")
    assert base.sampled_rows <= max(cap, base.n_rows)
    # directory round trip: _save_to writes baseline.json, load restores
    # it BIT-COMPATIBLY (dict equality is the strongest exactness check)
    path = str(tmp_path / "m")
    model.write().save(path)
    back = Saveable.load(path)
    bspec = back.stages[-1]._spec
    assert bspec.baseline is not None
    assert bspec.baseline.to_dict() == base.to_dict()
    for f, sk in base.features.features.items():
        assert drift.psi_distance(sk, bspec.baseline.features.features[f]) \
            == 0.0


def test_log_model_roundtrip_carries_baseline(spark, tmp_path, obs_on):
    mlflow.set_tracking_uri(str(tmp_path / "runs"))
    model, _X = _fit_tree_pipeline(spark)
    base = model.stages[-1]._spec.baseline
    with mlflow.start_run():
        mlflow.spark.log_model(model, "model",
                               registered_model_name="drift-model")
    back = mlflow.spark.load_model("models:/drift-model/1")
    bbase = back.stages[-1]._spec.baseline
    assert bbase is not None
    assert bbase.to_dict() == base.to_dict()


def test_chunked_fit_reuses_ingest_sketch(obs_on):
    from sml_tpu.ml._chunked import fit_ensemble_chunked
    X, y = make_xy(4000, seed=21)
    spec = fit_ensemble_chunked(
        ArrayChunkSource(X, y, chunk_rows=1000), categorical=CAT,
        max_depth=3, max_bins=16, n_trees=2, bootstrap=True, seed=5)
    base = spec.baseline
    assert base is not None
    # full-data fidelity: the pass-1 sketch saw every row
    assert base.features.n_rows == 4000
    assert base.n_rows == 4000
    # and an iid stream judged against it stays clean
    Xi, _ = make_xy(2000, seed=22)
    assert drift.evaluate_block(base, Xi)["n_flagged"] == 0


# ----------------------------------------------------- serving + ingest
# The traffic below is 64 requests of 8 rows, and a request still queued
# `sml.serve.requestTimeoutMillis` (250 ms) after admission is shed. A
# flush at a width the process has not run compiles `forest_forward` on
# the serving thread (0.4 s alone), so whenever a busy machine split the
# 64 over two flushes, the second lot expired behind the first's compile.
# The set-up makes the width ONE and warm: a full batch of 512 rows
# flushes at once, and `drift_serving` has run that program already.
_ONE_FLUSH = dict(max_batch_rows=512, flush_micros=100_000)


@pytest.fixture()
def drift_serving(spark, tmp_path):
    mlflow.set_tracking_uri(str(tmp_path / "runs"))
    prev = {k: GLOBAL_CONF.get(k) for k in
            ("sml.obs.enabled", "sml.obs.driftMinRows")}
    GLOBAL_CONF.set("sml.obs.enabled", True)
    GLOBAL_CONF.set("sml.obs.driftMinRows", 64)
    obs.reset()
    model, X = _fit_tree_pipeline(spark)
    with mlflow.start_run():
        mlflow.spark.log_model(model, "model",
                               registered_model_name="drift-serve")
    mlflow.MlflowClient().transition_model_version_stage(
        "drift-serve", 1, stage="Production")
    DeviceScorer(model).score_block(X[:_ONE_FLUSH["max_batch_rows"]])
    yield model
    for k, v in prev.items():
        GLOBAL_CONF.set(k, v)


def test_serving_drift_block_and_exemplars(drift_serving):
    Xs, _ = make_xy(512, seed=91, shift=True)
    with ServingEndpoint("drift-serve", "Production", **_ONE_FLUSH) as ep:
        futs = [ep.submit(Xs[lo:lo + 8]) for lo in range(0, 512, 8)]
        for f in futs:
            f.result(timeout=30)
        health = ep.health_report()
        block = health["drift"]["serve.drift-serve/Production"]
        assert block["ready"] and block["rows"] >= 512
        assert "f0" in block["flagged"] and "f2" in block["flagged"]
        # worst-request trace exemplars name a literal request
        by_name = {e["feature"]: e for e in block["features"]}
        assert by_name["f0"]["worst_trace"] is not None
        assert by_name["f0"]["worst_trace"].startswith("0x")
        traced = {f.trace_id for f in futs}
        assert int(by_name["f0"]["worst_trace"], 16) in traced
        # the same block surfaces on the engine-wide surface
        assert obs.engine_health()["drift"]["serve.drift-serve/Production"][
            "rows"] == block["rows"]
        # drift.* receipts landed in the recorder
        names = {e.name for e in obs.RECORDER.events()}
        assert "drift.report" in names
    # close() unregisters the monitor
    assert obs.engine_health()["drift"] is None \
        or "serve.drift-serve/Production" not in obs.engine_health()["drift"]


def test_serving_iid_traffic_stays_clean(drift_serving):
    Xi, _ = make_xy(512, seed=92)
    with ServingEndpoint("drift-serve", "Production", **_ONE_FLUSH) as ep:
        futs = [ep.submit(Xi[lo:lo + 8]) for lo in range(0, 512, 8)]
        for f in futs:
            f.result(timeout=30)
        block = ep.health_report()["drift"]["serve.drift-serve/Production"]
        assert block["ready"]
        assert block["flagged"] == []


def test_per_chunk_ingest_drift(obs_on):
    from sml_tpu.ml._chunked import ingest_source
    prev = GLOBAL_CONF.get("sml.obs.driftMinRows")
    GLOBAL_CONF.set("sml.obs.driftMinRows", 64)
    try:
        base = make_baseline()
        Xs, ys = make_xy(2000, seed=41, shift=True)
        ingest_source(ArrayChunkSource(Xs, ys, chunk_rows=500), 16, CAT,
                      label="drift-test", drift_baseline=base)
        rep = obs.engine_health()["drift"]["ingest"]
        assert rep["chunks"]["observed"] == 4
        assert rep["chunks"]["flagged"] == 4
        assert PROFILER.counters().get("drift.chunk_flagged", 0) >= 4 or \
            obs.RECORDER.counters().get("drift.chunk_flagged", 0) >= 4
        # the merged window names the moved features too
        assert "f0" in rep["flagged"]
        # iid chunks stay clean
        Xi, yi = make_xy(2000, seed=42)
        ingest_source(ArrayChunkSource(Xi, yi, chunk_rows=500), 16, CAT,
                      label="drift-test-iid", drift_baseline=base)
        rep2 = obs.engine_health()["drift"]["ingest"]
        assert rep2["chunks"]["observed"] == 4
        assert rep2["chunks"]["flagged"] == 0
    finally:
        GLOBAL_CONF.set("sml.obs.driftMinRows", prev)


# ------------------------------------------------- disabled-path overhead
def test_disabled_overhead_drift_observation_sites():
    GLOBAL_CONF.set("sml.obs.enabled", False)
    assert not obs.RECORDER.enabled
    base = make_baseline(n=1000, seed=55)
    mon = drift.DriftMonitor(base, name="overhead")
    X, _ = make_xy(8, seed=56)
    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        mon.observe_block(X)
    per = (time.perf_counter() - t0) / n
    assert per < 20e-6, f"{per * 1e6:.2f}us per disabled observe_block"
    assert mon._slots == []          # no sketch allocation happened
    chunk = DatasetSketch(F, CAT)
    t0 = time.perf_counter()
    for _ in range(2000):
        mon.observe_sketch(chunk, 0)
    per = (time.perf_counter() - t0) / 2000
    assert per < 20e-6, f"{per * 1e6:.2f}us per disabled observe_sketch"
    assert mon._chunks == []
    # fit-time capture honors the same kill-switch: an obs-off fit
    # stamps NO baseline (and pays no sketch/traversal)
    assert drift.capture_fit_baseline(
        np.zeros((10, F)), np.zeros(10), None, object()) is None


# ------------------------------------------------------ canary satellites
def _make_linear_frame(spark, seed=0, slope=2.0):
    rng = np.random.default_rng(seed)
    pdf = pd.DataFrame({"a": rng.normal(size=400),
                        "b": rng.normal(size=400)})
    pdf["y"] = slope * pdf["a"] - pdf["b"] + rng.normal(0, 0.1, 400)
    return spark.createDataFrame(pdf)


@pytest.fixture()
def canary_pair(spark, tmp_path):
    mlflow.set_tracking_uri(str(tmp_path / "runs"))
    prev = GLOBAL_CONF.get("sml.obs.enabled")
    GLOBAL_CONF.set("sml.obs.enabled", True)
    obs.reset()
    for seed, slope in ((0, 2.0), (1, -3.0)):
        va = VectorAssembler(inputCols=["a", "b"], outputCol="features")
        m = Pipeline(stages=[va, LinearRegression(labelCol="y")]).fit(
            _make_linear_frame(spark, seed, slope))
        with mlflow.start_run():
            mlflow.spark.log_model(m, "model",
                                   registered_model_name="canary-model")
    client = mlflow.MlflowClient()
    client.transition_model_version_stage("canary-model", 1,
                                          stage="Production")
    client.transition_model_version_stage("canary-model", 2,
                                          stage="Staging")
    yield
    GLOBAL_CONF.set("sml.obs.enabled", bool(prev))


def test_canary_divergence_through_metrics_core(canary_pair):
    X = np.random.default_rng(9).normal(size=(64, 2))
    with ServingEndpoint("canary-model", "Production", canary_fraction=1.0,
                         flush_micros=500) as ep:
        futs = [ep.submit(X[i:i + 1]) for i in range(64)]
        for f in futs:
            f.result(timeout=30)
        deadline = time.time() + 10
        while time.time() < deadline:
            if ep.canary_stats()["mirrored"] >= 64:
                break
            time.sleep(0.05)
        stats = ep.canary_stats()
    assert stats["mirrored"] >= 1 and stats["errors"] == 0
    # windowed quantiles + the literal worst-diverging request come from
    # the serve.canary_abs_diff histogram (v1 vs v2 genuinely diverge)
    assert stats["abs_diff_p99"] > 0.0
    assert stats["worst_abs_diff"] > 0.0
    assert stats["worst_trace"] is not None
    traced = {obs.trace_hex(f.trace_id) for f in futs}
    assert stats["worst_trace"] in traced


def test_dead_canary_is_counted_not_silent(canary_pair):
    X = np.random.default_rng(10).normal(size=(16, 2))
    with ServingEndpoint("canary-model", "Production", canary_fraction=1.0,
                         flush_micros=500) as ep:
        # kill the shadow scorer: every mirror now raises
        class Boom:
            def score_block_host(self, X):
                raise RuntimeError("shadow died")

        ep._staging_scorer = Boom()
        before = obs.RECORDER.counters().get("serve.canary_error", 0)
        futs = [ep.submit(X[i:i + 1]) for i in range(16)]
        for f in futs:
            f.result(timeout=30)
        deadline = time.time() + 10
        while time.time() < deadline:
            if ep.canary_stats()["errors"] >= 16:
                break
            time.sleep(0.05)
        stats = ep.canary_stats()
        after = obs.RECORDER.counters().get("serve.canary_error", 0)
    assert stats["errors"] >= 1            # visible in canary_stats()
    assert stats["mirrored"] == 0          # and not double-counted
    assert after - before == stats["errors"]  # taxonomy counter agrees
