"""Multi-chip execution mode (ISSUE 6): the ICI-sharded histogram engine
proven on the simulated 8-device mesh.

Contracts:

- DEVICE-COUNT INVARIANCE: DT/RF/xgboost fits and CV avgMetrics on an
  8-device mesh match a 1-device mesh (sampling draws are
  mesh-layout-invariant — `tree_impl._sliced_draw`; remaining drift is
  float reduction order, bounded by tolerance), and `tree.fit_dispatch`
  counts are identical (the fused-dispatch contract of
  tests/test_dispatch_economics.py holds at every width).
- SHARDED BIN RESIDENCY: the quantized bin matrix staged by
  `stage_sharded` genuinely spans all 8 devices, one row block apiece.
- OBSERVABLE ALLREDUCE VOLUME: `collective.psum_bytes` counts the
  histogram payload per split round, halves under histogram
  subtraction, and renders on the trace exporter's counter tracks.
- CROSS-CHIP TRIAL PARALLELISM: `sml.cv.trialAxisDevices` shards fused
  (grid x fold) elements over a second mesh axis with unchanged metrics.
- The 8-simulated-device dryrun subprocess exits 0 (the first dryrun's
  crash class can never regress silently), and a foreign-mesh prewarm
  manifest is skipped, not replayed onto the 8-device mesh.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pandas as pd
import pytest

from sml_tpu.conf import GLOBAL_CONF
from sml_tpu.utils.profiler import PROFILER

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


@pytest.fixture()
def fused_debug(monkeypatch):
    monkeypatch.setenv("SML_FUSED_DEBUG", "1")


@pytest.fixture()
def profiled():
    prev = GLOBAL_CONF.get("sml.profiler.enabled")
    GLOBAL_CONF.set("sml.profiler.enabled", True)
    yield PROFILER
    GLOBAL_CONF.set("sml.profiler.enabled", prev)


@pytest.fixture()
def xy():
    rng = np.random.default_rng(11)
    n = 4096
    X = rng.normal(size=(n, 6)).astype(np.float32)
    y = (X[:, 0] * 3 - X[:, 1] ** 2 + 0.5 * X[:, 2]
         + rng.normal(0, 0.2, n)).astype(np.float32)
    return X, y


def _frame(spark, X, y, label="label"):
    from sml_tpu.ml.feature import VectorAssembler
    pdf = pd.DataFrame({f"f{i}": X[:, i] for i in range(X.shape[1])})
    pdf[label] = y
    fdf = VectorAssembler(inputCols=[f"f{i}" for i in range(X.shape[1])],
                          outputCol="features") \
        .transform(spark.createDataFrame(pdf))
    fdf.cache()
    return fdf


def _mesh(width):
    from sml_tpu.parallel import mesh as meshlib
    return meshlib.use_mesh(meshlib.build_mesh(width))


# --------------------------------------------------- sharded bin residency
def test_bin_matrix_shards_rows_across_all_devices(xy):
    """The quantized bin matrix staged for a fit is genuinely distributed:
    8 addressable shards, each holding exactly 1/8 of the padded rows —
    per-device partial histograms + psum are real, not a replicated
    array pretending to be sharded."""
    import jax

    from sml_tpu.ml import tree_impl
    from sml_tpu.parallel import mesh as meshlib

    X, y = xy
    assert len(jax.devices()) >= 8
    with _mesh(8):
        staged = tree_impl.stage_tree_data(X, y, max_bins=16)
        arr = staged.binned_dev
        assert arr.dtype == np.uint8  # compact quantized residency
        assert len(arr.sharding.device_set) == 8
        shards = arr.addressable_shards
        assert len(shards) == 8
        n_pad = arr.shape[0]
        assert n_pad % 8 == 0
        assert all(s.data.shape[0] == n_pad // 8 for s in shards)
        # aligned per-row operands ride the same row split
        assert len(staged.mask_dev.sharding.device_set) == 8
        assert meshlib.mesh_device_count() == 8


# ------------------------------------------------ device-count invariance
def _fit_predict(spark, X, y, estimator_factory, width, log_label=False):
    from sml_tpu.ml.evaluation import RegressionEvaluator
    yy = np.log(y - y.min() + 1.0) if log_label else y
    fdf = _frame(spark, X, yy)
    with _mesh(width):
        model = estimator_factory().fit(fdf)
        pred = model.transform(fdf).toPandas()["prediction"].to_numpy()
        rmse = RegressionEvaluator(labelCol="label").evaluate(
            model.transform(fdf))
    return pred, rmse


@pytest.mark.parametrize("kind", ["dt", "rf", "xgb"])
def test_fit_goldens_8dev_vs_1dev(spark, xy, kind, same_boosted_fit):
    """The same estimator fit on 8 devices and on 1 device produces the
    same model (predictions + rmse within float reduction-order
    tolerance). Before r6, RF/boosting sampling folded the shard index
    into its key, so the fitted forest depended on the mesh LAYOUT.

    Boosting is held to what "the same model" can mean for rounds that
    build on each other (`conftest.same_boosted_fit`): the two layouts are two
    compilations that sum a histogram in another order, and a split whose
    two best candidates tie to the last ulp may fall either way."""
    X, y = xy

    def factory():
        from sml_tpu.ml.regression import (DecisionTreeRegressor,
                                           RandomForestRegressor)
        from sml_tpu.xgboost import XgboostRegressor
        if kind == "dt":
            return DecisionTreeRegressor(labelCol="label", maxDepth=5,
                                         maxBins=16)
        if kind == "rf":
            return RandomForestRegressor(labelCol="label", maxDepth=4,
                                         numTrees=8, maxBins=16,
                                         subsamplingRate=0.9, seed=7)
        return XgboostRegressor(n_estimators=8, max_depth=4, max_bins=16,
                                learning_rate=0.3, subsample=0.8,
                                random_state=5)

    p8, rmse8 = _fit_predict(spark, X, y, factory, 8)
    p1, rmse1 = _fit_predict(spark, X, y, factory, 1)
    if kind == "xgb":
        assert same_boosted_fit(p8, p1, rmse8, rmse1) == []
        # the control: one layout's own predictions rounded to bfloat16 (a
        # descent in the next lower precision) are NOT the same fit
        import ml_dtypes
        low = p1.astype(ml_dtypes.bfloat16).astype(np.float64)
        assert len(same_boosted_fit(low, p1, rmse1, rmse1)) == 2
        return
    np.testing.assert_allclose(p8, p1, rtol=1e-4, atol=1e-4)
    assert abs(rmse8 - rmse1) < 1e-4 * max(abs(rmse1), 1.0)


def test_cv_avgmetrics_and_dispatch_parity_8dev_vs_1dev(spark, xy,
                                                        profiled,
                                                        fused_debug):
    """Grid-fused CV on the 8-device mesh: avgMetrics match the 1-device
    run AND both widths spend the same `tree.fit_dispatch` budget —
    ceil(G*k/maxFusedTrials) fused dispatches + the winner refit (the
    test_dispatch_economics contract, now asserted per mesh width)."""
    import math

    from sml_tpu.ml.evaluation import RegressionEvaluator
    from sml_tpu.ml.regression import RandomForestRegressor
    from sml_tpu.ml.tuning import CrossValidator, ParamGridBuilder

    X, y = xy
    fdf = _frame(spark, X, y)
    rf = RandomForestRegressor(labelCol="label", maxBins=16, seed=7)
    grid = (ParamGridBuilder()
            .addGrid(rf.getParam("maxDepth"), [2, 4])
            .addGrid(rf.getParam("numTrees"), [3, 6]).build())
    cv = CrossValidator(estimator=rf, estimatorParamMaps=grid,
                        evaluator=RegressionEvaluator(labelCol="label"),
                        numFolds=3, parallelism=1, seed=13)
    G, k, fuse = len(grid), 3, 6
    GLOBAL_CONF.set("sml.cv.batchFolds", True)
    GLOBAL_CONF.set("sml.cv.maxFusedTrials", fuse)
    try:
        counts, metrics = {}, {}
        for width in (8, 1):
            with _mesh(width):
                c0 = PROFILER.counters()
                metrics[width] = cv.fit(fdf).avgMetrics
                c1 = PROFILER.counters()
            counts[width] = c1.get("tree.fit_dispatch", 0.0) \
                - c0.get("tree.fit_dispatch", 0.0)
    finally:
        GLOBAL_CONF.unset("sml.cv.maxFusedTrials")
        GLOBAL_CONF.unset("sml.cv.batchFolds")
    np.testing.assert_allclose(metrics[8], metrics[1],
                               rtol=1e-4, atol=1e-4)
    assert counts[8] == counts[1]
    assert counts[8] <= math.ceil(G * k / fuse) + 1


# ------------------------------------------- cross-chip trial parallelism
def test_trial_axis_sharding_parity_and_widths(spark, xy, fused_debug):
    """`sml.cv.trialAxisDevices` moves fused elements onto a second mesh
    axis: metrics match the rows-only layout, and the auto policy picks
    a real width on the 8-device mesh for small-row trials."""
    from sml_tpu.ml import tree_impl
    from sml_tpu.ml.evaluation import RegressionEvaluator
    from sml_tpu.ml.regression import RandomForestRegressor
    from sml_tpu.ml.tuning import CrossValidator, ParamGridBuilder

    X, y = xy
    fdf = _frame(spark, X, y)
    rf = RandomForestRegressor(labelCol="label", maxBins=16, seed=3)
    grid = (ParamGridBuilder()
            .addGrid(rf.getParam("maxDepth"), [2, 3])
            .addGrid(rf.getParam("numTrees"), [2, 4]).build())
    cv = CrossValidator(estimator=rf, estimatorParamMaps=grid,
                        evaluator=RegressionEvaluator(labelCol="label"),
                        numFolds=2, parallelism=1, seed=5)
    out = {}
    GLOBAL_CONF.set("sml.cv.batchFolds", True)
    try:
        with _mesh(8):
            for knob in (1, 8, 0):
                GLOBAL_CONF.set("sml.cv.trialAxisDevices", knob)
                out[knob] = cv.fit(fdf).avgMetrics
    finally:
        GLOBAL_CONF.unset("sml.cv.trialAxisDevices")
        GLOBAL_CONF.unset("sml.cv.batchFolds")
    np.testing.assert_allclose(out[8], out[1], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(out[0], out[1], rtol=1e-4, atol=1e-4)
    # the auto policy: 8 fused elements x small rows -> full trial width;
    # a giant per-trial row count keeps the rows-only layout; auto never
    # pads (E=5 has no admissible divisor) but an EXPLICIT width is
    # honored by padding the element axis
    with _mesh(8):
        assert tree_impl._trial_axis_width(8, 4096) == 8
        assert tree_impl._trial_axis_width(12, 4096) == 4  # zero padding
        assert tree_impl._trial_axis_width(8, 1 << 20) == 1
        assert tree_impl._trial_axis_width(5, 4096) == 1
        GLOBAL_CONF.set("sml.cv.trialAxisDevices", 8)
        try:
            assert tree_impl._trial_axis_width(5, 4096) == 8  # pads 5->8
        finally:
            GLOBAL_CONF.unset("sml.cv.trialAxisDevices")
    with _mesh(1):
        assert tree_impl._trial_axis_width(8, 4096) == 1


def test_explicit_trial_width_pads_elements_with_parity(xy):
    """An explicit `sml.cv.trialAxisDevices` that does not divide the
    element count pads the trial axis (repeating element 0) and still
    returns exactly E correct results — the knob is honored, never
    silently ignored."""
    import jax

    from sml_tpu.ml import tree_impl

    X, y = xy
    E, nr = 5, 1024
    rng = np.random.default_rng(2)
    from sml_tpu.parallel import mesh as meshlib
    with _mesh(8):
        n_pad = meshlib.bucket_rows(nr, 8)
        bst = rng.integers(0, 8, (E, n_pad, 4)).astype(np.uint8)
        yst = rng.normal(size=(E, n_pad)).astype(np.float32)
        mst = np.zeros((E, n_pad), np.float32)
        mst[:, :nr] = 1.0
        rngs = np.stack([np.asarray(jax.random.key_data(
            jax.random.PRNGKey(i)), np.uint32) for i in range(E)])
        spec = tree_impl.TreeSpec(max_depth=3, n_bins=8, n_features=4,
                                  feature_k=4, min_instances=1,
                                  min_info_gain=0.0, reg_lambda=0.0,
                                  gamma=0.0)
        es = tree_impl.EnsembleSpec(tree=spec, n_trees=2, loss="squared",
                                    boosting=False, bootstrap=False,
                                    subsample=1.0, step_size=0.1)
        dyn = (np.full(E, 3, np.int32), np.full(E, 4, np.int32),
               np.ones(E, np.float32), np.zeros(E, np.float32),
               np.zeros(E, bool), np.ones(E, np.float32))
        outs = {}
        for knob in (1, 8):
            GLOBAL_CONF.set("sml.cv.trialAxisDevices", knob)
            try:
                packs, bases = tree_impl.fit_ensembles_trials(
                    bst, yst, mst, es, rngs, *dyn)
            finally:
                GLOBAL_CONF.unset("sml.cv.trialAxisDevices")
            assert packs.shape[0] == E and bases.shape[0] == E
            outs[knob] = (packs, bases)
    np.testing.assert_allclose(outs[8][1], outs[1][1], rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(outs[8][0], outs[1][0], rtol=1e-4,
                               atol=1e-4)


# --------------------------------------------- collective payload volume
def test_collective_psum_bytes_counted_and_on_trace(xy):
    """Per-op payload counters: a fresh tree program's trace counts
    `collective.psum` launches AND their byte volume; the bytes land on
    the Chrome-trace counter tracks."""
    from sml_tpu import obs
    from sml_tpu.ml import tree_impl
    from sml_tpu.obs._trace import to_trace_events

    X, y = xy
    GLOBAL_CONF.set("sml.obs.enabled", True)
    try:
        obs.reset()
        with _mesh(8):
            staged = tree_impl.stage_tree_data(X, y, max_bins=16)
            g = tree_impl.stage_aligned(-y, staged.n_padded)
            h = tree_impl.stage_aligned(np.ones_like(y), staged.n_padded)
            w = tree_impl.stage_aligned(np.ones_like(y), staged.n_padded)
            spec = tree_impl.TreeSpec(max_depth=3, n_bins=16, n_features=6,
                                      feature_k=6, min_instances=1,
                                      min_info_gain=0.0, reg_lambda=0.0,
                                      gamma=0.0)
            tree_impl.fit_tree(staged.binned_dev, g, h, w, spec)
        counters = obs.RECORDER.counters()
        assert counters.get("collective.psum", 0) >= 1
        assert counters.get("collective.psum_bytes", 0) > 0
        trace = to_trace_events(obs.RECORDER.events())
        tracks = {e["name"] for e in trace if e["ph"] == "C"}
        assert "collective.psum_bytes" in tracks
    finally:
        GLOBAL_CONF.set("sml.obs.enabled", False)


def test_hist_subtraction_halves_psum_payload(xy):
    """The histogram-subtraction trick is visible in the flight recorder:
    the same ensemble traced with subtraction ON moves fewer psum bytes
    per program than with it OFF (right children are parent - left,
    post-psum, so the below-root payload halves)."""
    from sml_tpu import obs
    from sml_tpu.ml import tree_impl
    from sml_tpu.ml._tree_models import _fit_ensemble

    X, y = xy
    GLOBAL_CONF.set("sml.obs.enabled", True)
    try:
        volumes = {}
        for sub in (True, False):
            GLOBAL_CONF.set("sml.tree.histSubtraction", sub)
            obs.reset()
            with _mesh(8):
                # fresh program per toggle (the setting is a cache key),
                # so trace-time counters fire for both variants; static
                # params no other fit of the suite has (test_hierarchical
                # fits 16 bins on this very mesh: a worker that ran it
                # first would hit its program and count nothing)
                _fit_ensemble(X, y, categorical={}, max_depth=4,
                              max_bins=20, min_instances=1,
                              min_info_gain=0.0, n_trees=2, feature_k=None,
                              bootstrap=False, subsample=1.0, seed=3,
                              loss="squared")
            volumes[sub] = obs.RECORDER.counters() \
                .get("collective.psum_bytes", 0.0)
    finally:
        GLOBAL_CONF.unset("sml.tree.histSubtraction")
        GLOBAL_CONF.set("sml.obs.enabled", False)
    assert 0 < volumes[True] < volumes[False]


# ----------------------------------------------------- prewarm mesh gating
def test_prewarm_foreign_manifest_skipped_on_8dev_mesh(spark, xy,
                                                       tmp_path):
    """A manifest recorded under a 1-device mesh signature must be
    SKIPPED when replayed on the 8-device mesh (and vice versa) — a
    first-dispatch on the wrong mesh would compile dead programs."""
    from sml_tpu.ml.regression import DecisionTreeRegressor
    from sml_tpu.parallel import prewarm

    prev = GLOBAL_CONF.get("sml.compile.cacheDir")
    GLOBAL_CONF.set("sml.compile.cacheDir", str(tmp_path))
    try:
        fdf = _frame(spark, *xy)
        with _mesh(8):
            DecisionTreeRegressor(labelCol="label", maxDepth=2,
                                  seed=1).fit(fdf)
        mpath = os.path.join(str(tmp_path), "prewarm_manifest.json")
        with open(mpath) as f:
            man = json.load(f)
        assert man["entries"]
        assert all(e["mesh"][0] == 8 for e in man["entries"].values())
        for e in man["entries"].values():
            e["mesh"] = [1, e["mesh"][1]]  # doctored: 1-device recording
        with open(mpath, "w") as f:
            json.dump(man, f)
        prewarm._state["entries"] = None
        with _mesh(8):
            stats = prewarm.prewarm()
        assert stats["programs"] == 0
        assert stats["skipped"] == len(man["entries"])
    finally:
        GLOBAL_CONF.set("sml.compile.cacheDir", prev or "")


# ------------------------------------------------------ dryrun regression
def test_dryrun_8dev_subprocess_exits_zero():
    """The CI gate for the first dryrun's crash class: the 8-simulated-
    device dryrun runs end-to-end in a clean subprocess and exits 0 —
    mesh sizing from materialized devices, sharded staging, histogram
    trees, eval pushdown, ALS, KMeans, scorer forward, compact linear."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # the dryrun provisions its own devices
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "__graft_entry__.py"),
         "--dryrun", "8"],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "dryrun_multichip OK" in proc.stdout
