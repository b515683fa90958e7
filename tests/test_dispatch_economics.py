"""PR 5 dispatch economics: grid-fused trial batching, fused-TPE
generations, and the mapInPandas routing hint (docs/DESIGN_NOTES.md
§ Dispatch economics).

The fusion contract: a G-point tree-regressor grid over k folds executes
its fold-fits in <= ceil(G*k / sml.cv.maxFusedTrials) tree-fit device
dispatches (asserted from the `tree.fit_dispatch` flight-recorder
counter), with metrics matching the placed-trials path — results never
depend on fusion firing.
"""

import math
import os

import numpy as np
import pandas as pd
import pytest

from sml_tpu.conf import GLOBAL_CONF
from sml_tpu.utils.profiler import PROFILER


@pytest.fixture()
def fused_debug(monkeypatch):
    """Surface fused-path bugs instead of silently falling back."""
    monkeypatch.setenv("SML_FUSED_DEBUG", "1")


@pytest.fixture()
def profiled():
    prev = GLOBAL_CONF.get("sml.profiler.enabled")
    GLOBAL_CONF.set("sml.profiler.enabled", True)
    yield PROFILER
    GLOBAL_CONF.set("sml.profiler.enabled", prev)


@pytest.fixture()
def reg_fdf(spark):
    rng = np.random.default_rng(4)
    n = 9000
    pdf = pd.DataFrame({f"f{i}": rng.normal(size=n) for i in range(5)})
    pdf["label"] = pdf["f0"] * 3 - pdf["f1"] ** 2 + rng.normal(0, 0.2, n)
    from sml_tpu.ml.feature import VectorAssembler
    fdf = VectorAssembler(inputCols=[f"f{i}" for i in range(5)],
                          outputCol="features") \
        .transform(spark.createDataFrame(pdf))
    fdf.cache()
    return fdf


def _counter_delta(c0, c1, name):
    return c1.get(name, 0.0) - c0.get(name, 0.0)


def test_grid_fused_cv_dispatch_count_and_parity(reg_fdf, profiled,
                                                 fused_debug):
    """The acceptance contract: G=4 grid x k=3 folds at maxFusedTrials=6
    -> ceil(12/6)=2 fused tree-fit dispatches (+1 winner refit), with
    avgMetrics matching the sequential placed-trials path."""
    from sml_tpu.ml.evaluation import RegressionEvaluator
    from sml_tpu.ml.regression import RandomForestRegressor
    from sml_tpu.ml.tuning import CrossValidator, ParamGridBuilder

    rf = RandomForestRegressor(labelCol="label", maxBins=16, seed=7)
    grid = (ParamGridBuilder()
            .addGrid(rf.getParam("maxDepth"), [2, 4])
            .addGrid(rf.getParam("numTrees"), [3, 6]).build())
    ev = RegressionEvaluator(labelCol="label")
    # parallelism=1 keeps the sequential arm on the FULL mesh (RF
    # bootstrap streams fold in the shard index; a submesh layout draws
    # different weights — a placed-trials property, not fusion's)
    cv = CrossValidator(estimator=rf, estimatorParamMaps=grid, evaluator=ev,
                        numFolds=3, parallelism=1, seed=11)
    G, k, fuse = len(grid), 3, 6
    GLOBAL_CONF.set("sml.cv.batchFolds", True)
    GLOBAL_CONF.set("sml.cv.maxFusedTrials", fuse)
    try:
        c0 = PROFILER.counters()
        fused = cv.fit(reg_fdf).avgMetrics
        c1 = PROFILER.counters()
    finally:
        GLOBAL_CONF.unset("sml.cv.maxFusedTrials")
    assert _counter_delta(c0, c1, "cv.batchFolds.fallback") == 0
    # fold-fits fused to ceil(G*k/fuse) dispatches; +1 = bestModel refit
    assert _counter_delta(c0, c1, "tree.fit_dispatch") \
        <= math.ceil(G * k / fuse) + 1
    GLOBAL_CONF.set("sml.cv.batchFolds", False)
    try:
        c0 = PROFILER.counters()
        sequential = cv.fit(reg_fdf).avgMetrics
        c1 = PROFILER.counters()
    finally:
        GLOBAL_CONF.unset("sml.cv.batchFolds")
    # the placed-trials path pays one dispatch per (grid, fold) fit
    assert _counter_delta(c0, c1, "tree.fit_dispatch") == G * k + 1
    np.testing.assert_allclose(fused, sequential, rtol=1e-4, atol=1e-4)


def test_grid_fused_dt_maxbins_grid_parity(reg_fdf, fused_debug):
    """A grid that varies maxBins re-quantizes per (fold, maxBins) and
    pads the histogram axis to the grid max — metrics must still match
    the per-trial path (DecisionTree arm: no sampling involved)."""
    from sml_tpu.ml.evaluation import RegressionEvaluator
    from sml_tpu.ml.regression import DecisionTreeRegressor
    from sml_tpu.ml.tuning import CrossValidator, ParamGridBuilder

    dt = DecisionTreeRegressor(labelCol="label", seed=3)
    grid = (ParamGridBuilder()
            .addGrid(dt.getParam("maxDepth"), [2, 3])
            .addGrid(dt.getParam("maxBins"), [8, 16]).build())
    ev = RegressionEvaluator(labelCol="label")
    cv = CrossValidator(estimator=dt, estimatorParamMaps=grid, evaluator=ev,
                        numFolds=2, parallelism=1, seed=5)
    GLOBAL_CONF.set("sml.cv.batchFolds", True)
    try:
        fused = cv.fit(reg_fdf).avgMetrics
        GLOBAL_CONF.set("sml.cv.batchFolds", False)
        sequential = cv.fit(reg_fdf).avgMetrics
    finally:
        GLOBAL_CONF.unset("sml.cv.batchFolds")
    np.testing.assert_allclose(fused, sequential, rtol=1e-4, atol=1e-4)


def test_train_validation_split_fused_parity(reg_fdf, fused_debug):
    """TrainValidationSplit rides the same fused evaluator (a 1-fold
    grid); validationMetrics must match the placed-trials path."""
    from sml_tpu.ml.evaluation import RegressionEvaluator
    from sml_tpu.ml.regression import RandomForestRegressor
    from sml_tpu.ml.tuning import ParamGridBuilder, TrainValidationSplit

    rf = RandomForestRegressor(labelCol="label", maxBins=16, seed=5)
    grid = (ParamGridBuilder()
            .addGrid(rf.getParam("maxDepth"), [2, 4])
            .addGrid(rf.getParam("numTrees"), [3, 5]).build())
    tvs = TrainValidationSplit(estimator=rf, estimatorParamMaps=grid,
                               evaluator=RegressionEvaluator(
                                   labelCol="label"), seed=9)
    GLOBAL_CONF.set("sml.cv.batchFolds", True)
    try:
        fused = tvs.fit(reg_fdf).validationMetrics
        GLOBAL_CONF.set("sml.cv.batchFolds", False)
        sequential = tvs.fit(reg_fdf).validationMetrics
    finally:
        GLOBAL_CONF.unset("sml.cv.batchFolds")
    np.testing.assert_allclose(fused, sequential, rtol=1e-4, atol=1e-4)


def test_fused_tpe_trial_history_parity(reg_fdf, profiled, fused_debug):
    """A batch-capable fmin objective (fn.score_batch backed by
    ml.tuning.fused_param_scores) must produce the SAME trial history
    (params AND losses) as the per-trial loop — in a fraction of the
    tree-fit dispatches."""
    from sml_tpu.ml.evaluation import RegressionEvaluator
    from sml_tpu.ml.regression import RandomForestRegressor
    from sml_tpu.ml.tuning import fused_param_scores
    from sml_tpu.tune import Trials, fmin, hp, tpe

    train, val = reg_fdf.randomSplit([0.8, 0.2], seed=42)
    train.cache()
    val.cache()
    rf = RandomForestRegressor(labelCol="label", maxBins=16, seed=5)
    ev = RegressionEvaluator(labelCol="label")
    space = {"max_depth": hp.quniform("max_depth", 2, 5, 1),
             "num_trees": hp.quniform("num_trees", 3, 9, 3)}

    def make_objective(batched):
        def objective(params):
            m = rf.copy({rf.getParam("maxDepth"): int(params["max_depth"]),
                         rf.getParam("numTrees"): int(params["num_trees"])}
                        ).fit(train)
            return ev.evaluate(m.transform(val))

        if batched:
            def score_batch(values):
                pmaps = [{rf.getParam("maxDepth"): int(v["max_depth"]),
                          rf.getParam("numTrees"): int(v["num_trees"])}
                         for v in values]
                return fused_param_scores(rf, pmaps, train, val, ev)

            objective.score_batch = score_batch
        return objective

    def run(batched):
        c0 = PROFILER.counters()
        trials = Trials()
        GLOBAL_CONF.set("sml.cv.batchFolds", True)
        GLOBAL_CONF.set("sml.tune.candidatesPerDispatch", 4)
        try:
            fmin(make_objective(batched), space, algo=tpe, max_evals=8,
                 trials=trials, rstate=np.random.RandomState(3))
        finally:
            GLOBAL_CONF.unset("sml.tune.candidatesPerDispatch")
            GLOBAL_CONF.unset("sml.cv.batchFolds")
        params = [{k: v[0] for k, v in t["misc"]["vals"].items()}
                  for t in trials.trials]
        dispatches = _counter_delta(c0, PROFILER.counters(),
                                    "tree.fit_dispatch")
        return params, trials.losses(), dispatches

    p_fused, l_fused, d_fused = run(batched=True)
    p_seq, l_seq, d_seq = run(batched=False)
    assert p_fused == p_seq
    np.testing.assert_allclose(l_fused, l_seq, rtol=1e-4, atol=1e-4)
    # 8 trials in 2 generations of 4 vs 8 per-trial fits
    assert d_fused <= math.ceil(8 / 4)
    assert d_seq == 8


def test_mapinpandas_small_leg_binds_host_mesh(spark, monkeypatch):
    """The ml12 satellite: on a device the dispatcher prices (one this
    process does not own, slow link), a small pandas-fn leg's WorkHint
    prices host, and the UDF body runs under the host mesh — a
    device-capable body stops paying a dispatch round trip per batch."""
    from sml_tpu.parallel import dispatch, mesh as meshlib

    monkeypatch.setattr(dispatch, "_default_backend", lambda: "tpu")
    monkeypatch.setattr(dispatch, "_locally_attached", lambda: False)
    cal = dispatch._Calibration()
    cal._done = True
    cal.rt_fixed = 0.15
    cal.h2d_bw = 200e6
    cal.d2h_bw = 20e6
    monkeypatch.setattr(dispatch, "CALIBRATION", cal)

    df = spark.createDataFrame(pd.DataFrame({"x": np.arange(200.0)}))
    seen = []

    def fn(batches):
        for b in batches:
            seen.append(meshlib.get_mesh() is dispatch.host_mesh())
            yield pd.DataFrame({"y": b["x"] * 2})

    out = df.mapInPandas(fn, "y double")
    assert out.count() == 200
    assert seen and all(seen)


def test_mapinpandas_cpu_backend_unchanged(spark):
    """CPU backend -> no binding: the active (virtual device) mesh stays in
    force, so CPU-mesh tests and pinned-mesh flows see zero change."""
    from sml_tpu.parallel import dispatch, mesh as meshlib

    df = spark.createDataFrame(pd.DataFrame({"x": np.arange(50.0)}))
    seen = []

    def fn(batches):
        for b in batches:
            seen.append(meshlib.get_mesh() is dispatch.host_mesh())
            yield pd.DataFrame({"y": b["x"]})

    assert df.mapInPandas(fn, "y double").count() == 50
    assert seen and not any(seen)


def test_dryrun_mesh_dims():
    """The MULTICHIP_r01 crash shape: the dryrun mesh must be sized from
    the devices that MATERIALIZED, falling back to a 1-D data mesh when
    2 doesn't divide them (1 chip => (1, 1), never a (4, 2) reshape)."""
    import importlib.util

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "_graft_entry_test", os.path.join(here, "__graft_entry__.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod._mesh_dims(1) == (1, 1)
    assert mod._mesh_dims(2) == (1, 2)
    assert mod._mesh_dims(5) == (5, 1)
    assert mod._mesh_dims(8) == (4, 2)
    assert mod._mesh_dims(0) == (1, 1)
