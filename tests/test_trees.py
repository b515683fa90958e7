"""Tree learner tests: the ML 06 / ML 07 / ML 11 behaviors.

Reference anchors reproduced here: the maxBins-vs-cardinality error and its
setMaxBins fix (`ML 06:91-126`), featureImportances (`ML 06:141-154`),
RF beating a single DT (`ML 07:171`), and the XGBoost surface of `ML 11`.
"""

import numpy as np
import pandas as pd
import pytest

from sml_tpu.ml import Pipeline
from sml_tpu.ml.evaluation import (BinaryClassificationEvaluator,
                                   RegressionEvaluator)
from sml_tpu.ml.feature import StringIndexer, VectorAssembler
from sml_tpu.ml.regression import (DecisionTreeRegressor, GBTRegressor,
                                   RandomForestRegressor)
from sml_tpu.ml.classification import RandomForestClassifier
from sml_tpu.xgboost import XgboostRegressor


def _friedman(n=3000, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.random((n, 5))
    y = (10 * np.sin(np.pi * X[:, 0] * X[:, 1]) + 20 * (X[:, 2] - 0.5) ** 2
         + 10 * X[:, 3] + 5 * X[:, 4] + rng.normal(0, 1, n))
    cols = {f"f{i}": X[:, i] for i in range(5)}
    cols["label"] = y
    return pd.DataFrame(cols)


@pytest.fixture()
def friedman_df(spark):
    return spark.createDataFrame(_friedman())


def _assembled(df):
    va = VectorAssembler(inputCols=[f"f{i}" for i in range(5)],
                         outputCol="features")
    return va.transform(df)


def test_decision_tree_beats_mean(friedman_df):
    train, test = friedman_df.randomSplit([0.8, 0.2], seed=42)
    dt = DecisionTreeRegressor(maxDepth=6)
    model = dt.fit(_assembled(train))
    pred = model.transform(_assembled(test))
    rmse = RegressionEvaluator().evaluate(pred)
    base = float(np.std(test.toPandas()["label"]))
    assert rmse < base * 0.6


def test_decision_tree_feature_importances(friedman_df):
    dt = DecisionTreeRegressor(maxDepth=6)
    model = dt.fit(_assembled(friedman_df))
    imp = model.featureImportances.toArray()
    assert imp.sum() == pytest.approx(1.0, abs=1e-6)
    assert imp[3] > 0.05  # f3 is strongly predictive
    assert model.toDebugString


def test_max_bins_categorical_error(spark):
    # high-cardinality indexed categorical must error with default maxBins,
    # and succeed after setMaxBins — the ML 06:91-126 behavior
    rng = np.random.default_rng(3)
    n = 400
    cats = [f"c{i}" for i in range(36)]  # cardinality 36 > 32
    pdf = pd.DataFrame({"cat": rng.choice(cats, n),
                        "x": rng.random(n),
                        "label": rng.random(n)})
    df = spark.createDataFrame(pdf)
    pipe_df = VectorAssembler(inputCols=["cat_idx", "x"], outputCol="features") \
        .transform(StringIndexer(inputCol="cat", outputCol="cat_idx")
                   .fit(df).transform(df))
    dt = DecisionTreeRegressor()
    with pytest.raises(ValueError, match="maxBins"):
        dt.fit(pipe_df)
    dt.setMaxBins(40)
    model = dt.fit(pipe_df)  # no error
    assert model.numFeatures == 2


def test_random_forest_beats_single_tree(friedman_df):
    # deep single trees overfit; bagged + feature-subspaced forests don't —
    # the ML 07:171 "RF beats DT" anchor
    train, test = friedman_df.randomSplit([0.8, 0.2], seed=42)
    ev = RegressionEvaluator()
    dt_rmse = ev.evaluate(DecisionTreeRegressor(maxDepth=8)
                          .fit(_assembled(train)).transform(_assembled(test)))
    rf_rmse = ev.evaluate(
        RandomForestRegressor(maxDepth=8, numTrees=30, seed=42)
        .fit(_assembled(train)).transform(_assembled(test)))
    assert rf_rmse < dt_rmse


def test_gbt_beats_random_forest(friedman_df):
    train, test = friedman_df.randomSplit([0.8, 0.2], seed=42)
    ev = RegressionEvaluator()
    gbt_rmse = ev.evaluate(
        GBTRegressor(maxDepth=5, maxIter=40, stepSize=0.2, seed=42)
        .fit(_assembled(train)).transform(_assembled(test)))
    base = float(np.std(test.toPandas()["label"]))
    assert gbt_rmse < base * 0.35


def test_rf_classifier_auroc(spark):
    rng = np.random.default_rng(11)
    n = 2000
    X = rng.normal(size=(n, 4))
    y = ((X[:, 0] + X[:, 1] ** 2 + rng.normal(0, 0.3, n)) > 1.0).astype(float)
    pdf = pd.DataFrame({f"f{i}": X[:, i] for i in range(4)})
    pdf["label"] = y
    df = spark.createDataFrame(pdf)
    va = VectorAssembler(inputCols=[f"f{i}" for i in range(4)], outputCol="features")
    train, test = df.randomSplit([0.8, 0.2], seed=42)
    m = RandomForestClassifier(numTrees=25, maxDepth=5, seed=42).fit(va.transform(train))
    pred = m.transform(va.transform(test))
    auc = BinaryClassificationEvaluator().evaluate(pred)
    assert auc > 0.85


def test_tree_model_persistence(friedman_df, tmp_path):
    train, test = friedman_df.randomSplit([0.8, 0.2], seed=42)
    pipeline = Pipeline(stages=[
        VectorAssembler(inputCols=[f"f{i}" for i in range(5)], outputCol="features"),
        RandomForestRegressor(maxDepth=4, numTrees=10, seed=7)])
    model = pipeline.fit(train)
    pred1 = model.transform(test).toPandas()["prediction"].values
    path = str(tmp_path / "rf_pipe")
    model.write().overwrite().save(path)
    from sml_tpu.ml import PipelineModel
    loaded = PipelineModel.load(path)
    pred2 = loaded.transform(test).toPandas()["prediction"].values
    assert np.allclose(pred1, pred2)
    assert loaded.stages[-1].getNumTrees() == 10


def test_xgboost_regressor_in_pipeline(friedman_df):
    # the ML 11 shape: log-transform + XgboostRegressor inside a Pipeline
    train, test = friedman_df.randomSplit([0.8, 0.2], seed=42)
    params = {"n_estimators": 40, "learning_rate": 0.2, "max_depth": 4,
              "random_state": 42, "missing": 0.0}
    xgb = XgboostRegressor(**params)
    pipeline = Pipeline(stages=[
        VectorAssembler(inputCols=[f"f{i}" for i in range(5)], outputCol="features"),
        xgb])
    model = pipeline.fit(train)
    pred = model.transform(test)
    rmse = RegressionEvaluator().evaluate(pred)
    base = float(np.std(test.toPandas()["label"]))
    assert rmse < base * 0.4
    r2 = RegressionEvaluator(metricName="r2").evaluate(pred)
    assert r2 > 0.8


def _layout_of_a_fit(df, estimator):
    """(devices that held a shard, rows on the fullest, the mesh the tree
    program was dispatched under, the model) of one fit, from the
    recorder's counters."""
    from sml_tpu import obs
    from sml_tpu.conf import GLOBAL_CONF
    from sml_tpu.ml import tree_impl
    from sml_tpu.parallel import mesh as meshlib
    seen = []
    real = tree_impl.fit_ensemble_on_device

    def spy(*args, **kwargs):
        seen.append(meshlib.get_mesh())
        return real(*args, **kwargs)

    was = GLOBAL_CONF.get("sml.obs.enabled")
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(tree_impl, "fit_ensemble_on_device", spy)
        GLOBAL_CONF.set("sml.obs.enabled", True)
        obs.reset()
        try:
            model = estimator.fit(df)
            counters = obs.RECORDER.counters()
        finally:
            GLOBAL_CONF.set("sml.obs.enabled", was)
            obs.reset()
    assert len(seen) == 1
    return (counters["fit.shards"], counters["fit.shard_rows_max"],
            seen[0], model)


@pytest.mark.parametrize("workers,shards", [(None, 8), (8, 8), (4, 4),
                                            (2, 2), (1, 1)])
def test_num_workers_is_the_layout_of_the_fit(friedman_df, workers, shards):
    """ML 11's `num_workers`: None and the active mesh's own width fit on
    the active mesh ITSELF (program caches hit), a divisor of its devices
    on the first submesh that wide; the counters say what was staged."""
    from sml_tpu.parallel import mesh as meshlib
    df = _assembled(friedman_df)
    active = meshlib.get_mesh()
    assert meshlib.data_width(active) == 8
    xgb = XgboostRegressor(n_estimators=3, max_depth=3, max_bins=16,
                           num_workers=workers)
    held, fullest, mesh, model = _layout_of_a_fit(df, xgb)
    assert held == shards
    assert fullest == meshlib.bucket_rows(3000, shards) // shards
    assert meshlib.data_width(mesh) == shards
    assert (mesh is active) == (shards == 8)
    if shards < 8:
        assert mesh is meshlib.submeshes(8 // shards)[0]
        assert [d.id for d in mesh.devices.flat] == list(range(shards))
    assert meshlib.get_mesh() is active      # bound for the fit alone
    assert model.getOrDefault("num_workers") == workers
    pred = model.transform(df).toPandas()["prediction"]
    assert np.isfinite(pred).all()


@pytest.mark.parametrize("workers", [3, 16, 0, -4])
def test_num_workers_the_host_cannot_give_is_refused(friedman_df, workers):
    from sml_tpu.xgboost import XgboostClassifier
    df = _assembled(friedman_df)
    for cls in (XgboostRegressor, XgboostClassifier):
        with pytest.raises(ValueError) as e:
            cls(n_estimators=2, max_depth=2, num_workers=workers).fit(df)
        assert f"num_workers={workers}" in str(e.value)
        assert "8 device(s)" in str(e.value)


def test_num_workers_on_a_one_device_mesh(friedman_df):
    """A four-way layout on a host that has one chip raises; it is not
    fitted on the one."""
    from sml_tpu.parallel import mesh as meshlib
    df = _assembled(friedman_df)
    with meshlib.use_mesh(meshlib.build_mesh(1)) as one:
        with pytest.raises(ValueError, match=r"num_workers=4 .* 1 device"):
            XgboostRegressor(n_estimators=2, num_workers=4).fit(df)
        held, _, mesh, _ = _layout_of_a_fit(
            df, XgboostRegressor(n_estimators=2, max_depth=2, max_bins=16,
                                 num_workers=1))
        assert held == 1 and mesh is one


def test_native_binning_matches_numpy():
    """native/binning.cc vs the NumPy searchsorted path: identical bins,
    including NaN/±inf (→ bin 0) and categorical remap slots."""
    import numpy as np
    from sml_tpu.native import binning as nb
    from sml_tpu.ml.tree_impl import make_bins, bin_with

    rng = np.random.default_rng(0)
    n, F = 50_000, 6
    X = rng.normal(size=(n, F))
    X[rng.random(n) < 0.01, 0] = np.nan
    X[rng.random(n) < 0.01, 1] = np.inf
    X[:, 5] = rng.integers(0, 7, n)  # categorical slot
    y = rng.normal(size=n).astype(np.float32)

    binned, binning = make_bins(X, y, 32, {5: 7})
    # recompute continuous slots with the pure-NumPy path and compare
    ref = np.zeros((n, F), dtype=np.int32)
    for f in range(F):
        if f == 5:
            continue
        e = binning.edges[f][np.isfinite(binning.edges[f])]
        ref[:, f] = np.searchsorted(e, X[:, f], side="left").astype(np.int32)
        ref[~np.isfinite(X[:, f]), f] = 0
    np.testing.assert_array_equal(binned[:, :5], ref[:, :5])
    # kernel availability: if g++ built the library, exercise it directly
    out = nb.bin_continuous(X, [binning.edges[f][np.isfinite(binning.edges[f])]
                                for f in range(F)], {5: 7})
    if out is not None:
        np.testing.assert_array_equal(out[:, :5], ref[:, :5])
    # predict-time binning round-trips
    np.testing.assert_array_equal(bin_with(X, binning), binned)


def test_hist_subtraction_matches_direct(spark):
    """The histogram-subtraction build (right child = parent - left) must
    reproduce the direct build: identical split structure, leaf values
    within f32 cancellation noise."""
    import numpy as np
    from sml_tpu.conf import GLOBAL_CONF
    from sml_tpu.ml import Pipeline
    from sml_tpu.ml.feature import VectorAssembler
    from sml_tpu.ml.regression import GBTRegressor, RandomForestRegressor

    rng = np.random.default_rng(3)
    n = 20000
    import pandas as pd
    pdf = pd.DataFrame({f"f{i}": rng.normal(size=n) for i in range(6)})
    pdf["label"] = (pdf.f0 * 2 - pdf.f1 + (pdf.f2 > 0) * 3
                    + rng.normal(0, 0.3, n))
    df = spark.createDataFrame(pdf)
    va = VectorAssembler(inputCols=[f"f{i}" for i in range(6)],
                         outputCol="features")
    old = GLOBAL_CONF.get("sml.tree.histSubtraction")
    try:
        for est_fn in (
            lambda: RandomForestRegressor(labelCol="label", maxDepth=5,
                                          numTrees=6, maxBins=32, seed=7),
            lambda: GBTRegressor(labelCol="label", maxDepth=4, maxIter=8,
                                 maxBins=32),
        ):
            specs = {}
            for flag in (False, True):
                GLOBAL_CONF.set("sml.tree.histSubtraction", flag)
                specs[flag] = Pipeline(stages=[va, est_fn()]) \
                    .fit(df).stages[-1]._spec
            for ta, tb in zip(specs[False].trees, specs[True].trees):
                np.testing.assert_array_equal(ta.split_feature,
                                              tb.split_feature)
                # split bins must agree EXCEPT where the two candidates'
                # gains tie within f32 cancellation noise (parent-minus-
                # left accumulates last-ulp error that can flip an argmax
                # between score-equal thresholds; which ties flip varies
                # with the XLA version's fusion choices)
                diff = np.flatnonzero(ta.split_bin != tb.split_bin)
                assert len(diff) <= max(1, len(ta.split_bin) // 50), \
                    f"{len(diff)} split bins differ: beyond tie noise"
                for node in diff:
                    ga, gb = float(ta.gain[node]), float(tb.gain[node])
                    assert abs(ga - gb) <= 1e-3 * max(1.0, abs(ga)), \
                        f"node {node}: differing split bins with " \
                        f"non-tied gains {ga} vs {gb}"
                np.testing.assert_allclose(ta.leaf_value, tb.leaf_value,
                                           atol=1e-3)
    finally:
        GLOBAL_CONF.set("sml.tree.histSubtraction", old)
