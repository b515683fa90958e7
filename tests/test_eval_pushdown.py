"""Evaluator pushdown (`_fused_eval` hooks): RegressionEvaluator on a LAZY
model-transform frame computes its metric without materializing the frame,
and the value must match the ordinary materialize path exactly enough to be
indistinguishable (same predictions, f32-sum-order differences only).

Covers the two hook producers: `_TreeRegressionModel._transform`
(fused traverse+stats device program) and the fused `PipelineModel`
transform (`_ScorerEvalHook`: featurize + routed predict + host stats)."""

import numpy as np
import pandas as pd
import pytest

from sml_tpu.ml import Pipeline
from sml_tpu.ml.evaluation import RegressionEvaluator
from sml_tpu.ml.feature import (Imputer, OneHotEncoder, StringIndexer,
                                VectorAssembler)
from sml_tpu.ml.regression import LinearRegression, RandomForestRegressor


def _frame(spark, n=4000, seed=7, with_nan_label=True):
    rng = np.random.default_rng(seed)
    pdf = pd.DataFrame({
        "cat": rng.choice(["a", "b", "c"], n),
        "x1": rng.normal(2.0, 1.0, n),
        "x2": rng.normal(-1.0, 2.0, n),
        "label": rng.normal(100.0, 20.0, n),
    })
    if with_nan_label:
        pdf.loc[::97, "label"] = np.nan  # finite-filter parity
    return spark.createDataFrame(pdf)


def test_tree_eval_pushdown_matches_materialized(spark):
    df = _frame(spark)
    feats = Pipeline(stages=[
        StringIndexer(inputCols=["cat"], outputCols=["cat_idx"]),
        VectorAssembler(inputCols=["cat_idx", "x1", "x2"],
                        outputCol="features"),
    ]).fit(df).transform(df)
    feats.cache()
    model = RandomForestRegressor(labelCol="label", numTrees=5, maxDepth=4,
                                  seed=42).fit(feats)
    ev = RegressionEvaluator(labelCol="label")

    lazy = model.transform(feats)
    assert getattr(lazy, "_fused_eval", None) is not None
    assert lazy._parts is None
    rmse_hook = ev.evaluate(lazy)
    # hook path must not have materialized the frame
    assert lazy._parts is None

    materialized = model.transform(feats)
    materialized.toPandas()
    rmse_plain = ev.evaluate(materialized)
    assert rmse_hook == pytest.approx(rmse_plain, rel=1e-5)
    # r2 exercises the sl/sl2 statistics; 1 - mse/var amplifies the
    # f32-sum-order difference by ~1/(1-r2), so gate absolutely
    ev2 = RegressionEvaluator(labelCol="label", metricName="r2")
    assert ev2.evaluate(model.transform(feats)) == \
        pytest.approx(ev2.evaluate(materialized), abs=5e-4)


def test_pipeline_eval_pushdown_matches_materialized(spark):
    df = _frame(spark)
    model = Pipeline(stages=[
        Imputer(strategy="median", inputCols=["x1", "x2"],
                outputCols=["x1_i", "x2_i"]),
        StringIndexer(inputCols=["cat"], outputCols=["cat_idx"],
                      handleInvalid="skip"),
        OneHotEncoder(inputCols=["cat_idx"], outputCols=["cat_ohe"]),
        VectorAssembler(inputCols=["cat_ohe", "x1_i", "x2_i"],
                        outputCol="features"),
        LinearRegression(labelCol="label"),
    ]).fit(df)
    ev = RegressionEvaluator(labelCol="label")

    lazy = model.transform(df)
    assert getattr(lazy, "_fused_eval", None) is not None
    assert lazy._parts is None
    rmse_hook = ev.evaluate(lazy)
    assert lazy._parts is None  # never materialized

    materialized = model.transform(df)
    materialized.toPandas()
    rmse_plain = ev.evaluate(materialized)
    assert rmse_hook == pytest.approx(rmse_plain, rel=1e-5)


def test_pushdown_declines_when_label_is_produced(spark):
    """A prep stage overwriting labelCol means raw labels are stale: the
    hook must decline and the materialize path must serve the metric."""
    df = _frame(spark, with_nan_label=False)
    model = Pipeline(stages=[
        Imputer(strategy="median", inputCols=["label"],
                outputCols=["label"]),  # writes labelCol in place
        VectorAssembler(inputCols=["x1", "x2"], outputCol="features"),
        LinearRegression(labelCol="label"),
    ]).fit(df)
    lazy = model.transform(df)
    hook = getattr(lazy, "_fused_eval", None)
    if hook is not None:
        assert hook.reg_stats("prediction", "label") is None
    ev = RegressionEvaluator(labelCol="label")
    assert np.isfinite(ev.evaluate(model.transform(df)))


def test_pushdown_ignored_for_mismatched_prediction_col(spark):
    df = _frame(spark)
    feats = VectorAssembler(inputCols=["x1", "x2"], outputCol="features") \
        .transform(df)
    model = RandomForestRegressor(labelCol="label", numTrees=3, maxDepth=3,
                                  seed=1, predictionCol="my_pred").fit(feats)
    lazy = model.transform(feats)
    # evaluator asks for the default "prediction": hook declines, normal
    # path raises/handles as it always did — here the column exists under
    # the model's name, so evaluating with the right name still works
    ev = RegressionEvaluator(labelCol="label", predictionCol="my_pred")
    assert np.isfinite(ev.evaluate(lazy))
    assert lazy._fused_eval.reg_stats("prediction", "label") is None


def test_link_pushdown_matches_materialized(spark):
    """ML 11's shape: fit on log(label), evaluate exp(prediction) on the
    raw scale. The withColumn(exp(pred)) frame keeps a LINKED fused-eval
    hook whose device program applies exp inside; the metric must equal
    the materialized path exactly."""
    import numpy as np
    import pandas as pd
    from sml_tpu.frame import functions as F
    from sml_tpu.ml import Pipeline
    from sml_tpu.ml.evaluation import RegressionEvaluator
    from sml_tpu.ml.feature import VectorAssembler
    from sml_tpu.ml.regression import GBTRegressor

    rng = np.random.default_rng(5)
    n = 6000
    pdf = pd.DataFrame({"x1": rng.normal(size=n), "x2": rng.normal(size=n)})
    pdf["price"] = np.exp(0.5 * pdf.x1 - 0.2 * pdf.x2
                          + rng.normal(0, 0.1, n) + 3.0)
    df = spark.createDataFrame(pdf)
    train, test = df.randomSplit([0.8, 0.2], seed=42)
    log_train = train.withColumn("label", F.log(F.col("price")))
    log_test = test.withColumn("label", F.log(F.col("price")))
    va = VectorAssembler(inputCols=["x1", "x2"], outputCol="features")
    m = Pipeline(stages=[va, GBTRegressor(labelCol="label", maxDepth=3,
                                          maxIter=8)]).fit(log_train)
    pred = m.transform(log_test).withColumn(
        "prediction", F.exp(F.col("prediction")))
    # linked hook is attached and tagged
    hook = getattr(pred, "_fused_eval", None)
    assert hook is not None and hook._link == "exp"
    ev = RegressionEvaluator(labelCol="price", metricName="rmse")
    rmse_hook = ev.evaluate(pred)
    assert pred._parts is None  # the hook served; no materialization
    # materialized ground truth
    pp = m.transform(log_test).toPandas()
    truth = float(np.sqrt(np.mean(
        (np.exp(pp["prediction"]) - pp["price"]) ** 2)))
    assert abs(rmse_hook - truth) < 1e-6 * max(truth, 1.0)

    # a link over a NON-prediction column must drop the hook, and a
    # second link over an already-linked hook must too
    other = m.transform(log_test).withColumn("price",
                                             F.exp(F.col("price")))
    assert getattr(other, "_fused_eval", None) is None
    double = pred.withColumn("prediction", F.exp(F.col("prediction")))
    assert getattr(double, "_fused_eval", None) is None


def test_link_pushdown_on_bare_tree_transform(spark):
    """The link propagation also covers the CV/tuning shape: a bare tree
    model's transform over a featurized frame carries _TreeEvalHook, and
    withColumn(exp(pred)) keeps it linked."""
    from sml_tpu.frame import functions as F

    rng = np.random.default_rng(9)
    n = 5000
    pdf = pd.DataFrame({"x1": rng.normal(size=n), "x2": rng.normal(size=n)})
    pdf["label"] = 0.4 * pdf.x1 - 0.3 * pdf.x2 + rng.normal(0, 0.1, n) + 2.0
    pdf["price"] = np.exp(pdf["label"])
    df = spark.createDataFrame(pdf)
    feat = Pipeline(stages=[VectorAssembler(
        inputCols=["x1", "x2"], outputCol="features")]).fit(df).transform(df)
    feat.cache()
    m = RandomForestRegressor(labelCol="label", maxDepth=4, numTrees=6,
                              seed=3).fit(feat)
    pred = m.transform(feat).withColumn("prediction",
                                       F.exp(F.col("prediction")))
    hook = getattr(pred, "_fused_eval", None)
    assert hook is not None and hook._link == "exp"
    rmse = RegressionEvaluator(labelCol="price",
                               metricName="rmse").evaluate(pred)
    # the HOOK must have served the metric: the lazy frame stays
    # unmaterialized (otherwise this only re-tests the fallback path)
    assert pred._parts is None
    pp = m.transform(feat).toPandas()
    truth = float(np.sqrt(np.mean(
        (np.exp(pp["prediction"]) - pp["price"]) ** 2)))
    assert abs(rmse - truth) < 1e-6 * max(truth, 1.0)


# ----------------------------------- device errors are not absorbed (PR 21)
class _DeviceBoom(RuntimeError):
    """Stands in for an error raised by a compiled program or the
    compiler (an XlaRuntimeError / MosaicError on the chip)."""


def _tree_pipeline(spark):
    df = _frame(spark, with_nan_label=False)
    model = Pipeline(stages=[
        StringIndexer(inputCols=["cat"], outputCols=["cat_idx"]),
        VectorAssembler(inputCols=["cat_idx", "x1", "x2"],
                        outputCol="features"),
        RandomForestRegressor(labelCol="label", numTrees=3, maxDepth=3,
                              seed=1),
    ]).fit(df)
    return df, model


def test_device_error_propagates_out_of_reg_stats(spark, monkeypatch):
    """An exception raised by the device program inside the evaluator
    pushdown reaches the caller: before PR 21 `reg_stats` caught it and
    the materialize path produced the right metric from another route."""
    from sml_tpu.ml import _staging
    df, model = _tree_pipeline(spark)
    lazy = model.transform(df)
    assert getattr(lazy, "_fused_eval", None) is not None

    def boom(*a, **k):
        raise _DeviceBoom("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(_staging, "run_data_parallel", boom)
    with pytest.raises(_DeviceBoom):
        lazy._fused_eval.reg_stats("prediction", "label")
    with pytest.raises(_DeviceBoom):
        RegressionEvaluator(labelCol="label").evaluate(model.transform(df))


def test_device_error_propagates_out_of_fused_transform(spark, monkeypatch):
    """The lazy fused transform's `compute_or_fallback` may fall back to
    the per-stage chain on a HOST featurize surprise only; a scorer
    (device dispatch) error must surface, not be replaced by a slower
    path that prints the same predictions."""
    from sml_tpu.ml.inference import DeviceScorer
    df, model = _tree_pipeline(spark)

    def boom(self, X):
        raise _DeviceBoom("RESOURCE_EXHAUSTED: out of vmem")

    monkeypatch.setattr(DeviceScorer, "score_block", boom)
    lazy = model.transform(df)
    with pytest.raises(_DeviceBoom):
        lazy.toPandas()


def test_host_featurize_surprise_still_falls_back(spark, monkeypatch):
    """The other half of the contract: a host-side surprise in the
    compiled feature chain declines the pushdown and falls back to the
    generic per-stage transform, which serves the same predictions."""
    from sml_tpu.ml.featurizer import CompiledFeaturizer
    df, model = _tree_pipeline(spark)
    want = model.transform(df).toPandas()["prediction"].to_numpy()

    def surprise(self, raw):
        raise KeyError("a column the compiled chain assumed raw")

    monkeypatch.setattr(CompiledFeaturizer, "transform_with_mask", surprise)
    monkeypatch.setattr(CompiledFeaturizer, "transform_with_columns",
                        surprise)
    lazy = model.transform(df)
    assert lazy._fused_eval.reg_stats("prediction", "label") is None
    np.testing.assert_allclose(
        lazy.toPandas()["prediction"].to_numpy(), want, rtol=1e-6)
