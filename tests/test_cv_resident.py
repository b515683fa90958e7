"""`CrossValidator` over ONE staged block (ISSUE 40), tiny and on the CPU:
the fold id a row is `randomSplit`'s own membership; the metrics and the best
model are the frame-by-frame path's; the host ranking is the exact midrank
area; one `Pipeline.fit` counts one plan, one block of H2D, 19 fits and no
fold frame; and whatever cannot take its folds as a mask keeps the old path."""

import numpy as np
import pandas as pd
import pytest

from sml_tpu import obs
from sml_tpu.conf import GLOBAL_CONF
from sml_tpu.frame import functions as F
from sml_tpu.frame.session import get_session
from sml_tpu.ml import Pipeline
from sml_tpu.ml.classification import (DecisionTreeClassifier,
                                       LogisticRegression, _fast_auc,
                                       _midrank_auc)
from sml_tpu.ml.evaluation import BinaryClassificationEvaluator
from sml_tpu.ml.feature import RFormula
from sml_tpu.ml.tuning import CrossValidator, ParamGridBuilder, _fold_ids

COUNTED = ("cv.fits", "cv.evals", "cv.fold_frames", "linear.host_loops",
           "linear.irls.fits", "featurize.plan.fits",
           "featurize.plan.declined", "staging.h2d_bytes")


@pytest.fixture(scope="module", autouse=True)
def recorder_on():
    held = GLOBAL_CONF.get("sml.obs.enabled")
    GLOBAL_CONF.set("sml.obs.enabled", True)
    yield
    GLOBAL_CONF.set("sml.obs.enabled", held)


def _table(rows: int, seed: int) -> pd.DataFrame:
    """Listings whose columns take a few values each, so that a margin
    takes a few hundred and every fold ranks thousands of ties: two paths
    whose coefficients differ in their last float32 digits then rank the
    same, and their areas agree to the last digit of a float64."""
    rng = np.random.default_rng(seed)
    pdf = pd.DataFrame({
        "room": rng.choice(["entire", "private", "shared"], rows,
                           p=[0.6, 0.3, 0.1]),
        "policy": rng.choice(["flexible", "moderate", "strict", "super"],
                             rows),
        "beds": rng.integers(1, 5, rows).astype(np.float64),
        "baths": rng.integers(1, 4, rows).astype(np.float64),
        "score": rng.integers(6, 11, rows).astype(np.float64)})
    eta = (-4.0 + 0.8 * (pdf["room"] == "entire") + 0.3 * pdf["beds"]
           + 0.35 * pdf["score"] - 0.4 * (pdf["policy"] == "strict"))
    pdf["label"] = (rng.random(rows) < 1 / (1 + np.exp(-eta))).astype(
        np.float64)
    return pdf


def _frame(pdf: pd.DataFrame, partitions: int = 8):
    df = get_session().createDataFrame(pdf).repartition(partitions)
    df.cache()
    return df


def _validator(estimator=None, grid=None, evaluator=None, folds=3):
    lr = estimator or LogisticRegression(labelCol="label",
                                         featuresCol="features")
    if grid is None:
        grid = (ParamGridBuilder().addGrid(lr.regParam, [0.1, 0.2])
                .addGrid(lr.elasticNetParam, [0.0, 0.5, 1.0]).build())
    return CrossValidator(
        estimator=lr, estimatorParamMaps=grid, numFolds=folds,
        parallelism=4, seed=42, evaluator=evaluator
        or BinaryClassificationEvaluator(metricName="areaUnderROC"))


def _formula():
    return RFormula(formula="label ~ .", featuresCol="features",
                    labelCol="label", handleInvalid="skip")


def _counted(run):
    before = dict(obs.RECORDER.counters())
    out = run()
    after = obs.RECORDER.counters()
    return out, {k: after.get(k, 0.0) - before.get(k, 0.0) for k in COUNTED}


def _membership(frame, k: int, seed: int) -> np.ndarray:
    """The fold of every row of `frame.toPandas()` by the public API: the
    rows numbered, the frame split, the numbers read back."""
    numbered = frame.withColumn("_row", F.monotonically_increasing_id())
    every = numbered.select("_row").toPandas()["_row"].to_numpy()
    out = np.full(len(every), -1)
    for f, part in enumerate(numbered.randomSplit([1.0 / k] * k, seed=seed)):
        ids = part.select("_row").toPandas()["_row"].to_numpy()
        out[np.searchsorted(every, ids)] = f
    return out


# ------------------------------------------------------------- fold ids
@pytest.mark.parametrize("rows,partitions,k,seed", [
    (5000, 8, 3, 42), (70000, 4, 3, 42), (3000, 1, 5, 7), (9000, 3, 2, 2**31)])
def test_fold_ids_are_random_splits_membership(rows, partitions, k, seed):
    frame = _frame(_table(rows, seed=rows), partitions)
    ids = _fold_ids(frame, k, seed, None)
    assert ids.dtype == np.int8 and len(ids) == rows
    want = _membership(frame, k, seed)
    assert (want >= 0).all()
    assert (ids == want).all()


def test_fold_ids_of_the_rows_a_featurizer_kept():
    """A row dropped before the split never drew: the ids are those of
    the split of the kept rows, partition by partition."""
    frame = _frame(_table(6000, seed=5), 4)
    keep = np.random.default_rng(1).random(6000) > 0.1
    sizes = np.cumsum([0] + [len(p) for p in frame._materialize()])
    parts = [p[keep[a:b]] for p, a, b in zip(frame._materialize(),
                                             sizes[:-1], sizes[1:])]
    from sml_tpu.frame.dataframe import DataFrame
    filtered = DataFrame.from_partitions(
        [p.reset_index(drop=True) for p in parts], session=frame._session)
    assert (_fold_ids(frame, 3, 42, keep)
            == _membership(filtered, 3, 42)).all()


# ------------------------------------------------------ the two paths agree
@pytest.fixture(scope="module")
def both_paths():
    train = _frame(_table(6000, seed=3))
    resident, counted = _counted(
        lambda: Pipeline(stages=[_formula(), _validator()]).fit(train))
    # frame by frame: the formula's model, its transform, the validator on
    # the featurized frame (a features column, no compact block)
    featurized = _formula().fit(train).transform(train)
    framed, framed_counted = _counted(
        lambda: _validator().fit(featurized))
    return resident.stages[-1], counted, framed, framed_counted, train


def test_avg_metrics_are_the_frame_paths(both_paths):
    resident, _, framed, _, _ = both_paths
    assert len(resident.avgMetrics) == 6
    assert np.max(np.abs(np.array(resident.avgMetrics)
                         - np.array(framed.avgMetrics))) < 1e-9
    assert 0.6 < max(resident.avgMetrics) < 0.9


def test_best_model_is_the_frame_paths(both_paths):
    resident, _, framed, _, _ = both_paths
    a, b = resident.bestModel, framed.bestModel
    for name in ("regParam", "elasticNetParam"):
        assert a.getOrDefault(name) == b.getOrDefault(name)
    assert np.max(np.abs(a.coefficients.toArray()
                         - b.coefficients.toArray())) < 1e-4
    assert abs(a.intercept - b.intercept) < 1e-4
    assert abs(a.summary.areaUnderROC - b.summary.areaUnderROC) < 1e-9
    assert a.summary.numInstances == 6000
    best = int(np.argmax(resident.avgMetrics))
    at = resident.getEstimatorParamMaps()[best]
    assert {p.name: v for p, v in at.items()} == {
        "regParam": a.getOrDefault("regParam"),
        "elasticNetParam": a.getOrDefault("elasticNetParam")}


def test_one_pipeline_fit_counts_one_plan_one_block_and_no_fold_frame(
        both_paths):
    _, counted, _, framed_counted, _ = both_paths
    assert counted["cv.fits"] == 19 and counted["cv.evals"] == 18
    assert counted["cv.fold_frames"] == 0
    assert counted["linear.irls.fits"] == 19
    assert counted["linear.host_loops"] == 0
    assert counted["featurize.plan.fits"] == 1
    assert counted["featurize.plan.declined"] == 0
    # one padded block: 3 float32 and 2 int32 columns, the label, a fold id
    padded = counted["staging.h2d_bytes"] / (4 * 6 + 1)
    assert 6000 <= padded <= 6000 * 1.125 + 8
    # the frame path: fold frames, a host loop a fit
    assert framed_counted["cv.fold_frames"] == 6
    assert framed_counted["linear.host_loops"] == 19
    assert framed_counted["cv.fits"] == 19


def test_the_pipeline_model_transforms(both_paths):
    resident, _, framed, _, train = both_paths
    out = resident.transform(_formula().fit(train).transform(train))
    got = out.select("prediction").toPandas()["prediction"]
    assert len(got) == 6000 and set(got.unique()) <= {0.0, 1.0}


# ------------------------------------------------------------- the ranking
def _evaluator_auc(score, label):
    pdf = pd.DataFrame({"rawPrediction": score, "label": label})
    return BinaryClassificationEvaluator(
        metricName="areaUnderROC").evaluate(pdf)


@pytest.mark.parametrize("ties", [False, True], ids=["distinct", "tied"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_midrank_area_is_exact(seed, ties):
    rng = np.random.default_rng(seed)
    n = 4000
    score = rng.normal(size=n).astype(np.float32)
    if ties:
        score = np.round(score, 1)
    label = (rng.random(n) < 0.3).astype(np.float64)
    rows = np.flatnonzero(rng.random(n) < 0.4)
    got = _midrank_auc(score, rows, label[rows] > 0.5)
    # every pair counted in Python's own integers
    s, pos = score[rows].astype(np.float64), label[rows] > 0.5
    wins = sum(int((s[~pos] < v).sum()) * 2 + int((s[~pos] == v).sum())
               for v in s[pos])
    assert got == wins / (2 * int(pos.sum()) * int((~pos).sum()))
    assert abs(got - _evaluator_auc(s, label[rows])) < 1e-12
    if not ties:
        assert abs(got - _fast_auc(s, label[rows])) < 1e-12


def test_the_midrank_area_of_one_class_is_nan():
    score = np.arange(5, dtype=np.float32)
    assert np.isnan(_midrank_auc(score, np.arange(5), np.ones(5, bool)))
    assert _midrank_auc(np.zeros(6, np.float32), np.arange(6),
                        np.array([1, 0, 1, 0, 0, 1], bool)) == 0.5


# ------------------------------------------------- who keeps the old path
def _declines(validator, train):
    model, counted = _counted(
        lambda: Pipeline(stages=[_formula(), validator]).fit(train))
    assert counted["featurize.plan.declined"] == 1
    assert counted["cv.fold_frames"] == 2 * validator.getOrDefault("numFolds")
    return model.stages[-1], counted


@pytest.fixture(scope="module")
def small():
    return _frame(_table(1500, seed=9), 2)


def test_an_estimator_that_takes_no_mask_keeps_its_fold_frames(small):
    tree = DecisionTreeClassifier(labelCol="label", featuresCol="features")
    grid = ParamGridBuilder().addGrid(tree.maxDepth, [2, 3]).build()
    from sml_tpu.ml.evaluation import MulticlassClassificationEvaluator
    tuned, counted = _declines(_validator(
        tree, grid, MulticlassClassificationEvaluator(
            metricName="accuracy"), folds=2), small)
    assert counted["cv.fits"] == 5 and len(tuned.avgMetrics) == 2


def test_a_grid_over_another_parameter_keeps_its_fold_frames(small):
    lr = LogisticRegression(labelCol="label", featuresCol="features")
    grid = ParamGridBuilder().addGrid(lr.maxIter, [5, 50]).build()
    tuned, counted = _declines(_validator(lr, grid, folds=2), small)
    assert counted["linear.host_loops"] == 5


def test_another_metric_keeps_its_fold_frames(small):
    lr = LogisticRegression(labelCol="label", featuresCol="features")
    grid = ParamGridBuilder().addGrid(lr.regParam, [0.0, 0.1]).build()
    _declines(_validator(lr, grid, BinaryClassificationEvaluator(
        metricName="areaUnderPR"), folds=2), small)


def test_a_validator_outside_a_pipeline_keeps_its_fold_frames(small):
    featurized = _formula().fit(small).transform(small)
    _, counted = _counted(lambda: _validator(folds=2).fit(featurized))
    assert counted["cv.fold_frames"] == 4
    assert counted["featurize.plan.fits"] == 0


def test_a_penalized_fit_outside_a_validator_is_the_fused_program(small):
    GLOBAL_CONF.set("sml.linear.compactBytes", 0)
    try:
        _, counted = _counted(lambda: Pipeline(stages=[
            _formula(), LogisticRegression(
                labelCol="label", featuresCol="features", regParam=0.1,
                elasticNetParam=0.5)]).fit(small))
    finally:
        GLOBAL_CONF.unset("sml.linear.compactBytes")
    assert counted["linear.irls.fits"] == 1
    assert counted["linear.host_loops"] == 0
    assert counted["cv.fits"] == 0
