"""Golden-metric regression gate (VERDICT r2 #4, SURVEY §4/§7 hard-part #1).

GOLDEN.json pins the bench-shaped model metrics at n=100k/seed=42 on the
CPU test mesh (f32 histograms — the TPU bench runs bf16 histogram operands
and reports its own values in BENCH_r*.json). Any numerics change that
moves a pinned metric fails CI, each pin its own case so that one that
moves does not hide the others; intentional changes regenerate with

    python tests/test_golden_metrics.py --regen [metric ...]

(no names: every pin; with names: those alone, the others left as they
stand). The pins date from PR 6 but for `rmse_rf`, re-pinned in PR 30 on
jax 0.9.0 / jaxlib 0.9.0: since jax 0.5.0 `jax_threefry_partitionable`
defaults to True, so a key yields other random bits than the ones the pin
was taken on, and the forest's Poisson bootstrap weights and per-node
feature subsets are other draws (tree 0's root cover 80,141 for 80,593,
its root split on another feature). With the flag set False this tree
still reads the old 69.071877 to 3e-6. Nothing else pinned here draws
from `jax.random`: the other nine held within 1.2e-5 across the same
upgrade.

Also asserts the orderings the course states in prose: LR beats the
mean-price baseline (`ML 02:155`), tuned RF at least matches a single
tree (`ML 07:171`), XGBoost beats the plain forest (`ML 11`).
"""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
GOLDEN_PATH = os.path.join(HERE, os.pardir, "GOLDEN.json")
N_ROWS = 100_000


def compute_metrics():
    """The bench legs' fits at golden size; returns {metric: value}."""
    import pandas as pd

    from sml_tpu import functions as F
    from sml_tpu.courseware import make_airbnb_dataset
    from sml_tpu.frame.session import get_session
    from sml_tpu.ml import Pipeline
    from sml_tpu.ml.evaluation import RegressionEvaluator
    from sml_tpu.ml.feature import (Imputer, OneHotEncoder, StringIndexer,
                                    VectorAssembler)
    from sml_tpu.ml.regression import (DecisionTreeRegressor,
                                       LinearRegression,
                                       RandomForestRegressor)
    from sml_tpu.xgboost import XgboostRegressor

    CAT = ["neighbourhood_cleansed", "room_type", "property_type"]
    NUM = ["accommodates", "bathrooms", "bedrooms", "beds",
           "minimum_nights", "number_of_reviews", "review_scores_rating"]
    spark = get_session()
    df = spark.createDataFrame(make_airbnb_dataset(n=N_ROWS, seed=42))
    train, test = df.randomSplit([0.8, 0.2], seed=42)
    train.cache()
    test.cache()
    idx = [c + "_idx" for c in CAT]
    ohe = [c + "_ohe" for c in CAT]
    imp = [c + "_imp" for c in NUM]
    prep = [Imputer(strategy="median", inputCols=NUM, outputCols=imp),
            StringIndexer(inputCols=CAT, outputCols=idx,
                          handleInvalid="skip")]
    ev = RegressionEvaluator(labelCol="price")
    out = {}

    lr = Pipeline(stages=prep + [
        OneHotEncoder(inputCols=idx, outputCols=ohe),
        VectorAssembler(inputCols=ohe + imp, outputCol="features"),
        LinearRegression(labelCol="price")]).fit(train)
    out["rmse_lr"] = ev.evaluate(lr.transform(test))
    mean_price = float(train.toPandas()["price"].mean())
    out["rmse_mean_baseline"] = ev.evaluate(
        lr.transform(test).withColumn("prediction", F.lit(mean_price)))

    tree_feats = VectorAssembler(inputCols=idx + imp, outputCol="features")
    dt = Pipeline(stages=prep + [tree_feats,
                  DecisionTreeRegressor(labelCol="price", maxDepth=5,
                                        maxBins=40)]).fit(train)
    out["rmse_dt"] = ev.evaluate(dt.transform(test))

    rf = Pipeline(stages=prep + [tree_feats,
                  RandomForestRegressor(labelCol="price", maxDepth=6,
                                        numTrees=20, maxBins=40,
                                        seed=42)]).fit(train)
    out["rmse_rf"] = ev.evaluate(rf.transform(test))

    log_train = train.withColumn("label", F.log(F.col("price")))
    log_test = test.withColumn("label", F.log(F.col("price")))
    xgb = Pipeline(stages=prep + [tree_feats,
                   XgboostRegressor(n_estimators=40, learning_rate=0.15,
                                    max_depth=6, max_bins=64,
                                    random_state=42)]).fit(log_train)
    pred = xgb.transform(log_test).withColumn(
        "prediction", F.exp(F.col("prediction")))
    out["rmse_xgb"] = ev.evaluate(pred)

    # ML 07L's priceClass binarization (`Labs/ML 07L:36-58`), AUROC pin
    from sml_tpu.ml.classification import LogisticRegression
    from sml_tpu.ml.evaluation import BinaryClassificationEvaluator
    median_price = float(train.toPandas()["price"].median())
    sh_train = train.withColumn(
        "label", F.when(F.col("price") >= median_price, 1.0).otherwise(0.0))
    sh_test = test.withColumn(
        "label", F.when(F.col("price") >= median_price, 1.0).otherwise(0.0))
    logit = Pipeline(stages=prep + [
        OneHotEncoder(inputCols=idx, outputCols=ohe),
        VectorAssembler(inputCols=ohe + imp, outputCol="features"),
        LogisticRegression(labelCol="label")]).fit(sh_train)
    out["auroc_logistic"] = BinaryClassificationEvaluator(
        labelCol="label").evaluate(logit.transform(sh_test))

    # MLE 01: ALS on a MovieLens-shaped set, cold-start drop
    from sml_tpu.courseware import make_movielens_dataset
    from sml_tpu.ml.recommendation import ALS
    ratings = spark.createDataFrame(
        make_movielens_dataset(n_users=1000, n_items=400,
                               n_ratings=N_ROWS, seed=42))
    als_train, als_test = ratings.randomSplit([0.8, 0.2], seed=42)
    als_model = ALS(userCol="userId", itemCol="movieId", ratingCol="rating",
                    rank=8, maxIter=10, regParam=0.1, seed=42,
                    coldStartStrategy="drop").fit(als_train)
    out["rmse_als"] = RegressionEvaluator(labelCol="rating").evaluate(
        als_model.transform(als_test))
    mean_rating = float(als_train.toPandas()["rating"].mean())
    out["rmse_als_mean_baseline"] = RegressionEvaluator(
        labelCol="rating").evaluate(als_model.transform(als_test)
                                    .withColumn("prediction",
                                                F.lit(mean_rating)))

    # MLE 02: KMeans training cost + centers
    from sml_tpu.ml.clustering import KMeans
    km_feats = Pipeline(stages=[
        Imputer(strategy="median", inputCols=NUM, outputCols=imp),
        VectorAssembler(inputCols=imp, outputCol="features"),
    ]).fit(train).transform(train)
    km = KMeans(k=3, maxIter=20, seed=221).fit(km_feats)
    out["kmeans_cost"] = km.summary.trainingCost
    centers = np.stack([np.asarray(c) for c in km.clusterCenters()])
    # stable pin order: sort by the well-separated reviews column (66 /
    # 199 / 332), not col 0 whose values differ by less than the pin tol
    centers = centers[np.argsort(centers[:, 5])]
    out["_kmeans_centers"] = [[round(float(v), 5) for v in row]
                              for row in centers]
    return {k: (v if k.startswith("_") else round(float(v), 6))
            for k, v in out.items()}


@pytest.fixture(scope="module")
def metrics():
    return compute_metrics()


def _golden() -> dict:
    assert os.path.exists(GOLDEN_PATH), \
        "GOLDEN.json missing; run: python tests/test_golden_metrics.py --regen"
    with open(GOLDEN_PATH) as f:
        return json.load(f)


@pytest.mark.parametrize("k", list(_golden()["metrics"]))
def test_metrics_match_golden(metrics, k):
    golden = _golden()
    assert golden["n_rows"] == N_ROWS and golden["seed"] == 42
    # pin breadth: the gate must cover regression, classification,
    # recommendation, and clustering metrics (VERDICT r3 #9)
    assert len(golden["metrics"]) >= 10
    got, want = metrics[k], golden["metrics"][k]
    if k == "_kmeans_centers":
        np.testing.assert_allclose(np.asarray(got, dtype=float),
                                   np.asarray(want, dtype=float),
                                   atol=1e-3)
        return
    # large-magnitude pins (kmeans_cost ~1e8) get a relative gate: an
    # absolute 1e-3 there would be tighter than one float32 ULP
    tol = max(1e-3, 1e-5 * abs(want))
    assert abs(got - want) < tol, \
        f"{k}: got {got}, golden {want} (Δ={abs(got - want):.2e})"


def test_course_stated_orderings(metrics):
    # ML 02:155 — the model must beat predicting the average price
    assert metrics["rmse_lr"] < metrics["rmse_mean_baseline"]
    # ML 07:171 — the (deeper, ensembled) forest beats the single tree
    assert metrics["rmse_rf"] < metrics["rmse_dt"]
    # ML 11 — boosted trees beat the forest on this data
    assert metrics["rmse_xgb"] < metrics["rmse_rf"]
    # everything is a real improvement over the constant baseline
    for k in ("rmse_dt", "rmse_rf", "rmse_xgb"):
        assert metrics[k] < metrics["rmse_mean_baseline"]
    # MLE 01 — ALS beats the global-mean-rating baseline (`MLE 01:147-159`)
    assert metrics["rmse_als"] < metrics["rmse_als_mean_baseline"]
    # MLE 03 — the classifier separates better than chance
    assert metrics["auroc_logistic"] > 0.6


def _regen(only=()):
    """Re-pin every metric, or the ones named in `only` alone."""
    import jax
    import jaxlib
    doc = {}
    if os.path.exists(GOLDEN_PATH):
        with open(GOLDEN_PATH) as f:
            doc = json.load(f)
    got = compute_metrics()
    on = f"jax {jax.__version__} / jaxlib {jaxlib.__version__}"
    if only:
        doc["metrics"].update({k: got[k] for k in only})
        doc["environment"] += f"; {', '.join(only)} re-pinned on {on}"
    else:
        doc.update({"n_rows": N_ROWS, "seed": 42,
                    "environment": "virtual 8-device CPU mesh (f32 "
                                   "histograms); the chip's bf16 "
                                   "histogram operands read other digits; "
                                   f"pinned on {on}",
                    "metrics": got})
    with open(GOLDEN_PATH, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"wrote {os.path.abspath(GOLDEN_PATH)}")


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(HERE, os.pardir))
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = \
            (flags + " --xla_force_host_platform_device_count=8").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
    if "--regen" in sys.argv:
        _regen(sys.argv[sys.argv.index("--regen") + 1:])
