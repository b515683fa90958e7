"""Compact (expand-on-device) linear paths vs the materialized block.

The scale path (`featurizer.CompactParts` + `linear_impl.fit_*_compact`)
must reproduce the standard path's fits: the Gram moments and IRLS steps
are the same math, only the one-hot expansion moves on-chip. Gated by
`sml.linear.compactBytes`, flipped per-case here.
"""

import numpy as np
import pytest

from sml_tpu.conf import GLOBAL_CONF
from sml_tpu.courseware import make_airbnb_dataset
from sml_tpu.ml import Pipeline
from sml_tpu.ml.classification import LogisticRegression
from sml_tpu.ml.feature import (Imputer, OneHotEncoder, StringIndexer,
                                VectorAssembler)
from sml_tpu.ml.regression import LinearRegression

CAT = ["neighbourhood_cleansed", "room_type", "property_type"]
NUM = ["accommodates", "bathrooms", "bedrooms", "beds",
       "minimum_nights", "number_of_reviews", "review_scores_rating"]


def _stages(est):
    idx = [c + "_idx" for c in CAT]
    ohe = [c + "_ohe" for c in CAT]
    imp = [c + "_imp" for c in NUM]
    return [
        Imputer(strategy="median", inputCols=NUM, outputCols=imp),
        StringIndexer(inputCols=CAT, outputCols=idx, handleInvalid="skip"),
        OneHotEncoder(inputCols=idx, outputCols=ohe),
        VectorAssembler(inputCols=ohe + imp, outputCol="features"),
        est,
    ]


@pytest.fixture
def frames(spark):
    pdf = make_airbnb_dataset(n=8000, seed=7)
    pdf_bin = pdf.copy()
    pdf_bin["label"] = (pdf_bin["price"]
                        > pdf_bin["price"].median()).astype(float)
    return spark.createDataFrame(pdf), spark.createDataFrame(pdf_bin)


@pytest.fixture
def compact_toggle():
    old = GLOBAL_CONF.get("sml.linear.compactBytes")
    yield lambda on: GLOBAL_CONF.set("sml.linear.compactBytes",
                                     0 if on else 1 << 40)
    GLOBAL_CONF.set("sml.linear.compactBytes", old)


def _coefs(model):
    tail = model.stages[-1]
    return tail.coefficients.toArray(), tail.intercept


def test_linear_compact_matches_materialized(frames, compact_toggle):
    df, _ = frames
    compact_toggle(False)
    c1, i1 = _coefs(Pipeline(stages=_stages(
        LinearRegression(labelCol="price"))).fit(df))
    compact_toggle(True)
    c2, i2 = _coefs(Pipeline(stages=_stages(
        LinearRegression(labelCol="price"))).fit(df))
    np.testing.assert_allclose(c1, c2, rtol=1e-5, atol=1e-5)
    assert abs(i1 - i2) < 1e-5


def _course_table():
    """The course's listings WITH their coordinates (a raw latitude of
    37.76 +- 0.026 beside the intercept: a Gram of the raw columns has a
    condition number past 1e7), the numeric gaps filled, and a label the
    columns predict, the latitude most of all."""
    pdf = make_airbnb_dataset(n=7000, seed=11).drop(
        columns=["host_is_superhost"])
    for c in ("bedrooms", "bathrooms", "review_scores_rating"):
        pdf[c] = pdf[c].fillna(pdf[c].median())
    eta = (0.02 * (pdf["review_scores_rating"] - 95)
           + 0.3 * (pdf["room_type"] == "Entire home/apt")
           + 8 * (pdf["latitude"] - 37.76))
    pdf["label"] = (np.random.default_rng(3).random(len(pdf))
                    < 1 / (1 + np.exp(-eta))).astype(float)
    return pdf


def _float64_answer(X, y, logistic):
    """(coefficients with the intercept last, standard errors), both in
    the standardized coordinates, and the moments that define those:
    least squares, or Newton's method from zero to a gradient of 1e-12 a
    row, in float64."""
    mu, sd = X.mean(0), X.std(0)
    sd[sd == 0] = 1.0
    Z = np.concatenate([(X - mu) / sd, np.ones((len(X), 1))], axis=1)
    if not logistic:
        z = np.linalg.lstsq(Z, y, rcond=None)[0]
        cov = np.linalg.inv(Z.T @ Z) * np.var(y - Z @ z)
        return z, np.sqrt(np.diag(cov)), mu, sd
    z = np.zeros(Z.shape[1])
    for _ in range(50):
        p = 1 / (1 + np.exp(-(Z @ z)))
        grad, hess = Z.T @ (p - y), (Z * (p * (1 - p))[:, None]).T @ Z
        z = z - np.linalg.solve(hess, grad)
        if np.max(np.abs(grad)) < 1e-12 * len(y):
            break
    return z, np.sqrt(np.diag(np.linalg.inv(hess))), mu, sd


@pytest.mark.parametrize("compact", [False, True], ids=["block", "compact"])
@pytest.mark.parametrize("family", ["linear", "logistic"])
def test_both_paths_reach_the_float64_answer_on_the_courses_table(
        spark, compact_toggle, family, compact):
    """One standardize-then-map-back step under both sides of
    `sml.linear.compactBytes` (`linear_impl._raw_map`): the formula's fit
    on the course's own columns, against float64 from the sequential
    formula's features, in standard errors of the float64 answer. Read
    (CPU, PR 32): logistic 1.9e-5 on both sides, linear 6.1e-5; on the raw
    columns the parent's Newton steps diverged (4.9e6 standard errors off)
    and its least squares read 1.70. The limit is 5 times the larger
    reading."""
    from sml_tpu.ml.feature import RFormula
    from sml_tpu.ml.linalg import to_matrix
    logistic = family == "logistic"
    formula = "label ~ ." if logistic else "price ~ . - label"
    df = spark.createDataFrame(_course_table())

    def rformula():
        return RFormula(formula=formula, handleInvalid="skip",
                        labelCol="target")

    seen = rformula().fit(df).transform(df).toPandas()
    X = to_matrix(seen["features"]).astype(np.float64)
    want, se, mu, sd = _float64_answer(
        X, seen["target"].to_numpy(np.float64), logistic)
    compact_toggle(compact)
    estimator = (LogisticRegression if logistic else LinearRegression)(
        labelCol="target")
    w, b = _coefs(Pipeline(stages=[rformula(), estimator]).fit(df))
    got = np.append(w * sd, b + w @ mu)
    assert np.max(np.abs(got - want) / se) < 3e-4


def test_a_gram_of_counts_and_small_integers_is_exact(spark):
    """The shift and the scale of a slot are dyadic (`linear_impl._dyadic`:
    a power of two and a multiple of it), so the standardized block of a
    one-hot slot, a count or a half-integer is exact in float32, its sums
    are exact in any order, and the Gram mapped back is the integer Gram
    to the bit. (A shift by the mean itself read 5e-4 on the coefficients
    of this file's first table where the raw float32 Gram read 5e-5, for
    this reason; the dyadic one reads 9e-7.)"""
    from sml_tpu.ml import linear_impl
    rng = np.random.default_rng(0)
    n = 5000
    X = np.column_stack([
        (rng.random(n) < 0.3), (rng.random(n) < 0.004),
        rng.integers(0, 17, n), rng.integers(1, 9, n) / 2,
        1000 + rng.integers(0, 3, n)]).astype(np.float32)
    y = rng.integers(0, 500, n).astype(np.float32)
    A, b, n_f, yy = linear_impl.gram_stats(X, y)
    Xa = np.column_stack([X, np.ones(n)]).astype(np.float64)
    np.testing.assert_array_equal(A, Xa.T @ Xa)
    np.testing.assert_array_equal(b, Xa.T @ y.astype(np.float64))
    assert n_f == n
    # the host's pair (the block path's Newton loop) is the device's
    mean = rng.normal(scale=50, size=64).astype(np.float32)
    std = np.exp(rng.normal(scale=6, size=64)).astype(np.float32)
    for mine, theirs in zip(linear_impl._dyadic_host(mean, std),
                            linear_impl._dyadic(mean, std)):
        np.testing.assert_array_equal(mine, np.asarray(theirs))
    scale = linear_impl._dyadic_host(mean, std)[1]
    assert np.all((scale <= std) & (std < 2 * scale))
    assert np.all(np.frexp(scale)[0] == 0.5)          # powers of two


def test_elastic_net_runs_on_compact_gram(frames, compact_toggle):
    df, _ = frames
    est = lambda: LinearRegression(labelCol="price", regParam=0.1,  # noqa
                                   elasticNetParam=0.5)
    compact_toggle(False)
    c1, _ = _coefs(Pipeline(stages=_stages(est())).fit(df))
    compact_toggle(True)
    c2, _ = _coefs(Pipeline(stages=_stages(est())).fit(df))
    np.testing.assert_allclose(c1, c2, atol=1e-4)


def test_logistic_fused_irls_matches_host_loop(frames, compact_toggle):
    _, df = frames
    est = lambda: LogisticRegression(labelCol="label", maxIter=12)  # noqa
    compact_toggle(False)
    m1 = Pipeline(stages=_stages(est())).fit(df)
    compact_toggle(True)
    m2 = Pipeline(stages=_stages(est())).fit(df)
    c1, _ = _coefs(m1)
    c2, _ = _coefs(m2)
    np.testing.assert_allclose(c1, c2, atol=5e-4)
    s1, s2 = m1.stages[-1].summary, m2.stages[-1].summary
    assert abs(s1.accuracy - s2.accuracy) < 5e-3
    assert abs(s1.areaUnderROC - s2.areaUnderROC) < 5e-3


def test_penalized_logistic_falls_back_correctly(frames, compact_toggle):
    _, df = frames
    est = lambda: LogisticRegression(labelCol="label", maxIter=8,  # noqa
                                     regParam=0.01)
    compact_toggle(False)
    c1, _ = _coefs(Pipeline(stages=_stages(est())).fit(df))
    compact_toggle(True)  # compact attach + expand_host fallback
    c2, _ = _coefs(Pipeline(stages=_stages(est())).fit(df))
    np.testing.assert_allclose(c1, c2, atol=1e-5)


def test_compact_parts_expand_matches_block(frames):
    """CompactParts.expand_host reproduces the featurizer's block and
    predict_affine equals X @ w."""
    df, _ = frames
    from sml_tpu.ml.featurizer import CompiledFeaturizer
    stages = _stages(LinearRegression(labelCol="price"))
    fitted = [stages[0].fit(df), stages[1].fit(df)]
    ohe_m = stages[2]._fit_with_sizes if hasattr(stages[2], "_fit_with_sizes") \
        else None
    prep = Pipeline(stages=stages[:-1]).fit(df)
    feat = CompiledFeaturizer.from_stages(prep.stages[:-1], prep.stages[-1])
    assert feat is not None
    pdf = df.toPandas()
    parts = feat.compact_parts(pdf)
    assert parts is not None
    X, keep = feat.transform_with_mask(pdf)
    np.testing.assert_array_equal(parts.expand_host(), X)
    rng = np.random.default_rng(0)
    w = rng.normal(size=parts.width)
    np.testing.assert_allclose(parts.predict_affine(w, 1.5),
                               X.astype(np.float64) @ w + 1.5, rtol=1e-6)
    assert fitted and ohe_m is None  # silence lints; fixtures exercised
