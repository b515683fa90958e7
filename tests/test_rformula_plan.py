"""`Pipeline([RFormula, estimator])` through the fit-time column plan.

A formula IS a StringIndexer, a OneHotEncoder and a VectorAssembler over raw
columns (`RFormula._chain`), so the plan runs its jobs and makes the
`RFormulaModel` from their results. Held here: that model equals the
sequential `RFormula.fit`'s to the bit (labels, category sizes, the
assembler's inputs, the slot metadata), its `transform` equals the
sequential model's on the fitted table and on one with labels it has not
seen, and the estimator fitted behind it equals the one fitted on the
sequential model's output, under `sml.linear.compactBytes` (the block path,
to the bit) and over it (the compact device path)."""

import numpy as np
import pandas as pd
import pytest

from sml_tpu import obs
from sml_tpu.conf import GLOBAL_CONF
from sml_tpu.courseware import make_airbnb_dataset
from sml_tpu.ml import Pipeline
from sml_tpu.ml.classification import LogisticRegression
from sml_tpu.ml.feature import RFormula, RFormulaModel
from sml_tpu.ml.linalg import to_matrix
from sml_tpu.ml.regression import LinearRegression

FORMULAS = {
    "label ~ .": ("label", LogisticRegression),
    "price ~ .": ("price", LinearRegression),
    "log_price ~ . - price": ("log_price", LinearRegression),
    "price ~ bedrooms + room_type + review_scores_rating + bed_type":
        ("price", LinearRegression),
}


def _table(nulls: bool) -> pd.DataFrame:
    """The course's listings, coordinates and all, the numeric gaps
    filled, and, with `nulls`, gaps in two string columns."""
    pdf = make_airbnb_dataset(n=3000, seed=11)
    for c in ("bedrooms", "bathrooms", "review_scores_rating"):
        pdf[c] = pdf[c].fillna(pdf[c].median())
    pdf["label"] = (pdf.pop("host_is_superhost") == "t").astype(float)
    pdf["log_price"] = np.log(pdf["price"])
    if nulls:
        rng = np.random.default_rng(5)
        for c in ("room_type", "bed_type"):
            col = pdf[c].astype(object)
            col[rng.random(len(pdf)) < 0.02] = None
            pdf[c] = col
    return pdf


@pytest.fixture
def conf():
    held = GLOBAL_CONF.get("sml.linear.compactBytes")
    GLOBAL_CONF.set("sml.obs.enabled", True)
    obs.reset()
    yield GLOBAL_CONF
    GLOBAL_CONF.set("sml.linear.compactBytes", held)
    GLOBAL_CONF.set("sml.obs.enabled", False)
    obs.reset()


def _counted(name: str) -> float:
    return obs.RECORDER.counters().get(name, 0.0)


def _features(frame, label_col):
    pdf = frame.toPandas()
    return to_matrix(pdf["features"]), pdf[label_col].to_numpy()


CASES = [(f, invalid, nulls, compact)
         for f in FORMULAS
         for invalid, nulls in (("skip", True), ("keep", True),
                                ("error", False))
         for compact in (False, True)]


@pytest.mark.parametrize("formula,invalid,nulls,compact", CASES)
def test_the_plans_formula_model_is_the_sequential_fits(
        spark, conf, formula, invalid, nulls, compact):
    label, estimator = FORMULAS[formula]
    pdf = _table(nulls)
    df = spark.createDataFrame(pdf)
    conf.set("sml.linear.compactBytes", 0 if compact else 1 << 40)

    def rformula():
        return RFormula(formula=formula, handleInvalid=invalid,
                        labelCol="target")

    fits, declined = (_counted("featurize.plan.fits"),
                      _counted("featurize.plan.declined"))
    model = Pipeline(stages=[rformula(), estimator(
        labelCol="target", maxIter=10)]).fit(df)
    assert _counted("featurize.plan.fits") == fits + 1
    assert _counted("featurize.plan.declined") == declined
    planned, want = model.stages[0], rformula().fit(df)

    # the fitted formula, to the bit
    assert isinstance(planned, RFormulaModel)
    assert (planned.label_source, planned._label_col) == (label, "target")
    assert [type(s) for s in planned.stages] == [type(s) for s in want.stages]
    for mine, theirs in zip(planned.stages, want.stages):
        assert mine._params_to_dict() == theirs._params_to_dict()
        assert getattr(mine, "labelsArray", None) == \
            getattr(theirs, "labelsArray", None)
        assert getattr(mine, "categorySizes", None) == \
            getattr(theirs, "categorySizes", None)
    assert planned._params_to_dict() == want._params_to_dict()

    # its transform: the fitted table, and labels it has not seen
    unseen = pdf.head(200).copy()
    unseen["room_type"] = unseen["room_type"].astype(object)
    unseen.loc[unseen.index[:5], "room_type"] = "Yurt"
    for frame in (df, spark.createDataFrame(unseen)):
        if invalid == "error" and frame is not df:
            with pytest.raises(ValueError, match="Unseen label 'Yurt'"):
                planned.transform(frame).toPandas()
            continue
        got, expected = planned.transform(frame), want.transform(frame)
        assert got._ml_attrs["features"] == expected._ml_attrs["features"]
        (X, y), (Xw, yw) = _features(got, "target"), \
            _features(expected, "target")
        np.testing.assert_array_equal(X, Xw)
        np.testing.assert_array_equal(y, yw)

    # the estimator behind it: the sequential model's output, fitted
    tail = model.stages[-1]
    seq = estimator(labelCol="target", maxIter=10).fit(want.transform(df))
    if compact:
        # the compact program sums over the table's rows in another order
        # than the block path's (both on the standardized slots,
        # `linear_impl._raw_map`): the same fitted values, to a hundred-
        # thousandth of the label's spread (2.7e-6 is the most the twelve
        # cases read)
        X, y = _features(want.transform(df), "target")
        fitted = [X @ m.coefficients.toArray() + m.intercept
                  for m in (tail, seq)]
        np.testing.assert_allclose(fitted[0], fitted[1], rtol=0,
                                   atol=1e-5 * max(np.std(y), 1.0))
    else:
        np.testing.assert_array_equal(tail.coefficients.toArray(),
                                      seq.coefficients.toArray())
        assert tail.intercept == seq.intercept


def test_an_assembler_that_skips_drops_the_rows_the_stage_drops(spark, conf):
    """`handleInvalid="skip"` drops a row for a null string AND for a
    number that is not finite; the plan drops the same rows, in the block
    path and in the compact one."""
    pdf = _table(nulls=True)
    pdf.loc[pdf.index[::50], "bedrooms"] = np.nan
    df = spark.createDataFrame(pdf)
    want = RFormula(formula="price ~ .", handleInvalid="skip").fit(df)
    Xw, yw = _features(want.transform(df), "label")
    assert 0 < len(Xw) < len(pdf) - len(pdf[::50]) + 1
    coefs = []
    for compact in (False, True):
        conf.set("sml.linear.compactBytes", 0 if compact else 1 << 40)
        model = Pipeline(stages=[
            RFormula(formula="price ~ .", handleInvalid="skip"),
            LinearRegression()]).fit(df)
        assert model.stages[-1].summary.numInstances == len(Xw)
        coefs.append(model.stages[-1].coefficients.toArray())
    seq = LinearRegression().fit(want.transform(df))
    np.testing.assert_array_equal(coefs[0], seq.coefficients.toArray())
    np.testing.assert_allclose(Xw @ coefs[1], Xw @ coefs[0], rtol=0,
                               atol=1e-3 * np.std(yw))


def test_a_formula_beside_other_prep_stages_declines_and_says_why(spark, conf):
    from sml_tpu.ml.feature import Imputer
    pdf = _table(nulls=False)
    df = spark.createDataFrame(pdf)
    declined = _counted("featurize.plan.declined")
    model = Pipeline(stages=[
        Imputer(strategy="median", inputCols=["beds"], outputCols=["beds"]),
        RFormula(formula="price ~ beds + room_type"),
        LinearRegression(maxIter=5)]).fit(df)
    assert _counted("featurize.plan.declined") == declined + 1
    reasons = [e.args.get("reason") for e in obs.RECORDER.events()
               if e.name == "featurize.plan.declined"]
    assert reasons[-1] == "a RFormula stage beside other prep stages"
    assert isinstance(model.stages[1], RFormulaModel)   # the generic path


@pytest.mark.parametrize("compact", [False, True], ids=["block", "compact"])
@pytest.mark.parametrize("formula", ["label ~ .", "log_price ~ . - price"])
def test_a_formula_over_the_frames_partitions_makes_no_concat(
        spark, conf, monkeypatch, formula, compact):
    """The plan reads the eight partitions where they lie: the model is
    the one-partition frame's to the bit, the label under another name
    too, and the frame holds no table of its own afterwards."""
    import sml_tpu.ml._column_plan as cp
    monkeypatch.setattr(cp, "_INLINE_ROWS", 2048)   # under the table's rows
    label, estimator = FORMULAS[formula]
    pdf = _table(nulls=True)
    conf.set("sml.linear.compactBytes", 0 if compact else 1 << 40)

    def fit(df):
        before = obs.RECORDER.counters()
        model = Pipeline(stages=[
            RFormula(formula=formula, handleInvalid="skip", labelCol="target"),
            estimator(labelCol="target", maxIter=10)]).fit(df)
        after = obs.RECORDER.counters()
        return model, {k: after[k] - before.get(k, 0) for k in (
            "featurize.plan.fits", "featurize.plan.pieces",
            "featurize.collect.concats") if after.get(k, 0) != before.get(k, 0)}

    df = spark.createDataFrame(pdf, numPartitions=8)
    model, moved = fit(df)
    assert moved == {"featurize.plan.fits": 1, "featurize.plan.pieces": 8}
    assert df._pdf_cache is None
    want, moved = fit(spark.createDataFrame(pdf, numPartitions=1))
    assert moved == {"featurize.plan.fits": 1, "featurize.plan.pieces": 1,
                     "featurize.collect.concats": 1}
    assert model.stages[0].stages[0].labelsArray \
        == want.stages[0].stages[0].labelsArray
    np.testing.assert_array_equal(model.stages[-1].coefficients.toArray(),
                                  want.stages[-1].coefficients.toArray())
    assert model.stages[-1].intercept == want.stages[-1].intercept
