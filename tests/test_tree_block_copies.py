"""After the column plan has written the feature block, no step of a tree fit
copies or writes it again: `_extract` gathers only where a label is not
finite, and a boosted fit's `missing` is a compare the quantizer's jobs (and
the drift baseline's sample) make as they read. The bins, the `Binning`, the
trees and the baseline are those of a fit on a copy with NaN written in."""

import json
import warnings

import numpy as np
import pandas as pd
import pytest

from sml_tpu import obs
from sml_tpu.conf import GLOBAL_CONF
from sml_tpu.ml import Pipeline, _staging, _tree_models, tree_impl
from sml_tpu.ml.classification import (DecisionTreeClassifier, GBTClassifier,
                                       RandomForestClassifier)
from sml_tpu.ml.feature import Imputer, StringIndexer, VectorAssembler
from sml_tpu.ml.regression import (DecisionTreeRegressor, GBTRegressor,
                                   RandomForestRegressor)
from sml_tpu.native import binning as native_binning
from sml_tpu.xgboost import XgboostClassifier, XgboostRegressor

MISSING = [0.0, -999.0, float("inf"), float("nan"), None]


@pytest.fixture()
def recorder():
    GLOBAL_CONF.set("sml.obs.enabled", True)
    obs.reset()
    try:
        yield obs.RECORDER
    finally:
        GLOBAL_CONF.set("sml.obs.enabled", False)
        obs.reset()


@pytest.fixture(params=["native", "numpy"])
def kernel(request, monkeypatch):
    if request.param == "numpy":
        monkeypatch.setattr(native_binning, "_lib", lambda: None)
    elif native_binning._lib() is None:
        pytest.skip("no compiler built native/binning.cc here")
    return request.param


def _with_nan(X, missing):
    """What `_fit_ensemble` made until PR 47: a copy, NaN where X equals
    `missing`."""
    X = X.copy()
    if missing is not None and not np.isnan(missing):
        X[X == missing] = np.nan
    return X


def _block(n, dtype, seed=0):
    """Continuous slots that hold every value of `MISSING`, `-0.0`, NaN and
    both infinities, and two categorical slots (4 and 5) that hold 0, NaN,
    +inf and a negative."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 6)).astype(dtype)
    X[:, 0] = np.round(X[:, 0] * 2)            # a fifth of it 0.0
    X[:, 4] = rng.integers(0, 7, size=n)
    X[:, 5] = rng.integers(0, 3, size=n)
    for col, value in [(0, -0.0), (1, np.nan), (1, 0.0), (2, np.inf),
                       (2, -np.inf), (3, -999.0), (3, -0.0), (4, np.nan),
                       (4, -999.0), (5, np.inf)]:
        X[rng.integers(0, n, size=max(n // 100, 3)), col] = value
    y = (X[:, 5] + rng.normal(size=n)).astype(np.float32)
    return X, y


def _assert_same_bins(got, want):
    (binned, binning), (ref, ref_binning) = got, want
    assert binned.dtype == ref.dtype and binned.tobytes() == ref.tobytes()
    assert binning.edges.dtype == ref_binning.edges.dtype
    assert binning.edges.tobytes() == ref_binning.edges.tobytes()
    assert sorted(binning.cat_remap) == sorted(ref_binning.cat_remap)
    for slot, rank in ref_binning.cat_remap.items():
        assert binning.cat_remap[slot].dtype == rank.dtype
        np.testing.assert_array_equal(binning.cat_remap[slot], rank)


# ------------------------------------------------- (a) the quantizer's compare
@pytest.mark.parametrize("rows", [3000, 70_001], ids=["inline", "pool"])
@pytest.mark.parametrize("dtype", [np.float32, np.float64],
                         ids=["float32", "float64"])
@pytest.mark.parametrize("missing", MISSING, ids=[str(m) for m in MISSING])
def test_make_bins_with_missing_is_make_bins_of_the_nan_copy(
        kernel, missing, dtype, rows):
    X, y = _block(rows, dtype)
    before = X.tobytes()
    categorical = {4: 7, 5: 3}
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)  # NaN -> int64 casts
        got = tree_impl.make_bins(X, y, 32, categorical, missing=missing)
        want = tree_impl.make_bins(_with_nan(X, missing), y, 32, categorical)
    _assert_same_bins(got, want)
    assert X.tobytes() == before, "the block was written"


@pytest.mark.parametrize("missing", [0.0, -999.0])
def test_a_block_of_one_column_is_not_written(kernel, missing):
    """`X[:, 0]` of an (n, 1) block is contiguous as it lies: the column
    job's copy has to be its own all the same."""
    X, y = _block(5000, np.float32)
    X = np.ascontiguousarray(X[:, 3:4])
    before = X.tobytes()
    got = tree_impl.make_bins(X, y, 16, missing=missing)
    assert X.tobytes() == before
    _assert_same_bins(got, tree_impl.make_bins(_with_nan(X, missing), y, 16))


def test_the_other_callers_pass_no_missing_and_bin_what_they_binned(kernel):
    """`bin_with` and `_bin_columns` without `missing`: a zero is a zero."""
    X, y = _block(4000, np.float32, seed=3)
    binned, binning = tree_impl.make_bins(X, y, 32, {4: 7, 5: 3})
    edge_list, out_dtype = tree_impl.binning_edges_and_dtype(binning)
    again = tree_impl._bin_columns(X, edge_list, binning.cat_remap, out_dtype)
    assert again.tobytes() == binned.tobytes()
    zero = tree_impl._bin_columns(X, edge_list, binning.cat_remap, out_dtype,
                                  0.0)
    assert (zero[X[:, 0] == 0.0, 0] == 0).all()
    assert (binned[X[:, 0] == 0.0, 0] > 0).any()


# ------------------------------------------------------------ (b) `_extract`
def _frame(spark, seed, n=3000, bad_labels=()):
    rng = np.random.default_rng(np.random.SeedSequence([seed, 47]))
    pdf = pd.DataFrame({"a": np.round(rng.normal(size=n)),   # zeros
                        "b": rng.normal(size=n),
                        "c": rng.choice(["x", "y", "z"], n)})
    pdf.loc[::7, "b"] = np.nan
    pdf["price"] = pdf["a"] * 2 + rng.normal(size=n)
    pdf["label"] = (pdf["price"] > 0).astype(float)
    for row, value in bad_labels:
        pdf.loc[row, ["price", "label"]] = value
    df = spark.createDataFrame(pdf)
    df.cache()
    df.count()
    return df


def _pipeline(estimator):
    return Pipeline(stages=[
        Imputer(strategy="median", inputCols=["a", "b"],
                outputCols=["a_i", "b_i"]),
        StringIndexer(inputCols=["c"], outputCols=["c_i"],
                      handleInvalid="skip"),
        VectorAssembler(inputCols=["a_i", "b_i", "c_i"],
                        outputCol="features"),
        estimator])


def _boosted(**kw):
    return XgboostRegressor(n_estimators=3, max_depth=2, max_bins=8,
                            labelCol="price", **kw)


@pytest.fixture()
def blocks(monkeypatch):
    """What `extract_xy` handed a tree estimator's `_extract`, and what
    `_extract` handed on: (X, y) each, of the last fit, and the `_featurized`
    memo of the frame the estimator was given."""
    seen = {}
    extract_xy = _tree_models.extract_xy
    extract = _tree_models._TreeEstimatorBase._extract

    def spy_xy(*args, **kw):
        X, y, w = extract_xy(*args, **kw)
        seen["given"] = (X, y)
        return X, y, w

    def spy(self, df):
        X, y, cat = extract(self, df)
        seen["handed"] = (X, y)
        seen["bytes"] = X.tobytes()
        seen["memo"] = getattr(df, "_featurized", None)
        return X, y, cat

    monkeypatch.setattr(_tree_models, "extract_xy", spy_xy)
    monkeypatch.setattr(_tree_models._TreeEstimatorBase, "_extract", spy)
    return seen


def test_extract_hands_on_the_plans_block_where_every_label_is_finite(
        spark, recorder, blocks):
    df = _frame(spark, seed=1)
    obs.reset()
    _pipeline(_boosted(missing=0.0)).fit(df)
    (X, y), (X_out, y_out) = blocks["given"], blocks["handed"]
    assert X_out is X and np.shares_memory(X_out, X)
    assert y_out is y and y.dtype == np.float32
    assert blocks["memo"]["features"][0] is X, "the plan's own block"
    # and the quantizer's key takes it as it lies: `_normalize` copies nothing
    assert X.dtype == np.float32 and X.flags.c_contiguous
    assert _staging._normalize(X) is X
    counters = recorder.counters()
    assert counters["featurize.extract.whole"] == 1.0
    assert "featurize.extract.gathered" not in counters
    assert counters["featurize.plan.fits"] == 1.0


@pytest.mark.parametrize("bad", [float("nan"), float("inf"), float("-inf")],
                         ids=["nan", "inf", "-inf"])
def test_extract_gathers_the_rows_whose_label_is_finite(
        spark, recorder, blocks, bad):
    df = _frame(spark, seed=2, bad_labels=[(5, bad), (2999, bad)])
    obs.reset()
    _pipeline(_boosted()).fit(df)
    (X, y), (X_out, y_out) = blocks["given"], blocks["handed"]
    ok = np.isfinite(y)
    assert int((~ok).sum()) == 2
    assert not np.shares_memory(X_out, X)
    assert X_out.tobytes() == X[ok].tobytes()      # the rows as before
    assert y_out.tobytes() == y[ok].tobytes()
    counters = recorder.counters()
    assert counters["featurize.extract.gathered"] == 1.0
    assert "featurize.extract.whole" not in counters
    spans = [e for e in recorder.events()
             if e.kind == "span" and e.name == "fit.featurize"]
    assert X_out.shape[0] in {e.args.get("rows") for e in spans}


# --------------------------------------------- (c) the fit, end to end
def _same_spec(spec, ref):
    assert len(spec.trees) == len(ref.trees)
    for tree, ref_tree in zip(spec.trees, ref.trees):
        for got, want in zip(tree, ref_tree):
            assert got.dtype == want.dtype
            assert got.tobytes() == want.tobytes()
    assert spec.binning.edges.tobytes() == ref.binning.edges.tobytes()
    assert sorted(spec.binning.cat_remap) == sorted(ref.binning.cat_remap)
    for slot, rank in ref.binning.cat_remap.items():
        np.testing.assert_array_equal(spec.binning.cat_remap[slot], rank)
    assert spec.base == ref.base
    assert spec.baseline is not None
    assert json.dumps(spec.baseline.to_dict(), sort_keys=True) == \
        json.dumps(ref.baseline.to_dict(), sort_keys=True)


@pytest.mark.parametrize("estimator", [XgboostRegressor, XgboostClassifier])
def test_a_boosted_fit_leaves_its_block_unwritten_and_fits_the_same_model(
        spark, recorder, blocks, monkeypatch, estimator):
    label = "price" if estimator is XgboostRegressor else "label"
    kw = dict(n_estimators=3, max_depth=3, max_bins=16, labelCol=label)
    df = _frame(spark, seed=3 if estimator is XgboostRegressor else 4)
    model = _pipeline(estimator(missing=0.0, **kw)).fit(df)
    X, _ = blocks["handed"]
    assert (X == 0.0).any() and not np.isnan(X).any()
    assert X.tobytes() == blocks["bytes"], "the fit wrote the block"
    assert blocks["memo"]["features"][0] is X, "the frame's memo holds it"

    # the same fit on the block with the NaNs put in by hand, no `missing`
    extract = _tree_models._TreeEstimatorBase._extract

    def by_hand(self, df):
        X, y, cat = extract(self, df)
        return _with_nan(X, 0.0), y, cat

    monkeypatch.setattr(_tree_models._TreeEstimatorBase, "_extract", by_hand)
    ref = _pipeline(estimator(**kw)).fit(df)
    _same_spec(model.stages[-1]._spec, ref.stages[-1]._spec)
    assert any(np.isinf(t.leaf_value).sum() == 0 and (t.gain > 0).any()
               for t in model.stages[-1]._spec.trees), "the trees split"


def test_the_baselines_sample_reads_missing_as_nan_and_leaves_x(recorder):
    from sml_tpu.obs import drift
    X, y = _block(40_000, np.float32, seed=5)
    before = X.tobytes()
    cap = GLOBAL_CONF.getInt("sml.obs.driftBaselineRows")
    assert len(X) > cap, "the sample is strided"
    got = drift.capture_fit_baseline(X, y, {4: 7}, None, missing=0.0)
    want = drift.capture_fit_baseline(_with_nan(X, 0.0), y, {4: 7}, None)
    plain = drift.capture_fit_baseline(X, y, {4: 7}, None)
    assert X.tobytes() == before
    as_json = [json.dumps(b.to_dict(), sort_keys=True)
               for b in (got, want, plain)]
    assert as_json[0] == as_json[1] != as_json[2]


# ------------------------------------------------------ (d) the bins cache
def test_one_block_under_two_missing_values_is_two_cache_entries():
    X, y = _block(4000, np.float32, seed=6)
    held = set(_tree_models._bins_cache)
    zero = _tree_models._cached_bins(X, y, 16, {4: 7}, 0.0)
    nines = _tree_models._cached_bins(X, y, 16, {4: 7}, -999.0)
    assert len(set(_tree_models._bins_cache) - held) == 2
    assert zero[0].tobytes() != nines[0].tobytes()
    _assert_same_bins(zero, tree_impl.make_bins(_with_nan(X, 0.0), y, 16,
                                                {4: 7}))
    _assert_same_bins(nines, tree_impl.make_bins(_with_nan(X, -999.0), y, 16,
                                                 {4: 7}))
    # a hit under each, and -0.0 is 0.0 (they compare equal to the same)
    assert _tree_models._cached_bins(X, y, 16, {4: 7}, 0.0) is zero
    assert _tree_models._cached_bins(X, y, 16, {4: 7}, -0.0) is zero
    assert _tree_models._cached_bins(X, y, 16, {4: 7}, -999.0) is nines
    # no `missing`, None and NaN are ONE more entry
    none = _tree_models._cached_bins(X, y, 16, {4: 7})
    assert _tree_models._cached_bins(X, y, 16, {4: 7}, float("nan")) is none
    assert _tree_models._cached_bins(X, y, 16, {4: 7}, None) is none
    assert len(set(_tree_models._bins_cache) - held) == 3
    assert none[0].tobytes() != zero[0].tobytes()


# ------------------------------------------------------------ (e) the spans
ESTIMATORS = {
    "dt": lambda: DecisionTreeRegressor(labelCol="price", maxBins=8,
                                        maxDepth=2),
    "rf": lambda: RandomForestRegressor(labelCol="price", maxBins=8,
                                        maxDepth=2, numTrees=2, seed=1),
    "gbt": lambda: GBTRegressor(labelCol="price", maxBins=8, maxDepth=2,
                                maxIter=2),
    "xgb": lambda: _boosted(missing=0.0),
    "dt_cls": lambda: DecisionTreeClassifier(labelCol="label", maxBins=8,
                                             maxDepth=2),
    "rf_cls": lambda: RandomForestClassifier(labelCol="label", maxBins=8,
                                             maxDepth=2, numTrees=2, seed=1),
    "gbt_cls": lambda: GBTClassifier(labelCol="label", maxBins=8, maxDepth=2,
                                     maxIter=2),
    "xgb_cls": lambda: XgboostClassifier(
        n_estimators=2, max_depth=2, max_bins=8, labelCol="label",
        missing=0.0),
}


@pytest.mark.parametrize("kind", list(ESTIMATORS))
def test_every_tree_fit_has_its_extract_span_and_none_copies_for_missing(
        spark, recorder, kind):
    df = _frame(spark, seed=10 + list(ESTIMATORS).index(kind))
    obs.reset()
    _pipeline(ESTIMATORS[kind]()).fit(df)
    names = [e.name for e in recorder.events() if e.kind == "span"]
    assert names.count("fit.featurize.extract") == 1
    assert "fit.featurize.missing" not in names
    totals = recorder.counters()
    assert totals["span_n.fit.featurize.extract"] == 1.0
    assert totals["span_s.fit.featurize.extract"] > 0.0
    assert "span_s.fit.featurize.missing" not in totals
    assert totals["featurize.extract.whole"] == 1.0
    # the plan's span and `_extract`'s: no third `fit.featurize`
    assert names.count("fit.featurize") == 2
