"""`XgboostClassifier` at its own defaults against the plain reference
(ISSUE 50): 256 bins (`max_bins` left alone), depth 8, 28 columns, the
logistic loss, on seeded rows of the Higgs-shaped generator, through
`Pipeline.fit` on the CPU at a few thousand rows.

`benchmark/reference/boost_logistic.py` replays the margin in float64 from
the fitted tables and holds the fit to the log loss's gradients: the chosen
splits' gains, the leaves' Newton steps, the nodes' hessian mass, and the
served probabilities (the logistic function of the replayed margin). Its two
controls must NOT pass: operands rounded to fp8, and the squared loss's
gradients in the reference's place. A column with more than 256 distinct
values uses all 256 bins, the last one, 255, among them."""

import os

import numpy as np
import pytest

from benchmark.harness import runner, spec
from benchmark.reference import boost_logistic, featurize

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = spec.load_json(os.path.join(REPO, "benchmark/configs/xgb_higgs.json"))
ROWS, ROUNDS = 6000, 6
STATISTICS = {"split_gain_gap_median": "split_gain_gap_max",
              "leaf_value_err_median": "leaf_value_err_max",
              "hessian_mass_gap_median": "hessian_mass_gap_max"}


@pytest.fixture(scope="module")
def kind():
    return runner.load_module(
        os.path.join(REPO, "benchmark/kinds/fit_boost_logistic.py"),
        "bench_kind_fit_boost_logistic")


@pytest.fixture(scope="module")
def fitted(spark, kind):
    """(model, tables, the training rows' bins, their labels, the raw
    rows) of the configuration's pipeline at `ROUNDS` rounds."""
    from benchmark.harness import program
    higgs = runner.load_module(
        os.path.join(REPO, "benchmark/data/higgs.py"), "bench_data_higgs")
    raw = higgs.make({"rows": ROWS}, 20260504)
    stages = [dict(s) for s in CONFIG["pipeline"]]
    stages[-1] = dict(stages[-1], params=dict(stages[-1]["params"],
                                              n_estimators=ROUNDS))
    assert "max_bins" not in stages[-1]["params"], "the default is the point"
    model = program.build_pipeline(dict(CONFIG, pipeline=stages)).fit(
        spark.createDataFrame(raw))
    tables = kind.Program.model_tables(model)
    return model, tables, featurize.bins(raw, tables), \
        raw["label"].to_numpy(np.float64), raw


@pytest.fixture(scope="module")
def replayed(fitted):
    _, tables, bins, y, _ = fitted
    return boost_logistic.fit_statistics(
        bins, y, tables, CONFIG["fit_math"], seed=7, n_trees=3,
        nodes_per_tree=10, leaves_per_tree=24, leaf_only_trees=2)


def test_the_default_is_256_bins_and_the_fit_used_them(fitted):
    from sml_tpu.xgboost import XgboostClassifier
    assert XgboostClassifier().getOrDefault("max_bins") == 256
    model, tables, bins, _, raw = fitted
    assert tables["edges"].shape == (28, 255)
    assert tables["depth"] == 8 and tables["split_feature"].shape == (
        ROUNDS, 2 ** 9 - 1)
    assert (tables["split_feature"] >= 0).sum(axis=1).min() > 20, \
        "every round grew a tree"


@pytest.mark.parametrize("column", ["lepton_pT", "m_bb", "jet2_phi"])
def test_a_column_of_many_values_uses_bin_255(fitted, column):
    """A continuous column (6,000 distinct values) has 255 finite cuts and
    its rows fall in every bin, the last one uint8 holds among them."""
    _, tables, bins, _, raw = fitted
    f = [c for _, c in tables["columns"]].index(column)
    assert len(np.unique(raw[column])) > 256
    assert np.isfinite(tables["edges"][f]).sum() == 255
    assert bins[:, f].max() == 255
    assert len(np.unique(bins[:, f])) == 256


def test_a_column_of_three_values_takes_three_bins(fitted):
    _, tables, bins, _, _ = fitted
    f = [c for _, c in tables["columns"]].index("jet1_b_tag")
    assert len(np.unique(bins[:, f])) == 3


@pytest.mark.parametrize("statistic", list(STATISTICS))
def test_the_fit_agrees_with_the_float64_replay(replayed, statistic):
    """On this platform the histogram operands are float32: the fit is the
    reference's to float32's rounding, far inside the chip's limits."""
    assert replayed["nodes"] == 30 and replayed["leaves"] >= 100
    assert replayed[statistic] <= CONFIG["correct"][STATISTICS[statistic]]
    assert replayed[statistic] < 1e-5


def test_served_probabilities_are_the_sigmoid_of_the_replayed_margin(
        fitted, replayed, kind, spark):
    model, tables, bins, y, raw = fitted
    served = kind.Program.probabilities(model, spark.createDataFrame(raw))
    want = 1.0 / (1.0 + np.exp(-replayed["margin"]))
    np.testing.assert_allclose(served, want, rtol=0, atol=2e-6)
    np.testing.assert_allclose(
        served, boost_logistic.probabilities(bins, tables), rtol=1e-5)
    # and the fit descends: the log loss is under the base rate's
    assert boost_logistic.log_loss(served, y) < 0.95 * boost_logistic.log_loss(
        np.full(len(y), y.mean()), y)
    assert boost_logistic.auroc(served, y) > 0.75


@pytest.mark.parametrize("control,fails", [
    ({"precision": "fp8_e4m3"}, {"leaf_value_err_median",
                                 "hessian_mass_gap_median"}),
    ({"gradients": "squared"}, {"leaf_value_err_median",
                                "hessian_mass_gap_median"}),
], ids=["fp8", "squared-loss"])
def test_a_control_is_not_within_the_limits(fitted, control, fails):
    """What the reference gives in fp8, and what `fitcheck`'s gradients
    give in its place, is outside the cell's limits by the lines named."""
    _, tables, bins, y, _ = fitted
    got = boost_logistic.fit_statistics(
        bins, y, tables, CONFIG["fit_math"], seed=7, n_trees=3,
        nodes_per_tree=10, leaves_per_tree=24, leaf_only_trees=2, **control)
    outside = {name for name, limit in STATISTICS.items()
               if not got[name] <= CONFIG["correct"][limit]}
    assert fails <= outside, (got, outside)


# ------------------------------------------------- the reference's own parts
def test_the_logistic_gradients_are_the_log_losss_derivatives():
    rng = np.random.default_rng(3)
    margin, y = rng.normal(0, 2, 200), (rng.random(200) < 0.5) * 1.0

    def loss(m):
        return np.log1p(np.exp(-m)) * y + np.log1p(np.exp(m)) * (1 - y)
    g, h = boost_logistic.gradients_of("logistic", margin, y)
    eps = 1e-4
    np.testing.assert_allclose(
        g, (loss(margin + eps) - loss(margin - eps)) / (2 * eps), atol=1e-7)
    np.testing.assert_allclose(
        h, (loss(margin + eps) - 2 * loss(margin) + loss(margin - eps))
        / eps ** 2, atol=1e-5)
    far = boost_logistic.gradients_of("logistic", np.array([40.0]),
                                      np.array([1.0]))
    assert far[1][0] == boost_logistic.HESSIAN_FLOOR
    g2, h2 = boost_logistic.gradients_of("squared", margin, y)
    np.testing.assert_array_equal(g2, margin - y)
    assert (h2 == 1).all()
    with pytest.raises(ValueError):
        boost_logistic.gradients_of("hinge", margin, y)


@pytest.mark.parametrize("ties", [False, True])
def test_auroc_is_the_share_of_ordered_pairs(ties):
    rng = np.random.default_rng(11)
    score = rng.normal(size=300) + 0.8 * (np.arange(300) % 2)
    if ties:
        score = np.round(score, 1)
    y = (np.arange(300) % 2).astype(float)
    pos, neg = score[y == 1], score[y == 0]
    pairs = (pos[:, None] > neg[None, :]).mean() \
        + 0.5 * (pos[:, None] == neg[None, :]).mean()
    assert boost_logistic.auroc(score, y) == pytest.approx(pairs, abs=1e-12)
    assert np.isnan(boost_logistic.auroc(score, np.ones(300)))
