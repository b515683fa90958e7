"""Each `correct` comparison passes on what the engine produces at a tiny
size and FAILS when the compared values are computed in a lower precision
first: the control of each limit, kept where a test run can hold it."""

import numpy as np
import pytest

import bench_tiny
from benchmark.harness import program, runner, spec
from benchmark.reference import (bootstrap, featurize, fitcheck, forest,
                                 precision)


@pytest.fixture(scope="module")
def fitted(tmp_path_factory):
    """name -> (config, tables, training rows, their bins and labels,
    holdout rows, the engine's predictions for them)."""
    root, bench = bench_tiny.make_tiny_root(tmp_path_factory.mktemp("ref"))
    out = {}
    for name in ("tiny_xgb", "tiny_rf"):
        cfg = spec.load_json(f"{root}/benchmark/configs/{name}.json")
        program.configure(cfg["conf"])
        parts = spec.resolve(root, bench, name + ".tiny_fit")
        rows = runner.load_module(parts["data_path"], "d").make(cfg["data"], 31)
        table = program.make_table(rows)
        train, rest = program.split(table, [0.8, 0.2], 31)
        frame = program.with_label(cfg, train)
        model = program.build_pipeline(cfg).fit(frame)
        tables = program.model_tables(model)
        raw = frame.toPandas()
        y = raw[cfg["label"]["fit_column"]].to_numpy(dtype=np.float64)
        bins = featurize.bins(raw, tables, cfg["fit_math"]["missing"])
        held = program.with_label(cfg, rest)
        from sml_tpu.ml.linalg import to_matrix
        blocks = to_matrix(model.transform(held).select("features")
                           .toPandas()["features"])
        out[name] = dict(cfg=cfg, tables=tables, bins=bins, y=y,
                         held=held.toPandas(),
                         served=program.predictions(model, held),
                         blocks=np.asarray(blocks, dtype=np.float32))
    return out


def _stats(f, precision=None, n_trees=3, leaf_only_trees=0):
    math = f["cfg"]["fit_math"]
    weights, mask = bootstrap.streams(math, *f["bins"].shape)
    return fitcheck.fit_statistics(
        f["bins"], f["y"], f["tables"], math, 5, tree_weights=weights,
        feature_mask=mask, n_trees=n_trees, nodes_per_tree=7,
        leaves_per_tree=8, leaf_only_trees=leaf_only_trees,
        precision=precision)


def test_leaf_only_trees_add_leaves_and_no_nodes(fitted):
    f = fitted["tiny_xgb"]
    few, more = _stats(f, n_trees=2), _stats(f, n_trees=2, leaf_only_trees=2)
    assert more["nodes"] == few["nodes"]
    assert more["split_gain_gaps"] == few["split_gain_gaps"]
    assert more["leaves"] == 2 * few["leaves"]
    assert more["leaf_value_err_median"] <= 1e-4
    assert _stats(f, "fp8_e4m3", 2, 2)["leaf_value_err_median"] > \
        f["cfg"]["correct"]["leaf_value_err_max"]


@pytest.mark.parametrize("name", ["tiny_xgb", "tiny_rf"])
def test_descent_from_raw_rows_equals_the_engines_predictions(fitted, name):
    f = fitted[name]
    X = featurize.featurize(f["held"], f["tables"])
    assert np.array_equal(X, f["blocks"]), "features differ from the engine's"
    bins = forest.bin_features(X, f["tables"]["edges"],
                               f["tables"]["cat_rank"],
                               f["cfg"]["fit_math"]["missing"])
    want = forest.predict(bins, f["tables"])
    limit = f["cfg"]["correct"]["score_rtol"]
    assert forest.worst_relative_gap(f["served"], want) <= limit
    # the control: the same descent in bfloat16 is far outside the limit
    low = forest.predict(bins, f["tables"], "bfloat16")
    assert forest.worst_relative_gap(low, want) > 100 * limit
    # and an answer altered where it is produced is caught
    wrong = f["served"].copy()
    wrong[len(wrong) // 2] *= 1.001
    assert forest.worst_relative_gap(wrong, want) > limit
    assert forest.worst_relative_gap(f["served"][:-1], want) == float("inf")


@pytest.mark.parametrize("name", ["tiny_xgb", "tiny_rf"])
def test_fit_statistics_pass_on_the_engine_and_fail_in_fp8(fitted, name):
    f = fitted[name]
    sound = _stats(f)
    assert sound["nodes"] >= 10 and sound["leaves"] >= 10
    # the engine's float32 histograms on the CPU: float32 rounding only
    assert sound["split_gain_gap_median"] <= 1e-6
    assert sound["leaf_value_err_median"] <= 1e-4
    assert sound["cover_gap_max"] == 0.0
    limits = f["cfg"]["correct"]
    assert sound["split_gain_gap_median"] <= limits["split_gain_gap_max"]
    assert sound["leaf_value_err_median"] <= limits["leaf_value_err_max"]
    # the control: operands rounded to fp8 before the histograms
    low = _stats(f, "fp8_e4m3")
    assert low["leaf_value_err_median"] > limits["leaf_value_err_max"]
    assert low["leaf_value_err_median"] > 100 * sound["leaf_value_err_median"]
    assert low["split_gain_gap_median"] >= sound["split_gain_gap_median"]


@pytest.mark.parametrize("name", ["tiny_xgb", "tiny_rf"])
def test_a_fit_that_returns_wrong_leaves_or_splits_is_caught(fitted, name):
    f = fitted[name]
    limits = f["cfg"]["correct"]
    broken = dict(f["tables"])
    broken["leaf_value"] = f["tables"]["leaf_value"] * np.float32(1.05)
    bad = _stats(dict(f, tables=broken))
    assert bad["leaf_value_err_median"] > limits["leaf_value_err_max"]
    moved = dict(f["tables"])
    moved["split_bin"] = np.where(f["tables"]["split_feature"] >= 0,
                                  (f["tables"]["split_bin"] + 7) % 39,
                                  f["tables"]["split_bin"])
    worse = _stats(dict(f, tables=moved))
    assert worse["split_gain_gap_median"] > limits["split_gain_gap_max"]


def test_bootstrap_streams_are_the_forests(fitted):
    """With the reproduced Poisson weights every sampled node's hessian
    mass equals the fitted cover EXACTLY; with other weights it does not."""
    f = fitted["tiny_rf"]
    assert _stats(f)["cover_gap_max"] == 0.0
    math = dict(f["cfg"]["fit_math"], seed=43)
    other = dict(f, cfg=dict(f["cfg"], fit_math=math))
    assert _stats(other)["cover_gap_max"] > 0.0


@pytest.mark.parametrize("kind,rel", [("bfloat16", 2.0 ** -8),
                                      ("fp8_e4m3", 2.0 ** -4)])
def test_round_to_keeps_that_many_bits(kind, rel):
    x = np.random.default_rng(0).normal(0, 3, 10000)
    r = precision.round_to(x, kind)
    err = np.abs(r - x) / np.abs(x)
    assert err.max() <= rel * (1 + 1e-9)
    assert err.max() > rel / 4
    assert np.array_equal(precision.round_to(r, kind), r)


def test_binning_rules():
    edges = np.array([[1.0, 2.0, np.inf], [np.inf] * 3], dtype=np.float32)
    rank = {1: np.array([2, 0, 1])}
    X = np.array([[0.5, 0], [1.0, 1], [1.5, 2], [2.0, 9], [7.0, 0],
                  [np.nan, 1]], dtype=np.float32)
    bins = forest.bin_features(X, edges, rank)
    assert bins[:, 0].tolist() == [0, 0, 1, 1, 2, 0]   # upper-inclusive, nan->0
    assert bins[:, 1].tolist() == [2, 0, 1, 1, 2, 0]   # ranks, clipped
    # a value the estimator treats as missing is absent: bin 0, whatever
    # the edges say; a categorical slot keeps its rank
    absent = forest.bin_features(X, edges, rank, missing=1.5)
    assert absent[:, 0].tolist() == [0, 0, 0, 1, 2, 0]
    assert absent[:, 1].tolist() == bins[:, 1].tolist()


def test_padded_rows_grid():
    assert [bootstrap.padded_rows(n) for n in (1, 15, 17, 800_185)] == \
        [1, 15, 18, 851_968]
