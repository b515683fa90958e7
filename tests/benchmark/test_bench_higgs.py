"""The boosted classifier's cell (`xgb_higgs.fit_boost_logistic`, kind
`fit_boost_logistic`) on the CPU at a tiny size: a sound run is correct
against the float64 replay of the log loss's gradients; a program that
boosts on another loss's gradients, or quietly takes fewer bins, fails a
line of its own; a program that does not count an operand built by row
blocks is refused before the table is made; the readers read what the
program adds and nothing on a program without it; and BENCHMARK.json holds
the cell and its entries appended to what was there."""

import json
import os
import time
import types

import numpy as np
import pytest

import bench_tiny
from benchmark.harness import runner, spec

REPO = bench_tiny.REPO
BENCH = spec.load_benchmark(REPO)
CELL = "xgb_higgs.fit_boost_logistic"
BEFORE = ["ml11_xgb.fit", "ml07_rf.fit", "ml11_xgb_4chip.fit_sharded",
          "mle03_logreg.fit_logistic", "mle03_logreg_cv.fit_cv",
          "mle01_als.fit_als", "mle02_kmeans.fit_kmeans"]
TINY = "tiny_higgs.tiny_fit_boost_logistic"
METRICS = os.path.join(REPO, "benchmark", "layer_metrics")
NEW = ["tree.operand_bytes_per_fit", "tree.hist_roofline"]
SHARED = ["staging.h2d_bytes_per_fit", "fit.device_busy_s",
          "compile.backend_s", "compile.in_window", "fit.host.featurize_s",
          "fit.host.stage_s", "fit.host.dispatch_s", "fit.host.device_wait_s",
          "fit.host.readback_s", "fit.host.observe_s",
          "fit.host.unattributed_s", "fit.host.stage.key_s",
          "fit.host.stage.pad_s", "fit.host.stage.put_s",
          "fit.host.featurize.cpu_s", "setup.before_program_s",
          "setup.import_s", "setup.table_s", "setup.split_s",
          "setup.warm_fit_s", "setup.first_dispatch_s"]
TREE = ["fit.host.quantize_s", "fit.host.featurize.copies_s",
        "fit.device.operand_s", "fit.device.hist_s", "fit.device.split_s",
        "fit.device.route_s", "fit.device.update_s", "fit.device.unscoped_s",
        "fit.host.featurize.jobs_s", "fit.host.featurize.block_s"]
LINES = ("fit.probabilities_vs_descent.rel_gap_max",
         "fit.split_gain_gap.median", "fit.leaf_value_err.median",
         "fit.hessian_mass_gap.median",
         "fit.holdout_log_loss_vs_base_rate.ratio",
         "fit.holdout_auroc.shortfall", "fit.bins_used.max.shortfall",
         "fit.dispatches_per_fit", "tree.operand.bytes",
         "tree.operand.padding", "tree.operand.blocks.shortfall",
         "fit.featurize.plan.declined", "all.route_device_share_pct",
         "all.compile_requests_in_window")
TINY_ROUNDS = 4


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """`bench_tiny`'s copy with the deployment added at 8,000 rows and 4
    rounds (every width as the cell has it: 28 columns, 256 bins, depth
    8), as new files and entries."""
    root, bench = bench_tiny.make_tiny_root(tmp_path_factory.mktemp("hg"))

    def write(rel, obj):
        path = os.path.join(root, rel)
        assert not os.path.exists(path), f"{rel} would edit an existing file"
        with open(path, "w") as f:
            json.dump(obj, f)

    cfg = spec.load_json(os.path.join(root, "benchmark/configs/xgb_higgs.json"))
    cfg.update(name="tiny_higgs", reduced=["data", "n_estimators"])
    cfg["data"].update(rows=8000)
    cfg["pipeline"][-1]["params"].update(n_estimators=TINY_ROUNDS)
    cfg["correct"].update(sample_rows=1000, fit_sample_trees=2,
                          fit_sample_nodes=6, fit_sample_leaves=12,
                          fit_leaf_only_trees=1,
                          # four rounds at rate 0.1 have barely left the
                          # base rate: the cell's 24 go much further
                          log_loss_ratio_max=0.97, auroc_min=0.65)
    write("benchmark/configs/tiny_higgs.json", cfg)
    traffic = spec.load_json(os.path.join(
        root, "benchmark/traffic/fit_boost_logistic.json"))
    traffic.update(warm_iterations=1)
    write("benchmark/traffic/tiny_fit_boost_logistic.json", traffic)
    bench["configs"].append({
        "name": "tiny_higgs", "source": "test fixture", "why": "tiny",
        "reduced": ["data"], "file": "benchmark/configs/tiny_higgs.json"})
    bench["workloads"].append({
        "name": TINY, "config": "tiny_higgs",
        "traffic": "tiny_fit_boost_logistic", "chips": 1,
        "why": "tiny boosted-classifier cell for the CPU tests"})
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if CELL in m.get("workloads", []):
                m["workloads"].append(TINY)
    assert spec.validate(root, bench) == []
    return root, bench


def drive(tiny, seed, trace=False, stand_in=None):
    root, bench = tiny
    return runner.run(root, TINY, seed, 1.0, trace, time.perf_counter(),
                      require_chip=False, bench=bench, program=stand_in)


def _checks(out):
    return {ln.split()[1].rstrip(":"): ln for ln in out.splitlines()
            if ln.startswith("check ")}


def _observed(line):
    return float(line.split("observed=")[1].split()[0])


def _failed(out):
    return sorted(n for n, ln in _checks(out).items() if ": FAIL" in ln)


@pytest.fixture()
def fresh_programs(monkeypatch):
    """The tree programs traced before forgotten on the way in and out, so
    that a patched estimator compiles its own."""
    from sml_tpu.ml import tree_impl
    monkeypatch.setattr(tree_impl, "_ensemble_cache", {})
    yield monkeypatch


# ------------------------------------------------------------------ sound
@pytest.mark.parametrize("seed", [7, 2**31 + 4321])
def test_a_sound_run_is_correct(tiny, seed, capsys):
    line = drive(tiny, seed)
    out = capsys.readouterr().out
    checks = _checks(out)
    assert line["correct"] is True, out
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["metrics"]) == {"fit_s", "setup_s"}
    for name in LINES:
        assert ": PASS" in checks[name], checks[name]
    assert "built its operand by blocks" in out
    # float32 operands on this platform: the fit IS the replay's
    assert _observed(checks["fit.split_gain_gap.median"]) < 1e-6
    assert _observed(checks["fit.leaf_value_err.median"]) < 1e-5
    assert _observed(checks["fit.hessian_mass_gap.median"]) < 1e-5
    assert _observed(checks["fit.probabilities_vs_descent.rel_gap_max"]) < 1e-5
    assert "256 of 256 bins" in checks["fit.bins_used.max.shortfall"]


def test_a_traced_run_reports_the_counter_fed_layers(tiny):
    line = drive(tiny, seed=2**31 + 611, trace=True)
    assert line["correct"] is True
    # no device plane on the CPU: the trace-fed and span-fed readers find
    # nothing to read and are left out; the new counter's reader reads
    assert set(line["metrics"]) == {
        "staging.h2d_bytes_per_fit", "compile.backend_s", "compile.in_window",
        "tree.operand_bytes_per_fit"}
    stored = line["metrics"]["tree.operand_bytes_per_fit"]["value"]
    # 28 x 256 columns of the float32 one-hot this platform stores, a
    # staged row: a split of 8,000 rows pads to 6,656 on the eight shards
    assert stored % (28 * 256 * 4) == 0
    assert 0.78 * 8000 <= stored / (28 * 256 * 4) <= 1.125 * 0.82 * 8000 + 64
    assert line["metrics"]["compile.in_window"]["value"] == 0.0


# --------------------------------------------------------------- controls
def test_a_fit_on_the_squared_losss_gradients_fails_the_replay(
        tiny, capsys, fresh_programs):
    """The other loss under the classifier's name: `margin - y` and a
    hessian of 1, what `fitcheck` knows and this kind's reference does
    not accept."""
    from sml_tpu.xgboost import XgboostClassifier
    fresh_programs.setattr(XgboostClassifier, "_loss", "squared")
    line = drive(tiny, seed=31)
    out = capsys.readouterr().out
    assert line["correct"] is False
    failed = _failed(out)
    assert "fit.leaf_value_err.median" in failed, out
    assert "fit.hessian_mass_gap.median" in failed, out
    assert _observed(_checks(out)["fit.hessian_mass_gap.median"]) > 0.5


def test_a_fit_that_quietly_takes_fewer_bins_fails_the_bins_line(
        tiny, capsys, fresh_programs):
    from sml_tpu import xgboost
    real = xgboost._fit_ensemble

    def fewer(X, y, **kw):
        return real(X, y, **dict(kw, max_bins=min(kw["max_bins"], 64)))
    fresh_programs.setattr(xgboost, "_fit_ensemble", fewer)
    line = drive(tiny, seed=32)
    out = capsys.readouterr().out
    assert line["correct"] is False
    failed = _failed(out)
    assert "fit.bins_used.max.shortfall" in failed, out
    assert "tree.operand.bytes" in failed, out
    assert "64 of 256 bins" in _checks(out)["fit.bins_used.max.shortfall"]


# ---------------------------------------------------- the probe's refusal
def test_a_program_without_operand_blocks_is_refused_before_the_table(
        tiny, capsys, monkeypatch):
    """The parent's shape: `jax.nn.one_hot` over the whole table, no blocks
    to count. Set-up raises what `runner.main` answers with exit code 2,
    and no table was made."""
    from sml_tpu.utils.profiler import PROFILER
    real = PROFILER.count

    def count(name, value=1):
        if not name.startswith("tree.operand."):
            real(name, value)
    monkeypatch.setattr(PROFILER, "count", count)
    with pytest.raises(spec.SpecError, match="by blocks of rows"):
        drive(tiny, seed=5)
    assert "table made" not in capsys.readouterr().out


def test_the_command_answers_a_refusal_with_exit_code_2(tiny, monkeypatch,
                                                        capsys):
    def refuse(*a, **k):
        raise spec.SpecError("does not build its histogram operand by "
                             "blocks of rows")
    monkeypatch.setattr(runner, "run", refuse)
    assert runner.main(tiny[0], TINY, 1, 1.0, False, time.perf_counter()) == 2
    assert "by blocks" in capsys.readouterr().err


# ------------------------------------------------------------ the readers
def _reader(name):
    return runner.load_module(os.path.join(METRICS, name + ".py"),
                              "bench_metric_" + name.replace(".", "_"))


def _run(**over):
    """A traced run's `Reading` as the readers see it, the device's seconds
    by scope already reduced (`_fit_scopes`' memo)."""
    run = types.SimpleNamespace(
        cell=CELL, trace=types.SimpleNamespace(device_ops=[[1]]),
        facts={"fits": 2, "fit_rows": [800_000, 800_400], "tree_rounds": 24,
               "tree_depth": 8, "tree_columns": 28, "tree_bins": 256},
        device={"kind": "TPU v5 lite"},
        counters_start={"tree.operand.bytes": 1.0e9},
        counters_end={"tree.operand.bytes": 1.0e9 + 2 * 7168 * 851_968},
        _fit_device_seconds_by_scope={"tree.hist": 1.5,
                                      "tree.hist.allreduce": 0.1,
                                      "tree.route": 0.4, "": 0.05})
    run.counter_delta = lambda n: run.counters_end.get(n, 0.0) \
        - run.counters_start.get(n, 0.0)
    for key, value in over.items():
        setattr(run, key, value)
    return run


def test_the_operand_bytes_are_the_counters_over_the_fits():
    assert _reader("tree.operand_bytes_per_fit").read(_run()) == \
        7168 * 851_968


def test_the_roofline_is_the_levels_least_time_over_their_seconds():
    from benchmark.layer_metrics import _tree_work
    assert _tree_work.level_widths(8) == [1, 1, 2, 4, 8, 16, 32, 64]
    assert _tree_work.level_widths(1) == [1]
    operations, nbytes = _tree_work.level_work(1000.0, 28, 256, 64)
    assert operations == 2 * 7168 * 1000 * 192
    assert nbytes == 1000 * 28 + 1000 * 192 * 2 + 7168 * 192 * 4
    # every level of the cell is bound by the MXU in this count: the floor
    # is the operations of 128 node columns over the peak
    rows = 800_200.0
    floor = _tree_work.hist_floor_s(rows, 28, 256, 8, 24, "TPU v5 lite")
    assert floor == pytest.approx(
        24 * 2 * 7168 * rows * 3 * 128 / 197e12, rel=1e-12)
    share = _reader("tree.hist_roofline").read(_run())
    assert share == pytest.approx(100 * floor / 1.6, rel=1e-12)
    assert 0 < share < 100
    with pytest.raises(KeyError):
        _tree_work.peaks("TPU v9")


@pytest.mark.parametrize("name", NEW)
def test_a_new_reader_returns_nothing_on_a_program_without_the_path(name):
    """The parent: no `tree.operand.*` counter, no facts of this kind; and a
    run that was not traced."""
    bare = _run(facts={"fits": 2, "fit_rows": [1, 1]}, counters_start={},
                counters_end={})
    assert _reader(name).read(bare) is None
    assert _reader(name).read(_run(facts={})) is None
    if name == "tree.hist_roofline":
        assert _reader(name).read(_run(trace=None)) is None


# ------------------------------------------- BENCHMARK.json, appended to
def test_the_benchmark_is_valid_and_holds_the_cell():
    assert spec.validate(REPO, BENCH) == []
    names = [w["name"] for w in BENCH["workloads"]]
    assert names[:len(BEFORE)] == BEFORE and names.index(CELL) == len(BEFORE)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    cell = spec.workload(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "xgb_higgs", "fit_boost_logistic", 1)
    assert len(cell["why"]) <= 200
    assert BENCH["run_seconds"] == 51
    assert [c["name"] for c in BENCH["configs"]][7] == "xgb_higgs"
    parts = spec.resolve(REPO, BENCH, CELL)
    assert parts["traffic"]["kind"] == "fit_boost_logistic"
    assert parts["traffic"]["warm_iterations"] == 2
    assert parts["traffic"]["fractions"] == [0.8, 0.2]
    assert set(parts["readers"]) == set(NEW) | set(SHARED) | set(TREE)


def test_the_new_entries_follow_the_accepted_ones_in_their_order():
    names = [m["name"] for m in BENCH["per_layer"]]
    at = names.index(NEW[0])
    assert names[at:at + 2] == NEW and at >= 60
    for entry in BENCH["per_layer"][at:at + 2]:
        assert entry["workloads"][0] == CELL and entry["moves"] == "fit_s"
        assert entry["layer"] == "tree fit programs"
        assert set(entry) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
        assert os.path.isfile(os.path.join(METRICS, entry["name"] + ".py"))
    roofline = BENCH["per_layer"][at + 1]
    assert (roofline["unit"], roofline["better"], roofline["source"]) == (
        "%", "higher", "device_trace")


@pytest.mark.parametrize("name", ["fit_s"] + SHARED + TREE)
def test_an_accepted_list_is_only_appended_to(name):
    entry, = [m for g in ("end_to_end", "per_layer") for m in BENCH[g]
              if m["name"] == name]
    cells = entry["workloads"]
    old = [c for c in cells if c in BEFORE]
    assert cells[:len(old)] == old == [c for c in BEFORE if c in old]
    assert cells[len(old)] == CELL


def test_no_other_accepted_list_gained_the_cell():
    joined = set(NEW) | set(SHARED) | set(TREE) | {"fit_s"}
    for group in ("end_to_end", "per_layer"):
        for m in BENCH[group]:
            if m["name"] not in joined:
                assert CELL not in m.get("workloads", [CELL + "?"]), m["name"]
    bounds = {m["name"]: m["bound"] for m in BENCH["end_to_end"]}
    assert bounds == {"fit_s": 0.05, "setup_s": 0.1}


def test_the_configuration_is_the_papers_with_the_librarys_default_bins():
    entry = spec.config_entry(BENCH, "xgb_higgs")
    assert entry["reduced"] == ["n_estimators"]
    assert "XGBoost" in entry["source"] and "Higgs-1M" in entry["source"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    cfg = spec.load_json(os.path.join(REPO, entry["file"]))
    for key in ("source", "deployment", "data", "label", "pipeline",
                "fit_math", "published", "correct", "assumed", "precision",
                "conf", "reduced"):
        assert key in cfg, key
    assert cfg["name"] == "xgb_higgs" and cfg["reduced"] == ["n_estimators"]
    assert cfg["data"] == {"generator": "higgs", "rows": 1_000_000}
    assembler, estimator = cfg["pipeline"]
    assert len(assembler["params"]["inputCols"]) == 28
    assert estimator["class"] == "XgboostClassifier"
    # the bins are the library's default: stated in fit_math, set nowhere
    assert "max_bins" not in estimator["params"]
    assert cfg["fit_math"]["n_bins"] == 256 == cfg["published"]["max_bin"]
    params, published = estimator["params"], cfg["published"]
    assert params["max_depth"] == published["max_depth"] == 8
    assert params["learning_rate"] == published["learning_rate"] == 0.1
    assert published["n_estimators"] == 500
    assert params["n_estimators"] in (16, 24)
    assert cfg["conf"]["sml.tree.roundsPerDispatch"] == 0
    assert len(cfg["assumed"]) >= 5
    for limit in ("score_rtol", "split_gain_gap_max", "leaf_value_err_max",
                  "hessian_mass_gap_max", "log_loss_ratio_max", "auroc_min",
                  "bins_used_min"):
        assert limit in cfg["correct"], limit
    assert cfg["correct"]["bins_used_min"] >= 250


# ----------------------------------------------------------- the generator
@pytest.fixture(scope="module")
def higgs():
    return runner.load_module(
        os.path.join(REPO, "benchmark/data/higgs.py"), "bench_data_higgs")


def test_the_generator_makes_the_same_table_from_the_same_seed(higgs):
    a = higgs.make({"rows": 5000}, 2**31 + 5)
    b = higgs.make({"rows": 5000}, 2**31 + 5)
    c = higgs.make({"rows": 5000}, 2**31 + 6)
    assert list(a.columns) == higgs.COLUMNS + ["label"] and len(a) == 5000
    assert len(higgs.COLUMNS) == 28 and len(higgs.LOW) == 21
    assert a.equals(b) and not a.equals(c)
    assert not a.isna().any().any()
    assert set(a["label"].unique()) == {0.0, 1.0}


def test_the_generators_columns_are_higgss_kinds(higgs):
    table = higgs.make({"rows": 60000}, 3)
    for column in higgs.COLUMNS:
        distinct = table[column].nunique()
        if column.endswith("b_tag"):
            assert distinct == 3, column
        else:
            assert distinct > 50000, column
    # heavy right tails where HIGGS has them, bounded angles
    assert table["jet1_pt"].max() > 4 * table["jet1_pt"].median()
    assert table["lepton_phi"].abs().max() <= np.pi
    assert table["jet3_eta"].abs().max() <= 2.5
    # the high-level columns are functions of the low-level ones
    again = higgs.make({"rows": 60000}, 3)
    assert (table["m_wwbb"] == again["m_wwbb"]).all()
    assert 0.5 < table["m_bb"].median() < 1.5


def test_the_planted_model_has_higgss_share_and_a_ceiling_to_reach(higgs):
    from benchmark.reference import boost_logistic
    _, p_signal, label = higgs.events(200_000, 9)
    assert 0.51 < label.mean() < 0.55
    assert 0.80 < boost_logistic.auroc(p_signal, label) < 0.85
    # no single column orders the events nearly as well: interactions
    table, _, _ = higgs.events(200_000, 9)
    best = max(abs(boost_logistic.auroc(table[c], label) - 0.5)
               for c in higgs.COLUMNS)
    assert best < 0.15
