"""The linear family's cell (`mle03_logreg.fit_logistic`, kind `fit_logistic`)
on the CPU at a tiny size: a sound run is correct against the float64 Newton
reference, the controls (a fit in the next lower precision, a fit on half the
rows, a featurization with its slots shifted) are NOT, a program that cannot
take the pipeline on the compact device path is refused before the table is
made, the reference meets its closed forms, the new readers read the nested
scopes, and BENCHMARK.json is what PR 28 left with this PR's entries appended
to it."""

import json
import os
import time
import types

import numpy as np
import pandas as pd
import pytest

import bench_tiny
from benchmark.harness import program, runner, spec, xplane
from benchmark.reference import logistic

REPO = bench_tiny.REPO
BENCH = spec.load_benchmark(REPO)
CELL = "mle03_logreg.fit_logistic"
TINY = "tiny_logreg.tiny_fit_logistic"
TINY_ROWS = 20000
METRICS = os.path.join(REPO, "benchmark", "layer_metrics")


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """`bench_tiny`'s copy with the deployment added at 20,000 rows, as new
    files and entries: the configuration (the compact form forced, as the
    cell's size forces it), a traffic mix of kind `fit_logistic`, the
    cell."""
    root, bench = bench_tiny.make_tiny_root(tmp_path_factory.mktemp("logreg"))

    def write(rel, obj):
        path = os.path.join(root, rel)
        assert not os.path.exists(path), f"{rel} would edit an existing file"
        with open(path, "w") as f:
            json.dump(obj, f, indent=1)

    cfg = spec.load_json(os.path.join(
        root, "benchmark/configs/mle03_logreg.json"))
    cfg.update(name="tiny_logreg", reduced=["data"])
    cfg["data"]["rows"] = TINY_ROWS
    cfg["conf"]["sml.linear.compactBytes"] = 0
    cfg["correct"]["sample_rows"] = 500
    write("benchmark/configs/tiny_logreg.json", cfg)
    traffic = spec.load_json(os.path.join(
        root, "benchmark/traffic/fit_logistic.json"))
    traffic.update(warm_iterations=1, fractions=[0.7, 0.3])
    write("benchmark/traffic/tiny_fit_logistic.json", traffic)
    bench["configs"].append({
        "name": "tiny_logreg", "source": "test fixture", "why": "tiny",
        "reduced": ["data"], "file": "benchmark/configs/tiny_logreg.json"})
    bench["workloads"].append({
        "name": TINY, "config": "tiny_logreg",
        "traffic": "tiny_fit_logistic", "chips": 1,
        "why": "tiny logistic cell for the CPU tests"})
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if CELL in m.get("workloads", []):
                m["workloads"].append(TINY)
    assert spec.validate(root, bench) == []
    return root, bench


@pytest.fixture(autouse=True)
def compact_threshold_restored():
    from sml_tpu.conf import GLOBAL_CONF
    yield
    GLOBAL_CONF.unset("sml.linear.compactBytes")


def drive(tiny, seed, stand_in=None, trace=False):
    root, bench = tiny
    return runner.run(root, TINY, seed, 1.0, trace, time.perf_counter(),
                      require_chip=False, bench=bench, program=stand_in)


def stand_in(**replaced):
    shim = types.SimpleNamespace(**{k: getattr(program, k)
                                    for k in dir(program)
                                    if not k.startswith("__")})
    for name, fn in replaced.items():
        setattr(shim, name, fn)
    return shim


def _checks(out):
    return {ln.split()[1].rstrip(":"): ln for ln in out.splitlines()
            if ln.startswith("check ")}


def _observed(line):
    return float(line.split("observed=")[1].split()[0])


LINES = ("fit.indexer_labels.columns_differing",
         "fit.probability_vs_margin.abs_gap_max", "fit.coefficient_err.max",
         "fit.loglik_gap.rel", "fit.gradient_norm.max", "fit.holdout_auc",
         "fit.irls_fits_per_fit", "fit.plan_fits_per_fit",
         "fit.plan_declined", "fit.iterations_per_fit",
         "all.route_device_share_pct", "all.compile_requests_in_window")


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
    assert "the probe fit of 4000 rows took the compact device path" in out
    # float32 on the CPU sits far under the limits the chip's readings set
    assert _observed(checks["fit.coefficient_err.max"]) < 1e-3
    assert 0.7 < _observed(checks["fit.holdout_auc"]) < 0.8
    assert 3 <= _observed(checks["fit.iterations_per_fit"]) <= 10


def test_a_traced_run_reports_the_counter_fed_layers(tiny):
    line = drive(tiny, seed=2**31 + 611, trace=True)
    assert line["correct"] is True
    # no device plane on the CPU: the trace-fed and span-fed readers, the
    # new ones too, find nothing to read and are left out
    assert set(line["metrics"]) == {
        "staging.h2d_bytes_per_fit", "compile.backend_s", "compile.in_window",
        "linear.irls.steps_per_fit", "linear.irls.iterations_per_fit"}
    assert line["metrics"]["linear.irls.steps_per_fit"]["value"] == 100.0
    assert 3 <= line["metrics"]["linear.irls.iterations_per_fit"]["value"] \
        <= 10
    # the compact block of 14,000 rows, padded: 17 float32 and 5 int32 a row
    rows = 14336
    assert line["metrics"]["staging.h2d_bytes_per_fit"]["value"] == \
        pytest.approx(rows * (17 + 5 + 1) * 4, rel=0.02)


# ------------------------------------------------------------ the controls
class _Wrapped:
    def __init__(self, pipeline, fit):
        self._pipeline, self._fit = pipeline, fit

    def fit(self, frame):
        return self._fit(self._pipeline, frame)


def _control(fit):
    return stand_in(build_pipeline=lambda config: _Wrapped(
        program.build_pipeline(config), fit))


def test_a_fit_in_bfloat16_is_not_correct(tiny, capsys):
    """The next lower precision: every operand of a product over the block
    rounded to bfloat16 where the program hands it to the product, as the
    chip's control does (`tools_logistic.bfloat16_products`)."""
    from benchmark import tools_logistic
    with tools_logistic.bfloat16_products():
        line = drive(tiny, seed=2**31 + 701)
    checks = _checks(capsys.readouterr().out)
    assert line["correct"] is False
    assert ": FAIL" in checks["fit.coefficient_err.max"]
    # at 14,000 rows 0.03-0.05 standard errors, where the sound run reads
    # under 1e-3 (the rounding's bias does not shrink with the table and a
    # standard error does: the chip's control reads far more, PERF.md 2)
    assert _observed(checks["fit.coefficient_err.max"]) > 0.03
    assert ": PASS" in checks["fit.irls_fits_per_fit"]   # the path was kept
    # the served margin's operands are rounded too (0.08 at this size)
    assert ": FAIL" in checks["fit.probability_vs_margin.abs_gap_max"]
    assert _observed(checks["fit.probability_vs_margin.abs_gap_max"]) > 1e-3


def test_a_fit_on_half_the_rows_is_not_correct(tiny, capsys):
    line = drive(tiny, seed=2**31 + 702, stand_in=_control(
        lambda pipeline, frame: pipeline.fit(
            frame.limit(frame.count() // 2))))
    checks = _checks(capsys.readouterr().out)
    assert line["correct"] is False
    assert ": FAIL" in checks["fit.coefficient_err.max"]
    assert ": FAIL" in checks["fit.gradient_norm.max"]
    assert ": PASS" in checks["fit.holdout_auc"]        # as good a model


def test_a_featurization_with_its_slots_shifted_is_not_correct(
        tiny, capsys, monkeypatch):
    """The reference's own features moved by one slot within every string
    column: what a wrong label order in the program would look like from
    the reference's side."""
    block = logistic.Compact.block
    monkeypatch.setattr(
        logistic.Compact, "block",
        lambda self, lo, hi, shift=0: block(self, lo, hi, shift + 1))
    line = drive(tiny, seed=2**31 + 703)
    checks = _checks(capsys.readouterr().out)
    assert line["correct"] is False
    assert ": FAIL" in checks["fit.coefficient_err.max"]
    assert ": FAIL" in checks["fit.probability_vs_margin.abs_gap_max"]


def test_a_model_whose_labels_are_ordered_otherwise_is_not_correct(
        tiny, capsys):
    def fit(pipeline, frame):
        model = pipeline.fit(frame)
        indexer = model.stages[0].stages[0]
        indexer.labelsArray[2] = indexer.labelsArray[2][::-1]
        return model

    line = drive(tiny, seed=2**31 + 704, stand_in=_control(fit))
    checks = _checks(capsys.readouterr().out)
    assert line["correct"] is False
    assert ": FAIL observed=1.0" in \
        checks["fit.indexer_labels.columns_differing"]
    assert ": FAIL" in checks["fit.probability_vs_margin.abs_gap_max"]


def test_a_program_without_the_compact_path_is_refused_at_once(tiny, capsys):
    """What the parent commit does with this cell: the plan declines the
    formula and the generic path fits it, so no fused program is counted.
    Refused before the table is made (exit code 2 from `runner.main`)."""
    def counters():
        return {k: v for k, v in program.counters().items()
                if not k.startswith("linear.irls.")}

    with pytest.raises(spec.SpecError, match="compact device path"):
        drive(tiny, seed=1, stand_in=stand_in(counters=counters))
    assert "table made" not in capsys.readouterr().out


def test_a_program_whose_plan_declines_is_refused_at_once(
        tiny, capsys, monkeypatch):
    from sml_tpu.ml import featurizer
    monkeypatch.setattr(
        featurizer, "_try_fast_fit",
        lambda *a, **k: featurizer._decline("a RFormula stage outside the "
                                            "chain"))
    with pytest.raises(spec.SpecError, match="featurize.plan.declined"):
        drive(tiny, seed=1)
    assert "table made" not in capsys.readouterr().out


# ----------------------------------------------------------- the reference
def test_newton_meets_the_closed_form_of_a_two_by_two_table():
    """One binary column, no separation: the optimum is the table's own
    log-odds, and the standard errors the textbook's sqrt(sum of 1/cells)."""
    cells = {("a", 1.0): 30, ("a", 0.0): 70, ("b", 1.0): 45, ("b", 0.0): 15}
    rows = [(g, y) for (g, y), n in cells.items() for _ in range(n)]
    pdf = pd.DataFrame(rows, columns=["group", "label"]).sample(
        frac=1.0, random_state=3).reset_index(drop=True)
    plan = logistic.design(pdf, "label")
    assert plan["strings"] == [("group", ["a", "b"])]
    assert plan["slots"] == ["group=a"]          # the last label dropped
    table = logistic.Compact(pdf, plan)
    fit = logistic.newton(table, pdf["label"].to_numpy())
    odds_a, odds_b = np.log(30 / 70), np.log(45 / 15)
    np.testing.assert_allclose(fit["coefficients"],
                               [odds_a - odds_b, odds_b], atol=1e-9)
    np.testing.assert_allclose(fit["standard_errors"], [
        np.sqrt(1 / 30 + 1 / 70 + 1 / 45 + 1 / 15),
        np.sqrt(1 / 45 + 1 / 15)], rtol=1e-8)
    want = 30 * np.log(.3) + 70 * np.log(.7) + 45 * np.log(.75) \
        + 15 * np.log(.25)
    assert fit["loglik"] == pytest.approx(want, rel=1e-12)
    assert fit["gradient_max"] < 1e-10
    here = logistic.at(table, pdf["label"].to_numpy(), fit["coefficients"],
                       fit)
    assert here["gradient_max"] < 1e-10
    assert here["loglik"] == pytest.approx(want, rel=1e-12)
    off = logistic.at(table, pdf["label"].to_numpy(),
                      fit["coefficients"] + [0.1, 0.0], fit)
    assert off["gradient_max"] > 1e-3 and off["loglik"] < want


def test_the_formula_orders_labels_by_frequency_then_value_and_skips():
    pdf = pd.DataFrame({
        "kind": ["b", "a", "c", "a", "b", "d", None, "c"],
        "x": [1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 7.0, np.nan],
        "label": [0.0, 1.0] * 4})
    plan = logistic.design(pdf, "label")
    # a, b and c twice each: ties by value; d once, last, dropped
    assert plan["strings"] == [("kind", ["a", "b", "c", "d"])]
    assert plan["slots"] == ["kind=a", "kind=b", "kind=c", "x"]
    table = logistic.Compact(pdf, plan)
    assert table.keep.tolist() == [True] * 6 + [False, False]
    np.testing.assert_array_equal(table.block(0, 6), [
        [0, 1, 0, 1], [1, 0, 0, 2], [0, 0, 1, 3],
        [1, 0, 0, 4], [0, 1, 0, 5], [0, 0, 0, 6]])
    unseen = logistic.Compact(pd.DataFrame({
        "kind": ["a", "z"], "x": [1.0, 1.0], "label": [0.0, 0.0]}), plan)
    assert unseen.keep.tolist() == [True, False]


def test_the_generators_true_slopes_are_recovered_within_their_errors():
    data = runner.load_module(os.path.join(
        REPO, "benchmark", "data", "airbnb_superhost.py"), "superhost")
    seed = 2**31 + 99
    pdf = data.make({"rows": 60000}, seed)
    assert "host_is_superhost" not in pdf and not pdf.isna().any().any()
    assert 0.2 < pdf["label"].mean() < 0.35
    listings = runner.load_module(os.path.join(
        REPO, "benchmark", "data", "airbnb.py"), "airbnb").make(
            {"rows": 60000}, seed)
    assert pdf["price"].equals(listings["price"])      # airbnb's draws
    slopes, margin = data.true_model(pdf, seed)
    assert 0.70 < logistic.auc(margin, pdf["label"].to_numpy()) < 0.80
    plan = logistic.design(pdf, "label")
    assert len(plan["slots"]) == 62
    table = logistic.Compact(pdf, plan)
    fit = logistic.newton(table, pdf["label"].to_numpy())
    assert fit["gradient_max"] < 1e-10 and fit["iterations"] <= 10
    at = {name: i for i, name in enumerate(plan["slots"])}
    # the dropped label is each string column's baseline: "t" (the rarer)
    # for instant_bookable, "Shared room" for room_type
    want = {name: slopes[name] for name, _ in data.EFFECTS}
    want["instant_bookable=f"] = -slopes["instant_bookable=t"]
    want["room_type=Entire home/apt"] = slopes["room_type=Entire home/apt"] \
        - slopes["room_type=Shared room"]
    want["room_type=Private room"] = -slopes["room_type=Shared room"]
    z = [(fit["coefficients"][at[n]] - v) / fit["standard_errors"][at[n]]
         for n, v in want.items()]
    assert np.max(np.abs(z)) < 4.0, dict(zip(want, z))
    # and the columns the true model does not read, at zero within theirs
    idle = [fit["coefficients"][i] / fit["standard_errors"][i]
            for n, i in at.items() if n not in want]
    assert np.max(np.abs(idle)) < 4.5


def test_a_rounded_newton_misses_the_optimum_by_whole_standard_errors():
    """The reference's own control: its steps with every operand of a
    product rounded to bfloat16."""
    data = runner.load_module(os.path.join(
        REPO, "benchmark", "data", "airbnb_superhost.py"), "superhost")
    pdf = data.make({"rows": 30000}, 77)
    plan = logistic.design(pdf, "label")
    table, y = logistic.Compact(pdf, plan), pdf["label"].to_numpy()
    best = logistic.newton(table, y)
    lossy = logistic.newton(table, y, precision="bfloat16")
    err = np.abs(lossy["coefficients"] - best["coefficients"]) \
        / best["standard_errors"]
    assert err.max() > 0.05
    same = logistic.newton(table, y, precision="float32")
    err32 = np.abs(same["coefficients"] - best["coefficients"]) \
        / best["standard_errors"]
    assert err32.max() < 1e-3


def test_auc_is_the_share_of_ordered_pairs_with_ties_halved():
    score = np.array([0.1, 0.4, 0.4, 0.8, 0.9])
    y = np.array([0.0, 0.0, 1.0, 1.0, 0.0])
    # pairs (positive, negative): 6; ordered 3, tied 1
    assert logistic.auc(score, y) == pytest.approx((3 + 0.5) / 6)
    assert np.isnan(logistic.auc(score, np.ones(5)))


# ------------------------------------------------------------- the readers
def _reader(name):
    return runner.load_module(os.path.join(METRICS, name + ".py"),
                              "bench_metric_" + name.replace(".", "_"))


def _hlo(name, stack, kind="fusion"):
    meta = f', metadata={{op_name="jit(wrapped)/jit(main)/shard_map/' \
           f'{stack}/dot_general"}}' if stack else ""
    return f"%{name} = f32[8]{{0}} {kind}(f32[8]{{0}} %p){meta}"


def _reading(trace, fits=2, counters=None, kind="TPU v5 lite"):
    counters = counters or {}
    return runner.Reading(
        cell="logistic.no_trace_file", config={}, traffic={}, seconds=1.0,
        facts={"fits": fits, "fit_rows": [1000] * fits, "features": 9},
        counters_start={k: 0.0 for k in counters}, counters_end=counters,
        compiles=None, device={"platform": "tpu", "kind": kind},
        program=None, trace=trace)


def _two_fits():
    """Two fits on one plane. Each: an expansion [100, 300), then the scan
    [300, 900) over a margin [300, 400), a Hessian [400, 700) and a solve
    [700, 800): 100 ns of the scan under no inner scope."""
    inner = "linear.irls/while/body/closed_call/"
    ops = []
    for t in (0.0, 2000.0):
        ops += [(n, a + t, b + t) for n, a, b in [
            (_hlo("fusion.1", "linear.expand"), 100.0, 300.0),
            (_hlo("while.9", "linear.irls", "while"), 300.0, 900.0),
            (_hlo("fusion.2", inner + "linear.irls.margin"), 300.0, 400.0),
            (_hlo("fusion.3", inner + "linear.irls.hess"), 400.0, 700.0),
            (_hlo("custom.4", inner + "linear.irls.solve"), 700.0, 800.0),
            (_hlo("copy.5", None), 950.0, 1000.0)]]
    notes = [("bench.window", 0.0, 4000.0), ("bench.fit", 50.0, 1100.0),
             ("bench.split", 1100.0, 2000.0), ("bench.fit", 2050.0, 3100.0)]
    return xplane.Trace([ops], notes)


def test_the_scopes_nest_and_each_reader_finds_its_own():
    run = _reading(_two_fits())
    read = {n: _reader(n).read(run) for n in (
        "fit.device.expand_s", "fit.device.irls_s", "fit.device.irls.hess_s",
        "fit.device.irls.solve_s")}
    assert read["fit.device.expand_s"] == pytest.approx(200e-9)
    assert read["fit.device.irls_s"] == pytest.approx(600e-9)   # the whole
    assert read["fit.device.irls.hess_s"] == pytest.approx(300e-9)
    assert read["fit.device.irls.solve_s"] == pytest.approx(100e-9)
    busy = _reader("fit.device_busy_s").read(run)
    assert busy == pytest.approx(850e-9)
    assert read["fit.device.expand_s"] + read["fit.device.irls_s"] <= busy


def test_the_roofline_share_is_the_useful_work_over_the_scans_seconds():
    work = runner.load_module(os.path.join(METRICS, "_linear_work.py"), "w")
    assert work.hess_flops(rows=1000, slots=10, iterations=5) == 5 * 2e5
    with pytest.raises(KeyError, match="no peak"):
        work.peak_flops("cpu")
    counters = {"linear.irls.iterations": 10.0, "linear.irls.steps_run": 200.0}
    run = _reading(_two_fits(), counters=counters)
    share = _reader("linear.hess_roofline").read(run)
    # 5 steps a fit x 2 x 1000 rows x 10^2 over 600 ns x 197 TFLOP/s
    assert share == pytest.approx(100.0 * 1e6 / (600e-9 * 197e12))
    assert _reader("linear.irls.steps_per_fit").read(run) == 100.0
    assert _reader("linear.irls.iterations_per_fit").read(run) == 5.0


def test_the_new_readers_return_nothing_where_there_is_nothing_to_read():
    """The parent's program: no scope, no counter, no span."""
    bare = xplane.Trace(
        [[(_hlo("fusion.2", "while/body/closed_call"), 300.0, 600.0)]],
        [("bench.window", 0.0, 4000.0), ("bench.fit", 100.0, 1100.0)])
    for name in ("fit.device.expand_s", "fit.device.irls_s",
                 "fit.device.irls.hess_s", "fit.device.irls.solve_s",
                 "fit.host.summary_s", "linear.irls.steps_per_fit",
                 "linear.irls.iterations_per_fit", "linear.hess_roofline"):
        read = _reader(name).read
        assert read(_reading(bare, fits=1)) is None, name
        assert read(_reading(None)) is None, name              # untraced
        assert read(_reading(xplane.Trace([], []))) is None, name


def test_the_summary_span_is_read_from_the_recorders_totals():
    counters = {"span_n.fit": 2.0, "span_s.fit": 9.0,
                "span_n.fit.summary": 2.0, "span_s.fit.summary": 0.5}
    run = _reading(_two_fits(), counters=counters)
    assert _reader("fit.host.summary_s").read(run) == pytest.approx(0.25)
    # no phase holds it: it stays inside what no phase covers
    assert _reader("fit.host.unattributed_s").read(run) == pytest.approx(4.5)


# ------------------------------------------- BENCHMARK.json, appended to
def test_the_benchmark_is_valid_and_the_cell_is_its_fourth():
    assert spec.validate(REPO, BENCH) == []
    assert [w["name"] for w in BENCH["workloads"]][:4] == [
        "ml11_xgb.fit", "ml07_rf.fit", "ml11_xgb_4chip.fit_sharded", CELL]
    cell = spec.workload(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "mle03_logreg", "fit_logistic", 1)
    assert BENCH["run_seconds"] == 51
    reported = {m["name"] for g in ("end_to_end", "per_layer")
                for m in spec.metrics_for(BENCH, CELL, g)}
    assert reported == {
        "fit_s", "setup_s", "compile.backend_s", "compile.in_window",
        "staging.h2d_bytes_per_fit", "fit.device_busy_s",
        "fit.host.featurize_s", "fit.host.stage_s", "fit.host.dispatch_s",
        "fit.host.device_wait_s", "fit.host.readback_s",
        "fit.host.observe_s", "fit.host.unattributed_s",
        "fit.device.expand_s", "fit.device.irls_s", "fit.device.irls.hess_s",
        "fit.device.irls.solve_s", "fit.host.summary_s",
        "linear.irls.steps_per_fit", "linear.irls.iterations_per_fit",
        "linear.hess_roofline"}


def test_what_pr_28_left_stands_and_is_only_appended_to():
    entries = BENCH["per_layer"]
    assert entries[18]["name"] == "fit.device.allreduce_s"
    assert entries[18]["workloads"] == ["ml11_xgb_4chip.fit_sharded"]
    old = ["ml11_xgb.fit", "ml07_rf.fit", "ml11_xgb_4chip.fit_sharded"]
    for m in entries[:18]:
        if "workloads" in m:
            assert m["workloads"][:3] == old
            assert m["workloads"][3:] in ([], [CELL])
    tree_only = {"fit.host.quantize_s"} | {
        m["name"] for m in entries[:18] if m["name"].startswith(
            "fit.device.") and m["name"] != "fit.device_busy_s"}
    for m in entries[:18]:
        if m["name"] in tree_only:
            assert CELL not in m["workloads"]
    for m in entries[19:]:
        assert m["workloads"] == [CELL] and m["moves"] == "fit_s"
        assert m["layer"] == "linear fit programs"
        assert os.path.isfile(os.path.join(METRICS, m["name"] + ".py"))
    assert entries[-1] == {
        "name": "linear.hess_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "linear fit programs",
        "moves": "fit_s", "workloads": [CELL]}
    fit_s = BENCH["end_to_end"][0]
    assert fit_s["bound"] == 0.05 and fit_s["workloads"] == old + [CELL]
    assert [c["name"] for c in BENCH["configs"]][:3] == [
        "ml11_xgb", "ml07_rf", "ml11_xgb_4chip"]


def test_the_configuration_is_the_notebooks_pipeline_at_its_defaults():
    entry = spec.config_entry(BENCH, "mle03_logreg")
    assert entry["reduced"] == [] and "MLE 03" in entry["source"]
    cfg = spec.load_json(os.path.join(REPO, entry["file"]))
    for key in ("source", "deployment", "data", "label", "pipeline",
                "fit_math", "correct", "assumed", "precision", "conf"):
        assert key in cfg, key
    assert cfg["name"] == "mle03_logreg" and cfg["reduced"] == []
    assert cfg["data"] == {"generator": "airbnb_superhost", "rows": 8_000_000}
    assert cfg["label"] == {"column": "label", "fit_column": "label"}
    assert [(s["class"], s["params"]) for s in cfg["pipeline"]] == [
        ("RFormula", {"formula": "label ~ .", "featuresCol": "features",
                      "labelCol": "label", "handleInvalid": "skip"}),
        ("LogisticRegression", {"labelCol": "label",
                                "featuresCol": "features"})]
    from sml_tpu.ml.classification import LogisticRegression
    est = LogisticRegression()
    for key in ("maxIter", "tol", "regParam", "elasticNetParam",
                "fitIntercept"):
        assert cfg["fit_math"][key] == est.getOrDefault(key), key
    limits = cfg["correct"]
    assert set(limits["reasons"]) == set(limits) - {"sample_rows", "reasons"}
    assert spec.resolve(REPO, BENCH, CELL)["traffic"] == {
        **spec.load_json(f"{REPO}/benchmark/traffic/fit.json"),
        "kind": "fit_logistic"}
