"""The tuning cell (`mle03_logreg_cv.fit_cv`, kind `fit_cv`) on the CPU at a
tiny size: a sound run is correct against the float64 proximal-Newton
reference and its fold AUROCs; the controls (every product in bfloat16,
another fold membership, a best model that is not the arg-max's, a grid
point left out of the refit's parameters) each fail the line they should; a
program that cannot tune on one staged block is refused before the table is
made; a traced CPU run prints the counter-fed metrics; the readers read what
the program adds and nothing on a program without it; and BENCHMARK.json is
what PR 39 left with this PR's entries appended and the eight set-up metrics
given their lists."""

import json
import os
import time
import types

import numpy as np
import pytest

import bench_tiny
from benchmark.harness import program, runner, spec, xplane

REPO = bench_tiny.REPO
BENCH = spec.load_benchmark(REPO)
CELL = "mle03_logreg_cv.fit_cv"
ACCEPTED = ["ml11_xgb.fit", "ml07_rf.fit", "ml11_xgb_4chip.fit_sharded",
            "mle03_logreg.fit_logistic"]
TINY = "tiny_logreg_cv.tiny_fit_cv"
TINY_ROWS = 20000
METRICS = os.path.join(REPO, "benchmark", "layer_metrics")
NEW = ["fit.host.cv.folds_s", "fit.host.cv.eval_s", "fit.device.irls.prox_s",
       "fit.device.cv.eval_s", "cv.fits_per_fit",
       "linear.irls.prox_sweeps_per_fit", "cv.eval_roofline"]
EIGHT = ["compile.backend_s", "compile.in_window", "setup.before_program_s",
         "setup.import_s", "setup.table_s", "setup.split_s",
         "setup.warm_fit_s", "setup.first_dispatch_s"]


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """`bench_tiny`'s copy with the deployment added at 20,000 rows, as new
    files and entries."""
    root, bench = bench_tiny.make_tiny_root(tmp_path_factory.mktemp("cv"))

    def write(rel, obj):
        path = os.path.join(root, rel)
        assert not os.path.exists(path), f"{rel} would edit an existing file"
        with open(path, "w") as f:
            json.dump(obj, f, indent=1)

    cfg = spec.load_json(os.path.join(
        root, "benchmark/configs/mle03_logreg_cv.json"))
    cfg.update(name="tiny_logreg_cv", reduced=["data"])
    cfg["data"]["rows"] = TINY_ROWS
    cfg["correct"]["sample_rows"] = 500
    # the cell's limit is for folds of 2.1 M rows, where one swapped pair
    # of a positive and a negative moves the area by 1e-12; in a fold of
    # 4,700 rows it moves it by 2e-7
    cfg["correct"]["avg_metric_atol"] = 5e-6
    write("benchmark/configs/tiny_logreg_cv.json", cfg)
    traffic = spec.load_json(os.path.join(
        root, "benchmark/traffic/fit_cv.json"))
    traffic.update(warm_iterations=1, fractions=[0.7, 0.3])
    write("benchmark/traffic/tiny_fit_cv.json", traffic)
    bench["configs"].append({
        "name": "tiny_logreg_cv", "source": "test fixture", "why": "tiny",
        "reduced": ["data"],
        "file": "benchmark/configs/tiny_logreg_cv.json"})
    bench["workloads"].append({
        "name": TINY, "config": "tiny_logreg_cv", "traffic": "tiny_fit_cv",
        "chips": 1, "why": "tiny tuning cell for the CPU tests"})
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


def _failed(out):
    return sorted(n for n, ln in _checks(out).items() if ": FAIL" in ln)


LINES = ("fit.indexer_labels.columns_differing", "cv.best_index_agrees",
         "cv.best.kkt_residual.max", "cv.best.coefficient_err.max",
         "reference.residual.max",
         "cv.best.support_differing", "cv.best.support_near_threshold",
         "cv.avg_metric.abs_gap_max", "cv.lasso_point.kkt_residual.max",
         "cv.lasso_point.nonzero", "fit.probability_vs_margin.abs_gap_max",
         "fit.holdout_auc", "cv.fits_per_fit", "cv.evals_per_fit",
         "cv.fold_frames_per_fit", "linear.host_loops_per_fit",
         "linear.irls_fits_per_fit", "linear.irls.unconverged",
         "linear.irls.floor_ended_share",
         "linear.irls.iterations_per_irls_fit", "fit.plan_fits_per_fit",
         "fit.plan_declined", "fit.h2d_blocks_per_fit",
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
    assert "the probe fit of 4000 rows tuned on one staged block" in out
    assert _observed(checks["cv.fits_per_fit"]) == 19
    assert _observed(checks["cv.evals_per_fit"]) == 18
    assert _observed(checks["cv.best.kkt_residual.max"]) < 2e-6
    assert _observed(checks["cv.avg_metric.abs_gap_max"]) < 2e-6
    assert 0.7 < _observed(checks["fit.holdout_auc"]) < 0.8
    assert 0.95 < _observed(checks["fit.h2d_blocks_per_fit"]) < 1.15


def test_a_traced_run_reports_the_counter_fed_layers(tiny):
    line = drive(tiny, seed=2**31 + 611, trace=True)
    assert line["correct"] is True
    # no device plane on the CPU: the trace-fed and span-fed readers, the
    # new ones too, find nothing to read and are left out
    assert set(line["metrics"]) == {
        "staging.h2d_bytes_per_fit", "compile.backend_s", "compile.in_window",
        "linear.irls.steps_per_fit", "linear.irls.iterations_per_fit",
        "cv.fits_per_fit", "linear.irls.prox_sweeps_per_fit"}
    assert line["metrics"]["cv.fits_per_fit"]["value"] == 19.0
    steps = line["metrics"]["linear.irls.steps_per_fit"]["value"]
    assert 19 <= steps <= 19 * 12
    assert steps == line["metrics"]["linear.irls.iterations_per_fit"]["value"]
    assert line["metrics"]["linear.irls.prox_sweeps_per_fit"]["value"] > 0
    assert line["metrics"]["compile.in_window"]["value"] == 0.0


# --------------------------------------------------------------- controls
def test_bfloat16_products_fail_the_optimality_line(tiny):
    """One `Pipeline.fit` with every product's operands rounded to
    bfloat16 (`tools_cv.py`), measured as `check` measures: it is no
    optimum by the limit, and the sound fit of the same rows is. (Not a
    whole run of the cell: a bfloat16 step never moves by less than tol,
    so each of the 19 fits runs its 100 steps, three fits a run.)"""
    tools = runner.load_module(os.path.join(REPO, "benchmark",
                                            "tools_cv.py"), "bench_tools_cv")
    root, bench = tiny
    parts = spec.resolve(root, bench, TINY)
    kind = runner.load_module(parts["kind_path"], "bench_kind_fit_cv_c")
    data = runner.load_module(parts["data_path"], "bench_data_c")
    cfg = dict(parts["config"], data=dict(parts["config"]["data"], rows=6000))
    program.configure(cfg.get("conf", {}))
    tuned = kind.Program(program)
    train, rest = program.split(program.make_table(
        data.make(cfg["data"], 21)), [0.7, 0.3], 21)
    quiet = lambda message: None    # noqa: E731
    sound = kind.measure(tuned, cfg, tuned.build_pipeline(cfg).fit(train),
                         train, rest, 21, quiet)
    with tools.bfloat16_products():
        lossy = kind.measure(tuned, cfg, tuned.build_pipeline(cfg).fit(train),
                             train, rest, 21, quiet)
    limit = cfg["correct"]["kkt_residual_max"]
    assert sound["kkt_residual_max"] < limit / 5
    assert lossy["kkt_residual_max"] > 5 * limit
    assert lossy["coefficient_err_max"] > 20 * sound["coefficient_err_max"]


def test_another_fold_membership_fails_the_metric_line(tiny, capsys):
    """The validator split by another seed than the one its model states:
    the reference's folds are the stated seed's, and a third of every fold
    differs."""
    def build(cfg):
        pipeline = program.build_pipeline(cfg)
        pipeline.getStages()[-1]._set(seed=43)
        real_fit = pipeline.fit

        def fit(frame):
            model = real_fit(frame)
            model.stages[-1]._set(seed=42)
            return model
        pipeline.fit = fit
        return pipeline
    line = drive(tiny, seed=33, stand_in=stand_in(build_pipeline=build))
    out = capsys.readouterr().out
    assert line["correct"] is False
    assert _failed(out) == ["cv.avg_metric.abs_gap_max"], out
    assert _observed(_checks(out)["cv.avg_metric.abs_gap_max"]) > 1e-4


def test_a_best_model_of_another_point_fails_its_lines(tiny, capsys):
    """bestModel refitted at a grid point that is not the arg-max's."""
    def build(cfg):
        pipeline = program.build_pipeline(cfg)
        real_fit = pipeline.fit

        def fit(frame):
            model = real_fit(frame)
            tail = model.stages[-1]
            worst = int(np.argmin(tail.avgMetrics))
            at = tail.getEstimatorParamMaps()[worst]
            for p, v in at.items():
                tail.bestModel._set(**{p.name: v})
            return model
        pipeline.fit = fit
        return pipeline
    line = drive(tiny, seed=34, stand_in=stand_in(build_pipeline=build))
    out = capsys.readouterr().out
    assert line["correct"] is False
    assert "cv.best_index_agrees" in _failed(out)


def test_a_shrunk_best_model_fails_the_optimality_lines(tiny, capsys):
    """Every coefficient of the best model a thousandth smaller: no
    minimizer, and whole standard errors off the reference's."""
    def build(cfg):
        pipeline = program.build_pipeline(cfg)
        real_fit = pipeline.fit

        def fit(frame):
            model = real_fit(frame)
            model.stages[-1].bestModel._coefficients *= 0.999
            return model
        pipeline.fit = fit
        return pipeline
    line = drive(tiny, seed=35, stand_in=stand_in(build_pipeline=build))
    out = capsys.readouterr().out
    assert line["correct"] is False
    assert "cv.best.kkt_residual.max" in _failed(out)


def test_a_reference_that_stops_short_fails_its_own_line(tiny, capsys,
                                                         monkeypatch):
    """`enet.fit` does not raise at `max_iter`: with three passes from
    the null model it is nowhere near its optimum, and the run is not
    correct by the reference's own residual (and by the coefficients it
    is then compared with), whatever the program fitted."""
    from functools import partial
    from benchmark.reference import logistic_enet as enet
    monkeypatch.setattr(enet, "fit", partial(enet.fit, max_iter=3))
    line = drive(tiny, seed=36)
    out = capsys.readouterr().out
    assert line["correct"] is False
    assert "reference.residual.max" in _failed(out)
    assert _observed(_checks(out)["reference.residual.max"]) > 1e-8


def test_a_lasso_part_left_out_fails_the_sparse_points_lines(tiny, capsys,
                                                             monkeypatch):
    """An estimator that fits the ridge part alone (the grid's ridge
    points, and so the tuned point, are fitted as ever): the sparse
    point's model is dense and no optimum of its objective."""
    from sml_tpu.ml import linear_impl
    # the fused program at the cell's size, the host loop at this one
    # (under `sml.linear.compactBytes` an estimator alone has no compact
    # block): both
    for name in ("fit_logistic_compact", "fit_logistic"):
        def ridge_only(X, y, real=getattr(linear_impl, name), **kw):
            return real(X, y, **dict(kw, elasticNetParam=0.0))
        monkeypatch.setattr(linear_impl, name, ridge_only)
    line = drive(tiny, seed=37)
    out = capsys.readouterr().out
    assert line["correct"] is False
    assert _failed(out) == ["cv.lasso_point.kkt_residual.max",
                            "cv.lasso_point.nonzero"], out


def test_fits_that_end_at_the_floor_are_counted_and_held(tiny, capsys):
    """A program a tenth of whose fits end at float32's floor and not by
    tol: every other line passes, this one does not."""
    def counters():
        got = dict(program.counters())
        got["linear.irls.floor_ended"] = \
            got.get("linear.irls.floor_ended", 0.0) \
            + 0.1 * got.get("linear.irls.fits", 0.0)
        return got
    line = drive(tiny, seed=38, stand_in=stand_in(counters=counters))
    out = capsys.readouterr().out
    assert line["correct"] is False
    assert _failed(out) == ["linear.irls.floor_ended_share"], out
    assert _observed(_checks(out)["linear.irls.floor_ended_share"]) == \
        pytest.approx(0.1)


# ---------------------------------------------------- the probe's refusal
def test_a_program_that_needs_fold_frames_is_refused_before_the_table(
        tiny, capsys, monkeypatch):
    """The parent's shape: the validator cannot read its folds off one
    block, the plan declines, the generic path makes fold frames and takes
    the host loop. Set-up raises what `runner.main` answers with exit code
    2, and no table was made."""
    from sml_tpu.ml.tuning import CrossValidator
    monkeypatch.setattr(CrossValidator, "_block_estimator", lambda self: None)
    with pytest.raises(spec.SpecError, match="does not tune"):
        drive(tiny, seed=5)
    out = capsys.readouterr().out
    assert "table made" not in out


def test_the_command_answers_a_refusal_with_exit_code_2(tiny, monkeypatch,
                                                        capsys):
    def refuse(*a, **k):
        raise spec.SpecError("this program does not tune")
    monkeypatch.setattr(runner, "run", refuse)
    assert runner.main(tiny[0], TINY, 1, 1.0, False, time.perf_counter()) == 2
    assert "does not tune" in capsys.readouterr().err


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
        cell="cv.no_trace_file", config={}, traffic={}, seconds=1.0,
        facts={"fits": fits, "fit_rows": [1000] * fits, "features": 9,
               "folds": 3},
        counters_start={k: 0.0 for k in counters}, counters_end=counters,
        compiles=None, device={"platform": "tpu", "kind": kind},
        program=None, trace=trace)


def _two_fits():
    """Two fits on one plane. Each: the loop over grid points [100, 900)
    holding a Newton loop [100, 600) with a Hessian [100, 300), a ridge
    solve [300, 350) and coordinate sweeps [350, 550), then the held
    fold's margins [600, 800)."""
    point = "while/body/closed_call/"
    inner = point + "linear.irls/while/body/closed_call/"
    ops = []
    for t in (0.0, 2000.0):
        ops += [(n, a + t, b + t) for n, a, b in [
            (_hlo("while.1", "linear.irls", "while"), 100.0, 600.0),
            (_hlo("fusion.3", inner + "linear.irls.hess"), 100.0, 300.0),
            (_hlo("custom.4", inner + "cond/branch_0_fun/"
                  "linear.irls.solve"), 300.0, 350.0),
            (_hlo("while.5", inner + "cond/branch_1_fun/"
                  "linear.irls.prox", "while"), 350.0, 550.0),
            (_hlo("fusion.6", point + "cv.eval"), 600.0, 800.0)]]
    notes = [("bench.window", 0.0, 4000.0), ("bench.fit", 50.0, 1100.0),
             ("bench.split", 1100.0, 2000.0), ("bench.fit", 2050.0, 3100.0)]
    return xplane.Trace([ops], notes)


def test_the_new_scopes_are_read_at_any_depth():
    counters = {"linear.irls.prox_sweeps": 30.0, "cv.evals": 36.0,
                "cv.fits": 38.0}
    run = _reading(_two_fits(), counters=counters)
    assert _reader("fit.device.irls.prox_s").read(run) == \
        pytest.approx(200e-9)
    assert _reader("fit.device.cv.eval_s").read(run) == pytest.approx(200e-9)
    # the accepted readers read the penalized program's scopes unchanged
    assert _reader("fit.device.irls_s").read(run) == pytest.approx(500e-9)
    assert _reader("fit.device.irls.hess_s").read(run) == \
        pytest.approx(200e-9)
    assert _reader("fit.device.irls.solve_s").read(run) == \
        pytest.approx(50e-9)
    assert _reader("cv.fits_per_fit").read(run) == 19.0
    assert _reader("linear.irls.prox_sweeps_per_fit").read(run) == 15.0


def test_the_evaluations_roofline_is_the_block_read_once_a_fold():
    """The work is what the problem needs, not the program's layout: every
    grid point's margins of a fold can come of ONE read of the block, so a
    program that reads it once a (point, fold) reads at most a sixth, and
    one that batches the points stays under 100 %."""
    work = runner.load_module(os.path.join(METRICS, "_cv_work.py"), "w")
    assert work.eval_bytes(rows=1000, slots=10, folds=3) == 120000.0
    with pytest.raises(KeyError, match="no peak"):
        work.peak_bytes_per_s("cpu")
    run = _reading(_two_fits(), counters={"cv.evals": 36.0})
    share = _reader("cv.eval_roofline").read(run)
    # 3 folds a fit x 1000 rows x 10 slots x 4 bytes over 200 ns, however
    # many evaluations the counter says
    assert share == pytest.approx(100.0 * 120000.0 / (200e-9 * 819e9))
    more = _reading(_two_fits(), counters={"cv.evals": 72.0})
    assert _reader("cv.eval_roofline").read(more) == share


def test_the_host_spans_are_read_from_the_recorders_totals():
    counters = {"span_n.fit": 2.0, "span_s.fit": 9.0,
                "span_n.fit.cv.folds": 2.0, "span_s.fit.cv.folds": 1.0,
                "span_n.fit.cv.eval": 2.0, "span_s.fit.cv.eval": 0.25}
    run = _reading(_two_fits(), counters=counters)
    assert _reader("fit.host.cv.folds_s").read(run) == pytest.approx(0.5)
    assert _reader("fit.host.cv.eval_s").read(run) == pytest.approx(0.125)
    # no phase of the eight holds them
    assert _reader("fit.host.unattributed_s").read(run) == pytest.approx(4.5)


@pytest.mark.parametrize("name", NEW)
def test_a_new_reader_returns_nothing_on_a_program_without_the_path(name):
    """The parent's program: no scope, no counter, no span."""
    bare = xplane.Trace(
        [[(_hlo("fusion.2", "linear.irls/while/body/closed_call/"
                "linear.irls.hess"), 300.0, 600.0)]],
        [("bench.window", 0.0, 4000.0), ("bench.fit", 100.0, 1100.0)])
    read = _reader(name).read
    assert read(_reading(bare, fits=1)) is None
    assert read(_reading(None)) is None              # untraced
    assert read(_reading(xplane.Trace([], []))) is None


# ------------------------------------------- BENCHMARK.json, appended to
def test_the_benchmark_is_valid_and_the_cell_is_its_fifth():
    assert spec.validate(REPO, BENCH) == []
    names = [w["name"] for w in BENCH["workloads"]]
    assert names[:5] == ACCEPTED + [CELL]
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    cell = spec.workload(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "mle03_logreg_cv", "fit_cv", 1)
    assert BENCH["run_seconds"] == 51
    assert [c["name"] for c in BENCH["configs"]][:5] == [
        "ml11_xgb", "ml07_rf", "ml11_xgb_4chip", "mle03_logreg",
        "mle03_logreg_cv"]
    assert all("workloads" in m for m in BENCH["per_layer"])


def test_the_cell_reports_what_cell_4_does_and_its_own():
    def reported(cell):
        return {m["name"] for g in ("end_to_end", "per_layer")
                for m in spec.metrics_for(BENCH, cell, g)}
    assert reported(CELL) == reported(ACCEPTED[3]) | set(NEW)
    assert {"fit_s", "setup_s", "linear.hess_roofline"} <= reported(CELL)


@pytest.mark.parametrize("name", EIGHT)
def test_a_set_up_metric_lists_every_cell_that_reports_setup_s(name):
    """The one repair: the eight that move `setup_s` list the accepted
    cells and this one (they had no list: every cell, which a new cell
    cannot be held to before its readers are seen to read it)."""
    entry, = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert entry["moves"] == "setup_s" and entry["better"] == "lower"
    assert entry["workloads"][:5] == ACCEPTED + [CELL]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert os.path.isfile(os.path.join(METRICS, name + ".py"))


SETUP_AS_PR_38_SET_THEM = {
    "setup.before_program_s": ("program_counter",
                               "process start and import"),
    "setup.import_s": ("program_counter", "process start and import"),
    "setup.table_s": ("program_span", "frame engine"),
    "setup.split_s": ("program_span", "frame engine"),
    "setup.warm_fit_s": ("program_span", "pipeline fit"),
    "setup.first_dispatch_s": ("program_span", "compile and cache")}


@pytest.mark.parametrize("name", sorted(SETUP_AS_PR_38_SET_THEM))
def test_a_pr_38_set_up_entry_is_as_it_was_but_for_its_list(name):
    """What `test_bench_host_children.py`'s six `setup.*` cases held (they
    are red by their letter since the entries were given their lists), in
    the form that survives an append."""
    entry, = [m for m in BENCH["per_layer"] if m["name"] == name]
    source, layer = SETUP_AS_PR_38_SET_THEM[name]
    assert (entry["unit"], entry["source"], entry["layer"]) == \
        ("s", source, layer)
    assert entry["moves"] == "setup_s" and entry["better"] == "lower"


@pytest.mark.parametrize("name", NEW)
def test_a_new_entry_is_appended_with_its_reader(name):
    entry, = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert entry["workloads"][:1] == [CELL] and entry["moves"] == "fit_s"
    assert entry["layer"] in ("model selection", "linear fit programs")
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names.index(name) > names.index("setup.first_dispatch_s")
    assert os.path.isfile(os.path.join(METRICS, name + ".py"))


def test_the_accepted_lists_are_only_appended_to():
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        cells = m.get("workloads")
        if cells is None or m["name"] in NEW:
            continue
        old = [c for c in cells if c in ACCEPTED]
        assert cells[:len(old)] == old, m["name"]
        assert old == [c for c in ACCEPTED if c in old], m["name"]
    fit_s = BENCH["end_to_end"][0]
    assert fit_s["bound"] == 0.05 and fit_s["workloads"][:5] == \
        ACCEPTED + [CELL]
    assert BENCH["end_to_end"][1] == {
        "name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1,
        "source": "host_clock"}


def test_the_configuration_is_the_notebooks_tuned_pipeline():
    entry = spec.config_entry(BENCH, "mle03_logreg_cv")
    assert entry["reduced"] == [] and "MLE 03" in entry["source"]
    cfg = spec.load_json(os.path.join(REPO, entry["file"]))
    for key in ("source", "deployment", "data", "label", "pipeline",
                "validator", "fit_math", "correct", "assumed", "precision",
                "conf", "guarantees"):
        assert key in cfg, key
    assert cfg["name"] == "mle03_logreg_cv" and cfg["reduced"] == []
    assert cfg["data"] == {"generator": "airbnb_superhost", "rows": 8_000_000}
    assert [(s["class"], s["params"]) for s in cfg["pipeline"]] == [
        ("RFormula", {"formula": "label ~ .", "featuresCol": "features",
                      "labelCol": "label", "handleInvalid": "skip"}),
        ("CrossValidator", {"numFolds": 3, "parallelism": 4, "seed": 42})]
    tuned = cfg["validator"]
    assert tuned["estimator"]["class"] == "LogisticRegression"
    assert tuned["evaluator"]["params"] == {"metricName": "areaUnderROC"}
    assert tuned["grid"] == [["regParam", [0.1, 0.2]],
                             ["elasticNetParam", [0.0, 0.5, 1.0]]]
    from sml_tpu.ml.classification import LogisticRegression
    est = LogisticRegression()
    for key in ("maxIter", "tol", "fitIntercept", "threshold"):
        assert cfg["fit_math"][key] == est.getOrDefault(key), key
    assert (cfg["fit_math"]["raw_columns"], cfg["fit_math"]["slots"]) == \
        (22, 62)
    same = spec.load_json(os.path.join(
        REPO, "benchmark/configs/mle03_logreg.json"))
    for key in ("data", "label", "precision", "conf"):
        assert cfg[key] == same[key], key
    limits = cfg["correct"]
    assert set(limits["reasons"]) == set(limits) - {"sample_rows", "reasons"}
    assert any("recollection" in a for a in cfg["assumed"])
    assert spec.resolve(REPO, BENCH, CELL)["traffic"]["kind"] == "fit_cv"


def test_the_kind_builds_the_grid_in_the_builders_order():
    kind = runner.load_module(os.path.join(
        REPO, "benchmark", "kinds", "fit_cv.py"), "bench_kind_fit_cv_t")
    cfg = spec.resolve(REPO, BENCH, CELL)["config"]
    tail = kind.Program(program).build_pipeline(cfg).getStages()[-1]
    from sml_tpu.ml.tuning import ParamGridBuilder
    lr = tail.getOrDefault("estimator")
    want = (ParamGridBuilder().addGrid(lr.regParam, [0.1, 0.2])
            .addGrid(lr.elasticNetParam, [0.0, 0.5, 1.0]).build())
    assert tail.getOrDefault("estimatorParamMaps") == want
    assert kind._fits_a_fit(cfg) == (19, 18)
