"""The factorization cell (`mle01_als.fit_als`, kind `fit_als`) on the CPU at
a tiny size: a sound run is correct against the float64 ALS-WR reference; a
traced CPU run prints the counter-fed metrics; a program that does not
build its normal equations by blocks is refused before the table is made;
the reference recovers a planted model and its residual line catches a
solve without the regularization; the readers read what the program adds
and nothing on a program without it; and BENCHMARK.json holds the cell and
its entries appended to what was there."""

import json
import os
import time
import types

import numpy as np
import pytest

import bench_tiny
from benchmark.harness import program, runner, spec, xplane
from benchmark.reference import als

REPO = bench_tiny.REPO
BENCH = spec.load_benchmark(REPO)
CELL = "mle01_als.fit_als"
BEFORE = ["ml11_xgb.fit", "ml07_rf.fit", "ml11_xgb_4chip.fit_sharded",
          "mle03_logreg.fit_logistic", "mle03_logreg_cv.fit_cv"]
TINY = "tiny_als.tiny_fit_als"
METRICS = os.path.join(REPO, "benchmark", "layer_metrics")
NEW = ["fit.device.als.gather_s", "fit.device.als.normal_s",
       "fit.device.als.solve_s", "fit.host.featurize.als.index_s",
       "fit.host.featurize.als.sort_s", "als.half_steps_per_fit",
       "als.normal_roofline"]
JOINED = ["staging.h2d_bytes_per_fit", "fit.device_busy_s",
          "compile.backend_s", "compile.in_window", "fit.host.featurize_s",
          "fit.host.stage_s", "fit.host.dispatch_s", "fit.host.device_wait_s",
          "fit.host.readback_s", "fit.host.observe_s",
          "fit.host.unattributed_s", "fit.host.stage.key_s",
          "fit.host.stage.pad_s", "fit.host.stage.put_s",
          "fit.host.featurize.cpu_s", "setup.before_program_s",
          "setup.import_s", "setup.table_s", "setup.split_s",
          "setup.warm_fit_s", "setup.first_dispatch_s"]
LINES = ("fit.ids.sides_differing",
         "fit.prediction_vs_reference.abs_gap_max", "fit.normal_residual.max",
         "fit.holdout_rmse_vs_mean.ratio", "fit.cold_start.dropped",
         "fit.transform.rows", "als.fits_per_fit", "als.half_steps_per_fit",
         "als.ratings_per_fit", "fit.h2d_arrays_per_fit",
         "all.route_device_share_pct", "all.compile_requests_in_window")


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """`bench_tiny`'s copy with the deployment added at 60,000 ratings, as
    new files and entries."""
    root, bench = bench_tiny.make_tiny_root(tmp_path_factory.mktemp("als"))

    def write(rel, obj):
        path = os.path.join(root, rel)
        assert not os.path.exists(path), f"{rel} would edit an existing file"
        with open(path, "w") as f:
            json.dump(obj, f, indent=1)

    cfg = spec.load_json(os.path.join(root,
                                      "benchmark/configs/mle01_als.json"))
    cfg.update(name="tiny_als", reduced=["data"])
    # the generator's own skews are `ml-25m`'s; 60,000 ratings need a
    # steeper one for hundreds of movies rated once, so that every split
    # leaves some out
    cfg["data"].update(rows=60000, users=900, items=2500, max_item_id=9000,
                       item_offset=20.0, item_skew=1.5)
    # the limit is for one chip and 20 M ratings, where the bounds are a
    # two-hundredth of the staged bytes; the CPU mesh's eight devices hold
    # a copy of them each, a third of what 42,000 ratings stage
    cfg["correct"].update(sample_rows=500, residual_items=300,
                          h2d_arrays_max=1.6)
    write("benchmark/configs/tiny_als.json", cfg)
    traffic = spec.load_json(os.path.join(root,
                                          "benchmark/traffic/fit_als.json"))
    traffic.update(warm_iterations=1, fractions=[0.7, 0.3])
    write("benchmark/traffic/tiny_fit_als.json", traffic)
    bench["configs"].append({
        "name": "tiny_als", "source": "test fixture", "why": "tiny",
        "reduced": ["data"], "file": "benchmark/configs/tiny_als.json"})
    bench["workloads"].append({
        "name": TINY, "config": "tiny_als", "traffic": "tiny_fit_als",
        "chips": 1, "why": "tiny factorization cell for the CPU tests"})
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if CELL in m.get("workloads", []):
                m["workloads"].append(TINY)
    assert spec.validate(root, bench) == []
    return root, bench


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
    assert "built its normal equations by blocks" in out
    assert _observed(checks["als.half_steps_per_fit"]) == 10
    assert _observed(checks["fit.prediction_vs_reference.abs_gap_max"]) < 1e-3
    assert _observed(checks["fit.normal_residual.max"]) < 1e-4
    assert _observed(checks["fit.holdout_rmse_vs_mean.ratio"]) < 0.9
    # the long tail: an 70 % split never holds every movie
    assert _observed(checks["fit.cold_start.dropped"]) > 0
    assert 0.95 < _observed(checks["fit.h2d_arrays_per_fit"]) < 1.45


def test_a_traced_run_reports_the_counter_fed_layers(tiny):
    line = drive(tiny, seed=2**31 + 611, trace=True)
    assert line["correct"] is True
    # no device plane on the CPU: the trace-fed and span-fed readers, the
    # new ones too, find nothing to read and are left out
    assert set(line["metrics"]) == {
        "staging.h2d_bytes_per_fit", "compile.backend_s", "compile.in_window",
        "als.half_steps_per_fit"}
    assert line["metrics"]["als.half_steps_per_fit"]["value"] == 10.0
    assert line["metrics"]["compile.in_window"]["value"] == 0.0


# --------------------------------------------------------------- controls
def test_a_model_of_another_seed_fails_the_prediction_line(tiny, capsys):
    """The estimator seeded otherwise than its model states: another init,
    another path of five alternations; its own normal equations still
    hold at its factors."""
    def build(cfg):
        pipeline = program.build_pipeline(cfg)
        est, = pipeline.getStages()
        real_fit = est.fit

        def fit(frame):
            est._set(seed=43)
            return real_fit(frame)._set(seed=42)
        est.fit = fit
        return pipeline
    line = drive(tiny, seed=33, stand_in=stand_in(build_pipeline=build))
    out = capsys.readouterr().out
    assert line["correct"] is False
    assert _failed(out) == ["fit.prediction_vs_reference.abs_gap_max"], out


def test_shrunk_item_factors_fail_the_residual_line(tiny, capsys):
    """Every movie's factors a hundredth smaller: no solution of its
    normal equations, whatever the users' are."""
    def build(cfg):
        pipeline = program.build_pipeline(cfg)
        est, = pipeline.getStages()
        real_fit = est.fit

        def fit(frame):
            model = real_fit(frame)
            model._if = model._if * np.float32(0.99)
            return model
        est.fit = fit
        return pipeline
    line = drive(tiny, seed=34, stand_in=stand_in(build_pipeline=build))
    out = capsys.readouterr().out
    assert line["correct"] is False
    assert "fit.normal_residual.max" in _failed(out)
    assert _observed(_checks(out)["fit.normal_residual.max"]) > 5e-3


# ---------------------------------------------------- the probe's refusal
def test_a_program_that_forms_the_whole_table_is_refused_before_the_table(
        tiny, capsys, monkeypatch):
    """The parent's shape: one array of statistics for the whole table,
    no blocks to count. Set-up raises what `runner.main` answers with exit
    code 2, and no table was made."""
    from sml_tpu.utils.profiler import PROFILER
    real = PROFILER.count

    def count(name, value=1):
        if name != "als.blocks":
            real(name, value)
    monkeypatch.setattr(PROFILER, "count", count)
    with pytest.raises(spec.SpecError, match="by blocks of rows"):
        drive(tiny, seed=5)
    assert "table made" not in capsys.readouterr().out


def test_the_command_answers_a_refusal_with_exit_code_2(tiny, monkeypatch,
                                                        capsys):
    def refuse(*a, **k):
        raise spec.SpecError("does not build the normal equations by blocks")
    monkeypatch.setattr(runner, "run", refuse)
    assert runner.main(tiny[0], TINY, 1, 1.0, False, time.perf_counter()) == 2
    assert "by blocks" in capsys.readouterr().err


# ------------------------------------------------------------ the reference
def _planted(rng, users=300, items=200, rank=3, per_user=60):
    x = rng.standard_normal((users, rank))
    y = rng.standard_normal((items, rank))
    u = np.repeat(np.arange(users), per_user)
    i = np.concatenate([rng.choice(items, per_user, replace=False)
                        for _ in range(users)])
    return u * 7 + 3, i * 11 + 5, np.einsum("ij,ij->i", x[u], y[i])


def test_the_reference_recovers_a_planted_model():
    """Noise-free ratings of a rank-3 model, fitted at rank 3 with next to
    no regularization: the alternations reach the ratings themselves."""
    u, i, r = _planted(np.random.default_rng(0))
    fitted = als.fit(u, i, r, rank=3, max_iter=30, reg=1e-9, seed=1)
    assert als.rmse(als.predict(fitted, u, i), r) < 1e-6
    assert np.isnan(als.predict(fitted, np.array([u[0], 10**9]),
                                np.array([10**9, i[0]]))).all()


def test_the_reference_sums_a_segment_across_its_blocks(monkeypatch):
    """Blocks far shorter than a user's 60 ratings: every segment spans
    several, and the sums are those of one block to the bit's
    neighbourhood."""
    u, i, r = _planted(np.random.default_rng(1))
    whole = als.fit(u, i, r, rank=4, max_iter=2, reg=0.1, seed=2)
    monkeypatch.setattr(als, "BLOCK_ROWS", 17)
    blocked = als.fit(u, i, r, rank=4, max_iter=2, reg=0.1, seed=2)
    for key in ("user_factors", "item_factors"):
        np.testing.assert_allclose(blocked[key], whole[key], rtol=1e-11,
                                   atol=1e-13)


def test_the_residual_line_fails_a_solve_without_the_regularization():
    u, i, r = _planted(np.random.default_rng(2))
    fitted = als.fit(u, i, r, rank=4, max_iter=3, reg=0.1, seed=3)
    every = np.arange(len(fitted["item_ids"]))
    sound = als.normal_residual(fitted["by_item"], fitted["item_factors"],
                                fitted["user_factors"], 0.1, every)
    assert sound.max() < 1e-12
    A, b = fitted["by_item"].normal_equations(fitted["user_factors"])
    bare = np.linalg.solve(A + 1e-12 * np.eye(4), b[:, :, None])[:, :, 0]
    wrong = als.normal_residual(fitted["by_item"], bare,
                                fitted["user_factors"], 0.1, every)
    assert wrong.max() > 1e-2


def test_the_references_bfloat16_steps_are_not_its_float64_steps():
    u, i, r = _planted(np.random.default_rng(3))
    exact = als.fit(u, i, r, rank=4, max_iter=3, reg=0.1, seed=3)
    rounded = als.fit(u, i, r, rank=4, max_iter=3, reg=0.1, seed=3,
                      round_to="bfloat16")
    gap = np.abs(als.predict(rounded, u, i) - als.predict(exact, u, i))
    assert 1e-3 < gap.max() < 1.0


# ------------------------------------------------------------- the readers
def _reader(name):
    return runner.load_module(os.path.join(METRICS, name + ".py"),
                              "bench_metric_" + name.replace(".", "_"))


def _hlo(name, stack, kind="fusion"):
    meta = f', metadata={{op_name="jit(program)/while/body/closed_call/' \
           f'{stack}/add"}}' if stack else ""
    return f"%{name} = f32[8]{{0}} {kind}(f32[8]{{0}} %p){meta}"


def _reading(trace, fits=2, counters=None, kind="TPU v5 lite"):
    counters = counters or {}
    return runner.Reading(
        cell="als.no_trace_file", config={}, traffic={}, seconds=1.0,
        facts={"fits": fits, "fit_rows": [1000] * fits, "als_rank": 12,
               "als_entities": 50},
        counters_start={k: 0.0 for k in counters}, counters_end=counters,
        compiles=None, device={"platform": "tpu", "kind": kind},
        program=None, trace=trace)


def _two_fits():
    """Two fits on one plane. Each: a block's gather [100, 300), its
    statistics and sums [300, 700), the all-reduce [700, 750) nested in
    the build's scope, the solves [750, 900)."""
    block = "while/body/closed_call/"
    ops = []
    for t in (0.0, 2000.0):
        ops += [(n, a + t, b + t) for n, a, b in [
            (_hlo("gather.1", block + "als.gather", "gather"), 100.0, 300.0),
            (_hlo("fusion.2", block + "als.normal"), 300.0, 700.0),
            (_hlo("all-reduce.3", "als.normal/als.normal.allreduce",
                  "all-reduce"), 700.0, 750.0),
            (_hlo("while.4", "als.solve", "while"), 750.0, 900.0)]]
    notes = [("bench.window", 0.0, 4000.0), ("bench.fit", 50.0, 1100.0),
             ("bench.split", 1100.0, 2000.0), ("bench.fit", 2050.0, 3100.0)]
    return xplane.Trace([ops], notes)


def test_the_scopes_are_read_at_any_depth():
    run = _reading(_two_fits(), counters={"als.half_steps": 20.0})
    assert _reader("fit.device.als.gather_s").read(run) == \
        pytest.approx(200e-9)
    assert _reader("fit.device.als.normal_s").read(run) == \
        pytest.approx(450e-9)
    assert _reader("fit.device.als.solve_s").read(run) == \
        pytest.approx(150e-9)
    assert _reader("als.half_steps_per_fit").read(run) == 10.0


def test_the_roofline_is_the_bytes_the_build_needs():
    """The work is what the problem needs, not the program's layout: an id,
    a rating and a factor row read a rating, an entity's sums written
    once, a half-step; whatever passes a scan makes over the statistics."""
    work = runner.load_module(os.path.join(METRICS, "_als_work.py"), "w")
    assert work.build_bytes(ratings=1000, entities=50, rank=12,
                            half_steps=10) == 5 * (2 * 1000 * 56 + 50 * 624)
    with pytest.raises(KeyError, match="no peak"):
        work.peak_bytes_per_s("cpu")
    run = _reading(_two_fits(), counters={"als.half_steps": 20.0})
    share = _reader("als.normal_roofline").read(run)
    assert share == pytest.approx(
        100.0 * 5 * (2 * 1000 * 56 + 50 * 624) / (650e-9 * 819e9))


def test_the_host_spans_are_read_from_the_recorders_totals():
    counters = {"span_n.fit": 2.0, "span_s.fit": 9.0,
                "span_n.fit.featurize": 2.0, "span_s.fit.featurize": 4.0,
                "span_n.fit.featurize.als.index": 2.0,
                "span_s.fit.featurize.als.index": 1.0,
                "span_n.fit.featurize.als.sort": 2.0,
                "span_s.fit.featurize.als.sort": 2.5}
    run = _reading(_two_fits(), counters=counters)
    assert _reader("fit.host.featurize.als.index_s").read(run) == \
        pytest.approx(0.5)
    assert _reader("fit.host.featurize.als.sort_s").read(run) == \
        pytest.approx(1.25)
    # parts of the featurize phase, not phases beside it
    assert _reader("fit.host.featurize_s").read(run) == pytest.approx(2.0)
    assert _reader("fit.host.unattributed_s").read(run) == pytest.approx(2.5)


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
def test_the_benchmark_is_valid_and_holds_the_cell():
    assert spec.validate(REPO, BENCH) == []
    names = [w["name"] for w in BENCH["workloads"]]
    assert names[:6] == BEFORE + [CELL]
    assert sum(w["chips"] == 4 for w in BENCH["workloads"][:6]) == 1
    cell = spec.workload(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "mle01_als", "fit_als", 1)
    assert BENCH["run_seconds"] == 51
    assert [c["name"] for c in BENCH["configs"]][5] == "mle01_als"
    parts = spec.resolve(REPO, BENCH, CELL)
    assert parts["traffic"]["kind"] == "fit_als"
    assert set(parts["readers"]) == set(NEW) | set(JOINED)


@pytest.mark.parametrize("name", NEW)
def test_a_new_entry_is_appended_with_its_reader(name):
    entry, = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert entry["workloads"][:1] == [CELL] and entry["moves"] == "fit_s"
    assert entry["layer"] in ("factorization fit programs", "featurize")
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names.index(name) > names.index("cv.eval_roofline")
    assert os.path.isfile(os.path.join(METRICS, name + ".py"))


@pytest.mark.parametrize("name", ["fit_s"] + JOINED)
def test_an_accepted_list_is_only_appended_to(name):
    entry, = [m for g in ("end_to_end", "per_layer") for m in BENCH[g]
              if m["name"] == name]
    cells = entry["workloads"]
    old = [c for c in cells if c in BEFORE]
    assert cells[:len(old)] == old == [c for c in BEFORE if c in old]
    assert cells[len(old)] == CELL


def test_the_configuration_is_the_labs_estimator_at_ml_25ms_shape():
    entry = spec.config_entry(BENCH, "mle01_als")
    assert entry["reduced"] == [] and "MLE 01" in entry["source"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    cfg = spec.load_json(os.path.join(REPO, entry["file"]))
    for key in ("source", "deployment", "data", "label", "pipeline",
                "fit_math", "correct", "assumed", "precision", "conf",
                "guarantees", "reduced"):
        assert key in cfg, key
    assert cfg["name"] == "mle01_als" and cfg["reduced"] == []
    assert cfg["data"] == {"generator": "movielens", "rows": 25_000_095,
                           "users": 162_541, "items": 59_047}
    stage, = cfg["pipeline"]
    assert (stage["module"], stage["class"]) == (
        "sml_tpu.ml.recommendation", "ALS")
    assert stage["params"] == {
        "userCol": "userId", "itemCol": "movieId", "ratingCol": "rating",
        "rank": 12, "maxIter": 5, "regParam": 0.1, "seed": 42,
        "coldStartStrategy": "drop"}
    for key, value in stage["params"].items():
        if key != "coldStartStrategy":
            assert cfg["fit_math"][key] == value, key
    from sml_tpu.ml.recommendation import ALS
    est = ALS()
    for key in ("nonnegative", "implicitPrefs"):
        assert cfg["fit_math"][key] == est.getOrDefault(key), key
    limits = cfg["correct"]
    assert set(limits["reasons"]) == set(limits) - {"sample_rows", "reasons"}
    assert limits["rmse_ratio_max"] <= 0.9 and limits["h2d_arrays_max"] == 1.2
    assert any("MovieLens 1M" in a for a in cfg["assumed"])
    assert len(cfg["guarantees"]) >= 3
