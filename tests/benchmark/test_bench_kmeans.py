"""The clustering cell (`mle02_kmeans.fit_kmeans`, kind `fit_kmeans`) on the
CPU at a tiny size: a sound run is correct against the float64 reference;
every line of the check fails on a control of its own (bfloat16 products, a
seeding that reads k rows, a loop that stops at 3, a step that skips a
block, another tie rule); a program without blocks is refused before the
table is made; the reference finds planted clusters; the readers read what
the program adds and nothing on a program without it; and BENCHMARK.json
holds the cell and its entries appended to what was there."""

import json
import os
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
from benchmark.harness import program, runner, spec, xplane
from benchmark.reference import kmeans

REPO = bench_tiny.REPO
BENCH = spec.load_benchmark(REPO)
CELL = "mle02_kmeans.fit_kmeans"
BEFORE = ["ml11_xgb.fit", "ml07_rf.fit", "ml11_xgb_4chip.fit_sharded",
          "mle03_logreg.fit_logistic", "mle03_logreg_cv.fit_cv",
          "mle01_als.fit_als"]
TINY = "tiny_kmeans.tiny_fit_kmeans"
TINY_K = 24
METRICS = os.path.join(REPO, "benchmark", "layer_metrics")
NEW = ["fit.device.kmeans.init_s", "fit.device.kmeans.assign_s",
       "fit.device.kmeans.update_s", "fit.host.kmeans.init_s",
       "kmeans.iterations_per_fit", "kmeans.lloyd_roofline"]
JOINED = ["staging.h2d_bytes_per_fit", "fit.device_busy_s",
          "compile.backend_s", "compile.in_window", "fit.host.featurize_s",
          "fit.host.stage_s", "fit.host.dispatch_s", "fit.host.device_wait_s",
          "fit.host.readback_s", "fit.host.observe_s",
          "fit.host.unattributed_s", "fit.host.stage.key_s",
          "fit.host.stage.pad_s", "fit.host.stage.put_s",
          "fit.host.featurize.jobs_s", "fit.host.featurize.block_s",
          "fit.host.featurize.cpu_s", "setup.before_program_s",
          "setup.import_s", "setup.table_s", "setup.split_s",
          "setup.warm_fit_s", "setup.first_dispatch_s"]
LINES = ("fit.assignment_vs_reference.disagree_share",
         "fit.assignment.near_ties", "fit.training_cost.rel_gap",
         "fit.lloyd_step.center_err.max", "fit.lloyd_step.clusters_compared",
         "fit.lloyd_step.count_gap.max",
         "fit.seeding_cost_vs_reference.ratio", "fit.cost_vs_one_center.ratio",
         "fit.early_stop.rows_moving.share", "kmeans.fits_per_fit",
         "kmeans.iterations_per_fit", "kmeans.rows",
         "kmeans.init.rounds_per_fit", "kmeans.init.candidates_per_fit",
         "kmeans.empty_clusters_per_fit", "fit.plan_fits_per_fit",
         "fit.plan_declined", "fit.h2d_blocks_per_fit",
         "all.route_device_share_pct", "all.compile_requests_in_window")

@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """`bench_tiny`'s copy with the deployment added at 30,000 rows and 24
    centers, as new files and entries."""
    root, bench = bench_tiny.make_tiny_root(tmp_path_factory.mktemp("km"))

    def write(rel, text):
        path = os.path.join(root, rel)
        assert not os.path.exists(path), f"{rel} would edit an existing file"
        with open(path, "w") as f:
            f.write(text if isinstance(text, str) else json.dumps(text))

    cfg = spec.load_json(os.path.join(root,
                                      "benchmark/configs/mle02_kmeans.json"))
    cfg.update(name="tiny_kmeans", reduced=["data", "pipeline"])
    cfg["data"].update(rows=30000)
    cfg["pipeline"][-1]["params"].update(k=TINY_K)
    cfg["fit_math"].update(k=TINY_K)
    # the CPU mesh's eight shards pad a split of 24,000 rows further than
    # one chip pads 6.4 M
    cfg["correct"].update(sample_rows=1500, h2d_blocks_max=1.3,
                          # 24 centers' seeding costs scatter more than
                          # 1000's: two independent draws of it may differ
                          # by half (a seeding that reads k rows: 100 x)
                          seeding_cost_ratio_max=3.0, step_clusters_min=8)
    write("benchmark/configs/tiny_kmeans.json", cfg)
    traffic = spec.load_json(os.path.join(
        root, "benchmark/traffic/fit_kmeans.json"))
    traffic.update(warm_iterations=1)
    write("benchmark/traffic/tiny_fit_kmeans.json", traffic)
    bench["configs"].append({
        "name": "tiny_kmeans", "source": "test fixture", "why": "tiny",
        "reduced": ["data"], "file": "benchmark/configs/tiny_kmeans.json"})
    bench["workloads"].append({
        "name": TINY, "config": "tiny_kmeans", "traffic": "tiny_fit_kmeans",
        "chips": 1, "why": "tiny clustering cell for the CPU tests"})
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
def patched(monkeypatch):
    """`patched(name, fn)`: `clustering.<name>` replaced, with the programs
    traced before forgotten on the way in and out."""
    from sml_tpu.ml import clustering

    def patch(name, fn):
        monkeypatch.setattr(clustering, name, fn)
        clustering.forget_programs()
    yield patch
    monkeypatch.undo()
    clustering.forget_programs()


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
    assert "walked its rows by blocks" in out
    assert 1 <= _observed(checks["kmeans.iterations_per_fit"]) <= 20
    assert _observed(checks["fit.assignment_vs_reference.disagree_share"]) \
        < 1e-3
    assert _observed(checks["fit.lloyd_step.center_err.max"]) < 1e-2
    assert _observed(checks["fit.training_cost.rel_gap"]) < 1e-4
    assert 0.3 < _observed(checks["fit.seeding_cost_vs_reference.ratio"]) < 3
    assert 0.95 < _observed(checks["fit.h2d_blocks_per_fit"]) < 1.3


def test_a_traced_run_reports_the_counter_fed_layers(tiny):
    line = drive(tiny, seed=2**31 + 611, trace=True)
    assert line["correct"] is True
    # no device plane on the CPU: the trace-fed and span-fed readers, the
    # new ones too, find nothing to read and are left out
    assert set(line["metrics"]) == {
        "staging.h2d_bytes_per_fit", "compile.backend_s", "compile.in_window",
        "kmeans.iterations_per_fit"}
    assert 1 <= line["metrics"]["kmeans.iterations_per_fit"]["value"] <= 20
    assert line["metrics"]["compile.in_window"]["value"] == 0.0


# --------------------------------------------------------------- controls
def test_bfloat16_products_fail_the_step_and_the_cost(tiny, capsys, patched):
    patched("_product_operand", lambda a: jax.lax.reduce_precision(a, 8, 7))
    line = drive(tiny, seed=21)
    out = capsys.readouterr().out
    assert line["correct"] is False
    assert "fit.lloyd_step.center_err.max" in _failed(out), out
    assert _observed(_checks(out)["fit.lloyd_step.center_err.max"]) > 10


def test_a_seeding_that_reads_k_rows_fails_the_seeding_line(
        tiny, capsys, patched):
    """The first k rows under k-means||'s name: what a seeding that does
    not read the table gives."""
    from sml_tpu.ml import clustering

    def first_rows(Xt, live, origin, shard_lo, n, key, k, steps, shards):
        rows = jnp.arange(k, dtype=jnp.int32)
        return clustering._global_rows(Xt, rows, origin, shard_lo).T, \
            jnp.int32(1 + 2 * steps * k)
    patched("_parallel_seeding", first_rows)
    line = drive(tiny, seed=22)
    out = capsys.readouterr().out
    assert line["correct"] is False
    # (rows far from all of such centers are beyond float32's grain of
    # their own squares, so the step's lines may fail with it)
    assert "fit.seeding_cost_vs_reference.ratio" in _failed(out), out
    assert _observed(
        _checks(out)["fit.seeding_cost_vs_reference.ratio"]) > 10


def test_a_loop_that_stops_at_3_fails_the_early_stop_line(
        tiny, capsys, monkeypatch):
    from sml_tpu.ml.clustering import KMeans
    real = KMeans._fit

    def short(self, df):
        asked = self.getOrDefault("maxIter")
        self._set(maxIter=min(asked, 3))
        try:
            return real(self, df)
        finally:
            self._set(maxIter=asked)
    monkeypatch.setattr(KMeans, "_fit", short)
    line = drive(tiny, seed=23)
    out = capsys.readouterr().out
    assert line["correct"] is False
    assert _failed(out) == ["fit.early_stop.rows_moving.share"], out


def test_a_step_that_skips_a_block_fails_the_step_lines(
        tiny, capsys, patched):
    from sml_tpu.ml import clustering
    real = clustering._walk

    def skipping(Xt, origin, block, body, carry):
        rows = Xt.shape[1]
        if rows > block:     # all but the last block
            Xt = Xt[:, :rows - rows % block - (block if rows % block == 0
                                               else 0)]
        return real(Xt, origin, block, body, carry)
    patched("_block_rows", lambda width: 1024)
    patched("_walk", skipping)
    line = drive(tiny, seed=24)
    out = capsys.readouterr().out
    assert line["correct"] is False
    failed = _failed(out)
    assert "fit.lloyd_step.count_gap.max" in failed, out
    assert "kmeans.rows" in failed, out


def test_another_tie_rule_fails_the_count_line(tiny, patched):
    """Centers that coincide, to the bit: the LOWER index takes the rows in
    the program as in the reference, and the count line's arithmetic reads
    0; where the HIGHEST takes them it reads the rows of the largest
    doubled cluster. (No seeding gives such centers on the cell's table:
    the line is held here, on centers made to coincide.)"""
    from sml_tpu.ml import clustering
    kind = runner.load_module(os.path.join(
        tiny[0], "benchmark", "kinds", "fit_kmeans.py"), "bench_kind_km")
    limit = spec.load_json(os.path.join(
        REPO, "benchmark/configs/mle02_kmeans.json"))[
            "correct"]["step_count_gap_max"]
    X, centers, _ = _planted(np.random.default_rng(5), n=4000, d=6, k=8)
    X = X.astype(np.float32).astype(np.float64)
    doubled = np.concatenate([X[:8], X[2:5]]).astype(np.float32)
    Xt = np.ascontiguousarray(X.T, np.float32)
    origin = X.mean(axis=0).astype(np.float32)
    counts = kmeans.lloyd_step(X, doubled.astype(np.float64))["counts"]

    def sizes():
        served = clustering._assign(Xt, doubled, origin, True)
        return np.bincount(served, minlength=len(doubled))
    assert kind.count_gap(sizes(), counts) == 0.0

    def last_min(score):
        k = score.shape[0]
        return (k - 1 - jnp.argmin(score[::-1], axis=0)).astype(jnp.int32)
    patched("_first_min", last_min)
    # (the line takes the gap over the share of tied rows, 1 at most)
    assert kind.count_gap(sizes(), counts) > 2 * limit


# ---------------------------------------------------- the probe's refusal
def test_a_program_without_blocks_is_refused_before_the_table(
        tiny, capsys, monkeypatch):
    """The parent's shape: the distances of the whole table at once, no
    blocks and no rounds to count. Set-up raises what `runner.main` answers
    with exit code 2, and no table was made."""
    from sml_tpu.utils.profiler import PROFILER
    real = PROFILER.count

    def count(name, value=1):
        if not name.startswith("kmeans."):
            real(name, value)
    monkeypatch.setattr(PROFILER, "count", count)
    with pytest.raises(spec.SpecError, match="by blocks of rows"):
        drive(tiny, seed=5)
    assert "table made" not in capsys.readouterr().out


def test_an_estimator_without_the_parameters_is_refused_too(tiny, capsys):
    """The parent's estimator: `KMeans(initSteps=...)` is a TypeError."""
    def build(cfg):
        raise TypeError("KMeans.__init__() got an unexpected keyword "
                        "argument 'initSteps'")
    shim = types.SimpleNamespace(**{k: getattr(program, k)
                                    for k in dir(program)
                                    if not k.startswith("__")})
    shim.build_pipeline = build
    with pytest.raises(spec.SpecError, match="does not take"):
        drive(tiny, seed=6, stand_in=shim)
    assert "table made" not in capsys.readouterr().out


def test_the_command_answers_a_refusal_with_exit_code_2(tiny, monkeypatch,
                                                        capsys):
    def refuse(*a, **k):
        raise spec.SpecError("does not build a Lloyd step by blocks")
    monkeypatch.setattr(runner, "run", refuse)
    assert runner.main(tiny[0], TINY, 1, 1.0, False, time.perf_counter()) == 2
    assert "by blocks" in capsys.readouterr().err


# ------------------------------------------------------------ the reference
def _planted(rng, n=3000, d=4, k=5, spread=0.5):
    centers = rng.normal(0, 20, (k, d))
    idx = rng.integers(k, size=n)
    return centers[idx] + rng.normal(0, spread, (n, d)), centers, idx


def test_the_reference_finds_planted_clusters():
    X, centers, idx = _planted(np.random.default_rng(0))
    seeded = kmeans.kmeans_parallel(X, 5, 2, seed=1)
    assert 5 <= seeded["candidates"] <= 1 + 2 * 40
    fitted, steps = kmeans.lloyd(X, seeded["centers"], 20, 1e-4)
    assert steps < 20
    gaps = np.sqrt(((fitted[:, None] - centers[None]) ** 2).sum(-1))
    assert gaps.min(axis=0).max() < 0.1
    assert kmeans.cost(X, fitted) < 1.05 * kmeans.cost(X, centers)


def test_the_reference_blocks_add_up_and_the_lower_index_wins_a_tie(
        monkeypatch):
    X, centers, _ = _planted(np.random.default_rng(1))
    doubled = np.concatenate([centers, centers[:2]])
    whole = kmeans.lloyd_step(X, doubled)
    monkeypatch.setattr(kmeans, "BLOCK_ROWS", 37)
    blocked = kmeans.lloyd_step(X, doubled)
    np.testing.assert_array_equal(whole["assignment"], blocked["assignment"])
    np.testing.assert_allclose(whole["centers"], blocked["centers"],
                               rtol=1e-12)
    assert (whole["counts"][5:] == 0).all()
    np.testing.assert_array_equal(whole["centers"][5:], centers[:2])
    direct = ((X[:, None, :] - doubled[None]) ** 2).sum(-1)
    np.testing.assert_array_equal(whole["assignment"], direct.argmin(axis=1))
    assert whole["cost"] == pytest.approx(direct.min(axis=1).sum(), rel=1e-12)


def test_the_references_bfloat16_step_is_not_its_float64_step():
    # tight clusters far apart: a cluster's rows round the same way
    X, centers, _ = _planted(np.random.default_rng(2), spread=0.01)
    start = centers + 1.0
    exact = kmeans.lloyd_step(X, start)
    rounded = kmeans.lloyd_step(X, start, round_to="bfloat16")
    errors = kmeans.center_errors(exact, rounded["centers"], X)
    assert errors.max() > 10
    assert kmeans.center_errors(exact, exact["centers"], X).max() == 0.0
    # a cluster of one row has no radius: float32's grain is its unit
    lone = kmeans.lloyd_step(np.concatenate([X, [[9e4, 0, 0, 0]]]),
                             np.concatenate([start, [[9e4, 0, 0, 0]]]))
    off = lone["centers"].copy()
    off[5, 0] *= 1 + 2.0 ** -24
    assert 0 < kmeans.center_errors(lone, off, np.concatenate(
        [X, [[9e4, 0, 0, 0]]]))[5] < 1


def test_the_control_seedings_cost_more():
    rng = np.random.default_rng(3)
    X = np.concatenate([c + rng.normal(0, 0.3, (int(n), 3)) for c, n in zip(
        rng.normal(0, 30, (40, 3)), 4000 / np.arange(1, 41))])
    own = kmeans.cost(X, kmeans.kmeans_parallel(X, 40, 2, seed=4)["centers"])
    assert kmeans.cost(X, kmeans.random_rows(X, 40, 4)) > 2 * own
    assert kmeans.cost(X, kmeans.sampled_kmeans_pp(X, 40, 4, sample=80)) \
        > 1.2 * own


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
        cell="kmeans.no_trace_file", config={}, traffic={}, seconds=1.0,
        facts={"fits": fits, "fit_rows": [1000] * fits, "kmeans_k": 50,
               "kmeans_d": 42},
        counters_start={k: 0.0 for k in counters}, counters_end=counters,
        compiles=None, device={"platform": "tpu", "kind": kind},
        program=None, trace=trace)


def _two_fits():
    """Two fits on one plane. Each: the seeding [100, 300), a block's
    distances [300, 500) and its sums [500, 650) inside the loops, the
    cost pass [650, 700)."""
    block = "while/body/closed_call/while/body/closed_call/"
    ops = []
    for t in (0.0, 2000.0):
        ops += [(n, a + t, b + t) for n, a, b in [
            (_hlo("fusion.1", "kmeans.init/while/body/closed_call"),
             100.0, 300.0),
            (_hlo("fusion.2", block + "kmeans.assign"), 300.0, 500.0),
            (_hlo("fusion.3", block + "kmeans.update"), 500.0, 650.0),
            (_hlo("fusion.4", "kmeans.cost/while/body/closed_call"),
             650.0, 700.0)]]
    notes = [("bench.window", 0.0, 4000.0), ("bench.fit", 50.0, 1100.0),
             ("bench.split", 1100.0, 2000.0), ("bench.fit", 2050.0, 3100.0)]
    return xplane.Trace([ops], notes)


def test_the_scopes_are_read_at_any_depth():
    run = _reading(_two_fits(), counters={"kmeans.iterations": 40.0})
    assert _reader("fit.device.kmeans.init_s").read(run) == \
        pytest.approx(200e-9)
    # the cost pass is an assignment: counted with them
    assert _reader("fit.device.kmeans.assign_s").read(run) == \
        pytest.approx(250e-9)
    assert _reader("fit.device.kmeans.update_s").read(run) == \
        pytest.approx(150e-9)
    assert _reader("kmeans.iterations_per_fit").read(run) == 20.0


def test_the_roofline_is_the_operations_the_steps_need():
    """By hand: a step is a multiply and an add a coordinate a (row,
    center) pair, twice (the distances, the sums): 4 x rows x d x k,
    whatever precision or passes implement it."""
    work = runner.load_module(os.path.join(METRICS, "_kmeans_work.py"), "w")
    assert work.lloyd_flops(rows=1000, d=42, k=50, iterations=20) \
        == 20 * (2 * 1000 * 42 * 50 + 2 * 1000 * 50 * 42)
    assert work.lloyd_flops(6_400_000, 42, 1000, 1) == 1.0752e12
    with pytest.raises(KeyError, match="no peak"):
        work.peak_flops("cpu")
    run = _reading(_two_fits(), counters={"kmeans.iterations": 40.0})
    share = _reader("kmeans.lloyd_roofline").read(run)
    assert share == pytest.approx(
        100.0 * 20 * 4 * 1000 * 42 * 50 / (400e-9 * 197e12))


def test_the_host_span_is_read_from_the_recorders_totals():
    counters = {"span_n.fit": 2.0, "span_s.fit": 9.0,
                "span_n.kmeans.init.local": 2.0,
                "span_s.kmeans.init.local": 0.5}
    run = _reading(_two_fits(), counters=counters)
    assert _reader("fit.host.kmeans.init_s").read(run) == pytest.approx(0.25)


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
    assert names == BEFORE + [CELL]
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    cell = spec.workload(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "mle02_kmeans", "fit_kmeans", 1)
    assert len(cell["why"]) <= 200
    assert BENCH["run_seconds"] == 51
    assert [c["name"] for c in BENCH["configs"]][6] == "mle02_kmeans"
    parts = spec.resolve(REPO, BENCH, CELL)
    assert parts["traffic"]["kind"] == "fit_kmeans"
    assert parts["traffic"]["warm_iterations"] == 2
    assert parts["traffic"]["fractions"] == [0.8, 0.2]
    assert set(parts["readers"]) == set(NEW) | set(JOINED)


def test_the_new_entries_are_the_last_six_in_their_order():
    tail = BENCH["per_layer"][-6:]
    assert [m["name"] for m in tail] == NEW
    for entry in tail:
        assert entry["workloads"] == [CELL] and entry["moves"] == "fit_s"
        assert entry["layer"] == "clustering fit programs"
        assert set(entry) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
        assert os.path.isfile(os.path.join(METRICS, entry["name"] + ".py"))
    roofline = tail[-1]
    assert (roofline["unit"], roofline["better"], roofline["source"]) == (
        "%", "higher", "device_trace")


@pytest.mark.parametrize("name", ["fit_s"] + JOINED)
def test_an_accepted_list_is_only_appended_to(name):
    entry, = [m for g in ("end_to_end", "per_layer") for m in BENCH[g]
              if m["name"] == name]
    cells = entry["workloads"]
    old = [c for c in cells if c in BEFORE]
    assert cells[:len(old)] == old == [c for c in BEFORE if c in old]
    assert cells[len(old):] == [CELL]


def test_no_other_accepted_list_gained_the_cell():
    joined = set(NEW) | set(JOINED) | {"fit_s"}
    for group in ("end_to_end", "per_layer"):
        for m in BENCH[group]:
            if m["name"] not in joined:
                assert CELL not in m.get("workloads", [CELL + "?"]), m["name"]
    bounds = {m["name"]: m["bound"] for m in BENCH["end_to_end"]}
    assert bounds == {"fit_s": 0.05, "setup_s": 0.1}


def test_the_configuration_is_mllibs_estimator_at_the_papers_shape():
    entry = spec.config_entry(BENCH, "mle02_kmeans")
    assert entry["reduced"] == [] and "Scalable K-Means++" in entry["source"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    cfg = spec.load_json(os.path.join(REPO, entry["file"]))
    for key in ("source", "deployment", "data", "label", "pipeline",
                "fit_math", "correct", "assumed", "precision", "conf",
                "guarantees", "reduced"):
        assert key in cfg, key
    assert cfg["name"] == "mle02_kmeans" and cfg["reduced"] == []
    assert cfg["data"]["generator"] == "kddcup"
    assembler, estimator = cfg["pipeline"]
    assert (assembler["class"], len(assembler["params"]["inputCols"])) == (
        "VectorAssembler", 42)
    assert (estimator["module"], estimator["class"]) == (
        "sml_tpu.ml.clustering", "KMeans")
    assert estimator["params"] == {
        "k": 1000, "maxIter": 20, "tol": 1e-4, "initMode": "k-means||",
        "initSteps": 2, "seed": 221}
    for key, value in estimator["params"].items():
        assert cfg["fit_math"][key] == value, key
    # MLlib's documented defaults are the estimator's own
    from sml_tpu.ml.clustering import KMeans
    est = KMeans()
    for key in ("maxIter", "tol", "initMode", "initSteps"):
        assert est.getOrDefault(key) == estimator["params"][key], key
    limits = cfg["correct"]
    assert set(limits["reasons"]) == set(limits) - {"sample_rows", "reasons"}
    assert limits["h2d_blocks_max"] == 1.2
    assert any("4,898,431" in a for a in cfg["assumed"])
    assert len(cfg["guarantees"]) >= 4


def test_the_generator_makes_the_same_table_from_the_same_seed():
    data = runner.load_module(
        os.path.join(REPO, "benchmark", "data", "kddcup.py"), "bench_data")
    a = data.make({"rows": 20001}, 2**31 + 5)
    b = data.make({"rows": 20001}, 2**31 + 5)
    c = data.make({"rows": 20001}, 2**31 + 6)
    assert a.shape == (20001, 42) and list(a.columns) == data.COLUMNS
    assert (a.dtypes == np.float64).all()
    assert a.equals(b) and not a.equals(c)
    heavy = a[data.HEAVY].to_numpy()
    assert heavy.max() > 1e5 and (heavy == np.rint(heavy)).all()
    assert a[data.COUNTS].to_numpy().max() <= 511
    rates = a[data.RATES].to_numpy()
    assert rates.min() >= 0 and rates.max() <= 1
    assert np.allclose(rates * 100, np.rint(rates * 100))
    assert set(np.unique(a[data.FLAGS].to_numpy())) <= {0.0, 1.0}
