"""The four-chip deployment (`ml11_xgb_4chip.fit_sharded`, kind
`fit_sharded`) on the CPU's virtual devices at a tiny size: a sound run with
`num_workers=4` is correct against the float64 references, runs whose layout
or rows were quietly changed are NOT, the shards add up to the table in plain
NumPy, the new reader finds the nested scope, and BENCHMARK.json is still
what PR 25 and PR 26 accepted with this PR's entries appended to it."""

import json
import os
import time
import types

import numpy as np
import pytest

import bench_tiny
from benchmark.harness import program, runner, spec, xplane
from benchmark.reference import columnwise, fitcheck, precision

REPO = bench_tiny.REPO
BENCH = spec.load_benchmark(REPO)
CELL = "ml11_xgb_4chip.fit_sharded"
TINY = {4: "tiny_xgb_4chip.tiny_fit_sharded", 1: "tiny_xgb_1shard.tiny_fit_sharded"}


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """`bench_tiny`'s copy with the deployment added at 12,000 rows, as new
    files and entries: the configuration with `num_workers=4`, the same one
    on one shard (the layout a one-chip cell has), a traffic mix of kind
    `fit_sharded`."""
    root, bench = bench_tiny.make_tiny_root(tmp_path_factory.mktemp("sharded"))

    def write(rel, obj):
        path = os.path.join(root, rel)
        assert not os.path.exists(path), f"{rel} would edit an existing file"
        with open(path, "w") as f:
            json.dump(obj, f, indent=1)

    src = spec.load_json(os.path.join(root, "benchmark/configs/ml11_xgb_4chip.json"))
    traffic = spec.load_json(os.path.join(root, "benchmark/traffic/fit_sharded.json"))
    traffic.update(warm_iterations=1, fractions=[0.7, 0.3])
    write("benchmark/traffic/tiny_fit_sharded.json", traffic)
    for shards, cell in TINY.items():
        name = cell.split(".")[0]
        cfg = bench_tiny._tiny_config(
            src, name, {"n_estimators": 4, "max_depth": 3, "max_bins": 40,
                        "learning_rate": 0.5, "num_workers": shards},
            {"n_bins": 40})
        cfg["correct"]["shards"] = shards
        write(f"benchmark/configs/{name}.json", cfg)
        bench["configs"].append({
            "name": name, "source": "test fixture", "why": "tiny",
            "reduced": ["data", "pipeline"],
            "file": f"benchmark/configs/{name}.json"})
        bench["workloads"].append({
            "name": cell, "config": name, "traffic": "tiny_fit_sharded",
            "chips": shards, "why": "tiny sharded cell for the CPU tests"})
        for group in ("end_to_end", "per_layer"):
            for m in bench[group]:
                if CELL in m.get("workloads", []):
                    m["workloads"].append(cell)
    assert spec.validate(root, bench) == []
    return root, bench


def drive(tiny, cell, seed, stand_in=None, trace=False):
    root, bench = tiny
    return runner.run(root, cell, seed, 1.5, trace, time.perf_counter(),
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


# --------------------------------------------------------- (a), (c): sound
@pytest.mark.parametrize("shards", [4, 1])
def test_sound_run_is_correct_at_four_shards_and_at_one(tiny, shards, capsys):
    """The fitted tables at 4 shards and at 1 pass the SAME reference
    limits (both configurations carry ml11_xgb's `correct`), and each run
    holds the layout its configuration states."""
    line = drive(tiny, TINY[shards], seed=2**31 + 500 + shards)
    out = capsys.readouterr().out
    checks = _checks(out)
    assert line["correct"] is True, out
    assert line["device"] == {"platform": "cpu", "kind": "cpu",
                              "count": shards, "memory_peak_bytes": 0}
    assert line["metrics"]["fit_s"]["value"] > 0
    assert f"names devices {list(range(shards))}" in out
    for name in ("fit.predictions_vs_descent.rel_gap_max",
                 "fit.split_gain_gap.median", "fit.leaf_value_err.median",
                 "fit.cover_gap.max", "fit.shards", "fit.shard_rows_max",
                 "all.route_device_share_pct", "all.kernel.fallback"):
        assert ": PASS" in checks[name], checks[name]
    assert f"observed={float(shards)!r} limit={float(shards)!r}" \
        in checks["fit.shards"]
    # 8,400 rows a fit: one shard holds them all, four a padded quarter
    observed = float(checks["fit.shard_rows_max"].split("observed=")[1]
                     .split()[0])
    assert 8400 / shards * 0.97 <= observed <= 8400 / shards * 1.125 + shards


def test_a_traced_sharded_run_reports_the_counter_fed_layers(tiny):
    line = drive(tiny, TINY[4], seed=2**31 + 611, trace=True)
    assert line["correct"] is True
    # no device plane on the CPU: every trace-fed reader, the new one too,
    # finds nothing to read and is left out
    assert set(line["metrics"]) == {"staging.h2d_bytes_per_fit",
                                    "compile.backend_s", "compile.in_window"}


# ------------------------------------------------------- (b): the controls
def test_a_fit_that_saw_one_shards_rows_is_not_correct(tiny, capsys):
    """The timed path broken underneath: three of the four shards' rows
    never reach the histograms."""
    class OneShard:
        def __init__(self, pipeline):
            self._pipeline = pipeline

        def fit(self, frame):
            return self._pipeline.fit(frame.limit(frame.count() // 4))

    line = drive(tiny, TINY[4], seed=2**31 + 701, stand_in=stand_in(
        build_pipeline=lambda config: OneShard(program.build_pipeline(config))))
    checks = _checks(capsys.readouterr().out)
    assert line["correct"] is False
    assert ": FAIL" in checks["fit.cover_gap.max"]
    assert ": PASS" in checks["fit.shards"]       # the layout was kept


def test_a_table_left_on_one_device_is_not_correct(tiny, capsys):
    """The layout quietly replaced: the same estimator without its
    `num_workers`, fitted on a one-device mesh. The model is as good; the
    deployment is another one, and both layout lines say so."""
    from sml_tpu.parallel import mesh as meshlib

    class OneDevice:
        def __init__(self, pipeline):
            self._pipeline = pipeline

        def fit(self, frame):
            with meshlib.use_mesh_local(meshlib.worker_mesh(1)):
                return self._pipeline.fit(frame)

    def build(config):
        config = json.loads(json.dumps(config))
        del config["pipeline"][-1]["params"]["num_workers"]
        return OneDevice(program.build_pipeline(config))

    line = drive(tiny, TINY[4], seed=2**31 + 702,
                 stand_in=stand_in(build_pipeline=build))
    checks = _checks(capsys.readouterr().out)
    assert line["correct"] is False
    assert ": FAIL observed=1.0 limit=4.0" in checks["fit.shards"]
    assert ": FAIL" in checks["fit.shard_rows_max"]
    assert ": PASS" in checks["fit.cover_gap.max"]
    assert ": PASS" in checks["fit.leaf_value_err.median"]


def test_a_bfloat16_descent_is_not_correct(tiny, capsys):
    def predictions(model, df):
        return precision.round_to(program.predictions(model, df), "bfloat16")

    line = drive(tiny, TINY[4], seed=2**31 + 703,
                 stand_in=stand_in(predictions=predictions))
    checks = _checks(capsys.readouterr().out)
    assert line["correct"] is False
    assert ": FAIL" in checks["fit.predictions_vs_descent.rel_gap_max"]
    assert ": PASS" in checks["fit.shards"]


def test_a_program_that_cannot_state_the_layout_is_refused_at_once(
        tiny, monkeypatch, capsys):
    """What the parent commit does with this cell: it accepts `num_workers`
    and reads it nowhere. Refused before the table is made, exit code 2."""
    from sml_tpu.parallel import mesh as meshlib
    monkeypatch.delattr(meshlib, "worker_mesh")
    with pytest.raises(spec.SpecError, match="num_workers=4"):
        drive(tiny, TINY[4], seed=1)
    assert "table made" not in capsys.readouterr().out


# --------------------------------- (c): the shards tied to the whole table
def test_the_four_shards_partial_histograms_add_up_to_the_tables():
    """Plain NumPy, float64: the table cut the way the program stages it
    (`bucket_rows` padding, four equal blocks, padding masked) gives four
    partial (grad, hess, count) histograms a node whose sum is the whole
    table's: what the all-reduce hands every chip."""
    from sml_tpu.parallel import mesh as meshlib
    rng = np.random.default_rng(28)
    n, features, bins, nodes, shards = 8400, 10, 40, 4, 4
    binned = rng.integers(0, bins, size=(n, features))
    node = rng.integers(0, nodes, size=n)
    grad, hess = rng.normal(size=n), rng.uniform(0.5, 2.0, size=n)

    def histogram(rows):
        out = np.zeros((nodes, features, bins, 3))
        for f in range(features):
            np.add.at(out, (node[rows], f, binned[rows, f]),
                      np.stack([grad[rows], hess[rows],
                                np.ones(len(rows))], axis=1))
        return out

    padded = meshlib.bucket_rows(n, shards)
    assert padded == 9216 and padded % shards == 0
    block = padded // shards
    parts = [histogram(np.arange(s * block, min((s + 1) * block, n)))
             for s in range(shards)]
    whole = histogram(np.arange(n))
    assert len(parts) == 4 and all(p[..., 2].sum() > 0 for p in parts)
    assert np.array_equal(sum(parts)[..., 2], whole[..., 2])   # counts: exact
    np.testing.assert_allclose(sum(parts), whole, rtol=0, atol=1e-11)
    assert whole[..., 2].sum() == n * features
    # the real table's shape: 6.4M rows over four chips is cell 1's 1.6M on one
    assert meshlib.bucket_rows(6_400_000, 4) == 4 * 1_703_936
    assert meshlib.bucket_rows(1_600_000, 1) == 1_703_936
    for rows in (6_398_720, 6_401_280):          # a split's +- 0.02 % of rows
        assert meshlib.bucket_rows(rows, 4) == 4 * 1_703_936


# ------------------------- the references at this size: column-wise descents
def _random_forest_tables(rng, trees, depth, features, n_bins):
    nodes = 2 ** (depth + 1) - 1
    sf = rng.integers(0, features, size=(trees, nodes))
    sf[:, 2 ** depth - 1:] = -1                  # the last level: leaves
    sf[rng.random(sf.shape) < 0.15] = -1         # and some that stop early
    return {"split_feature": sf, "depth": depth, "base": 0.25,
            "split_bin": rng.integers(0, n_bins - 1, size=(trees, nodes)),
            "leaf_value": rng.normal(size=(trees, nodes)),
            "tree_weight": np.full(trees, 0.3),
            "cover": rng.uniform(1, 9, size=(trees, nodes))}


@pytest.mark.parametrize("depth, dtype", [(1, np.uint8), (4, np.uint8),
                                          (7, np.uint8), (8, np.int64)])
def test_columnwise_descent_gives_the_plain_descents_nodes(depth, dtype):
    rng = np.random.default_rng(2800 + depth)
    bins = rng.integers(0, 64, size=(5000, 10)).astype(np.uint8)
    tables = _random_forest_tables(rng, 6, depth, 10, 64)
    for sf, sb in zip(tables["split_feature"], tables["split_bin"]):
        plain = fitcheck.node_paths(bins, sf, sb, depth)
        quick = columnwise.node_paths(bins, sf, sb, depth)
        assert quick.dtype == dtype and plain.shape == quick.shape
        assert np.array_equal(plain, quick)
    # another table of the same shape is transposed anew, not served stale
    other = bins[::-1].copy()
    assert np.array_equal(fitcheck.node_paths(other, sf, sb, depth),
                          columnwise.node_paths(other, sf, sb, depth))


@pytest.mark.parametrize("boosting", [True, False])
def test_the_fit_statistics_are_the_same_to_the_bit_either_way(boosting):
    """What `fit_sharded.check` swaps is the descent alone: every
    statistic, sampled node and sampled leaf of `fit_statistics` is equal,
    and `fitcheck` is itself again afterwards."""
    rng = np.random.default_rng(2828)
    bins = rng.integers(0, 40, size=(20000, 10))
    y = rng.normal(size=20000)
    tables = _random_forest_tables(rng, 9, 4, 10, 40)
    math = {"boosting": boosting, "reg_lambda": 1.0, "gamma": 0.0,
            "min_instances": 1.0, "n_bins": 40}
    kw = dict(n_trees=3, nodes_per_tree=5, leaves_per_tree=6,
              leaf_only_trees=2)
    plain_descent = fitcheck.node_paths
    plain = fitcheck.fit_statistics(bins, y, tables, math, 7, **kw)
    with columnwise.descents():
        assert fitcheck.node_paths is columnwise.node_paths
        quick = fitcheck.fit_statistics(bins, y, tables, math, 7, **kw)
        control = fitcheck.fit_statistics(bins, y, tables, math, 7,
                                          precision="fp8_e4m3", **kw)
    assert fitcheck.node_paths is plain_descent
    assert plain["nodes"] == 15 and plain["leaves"] > 10
    assert quick == plain
    assert control == fitcheck.fit_statistics(bins, y, tables, math, 7,
                                              precision="fp8_e4m3", **kw)
    assert control != plain


def test_the_sharded_kinds_check_descends_column_wise(tiny, capsys,
                                                      monkeypatch):
    seen = []
    quick = columnwise.node_paths
    monkeypatch.setattr(columnwise, "node_paths",
                        lambda *a: seen.append(a[0].shape) or quick(*a))
    line = drive(tiny, TINY[4], seed=2**31 + 733)
    out = capsys.readouterr().out
    assert line["correct"] is True, out
    assert seen and len(set(seen)) == 1 and 8000 < seen[0][0] < 8800
    assert "the references took" in out
    assert fitcheck.node_paths.__module__ == fitcheck.__name__


# ------------------------------------------------ (d), (g): BENCHMARK.json
ACCEPTED_PR25 = ["staging.h2d_bytes_per_fit", "fit.device_busy_s",
                 "compile.backend_s", "compile.in_window"]
ACCEPTED_PR26 = [
    "fit.host.featurize_s", "fit.host.quantize_s", "fit.host.stage_s",
    "fit.host.dispatch_s", "fit.host.device_wait_s", "fit.host.readback_s",
    "fit.host.observe_s", "fit.host.unattributed_s", "fit.device.operand_s",
    "fit.device.hist_s", "fit.device.split_s", "fit.device.route_s",
    "fit.device.update_s", "fit.device.unscoped_s"]
LAYERS = {"staging.h2d_bytes_per_fit": "staging and quantize",
          "compile.backend_s": "compile and cache",
          "compile.in_window": "compile and cache",
          "fit.host.featurize_s": "featurize",
          "fit.host.quantize_s": "staging and quantize",
          "fit.host.stage_s": "staging and quantize",
          "fit.host.observe_s": "observability",
          "fit.host.unattributed_s": "pipeline fit"}


def test_the_benchmark_is_valid_and_has_the_four_chip_cell():
    assert spec.validate(REPO, BENCH) == []
    assert [w["name"] for w in BENCH["workloads"]] == [
        "ml11_xgb.fit", "ml07_rf.fit", CELL]
    assert [w["chips"] for w in BENCH["workloads"]] == [1, 1, 4]
    cell = spec.workload(BENCH, CELL)
    assert (cell["config"], cell["traffic"]) == ("ml11_xgb_4chip",
                                                 "fit_sharded")
    entry = spec.config_entry(BENCH, "ml11_xgb_4chip")
    assert entry["reduced"] == [] and "num_workers" in entry["source"]
    assert BENCH["run_seconds"] == 51
    reported = {m["name"] for g in ("end_to_end", "per_layer")
                for m in spec.metrics_for(BENCH, CELL, g)}
    assert reported == {"fit_s", "setup_s", "fit.device.allreduce_s"} \
        | set(ACCEPTED_PR25) | set(ACCEPTED_PR26)
    assert spec.resolve(REPO, BENCH, CELL)["traffic"] == {
        **spec.load_json(f"{REPO}/benchmark/traffic/fit.json"),
        "kind": "fit_sharded"}


def test_the_accepted_per_layer_entries_stand_and_are_only_appended_to():
    """`test_bench_fit_split.py`'s "only appended to", in the form that
    survives an append: the first eighteen entries are PR 25's four and
    PR 26's fourteen, by name and in order, each as it was accepted; the
    two cells they listed are a PREFIX of what they list now."""
    entries = BENCH["per_layer"]
    assert [m["name"] for m in entries[:18]] == ACCEPTED_PR25 + ACCEPTED_PR26
    for m in entries[:18]:
        name = m["name"]
        counter = name in ACCEPTED_PR25 and name != "fit.device_busy_s"
        assert m["layer"] == LAYERS.get(name, "tree fit programs")
        assert m["better"] == "lower"
        assert m["unit"] == ("bytes" if name.startswith("staging.") else
                             "count" if name == "compile.in_window" else "s")
        assert m["source"] == ("program_counter" if counter else
                               "program_span" if ".host." in name else
                               "device_trace")
        assert m["moves"] == ("setup_s" if name.startswith("compile.")
                              else "fit_s")
        if name.startswith("compile."):
            assert "workloads" not in m
        else:
            assert m["workloads"][:2] == ["ml11_xgb.fit", "ml07_rf.fit"]
    for m in entries:
        assert os.path.isfile(os.path.join(
            REPO, "benchmark", "layer_metrics", m["name"] + ".py"))
    assert entries[18:] == [{
        "name": "fit.device.allreduce_s", "unit": "s", "better": "lower",
        "source": "device_trace", "layer": "tree fit programs",
        "moves": "fit_s", "workloads": [CELL]}]
    fit_s = BENCH["end_to_end"][0]
    assert fit_s["name"] == "fit_s" and fit_s["bound"] == 0.05
    assert fit_s["workloads"][:2] == ["ml11_xgb.fit", "ml07_rf.fit"]
    assert BENCH["end_to_end"][1] == {
        "name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1,
        "source": "host_clock"}


def test_every_configuration_states_its_source_sizes_and_limits():
    """`test_bench_spec.py` holds every configuration to 2,000,000 rows;
    held here to the rows its own cell's `why` and `assumed` argue for."""
    rows = {"ml11_xgb": 2_000_000, "ml07_rf": 2_000_000,
            "ml11_xgb_4chip": 8_000_000}
    assert {c["name"] for c in BENCH["configs"]} == set(rows)
    for entry in BENCH["configs"]:
        cfg = spec.load_json(os.path.join(REPO, entry["file"]))
        assert cfg["name"] == entry["name"]
        assert sorted(cfg["reduced"]) == sorted(entry["reduced"]) == []
        for key in ("source", "deployment", "data", "label", "pipeline",
                    "fit_math", "correct", "assumed", "precision", "conf"):
            assert key in cfg, f"{entry['name']} lacks {key}"
        assert cfg["data"]["rows"] == rows[entry["name"]]
        assert cfg["correct"]["score_rtol"] == 1e-5


def test_the_deployment_is_ml11_but_for_its_layout_and_size():
    one = spec.load_json(f"{REPO}/benchmark/configs/ml11_xgb.json")
    four = spec.load_json(f"{REPO}/benchmark/configs/ml11_xgb_4chip.json")
    params = four["pipeline"][-1]["params"]
    assert params == {**one["pipeline"][-1]["params"], "num_workers": 4}
    assert four["pipeline"][:-1] == one["pipeline"][:-1]
    for key in ("label", "fit_math", "precision", "conf"):
        assert four[key] == one[key]
    assert four["data"] == {"generator": "airbnb", "rows": 8_000_000}
    assert four["correct"] == {**one["correct"], "shards": 4,
                               "shard_rows_max_ratio": 1.125}
    assert "2x2" in four["deployment"] and "sharded" in four["deployment"]
    assert four["conf"]["sml.tree.roundsPerDispatch"] == 0


# ------------------------------------------------------ (e): the new reader
def _reader():
    return runner.load_module(os.path.join(
        REPO, "benchmark", "layer_metrics", "fit.device.allreduce_s.py"),
        "bench_metric_allreduce")


def _hlo(name, scope=None, kind="fusion"):
    meta = f', metadata={{op_name="jit(tree_ensemble)/jit(main)/shard_map/' \
           f'while/body/{scope}/psum"}}' if scope else ""
    return f"%{name} = f32[8]{{0}} {kind}(f32[8]{{0}} %p){meta}"


def _reading(trace, fits, cell="sharded.no_trace_file"):
    return runner.Reading(
        cell=cell, config={}, traffic={}, seconds=1.0, facts={"fits": fits},
        counters_start={}, counters_end={}, compiles=None,
        device={"platform": "tpu"}, program=None, trace=trace)


def _two_planes():
    """Two fits on two device planes. Each plane: a while [300, 1000) over a
    dot [300, 600) under tree.hist and an all-reduce under
    tree.hist/tree.hist.allreduce, [600, 650) on plane 0 and [600, 750) on
    plane 1 (the chip that arrived early waits); in fit 2 an all-reduce
    [2400, 2500) on both. Outside every fit, one more [1500, 1600)."""
    def plane(wait):
        ar = _hlo("all-reduce.3", "tree.hist/tree.hist.allreduce", "all-reduce")
        return [(n, float(a), float(b)) for n, a, b in [
            ("%while.7 = (f32[8]) while((f32[8]) %t), body=%b", 300, 1000),
            (_hlo("fusion.2", "tree.hist"), 300, 600),
            (ar, 600, 600 + wait),
            (_hlo("fusion.4", "tree.split"), 800, 900),
            (ar, 1500, 1600),
            (_hlo("fusion.2", "tree.hist"), 2000, 2400),
            (ar, 2400, 2500)]]
    notes = [("bench.window", 0.0, 4000.0), ("bench.fit", 100.0, 1100.0),
             ("bench.split", 1100.0, 2000.0), ("bench.fit", 2000.0, 3000.0)]
    return xplane.Trace([plane(50), plane(150)], notes)


def test_allreduce_seconds_are_the_nested_scopes_over_planes_and_fits():
    read = _reader().read
    run = _reading(_two_planes(), fits=2)
    # plane 0: 50 + 100, plane 1: 150 + 100; over 2 planes and 2 fits
    assert read(run) == pytest.approx((150 + 250) / 2 / 2 * 1e-9)
    # nested in tree.hist: the accepted reader counts the same operations there
    hist = runner.load_module(os.path.join(
        REPO, "benchmark", "layer_metrics", "fit.device.hist_s.py"), "h").read
    assert hist(run) == pytest.approx((850 + 950) / 2 / 2 * 1e-9)
    assert read(run) <= hist(run)


def test_allreduce_reader_returns_nothing_where_there_is_nothing_to_read():
    read = _reader().read
    one_chip = xplane.Trace(
        [[(_hlo("fusion.2", "tree.hist"), 300.0, 600.0)]],
        [("bench.window", 0.0, 4000.0), ("bench.fit", 100.0, 1100.0)])
    assert read(_reading(one_chip, fits=1)) is None    # no all-reduce
    assert read(_reading(_two_planes(), fits=0)) is None
    assert read(_reading(None, fits=2)) is None                  # untraced
    assert read(_reading(xplane.Trace([], []), fits=2)) is None  # no device


def test_allreduce_scope_from_the_statistics_kept_with_an_operation():
    """As the v5e writes it: bare HLO text, the name stack in `tf_op`."""
    from jax.profiler import ProfileData
    text = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 500000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[2] fusion()"
    stats { metadata_id: 1 str_value: "jit(f)/while/body/tree.hist/dot" } } }
  event_metadata { key: 2 value { id: 2 name: "%psum.78 = f32[640,3] all-reduce()"
    stats { metadata_id: 1 str_value: "jit(f)/shard_map/while/body/closed_call/tree.hist/tree.hist.allreduce/psum" } } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 9000000 }
    events { metadata_id: 2 offset_ps: 500000 duration_ps: 8000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.fit" } }
}
'''
    import shutil
    cell = "allreduce_statistics.test_cell"
    work = os.path.join(REPO, runner.WORK_DIR, cell)
    trace_dir = os.path.join(work, "trace", "plugins", "profile", "t")
    os.makedirs(trace_dir, exist_ok=True)
    path = os.path.join(trace_dir, "host.xplane.pb")
    try:
        with open(path, "wb") as f:
            f.write(ProfileData.text_proto_to_serialized_xspace(text))
        run = _reading(xplane.Trace.from_file(path), fits=1, cell=cell)
        assert _reader().read(run) == pytest.approx(0.5e-6)
    finally:
        shutil.rmtree(work, ignore_errors=True)
