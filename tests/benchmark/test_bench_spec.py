"""BENCHMARK.json against its contract, and the data-driven lookup: every
cell resolves to files, and a later cell arrives as files and entries."""

import copy
import json
import os

import pytest

import bench_tiny
from benchmark.harness import spec

REPO = bench_tiny.REPO
BENCH = spec.load_benchmark(REPO)


def test_benchmark_json_has_no_fault():
    assert spec.validate(REPO, BENCH) == []
    assert len(json.dumps(BENCH)) < 64 * 1024
    assert 1 <= BENCH["run_seconds"] <= 51
    assert BENCH["command"] == ["python3", "benchmark/run.py"]


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves_to_its_files(cell):
    parts = spec.resolve(REPO, BENCH, cell)
    assert parts["config"]["name"] == parts["workload"]["config"]
    assert os.path.isfile(parts["kind_path"])
    assert parts["readers"], "a cell reports at least one per-layer metric"
    for path in parts["readers"].values():
        assert os.path.isfile(path)
    e2e = [m["name"] for m in spec.metrics_for(BENCH, cell, "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    for m in spec.metrics_for(BENCH, cell, "per_layer"):
        assert m["moves"] in e2e, f"{m['name']} moves a metric {cell} lacks"


@pytest.mark.parametrize("group", ["configs", "workloads", "end_to_end",
                                   "per_layer"])
def test_names_and_units_use_the_allowed_characters(group):
    for entry in BENCH[group]:
        assert spec.NAME_RE.match(entry["name"]), entry["name"]
        if "unit" in entry:
            assert spec.UNIT_RE.match(entry["unit"]), entry["unit"]
        for key in ("config", "traffic"):
            if key in entry:
                assert spec.NAME_RE.match(entry[key])
        allowed = {"configs": {"name", "source", "file", "reduced", "why"},
                   "workloads": {"name", "config", "traffic", "chips", "why"},
                   "end_to_end": {"name", "unit", "better", "bound",
                                  "source", "workloads"},
                   "per_layer": {"name", "unit", "better", "source", "layer",
                                 "moves", "workloads"}}[group]
        assert set(entry) <= allowed, set(entry) - allowed


def test_files_under_paths_are_named_from_allowed_characters():
    for base in BENCH["paths"]:
        for dirpath, dirs, files in os.walk(os.path.join(REPO, base)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(dirpath, f), REPO)
                assert all(c.isalnum() or c in "_.-/" for c in rel), rel


def test_configurations_state_source_sizes_and_limits():
    for entry in BENCH["configs"]:
        cfg = spec.load_json(os.path.join(REPO, entry["file"]))
        assert cfg["name"] == entry["name"]
        assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
        for key in ("source", "data", "label", "pipeline", "fit_math",
                    "correct", "assumed", "precision", "conf"):
            assert key in cfg, f"{entry['name']} lacks {key}"
        assert cfg["data"]["rows"] == 2_000_000
        assert cfg["correct"]["score_rtol"] == 1e-5
        for stage in cfg["pipeline"]:
            assert set(stage) == {"module", "class", "params"}


def test_the_courses_own_hyper_parameters():
    """ML 11's params dictionary and the largest point of ML 07's grid."""
    xgb = spec.load_json(f"{REPO}/benchmark/configs/ml11_xgb.json")
    assert xgb["pipeline"][-1]["params"] == {
        "n_estimators": 100, "learning_rate": 0.1, "max_depth": 4,
        "random_state": 42, "missing": 0, "max_bins": 64}
    rf = spec.load_json(f"{REPO}/benchmark/configs/ml07_rf.json")
    assert rf["pipeline"][-1]["params"] == {
        "labelCol": "price", "maxBins": 40, "maxDepth": 5, "numTrees": 10,
        "seed": 42}
    for cfg in (xgb, rf):
        params = cfg["pipeline"][-1]["params"]
        math = cfg["fit_math"]
        assert math["n_bins"] == params.get("max_bins", params.get("maxBins"))
        assert math["seed"] == params.get("random_state", params.get("seed"))


def _with(path, value):
    broken = copy.deepcopy(BENCH)
    node = broken
    for key in path[:-1]:
        node = node[key]
    node[path[-1]] = value
    return broken


@pytest.mark.parametrize("path,value,says", [
    (("end_to_end", 0, "name"), "fit s", "characters"),
    (("end_to_end", 0, "unit"), "seconds per fit", "unit"),
    (("end_to_end", 0, "bound"), 0.5, "bound"),
    (("end_to_end", 0, "source"), "program_counter", "source"),
    (("per_layer", 0, "moves"), "nothing", "moves unknown"),
    (("per_layer", 0, "workloads"), ["no.such_cell"], "unknown cell"),
    (("end_to_end", 0, "workloads"), ["ml11_xgb.fit"], "does not report"),
    (("end_to_end", 0, "workloads"), ["ml11_xgb.fit"], "reports only setup_s"),
    (("workloads", 0, "traffic"), "no_such_mix", "no traffic file"),
    (("workloads", 0, "chips"), 2, "chips"),
    (("workloads", 1, "config"), "nowhere", "unknown config"),
    (("workloads", 0, "why"), "x" * 201, "why"),
])
def test_validate_names_the_fault(path, value, says):
    faults = spec.validate(REPO, _with(path, value))
    assert any(says in f for f in faults), faults


def test_a_later_cell_arrives_as_new_files_and_entries(tmp_path):
    """`make_tiny_root` adds a data generator, two configurations, a
    traffic mix and two cells to a copy and asserts that it edited no file
    that was there."""
    root, bench = bench_tiny.make_tiny_root(tmp_path)
    assert spec.validate(root, bench) == []
    added = [w["name"] for w in bench["workloads"]
             if w["name"].startswith("tiny_")]
    assert len(added) == 2
    for cell in added:
        parts = spec.resolve(root, bench, cell)
        assert parts["config"]["data"]["rows"] == bench_tiny.TINY_ROWS
        assert parts["traffic"]["fractions"] == [0.7, 0.3]
    parts = spec.resolve(root, bench, "tiny_rf.tiny_fit")
    assert parts["data_path"].endswith("benchmark/data/tiny_listings.py")
    # the new generator is found by its name and makes other rows
    from benchmark.harness import runner
    data = parts["config"]["data"]
    new = runner.load_module(parts["data_path"], "t").make(data, 9)
    old = runner.load_module(spec.resolve(root, bench, "ml07_rf.fit")[
        "data_path"], "a").make(data, 9)
    assert (new["price"] == old["price"] * 0.5).all() and len(new) == 12000


def test_a_configuration_that_names_no_generator_file_is_refused(tmp_path):
    root, bench = bench_tiny.make_tiny_root(tmp_path)
    os.remove(os.path.join(root, "benchmark", "data", "tiny_listings.py"))
    with pytest.raises(spec.SpecError, match="data generator"):
        spec.resolve(root, bench, "tiny_rf.tiny_fit")
    assert any("data generator" in f for f in spec.validate(root, bench))


@pytest.mark.parametrize("seed", [7, 2**31 + 12345])
def test_the_table_is_made_from_the_seed(seed):
    from benchmark.harness import runner
    parts = spec.resolve(REPO, BENCH, "ml11_xgb.fit")
    make = runner.load_module(parts["data_path"], "airbnb").make
    a, b = make({"rows": 3000}, seed), make({"rows": 3000}, seed)
    assert a.equals(b) and len(a) == 3000
    assert not a.equals(make({"rows": 3000}, seed + 1))
    assert {"price", "neighbourhood_cleansed", "bedrooms"} <= set(a.columns)
    assert a["bedrooms"].isna().any()          # imputation targets


def test_unknown_cell_is_an_error():
    with pytest.raises(spec.SpecError):
        spec.resolve(REPO, BENCH, "no.such_cell")
