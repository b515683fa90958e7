"""A temporary copy of the benchmark with tiny cells ADDED to it as new
files and entries, never by editing a file that is there: what a later PR
does, and what the CPU tests drive."""

from __future__ import annotations

import copy
import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

TINY_ROWS = 12000

#: a data generator the benchmark does not have: the same listings with
#: every price scaled, made by a file of its own that a configuration names
TINY_GENERATOR = '''"""Data generator `tiny_listings`: the airbnb rows, prices scaled."""

import importlib.util
import os


def make(params, seed):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "airbnb.py")
    spec = importlib.util.spec_from_file_location("bench_data_airbnb", path)
    airbnb = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(airbnb)
    rows = airbnb.make(params, seed)
    rows["price"] = rows["price"] * float(params["price_scale"])
    return rows
'''


def _tiny_config(src: dict, name: str, params: dict, math: dict) -> dict:
    cfg = copy.deepcopy(src)
    cfg["name"] = name
    cfg["data"]["rows"] = TINY_ROWS
    cfg["pipeline"][-1]["params"].update(params)
    cfg["fit_math"].update(math)
    cfg["correct"].update({"sample_rows": 500, "fit_sample_trees": 2,
                           "fit_sample_nodes": 4, "fit_sample_leaves": 6})
    cfg["reduced"] = ["data", "pipeline"]
    return cfg


def make_tiny_root(tmp: str):
    """(root, bench): a copy of BENCHMARK.json and `benchmark/` under `tmp`
    with a data generator, two tiny configurations, a traffic mix and two
    cells added."""
    root = os.path.join(str(tmp), "root")
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)

    def snapshot():
        return {os.path.join(dp, p): open(os.path.join(dp, p), "rb").read()
                for dp, _, fs in os.walk(os.path.join(root, "benchmark"))
                for p in fs if "__pycache__" not in dp}
    before = snapshot()

    def load(rel):
        with open(os.path.join(root, rel)) as f:
            return json.load(f)

    def write(rel, text):
        path = os.path.join(root, rel)
        assert not os.path.exists(path), f"{rel} would edit an existing file"
        with open(path, "w") as f:
            f.write(text)

    write("benchmark/data/tiny_listings.py", TINY_GENERATOR)
    write("benchmark/configs/tiny_xgb.json", json.dumps(_tiny_config(
        load("benchmark/configs/ml11_xgb.json"), "tiny_xgb",
        {"n_estimators": 4, "max_depth": 3, "max_bins": 40,
         "learning_rate": 0.5},
        {"n_bins": 40}), indent=1))
    rf = _tiny_config(load("benchmark/configs/ml07_rf.json"), "tiny_rf",
                      {"numTrees": 3, "maxDepth": 3}, {})
    rf["data"].update(generator="tiny_listings", price_scale=0.5)
    write("benchmark/configs/tiny_rf.json", json.dumps(rf, indent=1))
    fit = load("benchmark/traffic/fit.json")
    fit.update(warm_iterations=1, fractions=[0.7, 0.3])
    write("benchmark/traffic/tiny_fit.json", json.dumps(fit, indent=1))
    for name in ("tiny_xgb", "tiny_rf"):
        bench["configs"].append({
            "name": name, "source": "test fixture",
            "reduced": ["data", "pipeline"],
            "file": f"benchmark/configs/{name}.json", "why": "tiny"})
    cells = [("tiny_xgb.tiny_fit", "tiny_xgb", "tiny_fit"),
             ("tiny_rf.tiny_fit", "tiny_rf", "tiny_fit")]
    for cell, config, traffic in cells:
        bench["workloads"].append({"name": cell, "config": config,
                                   "traffic": traffic, "chips": 1,
                                   "why": "tiny cell for the CPU tests"})
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if "workloads" in m and "ml11_xgb.fit" in m["workloads"]:
                m["workloads"] += [c for c, _, _ in cells]
    after = snapshot()
    assert all(after[p] == before[p] for p in before), "a file was edited"
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    return root, bench
