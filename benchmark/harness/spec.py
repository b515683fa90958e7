"""BENCHMARK.json and the data files a cell names: lookup and validation."""

from __future__ import annotations

import json
import os
import re
from typing import Dict, List

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TRAFFIC_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")


class SpecError(ValueError):
    """BENCHMARK.json or a file it names is missing or malformed."""


def load_json(path: str) -> dict:
    if not os.path.isfile(path):
        raise SpecError(f"missing file: {path}")
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SpecError(f"no workload {name!r} in BENCHMARK.json; it has "
                    f"{[w['name'] for w in bench['workloads']]}")


def config_entry(bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return c
    raise SpecError(f"no configuration {name!r} in BENCHMARK.json")


def bench_dir(root: str, bench: dict) -> str:
    """The first of `paths`: where kinds/, traffic/, data/ and
    layer_metrics/ live."""
    return os.path.join(root, bench["paths"][0])


def traffic_path(root: str, bench: dict, traffic: str) -> str:
    base = os.path.join(bench_dir(root, bench), "traffic", traffic)
    for suffix in TRAFFIC_SUFFIXES:
        if os.path.isfile(base + suffix):
            return base + suffix
    raise SpecError(f"no traffic file {base}.* for traffic {traffic!r}")


def metric_applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def metrics_for(bench: dict, cell: str, group: str) -> List[dict]:
    return [m for m in bench[group] if metric_applies(m, cell)]


def resolve(root: str, bench: dict, cell: str) -> Dict[str, object]:
    """Everything one cell names, loaded: its entry, configuration, traffic
    parameters, and the paths of its kind, its data generator and its
    layer-metric readers."""
    w = workload(bench, cell)
    c = config_entry(bench, w["config"])
    config = load_json(os.path.join(root, c["file"]))
    tpath = traffic_path(root, bench, w["traffic"])
    if not tpath.endswith(".json"):
        raise SpecError(f"{tpath}: this harness reads .json traffic files")
    traffic = load_json(tpath)
    bdir = bench_dir(root, bench)
    kind_path = os.path.join(bdir, "kinds", traffic["kind"] + ".py")
    if not os.path.isfile(kind_path):
        raise SpecError(f"traffic {w['traffic']!r} is of kind "
                        f"{traffic['kind']!r}; no {kind_path}")
    generator = config.get("data", {}).get("generator", "")
    data_path = os.path.join(bdir, "data", generator + ".py")
    if not NAME_RE.match(generator) or not os.path.isfile(data_path):
        raise SpecError(f"configuration {c['name']!r} names the data "
                        f"generator {generator!r}; no {data_path}")
    readers = {}
    for m in metrics_for(bench, cell, "per_layer"):
        p = os.path.join(bdir, "layer_metrics", m["name"] + ".py")
        if not os.path.isfile(p):
            raise SpecError(f"per-layer metric {m['name']!r} has no reader {p}")
        readers[m["name"]] = p
    return {"workload": w, "config_entry": c, "config": config,
            "traffic": traffic, "kind_path": kind_path,
            "data_path": data_path, "readers": readers}


def validate(root: str, bench: dict) -> List[str]:
    """Faults against the benchmark's contract that can be seen without a
    run; an empty list means none found."""
    faults: List[str] = []
    keys = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(bench) != keys:
        faults.append(f"keys {sorted(bench)} are not exactly {sorted(keys)}")
        return faults

    def name_ok(kind: str, n: str) -> None:
        if not NAME_RE.match(n):
            faults.append(f"{kind} name {n!r} has characters outside "
                          f"letters, digits, '_', '.', '-' or is too long")

    # no two configurations, no two cells and no two metrics share a name
    for groups in (("configs",), ("workloads",), ("end_to_end", "per_layer")):
        seen = set()
        for group in groups:
            for e in bench[group]:
                name_ok(group, e["name"])
                if e["name"] in seen:
                    faults.append(f"duplicate name {e['name']!r} in {group}")
                seen.add(e["name"])
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    if "setup_s" not in e2e:
        faults.append("no end-to-end metric setup_s")
    for m in bench["end_to_end"]:
        if not UNIT_RE.match(m["unit"]):
            faults.append(f"unit {m['unit']!r} of {m['name']}")
        if m["source"] not in ("host_clock", "device_trace"):
            faults.append(f"end-to-end {m['name']} source {m['source']!r}")
        if not 0.0 < m["bound"] <= 0.1:
            faults.append(f"bound of {m['name']} outside (0, 0.1]")
        if m["better"] not in ("lower", "higher"):
            faults.append(f"better of {m['name']}")
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        if not UNIT_RE.match(m["unit"]):
            faults.append(f"unit {m['unit']!r} of {m['name']}")
        if m["source"] not in SOURCES:
            faults.append(f"per-layer {m['name']} source {m['source']!r}")
        if m["moves"] not in e2e:
            faults.append(f"{m['name']} moves unknown {m['moves']!r}")
            continue
        for cell in cells:
            if metric_applies(m, cell) and \
                    not metric_applies(e2e[m["moves"]], cell):
                faults.append(f"{m['name']} is read in {cell}, which does "
                              f"not report {m['moves']}")
        for cell in m.get("workloads", []):
            if cell not in cells:
                faults.append(f"{m['name']} lists unknown cell {cell!r}")
    configs = {c["name"] for c in bench["configs"]}
    pairs = set()
    for w in bench["workloads"]:
        name_ok("traffic", w["traffic"])
        if w["config"] not in configs:
            faults.append(f"cell {w['name']} names unknown config")
        if (w["config"], w["traffic"]) in pairs:
            faults.append(f"pair {(w['config'], w['traffic'])} twice")
        pairs.add((w["config"], w["traffic"]))
        if w["chips"] not in (1, 4):
            faults.append(f"cell {w['name']} chips {w['chips']}")
        if not 1 <= len(w["why"]) <= 200 or "\n" in w["why"] or "\t" in w["why"]:
            faults.append(f"why of {w['name']} not 1..200 chars on one line")
        try:
            resolve(root, bench, w["name"])
        except SpecError as e:
            faults.append(str(e))
        if len(metrics_for(bench, w["name"], "end_to_end")) < 2:
            faults.append(f"cell {w['name']} reports only setup_s")
        if not metrics_for(bench, w["name"], "per_layer"):
            faults.append(f"cell {w['name']} has no per-layer metric")
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        if c["name"] not in used:
            faults.append(f"configuration {c['name']} is used by no cell")
        if not any(c["file"].startswith(p.rstrip("/") + "/")
                   for p in bench["paths"]):
            faults.append(f"file of {c['name']} is outside paths")
    return faults
