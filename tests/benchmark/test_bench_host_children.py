"""The readers of the host path's inside: the children of `fit.stage` and
`fit.featurize`, the CPU seconds beside the span totals, and
set-up's phases from the recorder's totals at the window's FIRST snapshot.
On made-up readings, and on the counters of a whole tiny run on the CPU.
BENCHMARK.json is checked in the form that survives an append: what this
file's entries are, where they start, and that each has its reader."""

import json
import os
import time
import types

import pytest

import bench_tiny
from benchmark.harness import program, runner, spec, xplane
from benchmark.layer_metrics import _fit_spans, _setup_spans

REPO = bench_tiny.REPO
BENCH = spec.load_benchmark(REPO)
ALL = ["ml11_xgb.fit", "ml07_rf.fit", "ml11_xgb_4chip.fit_sharded",
       "mle03_logreg.fit_logistic"]
SQ, FE = "staging and quantize", "featurize"

#: name -> (unit, source, layer, the cells it lists, the recorder's totals
#: it reads); all move `fit_s`
FIT = {
    "fit.host.stage.key_s": ("s", "program_span", SQ, ALL,
                             ["span_s.stage.key"]),
    "fit.host.stage.pad_s": ("s", "program_span", SQ, ALL,
                             ["span_s.stage.pad"]),
    "fit.host.stage.put_s": ("s", "program_span", SQ, ALL,
                             ["span_s.stage.put"]),
    "fit.host.featurize.jobs_s": ("s", "program_span", FE, ALL,
                                  ["span_s.fit.featurize.plan.jobs"]),
    "fit.host.featurize.block_s": ("s", "program_span", FE, ALL,
                                   ["span_s.fit.featurize.plan.block"]),
    "fit.host.featurize.copies_s": (
        "s", "program_span", FE, ALL[:3],
        ["span_s.fit.featurize.extract", "span_s.fit.featurize.missing"]),
    "fit.host.featurize.cpu_s": ("s", "program_counter", FE, ALL,
                                 ["span_cpu_s.fit.featurize"]),
}
#: name -> (source, layer, the total it reads at the window's first
#: snapshot); all in seconds, all move `setup_s`, none lists its cells
SETUP = {
    "setup.before_program_s": ("program_counter", "process start and import",
                               "process.age_at_import_s"),
    "setup.import_s": ("program_counter", "process start and import",
                       "process.import_s"),
    "setup.table_s": ("program_span", "frame engine",
                      "span_s.materialize.createDataFrame"),
    "setup.split_s": ("program_span", "frame engine",
                      "span_s.materialize.randomSplit"),
    "setup.warm_fit_s": ("program_span", "pipeline fit", "span_s.fit"),
    "setup.first_dispatch_s": ("program_span", "compile and cache",
                               "span_s.fit.dispatch"),
}
NEW = list(FIT) + list(SETUP)
#: the spans this PR opens inside the phases' spans
CHILDREN = ["stage.key", "stage.pad", "stage.put", "fit.featurize.plan.jobs",
            "fit.featurize.plan.block", "fit.featurize.extract",
            "fit.featurize.missing"]


def reader(name):
    return runner.load_module(os.path.join(
        REPO, "benchmark", "layer_metrics", name + ".py"), "bench_metric")


def reading(trace=None, fits=2, start=None, end=None):
    return runner.Reading(
        cell="no.such_cell", config={}, traffic={}, seconds=1.0,
        facts={"fits": fits} if fits is not None else {},
        counters_start=start or {}, counters_end=end or {}, compiles=None,
        device={"platform": "tpu"}, program=None, trace=trace)


def _device_plane():
    return xplane.Trace([[("%copy.1 = f32[8] copy(f32[8] %p)", 10.0, 20.0)]],
                        [("bench.window", 0.0, 100.0)])


# --------------------------------------------------------- BENCHMARK.json
def test_the_benchmark_is_valid_and_the_entries_follow_pr_32s():
    assert spec.validate(REPO, BENCH) == []
    names = [m["name"] for m in BENCH["per_layer"]]
    at = names.index("linear.hess_roofline") + 1
    assert names[at:at + len(NEW)] == NEW, "appended after what was there"
    assert len(set(names)) == len(names)


@pytest.mark.parametrize("name", NEW)
def test_an_entry_is_as_the_issue_sets_it_and_has_its_reader(name):
    entry, = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert entry["better"] == "lower"
    if name in FIT:
        unit, source, layer, cells, _ = FIT[name]
        assert entry["moves"] == "fit_s"
        # a PREFIX of what it lists now: a later cell may be appended
        assert entry["workloads"][:len(cells)] == cells
    else:
        (source, layer, _), unit = SETUP[name], "s"
        assert entry["moves"] == "setup_s"
        assert "workloads" not in entry      # every cell, as `compile.*`
    assert (entry["unit"], entry["source"], entry["layer"]) == \
        (unit, source, layer)
    assert set(entry) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert os.path.isfile(os.path.join(
        REPO, "benchmark", "layer_metrics", name + ".py"))


def test_no_phase_of_the_eight_reads_a_child():
    """Nothing is counted twice: the children lie INSIDE the phases' spans,
    under names `_fit_spans.PHASES` does not list."""
    phases = {n for names in _fit_spans.PHASES.values() for n in names}
    assert not phases & set(CHILDREN)
    assert not set(NEW) & (set(_fit_spans.PHASES)
                           | {"fit.host.unattributed_s"})


# ------------------------------------------------------ made-up readings
def _fit_totals(scale=1.0):
    """A program of this PR: the phases' totals with their children's, and
    the CPU seconds of the span that asks."""
    spans = {"fit": 10.0, "fit.collect": 0.5, "fit.featurize": 2.5,
             "fit.featurize.plan.jobs": 1.0,
             "fit.featurize.plan.block": 0.5,
             "fit.featurize.extract": 0.75, "fit.featurize.missing": 0.25,
             "fit.stage": 1.0, "stage.key": 0.5, "stage.pad": 0.25,
             "stage.put": 0.125, "fit.dispatch": 0.125,
             "fit.device_wait": 3.0}
    out = {"span_s." + k: v * scale for k, v in spans.items()}
    out.update({"span_n." + k: 4.0 * scale for k in spans})
    out["span_cpu_s.fit.featurize"] = 9.0 * scale
    return out


def _parent_totals(scale=1.0):
    """The parent commit: the phases' totals, none of the children's and no
    CPU seconds."""
    return {k: v for k, v in _fit_totals(scale).items()
            if k.startswith(("span_s.", "span_n."))
            and k.split(".", 1)[1] not in CHILDREN}


def test_the_children_are_span_seconds_between_the_snapshots_over_fits():
    run = reading(_device_plane(), fits=2, start=_fit_totals(1.0),
                  end=_fit_totals(3.0))
    got = {name: reader(name).read(run) for name in FIT}
    assert got == {
        "fit.host.stage.key_s": 0.5, "fit.host.stage.pad_s": 0.25,
        "fit.host.stage.put_s": 0.125,
        "fit.host.featurize.jobs_s": 1.0, "fit.host.featurize.block_s": 0.5,
        "fit.host.featurize.copies_s": 1.0,
        "fit.host.featurize.cpu_s": 9.0}
    # parts of their phases, and the phases read what they read before
    stage = reader("fit.host.stage_s").read(run)
    featurize = reader("fit.host.featurize_s").read(run)
    assert (stage, featurize) == (1.0, 3.0)
    assert sum(got[n] for n in FIT if n.endswith(
        ("key_s", "pad_s", "put_s"))) <= stage
    assert sum(got[n] for n in FIT if n.endswith(
        ("jobs_s", "block_s", "copies_s"))) <= featurize


def test_a_forest_has_no_missing_copy_and_still_reads_its_extract():
    totals = {k: v for k, v in _fit_totals(3.0).items()
              if "featurize.missing" not in k}
    run = reading(_device_plane(), fits=2, end=totals)
    assert reader("fit.host.featurize.copies_s").read(run) == 0.75 * 3 / 2


@pytest.mark.parametrize("name", list(FIT))
def test_a_fit_reader_finds_nothing_to_read(name):
    read = reader(name).read
    end = _fit_totals(3.0)
    assert read(reading(_device_plane(), end=end)) is not None
    assert read(reading(_device_plane(), fits=0, end=end)) is None
    assert read(reading(_device_plane(), fits=None, end=end)) is None
    assert read(reading(None, end=end)) is None           # untraced
    assert read(reading(xplane.Trace([], []), end=end)) is None  # no device
    parent = _parent_totals(3.0)
    assert "span_s.fit.stage" in parent and not set(FIT[name][4]) & set(parent)
    assert read(reading(_device_plane(), end=parent)) is None
    # and the phases read there what they read here
    for phase in ("fit.host.stage_s", "fit.host.featurize_s"):
        assert reader(phase).read(reading(_device_plane(), end=parent)) == \
            reader(phase).read(reading(_device_plane(), end=end))


def _setup_totals():
    return {"process.age_at_import_s": 9.5, "process.import_s": 4.25,
            "span_s.materialize.createDataFrame": 3.0,
            "span_s.materialize.randomSplit": 2.0, "span_s.fit": 6.0,
            "span_s.fit.dispatch": 1.5, "span_n.fit": 3.0}


def test_set_up_is_the_totals_at_the_windows_first_snapshot():
    start = _setup_totals()
    end = {k: v * 5 for k, v in start.items()}    # the window's: not read
    run = reading(_device_plane(), start=start, end=end)
    got = {name: reader(name).read(run) for name in SETUP}
    assert got == {
        "setup.before_program_s": 9.5, "setup.import_s": 4.25,
        "setup.table_s": 3.0, "setup.split_s": 2.0, "setup.warm_fit_s": 6.0,
        "setup.first_dispatch_s": 1.5}
    assert got["setup.first_dispatch_s"] <= got["setup.warm_fit_s"]
    assert _setup_spans.at_window_start(run, "span_s.fit") == 6.0


@pytest.mark.parametrize("name", list(SETUP))
def test_a_set_up_reader_finds_nothing_to_read(name):
    read = reader(name).read
    start = _setup_totals()
    assert read(reading(_device_plane(), start=start)) is not None
    assert read(reading(None, start=start)) is None       # untraced
    assert read(reading(xplane.Trace([], []), start=start)) is None
    # a program that keeps no such total: the parent's recorder has no
    # `process.*`, one from before the spans no `span_s.*` either
    total = SETUP[name][2]
    without = {k: v for k, v in start.items() if k != total}
    assert read(reading(_device_plane(), start=without)) is None
    # the window's own totals are not set-up's
    assert read(reading(_device_plane(), end=start)) is None


# ------------------------------------------- a whole tiny run on the CPU
def test_a_tiny_run_gives_every_new_reader_a_number(tmp_path, monkeypatch):
    """`bench_tiny`'s boosted cell, traced, through `runner.run`, with the
    staging threshold lowered to its size (12,000 rows: 96 KB of bins). Its
    line leaves the new metrics out (no device plane on the CPU); the run's
    own counter snapshots, read beside a device plane, give every reader a
    number: the children stay inside their phases, the eight `fit.host.*`
    still sum to the root span, and set-up's totals stay under the run."""
    from sml_tpu.ml import _staging
    monkeypatch.setattr(_staging, "_SPAN_BYTES", 1 << 10)
    root, bench = bench_tiny.make_tiny_root(tmp_path)
    snapshots = []

    def counters():
        snapshots.append(program.counters())
        return snapshots[-1]

    shim = types.SimpleNamespace(**{k: getattr(program, k)
                                    for k in dir(program)
                                    if not k.startswith("__")})
    shim.counters = counters
    t0 = time.perf_counter()
    line = runner.run(root, "tiny_xgb.tiny_fit", 2**31 + 3838, 1.5, True,
                      t0, require_chip=False, bench=bench, program=shim)
    wall = time.perf_counter() - t0
    assert line["correct"] is True
    assert not set(line["metrics"]) & set(NEW)
    json.dumps(line)
    start, end = snapshots
    fits = line["attempted"]
    run = reading(_device_plane(), fits=fits, start=start, end=end)
    got = {name: reader(name).read(run) for name in NEW}
    assert all(isinstance(v, float) and v >= 0.0 for v in got.values()), got

    stage = reader("fit.host.stage_s").read(run)
    parts = sum(got["fit.host.stage." + p]
                for p in ("key_s", "pad_s", "put_s"))
    assert 0.0 < parts <= stage
    assert got["fit.host.stage.put_s"] > 0.0   # a fresh split a fit: a miss
    featurize = reader("fit.host.featurize_s").read(run)
    inside = sum(got["fit.host.featurize." + p]
                 for p in ("jobs_s", "block_s", "copies_s"))
    assert 0.0 < inside <= featurize
    assert got["fit.host.featurize.cpu_s"] > 0.0
    # the eight still sum to the root: no phase reads a child
    eight = sorted(_fit_spans.PHASES) + ["fit.host.unattributed_s"]
    root_s = (end["span_s.fit"] - start["span_s.fit"]) / fits
    assert sum(reader(n).read(run) for n in eight) == pytest.approx(root_s)

    # set-up: the table, the split and the warm fit happened before the
    # window and inside this run; the process's two facts are the process's
    spans = sum(got["setup." + p] for p in ("table_s", "split_s",
                                            "warm_fit_s"))
    assert 0.0 < spans < wall
    assert 0.0 < got["setup.first_dispatch_s"] <= got["setup.warm_fit_s"]
    assert got["setup.import_s"] > 0.0
    assert got["setup.before_program_s"] == \
        start["process.age_at_import_s"] == end["process.age_at_import_s"]
