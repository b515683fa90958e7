"""The four readers of a fit's record (PR 52): the window's slow fits'
seconds over their medians, the collector's pauses inside timed fits, and
the process's CPU seconds a fit and a `fit.quantize`. On made-up readings.
BENCHMARK.json is checked in the form that survives an append: what this
file's entries are, where they start, and that each has its reader."""

import os

import pytest

import bench_tiny
from benchmark.harness import runner, spec, xplane

REPO = bench_tiny.REPO
BENCH = spec.load_benchmark(REPO)
ALL = ["ml11_xgb.fit", "ml07_rf.fit", "ml11_xgb_4chip.fit_sharded",
       "mle03_logreg.fit_logistic", "mle03_logreg_cv.fit_cv",
       "mle01_als.fit_als", "mle02_kmeans.fit_kmeans",
       "xgb_higgs.fit_boost_logistic"]
TREES = [ALL[0], ALL[1], ALL[2], ALL[7]]

#: name -> (layer, the cells it lists, the recorder's total it reads); all
#: in seconds from `program_counter`, lower is better, all move `fit_s`
NEW = {
    "fit.tail.excess_s": ("pipeline fit", ALL, "fit.slow.excess_s"),
    "fit.host.gc_s": ("pipeline fit", ALL, "fit.gc_s"),
    "fit.host.cpu_s": ("pipeline fit", ALL, "span_cpu_s.fit"),
    "fit.host.quantize.cpu_s": ("staging and quantize", TREES,
                                "span_cpu_s.fit.quantize"),
}


def reader(name):
    return runner.load_module(os.path.join(
        REPO, "benchmark", "layer_metrics", name + ".py"), "bench_metric")


def reading(trace=None, fits=4, start=None, end=None):
    return runner.Reading(
        cell="no.such_cell", config={}, traffic={}, seconds=1.0,
        facts={"fits": fits} if fits is not None else {},
        counters_start=start or {}, counters_end=end or {}, compiles=None,
        device={"platform": "tpu"}, program=None, trace=trace)


def _device_plane():
    return xplane.Trace([[("%copy.1 = f32[8] copy(f32[8] %p)", 10.0, 20.0)]],
                        [("bench.window", 0.0, 100.0)])


def _totals(scale=1.0):
    """A program of this PR after `scale` windows of four fits."""
    out = {"span_s.fit": 8.0, "span_n.fit": 4.0, "span_s.fit.featurize": 2.0,
           "span_s.fit.quantize": 1.0, "span_cpu_s.fit": 24.0,
           "span_cpu_s.fit.featurize": 6.0, "span_cpu_s.fit.quantize": 5.0,
           "fit.gc_s": 0.02, "fit.slow": 1.0, "fit.slow.excess_s": 3.0}
    return {k: v * scale for k, v in out.items()}


def _parent_totals(scale=1.0):
    """The parent commit: the span totals, CPU seconds of `fit.featurize`
    alone, no record's totals."""
    return {k: v for k, v in _totals(scale).items()
            if k.startswith(("span_s.", "span_n."))
            or k == "span_cpu_s.fit.featurize"}


# --------------------------------------------------------- BENCHMARK.json
def test_the_benchmark_is_valid_and_the_four_follow_pr_50s():
    assert spec.validate(REPO, BENCH) == []
    names = [m["name"] for m in BENCH["per_layer"]]
    at = names.index("tree.hist_roofline") + 1
    assert names[at:at + len(NEW)] == list(NEW), "appended after what was"
    assert len(set(names)) == len(names)


@pytest.mark.parametrize("name", list(NEW))
def test_an_entry_is_as_the_issue_sets_it_and_has_its_reader(name):
    entry, = [m for m in BENCH["per_layer"] if m["name"] == name]
    layer, cells, _ = NEW[name]
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"],
            entry["moves"]) == ("s", "lower", "program_counter", layer,
                                "fit_s")
    # a PREFIX of what it lists now: a later cell may be appended
    assert entry["workloads"][:len(cells)] == cells
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert os.path.isfile(os.path.join(
        REPO, "benchmark", "layer_metrics", name + ".py"))


# ------------------------------------------------------ made-up readings
@pytest.mark.parametrize("name, value", [
    ("fit.tail.excess_s", 1.5), ("fit.host.gc_s", 0.01),
    ("fit.host.cpu_s", 12.0), ("fit.host.quantize.cpu_s", 2.5)])
def test_a_reader_takes_its_total_between_the_snapshots_over_the_fits(
        name, value):
    run = reading(_device_plane(), fits=4, start=_totals(1.0),
                  end=_totals(3.0))
    assert reader(name).read(run) == pytest.approx(value)
    # a quiet window reads 0, not nothing: the total is there and stood
    quiet = reading(_device_plane(), fits=4, start=_totals(1.0),
                    end=dict(_totals(1.0), **{"span_s.fit": 16.0}))
    assert reader(name).read(quiet) == 0.0


@pytest.mark.parametrize("name", list(NEW))
def test_a_reader_finds_nothing_to_read(name):
    read = reader(name).read
    end = _totals(3.0)
    assert read(reading(_device_plane(), end=end)) is not None
    assert read(reading(_device_plane(), fits=0, end=end)) is None
    assert read(reading(_device_plane(), fits=None, end=end)) is None
    assert read(reading(None, end=end)) is None           # untraced
    assert read(reading(xplane.Trace([], []), end=end)) is None  # no device
    parent = _parent_totals(3.0)
    assert "span_s.fit" in parent and NEW[name][2] not in parent
    assert read(reading(_device_plane(), end=parent)) is None
    # and the accepted reader beside them reads there what it reads here
    beside = reader("fit.host.featurize.cpu_s").read
    assert beside(reading(_device_plane(), end=parent)) == \
        beside(reading(_device_plane(), end=end)) == 4.5
