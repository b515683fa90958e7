"""A record for every fit (`sml_tpu/obs/_fits.py`, PR 52): the per-fit form
of what the span totals sum, the verdict on a slow fit as a function of
records with GIVEN seconds, one slow fit made end to end (a sleep of 0.5 s
dwarfs the clock's noise), the collector's pauses, and the watchdog's own
lateness. Tiny, on the CPU."""

import gc
import importlib.util
import json
import logging
import os
import time

import numpy as np
import pandas as pd
import pytest

from sml_tpu import obs
from sml_tpu.conf import GLOBAL_CONF
from sml_tpu.ml import Pipeline, tree_impl
from sml_tpu.ml.classification import LogisticRegression
from sml_tpu.ml.evaluation import BinaryClassificationEvaluator
from sml_tpu.ml.feature import (Imputer, RFormula, StringIndexer,
                                VectorAssembler)
from sml_tpu.ml.regression import RandomForestRegressor
from sml_tpu.ml.tuning import CrossValidator, ParamGridBuilder
from sml_tpu.obs import _fits, blackbox, taxonomy
from sml_tpu.obs._recorder import _MAX_FIT_RECORDS
from sml_tpu.utils.profiler import PROFILER
from sml_tpu.xgboost import XgboostRegressor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EIGHT = list(taxonomy.FIT_PHASES) + [_fits.UNATTRIBUTED]


@pytest.fixture()
def recorder():
    GLOBAL_CONF.set("sml.obs.enabled", True)
    obs.reset()
    try:
        yield obs.RECORDER
    finally:
        GLOBAL_CONF.set("sml.obs.enabled", False)
        obs.reset()


def _listings(spark, seed, n=3000):
    """Rows no other test has fitted, materialized: a fit knows its rows."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 52]))
    pdf = pd.DataFrame({"a": rng.normal(size=n), "b": rng.normal(size=n),
                        "c": rng.choice(["x", "y", "z"], n)})
    pdf.loc[::7, "a"] = np.nan
    pdf["price"] = pdf["b"] * 2 + rng.normal(size=n)
    df = spark.createDataFrame(pdf)
    df.cache()
    df.count()
    return df


def _labelled(spark, seed, n=3000):
    rng = np.random.default_rng(np.random.SeedSequence([seed, 53]))
    pdf = pd.DataFrame({
        "room": rng.choice(["entire", "private", "shared"], n),
        "beds": rng.integers(1, 5, n).astype(np.float64),
        "score": rng.integers(6, 11, n).astype(np.float64)})
    eta = -3.0 + 0.8 * (pdf["room"] == "entire") + 0.3 * pdf["score"]
    pdf["label"] = (rng.random(n) < 1 / (1 + np.exp(-eta))).astype(
        np.float64)
    df = spark.createDataFrame(pdf).repartition(4)
    df.cache()
    df.count()
    return df


def _tree_pipeline(last):
    return Pipeline(stages=[
        Imputer(strategy="median", inputCols=["a", "b"],
                outputCols=["a_i", "b_i"]),
        StringIndexer(inputCols=["c"], outputCols=["c_i"],
                      handleInvalid="skip"),
        VectorAssembler(inputCols=["a_i", "b_i", "c_i"],
                        outputCol="features"),
        last])


def _pipeline(kind):
    if kind == "boosted":
        return _tree_pipeline(XgboostRegressor(
            n_estimators=3, max_depth=2, max_bins=8, labelCol="price",
            missing=0.0))
    if kind == "forest":
        return _tree_pipeline(RandomForestRegressor(
            labelCol="price", maxBins=8, maxDepth=2, numTrees=3, seed=1))
    formula = RFormula(formula="label ~ .", featuresCol="features",
                       labelCol="label", handleInvalid="skip")
    lr = LogisticRegression(labelCol="label", featuresCol="features")
    if kind == "logistic":
        return Pipeline(stages=[formula, lr])
    grid = ParamGridBuilder().addGrid(lr.regParam, [0.1, 0.2]).build()
    return Pipeline(stages=[formula, CrossValidator(
        estimator=lr, estimatorParamMaps=grid, numFolds=3, parallelism=2,
        seed=42, evaluator=BinaryClassificationEvaluator(
            metricName="areaUnderROC"))])


# ----------------------------------- (1) the per-fit form of the totals
@pytest.mark.parametrize("kind", ["boosted", "forest", "logistic", "cv"])
def test_a_records_seconds_add_up_to_the_span_totals(spark, recorder, kind):
    make = _listings if kind in ("boosted", "forest") else _labelled
    frames = [make(spark, seed) for seed in (1, 2)]
    before = recorder.counters()
    for df in frames:
        _pipeline(kind).fit(df)
    after = recorder.counters()
    records = obs.fit_records()
    assert len(records) == 2
    assert [r["rows"] for r in records] == [3000, 3000]
    assert records[0]["estimator"].startswith("Pipeline(") \
        and records[0]["shape"] == (records[0]["estimator"], 12)

    def moved(total):
        return after.get(total, 0.0) - before.get(total, 0.0)

    names = {n for r in records for n in r["spans"]}
    assert {"fit", "fit.dispatch", "fit.device_wait"} <= names
    for name in names:
        entries = [r["spans"][name] for r in records if name in r["spans"]]
        assert sum(e["wall_s"] for e in entries) == pytest.approx(
            moved("span_s." + name), abs=1e-6), name
        assert sum(e["n"] for e in entries) == moved("span_n." + name), name
        assert sum(e.get("cpu_s", 0.0) for e in entries) == pytest.approx(
            moved("span_cpu_s." + name), abs=1e-6), name
        assert ("cpu_s" in entries[0]) == (name in taxonomy.CPU_SPANS), name
    for r in records:
        assert list(r["phases"]) == list(r["phases_cpu_s"]) == EIGHT
        assert sum(r["phases"].values()) == pytest.approx(
            r["wall_s"], abs=1e-6)
        assert r["wall_s"] == r["spans"]["fit"]["wall_s"]
        assert sum(r["phases_cpu_s"].values()) == pytest.approx(
            r["cpu_s"], abs=1e-6)
        for metric, spans in taxonomy.FIT_PHASES.items():
            assert r["phases"][metric] == pytest.approx(sum(
                r["spans"][n]["wall_s"] for n in spans if n in r["spans"]))
        # `fit.dispatch` sits inside a program's span and is found all the
        # same; the program's span merely contains phases and is in none
        assert r["spans"]["fit.dispatch"]["phase"] == "fit.host.dispatch_s"
        program = next(n for n in r["spans"] if n.startswith("program."))
        assert "phase" not in r["spans"][program]
        assert "phase" not in r["spans"]["fit"]
        assert r["gc_s"] >= 0.0 and r["watchdog_late_s"] >= 0.0
        assert "rss_bytes" not in r       # a slow fit's record alone
        json.dumps(r)
    # the record is the totals' per-fit form: the same phases, per fit
    assert sum(r["phases"]["fit.host.featurize_s"] for r in records) == \
        pytest.approx(sum(moved("span_s." + n) for n in
                          taxonomy.FIT_PHASES["fit.host.featurize_s"]),
                      abs=1e-6)
    assert moved("fit.gc_s") == pytest.approx(
        sum(r["gc_s"] for r in records), abs=1e-9)
    assert "fit.slow.excess_s" in after and moved("fit.slow") == 0.0


def test_the_programs_phase_lists_are_the_benchmarks():
    spec = importlib.util.spec_from_file_location(
        "_fit_spans", os.path.join(REPO, "benchmark", "layer_metrics",
                                   "_fit_spans.py"))
    fit_spans = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fit_spans)
    assert taxonomy.FIT_PHASES == fit_spans.PHASES
    assert list(taxonomy.FIT_PHASES) == list(fit_spans.PHASES)
    assert _fits.UNATTRIBUTED == "fit.host.unattributed_s"
    assert "fit.host.unattributed_s" not in taxonomy.FIT_PHASES


# ------------------------- (2) the verdict, on records with given seconds
INNER = {"fit.quantize": "fit.quantize.stats", "fit.featurize":
         "fit.featurize.plan.jobs", "fit.stage": "stage.pad"}


def _record(wall, estimator="Pipeline(A,B)", rows=1 << 20, trace=7, **over):
    """A record whose eight phases split `wall` evenly but for `over`
    (phase span -> seconds more, all of it in the span's one child)."""
    base = wall - sum(over.values())
    spans = {"fit": {"wall_s": wall, "n": 1, "cpu_s": 0.5 * base}}
    for metric, names in taxonomy.FIT_PHASES.items():
        name = names[-1]
        seconds = base / 8 + over.get(name, 0.0)
        spans[name] = {"wall_s": seconds, "n": 1, "cpu_s": base / 16,
                       "phase": metric}
        if name in INNER:
            spans[INNER[name]] = {"wall_s": seconds * 0.9, "n": 1,
                                  "phase": metric}
    phases = {m: sum(spans[n]["wall_s"] for n in names if n in spans)
              for m, names in taxonomy.FIT_PHASES.items()}
    phases[_fits.UNATTRIBUTED] = wall - sum(phases.values())
    cpu = {m: base / 16 for m in phases}
    return {"trace": trace, "estimator": estimator, "rows": rows,
            "shape": _fits.shape_of(estimator, rows), "t0": 0.0,
            "wall_s": wall, "cpu_s": 0.5 * base, "spans": spans,
            "phases": phases, "phases_cpu_s": cpu, "gc_s": 0.0, "gc_n": 0,
            "watchdog_late_s": 0.0}


@pytest.mark.parametrize("median, wall, peers, slow", [
    (1.0, 1.3, 6, True),        # +30 % and +0.3 s
    (1.0, 1.21, 6, False),      # +0.21 s but +21 %
    (0.2, 0.26, 6, False),      # +30 % of a 0.2 s fit: +0.06 s
    (2.0, 2.2, 6, False),       # +0.2 s on a 2 s fit: +10 %
    (2.0, 2.6, 32, True),
    (1.0, 9.0, 3, False),       # fewer than 4 records: no threshold
    (1.0, 9.0, 4, True),
])
def test_the_verdict_is_a_quarter_and_a_tenth_of_a_second_over_the_median(
        median, wall, peers, slow):
    earlier = [_record(median * (1 + 0.01 * (i % 3 - 1)))
               for i in range(peers)]
    found = _fits.verdict(_record(wall), earlier)
    assert (found is not None) == slow
    assert (_fits.expectation(earlier) is None) == (peers < 4)
    if slow:
        assert found["median_s"] == pytest.approx(median, rel=0.011)
        assert found["of"] == peers
        assert found["excess_s"] == pytest.approx(wall - found["median_s"])
        assert wall > _fits.threshold(found["median_s"])


def test_a_record_of_another_shape_does_not_enter_the_median():
    mine = [_record(1.0) for _ in range(5)]
    others = [_record(5.0, estimator="Pipeline(A,C)") for _ in range(5)] + \
        [_record(5.0, rows=1 << 23) for _ in range(5)]
    records = [r for pair in zip(mine, others, others[5:]) for r in pair]
    peers = _fits.peers_of(records, _fits.shape_of("Pipeline(A,B)", 1 << 20))
    assert [p["wall_s"] for p in peers] == [1.0] * 5
    assert _fits.verdict(_record(1.5), peers) is not None
    assert _fits.verdict(_record(1.5), _fits.peers_of(
        records, _fits.shape_of("Pipeline(A,C)", 1 << 20))) is None
    # rows a little apart are one shape, rows a factor of two apart are not
    assert _fits.shape_of("E", 1_600_000) == _fits.shape_of("E", 1_599_000)
    assert _fits.shape_of("E", 1_600_000) != _fits.shape_of("E", 800_000)
    # a frame not materialized has no rows: no shape, no peers, no verdict
    assert _fits.shape_of("E", None) is None
    assert _fits.peers_of(records, None) == []
    # the newest 32 at most, newest first
    many = [_record(float(i)) for i in range(40)]
    assert [p["wall_s"] for p in _fits.peers_of(many, many[0]["shape"])] \
        == [float(i) for i in range(39, 7, -1)]


def test_the_phase_with_the_largest_excess_is_named_first_with_its_inside():
    earlier = [_record(1.0) for _ in range(8)]
    slow = _record(1.7, trace=0x1234, **{"fit.quantize": 0.5,
                                         "fit.featurize": 0.15,
                                         "fit.stage": 0.05})
    found = _fits.verdict(slow, earlier)
    assert [row[0] for row in found["phases"][:3]] == [
        "fit.host.quantize_s", "fit.host.featurize_s", "fit.host.stage_s"]
    assert len(found["phases"]) == 8
    assert found["phases"][0][1] == pytest.approx(0.5)
    assert found["phases"][0][2] == pytest.approx(0.0)      # no CPU burnt
    assert [row[0] for row in found["inside"]] == ["fit.quantize",
                                                   "fit.quantize.stats"]
    assert found["inside"][0][1:] == [pytest.approx(0.5), pytest.approx(0.0)]
    assert found["inside"][1] == ["fit.quantize.stats", pytest.approx(0.45),
                                  None]
    text = _fits.line(dict(slow, rss_bytes=3 << 30,
                           mem_available_bytes=5 << 29), found)
    assert text == ("slow fit 1.70 s (median 1.00 of 8): fit.quantize "
                    "+0.50 s wall / +0.00 s cpu (fit.quantize.stats +0.45 s)"
                    "; gc 0.00 s; watchdog late 0.00 s; rss 3.0 GiB, "
                    "available 2.5 GiB; trace 0x0000000001234")
    assert "rss" not in _fits.line(slow, found)      # no /proc: left out


# ----------------------------------------- (3) one slow fit, end to end
class _Lines(logging.Handler):
    def __init__(self):
        super().__init__(logging.WARNING)
        self.lines = []

    def emit(self, record):
        self.lines.append(record.getMessage())


@pytest.fixture(scope="module")
def slow_sixth(tmp_path_factory):
    """Eight fits of one shape, the sixth with 0.5 s of sleep inside
    `finalize_binning` (inside `fit.quantize.stats`, the quantize plan's
    first phase); the blackbox's stall hook armed over an empty directory.
    What the sixth left behind, the process handed back as it was."""
    from sml_tpu import TpuSession
    spark = TpuSession.builder.appName("tests").getOrCreate()
    bundles = str(tmp_path_factory.mktemp("blackbox"))
    GLOBAL_CONF.set("sml.obs.enabled", True)
    obs.reset()
    patch = pytest.MonkeyPatch()
    patch.setitem(blackbox._state, "stall_dumped", False)
    patch.setattr(obs.WATCHDOG, "_on_stall",
                  obs.WATCHDOG._on_stall + [blackbox._stall_hook])
    held = GLOBAL_CONF.get("sml.obs.blackboxDir")
    GLOBAL_CONF.set("sml.obs.blackboxDir", bundles)
    lines = _Lines()
    logging.getLogger("sml_tpu.obs").addHandler(lines)
    real = tree_impl.finalize_binning

    def finalize_binning_asleep(*args, **kwargs):
        time.sleep(0.5)
        return real(*args, **kwargs)

    out = {"bundles": bundles, "lines": lines.lines}
    try:
        frames = [_listings(spark, seed) for seed in range(100, 108)]
        for i, df in enumerate(frames):
            if i == 5:
                out["before"] = obs.RECORDER.counters()
                out["lines_before"] = len(lines.lines)
                patch.setattr(tree_impl, "finalize_binning",
                              finalize_binning_asleep)
            _pipeline("boosted").fit(df)
            if i == 5:
                patch.setattr(tree_impl, "finalize_binning", real)
                out["after"] = obs.RECORDER.counters()
                out["lines_after"] = len(lines.lines)
        out["records"] = obs.fit_records()
        out["events"] = obs.RECORDER.events()
        out["health"] = obs.engine_health()
        out["dumped"] = blackbox._state["stall_dumped"]
    finally:
        logging.getLogger("sml_tpu.obs").removeHandler(lines)
        patch.undo()
        GLOBAL_CONF.set("sml.obs.blackboxDir", held)
        GLOBAL_CONF.set("sml.obs.enabled", False)
        obs.reset()
    return out


def _of_the_sixth(run, name):
    trace = run["records"][5]["trace"]
    return [e for e in run["events"]
            if e.name == name and e.args.get("trace") == trace]


def test_the_slow_fit_leaves_one_event_that_names_the_phase(slow_sixth):
    records = slow_sixth["records"]
    assert len(records) == 8 and len({r["shape"] for r in records}) == 1
    event, = _of_the_sixth(slow_sixth, "fit.slow")
    args = event.args
    assert args["record"] is records[5]
    assert args["of"] == 5 and args["median_s"] < 0.45
    metric, wall, cpu = args["phases"][0]
    assert metric == "fit.host.quantize_s"
    assert 0.45 < wall < 0.75 and abs(cpu) < 0.2        # asleep: no CPU
    assert {row[0] for row in args["inside"][:3]} == {
        "fit.quantize", "fit.quantize.bins", "fit.quantize.stats"}
    assert records[5]["phases"]["fit.host.quantize_s"] > 0.5
    assert records[5]["phases_cpu_s"]["fit.host.quantize_s"] < 0.25
    # the process's memory is read for the slow fit alone
    if os.path.exists("/proc/self/statm"):
        assert records[5]["rss_bytes"] > 0 \
            and records[5]["mem_available_bytes"] > 0
    assert "rss_bytes" not in records[4] and "rss_bytes" not in records[6]


def test_the_totals_count_the_slow_fit_and_its_excess(slow_sixth):
    before, after = slow_sixth["before"], slow_sixth["after"]
    assert after["fit.slow"] - before["fit.slow"] == 1.0
    excess = after["fit.slow.excess_s"] - before["fit.slow.excess_s"]
    assert 0.45 < excess < 0.75
    assert excess == pytest.approx(
        _of_the_sixth(slow_sixth, "fit.slow")[0].args["excess_s"])


def test_the_slow_fit_leaves_one_warning_line(slow_sixth):
    lines = slow_sixth["lines"][slow_sixth["lines_before"]:
                                slow_sixth["lines_after"]]
    assert len(lines) == 1
    line, = lines
    assert line.startswith("slow fit 0.") and " of 5): fit.quantize +0." in \
        line and "s cpu (fit.quantize." in line
    assert "; gc 0." in line and "; watchdog late 0." in line
    assert line.endswith(
        "trace " + obs.trace_hex(slow_sixth["records"][5]["trace"]))


def test_the_ticket_is_flagged_while_the_fit_sleeps_and_then_resolved(
        slow_sixth):
    detected, = _of_the_sixth(slow_sixth, "stall.detected")
    args = detected.args
    assert args["kind"] == "fit" and args["name"] == \
        slow_sixth["records"][5]["estimator"]
    assert args["expected_s"] == pytest.approx(
        _of_the_sixth(slow_sixth, "fit.slow")[0].args["median_s"])
    assert args["threshold_s"] == pytest.approx(
        _fits.threshold(args["expected_s"]), abs=1e-4)
    assert args["elapsed_s"] < 0.5 + args["threshold_s"]   # while asleep
    assert any("finalize_binning_asleep" in ln
               for stack in args["stacks"].values() for ln in stack)
    closed = [name for name, _ in args["closed"]]
    assert closed[:3] == ["fit.collect", "fit.featurize.plan.jobs",
                          "fit.featurize.plan.block"]
    assert "fit.quantize.key" in closed and not {
        "fit.quantize", "fit.quantize.bins", "fit.quantize.stats"} \
        & set(closed)
    assert args["late_s"] < 0.3
    resolved, = _of_the_sixth(slow_sixth, "stall.resolved")
    assert resolved.args["kind"] == "fit" and resolved.args["wall_s"] > 0.5
    assert detected.ts < resolved.ts < \
        _of_the_sixth(slow_sixth, "fit.slow")[0].ts


def test_a_slow_fit_is_no_hard_stall_and_writes_no_bundle(slow_sixth):
    assert os.listdir(slow_sixth["bundles"]) == []
    assert slow_sixth["dumped"] is False
    assert not [e for e in slow_sixth["events"] if e.name == "blackbox.dump"]


def test_fit_wall_ms_names_the_slowest_fits_trace(slow_sixth):
    hist = slow_sixth["health"]["metrics"]["fit.wall_ms"]
    assert hist["count"] == 8
    assert hist["max_exemplar"] == slow_sixth["records"][5]["trace"]
    assert hist["max"] == pytest.approx(
        slow_sixth["records"][5]["wall_s"] * 1e3)


# ------------------------------------------------- (4) the collector
class _Frame:
    _parts = [range(4096)]


def _bare_fit(inside):
    with obs.autolog_fit(Pipeline(stages=[]), _Frame()):
        with PROFILER.span("fit.prep"):
            inside()


def test_a_collection_inside_a_fit_is_the_fits_and_lands_a_span(recorder):
    _bare_fit(lambda: None)
    before = recorder.counters()
    _bare_fit(gc.collect)
    after = recorder.counters()
    quiet, record = obs.fit_records()
    assert record["gc_n"] >= 1 and record["gc_s"] > 0.0
    assert after["gc.pause_s"] - before.get("gc.pause_s", 0.0) >= \
        record["gc_s"] > 0.0
    assert after["fit.gc_s"] - before["fit.gc_s"] == pytest.approx(
        record["gc_s"])
    spans = [e for e in recorder.events() if e.kind == "span"]
    pause = [e for e in spans if e.name == "gc.pause"
             and e.args["generation"] == 2][-1]
    root = [e for e in spans if e.name == "fit"][-1]
    assert root.ts <= pause.ts and \
        pause.ts + pause.dur <= root.ts + root.dur
    assert pause.tid == root.tid and "collected" in pause.args
    assert after["span_n.gc.pause"] >= 1.0


def test_a_collection_of_generation_0_lands_no_event(recorder):
    gc.collect()
    spans = len([e for e in recorder.events() if e.name == "gc.pause"])
    before = recorder.counters()["gc.collections"]
    for _ in range(5):
        gc.collect(0)
    assert recorder.counters()["gc.collections"] - before == 5.0
    # under a millisecond each (nothing young to look at): totals alone
    assert len([e for e in recorder.events() if e.name == "gc.pause"]) \
        == spans


def test_the_hook_is_in_the_list_only_while_the_recorder_is_on():
    def hooks():
        return [h for h in gc.callbacks if h is obs.RECORDER._gc]
    assert not obs.RECORDER.enabled and hooks() == []
    GLOBAL_CONF.set("sml.obs.enabled", True)
    try:
        GLOBAL_CONF.set("sml.obs.enabled", True)
        assert len(hooks()) == 1
    finally:
        GLOBAL_CONF.set("sml.obs.enabled", False)
    assert hooks() == []
    before = obs.RECORDER.gc_totals()
    gc.collect()
    assert obs.RECORDER.gc_totals() == before       # off: nothing runs


# ------------------------------------------- (5) kept, dropped, carried
def test_reset_clears_the_records_and_the_deque_keeps_the_newest(recorder):
    for i in range(_MAX_FIT_RECORDS + 44):
        recorder.keep_fit(_record(1.0, trace=i))
    records = obs.fit_records()
    assert len(records) == _MAX_FIT_RECORDS == 256
    assert [records[0]["trace"], records[-1]["trace"]] == [44, 299]
    obs.reset()
    assert obs.fit_records() == []


def test_the_blackbox_bundle_carries_the_records(recorder, tmp_path):
    _bare_fit(lambda: None)
    _bare_fit(lambda: None)
    bundle = obs.dump_blackbox("test", directory=str(tmp_path))
    with open(os.path.join(bundle, "fits.jsonl")) as f:
        kept = [json.loads(line) for line in f]
    assert [r["trace"] for r in kept] == \
        [r["trace"] for r in obs.fit_records()]
    assert kept[0]["estimator"] == "Pipeline" and kept[0]["rows"] == 4096
    assert set(kept[0]["phases"]) == set(EIGHT)


def test_a_fit_that_raises_leaves_no_record_and_no_ticket(recorder):
    for _ in range(4):
        _bare_fit(lambda: None)
    with pytest.raises(ZeroDivisionError):
        _bare_fit(lambda: 1 / 0)
    assert len(obs.fit_records()) == 4
    assert obs.WATCHDOG.report()["open"] == 0


def test_the_watchdog_times_its_own_wait(recorder, monkeypatch):
    class Late:
        """The loop's `wait`: the first returns 0.3 s later than asked."""
        waits = 0

        def wait(self, timeout):
            self.waits += 1
            time.sleep(timeout + (0.3 if self.waits == 1 else 0.0))
            return False

        def clear(self):
            pass

    late = Late()
    monkeypatch.setattr(obs.WATCHDOG, "_wake", late)
    ticket = obs.WATCHDOG.open("fit", "a_fit", expected_s=30.0,
                               threshold_s=60.0)
    try:
        deadline = time.monotonic() + 10.0
        while late.waits < 3 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert late.waits >= 3
        assert obs.WATCHDOG.late_s == pytest.approx(0.3, abs=0.1)
        assert recorder.counters()["watchdog.late_s"] == obs.WATCHDOG.late_s
        mine, = [t for t in obs.WATCHDOG.inflight() if t["id"] == ticket]
        assert mine["hard"] is False and mine["threshold_s"] == 60.0
    finally:
        obs.WATCHDOG.close(ticket)
    obs.reset()
    assert obs.WATCHDOG.late_s == 0.0

