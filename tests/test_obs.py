"""Flight recorder (sml_tpu.obs): event bus, Chrome-trace export,
dispatch audit, HBM memory ledger, run autologging, and the
disabled-path overhead contract (PR 2 tentpole + acceptance criteria).
"""

import json
import time

import numpy as np
import pandas as pd
import pytest

from sml_tpu import obs
from sml_tpu.conf import GLOBAL_CONF
from sml_tpu.parallel import dispatch
from sml_tpu.parallel.dispatch import WorkHint
from sml_tpu.utils.profiler import PROFILER


@pytest.fixture()
def recorder():
    """Recorder + profiler on, clean state; everything restored after."""
    GLOBAL_CONF.set("sml.obs.enabled", True)
    GLOBAL_CONF.set("sml.profiler.enabled", True)
    PROFILER.reset()
    obs.reset()
    try:
        yield obs.RECORDER
    finally:
        GLOBAL_CONF.set("sml.obs.enabled", False)
        GLOBAL_CONF.set("sml.profiler.enabled", False)
        GLOBAL_CONF.set("sml.obs.sinkPath", "")
        GLOBAL_CONF.set("sml.obs.ringEvents", 65536)
        GLOBAL_CONF.set("sml.obs.sinkMaxBytes", 64 << 20)
        PROFILER.reset()
        obs.reset()


def _fresh_frame(spark, n=4000, seed=None):
    """Unique-content frame so staging-cache misses are guaranteed (the
    content-keyed caches survive across tests in one process)."""
    rng = np.random.default_rng(np.random.SeedSequence(seed))
    pdf = pd.DataFrame({
        "k": rng.choice(["a", "b", "c"], n, p=[0.8, 0.1, 0.1]),
        "x1": rng.normal(size=n), "x2": rng.normal(size=n),
    })
    pdf["label"] = pdf["x1"] * 2 + rng.normal(size=n)
    return spark.createDataFrame(pdf)


def _fit_and_shuffle(spark):
    from sml_tpu.ml import Pipeline
    from sml_tpu.ml.feature import VectorAssembler
    from sml_tpu.ml.regression import LinearRegression
    df = _fresh_frame(spark)
    df.groupBy("k").count().toPandas()
    Pipeline(stages=[
        VectorAssembler(inputCols=["x1", "x2"], outputCol="features"),
        LinearRegression(labelCol="label")]).fit(df)
    return df


# ------------------------------------------------------- chrome trace export
def test_chrome_trace_roundtrip(spark, recorder, tmp_path):
    """Acceptance: a Pipeline fit + groupBy shuffle exports a trace that
    json.loads with >= 4 distinct tracks (host ops, device programs,
    >= 2 counter tracks), well-formed ph/ts/dur/pid/tid fields, properly
    stacked nested spans, and monotonic byte-volume counter tracks."""
    _fit_and_shuffle(spark)
    path = str(tmp_path / "trace.json")
    assert obs.export_chrome_trace(path) == path
    doc = json.load(open(path))
    evs = doc["traceEvents"]

    spans = [e for e in evs if e["ph"] == "X"]
    counters = [e for e in evs if e["ph"] == "C"]
    assert spans and counters
    for e in spans:
        assert {"ph", "ts", "dur", "pid", "tid", "name"} <= set(e)
        assert e["ts"] >= 0 and e["dur"] >= 0
    for e in counters:
        assert {"ph", "ts", "pid", "tid", "name", "args"} <= set(e)

    # >= 4 distinct tracks: host-op thread lanes + the virtual device
    # track + counter tracks
    span_tracks = {(e["pid"], e["tid"]) for e in spans}
    counter_tracks = {e["name"] for e in counters}
    host_tracks = {t for t in span_tracks if t[0] == 1}
    device_tracks = {t for t in span_tracks if t[0] == 2}
    assert host_tracks, "no host-op track"
    assert device_tracks, "no device-program track"
    assert len(counter_tracks) >= 2, counter_tracks
    assert len(span_tracks) + len(counter_tracks) >= 4
    # dispatched programs (and only those) ride the device track
    assert all(e["name"].startswith("program.")
               for e in spans if e["pid"] == 2)

    # nested spans stack: within a lane, spans are disjoint or contained
    for track in span_tracks:
        lane = sorted((e for e in spans if (e["pid"], e["tid"]) == track),
                      key=lambda e: (e["ts"], -e["dur"]))
        stack = []
        eps = 50.0  # us: perf_counter rounding slack
        for e in lane:
            while stack and e["ts"] >= stack[-1]["ts"] + stack[-1]["dur"] - eps:
                stack.pop()
            if stack:
                assert e["ts"] + e["dur"] <= \
                    stack[-1]["ts"] + stack[-1]["dur"] + eps, \
                    (e, stack[-1])
            stack.append(e)

    # byte-volume counter tracks are cumulative => monotone nondecreasing
    for name in ("staging.h2d_bytes", "staging.d2h_bytes"):
        vals = [e["args"]["value"] for e in counters if e["name"] == name]
        assert vals, f"missing counter track {name}"
        assert vals == sorted(vals), name

    # a nested-span pair actually exists (materialize chains nest)
    host_lane = [e for e in spans if e["pid"] == 1]
    nested = any(
        a is not b and a["ts"] <= b["ts"]
        and b["ts"] + b["dur"] <= a["ts"] + a["dur"] + 50.0
        for a in host_lane for b in host_lane)
    assert nested, "expected at least one nested host span pair"


# ------------------------------------------------------------ dispatch audit
def test_audit_lists_dispatches_with_predictions(spark, recorder):
    """Acceptance: after a fit, audit_report() lists every dispatch with
    predicted host/device times, and program spans attach measured wall
    times."""
    _fit_and_shuffle(spark)
    recs = obs.audit_records()
    assert recs, "no dispatch decisions recorded"
    for r in recs:
        assert r.route in ("host", "device")
        assert r.t_host >= 0 and r.t_device >= 0
        assert r.kind
    assert any(r.measured is not None for r in recs)
    report = obs.audit_report()
    assert "dispatch audit" in report
    assert "pred_host" in report and "measured" in report
    assert f"{len(recs)} decisions" in report


@pytest.fixture
def remote_device(monkeypatch):
    """A TPU this process does not own, with a pinned slow link
    calibration (as in test_dispatch.py) — the case `auto` prices."""
    monkeypatch.setattr(dispatch, "_default_backend", lambda: "tpu")
    monkeypatch.setattr(dispatch, "_locally_attached", lambda: False)
    cal = dispatch._Calibration()
    cal._done = True
    cal.rt_fixed = 0.15
    cal.h2d_bw = 200e6
    cal.d2h_bw = 20e6
    monkeypatch.setattr(dispatch, "CALIBRATION", cal)
    yield cal


def test_forced_device_misroute_flagged(recorder, remote_device):
    """Satellite: sml.dispatch.mode=device on tiny work must surface a
    predicted-vs-actual inversion in the audit — the forced device route
    measured far slower than the host prediction."""
    GLOBAL_CONF.set("sml.dispatch.mode", "device")
    try:
        route, _ = dispatch.decide(WorkHint(flops=1e6, kind="blas"))
        assert route == "device"
        with PROFILER.span("program.tiny", route="device"):
            time.sleep(0.02)
    finally:
        GLOBAL_CONF.set("sml.dispatch.mode", "auto")
    rec = obs.audit_records()[-1]
    assert rec.forced and rec.reason == "forced-mode"
    assert rec.route == "device"
    assert rec.measured is not None and rec.measured >= 0.02
    assert rec.t_host < rec.t_device  # the model would have said host
    assert rec.misroute
    report = obs.audit_report()
    assert "MISROUTE" in report and "predicted-inversion" in report


def test_probe_decisions_are_not_double_counted(recorder, remote_device,
                                                monkeypatch):
    """_route_mesh prices with internal decide() probes; the audit must
    count DISPATCHES, not probes — exactly one row per routed program."""
    from sml_tpu.ml import _staging
    monkeypatch.setattr(dispatch, "OBSERVED_HOST", dispatch._ObservedRates())
    # resident device loses outright -> the early host fast path
    obs._audit.reset()
    _mesh, route = _staging._route_mesh(WorkHint(flops=1e8, kind="blas"), ())
    assert route == "host"
    recs = obs.audit_records()
    assert len(recs) == 1, [(r.route, r.forced) for r in recs]
    assert recs[0].route == "host" and not recs[0].forced
    # resident device wins but the H2D charge flips it -> the priced path
    obs._audit.reset()
    X = np.random.default_rng(3).normal(size=(4096, 64)).astype(np.float32)
    remote_device.h2d_bw = 1e6
    _mesh, route = _staging._route_mesh(WorkHint(flops=5e9, kind="blas"),
                                        (X,), may_promote=False)
    assert route == "host"
    recs = obs.audit_records()
    assert len(recs) == 1, [(r.route, r.forced) for r in recs]
    assert recs[0].route == "host" and not recs[0].forced


def test_uncalibrated_forced_route_does_not_calibrate(recorder, monkeypatch):
    """audit_preroute on a forced route must not trigger the link
    calibration probe (observability must not change engine behavior);
    the uncalibrated record is marked and exempt from host-side misroute
    judgment."""
    monkeypatch.setattr(dispatch, "_default_backend", lambda: "tpu")
    cal = dispatch._Calibration()   # NOT done: ensure() would measure
    monkeypatch.setattr(dispatch, "CALIBRATION", cal)
    GLOBAL_CONF.set("sml.dispatch.mode", "host")
    try:
        route, _ = dispatch.decide(WorkHint(flops=1e6, kind="blas"))
    finally:
        GLOBAL_CONF.set("sml.dispatch.mode", "auto")
    assert route == "host"
    assert not cal._done, "audit must not have run the calibration probe"
    rec = obs.audit_records()[-1]
    assert rec.forced and not rec.calibrated
    rec.measured = 10.0  # even a huge wall can't flag an unjudgeable row
    assert not rec.misroute


def test_audit_not_recorded_when_disabled(remote_device):
    GLOBAL_CONF.set("sml.obs.enabled", False)
    assert not obs.RECORDER.enabled
    obs._audit.reset()
    dispatch.decide(WorkHint(flops=1e12, kind="blas"))
    assert obs.audit_records() == []


# ------------------------------------------------------------- memory ledger
def test_memory_ledger_tracks_pools(spark, recorder):
    """A tree fit allocates into the bin cache; the ledger's live/peak
    bytes and memory_report() surface it."""
    from sml_tpu.ml import Pipeline
    from sml_tpu.ml.feature import VectorAssembler
    from sml_tpu.ml.regression import DecisionTreeRegressor
    df = _fresh_frame(spark, seed=None)
    before = obs.LEDGER.snapshot().get("bin_cache", {"live": 0})["live"]
    Pipeline(stages=[
        VectorAssembler(inputCols=["x1", "x2"], outputCol="features"),
        DecisionTreeRegressor(labelCol="label", maxDepth=3, maxBins=16),
    ]).fit(df)
    snap = obs.LEDGER.snapshot()
    assert snap["bin_cache"]["live"] > before
    assert snap["bin_cache"]["peak"] >= snap["bin_cache"]["live"]
    assert snap["_total"]["peak"] >= snap["bin_cache"]["peak"]
    report = obs.memory_report()
    assert "bin_cache" in report and "TOTAL" in report
    # the exporter got hbm counter-track events for the allocation
    assert any(e.name == "hbm.bin_cache_bytes"
               for e in obs.RECORDER.events())


def test_ledger_alloc_free_and_peaks():
    obs.LEDGER.alloc("boost_margin", 1000)
    obs.LEDGER.alloc("boost_margin", 500)
    obs.LEDGER.free("boost_margin", 1500)
    snap = obs.LEDGER.snapshot()["boost_margin"]
    assert snap["live"] == 0 and snap["peak"] >= 1500
    obs.LEDGER.reset_peaks()
    assert obs.LEDGER.snapshot()["boost_margin"]["peak"] == 0


# ----------------------------------------------------- ring + sink mechanics
def test_tid_map_bounded_under_short_lived_threads(recorder):
    """Satellite: the thread-id -> dense-tid map must not grow forever
    under serving's short-lived client threads — past _MAX_TIDS, dead
    threads' slots are reclaimed and reused."""
    import threading

    from sml_tpu.obs._recorder import _MAX_TIDS

    def emit_once(i):
        obs.RECORDER.emit("cache", "cache.test", args={"i": i})

    for i in range(_MAX_TIDS + 90):
        t = threading.Thread(target=emit_once, args=(i,))
        t.start()
        t.join()
    assert len(obs.RECORDER._tids) <= _MAX_TIDS + 1, \
        "dead-thread tid slots leaked"
    # reclaimed lanes stay DENSE: no tid ever exceeded the bound
    tids = {e.tid for e in obs.RECORDER.events()
            if e.name == "cache.test"}
    assert max(tids) < _MAX_TIDS + 1
    # and the newest emits were recorded (reuse, not refusal)
    seen = {e.args["i"] for e in obs.RECORDER.events()
            if e.name == "cache.test"}
    assert _MAX_TIDS + 89 in seen


def test_ring_is_bounded_and_counts_drops(recorder):
    GLOBAL_CONF.set("sml.obs.ringEvents", 32)
    for i in range(100):
        obs.RECORDER.emit("cache", "cache.test", args={"i": i})
    evs = obs.RECORDER.events()
    assert len(evs) == 32
    assert evs[-1].args["i"] == 99  # newest survive
    assert obs.RECORDER.dropped >= 68


def test_jsonl_sink_writes_events(recorder, tmp_path):
    sink = tmp_path / "events.jsonl"
    GLOBAL_CONF.set("sml.obs.sinkPath", str(sink))
    PROFILER.count("staging.cache_hit")
    with PROFILER.span("program.sink_test", route="host"):
        pass
    lines = [json.loads(ln) for ln in sink.read_text().splitlines()]
    assert lines
    kinds = {ln["kind"] for ln in lines}
    assert "counter" in kinds and "span" in kinds
    assert all("ts" in ln and "name" in ln for ln in lines)


def test_jsonl_sink_rotates_once_at_byte_bound(recorder, tmp_path):
    """Satellite: past sml.obs.sinkMaxBytes the live file rolls to
    <path>.1 (replacing the previous roll) and reopens fresh — the sink
    is bounded at ~2x the knob instead of growing without limit, and
    rotation never splits a record."""
    sink = tmp_path / "events.jsonl"
    GLOBAL_CONF.set("sml.obs.sinkPath", str(sink))
    GLOBAL_CONF.set("sml.obs.sinkMaxBytes", 4096)
    for i in range(400):
        obs.RECORDER.emit("cache", "cache.rotate_test", args={"i": i})
    rolled = tmp_path / "events.jsonl.1"
    assert rolled.exists(), "no rotation happened"
    assert sink.stat().st_size < 4096 + 512  # live file re-bounded
    # every line in BOTH files is a complete JSON record, and the live
    # file continues the sequence the roll left off at
    seen = []
    for path in (rolled, sink):
        for ln in path.read_text().splitlines():
            rec = json.loads(ln)
            if rec["name"] == "cache.rotate_test":
                seen.append(rec["args"]["i"])
    assert seen == sorted(seen)
    assert seen[-1] == 399
    # ~2x bound: at most bound bytes per file (+ one record of slack)
    assert rolled.stat().st_size <= 4096 + 512


def test_jsonl_sink_rotation_preserves_line_atomicity(recorder, tmp_path):
    """Satellite: concurrent emitters across a rotation never interleave
    or tear a line — writes and the roll both happen under the emit
    lock."""
    import threading
    sink = tmp_path / "events.jsonl"
    GLOBAL_CONF.set("sml.obs.sinkPath", str(sink))
    GLOBAL_CONF.set("sml.obs.sinkMaxBytes", 2048)

    def emitter(tid):
        for i in range(150):
            obs.RECORDER.emit("cache", "cache.rotate_test",
                              args={"t": tid, "i": i, "pad": "x" * 40})

    threads = [threading.Thread(target=emitter, args=(t,))
               for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = 0
    for path in (tmp_path / "events.jsonl.1", sink):
        if not path.exists():
            continue
        for ln in path.read_text().splitlines():
            rec = json.loads(ln)  # raises on a torn/interleaved line
            if rec["name"] == "cache.rotate_test":
                total += 1
    # both surviving files parse cleanly; with a single rotation the
    # oldest roll may be gone, but what is on disk is never torn
    assert total > 0


# ------------------------------------------------- disabled-path overhead
def test_disabled_recorder_costs_one_attribute_load():
    """Satellite + acceptance: with sml.obs.enabled=false the
    instrumentation is within noise of free — the ring records nothing,
    and per-event cost stays microscopic (generous bound: the actual
    cost is ~1us; the bound only guards against an accidental conf
    lookup or lock acquisition landing on the hot path)."""
    GLOBAL_CONF.set("sml.obs.enabled", False)
    GLOBAL_CONF.set("sml.profiler.enabled", False)
    assert not obs.RECORDER.enabled
    obs.RECORDER.reset()
    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        PROFILER.count("staging.cache_hit")
    per_event = (time.perf_counter() - t0) / n
    assert per_event < 20e-6, f"{per_event * 1e6:.2f}us per disabled event"
    assert obs.RECORDER.events() == []
    assert obs.RECORDER.counters() == {}
    # spans: same contract
    t0 = time.perf_counter()
    for _ in range(n):
        with PROFILER.span("program.noop"):
            pass
    per_span = (time.perf_counter() - t0) / n
    assert per_span < 30e-6, f"{per_span * 1e6:.2f}us per disabled span"
    assert obs.RECORDER.events() == []
    # streaming metrics registry (PR 7): same contract — recording into a
    # disabled registry is a no-op with no histogram allocation
    obs.METRICS.reset()
    t0 = time.perf_counter()
    for _ in range(n):
        obs.METRICS.observe("serve.request_ms", 1.5)
    per_obs = (time.perf_counter() - t0) / n
    assert per_obs < 20e-6, f"{per_obs * 1e6:.2f}us per disabled observe"
    assert obs.METRICS.names() == []
    assert obs.METRICS.histogram("serve.request_ms") is None
    # skew hooks (PR 7): a disabled note() allocates nothing either
    obs.SKEW.reset()
    profile = [0.01] * 8
    t0 = time.perf_counter()
    for _ in range(2000):
        obs.SKEW.note("program.noop", profile)
    per_note = (time.perf_counter() - t0) / 2000
    assert per_note < 20e-6, f"{per_note * 1e6:.2f}us per disabled note"
    assert obs.SKEW.programs() == []
    assert obs.straggler_report() is None
    # trace context (PR 8): disabled current()/mint/fan_in return None
    # behind one attribute load — no ContextVar read, no allocation
    from sml_tpu.obs import _context
    t0 = time.perf_counter()
    for _ in range(n):
        _context.current()
    per_ctx = (time.perf_counter() - t0) / n
    assert per_ctx < 20e-6, f"{per_ctx * 1e6:.2f}us per disabled current"
    assert _context.current() is None
    assert _context.mint_request(rows=1) is None
    assert _context.fan_in([]) is None
    assert obs.RECORDER.events() == []  # mint emitted nothing
    # stall watchdog (PR 8): disabled open() registers nothing, starts
    # no thread, and costs one attribute load
    t0 = time.perf_counter()
    for _ in range(n):
        obs.WATCHDOG.open("dispatch", "program.noop")
    per_open = (time.perf_counter() - t0) / n
    assert per_open < 20e-6, f"{per_open * 1e6:.2f}us per disabled open"
    assert obs.WATCHDOG.report()["open"] == 0
    # exemplar-carrying observe: same disabled contract as plain observe
    t0 = time.perf_counter()
    for _ in range(n):
        obs.METRICS.observe("serve.request_ms", 1.5, exemplar=12345)
    per_ex = (time.perf_counter() - t0) / n
    assert per_ex < 20e-6, f"{per_ex * 1e6:.2f}us per disabled exemplar"
    assert obs.METRICS.histogram("serve.request_ms") is None
    # a fit's record (PR 52): off, a running total and a kept record are
    # no-ops behind the same attribute load, a root fit leaves no record,
    # reads no clock of the process's and opens no ticket, and the
    # collector's hook is not in the interpreter's list: nothing runs
    import gc
    t0 = time.perf_counter()
    for _ in range(n):
        obs.RECORDER.total("fit.gc_s", 0.5)
    per_total = (time.perf_counter() - t0) / n
    assert per_total < 20e-6, f"{per_total * 1e6:.2f}us per disabled total"
    obs.RECORDER.keep_fit({"trace": 1})
    assert obs.fit_records() == [] and obs.RECORDER.counters() == {}
    assert obs.RECORDER._gc not in gc.callbacks
    from sml_tpu.ml import Pipeline
    estimator = Pipeline(stages=[])
    t0 = time.perf_counter()
    for _ in range(n):
        with obs.autolog_fit(estimator, None):
            pass
    per_fit = (time.perf_counter() - t0) / n
    assert per_fit < 30e-6, f"{per_fit * 1e6:.2f}us per disabled root fit"
    assert obs.fit_records() == [] and obs.RECORDER.events() == []
    t0 = time.perf_counter()
    for _ in range(n):
        obs.WATCHDOG.open("fit", "Pipeline", expected_s=1.0, threshold_s=1.25)
    per_open = (time.perf_counter() - t0) / n
    assert per_open < 20e-6, f"{per_open * 1e6:.2f}us per disabled fit ticket"
    assert obs.WATCHDOG.report()["open"] == 0


# -------------------------------------------------------- profiler reset fix
def test_profiler_reset_mid_span_invalidates_stack():
    """Satellite: a reset() while a span is open must not attribute later
    child time to the stale parent entry, and the straddling span itself
    must not be recorded (generation counter)."""
    GLOBAL_CONF.set("sml.profiler.enabled", True)
    try:
        PROFILER.reset()
        with PROFILER.span("outer"):
            PROFILER.reset()  # fires while `outer` is open
            with PROFILER.span("child"):
                time.sleep(0.005)
        spans = {s.name: s for s in PROFILER.spans()}
        # the straddling span is dropped; the post-reset child is intact
        assert "outer" not in spans
        assert "child" in spans
        child = spans["child"]
        # the child's full wall time is its own (no stale parent absorbed
        # it, and no stale stack entry corrupted its self time)
        assert child.self_s == pytest.approx(child.wall_s)
        # a fresh span after the dust settles records normally
        with PROFILER.span("after"):
            pass
        assert any(s.name == "after" for s in PROFILER.spans())
    finally:
        GLOBAL_CONF.set("sml.profiler.enabled", False)
        PROFILER.reset()


# -------------------------------------------------------- tracking autolog
def test_fit_autologs_engine_metrics(spark, recorder, tmp_path):
    """Acceptance: a fit under an active tracking run logs >= 6 engine.*
    metrics retrievable from the file-based store."""
    from sml_tpu import tracking
    from sml_tpu.ml import Pipeline
    from sml_tpu.ml.feature import VectorAssembler
    from sml_tpu.ml.regression import LinearRegression
    tracking.set_tracking_uri(str(tmp_path / "runs"))
    df = _fresh_frame(spark)
    with tracking.start_run(run_name="obs-autolog") as run:
        Pipeline(stages=[
            VectorAssembler(inputCols=["x1", "x2"], outputCol="features"),
            LinearRegression(labelCol="label")]).fit(df)
    rec = tracking.get_run(run.info.run_id)
    eng = {k: v for k, v in rec.data.metrics.items()
           if k.startswith("engine.")}
    assert len(eng) >= 6, sorted(eng)
    assert eng["engine.h2d_bytes"] > 0
    assert 0.0 <= eng["engine.cache_hit_rate"] <= 1.0


def test_no_autolog_without_active_run(spark, recorder, tmp_path):
    from sml_tpu import tracking
    from sml_tpu.ml import Pipeline
    from sml_tpu.ml.feature import VectorAssembler
    from sml_tpu.ml.regression import LinearRegression
    tracking.set_tracking_uri(str(tmp_path / "runs"))
    df = _fresh_frame(spark)
    Pipeline(stages=[
        VectorAssembler(inputCols=["x1", "x2"], outputCol="features"),
        LinearRegression(labelCol="label")]).fit(df)
    exp = tracking._store.default_experiment()["experiment_id"]
    assert tracking._store.list_runs(exp) == []  # no implicit runs


def test_engine_metrics_shape(recorder):
    m = obs.engine_metrics()
    assert len(m) >= 6
    assert all(k.startswith("engine.") for k in m)
    assert all(isinstance(v, float) for v in m.values())
