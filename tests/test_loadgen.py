"""Open-loop load harness (sml_tpu/loadgen, ISSUE 19).

Acceptance covered here: deterministic trace compilation, the
coordinated-omission proof (open- vs closed-loop tails diverge on a
stalled scorer), explicit overrun accounting (never silent), the typed
bounded-wait `RequestTimeout`, the tail-engineering ladder (flush
auto-tune bounds, burn-slope admission pre-tightening) and per-phase
worst-request exemplar recovery through the flight-recorder ring.
"""

import threading
import time

import numpy as np
import pytest

from sml_tpu import obs
from sml_tpu.conf import GLOBAL_CONF
from sml_tpu.loadgen import (OpenLoopDriver, PhaseSpec, TraceSpec,
                             closed_loop_probe)
from sml_tpu.serving import MicroBatcher, RequestTimeout
from sml_tpu.utils.profiler import PROFILER, now


@pytest.fixture()
def profiler_on():
    old = GLOBAL_CONF.get("sml.profiler.enabled")
    GLOBAL_CONF.set("sml.profiler.enabled", True)
    yield PROFILER
    GLOBAL_CONF.set("sml.profiler.enabled", old)


@pytest.fixture()
def obs_on():
    old = GLOBAL_CONF.get("sml.obs.enabled")
    GLOBAL_CONF.set("sml.obs.enabled", True)
    obs.reset()
    yield
    GLOBAL_CONF.set("sml.obs.enabled", old)
    obs.reset()


# ---------------------------------------------------------------- spec
def test_trace_compile_deterministic():
    """Same spec + seed -> byte-identical schedule; the mixes only ever
    sample declared values; phase offsets partition the timeline."""
    spec = TraceSpec(
        phases=(PhaseSpec("steady", 2.0, 40.0),
                PhaseSpec("burst", 2.0, 40.0, arrival="bursty"),
                PhaseSpec("ramp", 2.0, 20.0, 60.0)),
        widths=((8, 0.8), (128, 0.2)),
        classes=(("high", 0.3), ("normal", 0.7)),
        models=(("a", 0.5), ("b", 0.5)),
        seed=7)
    a, b = spec.compile(), spec.compile()
    assert a == b
    assert len(a) > 100
    assert [r.index for r in a] == list(range(len(a)))
    ts = [r.t for r in a]
    assert ts == sorted(ts)
    assert {r.phase for r in a} == {"steady", "burst", "ramp"}
    assert {r.rows for r in a} <= {8, 128}
    assert {r.priority for r in a} <= {"high", "normal"}
    assert {r.model for r in a} <= {"a", "b"}
    bounds = {"steady": (0.0, 2.0), "burst": (2.0, 4.0),
              "ramp": (4.0, 6.0)}
    for r in a:
        lo, hi = bounds[r.phase]
        assert lo <= r.t < hi
    other = TraceSpec(phases=spec.phases, widths=spec.widths,
                      classes=spec.classes, models=spec.models,
                      seed=8).compile()
    assert other != a


def test_bursty_modulation_and_validation():
    """The burst square wave preserves the phase MEAN rate while the
    instantaneous rate swings to burst_factor x nominal; impossible
    burst parameters and unknown processes refuse at compile."""
    ph = PhaseSpec("b", 8.0, 50.0, arrival="bursty")
    grid = np.linspace(0.0, 8.0, 8001)[:-1]
    rates = [ph.rate_at(float(t)) for t in grid]
    assert abs(float(np.mean(rates)) - 50.0) / 50.0 < 0.02
    assert max(rates) == pytest.approx(150.0)
    # the thinning generator realizes roughly the declared mean
    n = len(TraceSpec(phases=(ph,), seed=3).compile())
    assert 0.7 * 400 < n < 1.3 * 400
    rng = np.random.default_rng(0)
    with pytest.raises(ValueError):
        PhaseSpec("x", 1.0, 10.0, arrival="bursty", burst_factor=6.0,
                  burst_fraction=0.2).arrivals(rng)
    with pytest.raises(ValueError):
        PhaseSpec("x", 1.0, 10.0, arrival="warp").arrivals(rng)
    with pytest.raises(ValueError):
        TraceSpec(phases=(PhaseSpec("dup", 1.0, 1.0),
                          PhaseSpec("dup", 1.0, 1.0))).compile()


# -------------------------------------------------------------- driver
def _stall_scorer(stall_at=5, stall_s=0.5):
    """Single-threaded server that freezes for `stall_s` on one call —
    the pathology coordinated omission hides."""
    lock = threading.Lock()
    calls = {"n": 0}

    def score(X, priority, model):
        with lock:
            calls["n"] += 1
            if calls["n"] == stall_at:
                time.sleep(stall_s)
        return X

    return score


def test_open_vs_closed_loop_divergence_omission_proof():
    """THE reason this package exists: on a stalled server, the
    open-loop driver charges every scheduled-but-unanswered request the
    stall it sat through, while the closed-loop control slows its own
    arrivals down and reports one slow sample — tails that differ by an
    order of magnitude for the same server and the same schedule."""
    spec = TraceSpec(
        phases=(PhaseSpec("steady", 1.0, 100.0, arrival="uniform"),),
        seed=1)
    reqs = spec.compile()
    open_rep = OpenLoopDriver(_stall_scorer(), reqs, workers=8,
                              overrun_micros=10_000_000).run()
    closed = closed_loop_probe(_stall_scorer(), reqs)
    assert len(closed) == len(reqs)
    closed_p99 = float(np.percentile(np.asarray(closed), 99.0))
    open_p99 = float(open_rep["phases"]["steady"]["p99_ms"])
    # ~half the schedule lands inside the 500ms stall open-loop
    assert open_p99 > 100.0
    assert closed_p99 < open_p99 / 5.0


def test_overrun_accounting_never_silent_and_single_shot():
    """A pool too small for the schedule books every delayed fire as an
    overrun in the driver's OWN accounting (profiler off), and the
    delayed requests still get pessimistic schedule-charged latency."""
    spec = TraceSpec(
        phases=(PhaseSpec("steady", 0.3, 50.0, arrival="uniform"),),
        classes=(("high", 0.5), ("normal", 0.5)), seed=2)
    reqs = spec.compile()

    def slow(X, priority, model):
        time.sleep(0.08)
        return X

    driver = OpenLoopDriver(slow, reqs, workers=1, overrun_micros=5000)
    rep = driver.run()
    assert rep["overrun"] > 0
    assert rep["requests"] == len(reqs) == rep["served"]
    assert rep["shed"] == rep["timeout"] == rep["errors"] == 0
    ph = rep["phases"]["steady"]
    assert ph["p50_ms"] <= ph["p99_ms"] <= ph["p999_ms"] \
        <= ph["worst_ms"] + 1e-6
    assert sum(c["count"] for c in ph["classes"].values()) \
        == ph["requests"]
    # serialized 80ms service behind one worker: the last request's
    # schedule-charged latency dwarfs its service time
    assert ph["worst_ms"] > 200.0
    with pytest.raises(RuntimeError):
        driver.run()


def test_load_report_exemplars_and_engine_health(obs_on):
    """Per-phase worst-request exemplars round-trip through the
    flight-recorder ring, and the last completed replay is the `load`
    block of engine_health()."""
    spec = TraceSpec(
        phases=(PhaseSpec("a", 0.2, 60.0, arrival="uniform"),
                PhaseSpec("b", 0.2, 60.0, arrival="uniform")),
        classes=(("high", 0.5), ("normal", 0.5)), seed=4)
    rep = OpenLoopDriver(lambda X, p, m: X, spec.compile(), workers=4,
                         overrun_micros=10_000_000).run()
    ring = {(e.args or {}).get("trace")
            for e in obs.RECORDER.events() if e.name == "trace.request"}
    assert set(rep["phases"]) == {"a", "b"}
    for ph in rep["phases"].values():
        assert ph["worst_trace"]
        assert int(ph["worst_trace"], 16) in ring
    health = obs.engine_health()
    assert health["load"]["requests"] == rep["requests"]
    assert set(health["load"]["phases"]) == {"a", "b"}


# ------------------------------------------------- bounded-wait futures
def test_request_timeout_typed_counted_and_future_resolvable(
        profiler_on):
    """result(timeout=) raises the TYPED RequestTimeout (a TimeoutError
    subclass), counts serve.timeout, and leaves the future resolvable —
    the batch that finally flushes still completes it."""
    mb = MicroBatcher(lambda X: np.asarray(X).sum(axis=1),
                      flush_micros=5_000, start=False)
    try:
        fut = mb.submit(np.ones((2, 3), dtype=np.float32))
        before = PROFILER.counters().get("serve.timeout", 0.0)
        with pytest.raises(RequestTimeout):
            fut.result(timeout=0.05)
        assert isinstance(RequestTimeout("x"), TimeoutError)
        assert PROFILER.counters().get("serve.timeout", 0.0) \
            == before + 1
        mb.start()  # arm the flush worker: the SAME future resolves
        out = fut.result(timeout=5.0)
        np.testing.assert_allclose(np.asarray(out).ravel(), [3.0, 3.0])
    finally:
        mb.close()


# ------------------------------------------------- tail engineering
def test_flush_autotune_within_slo_budget_never_below_drain(obs_on):
    """sml.serve.flushAutoTune: sparse traffic converges the deadline
    to the SLO-slack ceiling (never holds lone requests to a mis-tuned
    window); intense traffic tracks the batch fill time; the deadline
    never tunes below the measured drain. The drain signal is the
    serving path's OWN flush wall (serve.batch_ms) — the audit's
    dispatch walls, fed here with a wildly different value, must lose."""
    from sml_tpu.obs._metrics import METRICS
    prev_slo = GLOBAL_CONF.get("sml.serve.sloMillis")
    GLOBAL_CONF.set("sml.serve.sloMillis", 50)
    try:
        for _ in range(32):
            METRICS.observe("serve.batch_ms", 5.0)
            # decoy: were the tuner still reading the audit histograms,
            # drain=30ms would pin the ceiling at 30ms, not 20ms
            METRICS.observe("dispatch.device_ms", 30.0)
        mb = MicroBatcher(lambda X: X, flush_auto=True,
                          flush_micros=40_000, max_batch_rows=64,
                          start=False)
        try:
            # sparse traffic (no arrivals): target = SLO-slack ceiling
            # = max(50*0.5 - drain, drain) = 20ms, down from 40ms
            for _ in range(20):
                mb._autotune()
            assert mb.flush_micros == pytest.approx(20_000, rel=0.05)
            # intense traffic: 5000 rows/s fills a 64-row batch in
            # 12.8ms — the deadline follows the fill time instead
            t = now()
            for _ in range(100):
                mb._arrivals.append((t, 100))
            for _ in range(20):
                mb._autotune()
            assert mb.flush_micros == pytest.approx(12_800, rel=0.10)
            # floor: never below the predicted drain (5ms median)
            assert mb.flush_micros >= 5_000
        finally:
            mb.close()
    finally:
        GLOBAL_CONF.set("sml.serve.sloMillis", prev_slo)


def test_burn_slope_tightens_admission_before_breach(profiler_on):
    """sml.fleet.burstSlope*: a rising burn TREND that extrapolates
    past 1.0 within the horizon pre-tightens the non-top classes
    (counted fleet.burst_tighten) while the LEVEL is still under
    budget; horizon 0 disables the predictor; the top class never
    tightens."""
    from sml_tpu.fleet import Router
    keys = ("sml.fleet.burstSlopeWindowSec",
            "sml.fleet.burstSlopeHorizonSec",
            "sml.fleet.burstSlopeTighten")
    prev = {k: GLOBAL_CONF.get(k) for k in keys}
    try:
        GLOBAL_CONF.set("sml.fleet.burstSlopeWindowSec", 30.0)
        GLOBAL_CONF.set("sml.fleet.burstSlopeTighten", 0.25)
        router = Router(None, priorities=["high", "normal"])
        t = now()
        # cached burn LEVEL 0.9 (under budget), TREND +0.2/s
        router._burn = (0.9, t + 60.0)
        for dt, v in ((-2.0, 0.5), (-1.0, 0.7), (0.0, 0.9)):
            router._burn_hist.append((t + dt, v))
        GLOBAL_CONF.set("sml.fleet.burstSlopeHorizonSec", 0.0)
        assert router._class_fraction(1) == pytest.approx(0.5)
        GLOBAL_CONF.set("sml.fleet.burstSlopeHorizonSec", 1.0)
        before = PROFILER.counters().get("fleet.burst_tighten", 0.0)
        # 0.9 + 0.2 * 1.0 = 1.1 > 1.0: breach predicted -> tighten
        assert router._class_fraction(1) == pytest.approx(0.5 * 0.25)
        assert PROFILER.counters().get("fleet.burst_tighten", 0.0) \
            == before + 1
        assert router._class_fraction(0) == pytest.approx(1.0)
        # once the LEVEL itself breaches, the level rule takes over
        router._burn = (1.2, now() + 60.0)
        assert router._class_fraction(1) == pytest.approx(0.5 * 0.5)
    finally:
        for k, v in prev.items():
            GLOBAL_CONF.set(k, v)
