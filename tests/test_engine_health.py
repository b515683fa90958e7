"""Engine health layer (ISSUE 7): streaming metrics core, per-device
straggler attribution and the engine_health() snapshot.

Acceptance:
- log-bucketed p50/p99 land within ONE BUCKET WIDTH (2**(1/8)) of the
  exact sorted-sample computation at the same rank;
- an injected 8-device skewed timing profile names the slow chip, the
  skew ratio matches the injected imbalance, and the report survives a
  Chrome-trace export round-trip;
- `engine_health()` is populated (metric quantiles, audit, ledger, SLO
  burn-rate) after a serving-shaped load.
"""

import math
import time

import numpy as np
import pytest

from sml_tpu import obs
from sml_tpu.conf import GLOBAL_CONF
from sml_tpu.obs._metrics import BUCKET_GROWTH, LogHistogram
from sml_tpu.obs._trace import PID_SKEW, to_trace_events


@pytest.fixture()
def recorder():
    GLOBAL_CONF.set("sml.obs.enabled", True)
    obs.reset()
    try:
        yield obs.RECORDER
    finally:
        GLOBAL_CONF.set("sml.obs.enabled", False)
        obs.reset()


# ------------------------------------------------------- metrics histograms
def _exact_quantile(samples, q):
    srt = np.sort(samples)
    rank = min(max(int(math.ceil(q * len(srt))), 1), len(srt))
    return float(srt[rank - 1])


def test_histogram_percentile_parity_with_exact_sort():
    """Satellite: the log-bucketed p50/p99 over a serving-leg-shaped
    latency sample lands within one bucket width of the exact
    sorted-sample quantile at the same rank — the precision contract
    of reading percentiles from buckets and not from kept samples."""
    rng = np.random.default_rng(42)
    # a serving run's shape: ~2000 lognormal request latencies ms
    samples = np.exp(rng.normal(1.2, 0.9, 2000))
    h = LogHistogram()
    for s in samples:
        h.observe(float(s))
    for q in (0.50, 0.90, 0.99):
        exact = _exact_quantile(samples, q)
        got = h.quantile(q)
        assert got > 0
        ratio = got / exact
        assert 1.0 / BUCKET_GROWTH <= ratio <= BUCKET_GROWTH, \
            (q, exact, got, ratio)
    assert h.count == len(samples)
    assert h.max == pytest.approx(float(samples.max()))
    assert h.min == pytest.approx(float(samples.min()))
    # mean is exact (tracked as a true sum, not from buckets)
    snap = h.snapshot()
    assert snap["mean"] == pytest.approx(float(samples.mean()))


def test_histogram_snapshots_merge_by_bucket_addition():
    """Mergeable snapshots: two shards' histograms combine into the same
    quantiles as one histogram over the union."""
    rng = np.random.default_rng(3)
    a_s, b_s = rng.exponential(5.0, 800), rng.exponential(20.0, 400)
    ha, hb, hu = LogHistogram(), LogHistogram(), LogHistogram()
    for s in a_s:
        ha.observe(float(s))
        hu.observe(float(s))
    for s in b_s:
        hb.observe(float(s))
        hu.observe(float(s))
    merged = obs.merge_snapshots(ha.snapshot(), hb.snapshot())
    assert merged["count"] == 1200
    assert merged["p50"] == pytest.approx(hu.quantile(0.5))
    assert merged["p99"] == pytest.approx(hu.quantile(0.99))
    assert merged["mean"] == pytest.approx(hu.snapshot()["mean"])
    # object-level merge matches too
    ha.merge(hb)
    assert ha.count == 1200
    assert ha.quantile(0.99) == pytest.approx(hu.quantile(0.99))


def test_histogram_count_above_and_rate():
    h = LogHistogram(window_s=60.0)
    for v in (1.0, 2.0, 4.0, 100.0, 200.0):
        h.observe(v)
    assert h.total_count() == 5
    # threshold far from bucket edges: exactly the two large samples
    assert h.count_above(50.0) == 2
    assert h.count_above(0.001) == 5
    assert h.rate_per_s(60.0) >= 0.0


def test_registry_routes_through_recorder_flag(recorder):
    obs.METRICS.observe("serve.request_ms", 3.0)
    assert obs.METRICS.histogram("serve.request_ms").count == 1
    snap = obs.METRICS.snapshot()
    assert snap["serve.request_ms"]["count"] == 1


# --------------------------------------------------- straggler attribution
INJECTED = [0.010] * 7 + [0.030]  # device 7 is 3x the others


def test_straggler_report_names_slow_chip_and_matches_imbalance(recorder):
    """Satellite: an injected 8-device skewed profile — the report names
    the slow chip and the skew ratio matches the injected imbalance."""
    attr = obs.SKEW.note("fit_8dev", INJECTED, wall_s=0.040,
                         psum_bytes=123456.0, psum_launches=8)
    assert attr["slowest_device"] == 7
    expected_ratio = max(INJECTED) / (sum(INJECTED) / len(INJECTED))
    assert attr["skew_ratio"] == pytest.approx(expected_ratio, rel=1e-6)
    # BSP decomposition: 7 chips each wait (0.030 - 0.010)
    assert attr["wait_s"] == pytest.approx(7 * 0.020)
    assert attr["collective_overhead_s"] == pytest.approx(0.010)
    rep = obs.straggler_report()
    assert rep["slowest_device"] == 7
    assert rep["n_devices"] == 8
    assert rep["skew_ratio"] == pytest.approx(expected_ratio, rel=1e-4)
    assert rep["psum_bytes"] == 123456.0
    assert rep["psum_launches"] == 8
    # wait share: 7 * 0.02 wait vs 8 * 0.01 + 0.03 compute
    total_c, total_w = sum(INJECTED), 7 * 0.020
    assert rep["wait_share"] == pytest.approx(
        total_w / (total_c + total_w), abs=1e-3)


def test_straggler_report_stable_across_trace_roundtrip(recorder):
    """Satellite: export the ring as a Chrome trace, rebuild the report
    from the trace's skew lanes — same slow chip, same skew ratio."""
    obs.SKEW.note("fit_8dev", INJECTED)
    obs.SKEW.note("fit_8dev_round2", [c * 2 for c in INJECTED])
    live = obs.straggler_report()
    trace = to_trace_events(obs.RECORDER.events())
    rebuilt = obs.skew_report_from_trace(trace)
    assert rebuilt is not None
    assert rebuilt["slowest_device"] == live["slowest_device"]
    assert rebuilt["n_devices"] == live["n_devices"]
    assert rebuilt["skew_ratio"] == pytest.approx(live["skew_ratio"],
                                                  rel=1e-3)
    assert rebuilt["wait_share"] == pytest.approx(live["wait_share"],
                                                  abs=1e-3)


def test_trace_renders_one_lane_per_device(recorder):
    """Acceptance: the Chrome trace gains a per-device process (pid 3)
    with one named lane per chip, compute and wait spans disjoint within
    each lane."""
    obs.SKEW.note("fit_8dev", INJECTED)
    trace = to_trace_events(obs.RECORDER.events())
    lanes = {e["tid"] for e in trace
             if e.get("ph") == "X" and e["pid"] == PID_SKEW}
    assert lanes == set(range(8))
    names = {e["args"]["name"] for e in trace
             if e.get("ph") == "M" and e["pid"] == PID_SKEW
             and e["name"] == "thread_name"}
    assert "device-7" in names
    # within a lane, compute ends where wait begins (no overlap)
    for tid in lanes - {7}:  # device 7 has no wait span
        lane = [e for e in trace if e.get("ph") == "X"
                and e["pid"] == PID_SKEW and e["tid"] == tid]
        lane.sort(key=lambda e: e["ts"])
        assert len(lane) == 2
        assert lane[0]["name"] == "skew.compute"
        assert lane[1]["name"] == "skew.wait"
        assert lane[1]["ts"] == pytest.approx(
            lane[0]["ts"] + lane[0]["dur"], abs=1.0)


def test_skew_note_honors_real_device_ids(recorder):
    """A caller may pass jax.Device.ids: the report and the trace
    lanes must indict the REAL chip, not the shard's row-order
    position (they differ on non-identity device assignments)."""
    attr = obs.SKEW.note("fit", [0.01, 0.09, 0.02], devices=[12, 7, 30])
    assert attr["slowest_device"] == 7
    rep = obs.straggler_report()
    assert rep["slowest_device"] == 7
    assert {d["device"] for d in rep["per_device"]} == {7, 12, 30}
    trace = to_trace_events(obs.RECORDER.events())
    lanes = {e["tid"] for e in trace
             if e.get("ph") == "X" and e["pid"] == PID_SKEW}
    assert lanes == {7, 12, 30}
    rebuilt = obs.skew_report_from_trace(trace)
    assert rebuilt["slowest_device"] == 7


def test_skew_note_noop_when_disabled():
    GLOBAL_CONF.set("sml.obs.enabled", False)
    obs.SKEW.reset()
    assert obs.SKEW.note("x", [1.0, 2.0]) is None
    assert obs.SKEW.programs() == []
    assert obs.straggler_report() is None


# ------------------------------------------------------------ engine health
def _drive_serving_load(n_requests=64):
    from sml_tpu.serving import MicroBatcher

    def score(X):
        time.sleep(0.0002)  # a visible, sub-SLO device cost
        return np.asarray(X).sum(axis=1)

    with MicroBatcher(score, max_batch_rows=32, flush_micros=200,
                      timeout_millis=0) as mb:
        futs = [mb.submit(np.ones((2, 4), np.float32))
                for _ in range(n_requests)]
        for f in futs:
            f.result(timeout=10)
    return n_requests


def test_engine_health_populated_after_serving_load(recorder):
    """Acceptance: after a serving-shaped load, engine_health() carries
    populated metric quantiles, the audit block, the HBM ledger, and the
    SLO burn-rate; the snapshot also lands a health.snapshot event."""
    n = _drive_serving_load()
    health = obs.engine_health()
    m = health["metrics"]["serve.request_ms"]
    assert m["count"] == n
    assert m["p50"] > 0 and m["p99"] >= m["p50"]
    assert health["slo"]["requests"] == n
    assert health["slo"]["target_ms"] == 250.0
    assert health["slo"]["burn_rate"] == 0.0  # sub-ms requests, 250ms SLO
    assert "decisions" in health["audit"]
    assert "dispatch audit" in health["audit"]["report"]
    assert "_total" in health["hbm"]
    assert health["engine"]["engine.cache_hit_rate"] >= 0.0
    assert any(e.name == "health.snapshot" and e.kind == "health"
               for e in obs.RECORDER.events())


def test_slo_burn_rate_counts_breaches(recorder):
    """A 1ms SLO against ~constant >=1ms latencies burns the budget: the
    breach fraction comes from the histogram's bucket-exact count."""
    GLOBAL_CONF.set("sml.serve.sloMillis", 1)
    try:
        for _ in range(100):
            obs.METRICS.observe("serve.request_ms", 50.0)
        slo = obs.slo_report()
    finally:
        GLOBAL_CONF.unset("sml.serve.sloMillis")
    assert slo["requests"] == 100
    assert slo["breaches"] == 100
    assert slo["breach_fraction"] == 1.0
    assert slo["burn_rate"] == pytest.approx(100.0)  # 100% over a 1% budget
    assert any(e.name == "slo.burn_rate" for e in obs.RECORDER.events())


def test_endpoint_latency_flows_into_dispatch_histograms(recorder):
    """The audit's measured-wall attach also feeds per-route dispatch
    histograms in the metrics core."""
    from sml_tpu.utils.profiler import PROFILER
    GLOBAL_CONF.set("sml.profiler.enabled", True)
    try:
        with PROFILER.span("program.health_probe", route="host"):
            time.sleep(0.002)
    finally:
        GLOBAL_CONF.set("sml.profiler.enabled", False)
    h = obs.METRICS.histogram("dispatch.host_ms")
    assert h is not None and h.count >= 1
    assert h.quantile(0.5) >= 1.0  # >= ~2ms measured, one-bucket exact
