"""Engine health layer (ISSUE 7): streaming metrics core, per-device
straggler attribution, engine_health() snapshot, and the bench_diff
perf-regression sentry CI gate.

Acceptance:
- log-bucketed p50/p99 land within ONE BUCKET WIDTH (2**(1/8)) of the
  exact sorted-sample computation at the same rank (the contract that
  let bench.py's raw-sort path be deleted);
- an injected 8-device skewed timing profile names the slow chip, the
  skew ratio matches the injected imbalance, and the report survives a
  Chrome-trace export round-trip;
- `engine_health()` is populated (metric quantiles, audit, ledger, SLO
  burn-rate) after a serving-shaped load;
- `scripts/bench_diff.py` self-compare on the committed artifacts exits
  0 with zero findings; a >=20% injected wall regression on any leg is
  flagged and exits non-zero.
"""

import json
import math
import subprocess
import sys
import os
import re
import time

import numpy as np
import pytest

from sml_tpu import obs
from sml_tpu.conf import GLOBAL_CONF
from sml_tpu.obs._metrics import BUCKET_GROWTH, LogHistogram
from sml_tpu.obs._trace import PID_SKEW, to_trace_events

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
BENCH_DIFF = os.path.join(REPO, "scripts", "bench_diff.py")


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
    that replaced bench.py's raw-sort percentile path."""
    rng = np.random.default_rng(42)
    # the bench serving leg's shape: ~2000 lognormal request latencies ms
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
    """The bench probe passes jax.Device.ids: the report and the trace
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


# -------------------------------------------------------- regression sentry
def _run_diff(*args):
    return subprocess.run(
        [sys.executable, BENCH_DIFF, *args],
        capture_output=True, text=True, timeout=120, cwd=REPO)


def _driver_record() -> dict:
    """A document in the driver's record shape (`{n, cmd, rc, tail,
    parsed}`: the captured end of a `python bench.py` run plus its parsed
    headline), built inline with made-up walls — the format
    `obs/regress.py` must keep reading, without keeping any particular
    run of it in the tree."""
    legs = {"ml02_lr": 4.0, "ml06_dt": 2.5, "ml07_rf": 2.5,
            "ml11_xgb": 3.5, "ml12_mapinpandas": 0.4,
            "ml13_applyinpandas": 0.05}
    headline = {"metric": "ml02-ml13 suite wall-clock (synthetic record)",
                "value": round(sum(legs.values()), 3), "unit": "seconds",
                "vs_baseline": 2.0}
    tail = "devices: [synthetic]\nwarmup (incl. compiles): 100.0s\n" \
        + "".join(f"  {k:<24s}{v:>6.2f}s\n" for k, v in legs.items()) \
        + "  rmse_xgb                   65.000\n" \
        + json.dumps(headline) + "\n"
    return {"n": 1, "cmd": "python bench.py", "rc": 0, "tail": tail,
            "parsed": headline}


def test_bench_diff_self_compare_committed_artifacts(tmp_path):
    """Satellite/acceptance: a driver-shaped record and the committed
    sidecar each self-compare to ZERO findings, exit 0 — and the gate
    runs jax-free (it is a tier-1 CI test)."""
    record = tmp_path / "driver_record.json"
    record.write_text(json.dumps(_driver_record()))
    for artifact in (str(record), os.path.join(REPO, "bench_legs.json")):
        proc = _run_diff(artifact, "--json")
        assert proc.returncode == 0, proc.stdout + proc.stderr
        result = json.loads(proc.stdout)
        assert result["ok"] is True
        assert result["regressions"] == []
        assert result["checked"] > 0


def test_bench_diff_flags_injected_sidecar_regression(tmp_path):
    """Acceptance: a >=20% injected wall regression on any sidecar leg is
    flagged and exits non-zero; engine-counter growth is flagged too."""
    with open(os.path.join(REPO, "bench_legs.json")) as f:
        doc = json.load(f)
    leg = doc["legs"]["ml07_cv"]
    leg["seconds"] = round(leg["seconds"] * 1.25, 3)
    leg["seconds_per_pass"] = [round(x * 1.25, 3)
                               for x in leg["seconds_per_pass"]]
    cand = tmp_path / "cand.json"
    cand.write_text(json.dumps(doc))
    proc = _run_diff(os.path.join(REPO, "bench_legs.json"), str(cand),
                     "--json")
    assert proc.returncode == 1, proc.stdout
    result = json.loads(proc.stdout)
    keys = {f["key"] for f in result["regressions"]}
    assert "ml07_cv" in keys


def test_bench_diff_flags_injected_bench_record_regression(tmp_path):
    """The driver-record format is diffable too: a 30% slower leg in
    the tail flags."""
    base = tmp_path / "base.json"
    base.write_text(json.dumps(_driver_record()))
    doc = _driver_record()
    doc["tail"] = re.sub(
        r"ml11_xgb(\s+)([0-9.]+)s",
        lambda m: f"ml11_xgb{m.group(1)}{float(m.group(2)) * 1.3:.2f}s",
        doc["tail"])
    cand = tmp_path / "cand.json"
    cand.write_text(json.dumps(doc))
    proc = _run_diff(str(base), str(cand), "--json")
    assert proc.returncode == 1, proc.stdout
    result = json.loads(proc.stdout)
    assert any(f["key"] == "ml11_xgb" and f["kind"] == "leg-wall"
               for f in result["regressions"])


def test_bench_diff_counter_and_collective_and_coverage_rules(tmp_path):
    """The non-wall rules: a leg vanishing, a dispatch-count growth, and
    a multichip psum-payload growth each flag independently."""
    from sml_tpu.obs import regress
    base = regress.load(os.path.join(REPO, "bench_legs.json"))
    # a leg disappears -> coverage regression
    import copy
    cand = copy.deepcopy(base)
    cand["legs"].pop("ml06_dt")
    res = regress.compare(base, cand)
    assert any(f["kind"] == "missing-leg" and f["key"] == "ml06_dt"
               for f in res["regressions"])
    # tree-fit dispatch count grows -> fusion-contract regression (the
    # committed sidecar predates per-leg counters, so pin them on both
    # sides and grow the candidate's)
    base2 = copy.deepcopy(base)
    base2["legs"]["ml07_cv"]["counters"]["tree.fit_dispatch"] = 4.0
    cand = copy.deepcopy(base2)
    cand["legs"]["ml07_cv"]["counters"]["tree.fit_dispatch"] = 13.0
    res = regress.compare(base2, cand)
    assert any(f["kind"] == "leg-counter"
               and f["key"].endswith("tree.fit_dispatch")
               for f in res["regressions"])
    # multichip psum payload grows 10% -> collective-static regression
    with open(os.path.join(REPO, "bench_legs.json")) as f:
        raw = json.load(f)
    if raw.get("multichip"):
        cand_raw = copy.deepcopy(raw)
        for e in cand_raw["multichip"]["widths"]:
            e["collective_psum_bytes"] *= 1.10
        res = regress.compare(regress.normalize(raw),
                              regress.normalize(cand_raw))
        assert any(f["kind"] == "multichip-collective"
                   for f in res["regressions"])
    # kernel.fallback growth -> EXACT rule: growth by even 1 flags, and
    # a key ABSENT from the base leg counts as 0 (legs only record
    # counters that fired, so the realistic regression is 0 -> N with no
    # base key at all)
    cand = copy.deepcopy(base)
    assert "kernel.fallback" not in cand["legs"]["ml07_rf"]["counters"]
    cand["legs"]["ml07_rf"]["counters"]["kernel.fallback"] = 1.0
    res = regress.compare(base, cand)
    assert any(f["kind"] == "leg-counter"
               and f["key"].endswith("kernel.fallback")
               for f in res["regressions"])
    if raw.get("kernel"):
        cand_raw = copy.deepcopy(raw)
        for e in cand_raw["kernel"]["legs"]:
            e["kernel_counters"]["kernel.fallback"] += 1.0
        res = regress.compare(regress.normalize(raw),
                              regress.normalize(cand_raw))
        assert any(f["kind"] == "kernel-fallback"
                   for f in res["regressions"])
        # the kernelbench gate vanishing (or one sweep leg) is coverage
        # loss, same as an ordinary leg going missing
        cand_raw = copy.deepcopy(raw)
        cand_raw.pop("kernel")
        res = regress.compare(regress.normalize(raw),
                              regress.normalize(cand_raw))
        assert any(f["kind"] == "missing-kernel-block"
                   for f in res["regressions"])
        cand_raw = copy.deepcopy(raw)
        cand_raw["kernel"]["legs"] = cand_raw["kernel"]["legs"][1:]
        res = regress.compare(regress.normalize(raw),
                              regress.normalize(cand_raw))
        assert any(f["kind"] == "missing-kernel-leg"
                   for f in res["regressions"])
        # and the committed kernel block self-compares clean
        res0 = regress.compare(regress.normalize(raw),
                               regress.normalize(raw))
        assert res0["ok"]


def test_regress_verdicts_annotate_the_trace(recorder, tmp_path):
    """Verdicts land in the flight recorder as regress.verdict events
    and render as instant markers in the exported trace; bench_diff
    --trace writes the standalone marker file."""
    from sml_tpu.obs import regress
    base = regress.load(os.path.join(REPO, "bench_legs.json"))
    import copy
    cand = copy.deepcopy(base)
    cand["legs"]["ml02_lr"]["seconds"] *= 1.5
    cand["legs"]["ml02_lr"]["passes"] = [
        x * 1.5 for x in cand["legs"]["ml02_lr"]["passes"]]
    res = regress.compare(base, cand)
    assert not res["ok"]
    n = obs.annotate_regressions(res["regressions"])
    assert n == len(res["regressions"]) >= 1
    trace = to_trace_events(obs.RECORDER.events())
    marks = [e for e in trace if e.get("ph") == "i"
             and e["name"] == "regress.verdict"]
    assert len(marks) >= 1
    assert marks[0]["args"]["key"] == "ml02_lr"
    # the CLI's standalone trace file
    out = tmp_path / "verdicts.json"
    proc = _run_diff(os.path.join(REPO, "bench_legs.json"),
                     os.path.join(REPO, "bench_legs.json"),
                     "--trace", str(out))
    assert proc.returncode == 0
    doc = json.loads(out.read_text())
    assert "traceEvents" in doc
