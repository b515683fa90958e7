"""Causal request tracing, stall watchdog, and black-box postmortem
(ISSUE 8 tentpole + acceptance criteria).

Acceptance:
- a serving request's trace id is recoverable at EVERY hop of an
  exported Chrome trace — admission span -> coalesced-flush fan-in ->
  dispatch span -> collective event — connected by flow events, with no
  bleed between N concurrent requests through one flush;
- a slow-but-PREDICTED-slow dispatch does NOT flag (the watchdog judges
  against the audit's prediction, floored at sml.obs.stallMillis), while
  a forced hard stall emits `stall.*` events carrying an all-thread
  stack snapshot and surfaces in engine_health()'s `inflight` block;
- a forced stall/dump produces a blackbox bundle that
  `scripts/blackbox_view.py` renders WITHOUT jax ever being imported.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from sml_tpu import obs
from sml_tpu.conf import GLOBAL_CONF
from sml_tpu.obs._trace import to_trace_events
from sml_tpu.utils.profiler import PROFILER

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
VIEWER = os.path.join(REPO, "scripts", "blackbox_view.py")


@pytest.fixture()
def recorder():
    GLOBAL_CONF.set("sml.obs.enabled", True)
    obs.reset()
    try:
        yield obs.RECORDER
    finally:
        GLOBAL_CONF.set("sml.obs.enabled", False)
        for key in ("sml.obs.stallMillis", "sml.obs.stallFactor",
                    "sml.obs.blackboxDir"):
            GLOBAL_CONF.unset(key)
        obs.reset()


# ------------------------------------------------------------ causal tracing
def _flow_points(trace, flow_id):
    """(ph, ts) anchors of one flow id, in ts order."""
    pts = [(e["ph"], e["ts"]) for e in trace
           if e.get("ph") in ("s", "t", "f") and e.get("id") == flow_id]
    return sorted(pts, key=lambda p: p[1])


def test_request_trace_round_trip(recorder):
    """Acceptance: N concurrent requests coalesce into ONE flush; each
    request's trace id is recoverable at every hop of the exported trace
    (admission -> flush fan-in -> dispatch -> collective), flow events
    connect the hops, and no request's id bleeds onto another's."""
    from sml_tpu.parallel import collectives
    from sml_tpu.serving import MicroBatcher

    def score(X):
        # the dispatch hop (a routed program span) and the collective
        # hop (a trace-time _note) run on the BATCHER thread: both must
        # pick up the flush context handed across the queue
        with PROFILER.span("program.trace_probe", route="device"):
            collectives._note("psum", np.ones((4,), np.float32))
        return np.asarray(X).sum(axis=1)

    n = 6
    mb = MicroBatcher(score, max_batch_rows=64, flush_micros=2000,
                      timeout_millis=0, start=False)
    futs = [mb.submit(np.full((2, 4), float(i), np.float32))
            for i in range(n)]
    mb.start()
    for i, f in enumerate(futs):
        np.testing.assert_allclose(f.result(timeout=10),
                                   np.full((2,), 4.0 * i))
    mb.close()

    ids = [f.trace_id for f in futs]
    assert all(isinstance(t, int) for t in ids)
    assert len(set(ids)) == n, "trace ids bled between requests"

    evs = obs.RECORDER.events()
    admissions = {e.args["trace"]: e for e in evs
                  if e.name == "trace.request"}
    assert set(ids) <= set(admissions), "an admission span is missing"

    flushes = [e for e in evs if e.name == "serve.batch"
               and e.kind == "span"]
    assert len(flushes) == 1, "expected ONE coalesced flush"
    flush = flushes[0]
    assert sorted(flush.args["parent_traces"]) == sorted(ids)
    assert len(flush.args["parent_spans"]) == n
    batch_trace = flush.args["trace"]
    assert batch_trace not in ids  # the fan-in mints a fresh trace

    # downstream hops carry the flush context
    prog = [e for e in evs if e.name == "program.trace_probe"
            and e.kind == "span"]
    coll = [e for e in evs if e.name == "collective.psum"
            and e.kind == "collective"]
    assert prog and prog[0].args["trace"] == batch_trace
    assert coll and coll[0].args["trace"] == batch_trace
    # the dispatch-launch ticket opened (and closed) for the probe span
    assert obs.WATCHDOG.report()["open"] == 0

    # ---- exported trace: flow events connect the hops ----------------
    trace = to_trace_events(evs)
    for rid in ids:
        pts = _flow_points(trace, rid)
        assert len(pts) >= 2, f"request {rid:#x} has no flow edge"
        assert pts[0][0] == "s" and pts[-1][0] == "f"
    bpts = _flow_points(trace, batch_trace)
    assert len(bpts) >= 2, "flush->dispatch flow missing"
    assert bpts[0][0] == "s" and bpts[-1][0] == "f"

    # ---- exemplars: the histogram names literal requests -------------
    snap = obs.METRICS.histogram("serve.request_ms").snapshot()
    assert set(snap["exemplars"].values()) <= set(ids)
    worst_ms, worst_trace = obs.METRICS.worst("serve.request_ms")
    assert worst_trace in ids and worst_ms > 0
    health = obs.engine_health()
    assert health["slo"]["worst_trace"] == f"0x{worst_trace:013x}"


def test_trace_context_explicit_handoff(recorder):
    """The cross-thread handoff is explicit: a captured context activated
    on another thread tags that thread's emissions; the origin thread's
    context is untouched."""
    import threading
    ctx = obs.new_trace()
    seen = {}

    def worker():
        with obs.activate_trace(ctx):
            seen["inside"] = obs.current_trace()
        seen["outside"] = obs.current_trace()

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    assert seen["inside"] is ctx
    assert seen["outside"] is None
    assert obs.current_trace() is None


# ------------------------------------------------------------ stall watchdog
def test_watchdog_predicted_slow_is_not_flagged(recorder):
    """Satellite: a dispatch that is slow but PREDICTED slow must not
    flag — the watchdog's threshold is stallFactor x the audit's
    predicted wall for this thread's pending decision, not a constant."""
    from sml_tpu.obs import _audit
    from sml_tpu.parallel.dispatch import WorkHint
    GLOBAL_CONF.set("sml.obs.stallMillis", 50)
    GLOBAL_CONF.set("sml.obs.stallFactor", 4.0)
    _audit.record(WorkHint(flops=1e9, kind="blas"), "device",
                  t_host=1.0, t_device=0.12, forced=False)
    assert _audit.expected_wall("device") == pytest.approx(0.12)
    with PROFILER.span("program.predicted_slow", route="device"):
        time.sleep(0.3)  # > the 50ms floor, < 4 x 0.12s threshold
    assert not [e for e in obs.RECORDER.events()
                if e.name.startswith("stall.")], \
        "predicted-slow dispatch false-positived"


def test_forced_stall_emits_stack_snapshot(recorder):
    """Acceptance: a ticket that breaks its prediction is flagged while
    STILL IN FLIGHT — stall.detected carries an all-thread stack
    snapshot and the trace id, engine_health()'s inflight block shows
    the stalled ticket, and stall.resolved closes the story."""
    GLOBAL_CONF.set("sml.obs.stallMillis", 50)
    GLOBAL_CONF.set("sml.obs.stallFactor", 2.0)
    ctx = obs.new_trace()
    with obs.WATCHDOG.watch("dispatch", "program.wedged",
                            expected_s=0.001, trace=ctx):
        deadline = time.monotonic() + 5.0
        flagged_inflight = None
        while time.monotonic() < deadline:
            rep = obs.WATCHDOG.report()
            # wait for the EVENT, not just the flag: the daemon marks
            # the ticket under its lock, then takes the (slow) stack
            # snapshot and emits outside it
            if rep["stalled"] and any(
                    e.name == "stall.detected"
                    for e in obs.RECORDER.events()):
                flagged_inflight = rep
                break
            time.sleep(0.02)
    assert flagged_inflight is not None, "watchdog never flagged"
    ticket = flagged_inflight["tickets"][0]
    assert ticket["name"] == "program.wedged"
    assert ticket["trace"] == ctx.trace_id
    health_inflight = obs.engine_health()["inflight"]
    assert health_inflight["flagged_total"] >= 1

    detected = [e for e in obs.RECORDER.events()
                if e.name == "stall.detected"]
    assert detected, "no stall.detected event"
    args = detected[0].args
    assert args["name"] == "program.wedged"
    assert args["trace"] == ctx.trace_id
    assert args["elapsed_s"] > args["threshold_s"]
    stacks = args["stacks"]
    assert isinstance(stacks, dict) and stacks
    # the snapshot was taken while the hang was LIVE: the stalling
    # thread's stack shows this test's wait loop
    all_frames = "\n".join(ln for frames in stacks.values()
                           for ln in frames)
    assert "test_forced_stall_emits_stack_snapshot" in all_frames
    resolved = [e for e in obs.RECORDER.events()
                if e.name == "stall.resolved"]
    assert resolved and resolved[0].args["trace"] == ctx.trace_id
    assert obs.RECORDER.counters().get("stall.flagged", 0) >= 1
    assert obs.WATCHDOG.report()["open"] == 0


# --------------------------------------------------------- black-box bundles
def _force_activity(tmp_path):
    """A little of everything for the bundle: events, a metric with an
    exemplar, and a flagged stall."""
    GLOBAL_CONF.set("sml.obs.stallMillis", 50)
    GLOBAL_CONF.set("sml.obs.stallFactor", 2.0)
    ctx = obs.new_trace()
    obs.METRICS.observe("serve.request_ms", 42.0, exemplar=ctx.trace_id)
    PROFILER.count("staging.cache_hit")
    with obs.WATCHDOG.watch("serve.flush", "serve.batch", trace=ctx):
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            # wait for the stall.detected EVENT (the flag lands first,
            # the stack snapshot + emit trail it) so the dump below is
            # guaranteed to contain it
            if any(e.name == "stall.detected"
                   for e in obs.RECORDER.events()):
                break
            time.sleep(0.02)
    return ctx


def test_blackbox_bundle_and_jax_free_viewer(recorder, tmp_path):
    """Acceptance: a forced hard stall dumps a bundle with every section,
    and scripts/blackbox_view.py renders it (trace.json + summary) in a
    subprocess that provably never imports jax."""
    ctx = _force_activity(tmp_path)
    bundle = obs.dump_blackbox("test-forced-stall",
                               directory=str(tmp_path))
    assert bundle and os.path.isdir(bundle)
    for name in ("MANIFEST.json", "events.jsonl", "metrics.json",
                 "audit.json", "ledger.json"):
        assert os.path.exists(os.path.join(bundle, name)), name

    with open(os.path.join(bundle, "MANIFEST.json")) as f:
        manifest = json.load(f)
    assert manifest["reason"] == "test-forced-stall"
    # wall-clock anchor: epoch_unix is a real recent Unix stamp
    assert abs(manifest["dumped_unix"] - time.time()) < 120
    assert manifest["epoch_unix"] <= manifest["dumped_unix"]
    assert manifest["conf"]["sml.obs.enabled"] is True
    assert manifest["thread_stacks"]
    with open(os.path.join(bundle, "metrics.json")) as f:
        metrics = json.load(f)
    assert metrics["metrics"]["serve.request_ms"]["count"] >= 1
    assert metrics["slo"]["worst_trace"] == f"0x{ctx.trace_id:013x}"

    # the ring dump carries the stall with its stacks
    stall_lines = [json.loads(ln) for ln in
                   open(os.path.join(bundle, "events.jsonl"))
                   if "stall.detected" in ln]
    assert stall_lines and stall_lines[0]["args"]["stacks"]

    # ---- viewer renders WITHOUT jax ----------------------------------
    probe = (
        "import importlib.util, sys\n"
        f"spec = importlib.util.spec_from_file_location('_v', {VIEWER!r})\n"
        "m = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(m)\n"
        f"rc = m.main([{bundle!r}])\n"
        "assert 'jax' not in sys.modules, 'viewer imported jax'\n"
        "assert 'sml_tpu' not in sys.modules, 'viewer imported the package'\n"
        "sys.exit(rc)\n")
    proc = subprocess.run([sys.executable, "-c", probe],
                          capture_output=True, text=True, timeout=120,
                          cwd=str(tmp_path))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "blackbox bundle" in proc.stdout
    assert "stall" in proc.stdout
    trace_path = os.path.join(bundle, "trace.json")
    assert os.path.exists(trace_path)
    with open(trace_path) as f:
        doc = json.load(f)
    assert doc["otherData"]["epoch_unix"] == pytest.approx(
        manifest["epoch_unix"], abs=1.0)
    names = {e.get("name") for e in doc["traceEvents"]}
    assert "stall.detected" in names


def test_blackbox_stall_hook_dumps_once(recorder, tmp_path, monkeypatch):
    """install()'s stall hook auto-dumps exactly ONE bundle per process
    (a stall storm must not fill the disk)."""
    from sml_tpu.obs import blackbox
    GLOBAL_CONF.set("sml.obs.blackboxDir", str(tmp_path / "bb"))
    monkeypatch.setitem(blackbox._state, "stall_dumped", False)
    blackbox._stall_hook({"name": "program.wedged"})
    blackbox._stall_hook({"name": "program.wedged"})
    root = tmp_path / "bb"
    bundles = [p for p in os.listdir(root)] if root.exists() else []
    assert len(bundles) == 1, bundles


def test_exception_block_shapes():
    from sml_tpu.obs import blackbox
    try:
        raise ValueError("boom")
    except ValueError as e:
        blk = blackbox._exception_block(e)
        blk2 = blackbox._exception_block(sys.exc_info())
    assert blk["type"] == "ValueError" and "boom" in blk["value"]
    assert any("boom" in ln for ln in blk["traceback"])
    assert blk2["type"] == "ValueError"
    assert blackbox._exception_block(None) is None


# ---------------------------------------------------------- sentry tolerance
# ------------------------------------------------------- wall-clock anchoring
def test_sink_header_and_trace_carry_epoch_anchor(recorder, tmp_path):
    """Satellite: the JSONL sink's header line and the exported trace's
    otherData both carry epoch_unix — the absolute anchor that lines the
    relative timeline up with external logs."""
    sink = tmp_path / "events.jsonl"
    GLOBAL_CONF.set("sml.obs.sinkPath", str(sink))
    try:
        obs.RECORDER.emit("cache", "cache.anchor_probe", args={})
        lines = [json.loads(ln) for ln in sink.read_text().splitlines()]
    finally:
        GLOBAL_CONF.set("sml.obs.sinkPath", "")
    assert lines[0]["kind"] == "meta"
    assert lines[0]["name"] == "obs.header"
    anchor = lines[0]["args"]["epoch_unix"]
    assert abs(anchor - time.time()) < 300  # epoch was re-zeroed by reset()
    assert anchor == pytest.approx(obs.RECORDER.epoch_unix(), abs=1.0)

    out = tmp_path / "trace.json"
    obs.export_chrome_trace(str(out))
    doc = json.loads(out.read_text())
    assert doc["otherData"]["epoch_unix"] == pytest.approx(anchor, abs=1.0)
