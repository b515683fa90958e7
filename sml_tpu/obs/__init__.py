"""sml_tpu.obs — the engine's flight recorder.

The reference debugs through the Spark UI / Ganglia (shuffle volumes,
storage, executor timelines — `SML/ML Electives/MLE 05 - Best
Practices.py:24-36`); this package is that surface for the mesh engine,
built on ONE structured event bus:

- `RECORDER` (`_recorder`): typed events — spans, counters, dispatch
  decisions, cache traffic, collective launches, program compiles, HBM
  gauges — in a bounded ring with an optional JSONL sink
  (`sml.obs.sinkPath`). Enabled by `sml.obs.enabled`; disabled it costs
  one attribute load per instrumentation site.
- `export_chrome_trace(path)` (`_trace`): the ring as a Chrome/Perfetto
  trace — host thread tracks, a virtual device track for dispatched
  programs, counter tracks for H2D/D2H bytes and cache/HBM occupancy.
- `audit_report()` (`_audit`): every `dispatch.decide` with its predicted
  host/device times and the routed program's measured wall — calibration
  drift and would-have-been-faster misroutes.
- `memory_report()` / `LEDGER` (`_ledger`): live/peak device bytes across
  the bin cache, staging cache, and donated boosting carries.
- `engine_metrics()` + fit autologging: outermost `Estimator.fit` under an
  active tracking run logs `engine.*` metrics (the MLflow system-metrics
  mirror), gated by `sml.obs.autoLogRunMetrics`.
- `METRICS` (`_metrics`): streaming log-bucketed histograms — latency
  quantiles and rates without retained samples; `engine_health()` is the
  one-call snapshot (metrics + audit + HBM ledger + SLO burn-rate),
  surfaced live on `ServingEndpoint.health_report()`.
- `SKEW` / `straggler_report()` (`_skew`): per-device compute vs
  collective-wait attribution of fused mesh programs, rendered as
  per-chip lanes in the Chrome trace.
- `TraceContext` / `current_trace` (`_context`): causal request tracing
  — a context minted at serving admission rides contextvars (with
  explicit cross-thread handoff) through micro-batch coalescing, the
  dispatch decision, program spans, collective notes, and prewarm
  replays; the trace exporter draws Chrome flow arrows across the hops
  and `METRICS` histograms carry per-bucket trace-id exemplars.
- `WATCHDOG` (`_watchdog`): in-flight stall detection — dispatch
  launches, micro-batch flushes, collective bring-up, and prewarm
  replays register tickets; anything exceeding `sml.obs.stallFactor` x
  its audit-predicted wall (floor `sml.obs.stallMillis`) is flagged
  with all-thread stack snapshots, surfaced as the `inflight` block of
  `engine_health()`.
- `fit_records()` (`_fits`): a record for every root fit — wall and CPU
  seconds by span name and by the benchmark's eight host phases, the
  collector's pauses (a slow fit's also the resident and the available
  memory) — the per-fit form
  of the `span_s.*` totals; the root `fit` carries a watchdog ticket
  whose expectation is its shape's own median, and a fit a quarter and
  0.1 s over it leaves a `fit.slow` event and ONE WARNING line that
  names the phase.
- `dump_blackbox` / `install_blackbox` (`blackbox`): black-box
  postmortem bundles (ring + metrics + audit + ledger + in-flight
  tickets + stacks + conf) on unhandled exception, hard stall, or
  demand — rendered offline by `scripts/blackbox_view.py` without jax.
- `drift` / `DRIFT` (`drift`): model & data drift — distribution
  distances (per-feature PSI, quantile shift, categorical frequency
  PSI, prediction-distribution drift) of live traffic against the
  training baseline sketch fitted tree models carry, with noise-aware
  thresholds (resampled-baseline self-distance floors so iid traffic
  never false-positives); fed by the serving micro-batch path and the
  chunked-ingest sketch pass, surfaced as `engine_health()["drift"]`.

See docs/OBSERVABILITY.md for the event model and worked examples.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional

from ..conf import GLOBAL_CONF
from . import _audit, _context, _fits, _ledger
from . import drift as drift  # noqa: F401 — re-exported subsystem
from ._audit import records as audit_records, report as audit_report
from ._context import TraceContext, activate as activate_trace, \
    current as current_trace, hex_id as trace_hex, new_trace, open_trace
from ._ledger import LEDGER, report as memory_report
from ._metrics import METRICS, LogHistogram, merge_snapshots
from ._recorder import RECORDER, Event
from ._skew import INGEST_SKEW, SKEW, \
    report_from_trace as skew_report_from_trace
from ._trace import export_chrome_trace
from ._watchdog import WATCHDOG, all_thread_stacks
from .blackbox import dump_blackbox, install as install_blackbox
from .drift import DRIFT

__all__ = ["RECORDER", "Event", "LEDGER", "METRICS", "SKEW", "INGEST_SKEW",
           "WATCHDOG", "drift", "DRIFT",
           "TraceContext", "current_trace", "new_trace", "activate_trace",
           "trace_hex", "all_thread_stacks", "dump_blackbox",
           "install_blackbox",
           "LogHistogram", "merge_snapshots", "export_chrome_trace",
           "audit_report", "audit_records", "memory_report",
           "engine_metrics", "engine_health", "straggler_report",
           "skew_report_from_trace", "reset", "fit_records",
           "enabled", "note_compile", "autolog_fit"]


def enabled() -> bool:
    return RECORDER.enabled


def fit_records():
    """A record for every root fit that ended, the newest 256, oldest
    first (`obs/_fits.py`: wall and CPU seconds by span name and by the
    benchmark's eight phases, the collector's pauses)."""
    return RECORDER.fit_records()


def reset() -> None:
    """Drop recorded events, fit records, audit records, metric histograms,
    skew attributions, watchdog statistics, and re-arm HBM peaks (live ledger
    bytes and OPEN watchdog tickets persist — they describe real cache
    residency / real in-flight work)."""
    RECORDER.reset()
    _audit.reset()
    METRICS.reset()
    SKEW.reset()
    INGEST_SKEW.reset()
    WATCHDOG.reset()
    LEDGER.reset_peaks()
    # drift monitors drop their live windows/exemplars but STAY
    # registered — they belong to live endpoints/ingests the way open
    # watchdog tickets belong to real in-flight work
    drift.DRIFT.reset()


def note_pipeline(family: str, phase: str, key: str, index: int) -> None:
    """Staging-pipeline event emitter (`parallel/pipeline.py`):
    `<family>.<phase>` with family "infer" (batch inference) or
    "ingest" (chunked ingest) — both registered wildcard families. The
    name is computed from the family parameter, and computed event
    names are reserved to this package by the taxonomy lint, so the
    shared pipeline emits through here."""
    if RECORDER.enabled:
        RECORDER.emit(family, family + "." + phase, args={key: index})


def note_compile(name: str) -> None:
    """Mark a program-cache miss (= a fresh trace + XLA compile/replay):
    bumps the `compile.programs` total AND the per-name
    `compile.program.<name>` counter (distinct-program / first-dispatch
    attribution reads the per-name deltas), and records a compile
    event."""
    from ..utils.profiler import PROFILER
    PROFILER.count("compile.programs")
    PROFILER.count(f"compile.program.{name}")
    if RECORDER.enabled:
        RECORDER.emit("compile", "compile.trace", args={"program": name})


# ------------------------------------------------------------ engine metrics
def engine_metrics() -> Dict[str, float]:
    """The engine's health snapshot as flat `engine.*` metrics — byte
    volumes, cache hit rates, route mix, compile count, peak HBM bytes.
    Sourced from the recorder's own totals (independent of
    `sml.profiler.enabled`), the dispatch audit, and the memory ledger."""
    t = RECORDER.counters()
    hits = t.get("staging.cache_hit", 0.0)
    misses = t.get("staging.cache_miss", 0.0)
    bhits = t.get("staging.bin_cache_hit", 0.0)
    bmisses = t.get("staging.bin_cache_miss", 0.0)
    return {
        "engine.h2d_bytes": t.get("staging.h2d_bytes", 0.0),
        "engine.d2h_bytes": t.get("staging.d2h_bytes", 0.0),
        "engine.h2d_bytes_saved": t.get("staging.h2d_bytes_saved", 0.0),
        "engine.cache_hit_rate": hits / max(hits + misses, 1.0),
        "engine.bin_cache_hit_rate": bhits / max(bhits + bmisses, 1.0),
        "engine.route_device": t.get("dispatch.route_device", 0.0),
        "engine.route_host": t.get("dispatch.route_host", 0.0),
        "engine.compile_programs": t.get("compile.programs", 0.0),
        "engine.hbm_peak_bytes": float(LEDGER.peak_total()),
        "engine.shuffle_rows": t.get("shuffle.rows", 0.0),
    }


# ------------------------------------------------------------- engine health
def straggler_report() -> Optional[Dict[str, object]]:
    """Aggregate per-device skew attribution across every program noted
    with `SKEW.note` (None when nothing was noted — e.g. no multichip
    fits ran). See obs/_skew.py for the BSP decomposition."""
    return SKEW.straggler_report()


def slo_report(window_s: Optional[float] = None) -> Dict[str, float]:
    """Latency-SLO burn for the serving path: the fraction of
    `serve.request_ms` observations above `sml.serve.sloMillis`, divided
    by the error budget (`sml.serve.sloBudget`) — burn_rate 1.0 means the
    budget is being spent exactly as fast as allowed; >1 means an alert.
    Breach counting is bucket-exact (within one ~9% histogram bucket of
    the threshold)."""
    target_ms = float(GLOBAL_CONF.get("sml.serve.sloMillis", 250))
    budget = float(GLOBAL_CONF.get("sml.serve.sloBudget", 0.01))
    hist = METRICS.histogram("serve.request_ms")
    # worst_ms/worst_trace are ALL-TIME exemplars: on a windowed report
    # they stay None so every populated field covers the same range (the
    # PR-7 snapshot contract) — a window-clean report must not name a
    # worst request from outside the window
    worst_ms, worst_trace = 0.0, None
    if hist is None:
        total = breaches = 0
    else:
        total = hist.total_count(window_s)
        breaches = hist.count_above(target_ms, window_s)
        if window_s is None:
            worst_ms, worst_trace = hist.worst()
    fraction = (breaches / total) if total else 0.0
    burn = fraction / budget if budget > 0 else 0.0
    if RECORDER.enabled and total:
        RECORDER.gauge("slo.burn_rate", burn)
    return {"target_ms": target_ms, "budget_fraction": budget,
            "requests": float(total), "breaches": float(breaches),
            "breach_fraction": round(fraction, 6),
            "burn_rate": round(burn, 4),
            # the LITERAL worst request, by trace-id exemplar: the id to
            # chase through an exported trace's flow arrows
            "worst_ms": round(float(worst_ms), 3),
            "worst_trace": _context.hex_id(worst_trace)}


def _infer_kernel_report() -> Optional[Dict[str, object]]:
    import sys
    mod = sys.modules.get("sml_tpu.ml.inference")
    return None if mod is None else mod.kernel_report()


def _fleet_report() -> Optional[Dict[str, object]]:
    import sys
    mod = sys.modules.get("sml_tpu.fleet")
    return None if mod is None else mod.fleet_report()


def _load_report() -> Optional[Dict[str, object]]:
    import sys
    mod = sys.modules.get("sml_tpu.loadgen")
    return None if mod is None else mod.load_report()


def engine_health(window_s: Optional[float] = None) -> Dict[str, object]:
    """ONE call, the engine's whole health surface: streaming-metric
    quantiles (serving latency, per-route dispatch walls), the dispatch
    audit's verdicts, the HBM ledger, the flat `engine.*` metrics, the
    serving SLO burn-rate, and (when multichip attribution ran) the
    straggler report. `window_s` restricts metric quantiles/rates to the
    trailing window (None = all-time). Cheap enough to poll — everything
    is read from bounded in-memory state."""
    recs = audit_records()
    measured = [r for r in recs if r.measured is not None]
    # shed counters live in whichever stream was on when they fired
    # (PROFILER.count forwards to the recorder only while obs is
    # enabled): max-merge the two, like fleet_report() — both see the
    # same increments when both are on, so max never double-counts
    counters = dict(RECORDER.counters())
    from ..utils.profiler import PROFILER as _PROF
    for k, v in _PROF.counters().items():
        if k.startswith("serve.shed"):
            counters[k] = max(counters.get(k, 0.0), v)
    health = {
        "metrics": METRICS.snapshot(window_s),
        "audit": {
            "decisions": len(recs),
            "measured": len(measured),
            "misroutes": sum(1 for r in measured if r.misroute),
            "report": audit_report(),
        },
        "hbm": LEDGER.snapshot(),
        "engine": engine_metrics(),
        "slo": slo_report(window_s),
        "skew": straggler_report(),
        # chunked-ingest straggler attribution (ml/_chunked.py feeds
        # per-chunk walls into the INGEST_SKEW tracker): same BSP report
        # shape as `skew`, but "slowest_device" is the slowest CHUNK
        # index — a slow ingest chunk is named here, not averaged away
        "ingest": INGEST_SKEW.straggler_report(),
        # in-flight watchdog tickets (obs/_watchdog.py): what is running
        # RIGHT NOW, how long it has been, and whether it broke its own
        # prediction — the block a liveness probe reads during a hang
        "inflight": WATCHDOG.report(),
        # model & data drift (obs/drift.py): every registered monitor's
        # live-vs-baseline verdict — serving endpoints under
        # "serve.<name>/<stage>", the chunked ingest under "ingest" (per-chunk
        # refit-trigger verdicts next to the `ingest` skew block above).
        # None until a monitor registers (a model carrying a baseline)
        "drift": drift.DRIFT.report(),
        # scoring traversal-kernel resolution (ml/inference.py): the
        # last resolved spec (kernel / block_rows) and cumulative
        # fallback+demotion counts. Read lazily off sys.modules so a
        # health poll never drags jax in — None until
        # the inference module has loaded (nothing scored yet)
        "infer_kernel": _infer_kernel_report(),
        # serving load-shed attribution (serving/_batcher.py): every
        # RequestShed path is reason-tagged (overflow / deadline /
        # closed), so a rising shed rate is attributable to its CAUSE —
        # a saturated queue sheds differently from a deadline storm
        "shed": {
            "total": counters.get("serve.shed", 0.0),
            "by_reason": {k.split("serve.shed.", 1)[1]: v
                          for k, v in counters.items()
                          if k.startswith("serve.shed.")},
        },
        # multi-replica serving fleet (sml_tpu/fleet): per-pool replica
        # tables (per-replica standing rows / occupancy / pinned
        # version), shed-by-priority-class, autoscale + rollout
        # receipts. Read lazily off sys.modules like infer_kernel —
        # None until a pool exists
        "fleet": _fleet_report(),
        # open-loop load harness (sml_tpu/loadgen): the last completed
        # replay's honest-tail report — per-phase/per-class p50/p99/
        # p99.9, shed/timeout rates, overrun count, worst-request trace
        # exemplars. Lazy like fleet — None until a replay ran
        "load": _load_report(),
    }
    if RECORDER.enabled:
        RECORDER.emit("health", "health.snapshot", args={
            "metrics": len(health["metrics"]),
            "audit_decisions": health["audit"]["decisions"],
            "slo_burn_rate": health["slo"]["burn_rate"]})
    return health


_fit_depth = threading.local()


@contextlib.contextmanager
def _fit_root(estimator, df):
    """The root of a fit's span tree: a `fit` span that opens a trace of
    the fit's own (unless a context already rides the thread, whose unit
    the fit then belongs to). Every `PROFILER.span` below is a child
    unit, so the recorded spans share the `trace` id and name their
    `parent` (docs/OBSERVABILITY.md "The span tree of a fit"). `rows`
    only where the frame is materialized already: counting costs nothing.
    Round the span, `_fits`: a watchdog ticket with the fit's own
    expectation while it runs, its record and verdict when it has ended."""
    from ..utils.profiler import PROFILER
    parts = getattr(df, "_parts", None)
    rows = None if parts is None else sum(len(p) for p in parts)
    opened = open_trace() if current_trace() is None else None
    with activate_trace(opened):
        state = _fits.open_fit(estimator, rows)
        root = None
        try:
            with PROFILER.span("fit", estimator=type(estimator).__name__,
                               rows=rows):
                ended = current_trace()
                yield
            root = ended
        finally:
            _fits.close_fit(state, root)


@contextlib.contextmanager
def autolog_fit(estimator, df=None):
    """Wrap one Estimator.fit of `df`. With the recorder on, the OUTERMOST
    fit on a thread is the root span `fit` of a span tree (`_fit_root`)
    and, with autologging enabled (`sml.obs.autoLogRunMetrics`) and a
    tracking run active on this thread, logs the fit's `engine.*` metric
    DELTAS to the run — the MLflow system-metrics mirror. A Pipeline's stage fits and a
    CrossValidator's inner fits fold into their parent, exactly like
    nested autologged models."""
    if not RECORDER.enabled:
        yield
        return
    depth = getattr(_fit_depth, "d", 0)
    _fit_depth.d = depth + 1
    before: Optional[Dict[str, float]] = None
    run = None
    try:
        if depth == 0 and GLOBAL_CONF.getBool("sml.obs.autoLogRunMetrics"):
            from .. import tracking
            run = tracking.active_run()
            if run is not None:
                before = engine_metrics()
        with (_fit_root(estimator, df) if depth == 0
              else contextlib.nullcontext()):
            yield
    finally:
        _fit_depth.d = depth
        if run is not None and before is not None:
            after = engine_metrics()
            delta = {}
            for k, v in after.items():
                if k.endswith(("_rate", "_peak_bytes")):
                    delta[k] = v          # level metrics: log the level
                else:
                    delta[k] = v - before.get(k, 0.0)
            try:
                from .. import tracking
                tracking.log_engine_metrics(delta)
            except Exception:
                pass  # autologging must never fail a fit
