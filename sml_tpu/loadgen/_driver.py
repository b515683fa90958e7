"""Open-loop replay — fire at the SCHEDULE, charge latency to the system.

The coordinated-omission trap (docs/LOADGEN.md): a closed-loop client
waits for each response before sending the next request, so the moment
the system queues, the client *slows its own arrival rate down* and the
percentiles it reports describe a workload nobody asked for. The
`OpenLoopDriver` is the fix: every request fires at its scheduled
arrival instant regardless of completions, and latency is measured from
the SCHEDULED arrival to the result — queueing delay (including any
delay inside the driver's own bounded worker pool) is charged to the
system under test, never hidden in the client.

Honesty guarantees:

- Bounded worker pool (`sml.load.workers`), but NEVER silent overrun:
  a request picked up more than `sml.load.overrunMicros` after its
  scheduled instant counts `load.overrun` — the driver telling you its
  own pool, not the system, became the bottleneck. Its latency is
  still charged from the schedule (pessimistic, not optimistic).
- Outcome accounting is internal and lock-guarded — the `load.*`
  PROFILER counters and `load.request_ms*` METRICS mirrors are
  best-effort (both no-op when their recorder is off), the driver's
  own report never is.
- Per-request trace contexts (`obs.mint_request`) ride the metrics
  exemplars, so `load.request_ms.<phase>` can name the literal worst
  request of each phase for the flight recorder to look up.

`closed_loop_probe` is the deliberately-wrong control: the same
schedule driven closed-loop, latency stamped from send time. Its only
job is the omission proof in tests — never report its numbers as load
results.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from ..conf import GLOBAL_CONF
from ..obs import _context as _trace
from ..obs._metrics import METRICS as _METRICS
from ..obs._recorder import RECORDER as _OBS
from ..serving._batcher import RequestShed
from ..utils.profiler import PROFILER, now
from ._spec import Request

#: outcome slots the driver accounts per request (shed/timeout/error
#: requests still get a latency sample — a shed IS a fast answer, a
#: timeout IS a slow one; hiding either would be omission again)
OUTCOMES = ("served", "shed", "timeout", "errors")


def _percentiles(samples: Sequence[float]) -> Dict[str, float]:
    if not samples:
        return {"p50_ms": 0.0, "p99_ms": 0.0, "p999_ms": 0.0}
    arr = np.asarray(samples, dtype=np.float64)
    p50, p99, p999 = np.percentile(arr, (50.0, 99.0, 99.9))
    return {"p50_ms": round(float(p50), 3),
            "p99_ms": round(float(p99), 3),
            "p999_ms": round(float(p999), 3)}


class OpenLoopDriver:
    """Replay a compiled schedule open-loop against a scoring callable.

    `score(X, priority, model)` is the system under test — typically a
    fleet router's bounded-wait `score` (raises `RequestShed` /
    `RequestTimeout` for the non-served outcomes). The driver owns the
    schedule, the worker pool, and the accounting; it never retries."""

    def __init__(self, score: Callable[[np.ndarray, Optional[str],
                                        Optional[str]], object],
                 requests: Sequence[Request], *,
                 feature_dim: int = 8,
                 workers: Optional[int] = None,
                 overrun_micros: Optional[int] = None):
        self._score = score
        self._requests = list(requests)
        self._feature_dim = int(feature_dim)
        self._workers = int(GLOBAL_CONF.getInt("sml.load.workers")
                            if workers is None else workers)
        self._overrun_s = float(
            GLOBAL_CONF.getInt("sml.load.overrunMicros")
            if overrun_micros is None else overrun_micros) / 1e6
        # one zero block per distinct width, built up front: the fire
        # path must not pay an allocation that scales with row width
        self._blocks = {
            rows: np.zeros((rows, self._feature_dim), dtype=np.float32)
            for rows in {r.rows for r in self._requests}}
        # the driver's OWN accounting — PROFILER/METRICS are mirrors
        # that no-op when disabled, this never does
        self._lock = threading.Lock()
        self._counts: Dict[str, int] = {k: 0 for k in OUTCOMES}
        self._counts["requests"] = 0
        self._counts["overrun"] = 0
        # (phase, priority) -> latency samples; (phase, None) = all
        self._samples: Dict[tuple, List[float]] = {}
        # phase -> (worst latency, trace id or None)
        self._worst: Dict[str, tuple] = {}
        self._wall_s = 0.0
        self._ran = False

    # ------------------------------------------------------- fire path
    def _record(self, r: Request, ms: float, outcome: str,
                trace_id: Optional[int]) -> None:
        with self._lock:
            self._counts["requests"] += 1
            self._counts[outcome] += 1
            self._samples.setdefault((r.phase, None), []).append(ms)
            self._samples.setdefault((r.phase, r.priority), []).append(ms)
            worst = self._worst.get(r.phase)
            if worst is None or ms > worst[0]:
                self._worst[r.phase] = (ms, trace_id)
        PROFILER.count("load.requests")
        PROFILER.count(f"load.{outcome}")
        _METRICS.observe("load.request_ms", ms, exemplar=trace_id)
        _METRICS.observe(f"load.request_ms.{r.phase}", ms,
                         exemplar=trace_id)
        _METRICS.observe(f"load.request_ms.{r.phase}.{r.priority}", ms,
                         exemplar=trace_id)

    def _fire_one(self, r: Request, epoch: float) -> None:
        sched = epoch + r.t
        lag = now() - sched
        if lag > self._overrun_s:
            # the schedule outran the pool: the driver itself delayed
            # this fire. NEVER silent — it flags in report()
            with self._lock:
                self._counts["overrun"] += 1
            PROFILER.count("load.overrun")
        ctx = _trace.mint_request(rows=r.rows)
        trace_id = None if ctx is None else ctx.trace_id
        outcome = "served"
        try:
            with _trace.activate(ctx):
                self._score(self._blocks[r.rows], r.priority, r.model)
        except RequestShed:
            outcome = "shed"
        except TimeoutError:  # RequestTimeout subclasses TimeoutError
            outcome = "timeout"
        except Exception:
            outcome = "errors"
        # latency from the SCHEDULED arrival: queueing delay anywhere
        # between the schedule and the result is the system's bill
        self._record(r, (now() - sched) * 1e3, outcome, trace_id)

    def run(self) -> Dict[str, object]:
        """Replay the whole schedule; returns `report()`. The dispatch
        loop sleeps to each scheduled instant and hands the fire to the
        pool — a full pool queues the fire (counted as overrun past the
        tolerance), it never re-times the schedule."""
        if self._ran:
            raise RuntimeError("OpenLoopDriver is single-shot; build a "
                               "new driver to replay again")
        self._ran = True
        if _OBS.enabled:
            _OBS.emit("load", "load.run", args={
                "requests": len(self._requests),
                "workers": self._workers,
                "phases": sorted({r.phase for r in self._requests})})
        from concurrent.futures import ThreadPoolExecutor
        with ThreadPoolExecutor(
                max_workers=max(1, self._workers),
                thread_name_prefix="sml-loadgen") as pool:
            # pre-spawn every worker thread: the executor creates them
            # lazily per submit, and a fire that also pays thread
            # start-up would book-keep as a spurious overrun. The
            # barrier holds each no-op on its own thread, forcing the
            # pool to its full width before the clock starts
            barrier = threading.Barrier(max(1, self._workers) + 1)
            for _ in range(max(1, self._workers)):
                pool.submit(barrier.wait)
            barrier.wait()
            t0 = now()
            epoch = t0
            futures = []
            for r in self._requests:
                delay = (epoch + r.t) - now()
                if delay > 0:
                    time.sleep(delay)
                futures.append(pool.submit(self._fire_one, r, epoch))
            for f in futures:
                f.result()
        self._wall_s = now() - t0
        from . import _register_driver
        _register_driver(self)
        return self.report()

    # -------------------------------------------------------- reporting
    def report(self) -> Dict[str, object]:
        """The honest-tail block: totals, overruns, and per-phase
        per-class p50/p99/p99.9 with the worst request's latency and
        trace exemplar."""
        with self._lock:
            counts = dict(self._counts)
            samples = {k: list(v) for k, v in self._samples.items()}
            worst = dict(self._worst)
        phases: Dict[str, dict] = {}
        order: List[str] = []
        for r in self._requests:
            if r.phase not in order:
                order.append(r.phase)
        for name in order:
            overall = samples.get((name, None), [])
            block = dict(_percentiles(overall))
            block["requests"] = len(overall)
            w_ms, w_trace = worst.get(name, (0.0, None))
            block["worst_ms"] = round(float(w_ms), 3)
            block["worst_trace"] = _trace.hex_id(w_trace)
            classes = {}
            for (ph, cls), lat in samples.items():
                if ph == name and cls is not None:
                    classes[cls] = dict(_percentiles(lat),
                                        count=len(lat))
            block["classes"] = dict(sorted(classes.items()))
            phases[name] = block
        n = max(counts["requests"], 1)
        return {
            "requests": counts["requests"],
            "served": counts["served"],
            "shed": counts["shed"],
            "timeout": counts["timeout"],
            "errors": counts["errors"],
            "overrun": counts["overrun"],
            "shed_rate": round(counts["shed"] / n, 4),
            "timeout_rate": round(counts["timeout"] / n, 4),
            "wall_s": round(self._wall_s, 3),
            "workers": self._workers,
            "phases": phases,
        }


def closed_loop_probe(score: Callable[[np.ndarray, Optional[str],
                                       Optional[str]], object],
                      requests: Sequence[Request], *,
                      feature_dim: int = 8) -> List[float]:
    """The coordinated-omission CONTROL: drive the same requests
    closed-loop (wait for each result before sending the next; latency
    stamped from SEND time, not schedule) and return the per-request
    latencies in ms. When the system stalls, these numbers stay small —
    that divergence from the open-loop report is the omission proof,
    which is the only thing this probe is for."""
    out: List[float] = []
    blocks = {r.rows: np.zeros((r.rows, int(feature_dim)),
                               dtype=np.float32)
              for r in requests}
    for r in requests:
        t0 = now()
        try:
            score(blocks[r.rows], r.priority, r.model)
        except Exception:
            pass  # the control only measures what a naive client times
        out.append((now() - t0) * 1e3)
    return out
