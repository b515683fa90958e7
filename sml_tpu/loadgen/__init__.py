"""sml_tpu.loadgen — open-loop, trace-driven load harness.

Closed-loop synthetic clients wait for each response before sending the
next, and therefore SLOW THEIR OWN ARRIVAL RATE the moment the system
queues — the classic coordinated-omission trap. The percentiles such a client
reports describe the workload the system degraded its clients into,
not the workload the users offered. This package measures the offered
workload honestly:

- `TraceSpec` / `PhaseSpec` (`_spec`): a declarative workload model —
  phases of fixed rate, diurnal ramps, Poisson/bursty inter-arrivals
  with a configurable burst factor, a fat-tailed request-width mix, a
  priority-class mix, an optional multi-model key mix — compiled by a
  deterministic seeded generator into a concrete arrival schedule.
- `OpenLoopDriver` (`_driver`): fires each request at its SCHEDULED
  arrival instant regardless of completions, from a bounded worker
  pool with explicit `load.overrun` accounting (never silent), and
  charges latency from scheduled-arrival→result so queueing delay
  lands on the system's bill, not the client's. Per-phase/per-class
  p50/p99/p99.9 + shed/timeout rates, with worst-request trace
  exemplars per phase (`load.request_ms.<phase>` metrics).
- `closed_loop_probe` (`_driver`): the deliberately-wrong control for
  the omission proof — same schedule, closed-loop, send-time latency.
- `prewarm_widths`: speculative shape-bucket prewarm keyed off the
  trace's DECLARED width mix (`parallel.prewarm.speculative_prewarm`),
  so measured phases hit warm per-bucket programs.

The last completed driver's report is the `load` block of
`obs.engine_health()` (`load_report()`). See docs/LOADGEN.md for the trace grammar, the open-loop
semantics, and the tail-engineering ladder this harness motivates
(`sml.serve.flushAutoTune`, `sml.fleet.burstSlope*`).
"""

from __future__ import annotations

import threading
from typing import Dict, Optional

from ..conf import _register

_register("sml.load.workers", 32, int,
          "Open-loop driver worker-pool width: how many in-flight "
          "requests the replay can hold before a fire is delayed past "
          "its scheduled instant (delays past sml.load.overrunMicros "
          "count load.overrun — the driver is never silently the "
          "bottleneck)")
_register("sml.load.overrunMicros", 5000, int,
          "Open-loop honesty tolerance: a request picked up this many "
          "microseconds after its SCHEDULED arrival instant counts "
          "load.overrun (the schedule outran the driver's pool). "
          "A load report with overruns indicts the harness, not the "
          "system")

from ._driver import OpenLoopDriver, closed_loop_probe  # noqa: E402
from ._spec import PhaseSpec, Request, TraceSpec  # noqa: E402

__all__ = ["PhaseSpec", "Request", "TraceSpec", "OpenLoopDriver",
           "closed_loop_probe", "load_report", "prewarm_widths"]

# ------------------------------------------------------------ registry
# the last COMPLETED driver, for the `load` block of engine_health()
# (read lazily off sys.modules — a health poll never imports this
# package, same contract as the fleet block)
_last_lock = threading.Lock()
_LAST: Dict[str, Optional[OpenLoopDriver]] = {"driver": None}


def _register_driver(driver: OpenLoopDriver) -> None:
    with _last_lock:
        _LAST["driver"] = driver


def load_report() -> Optional[Dict[str, object]]:
    """The load block of `obs.engine_health()`: the most recent
    completed open-loop replay's honest-tail report. None until a
    replay ran — like the fleet block, absence means the subsystem
    never ran."""
    with _last_lock:
        driver = _LAST["driver"]
    return None if driver is None else driver.report()


def prewarm_widths(fn, spec: TraceSpec, *, feature_dim: int = 8,
                   workers: Optional[int] = None) -> dict:
    """Speculative shape-bucket prewarm keyed off the trace's DECLARED
    width mix: pad each declared width onto the dispatch shape grid
    (`dispatch.bucket_rows`) and first-dispatch `fn` on a zero block
    per distinct bucket, so the measured phases reuse warm programs
    instead of paying trace+dispatch inside the tails."""
    from ..parallel import dispatch
    from ..parallel.prewarm import speculative_prewarm
    shapes = sorted({(dispatch.bucket_rows(int(rows), 1),
                      int(feature_dim))
                     for rows, _ in spec.widths})
    return speculative_prewarm(fn, shapes, workers=workers)
