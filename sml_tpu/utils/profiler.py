"""Op-level structured timing + XLA profiler hooks (SURVEY §5 "Tracing").

The reference leans on the Spark UI / Ganglia for shuffle, storage and
executor metrics (`SML/ML 00b - Spark Review.py:78-84`,
`SML/ML Electives/MLE 05 - Best Practices.py:31-36`). The replacement is a
structured in-process trace: every engine op records name, wall time, rows,
and bytes; `report()` renders the UI-equivalent table. While the flight
recorder is on, every span is also a `jax.profiler.TraceAnnotation`, so any
`jax.profiler` trace carries the engine's spans on its host plane.
"""

from __future__ import annotations

import contextlib
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional

from ..conf import GLOBAL_CONF
from ..obs import _audit as _obs_audit
from ..obs import _context as _obs_ctx
from ..obs._recorder import RECORDER as _OBS
from ..obs.taxonomy import CPU_SPANS as _CPU_SPANS
from ..obs._watchdog import WATCHDOG as _OBS_WATCHDOG


def now() -> float:
    """THE engine's monotonic clock (seconds, perf_counter domain — the
    same domain as recorder event stamps and audit walls). Every timing
    outside this module and obs/ must use `now()` / `wallclock()` / a
    `PROFILER.span` — enforced by the graftlint rule
    no-wallclock-in-engine — so measurements stay correlatable with the
    flight-recorder timeline."""
    return time.perf_counter()


def wallclock() -> float:
    """THE engine's epoch clock (seconds since the Unix epoch), for
    domain timestamps (Delta log entries, tracking runs, stream batch
    stamps, deadlines). See `now()` for the single-clock rule."""
    return time.time()


@dataclass
class Span:
    name: str
    wall_s: float
    rows: Optional[int] = None
    meta: Dict[str, object] = field(default_factory=dict)
    self_s: float = 0.0  # wall minus enclosed child spans (same thread)


class Profiler:
    def __init__(self) -> None:
        self._lock = threading.RLock()
        self._spans: List[Span] = []
        self._counters: Dict[str, float] = {}
        self._tls = threading.local()
        # reset() generation: bumped on every reset so spans OPEN across a
        # reset invalidate instead of attributing child time to a stale
        # parent entry (and instead of appending a span whose wall time
        # straddles the reset). Thread-local stacks lazily re-create when
        # their recorded generation goes stale — reset() cannot reach
        # other threads' TLS directly.
        self._gen = 0

    def count(self, name: str, inc: float = 1.0) -> None:
        """Engine counters (host↔device bytes, staging-cache hits, ...) —
        the MLE 05-style observability the Spark UI/Ganglia provided
        (`SML/ML Electives/MLE 05:24-36`). Forwarded to the flight
        recorder (`sml_tpu.obs`) when it is on, so counter tracks and
        engine.* run metrics see the same stream."""
        if _OBS.enabled:
            _OBS.counter(name, inc)
        if not self.enabled:
            return
        with self._lock:
            self._counters[name] = self._counters.get(name, 0.0) + inc

    def counters(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._counters)

    @property
    def enabled(self) -> bool:
        return GLOBAL_CONF.getBool("sml.profiler.enabled")

    @contextlib.contextmanager
    def span(self, name: str, rows: Optional[int] = None,
             **meta) -> Iterator[Dict[str, object]]:
        """Nested spans subtract from the parent's SELF time, so a
        `materialize` that waits on a device program reports only its own
        host-side cost — totals in the report stay attributable.

        Yields the span's `meta` dict: what is known only when the work
        is done (a cache hit, the bytes moved, `rows`) is added to it
        inside the block and lands on the recorded span.

        Runs when the profiler OR the flight recorder is on; the recorder
        additionally gets a timestamped span event (for the Chrome trace).
        Under a riding trace context (obs/_context.py) the span is a
        CHILD unit of it: the event carries the context's `trace` id, a
        `span` id of its own and its `parent`'s span id, and the child is
        the active context inside the block, so spans nest as they ran.
        The span is also a `jax.profiler.TraceAnnotation`, so a profiler
        trace shows it on the host plane above the device ops it caused.
        A span the taxonomy lists under `CPU_SPANS` (the root `fit` and
        the spans its host phases are made of) also reads the PROCESS's
        CPU seconds at its two ends while the recorder is on
        (`time.process_time`: a system call, 6 us on the chip's host):
        the delta rides its event as `cpu_s` and the recorder's running
        total `span_cpu_s.<name>`. Process-wide on purpose: a pooled
        phase's work is on the pool's threads.
        For spans carrying a dispatch `route`, it registers a
        stall-watchdog ticket (expected wall = the audit's prediction for
        this thread's pending decision) and feeds the measured wall time
        back to the dispatch audit."""
        prof_on = self.enabled
        obs_on = _OBS.enabled
        if not prof_on and not obs_on:
            yield meta
            return
        route = meta.get("route")
        ticket = ctx = None
        with contextlib.ExitStack() as entered:
            if obs_on:
                parent = _obs_ctx.current()
                if parent is not None and "trace" not in meta:
                    ctx = entered.enter_context(
                        _obs_ctx.activate(parent.child()))
                if route in ("host", "device"):
                    # a dispatch launch in flight: the watchdog flags it if
                    # it exceeds stallFactor x its own predicted wall (floor
                    # stallMillis) — obs/_watchdog.py
                    ticket = _OBS_WATCHDOG.open(
                        "dispatch", name,
                        expected_s=_obs_audit.expected_wall(route),
                        trace=parent)
                # only a process that already runs jax can be tracing
                jax = sys.modules.get("jax")
                if jax is not None:
                    entered.enter_context(jax.profiler.TraceAnnotation(name))
            if prof_on:
                gen = self._gen
                tls = self._tls
                if getattr(tls, "gen", None) != gen:
                    tls.stack = []   # stale stack from before a reset()
                    tls.gen = gen
                stack = tls.stack
                child_acc = [0.0]
                stack.append(child_acc)
            cpu0 = time.process_time() \
                if obs_on and name in _CPU_SPANS else None
            t0 = time.perf_counter()
            try:
                yield meta
            finally:
                dt = time.perf_counter() - t0
                if cpu0 is not None:
                    meta["cpu_s"] = time.process_time() - cpu0
                if "rows" in meta:   # counted inside the block
                    rows = meta.pop("rows")
                _OBS_WATCHDOG.close(ticket)
                if prof_on:
                    if self._gen == gen:
                        stack.pop()
                        if stack:
                            stack[-1][0] += dt
                        with self._lock:
                            self._spans.append(
                                Span(name, dt, rows, meta,
                                     self_s=max(0.0, dt - child_acc[0])))
                    # else: reset() fired mid-span — this span's timing
                    # straddles it and the stack was invalidated; drop both
                if obs_on and _OBS.enabled:
                    if ctx is not None:
                        _OBS.span(name, t0, dt, rows=rows,
                                  trace=ctx.trace_id, span=ctx.span_id,
                                  parent=ctx.parent_id, **meta)
                    else:
                        _OBS.span(name, t0, dt, rows=rows, **meta)
                    if route in ("host", "device"):
                        _obs_audit.attach(route, name, dt)

    def spans(self) -> List[Span]:
        with self._lock:
            return list(self._spans)

    def reset(self) -> None:
        with self._lock:
            self._spans.clear()
            self._counters.clear()
            self._gen += 1

    def report(self) -> str:
        """Spark-UI-style aggregate table: op, calls, total wall, SELF time
        (wall minus enclosed spans — the op's attributable cost), rows, and
        the dispatch route (host / device / mixed) where recorded."""
        agg: Dict[str, List[float]] = {}
        selfs: Dict[str, float] = {}
        rows_agg: Dict[str, int] = {}
        routes: Dict[str, set] = {}
        skews: Dict[str, float] = {}
        for s in self.spans():
            agg.setdefault(s.name, []).append(s.wall_s)
            selfs[s.name] = selfs.get(s.name, 0.0) + s.self_s
            if s.rows:
                rows_agg[s.name] = rows_agg.get(s.name, 0) + s.rows
            r = s.meta.get("route")
            if r:
                routes.setdefault(s.name, set()).add(r)
            sk = s.meta.get("skew")
            if sk is not None:
                skews[s.name] = max(skews.get(s.name, 0.0), float(sk))
        lines = [f"{'op':<34}{'calls':>7}{'total_s':>10}{'self_s':>10}"
                 f"{'rows':>13}{'route':>9}{'skew':>7}"]
        for name in sorted(agg, key=lambda n: -selfs.get(n, 0.0)):
            ts = agg[name]
            rset = routes.get(name, set())
            route = (rset.pop() if len(rset) == 1
                     else ("mixed" if rset else "-"))
            sk = f"{skews[name]:.2f}" if name in skews else "-"
            lines.append(f"{name:<34}{len(ts):>7}{sum(ts):>10.4f}"
                         f"{selfs.get(name, 0.0):>10.4f}"
                         f"{rows_agg.get(name, 0):>13}{route:>9}{sk:>7}")
        counters = self.counters()
        if counters:
            lines.append("---- engine counters ----")
            for k in sorted(counters):
                v = counters[k]
                if "_bytes" in k:
                    lines.append(f"{k:<34}{v / 1e6:>14.1f} MB")
                else:
                    lines.append(f"{k:<34}{v:>14.0f}")
        return "\n".join(lines)


PROFILER = Profiler()
