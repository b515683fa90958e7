"""The flight recorder's event bus: a bounded ring of typed events.

Every instrumentation site in the engine (profiler spans/counters, dispatch
decisions, cache traffic, collective launches, program compiles, HBM ledger
gauges) funnels through ONE recorder so the Chrome-trace exporter, the
dispatch audit, and run autologging all read the same record. The Spark-UI
analogue: the event-log JSON the UI and history server are rendered from.

Hot-path contract (asserted in tests/test_obs.py): with the recorder
disabled every emit site early-outs on a single attribute load
(`RECORDER.enabled` is a plain bool, kept current by conf on_set hooks) —
no lock, no allocation, no conf lookup.
"""

from __future__ import annotations

import gc
import json
import os
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from ..conf import GLOBAL_CONF


@dataclass
class Event:
    """One typed engine event.

    kind: "span" | "counter" | "dispatch" | "cache" | "collective" |
          "compile". Counter events carry the post-increment cumulative
          total (gauges carry the current value) in args["total"], so the
          trace exporter can render counter tracks without replaying.
          Span events carry `trace` / `span` / `parent` ids in args when
          a trace context rode the thread (obs/_context.py).
    ts:   seconds since the recorder epoch (reset() re-zeros it).
    dur:  seconds, spans only.
    tid:  small dense per-thread id (stable within a recorder lifetime).
    """
    ts: float
    kind: str
    name: str
    dur: Optional[float] = None
    tid: int = 0
    args: Dict[str, object] = field(default_factory=dict)


def event_record(ev: Event) -> Dict[str, object]:
    """ONE line shape for serialized events — the JSONL sink and the
    blackbox bundle's events.jsonl both write exactly this, so a field
    added to `Event` changes every consumer (and blackbox_view's reader)
    in one place."""
    rec: Dict[str, object] = {"ts": round(ev.ts, 6), "kind": ev.kind,
                              "name": ev.name, "tid": ev.tid}
    if ev.dur is not None:
        rec["dur"] = round(ev.dur, 6)
    if ev.args:
        rec["args"] = ev.args
    return rec


#: bound on the thread-id -> dense-tid map: serving's short-lived client
#: threads would otherwise grow it forever. Past the bound, slots of DEAD
#: threads are reclaimed and reused (a reused lane shows a new thread's
#: events after the old thread's death — acceptable for a trace, fatal
#: for a leak). 512 concurrent LIVE threads still grow — correctness
#: over the bound — but the dead-thread leak is closed (asserted in
#: tests/test_obs.py).
_MAX_TIDS = 512

#: fit records kept (`keep_fit`): the newest, in a deque beside the ring
_MAX_FIT_RECORDS = 256

#: a collection of this many seconds or more, or of generation 2, lands a
#: span `gc.pause`; every shorter one is in `gc.pause_s` / `gc.collections`
#: alone (generation 0 runs hundreds of times a fit)
_GC_SPAN_S = 1e-3


class _GcPauses:
    """The `gc.callbacks` hook: two clock reads a collection, summed into
    `seconds` / `collections`; a pause worth a span waits in `waiting` for
    the recorder's next `emit`. It takes no lock and emits nothing: a
    collection may start on a thread that is inside `emit`, under the
    recorder's lock. The interpreter runs one collection at a time and
    both callbacks inside it, so these attributes have ONE writer, this
    call; a `reset()` moves the recorder's baseline, not these."""

    def __init__(self) -> None:
        self.t0: Optional[float] = None
        self.seconds = 0.0
        self.collections = 0
        self.waiting: deque = deque()

    def __call__(self, phase: str, info: Dict[str, int]) -> None:
        if phase == "start":
            self.t0 = time.perf_counter()
            return
        t0, self.t0 = self.t0, None
        if t0 is None:      # hooked between a collection's two callbacks
            return
        dt = time.perf_counter() - t0
        self.seconds += dt
        self.collections += 1
        if dt >= _GC_SPAN_S or info["generation"] == 2:
            self.waiting.append(
                (t0, dt, threading.get_ident(),
                 {"generation": info["generation"],
                  "collected": info["collected"]}))


class Recorder:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._ring: deque = deque(
            maxlen=max(int(GLOBAL_CONF.getInt("sml.obs.ringEvents")), 16))
        self._totals: Dict[str, float] = {}
        self._process: Dict[str, float] = {}   # see note_process
        self._tids: Dict[int, int] = {}
        self._free_tids: List[int] = []
        self._next_tid = 0
        self._epoch = time.perf_counter()
        self._sink = None
        self._sink_path: Optional[str] = None
        self._sink_bytes = 0
        self._sink_max = max(int(GLOBAL_CONF.getInt("sml.obs.sinkMaxBytes")),
                             0)
        self.dropped = 0
        self._fits: deque = deque(maxlen=_MAX_FIT_RECORDS)
        self._gc = _GcPauses()
        self._gc_base = (0.0, 0)    # the hook's counts at the last reset()
        # plain attribute, NOT a property: the disabled-path cost per event
        self.enabled: bool = GLOBAL_CONF.getBool("sml.obs.enabled")
        self._sync_gc_hook()

    # ------------------------------------------------------------- config
    def reconfigure(self) -> None:
        """Re-read the sml.obs.* conf (fired by on_set hooks)."""
        with self._lock:
            size = max(int(GLOBAL_CONF.getInt("sml.obs.ringEvents")), 16)
            if size != self._ring.maxlen:
                self._ring = deque(self._ring, maxlen=size)
            path = str(GLOBAL_CONF.get("sml.obs.sinkPath") or "").strip()
            if path != (self._sink_path or ""):
                if self._sink is not None:
                    try:
                        self._sink.close()
                    except OSError:
                        pass
                self._sink = None
                self._sink_path = path or None
            self._sink_max = max(
                int(GLOBAL_CONF.getInt("sml.obs.sinkMaxBytes")), 0)
        self.enabled = GLOBAL_CONF.getBool("sml.obs.enabled")
        self._sync_gc_hook()

    def note_process(self, **facts: float) -> None:
        """Facts of the PROCESS, not of a recorder epoch, as gauges
        `process.<key>`: `sml_tpu/__init__.py` notes them once (the
        process's age when the package started to import, and the
        import's own wall seconds). Kept beside the totals, so no
        `reset()` drops them and they are no event of a timeline: an
        enabled recorder's `counters()` always carry them."""
        with self._lock:
            self._process.update(
                ("process." + key, float(value))
                for key, value in facts.items())

    # --------------------------------------------------------------- emit
    def emit(self, kind: str, name: str, dur: Optional[float] = None,
             ts: Optional[float] = None,
             args: Optional[Dict[str, object]] = None) -> None:
        """Record one event. `ts` is an absolute perf_counter stamp (span
        starts); None stamps now. A span also adds its duration and one
        call to the running totals `span_s.<name>` / `span_n.<name>`
        (under the one lock taken here, no extra ring event): busy
        seconds and work done by span name, read through `counters()`
        like every counter, whatever the ring still holds. A span that
        read the process's CPU seconds between its two ends (`cpu_s`
        among its args: `Profiler.span`, for `taxonomy.CPU_SPANS`) adds
        them to `span_cpu_s.<name>` the same way. Cheap no-op when
        disabled."""
        if not self.enabled:
            return
        at = (ts if ts is not None else time.perf_counter()) - self._epoch
        ident = threading.get_ident()
        with self._lock:
            if self._gc.waiting:
                self._land_gc_locked()
            self._append_locked(at, kind, name, dur, ident, args or {})

    def _append_locked(self, at: float, kind: str, name: str,
                       dur: Optional[float], ident: int,
                       args: Dict[str, object]) -> None:
        # tid assignment under the lock: two threads' first emits must
        # not share a lane (a counter read outside it is not unique)
        tid = self._tids.get(ident)
        if tid is None:
            tid = self._claim_tid_locked(ident)
        ev = Event(ts=max(at, 0.0), kind=kind, name=name, dur=dur,
                   tid=tid, args=args)
        if len(self._ring) == self._ring.maxlen:
            self.dropped += 1
        self._ring.append(ev)
        if kind == "span":
            totals, busy, calls = self._totals, "span_s." + name, \
                "span_n." + name
            totals[busy] = totals.get(busy, 0.0) + (dur or 0.0)
            totals[calls] = totals.get(calls, 0.0) + 1.0
            if "cpu_s" in args:
                cpu = "span_cpu_s." + name
                totals[cpu] = totals.get(cpu, 0.0) + args["cpu_s"]
        sink = self._ensure_sink()
        if sink is not None:  # under the lock: lines must not interleave
            self._write_sink(ev, sink)

    def _claim_tid_locked(self, ident: int) -> int:
        """Dense lane id for a newly-seen thread. At the _MAX_TIDS bound,
        dead threads' slots are reclaimed first (the serving layer's
        short-lived client threads must not grow the map forever)."""
        if len(self._tids) >= _MAX_TIDS and not self._free_tids:
            live = {t.ident for t in threading.enumerate()}
            for dead in [i for i in self._tids if i not in live]:
                self._free_tids.append(self._tids.pop(dead))
        if self._free_tids:
            tid = self._free_tids.pop()
        else:
            tid = self._next_tid
            self._next_tid += 1
        self._tids[ident] = tid
        return tid

    def epoch_unix(self) -> float:
        """Wall-clock (Unix epoch) instant of ts=0 on this recorder's
        timeline — the absolute anchor postmortems need to correlate
        events with external logs. Derived on demand from the live
        offset between the epoch clock and the perf_counter domain
        (both advance together), stamped into sink headers, exported
        traces, and blackbox bundles."""
        from ..utils.profiler import now, wallclock
        return wallclock() - (now() - self._epoch)

    def counter(self, name: str, inc: float = 1.0) -> None:
        """Cumulative counter: bumps the running total and records a
        counter event carrying the new total."""
        if not self.enabled:
            return
        with self._lock:
            total = self._totals.get(name, 0.0) + inc
            self._totals[name] = total
        self.emit("counter", name, args={"total": total, "inc": inc})

    def total(self, name: str, inc: float = 1.0) -> None:
        """Add to a running total with NO ring event: what is counted
        once a fit or once a watchdog wait and read as a delta between
        two `counters()` snapshots, where an event each would say
        nothing the fit's record does not."""
        if not self.enabled:
            return
        with self._lock:
            self._totals[name] = self._totals.get(name, 0.0) + inc

    def gauge(self, name: str, value: float) -> None:
        """Point-in-time gauge (HBM ledger live bytes): the recorded
        total IS the current value, not a sum."""
        if not self.enabled:
            return
        with self._lock:
            self._totals[name] = float(value)
        self.emit("counter", name, args={"total": float(value),
                                         "gauge": True})

    def span(self, name: str, t0: float, dur: float, **meta) -> None:
        """A completed span: `t0` is its absolute perf_counter start."""
        if not self.enabled:
            return
        self.emit("span", name, dur=dur, ts=t0,
                  args={k: v for k, v in meta.items() if v is not None})

    # --------------------------------------------------------- fit records
    def keep_fit(self, record: Dict[str, object]) -> None:
        """Keep one fit's record (`obs/_fits.py`), the newest
        `_MAX_FIT_RECORDS` of them: beside the ring, not in it."""
        if not self.enabled:
            return
        with self._lock:
            self._fits.append(record)

    def fit_records(self) -> List[Dict[str, object]]:
        with self._lock:
            return list(self._fits)

    def trace_spans(self, trace_id: int, since: float) -> List[Event]:
        """The span events of one trace that ended at or after `since` (an
        absolute perf_counter stamp), newest first: a walk of the ring
        backwards under the lock, until an event that ended before
        `since` (events are appended as they end, so a fit's are the
        ring's newest 40 or so; the ring is not copied)."""
        at = since - self._epoch
        out: List[Event] = []
        with self._lock:
            if self._gc.waiting:
                self._land_gc_locked()
            for ev in reversed(self._ring):
                if ev.ts + (ev.dur or 0.0) < at:
                    break
                if ev.kind == "span" and ev.args.get("trace") == trace_id:
                    out.append(ev)
        return out

    # ------------------------------------------------------ the collector
    def _sync_gc_hook(self) -> None:
        """The `gc.callbacks` hook is in the list while the recorder is on
        and out of it while it is off (off: nothing runs a collection)."""
        hooked = self._gc in gc.callbacks
        if self.enabled and not hooked:
            gc.callbacks.append(self._gc)
        elif hooked and not self.enabled:
            gc.callbacks.remove(self._gc)

    def _land_gc_locked(self) -> None:
        """The pauses that wait become `gc.pause` spans, each on the lane
        of the thread it ran on: before the next event, so the ring stays
        in the order things ended."""
        waiting = self._gc.waiting
        while waiting:
            t0, dt, ident, args = waiting.popleft()
            self._append_locked(t0 - self._epoch, "span", "gc.pause", dt,
                                ident, args)

    def gc_totals(self) -> tuple:
        """(seconds, collections) of the collector's pauses since the last
        `reset()`: what `counters()` reports as `gc.pause_s` and
        `gc.collections`, without the copy."""
        seconds, collections = self._gc_base
        return self._gc.seconds - seconds, self._gc.collections - collections

    # --------------------------------------------------------------- sink
    def _sink_header_locked(self, sink) -> None:
        """Anchor line stamped whenever the sink (re)opens: an
        event-shaped record carrying the wall-clock epoch, so a
        postmortem reader can place the relative timeline against
        external logs. Event-shaped (kind "meta") so line-oriented
        consumers need no special case."""
        try:
            hdr = {"ts": 0.0, "kind": "meta", "name": "obs.header",
                   "args": {"version": 1,
                            "epoch_unix": round(self.epoch_unix(), 6),
                            "pid": os.getpid()}}
            line = json.dumps(hdr) + "\n"
            sink.write(line)
            sink.flush()
            self._sink_bytes += len(line)
        except (OSError, ValueError):
            pass  # a header failure must not take the sink down

    def _ensure_sink(self):
        if self._sink is None and self._sink_path:
            try:
                self._sink = open(self._sink_path, "a")
                self._sink_bytes = os.path.getsize(self._sink_path)
                self._sink_header_locked(self._sink)
            except OSError:
                self._sink_path = None
        return self._sink

    def _write_sink(self, ev: Event, sink) -> None:
        try:
            line = json.dumps(event_record(ev), default=str) + "\n"
            sink.write(line)
            sink.flush()
            self._sink_bytes += len(line)
            # single rotation (sml.obs.sinkMaxBytes): the live file rolls
            # to <path>.1 (replacing the previous roll) and reopens fresh,
            # so the sink holds at most ~2x the bound instead of growing
            # without limit. Runs under the emit lock, after a COMPLETE
            # line: rotation can never split a record.
            if self._sink_max and self._sink_bytes >= self._sink_max:
                sink.close()
                self._sink = None
                os.replace(self._sink_path, self._sink_path + ".1")
                self._sink = open(self._sink_path, "a")
                self._sink_bytes = 0
                self._sink_header_locked(self._sink)
        except (OSError, ValueError):
            self._sink_path = None  # a dead sink must not take fits down
            self._sink = None

    # ------------------------------------------------------------ reading
    def events(self) -> List[Event]:
        with self._lock:
            if self._gc.waiting:
                self._land_gc_locked()
            return list(self._ring)

    def counters(self) -> Dict[str, float]:
        """The running totals, the collector's pauses (`gc.pause_s`,
        `gc.collections`: `_GcPauses`) and the `process.*` gauges
        (`note_process`). Off, the recorder answers with nothing at all,
        as its contract has been (`tests/test_obs.py`): the gauges too."""
        seconds, collections = self.gc_totals()
        with self._lock:
            if not self.enabled:
                return dict(self._totals)
            out = dict(self._totals, **self._process)
            if collections:
                out["gc.pause_s"] = seconds
                out["gc.collections"] = float(collections)
            return out

    def reset(self) -> None:
        """Drop all events, totals and fit records and re-zero the epoch
        (enabled state, sink configuration and the `process.*` gauges
        survive: those are facts of the process, not of an epoch; the
        collector's counts restart from here). An OPEN sink gets a fresh
        header line: its previous epoch_unix anchor no longer describes
        the re-zeroed timeline, and a postmortem reader re-anchors at the
        newest header above each line."""
        with self._lock:
            self._land_gc_locked()      # into the ring that is dropped
            self._ring.clear()
            self._totals.clear()
            self._fits.clear()
            self._gc_base = (self._gc.seconds, self._gc.collections)
            self.dropped = 0
            self._epoch = time.perf_counter()
            if self._sink is not None:
                self._sink_header_locked(self._sink)


RECORDER = Recorder()

for _key in ("sml.obs.enabled", "sml.obs.ringEvents", "sml.obs.sinkPath",
             "sml.obs.sinkMaxBytes"):
    GLOBAL_CONF.on_set(_key, RECORDER.reconfigure)
