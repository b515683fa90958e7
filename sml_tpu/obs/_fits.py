"""A record for every fit, and the verdict on a slow one.

The recorder's running totals (`span_s.<name>`) are a window's MEAN: one
fit in some hundreds takes seconds more than its neighbours, and a total
cannot say which fit, in which phase, or why. When the root span `fit`
closes, `obs._fit_root` makes ONE record of it here, the per-fit form of
exactly what the totals sum, and the recorder keeps the newest 256
(`obs.fit_records()`):

- `trace`, `estimator` (the root's class; a `Pipeline`'s stages' classes
  too), `rows`, `shape` (below), `t0` (the recorder's clock), `wall_s`,
  `cpu_s`;
- `spans`: for every span NAME of the fit's trace, at any depth, `wall_s`,
  `n`, the sum of every number the spans of that name noted (`cpu_s`,
  `longest_s`, `bytes`, `hit`, `copied`, `warm`, ...) and the `phase` it
  lies in (by its ancestors; none for the root and for a span that merely
  contains phases, as `program.tree_ensemble` does);
- `phases`: the eight seconds the benchmark reports, by the name lists of
  `taxonomy.FIT_PHASES`, `fit.host.unattributed_s` the root less the
  seven; `phases_cpu_s` the same of the spans' CPU seconds;
- `gc_s`, `gc_n` (the collector's pauses while the root was open),
  `watchdog_late_s` (the watchdog loop's own lateness meanwhile) and, in
  a SLOW fit's record alone, `rss_bytes` and `mem_available_bytes` at the
  close (`/proc`; absent where there is none).

A fit's SHAPE is its `estimator` and `round(log2(rows))` (none where the
frame's rows are not known when the fit starts). Its expectation
is the median `wall_s` of the last up to 32 records of its shape (none
before there are 4): the root's watchdog ticket is flagged past
`threshold(median)` while the fit is still slow, and a record past it is a
slow fit: one `fit.slow` event (the record, the median, the phases by their
excess over the peers' medians, largest first, and the span names inside
the first), the totals `fit.slow` / `fit.slow.excess_s`, and ONE line at
WARNING through `logging.getLogger("sml_tpu.obs")`, which Python's
last-resort handler puts on stderr: how an untraced run's stall gets a name.

Reading the line: wall and no CPU with the watchdog on time: that thread
was blocked; everything late (`watchdog late`): the process, or the
machine, stood still; CPU burnt: it was working, and the `stall.detected`
event's stacks say at what.
"""

from __future__ import annotations

import logging
import math
import numbers
import os
import statistics
import time
from typing import Dict, List, Optional, Tuple

from . import _context
from ._metrics import METRICS
from ._recorder import RECORDER, Event
from ._watchdog import WATCHDOG
from .taxonomy import FIT_PHASES

UNATTRIBUTED = "fit.host.unattributed_s"

#: a shape's expectation: the median of its last `_MAX_PEERS` records, none
#: before there are `_MIN_PEERS`
_MIN_PEERS = 4
_MAX_PEERS = 32
#: slow: over the median by more than a quarter AND by more than 0.1 s
_SLOW_FACTOR = 1.25
_SLOW_S = 0.1

_IDS = ("trace", "span", "parent")
_PHASE_OF = {name: metric for metric, names in FIT_PHASES.items()
             for name in names}
_LOG = logging.getLogger("sml_tpu.obs")
_PAGE = os.sysconf("SC_PAGE_SIZE") if hasattr(os, "sysconf") else 4096


def estimator_name(estimator) -> str:
    """The root's class and, for a `Pipeline`, its stages' classes."""
    name = type(estimator).__name__
    stages = estimator.getOrDefault("stages") \
        if estimator.hasParam("stages") else None
    if stages:
        name += "(" + ",".join(type(s).__name__ for s in stages) + ")"
    return name


def shape_of(estimator: str, rows: Optional[int]) -> Optional[Tuple]:
    """None where the rows are not known (a frame not yet materialized):
    such a fit has no peers, so no expectation and no verdict."""
    return (estimator, round(math.log2(rows))) if rows else None


def peers_of(records: List[Dict], shape: Optional[Tuple]) -> List[Dict]:
    """The last up to `_MAX_PEERS` of `records` (oldest first) of a shape,
    newest first."""
    out: List[Dict] = []
    if shape is None:
        return out
    for record in reversed(records):
        if record["shape"] == shape:
            out.append(record)
            if len(out) == _MAX_PEERS:
                break
    return out


def expectation(peers: List[Dict]) -> Optional[float]:
    """A shape's median wall seconds; None before it has `_MIN_PEERS`."""
    if len(peers) < _MIN_PEERS:
        return None
    return statistics.median(p["wall_s"] for p in peers)


def threshold(median: float) -> float:
    return max(_SLOW_FACTOR * median, median + _SLOW_S)


def _memory() -> Dict[str, int]:
    """The process's resident bytes and the machine's available ones, one
    read each; what cannot be read is left out. Read for a SLOW fit alone:
    on the chip tool's kernel (gVisor) the two reads take 0.16 ms where
    nothing was mapped since the last, and 8.3 ms after a fit that staged
    and freed its arrays (PERF.md §6, PR 52): 3 % of the shortest cell's
    fit, so not every fit's to pay."""
    out: Dict[str, int] = {}
    try:
        with open("/proc/self/statm") as f:
            out["rss_bytes"] = int(f.read().split()[1]) * _PAGE
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemAvailable:"):
                    out["mem_available_bytes"] = int(line.split()[1]) * 1024
                    break
    except (OSError, ValueError, IndexError):
        pass
    return out


def make_record(events: List[Event], root_id: int, estimator: str,
                **facts) -> Optional[Dict[str, object]]:
    """One fit's record from the span events of its trace (newest first:
    `Recorder.trace_spans`); `root_id` is the root span's id, `facts` what
    the caller measured round it. None where the root is not among them
    (a `reset()` while the fit ran)."""
    by_id = {ev.args.get("span"): ev for ev in events}
    root = by_id.get(root_id)
    if root is None:
        return None
    spans: Dict[str, Dict[str, object]] = {}
    containers = set()
    for ev in events:
        # the span's way up to the root: the first phase span on it is the
        # phase it lies in, and whatever is above that one contains phases
        phase, node = None, ev
        while node is not None and node is not root:
            if phase is not None:
                containers.add(node.name)
            elif node.name in _PHASE_OF:
                phase = _PHASE_OF[node.name]
            node = by_id.get(node.args.get("parent"))
        if node is None:
            continue        # of the trace, not of this fit
        entry = spans.get(ev.name)
        if entry is None:
            entry = spans[ev.name] = {"wall_s": 0.0, "n": 0}
            if ev is not root:
                entry["phase"] = phase or UNATTRIBUTED
        entry["wall_s"] += ev.dur or 0.0
        entry["n"] += 1
        for key, value in ev.args.items():
            if key not in _IDS and isinstance(value, numbers.Real):
                entry[key] = entry.get(key, 0) + value
    for name in containers:
        if spans[name].get("phase") == UNATTRIBUTED:
            del spans[name]["phase"]

    def summed(what: str, whole: float) -> Dict[str, float]:
        out = {metric: sum(spans[n].get(what, 0.0) for n in names
                           if n in spans)
               for metric, names in FIT_PHASES.items()}
        out[UNATTRIBUTED] = whole - sum(out.values())
        return out

    cpu_s = root.args.get("cpu_s", 0.0)
    rows = root.args.get("rows")
    return {"trace": root.args.get("trace"), "estimator": estimator,
            "rows": rows, "shape": shape_of(estimator, rows), "t0": root.ts,
            "wall_s": root.dur, "cpu_s": cpu_s, "spans": spans,
            "phases": summed("wall_s", root.dur),
            "phases_cpu_s": summed("cpu_s", cpu_s), **facts}


def verdict(record: Dict, peers: List[Dict]) -> Optional[Dict[str, object]]:
    """Whether `record` is a slow fit among the earlier records of its
    shape, and where: None where the shape has no expectation yet or the
    fit is within `threshold` of it. Else the median, and the eight phases
    by their excess over the peers' medians ([metric, wall, cpu] seconds),
    largest first, with the span names inside the first ranked the same
    way ([name, wall, cpu or None])."""
    median = expectation(peers)
    if median is None or record["wall_s"] <= threshold(median):
        return None

    def over(value: float, theirs) -> float:
        return value - statistics.median(theirs)

    phases = sorted(
        ([metric, over(wall, (p["phases"][metric] for p in peers)),
          over(record["phases_cpu_s"][metric],
               (p["phases_cpu_s"][metric] for p in peers))]
         for metric, wall in record["phases"].items()),
        key=lambda row: -row[1])
    first = phases[0][0]

    def noted(p: Dict, name: str, what: str) -> float:
        return p["spans"].get(name, {}).get(what, 0.0)

    inside = sorted(
        ([name, over(entry["wall_s"],
                     (noted(p, name, "wall_s") for p in peers)),
          over(entry["cpu_s"], (noted(p, name, "cpu_s") for p in peers))
          if "cpu_s" in entry else None]
         for name, entry in record["spans"].items()
         if entry.get("phase") == first),
        key=lambda row: -row[1])
    return {"median_s": median, "of": len(peers),
            "excess_s": record["wall_s"] - median,
            "phases": phases, "inside": inside}


def line(record: Dict, found: Dict) -> str:
    """`slow fit 5.75 s (median 0.98 of 31): fit.quantize +4.61 s wall /
    +0.03 s cpu (fit.quantize.stats +4.60 s); gc 0.00 s; watchdog late
    0.00 s; rss 22.9 GiB, available 3.1 GiB; trace 0x...`: the first
    phase's own span with the largest excess, then the child inside it
    with the largest."""
    metric, wall, cpu = found["phases"][0]
    own = next((row for row in found["inside"] if row[0] in _PHASE_OF), None)
    inner = next((row for row in found["inside"]
                  if row[0] not in _PHASE_OF), None)
    if own is not None:
        metric, wall, cpu = own
    text = (f"slow fit {record['wall_s']:.2f} s (median "
            f"{found['median_s']:.2f} of {found['of']}): {metric} "
            f"{wall:+.2f} s wall / {cpu:+.2f} s cpu")
    if inner is not None:
        text += f" ({inner[0]} {inner[1]:+.2f} s)"
    text += (f"; gc {record['gc_s']:.2f} s; watchdog late "
             f"{record['watchdog_late_s']:.2f} s")
    if "rss_bytes" in record and "mem_available_bytes" in record:
        text += (f"; rss {record['rss_bytes'] / 2**30:.1f} GiB, available "
                 f"{record['mem_available_bytes'] / 2**30:.1f} GiB")
    return text + f"; trace {_context.hex_id(record['trace'])}"


# ------------------------------------------------------- round a root fit
def open_fit(estimator, rows: Optional[int]) -> Dict[str, object]:
    """Before the root span opens: the shape's peers and, where they give
    an expectation, a watchdog ticket of kind `fit` flagged at
    `threshold(median)` (its own, not the dispatch's floor of seconds)."""
    name = estimator_name(estimator)
    peers = peers_of(RECORDER.fit_records(), shape_of(name, rows))
    expected = expectation(peers)
    gc_s, gc_n = RECORDER.gc_totals()
    return {"estimator": name, "peers": peers, "gc_s": gc_s, "gc_n": gc_n,
            "late_s": WATCHDOG.late_s, "t0": time.perf_counter(),
            "ticket": None if expected is None else WATCHDOG.open(
                "fit", name, expected_s=expected,
                threshold_s=threshold(expected),
                trace=_context.current())}


def close_fit(state: Dict[str, object], root: Optional[object]) -> None:
    """After the root span closed: retire the ticket and, for a fit that
    ended (`root` is its span's context; None where it raised), make the
    record, keep it, feed `fit.wall_ms` and give the verdict."""
    WATCHDOG.close(state["ticket"])
    if root is None or not RECORDER.enabled:
        return
    gc_s, gc_n = RECORDER.gc_totals()
    record = make_record(
        RECORDER.trace_spans(root.trace_id, state["t0"]), root.span_id,
        state["estimator"],
        gc_s=max(gc_s - state["gc_s"], 0.0),
        gc_n=max(gc_n - state["gc_n"], 0),
        watchdog_late_s=max(WATCHDOG.late_s - state["late_s"], 0.0))
    if record is None:
        return
    found = verdict(record, state["peers"])
    if found is not None:
        record.update(_memory())
    RECORDER.keep_fit(record)
    RECORDER.total("fit.gc_s", record["gc_s"])
    RECORDER.total("fit.slow", 0.0 if found is None else 1.0)
    RECORDER.total("fit.slow.excess_s",
                   0.0 if found is None else found["excess_s"])
    METRICS.observe("fit.wall_ms", record["wall_s"] * 1e3,
                    exemplar=record["trace"])
    if found is not None:
        RECORDER.emit("fit", "fit.slow", args=dict(
            found, record=record, trace=record["trace"]))
        _LOG.warning("%s", line(record, found))
