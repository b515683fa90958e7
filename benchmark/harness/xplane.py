"""From a profiler trace (`.xplane.pb`) to device busy time, the operations that took most time and the idle gaps named by what the
host was doing. Read with `jax.profiler.ProfileData`, nothing else.

Device planes are those named `/device:TPU:<n>`; the operations are the
events of their `XLA Ops` line. The benchmark's own host annotations
(`jax.profiler.TraceAnnotation("bench.<what>")`) are found on the host
planes; `bench.window` brackets the measured window and is the only part
of the trace that is reduced.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
Labelled = Tuple[str, float, float]

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
ANNOTATION_PREFIX = "bench."
WINDOW = "bench.window"
SHORT_GAP_NS = 50_000.0
#: how many enclosing intervals are looked through for the one covering a point
LOOK_BACK = 64


def short_op_name(name: str) -> str:
    """`%fusion.4 = f32[8,128]{...} fusion(operands...), kind=kLoop, ...`
    -> `%fusion.4 f32[8,128] fusion kLoop`: the trace names a device
    operation by its whole HLO text."""
    lhs, sep, rhs = name.partition(" = ")
    if not sep:
        return name[:120]
    shape = re.search(r"[a-z]+[0-9]*\[[0-9,]*\]", rhs)
    opcode = re.search(r"[\}\)\]] ([a-z][a-z0-9\-_.]*)\(", rhs)
    parts = [lhs, shape.group(0) if shape else "",
             opcode.group(1) if opcode else ""]
    for marker in ("kind=", "custom_call_target="):
        if marker in rhs:
            parts.append(rhs.split(marker, 1)[1].split(",")[0].strip('"'))
    return " ".join(p for p in parts if p)[:120]


def newest_trace_file(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def union(intervals: Sequence[Interval], lo: float, hi: float
          ) -> List[Interval]:
    """The union of the intervals, clipped to [lo, hi], as sorted disjoint
    intervals."""
    out: List[Interval] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals: Sequence[Interval]) -> float:
    return float(sum(b - a for a, b in intervals))


def self_times(events: Sequence[Labelled]) -> List[Tuple[str, float]]:
    """Each event's own time: its duration less that of the events nested
    in it (a `while` encloses the operations of its body on the same
    line). Events are (name, start, end)."""
    out: List[Tuple[str, float]] = []
    stack: List[List] = []   # [name, end, self]
    for name, a, b in sorted(events, key=lambda e: (e[1], -(e[2] - e[1]))):
        while stack and a >= stack[-1][1]:
            done = stack.pop()
            out.append((done[0], done[2]))
        if stack:
            stack[-1][2] -= min(b, stack[-1][1]) - a
        stack.append([name, b, b - a])
    while stack:
        done = stack.pop()
        out.append((done[0], done[2]))
    return out


class Trace:
    """The parts of one trace the metrics read, times in nanoseconds."""

    def __init__(self, device_ops: List[List[Labelled]],
                 annotations: List[Labelled]):
        self.device_ops = device_ops          # one list a device plane
        self.annotations = annotations        # bench.* host annotations

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData
        return cls.from_profile(ProfileData.from_file(path))

    @classmethod
    def from_profile(cls, profile) -> "Trace":
        device_ops: List[List[Labelled]] = []
        annotations: List[Labelled] = []
        for plane in profile.planes:
            if plane.name.startswith(DEVICE_PREFIX) and \
                    plane.name[len(DEVICE_PREFIX):].isdigit():
                ops: List[Labelled] = []
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        ops.extend((e.name, float(e.start_ns),
                                    float(e.start_ns + e.duration_ns))
                                   for e in line.events)
                device_ops.append(ops)
            elif not plane.name.startswith("/device:"):
                for line in plane.lines:
                    annotations.extend(
                        (e.name, float(e.start_ns),
                         float(e.start_ns + e.duration_ns))
                        for e in line.events
                        if e.name.startswith(ANNOTATION_PREFIX))
        return cls(device_ops, annotations)

    # ------------------------------------------------------------ windows
    def spans(self, name: str) -> List[Interval]:
        return sorted((a, b) for n, a, b in self.annotations if n == name)

    def window(self) -> Interval:
        spans = self.spans(WINDOW)
        if len(spans) != 1:
            raise ValueError(f"the trace holds {len(spans)} {WINDOW} "
                             f"annotations, not one")
        return spans[0]

    # --------------------------------------------------------------- busy
    def busy_ns(self, lo: float, hi: float) -> float:
        """Nanoseconds in [lo, hi] in which an operation ran on the device,
        averaged over the device planes."""
        if not self.device_ops:
            return 0.0
        return sum(total(union([(a, b) for _, a, b in ops], lo, hi))
                   for ops in self.device_ops) / len(self.device_ops)

    def busy_within(self, name: str) -> Tuple[float, int]:
        """Device-busy nanoseconds inside the annotations called `name`,
        and how many of them lie in the window."""
        lo, hi = self.window()
        spans = [(a, b) for a, b in self.spans(name) if a >= lo and b <= hi]
        return sum(self.busy_ns(a, b) for a, b in spans), len(spans)

    def _own_times(self, lo: float, hi: float) -> List[Tuple[str, float]]:
        """(name, own ns) of every operation inside [lo, hi], all planes."""
        return [pair for ops in self.device_ops for pair in self_times(
            [(n, a, b) for n, a, b in ops if a >= lo and b <= hi])]

    def top_ops(self, lo: float, hi: float, k: int = 10
                ) -> List[Tuple[str, float]]:
        """The k operations with most own time, in seconds, summed by name
        and averaged over the device planes."""
        if not self.device_ops:
            return []
        by_name: Dict[str, float] = {}
        for n, s in self._own_times(lo, hi):
            by_name[n] = by_name.get(n, 0.0) + s
        ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:k]
        return [(short_op_name(n), s / len(self.device_ops) / 1e9)
                for n, s in ranked]

    # --------------------------------------------------------------- idle
    def idle_gaps(self, lo: float, hi: float,
                  extra: Optional[Sequence[Labelled]] = None, k: int = 10
                  ) -> List[Tuple[str, float]]:
        """Idle seconds of the first device plane in [lo, hi], by what the
        host was doing: each gap is cut where a labelled interval (one of
        the benchmark's annotations, or of `extra`, the program's spans on
        the same clock) begins or ends, and each piece goes to the interval
        that began last among those covering it, else to `unlabelled`."""
        if not self.device_ops:
            return []
        busy = union([(a, b) for _, a, b in self.device_ops[0]], lo, hi)
        labelled = sorted(
            [(a, b, n[len(ANNOTATION_PREFIX):])
             for n, a, b in self.annotations if n != WINDOW]
            + [(a, b, n) for n, a, b in (extra or [])])
        starts = [a for a, _, _ in labelled]
        cuts = sorted({t for a, b, _ in labelled for t in (a, b)})

        def label_at(t: float) -> str:
            i = bisect.bisect_right(starts, t) - 1
            for j in range(i, max(i - LOOK_BACK, -1), -1):
                if labelled[j][1] >= t:
                    return labelled[j][2]
            return "unlabelled"

        by_label: Dict[str, float] = {}
        edge = lo
        for a, b in busy + [(hi, hi)]:
            if a - edge >= SHORT_GAP_NS:
                inner = cuts[bisect.bisect_right(cuts, edge):
                             bisect.bisect_left(cuts, a)]
                points = [edge] + inner + [a]
                for p, q in zip(points, points[1:]):
                    label = label_at((p + q) / 2)
                    by_label[label] = by_label.get(label, 0.0) + (q - p)
            elif a > edge:
                by_label["gaps_under_50_us"] = \
                    by_label.get("gaps_under_50_us", 0.0) + (a - edge)
            edge = max(edge, b)
        ranked = sorted(by_label.items(), key=lambda kv: -kv[1])[:k]
        return [(n, s / 1e9) for n, s in ranked]
