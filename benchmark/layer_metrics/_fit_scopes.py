"""Shared by the `fit.device.*` readers: own time of the device operations
inside the `bench.fit` annotations, by the `jax.named_scope` of the program
that each operation came from (`tree.operand`, `tree.hist`, ...), a fit.

An operation's scope is read from its `op_name` (the name stack jax gives
every operation it traces,
`jit(tree_ensemble)/while/body/closed_call/tree.hist/dot_general`). A trace
names an operation by its HLO text. Where that text carries no metadata, as
on the v5e, the `op_name` is a statistic (`tf_op`) the profiler keeps with
the operation's metadata in the `.xplane.pb` file of the run, and is looked
up there. A fusion is counted under the scope its own metadata names,
whatever else was fused into it; an operation with no `tree.*` scope counts
under none, and `fit.device.unscoped_s` is what they add up to. A program
without scopes gives nothing to read."""

import bisect
import os
import re
from typing import Dict, Optional, Tuple

from benchmark.harness import runner, xplane

FIT = "bench.fit"
#: the first `tree.<scope>` component of a name stack
_SCOPE = re.compile(r"(?:^|[/\"( ])(tree\.[a-z_]+(?:\.[a-z_]+)*)")
#: where a run keeps its reduction: six readers ask for the same one
_MEMO = "_fit_device_seconds_by_scope"


def scope_in(text: str) -> Optional[str]:
    found = _SCOPE.search(text)
    return found.group(1) if found else None


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        if byte < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint,
    a memoryview for a length-delimited or fixed field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield key >> 3, value


def operation_metadata(path: str) -> Dict[str, Dict[str, object]]:
    """Operation name -> its statistics, from the `event_metadata` of the
    device planes of one `.xplane.pb`. `jax.profiler.ProfileData` gives an
    event's own statistics and not those of its metadata, where the
    profiler keeps what is the same at every execution of an operation, so
    the few fields needed are read from the file's wire format: XSpace.planes
    = 1; XPlane.name = 2, .event_metadata = 4, .stat_metadata = 5 (maps:
    key = 1, value = 2); XEventMetadata.name = 2, .display_name = 4,
    .stats = 5; XStatMetadata.name = 2; XStat.metadata_id = 1, .double = 2,
    .uint64 = 3, .int64 = 4, .str = 5, .bytes = 6, .ref = 7."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, Dict[str, object]] = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for pf, value in _fields(plane):
            if pf == 2:
                name = bytes(value).decode()
            elif pf == 4:
                events.append(dict(_fields(value)).get(2))
            elif pf == 5:
                entry = dict(_fields(value))
                stat_names[entry.get(1, 0)] = bytes(
                    dict(_fields(entry.get(2, b""))).get(2, b"")).decode()
        if not name.startswith(xplane.DEVICE_PREFIX):
            continue
        for meta in events:
            if meta is None:
                continue
            op, stats = "", {}
            for mf, value in _fields(meta):
                if mf == 2:
                    op = bytes(value).decode()
                elif mf == 4:
                    stats["display_name"] = bytes(value).decode()
                elif mf == 5:
                    stat = dict(_fields(value))
                    key = stat_names.get(stat.get(1, 0), "")
                    if 5 in stat:
                        stats[key] = bytes(stat[5]).decode(errors="replace")
                    elif 7 in stat:
                        stats[key] = stat_names.get(stat[7], "")
                    elif 3 in stat or 4 in stat:
                        stats[key] = stat.get(3, stat.get(4))
            out.setdefault(op, stats)
    return out


def scopes_of_file(path: str) -> Dict[str, str]:
    """Operation name -> scope, for the operations of one `.xplane.pb`
    whose statistics name one."""
    table: Dict[str, str] = {}
    for op, stats in operation_metadata(path).items():
        for value in stats.values():
            scope = scope_in(value) if isinstance(value, str) else None
            if scope:
                table[op] = scope
                break
    return table


def _run_table(run) -> Dict[str, str]:
    """The table of the trace file this run wrote, if it is still there."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    try:
        path = xplane.newest_trace_file(
            os.path.join(root, runner.WORK_DIR, run.cell, "trace"))
    except FileNotFoundError:
        return {}
    return scopes_of_file(path)


def by_scope(run) -> Optional[Dict[str, float]]:
    """Scope -> own seconds a fit of the operations inside the `bench.fit`
    annotations of the window ("" for those with no scope), averaged over
    the device planes; None where there is nothing to read."""
    trace, fits = run.trace, run.facts.get("fits")
    if trace is None or not trace.device_ops or not fits:
        return None
    if _MEMO in vars(run):
        return vars(run)[_MEMO]
    lo, hi = trace.window()
    spans = [(a, b) for a, b in trace.spans(FIT) if a >= lo and b <= hi]
    starts = [a for a, _ in spans]
    table = _run_table(run)
    totals: Dict[str, float] = {}
    for ops in trace.device_ops:
        inside = []
        for op in ops:
            i = bisect.bisect_right(starts, op[1]) - 1
            if i >= 0 and op[2] <= spans[i][1]:
                inside.append(op)
        for name, own in xplane.self_times(inside):
            scope = scope_in(name) or table.get(name, "")
            totals[scope] = totals.get(scope, 0.0) + own
    found = None
    if any(ns for scope, ns in totals.items() if scope):
        planes = len(trace.device_ops)
        found = {s: ns / planes / fits / 1e9 for s, ns in totals.items()}
    vars(run)[_MEMO] = found
    return found


def seconds_per_fit(run, scope: str) -> Optional[float]:
    """Own seconds a fit under `scope` and the scopes nested in it."""
    found = by_scope(run)
    if found is None:
        return None
    return sum(s for name, s in found.items()
               if name == scope or name.startswith(scope + "."))


def unscoped(run) -> Optional[float]:
    """The device's busy seconds a fit less every `tree.*` scope's."""
    found = by_scope(run)
    if found is None:
        return None
    busy_ns, fits = run.trace.busy_within(FIT)
    return busy_ns / fits / 1e9 - sum(s for name, s in found.items() if name)
