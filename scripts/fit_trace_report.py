#!/usr/bin/env python3
"""One traced run of a benchmark fit cell, read three more ways than the
result line reads it. Needs the chip, as a run does; not part of a run.

    python3 scripts/fit_trace_report.py --workload ml11_xgb.fit --seed 7 \
        --seconds 51 [--out chiprun_out/trace_report]

Runs the cell through `benchmark.harness.runner.run` with `--trace 1`
(nothing of the harness is changed: the run's own result line is printed
first), then, from the same process's recorder ring and the run's
`.xplane.pb`, writes `<out>/<cell>.json` and prints a summary of:

1. the clock check: the benchmark places the program's recorder spans on
   the trace's clock through ONE anchor (the start of `bench.window`);
   `PROFILER.span` also enters a `jax.profiler.TraceAnnotation`, so the same
   spans exist on the profiler's own clock. For every `fit.device_wait` of
   the window: anchor-placed start/end minus profiler-recorded start/end, and
   the end of the last device operation of the fit minus the end of its
   `fit.device_wait` (both on the profiler's clock);
2. the phases of each fit of the window, from the program's own record of
   it (`obs.fit_records()`: the spans that share its trace id, summed by
   name), children beside their parents (`fit.quantize.*`, the
   `fit.featurize.*` children, the staging steps `stage.key` / `.pad` / `.put`)
   and what a span notes in numbers as `<name> [<note>]` (the process's
   `cpu_s`, the slowest column job's `longest_s`, a staging
   step's `bytes`, `copied`, `hit`, and `stage.pad [warm share]`: the share
   of a fit's pad steps written into the pad pool's warm pages), with the
   collector's pauses (and a slow fit's memory at its close): fits 1-3
   against the rest (which phase is still warming);
3. the one-clock check extended to the transfer: for every `fit.stage` of
   the window, the end of the fit's last host-to-device event on the trace's
   clock minus the end of its last `stage.put` span (positive: `device_put`
   returned before the transfer was done), the same for the transfers that
   started before the fit's first `fit.dispatch` (a tree program's dispatch
   makes transfers of its own, so there this is the staging's; where the
   runtime is still issuing the staged transfers when the dispatch begins,
   as for cell 4's 0.6 GB, it reads negative and the first number is the
   one to read), and which events of the trace were taken for transfers;
4. what the trace says about each device operation: the plane's lines, and
   a sample of operations with the statistics kept with them (where the
   `jax.named_scope` of an operation is to be found).
"""

from __future__ import annotations

import argparse
import bisect
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from sml_tpu.utils.profiler import now, wallclock  # noqa: E402

T_START = now()

WAIT = "fit.device_wait"
#: the programs' `jax.named_scope` families (docs/OBSERVABILITY.md): the
#: sample of operations shown with their statistics prefers those that name
#: one
SCOPE_FAMILIES = ("tree.", "linear.", "cv.", "als.", "kmeans.")
#: what a host-to-device transfer may be called in a trace, letters only
H2D_MARKS = ("h2d", "hosttodevice", "transfertodevice", "copytodevice",
             "bufferfromhostbuffer", "transferliteraltodevice", "infeed")


def _quartiles(values):
    values = sorted(values)
    if not values:
        return None
    return {"n": len(values), "median": statistics.median(values),
            "worst": max(values, key=abs), "min": values[0],
            "max": values[-1]}


def clock_check(trace, profile, placed, window):
    """Distances in microseconds, and the drift of the anchor-placed spans
    against the profiler's over the window (first and last quarter)."""
    lo, hi = window
    annotated = sorted(
        (float(e.start_ns), float(e.start_ns + e.duration_ns))
        for plane in profile.planes if not plane.name.startswith("/device:")
        for line in plane.lines for e in line.events if e.name == WAIT)
    annotated = [(a, b) for a, b in annotated if a >= lo and b <= hi]
    anchored = sorted((a, b) for n, a, b in placed
                      if n == WAIT and a >= lo and b <= hi)
    out = {"spans": {"profiler": len(annotated), "anchor": len(anchored)}}
    if not annotated or len(annotated) != len(anchored):
        return out
    start = [(p[0] - q[0]) / 1e3 for p, q in zip(anchored, annotated)]
    end = [(p[1] - q[1]) / 1e3 for p, q in zip(anchored, annotated)]
    out["anchor_minus_profiler_start_us"] = _quartiles(start)
    out["anchor_minus_profiler_end_us"] = _quartiles(end)
    quarter = max(len(start) // 4, 1)
    out["drift_us_first_to_last_quarter"] = (
        statistics.median(start[-quarter:])
        - statistics.median(start[:quarter]))
    out["window_s"] = (hi - lo) / 1e9
    if not trace.device_ops:
        return out
    # the last device operation of each fit against the end of its wait,
    # and where in the wait the device is idle: before its first
    # operation, or between operations
    spans = sorted((a, b) for _, a, b in trace.device_ops[0])
    starts = [a for a, _ in spans]
    ends = sorted(b for _, b in spans)
    gaps_prof, gaps_anchor, lead, inner = [], [], [], []
    for (pa, pb), (aa, ab) in zip(annotated, anchored):
        i = bisect.bisect_right(ends, pb + 5e6) - 1   # ends by wait end + 5 ms
        if i < 0 or ends[i] < pa:
            continue
        gaps_prof.append((pb - ends[i]) / 1e3)
        gaps_anchor.append((ab - ends[i]) / 1e3)
        first = bisect.bisect_left(starts, pa - 5e6)
        if first < len(starts) and starts[first] < pb:
            lead.append((starts[first] - pa) / 1e3)
            busy = trace.busy_ns(starts[first], ends[i])
            inner.append((ends[i] - starts[first] - busy) / 1e3)
    out["wait_end_minus_last_op_end_us.profiler_clock"] = \
        _quartiles(gaps_prof)
    out["wait_end_minus_last_op_end_us.anchor_placed"] = \
        _quartiles(gaps_anchor)
    out["first_op_start_minus_wait_start_us"] = _quartiles(lead)
    out["idle_between_first_and_last_op_us"] = _quartiles(inner)
    return out


def put_against_transfer(profile, placed, window):
    """The end of a fit's last host-to-device event minus the end of its
    last `stage.put` span, in microseconds, a `fit.stage` of the window. A
    fit's transfers are the events named like one (`H2D_MARKS`, any plane
    and line) that start between the start of its `fit.stage` and the end
    of the `fit.device_wait` that follows; the staging's own are those of
    them that start before the fit's first `fit.dispatch`. Where the trace
    names no such event, the lines it has, for the next reader."""
    lo, hi = window
    found, transfers, lines = {}, [], {}
    for plane in profile.planes:
        for line in plane.lines:
            label = f"{plane.name} / {line.name}"
            for e in line.events:
                lines[label] = lines.get(label, 0) + 1
                if not lo <= e.start_ns <= hi:
                    continue
                letters = "".join(c for c in e.name.lower() if c.isalpha())
                if any(mark in letters for mark in H2D_MARKS):
                    key = f"{label} / {e.name[:80]}"
                    found[key] = found.get(key, 0) + 1
                    transfers.append((float(e.start_ns),
                                      float(e.start_ns + e.duration_ns)))
    out = {"events_taken_for_transfers": found}
    if not transfers:
        out["lines"] = lines
        return out
    transfers.sort()
    starts = [a for a, _ in transfers]
    inside = sorted((a, b, n) for n, a, b in placed if lo <= a and b <= hi
                    and n in ("fit.stage", "stage.put", "fit.dispatch", WAIT))
    gaps, staged, last_put, stage_at, dispatch_at = [], [], None, None, None
    for a, b, name in inside:
        if name == "fit.stage":
            stage_at, last_put, dispatch_at = a, None, None
        elif name == "stage.put" and stage_at is not None:
            last_put = b
        elif name == "fit.dispatch" and dispatch_at is None:
            dispatch_at = a
        elif name == WAIT and stage_at is not None and last_put is not None:
            at = bisect.bisect_left(starts, stage_at)
            mine = transfers[at:bisect.bisect_right(starts, b)]
            if mine:
                gaps.append((max(e for _, e in mine) - last_put) / 1e3)
            # the staging's own: a program's dispatch transfers its scalars
            # and a tree program its margin, so those that start before the
            # fit's first dispatch are the ones `stage.put` asked for
            mine = transfers[at:bisect.bisect_left(
                starts, dispatch_at if dispatch_at is not None else b)]
            if mine:
                staged.append((max(e for _, e in mine) - last_put) / 1e3)
            stage_at = None
    out["last_transfer_end_minus_last_put_end_us"] = _quartiles(gaps)
    out["last_transfer_started_before_dispatch"
        "_end_minus_last_put_end_us"] = _quartiles(staged)
    return out


def fits_by_phase(records, since_s: float):
    """One row a fit whose root `fit` span started after `since_s` (the
    recorder's clock): a formatting of `obs.fit_records()`, the program's
    own per-fit form of the span totals (`sml_tpu/obs/_fits.py`). Seconds by
    span name, every child (of `fit.quantize`, `fit.featurize`,
    `fit.stage`) beside its parent under its own name, what a span notes in
    numbers beside it as `<name> [<note>]`, and the record's own facts:
    `(unattributed)` (the root less the seven phases the benchmark reports:
    `fit.host.unattributed_s`; until PR 52 this script took the root less
    its direct children, which counted a program's span whole), `(gc)` the
    collector's pauses inside the fit, and for a slow fit `(rss bytes)` /
    `(available bytes)` at its close."""
    rows = []
    for record in records:
        if record["t0"] < since_s:
            continue
        spans = record["spans"]
        row = {"fit": record["wall_s"]}
        pads = sum(e["n"] for e in spans.values() if "warm" in e)
        for name, entry in spans.items():
            # what a span notes beside its seconds: the process's CPU
            # seconds (`CPU_SPANS`), the slowest job and, for a staging
            # step, its bytes, how often it had to copy the caller's
            # array or found it cached and, of the pads, the share written
            # into warm pages
            for note in ("cpu_s", "longest_s", "bytes", "copied", "hit"):
                if note in entry:
                    row[f"{name} [{note}]"] = entry[note]
            if entry.get("warm"):
                row[f"{name} [warm share]"] = entry["warm"] / pads
            if name != "fit":
                row[name] = entry["wall_s"]
        row["(unattributed)"] = record["phases"]["fit.host.unattributed_s"]
        row["(gc)"] = record["gc_s"]
        for key, label in (("rss_bytes", "(rss bytes)"),
                           ("mem_available_bytes", "(available bytes)")):
            if key in record:
                row[label] = record[key]
        rows.append(row)
    return rows


def warm_against_rest(rows, first: int = 3):
    if len(rows) <= first:
        return {}
    names = sorted({n for r in rows for n in r})
    out = {}
    for name in names:
        head = [r.get(name, 0.0) for r in rows[:first]]
        rest = [r.get(name, 0.0) for r in rows[first:]]
        out[name] = {"first": sum(head) / len(head),
                     "rest": sum(rest) / len(rest)}
    return out


def device_metadata(profile, path, sample: int = 40):
    from benchmark.layer_metrics import _fit_scopes
    lines, event_stats = {}, []
    for plane in profile.planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            events = list(line.events)
            lines[f"{plane.name} / {line.name}"] = len(events)
            if line.name == "XLA Ops" and not event_stats:
                seen = set()
                for e in events:
                    if e.name in seen:
                        continue
                    seen.add(e.name)
                    event_stats.append({
                        "name": e.name[:160],
                        "stats": [[k, str(v)[:200]] for k, v in e.stats]})
                    if len(seen) >= sample:
                        break
    meta = _fit_scopes.operation_metadata(path)
    scoped = _fit_scopes.scopes_of_file(path)
    in_name = sum(1 for op in meta if _fit_scopes.scope_in(op))
    picked = list(meta.items())
    picked = picked[:sample // 2] + [
        kv for kv in picked[sample // 2:]
        if any(family in json.dumps(kv[1]) for family in SCOPE_FAMILIES)
    ][:sample // 2]
    return {"lines": lines, "operations": len(meta),
            "operations_with_a_scope_in_their_statistics": len(scoped),
            "operations_with_a_scope_in_their_name": in_name,
            "event_level_statistics": event_stats,
            "metadata_statistics": [
                {"name": op[:160],
                 "stats": {k: str(v)[:240] for k, v in stats.items()}}
                for op, stats in picked]}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "trace_report"))
    ap.add_argument("--rehearse", metavar="DIR", help="a REHEARSAL on any "
                    "backend: the tests' tiny copy of the benchmark is made "
                    "under DIR and --workload names one of its tiny cells")
    args = ap.parse_args()
    from benchmark.harness import runner, xplane
    from jax.profiler import ProfileData
    root, bench = ROOT, None
    if args.rehearse:
        sys.path.insert(0, os.path.join(ROOT, "tests", "benchmark"))
        import bench_tiny
        root, bench = bench_tiny.make_tiny_root(args.rehearse)
        print("REHEARSAL: tiny cell, any backend; no number below is a "
              "measurement", flush=True)

    seen = {}
    placed_by_anchor = runner.idle_labels

    def watching(ctx, trace, anchor_s):
        labels = placed_by_anchor(ctx, trace, anchor_s)
        seen.update(trace=trace, labels=labels, anchor_s=anchor_s)
        return labels

    runner.idle_labels = watching       # this process only: a reading tap
    try:
        line = runner.run(root, args.workload, args.seed, args.seconds, True,
                          T_START, require_chip=not args.rehearse,
                          bench=bench)
    finally:
        runner.idle_labels = placed_by_anchor
    print(json.dumps(line), flush=True)

    from sml_tpu import obs
    trace = seen["trace"]
    path = xplane.newest_trace_file(os.path.join(
        root, runner.WORK_DIR, args.workload, "trace"))
    profile = ProfileData.from_file(path)
    window = trace.window()
    recorder = obs.RECORDER
    # the recorder's clock starts at its epoch; the anchor is on perf_counter
    offset = recorder.epoch_unix() - (wallclock() - now())
    rows = fits_by_phase(obs.fit_records(), seen["anchor_s"] - offset)
    report = {
        "cell": args.workload, "seed": args.seed,
        "trace_file_bytes": os.path.getsize(path),
        "ring": {"events": len(recorder.events()),
                 "dropped": recorder.dropped},
        "clock": clock_check(trace, profile, seen["labels"], window),
        "put_against_transfer": put_against_transfer(
            profile, seen["labels"], window),
        "fits": rows,
        "first_three_against_rest": warm_against_rest(rows),
        "device": device_metadata(profile, path),
        "result": line,
    }
    os.makedirs(args.out, exist_ok=True)
    out = os.path.join(args.out, args.workload + ".json")
    with open(out, "w") as f:
        json.dump(report, f, indent=1)
    summary = {k: report[k] for k in ("cell", "clock", "put_against_transfer",
                                      "first_three_against_rest")}
    summary["device"] = {k: v for k, v in report["device"].items()
                         if not k.endswith("statistics")}
    print("TRACE REPORT " + json.dumps(summary), flush=True)
    print(f"wrote {out}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
