#!/usr/bin/env python3
"""Which all-reduce operations of a traced benchmark run carry the scope
`tree.hist.allreduce`, from the `.xplane.pb` the run left behind. Run it
right after `benchmark/run.py --workload <cell> --trace 1`, in the same
checkout (the next run of the cell deletes the trace):

    python3 scripts/allreduce_scope_report.py --workload ml11_xgb_4chip.fit_sharded

Prints one JSON object: the device planes, and for every operation whose
HLO text names a collective its executions in the window, its seconds, and
whether its kept statistics (`tf_op`) or its own text place it under the
scope: the share of all-reduce time `fit.device.allreduce_s` can see.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

SCOPE = "tree.hist.allreduce"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
#: the opcode of an instruction's HLO text: what follows its result shape
_OPCODE = re.compile(r"[\}\)\]] ([a-z][a-z0-9\-_.]*)\(")


def is_collective(name: str) -> bool:
    found = _OPCODE.search(name.partition(" = ")[2])
    return bool(found) and found.group(1).startswith(COLLECTIVES)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args()
    from benchmark.harness import runner, xplane
    from benchmark.layer_metrics import _fit_scopes
    path = xplane.newest_trace_file(
        os.path.join(ROOT, runner.WORK_DIR, args.workload, "trace"))
    trace = xplane.Trace.from_file(path)
    meta = _fit_scopes.operation_metadata(path)
    lo, hi = trace.window()
    rows = {}
    for plane, ops in enumerate(trace.device_ops):
        for name, a, b in ops:
            if a < lo or b > hi or not is_collective(name):
                continue
            stats = meta.get(name, {})
            scoped = SCOPE in name or any(
                isinstance(v, str) and SCOPE in v for v in stats.values())
            row = rows.setdefault(xplane.short_op_name(name), {
                "scoped": scoped, "tf_op": stats.get("tf_op", ""),
                "executions": 0, "seconds": 0.0, "planes": set()})
            row["executions"] += 1
            row["seconds"] += (b - a) / 1e9
            row["planes"].add(plane)
    for row in rows.values():
        row["planes"] = len(row["planes"])
    total = sum(r["seconds"] for r in rows.values())
    scoped = sum(r["seconds"] for r in rows.values() if r["scoped"])
    print(json.dumps({
        "trace": os.path.relpath(path, ROOT),
        "device_planes": len(trace.device_ops),
        "collective_ops": rows,
        "executions": sum(r["executions"] for r in rows.values()),
        "executions_scoped": sum(r["executions"] for r in rows.values()
                                 if r["scoped"]),
        "seconds": total, "seconds_scoped": scoped,
        "scoped_share_of_seconds": scoped / total if total else None}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
