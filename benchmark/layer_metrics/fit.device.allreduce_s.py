"""Device seconds a timed fit under the program's scope `tree.hist.allreduce`
(own time of the operations inside `bench.fit`, averaged over the device
planes): the all-reduce that merges the chips' partial histograms at every
level and their leaf statistics at the end of a tree, skew between the chips
included (a chip that arrives early waits here).

It is INSIDE `fit.device.hist_s`, not beside it: the scope is nested in
`tree.hist`, and `_fit_scopes` files an operation under the FIRST `tree.*`
component of its name stack, so there these operations count as `tree.hist`.
This reader looks for the nested component itself, in the operation's HLO
text or in the statistics kept with it (`tf_op`, as on the v5e). None where
no operation of a timed fit carries it: a one-chip program has no all-reduce,
and a program without the scope gives nothing to read.
"""

import bisect
import os

from benchmark.harness import runner, xplane
from benchmark.layer_metrics import _fit_scopes

SCOPE = "tree.hist.allreduce"


def _carrying(run) -> set:
    """Names of the operations whose kept statistics place them under the
    scope, from the trace file this run wrote, if it is still there."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    try:
        path = xplane.newest_trace_file(
            os.path.join(root, runner.WORK_DIR, run.cell, "trace"))
    except FileNotFoundError:
        return set()
    return {op for op, stats in _fit_scopes.operation_metadata(path).items()
            if any(isinstance(v, str) and SCOPE in v for v in stats.values())}


def read(run):
    trace, fits = run.trace, run.facts.get("fits")
    if trace is None or not trace.device_ops or not fits:
        return None
    lo, hi = trace.window()
    spans = [(a, b) for a, b in trace.spans(_fit_scopes.FIT)
             if a >= lo and b <= hi]
    starts = [a for a, _ in spans]
    carrying = _carrying(run)
    found = []
    for ops in trace.device_ops:
        inside = []
        for op in ops:
            i = bisect.bisect_right(starts, op[1]) - 1
            if i >= 0 and op[2] <= spans[i][1]:
                inside.append(op)
        found += [own for name, own in xplane.self_times(inside)
                  if SCOPE in name or name in carrying]
    if not found:
        return None
    return sum(found) / len(trace.device_ops) / fits / 1e9
