"""Shared by the linear fit's `fit.device.*` readers: own time of the device
operations inside the `bench.fit` annotations whose `op_name` carries a
given `jax.named_scope` of `sml_tpu/ml/linear_impl.py`, a fit.

The scopes NEST (`linear.irls` is the whole scan of Newton steps, and
`linear.irls.hess` a part of every step:
`jit(...)/linear.irls/while/body/closed_call/linear.irls.hess/dot_general`),
and `_fit_scopes` files an operation under the first `tree.*` component
alone, so this helper looks for a component anywhere in the name stack: in
the operation's HLO text or, as on the v5e, in the statistics the profiler
keeps with the operation's metadata (`_fit_scopes.operation_metadata`). A
program without the scopes, as every commit before they were added, gives
nothing to read."""

import bisect
import os
import re
from typing import Dict, Optional

from benchmark.harness import runner, xplane
from benchmark.layer_metrics import _fit_scopes

#: where a run keeps its reduction: four readers ask for the same one
_MEMO = "_linear_device_ns_by_name_stack"
_COMPONENT = re.compile(r"linear\.[a-z_]+(?:\.[a-z_]+)*")


def _stacks_of_file(run) -> Dict[str, str]:
    """Operation name -> the `linear.*` components its kept statistics
    name, from the trace file this run wrote, if it is still there."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    try:
        path = xplane.newest_trace_file(
            os.path.join(root, runner.WORK_DIR, run.cell, "trace"))
    except FileNotFoundError:
        return {}
    table = {}
    for op, stats in _fit_scopes.operation_metadata(path).items():
        found = [c for v in stats.values() if isinstance(v, str)
                 for c in _COMPONENT.findall(v)]
        if found:
            table[op] = " ".join(found)
    return table


def _by_stack(run) -> Optional[Dict[str, float]]:
    """The `linear.*` components of a name stack (joined by a space) ->
    own nanoseconds of the operations that carry them inside the window's
    `bench.fit` annotations, over the device planes."""
    trace, fits = run.trace, run.facts.get("fits")
    if trace is None or not trace.device_ops or not fits:
        return None
    if _MEMO in vars(run):
        return vars(run)[_MEMO]
    lo, hi = trace.window()
    spans = [(a, b) for a, b in trace.spans(_fit_scopes.FIT)
             if a >= lo and b <= hi]
    starts = [a for a, _ in spans]
    table = _stacks_of_file(run)
    totals: Dict[str, float] = {}
    for ops in trace.device_ops:
        inside = []
        for op in ops:
            i = bisect.bisect_right(starts, op[1]) - 1
            if i >= 0 and op[2] <= spans[i][1]:
                inside.append(op)
        for name, own in xplane.self_times(inside):
            stack = " ".join(_COMPONENT.findall(name)) or table.get(name, "")
            if stack:
                totals[stack] = totals.get(stack, 0.0) + own
    vars(run)[_MEMO] = totals or None
    return vars(run)[_MEMO]


def seconds_per_fit(run, scope: str) -> Optional[float]:
    """Own seconds a fit of the operations under `scope` or a scope nested
    in it, averaged over the device planes; None where no operation of a
    timed fit carries any `linear.*` scope."""
    found = _by_stack(run)
    if found is None:
        return None
    inside = sum(ns for stack, ns in found.items()
                 if any(c == scope or c.startswith(scope + ".")
                        for c in stack.split()))
    return inside / len(run.trace.device_ops) / run.facts["fits"] / 1e9
