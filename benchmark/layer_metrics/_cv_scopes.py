"""Shared by the validator's `fit.device.cv.*` readers: `_linear_scopes.py`'s
look-up of a `jax.named_scope` ANYWHERE in a device operation's name stack,
for the scope `cv.eval` of `sml_tpu/ml/linear_impl.py` (a held fold's
margins, and whatever of their ranking runs on the device). That file's
pattern knows the `linear.*` scopes alone and is not this PR's to edit, so
a copy of the module is loaded here under another name with the pattern
widened to `cv.*`, and a memo of its own on the run. A program without the
scope, as every commit before it, gives nothing to read."""

import os
import re

from benchmark.harness import runner

_scopes = runner.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "_linear_scopes.py"), "bench_layer_cv_scopes")
_scopes._COMPONENT = re.compile(r"(?:linear|cv)\.[a-z_]+(?:\.[a-z_]+)*")
_scopes._MEMO = "_cv_device_ns_by_name_stack"


def seconds_per_fit(run, scope: str):
    """Own seconds a timed `Pipeline.fit` of the operations under `scope`;
    None where no operation carries it."""
    found = _scopes._by_stack(run)
    if found is None or not any(
            c == scope or c.startswith(scope + ".")
            for stack in found for c in stack.split()):
        return None
    return _scopes.seconds_per_fit(run, scope)
