"""Shared by the factorization's `fit.device.als.*` readers:
`_linear_scopes.py`'s look-up of a `jax.named_scope` ANYWHERE in a device
operation's name stack, for the scopes of `sml_tpu/ml/recommendation.py`
(`als.gather`, `als.normal` with `als.normal.allreduce` inside it,
`als.solve`: all of them inside the loop over alternations and, the first
two, inside the loop over blocks). That file's pattern knows the
`linear.*` scopes alone and is not this PR's to edit, so a copy of the
module is loaded here under another name with the pattern `als.*`, and a
memo of its own on the run. A program without the scopes, as every commit
before them, gives nothing to read."""

import os
import re

from benchmark.harness import runner

_scopes = runner.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "_linear_scopes.py"), "bench_layer_als_scopes")
_scopes._COMPONENT = re.compile(r"als\.[a-z_]+(?:\.[a-z_]+)*")
_scopes._MEMO = "_als_device_ns_by_name_stack"


def seconds_per_fit(run, scope: str):
    """Own seconds a timed `ALS.fit` of the operations under `scope` or a
    scope nested in it; None where no operation carries it."""
    found = _scopes._by_stack(run)
    if found is None or not any(
            c == scope or c.startswith(scope + ".")
            for stack in found for c in stack.split()):
        return None
    return _scopes.seconds_per_fit(run, scope)
