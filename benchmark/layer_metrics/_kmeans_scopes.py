"""Shared by the clustering's `fit.device.kmeans.*` readers:
`_linear_scopes.py`'s look-up of a `jax.named_scope` ANYWHERE in a device
operation's name stack, for the scopes of `sml_tpu/ml/clustering.py`
(`kmeans.init`: the seeding's passes, the candidates' weights and the
weighted k-means++; `kmeans.assign` and `kmeans.update`, inside the loop
over Lloyd steps and the loop over blocks; `kmeans.cost`, the pass at the
returned centers). The scopes do not nest: an operation carries one. That
file's pattern knows the `linear.*` scopes alone and is not this PR's to
edit, so a copy of the module is loaded here under another name with the
pattern `kmeans.*`, and a memo of its own on the run. A program without
the scopes, as every commit before them, gives nothing to read."""

import os
import re

from benchmark.harness import runner

_scopes = runner.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "_linear_scopes.py"), "bench_layer_kmeans_scopes")
_scopes._COMPONENT = re.compile(r"kmeans\.[a-z_]+(?:\.[a-z_]+)*")
_scopes._MEMO = "_kmeans_device_ns_by_name_stack"


def seconds_per_fit(run, *scopes: str):
    """Own seconds a timed fit of the operations under one of `scopes`;
    None where no operation carries any of them."""
    found = _scopes._by_stack(run)
    if found is None:
        return None
    got = [_scopes.seconds_per_fit(run, scope) for scope in scopes
           if any(c == scope or c.startswith(scope + ".")
                  for stack in found for c in stack.split())]
    return sum(got) if got else None
