"""Seconds a timed fit spends in the program's spans `stage.put`, inside
`fit.stage`: `jax.device_put` of an array of at least 1 MiB until the call
returns (the transfer may end later: its wait lands where the data is first
needed). A PART of `fit.host.stage_s`."""

from benchmark.layer_metrics import _fit_spans


def read(run):
    if "span_n.stage.put" not in run.counters_end:
        return None
    return _fit_spans.seconds_per_fit(run, ("stage.put",))
