"""Seconds a timed fit spends in the program's spans `stage.pad`, inside
`fit.stage`: the host copy of an array of at least 1 MiB padded to its
bucketed row count (and the fill of the row mask). A PART of
`fit.host.stage_s`."""

from benchmark.layer_metrics import _fit_spans


def read(run):
    if "span_n.stage.pad" not in run.counters_end:
        return None
    return _fit_spans.seconds_per_fit(run, ("stage.pad",))
