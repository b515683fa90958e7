"""Seconds a timed fit spends in the program's span `fit.stage`: cache keys,
padding and the `device_put` of bins, labels and mask (the copy itself is
asynchronous: its wait lands where the data is first needed)."""

from benchmark.layer_metrics import _fit_spans


def read(run):
    return _fit_spans.phase(run, "fit.host.stage_s")
