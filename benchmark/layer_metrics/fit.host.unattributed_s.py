"""Seconds a timed fit spends inside the program's root span `fit` and under
none of the phases the other `fit.host.*` metrics read: what no span covers
yet. The eight sum to the root span."""

from benchmark.layer_metrics import _fit_spans


def read(run):
    return _fit_spans.unattributed(run)
