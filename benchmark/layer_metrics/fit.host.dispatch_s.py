"""Seconds a timed fit spends in the program's span `fit.dispatch`: the call
into the compiled tree program, until it returns."""

from benchmark.layer_metrics import _fit_spans


def read(run):
    return _fit_spans.phase(run, "fit.host.dispatch_s")
