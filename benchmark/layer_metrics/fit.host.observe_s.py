"""Seconds a timed fit spends in the program's span `fit.baseline`: the drift
baseline the recorder captures at the end of every fit while it is on (a
sketch of strided rows and a NumPy descent of them through every tree)."""

from benchmark.layer_metrics import _fit_spans


def read(run):
    return _fit_spans.phase(run, "fit.host.observe_s")
