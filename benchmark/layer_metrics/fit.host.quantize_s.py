"""Seconds a timed fit spends in the program's span `fit.quantize`: the content
keys of block and labels and, on a table not seen, bin edges and digitize."""

from benchmark.layer_metrics import _fit_spans


def read(run):
    return _fit_spans.phase(run, "fit.host.quantize_s")
