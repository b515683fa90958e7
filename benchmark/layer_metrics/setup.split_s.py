"""Seconds of set-up inside the program's spans `materialize.randomSplit`:
the untimed splits set-up makes before the window (each timed fit makes its
own, outside `fit_s`)."""

from benchmark.layer_metrics import _setup_spans


def read(run):
    return _setup_spans.at_window_start(run, "span_s.materialize.randomSplit")
