"""Seconds of set-up inside the program's spans `fit.dispatch`: a warm
fit's call of its program until it returns, which is the trace, the look-up
in the persistent cache and the load of the executable. A PART of
`setup.warm_fit_s`, not a sixth beside the five."""

from benchmark.layer_metrics import _setup_spans


def read(run):
    return _setup_spans.at_window_start(run, "span_s.fit.dispatch")
