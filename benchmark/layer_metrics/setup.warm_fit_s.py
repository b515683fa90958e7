"""Seconds of set-up inside the program's root span `fit`: the warm fits
(and a probe fit where the kind makes one), each with its first dispatch."""

from benchmark.layer_metrics import _setup_spans


def read(run):
    return _setup_spans.at_window_start(run, "span_s.fit")
