"""Seconds of set-up inside the program's span
`materialize.createDataFrame`: the generated rows made the cached table (the
generator's own seconds are the harness's, and no span's)."""

from benchmark.layer_metrics import _setup_spans


def read(run):
    return _setup_spans.at_window_start(
        run, "span_s.materialize.createDataFrame")
