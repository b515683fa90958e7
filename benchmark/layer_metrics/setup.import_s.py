"""Seconds of set-up the program's own import took (the gauge
`process.import_s`: wall seconds of `import sml_tpu`, pandas and pyarrow
with it; jax was imported before)."""

from benchmark.layer_metrics import _setup_spans


def read(run):
    return _setup_spans.at_window_start(run, "process.import_s")
