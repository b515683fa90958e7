"""Seconds of set-up before the program: the process's age when `sml_tpu`
started to import (the gauge `process.age_at_import_s`): the interpreter,
the harness's own imports, jax and the TPU runtime's start."""

from benchmark.layer_metrics import _setup_spans


def read(run):
    return _setup_spans.at_window_start(run, "process.age_at_import_s")
