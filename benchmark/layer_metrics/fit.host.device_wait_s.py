"""Seconds a timed fit spends in the program's span `fit.device_wait`: the host
in `block_until_ready` on the tree program's result. Beside
`fit.device_busy_s`: what of the device's time the host only waited for."""

from benchmark.layer_metrics import _fit_spans


def read(run):
    return _fit_spans.phase(run, "fit.host.device_wait_s")
