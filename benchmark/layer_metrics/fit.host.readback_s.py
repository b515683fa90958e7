"""Seconds a timed fit spends in the program's spans `fit.readback` (the one
batched `device_get` of the trees) and `fit.unpack` (packs to trees)."""

from benchmark.layer_metrics import _fit_spans


def read(run):
    return _fit_spans.phase(run, "fit.host.readback_s")
