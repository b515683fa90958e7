"""Seconds a timed fit spends in the program's spans `fit.cv.eval`: what of
the validator's evaluations is the host's. A PART of
`fit.host.unattributed_s`."""

from benchmark.layer_metrics import _fit_spans


def read(run):
    if "span_n.fit.cv.eval" not in run.counters_end:
        return None
    return _fit_spans.seconds_per_fit(run, ("fit.cv.eval",))
