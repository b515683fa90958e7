"""Seconds a timed fit spends in the program's span `fit.cv.folds`: the
validator's fold id a row (`randomSplit`'s own membership, the pre-split
sort of every partition on the host pool). No phase of `_fit_spans.PHASES`
holds it: a PART of `fit.host.unattributed_s`."""

from benchmark.layer_metrics import _fit_spans


def read(run):
    if "span_n.fit.cv.folds" not in run.counters_end:
        return None
    return _fit_spans.seconds_per_fit(run, ("fit.cv.folds",))
