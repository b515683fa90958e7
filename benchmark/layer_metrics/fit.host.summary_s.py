"""Seconds a timed fit spends in the program's span `fit.summary`: the
training summary's host pass over the fitted rows (the margin and the
accuracy). No phase of `_fit_spans.PHASES` holds it, so it is a PART of
`fit.host.unattributed_s`, not a ninth beside the eight."""

from benchmark.layer_metrics import _fit_spans


def read(run):
    if "span_n.fit.summary" not in run.counters_end:
        return None
    return _fit_spans.seconds_per_fit(run, ("fit.summary",))
