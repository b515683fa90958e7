"""Seconds a timed fit spends in the program's span
`fit.featurize.plan.block`, inside `fit.featurize`: the jobs' scratch
interleaved by row blocks into the estimator's block, or handed over as the
compact form. A PART of `fit.host.featurize_s`."""

from benchmark.layer_metrics import _fit_spans


def read(run):
    if "span_n.fit.featurize.plan.block" not in run.counters_end:
        return None
    return _fit_spans.seconds_per_fit(run, ("fit.featurize.plan.block",))
