"""Seconds a timed fit spends in the program's span
`fit.featurize.plan.jobs`, inside `fit.featurize`: the column plan's jobs, a
job a raw column on the pool, submit to last result (the span notes the
slowest job). A PART of `fit.host.featurize_s`."""

from benchmark.layer_metrics import _fit_spans


def read(run):
    if "span_n.fit.featurize.plan.jobs" not in run.counters_end:
        return None
    return _fit_spans.seconds_per_fit(run, ("fit.featurize.plan.jobs",))
