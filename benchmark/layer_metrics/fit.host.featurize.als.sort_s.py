"""Seconds a timed fit spends in the program's span
`fit.featurize.als.sort`, inside `fit.featurize`: the two stable orders of
the dense ids, the four row arrays gathered in them and every entity's
bounds. A PART of `fit.host.featurize_s`."""

from benchmark.layer_metrics import _fit_spans


def read(run):
    if "span_n.fit.featurize.als.sort" not in run.counters_end:
        return None
    return _fit_spans.seconds_per_fit(run, ("fit.featurize.als.sort",))
