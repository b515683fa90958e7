"""Seconds a timed fit spends in the program's span
`fit.featurize.als.index`, inside `fit.featurize`: the raw user and movie
ids made dense (a presence table and its running count, by chunks of rows
on the pool). A PART of `fit.host.featurize_s`."""

from benchmark.layer_metrics import _fit_spans


def read(run):
    if "span_n.fit.featurize.als.index" not in run.counters_end:
        return None
    return _fit_spans.seconds_per_fit(run, ("fit.featurize.als.index",))
