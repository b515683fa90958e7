"""Seconds a timed tree fit spends copying the feature block after the plan
made it: the program's spans `fit.featurize.extract` (the rows with a finite
label, `X[ok]`) and `fit.featurize.missing` (the copy whose `missing` values
become NaN; boosted fits alone). A PART of `fit.host.featurize_s`."""

from benchmark.layer_metrics import _fit_spans


def read(run):
    if "span_n.fit.featurize.extract" not in run.counters_end:
        return None
    return _fit_spans.seconds_per_fit(
        run, ("fit.featurize.extract", "fit.featurize.missing"))
