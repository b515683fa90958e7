"""Seconds a timed fit spends collecting the frame and making the feature
block: the program's spans `fit.collect` (the frame to one pandas table),
`fit.prep` (Imputer medians, StringIndexer frequencies) and `fit.featurize`
(the assembled block, the finite-label filter, the `missing` copy)."""

from benchmark.layer_metrics import _fit_spans


def read(run):
    return _fit_spans.phase(run, "fit.host.featurize_s")
