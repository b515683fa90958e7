"""Device seconds a timed fit under the program's scope `tree.split` (own time
of the operations inside `bench.fit`): the split search: the feature-subset
draw, the gain scan over the bins and the arg-max."""

from benchmark.layer_metrics import _fit_scopes


def read(run):
    return _fit_scopes.seconds_per_fit(run, "tree.split")
