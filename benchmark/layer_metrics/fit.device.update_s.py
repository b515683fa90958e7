"""Device seconds a timed fit under the program's scope `tree.update` (own time
of the operations inside `bench.fit`): gradients, hessians, bagging weights,
leaf values and the margin update."""

from benchmark.layer_metrics import _fit_scopes


def read(run):
    return _fit_scopes.seconds_per_fit(run, "tree.update")
