"""Device seconds a timed fit under the program's scope `tree.route` (own time
of the operations inside `bench.fit`): routing every row to its child node."""

from benchmark.layer_metrics import _fit_scopes


def read(run):
    return _fit_scopes.seconds_per_fit(run, "tree.route")
