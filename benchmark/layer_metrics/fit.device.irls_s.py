"""Device seconds a timed fit under the program's scope `linear.irls` (own
time of the operations inside `bench.fit`): the whole scan of Newton steps,
those a converged fit still runs included."""

from benchmark.layer_metrics import _linear_scopes


def read(run):
    return _linear_scopes.seconds_per_fit(run, "linear.irls")
