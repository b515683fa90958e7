"""Device seconds a timed fit under the program's scope `linear.irls.hess`,
nested in `linear.irls` and counted inside `fit.device.irls_s`: the weighted
copy of the block and the Gram product [X 1]^T W [X 1] of every step."""

from benchmark.layer_metrics import _linear_scopes


def read(run):
    return _linear_scopes.seconds_per_fit(run, "linear.irls.hess")
