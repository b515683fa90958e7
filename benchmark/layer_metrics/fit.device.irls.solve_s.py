"""Device seconds a timed fit under the program's scope `linear.irls.solve`,
nested in `linear.irls` and counted inside `fit.device.irls_s`: the
(d+1) x (d+1) solve of every step, its damping and its convergence test."""

from benchmark.layer_metrics import _linear_scopes


def read(run):
    return _linear_scopes.seconds_per_fit(run, "linear.irls.solve")
