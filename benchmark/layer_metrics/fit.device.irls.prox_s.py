"""Device seconds a timed fit under the program's scope `linear.irls.prox`,
nested in `linear.irls` and counted inside `fit.device.irls_s`: the
coordinate sweeps that minimize a Newton step's penalized quadratic model
on the (d+1) x (d+1) system, a latency-bound loop of small operations."""

from benchmark.layer_metrics import _linear_scopes


def read(run):
    if "linear.irls.prox_sweeps" not in run.counters_end:
        return None
    return _linear_scopes.seconds_per_fit(run, "linear.irls.prox")
