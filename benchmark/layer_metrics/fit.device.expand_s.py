"""Device seconds a timed fit under the program's scope `linear.expand` (own
time of the operations inside `bench.fit`): the slots' means and deviations,
and the compact block expanded to the standardized [X 1]^T block that stays
on the chip for the fit."""

from benchmark.layer_metrics import _linear_scopes


def read(run):
    return _linear_scopes.seconds_per_fit(run, "linear.expand")
