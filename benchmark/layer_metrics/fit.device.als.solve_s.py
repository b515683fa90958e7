"""Device seconds a timed fit under the program's scope `als.solve`:
the regularized systems assembled from the sums and the batched Cholesky solves of every user or movie, every half-step."""

from benchmark.layer_metrics import _als_scopes


def read(run):
    return _als_scopes.seconds_per_fit(run, "als.solve")
