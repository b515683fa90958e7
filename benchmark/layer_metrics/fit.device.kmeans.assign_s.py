"""Device seconds a timed fit under the program's scopes `kmeans.assign`
(a block's distance product and its arg-min, every Lloyd step) and
`kmeans.cost` (the same pass once more at the returned centers, for the
cost and the clusters' sizes: an assignment, so it is counted here)."""

from benchmark.layer_metrics import _kmeans_scopes


def read(run):
    return _kmeans_scopes.seconds_per_fit(run, "kmeans.assign", "kmeans.cost")
