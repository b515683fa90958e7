"""Device seconds a timed fit under the program's scope `kmeans.update`:
the 0/1 assignment matrix's product with a block (the clusters' sums and
counts), their all-reduce and the moved centers, every Lloyd step."""

from benchmark.layer_metrics import _kmeans_scopes


def read(run):
    return _kmeans_scopes.seconds_per_fit(run, "kmeans.update")
