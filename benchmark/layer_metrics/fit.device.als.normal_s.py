"""Device seconds a timed fit under the program's scope `als.normal`:
a block's statistics, their sums by segment, the add into the accumulators and, nested in it, the all-reduce of the shards' sums (`als.normal.allreduce`)."""

from benchmark.layer_metrics import _als_scopes


def read(run):
    return _als_scopes.seconds_per_fit(run, "als.normal")
