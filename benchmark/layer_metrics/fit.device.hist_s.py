"""Device seconds a timed fit under the program's scope `tree.hist` (own time
of the operations inside `bench.fit`): the histogram build: the per-level
node one-hot and statistics, the one-hot dots, the all-reduce of the partial
histograms (`tree.hist.allreduce`) and the parent-minus-sibling step."""

from benchmark.layer_metrics import _fit_scopes


def read(run):
    return _fit_scopes.seconds_per_fit(run, "tree.hist")
