"""Device seconds a timed fit under the program's scope `als.gather`:
the other side's factor rows gathered for a block of the sorted order, every block of every half-step."""

from benchmark.layer_metrics import _als_scopes


def read(run):
    return _als_scopes.seconds_per_fit(run, "als.gather")
