"""Device seconds a timed fit under the program's scope `cv.eval`: the
margins of every held fold under every grid point, and whatever of their
ranking runs on the device."""

from benchmark.layer_metrics import _cv_scopes


def read(run):
    return _cv_scopes.seconds_per_fit(run, "cv.eval")
