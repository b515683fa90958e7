"""Device seconds a timed fit under none of the program's `tree.*` scopes:
`fit.device_busy_s` less the five scoped metrics. What escapes the names."""

from benchmark.layer_metrics import _fit_scopes


def read(run):
    return _fit_scopes.unscoped(run)
