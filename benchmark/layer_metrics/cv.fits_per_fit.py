"""Estimator fits a timed `Pipeline.fit` made inside its validator: the
window's `cv.fits` over its fits (grid points x folds, and the refit)."""


def read(run):
    fits = run.facts.get("fits")
    if not fits or "cv.fits" not in run.counters_end:
        return None
    return run.counter_delta("cv.fits") / fits
