"""Lloyd steps a timed fit ran on the device, read back with the centers:
the window's `kmeans.iterations` over its fits (at most maxIter; fewer
where `tol` ended the loop)."""


def read(run):
    fits = run.facts.get("fits")
    if not fits or "kmeans.iterations" not in run.counters_end:
        return None
    return run.counter_delta("kmeans.iterations") / fits
