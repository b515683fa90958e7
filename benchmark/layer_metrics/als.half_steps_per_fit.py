"""Half-steps a timed `ALS.fit` ran on the device, read back with the
factors: the window's `als.half_steps` over its fits (2 x maxIter)."""


def read(run):
    fits = run.facts.get("fits")
    if not fits or "als.half_steps" not in run.counters_end:
        return None
    return run.counter_delta("als.half_steps") / fits
