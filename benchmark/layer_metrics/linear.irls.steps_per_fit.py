"""Newton steps the device executed a timed fit: the window's
`linear.irls.steps_run` over its fits (the scan's length, whatever
converged)."""


def read(run):
    fits = run.facts.get("fits")
    if not fits or "linear.irls.steps_run" not in run.counters_end:
        return None
    return run.counter_delta("linear.irls.steps_run") / fits
