"""Newton steps that moved the coefficients a timed fit: the window's
`linear.irls.iterations` over its fits (what the model reports)."""


def read(run):
    fits = run.facts.get("fits")
    if not fits or "linear.irls.iterations" not in run.counters_end:
        return None
    return run.counter_delta("linear.irls.iterations") / fits
