"""Inner coordinate sweeps a timed fit: the window's
`linear.irls.prox_sweeps` over its fits (every penalized Newton step of
every fit of the validator; a ridge step solves its system and sweeps 0)."""


def read(run):
    fits = run.facts.get("fits")
    if not fits or "linear.irls.prox_sweeps" not in run.counters_end:
        return None
    return run.counter_delta("linear.irls.prox_sweeps") / fits
