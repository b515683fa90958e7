"""Bytes staged to the device a timed fit: the window's
`staging.h2d_bytes` over its fits. A bin-cache hit shows as about 0, a
table not fitted before as about the padded bin matrix."""


def read(run):
    fits = run.facts.get("fits")
    if not fits:
        return None
    return run.counter_delta("staging.h2d_bytes") / fits
