"""Seconds a timed fit keeps the device busy: the union of the device
operations inside each `bench.fit` annotation of the trace, a fit."""


def read(run):
    if run.trace is None or not run.trace.device_ops:
        return None
    busy_ns, fits = run.trace.busy_within("bench.fit")
    if not fits:
        return None
    return busy_ns / fits / 1e9
