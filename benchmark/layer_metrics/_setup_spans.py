"""Shared by the `setup.*` readers: what the program's recorder had counted
when the window began. The harness resets the recorder once, in
`program.configure`, before set-up, and no kind resets it again, so a
running total at the window's FIRST snapshot (`Reading.counters_start`) is
set-up's own: seconds by span name (`span_s.<name>`: the table, the splits,
the warm fits and their first dispatches all pass through the program's
spans) and the two facts of the process the program notes at its import
(`process.*`). What is left of `setup_s` after them is the harness's own
(the generator, `gc`, the profiler's start) and what no span covers.

Reported beside the device's split, as the `fit.host.*` are: left out where
the trace has no device plane, and where the program keeps no such total."""


def at_window_start(run, total):
    """The recorder's running total `total` when the window began, or None."""
    if run.trace is None or not run.trace.device_ops:
        return None
    return run.counters_start.get(total)
