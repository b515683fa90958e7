"""Seconds a timed fit spends in the program's spans `stage.key`, inside
`fit.stage`: an array of at least 1 MiB made contiguous, its content key (a
word-sum over every byte) and the look-up in the staging cache. A PART of
`fit.host.stage_s`, not a phase beside it."""

from benchmark.layer_metrics import _fit_spans


def read(run):
    if "span_n.stage.key" not in run.counters_end:
        return None
    return _fit_spans.seconds_per_fit(run, ("stage.key",))
