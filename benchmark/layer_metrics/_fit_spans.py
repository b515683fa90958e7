"""Shared by the `fit.host.*` readers: seconds a timed fit spent inside the
program's spans of given names, from the running totals the program's
recorder keeps a span name (`span_s.<name>`), between the window's two
counter snapshots, over the fits of the window.

Reported beside the device's time of the same fits: where the trace has no
device plane the split is left out, as `fit.device_busy_s` is. A program
whose recorder keeps no such totals (every commit before the spans were
added) gives nothing to read, and the metric is left out."""

ROOT = "fit"
#: the host phases of a fit, by the metric that reports each; together with
#: `fit.host.unattributed_s` they sum to the root span `fit`
PHASES = {
    "fit.host.featurize_s": ("fit.collect", "fit.prep", "fit.featurize"),
    "fit.host.quantize_s": ("fit.quantize",),
    "fit.host.stage_s": ("fit.stage",),
    "fit.host.dispatch_s": ("fit.dispatch",),
    "fit.host.device_wait_s": ("fit.device_wait",),
    "fit.host.readback_s": ("fit.readback", "fit.unpack"),
    "fit.host.observe_s": ("fit.baseline",),
}


def seconds_per_fit(run, names):
    """Seconds a fit inside spans called one of `names`, or None."""
    fits = run.facts.get("fits")
    if not fits or run.trace is None or not run.trace.device_ops:
        return None
    if "span_n." + ROOT not in run.counters_end:
        return None
    return sum(run.counter_delta("span_s." + n) for n in names) / fits


def phase(run, metric):
    return seconds_per_fit(run, PHASES[metric])


def unattributed(run):
    """The root span less every named phase: what no span covers yet."""
    whole = seconds_per_fit(run, (ROOT,))
    if whole is None:
        return None
    return whole - sum(seconds_per_fit(run, names)
                       for names in PHASES.values())
