"""CPU seconds of the PROCESS, every thread, a timed fit while its root span
`fit` is open (`time.process_time` at the span's two ends, user plus
system): over `fit_s` it is the cores a fit keeps busy. A program whose root
span reads no CPU seconds gives nothing to read. Left out wherever
`fit.host.featurize_s` is."""

from benchmark.layer_metrics import _fit_spans


def read(run):
    if "span_cpu_s.fit" not in run.counters_end \
            or _fit_spans.phase(run, "fit.host.featurize_s") is None:
        return None
    return run.counter_delta("span_cpu_s.fit") / run.facts["fits"]
