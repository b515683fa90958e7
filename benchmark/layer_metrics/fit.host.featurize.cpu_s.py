"""CPU seconds of the PROCESS, every thread, a timed fit while a
`fit.featurize` span is open (`time.process_time` at the span's two ends,
user plus system): over the spans' wall seconds it is the cores the phase kept busy.
Left out wherever `fit.host.featurize_s` is."""

from benchmark.layer_metrics import _fit_spans


def read(run):
    if "span_cpu_s.fit.featurize" not in run.counters_end \
            or _fit_spans.phase(run, "fit.host.featurize_s") is None:
        return None
    return run.counter_delta("span_cpu_s.fit.featurize") / run.facts["fits"]
