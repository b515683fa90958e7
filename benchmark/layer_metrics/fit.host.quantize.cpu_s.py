"""CPU seconds of the PROCESS, every thread, a timed fit while a
`fit.quantize` span is open (`time.process_time` at the span's two ends,
user plus system): over `fit.host.quantize_s` it is the cores the quantize
plan's jobs got. A program whose `fit.quantize` reads no CPU seconds gives
nothing to read. Left out wherever `fit.host.featurize_s` is."""

from benchmark.layer_metrics import _fit_spans


def read(run):
    if "span_cpu_s.fit.quantize" not in run.counters_end \
            or _fit_spans.phase(run, "fit.host.featurize_s") is None:
        return None
    return run.counter_delta("span_cpu_s.fit.quantize") / run.facts["fits"]
