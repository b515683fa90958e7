"""Seconds a timed fit spends in the interpreter's collector: the program's
total `fit.gc_s` (the pauses between a collection's two `gc.callbacks`
calls that fall inside a root `fit` span, every generation; the untimed
splits' collections are not in it) between the window's two counter
snapshots, over its fits. A program that keeps no fit records gives nothing
to read. Left out wherever `fit.host.featurize_s` is."""

from benchmark.layer_metrics import _fit_spans


def read(run):
    if "fit.gc_s" not in run.counters_end \
            or _fit_spans.phase(run, "fit.host.featurize_s") is None:
        return None
    return run.counter_delta("fit.gc_s") / run.facts["fits"]
