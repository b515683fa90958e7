"""Seconds of the window's MEAN timed fit that its slow fits put there: the
program's total `fit.slow.excess_s` (a fit whose wall passed the median of
the earlier fits of its shape by a quarter and by 0.1 s adds its seconds
over that median; `sml_tpu/obs/_fits.py`) between the window's two counter
snapshots, over its fits. 0 in a quiet window. Where every fit of a window
is slow the median follows and this reads 0: `fit.host.quantize.cpu_s`
beside a high `fit.host.quantize_s` tells instead. A program that keeps no
fit records gives nothing to read. Left out wherever `fit.host.featurize_s`
is."""

from benchmark.layer_metrics import _fit_spans


def read(run):
    if "fit.slow.excess_s" not in run.counters_end \
            or _fit_spans.phase(run, "fit.host.featurize_s") is None:
        return None
    return run.counter_delta("fit.slow.excess_s") / run.facts["fits"]
