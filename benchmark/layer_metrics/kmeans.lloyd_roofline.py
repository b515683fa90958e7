"""Share of the chip's bf16 peak that the USEFUL work of the Lloyd steps
is, over their device time: `kmeans.iterations` x 4 x rows x d x k
operations a fit (`_kmeans_work.lloyd_flops`: the rows fitted, no padding)
over (`fit.device.kmeans.assign_s` + `fit.device.kmeans.update_s`) x the
peak keyed by `device_kind`. It counts the same work whatever implements
it: a float32 product is six bf16 passes, d = 42 fills a third of the
MXU's contraction, and the cost pass's time is in the denominator with no
work for it, so the share is low today and cannot pass 100 %. Bound by the
MXU: 4 x rows x d x k operations over 4 x d x rows bytes read a step is
k = 1,000 FLOP a byte against the v5e's ridge of 240."""

from benchmark.layer_metrics import _kmeans_scopes, _kmeans_work


def read(run):
    seconds = _kmeans_scopes.seconds_per_fit(
        run, "kmeans.assign", "kmeans.cost", "kmeans.update")
    fits, k, d = (run.facts.get(key) for key in (
        "fits", "kmeans_k", "kmeans_d"))
    if not seconds or not fits or not k or not d \
            or not run.counter_delta("kmeans.iterations"):
        return None
    work = _kmeans_work.lloyd_flops(
        sum(run.facts["fit_rows"]) / fits, d, k,
        run.counter_delta("kmeans.iterations") / fits)
    return 100.0 * work / (seconds * _kmeans_work.peak_flops(
        run.device["kind"]))
