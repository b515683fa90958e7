"""Share of the chip's bf16 peak that the USEFUL work of the Hessian is, over
the whole scan's device time: `linear.irls.iterations` x 2 x rows x (d+1)^2
operations (`_linear_work.hess_flops`) over `fit.device.irls_s` x the peak
keyed by `device_kind`. It counts the work whatever implements it: a
float32 product is several bf16 passes and a converged scan still runs its
length, so the share is low today and cannot pass 100 %."""

from benchmark.layer_metrics import _linear_scopes, _linear_work


def read(run):
    seconds = _linear_scopes.seconds_per_fit(run, "linear.irls")
    fits, slots = run.facts.get("fits"), run.facts.get("features")
    if not seconds or not fits or slots is None \
            or "linear.irls.iterations" not in run.counters_end:
        return None
    rows = sum(run.facts["fit_rows"]) / fits
    work = _linear_work.hess_flops(
        rows, slots + 1, run.counter_delta("linear.irls.iterations") / fits)
    return 100.0 * work / (seconds * _linear_work.peak_flops(
        run.device["kind"]))
