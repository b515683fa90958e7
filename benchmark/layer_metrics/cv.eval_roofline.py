"""Share of the chip's HBM bandwidth that the USEFUL traffic of the
validator's evaluations is, over their device time: folds x rows x (d + 1)
x 4 bytes a `Pipeline.fit` (`_cv_work.eval_bytes`: the expanded block read
once a FOLD, which is all that the margins of every grid point need) over
`fit.device.cv.eval_s` x the peak keyed by `device_kind`. Bandwidth-bound:
a margin is 2 FLOP a byte pair. A program that reads the block once a
(point, fold) reads at most 100 % / points."""

from benchmark.layer_metrics import _cv_scopes, _cv_work


def read(run):
    seconds = _cv_scopes.seconds_per_fit(run, "cv.eval")
    fits, slots = run.facts.get("fits"), run.facts.get("features")
    folds = run.facts.get("folds")
    if not seconds or not fits or slots is None or not folds \
            or not run.counter_delta("cv.evals"):
        return None
    rows = sum(run.facts["fit_rows"]) / fits
    work = _cv_work.eval_bytes(rows, slots + 1, folds)
    return 100.0 * work / (seconds * _cv_work.peak_bytes_per_s(
        run.device["kind"]))
