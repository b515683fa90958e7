"""Classification estimators.

`LogisticRegression` (`SML/Solutions/ML Electives/MLE 03` answer path) fits
by IRLS Newton steps whose X^T W X reduction is a mesh psum: over a compact
block the fused program, one dispatch a fit, the elastic-net penalty in it
(`linear_impl.fit_logistic_compact`), and `linear_impl.fit_logistic`'s loop
where there is no such block; a `CrossValidator` over it reads its folds
off the one staged block (`_fold_metrics`). Transform appends
`rawPrediction`, `probability`, and `prediction` columns like MLlib. Tree
classifiers ride `tree_impl`.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import numpy as np
import pandas as pd

from ..utils.profiler import PROFILER
from . import _column_plan as cp
from .base import Estimator, Model, load_arrays, save_arrays
from .feature import _as_object_series
from .linalg import DenseVector, vector_series
from ._staging import extract_compact, extract_features, extract_xy
from . import linear_impl
from .featurizer import margin_jobs
from ._tree_models import (DecisionTreeClassificationModel,
                           DecisionTreeClassifier, GBTClassificationModel,
                           GBTClassifier, RandomForestClassificationModel,
                           RandomForestClassifier)


class BinaryLogisticRegressionSummary:
    """Training summary. On the compact fast path the float64 margin and
    the accuracy are computed EAGERLY at fit time, in ONE visit of the
    rows, a job a block of them on the column plan's pool (span
    `fit.summary`; `CompactParts.predict_affine_agreeing`), so the summary
    closure need not pin the training block; only the O(n log n) AUC sort
    stays lazy, materializing on first read."""

    def __init__(self, accuracy: float = None, areaUnderROC: float = None,
                 numInstances: int = 0, lazy_fn=None):
        self._accuracy = accuracy
        self._auc = areaUnderROC
        self.numInstances = numInstances
        self._lazy_fn = lazy_fn

    def _force(self):
        if self._lazy_fn is not None:
            self._accuracy, self._auc = self._lazy_fn()
            self._lazy_fn = None

    @property
    def accuracy(self) -> float:
        if self._accuracy is None:  # an eager value must not force the
            self._force()           # lazy AUC sort alongside it
        return self._accuracy

    @property
    def areaUnderROC(self) -> float:
        self._force()
        return self._auc


class LogisticRegression(Estimator):
    def _init_params(self):
        self._declareParam("featuresCol", default="features", doc="features column")
        self._declareParam("labelCol", default="label", doc="label column")
        self._declareParam("predictionCol", default="prediction", doc="prediction column")
        self._declareParam("rawPredictionCol", default="rawPrediction", doc="margin column")
        self._declareParam("probabilityCol", default="probability", doc="probability column")
        self._declareParam("regParam", default=0.0, doc="regularization strength")
        self._declareParam("elasticNetParam", default=0.0, doc="L1 mixing in [0,1]")
        self._declareParam("maxIter", default=100, doc="max iterations")
        self._declareParam("tol", default=1e-6, doc="convergence tolerance")
        self._declareParam("fitIntercept", default=True, doc="fit intercept")
        self._declareParam("threshold", default=0.5, doc="decision threshold")

    def __init__(self, featuresCol=None, labelCol=None, predictionCol=None,
                 regParam=None, elasticNetParam=None, maxIter=None, tol=None,
                 fitIntercept=None, threshold=None):
        super().__init__()
        self._set(featuresCol=featuresCol, labelCol=labelCol,
                  predictionCol=predictionCol, regParam=regParam,
                  elasticNetParam=elasticNetParam, maxIter=maxIter, tol=tol,
                  fitIntercept=fitIntercept, threshold=threshold)

    def setLabelCol(self, v):
        return self._set(labelCol=v)

    def setFeaturesCol(self, v):
        return self._set(featuresCol=v)

    def _fit(self, df) -> "LogisticRegressionModel":
        lam = float(self.getOrDefault("regParam"))
        alpha = float(self.getOrDefault("elasticNetParam"))
        maxIter = int(self.getOrDefault("maxIter"))
        tol = float(self.getOrDefault("tol"))
        fit_int = bool(self.getOrDefault("fitIntercept"))
        compact = extract_compact(df, self.getOrDefault("featuresCol"),
                                  self.getOrDefault("labelCol"))
        if compact is not None and fit_int:
            # fused-IRLS device program: the whole Newton loop in one
            # dispatch, one-hot slots expanded on-chip, the elastic-net
            # penalty in it where there is one (linear_impl)
            parts, y = compact
            res = linear_impl.fit_logistic_compact(
                parts, y, regParam=lam, elasticNetParam=alpha,
                maxIter=maxIter, tol=tol)
            model = LogisticRegressionModel(coefficients=res.coefficients,
                                            intercept=res.intercept)
            model._inherit_params(self)

            # margin + accuracy run EAGERLY, so the summary closure holds
            # only two 1-D arrays — the previous closure pinned the full
            # CompactParts block (hundreds of MB at the 8M-row scale this
            # path is gated to) until the summary was read, or forever if
            # it never was. ONE visit of the rows, a job a block of them on
            # the column plan's pool (`CompactParts.predict_affine_agreeing`,
            # PERF.md section 6, PR 37): a job writes its block of the
            # float64 margin and counts the rows whose side of 0 is their
            # label, and the counts' sum over the rows is the mean of the
            # 0/1 agreements to the bit (NaN for no rows, as that mean is).
            # Only the O(n log n) AUC sort stays lazy; all metrics are
            # EXACT full-data values, and _force drops the arrays once
            # reduced to floats.
            n = len(y)
            workers, blocks = margin_jobs(n)
            with PROFILER.span("fit.summary", rows=n, workers=workers,
                               blocks=blocks):
                margin, agreeing = parts.predict_affine_agreeing(
                    res.coefficients, res.intercept, y)
                acc = float(np.divide(agreeing, n))

            def lazy_metrics(margin=margin, y=y, acc=acc):
                return acc, _fast_auc(margin, y)

            model._summary = BinaryLogisticRegressionSummary(
                accuracy=acc, numInstances=len(y), lazy_fn=lazy_metrics)
            return model
        if compact is not None:
            # no intercept: the materialized block and the host loop
            parts, y = compact
            X = parts.expand_host()
        else:
            X, y, _ = extract_xy(df, self.getOrDefault("featuresCol"),
                                 self.getOrDefault("labelCol"))
            ok = np.isfinite(y)
            X, y = X[ok], y[ok]
        res = linear_impl.fit_logistic(
            X, y, regParam=lam, elasticNetParam=alpha,
            fitIntercept=fit_int, maxIter=maxIter, tol=tol)
        margin = X @ res.coefficients + res.intercept
        model = LogisticRegressionModel(coefficients=res.coefficients,
                                        intercept=res.intercept)
        model._inherit_params(self)
        pred = (margin > 0).astype(float)
        model._summary = BinaryLogisticRegressionSummary(
            accuracy=float(np.mean(pred == y)),
            areaUnderROC=_fast_auc(margin, y), numInstances=len(y))
        return model

    #: the grid parameters `_fold_metrics` hands the fused program as
    #: numbers: any other one changes the program or the rows it reads
    _FOLD_GRID = frozenset({"regParam", "elasticNetParam"})

    def _folds_on_block(self, grid, evaluator) -> bool:
        """Whether a cross-validation of this estimator over `grid` under
        `evaluator` can read its folds off the one staged block
        (`_fold_metrics`): the grid moves the penalty alone, the fit has
        its intercept, and the metric is the area under the ROC curve of
        this estimator's own margin against its own label."""
        from .evaluation import BinaryClassificationEvaluator
        return (bool(self.getOrDefault("fitIntercept"))
                and all(p.parent == self.uid and p.name in self._FOLD_GRID
                        for pmap in grid for p in pmap)
                and type(evaluator) is BinaryClassificationEvaluator
                and evaluator.getOrDefault("metricName") == "areaUnderROC"
                and evaluator.getOrDefault("labelCol")
                == self.getOrDefault("labelCol")
                and evaluator.getOrDefault("rawPredictionCol")
                == self.getOrDefault("rawPredictionCol"))

    def _fold_metrics(self, df, grid, k: int, fold_ids):
        """The (grid point, fold) areas under the ROC curve of a k-fold
        cross-validation whose grid and evaluator `_folds_on_block` has
        accepted, read off the frame's compact block where it lies on the
        chip: a dispatch a fold fits every grid point on the rows outside
        the fold (the fused penalized program, the fold a mask) and makes
        every row's margin there (`linear_impl._compact_enet_fn`); the
        fold's own are ranked on the host pool (`_midrank_auc`, exact).
        No fold frame, no transform and no second H2D of the features.
        `fold_ids(keep)` gives a fold id a row the block keeps. None
        where the frame carries no compact block: the validator then
        makes its fold frames. Counters `cv.fits` and `cv.evals`; span
        `fit.cv.eval` holds what of the rankings the chip's work did not
        hide."""
        compact = extract_compact(df, self.getOrDefault("featuresCol"),
                                  self.getOrDefault("labelCol"))
        if compact is None:
            return None
        parts, y = compact
        fold = fold_ids(parts.keep)
        points = []
        for pmap in grid:
            at = self.copy(pmap)
            points.append((float(at.getOrDefault("regParam")),
                           float(at.getOrDefault("elasticNetParam"))))
        inline = cp.runs_inline(parts.rows)
        ranked = []
        for held in range(k):
            fits = linear_impl.fit_logistic_folds(
                parts, y, fold, held, points,
                maxIter=int(self.getOrDefault("maxIter")),
                tol=float(self.getOrDefault("tol")))
            PROFILER.count("cv.fits", len(points))
            # the fold's margins are ranked on the host pool while the
            # next fold is on the chip
            rows = np.flatnonzero(fold == held)
            positive = y[rows] > 0.5
            ranked.append(cp.start_tasks(
                [partial(_midrank_auc, margin, rows, positive)
                 for _, _, margin in fits], inline))
        with PROFILER.span("fit.cv.eval", evaluations=k * len(points)):
            metrics = np.array([r() for r in ranked], dtype=np.float64).T
        PROFILER.count("cv.evals", k * len(points))
        return metrics


def _midrank_auc(score: np.ndarray, rows: np.ndarray,
                 positive: np.ndarray) -> float:
    """The exact area under the ROC curve of `score[rows]`, ties by
    midrank: every positive row's count of negatives scored under it plus
    half of those scored as it, in whole numbers, over positives x
    negatives. Two sorts of a class each and two binary searches: no
    argsort of the whole, and integers until the last division."""
    s = score[rows]
    pos, neg = np.sort(s[positive]), np.sort(s[~positive])
    if not len(pos) or not len(neg):
        return float("nan")
    twice = int(np.searchsorted(neg, pos, side="left").sum(dtype=np.int64)) \
        + int(np.searchsorted(neg, pos, side="right").sum(dtype=np.int64))
    return twice / (2 * len(pos) * len(neg))


def _fast_auc(score: np.ndarray, label: np.ndarray) -> float:
    order = np.argsort(score)
    ranks = np.empty(len(score))
    ranks[order] = np.arange(1, len(score) + 1)
    pos = label > 0.5
    n_pos, n_neg = pos.sum(), (~pos).sum()
    if n_pos == 0 or n_neg == 0:
        return float("nan")
    return float((ranks[pos].sum() - n_pos * (n_pos + 1) / 2) / (n_pos * n_neg))


class LogisticRegressionModel(Model):
    def _init_params(self):
        LogisticRegression._init_params(self)

    def __init__(self, coefficients=None, intercept: float = 0.0):
        super().__init__()
        self._coefficients = np.asarray(coefficients, dtype=np.float64) \
            if coefficients is not None else None
        self._intercept = float(intercept)
        self._summary: Optional[BinaryLogisticRegressionSummary] = None

    @property
    def coefficients(self) -> DenseVector:
        return DenseVector(self._coefficients)

    @property
    def intercept(self) -> float:
        return self._intercept

    @property
    def summary(self):
        return self._summary

    @property
    def numClasses(self) -> int:
        return 2

    def _transform(self, df):
        fc = self.getOrDefault("featuresCol")
        pc = self.getOrDefault("predictionCol")
        rc = self.getOrDefault("rawPredictionCol")
        prc = self.getOrDefault("probabilityCol")
        thr = float(self.getOrDefault("threshold"))
        w, b = self._coefficients, self._intercept

        def fn(pdf: pd.DataFrame, ctx) -> pd.DataFrame:
            out = pdf.copy(deep=False)  # CoW: column adds never touch the parent
            if len(out) == 0:
                for c in (rc, prc, pc):
                    out[c] = pd.Series(dtype=object if c != pc else float)
                return out
            X = extract_features(out, fc)
            margin = linear_impl.predict_linear(X, w, b)
            p1 = 1.0 / (1.0 + np.exp(-margin))
            out[rc] = vector_series(np.stack([-margin, margin], axis=1),
                                    index=out.index)
            out[prc] = vector_series(np.stack([1 - p1, p1], axis=1),
                                     index=out.index)
            out[pc] = (p1 > thr).astype(float)
            return out

        return df._derive_rowlocal(fn)

    def _save_state(self, path):
        save_arrays(path, coefficients=self._coefficients,
                    intercept=np.asarray([self._intercept]))

    def _load_state(self, path, meta):
        d = load_arrays(path)
        self._coefficients = d["coefficients"]
        self._intercept = float(d["intercept"][0])
        self._summary = None
