"""Classification estimators.

`LogisticRegression` (`SML/Solutions/ML Electives/MLE 03` answer path) fits
by IRLS Newton steps whose X^T W X reduction is a mesh psum
(`linear_impl.fit_logistic`); transform appends `rawPrediction`,
`probability`, and `prediction` columns like MLlib. Tree classifiers ride
`tree_impl`.
"""

from __future__ import annotations

from typing import Optional

import numpy as np
import pandas as pd

from ..utils.profiler import PROFILER
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
        maxIter = int(self.getOrDefault("maxIter"))
        tol = float(self.getOrDefault("tol"))
        fit_int = bool(self.getOrDefault("fitIntercept"))
        compact = extract_compact(df, self.getOrDefault("featuresCol"),
                                  self.getOrDefault("labelCol"))
        if compact is not None and lam == 0.0 and fit_int:
            # fused-IRLS device program: the whole Newton loop in one
            # dispatch, one-hot slots expanded on-chip (linear_impl)
            parts, y = compact
            res = linear_impl.fit_logistic_compact(parts, y,
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
        else:
            if compact is not None:
                # penalized config needs the materialized block (prox on
                # raw coefficients); expand host-side and take the loop
                parts, y = compact
                X = parts.expand_host()
            else:
                X, y, _ = extract_xy(df, self.getOrDefault("featuresCol"),
                                     self.getOrDefault("labelCol"))
                ok = np.isfinite(y)
                X, y = X[ok], y[ok]
            res = linear_impl.fit_logistic(
                X, y, regParam=lam,
                elasticNetParam=float(self.getOrDefault("elasticNetParam")),
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
