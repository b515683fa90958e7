"""XGBoost-equivalent estimators with the `sparkdl.xgboost` surface.

The reference trains `XgboostRegressor(n_estimators=…, learning_rate=…,
max_depth=…, random_state=…, missing=0, num_workers=…, use_gpu=…)` inside an
MLlib Pipeline (`SML/ML 11 - XGBoost.py:55-72`). There the gradient/histogram
aggregation is Rabit allreduce in C++; here the SAME second-order histogram
boosting runs as the jitted mesh program in `sml_tpu.ml.tree_impl`, whose
per-level reduction is one psum over ICI — `tpu_hist`, the `gpu_hist`
equivalent named in SURVEY §2.2 P9. `use_gpu`/`device` is accepted for
surface parity ('tpu' is the only engine).

`num_workers` is the layout of the fit, as the notebook uses it to name the
cluster's task slots: the table's rows are sharded over that many devices
(`parallel.mesh.worker_mesh`), bin edges, merged histograms, split
selection and the trees replicated. None fits on the active mesh as it
stands; the active mesh's own width is the active mesh; a divisor of its
devices is the first submesh that wide; any other number raises a
`ValueError` that names it and the device count, so a layout the host
cannot give is never quietly replaced by one it can.

Quantized shared-histogram engine (the GPU boosting design of
arXiv:1806.11248 mapped to the mesh): features quantize ONCE into a compact
uint8/uint16 bin-index matrix, content-cached on device
(`ml/_staging.stage_bins_cached`, budget `sml.tree.binCacheBytes`) and
reused by every boosting round, every tree, and every CV fold. Boosting
rounds scan entirely on-device; `rounds_per_dispatch` (or the
`sml.tree.roundsPerDispatch` conf) chunks the scan into multiple dispatches
whose margin carry stays in HBM with the buffer DONATED between chunks —
no per-round host↔device transfers either way.
"""

from __future__ import annotations

from typing import Optional

from .ml._tree_models import (_EnsembleSpec, _TreeClassificationModel,
                              _TreeEstimatorBase, _TreeRegressionModel,
                              _categorical_slots, _fit_ensemble)
from .parallel import mesh as meshlib


class _XgboostParams:
    def _declare_xgb_params(self):
        self._declareParam("featuresCol", default="features", doc="features column")
        self._declareParam("labelCol", default="label", doc="label column")
        self._declareParam("predictionCol", default="prediction", doc="prediction column")
        self._declareParam("n_estimators", default=100, doc="boosting rounds")
        self._declareParam("learning_rate", default=0.3, doc="eta")
        self._declareParam("max_depth", default=6, doc="tree depth")
        self._declareParam(
            "max_bins", default=256,
            doc="histogram bins a column (xgboost's own default, max_bin). "
                "A fit holds columns x max_bins x padded rows bytes on each "
                "chip for its one dispatch (the one-hot histogram operand, "
                "tree_impl._tree_operand, built by row blocks): the default "
                "fits a v5e up to about 10 GB of it, 28 columns x 850 k "
                "rows or 10 columns x 4 M rows a chip")
        self._declareParam("reg_lambda", default=1.0, doc="L2 on leaf weights")
        self._declareParam("gamma", default=0.0, doc="min split loss")
        self._declareParam("subsample", default=1.0, doc="row subsample per round")
        self._declareParam("min_child_weight", default=1.0, doc="min hessian per child")
        self._declareParam("random_state", default=0, doc="seed")
        self._declareParam("missing", default=float("nan"), doc="value treated as missing")
        self._declareParam("num_workers", default=None,
                           doc="data shards of the fit's mesh (None: the "
                               "active mesh; else a width the devices "
                               "divide into, or the fit raises)")
        self._declareParam("use_gpu", default=False, doc="accepted for surface parity")
        self._declareParam("device", default="tpu", doc="compute engine")
        self._declareParam("tree_method", default="tpu_hist", doc="histogram engine")
        self._declareParam("rounds_per_dispatch", default=None,
                           doc="boosting rounds fused per device dispatch "
                               "(None = sml.tree.roundsPerDispatch conf; "
                               "0 = whole ensemble in one scan program)")


class _XgboostBase(_TreeEstimatorBase, _XgboostParams):
    _loss = "squared"
    _model_cls = None

    def _init_params(self):
        self._declare_xgb_params()

    def __init__(self, **kwargs):
        super(_TreeEstimatorBase, self).__init__()
        for k, v in kwargs.items():
            if self.hasParam(k):
                self._set(**{k: v})
            else:
                raise TypeError(f"unexpected param {k!r}")

    def _fit(self, df):
        # the layout first: a num_workers the host cannot give raises
        # before a row is featurized
        with meshlib.use_mesh_local(
                meshlib.worker_mesh(self.getOrDefault("num_workers"))):
            return self._fit_on_mesh(df)

    def _fit_on_mesh(self, df):
        X, y, cat = self._extract(df)
        spec = _fit_ensemble(
            X, y, categorical=cat,
            max_depth=int(self.getOrDefault("max_depth")),
            max_bins=int(self.getOrDefault("max_bins")),
            min_instances=int(self.getOrDefault("min_child_weight")),
            min_info_gain=0.0,
            n_trees=int(self.getOrDefault("n_estimators")), feature_k=None,
            bootstrap=False, subsample=float(self.getOrDefault("subsample")),
            seed=int(self.getOrDefault("random_state")), loss=self._loss,
            step_size=float(self.getOrDefault("learning_rate")),
            reg_lambda=float(self.getOrDefault("reg_lambda")),
            gamma=float(self.getOrDefault("gamma")), boosting=True,
            missing=float(self.getOrDefault("missing")),
            rounds_per_dispatch=(
                None if self.getOrDefault("rounds_per_dispatch") is None
                else int(self.getOrDefault("rounds_per_dispatch"))))
        m = self._model_cls(spec)
        m._inherit_params(self)
        return m


class XgboostRegressorModel(_TreeRegressionModel, _XgboostParams):
    def _init_params(self):
        self._declare_xgb_params()


class XgboostRegressor(_XgboostBase):
    _loss = "squared"
    _model_cls = XgboostRegressorModel


class XgboostClassifierModel(_TreeClassificationModel, _XgboostParams):
    def _init_params(self):
        self._declare_xgb_params()
        self._declareParam("rawPredictionCol", default="rawPrediction", doc="raw scores")
        self._declareParam("probabilityCol", default="probability", doc="probabilities")


class XgboostClassifier(_XgboostBase):
    _loss = "logistic"
    _model_cls = XgboostClassifierModel

    def _init_params(self):
        self._declare_xgb_params()
        self._declareParam("rawPredictionCol", default="rawPrediction", doc="raw scores")
        self._declareParam("probabilityCol", default="probability", doc="probabilities")
