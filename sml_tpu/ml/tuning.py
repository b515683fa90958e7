"""Model selection: grid search CV and train/validation split.

Reference surface (`SML/ML 07 - Random Forests and Hyperparameter
Tuning.py:72-158`): `ParamGridBuilder().addGrid(...).build()`,
`CrossValidator(estimator, evaluator, estimatorParamMaps, numFolds=3,
parallelism=4, seed=42)` with `avgMetrics`/`bestModel`, and both stage
orders (CV-inside-pipeline vs pipeline-inside-CV, `ML 07:134-149`).

Parallelism: trials run `parallelism`-wide with REAL chip placement — the
active mesh is partitioned into disjoint per-worker submeshes
(`parallel.mesh.run_placed_trials`), so concurrent fits execute on
different chips instead of serializing device programs on one shared mesh.
This is the TPU form of the reference's driver thread pool + executor
tasks (`ML 07:120-130`) — the task-parallel model-selection strategy
SURVEY §2.2 P6.

Folds without fold frames: where the estimator takes its folds as a mask
over ONE staged block (`LogisticRegression._fold_metrics`: a validator that
is a `Pipeline`'s last stage gets the column plan's compact block,
`CrossValidator._block_estimator`), `CrossValidator` makes a fold id a row
(`_fold_ids`: `randomSplit`'s own membership) and no split, union or cache;
a fold is one dispatch over the whole mesh, and they run one after another
whatever `parallelism` allows.
"""

from __future__ import annotations

import itertools
from functools import partial
from typing import Any, Dict, List, Optional

import numpy as np

from ..parallel.mesh import run_placed_trials
from ..utils.profiler import PROFILER
from ._staging import shared_keys
from .base import Estimator, Model, Saveable
from .param import Param


class ParamGridBuilder:
    def __init__(self):
        self._grid: Dict[Param, List[Any]] = {}

    def addGrid(self, param: Param, values) -> "ParamGridBuilder":
        self._grid[param] = list(values)
        return self

    def baseOn(self, *args) -> "ParamGridBuilder":
        for m in args:
            for p, v in (m.items() if isinstance(m, dict) else [m]):
                self._grid[p] = [v]
        return self

    def build(self) -> List[Dict[Param, Any]]:
        keys = list(self._grid.keys())
        out = []
        for combo in itertools.product(*[self._grid[k] for k in keys]):
            out.append(dict(zip(keys, combo)))
        return out or [{}]


class _ValidatorParams:
    def _declare_validator_params(self):
        self._declareParam("estimator", doc="estimator to tune")
        self._declareParam("estimatorParamMaps", doc="param grid")
        self._declareParam("evaluator", doc="metric evaluator")
        self._declareParam("seed", default=None, doc="fold assignment seed")
        self._declareParam("parallelism", default=1, doc="concurrent trials")
        self._declareParam("collectSubModels", default=False, doc="keep sub-models")
        # getEstimator/getEstimatorParamMaps/getEvaluator (the course reads
        # them off both the validator and its model, `ML 07:154-159`) come
        # from Params.__getattr__'s synthesized accessors


def _fit_and_eval(est: Estimator, pmap, train, val, evaluator) -> float:
    model = est.copy(pmap).fit(train)
    PROFILER.count("cv.fits")
    PROFILER.count("cv.evals")
    return evaluator.evaluate(model.transform(val))


#: the most folds a fold id a row holds (int8, what is staged beside the
#: block); a validator with more makes its fold frames
_FOLD_IDS_MAX = 127


def _fold_ids(frame, k: int, seed: int, keep) -> np.ndarray:
    """The fold `frame.randomSplit([1 / k] * k, seed)` puts each row in,
    int8, in the frame's own row order (partition after partition): the
    same draws on the same pre-split order (`frame/sampling.py`), with no
    fold frame made. `keep` masks the rows a featurizer dropped before
    the split saw them (None: none), and the ids are of the kept rows. A
    job a partition on the column plan's pool: the sort is most of it."""
    from ..frame.sampling import partition_uniforms, presplit_order
    from . import _column_plan as cp
    parts = frame._materialize()
    bounds = np.cumsum([1.0 / k] * k)
    starts = np.cumsum([0] + [len(p) for p in parts])

    def ids(i: int) -> np.ndarray:
        pdf = parts[i]
        if keep is not None and not keep[starts[i]:starts[i + 1]].all():
            pdf = pdf[keep[starts[i]:starts[i + 1]]]
        order = presplit_order(pdf)
        cell = np.minimum(np.searchsorted(bounds, partition_uniforms(
            seed, i, len(pdf)), side="right"), k - 1).astype(np.int8)
        if order is None:
            return cell
        out = np.empty(len(pdf), dtype=np.int8)
        out[order] = cell       # row j of the sorted partition is order[j]
        return out

    return np.concatenate(cp.run_tasks(
        [partial(ids, i) for i in range(len(parts))],
        cp.runs_inline(int(starts[-1]))))


def _batched_fold_metrics(est, grid, fold_pairs, evaluator):
    """Fused CV for tree regressors: the G×k (parameter map × fold)
    fit matrix runs as ceil(G*k / sml.cv.maxFusedTrials) trial-batched
    device programs (`_tree_models._fit_ensembles_grid`) — per-trial
    hyperparameters pad to the grid maxima and ride as traced scalars,
    so the dispatch count stops scaling with the grid. With
    sml.cv.maxFusedTrials <= 1 only the fold axis fuses (the VERDICT r3
    per-parameter-map `fit_ensembles_folds` shape: G dispatches). On a
    multi-device mesh the fused elements shard across a second "trial"
    mesh axis when that placement prices better
    (sml.cv.trialAxisDevices; see tree_impl._trial_axis_width) — E
    trials on disjoint chip groups instead of one all-chip vmap.
    Returns the (len(grid), k) metric matrix, or None whenever the shape
    doesn't apply (non-tree estimator, grid touching data-shaping
    params, sml.cv.batchFolds=false, or any surprise) — the caller then
    runs the ordinary placed-trials path, so results never depend on
    fusion firing."""
    from ..conf import GLOBAL_CONF
    from ._tree_models import (_feature_k, _fit_ensemble_folds,
                               _fit_ensembles_grid,
                               DecisionTreeRegressionModel,
                               DecisionTreeRegressor,
                               RandomForestRegressionModel,
                               RandomForestRegressor)
    if not GLOBAL_CONF.getBool("sml.cv.batchFolds"):
        return None
    kinds = {DecisionTreeRegressor: (DecisionTreeRegressionModel, False),
             RandomForestRegressor: (RandomForestRegressionModel, True)}
    info = kinds.get(type(est))
    if info is None:
        return None
    allowed = {"maxDepth", "maxBins", "numTrees", "featureSubsetStrategy",
               "subsamplingRate", "minInstancesPerNode", "minInfoGain",
               "seed"}
    if any(p.name not in allowed for pm in grid for p in pm):
        return None  # a param that reshapes the data: fall back
    try:
        model_cls, is_rf = info
        extracted = [(est._extract(train), val) for train, val in fold_pairs]
        Xs = [e[0][0] for e in extracted]
        ys = [e[0][1] for e in extracted]
        cat = extracted[0][0][2]
        F = Xs[0].shape[1]
        cfgs = []
        for pm in grid:
            ec = est.copy(pm)
            if is_rf:
                n_trees = int(ec.getOrDefault("numTrees"))
                feature_k = _feature_k(
                    ec.getOrDefault("featureSubsetStrategy"), F,
                    ec._is_classifier)
                bootstrap, subsample = True, \
                    float(ec.getOrDefault("subsamplingRate"))
            else:
                n_trees, feature_k, bootstrap, subsample = 1, None, False, 1.0
            cfgs.append(dict(
                est=ec,
                max_depth=int(ec.getOrDefault("maxDepth")),
                max_bins=int(ec.getOrDefault("maxBins")),
                min_instances=int(ec.getOrDefault("minInstancesPerNode")),
                min_info_gain=float(ec.getOrDefault("minInfoGain")),
                n_trees=n_trees, feature_k=feature_k, bootstrap=bootstrap,
                subsample=subsample, seed=ec._seed()))
        metrics = np.zeros((len(grid), len(fold_pairs)), dtype=np.float64)
        max_fused = GLOBAL_CONF.getInt("sml.cv.maxFusedTrials")
        # the padded-bins argmax argument needs min_instances >= 1 (a
        # candidate bin past a trial's own maxBins always leaves an empty
        # right child); 0 is below Spark's own floor, but guard anyway
        if max_fused > 1 and all(c["min_instances"] >= 1 for c in cfgs):
            fused = _fit_ensembles_grid(Xs, ys, cat, cfgs, max_fused)
            for (gi, fi), spec in fused.items():
                model = model_cls(spec)
                model._inherit_params(cfgs[gi]["est"])
                metrics[gi, fi] = evaluator.evaluate(
                    model.transform(extracted[fi][1]))
            return metrics
        for gi, c in enumerate(cfgs):
            specs = _fit_ensemble_folds(
                Xs, ys, cat,
                max_depth=c["max_depth"], max_bins=c["max_bins"],
                min_instances=c["min_instances"],
                min_info_gain=c["min_info_gain"],
                n_trees=c["n_trees"], feature_k=c["feature_k"],
                bootstrap=c["bootstrap"], subsample=c["subsample"],
                seed=c["seed"])
            for fi, (spec, (_, val)) in enumerate(zip(specs, extracted)):
                model = model_cls(spec)
                model._inherit_params(c["est"])
                metrics[gi, fi] = evaluator.evaluate(model.transform(val))
        return metrics
    except Exception:
        # the sequential path is always correct — but record that the
        # batched path bailed (a silent fallback would make a parity bug
        # in the experimental path invisible), and re-raise under the
        # debug env so it can be diagnosed
        import os

        from ..utils.profiler import PROFILER
        PROFILER.count("cv.batchFolds.fallback")
        if os.environ.get("SML_FUSED_DEBUG") == "1":
            raise
        return None


def fused_param_scores(est, pmaps, train, val, evaluator):
    """Score arbitrary param maps of a tree regressor on ONE (train, val)
    pair through the grid-fused trial batch — the evaluator behind
    TrainValidationSplit and the TPE loop's candidate batches
    (`tune.fmin` objectives expose it via `score_batch`). Returns the
    per-map metric list, or None whenever fusion doesn't apply — callers
    fall back to their per-trial path, so results never depend on fusion
    firing."""
    m = _batched_fold_metrics(est, pmaps, [(train, val)], evaluator)
    if m is None:
        return None
    return [float(x) for x in m[:, 0]]


class CrossValidator(Estimator, _ValidatorParams):
    def _init_params(self):
        self._declare_validator_params()
        self._declareParam("numFolds", default=3, doc="number of folds")

    def __init__(self, estimator=None, estimatorParamMaps=None, evaluator=None,
                 numFolds=None, seed=None, parallelism=None, collectSubModels=None):
        super().__init__()
        self._set(estimator=estimator, estimatorParamMaps=estimatorParamMaps,
                  evaluator=evaluator, numFolds=numFolds, seed=seed,
                  parallelism=parallelism, collectSubModels=collectSubModels)

    def _block_estimator(self):
        """The estimator whose folds this validator reads off ONE staged
        block (`_fit`), for `Pipeline.fit`'s column plan to featurize
        for (`_block_reader`); None where the folds need their frames."""
        est = self.getOrDefault("estimator")
        on_block = getattr(est, "_folds_on_block", None)
        if on_block is None \
                or int(self.getOrDefault("numFolds")) > _FOLD_IDS_MAX \
                or not on_block(self.getOrDefault("estimatorParamMaps"),
                                self.getOrDefault("evaluator")):
            return None
        return est

    def _block_reader(self):
        est = self._block_estimator()
        return None if est is None else (est, True)

    def _fit(self, df) -> "CrossValidatorModel":
        # the content keys of the frame's arrays are made once for every
        # dispatch of this fit (`_staging.shared_keys`)
        with PROFILER.span("fit.cv"), shared_keys():
            return self._fit_folds(df)

    def _fit_folds(self, df) -> "CrossValidatorModel":
        est = self.getOrDefault("estimator")
        grid = self.getOrDefault("estimatorParamMaps")
        evaluator = self.getOrDefault("evaluator")
        k = int(self.getOrDefault("numFolds"))
        seed = self.getOrDefault("seed")
        seed = int(seed) if seed is not None else 42
        par = max(1, int(self.getOrDefault("parallelism")))

        # an estimator that takes its folds as a mask over the one staged
        # block (`LogisticRegression._fold_metrics`): a fold id a row,
        # `randomSplit`'s own membership on the frame the user fitted
        # (`_row_source`: the frame whose rows, partition by partition,
        # are this one's), no fold frame, union or cache. Its dispatches
        # run one after another whatever `parallelism` says: each holds
        # the whole mesh and one fit's temporaries, and `parallelism` is
        # a bound on how many run at once
        metrics = None
        if self._block_estimator() is not None:
            def fold_ids(keep):
                with PROFILER.span("fit.cv.folds", folds=k):
                    return _fold_ids(getattr(df, "_row_source", df), k,
                                     seed, keep)
            metrics = est._fold_metrics(df, grid, k, fold_ids)
        if metrics is None:
            metrics = self._frame_metrics(df, est, grid, evaluator, k, seed,
                                          par)

        avg = metrics.mean(axis=1)
        best_idx = int(np.argmax(avg) if evaluator.isLargerBetter()
                       else np.argmin(avg))
        with PROFILER.span("fit.cv.refit", point=best_idx):
            best_model = est.copy(grid[best_idx]).fit(df)
        PROFILER.count("cv.fits")
        cvm = CrossValidatorModel(bestModel=best_model, avgMetrics=list(avg))
        cvm._inherit_params(self)
        return cvm

    @staticmethod
    def _frame_metrics(df, est, grid, evaluator, k, seed, par):
        """The (grid point, fold) metrics from fold FRAMES: k splits, k
        unions, each cached (counter `cv.fold_frames`)."""
        # seeded per-partition fold assignment — same contract class as
        # randomSplit (`ML 02:38-52`): deterministic given (seed, layout)
        folds = df.randomSplit([1.0 / k] * k, seed=seed)
        for f in folds:
            f.cache()

        fold_pairs = []
        for fi in range(k):
            val = folds[fi]
            rest = [folds[j] for j in range(k) if j != fi]
            train = rest[0]
            for r in rest[1:]:
                train = train.union(r)
            train.cache()
            fold_pairs.append((train, val))
        PROFILER.count("cv.fold_frames", 2 * k)

        metrics = _batched_fold_metrics(est, grid, fold_pairs, evaluator)
        if metrics is None:
            metrics = np.zeros((len(grid), k), dtype=np.float64)
            jobs = [(gi, fi, train, val, pmap)
                    for fi, (train, val) in enumerate(fold_pairs)
                    for gi, pmap in enumerate(grid)]

            def run(job):
                gi, fi, train, val, pmap = job
                return gi, fi, _fit_and_eval(est, pmap, train, val,
                                             evaluator)

            results = run_placed_trials(jobs, run, par)
            for gi, fi, m in results:
                metrics[gi, fi] = m
        return metrics


class CrossValidatorModel(Model, _ValidatorParams):
    def _init_params(self):
        CrossValidator._init_params(self)

    def __init__(self, bestModel=None, avgMetrics=None, subModels=None):
        super().__init__()
        self.bestModel = bestModel
        self.avgMetrics = avgMetrics or []
        self.subModels = subModels

    def _transform(self, df):
        return self.bestModel.transform(df)

    def _extra_metadata(self):
        return {"avgMetrics": [float(m) for m in self.avgMetrics]}

    def _save_state(self, path):
        import os
        self.bestModel._save_to(os.path.join(path, "bestModel"))

    def _load_state(self, path, meta):
        import os
        self.avgMetrics = meta.get("avgMetrics", [])
        self.bestModel = Saveable.load(os.path.join(path, "bestModel"))


class TrainValidationSplit(Estimator, _ValidatorParams):
    def _init_params(self):
        self._declare_validator_params()
        self._declareParam("trainRatio", default=0.75, doc="train fraction")

    def __init__(self, estimator=None, estimatorParamMaps=None, evaluator=None,
                 trainRatio=None, seed=None, parallelism=None):
        super().__init__()
        self._set(estimator=estimator, estimatorParamMaps=estimatorParamMaps,
                  evaluator=evaluator, trainRatio=trainRatio, seed=seed,
                  parallelism=parallelism)

    def _fit(self, df) -> "TrainValidationSplitModel":
        est = self.getOrDefault("estimator")
        grid = self.getOrDefault("estimatorParamMaps")
        evaluator = self.getOrDefault("evaluator")
        ratio = float(self.getOrDefault("trainRatio"))
        seed = self.getOrDefault("seed")
        seed = int(seed) if seed is not None else 42
        par = max(1, int(self.getOrDefault("parallelism")))
        train, val = df.randomSplit([ratio, 1 - ratio], seed=seed)
        train.cache()
        val.cache()

        # same fused evaluator as CrossValidator (one (train, val) pair =
        # a 1-fold grid); placed trials whenever fusion doesn't apply
        fused = _batched_fold_metrics(est, grid, [(train, val)], evaluator)
        if fused is not None:
            arr = np.asarray(fused[:, 0])
        else:
            def run(pmap):
                return _fit_and_eval(est, pmap, train, val, evaluator)

            arr = np.asarray(run_placed_trials(grid, run, par))
        best_idx = int(np.argmax(arr) if evaluator.isLargerBetter()
                       else np.argmin(arr))
        best_model = est.copy(grid[best_idx]).fit(df)
        m = TrainValidationSplitModel(bestModel=best_model,
                                      validationMetrics=list(arr))
        m._inherit_params(self)
        return m


class TrainValidationSplitModel(Model, _ValidatorParams):
    def _init_params(self):
        TrainValidationSplit._init_params(self)

    def __init__(self, bestModel=None, validationMetrics=None):
        super().__init__()
        self.bestModel = bestModel
        self.validationMetrics = validationMetrics or []

    def _transform(self, df):
        return self.bestModel.transform(df)

    def _save_state(self, path):
        import os
        self.bestModel._save_to(os.path.join(path, "bestModel"))

    def _load_state(self, path, meta):
        import os
        self.bestModel = Saveable.load(os.path.join(path, "bestModel"))
