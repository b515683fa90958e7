"""Tree estimators/models over the histogram engine (`tree_impl`).

Surface parity targets:
- `DecisionTreeRegressor` + `maxBins` failure semantics and
  `featureImportances` — `SML/ML 06 - Decision Trees.py:73-154`
- `RandomForestRegressor/Classifier` (numTrees, maxDepth,
  featureSubsetStrategy) — `SML/ML 07 - Random Forests and Hyperparameter
  Tuning.py:41-77`, `SML/Labs/ML 07L - Hyperparameter Tuning Lab.py`
- GBT (`SML/ML 11 - XGBoost.py:109` mentions GBTRegressor; the
  XGBoost-equivalent surface lives in `sml_tpu.xgboost`)

All learners share one second-order histogram program; the differences are
the (grad, hess) stream, bootstrap weights, and per-node feature subspaces.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional

import numpy as np
import pandas as pd

from ..obs import RECORDER
from ..parallel import dispatch
from ..utils.profiler import PROFILER
from .base import Estimator, Model, RegStatsHook, load_arrays, save_arrays
from .feature import _as_object_series
from .linalg import DenseVector, vector_series
from ._staging import extract_features, extract_xy
from . import tree_impl
from .tree_impl import (Binning, FittedTree, TreeSpec, bin_with,
                        feature_importances, fit_tree, predict_forest,
                        stage_aligned, stage_tree_data)


def _categorical_slots(df, featuresCol: str) -> Dict[int, int]:
    attrs = getattr(df, "_ml_attrs", {}).get(featuresCol) or {}
    return {int(k): int(v) for k, v in (attrs.get("slots") or {}).items()}


class _TreeParams:
    def _declare_tree_params(self):
        self._declareParam("featuresCol", default="features", doc="features column")
        self._declareParam("labelCol", default="label", doc="label column")
        self._declareParam("predictionCol", default="prediction", doc="prediction column")
        self._declareParam("maxDepth", default=5, doc="max tree depth")
        self._declareParam("maxBins", default=32, doc="max discretization bins")
        self._declareParam("minInstancesPerNode", default=1, doc="min rows per child")
        self._declareParam("minInfoGain", default=0.0, doc="min split gain")
        self._declareParam("seed", default=None, doc="random seed")


def _feature_k(strategy: str, F: int, is_classification: bool) -> int:
    s = str(strategy).lower()
    if s == "auto":
        s = "sqrt" if is_classification else "onethird"
    if s == "all":
        return F
    if s == "sqrt":
        return max(1, int(math.sqrt(F)))
    if s == "log2":
        return max(1, int(math.log2(F)))
    if s == "onethird":
        return max(1, int(F / 3))
    try:
        v = float(strategy)
        if v <= 1.0:
            return max(1, int(v * F))
        return min(F, int(v))
    except ValueError:
        raise ValueError(f"unknown featureSubsetStrategy {strategy!r}")


class _EnsembleSpec:
    """Host-side description of a fitted ensemble (persisted whole)."""

    #: training drift baseline (obs/drift.py DriftBaseline), stamped by
    #: `_fit_ensemble` and persisted as baseline.json next to data.npz —
    #: the distribution a serving/ingest drift monitor compares against
    baseline = None

    def __init__(self, trees: List[FittedTree], depth: int, binning: Binning,
                 tree_weights: Optional[np.ndarray], base: float,
                 n_features: int, mode: str):
        self.trees = trees
        self.depth = depth
        self.binning = binning
        self.tree_weights = tree_weights  # None → average
        self.base = base
        self.n_features = n_features
        self.mode = mode  # "regression" | "binary"

    def stacked(self):
        """Stacked (T, n_nodes) tree tensors + per-tree weights, cached —
        the replicated operands of the sharded traversal program."""
        if not hasattr(self, "_stacked"):
            sf = np.stack([t.split_feature for t in self.trees])
            sb = np.stack([t.split_bin for t in self.trees])
            lv = np.stack([t.leaf_value for t in self.trees])
            w = (np.full(len(self.trees), 1.0 / len(self.trees), np.float32)
                 if self.tree_weights is None
                 else np.asarray(self.tree_weights, dtype=np.float32))
            self._stacked = (sf, sb, lv, w)
        return self._stacked

    def predict_margin(self, X: np.ndarray) -> np.ndarray:
        from ..utils.profiler import PROFILER
        with PROFILER.span("binning.predict", rows=int(X.shape[0])):
            binned = bin_with(X, self.binning)
        n = binned.shape[0]
        from ._staging import route_for_arrays
        hint = dispatch.WorkHint(
            flops=4.0 * n * len(self.trees) * self.depth, kind="traverse",
            out_bytes=4.0 * n)
        mesh, route = route_for_arrays(hint, binned)
        with PROFILER.span("program.forest_predict", rows=n, route=route):
            if route == "device":
                # rows shard over the mesh; tree tensors replicate (P8 path)
                from .inference import predict_forest_sharded
                sf, sb, lv, w = self.stacked()
                return predict_forest_sharded(
                    binned, sf, sb, lv, w, self.depth, base=self.base)
            import jax
            with dispatch.observe_host("traverse", hint.flops), \
                    jax.default_device(list(mesh.devices.flat)[0]):
                return self.base + predict_forest(binned, self.trees,
                                                  self.depth,
                                                  self.tree_weights)

    def save(self, path: str) -> None:
        remap_keys = sorted(self.binning.cat_remap)
        save_arrays(
            path,
            split_feature=np.stack([t.split_feature for t in self.trees]),
            split_bin=np.stack([t.split_bin for t in self.trees]),
            leaf_value=np.stack([t.leaf_value for t in self.trees]),
            gain=np.stack([t.gain for t in self.trees]),
            cover=np.stack([t.cover for t in self.trees]),
            edges=self.binning.edges,
            tree_weights=(self.tree_weights if self.tree_weights is not None
                          else np.zeros(0)),
            scalars=np.asarray([self.depth, self.base, self.n_features,
                                1.0 if self.mode == "binary" else 0.0,
                                len(remap_keys)], dtype=np.float64),
            remap_slots=np.asarray(remap_keys, dtype=np.int64),
            **{f"remap_{k}": self.binning.cat_remap[k] for k in remap_keys},
        )
        if self.baseline is not None:
            import json as _json
            import os as _os
            with open(_os.path.join(path, "baseline.json"), "w") as f:
                _json.dump(self.baseline.to_dict(), f)

    @classmethod
    def load(cls, path: str) -> "_EnsembleSpec":
        d = load_arrays(path)
        depth, base, n_features, is_bin, _ = d["scalars"]
        remap = {int(k): d[f"remap_{int(k)}"] for k in d["remap_slots"]}
        trees = [FittedTree(sf, sb, lv, g, c) for sf, sb, lv, g, c in
                 zip(d["split_feature"], d["split_bin"], d["leaf_value"],
                     d["gain"], d["cover"])]
        tw = d["tree_weights"] if len(d["tree_weights"]) else None
        spec = cls(trees, int(depth),
                   Binning(edges=d["edges"], cat_remap=remap),
                   tw, float(base), int(n_features),
                   "binary" if is_bin else "regression")
        import os as _os
        bp = _os.path.join(path, "baseline.json")
        if _os.path.exists(bp):
            import json as _json

            from ..obs.drift import DriftBaseline
            with open(bp) as f:
                spec.baseline = DriftBaseline.from_dict(_json.load(f))
        return spec


import threading as _threading

_bins_cache: dict = {}
_bins_cache_order: list = []
_bins_cache_bytes: list = [0]
_bins_inflight: dict = {}  # key -> Event set when that key's bins land
_bins_lock = _threading.Lock()  # parallel tuning trials bin concurrently
_BINS_CACHE_MAX_BYTES = 1 << 30


def _cached_bins(X, y32, max_bins, categorical, missing=None):
    """make_bins memoized by (content fingerprint, bins, categorical,
    missing): CV folds and tuning trials re-fit trees on IDENTICAL matrices
    once per parameter set — re-quantizing 1M rows per fit was ~0.3s
    apiece. One block read under two `missing` values is two entries.
    Byte-budgeted and locked like the staging cache (same concurrent
    TpuTrials path, same multi-100MB operands)."""
    from ._staging import _content_key, _normalize
    from .tree_impl import _missing_value, make_bins
    with PROFILER.span("fit.quantize", rows=int(X.shape[0])) as note:
        with PROFILER.span("fit.quantize.key"):
            Xc = _normalize(X)
            missing = _missing_value(missing)
            key = (_content_key(Xc), _content_key(_normalize(y32)),
                   int(max_bins), tuple(sorted((categorical or {}).items())),
                   missing)
        while True:
            with _bins_lock:
                hit = _bins_cache.get(key)
                if hit is None and key not in _bins_inflight:
                    _bins_inflight[key] = _threading.Event()
                    break  # this thread computes
                waiter = _bins_inflight.get(key) if hit is None else None
            if hit is not None:
                note["hit"] = True
                return hit
            # another tuning trial is quantizing the SAME matrix: wait for
            # it instead of paying the ~0.3s re-binning the cache exists to
            # avoid
            waiter.wait()
        note["hit"] = False
        try:
            hit = make_bins(Xc, y32, max_bins, categorical,
                            missing=missing)  # its own span
            cost = hit[0].nbytes
            with _bins_lock:
                _bins_cache[key] = hit
                _bins_cache_order.append((key, cost))
                _bins_cache_bytes[0] += cost
                while _bins_cache_bytes[0] > _BINS_CACHE_MAX_BYTES \
                        and len(_bins_cache_order) > 1:
                    old, old_cost = _bins_cache_order.pop(0)
                    _bins_cache.pop(old, None)
                    _bins_cache_bytes[0] -= old_cost
        finally:
            with _bins_lock:
                ev = _bins_inflight.pop(key, None)
            if ev is not None:
                ev.set()
        return hit


def _fit_ensemble(X: np.ndarray, y: np.ndarray, *, categorical: Dict[int, int],
                  max_depth: int, max_bins: int, min_instances: int,
                  min_info_gain: float, n_trees: int, feature_k: Optional[int],
                  bootstrap: bool, subsample: float, seed: int, loss: str,
                  step_size: float = 0.1, reg_lambda: float = 0.0,
                  gamma: float = 0.0, boosting: bool = False,
                  missing: Optional[float] = None,
                  rounds_per_dispatch: Optional[int] = None,
                  prebinned=None, baseline_sketch=None,
                  on_rounds=None) -> _EnsembleSpec:
    """The one training path behind every tree learner: bin on host, then
    the WHOLE forest/boosting fit runs as a single on-device program
    (`tree_impl.fit_ensemble_on_device`).

    `prebinned=(binned, binning)` is the out-of-core entry
    (`ml/_chunked.py`): the compact matrix was quantized CHUNK BY CHUNK
    and (on the device route) its assembled device copy already sits in
    the bin cache, so X may be None — the raw float data never existed
    whole. Everything downstream is the SAME code path as the monolithic
    fit, which makes chunked-vs-monolithic bit-parity a structural
    property rather than a numerical accident.

    X is read and never written (it may be the block a frame's
    `_featurized` memo holds), nor copied: a `missing` that is a number
    (xgboost's) goes to the two readers that took the NaNs a copy used to
    carry, the quantizer's jobs and the drift baseline's sample."""
    from ._staging import routed_for
    y32 = np.asarray(y, np.float32)
    if prebinned is not None:
        binned, binning = prebinned
        F = binned.shape[1]
    else:
        F = X.shape[1]
        # bin on host FIRST so the dispatcher can probe the staging cache
        # with the actual device operand; histogram builds dominate the
        # program: trees x levels x (n x F x bins) one-hot accumulations
        binned, binning = _cached_bins(X, y32, max_bins, categorical,
                                       missing)
    # measured host-mesh rate for this program is ~1.2e9 ops/s (one-hot
    # expansion defeats CPU BLAS) — scatter-class, not blas
    hint = dispatch.WorkHint(
        flops=2.0 * n_trees * max_depth * binned.shape[0] * F * max_bins,
        kind="scatter")
    with routed_for(hint, binned):
        with PROFILER.span("fit.stage", rows=int(binned.shape[0])) as note:
            put = RECORDER.counters().get("staging.h2d_bytes", 0.0)
            staged = stage_tree_data(X, y32, max_bins, categorical,
                                     prebinned=(binned, binning))
            y_dev = stage_aligned(y32, staged.n_padded)
            note["bytes"] = int(RECORDER.counters().get(
                "staging.h2d_bytes", 0.0) - put)
            note["hit"] = note["bytes"] == 0
        spec = TreeSpec(max_depth=max_depth, n_bins=max_bins, n_features=F,
                        feature_k=feature_k or F, min_instances=min_instances,
                        min_info_gain=min_info_gain, reg_lambda=reg_lambda,
                        gamma=gamma)
        es = tree_impl.EnsembleSpec(
            tree=spec, n_trees=n_trees, loss=loss, boosting=boosting,
            bootstrap=bootstrap and n_trees > 1, subsample=float(subsample),
            step_size=float(step_size))
        trees, base = tree_impl.fit_ensemble_on_device(
            staged.binned_dev, y_dev, staged.mask_dev, es, seed=seed,
            rounds_per_dispatch=rounds_per_dispatch, on_rounds=on_rounds)
    mode = "binary" if loss == "logistic" else "regression"
    if boosting:
        weights = np.full(len(trees), step_size, dtype=np.float32)
        spec = _EnsembleSpec(trees, max_depth, staged.binning, weights,
                             base, F, mode)
    else:
        spec = _EnsembleSpec(trees, max_depth, staged.binning, None, 0.0,
                             F, mode)
    # training drift baseline (obs/drift.py): features + label + the
    # model's own training predictions, sketched from a strided
    # subsample bounded by sml.obs.driftBaselineRows (the chunked path
    # passes its full-data ingest sketch instead). Host-side numpy only
    # — capture must not perturb the fit's program/dispatch counters
    spec.baseline = _capture_baseline(X, y32, categorical, spec, binned,
                                      baseline_sketch, missing)
    return spec


def _capture_baseline(X, y32, categorical, spec, binned, sketch,
                      missing=None):
    """`drift.capture_fit_baseline` under its span: with the recorder on
    it is a host pass inside every fit (a sketch of the strided rows and
    a NumPy descent of them through every tree)."""
    from ..obs import drift as _drift
    with PROFILER.span("fit.baseline", trees=len(spec.trees)) as note:
        baseline = _drift.capture_fit_baseline(
            X, y32, categorical, spec, binned=binned, sketch=sketch,
            missing=missing)
        note["rows"] = None if baseline is None else baseline.sampled_rows
    return baseline


def _resume_ensemble(spec: _EnsembleSpec, binned: np.ndarray,
                     y32: np.ndarray, *, n_new_trees: int, seed: int,
                     feature_k: Optional[int] = None, min_instances: int = 1,
                     min_info_gain: float = 0.0, reg_lambda: float = 0.0,
                     gamma: float = 0.0, subsample: float = 1.0,
                     bootstrap: bool = False,
                     step_size: Optional[float] = None,
                     loss: Optional[str] = None,
                     rounds_per_dispatch: Optional[int] = None,
                     X: Optional[np.ndarray] = None, baseline_sketch=None,
                     on_rounds=None) -> _EnsembleSpec:
    """Warm-start core shared by the monolithic (`warm_start_ensemble`)
    and chunked (`ml/_chunked.warm_start_ensemble_chunked`) paths: stage
    the matrix ALREADY QUANTIZED under the saved spec's binning (the
    appended rounds must split on the bin ids the saved trees
    reference), replay the saved rounds' margin on device, and append
    `n_new_trees` boosting rounds through the same staged dispatch a
    fresh fit uses. Round t of the combined ensemble draws the same
    sampling/feature stream whether it was fitted monolithically or
    appended later (the fold_in(t) streams are round-indexed), so k
    rounds + warm-start (N-k) rounds == N rounds bit-identically on the
    same data/seed (tests/test_ct.py pins it)."""
    if spec.tree_weights is None:
        raise ValueError(
            "warm start needs a boosted spec (GBT/xgboost): forest/DT "
            "trees average independent rounds — refit those whole")
    saved_step = float(spec.tree_weights[0])
    step = float(step_size) if step_size is not None else saved_step
    if np.float32(step) != np.float32(saved_step):
        # the margin replay and the combined weight vector both apply
        # ONE step to every round: a different step would silently
        # rescale the SAVED rounds' contribution, changing the
        # incumbent's predictions retroactively
        raise ValueError(
            f"warm start cannot change step_size: the saved rounds were "
            f"fitted at {saved_step} (got {step}); refit full to move it")
    loss = loss or ("logistic" if spec.mode == "binary" else "squared")
    F = spec.n_features
    max_bins = spec.binning.edges.shape[1] + 1
    n_total = len(spec.trees) + int(n_new_trees)
    from ._staging import routed_for
    hint = dispatch.WorkHint(
        flops=2.0 * n_new_trees * spec.depth * binned.shape[0] * F
        * max_bins, kind="scatter")
    with routed_for(hint, binned):
        staged = stage_tree_data(X, y32, max_bins, None,
                                 prebinned=(binned, spec.binning))
        tspec = TreeSpec(max_depth=spec.depth, n_bins=max_bins,
                         n_features=F, feature_k=feature_k or F,
                         min_instances=min_instances,
                         min_info_gain=min_info_gain,
                         reg_lambda=reg_lambda, gamma=gamma)
        es = tree_impl.EnsembleSpec(
            tree=tspec, n_trees=n_total, loss=loss, boosting=True,
            bootstrap=bool(bootstrap) and n_total > 1,
            subsample=float(subsample), step_size=step)
        y_dev = stage_aligned(y32, staged.n_padded)
        new_trees, base = tree_impl.resume_ensemble_on_device(
            staged.binned_dev, y_dev, staged.mask_dev, es, seed=seed,
            init_trees=spec.trees, base=float(spec.base),
            rounds_per_dispatch=rounds_per_dispatch, on_rounds=on_rounds)
    trees = list(spec.trees) + list(new_trees)
    weights = np.full(len(trees), step, dtype=np.float32)
    out = _EnsembleSpec(trees, spec.depth, spec.binning, weights,
                        float(spec.base), F, spec.mode)
    categorical = {f: len(r) for f, r in spec.binning.cat_remap.items()}
    out.baseline = _capture_baseline(X, y32, categorical, out, binned,
                                     baseline_sketch)
    return out


def warm_start_ensemble(spec: _EnsembleSpec, X: np.ndarray, y: np.ndarray,
                        *, n_new_trees: int, seed: int,
                        **resume_kwargs) -> _EnsembleSpec:
    """Resume a saved boosted `_EnsembleSpec` on in-memory (X, y):
    quantize with the SAVED binning (`bin_with` — warm-started rounds
    never move the bin edges) and append `n_new_trees` rounds. Keyword
    knobs mirror `_fit_ensemble`'s (subsample, step_size, feature_k,
    rounds_per_dispatch, ...); step_size/loss default to the saved
    spec's. The out-of-core twin is
    `ml/_chunked.warm_start_ensemble_chunked`."""
    X = np.asarray(X)
    y32 = np.asarray(y, np.float32)
    binned = bin_with(X, spec.binning)
    return _resume_ensemble(spec, binned, y32, n_new_trees=n_new_trees,
                            seed=seed, X=X, **resume_kwargs)


def _fit_ensemble_folds(Xs, ys, cats, *, max_depth: int, max_bins: int,
                        min_instances: int, min_info_gain: float,
                        n_trees: int, feature_k: Optional[int],
                        bootstrap: bool, subsample: float, seed: int,
                        loss: str = "squared") -> List[_EnsembleSpec]:
    """`_fit_ensemble` for k SAME-SPEC fold datasets in one vmapped device
    program (`tree_impl.fit_ensembles_folds`): CV's fold fits share every
    static shape, so one dispatch replaces k. Binning stays per fold (each
    fold's quantile edges come from ITS rows, matching the sequential
    path's models exactly in structure)."""
    from ._staging import routed_for
    binned_list, binnings, y32s = [], [], []
    for X, y in zip(Xs, ys):
        y32 = np.asarray(y, np.float32)
        binned, binning = _cached_bins(X, y32, max_bins, cats)
        binned_list.append(binned)
        binnings.append(binning)
        y32s.append(y32)
    F = Xs[0].shape[1]
    n_total = sum(b.shape[0] for b in binned_list)
    # stack BEFORE routing so the router prices/promotes the exact
    # axis-1-sharded arrays the program stages (probing the per-fold 2-D
    # arrays would discount/promote dead copies)
    bst, yst, mst = tree_impl.build_fold_stacks(binned_list, y32s)
    hint = dispatch.WorkHint(
        flops=2.0 * n_trees * max_depth * n_total * F * max_bins,
        kind="scatter")
    with routed_for(hint, bst, yst, mst, stacked=True):
        spec = TreeSpec(max_depth=max_depth, n_bins=max_bins, n_features=F,
                        feature_k=feature_k or F, min_instances=min_instances,
                        min_info_gain=min_info_gain, reg_lambda=0.0,
                        gamma=0.0)
        es = tree_impl.EnsembleSpec(
            tree=spec, n_trees=n_trees, loss=loss, boosting=False,
            bootstrap=bootstrap and n_trees > 1, subsample=float(subsample),
            step_size=0.1)
        results = tree_impl.fit_ensembles_folds(bst, yst, mst, es, seed)
    mode = "binary" if loss == "logistic" else "regression"
    return [_EnsembleSpec(trees, max_depth, binnings[k], None, 0.0, F, mode)
            for k, (trees, base) in enumerate(results)]


def _fit_ensembles_grid(Xs, ys, cats, trials, max_fused: int,
                        loss: str = "squared"):
    """GRID-FUSED CV fits: `trials` carries one hyperparameter config per
    grid point (max_depth, max_bins, min_instances, min_info_gain,
    n_trees, feature_k (None = all features), bootstrap, subsample,
    seed); every (grid point, fold) pair becomes one ELEMENT of the
    trial-batched device program (`tree_impl.fit_ensembles_trials`),
    dispatched in chunks of `max_fused` elements — a G-point grid over k
    folds costs ceil(G*k / max_fused) tree-fit dispatches instead of G.

    Static shapes are the grid MAXIMA (depth/bins/trees), so the whole
    grid shares ONE compiled program per chunk width; each element gates
    itself down to its own hyperparameters with traced scalars, and its
    extra trees/nodes are sliced away host-side. Binning stays per
    (fold, maxBins): a grid over maxBins legitimately re-quantizes,
    everything else reuses the fold's cached matrices.

    On a multi-device mesh the fused elements may SHARD across a second
    "trial" mesh axis instead of all-replicating (cross-chip trial
    parallelism — `sml.cv.trialAxisDevices` /
    `tree_impl._trial_axis_width` decide placement inside
    `fit_ensembles_trials`); dispatch counts and results are unchanged
    up to float reduction order.

    Returns {(grid_index, fold_index): _EnsembleSpec}."""
    import jax

    from ..parallel import mesh as _meshlib
    from ._staging import routed_for

    F = Xs[0].shape[1]
    k = len(Xs)
    y32s = [np.asarray(y, np.float32) for y in ys]
    binned: Dict[tuple, np.ndarray] = {}
    binnings: Dict[tuple, object] = {}
    for mb in sorted({t["max_bins"] for t in trials}):
        for fi, (X, y32) in enumerate(zip(Xs, y32s)):
            b, bn = _cached_bins(X, y32, mb, cats)
            binned[(fi, mb)] = b
            binnings[(fi, mb)] = bn
    D = max(t["max_depth"] for t in trials)
    B = max(t["max_bins"] for t in trials)
    T = max(t["n_trees"] for t in trials)
    mesh = _meshlib.get_mesh()
    n_dev = _meshlib.data_width(mesh)
    n_pad = max(_meshlib.bucket_rows(b.shape[0], n_dev)
                for b in binned.values())
    stack_dtype = np.result_type(*[b.dtype for b in binned.values()])
    spec = TreeSpec(max_depth=D, n_bins=B, n_features=F, feature_k=F,
                    min_instances=1, min_info_gain=0.0, reg_lambda=0.0,
                    gamma=0.0)
    es = tree_impl.EnsembleSpec(tree=spec, n_trees=T, loss=loss,
                                boosting=False, bootstrap=False,
                                subsample=1.0, step_size=0.1)
    elems = [(gi, fi) for gi in range(len(trials)) for fi in range(k)]
    mode = "binary" if loss == "logistic" else "regression"
    out: Dict[tuple, _EnsembleSpec] = {}
    max_fused = max(1, int(max_fused))
    for lo in range(0, len(elems), max_fused):
        chunk = elems[lo:lo + max_fused]
        E = len(chunk)
        bst = np.zeros((E, n_pad, F), dtype=stack_dtype)
        yst = np.zeros((E, n_pad), dtype=np.float32)
        mst = np.zeros((E, n_pad), dtype=np.float32)
        depth = np.zeros(E, np.int32)
        feat_k = np.zeros(E, np.int32)
        min_inst = np.zeros(E, np.float32)
        min_gain = np.zeros(E, np.float32)
        boot = np.zeros(E, bool)
        sub = np.ones(E, np.float32)
        rngs = np.zeros((E, 2), np.uint32)
        n_rows = 0
        for e, (gi, fi) in enumerate(chunk):
            t = trials[gi]
            b = binned[(fi, t["max_bins"])]
            bst[e, :b.shape[0]] = b
            yst[e, :len(y32s[fi])] = y32s[fi]
            mst[e, :len(y32s[fi])] = 1.0
            n_rows += b.shape[0]
            depth[e] = t["max_depth"]
            feat_k[e] = t["feature_k"] or F
            min_inst[e] = t["min_instances"]
            min_gain[e] = t["min_info_gain"]
            boot[e] = bool(t["bootstrap"]) and t["n_trees"] > 1
            sub[e] = t["subsample"]
            rngs[e] = np.asarray(
                jax.random.key_data(jax.random.PRNGKey(int(t["seed"]))),
                np.uint32)
        hint = dispatch.WorkHint(
            flops=2.0 * T * D * n_rows * F * B, kind="scatter")
        with routed_for(hint, bst, yst, mst, stacked=True):
            packs, _bases = tree_impl.fit_ensembles_trials(
                bst, yst, mst, es, rngs, depth, feat_k, min_inst,
                min_gain, boot, sub)
        for e, (gi, fi) in enumerate(chunk):
            t = trials[gi]
            trees = tree_impl._unpack_trees(packs[e][:t["n_trees"]])
            out[(gi, fi)] = _EnsembleSpec(
                trees, int(t["max_depth"]),
                binnings[(fi, t["max_bins"])], None, 0.0, F, mode)
    return out


# ---------------------------------------------------------------------------
class _TreeModelBase(Model, _TreeParams):
    """Shared transform/persistence for tree ensemble models."""

    def __init__(self, spec: Optional[_EnsembleSpec] = None):
        super().__init__()
        self._spec = spec

    @property
    def featureImportances(self) -> DenseVector:
        return DenseVector(feature_importances(self._spec.trees,
                                               self._spec.n_features))

    @property
    def numFeatures(self) -> int:
        return self._spec.n_features

    def getNumTrees(self) -> int:
        return len(self._spec.trees)

    @property
    def treeWeights(self) -> List[float]:
        if self._spec.tree_weights is None:
            return [1.0] * len(self._spec.trees)
        return [float(w) for w in self._spec.tree_weights]

    @property
    def toDebugString(self) -> str:
        lines = [f"{type(self).__name__} with {len(self._spec.trees)} trees, "
                 f"depth {self._spec.depth}"]
        t0 = self._spec.trees[0]
        for node in range(min(len(t0.split_feature), 15)):
            f = int(t0.split_feature[node])
            if f >= 0:
                lines.append(f"  node {node}: split feature {f} "
                             f"@bin {int(t0.split_bin[node])} "
                             f"gain {float(t0.gain[node]):.4f}")
            else:
                lines.append(f"  node {node}: leaf "
                             f"value {float(t0.leaf_value[node]):.4f}")
        return "\n".join(lines)

    def _margin(self, pdf: pd.DataFrame) -> np.ndarray:
        X = extract_features(pdf, self.getOrDefault("featuresCol"))
        return self._spec.predict_margin(X)

    def _save_state(self, path):
        self._spec.save(path)

    def _load_state(self, path, meta):
        self._spec = _EnsembleSpec.load(path)


def fused_reg_stats_from_matrix(spec, X: np.ndarray, lab: np.ndarray,
                                link: str = "identity"):
    """The fused traverse+metric device pass over a raw feature matrix:
    bins (content-memoized), routes, and — on the device route — returns
    the five regression sufficient statistics from ONE program dispatch
    (D2H is five scalars). Returns None on the host route or a shape it
    screens out; callers then take the ordinary predict+stats path. An
    error from the device program itself propagates. Shared by the bare
    tree-model hook and the fused-pipeline hook."""
    if spec.mode != "regression":
        return None
    if link != "identity":
        import jax.numpy as _jnp
        if getattr(_jnp, link, None) is None:
            return None  # unresolvable device link: materialize path wins
    with PROFILER.span("binning.predict", rows=int(X.shape[0])):
        binned = bin_with(np.asarray(X, dtype=np.float64), spec.binning)
    n = binned.shape[0]
    if n != len(lab):
        return None
    finite = np.isfinite(lab)
    l32 = np.where(finite, lab, 0.0).astype(np.float32)
    f32 = finite.astype(np.float32)
    # compact quantized dtype preserved: the eval program shares the fit's
    # bin-cache device copy instead of staging an int32 duplicate
    binned_q = np.ascontiguousarray(binned)
    hint = dispatch.WorkHint(
        flops=(4.0 * len(spec.trees) * spec.depth + 10.0) * n,
        kind="traverse", out_bytes=64.0)
    from ._staging import routed_for, run_data_parallel
    with routed_for(hint, binned_q, l32, f32) as mesh:
        if dispatch.is_host_mesh(mesh):
            return None  # host route: ordinary path is cheaper
        from .inference import forest_eval_fn, resolve_infer_kernel
        sf, sb, lv, w = spec.stacked()
        kernel, block_rows = resolve_infer_kernel(
            n_trees=sf.shape[0], n_nodes=sf.shape[1],
            n_feat=binned_q.shape[1])
        stats = run_data_parallel(
            forest_eval_fn(spec.depth, link, kernel, block_rows),
            binned_q, l32, f32,
            replicated=(np.asarray(sf), np.asarray(sb),
                        np.asarray(lv, dtype=np.float32),
                        np.asarray(w, dtype=np.float32),
                        np.float32(spec.base)))
    return tuple(float(s) for s in stats)


class _TreeEvalHook(RegStatsHook):
    """Evaluator pushdown for lazy BARE tree-regression transforms (the
    CV/tuning shape: model.transform(featurized_frame)): the whole
    predict+metric computes as ONE device program
    (`inference.forest_eval_fn`) returning five scalars, instead of
    materializing a prediction column (host traversal or a 3.2MB/800k-row
    D2H) and re-uploading pred/label for the stats pass."""

    def _features(self, raw):
        return extract_features(
            raw, self._tail.getOrDefault("featuresCol")), None

    def _stats(self, X, lab):
        return fused_reg_stats_from_matrix(self._tail._spec, X, lab,
                                           link=self._link)


class _TreeRegressionModel(_TreeModelBase):
    def _transform(self, df):
        oc = self.getOrDefault("predictionCol")

        def fn(pdf, ctx):
            out = pdf.copy(deep=False)  # CoW: column adds never touch the parent
            if len(out) == 0:
                out[oc] = pd.Series(dtype=float)
                return out
            out[oc] = self._margin(out)
            return out

        out = df._derive_rowlocal(fn)
        out._fused_eval = _TreeEvalHook(self, df)
        return out


class _TreeClassificationModel(_TreeModelBase):
    def _transform(self, df):
        oc = self.getOrDefault("predictionCol")
        rc = self.getOrDefault("rawPredictionCol")
        prc = self.getOrDefault("probabilityCol")

        def fn(pdf, ctx):
            out = pdf.copy(deep=False)  # CoW: column adds never touch the parent
            if len(out) == 0:
                for c in (rc, prc):
                    out[c] = pd.Series(dtype=object)
                out[oc] = pd.Series(dtype=float)
                return out
            m = self._margin(out)
            if self._spec.tree_weights is None:  # forest of probability leaves
                p1 = np.clip(m, 0.0, 1.0)
            else:  # boosted margins
                p1 = 1.0 / (1.0 + np.exp(-m))
            probs = np.stack([1 - p1, p1], axis=1)
            out[rc] = vector_series(probs, index=out.index)
            out[prc] = vector_series(probs.copy(), index=out.index)
            out[oc] = (p1 > 0.5).astype(float)
            return out

        return df._derive_rowlocal(fn)


# ------------------------------------------------------------ estimators
class _TreeEstimatorBase(Estimator, _TreeParams):
    _is_classifier = False
    _loss = "squared"

    def _extract(self, df):
        with PROFILER.span("fit.featurize") as note, \
                PROFILER.span("fit.featurize.extract"):
            X, y, _ = extract_xy(df, self.getOrDefault("featuresCol"),
                                 self.getOrDefault("labelCol"))
            ok = np.isfinite(y)
            if ok.all():
                # X IS the block it was handed (the column plan's)
                PROFILER.count("featurize.extract.whole")
            else:
                X, y = X[ok], y[ok]
                PROFILER.count("featurize.extract.gathered")
            note["rows"], note["bytes"] = int(X.shape[0]), int(X.nbytes)
        return X, y, _categorical_slots(df, self.getOrDefault("featuresCol"))

    def _seed(self) -> int:
        s = self.getOrDefault("seed")
        return int(s) if s is not None else 17

    def fit_chunked(self, source):
        """Out-of-core fit: the same estimator params applied to a
        `frame._chunks.ChunkSource` through the streamed-quantization
        ingest (`ml/_chunked.py`) — the raw dataset is never resident
        whole. Returns the same model class `.fit` would (DT/RF/GBT,
        regressor/classifier); an exact-mode sketch makes the model
        bit-identical to fitting the materialized frame."""
        from ._chunked import fit_ensemble_chunked
        kwargs = dict(
            categorical={},
            max_depth=int(self.getOrDefault("maxDepth")),
            max_bins=int(self.getOrDefault("maxBins")),
            min_instances=int(self.getOrDefault("minInstancesPerNode")),
            min_info_gain=float(self.getOrDefault("minInfoGain")),
            seed=self._seed(),
            loss="logistic" if self._is_classifier else "squared")
        if self.hasParam("maxIter"):        # boosted (GBT) shape
            kwargs.update(
                n_trees=int(self.getOrDefault("maxIter")), feature_k=None,
                bootstrap=False,
                subsample=float(self.getOrDefault("subsamplingRate")),
                step_size=float(self.getOrDefault("stepSize")),
                boosting=True)
        elif self.hasParam("numTrees"):     # bootstrap-forest shape
            kwargs.update(
                n_trees=int(self.getOrDefault("numTrees")),
                feature_k=_feature_k(
                    self.getOrDefault("featureSubsetStrategy"),
                    source.n_features, self._is_classifier),
                bootstrap=True,
                subsample=float(self.getOrDefault("subsamplingRate")))
        else:                               # single decision tree
            kwargs.update(n_trees=1, feature_k=None, bootstrap=False,
                          subsample=1.0)
        spec = fit_ensemble_chunked(source, **kwargs)
        cls = getattr(self, "_model_cls", None) \
            or _CHUNKED_MODEL_FOR[type(self).__name__]
        m = cls(spec)
        m._inherit_params(self)
        return m


class DecisionTreeRegressor(_TreeEstimatorBase):
    def _init_params(self):
        self._declare_tree_params()

    def __init__(self, featuresCol=None, labelCol=None, predictionCol=None,
                 maxDepth=None, maxBins=None, minInstancesPerNode=None,
                 minInfoGain=None, seed=None):
        super().__init__()
        self._set(featuresCol=featuresCol, labelCol=labelCol,
                  predictionCol=predictionCol, maxDepth=maxDepth,
                  maxBins=maxBins, minInstancesPerNode=minInstancesPerNode,
                  minInfoGain=minInfoGain, seed=seed)

    def setMaxBins(self, v):
        return self._set(maxBins=v)

    def setMaxDepth(self, v):
        return self._set(maxDepth=v)

    def _fit(self, df):
        X, y, cat = self._extract(df)
        spec = _fit_ensemble(
            X, y, categorical=cat,
            max_depth=int(self.getOrDefault("maxDepth")),
            max_bins=int(self.getOrDefault("maxBins")),
            min_instances=int(self.getOrDefault("minInstancesPerNode")),
            min_info_gain=float(self.getOrDefault("minInfoGain")),
            n_trees=1, feature_k=None, bootstrap=False, subsample=1.0,
            seed=self._seed(), loss="squared")
        m = DecisionTreeRegressionModel(spec)
        m._inherit_params(self)
        return m


class DecisionTreeRegressionModel(_TreeRegressionModel):
    def _init_params(self):
        DecisionTreeRegressor._init_params(self)

    @property
    def depth(self) -> int:
        return self._spec.depth


class DecisionTreeClassifier(_TreeEstimatorBase):
    _is_classifier = True

    def _init_params(self):
        self._declare_tree_params()
        self._declareParam("rawPredictionCol", default="rawPrediction", doc="raw scores")
        self._declareParam("probabilityCol", default="probability", doc="probabilities")

    def __init__(self, featuresCol=None, labelCol=None, predictionCol=None,
                 maxDepth=None, maxBins=None, minInstancesPerNode=None,
                 minInfoGain=None, seed=None):
        super().__init__()
        self._set(featuresCol=featuresCol, labelCol=labelCol,
                  predictionCol=predictionCol, maxDepth=maxDepth,
                  maxBins=maxBins, minInstancesPerNode=minInstancesPerNode,
                  minInfoGain=minInfoGain, seed=seed)

    def setMaxBins(self, v):
        return self._set(maxBins=v)

    def _fit(self, df):
        X, y, cat = self._extract(df)
        spec = _fit_ensemble(
            X, y, categorical=cat,
            max_depth=int(self.getOrDefault("maxDepth")),
            max_bins=int(self.getOrDefault("maxBins")),
            min_instances=int(self.getOrDefault("minInstancesPerNode")),
            min_info_gain=float(self.getOrDefault("minInfoGain")),
            n_trees=1, feature_k=None, bootstrap=False, subsample=1.0,
            seed=self._seed(), loss="logistic")
        m = DecisionTreeClassificationModel(spec)
        m._inherit_params(self)
        return m


class DecisionTreeClassificationModel(_TreeClassificationModel):
    def _init_params(self):
        DecisionTreeClassifier._init_params(self)


class RandomForestRegressor(_TreeEstimatorBase):
    def _init_params(self):
        self._declare_tree_params()
        self._declareParam("numTrees", default=20, doc="number of trees")
        self._declareParam("featureSubsetStrategy", default="auto",
                           doc="auto|all|sqrt|log2|onethird|fraction")
        self._declareParam("subsamplingRate", default=1.0, doc="bootstrap rate")

    def __init__(self, featuresCol=None, labelCol=None, predictionCol=None,
                 maxDepth=None, maxBins=None, numTrees=None,
                 featureSubsetStrategy=None, subsamplingRate=None,
                 minInstancesPerNode=None, minInfoGain=None, seed=None):
        super().__init__()
        self._set(featuresCol=featuresCol, labelCol=labelCol,
                  predictionCol=predictionCol, maxDepth=maxDepth,
                  maxBins=maxBins, numTrees=numTrees,
                  featureSubsetStrategy=featureSubsetStrategy,
                  subsamplingRate=subsamplingRate,
                  minInstancesPerNode=minInstancesPerNode,
                  minInfoGain=minInfoGain, seed=seed)

    def setMaxBins(self, v):
        return self._set(maxBins=v)

    def _fit(self, df):
        X, y, cat = self._extract(df)
        F = X.shape[1]
        spec = _fit_ensemble(
            X, y, categorical=cat,
            max_depth=int(self.getOrDefault("maxDepth")),
            max_bins=int(self.getOrDefault("maxBins")),
            min_instances=int(self.getOrDefault("minInstancesPerNode")),
            min_info_gain=float(self.getOrDefault("minInfoGain")),
            n_trees=int(self.getOrDefault("numTrees")),
            feature_k=_feature_k(self.getOrDefault("featureSubsetStrategy"),
                                 F, self._is_classifier),
            bootstrap=True,
            subsample=float(self.getOrDefault("subsamplingRate")),
            seed=self._seed(), loss="squared")
        m = RandomForestRegressionModel(spec)
        m._inherit_params(self)
        return m


class RandomForestRegressionModel(_TreeRegressionModel):
    def _init_params(self):
        RandomForestRegressor._init_params(self)


class RandomForestClassifier(RandomForestRegressor):
    _is_classifier = True

    def _init_params(self):
        RandomForestRegressor._init_params(self)
        self._declareParam("rawPredictionCol", default="rawPrediction", doc="raw scores")
        self._declareParam("probabilityCol", default="probability", doc="probabilities")

    def _fit(self, df):
        X, y, cat = self._extract(df)
        F = X.shape[1]
        spec = _fit_ensemble(
            X, y, categorical=cat,
            max_depth=int(self.getOrDefault("maxDepth")),
            max_bins=int(self.getOrDefault("maxBins")),
            min_instances=int(self.getOrDefault("minInstancesPerNode")),
            min_info_gain=float(self.getOrDefault("minInfoGain")),
            n_trees=int(self.getOrDefault("numTrees")),
            feature_k=_feature_k(self.getOrDefault("featureSubsetStrategy"),
                                 F, True),
            bootstrap=True,
            subsample=float(self.getOrDefault("subsamplingRate")),
            seed=self._seed(), loss="logistic")
        m = RandomForestClassificationModel(spec)
        m._inherit_params(self)
        return m


class RandomForestClassificationModel(_TreeClassificationModel):
    def _init_params(self):
        RandomForestClassifier._init_params(self)


class GBTRegressor(_TreeEstimatorBase):
    def _init_params(self):
        self._declare_tree_params()
        self._declareParam("maxIter", default=20, doc="boosting rounds")
        self._declareParam("stepSize", default=0.1, doc="learning rate")
        self._declareParam("subsamplingRate", default=1.0, doc="row subsample per round")

    def __init__(self, featuresCol=None, labelCol=None, predictionCol=None,
                 maxDepth=None, maxBins=None, maxIter=None, stepSize=None,
                 subsamplingRate=None, minInstancesPerNode=None,
                 minInfoGain=None, seed=None):
        super().__init__()
        self._set(featuresCol=featuresCol, labelCol=labelCol,
                  predictionCol=predictionCol, maxDepth=maxDepth,
                  maxBins=maxBins, maxIter=maxIter, stepSize=stepSize,
                  subsamplingRate=subsamplingRate,
                  minInstancesPerNode=minInstancesPerNode,
                  minInfoGain=minInfoGain, seed=seed)

    _loss = "squared"
    _model_cls = None  # set below

    def _fit(self, df):
        X, y, cat = self._extract(df)
        spec = _fit_ensemble(
            X, y, categorical=cat,
            max_depth=int(self.getOrDefault("maxDepth")),
            max_bins=int(self.getOrDefault("maxBins")),
            min_instances=int(self.getOrDefault("minInstancesPerNode")),
            min_info_gain=float(self.getOrDefault("minInfoGain")),
            n_trees=int(self.getOrDefault("maxIter")), feature_k=None,
            bootstrap=False,
            subsample=float(self.getOrDefault("subsamplingRate")),
            seed=self._seed(), loss=self._loss,
            step_size=float(self.getOrDefault("stepSize")), boosting=True)
        m = self._model_cls(spec)
        m._inherit_params(self)
        return m


class GBTRegressionModel(_TreeRegressionModel):
    def _init_params(self):
        GBTRegressor._init_params(self)


GBTRegressor._model_cls = GBTRegressionModel


class GBTClassifier(GBTRegressor):
    _is_classifier = True
    _loss = "logistic"

    def _init_params(self):
        GBTRegressor._init_params(self)
        self._declareParam("rawPredictionCol", default="rawPrediction", doc="raw scores")
        self._declareParam("probabilityCol", default="probability", doc="probabilities")


class GBTClassificationModel(_TreeClassificationModel):
    def _init_params(self):
        GBTClassifier._init_params(self)


GBTClassifier._model_cls = GBTClassificationModel

#: estimator -> model class for `_TreeEstimatorBase.fit_chunked` (the
#: DT/RF classes construct their models inline in `_fit`; GBT's
#: `_model_cls` attribute wins when present)
_CHUNKED_MODEL_FOR = {
    "DecisionTreeRegressor": DecisionTreeRegressionModel,
    "DecisionTreeClassifier": DecisionTreeClassificationModel,
    "RandomForestRegressor": RandomForestRegressionModel,
    "RandomForestClassifier": RandomForestClassificationModel,
}
