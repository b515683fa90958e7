"""KMeans — Lloyd's map/reduce as one jitted mesh program (SURVEY §2.2 P5).

The reference teaches K-Means as the canonical distributed map (assign) /
reduce (recompute centers) algorithm, "communication is key"
(`SML/ML Electives/MLE 02 - K-Means.py:183-204`). Here both phases fuse into
a single XLA program per fit: the whole Lloyd's loop runs on-device via
`lax.fori_loop`, each iteration doing a vmapped distance kernel on the MXU
and ONE psum of per-cluster (sum, count) over ICI — no host round trips.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd

from ..parallel import collectives as coll
from .base import Estimator, Model, load_arrays, save_arrays
from .linalg import DenseVector
from ._staging import data_parallel, extract_features, stage_sharded


from functools import lru_cache


@lru_cache(maxsize=64)
def _lloyd_program(k: int, max_iter: int):
    def program(X, mask, init_centers):
        def step(_, centers):
            d2 = (jnp.sum(X * X, axis=1, keepdims=True)
                  - 2 * X @ centers.T
                  + jnp.sum(centers * centers, axis=1)[None, :])
            assign = jnp.argmin(d2, axis=1)
            onehot = jax.nn.one_hot(assign, k, dtype=X.dtype) * mask[:, None]
            sums = coll.psum(onehot.T @ X)          # (k, d) partial → allreduce
            counts = coll.psum(jnp.sum(onehot, axis=0))
            return jnp.where(counts[:, None] > 0, sums / counts[:, None],
                             centers)

        centers = jax.lax.fori_loop(0, max_iter, step, init_centers)
        # final assignment + cost
        d2 = (jnp.sum(X * X, axis=1, keepdims=True) - 2 * X @ centers.T
              + jnp.sum(centers * centers, axis=1)[None, :])
        assign = jnp.argmin(d2, axis=1)
        cost = coll.psum(jnp.sum(jnp.min(d2, axis=1) * mask))
        return centers, cost

    return program


class KMeans(Estimator):
    def _init_params(self):
        self._declareParam("featuresCol", default="features", doc="features column")
        self._declareParam("predictionCol", default="prediction", doc="cluster column")
        self._declareParam("k", default=2, doc="number of clusters")
        self._declareParam("maxIter", default=20, doc="Lloyd iterations")
        self._declareParam("seed", default=None, doc="init seed")
        self._declareParam("initMode", default="k-means||", doc="k-means||-style init")
        self._declareParam("tol", default=1e-4, doc="unused (fixed iterations)")

    def __init__(self, featuresCol=None, predictionCol=None, k=None,
                 maxIter=None, seed=None, initMode=None, tol=None):
        super().__init__()
        self._set(featuresCol=featuresCol, predictionCol=predictionCol, k=k,
                  maxIter=maxIter, seed=seed, initMode=initMode, tol=tol)

    def setK(self, v):
        return self._set(k=v)

    def setSeed(self, v):
        return self._set(seed=v)

    def setMaxIter(self, v):
        return self._set(maxIter=v)

    def _fit(self, df) -> "KMeansModel":
        X = extract_features(df, self.getOrDefault("featuresCol"))
        k = int(self.getOrDefault("k"))
        max_iter = int(self.getOrDefault("maxIter"))
        seed = self.getOrDefault("seed")
        rng = np.random.default_rng(int(seed) if seed is not None else 0)
        # k-means++-style seeding on host (cheap: k passes over a sample)
        sample = X[rng.choice(len(X), size=min(len(X), 4096), replace=False)]
        centers = [sample[rng.integers(len(sample))]]
        for _ in range(1, k):
            d2 = np.min(
                ((sample[:, None, :] - np.stack(centers)[None]) ** 2).sum(-1),
                axis=1)
            p = d2 / d2.sum() if d2.sum() > 0 else None
            centers.append(sample[rng.choice(len(sample), p=p)])
        init = np.stack(centers).astype(np.float32)

        from ..parallel import dispatch
        from ._staging import cached_data_parallel, routed_for
        X32 = np.asarray(X, np.float32)
        hint = dispatch.WorkHint(flops=3.0 * max_iter * X.size * k,
                                 kind="blas")
        with routed_for(hint, X32):
            Xd, mask, _ = stage_sharded(X32)
            program = cached_data_parallel(_lloyd_program(k, max_iter),
                                           replicated_argnums=(2,))
            # ONE batched D2H for (centers, cost): per-leaf np.asarray /
            # float() each pay the fixed cost of a device→host read
            final_centers, cost = jax.device_get(program(Xd, mask, init))
        m = KMeansModel(centers=np.asarray(final_centers),
                        trainingCost=float(cost))
        m._inherit_params(self)
        return m


class KMeansSummary:
    def __init__(self, trainingCost: float, k: int):
        self.trainingCost = trainingCost
        self.k = k


class KMeansModel(Model):
    def _init_params(self):
        KMeans._init_params(self)

    def __init__(self, centers: Optional[np.ndarray] = None,
                 trainingCost: float = 0.0):
        super().__init__()
        self._centers = centers
        self._trainingCost = trainingCost

    def clusterCenters(self):
        return [c for c in np.asarray(self._centers, dtype=np.float64)]

    @property
    def summary(self) -> KMeansSummary:
        return KMeansSummary(self._trainingCost, len(self._centers))

    def computeCost(self, df) -> float:
        X = extract_features(df, self.getOrDefault("featuresCol"))
        d2 = ((X[:, None, :] - self._centers[None]) ** 2).sum(-1)
        return float(np.min(d2, axis=1).sum())

    def _transform(self, df):
        oc = self.getOrDefault("predictionCol")
        fc = self.getOrDefault("featuresCol")
        centers = self._centers

        def fn(pdf: pd.DataFrame, ctx) -> pd.DataFrame:
            out = pdf.copy(deep=False)  # CoW: column adds never touch the parent
            if len(out) == 0:
                out[oc] = pd.Series(dtype=int)
                return out
            X = extract_features(out, fc)
            d2 = ((X[:, None, :] - centers[None]) ** 2).sum(-1)
            out[oc] = np.argmin(d2, axis=1).astype(np.int32)
            return out

        return df._derive_rowlocal(fn)

    def _save_state(self, path):
        save_arrays(path, centers=self._centers,
                    cost=np.asarray([self._trainingCost]))

    def _load_state(self, path, meta):
        d = load_arrays(path)
        self._centers = d["centers"]
        self._trainingCost = float(d["cost"][0])


class BisectingKMeans(KMeans):
    """Accepted for surface parity; trains plain KMeans (the course only
    instantiates the default variant)."""
