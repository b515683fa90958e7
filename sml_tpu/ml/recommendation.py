"""ALS — block-parallel alternating least squares (SURVEY §2.2 P4).

The reference's `ALS(userCol, itemCol, ratingCol, rank, maxIter,
coldStartStrategy)` trains MovieLens 1M (`SML/ML Electives/MLE 01 -
Collaborative Filtering Lab.py:159-201`). Spark's implementation blocks
users/items across executors and shuffles factor blocks; here the WHOLE
alternating fit is ONE jitted shard_map program (`fori_loop` over
iterations), each half-step inside it:

    per chip:  segment-sum of (f_i ⊗ f_i, r·f_i) by user  → (U, r, r), (U, r)
    psum       over ICI (the factor-block exchange)
    batched    solve of all U normal systems on-device

with ALS-WR regularization (λ·n_u, Spark's scheme). Ratings AND factors stay
in HBM for the entire fit: one dispatch, one packed factor download."""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd

from ..parallel import collectives as coll
from .base import Estimator, Model, load_arrays, save_arrays
from ._staging import data_parallel


from functools import lru_cache


@lru_cache(maxsize=64)
def _als_fit_program(n_users: int, n_items: int, rank: int, reg: float,
                     max_iter: int, nonneg: bool):
    """The WHOLE alternating fit as one XLA program: `fori_loop` over
    iterations, both half-steps inside, factors living on-device for the
    entire fit. One dispatch per fit instead of 2·maxIter — the per-launch
    round trips disappear, and the CPU test mesh never has multiple
    collective executables racing one rendezvous (r3: 20 async half-step
    launches could deadlock XLA:CPU's cross-module all-reduce).

    SORTED-SEGMENT normal equations, no scatters: `segment_sum` lowers to
    a serialized HBM read-modify-write scatter on TPU and made the
    half-steps ~3x slower than this formulation (measured 1.9s → 0.6s for
    a 10-iteration MovieLens-1M-scale fit). The rating triples are sorted
    by entity ON HOST once per fit (ids are static across iterations, so
    the permutation is too); each shard holds a contiguous slice of the
    sorted order plus its clipped local [start, end) bounds per entity,
    accumulates per-segment sums as cumsum boundary differences (a
    log-depth associative scan that streams at full HBM bandwidth), and
    `psum` merges the per-shard partial normal equations — segments that
    span a shard boundary add up across shards. Padding rows sit past
    every real segment's end, so bounds clipping makes them inert.

    Program args (leading axis row-sharded unless noted):
      ius     item ids in user-sorted order     (rows,)
      usi     user ids in item-sorted order     (rows,)
      rat_u   ratings in user-sorted order      (rows,)
      rat_i   ratings in item-sorted order      (rows,)
      ub      per-shard user bounds             (1, 2, n_users) per shard
      ib      per-shard item bounds             (1, 2, n_items) per shard
      uf0/if0 replicated factor inits
    (No mask arg: padding rows sit past every real segment's end, so the
    clipped bounds already exclude them.)
    """

    def half(other_sorted, rat_sorted, bounds, n_out):
        f = other_sorted
        stats = jnp.concatenate(
            [(f[:, :, None] * f[:, None, :]).reshape(f.shape[0],
                                                     rank * rank),
             f * rat_sorted[:, None]], axis=1)
        hi, lo = _ds_cumsum(stats)
        zero = jnp.zeros((1, stats.shape[1]), stats.dtype)
        hi = jnp.concatenate([zero, hi], axis=0)
        lo = jnp.concatenate([zero, lo], axis=0)
        starts, ends = bounds[0], bounds[1]
        # difference in double-single: the hi parts cancel exactly (both
        # exactly representable); the residual lives in lo
        seg = coll.psum((hi[ends] - hi[starts]) + (lo[ends] - lo[starts]))
        cnt = coll.psum((ends - starts).astype(jnp.float32))
        A = seg[:, :rank * rank].reshape(n_out, rank, rank)
        b = seg[:, rank * rank:]
        lam = reg * jnp.maximum(cnt, 1.0)
        A = A + lam[:, None, None] * jnp.eye(rank, dtype=A.dtype)[None]
        sol = jnp.linalg.solve(A, b[:, :, None])[:, :, 0]
        sol = jnp.where(cnt[:, None] > 0, sol, 0.0)
        return jnp.maximum(sol, 0.0) if nonneg else sol

    def program(ius, usi, rat_u, rat_i, ub, ib, uf0, if0):
        ub2 = ub[0]  # (2, n_users): this shard's local bounds
        ib2 = ib[0]

        def body(_, carry):
            uf, itf = carry
            uf = half(itf[ius], rat_u, ub2, n_users)
            itf = half(uf[usi], rat_i, ib2, n_items)
            return uf, itf

        return jax.lax.fori_loop(0, max_iter, body, (uf0, if0))

    return program


def _ds_cumsum(x):
    """Double-single (compensated) inclusive cumsum along axis 0: a
    TwoSum-combine associative scan carrying (sum, error) float32 pairs,
    ~float64-precision prefixes from float32 storage. A plain f32 prefix
    loses the tiny per-segment sums to cancellation once the prefix
    magnitude dwarfs them (at MovieLens-25M scale the boundary difference
    carried ~4% median error — r4 review); the compensated scan's
    residual keeps the difference exact to ~2^-45 of the prefix."""

    def two_sum(a, b):
        s = a + b
        bb = s - a
        err = (a - (s - bb)) + (b - bb)
        return s, err

    def combine(c1, c2):
        hi1, lo1 = c1
        hi2, lo2 = c2
        s, e = two_sum(hi1, hi2)
        return s, e + lo1 + lo2

    return jax.lax.associative_scan(
        combine, (x, jnp.zeros_like(x)), axis=0)


def sort_als_triples(u32: np.ndarray, i32: np.ndarray, ratings: np.ndarray):
    """Per-side stable sort of the rating triples (host, once per fit —
    ids are static across iterations). Returns the four row arrays the
    program will actually consume; callers pass THESE to the router so
    residency probes and background promotion see the staged arrays, not
    the unsorted originals."""
    u_order = np.argsort(u32, kind="stable")
    i_order = np.argsort(i32, kind="stable")
    return {
        "u_sorted": u32[u_order], "i_sorted": i32[i_order],
        "ius": i32[u_order], "usi": u32[i_order],
        "rat_u": ratings[u_order], "rat_i": ratings[i_order],
    }


def stage_als_sorted(prep: dict, n_users: int, n_items: int):
    """Stage the sorted triples + per-shard clipped local segment bounds
    for the active mesh. Returns the sharded program args
    (ius, usi, rat_u, rat_i, ub, ib)."""
    from ..parallel import mesh as meshlib
    from ._staging import stage_rows_cached

    mesh = meshlib.get_mesh()
    n_dev = meshlib.data_width(mesh)
    n = len(prep["rat_u"])
    n_padded = meshlib.bucket_rows(n, n_dev)
    blk = n_padded // n_dev

    def bounds_for(ids_sorted, n_out):
        g_starts = np.searchsorted(ids_sorted, np.arange(n_out)) \
            .astype(np.int64)
        g_ends = np.searchsorted(ids_sorted, np.arange(n_out) + 1) \
            .astype(np.int64)
        lo = (np.arange(n_dev) * blk)[:, None]
        hi = lo + blk
        st = np.clip(g_starts[None, :], lo, hi) - lo
        en = np.clip(g_ends[None, :], lo, hi) - lo
        return np.stack([st, en], axis=1).astype(np.int32)  # (n_dev,2,n_out)

    ub = bounds_for(prep["u_sorted"], n_users)
    ib = bounds_for(prep["i_sorted"], n_items)
    return (stage_rows_cached(prep["ius"]),
            stage_rows_cached(prep["usi"]),
            stage_rows_cached(prep["rat_u"]),
            stage_rows_cached(prep["rat_i"]),
            stage_rows_cached(ub, pad_to_multiple=False),
            stage_rows_cached(ib, pad_to_multiple=False))


class ALS(Estimator):
    def _init_params(self):
        self._declareParam("userCol", default="user", doc="user id column")
        self._declareParam("itemCol", default="item", doc="item id column")
        self._declareParam("ratingCol", default="rating", doc="rating column")
        self._declareParam("predictionCol", default="prediction", doc="prediction column")
        self._declareParam("rank", default=10, doc="latent factor size")
        self._declareParam("maxIter", default=10, doc="alternations")
        self._declareParam("regParam", default=0.1, doc="ALS-WR lambda")
        self._declareParam("coldStartStrategy", default="nan", doc="nan|drop")
        self._declareParam("nonnegative", default=False, doc="clip factors at 0")
        self._declareParam("implicitPrefs", default=False, doc="implicit feedback")
        self._declareParam("seed", default=None, doc="init seed")

    def __init__(self, userCol=None, itemCol=None, ratingCol=None, rank=None,
                 maxIter=None, regParam=None, coldStartStrategy=None,
                 nonnegative=None, implicitPrefs=None, seed=None,
                 predictionCol=None):
        super().__init__()
        self._set(userCol=userCol, itemCol=itemCol, ratingCol=ratingCol,
                  rank=rank, maxIter=maxIter, regParam=regParam,
                  coldStartStrategy=coldStartStrategy, nonnegative=nonnegative,
                  implicitPrefs=implicitPrefs, seed=seed,
                  predictionCol=predictionCol)

    def setColdStartStrategy(self, v):
        return self._set(coldStartStrategy=v)

    def getUserCol(self):
        return self.getOrDefault("userCol")

    def getItemCol(self):
        return self.getOrDefault("itemCol")

    def _fit(self, df) -> "ALSModel":
        pdf = df.toPandas()
        uc, ic, rc = (self.getOrDefault("userCol"), self.getOrDefault("itemCol"),
                      self.getOrDefault("ratingCol"))
        rank = int(self.getOrDefault("rank"))
        max_iter = int(self.getOrDefault("maxIter"))
        reg = float(self.getOrDefault("regParam"))
        seed = self.getOrDefault("seed")
        rng = np.random.default_rng(int(seed) if seed is not None else 0)

        users_raw = np.asarray(pdf[uc])
        items_raw = np.asarray(pdf[ic])
        ratings = np.asarray(pdf[rc], dtype=np.float32)
        u_ids, u_index = np.unique(users_raw, return_inverse=True)
        i_ids, i_index = np.unique(items_raw, return_inverse=True)
        U, I = len(u_ids), len(i_ids)

        # stage rating triples sharded by row; normal-equation accumulation
        # is nnz·rank² per half-step plus (U+I)·rank³ Cholesky solves
        from ..parallel import dispatch
        from ._staging import routed_for
        u32 = u_index.astype(np.int32)
        i32 = i_index.astype(np.int32)
        _hint = dispatch.WorkHint(
            flops=2.0 * max_iter * (len(ratings) * rank * rank
                                    + (U + I) * rank ** 3),
            kind="segment")
        nonneg = bool(self.getOrDefault("nonnegative"))
        from ..utils.profiler import PROFILER
        from ._staging import cached_data_parallel
        prep = sort_als_triples(u32, i32, ratings)
        with routed_for(_hint, prep["ius"], prep["usi"], prep["rat_u"],
                        prep["rat_i"]) as _mesh:
            staged = stage_als_sorted(prep, U, I)

            # MLlib-style init: |N(0,1)| rows normalized to unit norm
            # (ALS.scala initialize). r4's small signed init (0.1·N) sat
            # near the zero saddle: on ~25% of course-scale subsets the
            # alternating solves oscillated for >10 iterations at low reg
            # (observed rmse 1.7 vs 0.25 at maxIter=10), and MLE 01's
            # budget is 10 iterations — init quality IS convergence rate
            uf0 = np.abs(rng.standard_normal((U, rank))).astype(np.float32)
            if0 = np.abs(rng.standard_normal((I, rank))).astype(np.float32)
            uf0 /= np.linalg.norm(uf0, axis=1, keepdims=True) + 1e-12
            if0 /= np.linalg.norm(if0, axis=1, keepdims=True) + 1e-12

            fit = cached_data_parallel(
                _als_fit_program(U, I, rank, reg, max_iter, nonneg),
                replicated_argnums=(6, 7))
            _route = "host" if dispatch.is_host_mesh(_mesh) else "device"
            with PROFILER.span("program.als_fit", rows=len(ratings),
                               route=_route):
                # ONE dispatch for the whole alternating fit; one batched
                # device→host transfer for both factor matrices
                uf_h, itf_h = jax.device_get(fit(*staged, uf0, if0))
        m = ALSModel(user_ids=u_ids, item_ids=i_ids,
                     user_factors=uf_h, item_factors=itf_h)
        m._inherit_params(self)
        return m


class ALSModel(Model):
    def _init_params(self):
        ALS._init_params(self)

    def __init__(self, user_ids=None, item_ids=None, user_factors=None,
                 item_factors=None):
        super().__init__()
        self._user_ids = user_ids
        self._item_ids = item_ids
        self._uf = user_factors
        self._if = item_factors

    def setColdStartStrategy(self, v):
        return self._set(coldStartStrategy=v)

    @property
    def rank(self) -> int:
        if self._uf is None:
            # RuntimeError, not AttributeError: an AttributeError from a
            # property body would be re-reported by Params.__getattr__ as
            # "no attribute rank", hiding the real problem
            raise RuntimeError("ALSModel has no factors (not fitted)")
        return int(self._uf.shape[1])

    @property
    def userFactors(self):
        from ..frame.session import get_session
        return get_session().createDataFrame(pd.DataFrame(
            {"id": self._user_ids, "features": list(map(list, self._uf))}))

    @property
    def itemFactors(self):
        from ..frame.session import get_session
        return get_session().createDataFrame(pd.DataFrame(
            {"id": self._item_ids, "features": list(map(list, self._if))}))

    def _lookup(self, raw, ids, factors):
        idx = np.searchsorted(ids, raw)
        idx = np.clip(idx, 0, len(ids) - 1)
        known = ids[idx] == raw
        return idx, known

    def _transform(self, df):
        uc, ic = self.getOrDefault("userCol"), self.getOrDefault("itemCol")
        oc = self.getOrDefault("predictionCol")
        cold = self.getOrDefault("coldStartStrategy")

        def fn(pdf: pd.DataFrame, ctx) -> pd.DataFrame:
            out = pdf.copy()
            if len(out) == 0:
                out[oc] = pd.Series(dtype=float)
                return out
            ui, u_ok = self._lookup(np.asarray(out[uc]), self._user_ids, self._uf)
            ii, i_ok = self._lookup(np.asarray(out[ic]), self._item_ids, self._if)
            pred = np.einsum("ij,ij->i", self._uf[ui], self._if[ii])
            pred = np.where(u_ok & i_ok, pred, np.nan)
            out[oc] = pred.astype(np.float64)
            if cold == "drop":
                out = out[np.isfinite(out[oc])].reset_index(drop=True)
            return out

        return df._derive(fn)

    def _recommend(self, ids, factors, other_ids, other_factors, n: int,
                   id_col: str, rec_col: str):
        scores = factors @ other_factors.T                      # MXU matmul
        top = np.argsort(-scores, axis=1)[:, :n]
        rows = []
        for i, ident in enumerate(ids):
            recs = [
                {"id": int(other_ids[j]) if np.issubdtype(type(other_ids[j]), np.integer)
                 else other_ids[j], "rating": float(scores[i, j])}
                for j in top[i]]
            rows.append({id_col: ident, "recommendations": recs})
        from ..frame.session import get_session
        return get_session().createDataFrame(pd.DataFrame(rows))

    def recommendForAllUsers(self, numItems: int):
        return self._recommend(self._user_ids, self._uf, self._item_ids,
                               self._if, numItems,
                               self.getOrDefault("userCol"), "rec")

    def recommendForAllItems(self, numUsers: int):
        return self._recommend(self._item_ids, self._if, self._user_ids,
                               self._uf, numUsers,
                               self.getOrDefault("itemCol"), "rec")

    def recommendForUserSubset(self, dataset, numItems: int):
        uc = self.getOrDefault("userCol")
        want = np.unique(np.asarray(dataset.toPandas()[uc]))
        sel = np.isin(self._user_ids, want)
        return self._recommend(self._user_ids[sel], self._uf[sel],
                               self._item_ids, self._if, numItems, uc, "rec")

    def _save_state(self, path):
        save_arrays(path, user_ids=self._user_ids, item_ids=self._item_ids,
                    user_factors=self._uf, item_factors=self._if)

    def _load_state(self, path, meta):
        d = load_arrays(path)
        self._user_ids = d["user_ids"]
        self._item_ids = d["item_ids"]
        self._uf = d["user_factors"]
        self._if = d["item_factors"]
