"""Plain reference for the alternating-least-squares cells: ALS-WR on
explicit ratings in NumPy float64, from the raw rows. Nothing here is
imported from the program under test.

The model: ratings r_ui ~ x_u . y_i with rank-`rank` factors. One
alternation solves, for every user u with the movies' factors held,

    (sum_{i in R(u)} y_i y_i' + reg n_u I) x_u = sum_{i in R(u)} r_ui y_i

(n_u = |R(u)|, the ratings of u: Zhou et al.'s weighted-lambda
regularization, which MLlib's `ALS.scala` applies as `regParam * n`), then
the same for every movie with the users' factors held. `max_iter`
alternations from the init RULE the configuration states, re-implemented
here from that statement: rows |N(0, 1)| normalized to unit norm, drawn
from `np.random.default_rng(seed)`, every user's row and then every
movie's, in the order of the sorted ids.

Departures from MLlib's `ALS.scala`, which change no sum and no solution:
no blocking of users and movies into in/out blocks (one table, sorted once
a side); the sums of a side by `np.add.reduceat` over the rows sorted by
that side's id (the upper triangle of f f' and r f, a product a
statistic), in blocks of rows so that rows x statistics float64 never
exists, a block a thread; a Cholesky factor a row in place of
MLlib's packed `dppsv`; no `nonnegative` NNLS solver (the program's
`nonnegative` clips at zero, and so does this one, as a statement of what
the program does, not of what MLlib does).

`round_to`: every operand of a product (the gathered factor rows, the
ratings, the factor and the right-hand side of a solve) rounded to that
precision first (`precision.round_to`): the reference's OWN steps in the
nearest precision below the configuration's, for the control.
"""

from __future__ import annotations

import os
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Optional, Tuple

import numpy as np

from benchmark.reference import precision

#: rows of a block of a side's sums, and the threads they may run on
BLOCK_ROWS = 1 << 17
WORKERS = 8


class _Scratch:
    """The three arrays a block's sums are made in (the gathered factor
    rows, their transpose, the statistics), handed from block to block: a
    fresh 100 MB a block is paid for in page faults, several times the
    arithmetic's cost on a virtual machine."""

    def __init__(self):
        self._free: list = []
        self._lock = threading.Lock()

    def take(self, rank: int):
        with self._lock:
            for k, held in enumerate(self._free):
                if held[1].shape[0] == rank:
                    return self._free.pop(k)
        width = rank * (rank + 1) // 2 + rank
        return (np.empty((BLOCK_ROWS, rank)), np.empty((rank, BLOCK_ROWS)),
                np.empty((width, BLOCK_ROWS)))

    def give(self, held) -> None:
        with self._lock:
            self._free.append(held)


def _blockwise(task, count: int) -> list:
    workers = min(WORKERS, len(os.sched_getaffinity(0)), count)
    if workers < 2:
        return [task(i) for i in range(count)]
    with ThreadPoolExecutor(max_workers=workers) as pool:
        return list(pool.map(task, range(count)))


def _rounded(x: np.ndarray, round_to: Optional[str]) -> np.ndarray:
    if round_to is None:
        return x
    if round_to == "bfloat16":
        # the type's own rounding (nearest, ties to even) on the bits of
        # the float32: what `precision.round_to` computes through frexp,
        # several times faster over 20 M rows of factors
        bits = np.asarray(x, np.float32).view(np.uint32)
        bits = (bits + np.uint32(0x7FFF) + ((bits >> np.uint32(16))
                                            & np.uint32(1))) \
            & np.uint32(0xFFFF0000)
        return bits.view(np.float32).astype(np.float64)
    return np.asarray(precision.round_to(x, round_to), dtype=np.float64)


def dense(raw: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """(the distinct raw ids in order, each row's place among them)."""
    return np.unique(np.asarray(raw), return_inverse=True)


def init(users: int, items: int, rank: int, seed: int
         ) -> Tuple[np.ndarray, np.ndarray]:
    """The init rule: |N(0, 1)| rows of unit norm, users then movies."""
    rng = np.random.default_rng(int(seed))
    out = []
    for n in (users, items):
        # drawn as the float32 the rule's statement gives the draws
        f = np.abs(rng.standard_normal((n, rank))).astype(np.float32)
        f /= np.linalg.norm(f, axis=1, keepdims=True) + 1e-12
        out.append(f.astype(np.float64))
    return out[0], out[1]


class Side:
    """One side's view of the ratings: the rows in the order of this side's
    dense id, the OTHER side's dense id and the rating of each, and where
    every id's rows begin."""

    def __init__(self, own: np.ndarray, other: np.ndarray,
                 ratings: np.ndarray, n_out: int,
                 scratch: Optional[_Scratch] = None):
        self.scratch = scratch or _Scratch()
        order = np.argsort(own, kind="stable")
        self.other = other[order]
        self.ratings = np.asarray(ratings, np.float64)[order]
        self.counts = np.bincount(own, minlength=n_out)
        self.starts = np.concatenate([[0], np.cumsum(self.counts)[:-1]])
        self.n_out = int(n_out)
        self.own_sorted = own[order]

    def normal_equations(self, factors: np.ndarray,
                         round_to: Optional[str] = None,
                         only: Optional[np.ndarray] = None):
        """(A, b) of every id of this side (or of the ids `only`): sums of
        f f' and r f over the id's rows, f the other side's factor row;
        the regularization is NOT in A."""
        rank = factors.shape[1]
        if only is not None:
            rows = np.concatenate([np.arange(self.starts[e], self.starts[e]
                                             + self.counts[e]) for e in only])
            own = np.repeat(np.arange(len(only)), self.counts[only])
            other, ratings, n_out = self.other[rows], self.ratings[rows], \
                len(only)
        else:
            own, other, ratings, n_out = self.own_sorted, self.other, \
                self.ratings, self.n_out
        tri_i, tri_j = np.triu_indices(rank)
        n_tri = len(tri_i)
        sums = np.zeros((n_tri + rank, n_out))
        edges = list(range(0, len(own), BLOCK_ROWS)) + [len(own)]
        scratch = self.scratch if round_to is None else _Scratch()

        def block(i: int):
            # the rows ALONG the last axis: a statistic is one product of
            # two whole vectors, and `reduceat` runs along memory
            lo, hi = edges[i], edges[i + 1]
            held = scratch.take(rank)
            try:
                n = hi - lo
                rows, f, stats = held[0][:n], held[1][:, :n], held[2][:, :n]
                np.take(factors, other[lo:hi], axis=0, out=rows)
                f[...] = _rounded(rows, round_to).T
                r = _rounded(ratings[lo:hi], round_to)
                ids = own[lo:hi]
                first = np.flatnonzero(np.concatenate(
                    [[True], ids[1:] != ids[:-1]]))
                for k, (a, c) in enumerate(zip(tri_i, tri_j)):
                    np.multiply(f[a], f[c], out=stats[k])
                np.multiply(f, r[None, :], out=stats[n_tri:])
                return ids[first], np.add.reduceat(stats, first, axis=1)
            finally:
                scratch.give(held)

        # a segment that spans a block's end is two partial sums of the
        # same id: added up here in the blocks' order
        # (an id is once in a block's `ids`, so the indexed adds lose none)
        for ids, part in _blockwise(block, len(edges) - 1):
            sums[:, ids] += part
        A = np.empty((n_out, rank, rank))
        A[:, tri_i, tri_j] = A[:, tri_j, tri_i] = sums[:n_tri].T
        b = np.ascontiguousarray(sums[n_tri:].T)
        return A, b

    def solve(self, factors: np.ndarray, reg: float, nonneg: bool = False,
              round_to: Optional[str] = None) -> np.ndarray:
        """This side's factors from the other side's: a half-step."""
        A, b = self.normal_equations(factors, round_to)
        return solved(A, b, self.counts, reg, nonneg, round_to)


def solved(A: np.ndarray, b: np.ndarray, counts: np.ndarray, reg: float,
           nonneg: bool = False, round_to: Optional[str] = None
           ) -> np.ndarray:
    """x of (A + reg max(n, 1) I) x = b a row, by a Cholesky factor; a row
    with no rating is 0."""
    rank = b.shape[1]
    A = A + (reg * np.maximum(counts, 1))[:, None, None] * np.eye(rank)
    L = _rounded(np.linalg.cholesky(A), round_to)
    y = np.linalg.solve(L, _rounded(b, round_to)[:, :, None])
    x = np.linalg.solve(np.swapaxes(L, 1, 2), _rounded(y, round_to))[:, :, 0]
    x = np.where((counts > 0)[:, None], x, 0.0)
    return np.maximum(x, 0.0) if nonneg else x


def fit(users_raw: np.ndarray, items_raw: np.ndarray, ratings: np.ndarray,
        rank: int, max_iter: int, reg: float, seed: int,
        nonneg: bool = False, round_to: Optional[str] = None
        ) -> Dict[str, object]:
    """`max_iter` alternations from the init rule. Returns the ids in
    order, the factors of each, and the two `Side`s."""
    user_ids, u = dense(users_raw)
    item_ids, i = dense(items_raw)
    scratch = _Scratch()
    by_user = Side(u, i, ratings, len(user_ids), scratch)
    by_item = Side(i, u, ratings, len(item_ids), scratch)
    uf, itf = init(len(user_ids), len(item_ids), rank, seed)
    for _ in range(int(max_iter)):
        uf = by_user.solve(itf, reg, nonneg, round_to)
        itf = by_item.solve(uf, reg, nonneg, round_to)
    return {"user_ids": user_ids, "item_ids": item_ids, "user_factors": uf,
            "item_factors": itf, "by_user": by_user, "by_item": by_item}


def predict(model: Dict[str, object], users_raw: np.ndarray,
            items_raw: np.ndarray) -> np.ndarray:
    """x_u . y_i a pair; NaN where the user or the movie is not in the
    model (the cold start)."""
    def place(ids, raw):
        at = np.minimum(np.searchsorted(ids, raw), len(ids) - 1)
        return at, ids[at] == raw
    u, u_ok = place(model["user_ids"], np.asarray(users_raw))
    i, i_ok = place(model["item_ids"], np.asarray(items_raw))
    out = np.einsum("ij,ij->i", np.asarray(model["user_factors"],
                                           np.float64)[u],
                    np.asarray(model["item_factors"], np.float64)[i])
    return np.where(u_ok & i_ok, out, np.nan)


def normal_residual(side: Side, own_factors: np.ndarray,
                    other_factors: np.ndarray, reg: float,
                    only: np.ndarray) -> np.ndarray:
    """max_k |(A + reg n I) x - b|_k / max_k |b|_k for the ids `only` of
    `side`, in float64 at the factors GIVEN: the sums and the solve of the
    half-step that made `own_factors` from `other_factors`, whatever path
    the alternations took before it."""
    A, b = side.normal_equations(np.asarray(other_factors, np.float64),
                                 only=only)
    counts = side.counts[only]
    A = A + (reg * np.maximum(counts, 1))[:, None, None] * np.eye(b.shape[1])
    x = np.asarray(own_factors, np.float64)[only]
    gap = np.abs(np.einsum("nij,nj->ni", A, x) - b).max(axis=1)
    return gap / np.maximum(np.abs(b).max(axis=1), np.finfo(np.float64).tiny)


def rmse(predicted: np.ndarray, truth: np.ndarray) -> float:
    return float(np.sqrt(np.mean((np.asarray(predicted, np.float64)
                                  - np.asarray(truth, np.float64)) ** 2)))
