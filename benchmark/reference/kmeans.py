"""Plain reference for the clustering cells: Lloyd's k-means and the
k-means|| seeding in NumPy float64, from the raw rows. Nothing here is
imported from the program under test.

The objective: sum over rows of the squared Euclidean distance to the
nearest of k centers. One Lloyd step assigns every row to its nearest
center (the LOWEST index wins a tie, as MLlib's `findClosest` keeps the
first best) and moves every center to the mean of its rows; a center no
row chose stays where it is. The seeding is Bahmani et al.'s k-means||
as MLlib's `KMeans.scala` `initKMeansParallel` runs it: a first center
drawn uniformly, `steps` rounds that each draw EVERY row independently
with probability 2k x d² / phi (d² the squared distance to the nearest
candidate so far, phi their sum), the candidates weighted by the rows
nearest to each, and a weighted k-means++ over them
(`LocalKMeans.kMeansPlusPlus`: the first by weight, each next with
probability weight x d², then at most 30 weighted Lloyd steps). Its draws
are its own generator's (`np.random.default_rng(seed)`): the program's are
not reproduced, so what is compared of a seeding is its cost.

Departures from MLlib's `KMeans.scala`, which change no distance and no
mean: squared distances as |x|² - 2 x.c + |c|² about the column means for
EVERY pair (MLlib takes the expansion only where a precision bound holds
and the direct form elsewhere; about the means the float64 expansion's
error is under 1e-9 of a distance here), a BLAS product a block of rows, a
block a thread; a cluster's sum a `np.bincount` a column; an empty cluster
of the local k-means++ keeps its center (MLlib re-seeds it with a random
candidate: the program keeps it, and so does this); the candidates are
not made distinct (a duplicate has weight 0: the lower index takes its
rows).

`round_to`: every operand of a product (the rows and the centers in a
distance, the rows in a cluster's sum) rounded to that precision first
(`precision.round_to`): the reference's OWN step in the nearest precision
below the configuration's, for the control.
"""

from __future__ import annotations

import contextlib
import os
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Optional, Tuple

import numpy as np

from benchmark.reference import precision

#: rows of a block of distances ((BLOCK_ROWS, k) float64 a thread, and
#: three temporaries of it: 0.26 GB a thread at k = 1000), and the threads
#: the blocks may run on. Nothing here makes a second array of the table's
#: size: the host that checks 6.4 M rows x 42 holds the table three times
#: over already (the frame, its concat, this float64 copy)
BLOCK_ROWS = 1 << 13
WORKERS = 13
LOCAL_ITERATIONS = 30


_pool: Optional[ThreadPoolExecutor] = None
_local = threading.local()


def _blockwise(task, rows: int) -> list:
    """`task(lo, hi)` over the blocks of `rows` rows, on ONE pool of
    threads kept for the process: a thread keeps its block of distances
    from call to call (`_block_buffer`)."""
    global _pool
    starts = range(0, rows, BLOCK_ROWS)
    workers = min(WORKERS, len(os.sched_getaffinity(0)))
    if workers < 2 or len(starts) < 2:
        return [task(lo, min(lo + BLOCK_ROWS, rows)) for lo in starts]
    if _pool is None:
        _pool = ThreadPoolExecutor(max_workers=workers)
    # a block a thread, so ONE BLAS thread a block: the library's own
    # threads, asked for by every worker at once, wait on each other (a
    # pass of a million rows: 9.6 s, and 1.1 s so)
    with _one_blas_thread():
        return list(_pool.map(
            lambda lo: task(lo, min(lo + BLOCK_ROWS, rows)), starts))


def _one_blas_thread():
    try:
        from threadpoolctl import threadpool_limits
    except ImportError:     # slower, and the same numbers
        return contextlib.nullcontext()
    return threadpool_limits(limits=1, user_api="blas")


def _block_buffer(rows: int, k: int) -> np.ndarray:
    """This thread's (rows, k) float64 block, made once a `k`: a block
    allocated and freed a task is 0.5 GB of fresh pages a pass and a
    thread, and the machine that checks the cell counts pages it has not
    yet taken back (a pass of 6.4 M rows asked it for 40 GiB that way)."""
    held = getattr(_local, "blocks", None)
    if held is None:
        held = _local.blocks = {}
    if (BLOCK_ROWS, k) not in held:
        held[BLOCK_ROWS, k] = np.empty((BLOCK_ROWS, k), np.float64)
    return held[BLOCK_ROWS, k][:rows]


def _rounded(a: np.ndarray, round_to: Optional[str]) -> np.ndarray:
    return a if round_to is None else np.asarray(
        precision.round_to(a, round_to), np.float64)


def squared_distances(X: np.ndarray, centers: np.ndarray,
                      origin: np.ndarray, round_to: Optional[str] = None,
                      out: Optional[np.ndarray] = None) -> np.ndarray:
    """(rows, k) float64 squared distances of the rows of `X` to
    `centers`, both taken about `origin` first: |x|² - 2 x.c + |c|² as ONE
    product, of [x, |x|², 1] with [-2c, 1, |c|²], so that the block is
    written once (into `out` where one is given) and no temporary of its
    size is made. A distance the cancellation leaves under 0 is left so:
    the callers clamp the one they keep."""
    x = _rounded(X - origin, round_to)
    c = _rounded(centers - origin, round_to)
    left = np.concatenate([x, (x * x).sum(axis=1)[:, None],
                           np.ones((len(x), 1))], axis=1)
    right = np.concatenate([-2.0 * c, np.ones((len(c), 1)),
                            (c * c).sum(axis=1)[:, None]], axis=1)
    return np.matmul(left, right.T, out=out)


def nearest(X: np.ndarray, centers: np.ndarray,
            round_to: Optional[str] = None,
            origin: Optional[np.ndarray] = None
            ) -> Tuple[np.ndarray, np.ndarray]:
    """(index of every row's nearest center, the lowest on a tie; its
    squared distance), by blocks of rows."""
    origin = X.mean(axis=0) if origin is None else origin
    idx = np.empty(len(X), np.int64)
    d2 = np.empty(len(X), np.float64)

    def task(lo, hi):
        block = squared_distances(
            X[lo:hi], centers, origin, round_to,
            out=_block_buffer(hi - lo, len(centers)))
        idx[lo:hi] = block.argmin(axis=1)
        d2[lo:hi] = np.maximum(block[np.arange(hi - lo), idx[lo:hi]], 0.0)

    _blockwise(task, len(X))
    return idx, d2


def two_nearest(X: np.ndarray, centers: np.ndarray, origin: np.ndarray
                ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """(nearest index, its squared distance, the second nearest's, the
    scale a float32 expansion about `origin` resolves the two against:
    |x - origin|² + |c - origin|² + the distance)."""
    block = np.maximum(squared_distances(X, centers, origin), 0.0)
    idx = block.argmin(axis=1)
    rows = np.arange(len(X))
    best = block[rows, idx].copy()
    block[rows, idx] = np.inf
    scale = ((X - origin) ** 2).sum(axis=1) \
        + ((centers[idx] - origin) ** 2).sum(axis=1) + best
    return idx, best, block.min(axis=1), scale


def cost(X: np.ndarray, centers: np.ndarray) -> float:
    return float(nearest(X, centers)[1].sum())


def spread(X: np.ndarray, origin: np.ndarray) -> np.ndarray:
    """Every row's squared distance to `origin`, by blocks of rows."""
    out = np.empty(len(X), np.float64)

    def task(lo, hi):
        about = X[lo:hi] - origin
        out[lo:hi] = (about * about).sum(axis=1)

    _blockwise(task, len(X))
    return out


def cluster_sums(X: np.ndarray, idx: np.ndarray, k: int,
                 origin: np.ndarray, weights: Optional[np.ndarray] = None,
                 round_to: Optional[str] = None
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """((k, d) sums of the rows LESS `origin` by cluster, (k,) counts or
    weights): a `np.bincount` a column, a column a thread."""
    d = X.shape[1]
    sums = np.empty((k, d), np.float64)

    def column(j):
        col = _rounded(X[:, j] - origin[j], round_to)
        sums[:, j] = np.bincount(
            idx, weights=col if weights is None else col * weights,
            minlength=k)

    workers = min(WORKERS, len(os.sched_getaffinity(0)), d)
    with ThreadPoolExecutor(max_workers=workers) as pool:
        list(pool.map(column, range(d)))
    return sums, np.bincount(idx, weights=weights, minlength=k)


def lloyd_step(X: np.ndarray, centers: np.ndarray,
               round_to: Optional[str] = None) -> Dict[str, np.ndarray]:
    """One step from `centers`: the assignment, the moved centers (an
    empty cluster keeps its own), the counts and the cost AT `centers`.
    The sums are taken about the column means, as the distances are."""
    origin = X.mean(axis=0)
    idx, d2 = nearest(X, centers, round_to, origin)
    sums, counts = cluster_sums(X, idx, len(centers), origin,
                                round_to=round_to)
    moved = np.where(counts[:, None] > 0,
                     sums / np.maximum(counts, 1)[:, None] + origin, centers)
    return {"assignment": idx, "centers": moved, "counts": counts,
            "cost": float(d2.sum())}


def lloyd(X: np.ndarray, centers: np.ndarray, max_iter: int,
          tol: float) -> Tuple[np.ndarray, int]:
    """(centers after at most `max_iter` steps, steps run): ends when no
    center moved farther than `tol` (MLlib's test, after the update)."""
    steps = 0
    while steps < max_iter:
        moved = lloyd_step(X, centers)["centers"]
        shift = ((moved - centers) ** 2).sum(axis=1).max()
        centers, steps = moved, steps + 1
        if shift <= tol * tol:
            break
    return centers, steps


def _weighted_pick(p: np.ndarray, u: float) -> int:
    run = np.cumsum(p)
    return int(min(np.searchsorted(run, u * run[-1], side="right"),
                   len(p) - 1))


def local_kmeans(points: np.ndarray, weights: np.ndarray, k: int,
                 rng) -> np.ndarray:
    """k centers of the weighted `points` (m, d): `LocalKMeans`'
    weighted k-means++ and its Lloyd steps."""
    m = len(points)
    centers = np.empty((k, points.shape[1]), np.float64)
    centers[0] = points[_weighted_pick(weights, rng.random())]
    d2 = ((points - centers[0]) ** 2).sum(axis=1)
    for i in range(1, k):
        p = weights * d2
        if p.sum() <= 0:
            p = weights
        centers[i] = points[_weighted_pick(p, rng.random())]
        d2 = np.minimum(d2, ((points - centers[i]) ** 2).sum(axis=1))
    old = np.full(m, -1)
    for _ in range(LOCAL_ITERATIONS):
        idx, _ = nearest(points, centers)
        if (idx == old).all():
            break
        old = idx
        sums, counts = cluster_sums(points, idx, k, np.zeros(
            points.shape[1]), weights=weights)
        centers = np.where(counts[:, None] > 0,
                           sums / np.maximum(counts, 1e-300)[:, None],
                           centers)
    return centers


def kmeans_parallel(X: np.ndarray, k: int, steps: int, seed: int
                    ) -> Dict[str, object]:
    """k-means|| from its own draws: {"centers", "candidates"}."""
    rng = np.random.default_rng(seed)
    n = len(X)
    origin = X.mean(axis=0)
    cands = X[rng.integers(n)][None, :]
    d2 = spread(X, cands[0])
    near = np.zeros(n, np.int64)
    for _ in range(steps):
        chosen = rng.random(n) * d2.sum() < 2.0 * k * d2
        new = X[chosen]
        if len(new):
            idx, to_new = nearest(X, new, origin=origin)
            closer = to_new < d2
            near[closer] = len(cands) + idx[closer]
            d2 = np.where(closer, to_new, d2)
            cands = np.concatenate([cands, new])
    weights = np.bincount(near, minlength=len(cands)).astype(np.float64)
    return {"centers": local_kmeans(cands, weights, k, rng),
            "candidates": len(cands)}


def sampled_kmeans_pp(X: np.ndarray, k: int, seed: int,
                      sample: int = 4096) -> np.ndarray:
    """The seeding this program had before it had k-means||: k-means++
    over `sample` rows drawn without replacement. A control: at k in the
    thousand it is four rows a center."""
    rng = np.random.default_rng(seed)
    rows = X[rng.choice(len(X), size=min(len(X), sample), replace=False)]
    centers = [rows[rng.integers(len(rows))]]
    d2 = ((rows - centers[0]) ** 2).sum(axis=1)
    for _ in range(1, k):
        p = d2 / d2.sum() if d2.sum() > 0 else None
        centers.append(rows[rng.choice(len(rows), p=p)])
        d2 = np.minimum(d2, ((rows - centers[-1]) ** 2).sum(axis=1))
    return np.stack(centers)


def random_rows(X: np.ndarray, k: int, seed: int) -> np.ndarray:
    """k distinct rows: `initMode="random"`'s rule, for the control."""
    rng = np.random.default_rng(seed)
    return X[rng.choice(len(X), size=k, replace=False)]


#: the grain a float32 center is held to, as a share of its norm: 16 units
#: in the last place
FLOAT32_GRAIN = 2.0 ** -20


def center_errors(step: Dict[str, np.ndarray], got: np.ndarray,
                  X: np.ndarray) -> np.ndarray:
    """|got - the step's center| for every cluster the step filled, in
    units of what the center is known to: the cluster's standard error
    (its root-mean-square radius about the step's center over the square
    root of its count) plus the grain of a float32 center of its norm (a
    cluster of one row, or of identical rows, has no radius)."""
    idx, counts, want = step["assignment"], step["counts"], step["centers"]
    origin = X.mean(axis=0)
    square = np.bincount(idx, weights=spread(X, origin), minlength=len(want))
    filled = counts > 0
    mean = want[filled] - origin
    radius2 = square[filled] / counts[filled] - (mean * mean).sum(axis=1)
    unit = np.sqrt(np.maximum(radius2, 0.0) / counts[filled]) \
        + FLOAT32_GRAIN * np.sqrt((want[filled] ** 2).sum(axis=1))
    gap = np.sqrt(((got[filled] - want[filled]) ** 2).sum(axis=1))
    return np.where(gap > 0, gap / np.where(unit > 0, unit, 1.0), 0.0)
