"""KMeans — Lloyd's map/reduce by ROW BLOCKS, one program a fit (SURVEY §2.2 P5).

The reference teaches K-Means as the canonical distributed map (assign) /
reduce (recompute centers) algorithm, "communication is key"
(`SML/ML Electives/MLE 02 - K-Means.py:183-204`). Here a fit is ONE jitted
shard_map program over the table staged feature-major (`_staging.RowsLast`:
float32 (d, rows), the rows along the chip's lanes): the k-means|| seeding
(Bahmani et al., "Scalable K-Means++", as MLlib's `KMeans.scala` runs it),
the Lloyd loop (`lax.while_loop`: until no center moved by more than `tol`
or `maxIter`) and the cost at the returned centers, with ONE psum of the
(k, d) sums and the counts over ICI an iteration and no host round trip.

Every pass walks the rows in BLOCKS (`_walk`, `_block_rows`): a block's
distances to all k centers are one matrix product on the MXU about the
column means (float32 as its six bfloat16 products along ONE contraction
of 6d, `_nearest`), its arg-min (the lowest index wins a tie, MLlib's rule)
and its sums the product of the 0/1 assignment matrix with the block. What
lives at once is one block's (k, block) tile: no array of rows x k
elements exists, so the table's size is bounded by its own block and not
by k times it (docs/KERNELS.md "The blocked Lloyd step").
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd

from ..parallel import collectives as coll
from ..parallel import mesh as meshlib
from ..parallel.dispatch import WorkHint
from .base import Estimator, Model, load_arrays, save_arrays
from ._staging import RowsLast, extract_features, run_data_parallel

#: a dispatch's block temporaries may take this share of the device's
#: memory (`_block_rows`): the block's (centers, block) tile, counted as
#: `_BLOCK_COPIES` float32 arrays of it (the distance product, the scores
#: the arg-min reads, the 0/1 assignment matrix and what the compiler keeps
#: of them between the two products)
_BLOCK_SHARE = 1.0 / 16.0
_BLOCK_COPIES = 3
_SUBLANES = 8
#: the memory of a device that reports none (the CPU test mesh): a v5e's
_DEVICE_BYTES = 16 << 30
#: a block's counts ride the sums' product in float32: exact to 2^24 rows
_MAX_BLOCK_ROWS = 1 << 24
#: Lloyd steps of the seeding's weighted k-means++ over the candidates
#: (MLlib `LocalKMeans.kMeansPlusPlus`: 30)
_LOCAL_ITERATIONS = 30
_PRECISE = jax.lax.Precision.HIGHEST


def _block_bytes(width: int, rows: int) -> int:
    """Bytes of a block's temporaries against `width` centers:
    `_BLOCK_COPIES` float32 (width, rows) tiles, the centers padded to
    whole sublanes."""
    return 4 * _BLOCK_COPIES * rows * _SUBLANES * -(-width // _SUBLANES)


def _block_rows(width: int) -> int:
    """Rows of one block of a pass against `width` centers: the largest
    power of two whose temporaries fit `_BLOCK_SHARE` of the active mesh's
    first device (`recommendation._block_rows`' rule)."""
    stats = meshlib.get_mesh().devices.flat[0].memory_stats() or {}
    budget = _BLOCK_SHARE * float(stats.get("bytes_limit", _DEVICE_BYTES))
    rows = 1 << max(int(budget // _block_bytes(width, 1)).bit_length() - 1, 0)
    return min(rows, _MAX_BLOCK_ROWS)


def _candidate_slots(k: int) -> int:
    """Slots a seeding round's picks are kept in. A round draws every row
    independently with probability 2k·d²/φ, so 2k picks are expected and
    their deviation is at most sqrt(2k): eight deviations of room, in whole
    lane tiles. Picks past it are dropped in row order (MLlib keeps all: a
    static shape cannot)."""
    return 128 * -(-int(2 * k + 8 * np.sqrt(2.0 * k) + 16) // 128)


def _product_operand(a):
    """An operand of a matrix product as it enters it. The identity; the
    seam where `benchmark/tools_kmeans.py` rounds every one for the
    lower-precision control."""
    return a


def _first_min(score):
    """The row (center) of `score`'s smallest entry in every column: the
    LOWEST index wins a tie (MLlib `findClosest` keeps the first best)."""
    return jnp.argmin(score, axis=0).astype(jnp.int32)


def _three_bfloat16(x):
    """`x` (float32) as three bfloat16 arrays that add up to it (8 + 8 + 8
    bits of mantissa): what keeps a float32 operand's accuracy through a
    bfloat16 product. `reduce_precision`, not a cast there and back, which
    the compiler may drop."""
    parts = []
    for _ in range(3):
        head = jax.lax.reduce_precision(x, 8, 7)
        parts.append(head.astype(jnp.bfloat16))
        x = x - head
    return parts


def _block_parts(xb):
    """A block (d, rows) as the products read it: the three bfloat16 parts
    of `_product_operand(xb)`."""
    return _three_bfloat16(_product_operand(xb))


#: (part of -2c, part of x) of the six products that make a float32 product
#: of bfloat16 parts (`Precision.HIGHEST`'s six), in the order they lie
#: along the contraction: the SMALLEST first (2^-16 of c1x1, then 2^-8) and
#: c1x1 last. The MXU adds along the contraction in that order, and a small
#: term added to a sum that already holds c1x1 is rounded at c1x1's grain:
#: c1x1 first read 1.5-1.7 x the library product's error on the chip, this
#: order reads the library's (PERF.md section 6, PR 48)
_PRODUCTS = ((2, 0), (0, 2), (1, 1), (1, 0), (0, 1), (0, 0))


def _stacked_rows(parts):
    """The block's side of `_nearest`'s product, (6d, rows): the part of x
    of each of `_PRODUCTS` in turn, each padded with zeros to its row
    group and the six ADDED (in any place one is not zero: exact). A sum
    and not a `concatenate`: the chip's compiler writes a concatenate of
    repeated operands out before the product (33 MB and 0.18 ms a block of
    65,536 rows, as long as the product itself) and builds the sum INSIDE
    the product's fusion as it reads its operand (docs/KERNELS.md "The
    blocked Lloyd step")."""
    d = parts[0].shape[0]
    placed = [jnp.pad(parts[j], ((group * d, (5 - group) * d), (0, 0)))
              for group, (_, j) in enumerate(_PRODUCTS)]
    return sum(placed[1:], placed[0])


def _nearest(xb, centers, valid=None, parts=None):
    """(index of the nearest of `centers` (k, d), squared distance to it)
    for the columns of `xb` (d, rows), both about the same origin:
    |c|² − 2c·x + |x|², the norms float32 and the product float32 as this
    chip makes one: the six products of the operands' bfloat16 parts
    (`_PRODUCTS`: `Precision.HIGHEST`'s six, not `HIGH`'s three), here
    stacked along ONE contraction of 6d and accumulated in float32: the
    parts of -2c side by side (k, 6d) against `_stacked_rows` (6d, rows),
    two filled passes of the MXU at d = 42 where the library makes six
    over a contraction of d. `parts` is `_block_parts(xb)` where the caller
    has it. Centers where `valid` is false are nobody's nearest."""
    c, x = _product_operand(centers), _product_operand(xb)
    cn = jnp.sum(c * c, axis=1)
    if valid is not None:
        cn = jnp.where(valid, cn, jnp.inf)
    c_parts = _three_bfloat16(-2.0 * c)
    product = jax.lax.dot_general(
        jnp.concatenate([c_parts[i] for i, _ in _PRODUCTS], axis=1),
        _stacked_rows(_block_parts(xb) if parts is None else parts),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    score = cn[:, None] + product
    idx = _first_min(score)
    d2 = jnp.min(score, axis=0) + jnp.sum(x * x, axis=0)
    return idx, jnp.maximum(d2, 0.0)


def _assigned(idx, w, k: int):
    """The 0/1 assignment matrix (k, rows) as booleans: row r of the block
    belongs to center `idx[r]`, rows where `w` is false to none."""
    return (jnp.arange(k, dtype=jnp.int32)[:, None] == idx[None, :]) \
        & w[None, :]


def _block_sums(parts, idx, w, k: int):
    """(sums (k, d) float32, counts (k,) int32) of the columns of a block
    (d, rows), given as its `_block_parts`, by their center `idx`, rows
    where `w` is false left out: ONE bfloat16 product of the 0/1 assignment
    matrix (exact) with the three parts and a row of ones, accumulated in
    float32."""
    d, rows = parts[0].shape
    onehot = _assigned(idx, w, k).astype(jnp.bfloat16)
    rhs = jnp.concatenate(
        list(parts) + [jnp.ones((1, rows), jnp.bfloat16)])
    out = jax.lax.dot_general(onehot, rhs, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    sums = (out[:, :d] + out[:, d:2 * d]) + out[:, 2 * d:3 * d]
    return sums, out[:, 3 * d].astype(jnp.int32)


def _walk(Xt, origin, block: int, body, carry):
    """`carry` after `body(lo, xb, fresh, carry)` over the blocks of
    `Xt` (d, rows): `xb` the block's columns less `origin`, `lo` its first
    row. The last block of a table that is no multiple of `block` is moved
    back to end with the table, and `fresh` is false for the rows of it
    that the block before held (nothing is padded: a pad would copy the
    table)."""
    d, rows = Xt.shape
    block = min(block, rows)

    def one(i, carry):
        lo = jnp.minimum(i * block, rows - block)
        xb = jax.lax.dynamic_slice(Xt, (0, lo), (d, block)) - origin[:, None]
        fresh = lo + jnp.arange(block, dtype=jnp.int32) >= i * block
        return body(lo, xb, fresh, carry)

    return jax.lax.fori_loop(0, -(-rows // block), one, carry)


def _rows_of(a, lo, block: int):
    return jax.lax.dynamic_slice(a, (lo,), (min(block, a.shape[0]),))


def _lloyd_pass(Xt, live, origin, centers, block: int):
    """One assignment of every live row to the nearest of `centers`
    (about `origin`) and the clusters' (sums, counts), all-reduced once."""
    k, d = centers.shape

    def body(lo, xb, fresh, carry):
        sums, counts = carry
        with jax.named_scope("kmeans.assign"):
            parts = _block_parts(xb)
            idx, _ = _nearest(xb, centers, parts=parts)
        with jax.named_scope("kmeans.update"):
            s, c = _block_sums(parts, idx,
                               fresh & _rows_of(live, lo, block), k)
            return sums + s, counts + c

    sums, counts = _walk(Xt, origin, block, body, (
        jnp.zeros((k, d), jnp.float32), jnp.zeros((k,), jnp.int32)))
    with jax.named_scope("kmeans.update"):
        return coll.psum(sums), coll.psum(counts)


def _cost_pass(Xt, live, origin, centers, block: int):
    """(Σ over live rows of the squared distance to the nearest center,
    the rows nearest to each center)."""
    k = centers.shape[0]

    def body(lo, xb, fresh, carry):
        cost, sizes = carry
        idx, d2 = _nearest(xb, centers)
        w = fresh & _rows_of(live, lo, block)
        return (cost + jnp.sum(jnp.where(w, d2, 0.0)),
                sizes + jnp.sum(_assigned(idx, w, k), axis=1,
                                dtype=jnp.int32))

    with jax.named_scope("kmeans.cost"):
        cost, sizes = _walk(Xt, origin, block, body, (
            jnp.float32(0.0), jnp.zeros((k,), jnp.int32)))
        return coll.psum(cost), coll.psum(sizes)


#: entries of one run of `_flagged`
_RUN = 1024


def _flagged(flags, slots: int):
    """(the places of the first `slots` true entries of `flags`, in order;
    how many of the slots are filled). No scatter and no running count
    over the whole array: the true entries of every run of `_RUN` are
    counted, a slot finds its run by a binary search over the runs'
    running count and its place inside by the run's own."""
    n = flags.shape[0]
    run = min(_RUN, n)
    runs = jnp.pad(flags, (0, -n % run)).reshape(-1, run)
    per = jnp.sum(runs, axis=1, dtype=jnp.int32)
    upto = jnp.cumsum(per)
    want = jnp.arange(1, slots + 1, dtype=jnp.int32)
    r = jnp.minimum(jnp.searchsorted(upto, want), per.shape[0] - 1)
    inside = jnp.cumsum(runs[r].astype(jnp.int32), axis=1)
    place = jnp.sum(inside < (want - (upto[r] - per[r]))[:, None], axis=1)
    at = jnp.minimum(r * run + place, n - 1).astype(jnp.int32)
    return at, jnp.minimum(upto[-1], slots)


def _row_uniforms(key, rows):
    """A uniform draw in [0, 1) a GLOBAL row number: the same whatever the
    mesh and the block (one threefry a row, the folded key's first word)."""
    words = jax.random.key_data(jax.vmap(partial(jax.random.fold_in, key))(
        rows.astype(jnp.uint32)))[..., 0]
    return (words >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)


def _global_rows(Xt, rows, origin, shard_lo):
    """The columns of the GLOBAL rows `rows` (m,) of a sharded `Xt`, less
    `origin`, on every shard: each gives the rows it holds."""
    local = rows - shard_lo
    mine = (local >= 0) & (local < Xt.shape[1])
    got = jnp.take(Xt, jnp.clip(local, 0, Xt.shape[1] - 1), axis=1)
    return coll.psum(jnp.where(mine[None, :], got - origin[:, None], 0.0))


def _weighted_pick(p, u):
    """The first place whose running sum of `p` passes `u` x the total
    (MLlib `LocalKMeans`' pick): a draw with probability p / Σp."""
    run = jnp.cumsum(p)
    at = jnp.sum(run <= u * run[-1], dtype=jnp.int32)
    return jnp.minimum(at, p.shape[0] - 1)


def _local_kmeans(points, weights, valid, k: int, key):
    """k centers of the weighted candidates `points` (d, m): MLlib's
    `LocalKMeans.kMeansPlusPlus`. The first by weight, each next with
    probability weight x squared distance to the nearest chosen, then at
    most `_LOCAL_ITERATIONS` weighted Lloyd steps, until no candidate
    changes its center. Departures: where every candidate's distance is 0
    (fewer distinct candidates than k) the next is drawn by weight, and an
    empty cluster keeps its center (MLlib re-seeds it with a random
    candidate)."""
    d, m = points.shape
    w = jnp.where(valid, weights, 0.0)
    draws = jax.random.uniform(key, (k,))

    def distance_to(j):
        c = jax.lax.dynamic_slice(points, (0, j), (d, 1))
        return c[:, 0], jnp.sum((points - c) ** 2, axis=0)

    def pick(i, carry):
        centers, cost = carry
        p = w * cost
        p = jnp.where(jnp.sum(p) > 0, p, w)
        c, d2 = distance_to(_weighted_pick(p, draws[i]))
        return centers.at[i].set(c), jnp.minimum(cost, d2)

    c0, cost = distance_to(_weighted_pick(w, draws[0]))
    centers = jnp.zeros((k, d), jnp.float32).at[0].set(c0)
    centers, _ = jax.lax.fori_loop(1, k, pick, (centers, cost))

    def step(carry):
        centers, old, _, it = carry
        idx, _ = _nearest(points, centers)
        onehot = jnp.where(jnp.arange(k)[:, None] == idx[None, :],
                           w[None, :], 0.0)
        sums = jax.lax.dot_general(
            _product_operand(onehot), _product_operand(points),
            (((1,), (1,)), ((), ())), precision=_PRECISE)
        counts = jnp.sum(onehot, axis=1)
        centers = jnp.where(counts[:, None] > 0,
                            sums / jnp.maximum(counts, 1.0)[:, None], centers)
        return centers, idx, jnp.any((idx != old) & valid), it + 1

    centers, _, _, _ = jax.lax.while_loop(
        lambda c: c[2] & (c[3] < _LOCAL_ITERATIONS), step,
        (centers, jnp.full((m,), -1, jnp.int32), jnp.bool_(True),
         jnp.int32(0)))
    return centers


def _parallel_seeding(Xt, live, origin, shard_lo, n, key, k: int,
                      steps: int, shards: int):
    """k-means|| (MLlib `initKMeansParallel`): a first center drawn
    uniformly; `steps` rounds that each draw EVERY row independently with
    probability 2k·d²/φ (d² its squared distance to the nearest candidate
    so far, φ their sum) and add the picks to the candidates; the
    candidates weighted by the rows nearest to each; `_local_kmeans` over
    them. Every round reads all rows: a blocked pass against the round's
    NEW candidates keeps each row's nearest candidate and its distance.
    Returns (centers (k, d) about `origin`, candidates kept)."""
    d, rows = Xt.shape
    slots = _candidate_slots(k)
    block = _block_rows(slots)
    m = 128 + steps * slots
    k0, k1, k2 = jax.random.split(key, 3)
    g = shard_lo + jnp.arange(rows, dtype=jnp.int32)

    first = jnp.minimum((jax.random.uniform(k0) * n).astype(jnp.int32),
                        n - 1)
    c0 = _global_rows(Xt, first[None], origin, shard_lo)      # (d, 1)
    cands = jnp.zeros((d, m), jnp.float32).at[:, :1].set(c0)
    valid = jnp.zeros((m,), bool).at[0].set(True)
    cost = jnp.sum((Xt - origin[:, None] - c0) ** 2, axis=0)
    near = jnp.zeros((rows,), jnp.int32)

    for r in range(steps):
        phi = coll.psum(jnp.sum(jnp.where(live, cost, 0.0)))
        u = _row_uniforms(jax.random.fold_in(k1, r), g)
        at, filled = _flagged(live & (u * phi < 2.0 * k * cost), slots)
        new = jnp.take(Xt, at, axis=1) - origin[:, None]
        new_valid = jnp.arange(slots) < filled
        if shards > 1:
            # every shard's picks in the shards' order (the rows' own),
            # kept to the round's slots the same way
            every = jnp.moveaxis(coll.all_gather(new), 0, 1).reshape(d, -1)
            at, filled = _flagged(coll.all_gather(new_valid).reshape(-1),
                                  slots)
            new = jnp.take(every, at, axis=1)
            new_valid = jnp.arange(slots) < filled
        off = 128 + r * slots
        cands = jax.lax.dynamic_update_slice(cands, new, (0, off))
        valid = jax.lax.dynamic_update_slice(valid, new_valid, (off,))

        def body(lo, xb, fresh, carry):
            cost, near = carry
            idx, d2 = _nearest(xb, new.T, new_valid)
            held = _rows_of(cost, lo, block)
            closer = d2 < held
            return (jax.lax.dynamic_update_slice(
                        cost, jnp.where(closer, d2, held), (lo,)),
                    jax.lax.dynamic_update_slice(
                        near, jnp.where(closer, off + idx,
                                        _rows_of(near, lo, block)), (lo,)))

        cost, near = _walk(Xt, origin, block, body, (cost, near))

    def count(lo, xb, fresh, weights):
        hit = _assigned(_rows_of(near, lo, block),
                        fresh & _rows_of(live, lo, block), m)
        return weights + jnp.sum(hit, axis=1, dtype=jnp.int32)

    weights = coll.psum(_walk(Xt, origin, block, count,
                              jnp.zeros((m,), jnp.int32)))
    centers = _local_kmeans(cands, weights.astype(jnp.float32), valid, k, k2)
    return centers, jnp.sum(valid.astype(jnp.int32))


@lru_cache(maxsize=64)
def _fit_program(k: int, mode: str, steps: int):
    """The WHOLE fit as one XLA program a (k, seeding): the column means,
    the seeding, the Lloyd loop and the cost at the returned centers.
    `max_iter`, `tol` and the seed are operands: a sweep over `maxIter`
    (MLE 02's) is one compiled program. The blocks and the mesh's width
    are read where the program is traced, under the mesh it was routed to.

    Program args: Xt (d, rows) row-sharded along its last axis, mask
    (rows,), then replicated: key data (2,) uint32, max_iter, tol, and for
    `initMode="random"` the k GLOBAL rows drawn on the host.
    Returns (centers (k, d), the column means, cost and the clusters'
    sizes at those centers, Lloyd steps run, whether `tol` ended them,
    clusters the last step left empty, rows assigned over all steps,
    candidates the seeding kept, blocks a step walks a shard in)."""
    def kmeans_fit(Xt, mask, key_data, max_iter, tol, random_rows):
        shards = meshlib.data_width(meshlib.get_mesh())
        block = _block_rows(k)
        live = mask > 0
        rows = Xt.shape[1]
        shard_lo = (coll.axis_index() * rows).astype(jnp.int32) \
            if shards > 1 else jnp.int32(0)
        n = coll.psum(jnp.sum(live.astype(jnp.int32)))
        # the expansion's origin: the column means (padding rows are 0)
        origin = coll.psum(jnp.sum(Xt, axis=1)) / n.astype(jnp.float32)
        with jax.named_scope("kmeans.init"):
            if mode == "random":
                centers = _global_rows(Xt, random_rows, origin, shard_lo).T
                kept = jnp.int32(0)
            else:
                centers, kept = _parallel_seeding(
                    Xt, live, origin, shard_lo, n,
                    jax.random.wrap_key_data(key_data, impl="threefry2x32"),
                    k, steps, shards)

        def step(carry):
            centers, _, it, _, assigned = carry
            sums, counts = _lloyd_pass(Xt, live, origin, centers, block)
            with jax.named_scope("kmeans.update"):
                moved = jnp.where(
                    counts[:, None] > 0,
                    sums / jnp.maximum(counts, 1)[:, None].astype(
                        jnp.float32), centers)
                shift = jnp.max(jnp.sum((moved - centers) ** 2, axis=1))
            return (moved, shift <= tol * tol, it + 1,
                    jnp.sum((counts == 0).astype(jnp.int32)),
                    assigned + jnp.sum(counts).astype(jnp.uint32))

        centers, converged, steps_run, empty, assigned = jax.lax.while_loop(
            lambda c: (c[2] < max_iter) & ~c[1], step,
            (centers, jnp.bool_(False), jnp.int32(0), jnp.int32(0),
             jnp.uint32(0)))
        cost, sizes = _cost_pass(Xt, live, origin, centers, block)
        return (centers + origin[None, :], origin, cost, sizes, steps_run,
                converged, empty, assigned, kept,
                jnp.int32(-(-rows // min(block, rows))))

    return kmeans_fit


@lru_cache(maxsize=2)
def _assign_program(want_rows: bool):
    """A table's rows assigned to given centers by the fit's blocked
    routine: every row's cluster (`want_rows`, row-sharded) or the cost.
    Program args: Xt (d, rows), mask, then replicated the centers and the
    origin the distances are expanded about."""
    def kmeans_assign(Xt, mask, centers, origin):
        about = centers - origin[None, :]
        block = _block_rows(centers.shape[0])
        if not want_rows:
            return _cost_pass(Xt, mask > 0, origin, about, block)[0]

        def body(lo, xb, fresh, out):
            idx, _ = _nearest(xb, about)
            return jax.lax.dynamic_update_slice(out, idx, (lo,))

        with jax.named_scope("kmeans.assign"):
            return _walk(Xt, origin, block, body,
                         jnp.zeros((Xt.shape[1],), jnp.int32))

    return kmeans_assign


def forget_programs() -> None:
    """Drop the fit and assignment programs traced so far: for whoever
    replaces a seam of this module (`_block_rows`, `_product_operand`,
    `_first_min`: the tests and `benchmark/tools_kmeans.py`), since a
    program is traced once a (k, seeding) and a mesh."""
    from . import _staging
    _fit_program.cache_clear()
    _assign_program.cache_clear()
    _staging._compiled_cache.clear()


def _feature_major(df, featuresCol: str) -> np.ndarray:
    """The frame's features as the programs read them: float32 (d, rows).
    The column plan's block as it wrote it where a `Pipeline.fit` made
    one (`featurizer._try_fast_fit`), else the (rows, d) block turned."""
    feat = getattr(df, "_featurized_compact", None)
    if feat is not None and featuresCol in feat:
        return feat[featuresCol][0].num
    return np.ascontiguousarray(extract_features(df, featuresCol).T)


def _assign(Xt: np.ndarray, centers: np.ndarray, origin: np.ndarray,
            want_rows: bool):
    """`_assign_program` over a host block (d, rows)."""
    k, (d, rows) = len(centers), Xt.shape
    out = run_data_parallel(
        _assign_program(want_rows), RowsLast(Xt),
        out_replicated=not want_rows,
        replicated=(np.asarray(centers, np.float32),
                    np.asarray(origin, np.float32)),
        work=WorkHint(flops=2.0 * rows * d * k, kind="blas",
                      out_bytes=4.0 * rows if want_rows else 256.0))
    return np.asarray(out)[:rows] if want_rows else float(out)


class KMeans(Estimator):
    def _init_params(self):
        self._declareParam("featuresCol", default="features", doc="features column")
        self._declareParam("predictionCol", default="prediction", doc="cluster column")
        self._declareParam("k", default=2, doc="number of clusters")
        self._declareParam("maxIter", default=20, doc="most Lloyd iterations")
        self._declareParam("seed", default=None, doc="the seeding's draws")
        self._declareParam("initMode", default="k-means||",
                           doc="'k-means||' (Bahmani et al.) or 'random' (k distinct rows)")
        self._declareParam("initSteps", default=2,
                           doc="rounds of k-means||, each over all rows")
        self._declareParam("tol", default=1e-4,
                           doc="the loop ends when no center moved farther (Euclidean)")
        self._declareParam("distanceMeasure", default="euclidean",
                           doc="'euclidean' ('cosine' is not implemented)")

    def __init__(self, featuresCol=None, predictionCol=None, k=None,
                 maxIter=None, seed=None, initMode=None, tol=None,
                 initSteps=None, distanceMeasure=None):
        super().__init__()
        self._set(featuresCol=featuresCol, predictionCol=predictionCol, k=k,
                  maxIter=maxIter, seed=seed, initMode=initMode, tol=tol,
                  initSteps=initSteps, distanceMeasure=distanceMeasure)

    def setK(self, v):
        return self._set(k=v)

    def setSeed(self, v):
        return self._set(seed=v)

    def setMaxIter(self, v):
        return self._set(maxIter=v)

    def _fit(self, df) -> "KMeansModel":
        """The fit's standard children under the root `fit`: the feature
        block (a `Pipeline.fit`'s column plan made it inside
        `fit.featurize`; alone, the frame's vector column is turned
        here), `kmeans.init.local` (what of the seeding runs on the host:
        the draws' key, and the rows of `initMode="random"`), and inside
        `program.kmeans_fit` the four of every program: `fit.stage`,
        `fit.dispatch`, `fit.device_wait`, `fit.readback`."""
        from ..utils.profiler import PROFILER
        from ._staging import transient_hbm
        k = int(self.getOrDefault("k"))
        max_iter = int(self.getOrDefault("maxIter"))
        steps = int(self.getOrDefault("initSteps"))
        mode = self.getOrDefault("initMode")
        seed = self.getOrDefault("seed")
        seed = int(seed) if seed is not None else 0
        if mode not in ("k-means||", "random"):
            raise ValueError(f"initMode {mode!r} is neither 'k-means||' "
                             f"nor 'random'")
        if self.getOrDefault("distanceMeasure") != "euclidean":
            raise ValueError("distanceMeasure: only 'euclidean' is "
                             "implemented")
        if k < 1 or max_iter < 0 or steps < 1:
            raise ValueError("k and initSteps must be positive and maxIter "
                             "not negative")
        Xt = _feature_major(df, self.getOrDefault("featuresCol"))
        d, n = Xt.shape
        if n == 0:
            raise ValueError("KMeans.fit of a frame with no rows")
        with PROFILER.span("kmeans.init.local", mode=mode):
            # the draws' key as jax.random.key(seed) lays it out, made
            # here: asking jax for it would be a dispatch of its own
            key = np.asarray([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                             np.uint32)
            random_rows = np.zeros(k, np.int32)
            if mode == "random":
                random_rows = np.sort(np.random.default_rng(seed).choice(
                    n, size=k, replace=n < k)).astype(np.int32)

        width = max(k, _candidate_slots(k)) if mode == "k-means||" else k
        passes = max_iter + 1 + (steps + 1 if mode == "k-means||" else 0)
        with transient_hbm("kmeans_block",
                           _block_bytes(width, _block_rows(width))):
            (centers, origin, cost, sizes, steps_run, converged, empty,
             assigned, kept, blocks) = run_data_parallel(
                _fit_program(k, mode, steps), RowsLast(Xt),
                replicated=(key, np.int32(max_iter),
                            np.float32(self.getOrDefault("tol")),
                            random_rows),
                work=WorkHint(flops=4.0 * passes * n * d * k, kind="blas",
                              out_bytes=8.0 * k * d))
        PROFILER.count("kmeans.fits")
        PROFILER.count("kmeans.iterations", int(steps_run))
        PROFILER.count("kmeans.converged", int(converged))
        PROFILER.count("kmeans.init.rounds",
                       steps if mode == "k-means||" else 0)
        PROFILER.count("kmeans.init.candidates", int(kept))
        PROFILER.count("kmeans.blocks", int(blocks))
        PROFILER.count("kmeans.rows", int(assigned))
        PROFILER.count("kmeans.empty_clusters", int(empty))
        m = KMeansModel(centers=np.asarray(centers), origin=np.asarray(origin),
                        trainingCost=float(cost), iterations=int(steps_run),
                        sizes=np.asarray(sizes))
        m._inherit_params(self)
        return m


class KMeansSummary:
    """What the fit's ONE dispatch read back beside the centers: the cost
    and the clusters' sizes of the training rows AT the returned centers,
    and the Lloyd steps run."""

    def __init__(self, trainingCost: float, k: int, numIter: int = 0,
                 clusterSizes: Optional[list] = None):
        self.trainingCost = trainingCost
        self.k = k
        self.numIter = numIter
        self.clusterSizes = clusterSizes


class KMeansModel(Model):
    def _init_params(self):
        KMeans._init_params(self)

    def __init__(self, centers: Optional[np.ndarray] = None,
                 trainingCost: float = 0.0,
                 origin: Optional[np.ndarray] = None, iterations: int = 0,
                 sizes: Optional[np.ndarray] = None):
        super().__init__()
        self._centers = centers
        self._trainingCost = trainingCost
        self._iterations = iterations
        self._origin = origin
        self._sizes = sizes

    def clusterCenters(self):
        return [c for c in np.asarray(self._centers, dtype=np.float64)]

    def _expansion_origin(self) -> np.ndarray:
        """The point the distances are expanded about: the training rows'
        column means (a model saved before they were kept: its centers'
        mean; any point gives the same distances, a near one the better
        float32 ones)."""
        if self._origin is not None:
            return self._origin
        return np.mean(self._centers, axis=0, dtype=np.float64).astype(
            np.float32)

    @property
    def summary(self) -> KMeansSummary:
        return KMeansSummary(
            self._trainingCost, len(self._centers), self._iterations,
            None if self._sizes is None else [int(c) for c in self._sizes])

    def computeCost(self, df) -> float:
        Xt = _feature_major(df, self.getOrDefault("featuresCol"))
        return _assign(Xt, self._centers, self._expansion_origin(), False)

    def _transform(self, df):
        oc = self.getOrDefault("predictionCol")
        fc = self.getOrDefault("featuresCol")
        centers, origin = self._centers, self._expansion_origin()

        def fn(pdf: pd.DataFrame, ctx) -> pd.DataFrame:
            out = pdf.copy(deep=False)  # CoW: column adds never touch the parent
            if len(out) == 0:
                out[oc] = pd.Series(dtype=int)
                return out
            Xt = np.ascontiguousarray(extract_features(out, fc).T)
            out[oc] = _assign(Xt, centers, origin, True).astype(np.int32)
            return out

        return df._derive_rowlocal(fn)

    def _save_state(self, path):
        save_arrays(path, centers=self._centers,
                    cost=np.asarray([self._trainingCost]),
                    origin=self._expansion_origin())

    def _load_state(self, path, meta):
        d = load_arrays(path)
        self._centers = d["centers"]
        self._trainingCost = float(d["cost"][0])
        self._origin = d.get("origin") if hasattr(d, "get") else None


class BisectingKMeans(KMeans):
    """Accepted for surface parity and NOT a bisecting fit: it trains the
    plain `KMeans` above (the course only instantiates the default
    variant; ROADMAP.md Queue 2 says what a bisecting fit would take)."""
