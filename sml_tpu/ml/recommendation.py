"""ALS — block-parallel alternating least squares (SURVEY §2.2 P4).

The reference's `ALS(userCol, itemCol, ratingCol, rank, maxIter,
coldStartStrategy)` trains MovieLens 1M (`SML/ML Electives/MLE 01 -
Collaborative Filtering Lab.py:159-201`). Spark's implementation blocks
users/items across executors and shuffles factor blocks; here the WHOLE
alternating fit is ONE jitted shard_map program (`fori_loop` over
iterations), each half-step inside it:

    per chip:  the normal equations by ROW BLOCKS of the entity-sorted
               order: gather the other side's factor rows of a block,
               form its statistics (the upper triangle of f_i ⊗ f_i, and
               r·f_i), sum them by segment, sums that begin anew at each
               entity: ONE masked matrix product a tile of 128 rows, the
               tiles' last rows summed the same way a level up
               (`_segment_sums`); add a block's sums into the accumulators
    psum       over ICI (the factor-block exchange)
    batched    Cholesky solve of all the normal systems on-device

with ALS-WR regularization (λ·n_u, Spark's scheme). Ratings AND factors stay
in HBM for the entire fit: one dispatch, one packed factor download. No
array of ratings x statistics exists: a block's is the largest
(`_block_rows`), so the table's size is bounded by its four row arrays and
not by rank² times them (docs/KERNELS.md "The blocked normal equations")."""

from __future__ import annotations

from functools import lru_cache
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd

from ..parallel import collectives as coll
from .base import Estimator, Model, load_arrays, save_arrays

#: a dispatch's block temporaries may take this share of the device's
#: memory (`_block_rows`): the block's statistics and their running sums
#: (`_segment_sums`), counted as `_BLOCK_COPIES` arrays of a row's
#: statistics padded to the chip's 128 lanes. Read from the compiled
#: program's memory analysis at rank 12 and blocks of 2^21 rows: 2.21 GB,
#: 2.06 such arrays (the statistics a tile at a time with the rows along
#: the lanes, 0.81 GB; their running sums row by row, 1.07 GB; the
#: gathered factor rows), rounded up: the scan this replaced held 3.46 GB
#: (PERF.md sections 4 and 6)
_BLOCK_SHARE = 0.25
_BLOCK_COPIES = 3
_LANES = 128
#: the memory of a device that reports none (the CPU test mesh): a v5e's
_DEVICE_BYTES = 16 << 30


def _stat_width(rank: int) -> int:
    """Columns of a rating's statistics: the upper triangle of f ⊗ f, then
    r·f."""
    return rank * (rank + 1) // 2 + rank


def _block_bytes(rank: int, rows: int) -> int:
    """Bytes of a block's temporaries: `_BLOCK_COPIES` float32 arrays of
    its rows' statistics, the columns padded to whole lane tiles."""
    return 4 * _BLOCK_COPIES * rows * _LANES * -(-_stat_width(rank) // _LANES)


def _block_rows(rank: int) -> int:
    """Rows of one block of the blocked build: the largest power of two
    whose temporaries fit `_BLOCK_SHARE` of the active mesh's first
    device."""
    from ..parallel import mesh as meshlib
    stats = meshlib.get_mesh().devices.flat[0].memory_stats() or {}
    budget = _BLOCK_SHARE * float(stats.get("bytes_limit", _DEVICE_BYTES))
    return 1 << max(int(budget // _block_bytes(rank, 1)).bit_length() - 1, 0)


def _stat_operands(f, rat):
    """The operands of a block's statistics, as they enter the products:
    the gathered factor rows and the ratings. The identity; the seam where
    `benchmark/tools_als.py` rounds them for the lower-precision control."""
    return f, rat


#: rows of a tile of `_segment_sums`: a side of the chip's matrix unit (and
#: the lanes of a vector register: a tile's mask is whole registers)
_TILE = 128


def _tiles(stats, begins):
    """`stats` (rows, width) and `begins` (rows,) as tiles of `_TILE` rows
    (one tile of a block no longer than that): (tiles, tile, width) and
    (tiles, tile), the last tile filled with rows of zeros that begin
    nothing, past every row an entity's bounds reach."""
    rows, width = stats.shape
    tile = min(_TILE, rows)
    pad = -rows % tile
    if pad:
        stats = jnp.pad(stats, ((0, pad), (0, 0)))
        begins = jnp.pad(begins, (0, pad))
    return stats.reshape(-1, tile, width), begins.reshape(-1, tile)


def _tile_sums(x, flags):
    """(run, open) of tiles `x` (tiles, tile, width) whose rows `flags`
    (tiles, tile) begin a segment: `run[n, r]` the sum of the tile's rows
    from the last flagged one at or before r (the tile's first where
    `open[n, r]`: none is) to r. ONE masked product a tile: with `seg` the
    running count of the flags down a tile, `M[n, r, c]` is 1 for the rows
    c of r's own segment up to r and 0 for every other, so a sum holds its
    own rows alone (a 0 multiplies the rest: no prefix spans two segments,
    and no difference of prefixes is taken), the mask is exact in every
    pass of the matrix unit and the accumulator is float32. The mask is
    made from the flags where the product reads it and is never kept."""
    at = jnp.arange(x.shape[1])
    seg = jnp.cumsum(flags, axis=1, dtype=jnp.int32)
    mask = (seg[:, :, None] == seg[:, None, :]) & (at <= at[:, None])
    run = jnp.einsum("nrc,ncw->nrw", mask.astype(x.dtype), x,
                     precision=jax.lax.Precision.HIGHEST)
    return run, seg == 0


def _carries(run, flags):
    """What the tiles before it hand every tile, (tiles, width): the sum
    from the last flagged row before the tile (the first row where none
    is) to the tile's first row, 0 for the first tile. A tile hands on its
    LAST row's running sum and whether it holds a flag: a row a tile, so
    the level above is the same sums over 1/`_TILE` of the rows. Those rows
    are gathered: a strided slice of them reads the whole level."""
    tiles, tile, width = run.shape
    last = run.reshape(-1, width)[(jnp.arange(tiles) + 1) * tile - 1]
    held = _running_sums(last, flags.any(axis=1))
    return jnp.concatenate([jnp.zeros_like(held[:1]), held[:-1]])


def _running_sums(stats, begins):
    """Inclusive running sums of `stats` down axis 0 that begin anew at
    every row `begins` flags, every row whole: a tile's rows before its
    first flagged one take what the tiles before it carry. For the levels
    above the first, where a pass over every row costs nothing."""
    rows, width = stats.shape
    x, flags = _tiles(stats, begins)
    run, open_ = _tile_sums(x, flags)
    if x.shape[0] > 1:
        run = run + jnp.where(open_[:, :, None],
                              _carries(run, flags)[:, None, :], 0.0)
    return run.reshape(-1, width)[:rows]


def _segment_sums(stats, begins):
    """(run, carry) of a block's `stats` (rows, width) whose rows `begins`
    flags begin an entity's segment: the running sums in levels of radix
    `_TILE`. `run[i]` is row i's sum WITHIN ITS TILE (`_tile_sums`) and
    `carry[n]` what the rows before tile n hand it (`_carries`), so the
    sum from a segment's first row s to its row i is `run[i]`, plus
    `carry[i // tile]` where s lies before i's tile: the row a segment ends
    on holds the segment's sum, and no pass fixes up every row of the
    first level. No prefix ever spans two segments, so a sum carries the
    rounding of its own rows alone (a difference of two table-long
    prefixes carried ~4 % median error at MovieLens-25M scale in plain
    float32 — r4 review — and took a double-single pair of them to mend).
    `run` holds whole tiles: `rows` rounded up."""
    width = stats.shape[1]
    with jax.named_scope("als.normal.tiles"):
        x, flags = _tiles(stats, begins)
        run, _ = _tile_sums(x, flags)
        flat = run.reshape(-1, width)
    with jax.named_scope("als.normal.carry"):
        carry = _carries(run, flags) if x.shape[0] > 1 \
            else jnp.zeros((1, width), run.dtype)
    return flat, carry


def _block_sums(stats, begins, s, t):
    """Every entity's sum of `stats` over its rows [s, t) of one block
    (none where t == s): the running sum at the last of them, with what
    the tiles before that row's tile carry where the segment began before
    it; gathers of `entities` rows. The seam where
    `benchmark/tools_als.py` puts a plain float32 prefix and its boundary
    difference for the control."""
    run, carry = _segment_sums(stats, begins)
    tile = run.shape[0] // carry.shape[0]
    with jax.named_scope("als.normal.carry"):
        last = jnp.maximum(t - 1, 0)
        at = last // tile                   # the tile of an entity's last row
        total = run[last] + jnp.where((s < at * tile)[:, None],
                                      carry[at], 0.0)
        return jnp.where((t > s)[:, None], total, 0.0)


def _cholesky_solve(A, b):
    """x of A x = b for symmetric positive-definite systems laid out
    BATCH-LAST: A (rank, rank, n), b (rank, n). A column Cholesky and the
    two triangular solves as `fori_loop`s of elementwise steps over whole
    (rank, n) planes, so the batch runs along the lanes and no (n, rank,
    rank) tile of 12 x 12 padded to 16 x 128 is ever made; float32
    throughout, no matrix unit."""
    rank = A.shape[0]
    rows = jnp.arange(rank)

    def factor(j, carry):
        A, L = carry
        col = A[:, j, :] * jax.lax.rsqrt(A[j, j, :])[None, :]
        col = jnp.where((rows >= j)[:, None], col, 0.0)
        return A - col[:, None, :] * col[None, :, :], L.at[:, j, :].set(col)

    _, L = jax.lax.fori_loop(0, rank, factor, (A, jnp.zeros_like(A)))

    def forward(j, y):     # L y = b: y_j from the y_k, k < j, set so far
        return y.at[j].set((b[j] - (L[j] * y).sum(0)) / L[j, j])

    y = jax.lax.fori_loop(0, rank, forward, jnp.zeros_like(b))

    def backward(k, x):    # L' x = y, from the last row up
        j = rank - 1 - k
        return x.at[j].set((y[j] - (L[:, j] * x).sum(0)) / L[j, j])

    return jax.lax.fori_loop(0, rank, backward, jnp.zeros_like(b))


@lru_cache(maxsize=64)
def _als_fit_program(n_users: int, n_items: int, rank: int, reg: float,
                     max_iter: int, nonneg: bool, block_rows: int = 0):
    """The WHOLE alternating fit as one XLA program: `fori_loop` over
    iterations, both half-steps inside, factors living on-device for the
    entire fit. One dispatch per fit instead of 2·maxIter — the per-launch
    round trips disappear, and the CPU test mesh never has multiple
    collective executables racing one rendezvous (r3: 20 async half-step
    launches could deadlock XLA:CPU's cross-module all-reduce).

    SORTED-SEGMENT normal equations, no scatters: `segment_sum` lowers to
    a serialized HBM read-modify-write scatter on TPU and made the
    half-steps ~3x slower than a sorted formulation (an old chip reading:
    1.9s → 0.6s for a 10-iteration MovieLens-1M-scale fit). The rating
    triples are sorted by entity ON HOST once per fit (ids are static
    across iterations, so the permutation is too); each shard holds a
    contiguous slice of the sorted order plus its clipped local
    [start, end) bounds per entity, and walks its slice in blocks of
    `block_rows` rows (0: the whole slice is one block). A block gathers
    the other side's factor rows, forms its rows' statistics, sums them by
    segment (`_segment_sums`) and adds, for every entity, the running sum
    at the last of its rows that the block holds: a segment that spans a
    block's end adds up across blocks exactly as one that spans a shard's
    end adds up under `psum`. Padding rows sit past every real segment's
    end, so no entity's bounds reach them.

    Program args (leading axis row-sharded unless noted):
      ius     item ids in user-sorted order     (rows,)
      usi     user ids in item-sorted order     (rows,)
      rat_u   ratings in user-sorted order      (rows,)
      rat_i   ratings in item-sorted order      (rows,)
      ub      per-shard user bounds             (1, 2, n_users) per shard
      ib      per-shard item bounds             (1, 2, n_items) per shard
      uf0/if0 replicated factor inits
    Returns (user factors, item factors, half-steps run).
    """
    tri_i, tri_j = np.triu_indices(rank)
    n_tri, width = len(tri_i), _stat_width(rank)
    # stats = (f @ left) * ([f r] @ right): 0/1 selections, so the matrix
    # unit only routes columns (exact at HIGHEST) and every statistic is
    # one float32 product, with the rows on the sublanes all the way
    left = np.zeros((rank, width), np.float32)
    right = np.zeros((rank + 1, width), np.float32)
    left[tri_i, np.arange(n_tri)] = 1.0
    right[tri_j, np.arange(n_tri)] = 1.0
    left[np.arange(rank), n_tri + np.arange(rank)] = 1.0
    right[rank, n_tri:] = 1.0
    # where A[i, j] lies among the statistics
    tri_of = np.zeros((rank, rank), np.int32)
    tri_of[tri_i, tri_j] = tri_of[tri_j, tri_i] = np.arange(n_tri)
    select = jax.lax.Precision.HIGHEST

    def side(ids, rat, bounds):
        """A side's row arrays as the blocks read them: padded to whole
        blocks, with the rows that begin a segment flagged (once a fit:
        the order is static across iterations)."""
        rows = ids.shape[0]
        block = min(block_rows or rows, rows)
        pad = -rows % block
        begins = jnp.zeros(rows + pad, bool).at[bounds[0]].set(
            True, mode="drop")
        return (jnp.pad(ids, (0, pad)), jnp.pad(rat, (0, pad)), begins,
                block)

    def half(other, ids, rat, begins, block, bounds, n_out):
        starts, ends = bounds[0], bounds[1]

        tile = min(_TILE, block)

        def rows_of(a, lo):
            """A block's rows of `a`, filled to whole tiles."""
            return jnp.pad(jax.lax.dynamic_slice(a, (lo,), (block,)),
                           (0, -block % tile))

        def one_block(k, acc):
            lo = k * block
            with jax.named_scope("als.gather"):
                f = other[rows_of(ids, lo)]
            with jax.named_scope("als.normal"):
                f, r = _stat_operands(f, rows_of(rat, lo))
                # a tile at a time, as `_segment_sums` reads them: the
                # compiler then lays a tile's statistics out once, for the
                # two selections and the tile's product alike
                f = f.reshape(-1, tile, rank)
                fr = jnp.concatenate(
                    [f, r.reshape(-1, tile, 1)], axis=2)
                stats = jnp.einsum("nck,kw->ncw", f, left, precision=select) \
                    * jnp.einsum("nck,kw->ncw", fr, right, precision=select)
                return acc + _block_sums(
                    stats.reshape(-1, width), rows_of(begins, lo),
                    jnp.clip(starts - lo, 0, block),
                    jnp.clip(ends - lo, 0, block))

        acc = jax.lax.fori_loop(0, ids.shape[0] // block, one_block,
                                jnp.zeros((n_out, width), jnp.float32))
        with jax.named_scope("als.normal"):
            with jax.named_scope("als.normal.allreduce"):
                acc = coll.psum(acc)
                cnt = coll.psum((ends - starts).astype(jnp.float32))
        with jax.named_scope("als.solve"):
            seg = acc.T                              # (width, n_out)
            lam = reg * jnp.maximum(cnt, 1.0)
            A = seg[tri_of] + lam[None, None, :] * jnp.eye(
                rank, dtype=seg.dtype)[:, :, None]
            sol = _cholesky_solve(A, seg[n_tri:]).T
            sol = jnp.where(cnt[:, None] > 0, sol, 0.0)
            return jnp.maximum(sol, 0.0) if nonneg else sol

    def program(ius, usi, rat_u, rat_i, ub, ib, uf0, if0):
        ub2 = ub[0]  # (2, n_users): this shard's local bounds
        ib2 = ib[0]
        by_user = side(ius, rat_u, ub2)
        by_item = side(usi, rat_i, ib2)

        def body(_, carry):
            uf, itf, steps = carry
            uf = half(itf, *by_user, ub2, n_users)
            itf = half(uf, *by_item, ib2, n_items)
            return uf, itf, steps + 2

        return jax.lax.fori_loop(0, max_iter, body,
                                 (uf0, if0, jnp.int32(0)))

    return program


#: an integer id column whose values span at most this many times its rows
#: (and never fewer than 2^16 values) takes the presence table of
#: `dense_ids`; a wider one, and any other type, takes `np.unique`
_TABLE_SPAN = 8


def _row_chunks(n: int):
    """[lo, hi) row ranges a task each, about one a core of the column
    plan's pool (one range where the work runs inline)."""
    from ._column_plan import _cores, runs_inline
    parts = 1 if runs_inline(n) else _cores()
    edges = np.linspace(0, n, parts + 1).astype(np.int64)
    return [(int(a), int(b)) for a, b in zip(edges[:-1], edges[1:]) if b > a]


def _pooled(fn, n: int) -> list:
    """`fn(lo, hi)` over the row chunks of `n`, on the column plan's pool
    from `_INLINE_ROWS` rows on; the results in the chunks' order."""
    from ._column_plan import run_tasks, runs_inline
    return run_tasks([(lambda lo=lo, hi=hi: fn(lo, hi))
                      for lo, hi in _row_chunks(n)], runs_inline(n))


def dense_ids(raw: np.ndarray):
    """(the distinct ids in order, each row's place among them as int32):
    `np.unique(raw, return_inverse=True)` to the bit, without its sort
    where the ids are integers of a bounded range: a presence table over
    [min, max] and its running count, O(rows), marked and looked up a
    chunk of rows a task."""
    raw = np.asarray(raw)
    n = raw.size
    if n and raw.dtype.kind in "iu":
        lo, hi = int(raw.min()), int(raw.max())
        if hi - lo < max(_TABLE_SPAN * n, 1 << 16) and hi < 1 << 63:
            off = raw.astype(np.int64, copy=False)
            present = np.zeros(hi - lo + 1, bool)

            def mark(a, b):    # every task writes the same True: no race
                present[off[a:b] - lo] = True
            _pooled(mark, n)
            place = np.cumsum(present, dtype=np.int32) - 1
            index = np.empty(n, np.int32)

            def look(a, b):
                np.take(place, off[a:b] - lo, out=index[a:b], mode="clip")
            _pooled(look, n)
            ids = (np.flatnonzero(present) + lo).astype(raw.dtype)
            return ids, index
    ids, index = np.unique(raw, return_inverse=True)
    return ids, index.astype(np.int32)


def stable_order(dense: np.ndarray, n_out: int) -> np.ndarray:
    """`np.argsort(dense, kind="stable")` to the bit for dense ids in
    [0, n_out), as a histogram and a scatter by chunks of rows: a chunk
    sorts its rows' keys (id, row) — unique, so any sort of them is the
    stable order — and counts its ids; a chunk's run of an id then lies in
    the whole order after that id's runs of the chunks before it, and its
    rows are scattered there. The chunks run on the column plan's pool."""
    n = len(dense)
    chunks = _row_chunks(n)
    if len(chunks) < 2:
        return np.argsort(dense, kind="stable")

    def local(lo, hi):
        key = dense[lo:hi].astype(np.uint64) << np.uint64(32)
        key |= np.arange(lo, hi, dtype=np.uint64)
        key.sort()
        ids = (key >> np.uint64(32)).astype(np.int32)
        key &= np.uint64(0xFFFFFFFF)
        return key.astype(np.int64), ids, np.bincount(ids, minlength=n_out)

    sorted_chunks = _pooled(local, n)
    counts = np.stack([c for _, _, c in sorted_chunks])      # (chunks, ids)
    total = counts.sum(axis=0)
    # where a chunk's run of an id begins: in the whole order, and in the
    # chunk's own
    whole = (np.cumsum(total) - total)[None, :] \
        + np.cumsum(counts, axis=0) - counts
    own = np.cumsum(counts, axis=1) - counts
    order = np.empty(n, np.int64)

    def place(c):
        rows, ids, _ = sorted_chunks[c]
        order[np.arange(len(rows)) + (whole[c] - own[c])[ids]] = rows

    from ._column_plan import run_tasks
    run_tasks([(lambda c=c: place(c)) for c in range(len(chunks))], False)
    return order


def segment_bounds(dense: np.ndarray, n_out: int):
    """([start, end) of every id's rows in the id-sorted order, int64):
    `np.searchsorted(sorted ids, arange(n_out))` and `(.. + 1)` to the
    bit, from the ids' counts."""
    counts = np.bincount(dense, minlength=n_out)
    ends = np.cumsum(counts, dtype=np.int64)
    return ends - counts, ends


def sort_als_triples(u32: np.ndarray, i32: np.ndarray, ratings: np.ndarray):
    """Per-side stable sort of the rating triples (host, once per fit —
    ids are static across iterations). Returns the four row arrays the
    program will actually consume, which callers pass to the router so
    residency probes and background promotion see the staged arrays, not
    the unsorted originals, and each side's segment bounds in its order."""
    n_users = int(u32.max()) + 1 if len(u32) else 0
    n_items = int(i32.max()) + 1 if len(i32) else 0
    u_order = stable_order(u32, n_users)
    i_order = stable_order(i32, n_items)
    gathers = (("ius", i32, u_order), ("rat_u", ratings, u_order),
               ("usi", u32, i_order), ("rat_i", ratings, i_order))
    out = {name: np.empty(len(ratings), a.dtype) for name, a, _ in gathers}

    def gather(lo, hi):
        for name, a, order in gathers:
            np.take(a, order[lo:hi], out=out[name][lo:hi], mode="clip")
    _pooled(gather, len(ratings))
    out["u_bounds"] = segment_bounds(u32, n_users)
    out["i_bounds"] = segment_bounds(i32, n_items)
    return out


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

    def bounds_for(bounds, n_out):
        # an id past the last one rated (the bounds stop at the largest
        # id seen) has no row: [n, n)
        g_starts, g_ends = (np.concatenate(
            [b, np.full(n_out - len(b), n, np.int64)]) for b in bounds)
        lo = (np.arange(n_dev) * blk)[:, None]
        hi = lo + blk
        st = np.clip(g_starts[None, :], lo, hi) - lo
        en = np.clip(g_ends[None, :], lo, hi) - lo
        return np.stack([st, en], axis=1).astype(np.int32)  # (n_dev,2,n_out)

    ub = bounds_for(prep["u_bounds"], n_users)
    ib = bounds_for(prep["i_bounds"], n_items)
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
        """The fit's standard children under the root `fit`: `fit.collect`
        (the three columns, from the frame's partitions where they lie),
        `fit.featurize` (`.als.index`: raw ids to dense ids; `.als.sort`:
        the two orders, the sorted row arrays and the bounds; then the
        factors' init), and inside `program.als_fit` the four of every
        program: `fit.stage`, `fit.dispatch`, `fit.device_wait`,
        `fit.readback`."""
        from ..parallel import dispatch
        from ..parallel import mesh as meshlib
        from ..utils.profiler import PROFILER
        from ._column_plan import Pieces
        from ._staging import cached_data_parallel, routed_for, transient_hbm
        uc, ic, rc = (self.getOrDefault("userCol"), self.getOrDefault("itemCol"),
                      self.getOrDefault("ratingCol"))
        rank = int(self.getOrDefault("rank"))
        max_iter = int(self.getOrDefault("maxIter"))
        reg = float(self.getOrDefault("regParam"))
        seed = self.getOrDefault("seed")
        rng = np.random.default_rng(int(seed) if seed is not None else 0)
        nonneg = bool(self.getOrDefault("nonnegative"))

        with PROFILER.span("fit.collect") as note:
            src = Pieces.of(df)
            note["pieces"] = len(src.parts)
            # these three columns alone are gathered: the frame's others
            # never
            users_raw, items_raw = (np.asarray(src.column(c))
                                    for c in (uc, ic))
            ratings = np.asarray(src.column(rc), dtype=np.float32)
        with PROFILER.span("fit.featurize", rows=len(ratings)):
            with PROFILER.span("fit.featurize.als.index"):
                u_ids, u32 = dense_ids(users_raw)
                i_ids, i32 = dense_ids(items_raw)
            U, I = len(u_ids), len(i_ids)
            with PROFILER.span("fit.featurize.als.sort"):
                prep = sort_als_triples(u32, i32, ratings)
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
            # the program's shapes are the entity counts on `bucket_rows`'
            # grid (at most an eighth more, rows of zeros that no rating
            # names and whose solution is 0): a new split of the same
            # table that rates a few items more or fewer is the SAME
            # compiled program
            U_pad, I_pad = (meshlib.bucket_rows(k, 1) for k in (U, I))
            uf0 = np.pad(uf0, ((0, U_pad - U), (0, 0)))
            if0 = np.pad(if0, ((0, I_pad - I), (0, 0)))

        # rating triples staged sharded by row; the build is nnz·rank² a
        # half-step and the (U + I) Cholesky solves rank³ / 3 each
        _hint = dispatch.WorkHint(
            flops=2.0 * max_iter * (len(ratings) * rank * rank
                                    + (U + I) * rank ** 3 / 3.0),
            kind="segment")
        rows = (prep["ius"], prep["usi"], prep["rat_u"], prep["rat_i"])
        with routed_for(_hint, *rows) as _mesh:
            _route = "host" if dispatch.is_host_mesh(_mesh) else "device"
            with PROFILER.span("program.als_fit", rows=len(ratings),
                               route=_route):
                with PROFILER.span("fit.stage", rows=len(ratings)):
                    staged = stage_als_sorted(prep, U_pad, I_pad)
                shard = staged[0].shape[0] // meshlib.data_width(_mesh)
                block = min(_block_rows(rank), shard)
                blocks = -(-shard // block)
                with transient_hbm("als_block", _block_bytes(rank, block)):
                    # ONE dispatch for the whole alternating fit; one
                    # batched device→host transfer for both factor matrices
                    with PROFILER.span("fit.dispatch"):
                        fit = cached_data_parallel(
                            _als_fit_program(U_pad, I_pad, rank, reg,
                                             max_iter, nonneg, block),
                            replicated_argnums=(6, 7))
                        out = fit(*staged, uf0, if0)
                    with PROFILER.span("fit.device_wait"):
                        out = jax.block_until_ready(out)
                with PROFILER.span("fit.readback"):
                    uf_h, itf_h, steps = jax.device_get(out)
        PROFILER.count("staging.d2h_bytes", uf_h.nbytes + itf_h.nbytes)
        PROFILER.count("als.fits")
        PROFILER.count("als.half_steps", int(steps))
        PROFILER.count("als.blocks", blocks)
        PROFILER.count("als.ratings", len(ratings))
        m = ALSModel(user_ids=u_ids, item_ids=i_ids,
                     user_factors=uf_h[:U], item_factors=itf_h[:I])
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
        from ..utils.profiler import PROFILER
        uc, ic = self.getOrDefault("userCol"), self.getOrDefault("itemCol")
        oc = self.getOrDefault("predictionCol")
        cold = self.getOrDefault("coldStartStrategy")

        def fn(pdf: pd.DataFrame, ctx) -> pd.DataFrame:
            out = pdf.copy()
            if len(out) == 0:
                out[oc] = pd.Series(dtype=float)
                return out
            with PROFILER.span("transform.als.lookup", rows=len(out)):
                ui, u_ok = self._lookup(np.asarray(out[uc]), self._user_ids, self._uf)
                ii, i_ok = self._lookup(np.asarray(out[ic]), self._item_ids, self._if)
                pred = np.einsum("ij,ij->i", self._uf[ui], self._if[ii])
                pred = np.where(u_ok & i_ok, pred, np.nan)
            out[oc] = pred.astype(np.float64)
            if cold == "drop":
                keep = np.isfinite(out[oc])
                PROFILER.count("als.cold_start.dropped",
                               int(len(out) - keep.sum()))
                out = out[keep].reset_index(drop=True)
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
