"""`fitcheck.node_paths` for a table of millions of rows: the same descent,
node by node over whole columns instead of row by row through gathers.

`fitcheck.fit_statistics` sends every training row down every tree up to
the last one it samples (a boosted margin needs each earlier round), and
its `node_paths` does that with four fancy-indexed gathers a level over
int64 temporaries of the table's length: 1.1 to 2.6 s a tree at 6.4 M rows,
77 to 261 s a check (PERF.md section 6, PR 28), most of a run's time limit.
Here a level is, for each of its splitting nodes, one comparison of the
node's feature COLUMN (the bins transposed once a table) with the node's
bin, added into the rows that are at the node: streaming passes over one
byte a row, 0.14 s a tree. The node numbers are `fitcheck.node_paths`'s
own, value for value (tests/benchmark/test_bench_sharded.py holds them and
`fit_statistics`' whole result equal); only their integer type is narrower.
Plain NumPy; nothing of the program under test is imported.
"""

from __future__ import annotations

import contextlib
import weakref

import numpy as np

from . import fitcheck

_columns = [None, None]     # weak reference to the last table, its transpose


def _feature_columns(bins: np.ndarray) -> np.ndarray:
    """(F, n), C-contiguous; made once for each table (`fit_statistics`
    descends the same `bins` for every tree)."""
    if _columns[0] is None or _columns[0]() is not bins:
        _columns[:] = [weakref.ref(bins), np.ascontiguousarray(bins.T)]
    return _columns[1]


def node_paths(bins: np.ndarray, split_feature: np.ndarray,
               split_bin: np.ndarray, depth: int) -> np.ndarray:
    """(depth + 1, n): the node each row is in at every level (a row that
    has reached a leaf stays there). uint8 where the node numbers fit."""
    columns = _feature_columns(bins)
    dtype = np.uint8 if 2 ** (depth + 1) <= 256 else np.int64
    path = np.zeros((depth + 1, bins.shape[0]), dtype=dtype)
    for level in range(depth):
        node, below = path[level], path[level + 1]
        below[:] = node
        first = 2 ** level - 1
        for k in range(first, 2 * first + 1):
            f = int(split_feature[k])
            if f < 0:
                continue
            # a row at k goes to 2k + 1, or 2k + 2 where its bin is over
            # the split's: k + 1 + right more than k
            step = (columns[f] > split_bin[k]).astype(dtype)
            step += dtype(k + 1)
            step *= node == k
            below += step
    return path


@contextlib.contextmanager
def descents():
    """While the block runs, `fitcheck.fit_statistics` descends with
    `node_paths` above."""
    plain = fitcheck.node_paths
    fitcheck.node_paths = node_paths
    try:
        yield
    finally:
        fitcheck.node_paths = plain
        _columns[:] = [None, None]
