"""Binning and descent of a fitted tree ensemble, in plain NumPy.

A tree is three level-order arrays over 2^(depth+1) - 1 nodes: the feature
a node splits on (-1: a leaf), the bin it splits at (go left iff the row's
bin <= it), and the value of the node as a leaf. The prediction is
base + sum over trees of weight * leaf value, accumulated in float32 in
tree order.
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from .precision import round_to


def bin_features(X: np.ndarray, edges: np.ndarray,
                 cat_rank: Dict[int, np.ndarray],
                 missing: Optional[float] = None) -> np.ndarray:
    """(n, F) features -> (n, F) bin indices. A continuous feature's bin is
    the number of finite edges below its value (upper-inclusive edges; a
    non-finite value, or one equal to `missing`, is absent and goes to
    bin 0); a categorical slot's bin is its category's rank."""
    X = np.asarray(X, dtype=np.float64)
    n, F = X.shape
    bins = np.zeros((n, F), dtype=np.int64)
    for f in range(F):
        col = X[:, f]
        if f in cat_rank:
            rank = cat_rank[f]
            bins[:, f] = rank[np.clip(col.astype(np.int64), 0, len(rank) - 1)]
            continue
        finite = edges[f][np.isfinite(edges[f])].astype(np.float64)
        b = np.searchsorted(finite, col, side="left")
        b[~np.isfinite(col)] = 0
        if missing is not None:
            b[col == missing] = 0
        bins[:, f] = b
    return bins


def terminal_nodes(bins: np.ndarray, split_feature: np.ndarray,
                   split_bin: np.ndarray, depth: int) -> np.ndarray:
    """The node of ONE tree where each row stops."""
    n = bins.shape[0]
    node = np.zeros(n, dtype=np.int64)
    rows = np.arange(n)
    for _ in range(depth):
        f = split_feature[node]
        internal = f >= 0
        right = bins[rows, np.maximum(f, 0)] > split_bin[node]
        node = np.where(internal, 2 * node + 1 + right, node)
    return node


def predict(bins: np.ndarray, tables: Dict,
            precision: Optional[str] = None) -> np.ndarray:
    """base + the weighted sum of the trees' leaf values. `precision` names
    a lower precision for the CONTROL: leaf values, weights and every
    partial sum are rounded to it."""
    sf, sb = tables["split_feature"], tables["split_bin"]
    lv, w = tables["leaf_value"], tables["tree_weight"]
    depth = tables["depth"]
    acc = np.zeros(bins.shape[0], dtype=np.float32)
    for t in range(sf.shape[0]):
        leaf = lv[t][terminal_nodes(bins, sf[t], sb[t], depth)]
        if precision is None:
            acc = acc + np.float32(w[t]) * leaf.astype(np.float32)
        else:
            term = round_to(round_to(w[t], precision)
                            * round_to(leaf, precision), precision)
            acc = round_to(acc + term, precision).astype(np.float32)
    out = np.float64(tables["base"]) + acc.astype(np.float64)
    if precision is not None:
        out = round_to(out, precision)
    return out


def worst_relative_gap(served: np.ndarray, reference: np.ndarray) -> float:
    """max |served - reference| / |reference| over the answers."""
    served = np.asarray(served, dtype=np.float64)
    reference = np.asarray(reference, dtype=np.float64)
    if served.shape != reference.shape:
        return float("inf")
    if not served.size:
        return float("nan")
    gap = np.abs(served - reference) / np.maximum(np.abs(reference), 1e-12)
    return float(np.max(np.where(np.isfinite(gap), gap, np.inf)))
