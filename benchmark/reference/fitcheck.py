"""A fitted histogram-tree ensemble held to the mathematics of its fit.

For a seeded sample of trees the training rows are sent down the fitted
tree in float64, and at a seeded sample of its nodes:

- split: the gain of the split the fit CHOSE, recomputed in float64 from
  `np.bincount` histograms of the rows that reach the node, is compared
  with the best gain over every allowed (feature, bin). Gains and not
  indices, and the MEDIAN over the sampled nodes of the relative gap: a
  near-tie that bfloat16 histograms resolve the other way costs one node
  a part in a hundred and moves no median, a wrong scan moves every node.
- leaf: the fitted leaf value is compared with the float64 Newton step
  -G / (H + lambda) of the leaf's rows (for a forest: the weighted mean).
  The gap is measured in G and relative to sqrt(sum (g w)^2), the size of
  a sum of independently rounded operands: about 0.002 for bfloat16
  operands (8 significant bits) accumulated in float32 and sixteen times
  that for fp8, whatever the leaf's size.
  The statistic is the MEDIAN over the sampled leaves: a leaf whose rows
  share one label value rounds them all the same way, its error adds up
  coherently, and a mean or a maximum would follow those few leaves.
- cover: the fitted hessian mass of the node is compared with the rows'
  (exact: it is a count).

`precision` turns the same routine into the CONTROL: the split is chosen
and the leaf computed from operands rounded to that precision first.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

import numpy as np

from .precision import round_to


def node_paths(bins: np.ndarray, split_feature: np.ndarray,
               split_bin: np.ndarray, depth: int) -> np.ndarray:
    """(depth + 1, n): the node each row is in at every level (a row that
    has reached a leaf stays there)."""
    n = bins.shape[0]
    rows = np.arange(n)
    path = np.zeros((depth + 1, n), dtype=np.int64)
    for level in range(depth):
        node = path[level]
        f = split_feature[node]
        right = bins[rows, np.maximum(f, 0)] > split_bin[node]
        path[level + 1] = np.where(f >= 0, 2 * node + 1 + right, node)
    return path


def split_scores(bins: np.ndarray, gw: np.ndarray, hw: np.ndarray,
                 w: np.ndarray, n_bins: int, reg_lambda: float,
                 min_instances: float,
                 allowed: Optional[np.ndarray]) -> np.ndarray:
    """(F, n_bins) split scores of one node from its rows' float64
    histograms; -inf where a split is not allowed."""
    F = bins.shape[1]
    score = np.full((F, n_bins), -np.inf)
    G, H, W = gw.sum(), hw.sum(), w.sum()
    for f in range(F):
        if allowed is not None and not allowed[f]:
            continue
        GL = np.cumsum(np.bincount(bins[:, f], weights=gw, minlength=n_bins))
        HL = np.cumsum(np.bincount(bins[:, f], weights=hw, minlength=n_bins))
        WL = np.cumsum(np.bincount(bins[:, f], weights=w, minlength=n_bins))
        s = (GL ** 2 / (HL + reg_lambda + 1e-12)
             + (G - GL) ** 2 / (H - HL + reg_lambda + 1e-12)
             - G ** 2 / (H + reg_lambda + 1e-12))
        ok = (WL >= min_instances) & ((W - WL) >= min_instances)
        ok[n_bins - 1:] = False
        score[f] = np.where(ok, s, -np.inf)[:n_bins]
    return score


def fit_statistics(bins: np.ndarray, y: np.ndarray, tables: Dict,
                   params: Dict, seed: int,
                   tree_weights: Optional[Callable[[int], np.ndarray]] = None,
                   feature_mask: Optional[Callable[[int, int], np.ndarray]] = None,
                   n_trees: int = 4, nodes_per_tree: int = 12,
                   leaves_per_tree: int = 24, leaf_only_trees: int = 0,
                   precision: Optional[str] = None) -> Dict[str, float]:
    """`params`: boosting, reg_lambda, gamma, min_instances, n_bins.
    `tree_weights(t)` gives tree t's per-row sampling weights (None: all
    one), `feature_mask(t, level)` the (width, F) features each node of a
    level may split on (None: all). `leaf_only_trees` more trees are
    sampled for their leaves alone (a leaf costs one comparison a row, a
    node thirty `bincount`s of its rows), so that shallow trees with few
    leaves still give the median a couple of hundred. Returns the three
    statistics and how many nodes and leaves they were taken over."""
    rng = np.random.default_rng(int(seed))
    if bins.max() < 256:
        bins = bins.astype(np.uint8)     # the descents index it 6 x trees times
    sf, sb = tables["split_feature"], tables["split_bin"]
    lv = tables["leaf_value"].astype(np.float64)
    step = tables["tree_weight"].astype(np.float64)
    depth, T = tables["depth"], sf.shape[0]
    lam, gamma = float(params["reg_lambda"]), float(params["gamma"])
    n_bins = int(params["n_bins"])
    y = np.asarray(y, dtype=np.float64)
    chosen_trees = np.sort(rng.choice(T, size=min(n_trees, T), replace=False))
    node_trees = set(chosen_trees.tolist())
    rest = np.setdiff1d(np.arange(T), chosen_trees)
    if leaf_only_trees > 0 and len(rest):
        chosen_trees = np.sort(np.concatenate([chosen_trees, rng.choice(
            rest, size=min(int(leaf_only_trees), len(rest)), replace=False)]))
    margin = np.full(y.shape[0], tables["base"], dtype=np.float64)
    gaps, leaf_errs, cover_gaps = [], [], []
    for t in range(int(chosen_trees.max()) + 1):
        need = t in chosen_trees
        if not need and not params["boosting"]:
            continue
        path = node_paths(bins, sf[t], sb[t], depth)
        if need:
            g = (margin - y) if params["boosting"] else -y
            w = np.ones_like(y) if tree_weights is None else \
                np.asarray(tree_weights(t), dtype=np.float64)[:y.shape[0]]
            gw, hw = g * w, w
            if precision is not None:
                gw_q = round_to(gw, precision)
            else:
                gw_q = gw
            internal = np.flatnonzero(sf[t] >= 0)
            for k in rng.choice(internal, size=min(nodes_per_tree,
                                                   len(internal)),
                                replace=False) if t in node_trees else ():
                level = int(np.floor(np.log2(k + 1)))
                rows = np.flatnonzero((path[level] == k) & (w > 0))
                allowed = None if feature_mask is None else \
                    feature_mask(t, level)[k - (2 ** level - 1)]
                b = bins[rows]
                score = split_scores(b, gw[rows], hw[rows], w[rows], n_bins,
                                     lam, params["min_instances"], allowed)
                best = 0.5 * score.max() - gamma
                f_c, b_c = int(sf[t][k]), int(sb[t][k])
                if precision is not None:
                    score_q = split_scores(b, gw_q[rows], hw[rows], w[rows],
                                           n_bins, lam,
                                           params["min_instances"], allowed)
                    f_c, b_c = np.unravel_index(int(np.argmax(score_q)),
                                                score_q.shape)
                chosen = 0.5 * score[f_c, b_c] - gamma
                # a split the rules do not allow at all is as wrong as can be
                gaps.append((best - chosen) / max(abs(best), 1e-300)
                            if np.isfinite(chosen) else np.inf)
                cover_gaps.append(abs(float(tables["cover"][t][k])
                                      - hw[rows].sum()))
            terminal = path[depth]
            live = terminal[w > 0]
            leaves = np.unique(live)
            for k in rng.choice(leaves, size=min(leaves_per_tree, len(leaves)),
                                replace=False):
                rows = np.flatnonzero((terminal == k) & (w > 0))
                G, H = gw[rows].sum(), hw[rows].sum()
                if precision is None:
                    got = lv[t][k]
                else:
                    got = -gw_q[rows].sum() / (H + lam + 1e-12)
                dG = abs(got * (H + lam + 1e-12) + G)
                leaf_errs.append(dG / max(np.sqrt((gw[rows] ** 2).sum()),
                                          1e-300))
        if params["boosting"]:
            margin = margin + step[t] * lv[t][path[depth]]
    return {
        "split_gain_gap_median": float(np.median(gaps)) if gaps
        else float("nan"),
        "leaf_value_err_median": float(np.median(leaf_errs))
        if leaf_errs else float("nan"),
        "cover_gap_max": float(np.max(cover_gaps)) if cover_gaps
        else float("nan"),
        "nodes": len(gaps), "leaves": len(leaf_errs),
        "split_gain_gaps": [float(g) for g in gaps],
        "leaf_value_errs": [float(e) for e in leaf_errs],
    }


def rmse(pred: np.ndarray, truth: np.ndarray) -> float:
    return float(np.sqrt(np.mean((np.asarray(pred, np.float64)
                                  - np.asarray(truth, np.float64)) ** 2)))
