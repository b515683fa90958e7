"""A fitted boosted ensemble of the logistic loss held to the mathematics
of its fit: `fitcheck.py`'s three questions with the gradients a classifier
boosts on, which `fitcheck` does not know (it replays `margin - y`).

The margin is replayed in float64, round by round, from the fitted tables
alone: it starts at the fitted base (the label's log-odds), and round t
adds `tree_weight[t] x leaf_value[t]` of the leaf each row ends in. Before
round t the rows' gradients are those of the log loss at that margin,

    p = 1 / (1 + exp(-margin)),   g = p - y,   h = max(p (1 - p), 1e-6)

(the floor is the program's, `tree_impl._ensemble_pieces`; xgboost floors
the hessian at 1e-16), and at a seeded sample of trees, of their nodes and
of their leaves:

- split: the gain of the split the fit CHOSE, from float64 `np.bincount`
  histograms of g and h over the rows that reach the node, against the best
  gain over every allowed (feature, bin); the MEDIAN over the nodes of the
  relative gap, as `fitcheck` takes it and for its reason.
- leaf: the fitted value against the float64 Newton step -G / (H + lambda)
  of the leaf's rows, measured in G and relative to sqrt(sum g^2), the
  size of a sum of independently rounded operands; the MEDIAN over the
  leaves.
- hessian mass: the fitted cover of the node against sum h of its rows.
  Under the squared loss that is a count and exact; here it is a sum of
  rounded operands (h is rounded to the histogram's type before the dot),
  so the gap is relative, |cover - H| / H, and the statistic is the MEDIAN
  over the sampled nodes: about 2^-9 / sqrt(rows) a node for bfloat16,
  sixteen times that for fp8, and of the order of 1 where the gradients
  are another loss's (there h is 1 a row and the cover a count).

`precision` turns the routine into the first CONTROL: split chosen, leaf
and cover computed from operands rounded to that precision first.
`gradients="squared"` is the second: the gradients `fitcheck` knows put in
this reference's place, which a fit of the logistic loss must NOT agree
with. `probabilities` is what the served probabilities are compared with:
the logistic function of a float32 descent of the fitted tables.
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from . import forest
from .fitcheck import node_paths, split_scores
from .precision import round_to

HESSIAN_FLOOR = 1e-6


def gradients_of(loss: str, margin: np.ndarray, y: np.ndarray):
    """(g, h) of `loss` at `margin`, float64."""
    if loss == "squared":
        return margin - y, np.ones_like(y)
    if loss != "logistic":
        raise ValueError(f"no gradients for the loss {loss!r}")
    p = 1.0 / (1.0 + np.exp(-margin))
    return p - y, np.maximum(p * (1.0 - p), HESSIAN_FLOOR)


def probabilities(bins: np.ndarray, tables: Dict) -> np.ndarray:
    """P(label = 1) of binned rows: the logistic function of the margin a
    float32 descent of the fitted tables gives."""
    return 1.0 / (1.0 + np.exp(-forest.predict(bins, tables)))


def fit_statistics(bins: np.ndarray, y: np.ndarray, tables: Dict,
                   params: Dict, seed: int, n_trees: int = 4,
                   nodes_per_tree: int = 12, leaves_per_tree: int = 24,
                   leaf_only_trees: int = 0,
                   precision: Optional[str] = None,
                   gradients: str = "logistic") -> Dict[str, float]:
    """`params`: reg_lambda, gamma, min_instances, n_bins (the
    configuration's `fit_math`). Every row weighs 1 (no subsampling, no
    bootstrap: the deployment's). Returns the three statistics, how many
    nodes and leaves they were taken over, and the replayed margin."""
    rng = np.random.default_rng(int(seed))
    if bins.max() < 256:
        bins = bins.astype(np.uint8)
    sf, sb = tables["split_feature"], tables["split_bin"]
    lv = tables["leaf_value"].astype(np.float64)
    step = tables["tree_weight"].astype(np.float64)
    depth, T = tables["depth"], sf.shape[0]
    lam, gamma = float(params["reg_lambda"]), float(params["gamma"])
    n_bins, min_inst = int(params["n_bins"]), params["min_instances"]
    y = np.asarray(y, dtype=np.float64)
    ones = np.ones_like(y)
    node_trees = np.sort(rng.choice(T, size=min(n_trees, T), replace=False))
    rest = np.setdiff1d(np.arange(T), node_trees)
    leaf_trees = node_trees if not (leaf_only_trees > 0 and len(rest)) else \
        np.concatenate([node_trees, rng.choice(
            rest, size=min(int(leaf_only_trees), len(rest)), replace=False)])
    margin = np.full(y.shape[0], tables["base"], dtype=np.float64)
    gaps, leaf_errs, cover_gaps = [], [], []
    for t in range(T):
        path = node_paths(bins, sf[t], sb[t], depth)
        if t in leaf_trees:
            g, h = gradients_of(gradients, margin, y)
            g_q, h_q = (g, h) if precision is None else \
                (round_to(g, precision), round_to(h, precision))
            internal = np.flatnonzero(sf[t] >= 0)
            for k in rng.choice(internal, size=min(nodes_per_tree,
                                                   len(internal)),
                                replace=False) if t in node_trees else ():
                level = int(np.floor(np.log2(k + 1)))
                rows = np.flatnonzero(path[level] == k)
                b = bins[rows]
                score = split_scores(b, g[rows], h[rows], ones[rows], n_bins,
                                     lam, min_inst, None)
                best = 0.5 * score.max() - gamma
                f_c, b_c = int(sf[t][k]), int(sb[t][k])
                cover = float(tables["cover"][t][k])
                if precision is not None:
                    score_q = split_scores(b, g_q[rows], h_q[rows],
                                           ones[rows], n_bins, lam, min_inst,
                                           None)
                    f_c, b_c = np.unravel_index(int(np.argmax(score_q)),
                                                score_q.shape)
                    cover = float(h_q[rows].sum())
                chosen = 0.5 * score[f_c, b_c] - gamma
                # a split the rules do not allow at all is as wrong as can be
                gaps.append((best - chosen) / max(abs(best), 1e-300)
                            if np.isfinite(chosen) else np.inf)
                H = h[rows].sum()
                cover_gaps.append(abs(cover - H) / max(H, 1e-300))
            terminal = path[depth]
            leaves = np.unique(terminal)
            for k in rng.choice(leaves, size=min(leaves_per_tree, len(leaves)),
                                replace=False):
                rows = np.flatnonzero(terminal == k)
                G, H = g[rows].sum(), h[rows].sum()
                if precision is None:
                    got = lv[t][k]
                else:
                    got = -g_q[rows].sum() / (h_q[rows].sum() + lam + 1e-12)
                dG = abs(got * (H + lam + 1e-12) + G)
                leaf_errs.append(dG / max(np.sqrt((g[rows] ** 2).sum()),
                                          1e-300))
        margin = margin + step[t] * lv[t][path[depth]]

    def median(values):
        return float(np.median(values)) if values else float("nan")

    return {"split_gain_gap_median": median(gaps),
            "leaf_value_err_median": median(leaf_errs),
            "hessian_mass_gap_median": median(cover_gaps),
            "nodes": len(gaps), "leaves": len(leaf_errs),
            "split_gain_gaps": [float(v) for v in gaps],
            "leaf_value_errs": [float(v) for v in leaf_errs],
            "hessian_mass_gaps": [float(v) for v in cover_gaps],
            "margin": margin}


def log_loss(p: np.ndarray, y: np.ndarray) -> float:
    p = np.clip(np.asarray(p, np.float64), 1e-15, 1 - 1e-15)
    y = np.asarray(y, np.float64)
    return float(-np.mean(y * np.log(p) + (1 - y) * np.log(1 - p)))


def auroc(score: np.ndarray, y: np.ndarray) -> float:
    """The area under the ROC curve by ranks, ties sharing their mean rank."""
    score, y = np.asarray(score, np.float64), np.asarray(y) > 0.5
    order = np.argsort(score, kind="stable")
    s = score[order]
    starts = np.flatnonzero(np.r_[True, s[1:] != s[:-1]])
    ends = np.r_[starts[1:], len(s)]
    full = np.empty(len(s), np.float64)
    full[order] = np.repeat(0.5 * (starts + ends + 1), ends - starts)
    pos = int(y.sum())
    neg = len(y) - pos
    if not pos or not neg:
        return float("nan")
    return float((full[y].sum() - pos * (pos + 1) / 2) / (pos * neg))
