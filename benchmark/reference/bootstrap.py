"""The seeded sampling streams of a bagged forest, reproduced with
`jax.random` alone (threefry: the same bits on every backend).

The forest's definition includes its draws: tree t weighs the padded row
space with Poisson(rate) counts from key fold_in(fold_in(PRNGKey(seed), 0), t),
and at each level draws one uniform per (node, feature) from
fold_in(fold_in(PRNGKey(seed), t), level); a node may split on the
feature_k features of lowest rank. Rows are padded to the grid of eight
steps an octave before the draw, so the draw's shape is part of it.
"""

from __future__ import annotations

import numpy as np


def streams(math: dict, n_rows: int, n_features: int):
    """(tree_weights, feature_mask) as `fitcheck.fit_statistics` takes
    them, from a configuration's `fit_math`: None where the fit draws
    nothing (no bagging; every feature at every node)."""
    weights = mask = None
    if math.get("bootstrap_rate"):
        def weights(tree):
            return poisson_weights(math["seed"], tree, n_rows,
                                   math["bootstrap_rate"])
    if math["feature_k"] < n_features:
        def mask(tree, level):
            return feature_mask(math["seed"], tree, level, n_features,
                                math["feature_k"])
    return weights, mask


def padded_rows(n: int) -> int:
    """n rounded up to the next multiple of 2^(bit_length - 4)."""
    n = max(int(n), 1)
    step = 1 << max(0, n.bit_length() - 4)
    return ((n + step - 1) // step) * step


def poisson_weights(seed: int, tree: int, n_rows: int, rate: float
                    ) -> np.ndarray:
    import jax
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(seed), 0),
                             tree)
    draw = jax.random.poisson(key, rate, (padded_rows(n_rows),))
    return np.asarray(draw, dtype=np.float64)[:n_rows]


def feature_mask(seed: int, tree: int, level: int, n_features: int,
                 feature_k: int) -> np.ndarray:
    """(2^level, F) bool: the features each node of the level may use."""
    import jax
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(seed),
                                                tree), level)
    u = np.asarray(jax.random.uniform(key, (2 ** level, n_features)))
    ranks = np.argsort(np.argsort(u, axis=1, kind="stable"), axis=1,
                       kind="stable")
    return ranks < feature_k
