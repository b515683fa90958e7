"""The plain reference of an elastic-net-penalized binomial logistic fit and
of a cross-validation's AUROC, in NumPy float64, from the raw rows. Nothing
here is imported from the program; the featurization is `logistic.py`'s
(`design`, `Compact`).

The objective is MLlib's: over the n rows of a fit

    F(u, b) = -(1/n) loglik + lam (alpha sum_j |u_j| + (1 - alpha) / 2 sum_j u_j^2)

with u the coefficients on the columns scaled to a SAMPLE deviation of 1
over THAT fit's rows (the denominator n - 1, as MLlib's summarizer has it)
and the intercept b free. A table is standardized once, by its own means
and deviations (`Standardized`); a fit on some of its rows (a fold's
training rows) keeps those blocks and reads the penalty's weights off its
own rows: with c the coefficients on the blocks' columns, u_j = s_j c_j
where s_j is the sample deviation of the block's column j over the fit's
rows (centring moves the intercept alone), so the penalty is
lam alpha s_j |c_j| + lam (1 - alpha) s_j^2 c_j^2 / 2. A column with no
spread over the fit's rows has s_j = 0 and its coefficient is held at 0.

`fit` minimizes F by proximal Newton steps, each the penalized quadratic
model minimized by cyclic coordinate descent, to an optimality residual
(`residual`) of `rtol` = 1e-10 in the u coordinates. The Hessian is made
again only after a step that moved a coefficient by more than 1e-4: near
the optimum a step is one gradient pass. The result does not depend on
where the steps start (by default the null model: no coefficient, the
intercept the log-odds of the rows' share of ones), so a caller may start
them near the answer.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional

import numpy as np

from benchmark.reference import logistic

auc = logistic.auc
#: threads a pass over the blocks may use (`_blockwise`)
WORKERS = 8


def _blockwise(task, count: int) -> list:
    """`task(i)` for every block i, in order, a few at a time on threads:
    a pass over millions of rows is NumPy's elementwise functions and BLAS,
    which give the interpreter lock up, and what a block adds is summed in
    the blocks' order, so the sums are the sequential pass's."""
    workers = min(WORKERS, len(os.sched_getaffinity(0)), count)
    if workers < 2:
        return [task(i) for i in range(count)]
    with ThreadPoolExecutor(max_workers=workers) as pool:
        return list(pool.map(task, range(count)))


class Standardized:
    """A table (`logistic.Compact`) as blocks of rows [(x - mean) / std, 1]
    in float64 under its own moments, with its labels."""

    def __init__(self, table: logistic.Compact, y: np.ndarray):
        self.mean, self.std = table.moments()
        self.y = np.asarray(y, dtype=np.float64)
        self.rows, self.width = len(table), table.width
        step = logistic.BLOCK_ROWS
        self.starts = list(range(0, self.rows, step))

        def block(i: int) -> np.ndarray:
            lo = self.starts[i]
            Z = np.empty((min(lo + step, self.rows) - lo, self.width + 1))
            Z[:, :-1] = (table.block(lo, lo + len(Z)) - self.mean) / self.std
            Z[:, -1] = 1.0
            return Z
        self.blocks = _blockwise(block, len(self.starts))

    def to_raw(self, c: np.ndarray) -> np.ndarray:
        """Coefficients on the blocks' columns -> on the raw columns."""
        return logistic._to_raw(np.asarray(c, np.float64), self.mean,
                                self.std)

    def to_standard(self, w: np.ndarray) -> np.ndarray:
        return logistic._to_standard(np.asarray(w, np.float64), self.mean,
                                     self.std)

    def _rows_of(self, rows: Optional[np.ndarray], i: int):
        lo = self.starts[i]
        return None if rows is None else rows[lo:lo + len(self.blocks[i])]

    def spread(self, rows: Optional[np.ndarray]) -> np.ndarray:
        """Sample deviation (n - 1) of every column of the blocks over the
        rows `rows` keeps (a 0/1 weight a row; None: all), the intercept's
        place 0: the penalty's weight a coordinate."""
        def sums(i: int):
            Z, w = self.blocks[i][:, :-1], self._rows_of(rows, i)
            Zw = Z if w is None else Z * w[:, None]
            return (float(len(Z) if w is None else w.sum()),
                    Zw.sum(axis=0), np.einsum("ij,ij->j", Zw, Z))
        n, s1, s2 = (sum(part) for part in zip(
            *_blockwise(sums, len(self.blocks))))
        var = np.maximum(s2 - s1 * s1 / n, 0.0) / max(n - 1.0, 1.0)
        # a column with no spread reads its rounding, not 0
        var[var < 1e-20] = 0.0
        return np.append(np.sqrt(var), 0.0)

    def derivatives(self, c: np.ndarray, rows: Optional[np.ndarray],
                    hessian: bool, precision: Optional[str] = None):
        """(gradient, Hessian | None, -loglik) of -(1/n) loglik at `c` over
        the rows `rows` keeps, all three a row. With a `precision`, every
        operand of a product over the rows is rounded to it first and the
        sums stay float64 (`logistic._pass`'s control)."""
        rnd = logistic._rounded
        cp = rnd(c, precision)

        def part(i: int):
            Z, w = self.blocks[i], self._rows_of(rows, i)
            yb = self.y[self.starts[i]:self.starts[i] + len(Z)]
            w = np.ones(len(Z)) if w is None else w
            Zp = rnd(Z, precision)
            eta = Zp @ cp
            p = 0.5 * (1.0 + np.tanh(0.5 * eta))
            # -log p(y) = log(1 + exp(-(2y - 1) eta))
            nll = float(np.sum(w * np.logaddexp(0.0, (1.0 - 2.0 * yb) * eta)))
            H = Zp.T @ rnd(Zp * (w * p * (1.0 - p))[:, None], precision) \
                if hessian else None
            return Zp.T @ rnd(w * (p - yb), precision), H, nll, float(w.sum())
        parts = _blockwise(part, len(self.blocks))
        g = sum(p[0] for p in parts)
        n = sum(p[3] for p in parts)
        H = sum(p[1] for p in parts) / n if hessian else None
        return g / n, H, sum(p[2] for p in parts) / n

    def margins(self, c: np.ndarray) -> np.ndarray:
        return np.concatenate(_blockwise(lambda i: self.blocks[i] @ c,
                                         len(self.blocks)))


def weights(lam: float, alpha: float, spread: np.ndarray):
    """(l1w, l2w): the penalty's weights a coordinate of c."""
    return lam * alpha * spread, lam * (1.0 - alpha) * spread * spread


def residual(g: np.ndarray, c: np.ndarray, lam: float, alpha: float,
             spread: np.ndarray) -> np.ndarray:
    """The optimality residual of F a coordinate, in the u coordinates
    (u_j = s_j c_j, so a derivative by u_j is one by c_j over s_j): for
    u_j != 0, |g_j + lam (1 - alpha) u_j + lam alpha sign(u_j)|; for
    u_j = 0, max(0, |g_j| - lam alpha); for the intercept |g_b|. A
    coordinate with no spread has none: its coefficient is held at 0."""
    s = spread[:-1]
    live = s > 0
    gu = np.where(live, g[:-1] / np.where(live, s, 1.0), 0.0)
    u = s * c[:-1]
    at = np.where(u != 0,
                  np.abs(gu + lam * (1.0 - alpha) * u
                         + lam * alpha * np.sign(u)),
                  np.maximum(0.0, np.abs(gu) - lam * alpha))
    return np.append(np.where(live, at, 0.0), abs(g[-1]))


def _descend(H: np.ndarray, g: np.ndarray, c: np.ndarray, l1w, l2w,
             sweeps: int = 100000) -> np.ndarray:
    """The minimizer of the penalized quadratic model at c, g.(v - c)
    + (v - c)' H (v - c) / 2 + sum_j l1w_j |v_j| + l2w_j v_j^2 / 2, by
    cyclic coordinate descent to a sweep that moves nothing by 1e-14."""
    v = c.copy()
    r = g.copy()                      # the smooth gradient at v
    for _ in range(sweeps):
        moved = 0.0
        for j in range(len(v)):
            h = H[j, j]
            curve = h + l2w[j]
            a = h * v[j] - r[j]
            to = 0.0 if curve <= 0 else \
                np.sign(a) * max(abs(a) - l1w[j], 0.0) / curve
            delta = to - v[j]
            if delta != 0.0:
                r += H[:, j] * delta
                v[j] = to
                moved = max(moved, abs(delta))
        if moved < 1e-14:
            break
        r = g + H @ (v - c)           # made anew: no drift over the sweeps
    return v


def fit(data: Standardized, lam: float, alpha: float,
        rows: Optional[np.ndarray] = None,
        start: Optional[np.ndarray] = None, rtol: float = 1e-10,
        max_iter: int = 200, precision: Optional[str] = None
        ) -> Dict[str, object]:
    """The minimizer of F over the rows `rows` keeps (a 0/1 weight a row;
    None: all): coefficients on the blocks' columns (`c`), on the raw
    columns (`coefficients`, intercept last), the largest optimality
    residual, the passes over the rows it took. With a `precision` (the
    control) the passes are rounded to it and the steps end when one
    moves no coefficient by 1e-6, as the program's do: a rounded gradient
    has no zero to reach."""
    spread = data.spread(rows)
    l1w, l2w = weights(lam, alpha, spread)
    if start is None:
        # the null model: no coefficient, the intercept the log-odds of
        # the rows' own share of ones
        share = float(np.mean(data.y) if rows is None
                      else data.y @ rows / rows.sum())
        c = np.zeros(data.width + 1)
        c[-1] = np.log(share / (1.0 - share)) if 0.0 < share < 1.0 else 0.0
    else:
        c = np.asarray(start, np.float64).copy()
    c[:-1][spread[:-1] == 0] = 0.0
    H = None
    moved, passes, held = np.inf, 0, None
    for _ in range(max_iter):
        g, Hn, nll = data.derivatives(c, rows, H is None or moved > 1e-4,
                                      precision)
        passes += 1
        H = Hn if Hn is not None else H
        value = nll + float(np.sum(l1w * np.abs(c) + 0.5 * l2w * c * c))
        if held is not None and value > held[1] + 1e-12 * abs(held[1]) \
                and moved > 1e-9:
            c = 0.5 * (c + held[0])     # the step overshot: half of it
            moved *= 0.5
            continue
        res = residual(g, c, lam, alpha, spread)
        if res.max() < rtol or (precision is not None and moved < 1e-6):
            break
        v = _descend(H, g, c, l1w, l2w)
        held, moved = (c, value), float(np.max(np.abs(v - c)))
        c = v
    return {"c": c, "coefficients": data.to_raw(c),
            "residual_max": float(res.max()), "residual": res,
            "passes": passes, "spread": spread, "lam": lam, "alpha": alpha}


def residual_at(data: Standardized, w: np.ndarray, lam: float, alpha: float,
                rows: Optional[np.ndarray] = None) -> np.ndarray:
    """The optimality residual of F at raw coefficients `w` (intercept
    last) over the rows `rows` keeps, a coordinate."""
    c = data.to_standard(w)
    g, _, _ = data.derivatives(c, rows, hessian=False)
    return residual(g, c, lam, alpha, data.spread(rows))


def fold_aucs(data: Standardized, fold: np.ndarray, lam: float,
              alpha: float, start: Optional[np.ndarray] = None,
              residuals: Optional[List[float]] = None) -> List[float]:
    """A cross-validation's AUROC a fold for one grid point: the fit on
    the rows outside the fold (standardized by them), the exact midrank
    area of the fold's own rows' margins. `fold` is a fold id a row.
    Every fit's largest optimality residual is appended to `residuals`
    where a list is given."""
    out = []
    for f in range(int(fold.max()) + 1):
        inside = fold == f
        best = fit(data, lam, alpha, rows=(~inside).astype(np.float64),
                   start=start)
        if residuals is not None:
            residuals.append(best["residual_max"])
        out.append(auc(data.margins(best["c"])[inside], data.y[inside]))
    return out
