"""The plain reference of a formula-featurized binomial logistic fit, in
NumPy float64, from the raw rows. Nothing here is imported from the program.

`design` reads what `RFormula("label ~ .")` makes of a table: every string
column indexed by descending frequency of its values (ties by the value),
one-hot encoded with the LAST label dropped, in the table's column order,
then every other column as it is. `newton` is Newton's method from zero on
the whole table in row blocks, to a gradient of `gtol` a row, with the
log-likelihood and the standard errors sqrt(diag(H^-1)) at the optimum.

The steps run on the standardized columns (x - mean) / deviation, because
the raw table's Hessian (a latitude of 37.76 +- 0.026 beside the
intercept) has a condition number that costs float64 half its digits; the
optimum of an unpenalized fit does not move under that map, and everything
returned is mapped back to the raw columns. `at` evaluates any
coefficients on the raw columns: the gradient (in the standardized
coordinates, a row, so that it has no unit), and the log-likelihood.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np
import pandas as pd

BLOCK_ROWS = 262144


def _factorize(col: pd.Series) -> Tuple[np.ndarray, List[str]]:
    """(code a row, -1 for a null; the distinct values as strings)."""
    codes, uniques = pd.factorize(col)
    return codes.astype(np.int64), [str(u) for u in uniques]


def labels_by_frequency(col: pd.Series) -> List[str]:
    """Distinct values of a column that are not null, as strings, by
    count descending then value ascending."""
    codes, values = _factorize(col)
    counts = np.bincount(codes[codes >= 0], minlength=len(values))
    return [v for _, v in sorted(zip(-counts, values))]


def design(train: pd.DataFrame, label: str) -> Dict[str, object]:
    """The formula `label ~ .` over `train`: its string columns with their
    labels, its other columns, the slots of the block in order."""
    strings, numerics = [], []
    for c in train.columns:
        if c == label:
            continue
        kind = train[c].dtype.kind if isinstance(train[c].dtype, np.dtype) \
            else "O"
        (numerics if kind in "fiub" else strings).append(c)
    coded = [(c, labels_by_frequency(train[c])) for c in strings]
    slots = [f"{c}={v}" for c, labels in coded for v in labels[:-1]] \
        + list(numerics)
    return {"strings": coded, "numerics": numerics, "slots": slots,
            "label": label}


class Compact:
    """A table under a design, unexpanded: a code a string column (the
    last label and what is no label read -1 and -2), the other columns in
    float64, and the rows the formula keeps (`handleInvalid="skip"`: a
    null or a label the design has not, a number that is not finite)."""

    def __init__(self, raw: pd.DataFrame, plan: Dict[str, object]):
        n = len(raw)
        self.plan = plan
        codes = np.empty((n, len(plan["strings"])), dtype=np.int64)
        keep = np.ones(n, dtype=bool)
        for j, (c, labels) in enumerate(plan["strings"]):
            rank = {v: i for i, v in enumerate(labels)}
            seen, values = _factorize(raw[c])
            table = np.array([rank.get(v, -2) for v in values] + [-2],
                             dtype=np.int64)   # a null reads the last entry
            codes[:, j] = table[seen]
            keep &= codes[:, j] >= 0
            codes[codes[:, j] == len(labels) - 1, j] = -1   # dropLast
        self.num = raw[plan["numerics"]].to_numpy(dtype=np.float64)
        keep &= np.isfinite(self.num).all(axis=1)
        self.codes, self.keep = codes[keep], keep
        self.num = self.num[keep]
        self.widths = [len(labels) - 1 for _, labels in plan["strings"]]
        self.width = sum(self.widths) + self.num.shape[1]

    def __len__(self) -> int:
        return len(self.num)

    def take(self, rows: np.ndarray) -> "Compact":
        """The kept rows `rows` (positions among them) as a table."""
        out = object.__new__(Compact)
        out.__dict__.update(self.__dict__)
        out.codes, out.num, out.keep = self.codes[rows], self.num[rows], None
        return out

    def block(self, lo: int, hi: int, shift: int = 0) -> np.ndarray:
        """Rows lo..hi expanded, float64. `shift` moves every one-hot slot
        by that many places (the tests' broken featurization)."""
        out = np.zeros((hi - lo, self.width))
        at = 0
        for j, w in enumerate(self.widths):
            c = self.codes[lo:hi, j]
            rows = np.nonzero(c >= 0)[0]
            out[rows, at + (c[rows] + shift) % max(w, 1)] = 1.0
            at += w
        out[:, at:] = self.num[lo:hi]
        return out

    def moments(self) -> Tuple[np.ndarray, np.ndarray]:
        """(mean, deviation) of every slot, a slot of no spread given a
        deviation of 1; an indicator's from its count."""
        n = len(self)
        share = np.concatenate(
            [np.bincount(self.codes[self.codes[:, j] >= 0, j],
                         minlength=w)[:w] / n
             for j, w in enumerate(self.widths)] + [np.zeros(0)])
        mean = np.concatenate([share, self.num.mean(axis=0)])
        std = np.concatenate([np.sqrt(share * (1.0 - share)),
                              self.num.std(axis=0)])
        return mean, np.where(std > 0, std, 1.0)

    def standardized(self, mean, std, shift: int = 0,
                     precision: Optional[str] = None) -> List[np.ndarray]:
        """The table as blocks of rows [(x - mean) / std, 1], float64
        (rounded to `precision` where one is named)."""
        n, d = len(self), self.width
        blocks = []
        for lo in range(0, n, BLOCK_ROWS):
            hi = min(lo + BLOCK_ROWS, n)
            Z = np.empty((hi - lo, d + 1))
            Z[:, :d] = (self.block(lo, hi, shift) - mean) / std
            Z[:, d] = 1.0
            blocks.append(_rounded(Z, precision))
        return blocks


def _rounded(x: np.ndarray, precision: Optional[str]) -> np.ndarray:
    """x to the nearest value of `precision` (ties to even), float64 out.
    bfloat16 by its bits (the upper half of a float32): a table of
    millions of rows is rounded in a pass; the others by `round_to`."""
    if precision is None:
        return x
    if precision == "bfloat16":
        bits = np.asarray(x, dtype=np.float32).view(np.uint32)
        bits = (bits + np.uint32(0x7FFF) + ((bits >> np.uint32(16))
                                            & np.uint32(1))) \
            & np.uint32(0xFFFF0000)
        return bits.view(np.float32).astype(np.float64)
    from benchmark.reference.precision import round_to
    return np.asarray(round_to(x, precision), dtype=np.float64)


def _pass(blocks: List[np.ndarray], y: np.ndarray, z: np.ndarray,
          hessian: bool, precision: Optional[str] = None):
    """One pass at the standardized coefficients `z` (intercept last):
    gradient of the negative log-likelihood, its Hessian, the
    log-likelihood. With a `precision`, every operand of a product over
    the rows is rounded to it first and the sums stay float64: what a
    matrix unit of that precision with a wide accumulator computes."""
    d1 = len(z)
    g, H, ll, lo = np.zeros(d1), np.zeros((d1, d1)), 0.0, 0
    for Z in blocks:
        yb = y[lo:lo + len(Z)]
        lo += len(Z)
        eta = Z @ _rounded(z, precision)
        p = 0.5 * (1.0 + np.tanh(0.5 * eta))
        g += Z.T @ _rounded(p - yb, precision)
        # log sigmoid(eta) = -logaddexp(0, -eta)
        ll -= float(np.sum(yb * np.logaddexp(0.0, -eta)
                           + (1.0 - yb) * np.logaddexp(0.0, eta)))
        if hessian:
            H += Z.T @ _rounded(Z * (p * (1.0 - p))[:, None], precision)
    return g, H, ll


def _to_raw(z: np.ndarray, mean, std) -> np.ndarray:
    w = z[:-1] / std
    return np.append(w, z[-1] - w @ mean)


def _to_standard(w: np.ndarray, mean, std) -> np.ndarray:
    return np.append(w[:-1] * std, w[-1] + w[:-1] @ mean)


def newton(table: Compact, y: np.ndarray, gtol: float = 1e-10,
           max_iter: int = 50, precision: Optional[str] = None
           ) -> Dict[str, object]:
    """The maximum-likelihood coefficients on the raw columns (intercept
    last), their standard errors, the log-likelihood, the iterations.
    With a `precision` (the controls), the steps are those of `_pass` in
    it, and they stop when one moves no coefficient by 1e-6, as the
    program's do: a rounded gradient has no zero to reach."""
    n, d = len(table), table.width
    mean, std = table.moments()
    blocks = table.standardized(mean, std, precision=precision)
    z = np.zeros(d + 1)
    for it in range(1, max_iter + 1):
        g, H, ll = _pass(blocks, y, z, hessian=True, precision=precision)
        if np.max(np.abs(g)) / n < gtol:
            break
        step = np.linalg.solve(H, g)
        z = z - step
        if precision is not None and np.max(np.abs(step)) < 1e-6:
            break
    # the covariance of the raw coefficients: w = J z, J the map's matrix
    J = np.zeros((d + 1, d + 1))
    J[:d, :d] = np.diag(1.0 / std)
    J[d, :d] = -mean / std
    J[d, d] = 1.0
    cov = J @ np.linalg.inv(H) @ J.T
    return {"coefficients": _to_raw(z, mean, std),
            "standard_errors": np.sqrt(np.diag(cov)),
            "loglik": ll, "iterations": it, "gradient_max": float(
                np.max(np.abs(g)) / n), "mean": mean, "std": std}


def at(table: Compact, y: np.ndarray, w: np.ndarray, fit: Dict[str, object],
       shift: int = 0) -> Dict[str, float]:
    """Raw coefficients `w` (intercept last) on this table: the largest
    component of the gradient, a row, in the fit's standardized
    coordinates, and the log-likelihood."""
    mean, std = fit["mean"], fit["std"]
    g, _, ll = _pass(table.standardized(mean, std, shift), y, _to_standard(
        np.asarray(w, np.float64), mean, std), hessian=False)
    return {"gradient_max": float(np.max(np.abs(g)) / len(table)),
            "loglik": ll}


def margins(table: Compact, w: np.ndarray, shift: int = 0) -> np.ndarray:
    """[X 1] w for every kept row of the table, float64."""
    w = np.asarray(w, dtype=np.float64)
    n = len(table)
    out = np.empty(n)
    for lo in range(0, n, BLOCK_ROWS):
        hi = min(lo + BLOCK_ROWS, n)
        out[lo:hi] = table.block(lo, hi, shift) @ w[:-1] + w[-1]
    return out


def sigmoid(eta: np.ndarray) -> np.ndarray:
    return 0.5 * (1.0 + np.tanh(0.5 * np.asarray(eta, dtype=np.float64)))


def auc(score: np.ndarray, y: np.ndarray) -> float:
    """Area under the ROC curve by mean ranks (ties share their rank)."""
    score, y = np.asarray(score, np.float64), np.asarray(y, np.float64)
    order = np.argsort(score, kind="mergesort")
    s = score[order]
    bounds = np.nonzero(np.r_[True, s[1:] != s[:-1], True])[0]
    first, last = bounds[:-1], bounds[1:]
    ranks = np.repeat(0.5 * (first + last + 1), last - first)
    pos = y[order] > 0.5
    n_pos, n_neg = int(pos.sum()), int((~pos).sum())
    if not n_pos or not n_neg:
        return float("nan")
    return float((ranks[pos].sum() - n_pos * (n_pos + 1) / 2)
                 / (n_pos * n_neg))
