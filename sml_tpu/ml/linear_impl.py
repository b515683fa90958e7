"""Distributed linear-model solvers (SURVEY §2.2 P2).

The reference's LinearRegression trains by "matrix decomposition … else
L-BFGS", with per-iteration gradients tree-aggregated from executors
(`SML/Labs/ML 02L - Linear Regression I Lab.py:66-77`). Here the same math is
two jitted shard_map programs over the mesh's data axis:

- one pass building the Gram block `[X 1]^T [X 1]` and `[X 1]^T y` per chip,
  `psum`-reduced over ICI (the treeAggregate replacement). d is small, so the
  (d+1)² solve happens replicated on every chip.
- for L1/elastic-net and logistic loss, an iterative program (FISTA on the
  Gram for least squares; IRLS Newton for logistic) whose per-iteration
  reductions are the same psum.

All passes are masked so row padding (static shapes for XLA) is inert.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel import collectives as coll
from ..parallel.dispatch import WorkHint
from ._staging import run_data_parallel


class LinearFit(NamedTuple):
    coefficients: np.ndarray
    intercept: float
    iterations: int
    # training-fit statistics derived from the SAME Gram pass (no second
    # data pass): {"sse", "var_y", "var_pred", "n"} — see fit_linear
    stats: Optional[dict] = None


def _gram_pass(Xb, yb, mask):
    Xb = Xb * mask[:, None]
    yb = yb * mask
    ones = mask[:, None]
    Xa = jnp.concatenate([Xb, ones], axis=1)
    A = coll.psum(Xa.T @ Xa)            # MXU matmul then ICI allreduce
    b = coll.psum(Xa.T @ yb)
    n = coll.psum(jnp.sum(mask))
    yy = coll.psum(jnp.sum(yb * yb))
    return A, b, n, yy


def gram_stats(X: np.ndarray, y: np.ndarray) -> Tuple[np.ndarray, np.ndarray, float, float]:
    """One data-parallel pass: (A = [X 1]^T [X 1], b = [X 1]^T y, n, y^T y).
    ONE device round trip — every downstream fit statistic is a host-side
    identity on these moments."""
    n_rows, d = X.shape
    # asarray, not astype: astype always copies, which both costs ~0.1s/GB
    # and defeats the staging cache's identity keys on repeated fits
    A, b, n, yy = run_data_parallel(
        _gram_pass, np.asarray(X, np.float32), np.asarray(y, np.float32),
        work=WorkHint(flops=2.0 * n_rows * (d + 1) ** 2, kind="blas"))
    return (np.asarray(A, dtype=np.float64), np.asarray(b, dtype=np.float64),
            float(n), float(yy))


def _fit_stats(A, b, n_f, yy, w_full):
    """Training rmse/r2/explained-variance from Gram identities:
    SSE = y'y - 2 w'b + w'Aw;  sum(pred) = A[-1, :] @ w  (last Gram row is
    the column-sum of [X 1]);  var(pred) = w'Aw/n - mean(pred)^2."""
    sse = float(yy - 2.0 * w_full @ b + w_full @ A @ w_full)
    sy = b[-1] / n_f
    var_y = float(yy / n_f - sy * sy)
    mean_pred = float(A[-1, :] @ w_full) / n_f
    var_pred = float(w_full @ A @ w_full) / n_f - mean_pred ** 2
    return {"sse": max(sse, 0.0), "var_y": max(var_y, 0.0),
            "var_pred": max(var_pred, 0.0), "n": n_f}


def fit_linear(X: np.ndarray, y: np.ndarray, *, regParam: float = 0.0,
               elasticNetParam: float = 0.0, fitIntercept: bool = True,
               standardization: bool = True, maxIter: int = 100,
               tol: float = 1e-6) -> LinearFit:
    """Least squares with (optional) elastic-net penalty on the Gram
    sufficient statistics. Matches MLlib semantics: the penalty applies to
    standardized coefficients; the intercept is never penalized."""
    d = X.shape[1]
    A, b, n_f, yy = gram_stats(X, y)
    return _solve_gram(A, b, n_f, yy, d, regParam=regParam,
                       elasticNetParam=elasticNetParam,
                       fitIntercept=fitIntercept,
                       standardization=standardization,
                       maxIter=maxIter, tol=tol)


def _solve_gram(A, b, n_f, yy, d, *, regParam, elasticNetParam,
                fitIntercept, standardization, maxIter, tol) -> LinearFit:
    """Every least-squares variant from the (d+1)² Gram moments — shared
    by the materialized and compact front ends (the algebra must live in
    exactly one place)."""
    # moments from the Gram pass (last row/col hold the sums)
    sx = A[-1, :d] / n_f
    sy = b[-1] / n_f
    xx_diag = np.diag(A)[:d] / n_f
    std = np.sqrt(np.maximum(xx_diag - sx ** 2, 1e-12))
    lam = float(regParam)
    alpha = float(elasticNetParam)

    if lam == 0.0 or alpha == 0.0:
        # closed form: (A + λ n S²)⁻¹ b with S scaling the standardized L2
        # penalty back to raw space; intercept row/col unpenalized
        reg = np.zeros_like(A)
        if lam > 0:
            # penalizing standardized coefficients (w_std = w·std) puts a
            # λ·std² diagonal on the raw-space normal equations — same
            # semantics as the FISTA branch below
            scale = (std ** 2) if standardization else np.ones(d)
            reg[:d, :d] = np.diag(lam * n_f * scale)
        if not fitIntercept:
            sol = np.linalg.solve(A[:d, :d] + reg[:d, :d] + 1e-9 * np.eye(d),
                                  b[:d])
            w_full = np.concatenate([sol, [0.0]])
            return LinearFit(sol, 0.0, 1, _fit_stats(A, b, n_f, yy, w_full))
        sol = np.linalg.solve(A + reg + 1e-9 * np.eye(d + 1), b)
        return LinearFit(sol[:d], float(sol[d]), 1,
                         _fit_stats(A, b, n_f, yy, sol))

    # elastic net via FISTA on the (tiny, replicated) Gram — centered space
    Axx = A[:d, :d] / n_f - np.outer(sx, sx)
    bxy = b[:d] / n_f - sx * sy
    if standardization:
        Axx = Axx / np.outer(std, std)
        bxy = bxy / std
    L = float(np.linalg.eigvalsh(Axx).max()) + lam * (1 - alpha)
    l1 = lam * alpha
    l2 = lam * (1 - alpha)

    def prox_step(w):
        g = Axx @ w - bxy + l2 * w
        z = w - g / L
        return jnp.sign(z) * jnp.maximum(jnp.abs(z) - l1 / L, 0.0)

    # graftlint: disable=dispatch-bypass -- FISTA iterates a (d,d) replicated Gram already reduced on the mesh: pure host-side micro-solve, no data-sized work to route
    @jax.jit
    def fista(w0):
        def body(carry, _):
            w, v, t = carry
            w_new = prox_step(v)
            t_new = (1 + jnp.sqrt(1 + 4 * t * t)) / 2
            v_new = w_new + ((t - 1) / t_new) * (w_new - w)
            return (w_new, v_new, t_new), jnp.max(jnp.abs(w_new - w))
        (w, _, _), deltas = jax.lax.scan(body, (w0, w0, jnp.float32(1.0)),
                                         None, length=maxIter)
        return w, deltas

    w, _ = fista(jnp.zeros(d, dtype=jnp.float32))
    w = np.asarray(w, dtype=np.float64)
    if standardization:
        w = w / std
    intercept = float(sy - sx @ w) if fitIntercept else 0.0
    w_full = np.concatenate([w, [intercept]])
    return LinearFit(w, intercept, maxIter, _fit_stats(A, b, n_f, yy, w_full))


# --------------------------------------------- compact (expand-on-device)
def _expand_masked(num_b, codes_b, mask, layout):
    """Per-chip expansion of a CompactParts block into [X 1], rows masked.

    One-hot pieces are `code == iota` compares on the VPU — the (n, d)
    block exists only in HBM on the chip, never on the host or the H2D path
    (featurizer.CompactParts). Out-of-range codes (handleInvalid="keep"
    overflow slots) yield all-zero rows exactly like the host writer.
    Padding rows carry code 0, so EVERY piece is mask-multiplied."""
    pieces = []
    for item in layout:
        if item[0] == "num":
            pieces.append(num_b[:, item[1]][:, None])
        else:
            _, j, width = item
            iota = jnp.arange(width, dtype=codes_b.dtype)
            pieces.append((codes_b[:, j][:, None]
                           == iota[None, :]).astype(jnp.float32))
    pieces.append(jnp.ones((num_b.shape[0], 1), dtype=jnp.float32))
    return jnp.concatenate(pieces, axis=1) * mask[:, None]


_compact_gram_fns: dict = {}


def _compact_gram_fn(layout):
    fn = _compact_gram_fns.get(layout)
    if fn is not None:
        return fn

    def gram_compact(num_b, codes_b, yb, mask):
        # f32 matmul precision: bf16 operand truncation would corrupt the
        # Gram moments (counts up to n and squared sums are not bf16-exact)
        with jax.default_matmul_precision("float32"):
            Xa = _expand_masked(num_b, codes_b, mask, layout)
            yb = yb * mask
            A = coll.psum(Xa.T @ Xa)
            b = coll.psum(Xa.T @ yb)
            n = coll.psum(jnp.sum(mask))
            yy = coll.psum(jnp.sum(yb * yb))
        return A, b, n, yy

    gram_compact.__name__ = f"gram_compact_{abs(hash(layout)) % 99991}"
    _compact_gram_fns[layout] = gram_compact
    return gram_compact


def gram_stats_compact(parts, y: np.ndarray):
    """gram_stats over a featurizer.CompactParts block: one device pass,
    one-hot slots expanded on-chip (SURVEY §2.2 P2 at beyond-one-machine
    scale — `SML/ML 00b - Spark Review.py:84`)."""
    n_rows = parts.num.shape[0]
    d = parts.width
    A, b, n, yy = run_data_parallel(
        _compact_gram_fn(parts.layout), parts.num, parts.codes,
        np.asarray(y, np.float32),
        work=WorkHint(flops=2.0 * n_rows * (d + 1) ** 2, kind="blas"))
    return (np.asarray(A, dtype=np.float64), np.asarray(b, dtype=np.float64),
            float(n), float(yy))


def fit_linear_compact(parts, y: np.ndarray, *, regParam: float = 0.0,
                       elasticNetParam: float = 0.0,
                       fitIntercept: bool = True,
                       standardization: bool = True, maxIter: int = 100,
                       tol: float = 1e-6) -> LinearFit:
    """fit_linear without ever materializing the one-hot block: the Gram
    moments come from the on-device expansion, everything downstream is
    the same host algebra (_solve_gram). Supports every penalty config —
    elastic net runs on the Gram, not the data."""
    A, b, n_f, yy = gram_stats_compact(parts, y)
    return _solve_gram(A, b, n_f, yy, parts.width, regParam=regParam,
                       elasticNetParam=elasticNetParam,
                       fitIntercept=fitIntercept,
                       standardization=standardization,
                       maxIter=maxIter, tol=tol)


_compact_irls_fns: dict = {}


def _compact_irls_fn(layout, maxIter: int, tol: float):
    key = (layout, maxIter, float(tol))
    fn = _compact_irls_fns.get(key)
    if fn is not None:
        return fn

    def irls_compact(num_b, codes_b, yb, mask):
        """WHOLE-FIT fused IRLS: the expanded block stays resident in HBM
        and all maxIter Newton steps — grad/Hessian psum, (d+1)² solve,
        damping, convergence freeze — run in ONE dispatch. The host loop
        pays a dispatch round trip and a device→host read per iteration; at
        course-scale d that fixed cost IS the fit time. Semantics mirror
        fit_logistic's lam=0 loop: step = solve(H + 1e-8 I, g), damp to
        the midpoint when the log-likelihood drops by >1e3, freeze after
        max|Δw| < tol (executed iterations are reported)."""
        with jax.default_matmul_precision("float32"):
            Xa = _expand_masked(num_b, codes_b, mask, layout)
            d1 = Xa.shape[1]
            eye = jnp.eye(d1, dtype=jnp.float32)

            def body(carry, _):
                w, prev_ll, done, iters = carry
                eta = Xa @ w
                p = jax.nn.sigmoid(eta)
                Wd = jnp.maximum(p * (1 - p), 1e-6) * mask
                grad = coll.psum(Xa.T @ ((p - yb) * mask))
                hess = coll.psum((Xa * Wd[:, None]).T @ Xa)
                ll = coll.psum(jnp.sum(mask * (
                    yb * jax.nn.log_sigmoid(eta)
                    + (1 - yb) * jax.nn.log_sigmoid(-eta))))
                step = jnp.linalg.solve(hess + 1e-8 * eye, grad)
                w_new = w - step
                conv = jnp.max(jnp.abs(w_new - w)) < tol
                damp = ll < prev_ll - 1e3
                w_next = jnp.where(done, w,
                                   jnp.where(damp, (w + w_new) / 2, w_new))
                iters = iters + jnp.where(done, 0, 1)
                return (w_next, jnp.where(done, prev_ll, ll),
                        done | conv, iters), None

            init = (jnp.zeros((d1,), jnp.float32), jnp.float32(-jnp.inf),
                    jnp.bool_(False), jnp.int32(0))
            (w, _, _, iters), _ = jax.lax.scan(body, init, None,
                                               length=maxIter)
        return w, iters

    irls_compact.__name__ = \
        f"irls_compact_{abs(hash(key)) % 99991}"
    _compact_irls_fns[key] = irls_compact
    return irls_compact


def fit_logistic_compact(parts, y: np.ndarray, *, maxIter: int = 100,
                         tol: float = 1e-7) -> LinearFit:
    """Unpenalized binomial logistic fit over a CompactParts block — the
    fused-IRLS device program (see _compact_irls_fn). Penalized configs
    need the materialized block (prox shrinkage on raw coefficients);
    callers route those through parts.expand_host() + fit_logistic."""
    n_rows, d = parts.num.shape[0], parts.width
    w, iters = run_data_parallel(
        _compact_irls_fn(parts.layout, int(maxIter), float(tol)),
        parts.num, parts.codes, np.asarray(y, np.float32),
        work=WorkHint(flops=3.0 * maxIter * n_rows * (d + 1) ** 2,
                      kind="blas"))
    w = np.asarray(w, dtype=np.float64)
    return LinearFit(w[:d], float(w[d]), int(iters))


def _newton_pass(Xb, yb, mask, wb):
    ones = mask[:, None]
    Xa = jnp.concatenate([Xb * mask[:, None], ones], axis=1)
    eta = Xa @ wb
    p = jax.nn.sigmoid(eta)
    Wdiag = jnp.maximum(p * (1 - p), 1e-6) * mask
    grad = coll.psum(Xa.T @ ((p - yb) * mask))
    hess = coll.psum((Xa * Wdiag[:, None]).T @ Xa)
    ll = coll.psum(jnp.sum(mask * (yb * jax.nn.log_sigmoid(eta)
                                   + (1 - yb) * jax.nn.log_sigmoid(-eta))))
    return grad, hess, ll


def fit_logistic(X: np.ndarray, y: np.ndarray, *, regParam: float = 0.0,
                 elasticNetParam: float = 0.0, fitIntercept: bool = True,
                 standardization: bool = True, maxIter: int = 100,
                 tol: float = 1e-7) -> LinearFit:
    """Binomial logistic regression by IRLS Newton steps; the per-iteration
    `X^T W X` / gradient reduction is a psum over the mesh — the exact shape
    of MLlib's treeAggregate-per-iteration loop. As with fit_linear, the
    default penalty applies to standardized coefficients (reference's
    standardization=True), i.e. a per-feature std² scale in raw space."""
    n, d = X.shape
    lam = float(regParam)
    l2 = lam * (1 - float(elasticNetParam))
    l1 = lam * float(elasticNetParam)
    if standardization and lam > 0:
        # f64 accumulation without materializing an f64 copy of X
        pen_scale = np.maximum(X.var(axis=0, dtype=np.float64), 1e-12)
    else:
        pen_scale = np.ones(d)

    w = np.zeros(d + 1, dtype=np.float32)
    n_f = float(len(y))
    prev_ll = -np.inf
    iters = 0
    newton_work = WorkHint(flops=3.0 * n * (d + 1) ** 2, kind="blas")
    X32 = np.asarray(X, np.float32)
    y32 = np.asarray(y, np.float32)
    for it in range(maxIter):
        grad, hess, ll = run_data_parallel(
            _newton_pass, X32, y32,
            replicated=(jnp.asarray(w),), work=newton_work)
        grad = np.asarray(grad, dtype=np.float64)
        hess = np.asarray(hess, dtype=np.float64)
        if l2 > 0:
            grad[:d] += l2 * n_f * pen_scale * w[:d]
            hess[:d, :d] += l2 * n_f * np.diag(pen_scale)
        step = np.linalg.solve(hess + 1e-8 * np.eye(d + 1), grad)
        w_new = w - step.astype(np.float32)
        if l1 > 0:  # proximal shrink on coefficients (not intercept)
            # standardized L1 is λα·Σ σ_j|w_j| in raw space — linear in σ,
            # unlike the quadratic L2 term's σ²
            scale = np.abs(np.diag(hess)[:d]) + 1e-12
            w_new[:d] = np.sign(w_new[:d]) * np.maximum(
                np.abs(w_new[:d]) - l1 * n_f * np.sqrt(pen_scale) / scale, 0.0)
        iters = it + 1
        if np.max(np.abs(w_new - w)) < tol:
            w = w_new
            break
        if float(ll) < prev_ll - 1e3:  # diverging: damp
            w = (w + w_new) / 2
        else:
            w = w_new
        prev_ll = float(ll)
    if not fitIntercept:
        return LinearFit(np.asarray(w[:d], dtype=np.float64), 0.0, iters)
    return LinearFit(np.asarray(w[:d], dtype=np.float64), float(w[d]), iters)


def predict_linear(X: np.ndarray, coefficients: np.ndarray, intercept: float) -> np.ndarray:
    """Affine forward with a measured-latency cutover: batches whose matmul
    can't buy back the measured dispatch round trip run as host BLAS; the
    rest shard rows over the mesh (ML 12 throughput path)."""
    if X.size == 0:
        return np.zeros((X.shape[0],))
    from ..parallel import dispatch
    from ._staging import route_for_arrays
    n, d = X.shape
    X32 = np.asarray(X, np.float32)
    hint = dispatch.WorkHint(flops=2.0 * n * d, kind="blas",
                             out_bytes=4.0 * n)
    if route_for_arrays(hint, X32)[1] == "host":
        return (np.asarray(X, dtype=np.float64) @
                np.asarray(coefficients, dtype=np.float64) + intercept)
    from .inference import predict_linear_sharded
    return predict_linear_sharded(X, coefficients, intercept)
