"""Distributed linear-model solvers (SURVEY §2.2 P2).

The reference's LinearRegression trains by "matrix decomposition … else
L-BFGS", with per-iteration gradients tree-aggregated from executors
(`SML/Labs/ML 02L - Linear Regression I Lab.py:66-77`). Here the same math is
two jitted shard_map programs over the mesh's data axis:

- one pass building the Gram block `[X 1]^T [X 1]` and `[X 1]^T y` per chip,
  `psum`-reduced over ICI (the treeAggregate replacement). d is small, so the
  (d+1)² solve happens replicated on every chip.
- for L1/elastic-net and logistic loss, an iterative program (FISTA on the
  Gram for least squares; IRLS Newton for logistic, a proximal Newton step
  where it is penalized) whose per-iteration reductions are the same psum.

All passes are masked so row padding (static shapes for XLA) is inert.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel import collectives as coll
from ..parallel.dispatch import WorkHint
from ._staging import RowsLast, run_data_parallel


class LinearFit(NamedTuple):
    coefficients: np.ndarray
    intercept: float
    iterations: int
    # training-fit statistics derived from the SAME Gram pass (no second
    # data pass): {"sse", "var_y", "var_pred", "n"} — see fit_linear
    stats: Optional[dict] = None


# ------------------------------------------------- standardized coordinates
# Every device pass of this module (the Gram, a Newton step; the row-major
# block and the compact form alike) runs on the standardized slots
# Z = (X - shift) / scale, and the host maps what comes back to the raw
# coordinates in float64 (`_raw_map`). The map is exact for whatever
# (shift, scale) the pass used, so an answer does not move under it;
# float32 can only reach the answer there: a raw latitude of 37.76 +- 0.026
# beside the intercept gives a Gram whose condition number is past 1e7, on
# which the Newton steps of the course's own table diverged and its least
# squares were 1.7 standard errors off (PERF.md section 6, PR 32).
#
# `scale` is the power of two under the slot's deviation and `shift` the
# multiple of it nearest the mean (`_dyadic`), so Z's deviation is in
# [1, 2), its mean within a half of 0, and Z is EXACT in float32 wherever
# X - shift is: a count, a small integer or a one-hot slot stays one, and
# the sums of a Gram over such slots stay exact whatever their order.
_EXPONENT = 0x7F800000


def _dyadic(mean, std):
    """(shift, scale) of `_moments`' (mean, std), a slot each: the power
    of two not over std (its mantissa bits masked off) and the multiple of
    that nearest the mean."""
    scale = jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(std, jnp.uint32)
        & jnp.uint32(_EXPONENT), jnp.float32)
    return jnp.round(mean / scale) * scale, scale


def _dyadic_host(mean, std):
    """`_dyadic` in NumPy, float32 out, for moments made on the host."""
    scale = np.ldexp(1.0, np.frexp(std)[1] - 1)     # std = m * 2**e, m >= 0.5
    return ((np.round(mean / scale) * scale).astype(np.float32),
            scale.astype(np.float32))


def _mean_std(pieces, mask):
    """(n, mean, deviation) of every slot over the rows `mask` keeps, the
    deviation with n in the denominator. `pieces` are (width, rows)
    blocks, reduced one by one so that no block is made for them."""
    n = coll.psum(jnp.sum(mask))
    mean = jnp.concatenate(
        [coll.psum(jnp.sum(p * mask[None, :], axis=1)) for p in pieces]) / n
    lo, var = 0, []
    for p in pieces:
        c = (p - mean[lo:lo + p.shape[0], None]) * mask[None, :]
        var.append(coll.psum(jnp.sum(c * c, axis=1)))
        lo += p.shape[0]
    return n, mean, jnp.sqrt(jnp.concatenate(var) / n)


def _shift_scale(mean, std):
    """`_dyadic` of a slot's mean and deviation, a constant slot given a
    deviation of 1."""
    return _dyadic(mean, jnp.where(
        std >= jnp.finfo(jnp.float32).tiny, std, 1.0))


def _moments(pieces, mask):
    """(shift, scale) of every slot from its mean and deviation over the
    table's true rows (`_mean_std`, `_shift_scale`)."""
    _, mean, std = _mean_std(pieces, mask)
    return _shift_scale(mean, std)


def _standardized_rows(Xb, mask, shift, scale):
    """[Z 1] of a row-major block, rows masked."""
    Z = (Xb - shift[None, :]) / scale[None, :]
    return jnp.concatenate([Z, jnp.ones_like(mask)[:, None]],
                           axis=1) * mask[:, None]


def _raw_map(shift, scale) -> np.ndarray:
    """T, (d+1, d+1) float64, with [X 1]^T = T @ [Z 1]^T. A Gram or a
    Hessian of [Z 1] goes to the raw coordinates as T @ A @ T.T, a moment
    or a gradient as T @ b, raw coefficients to the standardized ones as
    T.T @ w and back by a solve."""
    d = len(scale)
    T = np.eye(d + 1)
    T[np.arange(d), np.arange(d)] = np.asarray(scale, dtype=np.float64)
    T[:d, d] = np.asarray(shift, dtype=np.float64)
    return T


def _gram_to_raw(out):
    """(A, b, n, yy) of [X 1] in float64 from a standardized Gram pass's
    (A, b, n, yy, shift, scale)."""
    A, b, n, yy, shift, scale = out
    T = _raw_map(shift, scale)
    return (T @ np.asarray(A, dtype=np.float64) @ T.T,
            T @ np.asarray(b, dtype=np.float64), float(n), float(yy))


def _gram_pass(Xb, yb, mask):
    shift, scale = _moments([Xb.T], mask)
    Za = _standardized_rows(Xb, mask, shift, scale)
    yb = yb * mask
    A = coll.psum(Za.T @ Za)            # MXU matmul then ICI allreduce
    b = coll.psum(Za.T @ yb)
    n = coll.psum(jnp.sum(mask))
    yy = coll.psum(jnp.sum(yb * yb))
    return A, b, n, yy, shift, scale


def gram_stats(X: np.ndarray, y: np.ndarray) -> Tuple[np.ndarray, np.ndarray, float, float]:
    """One data-parallel pass: (A = [X 1]^T [X 1], b = [X 1]^T y, n, y^T y).
    ONE device round trip — every downstream fit statistic is a host-side
    identity on these moments."""
    n_rows, d = X.shape
    # asarray, not astype: astype always copies, which both costs ~0.1s/GB
    # and defeats the staging cache's identity keys on repeated fits
    return _gram_to_raw(run_data_parallel(
        _gram_pass, np.asarray(X, np.float32), np.asarray(y, np.float32),
        work=WorkHint(flops=2.0 * n_rows * (d + 1) ** 2, kind="blas")))


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
def _expand_pieces(num_t, codes_t, layout):
    """The slots of a CompactParts block in the assembler's order, each a
    (width, rows) float32 piece (`code == iota` compares on the VPU)."""
    pieces = []
    for item in layout:
        if item[0] == "num":
            pieces.append(num_t[item[1]][None, :])
        else:
            _, j, width = item
            iota = jnp.arange(width, dtype=codes_t.dtype)
            pieces.append((codes_t[j][None, :]
                           == iota[:, None]).astype(jnp.float32))
    return pieces


def _expand_block(num_t, codes_t, mask, layout, train):
    """Per-chip expansion of a CompactParts block into [Z 1]^T, a slot a
    ROW and the chip's table rows along the last axis, rows masked, Z the
    slots standardized by the rows `train` keeps (the fit's own: `mask`
    itself, or a fold's training rows, whose validation rows are then
    read under the same standardization); with the (shift, scale) it used
    (`_raw_map`) and those rows' count and deviations (`_mean_std`).

    The block exists only in HBM on the chip, never on the host or the
    H2D path (featurizer.CompactParts). Out-of-range codes
    (handleInvalid="keep" overflow slots) are all-zero rows of X exactly
    like the host writer's. Padding rows carry code 0, so EVERY piece is
    mask-multiplied.

    Feature-major because the last axis is the chip's 128-lane one: a
    (rows, width) piece pads its width to 128 lanes, and the seven pieces
    of the course's table with their concatenation asked the v5e for
    26 GB at 6.8 M rows; (d + 1, rows) pads d + 1 to a multiple of 8."""
    with jax.named_scope("linear.expand"):
        pieces = _expand_pieces(num_t, codes_t, layout)
        n, mean, std = _mean_std(pieces, train)
        shift, scale = _shift_scale(mean, std)
        Z = (jnp.concatenate(pieces, axis=0)
             - shift[:, None]) / scale[:, None]
        ones = jnp.ones((1, num_t.shape[1]), dtype=jnp.float32)
        return (jnp.concatenate([Z, ones], axis=0) * mask[None, :],
                shift, scale, n, std)


def _expand_masked(num_t, codes_t, mask, layout):
    """`_expand_block` standardized by the block's own true rows: the
    block, the shift and the scale."""
    return _expand_block(num_t, codes_t, mask, layout, mask)[:3]


_compact_gram_fns: dict = {}


def _compact_gram_fn(layout):
    fn = _compact_gram_fns.get(layout)
    if fn is not None:
        return fn

    def gram_compact(num_t, codes_t, yb, mask):
        # f32 matmul precision: bf16 operand truncation would corrupt the
        # Gram moments (counts up to n and squared sums are not bf16-exact)
        with jax.default_matmul_precision("float32"):
            Za, shift, scale = _expand_masked(num_t, codes_t, mask,
                                              layout)
            yb = yb * mask
            A = coll.psum(Za @ Za.T)
            b = coll.psum(Za @ yb)
            n = coll.psum(jnp.sum(mask))
            yy = coll.psum(jnp.sum(yb * yb))
        return A, b, n, yy, shift, scale

    gram_compact.__name__ = f"gram_compact_{abs(hash(layout)) % 99991}"
    _compact_gram_fns[layout] = gram_compact
    return gram_compact


def gram_stats_compact(parts, y: np.ndarray):
    """gram_stats over a featurizer.CompactParts block: one device pass,
    one-hot slots expanded on-chip (SURVEY §2.2 P2 at beyond-one-machine
    scale — `SML/ML 00b - Spark Review.py:84`)."""
    n_rows = parts.rows
    d = parts.width
    return _gram_to_raw(run_data_parallel(
        _compact_gram_fn(parts.layout), RowsLast(parts.num),
        RowsLast(parts.codes), np.asarray(y, np.float32),
        work=WorkHint(flops=2.0 * n_rows * (d + 1) ** 2, kind="blas")))


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

    def irls_compact(num_t, codes_t, yb, mask):
        """WHOLE-FIT fused IRLS: the expanded block stays resident in HBM
        and the Newton steps to convergence, at most maxIter — grad/Hessian
        psum, (d+1)² solve, damping, the convergence test — run in ONE
        dispatch. The host loop pays a dispatch round trip and a
        device→host read per iteration; at course-scale d that fixed cost
        IS the fit time. Semantics mirror fit_logistic's lam=0 loop: step =
        solve(H + 1e-8 I, g), damp to the midpoint when the log-likelihood
        drops by >1e3, stop after the step whose max|Δw| < tol (a
        `while_loop` on `done`: every step it runs moves `w`, so the count
        it returns is the steps executed AND the iterations a fit reports;
        `done` comes from psum'd quantities, so every shard leaves at the
        same step). The block is
        [Z 1]^T (`_expand_masked`): a product over the table's rows
        contracts its last axis. `w` stays in the standardized space for
        all the steps and is returned there with the shift and the scale;
        the caller maps it back in float64 (`_raw_map`)."""
        with jax.default_matmul_precision("float32"):
            Xa, shift, scale = _expand_masked(num_t, codes_t, mask, layout)
            d1 = Xa.shape[0]
            eye = jnp.eye(d1, dtype=jnp.float32)

            def body(carry):
                w, prev_ll, _, iters = carry
                with jax.named_scope("linear.irls.margin"):
                    eta = w @ Xa
                    p = jax.nn.sigmoid(eta)
                    Wd = jnp.maximum(p * (1 - p), 1e-6) * mask
                with jax.named_scope("linear.irls.grad"):
                    grad = coll.psum(Xa @ ((p - yb) * mask))
                    ll = coll.psum(jnp.sum(mask * (
                        yb * jax.nn.log_sigmoid(eta)
                        + (1 - yb) * jax.nn.log_sigmoid(-eta))))
                with jax.named_scope("linear.irls.hess"):
                    hess = coll.psum((Xa * Wd[None, :]) @ Xa.T)
                with jax.named_scope("linear.irls.solve"):
                    step = jnp.linalg.solve(hess + 1e-8 * eye, grad)
                    w_new = w - step
                    conv = jnp.max(jnp.abs(w_new - w)) < tol
                    damp = ll < prev_ll - 1e3
                    w_next = jnp.where(damp, (w + w_new) / 2, w_new)
                return w_next, ll, conv, iters + 1

            def unfinished(carry):
                _, _, done, iters = carry
                return (iters < maxIter) & ~done

            init = (jnp.zeros((d1,), jnp.float32), jnp.float32(-jnp.inf),
                    jnp.bool_(False), jnp.int32(0))
            with jax.named_scope("linear.irls"):
                w, _, _, iters = jax.lax.while_loop(unfinished, body, init)
        return w, shift, scale, iters

    irls_compact.__name__ = \
        f"irls_compact_{abs(hash(key)) % 99991}"
    _compact_irls_fns[key] = irls_compact
    return irls_compact


def fit_logistic_compact(parts, y: np.ndarray, *, regParam: float = 0.0,
                         elasticNetParam: float = 0.0, maxIter: int = 100,
                         tol: float = 1e-7) -> LinearFit:
    """Binomial logistic fit over a CompactParts block: the fused-IRLS
    device program, one dispatch. With no penalty it is
    `_compact_irls_fn`'s program as ever; with `regParam` > 0 the
    elastic-net one (`_compact_enet_fn`).
    Counters: `linear.irls.fits`, `linear.irls.steps_run` (the steps the
    device executed: the loop's own count, read back with the fit),
    `linear.irls.iterations` (the steps that moved `w`: every step the
    loop runs does, so the two grow together) and, penalized,
    `linear.irls.prox_sweeps` (the inner coordinate sweeps)."""
    from ..utils.profiler import PROFILER
    if float(regParam) > 0.0:
        return _run_enet(parts, y, [(regParam, elasticNetParam)],
                         maxIter, tol)[0][0]
    n_rows, d = parts.rows, parts.width
    z, shift, scale, iters = run_data_parallel(
        _compact_irls_fn(parts.layout, int(maxIter), float(tol)),
        RowsLast(parts.num), RowsLast(parts.codes),
        np.asarray(y, np.float32),
        work=WorkHint(flops=3.0 * maxIter * n_rows * (d + 1) ** 2,
                      kind="blas"))
    steps = int(iters)
    PROFILER.count("linear.irls.fits")
    PROFILER.count("linear.irls.steps_run", steps)
    PROFILER.count("linear.irls.iterations", steps)
    w = np.linalg.solve(_raw_map(shift, scale).T, np.asarray(z, np.float64))
    return LinearFit(w[:d], float(w[d]), steps)


# ------------------------------------------- penalized fused fit and folds
#: inner sweeps one Newton step may take (`_enet_solve`): a bound, not a
#: schedule; the sweeps stop where none moves a coordinate by tol / 4
_SWEEPS_MAX = 256


#: float32's floor under the penalized loop's end. Near its end a step is
#: the noise of a float32 gradient over millions of rows divided by a flat
#: direction's curvature, and the same z gives the same noise: the steps
#: repeat at about tol and max|dz| < tol may never be met (3 fits of 133
#: ran maxIter steps in one run of the chip's twelve, PERF.md section 6,
#: PR 40). A step under `_FLOOR` x tol that is NO SMALLER than the one
#: before it is such noise: a fit that still converges, at whatever rate,
#: shrinks every step. The loop ends at the `_STALLS`-th such step, and the
#: fit is then NOT converged by tol: it is counted under
#: `linear.irls.floor_ended`, apart from the converged and from those that
#: ran maxIter steps (`linear.irls.unconverged`). The unpenalized program
#: (`_compact_irls_fn`) has no such end: it is cell 4's to the digit
_FLOOR = 16.0
_STALLS = 2


def _stalled(moved, prev_moved, tol):
    """Whether a step of size `moved` after one of `prev_moved` is
    float32's noise and no progress: under `_FLOOR` x tol and no smaller
    than its predecessor."""
    return (moved < _FLOOR * tol) & (moved >= prev_moved)


def _soft(x, t):
    return jnp.sign(x) * jnp.maximum(jnp.abs(x) - t, 0.0)


def _enet_solve(hess, grad, z, l1w, l2w, tol):
    """(v, sweeps): the minimizer v of the penalized quadratic model at z,
    grad.(v - z) + (v - z)' hess (v - z) / 2 + sum_j l1w_j |v_j|
    + l2w_j v_j^2 / 2, and the coordinate sweeps it took. With no l1w the
    model is a ridge system and one solve (scope `linear.irls.solve`, 0
    sweeps). Else cyclic coordinate descent from z on the (d+1)^2 model
    (scope `linear.irls.prox`): coordinate j goes to
    soft(h_jj v_j - r_j, l1w_j) / (h_jj + l2w_j), r the smooth gradient
    at v, made anew from `hess` at every sweep so float32 does not drift;
    a coordinate with no curvature (a constant slot) goes to 0."""
    d1 = z.shape[0]

    def ridge(_):
        with jax.named_scope("linear.irls.solve"):
            step = jnp.linalg.solve(
                hess + jnp.diag(l2w) + 1e-8 * jnp.eye(d1, dtype=z.dtype),
                grad + l2w * z)
            return z - step, jnp.int32(0)

    def descent(_):
        def sweep(state):
            v, _, sweeps = state
            r = grad + hess @ (v - z)

            def coordinate(j, c):
                v, r, moved = c
                h = hess[j, j]
                curve = h + l2w[j]
                to = jnp.where(curve > 0, _soft(h * v[j] - r[j], l1w[j])
                               / jnp.where(curve > 0, curve, 1.0), 0.0)
                delta = to - v[j]
                return (v.at[j].set(to), r + hess[:, j] * delta,
                        jnp.maximum(moved, jnp.abs(delta)))

            v, _, moved = jax.lax.fori_loop(
                0, d1, coordinate, (v, r, jnp.float32(0.0)))
            return v, moved, sweeps + 1

        def unsettled(state):
            _, moved, sweeps = state
            return (moved >= 0.25 * tol) & (sweeps < _SWEEPS_MAX)

        with jax.named_scope("linear.irls.prox"):
            v, _, sweeps = jax.lax.while_loop(
                unsettled, sweep, (z, jnp.float32(jnp.inf), jnp.int32(0)))
            return v, sweeps

    return jax.lax.cond(jnp.any(l1w > 0), descent, ridge, None)


_compact_enet_fns: dict = {}


def _compact_enet_fn(layout, maxIter: int, tol: float, folded: bool):
    key = (layout, maxIter, float(tol), folded)
    fn = _compact_enet_fns.get(key)
    if fn is not None:
        return fn

    def enet_compact(num_t, codes_t, yb, *rest):
        """The fused fit of `_compact_irls_fn` with the elastic-net
        penalty in it, a grid point after another in ONE dispatch:
        minimize -(1/n) loglik + lam (alpha sum |u_j| + (1 - alpha) / 2
        sum u_j^2), u the coefficients on the columns scaled to a SAMPLE
        deviation of 1 over the fit's own rows (MLlib's standardization),
        the intercept free. In the block's coordinates u_j = s_j z_j, s_j
        the sample deviation of Z_j, so the penalty's weights are
        lam alpha s_j and lam (1 - alpha) s_j^2. A step is proximal
        Newton: the gradient and the Hessian a row (the unpenalized
        program's passes and scopes), then the penalized quadratic model
        minimized on the (d+1)^2 system (`_enet_solve`), the same damping,
        and the same end, the step whose max|dz| < tol (`done`); and one
        end the unpenalized program has not: the `_STALLS`-th step under
        float32's floor that did not shrink (`_stalled`), after which the
        fit is `floored` and not `done`.

        `folded`: the rows carry a fold id; the fit is on the rows whose
        id is not `held` and standardized by them, and after each point's
        fit the margins of EVERY row under it are made there (scope
        `cv.eval`; the held rows' are the validation's): nothing of a fold
        leaves the chip but the coefficients and a margin a row."""
        if folded:
            fold, mask, held, lams, alphas = rest
            train = mask * (fold != held)
        else:
            mask, lams, alphas = rest
            train = mask
        with jax.default_matmul_precision("float32"):
            Xa, shift, scale, n, std = _expand_block(
                num_t, codes_t, mask, layout, train)
            d1 = Xa.shape[0]
            # sample deviation of every Z_j over the fit's rows; 0 for a
            # constant slot and for the intercept: no penalty there
            spread = jnp.concatenate([
                std * jnp.sqrt(n / jnp.maximum(n - 1.0, 1.0)) / scale,
                jnp.zeros((1,), jnp.float32)])

            def fit_point(point):
                lam, alpha = point
                l1w = lam * alpha * spread
                l2w = lam * (1.0 - alpha) * spread * spread

                def body(carry):
                    z, prev_ll, _, iters, sweeps, prev_moved, stalls = carry
                    with jax.named_scope("linear.irls.margin"):
                        eta = z @ Xa
                        p = jax.nn.sigmoid(eta)
                        Wd = jnp.maximum(p * (1 - p), 1e-6) * train
                    with jax.named_scope("linear.irls.grad"):
                        grad = coll.psum(Xa @ ((p - yb) * train)) / n
                        ll = coll.psum(jnp.sum(train * (
                            yb * jax.nn.log_sigmoid(eta)
                            + (1 - yb) * jax.nn.log_sigmoid(-eta))))
                    with jax.named_scope("linear.irls.hess"):
                        hess = coll.psum((Xa * Wd[None, :]) @ Xa.T) / n
                    v, used = _enet_solve(hess, grad, z, l1w, l2w, tol)
                    moved = jnp.max(jnp.abs(v - z))
                    conv = moved < tol
                    stalls = stalls + _stalled(moved, prev_moved, tol)
                    damp = ll < prev_ll - 1e3
                    z_next = jnp.where(damp, (z + v) / 2, v)
                    return (z_next, ll, conv, iters + 1, sweeps + used,
                            moved, stalls)

                def unfinished(carry):
                    _, _, done, iters, _, _, stalls = carry
                    return (iters < maxIter) & ~done & (stalls < _STALLS)

                init = (jnp.zeros((d1,), jnp.float32),
                        jnp.float32(-jnp.inf), jnp.bool_(False),
                        jnp.int32(0), jnp.int32(0), jnp.float32(jnp.inf),
                        jnp.int32(0))
                with jax.named_scope("linear.irls"):
                    z, _, done, iters, sweeps, _, stalls = \
                        jax.lax.while_loop(unfinished, body, init)
                out = (z, iters, sweeps, done, ~done & (stalls >= _STALLS))
                if folded:
                    # every chip holds every row's margin: the ranking is
                    # over the table, whatever it is sharded over
                    with jax.named_scope("cv.eval"):
                        out += (coll.all_gather(z @ Xa, tiled=True),)
                return out

            points = jax.lax.map(fit_point, (lams, alphas))
        return (shift, scale) + points

    enet_compact.__name__ = f"enet_compact_{abs(hash(key)) % 99991}"
    _compact_enet_fns[key] = enet_compact
    return enet_compact


def _run_enet(parts, y, points, maxIter, tol, fold=None, held=-1):
    """One dispatch of `_compact_enet_fn` over `points`, [(regParam,
    elasticNetParam)]: a list, a point, of (LinearFit, converged, None |
    the float32 margin of every row of the block under the fit).
    `converged` is by max|dz| < tol alone; a fit that ended at float32's
    floor (`_stalled`) is counted under `linear.irls.floor_ended`, one
    that ran `maxIter` steps under `linear.irls.unconverged`."""
    from ..utils.profiler import PROFILER
    n_rows, d = parts.rows, parts.width
    arrays = [RowsLast(parts.num), RowsLast(parts.codes),
              np.asarray(y, np.float32)]
    replicated = (np.asarray([p[0] for p in points], np.float32),
                  np.asarray([p[1] for p in points], np.float32))
    if fold is not None:
        arrays.append(fold)
        replicated = (np.float32(held),) + replicated
    shift, scale, zs, iters, sweeps, done, floored, *margins = \
        run_data_parallel(
            _compact_enet_fn(parts.layout, int(maxIter), float(tol),
                             fold is not None),
            *arrays, replicated=replicated,
            work=WorkHint(flops=3.0 * maxIter * len(points) * n_rows
                          * (d + 1) ** 2, kind="blas"))
    steps = int(np.sum(iters))
    PROFILER.count("linear.irls.fits", len(points))
    PROFILER.count("linear.irls.steps_run", steps)
    PROFILER.count("linear.irls.iterations", steps)
    PROFILER.count("linear.irls.prox_sweeps", int(np.sum(sweeps)))
    done, floored = np.asarray(done, bool), np.asarray(floored, bool)
    if floored.any():
        PROFILER.count("linear.irls.floor_ended", int(floored.sum()))
    if not np.all(done | floored):
        PROFILER.count("linear.irls.unconverged",
                       int(np.sum(~(done | floored))))
    shift, scale = np.float64(shift), np.float64(scale)
    out = []
    for g in range(len(points)):
        # `_raw_map`'s inverse written out (w_j = z_j / scale_j, then the
        # intercept): a coordinate the descent left at 0 stays an exact 0
        w = np.asarray(zs[g][:d], np.float64) / scale
        w = np.append(w, float(zs[g][d]) - shift @ w)
        out.append((LinearFit(w[:d], float(w[d]), int(iters[g])),
                    bool(done[g]),
                    margins[0][g][:n_rows] if margins else None))
    return out


def fit_logistic_folds(parts, y: np.ndarray, fold: np.ndarray, held: int,
                       points, *, maxIter: int = 100, tol: float = 1e-7):
    """One fold of a cross-validation over a CompactParts block, every
    grid point of `points` ([(regParam, elasticNetParam)]) in one
    dispatch: the fit on the rows whose `fold` id is not `held`, then
    every row's margin under it. A list, a point, of (LinearFit,
    converged, the margins); the rows whose id is `held` are the
    validation's."""
    return _run_enet(parts, y, points, maxIter, tol, fold=fold, held=held)


def _newton_pass(Xb, yb, mask, wb, shift, scale):
    """Gradient, Hessian and log-likelihood at `wb`, all three in the
    standardized coordinates that `shift` and `scale` define."""
    Xa = _standardized_rows(Xb, mask, shift, scale)
    eta = Xa @ wb
    p = jax.nn.sigmoid(eta)
    Wdiag = jnp.maximum(p * (1 - p), 1e-6) * mask
    grad = coll.psum(Xa.T @ ((p - yb) * mask))
    hess = coll.psum((Xa * Wdiag[:, None]).T @ Xa)
    ll = coll.psum(jnp.sum(mask * (yb * jax.nn.log_sigmoid(eta)
                                   + (1 - yb) * jax.nn.log_sigmoid(-eta))))
    return grad, hess, ll


def _enet_solve_host(hess, grad, z, l1w, l2w, tol):
    """`_enet_solve` in NumPy float64, for the host loop: the ridge
    system solved, or coordinate descent to a sweep that moves nothing by
    tol / 1000."""
    d1 = len(z)
    if not np.any(l1w > 0):
        return z - np.linalg.solve(
            hess + np.diag(l2w) + 1e-8 * np.eye(d1), grad + l2w * z)
    v = z.copy()
    for _ in range(40 * _SWEEPS_MAX):
        r = grad + hess @ (v - z)
        moved = 0.0
        for k in range(d1):
            h = hess[k, k]
            curve = h + l2w[k]
            a, t = h * v[k] - r[k], l1w[k]       # soft(a, t) / curve
            to = 0.0 if curve <= 0 else \
                (a - t if a > t else a + t if a < -t else 0.0) / curve
            delta = to - v[k]
            if delta:
                r += hess[:, k] * delta
                v[k] = to
                moved = max(moved, abs(delta))
        if moved < 1e-3 * tol:
            break
    return v


def fit_logistic(X: np.ndarray, y: np.ndarray, *, regParam: float = 0.0,
                 elasticNetParam: float = 0.0, fitIntercept: bool = True,
                 standardization: bool = True, maxIter: int = 100,
                 tol: float = 1e-7) -> LinearFit:
    """Binomial logistic regression over a row-major block by proximal
    Newton steps, a dispatch a step; the per-iteration `X^T W X` /
    gradient reduction is a psum over the mesh — the exact shape of
    MLlib's treeAggregate-per-iteration loop. What has no compact block
    takes this loop (counter `linear.host_loops`); the mathematics is the
    fused program's (`_compact_enet_fn`): the device passes and the loop
    run in the standardized coordinates Z, the penalty is MLlib's (on the
    coefficients of the columns scaled to a sample deviation of 1, or on
    the raw ones with standardization off; the intercept free), a step
    minimizes the penalized quadratic model on the host in float64
    (`_enet_solve_host`), and the loop ends with the step whose
    max|dz| < tol."""
    from ..utils.profiler import PROFILER
    PROFILER.count("linear.host_loops")
    n, d = X.shape
    lam, alpha = float(regParam), float(elasticNetParam)
    # f64 accumulation without materializing an f64 copy of X
    var = X.var(axis=0, dtype=np.float64)
    standard = _dyadic_host(X.mean(axis=0, dtype=np.float64),
                            np.sqrt(np.where(var > 0, var, 1.0)))
    shift, scale = (np.asarray(a, np.float64) for a in standard)
    # the penalty's weight a coordinate of z: u_j = spread_j z_j
    spread = np.append(
        np.sqrt(var * n / max(n - 1, 1)) / scale if standardization
        else 1.0 / scale, 0.0)
    l1w = lam * alpha * spread
    l2w = lam * (1.0 - alpha) * spread ** 2

    z = np.zeros(d + 1)
    prev_ll = -np.inf
    iters = 0
    newton_work = WorkHint(flops=3.0 * n * (d + 1) ** 2, kind="blas")
    X32 = np.asarray(X, np.float32)
    y32 = np.asarray(y, np.float32)
    for it in range(maxIter):
        grad, hess, ll = run_data_parallel(
            _newton_pass, X32, y32,
            replicated=(jnp.asarray(z, jnp.float32), *standard),
            work=newton_work)
        v = _enet_solve_host(np.asarray(hess, np.float64) / n,
                             np.asarray(grad, np.float64) / n,
                             z, l1w, l2w, tol)
        iters = it + 1
        # converged where the fused program says so: by the step in the
        # standardized coordinates, where float32's noise in a step is of
        # the order of tol and not of a raw slot's
        if np.max(np.abs(v - z)) < tol:
            z = v
            break
        z = (z + v) / 2 if float(ll) < prev_ll - 1e3 else v   # diverging
        prev_ll = float(ll)
    w = z[:d] / scale       # `_raw_map`'s inverse: an exact 0 stays one
    return LinearFit(w, float(z[d] - shift @ w) if fitIntercept else 0.0,
                     iters)


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
