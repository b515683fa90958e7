"""The fused logistic fit stops where it has converged (ISSUE 39).

`linear_impl._compact_irls_fn` ran `lax.scan(body, length=maxIter)` and
froze its carry behind `done`; it is a `lax.while_loop` on `done` now. The
step at which the convergence test first holds is executed as before, so
the answer is the parent's: the fixed-length scan of the parent is kept
HERE as the plain reference (`_scan_irls`, its body and its guards copied
as the parent had them), and the program is held to its bits on small
tables through `run_data_parallel`, on the 8-device CPU mesh (the `psum`s
are real) and on one device.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sml_tpu.conf import GLOBAL_CONF
from sml_tpu.ml import linear_impl
from sml_tpu.ml._staging import RowsLast, run_data_parallel
from sml_tpu.ml.featurizer import CompactParts
from sml_tpu.parallel import collectives as coll
from sml_tpu.parallel import mesh as meshlib
from sml_tpu.utils.profiler import PROFILER

# a numeric slot, a coded column of three slots (code 3 is the dropped
# last category: an all-zero row of the block), two numeric slots
LAYOUT = (("num", 0), ("oh", 0, 3), ("num", 1), ("num", 2))
# the damped table's: three heavy-tailed numeric slots and no coded column
NUMERIC = (("num", 0), ("num", 1), ("num", 2))
TOL = 1e-6
DAMPED_SEED = 81


@functools.lru_cache(maxsize=None)   # one function a program: one compile
def _scan_irls(layout, maxIter: int):
    """The parent's program: every one of `maxIter` steps runs, and `done`
    freezes `w`, `prev_ll` and `iters`. Beside the parent's four results,
    the steps that were damped."""

    def scan_irls(num_t, codes_t, yb, mask):
        with jax.default_matmul_precision("float32"):
            Xa, shift, scale = linear_impl._expand_masked(
                num_t, codes_t, mask, layout)
            d1 = Xa.shape[0]
            eye = jnp.eye(d1, dtype=jnp.float32)

            def body(carry, _):
                w, prev_ll, done, iters, damped = carry
                eta = w @ Xa
                p = jax.nn.sigmoid(eta)
                Wd = jnp.maximum(p * (1 - p), 1e-6) * mask
                grad = coll.psum(Xa @ ((p - yb) * mask))
                ll = coll.psum(jnp.sum(mask * (
                    yb * jax.nn.log_sigmoid(eta)
                    + (1 - yb) * jax.nn.log_sigmoid(-eta))))
                hess = coll.psum((Xa * Wd[None, :]) @ Xa.T)
                step = jnp.linalg.solve(hess + 1e-8 * eye, grad)
                w_new = w - step
                conv = jnp.max(jnp.abs(w_new - w)) < TOL
                damp = ll < prev_ll - 1e3
                w_next = jnp.where(
                    done, w, jnp.where(damp, (w + w_new) / 2, w_new))
                iters = iters + jnp.where(done, 0, 1)
                damped = damped + jnp.where(done | ~damp, 0, 1)
                return (w_next, jnp.where(done, prev_ll, ll),
                        done | conv, iters, damped), None

            init = (jnp.zeros((d1,), jnp.float32), jnp.float32(-jnp.inf),
                    jnp.bool_(False), jnp.int32(0), jnp.int32(0))
            (w, _, _, iters, damped), _ = jax.lax.scan(
                body, init, None, length=maxIter)
        return w, shift, scale, iters, damped

    scan_irls.__name__ = f"scan_irls_{maxIter}"
    return scan_irls


def _table(kind: str):
    """A layout and its table. `noisy`: labels drawn from a logistic model
    of the slots, 3,000 rows; Newton from zero converges in 5 steps.
    `separable`: the label is the sign of one slot, 6,000 rows with a
    heavy-tailed slot beside it; the steps never get under `tol` and the
    fit runs `maxIter` of them, none damped. `damped`: 24 rows of three
    log-normal slots (e^{2N}, signed) whose label follows them but for a
    little noise; from its tenth step or so the weights saturate, a step
    throws the log-likelihood down by far more than 1e3 and the next is
    halved, again and again."""
    if kind == "damped":
        rng = np.random.default_rng(DAMPED_SEED)
        n = 24
        num = (np.exp(2 * rng.normal(size=(3, n)))
               * rng.choice([-1, 1], (3, n))).astype(np.float32)
        y = 8 * rng.normal(size=3) @ num + rng.logistic(size=n) > 0
        return NUMERIC, (num, np.zeros((0, n), np.int32),
                         y.astype(np.float32))
    if kind == "noisy":
        rng = np.random.default_rng(3)
        n = 3000
    else:
        rng = np.random.default_rng(5)
        n = 6000
    num = rng.normal(size=(3, n)).astype(np.float32)
    num[1] = num[1] * 5 + 20
    codes = rng.integers(0, 4, size=(1, n)).astype(np.int32)
    if kind == "noisy":
        eta = (0.8 * num[0] - 0.1 * (num[1] - 20) + 0.5 * (codes[0] == 1)
               - 0.7 * (codes[0] == 2) + 0.3 * num[2])
        y = rng.random(n) < 1 / (1 + np.exp(-eta))
    else:
        num[2] = rng.standard_cauchy(n).astype(np.float32) * 3
        y = 8 * num[0] + 2.0 * num[2] + 0.05 * rng.logistic(size=n) > 0
    return LAYOUT, (num, codes, y.astype(np.float32))


def _run(fn, table):
    num, codes, y = table
    return [np.asarray(x) for x in run_data_parallel(
        fn, RowsLast(num), RowsLast(codes), y)]


@pytest.fixture(params=[8, 1], ids=["mesh8", "mesh1"])
def mesh(request):
    with meshlib.use_mesh(meshlib.build_mesh(request.param)) as m:
        yield m


# (a) converges under maxIter; (b) three steps; (c) one; (d) damped steps,
# and a separable table that runs its whole length: table, maxIter, the
# steps run, the steps damped
CASES = [("noisy", 100, 5, 0), ("noisy", 3, 3, 0), ("noisy", 1, 1, 0),
         ("damped", 100, 100, 44), ("separable", 100, 100, 0)]


@pytest.mark.parametrize("kind, maxIter, steps, damped", CASES, ids=[
    "converges", "maxIter3", "maxIter1", "damped", "separable"])
def test_the_loop_gives_the_fixed_length_scans_answer(mesh, kind, maxIter,
                                                      steps, damped):
    layout, table = _table(kind)
    w0, shift0, scale0, iters0, damped0 = _run(
        _scan_irls(layout, maxIter), table)
    w, shift, scale, iters = _run(
        linear_impl._compact_irls_fn(layout, maxIter, TOL), table)
    assert int(iters) == int(iters0) == steps
    np.testing.assert_array_equal(shift, shift0)
    np.testing.assert_array_equal(scale, scale0)
    assert np.isfinite(w).all()
    if maxIter == 1:
        # a scan of ONE step is no loop to the compiler: it inlines the
        # body and fuses it with the expansion, so it is the reference
        # whose sums are ordered anew (read: 0 to 6 ulp of a slot a
        # hundredth of the largest, on other tables). Held to 4 ulp of the
        # largest coefficient, as ISSUE 39 allows where the two loop forms
        # order a reduction differently.
        np.testing.assert_allclose(
            w, w0, rtol=0, atol=4 * np.spacing(np.abs(w0).max()))
    else:
        np.testing.assert_array_equal(w, w0)
    # the damping select is the parent's line. Newton from zero is damped
    # on no well-behaved table, separable or not (its first step is taken
    # under the largest Hessian the loss has); the `damped` table is made
    # to throw it
    assert int(damped0) == damped


def _parts(layout, table) -> CompactParts:
    num, codes, _ = table
    width = sum(slot[2] if slot[0] == "oh" else 1 for slot in layout)
    return CompactParts(num=num, codes=codes, layout=layout, width=width,
                        keep=None)


@pytest.fixture
def counting():
    prev = GLOBAL_CONF.get("sml.profiler.enabled")
    GLOBAL_CONF.set("sml.profiler.enabled", True)
    yield
    GLOBAL_CONF.set("sml.profiler.enabled", prev)


@pytest.mark.parametrize("kind, maxIter, ran", [
    ("noisy", 100, 5), ("noisy", 3, 3), ("separable", 7, 7)],
    ids=["converged", "cut_at_maxIter", "never_converges"])
def test_steps_run_counts_what_the_device_executed(counting, kind, maxIter,
                                                   ran):
    layout, table = _table(kind)
    before = PROFILER.counters()
    fit = linear_impl.fit_logistic_compact(_parts(layout, table), table[2],
                                           maxIter=maxIter, tol=TOL)
    after = PROFILER.counters()

    def grew(name):
        return after.get(name, 0.0) - before.get(name, 0.0)

    assert fit.iterations == ran
    assert grew("linear.irls.fits") == 1
    assert grew("linear.irls.steps_run") == ran
    assert grew("linear.irls.iterations") == ran


def test_the_sharded_fit_agrees_with_the_one_device_fit():
    """Eight partial sums for one: every shard leaves the loop at the same
    step, and the coefficients are apart by float32's noise (as they were:
    each layout has the scan's bits, above)."""
    layout, table = _table("noisy")
    fits = {}
    for width in (8, 1):
        with meshlib.use_mesh(meshlib.build_mesh(width)):
            fits[width] = _run(
                linear_impl._compact_irls_fn(layout, 100, TOL), table)
    (w8, _, _, iters8), (w1, _, _, iters1) = fits[8], fits[1]
    assert int(iters8) == int(iters1) == 5
    np.testing.assert_allclose(w8, w1, rtol=0, atol=1e-5)


def test_the_fit_maps_back_to_the_float64_optimum():
    """The stopped fit is a fit: its raw coefficients against float64
    Newton steps on the expanded block."""
    layout, table = _table("noisy")
    parts = _parts(layout, table)
    fit = linear_impl.fit_logistic_compact(parts, table[2], tol=TOL)
    X = np.column_stack([parts.expand_host().astype(np.float64),
                         np.ones(parts.rows)])
    mean, dev = X[:, :-1].mean(0), X[:, :-1].std(0)
    Z = np.column_stack([(X[:, :-1] - mean) / dev, np.ones(parts.rows)])
    y = table[2].astype(np.float64)
    w = np.zeros(Z.shape[1])
    for _ in range(25):
        p = 1 / (1 + np.exp(-Z @ w))
        w -= np.linalg.solve((Z * (p * (1 - p))[:, None]).T @ Z,
                             Z.T @ (p - y))
    coef = w[:-1] / dev
    np.testing.assert_allclose(fit.coefficients, coef, rtol=0, atol=2e-4)
    np.testing.assert_allclose(fit.intercept, w[-1] - coef @ mean,
                               rtol=0, atol=2e-3)
