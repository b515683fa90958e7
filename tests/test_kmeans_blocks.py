"""The blocked Lloyd step and the k-means|| seeding (`sml_tpu/ml/clustering.py`)
on the CPU, seeded, at tiny sizes: against a plain `jax.numpy` float32 Lloyd
at `jax.default_matmul_precision("highest")` and the float64 reference
(`benchmark/reference/kmeans.py`)."""

import os
import sys
import tracemalloc

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest
from jax.sharding import Mesh, PartitionSpec as P

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.reference import kmeans as reference  # noqa: E402
from sml_tpu import obs  # noqa: E402
from sml_tpu.conf import GLOBAL_CONF  # noqa: E402
from sml_tpu.ml import Pipeline, clustering  # noqa: E402
from sml_tpu.ml.clustering import KMeans, KMeansModel  # noqa: E402
from sml_tpu.ml.feature import VectorAssembler  # noqa: E402
from sml_tpu.parallel import mesh as meshlib  # noqa: E402

D = meshlib.DATA_AXIS


@pytest.fixture()
def blocks(monkeypatch):
    """`blocks(n)`: every pass walks its rows in blocks of `n` rows."""
    def force(rows: int):
        monkeypatch.setattr(clustering, "_block_rows", lambda width: rows)
        clustering.forget_programs()
    yield force
    monkeypatch.undo()
    clustering.forget_programs()


@pytest.fixture()
def counters():
    GLOBAL_CONF.set("sml.obs.enabled", True)
    obs.reset()

    def delta(before=None):
        now = dict(obs.RECORDER.counters())
        if before is None:
            return now
        return {k: v - before.get(k, 0.0) for k, v in now.items()
                if v != before.get(k, 0.0)}
    yield delta
    GLOBAL_CONF.set("sml.obs.enabled", False)
    obs.reset()


def _blobs(n=900, d=5, k=6, seed=0, spread=0.4):
    rng = np.random.default_rng(seed)
    centers = rng.normal(0, 5, (k, d))
    X = centers[rng.integers(k, size=n)] + rng.normal(0, spread, (n, d))
    return X.astype(np.float32).astype(np.float64)


def _frame(spark, X):
    cols = [f"f{i}" for i in range(X.shape[1])]
    return spark.createDataFrame(pd.DataFrame(X, columns=cols)), cols


def _assembled(spark, X):
    df, cols = _frame(spark, X)
    return VectorAssembler(inputCols=cols, outputCol="features").transform(df)


def _centers(model) -> np.ndarray:
    return np.stack(model.clusterCenters())


def _lloyd_pass(X, centers, block, devices=1, mask=None):
    """(sums about the means, counts) of `clustering._lloyd_pass` over
    `devices` CPU devices."""
    n = len(X)
    mask = np.ones(n, np.float32) if mask is None else mask
    mesh = Mesh(np.array(jax.devices()[:devices]), (D,))
    origin = (X * mask[:, None]).sum(axis=0) / mask.sum()

    def program(Xt, mask, centers, origin):
        return clustering._lloyd_pass(Xt, mask > 0, origin,
                                      centers - origin[None, :], block)

    mapped = jax.shard_map(program, mesh=mesh,
                           in_specs=(P(None, D), P(D), P(), P()),
                           out_specs=P(), check_vma=False)
    with meshlib.use_mesh_local(mesh):
        sums, counts = jax.jit(mapped)(
            jnp.asarray(X.T, jnp.float32), jnp.asarray(mask),
            jnp.asarray(centers, jnp.float32),
            jnp.asarray(origin, jnp.float32))
    return np.asarray(sums, np.float64) + np.asarray(counts)[:, None] \
        * origin, np.asarray(counts)


def _plain_step(X, centers):
    """One unblocked float32 Lloyd step in plain `jax.numpy`: (sums,
    counts)."""
    with jax.default_matmul_precision("highest"):
        x, c = jnp.asarray(X, jnp.float32), jnp.asarray(centers, jnp.float32)
        d2 = ((x[:, None, :] - c[None, :, :]) ** 2).sum(-1)
        onehot = jax.nn.one_hot(jnp.argmin(d2, axis=1), len(c),
                                dtype=jnp.float32)
        return np.asarray(onehot.T @ x, np.float64), \
            np.asarray(onehot.sum(axis=0), np.int64)


# ------------------------------------------------ the blocked step itself
@pytest.mark.parametrize("rows,k,block", [
    (1000, 6, 64),      # rows no multiple of the block
    (37, 4, 1),         # a block of one row
    (200, 24, 16),      # k larger than a block
    (512, 6, 512),      # one block
], ids=["ragged", "one-row-blocks", "k-over-block", "one-block"])
def test_the_blocked_step_is_the_unblocked_one(rows, k, block):
    X = _blobs(rows, 5, k, seed=rows)
    centers = X[np.random.default_rng(1).choice(rows, k, replace=False)]
    sums, counts = _lloyd_pass(X, centers, block)
    want_sums, want_counts = _plain_step(X, centers)
    np.testing.assert_array_equal(counts, want_counts)
    np.testing.assert_allclose(sums, want_sums, rtol=2e-6, atol=2e-4)
    step = reference.lloyd_step(X, centers)
    np.testing.assert_array_equal(counts, step["counts"])
    filled = counts > 0
    np.testing.assert_allclose(sums[filled] / counts[filled, None],
                               step["centers"][filled], rtol=1e-6, atol=1e-5)


def test_an_empty_cluster_and_a_tie():
    """A center far from every row gets none; of two equal centers the
    LOWER index takes the rows (MLlib's rule, and the reference's)."""
    X = _blobs(300, 3, 3, seed=4)
    centers = np.concatenate([X[:3], X[1:2], [[1e3, 1e3, 1e3]]])
    sums, counts = _lloyd_pass(X, centers, 32)
    step = reference.lloyd_step(X, centers)
    np.testing.assert_array_equal(counts, step["counts"])
    assert counts[4] == 0 and counts[3] == 0 and counts[1] > 0
    assert counts.sum() == 300


def test_masked_rows_are_left_out():
    X = _blobs(256, 4, 3, seed=8)
    mask = np.ones(256, np.float32)
    mask[200:] = 0.0
    centers = X[:3]
    sums, counts = _lloyd_pass(X, centers, 48, mask=mask)
    want = reference.lloyd_step(X[:200], centers)
    np.testing.assert_array_equal(counts, want["counts"])


@pytest.mark.parametrize("devices", [2, 8])
def test_the_mesh_gives_the_one_device_step(devices):
    X = _blobs(1024, 5, 6, seed=3)
    centers = X[::171][:6]
    one = _lloyd_pass(X, centers, 40)
    many = _lloyd_pass(X, centers, 40, devices=devices)
    np.testing.assert_array_equal(one[1], many[1])
    np.testing.assert_allclose(one[0], many[0], rtol=2e-6, atol=2e-4)


def test_three_bfloat16_parts_add_up_to_the_float32():
    x = jnp.asarray(np.random.default_rng(0).normal(0, 1e3, 4096), jnp.float32)
    parts = clustering._three_bfloat16(x)
    assert all(p.dtype == jnp.bfloat16 for p in parts)
    total = sum(np.asarray(p, np.float64) for p in parts)
    np.testing.assert_array_equal(total.astype(np.float32), np.asarray(x))


# ------------------------------------------- the distance product alone
def _distance_case(d, k, invalid, seed=11, rows=1280):
    """(block (d, rows), centers (k, d), valid (k,) or None), all about the
    rows' mean: columns of 1 to 1e5 as the cell's table has them, centers
    that are rows of the block a little moved."""
    rng = np.random.default_rng(seed)
    scale = np.exp(rng.uniform(0.0, 11.5, (d, 1)))
    x = rng.normal(0, 1, (d, rows)) * scale
    x -= x.mean(axis=1, keepdims=True)
    c = x[:, rng.choice(rows, k, replace=False)].T \
        + rng.normal(0, 1e-3, (k, d)) * scale.T
    valid = None
    if invalid:
        valid = np.ones(k, bool)
        valid[rng.choice(k, invalid, replace=False)] = False
    return jnp.asarray(x, jnp.float32), jnp.asarray(c, jnp.float32), valid


def _float64_distances(x, c):
    x, c = np.asarray(x, np.float64), np.asarray(c, np.float64)
    return (c ** 2).sum(axis=1)[:, None] - 2.0 * c @ x \
        + (x ** 2).sum(axis=0)[None, :]


def _expanded(xb, centers, valid, product):
    """`_nearest`'s expansion around another `product` (standing for
    -2 c·x)."""
    cn = jnp.sum(centers * centers, axis=1)
    if valid is not None:
        cn = jnp.where(valid, cn, jnp.inf)
    score = cn[:, None] + product
    return clustering._first_min(score), jnp.maximum(
        jnp.min(score, axis=0) + jnp.sum(xb * xb, axis=0), 0.0)


def _first_parts_nearest(xb, centers, valid=None):
    """The control: the expansion with ONE bfloat16 product, the first
    parts' (what `Precision.DEFAULT` makes of a float32 product)."""
    lone = lambda a: jax.lax.reduce_precision(a, 8, 7).astype(jnp.bfloat16)
    return _expanded(xb, centers, valid, jax.lax.dot_general(
        lone(-2.0 * centers), lone(xb), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32))


def _highest_nearest(xb, centers, valid=None):
    """What `_nearest` was: the library's float32 product."""
    return _expanded(xb, centers, valid, -2.0 * jnp.dot(
        centers, xb, precision=jax.lax.Precision.HIGHEST))


@pytest.mark.parametrize("what", [
    "float32-grain", "first-parts-control", "ties", "invalid",
    "rounded-operand"])
@pytest.mark.parametrize("d,k,invalid", [
    (42, 1000, 0), (42, 37, 9), (13, 1000, 0), (13, 37, 9)],
    ids=["d42-k1000", "d42-k37-invalid", "d13-k1000", "d13-k37-invalid"])
def test_the_stacked_product_is_a_float32_product(d, k, invalid, what,
                                                  monkeypatch):
    """`_nearest` alone: its six bfloat16 products along one contraction of
    6d against float64, beside the library's `highest` product (the bound
    it met) and a product of the first parts alone (the control)."""
    x, c, valid = _distance_case(d, k, invalid)
    want = _float64_distances(x, c)
    if valid is not None:
        want[~valid] = np.inf
    # what float32 cannot tell apart: its grain of the squares the
    # distance is a difference of
    grain = (np.asarray(x, np.float64) ** 2).sum(axis=0) \
        + (np.asarray(c, np.float64) ** 2).sum(axis=1)[want.argmin(axis=0)]
    cols = np.arange(want.shape[1])

    def worst(nearest):
        idx, d2 = jax.jit(lambda a, b: nearest(a, b, valid))(x, c)
        idx = np.asarray(idx)
        return (np.abs(np.asarray(d2, np.float64) - want.min(axis=0))
                / grain).max(), \
            ((want[idx, cols] - want.min(axis=0)) / grain).max()

    if what == "float32-grain":
        gap, excess = worst(clustering._nearest)
        held_gap, held_excess = worst(_highest_nearest)
        assert held_gap < 2e-6 and held_excess < 2e-6   # the bound it met
        assert gap < 2e-6 and excess < 2e-6
    elif what == "first-parts-control":
        gap, excess = worst(_first_parts_nearest)
        assert gap > 1e-4, "one part of three is no float32 product"
    elif what == "ties":
        # centers that coincide to the bit score the same to the bit: the
        # lower index takes their rows, and under the other tie rule the
        # higher takes exactly those rows and no other
        once = np.asarray(jax.jit(
            lambda a, b: clustering._nearest(a, b, valid))(x, c)[0])
        doubled = np.unique(once)[:5]
        twice = jnp.concatenate([c, c[doubled]])
        also = None if valid is None else np.concatenate(
            [valid, np.ones(5, bool)])
        first = np.asarray(jax.jit(
            lambda a, b: clustering._nearest(a, b, also))(x, twice)[0])
        np.testing.assert_array_equal(first, once)
        monkeypatch.setattr(
            clustering, "_first_min", lambda score: (
                score.shape[0] - 1 - jnp.argmin(score[::-1], axis=0)
            ).astype(jnp.int32))
        last = np.asarray(jax.jit(
            lambda a, b: clustering._nearest(a, b, also))(x, twice)[0])
        theirs = np.isin(once, doubled)
        assert theirs.any()
        np.testing.assert_array_equal(last[~theirs], once[~theirs])
        np.testing.assert_array_equal(
            last[theirs], k + np.searchsorted(doubled, once[theirs]))
    elif what == "invalid":
        dead = np.ones(k, bool)
        dead[::3] = False
        idx, _ = jax.jit(lambda a, b: clustering._nearest(a, b, dead))(x, c)
        assert dead[np.asarray(idx)].all()
        masked = np.where(dead[:, None], _float64_distances(x, c), np.inf)
        picked = masked[np.asarray(idx), cols]
        assert ((picked - masked.min(axis=0)) / grain).max() < 2e-6
    else:
        # the benchmark's control reaches every operand: a rounded
        # operand's second and third parts are zero, and what is left is
        # the one-part product, to the bit where it is summed over the
        # same contraction (zeros in the five other groups) and to a sum's
        # order where it is the plain product over d
        rounded = lambda a: jax.lax.reduce_precision(a, 8, 7)
        for part in clustering._three_bfloat16(rounded(x))[1:]:
            assert not np.asarray(part, np.float32).any()
        monkeypatch.setattr(clustering, "_product_operand", rounded)
        got = jax.jit(lambda a, b: clustering._nearest(a, b, valid))(x, c)

        def one_part(a):
            return [rounded(a).astype(jnp.bfloat16),
                    jnp.zeros(a.shape, jnp.bfloat16),
                    jnp.zeros(a.shape, jnp.bfloat16)]
        monkeypatch.setattr(clustering, "_three_bfloat16", one_part)
        lone = jax.jit(lambda a, b: clustering._nearest(a, b, valid))(x, c)
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(lone[0]))
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(lone[1]))
        plain = jax.jit(lambda a, b: _first_parts_nearest(
            rounded(a), rounded(b), valid))(x, c)
        assert (np.abs(np.asarray(got[1], np.float64)
                       - np.asarray(plain[1], np.float64))
                / grain).max() < 2e-6
        gap, _ = worst(lambda a, b, v: _first_parts_nearest(
            rounded(a), rounded(b), v))
        assert gap > 1e-4


def test_flagged_finds_the_places_in_order():
    rng = np.random.default_rng(2)
    for n, slots in ((5000, 64), (1024, 8), (300, 400), (7, 3)):
        flags = rng.random(n) < (0.01 if n > 500 else 0.3)
        flags[-1] = True
        at, filled = jax.jit(clustering._flagged, static_argnums=1)(
            jnp.asarray(flags), slots)
        want = np.flatnonzero(flags)[:slots]
        assert int(filled) == len(want)
        np.testing.assert_array_equal(np.asarray(at)[:len(want)], want)


# ------------------------------------------------------------ a whole fit
def test_the_eight_device_mesh_gives_the_one_device_centers(
        spark, blocks, counters):
    """The seeding's draws are a function of the GLOBAL row number, so the
    candidates, the centers and the steps are the mesh's whatever its
    width (sums in another order: to float32's grain)."""
    X = _blobs(4000, 5, 8, seed=5)
    fdf = _assembled(spark, X)
    blocks(300)
    GLOBAL_CONF.set("sml.dispatch.mode", "device")
    try:
        assert meshlib.data_width(meshlib.get_mesh()) == 8
        before = counters()
        many = KMeans(k=8, seed=3, maxIter=5).fit(fdf)
        assert counters(before)["kmeans.blocks"] == 2   # of 512 rows a shard
        clustering.forget_programs()
        with meshlib.use_mesh(meshlib.build_mesh(1)):
            before = counters()
            one = KMeans(k=8, seed=3, maxIter=5).fit(fdf)
            assert counters(before)["kmeans.blocks"] == 14
    finally:
        GLOBAL_CONF.set("sml.dispatch.mode", "auto")
        clustering.forget_programs()
    np.testing.assert_allclose(_centers(many), _centers(one), rtol=1e-5,
                               atol=1e-4)
    assert many.summary.clusterSizes == one.summary.clusterSizes


def test_max_iter_0_is_the_seeding_and_1_is_one_step_from_it(spark, blocks):
    X = _blobs(1500, 4, 5, seed=6, spread=1.5)
    fdf = _assembled(spark, X)
    blocks(128)
    c0 = _centers(KMeans(k=5, seed=9, maxIter=0).fit(fdf))
    again = _centers(KMeans(k=5, seed=9, maxIter=0).fit(fdf))
    np.testing.assert_array_equal(c0, again)
    m1 = KMeans(k=5, seed=9, maxIter=1).fit(fdf)
    step = reference.lloyd_step(X, c0)
    np.testing.assert_allclose(_centers(m1), step["centers"], rtol=1e-5,
                               atol=1e-5)
    assert m1.summary.numIter == 1
    # the summary is AT the returned centers
    after = reference.lloyd_step(X, _centers(m1))
    assert m1.summary.trainingCost == pytest.approx(after["cost"], rel=1e-5)
    assert m1.summary.clusterSizes == after["counts"].tolist()


def test_tol_ends_the_loop_and_the_count_says_when(spark, counters):
    X = _blobs(600, 3, 3, seed=7)
    fdf = _assembled(spark, X)
    before = counters()
    model = KMeans(k=3, seed=1, maxIter=50).fit(fdf)
    got = counters(before)
    assert 1 <= model.summary.numIter < 50
    assert got["kmeans.iterations"] == model.summary.numIter
    assert got["kmeans.converged"] == 1
    assert got["kmeans.rows"] == 600 * model.summary.numIter
    # the float64 loop from the same seeding ends at the same step
    c0 = _centers(KMeans(k=3, seed=1, maxIter=0).fit(fdf))
    _, steps = reference.lloyd(X, c0, 50, 1e-4)
    assert steps == model.summary.numIter
    # a tol no step meets runs them all, and says so
    before = counters()
    loose = KMeans(k=3, seed=1, maxIter=4, tol=0.0).fit(
        _assembled(spark, _blobs(600, 3, 3, seed=7, spread=2.5)))
    got = counters(before)
    assert loose.summary.numIter == got["kmeans.iterations"]
    assert got.get("kmeans.converged", 0) == (loose.summary.numIter < 4)


def test_the_seeding_is_a_function_of_the_seed(spark):
    X = _blobs(2000, 4, 10, seed=11, spread=2.0)
    fdf = _assembled(spark, X)
    a, b, c = (_centers(KMeans(k=10, seed=s, maxIter=0).fit(fdf))
               for s in (5, 5, 6))
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)


@pytest.mark.parametrize("place", [0, 777, 1999])
def test_every_round_reads_every_row(spark, blocks, counters, place):
    """A row far from all others is drawn with probability 1 wherever it
    lies (2k·d²/φ >= 1), the last row of a ragged last block included: a
    round that skipped a block would miss it."""
    X = _blobs(2000, 3, 4, seed=12)
    X[place] = [4e3, -4e3, 4e3]
    blocks(192)
    before = counters()
    model = KMeans(k=4, seed=2, maxIter=0).fit(_assembled(spark, X))
    got = counters(before)
    gaps = np.abs(_centers(model) - X[place]).sum(axis=1)
    assert gaps.min() == 0.0
    assert got["kmeans.init.rounds"] == 2
    assert 2 <= got["kmeans.init.candidates"] <= 1 + 2 * 128
    assert got["kmeans.blocks"] >= 2          # of a shard's rows
    assert "kmeans.rows" not in got          # no Lloyd step was asked for


def test_random_mode_and_a_bad_name(spark, counters):
    X = _blobs(500, 3, 4, seed=13)
    fdf = _assembled(spark, X)
    before = counters()
    c0 = _centers(KMeans(k=6, seed=4, maxIter=0, initMode="random").fit(fdf))
    got = counters(before)
    # k DISTINCT rows of the table
    rows = {tuple(np.float32(r)) for r in X}
    assert all(tuple(np.float32(c)) in rows for c in c0)
    assert len({tuple(c) for c in c0}) == 6
    assert got.get("kmeans.init.rounds", 0) == 0
    with pytest.raises(ValueError, match="initMode"):
        KMeans(k=3, initMode="k-means++").fit(fdf)
    with pytest.raises(ValueError, match="euclidean"):
        KMeans(k=3, distanceMeasure="cosine").fit(fdf)


def test_the_expansion_about_the_means_keeps_a_large_column_exact():
    """A column of magnitude 1e6 beside rates: about the column means the
    float32 distances assign every row as float64 does; about zero the
    large column's square swallows the rates."""
    rng = np.random.default_rng(14)
    n = 4000
    X = np.column_stack([1e6 + rng.integers(0, 3, n),
                         np.round(rng.random(n), 2),
                         np.round(rng.random(n), 2)])
    centers = np.array([[1e6 + 1, 0.25, 0.25], [1e6 + 1, 0.75, 0.25],
                        [1e6 + 1, 0.25, 0.75], [1e6 + 1, 0.75, 0.75]])
    want, best, second, _ = reference.two_nearest(X, centers,
                                                   X.mean(axis=0))
    clear = second - best > 1e-3
    Xt = np.ascontiguousarray(X.T, np.float32)
    about_means = clustering._assign(
        Xt, centers, X.mean(axis=0).astype(np.float32), True)
    about_zero = clustering._assign(Xt, centers, np.zeros(3, np.float32),
                                    True)
    assert (about_means[clear] == want[clear]).all()
    assert (about_zero[clear] != want[clear]).mean() > 0.2


def test_a_pipeline_takes_the_plan_and_gives_the_generic_centers(
        spark, counters):
    X = _blobs(70000, 6, 7, seed=15)
    df, cols = _frame(spark, X)
    va = VectorAssembler(inputCols=cols, outputCol="features")
    before = counters()
    planned = Pipeline(stages=[va, KMeans(k=7, seed=3, maxIter=3)]).fit(df)
    got = counters(before)
    assert got["featurize.plan.fits"] == 1
    assert "featurize.plan.declined" not in got
    assert "featurize.collect.concats" not in got    # no toPandas
    assert got["kmeans.fits"] == 1
    generic = KMeans(k=7, seed=3, maxIter=3).fit(va.transform(df))
    np.testing.assert_array_equal(_centers(planned.stages[-1]),
                                  _centers(generic))
    assert planned.stages[-1].summary.trainingCost \
        == generic.summary.trainingCost


def test_transform_and_cost_at_k_1000_stay_small_on_the_host(spark):
    """100,000 rows against 1000 centers: the host never holds rows x k
    (the broadcast this replaced asked for 33 GB)."""
    rng = np.random.default_rng(16)
    X = rng.normal(0, 1, (100_000, 8)).astype(np.float32).astype(np.float64)
    centers = X[rng.choice(len(X), 1000, replace=False)]
    model = KMeansModel(centers=centers.astype(np.float32),
                        origin=X.mean(axis=0).astype(np.float32))
    fdf = _assembled(spark, X)
    fdf.cache()
    fdf.count()
    tracemalloc.start()
    served = model.transform(fdf).select("prediction").toPandas()
    cost = model.computeCost(fdf)
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert peak < 256 << 20
    want, d2 = reference.nearest(X, centers)
    agree = served["prediction"].to_numpy() == want
    assert agree.mean() > 0.999
    assert cost == pytest.approx(d2.sum(), rel=1e-4)


def test_persistence_keeps_the_origin(spark, tmp_path):
    X = _blobs(400, 3, 3, seed=17) + 1e4
    model = KMeans(k=3, seed=1).fit(_assembled(spark, X))
    path = str(tmp_path / "km")
    model.write().overwrite().save(path)
    loaded = KMeansModel.load(path)
    np.testing.assert_array_equal(_centers(loaded), _centers(model))
    np.testing.assert_array_equal(loaded._expansion_origin(),
                                  model._expansion_origin())
