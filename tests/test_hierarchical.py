"""Hierarchical DCN-aware collectives (ISSUE 20): the two-level
histogram allreduce, the per-host data plane, and elastic
preemption-tolerant fits, proven on the simulated 8-device mesh
partitioned into virtual host groups (`parallel.mesh.host_mesh`).

Contracts:

- HOP PARITY: `psum_hierarchical` (intra-group reduce-scatter over
  "ici", inter-group allreduce over "dcn", allgather back) equals the
  flat psum BIT-EXACTLY on integer-valued payloads at every group shape
  {1x8, 2x4, 4x2}, and its per-hop byte counters obey
  dcn = ici / ici_size exactly — the cross-host hop carries only the
  inter-group fraction of the flat allreduce payload (the acceptance
  bound).
- HOST-SHAPE INVARIANCE: DT/RF/xgboost fits and CV avgMetrics on host
  meshes match the 1-host-group fit at every tested shape (sampling is
  layout-invariant; remaining drift is float reduction order, the same
  tolerance contract as tests/test_multichip.py) — and the 1-host-group
  mesh reproduces the flat 8-device fit EXACTLY.
- PER-HOST DATA PLANE: `ChunkSource.host_view` partitions the chunk
  stream into contiguous per-group row ranges that reassemble the
  parent bit-exactly, chunk-layout-invariantly.
- ELASTIC FITS: killing a host group mid-fit (chaos hook at a
  checkpoint boundary) resumes from the round-level checkpoint on the
  surviving groups and finishes the same model as the uninterrupted
  fit, counting `elastic.resume`/`elastic.repartition`.
- Straggler attribution grows HOST lanes (`skew.host.*`).
"""

import os

import numpy as np
import pandas as pd
import pytest

from sml_tpu.conf import GLOBAL_CONF

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


@pytest.fixture()
def xy():
    rng = np.random.default_rng(11)
    n = 4096
    X = rng.normal(size=(n, 6)).astype(np.float32)
    y = (X[:, 0] * 3 - X[:, 1] ** 2 + 0.5 * X[:, 2]
         + rng.normal(0, 0.2, n)).astype(np.float32)
    return X, y


@pytest.fixture()
def recording():
    prev = GLOBAL_CONF.get("sml.obs.enabled")
    GLOBAL_CONF.set("sml.obs.enabled", True)
    from sml_tpu import obs
    obs.reset()
    yield obs
    GLOBAL_CONF.set("sml.obs.enabled", bool(prev))


def _host(h):
    from sml_tpu.parallel import mesh as meshlib
    return meshlib.use_mesh(meshlib.host_mesh(h))


def _flat(w):
    from sml_tpu.parallel import mesh as meshlib
    return meshlib.use_mesh(meshlib.build_mesh(w))


def _frame(spark, X, y, label="label"):
    from sml_tpu.ml.feature import VectorAssembler
    pdf = pd.DataFrame({f"f{i}": X[:, i] for i in range(X.shape[1])})
    pdf[label] = y
    fdf = VectorAssembler(inputCols=[f"f{i}" for i in range(X.shape[1])],
                          outputCol="features") \
        .transform(spark.createDataFrame(pdf))
    fdf.cache()
    return fdf


# ------------------------------------------------------- host-mesh topology
def test_host_mesh_shapes_placement_and_partition():
    """`host_mesh(h)` declares the (dcn, ici) axes host-major, places
    every global row on exactly the device the flat mesh would, and
    `host_partition` splits row ranges contiguously with the remainder
    leading — the layout contract the whole data plane rides."""
    import jax

    from sml_tpu.parallel import mesh as meshlib

    assert len(jax.devices()) >= 8
    for h, per in ((1, 8), (2, 4), (4, 2), (8, 1)):
        m = meshlib.host_mesh(h)
        assert meshlib.is_hierarchical(m)
        assert dict(m.shape) == {"dcn": h, "ici": per}
        assert meshlib.data_width(m) == 8
        assert meshlib.row_axes(m) == ("dcn", "ici")
        # device d of the flat mesh sits at (d // per, d % per)
        flat = list(meshlib.build_mesh(8).devices.flat)
        grid = m.devices
        for d in range(8):
            assert grid[d // per][d % per] is flat[d]
        groups = meshlib.host_group_of(m)
        assert sorted(set(groups.values())) == list(range(h))
        # row-sharded placement identical to the flat mesh, shard by shard
        X = np.arange(64 * 3, dtype=np.float32).reshape(64, 3)
        with meshlib.use_mesh(m):
            arr, n_true = meshlib.shard_rows(X)
        with meshlib.use_mesh(meshlib.build_mesh(8)):
            ref, _ = meshlib.shard_rows(X)
        hb = {d.id: np.asarray(b)
              for d, b in meshlib.addressable_row_blocks(arr)}
        fb = {d.id: np.asarray(b)
              for d, b in meshlib.addressable_row_blocks(ref)}
        assert hb.keys() == fb.keys()
        for did in hb:
            np.testing.assert_array_equal(hb[did], fb[did])
    with pytest.raises(ValueError):
        meshlib.host_mesh(3)  # 3 groups do not divide 8 devices
    assert meshlib.host_partition(100, 3) == [(0, 34), (34, 67), (67, 100)]
    assert meshlib.host_partition(8, 8) == [(i, i + 1) for i in range(8)]


def test_host_groups_conf_knob_resolves_shape():
    from sml_tpu.parallel import mesh as meshlib
    GLOBAL_CONF.set("sml.mesh.hostGroups", 4)
    try:
        assert dict(meshlib.host_mesh().shape) == {"dcn": 4, "ici": 2}
    finally:
        GLOBAL_CONF.unset("sml.mesh.hostGroups")


# ------------------------------------------------ two-level psum bit parity
@pytest.mark.parametrize("h", [1, 2, 4])
def test_psum_hierarchical_bit_parity_and_hop_bytes(recording, h):
    """The two-level allreduce equals the flat psum bit-exactly on
    integer-valued payloads, and its per-hop byte statics obey
    dcn == ici / ici_size (the cross-host hop carries only the
    inter-group fraction) with the allgather return hop matching the
    dcn chunk — at every group shape."""
    import jax
    from jax.sharding import PartitionSpec as P

    from sml_tpu.parallel import collectives as coll
    from sml_tpu.parallel import mesh as meshlib

    obs = recording
    rng = np.random.default_rng(3)
    X = rng.integers(0, 100, size=(64, 7)).astype(np.float32)
    expect = X.reshape(8, 8, 7).sum(axis=0)  # exact: integer-valued f32

    mesh = meshlib.host_mesh(h)
    per = 8 // h
    spec = P(meshlib.row_spec_entry(mesh))

    def run(fn):
        f = meshlib.shard_map_compat(fn, mesh=mesh, in_specs=(spec,),
                                     out_specs=P())
        return np.asarray(jax.jit(f)(X))

    with meshlib.use_mesh(mesh):
        obs.reset()
        hier = run(lambda b: coll.psum_hierarchical(b, ici_size=per))
        hop = obs.RECORDER.counters()
        flat = run(lambda b: coll.psum(b, (meshlib.DCN_AXIS,
                                           meshlib.ICI_AXIS)))
    np.testing.assert_array_equal(hier, flat)
    np.testing.assert_array_equal(hier, expect)
    block_bytes = 8 * 7 * 4  # one device's (8, 7) f32 shard
    if per > 1:
        assert hop.get("collective.psum.ici") == 1
        assert hop.get("collective.psum.dcn") == 1
        assert hop.get("collective.psum_bytes.ici") == block_bytes
        assert hop.get("collective.psum_bytes.dcn") == block_bytes / per
        assert hop.get("collective.all_gather_bytes.ici") \
            == block_bytes / per
    else:
        # ici_size=1 degenerates to the flat psum over the dcn hop
        assert hop.get("collective.psum_bytes.dcn") == block_bytes
        assert "collective.psum_bytes.ici" not in hop


def test_psum_hierarchical_pads_non_divisible_payload(recording):
    """A payload whose flat size does not divide ici_size is zero-padded
    for the reduce-scatter and unpadded after the allgather — exact for
    sums, any shape."""
    import jax
    from jax.sharding import PartitionSpec as P

    from sml_tpu.parallel import collectives as coll
    from sml_tpu.parallel import mesh as meshlib

    mesh = meshlib.host_mesh(2)  # ici_size 4; 3*5=15 pads to 16
    rng = np.random.default_rng(5)
    X = rng.integers(0, 50, size=(24, 3, 5)).astype(np.float32)
    spec = P(meshlib.row_spec_entry(mesh))
    with meshlib.use_mesh(mesh):
        f = meshlib.shard_map_compat(
            lambda b: coll.psum_hierarchical(b, ici_size=4),
            mesh=mesh, in_specs=(spec,), out_specs=P())
        out = np.asarray(jax.jit(f)(X))
    np.testing.assert_array_equal(out, X.reshape(8, 3, 3, 5).sum(axis=0))


# -------------------------------------------------- fit parity across shapes
@pytest.mark.parametrize("kind", ["dt", "rf", "xgb"])
def test_fit_parity_host_shapes_vs_1host_and_flat(spark, xy, kind,
                                                  same_boosted_fit):
    """The same estimator fit at every host-group shape produces the
    same model as the 1-host-group fit (float reduction-order
    tolerance, the test_multichip contract), and the 1-host-group mesh
    reproduces the flat 8-device fit EXACTLY — the hierarchical path is
    a drop-in for the flat allreduce, not a different estimator.

    Boosting is held to what "the same model" can mean for rounds that
    build on each other (`conftest.same_boosted_fit`, its three limits
    and its bfloat16 control, as test_multichip's 8 shards against 1): a
    host-group shape is another compilation that sums a histogram in
    another order, and a split whose two best candidates tie to the last
    ulp may fall either way. Two groups fit the one group's trees to the
    bit; four groups take the neighbouring bin at one node of round 3,
    the very tie the 8-against-1 layouts split on (median 1.6e-5, 1.9 %
    of the rows beyond 1e-3, rmse 8.2e-5 of itself apart)."""
    from sml_tpu.ml.evaluation import RegressionEvaluator

    X, y = xy

    def factory():
        from sml_tpu.ml.regression import (DecisionTreeRegressor,
                                           RandomForestRegressor)
        from sml_tpu.xgboost import XgboostRegressor
        if kind == "dt":
            return DecisionTreeRegressor(labelCol="label", maxDepth=5,
                                         maxBins=16)
        if kind == "rf":
            return RandomForestRegressor(labelCol="label", maxDepth=4,
                                         numTrees=8, maxBins=16,
                                         subsamplingRate=0.9, seed=7)
        return XgboostRegressor(n_estimators=8, max_depth=4, max_bins=16,
                                learning_rate=0.3, subsample=0.8,
                                random_state=5)

    fdf = _frame(spark, X, y)

    def fit_predict(ctx):
        with ctx:
            model = factory().fit(fdf)
            pred = model.transform(fdf).toPandas()["prediction"].to_numpy()
            rmse = RegressionEvaluator(labelCol="label").evaluate(
                model.transform(fdf))
        return pred, rmse

    p_flat, rmse_flat = fit_predict(_flat(8))
    p1, rmse1 = fit_predict(_host(1))
    # 1 host group x 8 devices: same reduction topology as flat — exact
    np.testing.assert_array_equal(p1, p_flat)
    assert rmse1 == rmse_flat
    for h in (2, 4):
        ph, rmseh = fit_predict(_host(h))
        if kind == "xgb":
            assert same_boosted_fit(ph, p1, rmseh, rmse1) == []
            continue
        np.testing.assert_allclose(ph, p1, rtol=1e-4, atol=1e-4)
        assert abs(rmseh - rmse1) < 1e-4 * max(abs(rmse1), 1.0)
    if kind == "xgb":
        # the control: one layout's own predictions rounded to bfloat16 (a
        # descent in the next lower precision) are NOT the same fit
        import ml_dtypes
        low = p1.astype(ml_dtypes.bfloat16).astype(np.float64)
        assert len(same_boosted_fit(low, p1, rmse1, rmse1)) == 2


def _same_cv_grid(m_a, m_b) -> list:
    """What fails of "two layouts ran the same CV grid", from the two
    avgMetrics; empty when nothing does. A grid point's metric is the
    mean over the folds of a forest's rmse, so it moves in one of two
    ways. Read on this grid (maxDepth [2, 4] x numTrees [3, 6], 3 folds of
    2,730 rows, XLA:CPU), relative to the metric: the two depth-2 points
    agree to 0 and 6e-10 at every host shape (6e-8 for 8 shards against
    1), and the two depth-4 points differ by 2.3e-4 and 3.1e-4 (1.4e-4,
    2.4e-4 for one host group against flat): a node of a deep tree on a
    fold has few rows, two of its splits tie to the last ulp, and the one
    tree that takes the other moves its fold's rmse. The control, the
    flat fit with its histogram operands in bfloat16 (the next lower
    precision): EVERY point moves, 1.7e-5, 9.4e-6, 3.1e-4, 5.5e-4.

    - half the grid's points agree to 1e-6 (the sound readings' largest
      6e-8, the control's smallest 9.4e-6): where no tie falls, only the
      order of float32 sums differs;
    - no point differs by more than 1e-3 (sound 3.1e-4; another forest
      seed moves every point by 0.34-0.52): a tie moves a tree of a
      fold, not the grid;
    - the same point wins."""
    m_a, m_b = np.asarray(m_a, np.float64), np.asarray(m_b, np.float64)
    gap = np.sort(np.abs(m_a - m_b) / np.abs(m_b))
    failed = []
    if not gap[len(gap) // 2 - 1] <= 1e-6:
        failed.append(f"half the points beyond 1e-6: {gap.tolist()}")
    if not gap[-1] <= 1e-3:
        failed.append(f"a point beyond 1e-3: {gap.tolist()}")
    if int(np.argmin(m_a)) != int(np.argmin(m_b)):
        failed.append(f"another point wins: {m_a.tolist()}, {m_b.tolist()}")
    return failed


def test_cv_avgmetrics_parity_on_host_mesh(spark, xy, monkeypatch):
    """Grid-fused CV (TrialDyn fused trials) over a host-partitioned
    mesh: fused elements ride the replicated-element branch (the trial
    axis stays 1 on a 2-axis row mesh) and avgMetrics are the flat
    8-device run's as far as two compilations of a forest can agree
    (`_same_cv_grid`: its limits, and the bfloat16 control that fails
    the first of them)."""
    from sml_tpu.ml import tree_impl
    from sml_tpu.ml.evaluation import RegressionEvaluator
    from sml_tpu.ml.regression import RandomForestRegressor
    from sml_tpu.ml.tuning import CrossValidator, ParamGridBuilder

    X, y = xy
    fdf = _frame(spark, X, y)
    rf = RandomForestRegressor(labelCol="label", maxBins=16, seed=7)
    grid = (ParamGridBuilder()
            .addGrid(rf.getParam("maxDepth"), [2, 4])
            .addGrid(rf.getParam("numTrees"), [3, 6]).build())
    cv = CrossValidator(estimator=rf, estimatorParamMaps=grid,
                        evaluator=RegressionEvaluator(labelCol="label"),
                        numFolds=3, parallelism=1, seed=13)
    GLOBAL_CONF.set("sml.cv.batchFolds", True)
    try:
        with _host(2):
            assert tree_impl._trial_axis_width(8, 4096) == 1
            m_host = cv.fit(fdf).avgMetrics
        with _flat(8):
            m_flat = cv.fit(fdf).avgMetrics
        # the control, in program caches of its own: they are not keyed by
        # the operand's type, which a platform fixes for a process
        import jax.numpy as jnp
        monkeypatch.setattr(tree_impl, "_hist_dtype", lambda: jnp.bfloat16)
        for cache in ("_trials_cache", "_folds_cache", "_ensemble_cache"):
            monkeypatch.setattr(tree_impl, cache, {})
        with _flat(8):
            m_low = cv.fit(fdf).avgMetrics
    finally:
        GLOBAL_CONF.unset("sml.cv.batchFolds")
    assert _same_cv_grid(m_host, m_flat) == []
    low = _same_cv_grid(m_low, m_flat)
    assert len(low) == 1 and low[0].startswith("half the points")


# --------------------------------------------- per-hop byte economics
def test_dcn_bytes_bounded_by_inter_group_fraction(recording, xy):
    """ISSUE 20 acceptance: the DCN-hop psum bytes of a hierarchical
    fit are <= the inter-group fraction (1/ici_size) of the flat
    allreduce's bytes, exactly dcn == ici / ici_size per trace, with
    the allgather return hop the same size as the dcn chunk."""
    from sml_tpu.ml._tree_models import _fit_ensemble

    X, y = xy
    obs = recording

    def fit():
        return _fit_ensemble(X, y, categorical={}, max_depth=4,
                             max_bins=16, min_instances=1,
                             min_info_gain=0.0, n_trees=2, feature_k=None,
                             bootstrap=False, subsample=1.0, seed=3,
                             loss="squared")

    obs.reset()
    with _flat(8):
        fit()
    flat_bytes = obs.RECORDER.counters().get("collective.psum_bytes", 0.0)
    assert flat_bytes > 0
    for h, per in ((2, 4), (4, 2)):
        obs.reset()
        with _host(h):
            fit()
        c = obs.RECORDER.counters()
        ici_b = c.get("collective.psum_bytes.ici", 0.0)
        dcn_b = c.get("collective.psum_bytes.dcn", 0.0)
        ag_b = c.get("collective.all_gather_bytes.ici", 0.0)
        assert ici_b > 0 and dcn_b > 0
        assert dcn_b == ici_b / per  # exact: payload pads to ici_size
        assert ag_b == dcn_b
        # the acceptance bound vs the FLAT allreduce payload (1% slack
        # covers the flat path's extra scalar psums + padding)
        assert dcn_b <= flat_bytes / per * 1.01 + 1024


def test_hist_subtraction_halves_per_hop_payload(xy, recording):
    """The histogram-subtraction trick halves the below-root payload on
    BOTH hops of the hierarchical allreduce — the per-hop counters see
    the same saving the flat `collective.psum_bytes` counter does."""
    from sml_tpu.ml._tree_models import _fit_ensemble

    X, y = xy
    obs = recording
    volumes = {}
    try:
        for sub in (True, False):
            GLOBAL_CONF.set("sml.tree.histSubtraction", sub)
            obs.reset()
            # static params distinct from every other fit in this file:
            # per-hop counters are TRACE-time statics, so a program-cache
            # hit would record nothing
            with _host(2):
                _fit_ensemble(X, y, categorical={}, max_depth=5,
                              max_bins=24, min_instances=1,
                              min_info_gain=0.0, n_trees=3, feature_k=None,
                              bootstrap=False, subsample=1.0, seed=3,
                              loss="squared")
            c = obs.RECORDER.counters()
            volumes[sub] = (c.get("collective.psum_bytes.ici", 0.0),
                            c.get("collective.psum_bytes.dcn", 0.0))
    finally:
        GLOBAL_CONF.unset("sml.tree.histSubtraction")
    for hop in (0, 1):
        assert 0 < volumes[True][hop] < volumes[False][hop]


def test_hierarchical_knob_off_uses_flat_allreduce(xy, recording):
    """`sml.tree.hierarchicalAllreduce=false` on a host mesh routes the
    merge through ONE flat psum over both row axes (no per-hop
    counters), and the model still matches — the knob changes the wire
    pattern, never the estimator."""
    from sml_tpu.ml._tree_models import _fit_ensemble

    X, y = xy
    obs = recording

    def fit():
        # static params distinct from every other fit in this file: a
        # program-cache hit would skip the trace and record no counters
        return _fit_ensemble(X, y, categorical={}, max_depth=3,
                             max_bins=20, min_instances=1,
                             min_info_gain=0.0, n_trees=4, feature_k=None,
                             bootstrap=False, subsample=1.0, seed=3,
                             loss="squared")

    with _host(2):
        obs.reset()
        on = fit()
        c_on = obs.RECORDER.counters()
        GLOBAL_CONF.set("sml.tree.hierarchicalAllreduce", "false")
        try:
            obs.reset()
            off = fit()
            c_off = obs.RECORDER.counters()
        finally:
            GLOBAL_CONF.unset("sml.tree.hierarchicalAllreduce")
    assert c_on.get("collective.psum_bytes.ici", 0.0) > 0
    assert c_off.get("collective.psum_bytes.ici", 0.0) == 0
    assert c_off.get("collective.psum_bytes", 0.0) > 0
    pa = on.predict_margin(X[:512])
    pb = off.predict_margin(X[:512])
    np.testing.assert_allclose(pa, pb, rtol=1e-4, atol=1e-4)


# ------------------------------------------------------ per-host data plane
def test_host_view_partitions_and_reassembles_bit_exact():
    """`ChunkSource.host_view` yields each group's contiguous global row
    range: the views concatenate back to the parent bit-exactly,
    whatever the parent's chunk size (chunk-layout invariance), and an
    uncounted source refuses a host view instead of guessing."""
    from sml_tpu.frame._chunks import ArrayChunkSource

    rng = np.random.default_rng(7)
    X = rng.normal(size=(100, 4)).astype(np.float32)
    y = rng.normal(size=100).astype(np.float32)
    for chunk_rows in (7, 33, 100):
        src = ArrayChunkSource(X, y, chunk_rows=chunk_rows)
        views = [src.host_view(g, 3) for g in range(3)]
        assert [(v.start, v.stop) for v in views] \
            == [(0, 34), (34, 67), (67, 100)]
        Xs = np.concatenate([np.concatenate([c[0] for c in v.chunks()])
                             for v in views])
        ys = np.concatenate([np.concatenate([c[1] for c in v.chunks()])
                             for v in views])
        np.testing.assert_array_equal(Xs, X)
        np.testing.assert_array_equal(ys, y)
        # re-iterable (the two-pass ingest contract) + fingerprinted
        again = np.concatenate([c[0] for c in views[1].chunks()])
        np.testing.assert_array_equal(again, X[34:67])
        fp = views[1].fingerprint()
        assert fp[0] == "host" and fp[2:] == (1, 3)
    src = ArrayChunkSource(X, y, chunk_rows=10)
    src.n_rows = None  # an uncounted stream (pre-sketch-pass)
    with pytest.raises(ValueError, match="counted"):
        src.host_view(0, 2)
    with pytest.raises(ValueError):
        ArrayChunkSource(X, y, chunk_rows=10).host_view(5, 3)


# ------------------------------------------------------------- elastic fits
def test_elastic_fit_resumes_after_host_kill(tmp_path, recording):
    """ISSUE 20 acceptance: a host group killed mid-fit (chaos hook at
    a checkpoint boundary) resumes via the round-level checkpoint on
    the surviving groups and finishes the same final model as the
    uninterrupted fit, with `elastic.resume`/`elastic.repartition`
    counted and the checkpoint dir cleared on success."""
    from sml_tpu.ct import HostPreempted, elastic_fit
    from sml_tpu.frame._chunks import ArrayChunkSource

    obs = recording
    rng = np.random.default_rng(11)
    n = 960  # bucket_rows(960, 8) == bucket_rows(960, 6) == 960:
    #          the padded shape survives the 4x2 -> 3x2 mesh resize
    X = rng.normal(size=(n, 6)).astype(np.float32)
    y = (X @ rng.normal(size=6) + 0.1 * rng.normal(size=n)) \
        .astype(np.float32)
    params = dict(n_trees=6, max_depth=3, max_bins=32, seed=7,
                  step_size=0.3, rounds_per_dispatch=2)

    ref = elastic_fit(ArrayChunkSource(X, y, chunk_rows=128),
                      str(tmp_path / "ref"), hosts=4, **params)

    killed = {"fired": False}

    def chaos(t_done):
        if not killed["fired"] and t_done >= 2:
            killed["fired"] = True
            raise HostPreempted(group=1)

    obs.reset()
    spec = elastic_fit(ArrayChunkSource(X, y, chunk_rows=128),
                       str(tmp_path / "el"), hosts=4,
                       on_checkpoint=chaos, **params)
    assert killed["fired"]
    assert len(spec.trees) == len(ref.trees) == 6
    p, pr = spec.predict_margin(X), ref.predict_margin(X)
    # resumed rounds ran on a 3x2 mesh: float reduction-order tolerance
    np.testing.assert_allclose(p, pr, rtol=1e-4, atol=1e-5)
    c = obs.RECORDER.counters()
    assert c.get("elastic.resume") == 1
    assert c.get("elastic.repartition") == 1
    assert not os.path.exists(str(tmp_path / "el"))  # cleared on success


def test_elastic_fit_gate_off_and_budget_exhausted(tmp_path):
    """With `sml.ct.elasticResume` off the preemption propagates; with
    the restart budget exhausted a repeatedly-dying fit stops resuming
    instead of shrinking to nothing."""
    from sml_tpu.ct import HostPreempted, elastic_fit
    from sml_tpu.frame._chunks import ArrayChunkSource

    rng = np.random.default_rng(2)
    X = rng.normal(size=(512, 4)).astype(np.float32)
    y = rng.normal(size=512).astype(np.float32)
    params = dict(n_trees=4, max_depth=2, max_bins=16, seed=3,
                  rounds_per_dispatch=2)

    def always_die(t_done):
        raise HostPreempted(group=0)

    GLOBAL_CONF.set("sml.ct.elasticResume", "false")
    try:
        with pytest.raises(HostPreempted):
            elastic_fit(ArrayChunkSource(X, y, chunk_rows=128),
                        str(tmp_path / "off"), hosts=2,
                        on_checkpoint=always_die, **params)
    finally:
        GLOBAL_CONF.unset("sml.ct.elasticResume")
    # every attempt makes checkpoint progress (the resumed remainder can
    # finish inside one dispatch, past the last chaos boundary), so the
    # budget path is pinned at 0: the gate is ON but no restart is
    # allowed — the first preemption must propagate through the
    # budget branch, not the gate branch
    GLOBAL_CONF.set("sml.ct.elasticMaxRestarts", 0)
    try:
        with pytest.raises(HostPreempted):
            elastic_fit(ArrayChunkSource(X, y, chunk_rows=128),
                        str(tmp_path / "budget"), hosts=4,
                        on_checkpoint=always_die, **params)
    finally:
        GLOBAL_CONF.unset("sml.ct.elasticMaxRestarts")


def test_moved_rows_accounting():
    from sml_tpu.ct._elastic import moved_rows
    # 4 -> 3 groups over 960 rows: group 0 keeps [0,240) of [0,320);
    # overlaps are 240+160+80 = 480 kept, 480 moved
    assert moved_rows(960, 4, 3) == 480
    assert moved_rows(100, 2, 2) == 0
    assert moved_rows(0, 4, 2) == 0


# ------------------------------------------------- multihost init satellites
def test_initialize_multihost_single_process_fast_path(monkeypatch):
    """num_processes absent or 1: returns False WITHOUT touching
    jax.distributed (the fast path a single-host fit rides)."""
    import jax

    from sml_tpu.parallel import collectives

    def boom(**kw):
        raise AssertionError("jax.distributed.initialize must not be "
                             "called on the single-process fast path")

    monkeypatch.setattr(jax.distributed, "initialize", boom)
    assert collectives.initialize_multihost() is False
    assert collectives.initialize_multihost(num_processes=1) is False
    assert collectives.initialize_multihost("127.0.0.1:1",
                                            num_processes=0) is False


def test_initialize_multihost_wraps_failure_typed(monkeypatch):
    """A bring-up failure surfaces as `MultihostInitError` carrying the
    peer config (coordinator / num_processes / process_id), chained to
    the runtime's original exception — and the timeout kwarg is passed
    when the pinned jax supports it."""
    import jax

    from sml_tpu.parallel import collectives

    seen = {}

    def dying(coordinator_address=None, num_processes=None,
              process_id=None, initialization_timeout=None):
        seen.update(coordinator_address=coordinator_address,
                    num_processes=num_processes, process_id=process_id,
                    initialization_timeout=initialization_timeout)
        raise RuntimeError("coordination service unreachable")

    monkeypatch.setattr(jax.distributed, "initialize", dying)
    with pytest.raises(collectives.MultihostInitError) as ei:
        collectives.initialize_multihost("10.0.0.1:8476", num_processes=2,
                                         process_id=1, timeout_s=7)
    err = ei.value
    assert err.coordinator == "10.0.0.1:8476"
    assert err.num_processes == 2 and err.process_id == 1
    assert isinstance(err.__cause__, RuntimeError)
    assert seen["initialization_timeout"] == 7
    assert "10.0.0.1:8476" in str(err)


# --------------------------------------------------- host-level skew lanes
def test_skew_tracker_host_lanes_and_report(recording):
    """`SkewTracker.note(hosts=...)` rolls per-device timings up to host
    groups (a group's compute is its slowest member's), names the
    slowest host in the entry, the note event, and the aggregate
    straggler report, and lands skew.host.compute/.wait spans."""
    obs = recording
    tracker = obs.SKEW
    tracker.reset()
    e = tracker.note("hier_probe", [1.0, 2.0, 1.5, 0.5],
                     devices=[0, 1, 2, 3], hosts=[0, 0, 1, 1])
    assert e["host_ids"] == [0, 1]
    assert e["per_host_compute_s"] == [2.0, 1.5]
    assert e["slowest_host"] == 0
    rep = tracker.straggler_report()
    assert rep["slowest_host"] == 0 and rep["n_hosts"] == 2
    assert rep["per_host"][1]["wait_s"] == pytest.approx(0.5)
    assert rep["host_skew_ratio"] == pytest.approx(2.0 / 1.75, rel=1e-3)
    names = [ev.name for ev in obs.RECORDER.events()]
    assert "skew.host.compute" in names and "skew.host.wait" in names
    # host-free notes still work and the report omits the host block
    tracker.reset()
    tracker.note("flat_probe", [1.0, 1.2])
    assert "slowest_host" not in tracker.straggler_report()
    with pytest.raises(ValueError):
        tracker.note("bad", [1.0, 2.0], hosts=[0])
