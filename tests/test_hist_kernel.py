"""Pallas fused bin-accumulate + split-scan kernel (ISSUE 9).

The contract (docs/KERNELS.md): with `sml.tree.kernel=pallas` on a
non-TPU backend the kernels run in INTERPRET mode with a single row
block, making the traced kernel math op-for-op the XLA path's — fit
outputs must be BIT-IDENTICAL across {histogram subtraction on/off,
uint8/uint16 bin matrices, TrialDyn grid-fused gates, fractional
fit_tree weights}; `sml.tree.kernel=xla` must leave the pre-kernel path
byte-identical (same programs, same dispatch counts); the kernel choice
rides program cache keys AND the prewarm manifest; and the ml06/ml07
GOLDEN.json pins must hold under the pallas path.
"""

import json
import os

import numpy as np
import pytest

from sml_tpu.conf import GLOBAL_CONF
from sml_tpu.utils.profiler import PROFILER

TREE_FIELDS = ("split_feature", "split_bin", "leaf_value", "gain", "cover")


@pytest.fixture()
def kernel_conf():
    """Restore kernel/profiler/subtraction knobs after each test."""
    prev = {k: GLOBAL_CONF.get(k) for k in
            ("sml.tree.kernel", "sml.profiler.enabled",
             "sml.tree.histSubtraction")}
    GLOBAL_CONF.set("sml.profiler.enabled", True)
    yield
    for k, v in prev.items():
        GLOBAL_CONF.set(k, v)


def _toy(n=6000, f=6, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f))
    y = (2 * X[:, 0] - X[:, 1] + (X[:, 2] > 0) * 3
         + rng.normal(0, 0.3, n)).astype(np.float32)
    return X, y


def _fit(es, binned, y, seed=7):
    from sml_tpu.ml import tree_impl
    from sml_tpu.ml._staging import stage_sharded
    from sml_tpu.ml.tree_impl import stage_aligned
    b_dev, mask_dev, _ = stage_sharded(binned)
    y_dev = stage_aligned(y, b_dev.shape[0])
    return tree_impl.fit_ensemble_on_device(b_dev, y_dev, mask_dev, es,
                                            seed=seed)


def _assert_trees_bitwise(ta, tb):
    assert len(ta) == len(tb)
    for a, b in zip(ta, tb):
        for fld in TREE_FIELDS:
            np.testing.assert_array_equal(getattr(a, fld), getattr(b, fld),
                                          err_msg=fld)


def _spec_es(f, max_bins=32, max_depth=4, n_trees=5, boosting=True,
             bootstrap=False, subsample=1.0, feature_k=None):
    from sml_tpu.ml.tree_impl import EnsembleSpec, TreeSpec
    spec = TreeSpec(max_depth=max_depth, n_bins=max_bins, n_features=f,
                    feature_k=feature_k or f, min_instances=1,
                    min_info_gain=0.0, reg_lambda=1.0, gamma=0.0)
    return EnsembleSpec(tree=spec, n_trees=n_trees, loss="squared",
                        boosting=boosting, bootstrap=bootstrap,
                        subsample=subsample, step_size=0.2)


# -------------------------------------------------------------- bit parity
@pytest.mark.parametrize("subtract", [True, False])
def test_fit_parity_bitwise_vs_xla(spark, kernel_conf, subtract):
    """Interpret-mode pallas fits are bit-identical to the XLA path —
    with histogram subtraction both ON (the post-psum parent-minus-left
    glue between the two kernels) and OFF."""
    from sml_tpu.ml import tree_impl
    GLOBAL_CONF.set("sml.tree.histSubtraction", subtract)
    X, y = _toy()
    binned, _ = tree_impl.make_bins(X, y, 32)
    assert binned.dtype == np.uint8
    es = _spec_es(X.shape[1], bootstrap=True, boosting=False,
                  subsample=0.9, n_trees=4)
    out = {}
    for mode in ("xla", "pallas"):
        GLOBAL_CONF.set("sml.tree.kernel", mode)
        out[mode] = _fit(es, binned, y)
    (tx, bx), (tp, bp) = out["xla"], out["pallas"]
    assert bx == bp
    _assert_trees_bitwise(tx, tp)


def test_fit_parity_uint16_bins(spark, kernel_conf):
    """maxBins > 256 widens the bin cache to uint16 — the kernel one-hots
    the compact operand directly, so the wider dtype must hit the same
    bins (and the same bits) as the XLA path's int32 widen."""
    from sml_tpu.ml import tree_impl
    X, y = _toy(n=4000, f=4, seed=2)
    binned, _ = tree_impl.make_bins(X, y, 300)
    assert binned.dtype == np.uint16
    es = _spec_es(X.shape[1], max_bins=300, n_trees=3)
    out = {}
    for mode in ("xla", "pallas"):
        GLOBAL_CONF.set("sml.tree.kernel", mode)
        out[mode] = _fit(es, binned, y)
    assert out["xla"][1] == out["pallas"][1]
    _assert_trees_bitwise(out["xla"][0], out["pallas"][0])


def test_trialdyn_fused_trials_parity(spark, kernel_conf):
    """Grid-fused trials: the TrialDyn traced gates (per-trial depth /
    feature_k / min_instances / min_info_gain) ride into the split-scan
    kernel as operands (min_inst) and mask glue (feature subspace) — the
    full (E, n_trees, 5, n_nodes) pack stack must be bit-identical."""
    import jax

    from sml_tpu.ml import tree_impl
    X, y = _toy(n=4000, f=5, seed=1)
    binned, _ = tree_impl.make_bins(X, y, 32)
    bst, yst, mst = tree_impl.build_fold_stacks([binned] * 3, [y] * 3)
    es = _spec_es(X.shape[1], n_trees=6, boosting=False, bootstrap=True)
    rngs = np.stack([jax.random.key_data(jax.random.PRNGKey(s))
                     for s in (1, 2, 3)])
    dyn_args = (rngs, np.asarray([2, 4, 3]), np.asarray([3, 5, 2]),
                np.asarray([1.0, 2.0, 1.0]), np.asarray([0.0, 0.0, 0.01]),
                np.asarray([True, False, True]),
                np.asarray([0.9, 1.0, 0.7]))
    out = {}
    for mode in ("xla", "pallas"):
        GLOBAL_CONF.set("sml.tree.kernel", mode)
        out[mode] = tree_impl.fit_ensembles_trials(bst, yst, mst, es,
                                                   *dyn_args)
    np.testing.assert_array_equal(np.asarray(out["xla"][0]),
                                  np.asarray(out["pallas"][0]))
    np.testing.assert_array_equal(np.asarray(out["xla"][1]),
                                  np.asarray(out["pallas"][1]))


def test_fractional_weights_fit_tree_parity(spark, kernel_conf):
    """Arbitrary fractional weights through the public fit_tree surface:
    the kernel's (w > 0) gating and grad·w/hess·w/w products must match
    the XLA path bit-for-bit (no integer-weight shortcut hidden in the
    kernel)."""
    from sml_tpu.ml import tree_impl
    from sml_tpu.ml._staging import stage_sharded
    from sml_tpu.ml.tree_impl import TreeSpec, stage_aligned
    rng = np.random.default_rng(5)
    X, y = _toy(n=4000, f=5, seed=5)
    binned, _ = tree_impl.make_bins(X, y, 32)
    w = rng.uniform(0.1, 1.0, len(y)).astype(np.float32)
    w[rng.uniform(size=len(y)) < 0.1] = 0.0  # excluded rows
    spec = TreeSpec(max_depth=4, n_bins=32, n_features=X.shape[1],
                    feature_k=X.shape[1], min_instances=2,
                    min_info_gain=0.0, reg_lambda=1.0, gamma=0.0)
    b_dev, mask_dev, _ = stage_sharded(binned)
    g_dev = stage_aligned(-y, b_dev.shape[0])
    h_dev = stage_aligned(np.ones(len(y), np.float32), b_dev.shape[0])
    w_dev = stage_aligned(w, b_dev.shape[0])
    out = {}
    for mode in ("xla", "pallas"):
        GLOBAL_CONF.set("sml.tree.kernel", mode)
        out[mode] = tree_impl.fit_tree(b_dev, g_dev, h_dev, w_dev, spec,
                                       rng=3)
    for fld in TREE_FIELDS:
        np.testing.assert_array_equal(getattr(out["xla"], fld),
                                      getattr(out["pallas"], fld),
                                      err_msg=fld)


# --------------------------------------- counters, fallback, dispatch gate
def test_kernel_counters_and_onehot_ledger(spark, kernel_conf):
    """kernel.pallas_launch/.interpret are trace-time statics proving the
    kernel path actually ran (2 launches × levels per program trace);
    the XLA path counts nothing. The HBM ledger charges the XLA path's
    fit-long one-hot resident under `hist_onehot` and ZERO under the
    kernel path (the residency win, observable)."""
    from sml_tpu.ml import tree_impl
    from sml_tpu.obs import LEDGER
    X, y = _toy(n=3000, f=4, seed=3)
    binned, _ = tree_impl.make_bins(X, y, 32)
    deltas = {}
    onehot_allocs = {}
    for mode in ("xla", "pallas"):
        GLOBAL_CONF.set("sml.tree.kernel", mode)
        # fresh spec per mode is NOT needed — kernel choice is part of
        # the program cache key, so each mode traces its own program
        es = _spec_es(X.shape[1], max_depth=5, n_trees=3)
        p0 = dict(LEDGER.snapshot().get("hist_onehot",
                                        {"allocs": 0, "peak": 0}))
        c0 = PROFILER.counters()
        _fit(es, binned, y)
        c1 = PROFILER.counters()
        p1 = LEDGER.snapshot().get("hist_onehot", {"allocs": 0, "peak": 0})
        deltas[mode] = {k: c1.get(k, 0.0) - c0.get(k, 0.0)
                        for k in ("kernel.pallas_launch",
                                  "kernel.interpret", "tree.fit_dispatch")}
        onehot_allocs[mode] = p1["allocs"] - p0["allocs"]
    assert deltas["xla"]["kernel.pallas_launch"] == 0
    # 2 kernels (accumulate + scan) per level, traced once per program
    assert deltas["pallas"]["kernel.pallas_launch"] == 2 * 5
    assert deltas["pallas"]["kernel.interpret"] == 2 * 5  # CPU backend
    # the XLA path charged its one-hot transient; pallas charged nothing
    # (the ledger difference IS the kernel's HBM residency win)
    assert onehot_allocs["xla"] >= 1
    assert onehot_allocs["pallas"] == 0
    assert LEDGER.snapshot()["hist_onehot"]["peak"] > 0


def test_auto_never_selects_pallas_on_cpu(spark, kernel_conf):
    """`auto` = pallas on real TPU only: on this CPU backend it must
    resolve to xla (interpret emulation is an explicit 'pallas' opt-in),
    while 'pallas' resolves to the kernel path."""
    from sml_tpu.ml import tree_impl
    GLOBAL_CONF.set("sml.tree.kernel", "auto")
    assert tree_impl._kernel_choice() == "xla"
    GLOBAL_CONF.set("sml.tree.kernel", "pallas")
    assert tree_impl._kernel_choice() == "pallas"
    GLOBAL_CONF.set("sml.tree.kernel", "xla")
    assert tree_impl._kernel_choice() == "xla"


def test_explicit_pallas_raises_when_kernel_unavailable(spark, kernel_conf,
                                                        monkeypatch):
    """pallas requested but the toolchain probe fails: an explicit
    'pallas' is a demand, so the fit raises the probe's own error
    instead of landing on the XLA path and producing the right model
    from a path nobody asked for. No kernel.fallback is counted — that
    counter is for `auto` and the static shape guard."""
    from sml_tpu.ml import tree_impl
    from sml_tpu.native import hist_kernel
    X, y = _toy(n=3000, f=4, seed=4)
    binned, _ = tree_impl.make_bins(X, y, 32)
    es = _spec_es(X.shape[1], n_trees=3, max_depth=3)
    monkeypatch.setitem(hist_kernel._avail, True, "Boom: no pallas here")
    GLOBAL_CONF.set("sml.tree.kernel", "pallas")
    c0 = PROFILER.counters()
    with pytest.raises(RuntimeError, match="Boom: no pallas here"):
        _fit(es, binned, y)
    c1 = PROFILER.counters()
    assert c1.get("kernel.fallback", 0.0) == c0.get("kernel.fallback", 0.0)
    assert c1.get("kernel.pallas_launch", 0.0) \
        == c0.get("kernel.pallas_launch", 0.0)


def test_auto_resolves_to_xla_on_tpu_while_fit_kernels_do_not_lower(
        spark, kernel_conf):
    """PR 21: neither fit kernel compiles for v5e as written
    (`hist_kernel.AUTO_ON_TPU` records the compiler's messages), so on a
    (simulated) TPU mesh `auto` resolves to xla BEFORE the ladder runs —
    the resolver's answer for the platform, not a kernel.fallback."""
    from sml_tpu.ml import tree_impl
    from sml_tpu.native import hist_kernel
    from sml_tpu.parallel import mesh as meshlib
    assert hist_kernel.AUTO_ON_TPU is False
    GLOBAL_CONF.set("sml.tree.kernel", "auto")
    mesh = meshlib.get_mesh()
    tree_impl._platform_memo[id(mesh)] = (mesh, "tpu")  # simulate TPU
    try:
        c0 = PROFILER.counters()
        assert tree_impl._kernel_choice() == "xla"
        c1 = PROFILER.counters()
    finally:
        tree_impl._platform_memo.clear()
    assert c1.get("kernel.fallback", 0.0) == c0.get("kernel.fallback", 0.0)


def test_dispatch_count_parity_gate(spark, kernel_conf):
    """Tier-1 contract (ISSUE 9 satellite): the kernel choice must not
    perturb the dispatch economics — `sml.tree.kernel=xla` and `=pallas`
    produce IDENTICAL tree.fit_dispatch counts and identical fit outputs
    on the same small fit (monolithic AND chunked boosting)."""
    from sml_tpu.ml import tree_impl
    X, y = _toy(n=3000, f=4, seed=6)
    binned, _ = tree_impl.make_bins(X, y, 32)
    es = _spec_es(X.shape[1], n_trees=6, max_depth=3)
    counts, outs = {}, {}
    for mode in ("xla", "pallas"):
        GLOBAL_CONF.set("sml.tree.kernel", mode)
        c0 = PROFILER.counters()
        mono = _fit(es, binned, y)
        from sml_tpu.ml._staging import stage_sharded
        from sml_tpu.ml.tree_impl import stage_aligned
        b_dev, mask_dev, _ = stage_sharded(binned)
        y_dev = stage_aligned(y, b_dev.shape[0])
        chunked = tree_impl.fit_ensemble_on_device(
            b_dev, y_dev, mask_dev, es, seed=7, rounds_per_dispatch=2)
        c1 = PROFILER.counters()
        counts[mode] = c1.get("tree.fit_dispatch", 0.0) \
            - c0.get("tree.fit_dispatch", 0.0)
        outs[mode] = (mono, chunked)
    assert counts["xla"] == counts["pallas"]
    for k in (0, 1):
        _assert_trees_bitwise(outs["xla"][k][0], outs["pallas"][k][0])
        np.testing.assert_allclose(outs["xla"][k][1], outs["pallas"][k][1],
                                   rtol=0, atol=0)


def test_kernel_for_demotes_oversized_specs_on_tpu(spark, kernel_conf,
                                                   monkeypatch):
    """The compiled split-scan kernel holds the whole widest-level
    histogram in one VMEM block — on a (simulated) TPU mesh a spec past
    the budget demotes to xla with a kernel.fallback count instead of
    failing to lower mid-trace; interpret mode (CPU) never demotes. The
    budget counts the 128-lane padding of the block's minor dimension of
    3, which rules the kernel out at the course's ml11 shape."""
    from sml_tpu.ml import tree_impl
    from sml_tpu.ml.tree_impl import TreeSpec
    from sml_tpu.native import hist_kernel
    from sml_tpu.parallel import mesh as meshlib
    # the simulated TPU has no Mosaic: stand in for its compiled probe
    monkeypatch.setitem(hist_kernel._avail, False, None)
    GLOBAL_CONF.set("sml.tree.kernel", "pallas")
    small = TreeSpec(max_depth=4, n_bins=32, n_features=6, feature_k=6,
                     min_instances=1, min_info_gain=0.0, reg_lambda=0.0,
                     gamma=0.0)
    huge = small._replace(max_depth=12, n_bins=256, n_features=20)
    # CPU (interpret): both run the kernel path — no VMEM to respect
    assert tree_impl._kernel_for(small) == "pallas"
    assert tree_impl._kernel_for(huge) == "pallas"
    mesh = meshlib.get_mesh()
    tree_impl._platform_memo[id(mesh)] = (mesh, "tpu")  # simulate TPU
    try:
        c0 = PROFILER.counters()
        assert tree_impl._kernel_for(small) == "pallas"
        assert tree_impl._kernel_for(huge) == "xla"
        c1 = PROFILER.counters()
        assert c1.get("kernel.fallback", 0.0) \
            == c0.get("kernel.fallback", 0.0) + 1
        ml11 = small._replace(max_depth=6, n_bins=64, n_features=10)
        assert tree_impl._kernel_for(ml11) == "xla"  # 10.5 MB padded
    finally:
        tree_impl._platform_memo.clear()


# ------------------------------------------------- platform memo (satellite)
def test_mesh_platform_memo_and_invalidation(spark, kernel_conf):
    """`_hist_dtype`'s platform probe is memoized per MESH identity (it
    used to walk mesh.devices.flat on every fit-setup call); a different
    mesh re-probes, and conf changes are read fresh on top of the memo
    (the kernel choice must react to sml.tree.kernel immediately)."""
    import jax.numpy as jnp

    from sml_tpu.ml import tree_impl
    from sml_tpu.parallel import mesh as meshlib
    mesh = meshlib.get_mesh()
    tree_impl._platform_memo.clear()
    assert tree_impl._hist_dtype() == jnp.float32
    assert tree_impl._platform_memo.get(id(mesh))[1] == "cpu"
    # memo is authoritative for the same mesh: poison it, no re-probe
    tree_impl._platform_memo[id(mesh)] = (mesh, "tpu")
    assert tree_impl._hist_dtype() == jnp.bfloat16
    # a DIFFERENT mesh identity re-probes (the poison doesn't leak) —
    # including an id() COLLISION after GC: the memo re-checks identity
    other = meshlib.build_mesh(1)
    assert tree_impl._mesh_platform(other) == "cpu"
    tree_impl._platform_memo[id(other)] = (mesh, "tpu")  # stale identity
    assert tree_impl._mesh_platform(other) == "cpu"
    # conf changes are never memoized: flipping the knob flips the choice
    # immediately even though the platform memo is warm
    tree_impl._platform_memo[id(mesh)] = (mesh, "cpu")
    GLOBAL_CONF.set("sml.tree.kernel", "pallas")
    assert tree_impl._kernel_choice() == "pallas"
    GLOBAL_CONF.set("sml.tree.kernel", "xla")
    assert tree_impl._kernel_choice() == "xla"
    # an unrecognized value must raise, not silently behave like auto
    GLOBAL_CONF.set("sml.tree.kernel", "bogus")
    with pytest.raises(ValueError, match="sml.tree.kernel"):
        tree_impl._kernel_choice()
    tree_impl._platform_memo.clear()


# --------------------------------------------------- prewarm manifest flag
def test_prewarm_manifest_records_kernel_flag(spark, kernel_conf, tmp_path):
    """Program signatures in the prewarm manifest carry the RESOLVED
    kernel flag, and replay rebuilds through the same-flag cache entry —
    a pallas-recorded program must not silently replay as XLA (or vice
    versa) when the replaying process's conf differs."""
    from sml_tpu.ml import tree_impl
    prev_dir = GLOBAL_CONF.get("sml.compile.cacheDir")
    GLOBAL_CONF.set("sml.compile.cacheDir", str(tmp_path))
    try:
        X, y = _toy(n=3000, f=4, seed=8)
        binned, _ = tree_impl.make_bins(X, y, 32)
        es = _spec_es(X.shape[1], n_trees=3, max_depth=3)
        GLOBAL_CONF.set("sml.tree.kernel", "pallas")
        _fit(es, binned, y)
        mpath = os.path.join(str(tmp_path), "prewarm_manifest.json")
        assert os.path.exists(mpath)
        with open(mpath) as f:
            entries = json.load(f)["entries"]
        kernels = {e["meta"].get("kernel") for e in entries.values()
                   if e["kind"].startswith("tree_")}
        assert kernels == {"pallas"}
        # the block scheme rides the signature too (replay must rebuild
        # the recorded executable, not the live conf's)
        rows_flags = {e["meta"].get("kernel_rows")
                      for e in entries.values()
                      if e["kind"].startswith("tree_")}
        assert rows_flags == {GLOBAL_CONF.getInt(
            "sml.tree.kernelBlockRows")}
        # replay under a DIFFERENT live conf: the rebuilder must honor
        # the recorded flag — the pallas program cache entry appears (and
        # the kernel traces, counting launches) despite conf saying xla
        GLOBAL_CONF.set("sml.tree.kernel", "xla")
        tree_impl._ensemble_cache.clear()
        from sml_tpu.parallel import prewarm
        GLOBAL_CONF.set("sml.prewarm.enabled", True)
        try:
            c0 = PROFILER.counters()
            stats = prewarm.prewarm(workers=1)
            c1 = PROFILER.counters()
        finally:
            GLOBAL_CONF.set("sml.prewarm.enabled", False)
            # drop the (manifest, mesh)-keyed replay-guard claim this
            # prewarm() made, so a later maybe_prewarm in the process
            # can replay again
            prewarm._ran.clear()
        assert stats["replayed"] >= 1 and stats["failed"] == 0
        assert any("pallas" in k for k in tree_impl._ensemble_cache)
        assert c1.get("kernel.pallas_launch", 0.0) \
            > c0.get("kernel.pallas_launch", 0.0)
        # the resolved block scheme is part of the program cache key: a
        # knob change must compile a fresh executable, never silently
        # replay one traced under the old blocking
        GLOBAL_CONF.set("sml.tree.kernel", "pallas")
        prev_rows = GLOBAL_CONF.get("sml.tree.kernelBlockRows")
        try:
            n_before = len(tree_impl._ensemble_cache)
            es2 = _spec_es(4, n_trees=2, max_depth=2)
            tree_impl._ensemble_compiled(es2)
            GLOBAL_CONF.set("sml.tree.kernelBlockRows", 1234)
            tree_impl._ensemble_compiled(es2)
            assert len(tree_impl._ensemble_cache) == n_before + 2
        finally:
            GLOBAL_CONF.set("sml.tree.kernelBlockRows", prev_rows)
    finally:
        GLOBAL_CONF.set("sml.compile.cacheDir", prev_dir or "")


# ------------------------------------------------------- goldens unchanged
def test_goldens_unchanged_on_ml06_ml07_fits(spark, kernel_conf):
    """The ml06/ml07-shaped fixture fits (the GOLDEN.json rmse_dt /
    rmse_rf pins at 100k rows, seed 42) reproduce the pinned metrics with
    `sml.tree.kernel=pallas` (interpret) — the kernel path cannot move a
    shipped metric."""
    from sml_tpu import functions as F
    from sml_tpu.courseware import make_airbnb_dataset
    from sml_tpu.ml import Pipeline
    from sml_tpu.ml.evaluation import RegressionEvaluator
    from sml_tpu.ml.feature import Imputer, StringIndexer, VectorAssembler
    from sml_tpu.ml.regression import (DecisionTreeRegressor,
                                       RandomForestRegressor)

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, os.pardir, "GOLDEN.json")) as f:
        golden = json.load(f)["metrics"]

    GLOBAL_CONF.set("sml.tree.kernel", "pallas")
    CAT = ["neighbourhood_cleansed", "room_type", "property_type"]
    NUM = ["accommodates", "bathrooms", "bedrooms", "beds",
           "minimum_nights", "number_of_reviews", "review_scores_rating"]
    df = spark.createDataFrame(make_airbnb_dataset(n=100_000, seed=42))
    train, test = df.randomSplit([0.8, 0.2], seed=42)
    train.cache()
    test.cache()
    idx = [c + "_idx" for c in CAT]
    imp = [c + "_imp" for c in NUM]
    prep = [Imputer(strategy="median", inputCols=NUM, outputCols=imp),
            StringIndexer(inputCols=CAT, outputCols=idx,
                          handleInvalid="skip")]
    ev = RegressionEvaluator(labelCol="price")
    tree_feats = VectorAssembler(inputCols=idx + imp, outputCol="features")

    c0 = PROFILER.counters()
    dt = Pipeline(stages=prep + [tree_feats,
                  DecisionTreeRegressor(labelCol="price", maxDepth=5,
                                        maxBins=40)]).fit(train)
    rmse_dt = ev.evaluate(dt.transform(test))
    rf = Pipeline(stages=prep + [tree_feats,
                  RandomForestRegressor(labelCol="price", maxDepth=6,
                                        numTrees=20, maxBins=40,
                                        seed=42)]).fit(train)
    rmse_rf = ev.evaluate(rf.transform(test))
    c1 = PROFILER.counters()
    # the kernel path genuinely ran these fits
    assert c1.get("kernel.pallas_launch", 0.0) \
        > c0.get("kernel.pallas_launch", 0.0)
    for got, key in ((rmse_dt, "rmse_dt"), (rmse_rf, "rmse_rf")):
        want = golden[key]
        tol = max(1e-3, 1e-5 * abs(want))  # the golden gate's own tol
        assert abs(float(got) - want) < tol, \
            f"{key}: got {got}, golden {want}"


def test_block_plan_never_reads_conf_at_trace_time():
    """PR-18 regression (the untracked-compile-input lint fix): the
    accumulate kernel's block plan is a pure function of its arguments.
    The pre-fix fallback read `sml.tree.kernelBlockRows` from live conf
    at TRACE time, silently diverging from the cache-keyed value that
    `tree_impl._kernel_block_rows` resolved host-side."""
    import inspect

    from sml_tpu.native import hist_kernel as hk

    src = inspect.getsource(hk._block_plan)
    assert "GLOBAL_CONF" not in src, \
        "trace-time conf read reintroduced into _block_plan"
    # None/0 now mean "no blocking": one full block, conf untouched
    assert hk._block_plan(6000, False, None) == (1, 6000)
    assert hk._block_plan(6000, False, 0) == (1, 6000)
    assert hk._block_plan(6000, True, 4096) == (1, 6000)
    # an explicit host-resolved target still blocks as before
    nblk, blk = hk._block_plan(6000, False, 1024)
    assert nblk * blk == 6000 and blk <= 1024
    # and the plan is insensitive to the live conf value — the knob
    # only matters where it is keyed (the host-side resolver)
    prev = GLOBAL_CONF.get("sml.tree.kernelBlockRows")
    try:
        GLOBAL_CONF.set("sml.tree.kernelBlockRows", 7)
        assert hk._block_plan(6000, False, None) == (1, 6000)
        assert hk._block_plan(6000, False, 1024) == (nblk, blk)
    finally:
        GLOBAL_CONF.set("sml.tree.kernelBlockRows", prev)
