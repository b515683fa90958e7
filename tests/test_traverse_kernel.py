"""Pallas fused tree-traversal inference kernel (ISSUE 12).

The contract (docs/KERNELS.md): with `sml.infer.kernel=pallas` on a
non-TPU backend the traversal kernel runs in INTERPRET mode, op-for-op
`_forest_margin`'s math — kernel-path predictions must be BIT-IDENTICAL
to the XLA traversal for DT/RF/boosted ensembles across bin dtypes, NaN
rows, and the logistic finalize; 'auto' never emulates on CPU; the
resolved (kernel, block_rows) spec keys the program cache; the VMEM
guard demotes oversized (block_rows × trees) specs on real TPU; and the
fallback / spec surface shows in `engine_health()["infer_kernel"]`.
"""

import types

import numpy as np
import pytest

from sml_tpu.conf import GLOBAL_CONF


@pytest.fixture()
def infer_conf():
    """Restore scoring-kernel knobs after each test."""
    keys = ("sml.infer.kernel", "sml.infer.kernelBlockRows",
            "sml.profiler.enabled", "sml.dispatch.mode")
    prev = {k: GLOBAL_CONF.get(k) for k in keys}
    GLOBAL_CONF.set("sml.profiler.enabled", True)
    yield
    for k, v in prev.items():
        GLOBAL_CONF.set(k, v)


def _toy(n=5000, f=8, seed=0, nan_rows=True):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f))
    if nan_rows:
        X[::17, 2] = np.nan  # binned like any other value by bin_with
    y = (2 * X[:, 0] - np.nan_to_num(X[:, 1]) ** 2
         + rng.normal(0, 0.3, n)).astype(np.float32)
    return X.astype(np.float32), y


def _fit_kind(kind, X, y, max_bins):
    from sml_tpu.ml._tree_models import _fit_ensemble
    common = dict(categorical={}, max_bins=max_bins, min_instances=1,
                  min_info_gain=0.0, seed=7)
    if kind == "dt":
        return _fit_ensemble(X, y, max_depth=5, n_trees=1, feature_k=None,
                             bootstrap=False, subsample=1.0,
                             loss="squared", **common)
    if kind == "rf":
        return _fit_ensemble(X, y, max_depth=4, n_trees=6, feature_k=3,
                             bootstrap=True, subsample=1.0,
                             loss="squared", **common)
    if kind == "xgb":
        return _fit_ensemble(X, y, max_depth=4, n_trees=5, feature_k=None,
                             bootstrap=False, subsample=1.0,
                             loss="squared", boosting=True,
                             reg_lambda=1.0, **common)
    raise AssertionError(kind)


def _margins(spec, binned, kernel):
    from sml_tpu.ml import inference
    GLOBAL_CONF.set("sml.infer.kernel", kernel)
    sf, sb, lv, w = spec.stacked()
    return inference.predict_forest_sharded(
        binned, sf, sb, lv, w, spec.depth, base=spec.base)


# ------------------------------------------------------------ bit parity
@pytest.mark.parametrize("kind", ["dt", "rf", "xgb"])
@pytest.mark.parametrize("max_bins", [32, 300])  # uint8 / uint16 operands
def test_margin_parity_bitwise_vs_xla(spark, infer_conf, kind, max_bins):
    """Kernel-path margins == XLA-path margins, bit for bit, for every
    ensemble kind, both compact bin dtypes, NaN rows included."""
    from sml_tpu.ml import tree_impl
    X, y = _toy()
    spec = _fit_kind(kind, X, y, max_bins)
    binned = tree_impl.bin_with(np.asarray(X, np.float64), spec.binning)
    assert binned.dtype == (np.uint8 if max_bins <= 256 else np.uint16)
    m_xla = _margins(spec, binned, "xla")
    m_pal = _margins(spec, binned, "pallas")
    np.testing.assert_array_equal(m_xla, m_pal)


def test_logistic_finalize_parity_through_scorer(spark, infer_conf):
    """DeviceScorer.score_block on a boosted BINARY model: the sigmoid
    finalize sits on top of bit-identical margins, so kernel-path
    probabilities equal the XLA path's exactly. The scorer's resolved
    spec is surfaced by kernel_spec()."""
    from sml_tpu.ml.inference import DeviceScorer
    X, y = _toy()
    yb = (y > np.median(y)).astype(np.float32)
    spec = _fit_kind("xgb", X, yb, 32)
    spec_l = spec  # squared-boosted; refit logistic for the sigmoid path
    from sml_tpu.ml._tree_models import _fit_ensemble
    spec_l = _fit_ensemble(X, yb, categorical={}, max_depth=4, max_bins=32,
                           min_instances=1, min_info_gain=0.0, n_trees=5,
                           feature_k=None, bootstrap=False, subsample=1.0,
                           seed=7, loss="logistic", boosting=True)
    assert spec_l.mode == "binary"
    scorer = DeviceScorer(types.SimpleNamespace(_spec=spec_l))
    GLOBAL_CONF.set("sml.dispatch.mode", "device")  # pin the kernel route
    GLOBAL_CONF.set("sml.infer.kernel", "xla")
    p_xla = scorer.score_block(X)
    GLOBAL_CONF.set("sml.infer.kernel", "pallas")
    p_pal = scorer.score_block(X)
    np.testing.assert_array_equal(p_xla, p_pal)
    assert np.all((p_pal >= 0.0) & (p_pal <= 1.0))
    ks = scorer.kernel_spec()
    assert ks is not None and ks["kernel"] == "pallas"


def test_forest_eval_parity_bitwise(spark, infer_conf):
    """The fused predict+metric eval program under the kernel path:
    bit-identical margins feed the same psums, so all five sufficient
    statistics are exactly equal."""
    from sml_tpu.ml import tree_impl
    from sml_tpu.ml._staging import run_data_parallel
    from sml_tpu.ml.inference import forest_eval_fn
    X, y = _toy()
    spec = _fit_kind("rf", X, y, 32)
    binned = tree_impl.bin_with(np.asarray(X, np.float64), spec.binning)
    sf, sb, lv, w = spec.stacked()
    l32 = np.nan_to_num(y).astype(np.float32)
    f32 = np.isfinite(y).astype(np.float32)
    rep = (np.asarray(sf), np.asarray(sb),
           np.asarray(lv, dtype=np.float32),
           np.asarray(w, dtype=np.float32), np.float32(spec.base))
    stats_x = run_data_parallel(forest_eval_fn(spec.depth, "identity"),
                                binned, l32, f32, replicated=rep)
    stats_p = run_data_parallel(
        forest_eval_fn(spec.depth, "identity", "pallas", 2048),
        binned, l32, f32, replicated=rep)
    for a, b in zip(stats_x, stats_p):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------- counters & health
def test_kernel_counters_report_and_health(spark, infer_conf):
    """The kernel path traces pallas launches (interpret on CPU), the
    module report carries the resolved spec, and engine_health()
    surfaces it as the infer_kernel block."""
    import sml_tpu.obs as obs
    from sml_tpu.ml import inference, tree_impl
    X, y = _toy(n=3000)
    spec = _fit_kind("rf", X, y, 32)
    binned = tree_impl.bin_with(np.asarray(X, np.float64), spec.binning)
    prev_obs = GLOBAL_CONF.get("sml.obs.enabled")
    GLOBAL_CONF.set("sml.obs.enabled", True)
    obs.reset()
    try:
        _margins(spec, binned, "xla")   # guarantee a spec TRANSITION so
        _margins(spec, binned, "pallas")  # the change event fires below
        c = obs.RECORDER.counters()
        assert c.get("kernel.pallas_launch", 0.0) > 0
        assert c.get("kernel.interpret", 0.0) > 0  # CPU = interpret mode
        assert c.get("infer.kernel.pallas", 0.0) >= 1
        rep = inference.kernel_report()
        assert rep["kernel"] == "pallas" and rep["block_rows"] > 0
        health = obs.engine_health()
        assert health["infer_kernel"]["kernel"] == "pallas"
        assert health["infer_kernel"]["fallbacks"] == rep["fallbacks"]
        events = [e for e in obs.RECORDER.events()
                  if e.name == "infer.kernel.spec"]
        assert events and events[-1].args["kernel"] == "pallas"
    finally:
        GLOBAL_CONF.set("sml.obs.enabled", prev_obs)


def test_auto_never_selects_pallas_on_cpu(spark, infer_conf):
    """'auto' = pallas on real TPU only; CPU emulation is an explicit
    opt-in, and landing on xla via auto is NOT a fallback."""
    from sml_tpu.ml import inference
    GLOBAL_CONF.set("sml.infer.kernel", "auto")
    f0 = inference._KERNEL_STATE["fallbacks"]
    k, br = inference.resolve_infer_kernel(
        n_trees=5, n_nodes=31, n_feat=8)
    assert (k, br) == ("xla", 0)
    assert inference._KERNEL_STATE["fallbacks"] == f0
    GLOBAL_CONF.set("sml.infer.kernel", "bogus")
    with pytest.raises(ValueError, match="sml.infer.kernel"):
        inference.resolve_infer_kernel(
            n_trees=5, n_nodes=31, n_feat=8)


def test_explicit_pallas_raises_when_kernel_unavailable(spark, infer_conf,
                                                        monkeypatch):
    """Requested pallas with a dead toolchain: an explicit 'pallas' is a
    demand, so the resolver raises the probe's own error — it must not
    land on xla and report the right numbers from another path. Only
    `auto` on a TPU mesh may decline, and that is counted."""
    from sml_tpu.ml import inference, tree_impl
    from sml_tpu.native import traverse_kernel
    from sml_tpu.parallel import mesh as meshlib
    monkeypatch.setitem(traverse_kernel._avail, True, "Boom: no pallas here")
    GLOBAL_CONF.set("sml.infer.kernel", "pallas")
    f0 = inference._KERNEL_STATE["fallbacks"]
    with pytest.raises(RuntimeError, match="Boom: no pallas here"):
        inference.resolve_infer_kernel(
            n_trees=5, n_nodes=31, n_feat=8)
    assert inference._KERNEL_STATE["fallbacks"] == f0
    X, y = _toy(n=2000)
    spec = _fit_kind("dt", X, y, 32)
    binned = tree_impl.bin_with(np.asarray(X, np.float64), spec.binning)
    with pytest.raises(RuntimeError, match="sml.infer.kernel=pallas"):
        _margins(spec, binned, "pallas")
    # auto on a (simulated) TPU mesh whose COMPILED probe fails: xla,
    # counted as a fallback — the one place the ladder may decline
    monkeypatch.setitem(traverse_kernel._avail, False, "Boom: no mosaic")
    GLOBAL_CONF.set("sml.infer.kernel", "auto")
    mesh = meshlib.get_mesh()
    tree_impl._platform_memo[id(mesh)] = (mesh, "tpu")
    try:
        k, br = inference.resolve_infer_kernel(
            n_trees=5, n_nodes=31, n_feat=8)
    finally:
        tree_impl._platform_memo.clear()
    assert (k, br) == ("xla", 0)
    assert inference._KERNEL_STATE["fallbacks"] == f0 + 1


def test_vmem_guard_demotes_oversized_specs_on_tpu(spark, infer_conf,
                                                   monkeypatch):
    """On (simulated) real TPU the resolver clamps block_rows to the
    traversal VMEM budget, and a spec whose resident node tables alone
    bust it demotes to xla with fallback + demotion counts; CPU
    interpret mode never clamps or demotes."""
    from sml_tpu.ml import inference, tree_impl
    from sml_tpu.native import traverse_kernel
    from sml_tpu.parallel import mesh as meshlib
    # the simulated TPU has no Mosaic: stand in for its compiled probe
    monkeypatch.setitem(traverse_kernel._avail, False, None)
    GLOBAL_CONF.set("sml.infer.kernel", "pallas")
    GLOBAL_CONF.set("sml.infer.kernelBlockRows", 10 ** 6)
    k, br = inference.resolve_infer_kernel(
        n_trees=8, n_nodes=63, n_feat=10)
    assert (k, br) == ("pallas", 10 ** 6)  # CPU: conf taken verbatim
    mesh = meshlib.get_mesh()
    tree_impl._platform_memo[id(mesh)] = (mesh, "tpu")  # simulate TPU
    try:
        k, br = inference.resolve_infer_kernel(
            n_trees=8, n_nodes=63, n_feat=10)
        assert k == "pallas" and 32 <= br < 10 ** 6  # clamped to budget
        from sml_tpu.native import traverse_kernel as _tk
        assert br == _tk.max_block_rows(8, 63, 10)  # ONE arithmetic
        f0 = inference._KERNEL_STATE["fallbacks"]
        d0 = inference._KERNEL_STATE["demotions"]
        k, br = inference.resolve_infer_kernel(
            n_trees=2000, n_nodes=2047,
            n_feat=10)  # 2000×2047 node tables >> the VMEM budget
        assert (k, br) == ("xla", 0)
        assert inference._KERNEL_STATE["fallbacks"] == f0 + 1
        assert inference._KERNEL_STATE["demotions"] == d0 + 1
    finally:
        tree_impl._platform_memo.clear()


def test_block_plan_never_reads_conf_at_trace_time():
    """PR-18 regression (the untracked-compile-input lint fix): the
    traversal kernel's block plan is a pure function of its arguments.
    The pre-fix fallback read `sml.infer.kernelBlockRows` from live
    conf at TRACE time, silently diverging from the cache-keyed value
    `inference.resolve_infer_kernel` resolved host-side."""
    import inspect

    from sml_tpu.conf import GLOBAL_CONF
    from sml_tpu.native import traverse_kernel as tk

    src = inspect.getsource(tk._block_plan)
    assert "GLOBAL_CONF" not in src, \
        "trace-time conf read reintroduced into _block_plan"
    # None/0 now mean "no blocking": one full block, conf untouched
    assert tk._block_plan(4096, False, None) == (1, 4096)
    assert tk._block_plan(4096, False, 0) == (1, 4096)
    assert tk._block_plan(4096, True, 256) == (1, 4096)
    nblk, blk = tk._block_plan(4096, False, 256)
    assert nblk * blk == 4096 and blk <= 256
    prev = GLOBAL_CONF.get("sml.infer.kernelBlockRows")
    try:
        GLOBAL_CONF.set("sml.infer.kernelBlockRows", 7)
        assert tk._block_plan(4096, False, None) == (1, 4096)
        assert tk._block_plan(4096, False, 256) == (nblk, blk)
    finally:
        GLOBAL_CONF.set("sml.infer.kernelBlockRows", prev)
