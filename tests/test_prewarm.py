"""Program-prewarm manifest (sml_tpu/parallel/prewarm.py): recording,
concurrent replay, golden parity, and mesh-signature gating.

The contract: a process that replays a warm manifest first-dispatches
every recorded program BEFORE first use (prewarm.* counters + event
ordering), subsequent same-shape fits add ZERO program-cache misses,
and model outputs are bit-identical to an unprewarmed process.
"""

import json
import os

import numpy as np
import pandas as pd
import pytest

from sml_tpu.conf import GLOBAL_CONF
from sml_tpu.utils.profiler import PROFILER


@pytest.fixture()
def prewarm_env(tmp_path):
    """Point the compile cache (and therefore the manifest) at a fresh
    directory, with the profiler on for counter assertions."""
    prev_dir = GLOBAL_CONF.get("sml.compile.cacheDir")
    prev_prof = GLOBAL_CONF.get("sml.profiler.enabled")
    GLOBAL_CONF.set("sml.compile.cacheDir", str(tmp_path))
    GLOBAL_CONF.set("sml.profiler.enabled", True)
    yield str(tmp_path)
    GLOBAL_CONF.set("sml.compile.cacheDir", prev_dir or "")
    GLOBAL_CONF.set("sml.profiler.enabled", prev_prof)


@pytest.fixture()
def reg_frames(spark):
    rng = np.random.default_rng(0)
    n = 4000
    pdf = pd.DataFrame({f"f{i}": rng.normal(size=n) for i in range(4)})
    pdf["label"] = pdf["f0"] * 2 + rng.normal(0, 0.1, n)
    from sml_tpu.ml.feature import VectorAssembler
    fdf = VectorAssembler(inputCols=[f"f{i}" for i in range(4)],
                          outputCol="features") \
        .transform(spark.createDataFrame(pdf))
    fdf.cache()
    X = pdf[[f"f{i}" for i in range(4)]].to_numpy(np.float32)
    return fdf, X


def _clear_program_caches():
    """Simulate a cold process: drop every per-process program cache the
    prewarm replay is supposed to repopulate."""
    from sml_tpu.ml import _staging, inference, tree_impl
    tree_impl._ensemble_cache.clear()
    tree_impl._folds_cache.clear()
    tree_impl._trials_cache.clear()
    tree_impl._chunk_cache.clear()
    _staging._compiled_cache.clear()
    inference._forest_programs.clear()


def _delta(c0, c1, name):
    return c1.get(name, 0.0) - c0.get(name, 0.0)


def test_prewarm_records_replays_and_golden_parity(prewarm_env, reg_frames):
    from sml_tpu import obs
    from sml_tpu.ml import DeviceScorer
    from sml_tpu.ml.regression import RandomForestRegressor
    from sml_tpu.parallel import prewarm

    fdf, X = reg_frames
    rf = RandomForestRegressor(labelCol="label", numTrees=4, maxDepth=3,
                               seed=3)
    pred_before = DeviceScorer(rf.fit(fdf)).score_block(X)

    mpath = os.path.join(prewarm_env, "prewarm_manifest.json")
    assert os.path.exists(mpath)
    with open(mpath) as f:
        man = json.load(f)
    kinds = {e["kind"] for e in man["entries"].values()}
    assert "tree_ensemble" in kinds          # the fit program
    assert "data_parallel" in kinds          # the scorer forward

    _clear_program_caches()
    GLOBAL_CONF.set("sml.obs.enabled", True)
    try:
        obs.reset()
        c0 = PROFILER.counters()
        stats = prewarm.prewarm(workers=2)
        c1 = PROFILER.counters()
        assert stats["programs"] >= 2
        assert stats["failed"] == 0
        assert stats["replayed"] == stats["programs"]
        assert _delta(c0, c1, "prewarm.replayed") == stats["programs"]
        assert _delta(c0, c1, "prewarm.failed") == 0

        # warm caches: the SAME fit + score adds zero program-cache
        # misses — prewarm paid every build/first-dispatch up front...
        c0 = PROFILER.counters()
        pred_after = DeviceScorer(rf.fit(fdf)).score_block(X)
        c1 = PROFILER.counters()
        assert _delta(c0, c1, "compile.programs") == 0
        # ...and all prewarm activity strictly precedes first use: every
        # prewarm.* event sits before any post-prewarm program span
        events = obs.RECORDER.events()
        names = [e.name for e in events]
        assert "prewarm.start" in names and "prewarm.done" in names
        last_prewarm = max(i for i, n in enumerate(names)
                           if n.startswith("prewarm."))
        first_program = min((i for i, e in enumerate(events)
                             if e.kind == "span"
                             and e.name.startswith("program.")
                             and i > names.index("prewarm.done")),
                            default=len(events))
        assert last_prewarm < first_program or \
            names[last_prewarm] == "prewarm.done"
    finally:
        GLOBAL_CONF.set("sml.obs.enabled", False)
    # golden parity: a prewarmed process produces identical outputs
    np.testing.assert_array_equal(pred_before, pred_after)


def test_prewarm_covers_grid_fused_trials(prewarm_env, reg_frames):
    """A grid-fused CV records its trial-batched program; a cold process
    replays it and the next CV fit compiles nothing."""
    from sml_tpu.ml.evaluation import RegressionEvaluator
    from sml_tpu.ml.regression import RandomForestRegressor
    from sml_tpu.ml.tuning import CrossValidator, ParamGridBuilder
    from sml_tpu.parallel import prewarm

    fdf, _ = reg_frames
    rf = RandomForestRegressor(labelCol="label", maxBins=8, seed=7)
    grid = (ParamGridBuilder()
            .addGrid(rf.getParam("maxDepth"), [2, 3]).build())
    cv = CrossValidator(estimator=rf, estimatorParamMaps=grid,
                        evaluator=RegressionEvaluator(labelCol="label"),
                        numFolds=2, parallelism=1, seed=11)
    GLOBAL_CONF.set("sml.cv.batchFolds", True)
    try:
        metrics_before = cv.fit(fdf).avgMetrics
        with open(os.path.join(prewarm_env, "prewarm_manifest.json")) as f:
            kinds = {e["kind"] for e in json.load(f)["entries"].values()}
        assert "tree_trials" in kinds
        _clear_program_caches()
        stats = prewarm.prewarm(workers=4)
        assert stats["failed"] == 0 and stats["replayed"] >= 2
        c0 = PROFILER.counters()
        metrics_after = cv.fit(fdf).avgMetrics
        c1 = PROFILER.counters()
        assert _delta(c0, c1, "compile.programs") == 0
    finally:
        GLOBAL_CONF.unset("sml.cv.batchFolds")
    np.testing.assert_array_equal(metrics_before, metrics_after)


def test_prewarm_skips_foreign_mesh_entries(prewarm_env, reg_frames):
    """Entries recorded under a different mesh signature (data-axis width
    or platform) must be skipped, not replayed onto the wrong mesh."""
    from sml_tpu.ml.regression import DecisionTreeRegressor
    from sml_tpu.parallel import prewarm

    fdf, _ = reg_frames
    DecisionTreeRegressor(labelCol="label", maxDepth=2, seed=1).fit(fdf)
    mpath = os.path.join(prewarm_env, "prewarm_manifest.json")
    with open(mpath) as f:
        man = json.load(f)
    assert man["entries"]
    for e in man["entries"].values():
        e["mesh"] = [64, "tpu"]  # nothing local matches this
    with open(mpath, "w") as f:
        json.dump(man, f)
    # drop the in-memory manifest cache so the doctored file is re-read
    prewarm._state["entries"] = None
    stats = prewarm.prewarm()
    assert stats["programs"] == 0
    assert stats["skipped"] == len(man["entries"])


def test_a_manifest_from_before_pr30_replays_with_its_kernel_fields_ignored(
        prewarm_env, reg_frames):
    """Until PR 30 every tree entry carried `kernel` and `kernel_rows` (the
    Pallas fit kernels' switch and block scheme, deleted there). A manifest
    an older tree wrote still replays: the fields are ignored, the tree
    entries carry neither any more, and the replay warms the program the
    next fit uses."""
    from sml_tpu.ml.regression import RandomForestRegressor
    from sml_tpu.parallel import prewarm

    fdf, _ = reg_frames
    rf = RandomForestRegressor(labelCol="label", numTrees=3, maxDepth=2,
                               seed=5)
    rf.fit(fdf)
    mpath = os.path.join(prewarm_env, "prewarm_manifest.json")
    with open(mpath) as f:
        man = json.load(f)
    trees = [e for e in man["entries"].values()
             if e["kind"].startswith("tree_")]
    assert trees
    for e in trees:
        assert "kernel" not in e["meta"] and "kernel_rows" not in e["meta"]
        e["meta"].update(kernel="pallas", kernel_rows=4096)
    with open(mpath, "w") as f:
        json.dump(man, f)
    prewarm._state["entries"] = None
    _clear_program_caches()
    stats = prewarm.prewarm(workers=2)
    assert stats["failed"] == 0 and stats["replayed"] == stats["programs"]
    c0 = PROFILER.counters()
    rf.fit(fdf)
    assert _delta(c0, PROFILER.counters(), "compile.programs") == 0


def test_maybe_prewarm_is_opt_in_and_guarded_per_manifest_mesh(
        prewarm_env, monkeypatch, tmp_path):
    """The replay guard is keyed per (manifest, mesh) — NOT once per
    process: replica 2..N under the same warm caches skip (counted
    prewarm.replica_skip), while a re-pointed compile-cache dir is a
    genuinely cold world that warms again."""
    from sml_tpu.parallel import prewarm
    from sml_tpu.utils.profiler import PROFILER

    calls = []
    monkeypatch.setattr(prewarm, "prewarm", lambda **kw: calls.append(1))
    monkeypatch.setattr(prewarm, "_ran", {})
    assert prewarm.maybe_prewarm(block=True) is None  # conf off: no-op
    GLOBAL_CONF.set("sml.prewarm.enabled", True)
    try:
        prewarm.maybe_prewarm(block=True)
        assert calls == [1]
        # the claim happens in maybe_prewarm itself (not in the replay
        # thread), so back-to-back replica constructions cannot both
        # launch a replay; the shared-warm-cache skip is COUNTED
        assert prewarm._ran.get(prewarm._guard_key()) is True
        skip0 = PROFILER.counters().get("prewarm.replica_skip", 0.0)
        assert prewarm.maybe_prewarm(block=True) is None
        assert PROFILER.counters().get("prewarm.replica_skip", 0.0) \
            == skip0 + 1
        assert calls == [1]
        # a re-pointed compile cache = a different manifest = cold
        # caches for this key: the guard must NOT carry over
        other = tmp_path / "other-cache"
        GLOBAL_CONF.set("sml.compile.cacheDir", str(other))
        prewarm.maybe_prewarm(block=True)
        assert calls == [1, 1]
    finally:
        GLOBAL_CONF.unset("sml.prewarm.enabled")
        GLOBAL_CONF.set("sml.compile.cacheDir", prewarm_env)
    assert calls == [1, 1]
