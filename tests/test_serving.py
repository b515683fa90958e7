"""Serving layer (sml_tpu/serving): registry-backed endpoint, continuous
micro-batching, admission control, multi-model cache, canary mode.

Acceptance (ISSUE 4): endpoint resolves a registry "Production" model and
hot-swaps after `set_version_stage`; N concurrent 1-row requests are
served in <= ceil(N/maxBatchRows) device dispatches with per-request
results identical to unbatched `score_block`; an over-capacity burst
sheds (or host-routes) rather than deadlocking.
"""

import threading

import numpy as np
import pandas as pd
import pytest

import sml_tpu.tracking as mlflow
from sml_tpu.conf import GLOBAL_CONF
from sml_tpu.ml import DeviceScorer, Pipeline
from sml_tpu.ml.feature import VectorAssembler
from sml_tpu.ml.regression import LinearRegression, RandomForestRegressor
from sml_tpu.serving import (MicroBatcher, ModelCache, RequestShed,
                             ServingEndpoint)
from sml_tpu.tracking import _store
from sml_tpu.utils.profiler import PROFILER


@pytest.fixture(autouse=True)
def tracking_dir(tmp_path):
    mlflow.set_tracking_uri(str(tmp_path / "runs"))
    yield
    while mlflow.active_run():
        mlflow.end_run()


@pytest.fixture()
def profiler_on():
    old = GLOBAL_CONF.get("sml.profiler.enabled")
    GLOBAL_CONF.set("sml.profiler.enabled", True)
    yield PROFILER
    GLOBAL_CONF.set("sml.profiler.enabled", old)


def _counter(name):
    return PROFILER.counters().get(name, 0.0)


def _make_frame(spark, seed=0, slope=2.0):
    rng = np.random.default_rng(seed)
    pdf = pd.DataFrame({"a": rng.normal(size=600),
                        "b": rng.normal(size=600)})
    pdf["y"] = slope * pdf["a"] - pdf["b"] + 1.0 \
        + rng.normal(0, 0.1, len(pdf))
    return spark.createDataFrame(pdf)


def _fit_linear(df):
    va = VectorAssembler(inputCols=["a", "b"], outputCol="features")
    return Pipeline(stages=[va, LinearRegression(labelCol="y")]).fit(df)


@pytest.fixture()
def registered_pair(spark):
    """Two registered versions of 'serve-model' (different coefficients),
    v1 in Production. Returns (model_v1, model_v2, X_probe)."""
    m1 = _fit_linear(_make_frame(spark, seed=0, slope=2.0))
    m2 = _fit_linear(_make_frame(spark, seed=1, slope=-3.0))
    for m in (m1, m2):
        with mlflow.start_run():
            mlflow.spark.log_model(m, "model",
                                   registered_model_name="serve-model")
    client = mlflow.MlflowClient()
    client.transition_model_version_stage("serve-model", 1,
                                          stage="Production")
    X = np.random.default_rng(7).normal(size=(9, 2)).astype(np.float32)
    return m1, m2, X


# ---------------------------------------------------------------- registry
def test_resolve_stage_and_transition_listener(registered_pair):
    assert _store.resolve_stage("serve-model", "Production")["version"] == 1
    assert _store.resolve_stage("serve-model", "Staging") is None
    seen = []
    _store.on_stage_transition(
        lambda name, v, stage, archived: seen.append(
            (name, v, stage, archived)))
    try:
        _store.set_version_stage("serve-model", 2, "Production",
                                 archive_existing_versions=True)
    finally:
        _store._stage_listeners.clear()
    assert seen == [("serve-model", 2, "Production", [1])]
    assert _store.resolve_stage("serve-model", "Production")["version"] == 2
    assert _store.get_model_version("serve-model", 1)["current_stage"] \
        == "Archived"


def test_raising_listener_does_not_block_later_listeners(registered_pair,
                                                         profiler_on):
    """Listener hygiene (PR 14): a raising on_stage_transition listener
    must not prevent later listeners from observing the commit, must
    not bubble into the promoter, and must be COUNTED
    (tracking.listener_error) instead of silent."""
    calls = []

    def bad(name, v, stage, archived):
        calls.append("bad")
        raise RuntimeError("torn subscriber")

    def good(name, v, stage, archived):
        calls.append("good")

    _store.on_stage_transition(bad)
    _store.on_stage_transition(good)
    try:
        before = _counter("tracking.listener_error")
        meta = _store.set_version_stage("serve-model", 2, "Production",
                                        archive_existing_versions=True)
    finally:
        _store.remove_stage_listener(bad)
        _store.remove_stage_listener(good)
    assert meta["current_stage"] == "Production"
    assert calls == ["bad", "good"]  # the later listener still fired
    assert _counter("tracking.listener_error") == before + 1
    # the commit is fully observed, not half-applied
    assert _store.resolve_stage("serve-model", "Production")["version"] == 2
    assert _store.get_model_version("serve-model", 1)["current_stage"] \
        == "Archived"


def test_bad_promote_does_not_archive_incumbent(registered_pair):
    """Validation-order fix: a transition to a missing version must not
    half-apply (archiving the incumbents, then raising)."""
    with pytest.raises(ValueError):
        _store.set_version_stage("serve-model", 99, "Production",
                                 archive_existing_versions=True)
    assert _store.resolve_stage("serve-model", "Production")["version"] == 1


# -------------------------------------------------------------- endpoint
def test_endpoint_resolves_production_and_hot_swaps(registered_pair,
                                                    profiler_on):
    m1, m2, X = registered_pair
    cache = ModelCache()
    with ServingEndpoint("serve-model", "Production", model_cache=cache,
                         flush_micros=500) as ep:
        assert ep.current_version() == 1
        np.testing.assert_allclose(ep.score(X, timeout=30),
                                   DeviceScorer(m1).score_block(X),
                                   rtol=1e-6)
        swaps0 = _counter("serve.hot_swap")
        client = mlflow.MlflowClient()
        client.transition_model_version_stage(
            "serve-model", 2, stage="Production",
            archive_existing_versions=True)
        assert ep.current_version() == 2
        assert _counter("serve.hot_swap") == swaps0 + 1
        np.testing.assert_allclose(ep.score(X, timeout=30),
                                   DeviceScorer(m2).score_block(X),
                                   rtol=1e-6)
        # the archived v1's warm scorer was invalidated, not left to LRU
        assert cache.stats()["entries"] == 1


def test_endpoint_requires_a_staged_version(registered_pair):
    with pytest.raises(ValueError, match="Staging"):
        ServingEndpoint("serve-model", "Staging")


def test_promote_while_serving_race(registered_pair):
    """The transition race: a client loop scoring through the endpoint
    while a promotion lands. Every response must be v1's or v2's exact
    prediction (never a torn mix), and the endpoint must converge to v2."""
    m1, m2, X = registered_pair
    exp1 = DeviceScorer(m1).score_block(X)
    exp2 = DeviceScorer(m2).score_block(X)
    # three clients coalesce to one, two or three blocks a flush: run the
    # other two widths here, or the first flush at each compiles on the
    # serving thread and, on a busy machine, the requests queued behind
    # it pass `sml.serve.requestTimeoutMillis` and are shed
    for k in (2, 3):
        DeviceScorer(m1).score_block(np.tile(X, (k, 1)))
    errors, torn = [], []
    stop = threading.Event()

    with ServingEndpoint("serve-model", "Production",
                         flush_micros=200) as ep:
        def client():
            while not stop.is_set():
                try:
                    out = ep.score(X, timeout=30)
                except Exception as e:  # noqa: BLE001 — recorded, asserted
                    errors.append(e)
                    return
                if not (np.allclose(out, exp1, rtol=1e-6)
                        or np.allclose(out, exp2, rtol=1e-6)):
                    torn.append(out)

        threads = [threading.Thread(target=client) for _ in range(3)]
        for t in threads:
            t.start()
        mlflow.MlflowClient().transition_model_version_stage(
            "serve-model", 2, stage="Production",
            archive_existing_versions=True)
        stop.set()
        for t in threads:
            t.join(timeout=30)
        assert not errors and not torn
        assert ep.current_version() == 2
        np.testing.assert_allclose(ep.score(X, timeout=30), exp2, rtol=1e-6)


# ----------------------------------------------------------- micro-batcher
def test_concurrent_requests_coalesce_and_match_unbatched(registered_pair,
                                                          profiler_on):
    """N concurrent 1-row requests -> <= ceil(N/maxBatchRows) device
    dispatches, per-request results identical to unbatched score_block."""
    m1, _, X = registered_pair
    scorer = DeviceScorer(m1)
    n, max_rows = 48, 16
    rows = [X[i % len(X)][None, :] for i in range(n)]
    expected = scorer.score_block(np.concatenate(rows, axis=0))
    b = MicroBatcher(scorer.score_block, max_batch_rows=max_rows,
                     flush_micros=5000, start=False)
    futs = [None] * n
    barrier = threading.Barrier(8)

    def client(lo):
        barrier.wait()
        for i in range(lo, n, 8):
            futs[i] = b.submit(rows[i])

    threads = [threading.Thread(target=client, args=(lo,))
               for lo in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    batches0 = _counter("serve.batches")
    b.start()
    got = np.concatenate([futs[i].result(30) for i in range(n)])
    b.close()
    dispatches = _counter("serve.batches") - batches0
    assert dispatches <= int(np.ceil(n / max_rows))
    np.testing.assert_allclose(got, expected, rtol=1e-7)


def test_shape_bucket_reuse_zero_new_compiles(registered_pair, profiler_on):
    """The second batch of the same shape bucket must trigger ZERO fresh
    program compiles (obs.note_compile's compile.programs counter)."""
    _, m2, X = registered_pair
    scorer = DeviceScorer(m2)
    with MicroBatcher(scorer.score_block, max_batch_rows=32,
                      flush_micros=100) as b:
        b.submit(X[:5]).result(30)          # warm the bucket's program
        compiles0 = _counter("compile.programs")
        b.submit(X[2:6]).result(30)         # same bucket, different rows
        assert _counter("compile.programs") == compiles0


def test_deadline_flush_serves_a_lone_request(registered_pair, profiler_on):
    """A lone sub-batch request must flush on the flushMicros deadline,
    not wait for a full batch that will never arrive."""
    m1, _, X = registered_pair
    scorer = DeviceScorer(m1)
    with MicroBatcher(scorer.score_block, max_batch_rows=4096,
                      flush_micros=10_000) as b:
        batches0 = _counter("serve.batches")
        out = b.submit(X[:1]).result(30)
        assert _counter("serve.batches") == batches0 + 1
    np.testing.assert_allclose(out, scorer.score_block(X[:1]), rtol=1e-7)


def test_padded_row_masking_parity(registered_pair):
    """Mixed-size requests coalesced into one padded block must come back
    identical to each request scored alone (padding rows stay inert)."""
    m1, _, _ = registered_pair
    scorer = DeviceScorer(m1)
    rng = np.random.default_rng(3)
    blocks = [rng.normal(size=(r, 2)).astype(np.float32)
              for r in (3, 5, 7)]
    b = MicroBatcher(scorer.score_block, max_batch_rows=64,
                     flush_micros=5000, start=False)
    futs = [b.submit(blk) for blk in blocks]
    b.start()
    outs = [f.result(30) for f in futs]
    b.close()
    for blk, out in zip(blocks, outs):
        # f32 forward at a different padded shape may re-block the matmul
        np.testing.assert_allclose(out, scorer.score_block(blk),
                                   rtol=1e-6, atol=1e-6)


def test_forest_batching_parity(spark):
    """The tree-ensemble scorer rides the same batcher (margin finalize
    per request slice must survive the split)."""
    df = _make_frame(spark, seed=5)
    va = VectorAssembler(inputCols=["a", "b"], outputCol="features")
    rf = Pipeline(stages=[va, RandomForestRegressor(
        labelCol="y", numTrees=4, maxDepth=3, seed=1)]).fit(df)
    scorer = DeviceScorer(rf)
    X = np.random.default_rng(11).normal(size=(12, 2)).astype(np.float32)
    b = MicroBatcher(scorer.score_block, max_batch_rows=64,
                     flush_micros=5000, start=False)
    futs = [b.submit(X[i:i + 3]) for i in range(0, 12, 3)]
    b.start()
    outs = [f.result(30) for f in futs]
    b.close()
    for i, out in enumerate(outs):
        np.testing.assert_allclose(
            out, scorer.score_block(X[3 * i:3 * i + 3]), rtol=1e-6)


# ------------------------------------------------------- admission control
def test_over_capacity_burst_sheds_without_deadlock(registered_pair,
                                                    profiler_on):
    m1, _, X = registered_pair
    scorer = DeviceScorer(m1)
    shed0 = _counter("serve.shed")
    over0 = _counter("serve.shed.overflow")
    b = MicroBatcher(scorer.score_block, max_batch_rows=16, queue_rows=8,
                     host_fallback=False, start=False)
    futs = [b.submit(X[:1]) for _ in range(20)]
    # overflow futures are already resolved with RequestShed — no worker
    # needed, nothing blocks
    shed = [f for f in futs if f.done()]
    assert len(shed) == 12 and _counter("serve.shed") - shed0 == 12
    # reason-tagged next to the total: the cause is attributable
    assert _counter("serve.shed.overflow") - over0 == 12
    for f in shed:
        with pytest.raises(RequestShed):
            f.result(1)
    b.start()
    for f in futs:
        if f not in shed:
            f.result(30)  # admitted requests still serve
    b.close()


def test_over_capacity_burst_host_routes(registered_pair, profiler_on):
    """With hostFallback on, overflow degrades to the host route with
    correct results instead of shedding."""
    m1, _, X = registered_pair
    scorer = DeviceScorer(m1)
    expected = scorer.score_block(X[:1])
    routed0 = _counter("serve.host_routed")
    b = MicroBatcher(scorer.score_block,
                     host_score=scorer.score_block_host,
                     max_batch_rows=16, queue_rows=4,
                     host_fallback=True, start=False)
    futs = [b.submit(X[:1]) for _ in range(10)]
    assert _counter("serve.host_routed") - routed0 == 6
    for f in futs:
        if f.done():
            np.testing.assert_allclose(f.result(1), expected, rtol=1e-6)
    b.start()
    for f in futs:
        np.testing.assert_allclose(f.result(30), expected, rtol=1e-6)
    b.close()


def test_deadline_shed_of_stale_requests(registered_pair, profiler_on):
    """Queued requests past requestTimeoutMillis shed at flush time."""
    import time
    m1, _, X = registered_pair
    scorer = DeviceScorer(m1)
    b = MicroBatcher(scorer.score_block, max_batch_rows=16,
                     timeout_millis=30, flush_micros=1000, start=False)
    futs = [b.submit(X[:1]) for _ in range(4)]
    time.sleep(0.1)  # everything queued is now past its deadline
    expired0 = _counter("serve.expired")
    b.start()
    for f in futs:
        with pytest.raises(RequestShed):
            f.result(30)
    b.close()
    assert _counter("serve.expired") - expired0 == 4


# ------------------------------------------------------------ model cache
def test_model_cache_lru_byte_eviction(registered_pair, profiler_on):
    m1, m2, X = registered_pair
    s1, s2 = DeviceScorer(m1), DeviceScorer(m2)
    cache = ModelCache(max_bytes=2 * s1.resident_bytes() + 8)
    assert cache.get("m", 1, lambda: s1) is s1
    hits0 = _counter("serve.model_cache_hit")
    assert cache.get("m", 1, lambda: s1) is s1          # hit
    assert _counter("serve.model_cache_hit") == hits0 + 1
    cache.get("m", 2, lambda: s2)
    assert cache.stats()["entries"] == 2
    cache.get("m", 1, lambda: s1)                        # touch: 1 is MRU
    evict0 = _counter("serve.model_cache_evict_bytes")
    cache.get("other", 1, lambda: DeviceScorer(m1))      # evicts LRU (m,2)
    assert cache.stats()["entries"] == 2
    assert _counter("serve.model_cache_evict_bytes") > evict0
    # (m, 1) survived the eviction (it was most recently used)
    assert cache.get("m", 1, lambda: (_ for _ in ()).throw(
        AssertionError("LRU evicted the MRU entry"))) is s1


# ----------------------------------------------------------------- canary
def test_canary_mirrors_to_staging_and_records_divergence(registered_pair,
                                                          profiler_on):
    m1, m2, X = registered_pair
    mlflow.MlflowClient().transition_model_version_stage(
        "serve-model", 2, stage="Staging")
    with ServingEndpoint("serve-model", "Production", canary_fraction=1.0,
                         flush_micros=200) as ep:
        for i in range(5):
            ep.score(X[i:i + 2], timeout=30)
        stats = None
        for _ in range(100):  # the shadow worker is async — poll briefly
            stats = ep.canary_stats()
            if stats["mirrored"] >= 5:
                break
            import time
            time.sleep(0.02)
        assert stats["mirrored"] == 5 and stats["rows"] == 10
        assert stats["staging_version"] == 2
        # v1 and v2 were trained on different targets: divergence is real
        assert stats["mean_abs_diff"] > 0.1
        assert stats["max_abs_diff"] >= stats["mean_abs_diff"]


def test_canary_stats_reset_on_staging_change(registered_pair,
                                              profiler_on):
    """A new candidate entering (or leaving) Staging re-arms the
    divergence accumulator: the running max is folded monotonically, so
    a past candidate's divergence must not poison every later gate on
    this endpoint (the fleet rollout's max_abs_diff bound reads it)."""
    import time
    m1, m2, X = registered_pair
    mlflow.MlflowClient().transition_model_version_stage(
        "serve-model", 2, stage="Staging")
    with ServingEndpoint("serve-model", "Production", canary_fraction=1.0,
                         flush_micros=200) as ep:
        for _ in range(3):
            ep.score(X[:2], timeout=30)
        for _ in range(100):
            if ep.canary_stats()["mirrored"] >= 3:
                break
            time.sleep(0.02)
        assert ep.canary_stats()["max_abs_diff"] > 0
        # the candidate leaves Staging: stats describe nothing now
        _store.set_version_stage("serve-model", 2, "Archived")
        stats = ep.canary_stats()
        assert stats["mirrored"] == 0 and stats["max_abs_diff"] == 0.0
        assert stats["staging_version"] is None


def test_canary_fraction_paces_mirroring(registered_pair):
    m1, m2, X = registered_pair
    mlflow.MlflowClient().transition_model_version_stage(
        "serve-model", 2, stage="Staging")
    with ServingEndpoint("serve-model", "Production", canary_fraction=0.25,
                         flush_micros=200) as ep:
        for _ in range(8):
            ep.score(X[:1], timeout=30)
        for _ in range(100):
            if ep.canary_stats()["mirrored"] >= 2:
                break
            import time
            time.sleep(0.02)
        assert ep.canary_stats()["mirrored"] == 2  # every 4th request


# ----------------------------------------------------------------- health
def test_health_report_exposes_engine_health_live(registered_pair):
    """ISSUE 7 acceptance: ServingEndpoint.health_report() surfaces the
    obs.engine_health() snapshot live — populated serve.request_ms
    quantiles from real traffic, the SLO block, and the endpoint's own
    resolved-version/queue/canary state."""
    from sml_tpu import obs

    m1, m2, X = registered_pair
    GLOBAL_CONF.set("sml.obs.enabled", True)
    try:
        obs.METRICS.reset()
        with ServingEndpoint("serve-model", "Production",
                             flush_micros=200) as ep:
            for i in range(6):
                ep.score(X[i:i + 2], timeout=30)
            health = ep.health_report()
        m = health["metrics"]["serve.request_ms"]
        assert m["count"] == 6
        assert m["p50"] > 0 and m["p99"] >= m["p50"]
        assert health["slo"]["requests"] == 6
        assert health["slo"]["target_ms"] == 250.0
        assert "burn_rate" in health["slo"]
        assert "_total" in health["hbm"]
        assert "decisions" in health["audit"]
        ep_block = health["endpoint"]
        assert ep_block["name"] == "serve-model"
        assert ep_block["stage"] == "Production"
        assert ep_block["version"] == 1
        assert ep_block["queued_rows"] == 0
        assert ep_block["canary"]["mirrored"] == 0
    finally:
        GLOBAL_CONF.set("sml.obs.enabled", False)
        obs.reset()
