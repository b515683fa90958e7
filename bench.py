#!/usr/bin/env python
"""End-to-end benchmark: the course's ML 02–ML 13 compute path on TPU,
run at the scale class the reference claims ("data that exceeds one
machine", `SML/ML 00b - Spark Review.py:84`; MovieLens 1M, `MLE 01:18`):
ONE MILLION rows of the SF-Airbnb-shaped schema, seed 42, plus an
8M-row scale-escalation leg (`ml_scale`) where the host baseline takes
minutes and HBM residency pays off.

Legs (every BASELINE.json config):

  ML 02/03  StringIndexer+OHE+VectorAssembler+LinearRegression fit+predict
  ML 06/07  DecisionTree + RandomForest, then the ML 07 CrossValidator grid
            (maxDepth x numTrees, 3 folds, parallelism=4 — `ML 07:102-149`)
  ML 08     Hyperopt-style TPE search over RF params (4 evals, the course
            budget — `ML 08:146`)
  ML 11     XGBoost-equivalent (tpu_hist boosted trees), log-price target
  ML 12     batch inference via DeviceScorer-backed mapInPandas
  ML 13     applyInPandas per-group training
  serving   online scoring through sml_tpu/serving: closed-loop clients
            issuing low-row requests through the continuous micro-batcher
            (registry-style endpoint path); p50/p99 per-request latency,
            batch occupancy, and shed rate go to the sidecar as serve_*
            metrics (excluded from golden pins — they are load numbers)
  MLE 01/02 block-parallel ALS (MovieLens-1M scale) + fused-Lloyd KMeans
  ml_scale  8M-row LinearRegression + LogisticRegression fits through the
            compact expand-on-device programs (prepared features on BOTH
            sides, like the mle02 leg): the course's "exceeds one machine"
            claim made concrete — the host side runs sklearn on the same
            prepared matrix and takes minutes

Output contract (VERDICT r4 #2): the LAST stdout line is a SHORT headline
JSON — {metric, value, unit, vs_baseline, compile_seconds, pass_walls,
interference_suspected, golden_ok, backend, device, legs_file} — sized to survive
any capture tail window. Per-leg detail, probes, metrics, and each leg's
ENGINE-COUNTER deltas (staging bytes, cache hits, shuffle volume,
compile count — see docs/OBSERVABILITY.md) go to the `bench_legs.json`
sidecar and stderr.

Timing policy: THREE timed passes after two full warmup passes; each
leg's reported seconds is its BEST across the timed passes (every pass's
full detail is in the sidecar). The host can be co-tenant-loaded;
per-leg best-of-passes measures the framework rather than the noisiest
neighbor, and the device/host probes taken around every pass are
recorded so a globally-slow session is flagged
(`interference_suspected`) instead of silently reported.

The suite runs on whatever backend jax finds and says which in its
headline (`backend`, `device`); a record taken on the CPU mesh speaks for
counts and parity only, never for device times (ROADMAP S1 makes the
measurement path refuse a CPU).

`vs_baseline` anchors to a MEASURED single-node pandas/sklearn execution
of the same legs. Expensive legs (>30s host) come from the committed
cache (baseline_host.json); every cheap leg is RE-MEASURED in this run
on this machine (r4's losing legs were host-path times compared against
a baseline captured on a different, uncontended machine) with the SAME
best-of-N discipline as the device legs (best of HOST_TIMED_PASSES),
so vs_baseline compares best-against-best instead of best-against-one.

Run `python bench.py --pin-goldens` on the virtual CPU mesh to (re)pin
the 1M-row metric goldens that the TPU run is checked against.
"""

# graftlint: disable-file=no-wallclock-in-engine -- bench harness: leg wall-clocks ARE the product here, measured outside the engine so profiler overhead never lands inside a timed pass

import argparse
import json
import os
import sys
import time

# XLA:CPU AOT cache replays log a benign machine-feature banner (pseudo-
# features like +prefer-no-scatter) at ERROR level per entry — silence the
# C++ logs before jax loads so the bench output stays readable
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")

import numpy as np

N_ROWS = 1_000_000
N_RATINGS = 1_000_000  # MovieLens-1M-scale ALS workload (`MLE 01:18`)
N_SCALE = 8_000_000    # the scale-escalation leg (`ML 00b:84`)
SCALE_SEED = 43
SCALE_LOGIT_ITERS = 20  # both sides run the same Newton/lbfgs budget
LEGS_VERSION = 7  # bump when leg definitions change (invalidates the cache)
HERE = os.path.dirname(os.path.abspath(__file__))
BASELINE_CACHE = os.path.join(HERE, "baseline_host.json")
LEGS_FILE = os.path.join(HERE, "bench_legs.json")
GOLDEN_FILE = os.path.join(HERE, "GOLDEN.json")

# host legs cheaper than this re-measure EVERY run on the CURRENT machine;
# slower legs (30s-minutes, won by 10-50x margins that dwarf machine
# variance) come from the committed cache
HOST_REMEASURE_CUTOFF_S = 30.0

# re-measured host legs run this many passes and report their BEST — the
# SAME best-of-N discipline the device legs get (ADVICE r5 medium: one
# host pass against best-of-3 device passes structurally inflated
# vs_baseline). Expensive cached legs stay single-pass (their 10-50x
# margins dwarf pass noise; the sidecar labels them "cached").
HOST_TIMED_PASSES = 3

# metric golden tolerances (TPU bf16-histogram path vs the CPU-mesh f32
# pins): trees can shift whole splits under operand rounding, linear/ALS
# paths accumulate in f32 either way
GOLDEN_TOLERANCES = {
    "rmse_lr": 0.01, "rmse_dt": 0.05, "rmse_rf": 0.05, "rmse_xgb": 0.05,
    "cv_best_rmse": 0.05, "rmse_als": 0.05, "scale_rmse_lr": 0.01,
    "scale_accuracy": 0.02,
}


class EngineCounterTrack:
    """Per-leg engine-counter deltas (staging bytes, cache hits, shuffle
    volume, compile count) from the profiler's counter stream: `mark(leg)`
    attributes everything counted since the previous mark to `leg`.
    Recorded into the bench_legs.json sidecar so BENCH runs carry
    cache-hit/byte-volume trajectories alongside wall time — a perf PR can
    diff engine behavior, not just seconds."""

    def __init__(self):
        from sml_tpu.utils.profiler import PROFILER
        self._prof = PROFILER
        self._prev = PROFILER.counters()
        self.legs = {}

    def mark(self, leg):
        cur = self._prof.counters()
        delta = {k: round(v - self._prev.get(k, 0.0), 3)
                 for k, v in cur.items() if v != self._prev.get(k, 0.0)}
        self.legs[leg] = delta
        self._prev = cur


def build_dataset(n):
    from sml_tpu.courseware import make_airbnb_dataset
    from sml_tpu.frame.session import get_session
    pdf = make_airbnb_dataset(n=n, seed=42)
    return get_session().createDataFrame(pdf), pdf


def build_ratings(n):
    """MovieLens-1M-shaped ratings at the real set's entity dims
    (~6040 users x ~3700 movies, `SML/ML Electives/MLE 01:18`)."""
    from sml_tpu.courseware import make_movielens_dataset
    from sml_tpu.frame.session import get_session
    pdf = make_movielens_dataset(n_users=6040, n_items=3700,
                                 n_ratings=n, seed=42)
    return get_session().createDataFrame(pdf), pdf


CAT_COLS = ["neighbourhood_cleansed", "room_type", "property_type"]
NUM_COLS = ["accommodates", "bathrooms", "bedrooms", "beds",
            "minimum_nights", "number_of_reviews", "review_scores_rating"]

# serving leg: closed-loop load (SERVE_CLIENTS concurrent clients, each
# issuing SERVE_REQUEST_ROWS-row requests back-to-back until the shared
# budget of SERVE_REQUESTS is spent) — identical on both sides
SERVE_CLIENTS = 8
SERVE_REQUEST_ROWS = 8
SERVE_REQUESTS = 2000
SERVE_MAX_BATCH_ROWS = 256
SERVE_FLUSH_MICROS = 1000

_scale_cache: dict = {}


def build_scale_parts():
    """Prepared features for the ml_scale leg, built ONCE per process and
    shared by every pass (prep is outside the timed region on BOTH sides,
    like the mle02 leg): fit the course prep chain on the 8M frame, then
    extract the compact block (featurizer.CompactParts). The host side
    gets the same features expanded to the dense matrix sklearn wants."""
    if _scale_cache:
        return _scale_cache["parts"], _scale_cache["yp"], _scale_cache["yl"]
    from sml_tpu.courseware import make_airbnb_dataset
    from sml_tpu.frame.session import get_session
    from sml_tpu.ml import Pipeline
    from sml_tpu.ml.feature import (Imputer, OneHotEncoder, StringIndexer,
                                    VectorAssembler)
    from sml_tpu.ml.featurizer import CompiledFeaturizer
    print(f"preparing ml_scale data ({N_SCALE} rows)...", file=sys.stderr)
    pdf = make_airbnb_dataset(n=N_SCALE, seed=SCALE_SEED)
    yp = np.asarray(pdf["price"], np.float32)
    yl = (yp > float(np.median(yp))).astype(np.float32)
    df = get_session().createDataFrame(pdf)
    idx = [c + "_idx" for c in CAT_COLS]
    ohe = [c + "_ohe" for c in CAT_COLS]
    imp = [c + "_imp" for c in NUM_COLS]
    prep = Pipeline(stages=[
        Imputer(strategy="median", inputCols=NUM_COLS, outputCols=imp),
        StringIndexer(inputCols=CAT_COLS, outputCols=idx,
                      handleInvalid="skip"),
        OneHotEncoder(inputCols=idx, outputCols=ohe),
        VectorAssembler(inputCols=ohe + imp, outputCol="features"),
    ]).fit(df)
    feat = CompiledFeaturizer.from_stages(prep.stages[:-1], prep.stages[-1])
    parts = feat.compact_parts(pdf)
    assert parts is not None and parts.keep is None
    _scale_cache.update(parts=parts, yp=yp, yl=yl)
    return parts, yp, yl


def run_scale_leg(timings, flops, metrics, eng=None):
    """8M-row LinearRegression + LogisticRegression through the compact
    expand-on-device programs (`linear_impl.fit_*_compact`): one Gram
    dispatch + one fused-IRLS dispatch, one-hot slots expanded on-chip.
    The logistic budget (20 Newton steps, executed unconditionally by the
    fused scan) is matched by the host side's lbfgs max_iter."""
    from sml_tpu.ml import linear_impl
    parts, yp, yl = build_scale_parts()
    d = parts.width
    n8 = parts.rows
    t0 = time.perf_counter()
    res_lr = linear_impl.fit_linear_compact(parts, yp)
    res_lg = linear_impl.fit_logistic_compact(parts, yl,
                                              maxIter=SCALE_LOGIT_ITERS,
                                              tol=1e-9)
    timings["ml_scale"] = time.perf_counter() - t0
    if eng is not None:
        eng.mark("ml_scale")
    flops["ml_scale"] = (2.0 * n8 * (d + 1) ** 2
                         + 3.0 * SCALE_LOGIT_ITERS * n8 * (d + 1) ** 2)
    st = res_lr.stats or {}
    n_f = st.get("n", n8) or n8
    metrics["scale_rmse_lr"] = float(np.sqrt(st.get("sse", 0.0) / n_f))
    # accuracy on the first 1M rows, computed OUTSIDE the timed region
    # (an 8M predict_affine pass costs more than the fits themselves)
    head = parts._replace(num=parts.num[:, :1_000_000],
                          codes=parts.codes[:, :1_000_000])
    margin = head.predict_affine(res_lg.coefficients, res_lg.intercept)
    metrics["scale_accuracy"] = float(np.mean((margin > 0) == (yl[:1_000_000] > 0.5)))
    metrics["scale_d"] = float(d)


def run_serving_leg(lr_model, test, timings, flops, metrics, eng=None):
    """Online-serving leg (docs/SERVING.md): SERVE_CLIENTS closed-loop
    clients push SERVE_REQUEST_ROWS-row requests through the continuous
    micro-batcher in front of a warm DeviceScorer — the amortize-one-
    compiled-program-over-many-small-requests story, measured. Feature
    prep happens OUTSIDE the timed region on both sides (an online
    endpoint scores feature blocks); the timed region is admission →
    coalesce → device dispatch → per-request split.

    Latency percentiles come from the engine's OWN streaming metrics
    core (`obs.METRICS` `serve.request_ms`, fed by the micro-batcher at
    result time — docs/OBSERVABILITY.md): log-bucketed quantiles exact
    to one ~9% bucket, no raw sample lists, no sort. The leg also
    records the SLO burn-rate (`sml.serve.sloMillis`) from the same
    histogram — the number `obs.engine_health()` serves live."""
    import threading

    from sml_tpu import obs
    from sml_tpu.conf import GLOBAL_CONF as _SCONF
    from sml_tpu.ml import DeviceScorer
    from sml_tpu.serving import MicroBatcher
    from sml_tpu.utils.profiler import PROFILER

    from sml_tpu.serving import RequestShed

    scorer = DeviceScorer(lr_model)
    X = scorer._prep(test.toPandas())[:SERVE_REQUESTS * SERVE_REQUEST_ROWS]
    d = X.shape[1]
    slices = [X[lo:lo + SERVE_REQUEST_ROWS]
              for lo in range(0, len(X), SERVE_REQUEST_ROWS)]
    # warm the padded-shape buckets the coalescer can actually produce
    # (every multiple of the request size up to a full batch maps onto
    # bucket_rows' coarse grid — a handful of distinct shapes), so the
    # timed region measures serving, not first-seen-shape compiles; real
    # compile economics are the suite's warmup passes' job
    from sml_tpu.parallel.dispatch import bucket_rows
    warm = sorted({bucket_rows(r, 1) for r in
                   range(SERVE_REQUEST_ROWS,
                         SERVE_MAX_BATCH_ROWS + 1, SERVE_REQUEST_ROWS)})
    for rows in warm:
        scorer.score_block(np.ascontiguousarray(X[:rows]))
    c0 = PROFILER.counters()
    next_req = [0]
    req_lock = threading.Lock()

    def client(batcher):
        while True:
            with req_lock:
                i = next_req[0]
                if i >= len(slices):
                    return
                next_req[0] = i + 1
            try:
                batcher.submit(slices[i]).result(timeout=60)
            except RequestShed:
                continue  # shed is an answer, not a client crash — the
                # shed rate is reported from the serve.shed counter

    # the serving leg runs with the recorder ON: the micro-batcher feeds
    # every request's admission->result latency into the streaming
    # metrics core, and the percentiles below read from THAT histogram
    prev_obs = _SCONF.get("sml.obs.enabled")
    _SCONF.set("sml.obs.enabled", True)
    obs.METRICS.reset()  # this pass's leg owns its distribution
    t0 = time.perf_counter()
    try:
        with MicroBatcher(scorer.score_block,
                          host_score=scorer.score_block_host,
                          max_batch_rows=SERVE_MAX_BATCH_ROWS,
                          flush_micros=SERVE_FLUSH_MICROS) as batcher:
            threads = [threading.Thread(target=client, args=(batcher,))
                       for _ in range(SERVE_CLIENTS)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        timings["serving"] = time.perf_counter() - t0
        hist = obs.METRICS.histogram("serve.request_ms")
        slo = obs.slo_report()
    finally:
        _SCONF.set("sml.obs.enabled", bool(prev_obs))
    if eng is not None:
        eng.mark("serving")
    flops["serving"] = 2.0 * len(X) * d
    c1 = PROFILER.counters()

    def delta(k):
        return c1.get(k, 0.0) - c0.get(k, 0.0)

    batches = max(delta("serve.batches"), 1.0)
    reqs = max(delta("serve.requests"), 1.0)
    metrics["serve_p50_ms"] = round(hist.quantile(0.50), 3) if hist else 0.0
    metrics["serve_p99_ms"] = round(hist.quantile(0.99), 3) if hist else 0.0
    # like-for-like annotation (docs/LOADGEN.md): these percentiles come
    # from CLOSED-LOOP clients with no arrival schedule — they
    # self-throttle when the batcher queues, so they are NOT comparable
    # to open-loop numbers. The regress sentry only compares
    # serve_p50/p99 between records whose serve_closed_loop annotations
    # agree; the open-loop story lives in the `load` block
    metrics["serve_closed_loop"] = 1.0
    metrics["serve_slo_burn_rate"] = slo["burn_rate"]
    # the LITERAL worst request of the leg, by trace-id exemplar
    # (obs/_context.py): the id to chase through an exported trace's
    # flow arrows. A sidecar annotation, not a perf number — excluded
    # from golden pins (serve_*) and ignored by the bench_diff sentry
    # (non-numeric)
    metrics["serve_worst_trace"] = slo.get("worst_trace") or ""
    # numerator = rows that actually entered a device batch (serve.rows
    # also counts shed/host-routed admissions, which would inflate this
    # exactly when the degradation ladder is active)
    metrics["serve_occupancy"] = round(
        delta("serve.batch_rows") / (batches * SERVE_MAX_BATCH_ROWS), 4)
    metrics["serve_shed_rate"] = round(delta("serve.shed") / reqs, 4)
    metrics["serve_host_routed"] = delta("serve.host_routed")


def run_electives(ratings_df, train, timings, flops, eng=None):
    """MLE 01 (block-parallel ALS on MovieLens-1M scale) and MLE 02
    (fused-Lloyd KMeans) — the electives' flagship distributed fits
    (`MLE 01:159-201` "CV takes a few minutes, refit ~1 minute";
    `MLE 02:38-57`)."""
    from sml_tpu.ml import Pipeline
    from sml_tpu.ml.clustering import KMeans
    from sml_tpu.ml.evaluation import RegressionEvaluator
    from sml_tpu.ml.feature import Imputer, VectorAssembler
    from sml_tpu.ml.recommendation import ALS

    rank, als_iters = 8, 10
    t0 = time.perf_counter()
    als_train, als_test = ratings_df.randomSplit([0.8, 0.2], seed=42)
    als = ALS(userCol="userId", itemCol="movieId", ratingCol="rating",
              rank=rank, maxIter=als_iters, regParam=0.1, seed=42,
              coldStartStrategy="drop")
    als_model = als.fit(als_train)
    rmse_als = RegressionEvaluator(labelCol="rating").evaluate(
        als_model.transform(als_test))
    timings["mle01_als"] = time.perf_counter() - t0
    if eng is not None:
        eng.mark("mle01_als")
    n_tr = als_train.count()  # the fit's actual nnz (80% split)
    flops["mle01_als"] = 2.0 * als_iters * (n_tr * rank * rank
                                            + (6040 + 3700) * rank ** 3)

    k, km_iters = 8, 20
    # feature prep happens OUTSIDE the timed region on both sides: the
    # host baseline times only sklearn's KMeans.fit on a prepared matrix
    imp = [c + "_imp" for c in NUM_COLS]
    km_feats = Pipeline(stages=[
        Imputer(strategy="median", inputCols=NUM_COLS, outputCols=imp),
        VectorAssembler(inputCols=imp, outputCol="features"),
    ]).fit(train).transform(train)
    km_feats.cache()
    km_feats.toPandas()  # concat memoized: prep ends with features READY,
    # matching the host side's prepared matrix (Xk built outside timing)
    t0 = time.perf_counter()
    km_model = KMeans(k=k, maxIter=km_iters, seed=221).fit(km_feats)
    centers = km_model.clusterCenters()
    timings["mle02_kmeans"] = time.perf_counter() - t0
    if eng is not None:
        eng.mark("mle02_kmeans")
    n_train = train.count()
    flops["mle02_kmeans"] = 3.0 * km_iters * n_train * len(NUM_COLS) * k
    return {"rmse_als": rmse_als, "kmeans_k": float(len(centers))}


def run_suite(df, n_rows, ratings_df=None, with_scale=True):
    from sml_tpu.ml import DeviceScorer, Pipeline
    from sml_tpu.ml.evaluation import RegressionEvaluator
    from sml_tpu.ml.feature import (Imputer, OneHotEncoder, StringIndexer,
                                    VectorAssembler)
    from sml_tpu.ml.regression import (DecisionTreeRegressor,
                                       LinearRegression,
                                       RandomForestRegressor)
    from sml_tpu.ml.tuning import CrossValidator, ParamGridBuilder
    from sml_tpu.tune import Trials, fmin, hp, tpe
    from sml_tpu.xgboost import XgboostRegressor

    timings = {}
    flops = {}
    eng = EngineCounterTrack()
    train, test = df.randomSplit([0.8, 0.2], seed=42)
    train.cache()
    test.cache()
    n_train = train.count()
    idx = [c + "_idx" for c in CAT_COLS]
    ohe = [c + "_ohe" for c in CAT_COLS]
    imp = [c + "_imp" for c in NUM_COLS]
    prep = [
        Imputer(strategy="median", inputCols=NUM_COLS, outputCols=imp),
        StringIndexer(inputCols=CAT_COLS, outputCols=idx, handleInvalid="skip"),
    ]
    ev = RegressionEvaluator(labelCol="price")

    # ---- ML 02/03: linear pipeline --------------------------------------
    t0 = time.perf_counter()
    lr_model = Pipeline(stages=prep + [
        OneHotEncoder(inputCols=idx, outputCols=ohe),
        VectorAssembler(inputCols=ohe + imp, outputCol="features"),
        LinearRegression(labelCol="price"),
    ]).fit(train)
    rmse_lr = ev.evaluate(lr_model.transform(test))
    timings["ml02_lr"] = time.perf_counter() - t0
    eng.mark("ml02_lr")
    d_lr = lr_model.stages[-1].coefficients.toArray().shape[0] + 1
    flops["ml02_lr"] = 2.0 * n_train * d_lr * d_lr  # Gram pass X^T X

    # ---- ML 06/07: single trees then the CV grid ------------------------
    tree_feats = VectorAssembler(inputCols=idx + imp, outputCol="features")
    t0 = time.perf_counter()
    dt_model = Pipeline(stages=prep + [tree_feats,
                        DecisionTreeRegressor(labelCol="price", maxDepth=5,
                                              maxBins=40)]).fit(train)
    rmse_dt = ev.evaluate(dt_model.transform(test))
    timings["ml06_dt"] = time.perf_counter() - t0
    eng.mark("ml06_dt")
    flops["ml06_dt"] = 2.0 * 1 * 5 * n_train * 10 * 40

    t0 = time.perf_counter()
    rf_model = Pipeline(stages=prep + [tree_feats,
                        RandomForestRegressor(labelCol="price", maxDepth=6,
                                              numTrees=20, maxBins=40,
                                              seed=42)]).fit(train)
    rmse_rf = ev.evaluate(rf_model.transform(test))
    timings["ml07_rf"] = time.perf_counter() - t0
    eng.mark("ml07_rf")
    # histogram builds: trees x levels x (rows x features x bins) one-hot
    # accumulations (ops, not dense MXU flops — reported for scale)
    flops["ml07_rf"] = 2.0 * 20 * 6 * n_train * 10 * 40

    # the ML 07 tuning shape: grid over maxDepth x numTrees, 3 seeded folds,
    # parallelism=4 (trials placed on disjoint submeshes)
    t0 = time.perf_counter()
    feat_train = Pipeline(stages=prep + [tree_feats]).fit(train) \
        .transform(train)
    feat_train.cache()
    rf = RandomForestRegressor(labelCol="price", maxBins=40, seed=42)
    grid = (ParamGridBuilder()
            .addGrid(rf.getParam("maxDepth"), [2, 5])
            .addGrid(rf.getParam("numTrees"), [10, 20]).build())
    cv = CrossValidator(estimator=rf, estimatorParamMaps=grid, evaluator=ev,
                        numFolds=3, parallelism=4, seed=42)
    cv_model = cv.fit(feat_train)
    timings["ml07_cv"] = time.perf_counter() - t0
    eng.mark("ml07_cv")
    cv_best = float(min(cv_model.avgMetrics))
    # 12 fold fits (3 folds x 2/3 of train each = 2n per param map) + one
    # full-train refit of the winner (approximated by the grid mean)
    _grid_td = [(int(pm[rf.getParam("numTrees")]),
                 int(pm[rf.getParam("maxDepth")])) for pm in grid]
    flops["ml07_cv"] = (
        sum(2.0 * t * d * 2.0 * n_train * 10 * 40 for t, d in _grid_td)
        + 2.0 * np.mean([t * d for t, d in _grid_td]) * n_train * 10 * 40)

    # ---- ML 08: TPE search, course budget of 4 evals --------------------
    t0 = time.perf_counter()
    space = {"max_depth": hp.quniform("max_depth", 2, 8, 1),
             "num_trees": hp.quniform("num_trees", 5, 25, 5)}

    def objective(params):
        m = RandomForestRegressor(labelCol="price", maxBins=40, seed=42,
                                  maxDepth=int(params["max_depth"]),
                                  numTrees=int(params["num_trees"])) \
            .fit(feat_train)
        return ev.evaluate(m.transform(feat_train))

    fmin(objective, space, algo=tpe, max_evals=4, trials=Trials(),
         rstate=np.random.RandomState(42))
    timings["ml08_hyperopt"] = time.perf_counter() - t0
    eng.mark("ml08_hyperopt")
    # 4 evals at the space's mean budget (maxDepth~5, numTrees~15)
    flops["ml08_hyperopt"] = 4 * 2.0 * 15 * 5 * n_train * 10 * 40

    # ---- ML 11: boosted trees, log-price --------------------------------
    from sml_tpu.frame import functions as F
    t0 = time.perf_counter()
    log_train = train.withColumn("label", F.log(F.col("price")))
    log_test = test.withColumn("label", F.log(F.col("price")))
    xgb_model = Pipeline(stages=prep + [tree_feats,
                         XgboostRegressor(n_estimators=40, learning_rate=0.15,
                                          max_depth=6, max_bins=64,
                                          random_state=42)]).fit(log_train)
    pred = xgb_model.transform(log_test).withColumn(
        "prediction", F.exp(F.col("prediction")))
    rmse_xgb = ev.evaluate(pred)
    timings["ml11_xgb"] = time.perf_counter() - t0
    eng.mark("ml11_xgb")
    flops["ml11_xgb"] = 2.0 * 40 * 6 * n_train * 10 * 64

    # ---- ML 12: batch inference through the device scorer ---------------
    # the lesson's own tuning knob (`ML 12:90,121`): larger Arrow batches
    # amortize per-batch dispatch — the factorized scorer streams 50k rows
    from sml_tpu.conf import GLOBAL_CONF as _CONF
    _old_bs = _CONF.get("spark.sql.execution.arrow.maxRecordsPerBatch")
    _CONF.set("spark.sql.execution.arrow.maxRecordsPerBatch", 50000)
    t0 = time.perf_counter()
    scorer = DeviceScorer(lr_model)

    def predict_batches(it):
        import pandas as pd
        for out in scorer.score_batches(it):
            yield pd.DataFrame({"prediction": out})

    n_scored = test.mapInPandas(predict_batches, "prediction double").count()
    timings["ml12_mapinpandas"] = time.perf_counter() - t0
    eng.mark("ml12_mapinpandas")
    _CONF.set("spark.sql.execution.arrow.maxRecordsPerBatch", _old_bs)
    flops["ml12_mapinpandas"] = 2.0 * n_scored * d_lr

    # ---- serving: closed-loop online micro-batched scoring --------------
    serve_metrics = {}
    run_serving_leg(lr_model, test, timings, flops, serve_metrics, eng)

    # ---- ML 13: per-group training fan-out ------------------------------
    t0 = time.perf_counter()

    def train_group(pdf):
        import pandas as pd
        from sklearn.linear_model import LinearRegression as SkLR
        cols = ["accommodates", "bedrooms"]
        g = pdf.dropna(subset=cols + ["price"])
        if len(g) < 5:
            return pd.DataFrame({"room_type": [pdf["room_type"].iloc[0]],
                                 "n": [len(g)], "mse": [float("nan")]})
        m = SkLR().fit(g[cols], g["price"])
        mse = float(np.mean((m.predict(g[cols]) - g["price"]) ** 2))
        return pd.DataFrame({"room_type": [g["room_type"].iloc[0]],
                             "n": [len(g)], "mse": [mse]})

    n_groups = train.groupby("room_type").applyInPandas(
        train_group, "room_type string, n bigint, mse double").count()
    timings["ml13_applyinpandas"] = time.perf_counter() - t0
    eng.mark("ml13_applyinpandas")
    # per-group sklearn LR payload (host math by course design, `ML 13`)
    flops["ml13_applyinpandas"] = 2.0 * n_train * 2 * 2

    metrics = {"rmse_lr": rmse_lr, "rmse_dt": rmse_dt, "rmse_rf": rmse_rf,
               "rmse_xgb": rmse_xgb, "cv_best_rmse": cv_best,
               "rows_scored": n_scored, "groups": n_groups}
    metrics.update(serve_metrics)
    if ratings_df is not None:
        metrics.update(run_electives(ratings_df, train, timings, flops, eng))
    if with_scale:
        run_scale_leg(timings, flops, metrics, eng)
    return timings, metrics, flops, eng.legs


def _host_als(ratings, rank, iters, reg, seed=42):
    """Efficient single-node numpy ALS (the honest host anchor — sklearn
    has no ALS): per-side normal equations accumulated with sorted
    reduceat segment sums, batched np.linalg.solve, ALS-WR reg."""
    users = ratings["userId"].to_numpy(np.int64)
    items = ratings["movieId"].to_numpy(np.int64)
    r = ratings["rating"].to_numpy(np.float32)
    n_u, n_i = users.max() + 1, items.max() + 1
    rng = np.random.default_rng(seed)
    U = rng.normal(0, 0.1, (n_u, rank)).astype(np.float32)
    V = rng.normal(0, 0.1, (n_i, rank)).astype(np.float32)

    def half(ids, n_out, other_rows, rr):
        order = np.argsort(ids, kind="stable")
        ids_s = ids[order]
        F = other_rows[order]
        rs = rr[order]
        starts = np.minimum(np.searchsorted(ids_s, np.arange(n_out)),
                            max(len(F) - 1, 0))
        outer = (F[:, :, None] * F[:, None, :]).reshape(len(F), -1)
        A = np.add.reduceat(outer, starts, axis=0).reshape(n_out, rank, rank)
        b = np.add.reduceat(F * rs[:, None], starts, axis=0)
        cnt = np.bincount(ids_s, minlength=n_out).astype(np.float32)
        # reduceat yields a bogus single element for empty segments: zero
        empty = cnt == 0
        A[empty] = 0.0
        b[empty] = 0.0
        lam = reg * np.maximum(cnt, 1.0)
        A = A + lam[:, None, None] * np.eye(rank, dtype=np.float32)[None]
        sol = np.linalg.solve(A, b[:, :, None])[:, :, 0]
        sol[empty] = 0.0
        return sol.astype(np.float32)

    for _ in range(iters):
        U = half(users, n_u, V[items], r)
        V = half(items, n_i, U[users], r)
    return U, V


# ---------------------------------------------------------------- host baseline
def run_host_baseline(pdf, ratings_pdf=None, only=None):
    """The SAME legs executed the single-node pandas/sklearn way — the
    measured anchor for vs_baseline (replaces r1's invented constant).
    `only` restricts to a subset of leg names (the per-run re-measure of
    cheap legs); None measures everything."""
    import pandas as pd
    from sklearn.ensemble import (HistGradientBoostingRegressor,
                                  RandomForestRegressor as SkRF)
    from sklearn.linear_model import LinearRegression as SkLR
    from sklearn.model_selection import GridSearchCV, train_test_split
    from sklearn.tree import DecisionTreeRegressor as SkDT

    def want(leg):
        return only is None or leg in only

    timings = {}
    work = pdf.copy()
    for c in NUM_COLS:
        work[c] = pd.to_numeric(work[c], errors="coerce")
        work[c] = work[c].fillna(work[c].median())
    train, test = train_test_split(work, test_size=0.2, random_state=42)

    def featurize(frame, ohe):
        X = pd.get_dummies(frame[CAT_COLS], dtype=float) if ohe else \
            frame[CAT_COLS].apply(lambda s: s.astype("category").cat.codes)
        return pd.concat([X, frame[NUM_COLS]], axis=1).to_numpy(np.float64)

    m = None
    if want("ml02_lr") or want("ml12_mapinpandas") or want("serving"):
        t0 = time.perf_counter()
        Xtr, Xte = featurize(train, True), featurize(test, True)
        m = SkLR().fit(Xtr, train["price"])
        float(np.sqrt(np.mean((m.predict(Xte) - test["price"]) ** 2)))
        if want("ml02_lr"):
            timings["ml02_lr"] = time.perf_counter() - t0

    # featurization happens inside the leg, as in the framework leg (every
    # Pipeline.fit re-featurizes); later legs reuse the matrices, which
    # only favors the host baseline
    need_tree = any(want(k) for k in
                    ("ml06_dt", "ml07_rf", "ml07_cv", "ml08_hyperopt",
                     "ml11_xgb"))
    if need_tree:
        t0 = time.perf_counter()
        Xtr_t, Xte_t = featurize(train, False), featurize(test, False)
        if want("ml06_dt"):
            SkDT(max_depth=5).fit(Xtr_t, train["price"]).predict(Xte_t)
            timings["ml06_dt"] = time.perf_counter() - t0

    if want("ml07_rf"):
        t0 = time.perf_counter()
        SkRF(max_depth=6, n_estimators=20, random_state=42, n_jobs=-1) \
            .fit(Xtr_t, train["price"]).predict(Xte_t)
        timings["ml07_rf"] = time.perf_counter() - t0

    if want("ml07_cv"):
        t0 = time.perf_counter()
        gs = GridSearchCV(SkRF(random_state=42, n_jobs=-1),
                          {"max_depth": [2, 5], "n_estimators": [10, 20]},
                          cv=3, scoring="neg_root_mean_squared_error",
                          n_jobs=1)
        gs.fit(Xtr_t, train["price"])
        timings["ml07_cv"] = time.perf_counter() - t0

    if want("ml08_hyperopt"):
        t0 = time.perf_counter()
        rng = np.random.RandomState(42)
        for _ in range(4):  # 4-eval random/TPE-budget search (ML 08:146)
            SkRF(max_depth=int(rng.randint(2, 9)),
                 n_estimators=int(rng.choice([5, 10, 15, 20, 25])),
                 random_state=42, n_jobs=-1).fit(Xtr_t, train["price"]) \
                .predict(Xtr_t)
        timings["ml08_hyperopt"] = time.perf_counter() - t0

    if want("ml11_xgb"):
        t0 = time.perf_counter()
        hp = HistGradientBoostingRegressor(max_iter=40, learning_rate=0.15,
                                           max_depth=6, max_bins=64,
                                           random_state=42) \
            .fit(Xtr_t, np.log(train["price"])).predict(Xte_t)
        # same work as the framework leg: exp back to price scale + rmse
        float(np.sqrt(np.mean((np.exp(hp) - test["price"]) ** 2)))
        timings["ml11_xgb"] = time.perf_counter() - t0

    if want("ml12_mapinpandas"):
        # like the course's pyfunc (`ML 12:101-143`) and the framework leg,
        # the scorer featurizes each raw batch before predicting (with a
        # stable dummy-column layout, as a persisted pyfunc would)
        dummy_cols = pd.get_dummies(test[CAT_COLS], dtype=float).columns

        def featurize_batch(b):
            X = pd.get_dummies(b[CAT_COLS], dtype=float).reindex(
                columns=dummy_cols, fill_value=0.0)
            return pd.concat([X, b[NUM_COLS]], axis=1).to_numpy(np.float64)

        t0 = time.perf_counter()
        bs = 10_000  # the arrow batch size the framework leg streams at
        preds = [m.predict(featurize_batch(test.iloc[lo:lo + bs]))
                 for lo in range(0, len(test), bs)]
        np.concatenate(preds)
        timings["ml12_mapinpandas"] = time.perf_counter() - t0

    if want("serving"):
        # the no-batching anchor: the SAME closed-loop request stream
        # scored one request at a time (sklearn predict per request) —
        # what an endpoint without coalescing pays
        Xs = featurize(test, True)[:SERVE_REQUESTS * SERVE_REQUEST_ROWS]
        t0 = time.perf_counter()
        for lo in range(0, len(Xs), SERVE_REQUEST_ROWS):
            m.predict(Xs[lo:lo + SERVE_REQUEST_ROWS])
        timings["serving"] = time.perf_counter() - t0

    if want("ml13_applyinpandas"):
        # the framework leg groups the RAW train frame (NaNs intact, so the
        # fn's dropna drops ~24k real rows — 3% bedrooms NaN); the host side
        # must too — grouping the pre-imputed `train` made its dropna a
        # no-op and the baseline ~1.7x faster than the same loop on equal
        # data (r4 fairness fix). Same rows as `train` by construction:
        # select the split's surviving indices from the raw frame.
        raw_train = pdf.loc[train.index]
        t0 = time.perf_counter()
        for _, g in raw_train.groupby("room_type"):
            g = g.dropna(subset=["accommodates", "bedrooms", "price"])
            if len(g) >= 5:
                gm = SkLR().fit(g[["accommodates", "bedrooms"]], g["price"])
                float(np.mean((gm.predict(g[["accommodates", "bedrooms"]])
                               - g["price"]) ** 2))
        timings["ml13_applyinpandas"] = time.perf_counter() - t0

    if ratings_pdf is not None and want("mle01_als"):
        rng = np.random.RandomState(42)
        tr_mask = rng.rand(len(ratings_pdf)) < 0.8
        t0 = time.perf_counter()
        U, V = _host_als(ratings_pdf[tr_mask], rank=8, iters=10, reg=0.1)
        te = ratings_pdf[~tr_mask]
        pred = np.sum(U[te["userId"].to_numpy(np.int64)]
                      * V[te["movieId"].to_numpy(np.int64)], axis=1)
        float(np.sqrt(np.mean((pred - te["rating"].to_numpy(np.float64))
                              ** 2)))
        timings["mle01_als"] = time.perf_counter() - t0

    if want("mle02_kmeans"):
        from sklearn.cluster import KMeans as SkKMeans
        t0 = time.perf_counter()
        Xk = train[NUM_COLS].to_numpy(np.float64)
        SkKMeans(n_clusters=8, init="k-means++", n_init=1, max_iter=20,
                 random_state=221).fit(Xk)
        timings["mle02_kmeans"] = time.perf_counter() - t0

    if want("ml_scale"):
        # same prepared features as the device side (build_scale_parts),
        # expanded to the dense matrix sklearn operates on; same model
        # budgets (lstsq LR; logistic at SCALE_LOGIT_ITERS)
        from sklearn.linear_model import LogisticRegression as SkLogit
        parts, yp, yl = build_scale_parts()
        Xs = parts.expand_host()
        t0 = time.perf_counter()
        SkLR().fit(Xs, yp)
        SkLogit(max_iter=SCALE_LOGIT_ITERS, solver="lbfgs").fit(Xs, yl)
        timings["ml_scale"] = time.perf_counter() - t0
        del Xs

    return timings


def get_host_baseline(pdf, ratings_pdf=None):
    if os.path.exists(BASELINE_CACHE):
        with open(BASELINE_CACHE) as f:
            cached = json.load(f)
        if cached.get("n_rows") == N_ROWS and \
                cached.get("legs_version") == LEGS_VERSION:
            return cached["timings"]
    print("measuring single-node host baseline (cached afterwards)...",
          file=sys.stderr)
    timings = run_host_baseline(pdf, ratings_pdf)
    with open(BASELINE_CACHE, "w") as f:
        json.dump({"n_rows": N_ROWS, "legs_version": LEGS_VERSION,
                   "timings": timings,
                   "note": "single-node pandas/sklearn execution of the same "
                           "legs on the same host; measured, not assumed"},
                  f, indent=1)
    return timings


# ----------------------------------------------------------------- probes
_probe_state: dict = {}


def probe():
    """Co-tenant/interference probe (VERDICT r4 #4): a fixed tiny device
    round-trip and a fixed host numpy workload, best-of-3 each. Taken
    around every timed pass; a session whose BEST probes sit far above
    the session minimum is flagged instead of silently reported."""
    import jax
    import jax.numpy as jnp
    if "fn" not in _probe_state:
        # graftlint: disable=dispatch-bypass -- interference probe: must measure the raw dispatch round trip untouched by routing, caches, or the audit
        _probe_state["fn"] = jax.jit(lambda x: (x @ x).sum())
        _probe_state["x"] = jax.device_put(
            np.eye(64, dtype=np.float32), jax.devices()[0])
        _probe_state["host_a"] = np.random.default_rng(0).normal(
            size=(2_000_000,))
        jax.device_get(_probe_state["fn"](_probe_state["x"]))  # compile
    dev_ms = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.device_get(_probe_state["fn"](_probe_state["x"]))
        dev_ms.append((time.perf_counter() - t0) * 1e3)
    host_ms = []
    a = _probe_state["host_a"]
    for _ in range(3):
        t0 = time.perf_counter()
        float((a * a).sum())
        np.linalg.lstsq(np.outer(a[:200], a[:200]) + np.eye(200),
                        a[:200], rcond=None)
        host_ms.append((time.perf_counter() - t0) * 1e3)
    return {"device_ms": round(min(dev_ms), 2),
            "host_ms": round(min(host_ms), 2)}


def second_fit_probe(train):
    """Quantized-engine acceptance probe: two IDENTICAL-shape XGBoost fits
    in this (so-far tree-cold) process. Fit 1 pays python trace, XLA
    compile (or persistent-cache load), host binning, and H2D staging;
    fit 2 must ride the compiled-program cache, the quantized bin-index
    cache, and the staged device buffers — the engine's whole reuse story
    in one number. Run BEFORE the warmup passes so fit 1 is genuinely
    cold for the boosting path."""
    from sml_tpu.frame import functions as F
    from sml_tpu.ml import Pipeline
    from sml_tpu.ml.feature import Imputer, StringIndexer, VectorAssembler
    from sml_tpu.xgboost import XgboostRegressor

    idx = [c + "_idx" for c in CAT_COLS]
    imp = [c + "_imp" for c in NUM_COLS]
    labeled = train.withColumn("label", F.log(F.col("price")))
    feats = Pipeline(stages=[
        Imputer(strategy="median", inputCols=NUM_COLS, outputCols=imp),
        StringIndexer(inputCols=CAT_COLS, outputCols=idx,
                      handleInvalid="skip"),
        VectorAssembler(inputCols=idx + imp, outputCol="features"),
    ]).fit(labeled).transform(labeled)
    feats.cache()
    feats.toPandas()  # featurization outside both timed fits
    est = XgboostRegressor(n_estimators=40, learning_rate=0.15, max_depth=6,
                           max_bins=64, random_state=42)
    t0 = time.perf_counter()
    est.fit(feats)
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    est.fit(feats)
    second = time.perf_counter() - t0
    # release the probe's cached frame before the timed legs (the warm
    # bin-cache/program entries it leaves behind are the point; a pinned
    # 800k-row featurized frame is not)
    feats.unpersist()
    out = {"first_fit_s": round(first, 3), "second_fit_s": round(second, 3),
           "speedup": round(first / max(second, 1e-9), 2)}
    print(f"second-fit probe (identical-shape XGBoost): {out}",
          file=sys.stderr)
    return out


# ------------------------------------------------------------- multichip leg
MULTICHIP_ROWS = 200_000
MULTICHIP_TREES = 20
MULTICHIP_DEPTH = 6
MULTICHIP_BINS = 32


def run_multichip(rows: int = MULTICHIP_ROWS) -> dict:
    """`--multichip`: the fit-throughput SCALING leg (ISSUE 6) — the same
    bootstrap-forest fit executed on 1, 2, 4, ... device meshes over the
    live device set, with the quantized bin matrix row-sharded per mesh
    and every histogram merge a `psum` over the mesh's data axis.

    Per width the leg records: best-of-3 warm fit seconds (compile +
    staging paid in a warmup fit), fit throughput, speedup vs the
    1-device mesh, the per-trace collective launch/byte counters (the
    ICI allreduce volume one program carries — captured from the warmup
    trace, since collectives are counted at TRACE time), and a model
    PARITY check against the 1-device fit (sampling draws are
    mesh-layout-invariant, so every width must produce the same forest
    up to float reduction order).

    On a 1-device host this degenerates to a single row honestly; the
    committed MULTICHIP artifact runs it under the simulated 8-device
    CPU mesh (`XLA_FLAGS=--xla_force_host_platform_device_count=8`),
    where "scaling" measures the engine's dispatch structure, not real
    ICI — real-chip numbers come from running the same flag on a pod
    slice. Results merge into the bench sidecar as the `multichip`
    block, rendered by scripts/render_perf.py."""
    import jax
    import jax.numpy as jnp

    from sml_tpu import obs
    from sml_tpu.conf import GLOBAL_CONF
    from sml_tpu.ml import tree_impl
    from sml_tpu.ml._tree_models import _fit_ensemble
    from sml_tpu.parallel import mesh as meshlib

    n_avail = len(jax.devices())
    widths = [w for w in (1, 2, 4, 8, 16, 32, 64) if w <= n_avail]
    rng = np.random.default_rng(42)
    F = 10
    X = rng.normal(size=(rows, F)).astype(np.float32)
    y = (X[:, 0] * 3 - X[:, 1] ** 2 + 0.5 * X[:, 2]
         + rng.normal(0, 0.3, rows)).astype(np.float32)
    probe = X[:4096]

    prev_obs = GLOBAL_CONF.get("sml.obs.enabled")
    GLOBAL_CONF.set("sml.obs.enabled", True)
    entries = []
    ref_pred = None
    straggler = None
    try:
        for w in widths:
            mesh = meshlib.build_mesh(w)
            with meshlib.use_mesh(mesh):
                def fit():
                    return _fit_ensemble(
                        X, y, categorical={}, max_depth=MULTICHIP_DEPTH,
                        max_bins=MULTICHIP_BINS, min_instances=1,
                        min_info_gain=0.0, n_trees=MULTICHIP_TREES,
                        feature_k=None, bootstrap=True, subsample=1.0,
                        seed=42, loss="squared")

                obs.reset()
                spec = fit()  # warmup: compile + bin + stage + trace
                coll = obs.RECORDER.counters()
                best = float("inf")
                for _ in range(3):
                    t0 = time.perf_counter()
                    fit()
                    best = min(best, time.perf_counter() - t0)
                pred = spec.predict_margin(probe)
                # per-device straggler attribution (obs/_skew.py): time
                # the same per-shard reduction on EACH chip's resident
                # bin block (best-of-3) — the per-chip compute profile
                # the BSP decomposition splits into compute vs
                # collective-wait, rendered as per-device trace lanes
                staged = tree_impl.stage_tree_data(
                    X, y, max_bins=MULTICHIP_BINS)
                # group-aware iteration (host_row_blocks, not the flat
                # addressable list): on a hierarchical mesh each probe
                # carries its device's host-group id, so the timings
                # feed the per-HOST skew lanes next to the per-chip
                # ones; on a flat mesh every device is group 0 and the
                # host roll-up degenerates harmlessly
                blocks = [(g, dev, blk)
                          for g, devblks in meshlib.host_row_blocks(
                              staged.binned_dev, mesh)
                          for dev, blk in devblks]
                # graftlint: disable=dispatch-bypass -- skew probe: must time ONE chip's shard in isolation, untouched by routing or the mesh (a dispatched program would re-shard the block)
                probe_fn = jax.jit(
                    lambda b: (b.astype(jnp.float32) ** 2).sum(axis=0))
                jax.block_until_ready(probe_fn(blocks[0][2]))  # compile
                shard_walls = []
                for _g, _dev, blk in blocks:
                    bw = float("inf")
                    for _ in range(3):
                        t0 = time.perf_counter()
                        jax.block_until_ready(probe_fn(blk))
                        bw = min(bw, time.perf_counter() - t0)
                    shard_walls.append(bw)
                attr = obs.SKEW.note(
                    f"multichip_{w}dev", shard_walls,
                    devices=[d.id for _, d, _ in blocks],
                    hosts=[g for g, _, _ in blocks], wall_s=best,
                    psum_bytes=coll.get("collective.psum_bytes", 0.0),
                    psum_launches=coll.get("collective.psum", 0.0))
                straggler = obs.straggler_report()
            if ref_pred is None:
                ref_pred = pred
            parity = bool(np.allclose(pred, ref_pred, rtol=1e-4, atol=1e-4))
            entries.append({
                "devices": w,
                "seconds": round(best, 4),
                "rows_per_s": round(rows / best, 1),
                "speedup_vs_1": round(entries[0]["seconds"] / best, 3)
                if entries else 1.0,
                "collective_psum": int(coll.get("collective.psum", 0)),
                "collective_psum_bytes":
                    float(coll.get("collective.psum_bytes", 0.0)),
                "parity_vs_1": parity,
                "skew": None if attr is None else {
                    "slowest_device": int(attr["slowest_device"]),
                    "skew_ratio": round(attr["skew_ratio"], 4),
                    "wait_share": round(attr["wait_share"], 4),
                    "per_device_compute_ms": [round(c * 1e3, 4)
                                              for c in shard_walls],
                },
            })
            print(f"  multichip {w}d: {best:.3f}s "
                  f"({rows / best:,.0f} rows/s, "
                  f"psum {coll.get('collective.psum_bytes', 0) / 1e6:.2f} "
                  f"MB/trace, parity={parity}, skew "
                  f"{entries[-1]['skew']['skew_ratio'] if entries[-1]['skew'] else '-'}"
                  f")", file=sys.stderr)
    finally:
        GLOBAL_CONF.set("sml.obs.enabled", bool(prev_obs))
    return {
        "rows": rows, "n_features": F, "n_trees": MULTICHIP_TREES,
        "max_depth": MULTICHIP_DEPTH, "max_bins": MULTICHIP_BINS,
        "backend": jax.default_backend(), "n_devices": n_avail,
        "note": "best-of-3 warm fits per mesh width; collective counters "
                "are per-TRACE statics (multiply by executions for wire "
                "traffic); parity_vs_1 = same forest as the 1-device "
                "mesh (layout-invariant sampling); skew = per-device "
                "straggler attribution from per-shard compute probes "
                "(obs/_skew.py, docs/OBSERVABILITY.md)",
        "widths": entries,
        # aggregate straggler attribution for the WIDEST mesh (obs.reset
        # runs per width, so the tracker holds the last width's notes)
        "straggler": straggler,
    }


def multichip_main(rows: int) -> None:
    """Run the scaling leg standalone, merge the `multichip` block into
    the bench sidecar, and print the short headline JSON last."""
    block = run_multichip(rows)
    doc = {}
    if os.path.exists(LEGS_FILE):
        with open(LEGS_FILE) as f:
            doc = json.load(f)
    doc["multichip"] = block
    with open(LEGS_FILE, "w") as f:
        json.dump(doc, f, indent=1)
    best = max(e["speedup_vs_1"] for e in block["widths"])
    straggler = block.get("straggler") or {}
    print(json.dumps({
        "metric": "multichip fit-throughput scaling",
        "value": best,
        "unit": "x vs 1 device",
        "n_devices": block["n_devices"],
        "backend": block["backend"],
        "parity_ok": all(e["parity_vs_1"] for e in block["widths"]),
        "straggler_device": straggler.get("slowest_device"),
        "skew_ratio": straggler.get("skew_ratio"),
        "legs_file": "bench_legs.json",
    }))


# ------------------------------------------------------------ multihost leg
MULTIHOST_ROWS = 100_000


def run_multihost(rows: int = MULTIHOST_ROWS) -> dict:
    """`--multihost`: the DCN-aware hierarchical-collective leg (ISSUE
    20) — the same boosted fit executed on 1..H virtual-host meshes
    (`parallel.mesh.host_mesh`: the 8-device sim partitioned into host
    groups, `jax.process_index()` slices on a real pod), with every
    histogram merge a two-level `psum_hierarchical` (intra-group
    reduce-scatter over "ici", inter-group allreduce over "dcn",
    allgather back) instead of one flat allreduce.

    Per host-group shape the leg records: best-of-3 warm fit seconds
    and rows/s, the PER-HOP collective launch/byte statics
    (`collective.psum.ici/.dcn`, `collective.psum_bytes.ici/.dcn` —
    trace-time counts, like the multichip block), the DCN byte fraction
    vs the flat-mesh allreduce payload (the whole point: the cross-host
    hop must carry ~payload/ici_size, not the full payload), model
    parity vs the 1-host-group fit (layout-invariant sampling), and a
    per-HOST skew table from group-aware per-shard compute probes
    (obs/_skew.py host lanes). Merges into the bench sidecar as the
    `multihost` block; obs/regress.py judges DCN-byte growth, lost
    parity, and a vanished skew table as regressions."""
    import jax
    import jax.numpy as jnp

    from sml_tpu import obs
    from sml_tpu.conf import GLOBAL_CONF
    from sml_tpu.ml import tree_impl
    from sml_tpu.ml._tree_models import _fit_ensemble
    from sml_tpu.parallel import mesh as meshlib

    n_avail = len(jax.devices())
    shapes = [h for h in (1, 2, 4, 8, 16)
              if h <= n_avail and n_avail % h == 0]
    rng = np.random.default_rng(42)
    F = 10
    X = rng.normal(size=(rows, F)).astype(np.float32)
    y = (X[:, 0] * 3 - X[:, 1] ** 2 + 0.5 * X[:, 2]
         + rng.normal(0, 0.3, rows)).astype(np.float32)
    probe = X[:4096]

    def fit():
        return _fit_ensemble(
            X, y, categorical={}, max_depth=MULTICHIP_DEPTH,
            max_bins=MULTICHIP_BINS, min_instances=1, min_info_gain=0.0,
            n_trees=MULTICHIP_TREES, feature_k=None, bootstrap=True,
            subsample=1.0, seed=42, loss="squared")

    prev_obs = GLOBAL_CONF.get("sml.obs.enabled")
    GLOBAL_CONF.set("sml.obs.enabled", True)
    entries = []
    ref_pred = None
    straggler = None
    try:
        # flat-mesh reference: the single-hop allreduce payload every
        # DCN fraction below is judged against
        with meshlib.use_mesh(meshlib.build_mesh(n_avail)):
            obs.reset()
            fit()
            flat_bytes = float(obs.RECORDER.counters()
                               .get("collective.psum_bytes", 0.0))
        for h in shapes:
            mesh = meshlib.host_mesh(h)
            per = n_avail // h
            with meshlib.use_mesh(mesh):
                obs.reset()
                spec = fit()  # warmup: compile + bin + stage + trace
                coll = obs.RECORDER.counters()
                best = float("inf")
                for _ in range(3):
                    t0 = time.perf_counter()
                    fit()
                    best = min(best, time.perf_counter() - t0)
                pred = spec.predict_margin(probe)
                # per-host straggler attribution: same per-shard compute
                # probe as the multichip leg, iterated GROUP-AWARE so
                # each timing carries its host id and the tracker's
                # host lanes + slowest-host roll-up light up
                staged = tree_impl.stage_tree_data(
                    X, y, max_bins=MULTICHIP_BINS)
                blocks = [(g, dev, blk)
                          for g, devblks in meshlib.host_row_blocks(
                              staged.binned_dev, mesh)
                          for dev, blk in devblks]
                # graftlint: disable=dispatch-bypass -- skew probe: must time ONE chip's shard in isolation, untouched by routing or the mesh (a dispatched program would re-shard the block)
                probe_fn = jax.jit(
                    lambda b: (b.astype(jnp.float32) ** 2).sum(axis=0))
                jax.block_until_ready(probe_fn(blocks[0][2]))  # compile
                shard_walls = []
                for _g, _dev, blk in blocks:
                    bw = float("inf")
                    for _ in range(3):
                        t0 = time.perf_counter()
                        jax.block_until_ready(probe_fn(blk))
                        bw = min(bw, time.perf_counter() - t0)
                    shard_walls.append(bw)
                attr = obs.SKEW.note(
                    f"multihost_{h}x{per}", shard_walls,
                    devices=[d.id for _, d, _ in blocks],
                    hosts=[g for g, _, _ in blocks], wall_s=best,
                    psum_bytes=coll.get("collective.psum_bytes.dcn", 0.0),
                    psum_launches=coll.get("collective.psum.dcn", 0.0))
                straggler = obs.straggler_report()
            if ref_pred is None:
                ref_pred = pred
            parity = bool(np.allclose(pred, ref_pred, rtol=1e-4, atol=1e-4))
            dcn_b = float(coll.get("collective.psum_bytes.dcn", 0.0))
            ici_b = float(coll.get("collective.psum_bytes.ici", 0.0))
            # the acceptance bound: the cross-host hop may carry at most
            # the inter-group fraction (payload / ici_size) of the flat
            # allreduce's bytes — 1% slack covers padding-to-ici_size
            dcn_ok = (dcn_b <= flat_bytes / per * 1.01 + 1024
                      if dcn_b and flat_bytes else None)
            host_skew = None
            if attr is not None and attr.get("host_ids"):
                host_skew = [{"host": int(g),
                              "compute_ms": round(c * 1e3, 4)}
                             for g, c in zip(attr["host_ids"],
                                             attr["per_host_compute_s"])]
            entries.append({
                "hosts": h,
                "per_host": per,
                "seconds": round(best, 4),
                "rows_per_s": round(rows / best, 1),
                "speedup_vs_1": round(entries[0]["seconds"] / best, 3)
                if entries else 1.0,
                "psum_ici": int(coll.get("collective.psum.ici", 0)),
                "psum_dcn": int(coll.get("collective.psum.dcn", 0)),
                "psum_bytes_ici": ici_b,
                "psum_bytes_dcn": dcn_b,
                "all_gather_bytes_ici":
                    float(coll.get("collective.all_gather_bytes.ici", 0.0)),
                "dcn_fraction": round(dcn_b / flat_bytes, 4)
                if dcn_b and flat_bytes else None,
                "dcn_le_flat_fraction": dcn_ok,
                "parity_ok": parity,
                "slowest_host": None if attr is None
                else attr.get("slowest_host"),
                "host_skew": host_skew,
            })
            e = entries[-1]
            print(f"  multihost {h}x{per}: {best:.3f}s "
                  f"({rows / best:,.0f} rows/s, dcn "
                  f"{dcn_b / 1e3:.2f} KB/trace "
                  f"[{e['dcn_fraction'] if e['dcn_fraction'] is not None else '-'}"
                  f" of flat], parity={parity}, "
                  f"slowest_host={e['slowest_host']})", file=sys.stderr)
    finally:
        GLOBAL_CONF.set("sml.obs.enabled", bool(prev_obs))
    return {
        "rows": rows, "n_features": F, "n_trees": MULTICHIP_TREES,
        "max_depth": MULTICHIP_DEPTH, "max_bins": MULTICHIP_BINS,
        "backend": jax.default_backend(), "n_devices": n_avail,
        "flat_psum_bytes": flat_bytes,
        "note": "best-of-3 warm fits per host-group shape; per-hop "
                "collective counters are per-TRACE statics; "
                "dcn_fraction = the cross-host hop's psum bytes as a "
                "fraction of the flat allreduce payload (bounded by "
                "1/per_host — the hierarchical-allreduce win); "
                "parity_ok = same model as the 1-host-group mesh "
                "(layout-invariant sampling); host_skew = per-host "
                "compute attribution from group-aware shard probes "
                "(obs/_skew.py host lanes)",
        "shapes": entries,
        # aggregate straggler attribution for the LAST shape (obs.reset
        # runs per shape): includes the host-level roll-up
        "straggler": straggler,
    }


def multihost_main(rows: int) -> None:
    """Run the multi-host leg standalone, merge the `multihost` block
    into the bench sidecar, and print the short headline JSON last."""
    block = run_multihost(rows)
    doc = {}
    if os.path.exists(LEGS_FILE):
        with open(LEGS_FILE) as f:
            doc = json.load(f)
    doc["multihost"] = block
    with open(LEGS_FILE, "w") as f:
        json.dump(doc, f, indent=1)
    fracs = [e["dcn_fraction"] for e in block["shapes"]
             if e.get("dcn_fraction")]
    straggler = block.get("straggler") or {}
    print(json.dumps({
        "metric": "multihost DCN-byte fraction (hierarchical vs flat)",
        "value": min(fracs) if fracs else None,
        "unit": "x of flat allreduce payload (cross-host hop)",
        "n_devices": block["n_devices"],
        "backend": block["backend"],
        "parity_ok": all(e["parity_ok"] for e in block["shapes"]),
        "dcn_bound_ok": all(e["dcn_le_flat_fraction"] in (True, None)
                            for e in block["shapes"]),
        "slowest_host": straggler.get("slowest_host"),
        "host_skew_ratio": straggler.get("host_skew_ratio"),
        "legs_file": "bench_legs.json",
    }))


# ------------------------------------------- kernelbench inference autotuner
KERNELBENCH_ROWS = 60_000
KERNELBENCH_TREES = 8
KERNELBENCH_INFER_SHAPES = ((32, 4), (128, 6))   # (maxBins, maxDepth)
KERNELBENCH_INFER_BATCHES = (8192, 49152)        # scoring batch widths
KERNELBENCH_INFER_BLOCKS = (512, 2048, 8192)     # pallas block_rows sweep


def run_kernelbench_infer(rows: int = KERNELBENCH_ROWS) -> dict:
    """`--kernelbench` tentpole 2 (ISSUE 12): the traversal-kernel
    AUTOTUNER. For each (model shape, maxBins, batch width) point, sweep
    the candidate traversal specs — the XLA where-sum path plus the
    fused `native/traverse_kernel.py` launch at several `block_rows`
    schemes (the conf default `sml.infer.kernelBlockRows` among them) —
    best-of-3 warm scoring dispatches apiece, then PERSIST the winner
    into the prewarm manifest (`parallel.prewarm.record_tuned`), so
    replica spin-up and later processes resolve the tuned spec without
    re-sweeping (`sml.infer.autotune`).

    Every candidate's predictions are checked bit-identical against the
    XLA path (the interpret-mode contract on non-TPU backends, where
    these walls measure emulation overhead, not kernel speed — the
    `interpret` flag says which kind of run this is). `replay_ok` proves
    the round trip: with the sweep conf restored, the live resolver
    returns each point's persisted winner from the manifest alone, and
    the `infer_kernel` prewarm rebuilder replays one entry clean.
    Results merge into the sidecar as the `kernel_infer` block,
    rendered by scripts/render_perf.py; `obs/regress.py` flags a
    vanished block, fallback growth, or a lost beats-default/replay
    proof."""
    import jax

    from sml_tpu import obs
    from sml_tpu.conf import GLOBAL_CONF
    from sml_tpu.ml import inference, tree_impl
    from sml_tpu.ml._tree_models import _fit_ensemble
    from sml_tpu.parallel import prewarm
    from sml_tpu.utils.profiler import PROFILER

    rng = np.random.default_rng(11)
    F = 10
    n_fit = min(rows, 60_000)
    X = rng.normal(size=(n_fit, F)).astype(np.float32)
    y = (X[:, 0] * 2 - X[:, 1] ** 2 + 0.3 * X[:, 3]
         + rng.normal(0, 0.3, n_fit)).astype(np.float32)
    Xs = rng.normal(size=(max(KERNELBENCH_INFER_BATCHES), F)) \
        .astype(np.float32)

    prev = {k: GLOBAL_CONF.get(k) for k in
            ("sml.obs.enabled", "sml.infer.kernel",
             "sml.infer.kernelBlockRows", "sml.infer.autotune")}
    GLOBAL_CONF.set("sml.obs.enabled", True)
    GLOBAL_CONF.set("sml.infer.autotune", False)  # sweep forces specs
    default_rows = int(prev["sml.infer.kernelBlockRows"])
    legs = []
    tuned = []
    t_sweep0 = time.perf_counter()
    obs.reset()
    try:
        for max_bins, max_depth in KERNELBENCH_INFER_SHAPES:
            spec = _fit_ensemble(
                X, y, categorical={}, max_depth=max_depth,
                max_bins=max_bins, min_instances=1, min_info_gain=0.0,
                n_trees=KERNELBENCH_TREES, feature_k=None, bootstrap=True,
                subsample=1.0, seed=7, loss="squared")
            sf, sb, lv, w = spec.stacked()
            for batch in KERNELBENCH_INFER_BATCHES:
                binned = tree_impl.bin_with(
                    np.asarray(Xs[:batch], np.float64), spec.binning)

                def score():
                    return inference.predict_forest_sharded(
                        binned, sf, sb, lv, w, spec.depth,
                        base=spec.base, n_bins=max_bins)

                # the conf default is ALWAYS a candidate (the spec the
                # winner must beat), whatever the knob is set to
                blocks = sorted(set(KERNELBENCH_INFER_BLOCKS)
                                | {default_rows})
                cands = [("xla", 0)] + [("pallas", br) for br in blocks]
                entry = {"max_bins": max_bins, "max_depth": max_depth,
                         "batch_rows": batch, "candidates": []}
                preds = {}
                for kern, br in cands:
                    GLOBAL_CONF.set("sml.infer.kernel", kern)
                    GLOBAL_CONF.set("sml.infer.kernelBlockRows",
                                    br or default_rows)
                    preds[(kern, br)] = score()  # warmup: compile
                    best = float("inf")
                    for _ in range(3):
                        t0 = time.perf_counter()
                        score()
                        best = min(best, time.perf_counter() - t0)
                    entry["candidates"].append(
                        {"kernel": kern, "block_rows": br,
                         "seconds": round(best, 4)})
                xla_pred = preds[("xla", 0)]
                entry["parity_ok"] = all(
                    np.array_equal(xla_pred, p) for p in preds.values())
                default_s = next(
                    c["seconds"] for c in entry["candidates"]
                    if c["kernel"] == "pallas"
                    and c["block_rows"] == default_rows)
                winner = min(entry["candidates"], key=lambda c: c["seconds"])
                entry["default_s"] = default_s
                entry["best_s"] = winner["seconds"]
                entry["best_spec"] = {"kernel": winner["kernel"],
                                      "block_rows": winner["block_rows"]}
                entry["beats_default"] = winner["seconds"] < default_s
                key = inference.infer_spec_key(
                    sf.shape[0], spec.depth, F, max_bins, batch)
                prewarm.record_tuned("infer_kernel", key,
                                     entry["best_spec"])
                tuned.append((key, entry["best_spec"]))
                legs.append(entry)
                print(f"  infer b{max_bins} d{max_depth} n{batch}: "
                      f"default {default_s:.4f}s, best "
                      f"{winner['seconds']:.4f}s "
                      f"({winner['kernel']}/{winner['block_rows']}, "
                      f"parity={entry['parity_ok']})", file=sys.stderr)
        sweep_s = time.perf_counter() - t_sweep0
        PROFILER.count("infer.kernel.autotune_s", float(sweep_s))
        # round-trip proof: the live resolver must return each persisted
        # winner from the manifest WITHOUT a sweep, and the prewarm
        # rebuilder must replay an entry clean (replica spin-up's path)
        for k in ("sml.infer.kernel", "sml.infer.kernelBlockRows"):
            GLOBAL_CONF.set(k, prev[k])
        GLOBAL_CONF.set("sml.infer.autotune", True)
        replay_ok = True
        for key, spec_rec in tuned:
            kern, br, was_tuned = inference.resolve_infer_kernel(
                n_trees=key["trees"], depth=key["depth"],
                n_nodes=2 ** (key["depth"] + 1) - 1,
                n_feat=key["features"], n_bins=key["bins"],
                n_rows=key["rows"])
            if (kern, br) != (spec_rec["kernel"], spec_rec["block_rows"]) \
                    or not was_tuned:
                replay_ok = False
        try:
            inference._replay_infer_kernel(
                {"key": tuned[0][0], "spec": tuned[0][1]})
        except Exception:
            replay_ok = False
        fallbacks = float(obs.RECORDER.counters()
                          .get("infer.kernel.fallback", 0.0))
    finally:
        for k, v in prev.items():
            GLOBAL_CONF.set(k, v)
    return {
        "rows": n_fit, "n_features": F, "n_trees": KERNELBENCH_TREES,
        "backend": jax.default_backend(),
        "interpret": jax.default_backend() != "tpu",
        "default_block_rows": default_rows,
        "note": "best-of-3 warm scoring dispatches per candidate spec; "
                "winners persisted to the prewarm manifest "
                "(record_tuned) and resolved back without a sweep "
                "(replay_ok); on non-TPU backends pallas runs in "
                "interpret mode (parity, not speed — docs/KERNELS.md)",
        "legs": legs,
        "fallbacks": fallbacks,
        "tuned_beats_default": any(e["beats_default"] for e in legs),
        "replay_ok": replay_ok,
        "autotune_sweep_s": round(sweep_s, 3),
    }


def kernelbench_main(rows: int) -> None:
    """Run the inference autotuner standalone, merge its block into the
    bench sidecar as `kernel_infer` (the sidecar's `kernel` block is the
    recorded sweep of the fit kernels that PR 30 deleted: data, left as
    it is) and print the short headline JSON last."""
    infer_block = run_kernelbench_infer(rows)
    doc = {}
    if os.path.exists(LEGS_FILE):
        with open(LEGS_FILE) as f:
            doc = json.load(f)
    doc["kernel_infer"] = infer_block
    with open(LEGS_FILE, "w") as f:
        json.dump(doc, f, indent=1)
    print(json.dumps({
        "metric": "traversal-kernel autotune sweep",
        "backend": infer_block["backend"],
        "interpret": infer_block["interpret"],
        "parity_ok": all(e["parity_ok"] for e in infer_block["legs"]),
        "fallbacks": infer_block["fallbacks"],
        "infer_tuned_beats_default": infer_block["tuned_beats_default"],
        "infer_replay_ok": infer_block["replay_ok"],
        "legs_file": "bench_legs.json",
    }))


# ------------------------------------------------------------- scale leg
SCALE_INGEST_ROWS = 10_000_000
SCALE_INGEST_F = 10
SCALE_INGEST_TREES = 2
SCALE_INGEST_DEPTH = 4
SCALE_INGEST_BINS = 32
SCALE_PREDICT_CAP = 1_000_000


def make_scale_source(rows: int, chunk_rows=None):
    """The bench synthetic generator as a ChunkSource: every chunk is
    MANUFACTURED from its global row range (per-chunk seeded rng), so the
    raw float dataset never exists whole on host — the two ingest passes
    regenerate identical chunks. Same functional form as the multichip
    leg's dataset."""
    from sml_tpu.frame._chunks import GeneratorChunkSource

    def make(start, stop):
        r = np.random.default_rng((1_000_003 * start) ^ 0xC0FFEE)
        n = stop - start
        X = r.normal(size=(n, SCALE_INGEST_F)).astype(np.float32)
        y = (X[:, 0] * 3 - X[:, 1] ** 2 + 0.5 * X[:, 2]
             + r.normal(0, 0.3, n)).astype(np.float32)
        return X, y

    return GeneratorChunkSource(rows, SCALE_INGEST_F, make,
                                chunk_rows=chunk_rows,
                                fingerprint=("bench-scale", rows,
                                             chunk_rows or 0))


def run_scale(rows: int = SCALE_INGEST_ROWS) -> dict:
    """`--rows N`: the out-of-core data-plane leg (ISSUE 10) — chunked
    columnar ingestion + streamed bin quantization + double-buffered H2D
    prefetch at data-plane scale, then a small tree fit and a streamed
    predict pass over the ingested compact representation.

    The block records ingest throughput (rows/s through sketch +
    quantize + device assembly), peak HOST bytes actually held by the
    plane (chunk buffers + the compact mirror — vs the raw float bytes
    it SAW but never held), the HBM ledger peaks (`chunk_stage` +
    `bin_cache` bound device residency to the compact representation),
    and the prefetch-overlap attribution: serial host-quantization
    seconds vs the pipelined wall, plus the `ingest.dispatch`/
    `ingest.drain` event-order proof that chunk i+1's staging overlapped
    chunk i's device work. Results merge into the bench sidecar as the
    `scale` block, rendered by scripts/render_perf.py; vanishing-block
    and rows/s regressions are judged by obs/regress.py."""
    import jax

    from sml_tpu import obs
    from sml_tpu.conf import GLOBAL_CONF
    from sml_tpu.ml._chunked import (fit_ensemble_chunked, ingest_source,
                                     iter_predictions)

    prev_obs = GLOBAL_CONF.get("sml.obs.enabled")
    GLOBAL_CONF.set("sml.obs.enabled", True)
    try:
        obs.reset()
        # bound the chunk COUNT at scale: each device bin-accumulate on a
        # backend that ignores donation (XLA:CPU) copies the full buffer,
        # so per-chunk cost grows with n — ~32 chunks keeps the CPU
        # artifact honest while real-TPU donation makes the per-chunk
        # cost O(chunk) at any count
        chunk_rows = max(GLOBAL_CONF.getInt("sml.data.chunkRows"),
                         -(-rows // 32))
        source = make_scale_source(rows, chunk_rows=chunk_rows)
        t0 = time.perf_counter()
        ing = ingest_source(source, SCALE_INGEST_BINS, label="scale")
        ingest_s = time.perf_counter() - t0

        # event-order proof: some chunk i+1 dispatched before chunk i
        # drained (the double-buffer actually double-buffered)
        evs = [(e.name, e.args.get("chunk")) for e in obs.RECORDER.events()
               if e.name in ("ingest.dispatch", "ingest.drain")]
        overlap_ok = False
        if any(n == "ingest.drain" for n, _ in evs):
            first_drain = next(i for i, (n, c) in enumerate(evs)
                               if n == "ingest.drain")
            ahead = {c for n, c in evs[:first_drain]
                     if n == "ingest.dispatch"}
            overlap_ok = len(ahead) >= 2

        t0 = time.perf_counter()
        spec = fit_ensemble_chunked(
            source, max_depth=SCALE_INGEST_DEPTH,
            max_bins=SCALE_INGEST_BINS, n_trees=SCALE_INGEST_TREES,
            bootstrap=True, seed=42)  # ingest memo-hit: fit cost only
        fit_s = time.perf_counter() - t0

        # streamed predict over a capped prefix — SAME chunking as the
        # ingest so the per-chunk generator seeds reproduce the same
        # rows; rmse is a sanity metric, unpinned
        p_rows = min(rows, SCALE_PREDICT_CAP)
        psrc = make_scale_source(p_rows, chunk_rows=chunk_rows)
        t0 = time.perf_counter()
        sse = 0.0
        cnt = 0
        for pred, yc in iter_predictions(spec, psrc):
            d = pred - np.asarray(yc, dtype=np.float64)
            sse += float(d @ d)
            cnt += d.size
        predict_s = time.perf_counter() - t0

        led = obs.LEDGER.snapshot()
        st = ing.stats
        prep_s = st["prep_s"]
        dispatch_s = st.get("dispatch_s", 0.0)
        pipeline_s = st["pipeline_s"]
        block = {
            "rows": rows,
            "n_features": SCALE_INGEST_F,
            "chunk_rows": st["chunk_rows"],
            "n_chunks": st["n_chunks"],
            "backend": jax.default_backend(),
            "n_devices": len(jax.devices()),
            "ingest_seconds": round(ingest_s, 3),
            "ingest_rows_per_s": round(rows / max(ingest_s, 1e-9), 1),
            "sketch_exact": st["sketch_exact"],
            "sketch_seconds": st["sketch_s"],
            "fit_seconds": round(fit_s, 3),
            "fit_trees": SCALE_INGEST_TREES,
            "fit_depth": SCALE_INGEST_DEPTH,
            "max_bins": SCALE_INGEST_BINS,
            "predict_rows": p_rows,
            "predict_seconds": round(predict_s, 3),
            "predict_rows_per_s": round(p_rows / max(predict_s, 1e-9), 1),
            "rmse": round(float(np.sqrt(sse / max(cnt, 1))), 6),
            # residency ledger: what the plane SAW vs what it HELD
            "raw_bytes_seen": st["raw_bytes"],
            "compact_bytes": st["compact_bytes"],
            "host_peak_bytes": st["compact_bytes"]
            + st["chunk_rows"] * SCALE_INGEST_F * 4 * 4,  # ~4 raw chunks
            "hbm": {
                "chunk_stage_peak_bytes": int(
                    led.get("chunk_stage", {}).get("peak", 0)),
                "bin_cache_peak_bytes": int(
                    led.get("bin_cache", {}).get("peak", 0)),
            },
            "prefetch": {
                "depth": st["prefetch_depth"],
                # serial-equivalent = host quantization + device-side
                # submission walls run back to back; overlap > 1 is the
                # wall the double buffer actually bought
                "prep_serial_s": prep_s,
                "dispatch_serial_s": dispatch_s,
                "pipeline_s": pipeline_s,
                "overlap": round((prep_s + dispatch_s)
                                 / max(pipeline_s, 1e-9), 3),
                "events_ok": overlap_ok,
            },
            "note": "chunked columnar ingest (two-pass: mergeable "
                    "quantile sketch, then per-chunk quantize + "
                    "double-buffered H2D + device bin-accumulate); raw "
                    "float data never resident whole on host or device "
                    "— HBM holds the compact matrix + ~prefetchChunks "
                    "chunk blocks (docs/DATAPLANE.md)",
        }
        print(f"  scale {rows:,} rows: ingest {ingest_s:.1f}s "
              f"({rows / ingest_s:,.0f} rows/s, sketch_exact="
              f"{st['sketch_exact']}), fit {fit_s:.1f}s, predict "
              f"{p_rows:,} in {predict_s:.1f}s; raw seen "
              f"{st['raw_bytes'] / 1e9:.2f} GB vs compact "
              f"{st['compact_bytes'] / 1e6:.1f} MB, chunk_stage peak "
              f"{block['hbm']['chunk_stage_peak_bytes'] / 1e6:.1f} MB, "
              f"overlap {block['prefetch']['overlap']}x "
              f"(events_ok={overlap_ok})", file=sys.stderr)
        return block
    finally:
        GLOBAL_CONF.set("sml.obs.enabled", bool(prev_obs))


def scale_main(rows: int) -> None:
    """Run the out-of-core leg standalone, merge the `scale` block into
    the bench sidecar, and print the short headline JSON last."""
    block = run_scale(rows)
    doc = {}
    if os.path.exists(LEGS_FILE):
        with open(LEGS_FILE) as f:
            doc = json.load(f)
    doc["scale"] = block
    with open(LEGS_FILE, "w") as f:
        json.dump(doc, f, indent=1)
    print(json.dumps({
        "metric": "out-of-core ingest throughput",
        "value": block["ingest_rows_per_s"],
        "unit": "rows/s",
        "rows": block["rows"],
        "backend": block["backend"],
        "overlap": block["prefetch"]["overlap"],
        "overlap_events_ok": block["prefetch"]["events_ok"],
        "chunk_stage_peak_mb": round(
            block["hbm"]["chunk_stage_peak_bytes"] / 1e6, 2),
        "compact_vs_raw": round(block["raw_bytes_seen"]
                                / max(block["compact_bytes"], 1), 2),
        "legs_file": "bench_legs.json",
    }))


# --------------------------------------------------------------- drift leg
DRIFT_ROWS = 120_000
DRIFT_HOLDOUT = 20_000
DRIFT_F = 8          # 7 continuous + 1 categorical slot
DRIFT_CARD = 6
DRIFT_EXPECTED = ["f0", "f2", "f7"]  # the features the injection moves


def make_drift_frame(rows, seed, shift=False):
    """Synthetic (X, y) for the drift leg. `shift=True` injects the
    covariate shift the detector must name: feature 0 moves +1.25
    (location), feature 2 scales 1.9x, and the categorical slot 7's
    frequency table inverts — everything else stays iid with the
    training distribution, so flags on other features are false
    positives by construction."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(rows, DRIFT_F)).astype(np.float64)
    cat_p = np.asarray([0.30, 0.25, 0.20, 0.15, 0.07, 0.03])
    if shift:
        X[:, 0] += 1.25
        X[:, 2] *= 1.9
        cat_p = cat_p[::-1].copy()
    X[:, 7] = rng.choice(DRIFT_CARD, size=rows, p=cat_p)
    y = (3.0 * X[:, 0] - X[:, 1] ** 2 + 0.5 * X[:, 2]
         + rng.normal(0, 0.3, rows)).astype(np.float32)
    return X, y


def run_drift(rows: int = DRIFT_ROWS) -> dict:
    """`--drift`: the model/data-observability proof leg (ISSUE 11) —
    fit a small forest through the chunked ingest (so the fitted model
    carries its training `DriftBaseline` built from the full-data
    pass-1 sketch), then judge three streams against that baseline:

    - an IID holdout draw (same distribution, fresh seed) must come
      back CLEAN — the noise-aware thresholds' no-false-positive proof;
    - an injected covariate shift (location + scale + categorical
      frequency) must FLAG, naming exactly the moved features, with the
      prediction distribution flagging too;
    - the same shifted stream re-ingested chunk-by-chunk with
      `drift_baseline=` must flag chunks (the continuous-training
      refit-trigger signal), while the iid stream's chunks stay clean.

    The block also proves the baseline save→load round trip is
    bit-compatible (reloaded-vs-self distance exactly zero). Results
    merge into the bench sidecar as the `drift` block, rendered by
    scripts/render_perf.py; a vanished block or a lost proof is flagged
    by obs/regress.py."""
    import jax

    from sml_tpu import obs
    from sml_tpu.conf import GLOBAL_CONF
    from sml_tpu.frame._chunks import ArrayChunkSource
    from sml_tpu.ml._chunked import fit_ensemble_chunked, ingest_source
    from sml_tpu.obs import drift as driftmod

    prev_obs = GLOBAL_CONF.get("sml.obs.enabled")
    GLOBAL_CONF.set("sml.obs.enabled", True)
    try:
        obs.reset()
        cat = {7: DRIFT_CARD}
        X, y = make_drift_frame(rows, seed=11)
        t0 = time.perf_counter()
        spec = fit_ensemble_chunked(
            ArrayChunkSource(X, y, chunk_rows=max(rows // 8, 1)),
            categorical=cat, max_depth=4, max_bins=32, n_trees=4,
            bootstrap=True, seed=7)
        fit_s = time.perf_counter() - t0
        baseline = spec.baseline
        assert baseline is not None, "chunked fit did not stamp a baseline"

        # save->load bit-compat: a reloaded baseline's self-distance is 0
        reloaded = driftmod.DriftBaseline.from_dict(
            json.loads(json.dumps(baseline.to_dict())))
        self_d = max(
            max(driftmod.psi_distance(sk, reloaded.features.features[f]),
                driftmod.quantile_shift(sk, reloaded.features.features[f]))
            for f, sk in baseline.features.features.items())

        t0 = time.perf_counter()
        Xh, _ = make_drift_frame(DRIFT_HOLDOUT, seed=999)
        rep_iid = driftmod.evaluate_block(
            baseline, Xh, spec.predict_margin(Xh), name="bench-iid")
        Xs, ys = make_drift_frame(DRIFT_HOLDOUT, seed=555, shift=True)
        rep_shift = driftmod.evaluate_block(
            baseline, Xs, spec.predict_margin(Xs), name="bench-shift")
        judge_s = time.perf_counter() - t0
        named_ok = set(DRIFT_EXPECTED).issubset(set(rep_shift["flagged"]))

        # ingest-time monitor: per-chunk verdicts against the baseline
        def _ingest_chunks(Xi, yi, tag):
            ingest_source(
                ArrayChunkSource(Xi, yi, chunk_rows=DRIFT_HOLDOUT // 8),
                32, cat, label=tag, drift_baseline=baseline)
            rep = obs.engine_health()["drift"]["ingest"]
            ch = rep.get("chunks") or {}
            return int(ch.get("observed", 0)), int(ch.get("flagged", 0))

        iid_chunks, iid_flagged = _ingest_chunks(
            *make_drift_frame(DRIFT_HOLDOUT, seed=333), "drift-iid")
        shift_chunks, shift_flagged = _ingest_chunks(Xs, ys, "drift-shift")

        block = {
            "rows": rows,
            "holdout_rows": DRIFT_HOLDOUT,
            "n_features": DRIFT_F,
            "backend": jax.default_backend(),
            "fit_seconds": round(fit_s, 3),
            "judge_seconds": round(judge_s, 3),
            "baseline": {
                "rows": baseline.n_rows,
                "sampled_rows": baseline.sampled_rows,
                "sketch_exact": bool(baseline.features.exact),
                "reload_self_distance": self_d,
                "reload_bit_compat": bool(self_d == 0.0),
            },
            "iid": {
                "flagged": bool(rep_iid["n_flagged"] > 0),
                "n_flagged": int(rep_iid["n_flagged"]),
                "max_severity": float(rep_iid["max_severity"]),
            },
            "shift": {
                "flagged": bool(rep_shift["n_flagged"] > 0),
                "n_flagged": int(rep_shift["n_flagged"]),
                "max_severity": float(rep_shift["max_severity"]),
                "top_features": rep_shift["top"],
                "flagged_features": rep_shift["flagged"],
                "expected": DRIFT_EXPECTED,
                "named_ok": bool(named_ok),
                "prediction_flagged": bool(
                    (rep_shift.get("prediction") or {}).get("flagged")),
            },
            "ingest": {
                "iid_chunks": iid_chunks,
                "iid_flagged_chunks": iid_flagged,
                "shift_chunks": shift_chunks,
                "shift_flagged_chunks": shift_flagged,
            },
            "note": "distances = per-feature PSI over baseline deciles + "
                    "normalized quantile shift + categorical frequency "
                    "PSI, judged against noise-aware thresholds "
                    "(resampled-baseline self-distance floors x "
                    "sml.obs.driftMargin); the iid row is the "
                    "no-false-positive proof, the shift row the "
                    "detection proof (docs/OBSERVABILITY.md)",
        }
        print(f"  drift: iid clean={not block['iid']['flagged']} "
              f"(max severity {block['iid']['max_severity']:.2f}), "
              f"shift flagged={block['shift']['flagged']} "
              f"({block['shift']['flagged_features']} vs expected "
              f"{DRIFT_EXPECTED}, named_ok={named_ok}, prediction_flagged="
              f"{block['shift']['prediction_flagged']}); ingest chunks "
              f"iid {iid_flagged}/{iid_chunks} vs shift "
              f"{shift_flagged}/{shift_chunks} flagged; baseline reload "
              f"self-distance {self_d}", file=sys.stderr)
        return block
    finally:
        GLOBAL_CONF.set("sml.obs.enabled", bool(prev_obs))


def drift_main(rows: int) -> None:
    """Run the drift leg standalone, merge the `drift` block into the
    bench sidecar, and print the short headline JSON last."""
    block = run_drift(rows)
    doc = {}
    if os.path.exists(LEGS_FILE):
        with open(LEGS_FILE) as f:
            doc = json.load(f)
    doc["drift"] = block
    with open(LEGS_FILE, "w") as f:
        json.dump(doc, f, indent=1)
    ok = (block["shift"]["flagged"] and block["shift"]["named_ok"]
          and not block["iid"]["flagged"]
          and block["baseline"]["reload_bit_compat"])
    print(json.dumps({
        "metric": "drift detection (injected covariate shift vs iid "
                  "holdout)",
        "value": 1.0 if ok else 0.0,
        "unit": "1 = shift flagged + features named + iid clean + "
                "baseline round-trip bit-compatible",
        "shift_flagged": block["shift"]["flagged"],
        "named_ok": block["shift"]["named_ok"],
        "iid_clean": not block["iid"]["flagged"],
        "ingest_flagged_chunks": block["ingest"]["shift_flagged_chunks"],
        "backend": block["backend"],
        "legs_file": "bench_legs.json",
    }))
    if not ok:
        sys.exit(1)


# --------------------------------------------------- continuous-training leg
CT_ROWS = 24_000
CT_F = 6


def _ct_frame(rows, seed, shift=False):
    """Synthetic (X, y) for the continuous-training leg: `shift=True`
    injects the covariate drift the trainer must catch (f0 location,
    f2 scale) — the label function is unchanged, so a warm-start refit
    on the drifted window genuinely improves window RMSE."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(rows, CT_F))
    if shift:
        X[:, 0] += 1.5
        X[:, 2] *= 2.0
    y = (3.0 * X[:, 0] + 0.5 * X[:, 2] - X[:, 1] ** 2
         + rng.normal(0, 0.3, rows)).astype(np.float32)
    return X, y


def run_ct(rows: int = CT_ROWS) -> dict:
    """`--ct`: the closed-loop continuous-training proof (ISSUE 14) —
    seed a baseline-carrying GBT into the registry and serve it, then
    run `sml_tpu.ct.ContinuousTrainer` over two live Delta streams:

    - a DRIFTING stream (injected covariate shift appended as new Delta
      versions) must trigger >= 1 WARM-START refit whose new version
      passes the canary gate (Staging mirror via sml.serve
      .canaryFraction, zero canary/request errors, window-quality win)
      and hot-swaps Production on the live endpoint;
    - an IID control stream must trigger ZERO refits across the same
      number of cycles (the drift trigger's no-false-positive proof).

    Results merge into the bench sidecar as the `ct` block, rendered by
    scripts/render_perf.py; a vanished block, a lost promotion, or a
    refit on the iid control is flagged by obs/regress.py."""
    import shutil
    import tempfile

    import jax
    import pandas as pd

    import sml_tpu.tracking as mlflow
    from sml_tpu import TpuSession, obs
    from sml_tpu.conf import GLOBAL_CONF
    from sml_tpu.ct import CanaryGate, ContinuousTrainer, DeltaChunkSource
    from sml_tpu.frame._chunks import ArrayChunkSource
    from sml_tpu.ml._chunked import fit_ensemble_chunked
    from sml_tpu.ml.regression import GBTRegressionModel
    from sml_tpu.serving import ServingEndpoint
    from sml_tpu.tracking import _store
    from sml_tpu.utils.profiler import PROFILER

    prev_obs = GLOBAL_CONF.get("sml.obs.enabled")
    prev_prof = GLOBAL_CONF.get("sml.profiler.enabled")
    prev_uri = _store.get_tracking_uri()
    GLOBAL_CONF.set("sml.obs.enabled", True)
    GLOBAL_CONF.set("sml.profiler.enabled", True)  # hot-swap receipts
    tmp = tempfile.mkdtemp(prefix="sml-ct-bench-")
    mlflow.set_tracking_uri(os.path.join(tmp, "runs"))
    spark = TpuSession.builder.appName("ct-bench").getOrCreate()
    cols = [f"f{i}" for i in range(CT_F)]
    try:
        obs.reset()
        # ---- seed model: baseline-carrying boosted ensemble, v1 in
        # Production, served with the canary mirror armed
        Xt, yt = _ct_frame(rows, seed=11)
        t0 = time.perf_counter()
        spec = fit_ensemble_chunked(
            ArrayChunkSource(Xt, yt, chunk_rows=max(rows // 8, 1)),
            categorical={}, max_depth=4, max_bins=32, n_trees=8,
            seed=7, loss="squared", step_size=0.3, boosting=True)
        fit_s = time.perf_counter() - t0
        assert spec.baseline is not None, "seed fit did not stamp a baseline"
        # the same seed model anchors TWO independent lineages: the
        # drifting pipeline (whose promotion moves ITS Production) and
        # the iid control (whose baseline must stay the seed model —
        # sharing one name would make the control judge iid data
        # against the drift-refit model and "detect" the promotion)
        with mlflow.start_run():
            mlflow.spark.log_model(GBTRegressionModel(spec), "model",
                                   registered_model_name="ct-bench-model")
            mlflow.spark.log_model(GBTRegressionModel(spec), "model-iid",
                                   registered_model_name="ct-bench-iid")
        _store.set_version_stage("ct-bench-model", 1, "Production")
        _store.set_version_stage("ct-bench-iid", 1, "Production")

        def append(path, batch_rows, seed, shift):
            X, y = _ct_frame(batch_rows, seed, shift)
            pdf = pd.DataFrame({c: X[:, i] for i, c in enumerate(cols)})
            pdf["y"] = y.astype(float)
            mode = "append" if os.path.exists(path) else "errorifexists"
            spark.createDataFrame(pdf).write.format("delta") \
                .mode(mode).save(path)

        batch = max(rows // 8, 1024)
        gate = CanaryGate(min_mirrored=4, timeout_s=30.0,
                          quality_tol=1.2, batch_rows=256)
        swaps0 = PROFILER.counters().get("serve.hot_swap", 0.0)

        # ---- drifting stream: refit -> gate -> promote -> hot-swap
        dpath = os.path.join(tmp, "drift-stream")
        t0 = time.perf_counter()
        with ServingEndpoint("ct-bench-model", "Production",
                             canary_fraction=1.0, flush_micros=500) as ep:
            trainer = ContinuousTrainer(
                "ct-bench-model", DeltaChunkSource(dpath, cols, "y"),
                endpoint=ep, gate=gate,
                fit_params={"seed": 7, "rounds_per_dispatch": 2},
                warm_rounds=4, min_rows=512, full_severity=1e9)
            append(dpath, batch, seed=21, shift=False)
            clean = trainer.step()
            append(dpath, batch, seed=22, shift=True)
            drifted = trainer.step()
            dstats = trainer.stats()
            endpoint_version = ep.current_version()
        loop_s = time.perf_counter() - t0
        swaps = PROFILER.counters().get("serve.hot_swap", 0.0) - swaps0

        # ---- iid control stream: same cadence, zero refits
        ipath = os.path.join(tmp, "iid-stream")
        control = ContinuousTrainer(
            "ct-bench-iid", DeltaChunkSource(ipath, cols, "y"),
            gate=gate, fit_params={"seed": 7},
            warm_rounds=4, min_rows=512, full_severity=1e9)
        for i in range(2):
            append(ipath, batch, seed=31 + i, shift=False)
            control.step()
        istats = control.stats()

        gate_verdict = (drifted.get("gate") or {})
        block = {
            "rows": rows,
            "n_features": CT_F,
            "backend": jax.default_backend(),
            "seed_fit_seconds": round(fit_s, 3),
            "loop_seconds": round(loop_s, 3),
            "drift": {
                "cycles": dstats["cycles"],
                "clean_cycles": dstats["clean"],
                "refits": dstats["refits"],
                "warm_refits": dstats["warm_refits"],
                "full_refits": dstats["full_refits"],
                "severity": float(drifted.get("severity", 0.0)),
                "clean_severity": float(clean.get("severity", 0.0)),
                "promoted": bool(dstats["promotions"] >= 1),
                "rollbacks": dstats["rollbacks"],
                "endpoint_version": endpoint_version,
                "hot_swap": bool(swaps >= 1),
                "request_errors": int(
                    gate_verdict.get("request_errors", -1)),
                "gate": {k: gate_verdict.get(k) for k in
                         ("passed", "mirrored", "canary_errors",
                          "request_errors", "mean_abs_diff",
                          "rmse_candidate", "rmse_incumbent")},
            },
            "iid": {
                "cycles": istats["cycles"],
                "refits": istats["refits"],
                "severity": float((control.last_report or {})
                                  .get("severity", 0.0)),
            },
            "note": "closed loop: Delta appends -> snapshot/advance "
                    "watermark -> PR-11 ingest drift monitor -> "
                    "warm-start round append under the saved bin edges "
                    "-> registry version -> Staging canary mirror -> "
                    "gate -> Production hot-swap "
                    "(docs/CONTINUOUS_TRAINING.md)",
        }
        ok = (block["drift"]["promoted"] and block["drift"]["hot_swap"]
              and block["drift"]["warm_refits"] >= 1
              and block["drift"]["request_errors"] == 0
              and block["drift"]["endpoint_version"] == 2
              and block["iid"]["refits"] == 0)
        block["closed_loop_ok"] = bool(ok)
        print(f"  ct: drift severity {block['drift']['severity']:.1f} -> "
              f"{block['drift']['warm_refits']} warm refit(s), promoted="
              f"{block['drift']['promoted']} (endpoint v"
              f"{block['drift']['endpoint_version']}, hot_swap="
              f"{block['drift']['hot_swap']}, request_errors="
              f"{block['drift']['request_errors']}); iid control "
              f"{block['iid']['refits']} refits over "
              f"{block['iid']['cycles']} cycles (severity "
              f"{block['iid']['severity']:.2f})", file=sys.stderr)
        return block
    finally:
        GLOBAL_CONF.set("sml.obs.enabled", bool(prev_obs))
        GLOBAL_CONF.set("sml.profiler.enabled", bool(prev_prof))
        mlflow.set_tracking_uri(prev_uri)
        shutil.rmtree(tmp, ignore_errors=True)


def ct_main(rows: int) -> None:
    """Run the continuous-training leg standalone, merge the `ct` block
    into the bench sidecar, and print the short headline JSON last."""
    block = run_ct(rows)
    doc = {}
    if os.path.exists(LEGS_FILE):
        with open(LEGS_FILE) as f:
            doc = json.load(f)
    doc["ct"] = block
    with open(LEGS_FILE, "w") as f:
        json.dump(doc, f, indent=1)
    print(json.dumps({
        "metric": "continuous-training closed loop (drift stream "
                  "promotes, iid stream holds)",
        "value": 1.0 if block["closed_loop_ok"] else 0.0,
        "unit": "1 = warm refit fired + canary gate promoted + "
                "hot-swap + zero request errors + zero iid refits",
        "warm_refits": block["drift"]["warm_refits"],
        "promoted": block["drift"]["promoted"],
        "iid_refits": block["iid"]["refits"],
        "backend": block["backend"],
        "legs_file": "bench_legs.json",
    }))
    if not block["closed_loop_ok"]:
        sys.exit(1)


FLEET_REQUESTS = 10_000
#: per-client pacing interval for the fleet leg's clients (ms): each
#: client INTENDS to send request k at epoch + k*interval and charges
#: latency from that intended instant (wrk2-style re-basing) — a
#: completion that arrives late delays the send but not the clock, so
#: the queueing the old send-time stamp hid is now on the record. Small
#: enough that a saturated fleet never actually sleeps (the load shape
#: the proofs depend on is unchanged)
FLEET_PACE_MS = 5.0


def run_fleet(requests: int = FLEET_REQUESTS) -> dict:
    """`--fleet`: the multi-replica serving-fleet proof (ISSUE 15) —
    register a linear model (v1 Production, v2 clean twin, v3 injected
    divergence), spin a warm 2-replica `fleet.ReplicaPool`, and drive a
    closed-loop load of `requests` requests through the `Router` across
    the three priority classes:

    - per-replica queue attribution + per-class p50/p99/shed under the
      published SLO (`sml.serve.sloMillis`), shedding priority-ordered
      (low first, high never — it degrades through the host ladder);
    - at least one occupancy-driven scale-UP during the load and one
      scale-DOWN after it (autoscaler bands);
    - a staged rollout of the clean candidate that PROMOTES, then one
      of the divergent candidate that AUTO-ROLLS-BACK, archives, and
      evicts the diverging replica with its black-box bundle on disk;
    - zero hung futures, and per-request trace ids recoverable through
      the router fan-in (`fleet.route` events × admission spans).

    Results merge into the bench sidecar as the `fleet` block, rendered
    by scripts/render_perf.py; a vanished block, a lost rollback or
    scale proof, a hung future, or a shed-rate/p99 regression is
    flagged by obs/regress.py."""
    import shutil
    import tempfile
    import threading

    import jax
    import pandas as pd

    import sml_tpu.tracking as mlflow
    from sml_tpu import TpuSession, obs
    from sml_tpu.conf import GLOBAL_CONF
    from sml_tpu.ct import CanaryGate
    from sml_tpu.fleet import Autoscaler, ReplicaPool, Router
    from sml_tpu.ml import Pipeline
    from sml_tpu.ml.feature import VectorAssembler
    from sml_tpu.ml.regression import LinearRegression
    from sml_tpu.serving import RequestShed
    from sml_tpu.tracking import _store
    from sml_tpu.utils.profiler import PROFILER

    prev_obs = GLOBAL_CONF.get("sml.obs.enabled")
    prev_prof = GLOBAL_CONF.get("sml.profiler.enabled")
    prev_ring = GLOBAL_CONF.get("sml.obs.ringEvents")
    prev_uri = _store.get_tracking_uri()
    GLOBAL_CONF.set("sml.obs.enabled", True)
    GLOBAL_CONF.set("sml.profiler.enabled", True)
    # the fan-in proof scans the ring for every routed request's trace:
    # size it so a 10k-request load cannot evict its own evidence
    GLOBAL_CONF.set("sml.obs.ringEvents", 1 << 18)
    tmp = tempfile.mkdtemp(prefix="sml-fleet-bench-")
    mlflow.set_tracking_uri(os.path.join(tmp, "runs"))
    spark = TpuSession.builder.appName("fleet-bench").getOrCreate()

    def fit(seed, slope):
        rng = np.random.default_rng(seed)
        pdf = pd.DataFrame({"a": rng.normal(size=4000),
                            "b": rng.normal(size=4000)})
        pdf["y"] = slope * pdf["a"] - pdf["b"] + 1.0 \
            + rng.normal(0, 0.1, len(pdf))
        va = VectorAssembler(inputCols=["a", "b"], outputCol="features")
        return Pipeline(stages=[va, LinearRegression(labelCol="y")]) \
            .fit(spark.createDataFrame(pdf))

    pool = None
    try:
        obs.reset()
        for m in (fit(3, 2.0), fit(3, 2.0), fit(9, -4.0)):
            with mlflow.start_run():
                mlflow.spark.log_model(
                    m, "model", registered_model_name="fleet-bench-model")
        _store.set_version_stage("fleet-bench-model", 1, "Production")

        classes = ["high", "normal", "low"]
        rows_per_req = 32
        queue_rows = 128
        pool = ReplicaPool(
            "fleet-bench-model", replicas=2, canary_fraction=1.0,
            flush_micros=8000, queue_rows=queue_rows, timeout_millis=0,
            host_fallback=True,
            blackbox_dir=os.path.join(tmp, "blackbox"))
        router = Router(pool, priorities=classes)
        asc = Autoscaler(pool, router, min_replicas=2, max_replicas=3,
                         scale_up_occupancy=0.5, scale_down_occupancy=0.1)

        # ---- closed-loop load: 12 clients over 3 priority classes ----
        X = np.random.default_rng(5).normal(
            size=(rows_per_req, 2)).astype(np.float32)
        clients = {"high": 3, "normal": 4, "low": 5}
        share = {"high": 0.2, "normal": 0.4, "low": 0.4}
        lat = {c: [] for c in classes}
        shed = {c: 0 for c in classes}
        hung = [0]
        lat_lock = threading.Lock()

        # coordinated-omission fix (docs/LOADGEN.md): each client paces
        # a per-client SCHEDULE (request k intended at epoch +
        # k*FLEET_PACE_MS) and charges latency from the INTENDED
        # arrival, not the post-completion send time — when the fleet
        # queues and delays a completion, the next request's clock has
        # already started, so the queueing lands on the record instead
        # of silently slowing the client's arrival rate
        interval = FLEET_PACE_MS / 1e3

        def client(cls, n):
            my_lat, my_shed = [], 0
            epoch = time.perf_counter()
            for k in range(n):
                intended = epoch + k * interval
                spare = intended - time.perf_counter()
                if spare > 0:
                    time.sleep(spare)
                try:
                    router.submit(X, cls).result(30.0)
                    my_lat.append((time.perf_counter() - intended) * 1e3)
                except RequestShed:
                    my_shed += 1
                except TimeoutError:
                    with lat_lock:
                        hung[0] += 1
            with lat_lock:
                lat[cls].extend(my_lat)
                shed[cls] += my_shed

        threads = []
        sent = {c: 0 for c in classes}
        for cls in classes:
            per = int(requests * share[cls]) // clients[cls]
            for _ in range(clients[cls]):
                sent[cls] += per
                threads.append(threading.Thread(
                    target=client, args=(cls, per)))
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        actions = []
        peak = pool.size()
        while any(t.is_alive() for t in threads):
            time.sleep(0.25)
            actions.append(asc.step()["action"])
            peak = max(peak, pool.size())
        for t in threads:
            t.join()
        load_s = time.perf_counter() - t0
        # ---- cooldown: the idle fleet retires back to the floor (a
        # load lull may already have retired it mid-run — band events
        # count wherever they fired) -----------------------------------
        for _ in range(3):
            a = asc.step()["action"]
            actions.append(a)
            if a == "down":
                break
        up_events = sum(1 for a in actions if a in ("up", "backfill"))
        down_events = sum(1 for a in actions if a == "down")
        # the SLO snapshot (and its all-time worst-request exemplar) is
        # taken HERE, before the rollouts: gate traffic drives the
        # endpoints directly (no fleet.route event), so a slow gate
        # request after this point must not become the "worst" the
        # fan-in proof then fails to find among the router's traces
        slo = obs.slo_report()

        # ---- staged rollouts: clean promote, then forced rollback ----
        gate = CanaryGate(min_mirrored=4, timeout_s=30.0,
                          max_abs_diff=0.05, batch_rows=64)
        Xg = np.random.default_rng(6).normal(size=(256, 2)) \
            .astype(np.float32)
        _store.set_version_stage("fleet-bench-model", 2, "Staging")
        clean = pool.promote(2, gate=gate, X=Xg)
        _store.set_version_stage("fleet-bench-model", 3, "Staging")
        rollback = pool.promote(3, gate=gate, X=Xg)
        bb = rollback.get("blackbox")
        bb_ok = bool(bb) and os.path.isfile(
            os.path.join(bb, "MANIFEST.json"))
        backfilled = asc.step()["action"]  # refill the evicted slot

        # ---- trace fan-in proof: router decision ↔ admission span ----
        route_traces, request_traces = set(), set()
        for ev in obs.RECORDER.events():
            if ev.name == "fleet.route":
                tid = (ev.args or {}).get("trace")
                if tid is not None:
                    route_traces.add(tid)
            elif ev.name == "trace.request":
                request_traces.add((ev.args or {}).get("trace"))
        fanin = len(route_traces & request_traces)
        worst_hex = slo.get("worst_trace")
        worst_in_fanin = (worst_hex is not None
                          and int(worst_hex, 16) in route_traces)
        fanin_ok = fanin > 0 and worst_in_fanin

        health = obs.engine_health()
        counters = PROFILER.counters()
        per_class = {}
        rates = {}
        for cls in classes:
            ls = sorted(lat[cls])
            served = len(ls)
            rate = shed[cls] / max(sent[cls], 1)
            rates[cls] = rate
            per_class[cls] = {
                "requests": sent[cls],
                "served": served,
                "shed": shed[cls],
                "shed_rate": round(rate, 4),
                "fleet_shed_counter": counters.get(
                    f"fleet.shed.{cls}", 0.0),
                "p50_ms": round(ls[len(ls) // 2], 3) if ls else None,
                "p99_ms": round(ls[min(int(len(ls) * 0.99),
                                       len(ls) - 1)], 3) if ls else None,
            }
        priority_order_ok = (rates["low"] >= rates["normal"]
                             >= rates["high"] and rates["high"] == 0.0)
        block = {
            "requests": sum(sent.values()),
            "rows_per_request": rows_per_req,
            "queue_rows": queue_rows,
            "backend": jax.default_backend(),
            "load_seconds": round(load_s, 3),
            "replicas": {"initial": 2, "min": 2, "max": 3, "peak": peak,
                         "final": pool.size()},
            "slo": {"target_ms": slo["target_ms"],
                    "burn_rate": slo["burn_rate"],
                    "breaches": slo["breaches"]},
            "priority": per_class,
            "priority_order_ok": bool(priority_order_ok),
            # like-for-like annotation: these latencies come from
            # CLOSED-LOOP clients (re-based on intended arrivals, but
            # still self-throttling past one in-flight request each) —
            # the regress sentry only compares p99s between blocks
            # whose closed_loop flags agree (docs/LOADGEN.md)
            "closed_loop": True,
            "pace_ms": FLEET_PACE_MS,
            "hung_futures": int(hung[0]),
            "reroutes": counters.get("fleet.reroutes", 0.0),
            "scale": {"up_events": up_events, "down_events": down_events,
                      "up_ok": bool(up_events >= 1),
                      "down_ok": bool(down_events >= 1),
                      "events": [a for a in actions if a != "hold"],
                      "post_rollback": backfilled},
            "rollout": {
                "clean": {"passed": bool(clean["passed"]),
                          "stages": len(clean["stages"])},
                "rollback": {
                    "rolled_back": bool(not rollback["passed"]
                                        and rollback["action"]
                                        == "rolled_back"),
                    "evicted": rollback.get("evicted"),
                    "divergence_check": (rollback.get("checks") or {})
                    .get("divergence"),
                    "blackbox_on_disk": bool(bb_ok)},
            },
            "trace": {"worst_ms": slo["worst_ms"],
                      "worst_trace": worst_hex,
                      "route_events": len(route_traces),
                      "fanin_requests": fanin,
                      "fanin_ok": bool(fanin_ok)},
            "shed_by_reason": dict(health["shed"]["by_reason"]),
            "note": "closed loop: Router priority admission over "
                    "per-replica QueuePressure -> micro-batched "
                    "replicas -> occupancy-banded Autoscaler; staged "
                    "rollout via per-replica CanaryGate pins with "
                    "auto-rollback + forensic eviction "
                    "(docs/FLEET.md)",
        }
        ok = (hung[0] == 0
              and block["scale"]["up_ok"] and block["scale"]["down_ok"]
              and block["rollout"]["clean"]["passed"]
              and block["rollout"]["rollback"]["rolled_back"]
              and block["rollout"]["rollback"]["evicted"] is not None
              and bb_ok
              and priority_order_ok and shed["low"] > 0
              and fanin_ok)
        block["fleet_ok"] = bool(ok)
        print(f"  fleet: {block['requests']} requests over "
              f"{len(classes)} classes in {load_s:.1f}s — shed "
              f"low/normal/high = {shed['low']}/{shed['normal']}/"
              f"{shed['high']}, scale up×{up_events} down×"
              f"{down_events} (peak {peak}), clean rollout "
              f"{'PROMOTED' if clean['passed'] else 'FAILED'}, "
              f"divergent rollout "
              f"{'ROLLED BACK' if not rollback['passed'] else 'PASSED?!'}"
              f" (evicted r{rollback.get('evicted')}, blackbox "
              f"{'ok' if bb_ok else 'MISSING'}), hung {hung[0]}, "
              f"fan-in {'ok' if fanin_ok else 'LOST'}", file=sys.stderr)
        return block
    finally:
        # close BEFORE the tmp dir (blackbox/tracking roots) vanishes
        # under live replicas — a mid-proof exception must not leak
        # flush threads and a registered pool into the rest of the run
        if pool is not None:
            try:
                pool.close()
            except Exception:
                pass
        GLOBAL_CONF.set("sml.obs.enabled", bool(prev_obs))
        GLOBAL_CONF.set("sml.profiler.enabled", bool(prev_prof))
        GLOBAL_CONF.set("sml.obs.ringEvents", int(prev_ring))
        mlflow.set_tracking_uri(prev_uri)
        shutil.rmtree(tmp, ignore_errors=True)


def fleet_main(requests: int) -> None:
    """Run the fleet leg standalone, merge the `fleet` block into the
    bench sidecar, and print the short headline JSON last."""
    block = run_fleet(requests)
    doc = {}
    if os.path.exists(LEGS_FILE):
        with open(LEGS_FILE) as f:
            doc = json.load(f)
    doc["fleet"] = block
    with open(LEGS_FILE, "w") as f:
        json.dump(doc, f, indent=1)
    print(json.dumps({
        "metric": "serving-fleet closed loop (priority shed ladder, "
                  "autoscale cycle, staged rollout + rollback)",
        "value": 1.0 if block["fleet_ok"] else 0.0,
        "unit": "1 = priority-ordered shed + scale up/down + clean "
                "promote + divergent rollback w/ blackbox + zero hung "
                "futures + trace fan-in recoverable",
        "requests": block["requests"],
        "hung_futures": block["hung_futures"],
        "scale_up": block["scale"]["up_events"],
        "scale_down": block["scale"]["down_events"],
        "rolled_back": block["rollout"]["rollback"]["rolled_back"],
        "backend": block["backend"],
        "legs_file": "bench_legs.json",
    }))
    if not block["fleet_ok"]:
        sys.exit(1)


#: the committed multi-phase open-loop trace for `--load` (seconds of
#: each phase at the nominal req/s BEFORE --load-scale): steady Poisson,
#: a 3x burst (mean-preserving on/off modulation), then a diurnal-shaped
#: ramp. The seed makes the schedule byte-reproducible.
LOAD_TRACE_SEED = 19
LOAD_PHASES = (("steady", 6.0, 30.0, None, "poisson"),
               ("burst", 6.0, 30.0, None, "bursty"),
               ("ramp", 6.0, 15.0, 45.0, "poisson"))
LOAD_WIDTHS = ((8, 0.70), (32, 0.22), (128, 0.06), (256, 0.02))
LOAD_CLASSES = (("high", 0.2), ("normal", 0.6), ("low", 0.2))
#: open-loop honesty tolerance for the bench leg (µs): fire-lag past
#: this counts load.overrun. Much wider than the 5 ms library default —
#: the bench box can be a 1-core container where a just-woken driver
#: worker waits behind a whole herd of GIL slices (every future the OFF
#: run's mis-tuned flush resolves wakes a parked thread) before it can
#: stamp its fire. The value is RECORDED in the block, so the claim
#: "zero overruns" always names the tolerance it was measured at
LOAD_OVERRUN_MICROS = 100_000
#: mis-tuned static flush deadline for the engineering-OFF run (µs): a
#: plausible hand-tuned value that eats most of the 50 ms SLO budget in
#: queueing — exactly what the auto-tuner exists to fix
LOAD_OFF_FLUSH_MICROS = 40_000
LOAD_SLO_MILLIS = 50


def run_load(scale: float = 1.0) -> dict:
    """`--load`: the open-loop trace-driven load proof (docs/LOADGEN.md)
    — replay the committed steady→3x-burst→ramp `TraceSpec` through
    `loadgen.OpenLoopDriver` against a warm 2-replica fleet TWICE:

    - OFF: static mis-tuned flush deadline (LOAD_OFF_FLUSH_MICROS), no
      burst-anticipating admission, no speculative prewarm — honest
      open-loop tails of a hand-tuned fleet;
    - ON: `sml.serve.flushAutoTune` + `sml.fleet.burstSlopeHorizonSec`
      + `loadgen.prewarm_widths` — the tail-engineering ladder the
      harness motivates.

    The sidecar `load` block carries the ON run's per-phase/per-class
    p50/p99/p99.9 with worst-request trace exemplars (round-tripped
    through the flight-recorder ring), the overrun count (must be 0 —
    an overrun means the harness, not the fleet, shaped the tails), and
    the on-vs-off p99.9 delta on the burst phase. obs/regress.py flags
    a vanished block, tail regressions, overrun growth, or a lost
    engineering win."""
    import shutil
    import tempfile

    import jax
    import pandas as pd

    import sml_tpu.tracking as mlflow
    from sml_tpu import TpuSession, obs
    from sml_tpu.conf import GLOBAL_CONF
    from sml_tpu.fleet import ReplicaPool, Router
    from sml_tpu.loadgen import (OpenLoopDriver, PhaseSpec, TraceSpec,
                                 prewarm_widths)
    from sml_tpu.ml import Pipeline
    from sml_tpu.ml.feature import VectorAssembler
    from sml_tpu.ml.regression import LinearRegression
    from sml_tpu.tracking import _store
    from sml_tpu.utils.profiler import PROFILER

    spec = TraceSpec(
        phases=tuple(PhaseSpec(name, dur, rate * scale,
                               None if rate_end is None
                               else rate_end * scale, arrival)
                     for name, dur, rate, rate_end, arrival
                     in LOAD_PHASES),
        widths=LOAD_WIDTHS, classes=LOAD_CLASSES, seed=LOAD_TRACE_SEED)
    requests = spec.compile()

    prev = {k: GLOBAL_CONF.get(k) for k in (
        "sml.obs.enabled", "sml.profiler.enabled", "sml.obs.ringEvents",
        "sml.serve.sloMillis", "sml.serve.flushAutoTune",
        "sml.fleet.burstSlopeHorizonSec", "sml.load.overrunMicros")}
    prev_uri = _store.get_tracking_uri()
    GLOBAL_CONF.set("sml.obs.enabled", True)
    GLOBAL_CONF.set("sml.profiler.enabled", True)
    # per-request exemplar round-trip scans the ring for every phase's
    # worst request: size it so two full replays cannot evict evidence
    GLOBAL_CONF.set("sml.obs.ringEvents", 1 << 18)
    GLOBAL_CONF.set("sml.serve.sloMillis", LOAD_SLO_MILLIS)
    GLOBAL_CONF.set("sml.load.overrunMicros", LOAD_OVERRUN_MICROS)
    # on a 1-core box the default 5 ms GIL switch interval makes a
    # just-woken driver worker wait many whole slices behind parked
    # scorer threads before it can even STAMP its fire time — that lag
    # books as a harness overrun. Shorter slices trade a little
    # throughput for honest open-loop pickup; restored in the finally
    prev_switch = sys.getswitchinterval()
    sys.setswitchinterval(0.001)
    tmp = tempfile.mkdtemp(prefix="sml-load-bench-")
    mlflow.set_tracking_uri(os.path.join(tmp, "runs"))
    spark = TpuSession.builder.appName("load-bench").getOrCreate()
    timeout_s = float(GLOBAL_CONF.get("sml.load.resultTimeoutSec"))

    def fit():
        rng = np.random.default_rng(3)
        pdf = pd.DataFrame({"a": rng.normal(size=4000),
                            "b": rng.normal(size=4000)})
        pdf["y"] = 2.0 * pdf["a"] - pdf["b"] + 1.0 \
            + rng.normal(0, 0.1, len(pdf))
        va = VectorAssembler(inputCols=["a", "b"], outputCol="features")
        return Pipeline(stages=[va, LinearRegression(labelCol="y")]) \
            .fit(spark.createDataFrame(pdf))

    def one_run(engineering: bool) -> dict:
        """One full replay of the committed trace against a fresh
        2-replica fleet; returns the driver's report plus the fleet's
        final flush deadlines."""
        GLOBAL_CONF.set("sml.serve.flushAutoTune", bool(engineering))
        GLOBAL_CONF.set("sml.fleet.burstSlopeHorizonSec",
                        5.0 if engineering else 0.0)
        pool = ReplicaPool(
            "load-bench-model", replicas=2, canary_fraction=0.0,
            flush_micros=LOAD_OFF_FLUSH_MICROS, queue_rows=4096,
            timeout_millis=0, host_fallback=True,
            blackbox_dir=os.path.join(tmp, "blackbox"))
        try:
            router = Router(pool,
                            priorities=[c for c, _ in LOAD_CLASSES])

            def score(X, priority, model):
                return router.score(X, priority, timeout=timeout_s)

            # both runs see warm per-bucket programs (the suite's
            # compile story is measured elsewhere); the ON run
            # additionally exercises the declared-width-mix prewarm
            # path the trace's spec feeds. Beyond the declared widths,
            # also warm the AGGREGATE buckets a backed-up flush can
            # reach (the batcher concatenates its whole queue, so the
            # bucketed batch width exceeds any single request's) — a
            # mid-replay compile stalls the 1-core interpreter long
            # enough to book harness overruns
            for rows in [w for w, _ in LOAD_WIDTHS] + [512, 1024, 2048]:
                score(np.zeros((rows, 2), dtype=np.float32), "high",
                      None)
            prewarm_stats = None
            if engineering:
                prewarm_stats = prewarm_widths(
                    lambda X: score(X, "high", None), spec,
                    feature_dim=2)
            obs.METRICS.reset()  # each replay owns its distributions
            # worker budget: a worker is just a thread parked in
            # result(), so cover the trace's worst case — peak arrival
            # rate x the worst observed latency (the OFF run's
            # mis-tuned deadline backs requests up ~250ms under the 3x
            # burst) — WITHOUT oversubscribing the host: on a 1-core
            # bench box every extra runnable thread steals GIL slices
            # from the dispatch loop and manufactures harness overruns
            driver = OpenLoopDriver(score, requests, feature_dim=2,
                                    workers=96)
            report = driver.run()
            report["flush_micros"] = sorted(
                r.endpoint._batcher.flush_micros
                for r in pool.replicas())
            report["prewarm"] = prewarm_stats
            return report
        finally:
            pool.close()

    try:
        obs.reset()
        with mlflow.start_run():
            mlflow.spark.log_model(
                fit(), "model", registered_model_name="load-bench-model")
        _store.set_version_stage("load-bench-model", 1, "Production")

        off = one_run(engineering=False)
        on = one_run(engineering=True)

        # ---- exemplar round-trip: each phase's worst request must be
        # recoverable in the flight-recorder ring by its trace id ----
        ring_traces = set()
        for ev in obs.RECORDER.events():
            if ev.name == "trace.request":
                ring_traces.add((ev.args or {}).get("trace"))
        exemplars = {}
        for name, ph in on["phases"].items():
            hexid = ph.get("worst_trace")
            exemplars[name] = bool(
                hexid and int(hexid, 16) in ring_traces)
        exemplar_ok = bool(exemplars) and all(exemplars.values())

        off_p999 = off["phases"]["burst"]["p999_ms"]
        on_p999 = on["phases"]["burst"]["p999_ms"]
        counters = PROFILER.counters()
        block = dict(on)
        block.update({
            "backend": jax.default_backend(),
            "open_loop": True,
            "trace": {
                "seed": LOAD_TRACE_SEED,
                "scale": float(scale),
                "phases": [{"name": n, "duration_s": d, "rate": r,
                            "rate_end": re_, "arrival": a}
                           for n, d, r, re_, a in LOAD_PHASES],
                "widths": [list(w) for w in LOAD_WIDTHS],
                "classes": [list(c) for c in LOAD_CLASSES],
            },
            "slo_millis": LOAD_SLO_MILLIS,
            "off_flush_micros": LOAD_OFF_FLUSH_MICROS,
            "overrun_micros": LOAD_OVERRUN_MICROS,
            "engineering": {
                "off": {"p999_ms": off_p999,
                        "p99_ms": off["phases"]["burst"]["p99_ms"],
                        "overrun": off["overrun"],
                        "flush_micros": off["flush_micros"]},
                "on": {"p999_ms": on_p999,
                       "p99_ms": on["phases"]["burst"]["p99_ms"],
                       "overrun": on["overrun"],
                       "flush_micros": on["flush_micros"]},
                "delta_p999_ms": round(off_p999 - on_p999, 3),
                "win": bool(on_p999 < off_p999),
                "burst_tighten": counters.get("fleet.burst_tighten",
                                              0.0),
                "speculative_prewarm": on.get("prewarm"),
            },
            "exemplars_recovered": exemplars,
            "exemplar_roundtrip_ok": bool(exemplar_ok),
            "note": "open loop: TraceSpec(steady -> 3x burst -> ramp) "
                    "replayed at the SCHEDULE through OpenLoopDriver "
                    "over a 2-replica Router fleet; latency charged "
                    "from scheduled arrival (docs/LOADGEN.md). "
                    "engineering = flushAutoTune + burstSlope "
                    "admission + declared-width prewarm, on vs off",
        })
        overruns = int(off["overrun"]) + int(on["overrun"])
        ok = (overruns == 0
              and block["engineering"]["win"]
              and exemplar_ok
              and int(on["served"]) > 0)
        block["load_ok"] = bool(ok)
        print(f"  load: {on['requests']} open-loop requests/run "
              f"({len(on['phases'])} phases), overruns "
              f"off/on = {off['overrun']}/{on['overrun']}, burst "
              f"p99.9 off {off_p999:.1f}ms -> on {on_p999:.1f}ms "
              f"({'WIN' if block['engineering']['win'] else 'LOST'}), "
              f"exemplars {'ok' if exemplar_ok else 'LOST'}",
              file=sys.stderr)
        return block
    finally:
        sys.setswitchinterval(prev_switch)
        for k, v in prev.items():
            GLOBAL_CONF.set(k, v)
        mlflow.set_tracking_uri(prev_uri)
        shutil.rmtree(tmp, ignore_errors=True)


def load_main(scale: float) -> None:
    """Run the open-loop load leg standalone, merge the `load` block
    into the bench sidecar, and print the short headline JSON last."""
    block = run_load(scale)
    doc = {}
    if os.path.exists(LEGS_FILE):
        with open(LEGS_FILE) as f:
            doc = json.load(f)
    doc["load"] = block
    with open(LEGS_FILE, "w") as f:
        json.dump(doc, f, indent=1)
    print(json.dumps({
        "metric": "open-loop trace harness (coordinated-omission-free "
                  "tails + tail-engineering on-vs-off)",
        "value": 1.0 if block["load_ok"] else 0.0,
        "unit": "1 = zero overruns + burst-phase p99.9 win (auto-tune "
                "+ burst admission + width prewarm) + per-phase worst-"
                "request exemplars recoverable",
        "requests": block["requests"],
        "overrun": block["overrun"],
        "burst_p999_off_ms": block["engineering"]["off"]["p999_ms"],
        "burst_p999_on_ms": block["engineering"]["on"]["p999_ms"],
        "backend": block["backend"],
        "legs_file": "bench_legs.json",
    }))
    if not block["load_ok"]:
        sys.exit(1)


# ----------------------------------------------------------------- goldens
def check_goldens(metrics):
    """Compare this run's metric values against the CPU-mesh 1M-row pins
    (GOLDEN.json `bench_metrics_1m`, written by --pin-goldens). Relative
    tolerances per metric (GOLDEN_TOLERANCES); exact counts must match
    exactly. Returns (ok, drifts)."""
    try:
        with open(GOLDEN_FILE) as f:
            golden = json.load(f)
    except OSError:
        return True, {"note": "no GOLDEN.json"}
    pins = golden.get("bench_metrics_1m", {}).get("metrics")
    if not pins:
        return True, {"note": "no bench_metrics_1m pins"}
    drifts = {}
    ok = True
    for k, pinned in pins.items():
        if k not in metrics:
            continue
        got = float(metrics[k])
        if k in ("rows_scored", "groups", "kmeans_k", "scale_d"):
            if int(got) != int(pinned):
                ok = False
                drifts[k] = {"pinned": pinned, "got": got, "exact": True}
            continue
        tol = GOLDEN_TOLERANCES.get(k, 0.05)
        rel = abs(got - float(pinned)) / max(abs(float(pinned)), 1e-12)
        if rel > tol:
            ok = False
            drifts[k] = {"pinned": float(pinned), "got": got,
                         "rel_drift": round(rel, 5), "tol": tol}
    return ok, drifts


def pin_goldens():
    """Run the suite ONCE on the current backend (meant for the virtual
    8-device CPU mesh) and write the metric pins the TPU run is checked
    against. The 8M scale leg is skipped — its device programs take tens
    of minutes on a CPU mesh; scale metrics are recorded (unpinned) in
    the bench JSON."""
    import jax
    df, pdf = build_dataset(N_ROWS)
    df.cache()
    ratings_df, _ = build_ratings(N_RATINGS)
    ratings_df.cache()
    _, metrics, _, _ = run_suite(df, N_ROWS, ratings_df, with_scale=False)
    with open(GOLDEN_FILE) as f:
        golden = json.load(f)
    golden["bench_metrics_1m"] = {
        "backend": jax.default_backend(),
        "n_rows": N_ROWS,
        "note": "suite metrics pinned on the virtual 8-device CPU mesh "
                "(f32); the TPU bench asserts its metrics within "
                "GOLDEN_TOLERANCES of these",
        # serve_* metrics are LOAD numbers (latency/occupancy under this
        # machine's contention), not model outputs — never pinned
        "metrics": {k: (round(float(v), 6) if isinstance(v, float)
                        else v) for k, v in metrics.items()
                    if not k.startswith("serve_")},
    }
    with open(GOLDEN_FILE, "w") as f:
        json.dump(golden, f, indent=1)
    print(json.dumps({"pinned": golden["bench_metrics_1m"]["metrics"]},
                     default=float))


def main():
    import jax
    backend = jax.default_backend()
    device = {"platform": jax.devices()[0].platform,
              "kind": jax.devices()[0].device_kind,
              "count": len(jax.devices())}
    print(f"devices: {device}", file=sys.stderr)
    df, pdf = build_dataset(N_ROWS)
    df.cache()
    ratings_df, ratings_pdf = build_ratings(N_RATINGS)
    ratings_df.cache()
    base = get_host_baseline(pdf, ratings_pdf)

    from sml_tpu.conf import GLOBAL_CONF
    GLOBAL_CONF.set("sml.profiler.enabled", True)
    build_scale_parts()  # data gen + prep OUTSIDE the warmup accounting

    # opt-in (--prewarm / sml.prewarm.enabled): replay the program-prewarm
    # manifest BEFORE the warmup passes — every recorded program signature
    # rebuilds and first-dispatches from a concurrent pool, so the ~25
    # serial first-dispatch payments the r01 warmup measured overlap.
    # serial_s/wall_s in the sidecar is the overlap actually bought.
    prewarm_stats = None
    if GLOBAL_CONF.getBool("sml.prewarm.enabled"):
        from sml_tpu.parallel import prewarm as _prewarm
        prewarm_stats = _prewarm.prewarm()
        prewarm_stats = {k: (round(v, 3) if isinstance(v, float) else v)
                         for k, v in prewarm_stats.items()}
        print(f"prewarm: {prewarm_stats}", file=sys.stderr)

    # first/second identical-shape fit in a FRESH process: the quantized
    # bin cache + program caches + persistent compile cache at work (this
    # also pre-warms the ml11-shaped programs, shrinking warmup pass 1)
    sf_probe = second_fit_probe(df.randomSplit([0.8, 0.2], seed=42)[0])

    # TWO warmup passes at FULL shapes: pass 1 pays cold compiles, route
    # discovery, and background promotion of the datasets into HBM; pass 2
    # pays the post-promotion device-program compiles. The timed passes then
    # measure the converged steady state. Total warmup cost is reported as
    # compile_seconds — compile economics are part of the story, not
    # discarded (SURVEY §7 hard-part #6).
    t0 = time.perf_counter()
    run_suite(df, N_ROWS, ratings_df)
    pass1 = time.perf_counter() - t0
    t0 = time.perf_counter()
    run_suite(df, N_ROWS, ratings_df)
    pass2 = time.perf_counter() - t0
    warmup_secs = pass1 + pass2
    cal_probe = probe()

    # THREE timed passes. Each leg reports its BEST seconds across the
    # passes: the host can be co-tenant-loaded (observed: the same ALS
    # fit at 1.6s and 15.8s within an hour, code identical). Per-pass
    # walls and probes are all recorded; a globally-noisy session trips
    # interference_suspected.
    from sml_tpu.utils.profiler import PROFILER
    passes = []
    for i in range(3):
        PROFILER.reset()
        p_before = probe()
        t0 = time.perf_counter()
        timings, metrics, flops, eng_legs = run_suite(df, N_ROWS, ratings_df)
        wall = time.perf_counter() - t0
        passes.append({"wall": wall, "timings": timings, "metrics": metrics,
                       "flops": flops, "engine_counters": eng_legs,
                       "probe_before": p_before,
                       "probe_after": probe(),
                       "profiler": PROFILER.report()})
    pass_walls = [round(p["wall"], 3) for p in passes]
    best_pass = min(passes, key=lambda p: p["wall"])
    metrics, flops = best_pass["metrics"], best_pass["flops"]

    # per-leg best across passes (the pass index is recorded per leg)
    leg_secs, leg_pass = {}, {}
    for k in best_pass["timings"]:
        vals = [p["timings"][k] for p in passes]
        leg_secs[k] = min(vals)
        leg_pass[k] = int(np.argmin(vals))
    value = sum(leg_secs.values())

    # per-run host re-measure of every cheap leg (same machine, same
    # session — r4's fairness gap), best of HOST_TIMED_PASSES to match the
    # device legs' best-of-3 discipline; expensive legs keep the cached
    # anchor
    # a leg MISSING from the committed cache (e.g. a newly added leg) is
    # treated as cheap and measured fresh this run, so adding a leg does
    # not force a LEGS_VERSION bump (= a full multi-minute re-measure of
    # the expensive cached legs)
    thin = [k for k in leg_secs
            if base.get(k, 0.0) < HOST_REMEASURE_CUTOFF_S]
    print(f"re-measuring host baseline for cheap legs "
          f"(best of {HOST_TIMED_PASSES}): {thin}", file=sys.stderr)
    host_passes = [run_host_baseline(pdf, ratings_pdf, only=set(thin))
                   for _ in range(HOST_TIMED_PASSES)]
    fresh = {k: min(p[k] for p in host_passes if k in p)
             for k in set().union(*host_passes)}
    host_eff = {k: fresh.get(k, base.get(k)) for k in leg_secs}
    base_wall = sum(v for v in host_eff.values() if v is not None)

    probes = [{"before": p["probe_before"], "after": p["probe_after"]}
              for p in passes]
    all_dev = [cal_probe["device_ms"]] + \
        [x[k]["device_ms"] for x in probes for k in ("before", "after")]
    all_host = [cal_probe["host_ms"]] + \
        [x[k]["host_ms"] for x in probes for k in ("before", "after")]
    # a wide probe spread means some pass ran while the device/host was
    # co-tenant-loaded — the record says so instead of silently mixing
    # contended and clean measurements
    spread_dev = max(all_dev) / max(min(all_dev), 1e-9)
    spread_host = max(all_host) / max(min(all_host), 1e-9)
    interference = bool(spread_dev > 3.0 or spread_host > 3.0)

    per_leg = {}
    for k in sorted(leg_secs):
        v = leg_secs[k]
        hb = host_eff.get(k)
        leg = {"seconds": round(v, 3),
               "seconds_per_pass": [round(p["timings"][k], 3)
                                    for p in passes],
               "best_pass": leg_pass[k],
               "rows_per_sec": round((N_SCALE if k == "ml_scale"
                                      else N_ROWS) / v, 1),
               "host_baseline_seconds": round(hb, 3) if hb else None,
               "host_measured": (f"this-run-best-of-{HOST_TIMED_PASSES}"
                                 if k in fresh else "cached"),
               "host_seconds_per_pass": ([round(p[k], 3) for p in host_passes
                                          if k in p] if k in fresh else None),
               "speedup_vs_host": round(hb / v, 2) if hb else None,
               # engine-counter deltas for this leg from the BEST pass
               # (one coherent pass snapshot, not a per-leg mix): cache
               # hits/misses, h2d/d2h bytes, shuffle volume, compiles
               "engine_counters": best_pass["engine_counters"].get(k, {})}
        # dispatch-economics attribution (via the obs.note_compile
        # counters): programs first-built-and-dispatched during this leg
        # (a prewarmed run should show ~0 here), distinct program names
        # behind them, and tree-fit dispatch count (the fusion contract)
        eng_k = leg["engine_counters"]
        leg["programs_compiled"] = int(eng_k.get("compile.programs", 0))
        leg["programs_distinct"] = sum(
            1 for c in eng_k if c.startswith("compile.program."))
        leg["tree_fit_dispatches"] = int(eng_k.get("tree.fit_dispatch", 0))
        if k in flops:
            leg["device_flops_est"] = flops[k]
            # histogram legs count scatter-accumulation OPS (XLA rewrites
            # the one-hot dot), linear legs count real MXU flops. No
            # utilization is derived here: that needs a peak keyed by
            # the device kind the run actually found (ROADMAP S2)
            if k == "ml13_applyinpandas":
                # per-group sklearn payload runs on HOST by course design
                # (`ML 13`): zero device flops
                leg["flops_kind"] = "host-sklearn"
            else:
                leg["flops_kind"] = ("mxu-dense" if k in
                                     ("ml02_lr", "ml12_mapinpandas",
                                      "ml_scale")
                                     else "hist-ops")
        per_leg[k] = leg
        print(f"  {k:22s} {v:7.2f}s  (host "
              f"{hb if hb is not None else float('nan'):7.2f}s  "
              f"{per_leg[k].get('speedup_vs_host')}x)", file=sys.stderr)
    for k, v in sorted(metrics.items()):
        val = f"{v:10.3f}" if isinstance(v, (int, float)) else f"{v:>10}"
        print(f"  {k:22s} {val}", file=sys.stderr)

    golden_ok, golden_drifts = (check_goldens(metrics)
                                if backend == "tpu" else (True, {}))

    # compile_seconds = warmup excess over two steady-state passes: the
    # compile + route-discovery + HBM-promotion overhead actually paid,
    # separated from the workload's own runtime. Steady state is the
    # MEDIAN timed pass, not the best — warmup has no contention
    # protection, so subtracting the best-of-3 would book a co-tenant's
    # slowdown as "compile overhead"
    median_wall = sorted(pass_walls)[len(pass_walls) // 2]
    compile_secs = max(0.0, warmup_secs - 2.0 * median_wall)
    print(f"  warmup passes: {pass1:.1f}s + {pass2:.1f}s "
          f"(compile overhead {compile_secs:.1f}s); "
          f"timed passes {pass_walls}; per-leg-best sum {value:.1f}s",
          file=sys.stderr)
    print("---- profiler (best timed pass) ----", file=sys.stderr)
    print(best_pass["profiler"], file=sys.stderr)

    sidecar = {
        "metric": "ml02-ml13 + mle01/mle02 + ml_scale suite (1M-row "
                  "SF-Airbnb-class, MovieLens-1M ALS, 8M-row scale leg)",
        "definition": "per-leg seconds are the BEST of 3 timed passes "
                      "after 2 warmup passes; value = sum of per-leg "
                      "best; re-measured host legs are the BEST of "
                      f"{HOST_TIMED_PASSES} passes (symmetric discipline); "
                      "all per-pass walls/probes recorded here",
        "value": round(value, 3),
        "vs_baseline": round(base_wall / value, 3),
        "baseline_seconds_measured_host": round(base_wall, 3),
        "host_remeasured_this_run": sorted(fresh.keys()),
        "compile_seconds": round(compile_secs, 1),
        "warmup_seconds": round(warmup_secs, 1),
        "warmup_note": "warm persistent cache: programs load as cache "
                       "hits, and what remains is each distinct "
                       "program's first dispatch (python trace + "
                       "executable load), paid once per process",
        "timed_pass_walls": pass_walls,
        "probe_calibration": cal_probe,
        "probes_per_pass": probes,
        "probe_spread": {"device": round(spread_dev, 2),
                         "host": round(spread_host, 2)},
        "interference_suspected": interference,
        "second_fit_probe": sf_probe,
        # warmup attribution for prewarmed runs: programs replayed before
        # the warmup passes, the pool wall-clock, and what those
        # first-dispatches would have cost serially (serial_s / wall_s =
        # overlap factor). None = prewarm off (cold manifest economics)
        "prewarm": prewarm_stats,
        "golden_ok": golden_ok,
        "golden_drifts": golden_drifts,
        "backend": backend,
        "device": device,
        "n_rows": N_ROWS,
        "n_scale_rows": N_SCALE,
        # non-numeric values (the serve_worst_trace exemplar) pass
        # through as annotations — bench_diff only judges numbers
        "metrics": {k: (float(v) if isinstance(v, (int, float)) else v)
                    for k, v in metrics.items()},
        "legs": per_leg,
    }
    if LINT_STATS is not None:
        # the --lint gate's receipts: 0 unsuppressed violations by
        # construction (the gate refuses otherwise); suppression counts
        # and the active-rule census are what obs/regress.py judges
        sidecar["lint"] = LINT_STATS
    # the standalone-leg blocks (--multichip / --kernelbench) merge into
    # this sidecar from their own runs: carry them across a plain suite
    # run instead of silently dropping them — bench_diff treats a
    # vanished kernel block as coverage loss
    if os.path.exists(LEGS_FILE):
        try:
            with open(LEGS_FILE) as f:
                prev_doc = json.load(f)
            for block in ("multichip", "multihost", "kernel",
                          "kernel_infer", "scale", "drift", "lint", "ct",
                          "fleet", "load"):
                if block in prev_doc and block not in sidecar:
                    sidecar[block] = prev_doc[block]
        except (OSError, ValueError):
            pass
    with open(LEGS_FILE, "w") as f:
        json.dump(sidecar, f, indent=1)

    # the headline: SHORT, LAST, parseable inside any tail window
    print(json.dumps({
        "metric": "suite wall-clock (sum of per-leg best-of-3)",
        "value": round(value, 3),
        "unit": "seconds",
        "vs_baseline": round(base_wall / value, 3),
        "compile_seconds": round(compile_secs, 1),
        "prewarm": prewarm_stats,
        "pass_walls": pass_walls,
        "min_leg_speedup": min(v["speedup_vs_host"] for v in per_leg.values()
                               if v["speedup_vs_host"] is not None),
        "second_fit_speedup": sf_probe["speedup"],
        "interference_suspected": interference,
        "golden_ok": golden_ok,
        "backend": backend,
        "device": device,
        "legs_file": "bench_legs.json",
    }))
    if not golden_ok:
        sys.exit(1)


#: stats of the --lint gate run, merged into the sidecar `lint` block
#: (and emitted as lint.* engine counters) so obs/regress.py can flag a
#: violation-count increase or a rule-count decrease between records
LINT_STATS = None


def run_graftlint() -> int:
    """`scripts/graftlint.py`'s engine via the standalone loader (no
    extra process, no jax import on the lint side). ONE lint pass
    produces both the gate verdict and LINT_STATS, so the receipts can
    never disagree with the verdict. Return contract mirrors the
    runner's: 0 clean, 1 violations, 2 internal error — the gate
    refuses to record on anything nonzero."""
    global LINT_STATS
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "_graftlint_runner",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "scripts", "graftlint.py"))
    runner = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(runner)
    lint = runner.load_linter()
    try:
        report = lint.run(root=os.path.dirname(os.path.abspath(__file__)))
    except Exception as e:
        print(f"bench: graftlint internal error: {type(e).__name__}: {e}",
              file=sys.stderr)
        LINT_STATS = None
        return 2
    print(report.format())
    by_rule = {}
    for v in report.violations:
        by_rule[v.rule] = by_rule.get(v.rule, 0) + 1
    LINT_STATS = {
        "rules": len(report.rule_names),
        "files": report.n_files,
        "violations": len(report.violations),
        "violations_by_rule": by_rule,
        "suppressed_pragma": report.n_suppressed_pragma,
        "suppressed_baseline": report.n_suppressed_baseline,
        # per-rule check() wall time (ms) — a rule whose cost quietly
        # balloons shows up in the sidecar and the lint.* counters
        "rule_times_ms": {n: round(t * 1000.0, 3)
                          for n, t in sorted(
                              getattr(report, "rule_times", {}).items())},
    }
    return 0 if report.clean else 1


def _emit_lint_counters() -> None:
    """lint.* engine counters for the flight recorder / per-leg counter
    snapshots — called once the engine is importable (the gate itself
    runs jax-free BEFORE any sml_tpu import)."""
    if LINT_STATS is None:
        return
    from sml_tpu.utils.profiler import PROFILER
    PROFILER.count("lint.runs")
    PROFILER.count("lint.rules", float(LINT_STATS["rules"]))
    PROFILER.count("lint.violations", float(LINT_STATS["violations"]))
    PROFILER.count("lint.suppressed_pragma",
                   float(LINT_STATS["suppressed_pragma"]))
    PROFILER.count("lint.suppressed_baseline",
                   float(LINT_STATS["suppressed_baseline"]))
    for rule_name, n in sorted(LINT_STATS["violations_by_rule"].items()):
        PROFILER.count(f"lint.rule.{rule_name}", float(n))
    for rule_name, ms in LINT_STATS.get("rule_times_ms", {}).items():
        PROFILER.count(f"lint.rule_ms.{rule_name}", float(ms))


if __name__ == "__main__":
    parser = argparse.ArgumentParser()
    parser.add_argument("--pin-goldens", action="store_true",
                        help="run once on the current backend and write "
                             "GOLDEN.json bench_metrics_1m pins")
    parser.add_argument("--prewarm", action="store_true",
                        help="replay the program-prewarm manifest (from a "
                             "previous run's recordings next to the compile "
                             "cache) concurrently before warmup; equivalent "
                             "to setting sml.prewarm.enabled=true")
    parser.add_argument("--multichip", action="store_true",
                        help="run ONLY the multi-chip fit-throughput "
                             "scaling leg over 1..n-device meshes and "
                             "merge the `multichip` block into the "
                             "bench sidecar (simulate chips on CPU with "
                             "XLA_FLAGS=--xla_force_host_platform_"
                             "device_count=8)")
    parser.add_argument("--multichip-rows", type=int, default=MULTICHIP_ROWS,
                        help="row count for the --multichip leg")
    parser.add_argument("--multihost", action="store_true",
                        help="run ONLY the hierarchical DCN-aware "
                             "collective leg over 1..H virtual-host "
                             "meshes (host groups over the live device "
                             "set) and merge the `multihost` block into "
                             "the bench sidecar (simulate hosts on CPU "
                             "with XLA_FLAGS=--xla_force_host_platform_"
                             "device_count=8)")
    parser.add_argument("--multihost-rows", type=int, default=MULTIHOST_ROWS,
                        help="row count for the --multihost leg")
    parser.add_argument("--kernelbench", action="store_true",
                        help="run ONLY the traversal-kernel autotune "
                             "sweep and merge the `kernel_infer` block "
                             "into the bench sidecar; on non-TPU backends "
                             "the pallas path runs in interpret mode "
                             "(parity, not speed)")
    parser.add_argument("--kernelbench-rows", type=int,
                        default=KERNELBENCH_ROWS,
                        help="row count for the --kernelbench leg")
    parser.add_argument("--rows", type=int, default=None,
                        help="run ONLY the out-of-core data-plane leg at "
                             "this many rows (chunked ingest + streamed "
                             "quantization + double-buffered prefetch + "
                             "small fit + streamed predict; e.g. "
                             "--rows 10000000) and merge the `scale` "
                             "block into the bench sidecar")
    parser.add_argument("--drift", action="store_true",
                        help="run ONLY the model/data drift proof leg "
                             "(fit a baseline-carrying model through the "
                             "chunked ingest, then: iid holdout CLEAN, "
                             "injected covariate shift FLAGGED with the "
                             "moved features named, per-chunk ingest "
                             "monitor firing, baseline save/load "
                             "bit-compat) and merge the `drift` block "
                             "into the bench sidecar; exits 1 when any "
                             "proof fails")
    parser.add_argument("--drift-rows", type=int, default=DRIFT_ROWS,
                        help="training rows for the --drift leg")
    parser.add_argument("--ct", action="store_true",
                        help="run ONLY the continuous-training closed-"
                             "loop proof (seed GBT registered + served, "
                             "drifting Delta stream triggers a warm-"
                             "start refit that passes the canary gate "
                             "and hot-swaps Production; iid control "
                             "stream triggers zero refits) and merge "
                             "the `ct` block into the bench sidecar; "
                             "exits 1 when any proof fails")
    parser.add_argument("--ct-rows", type=int, default=CT_ROWS,
                        help="seed-model training rows for the --ct leg")
    parser.add_argument("--fleet", action="store_true",
                        help="run ONLY the multi-replica serving-fleet "
                             "proof (closed-loop priority-classed load "
                             "through the Router over a warm "
                             "ReplicaPool: per-class p50/p99/shed under "
                             "the SLO, one occupancy scale-up + one "
                             "scale-down, a clean staged rollout that "
                             "promotes and a divergent one that "
                             "auto-rolls-back with the evicted "
                             "replica's blackbox bundle, zero hung "
                             "futures, trace fan-in) and merge the "
                             "`fleet` block into the bench sidecar; "
                             "exits 1 when any proof fails")
    parser.add_argument("--fleet-requests", type=int,
                        default=FLEET_REQUESTS,
                        help="closed-loop request count for the "
                             "--fleet leg")
    parser.add_argument("--load", action="store_true",
                        help="run ONLY the open-loop trace-driven load "
                             "proof (committed steady -> 3x-burst -> "
                             "ramp TraceSpec replayed at the SCHEDULE "
                             "through loadgen.OpenLoopDriver over a "
                             "2-replica fleet, coordinated-omission-"
                             "free per-phase/per-class p50/p99/p99.9, "
                             "tail-engineering on-vs-off) and merge "
                             "the `load` block into the bench sidecar; "
                             "refuses a dirty tree like --lint; exits "
                             "1 when any proof fails")
    parser.add_argument("--load-scale", type=float, default=1.0,
                        help="rate multiplier applied to every phase "
                             "of the committed --load trace")
    parser.add_argument("--lint", action="store_true",
                        help="gate the run on a clean graftlint pass: a "
                             "bench record from a tree violating engine "
                             "invariants (stray host syncs, bypassed "
                             "dispatch) measures the wrong engine")
    parser.add_argument("--blackbox-on-fail", action="store_true",
                        help="arm black-box forensics (sml_tpu/obs/"
                             "blackbox.py): run with the flight recorder "
                             "on, and dump a postmortem bundle to "
                             "sml.obs.blackboxDir on an unhandled "
                             "exception, a hard stall, or a failed exit "
                             "— render it with scripts/blackbox_view.py")
    args = parser.parse_args()
    if args.prewarm:
        from sml_tpu.conf import GLOBAL_CONF as _CONF0
        _CONF0.set("sml.prewarm.enabled", True)
    if args.lint or args.load:
        # --load writes a committed, regress-judged record: like --lint,
        # a tree violating engine invariants measures the wrong engine,
        # so the gate refuses to record from one
        if run_graftlint() != 0:
            print("bench: refusing to record — graftlint found violations "
                  "(fix them or run without "
                  f"{'--lint' if args.lint else '--load'})",
                  file=sys.stderr)
            sys.exit(1)
        _emit_lint_counters()
    entry = (pin_goldens if args.pin_goldens else
             (lambda: multichip_main(args.multichip_rows))
             if args.multichip else
             (lambda: multihost_main(args.multihost_rows))
             if args.multihost else
             (lambda: kernelbench_main(args.kernelbench_rows))
             if args.kernelbench else
             (lambda: drift_main(args.drift_rows))
             if args.drift else
             (lambda: ct_main(args.ct_rows))
             if args.ct else
             (lambda: fleet_main(args.fleet_requests))
             if args.fleet else
             (lambda: load_main(args.load_scale))
             if args.load else
             (lambda: scale_main(args.rows))
             if args.rows else main)
    if args.blackbox_on_fail:
        from sml_tpu.conf import GLOBAL_CONF as _CONF1
        from sml_tpu.obs import blackbox as _blackbox
        _CONF1.set("sml.obs.enabled", True)
        _blackbox.install()
        try:
            entry()
        except SystemExit as e:
            # the excepthook never sees SystemExit (a golden-gate
            # failure exits 1 that way) — dump here; every OTHER
            # exception propagates to the armed excepthook, which dumps
            # exactly once
            if e.code not in (None, 0):
                path = _blackbox.dump_blackbox("bench-failure",
                                               exc=sys.exc_info())
                print(f"bench: blackbox bundle written: {path} "
                      f"(render with scripts/blackbox_view.py)",
                      file=sys.stderr)
            raise
    else:
        entry()
