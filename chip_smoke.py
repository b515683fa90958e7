#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, default conf, no arguments: drives the main path once through
the entry points a user calls, at the full width of an `ml11`-shaped boosted
ensemble (1M-row SF-Airbnb-shaped table from seed 42, 80/20 split, Imputer +
StringIndexer + VectorAssembler + XgboostRegressor(40 rounds, depth 6,
64 bins) on log(price)):

  train   Pipeline.fit on the 800k-row split (twice: first result, then steady)
  eval    model.transform(test) + exp link + RegressionEvaluator (the fused
          eval-pushdown program), rmse within 5% of GOLDEN_RMSE_XGB
  serve   registry -> Production -> ServingEndpoint: 64 requests of 8 rows
          and one of 256, each equal to the batch prediction for its rows
  proof   from the program's own counters (sml.obs.enabled): every audited
          dispatch on route `device`, no host-routed / shed request, no
          interpreted or fallen-back kernel, bins on TPU devices (one shard
          per chip and an all-reduce in the fit program on several chips),
          the one-hot histogram operand compiled OUTSIDE the loop over rounds,
          the three native host libraries loaded, and the compiled scoring
          kernel agreeing with the XLA traversal

It refuses to start unless `jax.devices()[0].platform == "tpu"` and never
sets `jax_platforms`. No phase is wrapped in a try/except: an exception
ends the run. Exit status is 0 only if every check passed, and only then
is the last line of stdout the JSON result

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

`--rows N` is a REHEARSAL for the CPU (debug here, measure there): the same
phases at a small size on whatever platform jax finds; it says that it is
one, skips the two checks that only the full width on the chip can answer
(the golden band, `kernel.interpret == 0`), and its result line carries
`"rehearsal": true`. `--conf KEY=VALUE` (rehearsal only) sets an
`sml.*` key, e.g. `--conf sml.infer.kernel=pallas` for interpret mode.

The compile cache is where `JAX_COMPILATION_CACHE_DIR` says, else
`<checkout>/.jax_cache`; entries are counted before and after.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

FULL_ROWS = 1_000_000
# rmse of this pipeline at FULL_ROWS, seed 42, pinned on the virtual
# 8-device CPU mesh with float32 histograms; the chip's bf16 histogram
# operands land within the band
GOLDEN_RMSE_XGB = 63.958794
GOLDEN_TOL = 0.05
CAT_COLS = ["neighbourhood_cleansed", "room_type", "property_type"]
NUM_COLS = ["accommodates", "bathrooms", "bedrooms", "beds",
            "minimum_nights", "number_of_reviews", "review_scores_rating"]
# Per-row where-sums of the traversal are exact in f32 (one nonzero term),
# so two scorings of a row can differ only in the order of the 40-term
# weighted tree sum: sequential in the Pallas kernel, XLA-determined on the
# XLA path — a few f32 ulps of a log-price margin near 5
SCORE_RTOL = 1e-5
MODEL_NAME = "chip-smoke-xgb"
HERE = os.path.dirname(os.path.abspath(__file__))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=None,
                    help="REHEARSAL at this many rows on any platform")
    ap.add_argument("--conf", action="append", default=[],
                    metavar="KEY=VALUE", help="rehearsal only: set a conf key")
    ap.add_argument("--out", default=os.path.join(HERE, "chiprun_out",
                                                  "chip_smoke"),
                    help="output directory (registry, summary.json)")
    args = ap.parse_args()
    rehearsal = args.rows is not None
    if args.conf and not rehearsal:
        ap.error("--conf is for a --rows rehearsal; the smoke runs default conf")
    rows = args.rows if rehearsal else FULL_ROWS
    t_start = time.perf_counter()

    # ------------------------------------------------------------ 1. device
    import jax
    dev0 = jax.devices()[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices())}
    on_tpu = dev0.platform == "tpu"
    if not on_tpu and not rehearsal:
        print(f"chip_smoke: jax found {device}; this smoke runs only on a "
              f"TPU (rehearse on the CPU with --rows N)", file=sys.stderr)
        return 2
    if rehearsal:
        print(f"REHEARSAL: {rows} rows on {device} — not a chip run, "
              f"no device number below means anything")

    compiles = {"requests": 0, "cache_hits": 0, "cache_writes": 0,
                "backend_s": 0.0}

    def _on_event(event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            compiles["requests"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            compiles["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            compiles["cache_writes"] += 1

    def _on_duration(event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles["backend_s"] += duration_secs

    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)

    import importlib.metadata as md

    import numpy as np

    import sml_tpu  # noqa: F401 — places the compile cache at import
    import sml_tpu.tracking as mlflow
    from sml_tpu import obs
    from sml_tpu.conf import GLOBAL_CONF
    from sml_tpu.courseware import make_airbnb_dataset
    from sml_tpu.frame import functions as F
    from sml_tpu.frame.session import get_session
    from sml_tpu.ml import Pipeline, _staging, inference, tree_impl
    from sml_tpu.ml.evaluation import RegressionEvaluator
    from sml_tpu.ml.feature import Imputer, StringIndexer, VectorAssembler
    from sml_tpu.ml.linalg import to_matrix
    from sml_tpu.native import build as native_build
    from sml_tpu.parallel import dispatch, mesh as meshlib
    from sml_tpu.serving import ServingEndpoint
    from sml_tpu.xgboost import XgboostRegressor

    cache_dir = dispatch.ensure_compile_cache()

    def cache_entries() -> int:
        if not os.path.isdir(cache_dir):
            return 0
        return sum(1 for f in os.listdir(cache_dir) if f.endswith("-cache"))

    entries_before = cache_entries()
    versions = {p: md.version(p) for p in ("jax", "jaxlib", "libtpu")}
    print(f"platform={device['platform']} device_kind={device['kind']!r} "
          f"count={device['count']}")
    print(f"versions: {versions}")
    print(f"compile cache: {cache_dir} "
          f"({'JAX_COMPILATION_CACHE_DIR' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'checkout default'}), "
          f"{entries_before} entries before")

    for kv in args.conf:
        key, _, value = kv.partition("=")
        GLOBAL_CONF.set(key, value)
        print(f"REHEARSAL conf: {key}={GLOBAL_CONF.get(key)!r}")
    GLOBAL_CONF.set("sml.obs.enabled", True)
    obs.reset()
    modes = {k: GLOBAL_CONF.get(k) for k in
             ("sml.dispatch.mode", "sml.infer.kernel")}
    print(f"conf: {modes}")

    failures = []

    def check(name: str, ok: bool, detail: str = "") -> None:
        print(f"  [{'PASS' if ok else 'FAIL'}] {name}" +
              (f": {detail}" if detail else ""))
        if not ok:
            failures.append(name)

    # -------------------------------------------------------------- data
    t0 = time.perf_counter()
    pdf = make_airbnb_dataset(n=rows, seed=42)
    df = get_session().createDataFrame(pdf)
    train, test = df.randomSplit([0.8, 0.2], seed=42)
    train.cache()
    test.cache()
    log_train = train.withColumn("label", F.log(F.col("price")))
    log_test = test.withColumn("label", F.log(F.col("price")))
    n_train, n_test = log_train.count(), log_test.count()
    print(f"data: {n_train} train / {n_test} test rows "
          f"({time.perf_counter() - t0:.1f}s host)")
    if n_test < 768:
        print(f"chip_smoke: the serve phase needs 768 test rows, this split "
              f"has {n_test}: rehearse with --rows 5000 or more",
              file=sys.stderr)
        return 2

    idx = [c + "_idx" for c in CAT_COLS]
    imp = [c + "_imp" for c in NUM_COLS]

    def pipeline():
        return Pipeline(stages=[
            Imputer(strategy="median", inputCols=NUM_COLS, outputCols=imp),
            StringIndexer(inputCols=CAT_COLS, outputCols=idx,
                          handleInvalid="skip"),
            VectorAssembler(inputCols=idx + imp, outputCol="features"),
            XgboostRegressor(n_estimators=40, learning_rate=0.15,
                             max_depth=6, max_bins=64, random_state=42)])

    # ---------------------------------------------------------- 2. train
    print("train:")
    t0 = time.perf_counter()
    model = pipeline().fit(log_train)
    fit_first_s = time.perf_counter() - t0
    compiles_fit = dict(compiles)
    t0 = time.perf_counter()
    model2 = pipeline().fit(log_train)
    fit_steady_s = time.perf_counter() - t0
    spec, spec2 = model.stages[-1]._spec, model2.stages[-1]._spec
    sf, sb, lv, _w = spec.stacked()
    sf2, sb2, lv2, _w2 = spec2.stacked()
    print(f"  first fit {fit_first_s:.2f}s (compile included), "
          f"steady fit {fit_steady_s:.2f}s; {len(spec.trees)} trees, "
          f"depth {spec.depth}, {spec.n_features} features")
    check("40 trees of depth 6 over 10 features",
          (len(spec.trees), spec.depth, spec.n_features) == (40, 6, 10))
    check("leaf values finite", bool(np.isfinite(lv).all()))
    check("trees split", int((sf >= 0).sum()) >= 40,
          f"{int((sf >= 0).sum())} internal nodes")
    check("second fit builds the same ensemble",
          np.array_equal(sf, sf2) and np.array_equal(sb, sb2)
          and np.array_equal(lv, lv2) and spec.base == spec2.base)

    # ----------------------------------------------------------- 3. eval
    print("eval:")
    ev = RegressionEvaluator(labelCol="price")

    def fused_rmse():
        pred = model.transform(log_test).withColumn(
            "prediction", F.exp(F.col("prediction")))
        return ev.evaluate(pred)

    c0 = obs.RECORDER.counters()
    t0 = time.perf_counter()
    rmse = fused_rmse()
    eval_first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    rmse_again = fused_rmse()
    eval_steady_s = time.perf_counter() - t0
    c1 = obs.RECORDER.counters()
    eval_programs = sorted(k for k in c1 if k.startswith(
        "compile.program.forest_eval") and c1[k] > c0.get(k, 0.0))
    print(f"  rmse_xgb {rmse:.6f}; first eval {eval_first_s:.2f}s "
          f"(compile included), steady eval {eval_steady_s:.3f}s")
    check("fused eval-pushdown program served the metric",
          bool(eval_programs), ", ".join(eval_programs))
    check("rmse finite and repeatable",
          bool(np.isfinite(rmse)) and rmse == rmse_again)
    if rows == FULL_ROWS:
        drift = abs(rmse - GOLDEN_RMSE_XGB) / GOLDEN_RMSE_XGB
        check(f"rmse_xgb within {GOLDEN_TOL:.0%} of {GOLDEN_RMSE_XGB}",
              drift <= GOLDEN_TOL, f"drift {drift:.4%}")
    else:
        print(f"  (golden band {GOLDEN_RMSE_XGB} is pinned at {FULL_ROWS} rows: "
              f"not checked in a rehearsal)")

    # the batch transform, materialized: the rows, their feature block and
    # their predictions — what the served answers must equal
    t0 = time.perf_counter()
    out = model.transform(log_test).toPandas()
    transform_s = time.perf_counter() - t0
    X = np.ascontiguousarray(to_matrix(out["features"]), dtype=np.float32)
    batch_pred = np.asarray(out["prediction"], dtype=np.float64)
    price = np.asarray(out["price"], dtype=np.float64)
    host_rmse = float(np.sqrt(np.mean((np.exp(batch_pred) - price) ** 2)))
    print(f"  first batch transform {len(out)} rows in {transform_s:.2f}s "
          f"(compile included); rmse from its predictions {host_rmse:.6f}")
    check("batch predictions finite, one per row",
          batch_pred.shape == (n_test,) and bool(np.isfinite(batch_pred).all()))
    check("fused rmse equals the rmse of the batch predictions",
          abs(rmse - host_rmse) <= 1e-4 * host_rmse,
          f"|diff| {abs(rmse - host_rmse):.3e}")

    # ---------------------------------------------------------- 4. serve
    print("serve:")
    registry = os.path.join(args.out, "registry")
    shutil.rmtree(registry, ignore_errors=True)
    os.makedirs(registry)
    mlflow.set_tracking_uri(registry)
    with mlflow.start_run():
        mlflow.spark.log_model(model, "model",
                               registered_model_name=MODEL_NAME)
    mlflow.MlflowClient().transition_model_version_stage(
        MODEL_NAME, 1, stage="Production")
    requests = [(i * 8, 8) for i in range(64)] + [(512, 256)]
    lat_ms, worst = [], 0.0
    with ServingEndpoint(MODEL_NAME, "Production") as ep:
        for lo, n in requests:
            t0 = time.perf_counter()
            answer = np.asarray(ep.score(X[lo:lo + n], timeout=600.0))
            lat_ms.append((time.perf_counter() - t0) * 1e3)
            want = batch_pred[lo:lo + n]
            ok = answer.shape == want.shape and np.allclose(
                answer, want, rtol=SCORE_RTOL, atol=0.0)
            if not ok:
                check(f"request rows [{lo}, {lo + n}) equals batch", False,
                      f"max |diff| {np.abs(answer - want).max():.3e}")
            worst = max(worst, float(np.abs(answer - want).max()))
        served_version = ep.current_version()
    steady = sorted(lat_ms[1:64])
    median_ms = steady[len(steady) // 2]
    print(f"  v{served_version}: first request {lat_ms[0]:.1f} ms (compile "
          f"included), next 63 median {median_ms:.2f} ms / "
          f"max {steady[-1]:.2f} ms, 256-row request {lat_ms[64]:.1f} ms "
          f"(first of its shape)")
    check(f"65 served answers equal the batch predictions "
          f"(rtol {SCORE_RTOL:g})", len(lat_ms) == 65, f"max |diff| {worst:.3e}")

    # ------------------------------------------ 5. where it ran (counters)
    print("proof:")
    c = obs.RECORDER.counters()
    audit = obs.audit_records()
    routes = sorted({r.route for r in audit})
    reasons = sorted({r.reason for r in audit})
    check("every audited dispatch on route `device`",
          bool(audit) and routes == ["device"],
          f"{len(audit)} rows, routes {routes}, reasons {reasons}")
    for name in ("serve.host_routed", "serve.shed", "infer.kernel.fallback"):
        check(f"{name} == 0", c.get(name, 0.0) == 0.0, str(c.get(name, 0.0)))
    if on_tpu:
        check("kernel.interpret == 0", c.get("kernel.interpret", 0.0) == 0.0,
              str(c.get("kernel.interpret", 0.0)))
    else:
        print(f"  (kernel.interpret = {c.get('kernel.interpret', 0.0):.0f}: "
              f"Pallas runs interpreted off the chip)")
    check("serve.requests == 65 in as many device batches",
          c.get("serve.requests", 0.0) == 65.0
          and c.get("serve.batches", 0.0) == 65.0,
          f"{c.get('serve.requests', 0.0):.0f} requests, "
          f"{c.get('serve.batches', 0.0):.0f} batches")

    mesh = meshlib.get_mesh()
    # default conf runs the whole ensemble as ONE scan program
    # (sml.tree.roundsPerDispatch = 0): `tree_impl._ensemble_compiled`
    fit_progs = [(k, v) for k, v in tree_impl._ensemble_cache.items()
                 if id(mesh) in k]
    score_kernel = inference.kernel_report()["kernel"]
    check("one fit program", len(fit_progs) == 1, f"{len(fit_progs)} programs")
    print(f"  kernel that scored: {score_kernel} (kernel.pallas_launch "
          f"traced {c.get('kernel.pallas_launch', 0.0):.0f}x; "
          f"resolutions: pallas "
          f"{c.get('infer.kernel.pallas', 0.0):.0f}, xla "
          f"{c.get('infer.kernel.xla', 0.0):.0f})")
    check("scoring resolved to one kernel throughout",
          (c.get("infer.kernel.pallas", 0.0) == 0.0)
          != (c.get("infer.kernel.xla", 0.0) == 0.0))
    if on_tpu and not rehearsal:
        check("auto resolved as recorded for TPU (score pallas)",
              score_kernel == "pallas")

    bins = _staging.bin_cache_arrays()
    train_bins = max(bins, key=lambda a: a.shape[0])
    platforms = sorted({d.platform for a in bins for d in a.devices()})
    check(f"staged bin matrices live on {device['platform']} devices",
          bool(bins) and platforms == [device["platform"]],
          f"{len(bins)} matrices, train {train_bins.shape} "
          f"{train_bins.dtype} on {len(train_bins.devices())} device(s)")
    shard_devs = sorted(s.device.id for s in train_bins.addressable_shards)
    check("one addressable shard of the bin matrix on each chip",
          shard_devs == sorted(d.id for d in jax.devices()),
          f"shards on devices {shard_devs}")
    # the executable the fits just ran, compiled again from the very arrays
    # they were given (a compile-cache hit on the chip)
    row = jax.ShapeDtypeStruct((train_bins.shape[0],), np.float32,
                               sharding=meshlib.data_sharding(mesh, 1))
    hlo = fit_progs[0][1].lower(
        train_bins, row, row,
        jax.ShapeDtypeStruct((2,), np.uint32)).compile().as_text()
    in_loop = tree_impl.ops_in_loop_bodies(hlo, "tree.operand")
    check("the one-hot operand is built outside the loop over rounds",
          "tree.operand" in hlo and not in_loop
          and bool(tree_impl.ops_in_loop_bodies(hlo, "tree.hist")),
          f"{len(in_loop)} tree.operand instructions in a while body"
          + (f": {in_loop[:4]}" if in_loop else ""))
    if device["count"] > 1:
        check("the compiled boosting program contains an all-reduce",
              "all-reduce" in hlo, f"{hlo.count('all-reduce')} mentions")

    for name in ("murmur3", "xorshift", "binning"):
        native_build.load_library(name)  # builds on first use, else cached
    libs = native_build.status()
    check("native host libraries murmur3, xorshift, binning loaded",
          all(n in libs and libs[n] is None
              for n in ("murmur3", "xorshift", "binning")),
          str({k: ("loaded" if v is None else v) for k, v in libs.items()}))

    cal = dispatch.CALIBRATION.ensure()
    print(f"  link calibration: rt_fixed {cal.rt_fixed * 1e6:.0f} us, "
          f"h2d {cal.h2d_bw / 1e9:.2f} GB/s, d2h {cal.d2h_bw / 1e9:.2f} GB/s; "
          f"locally attached: {dispatch._locally_attached()}")

    # ------------------- kernel agreement with the XLA path (not timed)
    parity = None
    if score_kernel == "pallas":
        GLOBAL_CONF.set("sml.infer.kernel", "xla")
        xla_pred = np.asarray(model.transform(log_test).toPandas()
                              ["prediction"], dtype=np.float64)
        GLOBAL_CONF.set("sml.infer.kernel", modes["sml.infer.kernel"])
        parity = float(np.abs(xla_pred - batch_pred).max())
        check(f"forest_traverse (pallas) agrees with the XLA traversal on "
              f"{n_test} rows (rtol {SCORE_RTOL:g})",
              np.allclose(batch_pred, xla_pred, rtol=SCORE_RTOL, atol=0.0),
              f"max |diff| {parity:.3e}")

    # ----------------------------------------------------- 6. the numbers
    entries_after = cache_entries()
    total_s = time.perf_counter() - t_start
    c = obs.RECORDER.counters()
    summary = {
        "device": device, "rehearsal": rehearsal, "rows": rows,
        "versions": versions, "conf": modes,
        "rmse_xgb": rmse, "golden_rmse_xgb": GOLDEN_RMSE_XGB,
        "score_kernel": score_kernel,
        "pallas_vs_xla_max_abs_diff": parity,
        "seconds_to_first_result": {
            "fit": round(fit_first_s, 3), "eval": round(eval_first_s, 3),
            "batch_transform": round(transform_s, 3),
            "serve_request": round(lat_ms[0] / 1e3, 4)},
        "steady": {
            "fit_s": round(fit_steady_s, 3),
            "eval_s": round(eval_steady_s, 4),
            "serve_request_ms_median": round(median_ms, 3),
            "serve_request_ms_max": round(steady[-1], 3)},
        "compiles": {
            "programs_built": int(c.get("compile.programs", 0.0)),
            "xla_compile_requests": compiles["requests"],
            "persistent_cache_hits": compiles["cache_hits"],
            "persistent_cache_writes": compiles["cache_writes"],
            "backend_compile_s": round(compiles["backend_s"], 2),
            "backend_compile_s_first_fit": round(compiles_fit["backend_s"], 2)},
        "compile_cache": {"dir": cache_dir, "entries_before": entries_before,
                          "entries_after": entries_after},
        "calibration": {"rt_fixed_s": cal.rt_fixed, "h2d_bw": cal.h2d_bw,
                        "d2h_bw": cal.d2h_bw},
        "audit_rows": len(audit), "total_s": round(total_s, 1),
        "failures": failures,
    }
    print(f"compiles: {summary['compiles']}")
    print(f"compile cache entries: {entries_before} before, "
          f"{entries_after} after")
    print(f"total {total_s:.1f}s")
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)

    if failures:
        print(f"chip_smoke FAILED: {failures}", file=sys.stderr)
        return 1
    result = {"ok": True, "device": device}
    if rehearsal:
        result["rehearsal"] = True
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
