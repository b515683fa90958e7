"""Traffic kind `fit`: `Pipeline.fit` on tables this process has not fitted.

Set-up makes the seeded table once and fits `warm_iterations` splits of it
(so every program is compiled and loaded and the host's allocator is warm). The window then repeats: draw a NEW
`randomSplit` of the table (seed = --seed + iteration, outside the timed
call), time `Pipeline.fit` on its training part from the DataFrame to the
fitted model. `fit_s` is the timed seconds over the fits of the window. The engine's
content-keyed bin caches therefore miss, as they do for a user who fits
once: quantize and H2D are inside every timed fit.
"""

from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from benchmark.harness import checks, stats
from benchmark.reference import bootstrap, featurize, fitcheck, forest


def _draw(ctx, state, iteration: int):
    program = ctx.program
    train, rest = program.split(state["table"], ctx.traffic["fractions"],
                                ctx.seed + iteration)
    frame = program.with_label(ctx.config, train)
    rows = frame.count()
    return frame, rest, rows


def setup(ctx) -> Dict:
    program = ctx.program
    t0 = time.perf_counter()
    state = {"table": program.make_table(
        ctx.data.make(ctx.config["data"], ctx.seed))}
    ctx.log(f"set-up: table made in {time.perf_counter() - t0:.2f}s")
    for i in range(int(ctx.traffic["warm_iterations"])):
        frame, _rest, rows = _draw(ctx, state, 1_000_000 + i)
        t0 = time.perf_counter()
        program.build_pipeline(ctx.config).fit(frame)
        ctx.log(f"set-up: warm fit {i} on {rows} rows took "
                f"{time.perf_counter() - t0:.2f}s")
    return state


def window(ctx, state) -> Dict:
    program = ctx.program
    fits: List[float] = []
    rows: List[int] = []
    last = None
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < ctx.seconds:
        with ctx.annotate("bench.split"):
            frame, rest, n = _draw(ctx, state, len(fits))
            pipeline = program.build_pipeline(ctx.config)
        with ctx.annotate("bench.fit"):
            t = time.perf_counter()
            model = pipeline.fit(frame)
            fits.append(time.perf_counter() - t)
        rows.append(n)
        last = (model, frame, rest)
    ctx.facts["fits"] = len(fits)
    ctx.facts["fit_rows"] = rows
    ctx.log("fits in the window (s): " + " ".join(f"{f:.3f}" for f in fits))
    return {"fits": fits, "rows": rows, "last": last}


def report(ctx, state, result) -> Dict:
    fits = result["fits"]
    ctx.log(f"fit_s is all timed seconds over all fits: mean "
            f"{sum(fits) / len(fits):.4f}, median {stats.median(fits):.4f}")
    return {"attempted": len(fits), "failed": 0,
            "end_to_end": {"fit_s": sum(fits) / len(fits)}}


def check(ctx, state, result) -> List[checks.Check]:
    """The LAST model fitted in the window, against the references."""
    program, limits = ctx.program, ctx.config["correct"]
    model, frame, rest = result["last"]
    tables = program.model_tables(model)
    label, math = ctx.config["label"], ctx.config["fit_math"]
    rng = np.random.default_rng(ctx.seed)
    out: List[checks.Check] = []

    # (a) the engine's predictions for a seeded sample of the holdout equal
    # a float32 NumPy descent of the fitted tables
    rest_label = program.with_label(ctx.config, rest)
    raw = rest_label.toPandas()
    served = program.predictions(model, rest_label)
    pick = rng.choice(len(raw), size=min(int(limits["sample_rows"]), len(raw)),
                      replace=False)
    bins = featurize.bins(raw.iloc[pick], tables, math.get("missing"))
    out.append(checks.at_most(
        "fit.predictions_vs_descent.rel_gap_max",
        forest.worst_relative_gap(served[pick], forest.predict(bins, tables))
        if len(served) == len(raw) else float("inf"),
        limits["score_rtol"], f"{len(pick)} holdout rows"))

    # (d) the model beats predicting the training mean on the holdout
    train_raw = frame.toPandas()
    y_fit = train_raw[label["fit_column"]].to_numpy(dtype=np.float64)
    keep = np.isfinite(y_fit)
    truth = raw[label["fit_column"]].to_numpy(dtype=np.float64)
    ok_rows = np.isfinite(truth)
    ratio = fitcheck.rmse(served[ok_rows], truth[ok_rows]) / fitcheck.rmse(
        np.full(ok_rows.sum(), y_fit[keep].mean()), truth[ok_rows])
    out.append(checks.at_most("fit.holdout_rmse_vs_mean.ratio", ratio,
                              limits["rmse_ratio_max"]))

    # (b), (c): splits and leaves against float64 from the rows themselves
    train_rows = train_raw[keep]
    tbins = featurize.bins(train_rows, tables, math.get("missing"))
    weights, mask = bootstrap.streams(math, *tbins.shape)
    t0 = time.perf_counter()
    got = fitcheck.fit_statistics(
        tbins, y_fit[keep], tables, math, ctx.seed, tree_weights=weights,
        feature_mask=mask, n_trees=limits["fit_sample_trees"],
        nodes_per_tree=limits["fit_sample_nodes"],
        leaves_per_tree=limits["fit_sample_leaves"],
        leaf_only_trees=limits.get("fit_leaf_only_trees", 0))
    ctx.log(f"fit reference over {got['nodes']} nodes and {got['leaves']} "
            f"leaves took {time.perf_counter() - t0:.1f}s")
    out.append(checks.at_most("fit.split_gain_gap.median",
                              got["split_gain_gap_median"],
                              limits["split_gain_gap_max"],
                              f"{got['nodes']} nodes"))
    out.append(checks.at_most("fit.leaf_value_err.median",
                              got["leaf_value_err_median"],
                              limits["leaf_value_err_max"],
                              f"{got['leaves']} leaves"))
    out.append(checks.at_most("fit.cover_gap.max", got["cover_gap_max"],
                              limits["cover_gap_max"]))
    return out
