"""Traffic kind `fit_cv`: kind `fit` (its set-up, its window and its report,
untouched) on a pipeline that ENDS IN A VALIDATOR: a formula's features,
then `CrossValidator(LogisticRegression)` over a grid of elastic-net
penalties, so one timed `Pipeline.fit` is grid x folds penalized fits, as
many validation AUROCs and the refit of the best point. The check is its
own: the best model against a float64 proximal-Newton optimum of MLlib's
objective from the raw rows (`reference/logistic_enet.py`, its steps
started at the null model and held to their own residual), the averaged
metrics against the reference's own fits and AUROCs on the folds the
public `randomSplit` gives, one fit of the grid's sparse point held to the
same optimality conditions (on this table the tuned point is a ridge, so
without it no coefficient a lasso part zeroed would be looked at), and the
path the fits took.

The deployment is the tuning loop on ONE staged block: the column plan
runs once and hands the validator the compact block, folds are a fold id
a row over it, every fit is the fused penalized program, and a fold's
dispatch returns every row's margin a grid point, which the host's pool
ranks (the exact midrank area) while the next fold is on the chip. A program that tunes any other way (fold frames, a
host loop a fit, a `transform` an evaluation) would sit in the generic
path for minutes a fit at the cell's size: set-up fits the pipeline once
on a few thousand rows and refuses at once (exit code 2, before the table
is made) a program whose counters do not show that path.

The configuration's `pipeline` names the stages as every kind's does; its
`validator` completes the last one with what JSON cannot hold in a
stage's `params`: the estimator, the evaluator and the grid.
"""

from __future__ import annotations

import importlib
import itertools
import os
import time
from typing import Dict, List, Tuple

import numpy as np

from benchmark.harness import checks, runner, spec
from benchmark.reference import logistic
from benchmark.reference import logistic_enet as enet

_HERE = os.path.dirname(os.path.abspath(__file__))
_fit = runner.load_module(os.path.join(_HERE, "fit.py"), "bench_kind_fit")
_logistic = runner.load_module(os.path.join(_HERE, "fit_logistic.py"),
                               "bench_kind_fit_logistic")
report = _fit.report
COUNTERS = ("cv.fits", "cv.evals", "cv.fold_frames", "linear.host_loops",
            "linear.irls.fits", "linear.irls.iterations",
            "linear.irls.unconverged", "linear.irls.floor_ended",
            "featurize.plan.fits",
            "featurize.plan.declined", "staging.h2d_bytes")
PROBE_ROWS = 4000
ROW = "_bench_row"


def _made(named: Dict):
    cls = getattr(importlib.import_module(named["module"]), named["class"])
    return cls(**named["params"])


class Program:
    """The harness's adapter to the program with `build_pipeline`
    completed for a pipeline whose last stage is a validator (the
    configuration's `validator`: estimator, evaluator, grid in
    `ParamGridBuilder`'s order), and what this kind alone takes from the
    program: the validator's parts of a fitted model and the folds of the
    public `randomSplit`."""

    def __init__(self, program):
        self._program = program

    def __getattr__(self, name):
        return getattr(self._program, name)

    def build_pipeline(self, config: Dict):
        pipeline = self._program.build_pipeline(config)
        named = config["validator"]
        estimator = _made(named["estimator"])
        names = [name for name, _ in named["grid"]]
        grid = [{estimator.getParam(n): v for n, v in zip(names, values)}
                for values in itertools.product(
                    *[values for _, values in named["grid"]])]
        pipeline.getStages()[-1]._set(
            estimator=estimator, evaluator=_made(named["evaluator"]),
            estimatorParamMaps=grid)
        return pipeline

    def build_single(self, config: Dict, point: Dict[str, float]):
        """The configuration's pipeline with the validator's ESTIMATOR at
        one point of the grid in the validator's place: the formula, then
        one penalized fit."""
        pipeline = self._program.build_pipeline(config)
        estimator = _made(config["validator"]["estimator"])
        estimator._set(**point)
        return type(pipeline)(stages=pipeline.getStages()[:-1] + [estimator])

    @staticmethod
    def validated(model) -> Dict[str, object]:
        """The fitted validator as plain values: the metric a grid point,
        the grid's points and the best model's own, by parameter name."""
        tail = model.stages[-1]
        best = tail.bestModel
        names = sorted({p.name for m in tail.getEstimatorParamMaps()
                        for p in m})
        return {"avg_metrics": [float(m) for m in tail.avgMetrics],
                "grid": [{p.name: float(v) for p, v in m.items()}
                         for m in tail.getEstimatorParamMaps()],
                "best": {n: float(best.getOrDefault(n)) for n in names},
                "folds": int(tail.getOrDefault("numFolds")),
                "seed": int(tail.getOrDefault("seed"))}

    @staticmethod
    def fold_ids(frame, folds: int, seed: int) -> np.ndarray:
        """The fold `frame.randomSplit([1 / folds] * folds, seed)` puts
        each row of `frame.toPandas()` in, through the public API alone:
        the frame with its rows numbered, split, and each part's numbers
        read back. (The number is the LAST column of the split's own sort,
        so it orders only rows that are equal in every column, and which
        of two equal rows a fold holds moves no statistic.)"""
        from sml_tpu.frame import functions as F
        numbered = frame.withColumn(ROW, F.monotonically_increasing_id())
        every = numbered.select(ROW).toPandas()[ROW].to_numpy()
        out = np.full(len(every), -1, dtype=np.int64)
        for f, part in enumerate(numbered.randomSplit(
                [1.0 / folds] * folds, seed=seed)):
            ids = part.select(ROW).toPandas()[ROW].to_numpy()
            out[np.searchsorted(every, ids)] = f
        return out


def _counted(before: Dict, after: Dict) -> Dict[str, float]:
    return {name: after.get(name, 0.0) - before.get(name, 0.0)
            for name in COUNTERS}


def _fits_a_fit(config: Dict) -> Tuple[int, int]:
    """(estimator fits, evaluations) one `Pipeline.fit` makes."""
    points = int(np.prod([len(v) for _, v in config["validator"]["grid"]]))
    folds = int(config["pipeline"][-1]["params"]["numFolds"])
    return points * folds + 1, points * folds


def _probe(ctx) -> Dict[str, float]:
    """What one fit of the configuration's pipeline counts, on
    `PROBE_ROWS` rows."""
    program = ctx.program
    rows = dict(ctx.config["data"], rows=PROBE_ROWS)
    frame = program.with_label(ctx.config, program.make_table(
        ctx.data.make(rows, ctx.seed)))
    before = program.counters()
    program.build_pipeline(ctx.config).fit(frame)
    return _counted(before, program.counters())


def setup(ctx) -> Dict:
    ctx.program = Program(ctx.program)
    t0 = time.perf_counter()
    counted = _probe(ctx)
    fits, _ = _fits_a_fit(ctx.config)
    wanted = {"featurize.plan.fits": 1.0, "featurize.plan.declined": 0.0,
              "cv.fits": float(fits), "linear.irls.fits": float(fits),
              "cv.fold_frames": 0.0, "linear.host_loops": 0.0}
    if any(counted[name] != value for name, value in wanted.items()):
        raise spec.SpecError(
            f"cell {ctx.cell}: this program does not tune the "
            f"configuration's pipeline on one staged block (one fit of "
            f"{PROBE_ROWS} rows counted {counted}; it wants {wanted}): at "
            f"the cell's size it would take the generic path, fold frames "
            f"and a host loop a fit")
    ctx.log(f"set-up: the probe fit of {PROBE_ROWS} rows tuned on one "
            f"staged block ({time.perf_counter() - t0:.2f}s): {counted}")
    return _fit.setup(ctx)


def window(ctx, state) -> Dict:
    before = ctx.program.counters()
    result = _fit.window(ctx, state)
    result["counted"] = _counted(before, ctx.program.counters())
    model = result["last"][0]
    ctx.facts["features"] = len(coefficients(model)) - 1
    ctx.facts["folds"] = int(ctx.config["fit_math"]["folds"])
    return result


def coefficients(model) -> np.ndarray:
    """The best model's coefficients on the raw columns, the intercept
    last: the MODEL. Everything else is recomputed from the raw rows."""
    return _with_intercept(model.stages[-1].bestModel)


def _with_intercept(fitted) -> np.ndarray:
    return np.append(np.asarray(fitted.coefficients.toArray(), np.float64),
                     float(fitted.intercept))


def standard_errors(data: enet.Standardized, c: np.ndarray) -> np.ndarray:
    """sqrt(diag) of the inverse Hessian of the log-likelihood at `c`, on
    the raw columns (intercept last): the unit `fit.coefficient_err.max`
    has in the unpenalized cell, here a scale and no error bar."""
    _, H, _ = data.derivatives(c, None, hessian=True)
    d = data.width
    J = np.zeros((d + 1, d + 1))
    J[:d, :d] = np.diag(1.0 / data.std)
    J[d, :d] = -data.mean / data.std
    J[d, d] = 1.0
    return np.sqrt(np.diag(J @ np.linalg.inv(H * data.rows) @ J.T))


def measure(program, config: Dict, model, frame, rest, seed: int,
            log=print) -> Dict[str, float]:
    """Every number `check` compares of one fitted pipeline model, from
    the raw rows: also what `tools_cv.py` reads for the limits."""
    limits, label = config["correct"], config["label"]["fit_column"]
    t0 = time.perf_counter()
    tuned = program.validated(model)
    w = coefficients(model)
    out: Dict[str, float] = {}

    train_raw = frame.toPandas()
    plan = logistic.design(train_raw, label)
    table = logistic.Compact(train_raw, plan)
    y = train_raw[label].to_numpy(dtype=np.float64)[table.keep]
    data = enet.Standardized(table, y)
    fitted = _logistic.indexer_labels(model)
    out["labels_differing"] = sum(
        a != ls for a, (_, ls) in zip(fitted, plan["strings"])) \
        + abs(len(fitted) - len(plan["strings"]))
    out["coefficients"] = len(w)
    out["slots"] = table.width
    if len(w) != table.width + 1:
        return out
    log(f"reference: {len(table)} rows x {table.width} slots standardized, "
        f"{time.perf_counter() - t0:.1f}s")

    # (a) the best point: the arg-max of the averaged metrics, the best
    # model's own parameters, its optimality and the reference's optimum
    avg, grid = tuned["avg_metrics"], tuned["grid"]
    chosen = int(np.argmax(avg))
    out["best_index_agrees"] = float(tuned["best"] == grid[chosen])
    lam, alpha = grid[chosen]["regParam"], grid[chosen]["elasticNetParam"]
    res = enet.residual_at(data, w, lam, alpha)
    out["kkt_residual_max"] = float(res.max())
    # the reference's steps start at the null model, nowhere near the
    # program's answer: a reference that stopped short could not agree
    # with it, and every reference fit's own residual is a line of `check`
    best = enet.fit(data, lam, alpha)
    log(f"reference: point {chosen} (regParam {lam}, elasticNetParam "
        f"{alpha}) from the null model to a residual of "
        f"{best['residual_max']:.3g} in {best['passes']} passes, "
        f"{time.perf_counter() - t0:.1f}s")
    residuals = [best["residual_max"]]
    err = np.abs(w - best["coefficients"]) / standard_errors(data, best["c"])
    out["coefficient_err_max"] = float(err.max())
    # support: a coordinate the reference holds within `support_margin`
    # of the threshold (|u_j| under it, or a zero whose gradient is within
    # it of lam alpha) is left out and counted
    margin = float(limits["support_margin"])
    u = best["spread"][:-1] * best["c"][:-1]
    slack = np.where(u != 0, np.abs(u), np.inf)
    zero = u == 0
    if zero.any():
        g, _, _ = data.derivatives(best["c"], None, hessian=False)
        live = best["spread"][:-1] > 0
        gu = np.where(live, np.abs(g[:-1]) / np.where(
            live, best["spread"][:-1], 1.0), 0.0)
        slack = np.where(zero & live, lam * alpha - gu, slack)
    near = (slack < margin) & (lam * alpha > 0)    # a ridge has no threshold
    out["support_near_threshold"] = float(near.sum())
    out["support_differing"] = float(
        (((w[:-1] == 0) != zero) & ~near).sum())
    out["support_zeros"] = float(zero.sum())

    # (b) the averaged metrics of the chosen point and of one more, drawn
    # from the seed, against the reference's own fits and AUROCs on the
    # folds the public randomSplit gives
    fold = program.fold_ids(frame, tuned["folds"], tuned["seed"])
    out["rows_in_no_fold"] = float((fold < 0).sum())
    fold = fold[table.keep]
    log(f"reference: the folds of the public randomSplit, "
        f"{time.perf_counter() - t0:.1f}s")
    others = [g for g in range(len(grid)) if g != chosen]
    other = others[int(np.random.default_rng(seed).integers(len(others)))]
    gaps = {}
    for g in (chosen, other):
        # a ridge point's steps start at the REFERENCE's optimum of the
        # chosen point, a lasso point's at the null model
        aucs = enet.fold_aucs(
            data, fold, grid[g]["regParam"], grid[g]["elasticNetParam"],
            start=None if grid[g]["elasticNetParam"] > 0 else best["c"],
            residuals=residuals)
        gaps[g] = abs(float(np.mean(aucs)) - avg[g])
        log(f"reference: point {g} {grid[g]} reads {np.mean(aucs):.9f} over "
            f"the folds, the program {avg[g]:.9f}, "
            f"{time.perf_counter() - t0:.1f}s")
    out["avg_metric_abs_gap_max"] = max(gaps.values())
    out["avg_metric_points"] = float(chosen * 100 + other)
    out["reference_residual_max"] = max(residuals)
    out["reference_fits"] = float(len(residuals))

    # (b') the grid's sparse point, fitted once more on the same rows by
    # the estimator alone (the refit's program): its optimality residual,
    # which holds every coordinate the lasso part zeroed to |g_j| <= lam
    # alpha and every one it kept to a zero derivative
    at = dict(zip(("regParam", "elasticNetParam"), limits["lasso_point"]))
    sparse = _with_intercept(
        program.build_single(config, at).fit(frame).stages[-1])
    out["lasso_kkt_residual_max"] = float(enet.residual_at(
        data, sparse, at["regParam"], at["elasticNetParam"]).max())
    out["lasso_nonzero"] = float((sparse[:-1] != 0).sum())
    log(f"the sparse point {at}: {out['lasso_nonzero']:.0f} of "
        f"{table.width} coefficients kept, {time.perf_counter() - t0:.1f}s")

    # (c) what the pipeline model serves, and what it learned
    hold_raw = rest.toPandas()
    pick = np.sort(np.random.default_rng(seed).choice(
        len(hold_raw), replace=False,
        size=min(int(limits["sample_rows"]), len(hold_raw))))
    sample = hold_raw.iloc[pick].reset_index(drop=True)
    served = getattr(program, "probabilities", _logistic.probabilities)(
        model, program.make_table(sample))
    out["probability_abs_gap_max"] = _logistic.probability_gap(
        served, sample, plan, w)
    held = logistic.Compact(hold_raw, plan)
    truth = hold_raw[label].to_numpy(dtype=np.float64)[held.keep]
    out["holdout_auc"] = logistic.auc(logistic.margins(held, w), truth)
    log(f"the reference and its comparisons took "
        f"{time.perf_counter() - t0:.1f}s in all")
    return out


def check(ctx, state, result) -> List[checks.Check]:
    """The LAST model fitted in the window, against the reference."""
    limits = ctx.config["correct"]
    model, frame, rest = result["last"]
    fits, counted = len(result["fits"]), result["counted"]
    got = measure(ctx.program, ctx.config, model, frame, rest, ctx.seed,
                  ctx.log)
    out = [checks.exactly("fit.indexer_labels.columns_differing",
                          got["labels_differing"], 0.0)]
    if "kkt_residual_max" not in got:
        out.append(checks.exactly("fit.coefficients.count",
                                  got["coefficients"], got["slots"] + 1))
        return out
    between = _logistic._between
    out += [
        checks.exactly("cv.best_index_agrees", got["best_index_agrees"], 1.0,
                       "bestModel's parameters are the arg-max's"),
        checks.at_most("cv.best.kkt_residual.max", got["kkt_residual_max"],
                       limits["kkt_residual_max"],
                       "float64, standardized coordinates"),
        checks.at_most("cv.best.coefficient_err.max",
                       got["coefficient_err_max"],
                       limits["coefficient_err_max"],
                       "against the reference's optimum from the null "
                       "model"),
        checks.at_most("reference.residual.max",
                       got["reference_residual_max"],
                       limits["reference_residual_max"],
                       f"the reference's own {got['reference_fits']:.0f} "
                       f"fits"),
        checks.exactly("cv.best.support_differing", got["support_differing"],
                       0.0, f"{got['support_zeros']:.0f} zeros"),
        checks.at_most("cv.best.support_near_threshold",
                       got["support_near_threshold"],
                       limits["support_near_threshold_max"],
                       f"within {limits['support_margin']} of it: left out"),
        checks.at_most("cv.avg_metric.abs_gap_max",
                       got["avg_metric_abs_gap_max"],
                       limits["avg_metric_atol"],
                       f"points {got['avg_metric_points']:.0f} (chosen x 100 "
                       f"+ drawn); {got['rows_in_no_fold']:.0f} rows in no "
                       f"fold"),
        checks.at_most("cv.lasso_point.kkt_residual.max",
                       got["lasso_kkt_residual_max"],
                       limits["kkt_residual_max"],
                       f"one fit at {limits['lasso_point']}"),
        between("cv.lasso_point.nonzero", got["lasso_nonzero"],
                limits["lasso_nonzero_min"], got["slots"] - 1.0,
                "neither the null model nor a dense one"),
        checks.at_most("fit.probability_vs_margin.abs_gap_max",
                       got["probability_abs_gap_max"],
                       limits["probability_atol"]),
        between("fit.holdout_auc", got["holdout_auc"],
                limits["holdout_auc_min"], 1.0, "a constant: 0.5"),
    ]

    # the path the window's fits took
    want_fits, want_evals = _fits_a_fit(ctx.config)
    irls = max(counted["linear.irls.fits"], 1.0)
    out += [
        checks.exactly("cv.fits_per_fit", counted["cv.fits"] / fits,
                       float(want_fits), f"{fits} fits"),
        checks.exactly("cv.evals_per_fit", counted["cv.evals"] / fits,
                       float(want_evals)),
        checks.exactly("cv.fold_frames_per_fit",
                       counted["cv.fold_frames"] / fits, 0.0),
        checks.exactly("linear.host_loops_per_fit",
                       counted["linear.host_loops"] / fits, 0.0),
        checks.exactly("linear.irls_fits_per_fit",
                       counted["linear.irls.fits"] / fits, float(want_fits),
                       "fused programs' fits"),
        checks.exactly("linear.irls.unconverged",
                       counted["linear.irls.unconverged"], 0.0,
                       "fits that ran maxIter steps"),
        checks.at_most("linear.irls.floor_ended_share",
                       counted["linear.irls.floor_ended"] / irls,
                       limits["floor_ended_share_max"],
                       f"{counted['linear.irls.floor_ended']:.0f} of "
                       f"{irls:.0f} fits ended at float32's floor, not by "
                       f"tol"),
        between("linear.irls.iterations_per_irls_fit",
                counted["linear.irls.iterations"] / irls, 1.0,
                int(ctx.config["fit_math"]["maxIter"]) - 1),
        checks.exactly("fit.plan_fits_per_fit",
                       counted["featurize.plan.fits"] / fits, 1.0),
        checks.exactly("fit.plan_declined",
                       counted["featurize.plan.declined"], 0.0),
        checks.at_most("fit.h2d_blocks_per_fit",
                       counted["staging.h2d_bytes"] / fits
                       / one_block_bytes(result["rows"], ctx.config),
                       limits["h2d_blocks_max"],
                       "staged bytes over one padded compact block's"),
    ]
    return out


def one_block_bytes(rows: List[int], config: Dict) -> float:
    """Bytes of ONE staged compact block of the window's mean fit: a
    float32 a numeric column, an int32 a string column, the label and the
    fold id (a byte) a row. The padding (an eighth at most) is the
    limit's room."""
    columns = int(config["fit_math"]["raw_columns"])
    return float(np.mean(rows)) * (4.0 * (columns + 1) + 1.0)
