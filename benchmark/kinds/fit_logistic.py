"""Traffic kind `fit_logistic`: kind `fit` (its set-up, its window and its
report, untouched) on a pipeline whose model is a binomial logistic
regression over a formula's features, with a check of its own: the linear
family's mathematics against a float64 Newton fit from the raw rows
(`reference/logistic.py`), and the path the fit took.

The deployment is the fused fit on the chip: the column plan takes the
formula, the compact block is staged whole and expanded there, and one
program runs every Newton step (`linear_impl.fit_logistic_compact`, counted
by `linear.irls.fits`). A program that fits the pipeline any other way, as
every commit did that declined an `RFormula` stage, would sit in the
generic sequential path for minutes at the cell's size: set-up fits the
pipeline once on a few thousand rows of the generator with the compact
form forced, and refuses at once (exit code 2, before the table is made) a
program whose counters do not show that fit.
"""

from __future__ import annotations

import os
import time
from typing import Dict, List

import numpy as np

from benchmark.harness import checks, runner, spec
from benchmark.reference import logistic

_fit = runner.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "fit.py"),
    "bench_kind_fit")
report = _fit.report
COUNTERS = ("linear.irls.fits", "linear.irls.iterations",
            "featurize.plan.fits", "featurize.plan.declined")
PROBE_ROWS = 4000
COMPACT_KEY = "sml.linear.compactBytes"


def _probe(ctx) -> Dict[str, float]:
    """What one fit of the configuration's pipeline counts, on
    `PROBE_ROWS` rows with the compact form forced."""
    from sml_tpu.conf import GLOBAL_CONF
    program = ctx.program
    rows = dict(ctx.config["data"], rows=PROBE_ROWS)
    frame = program.with_label(ctx.config, program.make_table(
        ctx.data.make(rows, ctx.seed)))
    before = program.counters()
    held = GLOBAL_CONF.get(COMPACT_KEY)
    GLOBAL_CONF.set(COMPACT_KEY, 0)
    try:
        program.build_pipeline(ctx.config).fit(frame)
    finally:
        GLOBAL_CONF.set(COMPACT_KEY, held)
    after = program.counters()
    return {name: after.get(name, 0.0) - before.get(name, 0.0)
            for name in COUNTERS}


def setup(ctx) -> Dict:
    t0 = time.perf_counter()
    counted = _probe(ctx)
    if counted["linear.irls.fits"] != 1.0 \
            or counted["featurize.plan.declined"] != 0.0:
        raise spec.SpecError(
            f"cell {ctx.cell}: this program does not fit the "
            f"configuration's pipeline on the compact device path (one fit "
            f"of {PROBE_ROWS} rows counted {counted}; it wants "
            f"linear.irls.fits 1 and featurize.plan.declined 0): at the "
            f"cell's size it would take the generic sequential path")
    ctx.log(f"set-up: the probe fit of {PROBE_ROWS} rows took the compact "
            f"device path ({time.perf_counter() - t0:.2f}s): {counted}")
    return _fit.setup(ctx)


def window(ctx, state) -> Dict:
    before = ctx.program.counters()
    result = _fit.window(ctx, state)
    after = ctx.program.counters()
    result["counted"] = {name: after.get(name, 0.0) - before.get(name, 0.0)
                         for name in COUNTERS}
    model = result["last"][0]
    ctx.facts["features"] = len(coefficients(model)) - 1
    return result


def coefficients(model) -> np.ndarray:
    """The fitted coefficients on the raw columns, the intercept last:
    the MODEL. Everything else is recomputed from the raw rows."""
    tail = model.stages[-1]
    return np.append(np.asarray(tail.coefficients.toArray(), np.float64),
                     float(tail.intercept))


def indexer_labels(model) -> List[List[str]]:
    """The labels of every string column the fitted formula indexed."""
    for stage in getattr(model.stages[0], "stages", []):
        if hasattr(stage, "labelsArray"):
            return [list(ls) for ls in stage.labelsArray]
    return []


def probabilities(model, df) -> np.ndarray:
    """`model.transform(df)`'s probability of the label 1.0, a row."""
    out = model.transform(df).select("probability").toPandas()
    return np.array([float(v[1]) for v in out["probability"]])


def fitted_point(table, y, w, best) -> Dict[str, float]:
    """The coefficients `w` against the float64 optimum `best` of the same
    table: what `check` compares and `tools_logistic.py` reads."""
    err = np.abs(w - best["coefficients"]) / best["standard_errors"]
    here = logistic.at(table, y, w, best)
    return {"coefficient_err_max": float(err.max()),
            "coefficient_err_slot": int(err.argmax()),
            "loglik_gap_rel": (best["loglik"] - here["loglik"])
            / abs(best["loglik"]),
            "gradient_norm_max": here["gradient_max"]}


def probability_gap(served, sample, plan, w) -> float:
    """Largest gap between served probabilities and the float64 sigmoid of
    the reference's own features of `sample` times `w`."""
    want = logistic.sigmoid(logistic.margins(logistic.Compact(sample, plan),
                                             w))
    if len(served) != len(want):
        return float("inf")
    return float(np.max(np.abs(served - want)))


def _between(name: str, observed: float, lo: float, hi: float,
             note: str = "") -> checks.Check:
    observed = float(observed)
    return checks.Check(name, bool(lo <= observed <= hi), observed,
                        float(lo), note)


def check(ctx, state, result) -> List[checks.Check]:
    """The LAST model fitted in the window, against the reference."""
    program, limits = ctx.program, ctx.config["correct"]
    model, frame, rest = result["last"]
    label = ctx.config["label"]["fit_column"]
    fits, counted = len(result["fits"]), result["counted"]
    w = coefficients(model)
    out: List[checks.Check] = []
    t0 = time.perf_counter()

    # the reference's own featurization and its float64 optimum
    train_raw = frame.toPandas()
    plan = logistic.design(train_raw, label)
    table = logistic.Compact(train_raw, plan)
    y = train_raw[label].to_numpy(dtype=np.float64)[table.keep]
    best = logistic.newton(table, y)
    ctx.log(f"reference: Newton on {len(table)} rows x {table.width} slots "
            f"took {best['iterations']} steps to a gradient of "
            f"{best['gradient_max']:.3g} a row, "
            f"{time.perf_counter() - t0:.1f}s")
    fitted = indexer_labels(model)
    differ = sum(a != ls for a, (_, ls) in zip(fitted, plan["strings"])) \
        + abs(len(fitted) - len(plan["strings"]))
    out.append(checks.exactly(
        "fit.indexer_labels.columns_differing", differ, 0.0,
        f"{len(plan['strings'])} string columns, {table.width} slots"))
    if len(w) != table.width + 1:
        out.append(checks.exactly("fit.coefficients.count", len(w),
                                  table.width + 1))
        return out

    # (a) what the model serves, against its own coefficients in float64
    hold_raw = rest.toPandas()
    rng = np.random.default_rng(ctx.seed)
    pick = np.sort(rng.choice(len(hold_raw), replace=False, size=min(
        int(limits["sample_rows"]), len(hold_raw))))
    sample = hold_raw.iloc[pick].reset_index(drop=True)
    served = getattr(program, "probabilities", probabilities)(
        model, program.make_table(sample))
    out.append(checks.at_most(
        "fit.probability_vs_margin.abs_gap_max",
        probability_gap(served, sample, plan, w),
        limits["probability_atol"], f"{len(sample)} holdout rows"))

    # (b) the fitted point against the float64 optimum
    point = fitted_point(table, y, w, best)
    out.append(checks.at_most(
        "fit.coefficient_err.max", point["coefficient_err_max"],
        limits["coefficient_err_max"],
        f"standard errors; slot {point['coefficient_err_slot']} of {len(w)}"))
    out.append(checks.at_most(
        "fit.loglik_gap.rel", point["loglik_gap_rel"],
        limits["loglik_gap_rel_max"], f"optimum {best['loglik']:.6f}"))
    out.append(checks.at_most(
        "fit.gradient_norm.max", point["gradient_norm_max"],
        limits["gradient_norm_max"], "a row"))

    # (c) it learned what the table holds
    held = logistic.Compact(hold_raw, plan)
    truth = hold_raw[label].to_numpy(dtype=np.float64)[held.keep]
    auc = logistic.auc(logistic.margins(held, w), truth)
    out.append(_between("fit.holdout_auc", auc, limits["holdout_auc_min"],
                        1.0, f"{len(held)} holdout rows; a constant: 0.5"))

    # (d) the path the window's fits took
    out.append(checks.exactly(
        "fit.irls_fits_per_fit", counted["linear.irls.fits"] / fits, 1.0,
        f"fused IRLS programs a fit; {fits} fits"))
    out.append(checks.exactly(
        "fit.plan_fits_per_fit", counted["featurize.plan.fits"] / fits, 1.0))
    out.append(checks.exactly("fit.plan_declined",
                              counted["featurize.plan.declined"], 0.0))
    max_iter = int(ctx.config["fit_math"]["maxIter"])
    out.append(_between(
        "fit.iterations_per_fit", counted["linear.irls.iterations"] / fits,
        limits["iterations_min"], max_iter - 1,
        f"steps that moved w, a fit; the scan runs {max_iter}"))
    ctx.log(f"the reference and its checks took "
            f"{time.perf_counter() - t0:.1f}s in all")
    return out
