"""Traffic kind `fit_als`: kind `fit` (its window and its report, untouched)
on an estimator that factorizes a table of explicit ratings: one timed
`ALS.fit` is the raw ids made dense, the two sorted orders, four row arrays
staged, ONE dispatch of every alternation and both factor matrices read
back. The check is its own: the model's predictions and its normal
equations against a float64 ALS-WR from the raw rows
(`reference/als.py`), what it learned, the cold start, and the path the
fits took.

The deployment is a table whose statistics do not fit the chip: ratings x
(rank^2 + rank) float32 is 12.5 GB at the cell's size, so the program has
to build the normal equations by blocks of rows. A program that forms them
for the whole table at once would end in the allocator, minutes into
set-up: set-up fits the estimator once on a few thousand ratings and
refuses at once (exit code 2, before the table is made) a program whose
counters do not show blocks.
"""

from __future__ import annotations

import os
import time
from typing import Dict, List

import numpy as np

from benchmark.harness import checks, runner, spec
from benchmark.reference import als

_fit = runner.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "fit.py"),
    "bench_kind_fit")
report = _fit.report
COUNTERS = ("als.fits", "als.half_steps", "als.blocks", "als.ratings",
            "staging.h2d_bytes")
DROPPED = "als.cold_start.dropped"
#: the probe's table: enough users and movies for every segment length
PROBE = {"rows": 4000, "users": 120, "items": 300}


class Program:
    """The harness's adapter to the program, with `build_pipeline` giving
    the configuration's ONE stage itself (the lab fits `ALS(...)`, no
    `Pipeline` around it), and what this kind alone takes from the
    program: the fitted factors and what `transform` serves."""

    def __init__(self, program):
        self._program = program

    def __getattr__(self, name):
        return getattr(self._program, name)

    def build_pipeline(self, config: Dict):
        stage, = self._program.build_pipeline(config).getStages()
        return stage

    @staticmethod
    def factors(model) -> Dict[str, np.ndarray]:
        """The fitted model as plain arrays: the MODEL. Everything else
        is recomputed from the raw rows."""
        return {"user_ids": np.asarray(model._user_ids),
                "item_ids": np.asarray(model._item_ids),
                "user_factors": np.asarray(model._uf),
                "item_factors": np.asarray(model._if)}

    @staticmethod
    def served(model, df):
        """`model.transform(df)` on the host: the rows it kept, with
        their predictions."""
        return model.transform(df).toPandas()


def _counted(before: Dict, after: Dict) -> Dict[str, float]:
    return {name: after.get(name, 0.0) - before.get(name, 0.0)
            for name in COUNTERS}


def _probe(ctx) -> Dict[str, float]:
    """What one fit of the configuration's estimator counts, on the
    probe's table."""
    program = ctx.program
    frame = program.make_table(
        ctx.data.make(dict(ctx.config["data"], **PROBE), ctx.seed))
    before = program.counters()
    program.build_pipeline(ctx.config).fit(frame)
    return _counted(before, program.counters())


def setup(ctx) -> Dict:
    ctx.program = Program(ctx.program)
    t0 = time.perf_counter()
    counted = _probe(ctx)
    steps = 2.0 * int(ctx.config["fit_math"]["maxIter"])
    if counted["als.fits"] != 1.0 or counted["als.blocks"] < 1.0 \
            or counted["als.half_steps"] != steps:
        raise spec.SpecError(
            f"cell {ctx.cell}: this program does not build the normal "
            f"equations by blocks of rows (one fit of {PROBE['rows']} "
            f"ratings counted {counted}; it wants als.fits 1, als.blocks "
            f"at least 1 and als.half_steps {steps:.0f}): at the cell's "
            f"size it would ask the chip for ratings x (rank^2 + rank) "
            f"float32 at once, several times the chip's memory")
    ctx.log(f"set-up: the probe fit of {PROBE['rows']} ratings built its "
            f"normal equations by blocks "
            f"({time.perf_counter() - t0:.2f}s): {counted}")
    return _fit.setup(ctx)


def window(ctx, state) -> Dict:
    before = ctx.program.counters()
    result = _fit.window(ctx, state)
    result["counted"] = _counted(before, ctx.program.counters())
    model = ctx.program.factors(result["last"][0])
    ctx.facts["als_rank"] = int(model["user_factors"].shape[1])
    ctx.facts["als_entities"] = len(model["user_ids"]) + len(model["item_ids"])
    return result


def _sampled_items(counts: np.ndarray, size: int, rng) -> np.ndarray:
    """`size` item places: the 50 most-rated, as many of the least-rated
    (those with the fewest ratings that the split left: one, at the
    cell's size) as a quarter of the sample holds, the rest drawn."""
    order = np.argsort(counts, kind="stable")
    top = order[-min(50, len(order)):]
    fewest = np.flatnonzero(counts == counts[order[0]])
    fewest = rng.permutation(fewest)[:size // 4]
    rest = np.setdiff1d(np.arange(len(counts)), np.concatenate([top, fewest]))
    drawn = rng.permutation(rest)[:max(size - len(top) - len(fewest), 0)]
    return np.sort(np.concatenate([top, fewest, drawn]))


def measure(program, config: Dict, model, frame, rest, seed: int,
            log=print, reference: Dict = None) -> Dict[str, float]:
    """Every number `check` compares of one fitted model, from the raw
    rows: also what `tools_als.py` reads for the limits. `reference`: a
    float64 fit of the same rows made before (the tool judges several
    programs by one)."""
    limits, math = config["correct"], config["fit_math"]
    user, item, rating = (math[k] for k in ("userCol", "itemCol", "ratingCol"))
    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    out: Dict[str, float] = {}

    train_raw = frame.toPandas()
    if reference is None:
        reference = als.fit(
            train_raw[user].to_numpy(), train_raw[item].to_numpy(),
            train_raw[rating].to_numpy(), int(math["rank"]),
            int(math["maxIter"]), float(math["regParam"]), int(math["seed"]),
            bool(math["nonnegative"]))
        log(f"reference: {math['maxIter']} float64 alternations of rank "
            f"{math['rank']} over {len(train_raw)} ratings, "
            f"{len(reference['user_ids'])} users and "
            f"{len(reference['item_ids'])} items, "
            f"{time.perf_counter() - t0:.1f}s")
    fitted = program.factors(model)
    out["ids_differing"] = float(sum(
        not np.array_equal(fitted[k], reference[k])
        for k in ("user_ids", "item_ids")))
    if out["ids_differing"]:
        return out

    # (a) what transform serves for sampled holdout pairs, against the
    # reference's own factors after the same alternations from the same
    # init; and the cold start, against the reference's own count
    hold_raw = rest.toPandas()
    before = program.counters().get(DROPPED, 0.0)
    served = program.served(model, rest)
    out["dropped_counted"] = program.counters().get(DROPPED, 0.0) - before
    want = als.predict(reference, hold_raw[user].to_numpy(),
                       hold_raw[item].to_numpy())
    out["dropped_reference"] = float(np.isnan(want).sum())
    out["rows_served"] = float(len(served))
    out["rows_wanted"] = float(len(hold_raw) - out["dropped_reference"])
    pick = np.sort(rng.choice(len(served), replace=False, size=min(
        int(limits["sample_rows"]), len(served))))
    sample = served.iloc[pick]
    gap = np.abs(sample["prediction"].to_numpy(dtype=np.float64) - als.predict(
        reference, sample[user].to_numpy(), sample[item].to_numpy()))
    # a model of NaNs serves no row at all: no pair, no agreement
    out["prediction_abs_gap_max"] = float(
        np.nan_to_num(gap, nan=np.inf).max()) if len(pick) else float("inf")
    out["sample_rows"] = float(len(pick))
    log(f"transform served {len(served)} of {len(hold_raw)} holdout rows, "
        f"{time.perf_counter() - t0:.1f}s")

    # (b) the item side's normal equations at the RETURNED factors, in
    # float64 from the raw rows: the sums and the solve of the last
    # half-step, whatever the alternations before it did
    by_item = reference["by_item"]
    items = _sampled_items(by_item.counts, int(limits["residual_items"]), rng)
    residual = als.normal_residual(
        by_item, fitted["item_factors"], fitted["user_factors"],
        float(math["regParam"]), items)
    out["normal_residual_max"] = float(residual.max())
    out["residual_items"] = float(len(items))
    out["residual_ratings_max"] = float(by_item.counts[items].max())
    out["residual_ratings_min"] = float(by_item.counts[items].min())

    # (c) it learned what the table holds
    truth = served[rating].to_numpy(dtype=np.float64)
    mean = float(train_raw[rating].mean())
    out["rmse_ratio"] = als.rmse(served["prediction"], truth) / als.rmse(
        np.full(len(truth), mean), truth) if len(truth) else float("inf")
    log(f"the reference and its comparisons took "
        f"{time.perf_counter() - t0:.1f}s in all")
    return out


def check(ctx, state, result) -> List[checks.Check]:
    """The LAST model fitted in the window, against the reference."""
    limits, math = ctx.config["correct"], ctx.config["fit_math"]
    model, frame, rest = result["last"]
    fits, counted = len(result["fits"]), result["counted"]
    got = measure(ctx.program, ctx.config, model, frame, rest, ctx.seed,
                  ctx.log, result.get("reference"))
    out = [checks.exactly("fit.ids.sides_differing", got["ids_differing"],
                          0.0, "the distinct users and movies of the split")]
    if got["ids_differing"]:
        return out
    out += [
        checks.at_most("fit.prediction_vs_reference.abs_gap_max",
                       got["prediction_abs_gap_max"],
                       limits["prediction_atol"],
                       f"{got['sample_rows']:.0f} holdout pairs"),
        checks.at_most("fit.normal_residual.max", got["normal_residual_max"],
                       limits["normal_residual_max"],
                       f"{got['residual_items']:.0f} items of "
                       f"{got['residual_ratings_min']:.0f} to "
                       f"{got['residual_ratings_max']:.0f} ratings"),
        checks.at_most("fit.holdout_rmse_vs_mean.ratio", got["rmse_ratio"],
                       limits["rmse_ratio_max"]),
        checks.exactly("fit.cold_start.dropped", got["dropped_counted"],
                       got["dropped_reference"],
                       "holdout rows whose user or movie the split lacks"),
        checks.exactly("fit.transform.rows", got["rows_served"],
                       got["rows_wanted"]),
        # the path the window's fits took
        checks.exactly("als.fits_per_fit", counted["als.fits"] / fits, 1.0,
                       f"{fits} fits"),
        checks.exactly("als.half_steps_per_fit",
                       counted["als.half_steps"] / fits,
                       2.0 * int(math["maxIter"])),
        checks.exactly("als.ratings_per_fit", counted["als.ratings"] / fits,
                       float(np.mean(result["rows"]))),
        checks.at_most("fit.h2d_arrays_per_fit",
                       counted["staging.h2d_bytes"] / fits
                       / staged_bytes(result["rows"],
                                      ctx.facts["als_entities"]),
                       limits["h2d_arrays_max"],
                       "staged bytes over the four row arrays' and the two "
                       "bounds arrays'"),
    ]
    return out


def staged_bytes(rows: List[int], entities: int) -> float:
    """Bytes of what ONE fit stages of the window's mean fit: an id and a
    rating a row in each of the two orders (four arrays of 4 bytes a row)
    and a [start, end) pair of int32 an entity. The padding (an eighth at
    most) is the limit's room."""
    return 16.0 * float(np.mean(rows)) + 8.0 * entities
