"""Traffic kind `fit_kmeans`: kind `fit` (its window and its report,
untouched) on a pipeline whose model is a k-means clustering: one timed
`Pipeline.fit` is the column plan's feature-major block, ONE dispatch of
the k-means|| seeding, every Lloyd step and the cost, and the centers read
back. The check is its own: what does not depend on the path the steps
took (the served assignment and the training cost at the RETURNED centers,
what the centers learned), ONE step that does (two fits more after the
window, `maxIter` 0 and 1, against a float64 step from the first's
centers), the seeding's cost against the reference's own k-means||
(`reference/kmeans.py`), and the path the fits took.

The deployment is a table whose distances do not fit the chip: rows x k
float32 is 27 GB at the cell's size, so the program has to walk the rows
by blocks. A program that forms the distances of the whole table at once
would end in the allocator, minutes into set-up: set-up fits the pipeline
once on a few thousand rows and refuses at once (exit code 2, before the
table is made) a program whose counters do not show a fit built by blocks
and seeded by rounds over all rows.
"""

from __future__ import annotations

import contextlib
import gc
import os
import time
from typing import Dict, List

import numpy as np

from benchmark.harness import checks, runner, spec
from benchmark.reference import kmeans

_fit = runner.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "fit.py"),
    "bench_kind_fit")
report = _fit.report
COUNTERS = ("kmeans.fits", "kmeans.iterations", "kmeans.converged",
            "kmeans.init.rounds", "kmeans.init.candidates", "kmeans.blocks",
            "kmeans.rows", "kmeans.empty_clusters", "featurize.plan.fits",
            "featurize.plan.declined", "staging.h2d_bytes")
PROBE_ROWS = 4000


class Program:
    """The harness's adapter to the program, and what this kind alone
    takes from it: the fitted centers, the summary's numbers and what
    `transform` serves."""

    def __init__(self, program):
        self._program = program

    def __getattr__(self, name):
        return getattr(self._program, name)

    def build_pipeline(self, config: Dict, **last_stage):
        """The configuration's pipeline, its LAST stage's parameters
        overridden by `last_stage` (the check's fits of 0 and 1 steps)."""
        stages = [dict(s) for s in config["pipeline"]]
        stages[-1]["params"] = dict(stages[-1]["params"], **last_stage)
        return self._program.build_pipeline(dict(config, pipeline=stages))

    @staticmethod
    def fitted(model) -> Dict[str, object]:
        """The fitted model as plain numbers: the MODEL. Everything else
        is recomputed from the raw rows."""
        tail = model.stages[-1]
        summary = tail.summary
        return {"centers": np.stack(tail.clusterCenters()).astype(np.float64),
                "training_cost": float(summary.trainingCost),
                "sizes": np.asarray(summary.clusterSizes, np.int64)}

    @staticmethod
    def rows(df, cols: List[str]) -> np.ndarray:
        """The frame's columns `cols` as ONE float64 (rows, columns)
        array. Through a frame of its own that dies here: `df.toPandas()`
        would leave its concat cached in `df`, a second copy of the table
        on a host that has no room for a third."""
        table = df.select(*cols).toPandas()
        out = np.empty((len(table), len(cols)), np.float64)
        for j, c in enumerate(cols):
            out[:, j] = table[c].to_numpy()
        return out

    @staticmethod
    def served(model, df) -> np.ndarray:
        """`model.transform(df)`'s cluster, a row."""
        out = model.transform(df).select("prediction").toPandas()
        return np.asarray(out["prediction"], dtype=np.int64)


def _counted(before: Dict, after: Dict) -> Dict[str, float]:
    return {name: after.get(name, 0.0) - before.get(name, 0.0)
            for name in COUNTERS}


def _probe(ctx) -> Dict[str, float]:
    """What one fit of the configuration's pipeline counts, on
    `PROBE_ROWS` rows of the generator."""
    program = ctx.program
    frame = program.make_table(ctx.data.make(
        dict(ctx.config["data"], rows=PROBE_ROWS), ctx.seed))
    before = program.counters()
    try:
        pipeline = program.build_pipeline(ctx.config)
    except TypeError as e:
        raise spec.SpecError(
            f"cell {ctx.cell}: this program's estimator does not take the "
            f"configuration's parameters ({e}): it has no k-means|| "
            f"seeding to run") from e
    pipeline.fit(frame)
    return _counted(before, program.counters())


def setup(ctx) -> Dict:
    ctx.program = Program(ctx.program)
    t0 = time.perf_counter()
    counted = _probe(ctx)
    rounds = float(ctx.config["fit_math"]["initSteps"])
    if counted["kmeans.fits"] != 1.0 or counted["kmeans.blocks"] < 1.0 \
            or counted["kmeans.init.rounds"] != rounds:
        raise spec.SpecError(
            f"cell {ctx.cell}: this program does not build a Lloyd step by "
            f"blocks of rows after a k-means|| seeding (one fit of "
            f"{PROBE_ROWS} rows counted {counted}; it wants kmeans.fits 1, "
            f"kmeans.blocks at least 1 and kmeans.init.rounds "
            f"{rounds:.0f}): at the cell's size it would ask the chip for "
            f"rows x k float32 at once, twice the chip's memory")
    ctx.log(f"set-up: the probe fit of {PROBE_ROWS} rows walked its rows by "
            f"blocks ({time.perf_counter() - t0:.2f}s): {counted}")
    return _fit.setup(ctx)


class _EveryFit:
    """The run's context as kind `fit`'s window sees it, with the steps
    every timed fit ran noted as its `bench.fit` annotation closes (after
    the fit's seconds were taken): `kmeans.rows` is held to rows x
    iterations fit by fit, and the window keeps the last model alone."""

    def __init__(self, ctx):
        self._ctx = ctx
        self.iterations: List[float] = []

    def __getattr__(self, name):
        return getattr(self._ctx, name)

    @contextlib.contextmanager
    def annotate(self, name: str):
        if name != "bench.fit":
            with self._ctx.annotate(name):
                yield
            return
        before = self._ctx.program.counters().get("kmeans.iterations", 0.0)
        with self._ctx.annotate(name):
            yield
        self.iterations.append(self._ctx.program.counters().get(
            "kmeans.iterations", 0.0) - before)


def window(ctx, state) -> Dict:
    before = ctx.program.counters()
    watched = _EveryFit(ctx)
    result = _fit.window(watched, state)
    result["iterations"] = watched.iterations
    result["counted"] = _counted(before, ctx.program.counters())
    centers = ctx.program.fitted(result["last"][0])["centers"]
    ctx.facts["kmeans_k"], ctx.facts["kmeans_d"] = centers.shape
    return result


def _resident() -> str:
    """The process's resident memory, for the check's log lines (the
    check holds a float64 copy of the table on a host of 40 GiB); nothing
    where the kernel does not say."""
    try:
        with open("/proc/self/statm") as f:
            pages = int(f.read().split()[1])
    except (OSError, ValueError, IndexError):
        return ""
    return f", {pages * os.sysconf('SC_PAGE_SIZE') / 2 ** 30:.1f} GiB resident"


def _columns(config: Dict) -> List[str]:
    return list(config["pipeline"][0]["params"]["inputCols"])


def measure(program, config: Dict, model, frame, rest, seed: int,
            log=print, steps=None, ran: float = None) -> Dict[str, float]:
    """Every number `check` compares of one fitted model, from the raw
    rows: also what `tools_kmeans.py` reads for the limits. `steps`: the
    models of the check's two fits more (`maxIter` 0 and 1) where the
    caller made them already; `ran`: the Lloyd steps the model's fit
    ran."""
    limits, math = config["correct"], config["fit_math"]
    cols = _columns(config)
    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    out: Dict[str, float] = {}
    fitted = program.fitted(model)
    centers, k = fitted["centers"], len(fitted["centers"])

    def took(what: str) -> None:
        log(f"reference: {what}, {time.perf_counter() - t0:.1f}s"
            f"{_resident()}")

    gc.collect()
    took("starts")
    X = program.rows(frame, cols)
    took(f"{len(X)} training rows x {len(cols)} columns as float64")

    # (a) what transform serves for sampled holdout rows, against the
    # float64 arg-min at the RETURNED centers; rows whose two nearest
    # centers are as good as tied are left out, and counted
    hold = rest.toPandas()
    pick = np.sort(rng.choice(len(hold), replace=False, size=min(
        int(limits["sample_rows"]), len(hold))))
    sample = hold.iloc[pick].reset_index(drop=True)
    served = program.served(model, program.make_table(sample))
    origin = X.mean(axis=0)
    want, best, second, scale = kmeans.two_nearest(
        sample[cols].to_numpy(dtype=np.float64), centers, origin)
    tied = second - best <= float(limits["near_tie_rel_gap"]) * scale
    out["near_ties"] = float(tied.sum())
    out["sample_rows"] = float(len(pick))
    # (every row tied: nothing to compare, and the next line's to bound)
    out["disagree_share"] = float("inf") if len(served) != len(want) \
        else float((served[~tied] != want[~tied]).mean()) \
        if (~tied).any() else 0.0
    took(f"transform of {len(pick)} holdout rows against the arg-min")

    # (b) the summary's cost against the float64 cost of ALL training rows
    # at the returned centers; (e) what the centers learned
    there = kmeans.lloyd_step(X, centers)
    cost, one_center = there["cost"], float(kmeans.spread(X, origin).sum())
    # (the distances are differences of squares that float32 holds to
    # 1e-7 of THEIR size, so a cost that is a hundred-thousandth of the
    # rows' spread about their mean is held to a grain of that spread)
    out["training_cost_rel_gap"] = abs(fitted["training_cost"] - cost) \
        / (cost + float(limits["training_cost_grain"]) * one_center)
    out["cost_vs_one_center"] = cost / one_center
    took("the cost of the training rows at the returned centers")

    # a loop that ended before maxIter ended by tol: one float64 step
    # more from the returned centers moves next to no row
    out["early_stop_rows_moving"] = 0.0
    if ran is not None and ran < int(math["maxIter"]):
        after = kmeans.nearest(X, there["centers"])[0]
        out["early_stop_rows_moving"] = float(
            (after != there["assignment"]).mean())
        took(f"the loop ended after {ran:.0f} steps: one step more")

    # (c) ONE step: the seeding's centers (maxIter 0) and one step from
    # them (maxIter 1), two fits of the same rows, parameters and seed,
    # against the float64 step from the first's
    if steps is None:
        steps = [program.build_pipeline(config, maxIter=m).fit(frame)
                 for m in (0, 1)]
        took("two fits more, of 0 and of 1 Lloyd steps")
    seeded, stepped = (program.fitted(m) for m in steps)
    step = kmeans.lloyd_step(X, seeded["centers"])
    # a row whose two nearest centers float32 cannot tell apart may sit in
    # either's cluster: the centers are compared where the program and the
    # reference count the same rows, and the counts' gap is taken against
    # the share of (sampled) rows that are so tied at these centers
    same = seeded["sizes"] == step["counts"]
    errors = kmeans.center_errors(step, stepped["centers"], X)[
        same[step["counts"] > 0]]
    out["step_center_err_max"] = float(errors.max()) if len(errors) \
        else float("inf")
    out["step_clusters"] = float(len(errors))
    some = np.sort(rng.choice(len(X), replace=False, size=min(
        int(limits["sample_rows"]), len(X))))
    _, best, second, scale = kmeans.two_nearest(
        X[some], seeded["centers"], origin)
    out["step_tie_share"] = float(np.mean(
        second - best <= float(limits["near_tie_rel_gap"]) * scale))
    out["step_count_gap_max"] = count_gap(seeded["sizes"], step["counts"]) \
        / max(out["step_tie_share"], float(limits["tie_share_floor"]))
    took("a float64 Lloyd step from the seeding's centers")

    # (d) the seeding's cost over that of the reference's OWN k-means||
    own = kmeans.kmeans_parallel(X, k, int(math["initSteps"]), seed)
    out["reference_candidates"] = float(own["candidates"])
    out["seeding_cost_ratio"] = step["cost"] / kmeans.cost(X, own["centers"])
    took(f"its own k-means|| ({own['candidates']} candidates) and its cost")
    return out


def count_gap(sizes: np.ndarray, counts: np.ndarray) -> float:
    """The largest difference, over the clusters, between the rows the
    program gave a cluster and the rows the reference gave it, as a share
    of all rows: rows that changed cluster, at least. (`measure` takes it
    over the share of rows that float32 leaves tied.)"""
    return float(np.abs(np.asarray(sizes) - np.asarray(counts)).max()) \
        / float(np.sum(counts))


def check(ctx, state, result) -> List[checks.Check]:
    """The LAST model fitted in the window, against the reference."""
    limits, math = ctx.config["correct"], ctx.config["fit_math"]
    model, frame, rest = result["last"]
    fits, counted = len(result["fits"]), result["counted"]
    got = measure(ctx.program, ctx.config, model, frame, rest, ctx.seed,
                  ctx.log, result.get("steps"), result["iterations"][-1])
    k, rows = int(math["k"]), float(np.mean(result["rows"]))
    d = len(_columns(ctx.config))
    return [
        checks.at_most("fit.assignment_vs_reference.disagree_share",
                       got["disagree_share"], limits["disagree_share_max"],
                       f"{got['sample_rows'] - got['near_ties']:.0f} "
                       f"holdout rows"),
        checks.at_most("fit.assignment.near_ties", got["near_ties"],
                       limits["near_ties_max"],
                       f"of {got['sample_rows']:.0f} rows, left out"),
        checks.at_most("fit.training_cost.rel_gap",
                       got["training_cost_rel_gap"],
                       limits["training_cost_rtol"]),
        checks.at_most("fit.lloyd_step.center_err.max",
                       got["step_center_err_max"],
                       limits["step_center_err_max"],
                       f"{got['step_clusters']:.0f} clusters of the same "
                       f"count, in their standard errors"),
        _between("fit.lloyd_step.clusters_compared", got["step_clusters"],
                 limits["step_clusters_min"], k),
        checks.at_most("fit.lloyd_step.count_gap.max",
                       got["step_count_gap_max"],
                       limits["step_count_gap_max"],
                       f"over the share of rows float32 leaves tied, "
                       f"{got['step_tie_share']:.2e}"),
        checks.at_most("fit.seeding_cost_vs_reference.ratio",
                       got["seeding_cost_ratio"],
                       limits["seeding_cost_ratio_max"],
                       f"its own {got['reference_candidates']:.0f} "
                       f"candidates"),
        checks.at_most("fit.cost_vs_one_center.ratio",
                       got["cost_vs_one_center"],
                       limits["cost_vs_one_center_max"]),
        checks.at_most("fit.early_stop.rows_moving.share",
                       got["early_stop_rows_moving"],
                       limits["early_stop_rows_moving_max"],
                       f"the last fit ran {result['iterations'][-1]:.0f} "
                       f"steps of at most {math['maxIter']}"),
        # the path the window's fits took
        checks.exactly("kmeans.fits_per_fit", counted["kmeans.fits"] / fits,
                       1.0, f"{fits} fits"),
        _between("kmeans.iterations_per_fit",
                 counted["kmeans.iterations"] / fits, 1,
                 int(math["maxIter"])),
        checks.exactly("kmeans.rows", counted["kmeans.rows"],
                       float(np.dot(result["rows"], result["iterations"])),
                       "rows x iterations, summed over the fits"),
        checks.exactly("kmeans.init.rounds_per_fit",
                       counted["kmeans.init.rounds"] / fits,
                       float(math["initSteps"])),
        # (2k a round are EXPECTED only where no row's probability is cut
        # at 1: a first round whose phi a few far rows hold picks those
        # few, so k, what the centers need, is the least)
        _between("kmeans.init.candidates_per_fit",
                 counted["kmeans.init.candidates"] / fits, k, 6 * k),
        checks.at_most("kmeans.empty_clusters_per_fit",
                       counted["kmeans.empty_clusters"] / fits,
                       limits["empty_clusters_max"]),
        checks.exactly("fit.plan_fits_per_fit",
                       counted["featurize.plan.fits"] / fits, 1.0),
        checks.exactly("fit.plan_declined",
                       counted["featurize.plan.declined"], 0.0),
        checks.at_most("fit.h2d_blocks_per_fit",
                       counted["staging.h2d_bytes"] / fits
                       / (4.0 * d * rows), limits["h2d_blocks_max"],
                       "staged bytes over the float32 block's"),
    ]


def _between(name: str, observed: float, lo: float, hi: float,
             note: str = "") -> checks.Check:
    observed = float(observed)
    return checks.Check(name, bool(lo <= observed <= hi), observed,
                        float(hi), (note + " " if note else "")
                        + f"at least {lo}")
