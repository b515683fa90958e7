"""Traffic kind `fit_boost_logistic`: kind `fit` (its set-up, its window and
its report, untouched) on a pipeline whose model is a boosted CLASSIFIER of
histogram trees: one timed `Pipeline.fit` is the column plan's block, the
quantizer's 255 cuts a column, ONE dispatch of every boosting round of the
logistic loss, and the trees read back. The check is its own, because
`kinds/fit.py`'s knows one loss (`fitcheck` replays `margin - y`): what the
timed path produced at the timed size is held to `reference/
boost_logistic.py`'s float64 replay of the log loss's gradients, the served
probabilities to the logistic function of a float32 descent of the fitted
tables, the holdout to what a classifier is for, and the path the fit took
to what the configuration states (256 bins in use, an operand of F x bins x
padded rows bytes, built by row blocks).

The deployment is a table whose one-hot does not fit the chip the way the
program built it until this cell: at 256 bins x 28 columns the table-wide
int32 broadcast of `jax.nn.one_hot` is 24 GB beside a 6 GB operand. Such a
program would end in the allocator, a minute into set-up: set-up fits the
pipeline once on a few thousand rows and refuses at once (exit code 2,
before the table is made) a program whose counters do not show an operand
built by blocks of rows.
"""

from __future__ import annotations

import os
import time
from typing import Dict, List

import numpy as np

from benchmark.harness import checks, runner, spec
from benchmark.reference import boost_logistic, featurize, forest

_fit = runner.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "fit.py"),
    "bench_kind_fit")
report = _fit.report
COUNTERS = ("tree.fit_dispatch", "tree.operand.blocks", "tree.operand.bytes",
            "fit.shards", "fit.shard_rows_max", "featurize.plan.fits",
            "featurize.plan.declined", "staging.h2d_bytes")
PROBE_ROWS = 4000


class Program:
    """The harness's adapter to the program, and what this kind alone
    takes from it: the tables of a pipeline that is an assembler and a
    tree classifier (no imputer, no indexer: `program.model_tables` looks
    for both), and the probabilities `transform` serves."""

    def __init__(self, program):
        self._program = program

    def __getattr__(self, name):
        return getattr(self._program, name)

    @staticmethod
    def model_tables(model) -> Dict[str, object]:
        """The fitted pipeline as plain arrays: bin edges and node tables
        are the MODEL; everything computed from them is not the
        program's."""
        trees = next(s for s in model.stages if hasattr(s, "_spec"))._spec
        assembler = next(s for s in model.stages
                         if s.hasParam("inputCols") and s.hasParam("outputCol"))
        sf, sb, lv, w = trees.stacked()
        columns = list(assembler.getOrDefault("inputCols"))
        return {
            "columns": [("numeric", c) for c in columns],
            "surrogates": {c: float("nan") for c in columns},
            "labels": {}, "cat_rank": {},
            "edges": np.asarray(trees.binning.edges, dtype=np.float32),
            "split_feature": np.asarray(sf, dtype=np.int64),
            "split_bin": np.asarray(sb, dtype=np.int64),
            "leaf_value": np.asarray(lv, dtype=np.float32),
            "tree_weight": np.asarray(w, dtype=np.float32),
            "cover": np.stack([np.asarray(t.cover) for t in trees.trees]),
            "base": float(trees.base),
            "depth": int(trees.depth),
        }

    @staticmethod
    def probabilities(model, df) -> np.ndarray:
        """`model.transform(df)`'s probability of the label 1.0, a row."""
        out = model.transform(df).select("probability").toPandas()
        return np.array([float(v[1]) for v in out["probability"]])


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
    program.build_pipeline(ctx.config).fit(frame)
    return _counted(before, program.counters())


def setup(ctx) -> Dict:
    ctx.program = Program(ctx.program)
    t0 = time.perf_counter()
    counted = _probe(ctx)
    if counted["tree.fit_dispatch"] != 1.0 \
            or counted["tree.operand.blocks"] < 1.0:
        math = ctx.config["fit_math"]
        raise spec.SpecError(
            f"cell {ctx.cell}: this program does not build its histogram "
            f"operand by blocks of rows (one fit of {PROBE_ROWS} rows "
            f"counted {counted}; it wants tree.fit_dispatch 1 and "
            f"tree.operand.blocks at least 1): at the cell's size it would "
            f"ask the chip for rows x {math['feature_k']} x {math['n_bins']} "
            f"int32 at once, 24 GB beside the 6 GB operand")
    ctx.log(f"set-up: the probe fit of {PROBE_ROWS} rows built its operand "
            f"by blocks ({time.perf_counter() - t0:.2f}s): {counted}")
    return _fit.setup(ctx)


def window(ctx, state) -> Dict:
    before = ctx.program.counters()
    result = _fit.window(ctx, state)
    result["counted"] = _counted(before, ctx.program.counters())
    math = ctx.config["fit_math"]
    trees = ctx.program.model_tables(result["last"][0])
    ctx.facts["tree_rounds"], ctx.facts["tree_depth"] = \
        trees["split_feature"].shape[0], trees["depth"]
    ctx.facts["tree_columns"] = trees["edges"].shape[0]
    ctx.facts["tree_bins"] = int(math["n_bins"])
    return result


def measure(program, config: Dict, model, frame, rest, seed: int,
            log=print, precision=None,
            gradients: str = "logistic") -> Dict[str, float]:
    """Every number `check` compares of one fitted model, from the raw
    rows: also what `tools_higgs.py` reads for the limits. `precision` and
    `gradients` are the two controls' (`reference/boost_logistic.py`)."""
    limits, math = config["correct"], config["fit_math"]
    label = config["label"]["fit_column"]
    tables = program.model_tables(model)
    rng = np.random.default_rng(seed)
    out: Dict[str, float] = {}

    # (a) served probabilities of a seeded holdout sample against the
    # logistic function of a float32 descent of the fitted tables
    raw = rest.toPandas()
    served = program.probabilities(model, rest)
    pick = rng.choice(len(raw), size=min(int(limits["sample_rows"]), len(raw)),
                      replace=False)
    bins = featurize.bins(raw.iloc[pick], tables)
    want = boost_logistic.probabilities(bins, tables) if precision is None \
        else 1.0 / (1.0 + np.exp(-forest.predict(bins, tables, precision)))
    out["probability_rel_gap_max"] = forest.worst_relative_gap(
        served[pick], want) if len(served) == len(raw) else float("inf")
    out["sample_rows"] = len(pick)

    # (d) the holdout: under the base rate's log loss, over a floor of AUROC
    truth = raw[label].to_numpy(dtype=np.float64)
    train_raw = frame.toPandas()
    y = train_raw[label].to_numpy(dtype=np.float64)
    base_rate = np.full(len(truth), y.mean())
    out["log_loss_ratio"] = boost_logistic.log_loss(served, truth) \
        / boost_logistic.log_loss(base_rate, truth)
    out["auroc"] = boost_logistic.auroc(served, truth)
    out["positive_share"] = float(y.mean())

    # (b), (c): splits, leaves and hessian mass against the float64 replay
    tbins = featurize.bins(train_raw, tables)
    out["bins_used_max"] = float(max(
        len(np.unique(tbins[:, f])) for f in range(tbins.shape[1])))
    t0 = time.perf_counter()
    got = boost_logistic.fit_statistics(
        tbins, y, tables, math, seed, n_trees=limits["fit_sample_trees"],
        nodes_per_tree=limits["fit_sample_nodes"],
        leaves_per_tree=limits["fit_sample_leaves"],
        leaf_only_trees=limits.get("fit_leaf_only_trees", 0),
        precision=precision, gradients=gradients)
    log(f"fit reference ({gradients} gradients, "
        f"{precision or 'float64'}) over {got['nodes']} nodes and "
        f"{got['leaves']} leaves of {len(y)} rows took "
        f"{time.perf_counter() - t0:.1f}s")
    for name in ("split_gain_gap_median", "leaf_value_err_median",
                 "hessian_mass_gap_median", "nodes", "leaves"):
        out[name] = got[name]
    # the replayed margin's own log loss on the training rows: the fit
    # descends (what a wrong sign or a wrong sigmoid would not)
    out["train_log_loss_ratio"] = boost_logistic.log_loss(
        1.0 / (1.0 + np.exp(-got["margin"])), y) / boost_logistic.log_loss(
        np.full(len(y), y.mean()), y)
    return out


def verdicts(config: Dict, got: Dict[str, float]) -> List[checks.Check]:
    """`measure`'s numbers against the configuration's limits."""
    limits = config["correct"]
    return [
        checks.at_most("fit.probabilities_vs_descent.rel_gap_max",
                       got["probability_rel_gap_max"], limits["score_rtol"],
                       f"{got['sample_rows']} holdout rows"),
        checks.at_most("fit.split_gain_gap.median",
                       got["split_gain_gap_median"],
                       limits["split_gain_gap_max"], f"{got['nodes']} nodes"),
        checks.at_most("fit.leaf_value_err.median",
                       got["leaf_value_err_median"],
                       limits["leaf_value_err_max"],
                       f"{got['leaves']} leaves"),
        checks.at_most("fit.hessian_mass_gap.median",
                       got["hessian_mass_gap_median"],
                       limits["hessian_mass_gap_max"],
                       f"{got['nodes']} nodes"),
        checks.at_most("fit.holdout_log_loss_vs_base_rate.ratio",
                       got["log_loss_ratio"], limits["log_loss_ratio_max"]),
        checks.at_most("fit.holdout_auroc.shortfall",
                       limits["auroc_min"] - got["auroc"], 0.0,
                       f"AUROC {got['auroc']:.4f}, floor "
                       f"{limits['auroc_min']}"),
        checks.at_most("fit.bins_used.max.shortfall",
                       limits["bins_used_min"] - got["bins_used_max"], 0.0,
                       f"{got['bins_used_max']:.0f} of "
                       f"{config['fit_math']['n_bins']} bins hold rows on "
                       f"the fullest column, floor {limits['bins_used_min']}"),
    ]


def check(ctx, state, result) -> List[checks.Check]:
    """The LAST model fitted in the window against the references, and the
    path the window's fits took."""
    model, frame, rest = result["last"]
    t0 = time.perf_counter()
    got = measure(ctx.program, ctx.config, model, frame, rest, ctx.seed,
                  log=ctx.log)
    ctx.log(f"the references took {time.perf_counter() - t0:.1f}s in all: "
            + ", ".join(f"{k} {v:.6g}" for k, v in got.items()))
    out = verdicts(ctx.config, got)
    fits = len(result["fits"])
    per_fit = {name: total / fits for name, total in result["counted"].items()}
    math = ctx.config["fit_math"]
    width = int(math["feature_k"]) * int(math["n_bins"])
    rows = sum(result["rows"]) / fits
    # the rows the bin matrix was STAGED at, padding included, counted
    # where it is staged (`fit.shard_rows_max` on each of `fit.shards`
    # devices), and the bytes an element is stored at: one where the dot
    # multiplies in bfloat16 (the chip: the configuration's `precision`),
    # the float32 one-hot itself where it does not (the CPU of the tests)
    staged = per_fit["fit.shard_rows_max"] * per_fit["fit.shards"]
    import jax
    itemsize = 1 if jax.devices()[0].platform == "tpu" else 4
    out += [
        checks.exactly("fit.dispatches_per_fit", per_fit["tree.fit_dispatch"],
                       1.0, f"{fits} fits"),
        checks.exactly("tree.operand.bytes", per_fit["tree.operand.bytes"],
                       width * staged * itemsize,
                       f"{width} x {staged:.0f} staged rows x {itemsize} B a "
                       f"fit; {rows:.0f} rows a fit"),
        checks.at_most("tree.operand.padding",
                       staged / rows, 1.125,
                       "staged rows over rows"),
        checks.at_most("tree.operand.blocks.shortfall",
                       1.0 - per_fit["tree.operand.blocks"], 0.0,
                       f"{per_fit['tree.operand.blocks']:.0f} blocks a fit"),
        checks.exactly("fit.featurize.plan.declined",
                       per_fit["featurize.plan.declined"], 0.0),
    ]
    return out
