#!/usr/bin/env python3
"""Readings the limits of `mle03_logreg_cv` were set from. Not part of a
run; needs the chip, as a run does.

    python3 benchmark/tools_cv.py control --seeds 2 [--first-seed N] [--rows N]
            [--rehearsal]
            [--stages floor_rule,program,program_bfloat16,reference_bfloat16]
        per seed: the table, one 80/20 split, one `Pipeline.fit` of the
        configuration's pipeline at the cell's own size, then a stage
        after another. `program` (sound) and `program_bfloat16` (the
        program with every product's operands rounded to bfloat16: the
        fits', every row's margin's and the served margin's) go through
        the kind's own `check`, the comparison a run is judged by: its
        lines as a run prints them, then a JSON line with `correct` and the
        lines that failed. `reference_bfloat16`: the REFERENCE's own fit of
        the chosen point with every operand of a product rounded to
        bfloat16, held to the coefficient and optimality lines.
        `floor_rule`: the estimator alone at three points with a lasso
        part that keep a support (and one that fits the null model), with
        the loop's floor rule (`linear_impl._stalled`) on and off: the
        steps each took, whether the coefficients are the same to the bit,
        and what ended at the floor. A stage's frames and blocks are
        collected before the next begins, and the one-chip host's 40 GiB
        still do not hold the third stage after the second at 8 M rows:
        give it a call of its own (`--stages reference_bfloat16`)
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import runner  # noqa: E402

_logistic_tools = runner.load_module(
    os.path.join(ROOT, "benchmark", "tools_logistic.py"),
    "bench_tools_logistic")


@contextlib.contextmanager
def bfloat16_products():
    """`tools_logistic.bfloat16_products` with the penalized program's
    block lossy too: `_expand_block` hands its products the block as a
    `_Bfloat16Block` (the Newton passes and the held folds' margins), and
    the penalized programs are forgotten on the way in and out."""
    from sml_tpu.ml import linear_impl
    expand = linear_impl._expand_block

    def lossy_expand(*a, **k):
        block, *rest = expand(*a, **k)
        return (_logistic_tools._Bfloat16Block(block), *rest)

    with _logistic_tools.bfloat16_products():
        linear_impl._expand_block = lossy_expand
        linear_impl._compact_enet_fns.clear()
        try:
            yield
        finally:
            linear_impl._expand_block = expand
            linear_impl._compact_enet_fns.clear()


#: (regParam, elasticNetParam): the grid's sparse point, two smaller
#: penalties whose lasso part keeps a wider support, one null model
FLOOR_POINTS = [(0.1, 0.5), (0.02, 0.5), (0.01, 1.0), (0.2, 1.0)]


def floor_rule(tuned, cfg, train) -> dict:
    """The estimator alone at `FLOOR_POINTS` on `train`, the loop's floor
    rule on and off (`linear_impl._FLOOR` 0: no step is under it): steps,
    what ended at the floor or at maxIter, and whether the two fits'
    coefficients are the same to the bit (they are where the rule never
    acted: the programs then differ in nothing that is computed)."""
    import numpy as np
    from sml_tpu.ml import _staging, linear_impl
    held, out = linear_impl._FLOOR, {}

    def fits(floor):
        linear_impl._FLOOR = floor
        linear_impl._compact_enet_fns.clear()
        _staging._compiled_cache.clear()
        got = []
        for lam, alpha in FLOOR_POINTS:
            before = tuned.counters()
            tail = tuned.build_single(cfg, {
                "regParam": lam, "elasticNetParam": alpha}).fit(
                    train).stages[-1]
            after = tuned.counters()
            got.append((np.append(tail.coefficients.toArray(),
                                  tail.intercept),
                        {k.rsplit(".", 1)[-1]: after.get(k, 0.0)
                         - before.get(k, 0.0) for k in (
                             "linear.irls.steps_run",
                             "linear.irls.floor_ended",
                             "linear.irls.unconverged")}))
        return got
    try:
        with_rule, without = fits(held), fits(0.0)
    finally:
        linear_impl._FLOOR = held
        linear_impl._compact_enet_fns.clear()
        _staging._compiled_cache.clear()
    for point, (w, on), (v, off) in zip(FLOOR_POINTS, with_rule, without):
        out[f"{point[0]}_{point[1]}"] = {
            "nonzero": int((w[:-1] != 0).sum()), "rule_on": on,
            "rule_off": off, "same_to_the_bit": bool(np.array_equal(w, v)),
            "coefficients_gap_max": float(np.max(np.abs(w - v)))}
    return out


def control(args) -> int:
    import numpy as np
    from benchmark.harness import checks, device, program, spec
    from benchmark.reference import logistic
    from benchmark.reference import logistic_enet as enet
    bench = spec.load_benchmark(ROOT)
    parts = spec.resolve(ROOT, bench, "mle03_logreg_cv.fit_cv")
    if args.rehearsal:
        print("REHEARSAL: not on the chip; no number here is a reading")
    else:
        device.require_tpu(1)
    cfg = parts["config"]
    if args.rehearsal and args.rows:
        # a small table takes the cell's fused path all the same
        cfg = dict(cfg, conf=dict(cfg.get("conf", {}),
                                  **{"sml.linear.compactBytes": 0}))
    program.configure(cfg.get("conf", {}))
    kind = runner.load_module(parts["kind_path"], "bench_kind_fit_cv")
    data = runner.load_module(parts["data_path"], "bench_data")
    tuned = kind.Program(program)
    label = cfg["label"]["fit_column"]
    rows = dict(cfg["data"], rows=args.rows or cfg["data"]["rows"])
    def held() -> float:
        """This process's resident gigabytes, for the stages' log."""
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE") / 1e9

    def say(seed, what, numbers, t0):
        # a line a stage, as it ends: a later stage's failure loses nothing
        print(json.dumps({"seed": seed, "what": what, **numbers,
                          "seconds": round(time.perf_counter() - t0, 1)}),
              flush=True)
        gc.collect()    # a stage's frames and blocks go before the next's

    def judged(seed, train, rest):
        """One `Pipeline.fit`, then the kind's own `check` of it as of a
        window of that one fit: the lines printed, and what they say."""
        before = program.counters()
        t = time.perf_counter()
        model = tuned.build_pipeline(cfg).fit(train)
        result = {"last": (model, train, rest), "rows": [train.count()],
                  "fits": [time.perf_counter() - t],
                  "counted": kind._counted(before, program.counters())}
        ctx = types.SimpleNamespace(config=cfg, program=tuned, seed=seed,
                                    log=print, cell="mle03_logreg_cv.fit_cv")
        lines = kind.check(ctx, None, result)
        for line in lines:
            print(line.line(), flush=True)
        return model, {"correct": checks.all_ok(lines),
                       "failed": [c.name for c in lines if not c.ok]}

    for seed in range(args.first_seed, args.first_seed + args.seeds):
        t0 = time.perf_counter()
        table = program.make_table(data.make(rows, seed))
        train, rest = program.split(table, [0.8, 0.2], seed)
        del table
        if "floor_rule" in args.stages:
            say(seed, "floor_rule", floor_rule(tuned, cfg, train), t0)
        if {"program", "reference_bfloat16"} & set(args.stages):
            model, numbers = judged(seed, train, rest)
            point = tuned.validated(model)
            del model
            say(seed, "program", numbers, t0)
        if "program_bfloat16" in args.stages:
            with bfloat16_products():
                lossy, numbers = judged(seed, train, rest)
            del lossy
            say(seed, "program_bfloat16", numbers, t0)
        if "reference_bfloat16" in args.stages:
            # the reference's own steps in bfloat16, held to its float64 fit
            raw = train.toPandas()
            tab = logistic.Compact(raw, logistic.design(raw, label))
            y = raw[label].to_numpy(dtype=np.float64)[tab.keep]
            std = enet.Standardized(tab, y)
            # the last stage: the frames (and the table-wide concat the
            # training frame keeps) go before the reference's passes
            del raw, tab, train, rest
            gc.collect()
            print(f"reference_bfloat16: {held():.1f} GB resident with the "
                  f"blocks made", flush=True)
            at = point["grid"][int(np.argmax(point["avg_metrics"]))]
            lam, alpha = at["regParam"], at["elasticNetParam"]
            best = enet.fit(std, lam, alpha)
            print(f"reference_bfloat16: {held():.1f} GB after the float64 "
                  f"fit, {best['passes']} passes", flush=True)
            # a rounded pass makes five temporaries the size of a block a
            # thread: two threads, not eight, on a host of 40 GiB
            enet.WORKERS = 2
            rounded = enet.fit(std, lam, alpha, precision="bfloat16",
                               max_iter=15)
            err = np.abs(rounded["coefficients"] - best["coefficients"]) \
                / kind.standard_errors(std, best["c"])
            numbers = {
                "coefficient_err_max": float(err.max()),
                "kkt_residual_max": float(enet.residual_at(
                    std, rounded["coefficients"], lam, alpha).max()),
                "passes": rounded["passes"]}
            del std
            say(seed, "reference_bfloat16", numbers, t0)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="what", required=True)
    c = sub.add_parser("control")
    c.add_argument("--seeds", type=int, default=2)
    c.add_argument("--first-seed", type=int, default=5000)
    c.add_argument("--rows", type=int, default=0)
    c.add_argument("--rehearsal", action="store_true",
                   help="run where there is no chip, to try the tool")
    c.add_argument("--stages", type=lambda v: v.split(","),
                   default=["floor_rule", "program", "program_bfloat16",
                            "reference_bfloat16"])
    args = ap.parse_args()
    return {"control": control}[args.what](args)


if __name__ == "__main__":
    sys.exit(main())
