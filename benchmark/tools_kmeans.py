#!/usr/bin/env python3
"""Readings the limits of `mle02_kmeans` were set from. Not part of a run;
the program's stages need the chip, as a run does.

    python3 benchmark/tools_kmeans.py control --seeds 1 [--first-seed N]
            [--rows N --k N] [--rehearsal]
            [--stages program,program_bfloat16,reference_bfloat16,seedings]
        per seed: the table, one 80/20 split, then a stage after another.
        `program`: ONE `Pipeline.fit` of the configuration's pipeline at
        the cell's own size, and the check's two fits more, judged by the
        kind's own `check` as a window of that one fit: its lines as a run
        prints them, then a JSON line with `correct` and the lines that
        failed. Sound. `program_bfloat16`: the same with the operands of
        every product of the program (the centers and the rows of a
        distance, the rows of a cluster's sum) rounded to bfloat16 by
        `jax.lax.reduce_precision` (`clustering._product_operand`
        replaced). `reference_bfloat16`: the REFERENCE's own step from the
        sound program's seeding with every operand of a product rounded to
        bfloat16, held to the step's two lines against its float64 self;
        plain NumPy, so it reads the same with `--rehearsal` where there is
        no chip. `seedings`: the cost of the seeding this program had
        before k-means|| (k-means++ over 4,096 sampled rows) and of
        `initMode="random"`'s k distinct rows, each over the cost of the
        reference's own k-means|| on the same rows: the controls of
        `fit.seeding_cost_vs_reference.ratio`; plain NumPy too.
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

CELL = "mle02_kmeans.fit_kmeans"


@contextlib.contextmanager
def bfloat16_operands():
    """Every operand of a product of the program rounded to bfloat16's 8
    bits of exponent and 7 of mantissa; the products' sums stay float32.
    The fit programs traced before are forgotten on the way in and out."""
    import jax
    from sml_tpu.ml import clustering
    held = clustering._product_operand
    clustering._product_operand = \
        lambda a: jax.lax.reduce_precision(a, 8, 7)
    clustering.forget_programs()
    try:
        yield
    finally:
        clustering._product_operand = held
        clustering.forget_programs()


STAGES = {"program": contextlib.nullcontext,
          "program_bfloat16": bfloat16_operands}


def control(args) -> int:
    import numpy as np
    from benchmark.harness import checks, device, program, spec
    from benchmark.reference import kmeans
    bench = spec.load_benchmark(ROOT)
    parts = spec.resolve(ROOT, bench, CELL)
    if args.rehearsal:
        print("REHEARSAL: not on the chip; no number of a program stage "
              "here is a reading")
    else:
        device.require_tpu(1)
    cfg = parts["config"]
    if args.k:
        cfg["pipeline"][-1]["params"]["k"] = cfg["fit_math"]["k"] = args.k
    program.configure(cfg.get("conf", {}))
    kind = runner.load_module(parts["kind_path"], "bench_kind_fit_kmeans")
    data = runner.load_module(parts["data_path"], "bench_data")
    fitted = kind.Program(program)
    math = cfg["fit_math"]
    k, cols = int(math["k"]), kind._columns(cfg)
    shape = dict(cfg["data"], **({"rows": args.rows} if args.rows else {}))

    def say(seed, what, numbers, t0):
        print(json.dumps({"seed": seed, "what": what, **numbers,
                          "seconds": round(time.perf_counter() - t0, 1)}),
              flush=True)
        gc.collect()

    def judged(seed, train, rest, keep):
        """One `Pipeline.fit`, then the kind's own `check` of it as of a
        window of that one fit."""
        before = program.counters()
        t = time.perf_counter()
        model = fitted.build_pipeline(cfg).fit(train)
        seconds = time.perf_counter() - t
        counted = kind._counted(before, program.counters())
        steps = [fitted.build_pipeline(cfg, maxIter=m).fit(train)
                 for m in (0, 1)]
        keep.setdefault("seeded", fitted.fitted(steps[0])["centers"])
        result = {"last": (model, train, rest), "rows": [train.count()],
                  "fits": [seconds], "steps": steps, "counted": counted,
                  "iterations": [counted["kmeans.iterations"]]}
        ctx = types.SimpleNamespace(config=cfg, program=fitted, seed=seed,
                                    log=print, cell=CELL, facts={})
        lines = kind.check(ctx, None, result)
        for line in lines:
            print(line.line(), flush=True)
        return {"correct": checks.all_ok(lines), "fit_s": seconds,
                "failed": [c.name for c in lines if not c.ok]}

    for seed in range(args.first_seed, args.first_seed + args.seeds):
        t0 = time.perf_counter()
        table = program.make_table(data.make(shape, seed))
        train, rest = program.split(table, [0.8, 0.2], seed)
        del table
        keep: dict = {}
        for stage in args.stages:
            if stage in STAGES:
                with STAGES[stage]():
                    say(seed, stage, judged(seed, train, rest, keep), t0)
        X = None
        if {"reference_bfloat16", "seedings"} & set(args.stages):
            X = fitted.rows(train, cols)
        if "reference_bfloat16" in args.stages:
            start = keep.get("seeded")
            if start is None:   # no program stage ran: its own seeding
                start = kmeans.kmeans_parallel(
                    X, k, int(math["initSteps"]), seed)["centers"]
            exact = kmeans.lloyd_step(X, start)
            rounded = kmeans.lloyd_step(X, start, round_to="bfloat16")
            say(seed, "reference_bfloat16", {
                "step_center_err_max": float(kmeans.center_errors(
                    exact, rounded["centers"], X).max()),
                "step_count_gap_max": float(np.abs(
                    rounded["counts"] - exact["counts"]).max()) / len(X),
                "assignment_changed_share": float(
                    (rounded["assignment"] != exact["assignment"]).mean())},
                t0)
        if "seedings" in args.stages:
            own = kmeans.cost(X, kmeans.kmeans_parallel(
                X, k, int(math["initSteps"]), seed)["centers"])
            say(seed, "seedings", {
                "sampled_kmeans_pp_ratio": kmeans.cost(
                    X, kmeans.sampled_kmeans_pp(X, k, seed)) / own,
                "random_rows_ratio": kmeans.cost(
                    X, kmeans.random_rows(X, k, seed)) / own}, t0)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="what", required=True)
    c = sub.add_parser("control")
    c.add_argument("--seeds", type=int, default=1)
    c.add_argument("--first-seed", type=int, default=5000)
    c.add_argument("--rows", type=int, default=0)
    c.add_argument("--k", type=int, default=0)
    c.add_argument("--rehearsal", action="store_true",
                   help="run where there is no chip, to try the tool")
    c.add_argument("--stages", type=lambda v: v.split(","),
                   default=list(STAGES) + ["reference_bfloat16", "seedings"])
    args = ap.parse_args()
    return {"control": control}[args.what](args)


if __name__ == "__main__":
    sys.exit(main())
