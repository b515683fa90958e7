#!/usr/bin/env python3
"""Readings the limits of `xgb_higgs` were set from. Not part of a run; the
program's stage needs the chip, as a run does.

    python3 benchmark/tools_higgs.py control --seeds 1 [--first-seed N]
            [--rows N --rounds N] [--rehearsal]
            [--stages program,reference_fp8,reference_squared]
        per seed: the table, one 80/20 split, ONE `Pipeline.fit` of the
        configuration's pipeline at the cell's own size, then a stage after
        another, each the kind's own `measure` of that one model judged by
        its `verdicts`: the lines as a run prints them, then a JSON line
        with every number, `correct` and the lines that failed. `program`:
        the reference as a run computes it. Sound. `reference_fp8`: the
        first control, the split chosen, the leaf, the hessian mass and the
        descent computed from operands rounded to fp8 (e4m3: the nearest
        precision below the bfloat16 the configuration states).
        `reference_squared`: the second, the squared loss's gradients
        (`fitcheck`'s `margin - y`, hessian 1) in the reference's place.
        Each control has to FAIL at least one line.

    python3 benchmark/tools_higgs.py ceiling [--seed N] [--rows N]
        the generator's own numbers, plain NumPy: the positive share and
        the area under the ROC curve of the planted probability, the best
        any model can reach on a holdout.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import runner  # noqa: E402

CELL = "xgb_higgs.fit_boost_logistic"
STAGES = {"program": {},
          "reference_fp8": {"precision": "fp8_e4m3"},
          "reference_squared": {"gradients": "squared"}}


def control(args) -> int:
    from benchmark.harness import checks, device, program, spec
    bench = spec.load_benchmark(ROOT)
    parts = spec.resolve(ROOT, bench, CELL)
    if args.rehearsal:
        print("REHEARSAL: not on the chip; no number of the program's here "
              "is a reading")
    else:
        device.require_tpu(1)
    cfg = parts["config"]
    if args.rounds:
        cfg["pipeline"][-1]["params"]["n_estimators"] = args.rounds
    program.configure(cfg.get("conf", {}))
    kind = runner.load_module(parts["kind_path"],
                              "bench_kind_fit_boost_logistic")
    data = runner.load_module(parts["data_path"], "bench_data")
    fitted = kind.Program(program)
    shape = dict(cfg["data"], **({"rows": args.rows} if args.rows else {}))
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        t0 = time.perf_counter()
        table = program.make_table(data.make(shape, seed))
        train, rest = program.split(table, [0.8, 0.2], seed)
        del table
        before = program.counters()
        t = time.perf_counter()
        model = fitted.build_pipeline(cfg).fit(train)
        fit_s = time.perf_counter() - t
        counted = kind._counted(before, program.counters())
        print(json.dumps({"seed": seed, "what": "fit", "fit_s": fit_s,
                          "rows": train.count(), **counted}), flush=True)
        for stage in args.stages:
            got = kind.measure(fitted, cfg, model, train, rest, seed,
                               **STAGES[stage])
            lines = kind.verdicts(cfg, got)
            for line in lines:
                print(line.line(), flush=True)
            print(json.dumps({
                "seed": seed, "what": stage,
                **{k: v for k, v in got.items() if k != "margin"},
                "correct": checks.all_ok(lines),
                "failed": [c.name for c in lines if not c.ok],
                "seconds": round(time.perf_counter() - t0, 1)}), flush=True)
            gc.collect()
    return 0


def ceiling(args) -> int:
    import numpy as np
    from benchmark.harness import spec
    from benchmark.reference import boost_logistic
    bench = spec.load_benchmark(ROOT)
    parts = spec.resolve(ROOT, bench, CELL)
    data = runner.load_module(parts["data_path"], "bench_data")
    rows = args.rows or int(parts["config"]["data"]["rows"])
    _, p_signal, label = data.events(rows, args.seed)
    print(json.dumps({
        "seed": args.seed, "rows": rows,
        "positive_share": float(label.mean()),
        "planted_auroc": boost_logistic.auroc(p_signal, label),
        "planted_log_loss_ratio": boost_logistic.log_loss(p_signal, label)
        / boost_logistic.log_loss(np.full(rows, label.mean()), label)}))
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="what", required=True)
    c = sub.add_parser("control")
    c.add_argument("--seeds", type=int, default=1)
    c.add_argument("--first-seed", type=int, default=5000)
    c.add_argument("--rows", type=int, default=0)
    c.add_argument("--rounds", type=int, default=0)
    c.add_argument("--rehearsal", action="store_true",
                   help="run where there is no chip, to try the tool")
    c.add_argument("--stages", type=lambda v: v.split(","),
                   default=list(STAGES))
    g = sub.add_parser("ceiling")
    g.add_argument("--seed", type=int, default=1)
    g.add_argument("--rows", type=int, default=0)
    args = ap.parse_args()
    return {"control": control, "ceiling": ceiling}[args.what](args)


if __name__ == "__main__":
    sys.exit(main())
