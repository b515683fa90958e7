#!/usr/bin/env python3
"""Readings the limits of `mle01_als` were set from. Not part of a run;
the program's stages need the chip, as a run does.

    python3 benchmark/tools_als.py control --seeds 1 [--first-seed N]
            [--rows N --users N --items N] [--rehearsal]
            [--stages program,program_plain_prefix,program_bfloat16,reference_bfloat16]
        per seed: the table, one 80/20 split, ONE float64 reference fit of
        its training part, then a stage after another, each ONE `ALS.fit`
        of the configuration's estimator at the cell's own size judged by
        the kind's own `check` against that reference: its lines as a run
        prints them, then a JSON line with `correct` and the lines that
        failed. `program`: sound. `program_plain_prefix`: a block's sums
        as the difference of two plain float32 prefixes over the whole
        block (`recommendation._block_sums` replaced), in place of the
        scan that begins anew at every entity. `program_bfloat16`: the
        operands of every statistic (the gathered factor rows, the
        ratings) rounded to bfloat16 by `jax.lax.reduce_precision`
        (`recommendation._stat_operands` replaced). `reference_bfloat16`:
        the REFERENCE's own alternations with every operand of a product
        rounded to bfloat16, held to the prediction and residual lines
        against its float64 self; plain NumPy, so it reads the same with
        `--rehearsal` where there is no chip
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

CELL = "mle01_als.fit_als"


@contextlib.contextmanager
def replaced(name: str, fn):
    """`recommendation.<name>` replaced by `fn`, with the fit programs
    traced before forgotten on the way in and out."""
    from sml_tpu.ml import _staging, recommendation

    def forget():
        recommendation._als_fit_program.cache_clear()
        _staging._compiled_cache.clear()

    held = getattr(recommendation, name)
    setattr(recommendation, name, fn)
    forget()
    try:
        yield
    finally:
        setattr(recommendation, name, held)
        forget()


def plain_prefix():
    """A block's sums by entity as the boundary difference of ONE plain
    float32 prefix over the block's rows."""
    import jax.numpy as jnp

    def block_sums(stats, begins, s, t):
        run = jnp.concatenate([jnp.zeros_like(stats[:1]),
                               jnp.cumsum(stats, axis=0)])
        return run[t] - run[s]
    return replaced("_block_sums", block_sums)


def bfloat16_operands():
    """The operands of every statistic rounded to bfloat16's 8 bits of
    exponent and 7 of mantissa; the products and sums stay float32."""
    import jax

    def operands(f, rat):
        return (jax.lax.reduce_precision(f, 8, 7),
                jax.lax.reduce_precision(rat, 8, 7))
    return replaced("_stat_operands", operands)


STAGES = {"program": contextlib.nullcontext,
          "program_plain_prefix": plain_prefix,
          "program_bfloat16": bfloat16_operands}


def control(args) -> int:
    import numpy as np
    from benchmark.harness import checks, device, program, spec
    from benchmark.reference import als
    bench = spec.load_benchmark(ROOT)
    parts = spec.resolve(ROOT, bench, CELL)
    if args.rehearsal:
        print("REHEARSAL: not on the chip; no number of a program stage "
              "here is a reading")
    else:
        device.require_tpu(1)
    cfg = parts["config"]
    program.configure(cfg.get("conf", {}))
    kind = runner.load_module(parts["kind_path"], "bench_kind_fit_als")
    data = runner.load_module(parts["data_path"], "bench_data")
    fitted = kind.Program(program)
    math, limits = cfg["fit_math"], cfg["correct"]
    shape = dict(cfg["data"], **{k: v for k, v in (
        ("rows", args.rows), ("users", args.users), ("items", args.items))
        if v})

    def say(seed, what, numbers, t0):
        print(json.dumps({"seed": seed, "what": what, **numbers,
                          "seconds": round(time.perf_counter() - t0, 1)}),
              flush=True)
        gc.collect()

    def judged(seed, train, rest, reference):
        """One `ALS.fit`, then the kind's own `check` of it as of a
        window of that one fit."""
        before = program.counters()
        t = time.perf_counter()
        model = fitted.build_pipeline(cfg).fit(train)
        result = {"last": (model, train, rest), "rows": [train.count()],
                  "fits": [time.perf_counter() - t], "reference": reference,
                  "counted": kind._counted(before, program.counters())}
        ctx = types.SimpleNamespace(
            config=cfg, program=fitted, seed=seed, log=print, cell=CELL,
            facts={"als_entities": len(reference["user_ids"])
                   + len(reference["item_ids"])})
        lines = kind.check(ctx, None, result)
        for line in lines:
            print(line.line(), flush=True)
        return {"correct": checks.all_ok(lines), "fit_s": result["fits"][0],
                "failed": [c.name for c in lines if not c.ok]}

    for seed in range(args.first_seed, args.first_seed + args.seeds):
        t0 = time.perf_counter()
        table = program.make_table(data.make(shape, seed))
        train, rest = program.split(table, [0.8, 0.2], seed)
        del table
        raw = train.toPandas()
        cols = [raw[math[k]].to_numpy() for k in ("userCol", "itemCol",
                                                  "ratingCol")]
        fit_args = (int(math["rank"]), int(math["maxIter"]),
                    float(math["regParam"]), int(math["seed"]))
        reference = als.fit(*cols, *fit_args)
        say(seed, "reference", {"ratings": len(raw)}, t0)
        for stage in args.stages:
            if stage in STAGES:
                with STAGES[stage]():
                    say(seed, stage, judged(seed, train, rest, reference), t0)
        if "reference_bfloat16" in args.stages:
            rounded = als.fit(*cols, *fit_args, round_to="bfloat16")
            hold = rest.toPandas()
            pick = np.sort(np.random.default_rng(seed).choice(
                len(hold), replace=False,
                size=min(int(limits["sample_rows"]), len(hold))))
            pairs = [hold[math[k]].to_numpy()[pick]
                     for k in ("userCol", "itemCol")]
            gap = np.abs(als.predict(rounded, *pairs)
                         - als.predict(reference, *pairs))
            items = kind._sampled_items(
                reference["by_item"].counts, int(limits["residual_items"]),
                np.random.default_rng(seed))
            residual = als.normal_residual(
                reference["by_item"], rounded["item_factors"],
                rounded["user_factors"], float(math["regParam"]), items)
            say(seed, "reference_bfloat16", {
                "prediction_abs_gap_max": float(np.nanmax(gap)),
                "normal_residual_max": float(residual.max())}, t0)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="what", required=True)
    c = sub.add_parser("control")
    c.add_argument("--seeds", type=int, default=1)
    c.add_argument("--first-seed", type=int, default=5000)
    c.add_argument("--rows", type=int, default=0)
    c.add_argument("--users", type=int, default=0)
    c.add_argument("--items", type=int, default=0)
    c.add_argument("--rehearsal", action="store_true",
                   help="run where there is no chip, to try the tool")
    c.add_argument("--stages", type=lambda v: v.split(","),
                   default=list(STAGES) + ["reference_bfloat16"])
    args = ap.parse_args()
    return {"control": control}[args.what](args)


if __name__ == "__main__":
    sys.exit(main())
