#!/usr/bin/env python3
"""Readings the limits of `mle03_logreg` were set from. Not part of a run;
needs the chip, as a run does.

    python3 benchmark/tools_logistic.py control --seeds 2 [--first-seed N] [--rows N]
        per seed: the table, one 80/20 split, one fit at the cell's own
        size, then every number `kinds/fit_logistic.py` compares: for the
        program as it is (sound); for the program with its products at the
        operands rounded to bfloat16 (control, the fit's and the served
        margin's); and for the reference's own Newton
        steps with every operand of a product rounded to bfloat16 (control)
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _lines(kind, model, plan, table, y, best, sample, served):
    w = kind.coefficients(model)
    point = kind.fitted_point(table, y, w, best)
    del point["coefficient_err_slot"]
    return dict(point, probability_abs_gap_max=kind.probability_gap(
        served, sample, plan, w))


class _Bfloat16Block:
    """The expanded block with every product over it as a matrix unit of
    bfloat16 operands and a float32 accumulator computes it: the block,
    the vector or the weighted copy it is multiplied with, each rounded to
    bfloat16 first."""

    def __init__(self, block):
        self.block, self.shape = _bf16(block), block.shape

    @property
    def T(self):
        return self.block.T

    def __rmatmul__(self, w):
        return _bf16(w) @ self.block

    def __matmul__(self, v):
        return self.block @ _bf16(v)

    def __mul__(self, v):
        return _bf16(self.block * v)


def _bf16(x):
    """x rounded to bfloat16's 8 exponent and 7 mantissa bits, float32
    out. `reduce_precision` and not a pair of casts: the chip's compiler
    drops a float32 -> bfloat16 -> float32 round trip as excess precision
    it may keep, and the control then reads as the sound program does."""
    import jax
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


@contextlib.contextmanager
def bfloat16_products():
    """The program in the next lower precision: the fit's block handed to
    its products as `_Bfloat16Block`, the served margin's operands rounded
    the same way, the compiled programs forgotten on the way in and out.
    (Dropping the program's `precision=` arguments does NOT do it: on the
    v5e with this libtpu a float32 product at the default precision reads
    the same as at the highest, PERF.md section 2.)"""
    import jax
    import jax.numpy as jnp
    from sml_tpu.ml import _staging, inference, linear_impl

    def forget():
        linear_impl._compact_irls_fns.clear()
        _staging._compiled_cache.clear()

    expand = linear_impl._expand_masked
    forwards = inference._linear_forward, inference._logistic_forward

    def lossy_expand(*a, **k):
        block, shift, scale = expand(*a, **k)
        return _Bfloat16Block(block), shift, scale

    def lossy_margin(Xb, mask, w, b):
        return (jnp.matmul(_bf16(Xb), _bf16(w), precision="highest")
                + b) * mask

    def lossy_probability(Xb, mask, w, b):
        return jax.nn.sigmoid(lossy_margin(Xb, 1.0, w, b)) * mask

    linear_impl._expand_masked = lossy_expand
    # `model.transform` serves the margin (`predict_linear`), a
    # `DeviceScorer` the probability: both products lose their bits
    inference._linear_forward = lossy_margin
    inference._logistic_forward = lossy_probability
    forget()
    try:
        yield
    finally:
        linear_impl._expand_masked = expand
        inference._linear_forward, inference._logistic_forward = forwards
        forget()


def control(args) -> int:
    import numpy as np
    from benchmark.harness import device, program, runner, spec
    from benchmark.reference import logistic
    bench = spec.load_benchmark(ROOT)
    parts = spec.resolve(ROOT, bench, "mle03_logreg.fit_logistic")
    device.require_tpu(1)
    cfg = parts["config"]
    program.configure(cfg.get("conf", {}))
    kind = runner.load_module(parts["kind_path"], "bench_kind_fit_logistic")
    data = runner.load_module(parts["data_path"], "bench_data")
    label = cfg["label"]["fit_column"]
    rows = dict(cfg["data"], rows=args.rows or cfg["data"]["rows"])
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        t0 = time.perf_counter()
        table = program.make_table(data.make(rows, seed))
        train, rest = program.split(table, [0.8, 0.2], seed)
        raw = train.toPandas()
        plan = logistic.design(raw, label)
        tab = logistic.Compact(raw, plan)
        y = raw[label].to_numpy(dtype=np.float64)[tab.keep]
        best = logistic.newton(tab, y)
        hold = rest.toPandas()
        pick = np.sort(np.random.default_rng(seed).choice(
            len(hold), size=min(10000, len(hold)), replace=False))
        sample = hold.iloc[pick].reset_index(drop=True)
        frame = program.make_table(sample)
        row = {"seed": seed, "rows": len(tab),
               "reference_iterations": best["iterations"]}
        model = program.build_pipeline(cfg).fit(train)
        row["program"] = _lines(kind, model, plan, tab, y, best,
                                sample, kind.probabilities(model, frame))
        with bfloat16_products():
            lossy = program.build_pipeline(cfg).fit(train)
            row["program_bfloat16"] = _lines(
                kind, lossy, plan, tab, y, best, sample,
                kind.probabilities(lossy, frame))
            # the sound model, its margins served with bfloat16 operands
            row["served_bfloat16"] = _lines(
                kind, model, plan, tab, y, best, sample,
                kind.probabilities(model, frame))["probability_abs_gap_max"]
        if args.skip_reference_control:
            print(json.dumps(row), flush=True)
            continue
        rounded = logistic.newton(tab, y, precision="bfloat16", max_iter=12)
        row["reference_bfloat16"] = dict(
            kind.fitted_point(tab, y, rounded["coefficients"], best),
            iterations=rounded["iterations"])
        row["seconds"] = round(time.perf_counter() - t0, 1)
        print(json.dumps(row), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="what", required=True)
    c = sub.add_parser("control")
    c.add_argument("--seeds", type=int, default=2)
    c.add_argument("--first-seed", type=int, default=5000)
    c.add_argument("--rows", type=int, default=0)
    c.add_argument("--skip-reference-control", action="store_true")
    args = ap.parse_args()
    return {"control": control}[args.what](args)


if __name__ == "__main__":
    sys.exit(main())
