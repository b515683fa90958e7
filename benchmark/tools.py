#!/usr/bin/env python3
"""Readings the benchmark's limits were set from.
Not part of a run; each needs the chip, as a run does.

    python3 benchmark/tools.py control --config ml11_xgb --seeds 12 [--control-seeds 4] [--first-seed N]
        per seed: fit once at the cell's own size, then every number
        `correct` compares, for the program (sound) and, on the first
        --control-seeds of them, for the reference computed in the next
        lower precision (control: fp8 operands for the fit's bfloat16
        ones, a bfloat16 descent for the float32 one)

    python3 benchmark/tools.py trace-cut <file.xplane.pb> <out.textproto> --label bench.fit --ops 400
        a small cut of a recorded trace, as a text proto the tests can read:
        the first --ops device operations after the start of the first
        --label annotation, and the annotations over them
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _context(config: str, seed: int):
    """The first cell of the configuration, set up as a run sets it up."""
    from benchmark.harness import device, program, runner, spec
    bench = spec.load_benchmark(ROOT)
    cell = next(w["name"] for w in bench["workloads"]
                if w["config"] == config)
    parts = spec.resolve(ROOT, bench, cell)
    device.require_tpu(int(parts["workload"]["chips"]))
    workdir = os.path.join(ROOT, runner.WORK_DIR, "tools")
    os.makedirs(workdir, exist_ok=True)
    program.configure(parts["config"].get("conf", {}))
    return runner.Context(
        root=ROOT, cell=cell, config=parts["config"],
        traffic=parts["traffic"], seed=seed, seconds=0.0, trace=False,
        workdir=workdir, program=program,
        data=runner.load_module(parts["data_path"], "bench_data"))


def control(args) -> int:
    import numpy as np
    from benchmark.reference import bootstrap, featurize, fitcheck, forest
    ctx = _context(args.config, args.first_seed)
    program, cfg = ctx.program, ctx.config
    math, limits = cfg["fit_math"], cfg["correct"]
    for i, seed in enumerate(range(args.first_seed,
                                   args.first_seed + args.seeds)):
        t0 = time.perf_counter()
        table = program.make_table(ctx.data.make(cfg["data"], seed))
        train, rest = program.split(table, [0.8, 0.2], seed)
        frame = program.with_label(cfg, train)
        model = program.build_pipeline(cfg).fit(frame)
        tables = program.model_tables(model)
        raw = frame.toPandas()
        y = raw[cfg["label"]["fit_column"]].to_numpy(dtype=np.float64)
        keep = np.isfinite(y)
        bins = featurize.bins(raw[keep], tables, math.get("missing"))
        weights, mask = bootstrap.streams(math, *bins.shape)
        lower = i < args.control_seeds
        row = {"seed": seed}
        for precision in (None, "fp8_e4m3") if lower else (None,):
            got = fitcheck.fit_statistics(
                bins, y[keep], tables, math, seed, tree_weights=weights,
                feature_mask=mask, n_trees=limits["fit_sample_trees"],
                nodes_per_tree=limits["fit_sample_nodes"],
                leaves_per_tree=limits["fit_sample_leaves"],
                leaf_only_trees=limits.get("fit_leaf_only_trees", 0),
                precision=precision)
            row[precision or "program"] = {k: got[k] for k in (
                "split_gain_gap_median", "leaf_value_err_median",
                "cover_gap_max", "nodes", "leaves")}
        held = program.with_label(cfg, rest)
        hraw = held.toPandas()
        served = program.predictions(model, held)
        pick = np.random.default_rng(seed).choice(
            len(hraw), size=min(int(limits["sample_rows"]), len(hraw)),
            replace=False)
        hbins = featurize.bins(hraw.iloc[pick], tables, math.get("missing"))
        want = forest.predict(hbins, tables)
        row["score_rel_gap"] = {
            "program": forest.worst_relative_gap(served[pick], want)}
        if lower:
            row["score_rel_gap"]["bfloat16"] = forest.worst_relative_gap(
                forest.predict(hbins, tables, "bfloat16"), want)
        truth = hraw[cfg["label"]["fit_column"]].to_numpy(dtype=np.float64)
        ok = np.isfinite(truth)
        row["rmse_ratio"] = fitcheck.rmse(served[ok], truth[ok]) / \
            fitcheck.rmse(np.full(ok.sum(), y[keep].mean()), truth[ok])
        row["seconds"] = round(time.perf_counter() - t0, 1)
        print(json.dumps(row), flush=True)
    return 0


def trace_cut(args) -> int:
    from benchmark.harness.xplane import Trace, WINDOW
    trace = Trace.from_file(args.file)
    spans = trace.spans(args.label)
    if not spans:
        print(f"no {args.label} annotation in {args.file}", file=sys.stderr)
        return 1
    t0 = spans[0][0]
    ops = sorted((e for e in trace.device_ops[0] if e[1] >= t0),
                 key=lambda e: e[1])[:args.ops]
    t1 = max(b for _, _, b in ops)
    notes = [(WINDOW, t0, t1)] + [
        (n, max(a, t0), min(b, t1)) for n, a, b in trace.annotations
        if n != WINDOW and b > t0 and a < t1]

    def plane(pid, name, line, events):
        names = sorted({n for n, _, _ in events})
        ids = {n: i + 1 for i, n in enumerate(names)}
        out = [f'planes {{ id: {pid} name: "{name}"',
               f'  lines {{ id: 1 name: "{line}" timestamp_ns: 0']
        for n, a, b in events:
            out.append(f"    events {{ metadata_id: {ids[n]} offset_ps: "
                       f"{int(round((a - t0) * 1000))} duration_ps: "
                       f"{int(round((b - a) * 1000))} }}")
        out.append("  }")
        for n in names:
            out.append(f"  event_metadata {{ key: {ids[n]} value {{ id: "
                       f"{ids[n]} name: {json.dumps(n)} }} }}")
        out.append("}")
        return out

    lines = [f"# cut of {os.path.basename(args.file)}: {len(ops)} device "
             f"operations from the start of the first {args.label}"]
    lines += plane(1, "/device:TPU:0", "XLA Ops", ops)
    lines += plane(2, "/host:CPU", "python3", notes)
    with open(args.out, "w") as f:
        f.write("\n".join(lines) + "\n")
    print(f"wrote {args.out}: {len(ops)} operations, {len(notes)} annotations")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="what", required=True)
    c = sub.add_parser("control")
    c.add_argument("--config", required=True)
    c.add_argument("--seeds", type=int, default=12)
    c.add_argument("--control-seeds", type=int, default=4)
    c.add_argument("--first-seed", type=int, default=5000)
    k = sub.add_parser("trace-cut")
    k.add_argument("file")
    k.add_argument("out")
    k.add_argument("--label", default="bench.fit")
    k.add_argument("--ops", type=int, default=400)
    args = ap.parse_args()
    return {"control": control, "trace-cut": trace_cut}[args.what](args)


if __name__ == "__main__":
    sys.exit(main())
