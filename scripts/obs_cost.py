#!/usr/bin/env python3
"""What the recorder costs a fit: `Pipeline.fit` of a benchmark fit cell with
`sml.obs.enabled` true against false, untraced, in one process. Needs the
chip, as a run does; not part of a run, and no switch of the benchmark.

    python3 scripts/obs_cost.py --workload ml07_rf.fit --seed 7 --fits 40

Set-up as the cell's kind does it (the seeded table, its warm fits, the
recorder on). Then `--fits` timed fits, each on a `randomSplit` this process
has not fitted, the recorder on and off in turn (on, off, off, on, ...). With
the recorder on, the span totals give the phases of the untraced fit
(`fit.baseline` is what the recorder itself adds to every fit). Writes
`<out>/<cell>.json` and prints it.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--fits", type=int, default=16)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "obs_cost"))
    ap.add_argument("--rehearse", metavar="DIR", help="a REHEARSAL on any "
                    "backend: the tests' tiny copy of the benchmark is made "
                    "under DIR and --workload names one of its tiny cells")
    args = ap.parse_args()
    from benchmark.harness import device, program, runner, spec
    from sml_tpu.conf import GLOBAL_CONF
    from sml_tpu.utils.profiler import now
    root, bench = ROOT, spec.load_benchmark(ROOT)
    if args.rehearse:
        sys.path.insert(0, os.path.join(ROOT, "tests", "benchmark"))
        import bench_tiny
        import jax
        root, bench = bench_tiny.make_tiny_root(args.rehearse)
        print("REHEARSAL: tiny cell, any backend; no number below is a "
              "measurement", flush=True)
        described = device.describe(jax.devices()[:1])
    else:
        described = device.describe(device.require_tpu(1)[:1])
    parts = spec.resolve(root, bench, args.workload)
    program.configure(parts["config"].get("conf", {}))
    kind = runner.load_module(parts["kind_path"], "bench_kind_fit")
    ctx = runner.Context(
        root=root, cell=args.workload, config=parts["config"],
        traffic=parts["traffic"], seed=args.seed, seconds=0.0, trace=False,
        workdir=os.path.join(root, runner.WORK_DIR, "obs_cost"),
        program=program,
        data=runner.load_module(parts["data_path"], "bench_data"))
    state = kind.setup(ctx)

    times = {True: [], False: []}
    phases = {}
    for i in range(args.fits):
        on = i % 4 in (0, 3)
        frame, _rest, _rows = kind._draw(ctx, state, i)
        pipeline = program.build_pipeline(ctx.config)
        GLOBAL_CONF.set("sml.obs.enabled", on)
        before = program.counters()
        t = now()
        pipeline.fit(frame)
        times[on].append(now() - t)
        after = program.counters()
        GLOBAL_CONF.set("sml.obs.enabled", True)
        for name, total in after.items():
            if on and name.startswith("span_s.fit"):
                phases.setdefault(name[7:], []).append(
                    total - before.get(name, 0.0))
        ctx.log(f"fit {i} recorder {'on' if on else 'off'}: "
                f"{times[on][-1]:.4f}s")
    mean_on, mean_off = (statistics.mean(times[k]) for k in (True, False))
    report = {
        "cell": args.workload, "seed": args.seed, "device": described,
        "fit_s": {"recorder_on": times[True], "recorder_off": times[False]},
        "mean_on_s": mean_on, "mean_off_s": mean_off,
        "median_on_s": statistics.median(times[True]),
        "median_off_s": statistics.median(times[False]),
        "on_over_off": mean_on / mean_off - 1.0,
        "phases_s_a_fit_recorder_on_untraced": {
            name: statistics.mean(v) for name, v in sorted(phases.items())},
    }
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, args.workload + ".json"), "w") as f:
        json.dump(report, f, indent=1)
    print("OBS COST " + json.dumps(report), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
