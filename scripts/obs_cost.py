#!/usr/bin/env python3
"""What the recorder costs a fit: `Pipeline.fit` of a benchmark fit cell with
`sml.obs.enabled` true against false, untraced, in one process. Needs the
chip, as a run does; not part of a run, and no switch of the benchmark.

    python3 scripts/obs_cost.py --workload ml07_rf.fit --seed 7 --fits 40

Set-up as the cell's kind does it (the seeded table, its warm fits, the
recorder on). Then `--fits` timed fits, each on a `randomSplit` this process
has not fitted, the recorder on and off in turn (on, off, off, on, ...). With
the recorder on, the span totals give the phases of the untraced fit
(`fit.baseline` is what the recorder itself adds to every fit). Then what
one `PROFILER.span` costs in this process (the runtime's and the pool's
threads alive, as in a fit), median microseconds of 10,000: the recorder
off, on for a plain span, on for a span that reads the process's CPU seconds
(`taxonomy.CPU_SPANS`: a `time.process_time` at each end), the read alone,
and the two spans again inside a profiler trace started as a traced run of
the benchmark starts it (every span is a `TraceAnnotation` there). Writes
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


def span_cost_us(trace_dir: str, calls: int = 10000) -> dict:
    """Median microseconds of an empty `PROFILER.span`: off, on, and on
    inside a profiler trace written under `trace_dir`."""
    import time

    import jax

    from sml_tpu.conf import GLOBAL_CONF
    from sml_tpu.utils.profiler import PROFILER, now

    def median_us(fn):
        walls = []
        for _ in range(calls):
            t = now()
            fn()
            walls.append(now() - t)
        return statistics.median(walls) * 1e6

    def span(name):
        def enter_and_leave():
            with PROFILER.span(name):
                pass
        return enter_and_leave

    def both(state):
        return {state + ".plain": median_us(span("fit.dispatch")),
                state + ".cpu": median_us(span("fit.featurize"))}

    out = {"process_time": median_us(time.process_time)}
    for on in (False, True):
        GLOBAL_CONF.set("sml.obs.enabled", on)
        out.update(both("on" if on else "off"))
    options = jax.profiler.ProfileOptions()    # as harness/runner.py's
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        out.update(both("traced"))
    finally:
        jax.profiler.stop_trace()
    return out


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
            if on and name.startswith(("span_s.fit", "span_s.stage.")):
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
        "span_us": span_cost_us(os.path.join(ctx.workdir, "span_trace")),
    }
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, args.workload + ".json"), "w") as f:
        json.dump(report, f, indent=1)
    print("OBS COST " + json.dumps(report), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
