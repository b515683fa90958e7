#!/usr/bin/env python3
"""What a fit's record costs: a root `fit` (`obs.autolog_fit`) round twelve
empty child spans, the recorder on, median and mean microseconds a fit; then
the parts (one `time.process_time`, the two `/proc` reads). Runs anywhere
and touches no device; `--root` runs the same loop on another checkout (the
parent commit's, for the difference).

    python3 scripts/fit_record_micro.py [--root DIR] [--fits 2000]
"""

import argparse
import json
import os
import statistics
import sys
import time

CHILDREN = ("fit.collect", "fit.prep", "fit.featurize", "fit.featurize",
            "fit.quantize", "fit.stage", "fit.dispatch", "fit.device_wait",
            "fit.readback", "fit.unpack", "fit.baseline", "fit.summary")


def median_us(fn, calls, now):
    walls = []
    for _ in range(calls):
        t = now()
        fn()
        walls.append(now() - t)
    return {"median_us": statistics.median(walls) * 1e6,
            "mean_us": statistics.fmean(walls) * 1e6,
            "max_us": max(walls) * 1e6}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--fits", type=int, default=2000)
    args = ap.parse_args()
    sys.path.insert(0, args.root)
    from sml_tpu import obs
    from sml_tpu.conf import GLOBAL_CONF
    from sml_tpu.ml import Pipeline
    from sml_tpu.utils.profiler import PROFILER, now

    class Frame:
        _parts = [range(1 << 20)]

    estimator, frame = Pipeline(stages=[]), Frame()

    def fit():
        with obs.autolog_fit(estimator, frame):
            for name in CHILDREN:
                with PROFILER.span(name):
                    pass

    def read(path):
        def once():
            with open(path) as f:
                f.read()
        return once

    GLOBAL_CONF.set("sml.obs.enabled", True)
    obs.reset()
    out = {"root": args.root, "fit_on": median_us(fit, args.fits, now),
           "records": len(getattr(obs, "fit_records", list)())}
    GLOBAL_CONF.set("sml.obs.enabled", False)
    out["fit_off"] = median_us(fit, args.fits, now)
    out["process_time"] = median_us(time.process_time, args.fits, now)
    for path in ("/proc/self/statm", "/proc/meminfo"):
        if os.path.exists(path):
            out[path] = median_us(read(path), args.fits, now)
    print("FIT RECORD MICRO " + json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
