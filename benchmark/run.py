#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's configuration and traffic (named in BENCHMARK.json), sets
the system up, warms every shape, measures for --seconds, checks what the
timed path produced against the plain references, and prints the result as
one JSON object on the last line of stdout. Runs only on a TPU.
"""

import os
import sys
import time

T_PROCESS_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark.harness import runner
    return runner.main(ROOT, args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=T_PROCESS_START)


if __name__ == "__main__":
    sys.exit(main())
