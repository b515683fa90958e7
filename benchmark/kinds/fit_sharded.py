"""Traffic kind `fit_sharded`: kind `fit` (its set-up, its window and its
report, untouched) on a deployment whose table is sharded over the chips of
one host, with the layout the configuration states held as part of
`correct`.

The configuration's estimator names its data shards (`num_workers`) and its
`correct` the devices they must land on (`shards`). The program counts, at
every tree fit, the devices that hold a shard of the staged bin matrix
(`fit.shards`) and the rows, padding included, on the fullest of them
(`fit.shard_rows_max`). Over the window's fits the first must equal `shards`
exactly, and the second be at most `shard_rows_max_ratio` x rows / shards +
shards: a table quietly left on one chip, or replicated, is another
deployment. The references (`kinds/fit.py`'s `check`) recompute from the raw
rows of the whole table and know nothing of shards: a shard whose rows did
not reach the histograms fails `fit.cover_gap.max`. At this deployment's
6.4 M training rows they descend column-wise (`reference/columnwise.py`:
the same node numbers, a fifth of the time), so that a run stays inside
its time limit.
"""

from __future__ import annotations

import os
import time
from typing import Dict, List

from benchmark.harness import checks, runner, spec
from benchmark.reference import columnwise

_fit = runner.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "fit.py"),
    "bench_kind_fit")
report = _fit.report
COUNTERS = ("fit.shards", "fit.shard_rows_max")


def setup(ctx) -> Dict:
    """`fit.setup`, after the program has said which devices the
    configuration's `num_workers` names. A program that cannot say runs
    another deployment than this one: refused at once (exit code 2),
    before the table is made."""
    from sml_tpu.parallel import mesh
    workers = ctx.config["pipeline"][-1]["params"]["num_workers"]
    if not hasattr(mesh, "worker_mesh"):
        raise spec.SpecError(
            f"cell {ctx.cell}: this program does not turn num_workers="
            f"{workers} into a layout (no sml_tpu.parallel.mesh.worker_mesh)")
    devices = [d.id for d in mesh.worker_mesh(workers).devices.flat]
    ctx.log(f"set-up: num_workers={workers} names devices {devices}")
    return _fit.setup(ctx)


def window(ctx, state) -> Dict:
    before = ctx.program.counters()
    result = _fit.window(ctx, state)
    after = ctx.program.counters()
    result["layout"] = {name: after.get(name, 0.0) - before.get(name, 0.0)
                        for name in COUNTERS}
    return result


def check(ctx, state, result) -> List[checks.Check]:
    limits, fits = ctx.config["correct"], len(result["fits"])
    shards = int(limits["shards"])
    per_fit = {name: total / fits for name, total in result["layout"].items()}
    rows = sum(result["rows"]) / fits
    t0 = time.perf_counter()
    with columnwise.descents():
        verdicts = _fit.check(ctx, state, result)
    ctx.log(f"the references took {time.perf_counter() - t0:.1f}s in all")
    return verdicts + [
        checks.exactly("fit.shards", per_fit["fit.shards"], shards,
                       f"devices holding a shard of the bin matrix, a fit; "
                       f"{fits} fits"),
        checks.at_most("fit.shard_rows_max", per_fit["fit.shard_rows_max"],
                       limits["shard_rows_max_ratio"] * rows / shards + shards,
                       f"rows on the fullest, a fit; {rows:.0f} rows a fit")]
