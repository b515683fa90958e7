#!/usr/bin/env python3
"""The pad step of the staging layer alone, on the arrays of the logistic
cells (`mle03_logreg.fit_logistic`): the parent's `np.pad` into a fresh
allocation against `_staging._padded_rows` into the pad pool's warm buffer,
then the same arrays through `stage_sharded` on the device, read back and
compared with `np.pad` to the byte. Needs the chip for its second half
(`--host-only` stops before it); not part of a run.

    python3 scripts/stage_pad_micro.py [--rows 6400000] [--reps 5]

Prints one JSON line: milliseconds a pad (median of `--reps`, a new row
count each so that no tail is the last one's), GB/s of the padded bytes,
and what the device held.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from sml_tpu.utils.profiler import now  # noqa: E402


def _arrays(rows: int, seed: int):
    """The compact block of 17 numeric slots and 5 category codes, rows
    last, and the labels: what a logistic fit of the cell stages."""
    rng = np.random.default_rng(seed)
    num = rng.random((17, rows), dtype=np.float32)
    codes = rng.integers(0, 40, size=(5, rows), dtype=np.int32)
    y = (rng.random(rows, dtype=np.float32) < 0.3).astype(np.float32)
    return {"num": (num, -1), "codes": (codes, -1), "y": (y, 0)}


def _np_pad(a, rows, axis):
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, (-a.shape[axis]) % rows)
    return np.pad(a, widths)


def _timed(fn):
    t0 = now()
    out = fn()
    return out, (now() - t0) * 1e3


def host_half(rows: int, reps: int) -> dict:
    from sml_tpu.ml import _staging
    from sml_tpu.parallel import mesh as meshlib
    bucket = meshlib.bucket_rows(rows, 1)
    out = {"rows": rows, "padded_rows": bucket}
    aliases = _staging._aliases_host
    _staging._aliases_host = lambda mesh: False     # no array is placed here
    try:
        for name, (a, axis) in _arrays(rows, 44).items():
            out[name] = _fresh_against_warm(_staging, a, axis, bucket, reps)
    finally:
        _staging._aliases_host = aliases
        _staging._PAD_POOL.clear()
    return out


def _fresh_against_warm(_staging, a, axis, bucket, reps) -> dict:
    fresh, warm, same = [], [], True
    for rep in range(reps + 1):
        # a shorter split each time
        part = np.ascontiguousarray(a[..., :a.shape[-1] - 1000 * rep])
        want, ms = _timed(lambda: _np_pad(part, bucket, axis))
        fresh.append(ms)
        (got, buf), ms = _timed(
            lambda: _staging._padded_rows(part, bucket, None, axis))
        warm.append(ms)                 # the first fills the pool: dropped
        same = same and got.tobytes() == want.tobytes()
        _staging._PAD_POOL.give_back(buf)
        del want, got
    gb = a.dtype.itemsize * bucket * (a.size // a.shape[axis]) / 1e9
    fresh_ms, warm_ms = (statistics.median(ms[1:]) for ms in (fresh, warm))
    return {"padded_GB": gb, "equal_to_np_pad": same,
            "np_pad_fresh_ms": fresh_ms, "pool_first_ms": warm[0],
            "pool_warm_ms": warm_ms, "fresh_GB_s": gb / fresh_ms * 1e3,
            "warm_GB_s": gb / warm_ms * 1e3}


def device_half(rows: int, reps: int) -> dict:
    """`stage_sharded` of `reps` splits in turn, pool as the platform says:
    every staged array read back whole and compared, AFTER the later splits
    were padded into the same buffers."""
    import jax
    from sml_tpu.ml import _staging
    from sml_tpu.parallel import mesh as meshlib
    from sml_tpu.utils.profiler import PROFILER
    from sml_tpu.conf import GLOBAL_CONF
    GLOBAL_CONF.set("sml.profiler.enabled", True)
    mesh = meshlib.get_mesh()
    out = {"platform": jax.devices()[0].platform,
           "aliases_host": _staging._aliases_host(mesh)}
    held, stage_ms = [], []
    for rep in range(reps):
        parts = {k: np.ascontiguousarray(a[..., :rows - 1000 * rep])
                 for k, (a, _) in _arrays(rows, 45 + rep).items()}
        staged, ms = _timed(lambda: _staging.stage_sharded(
            _staging.RowsLast(parts["num"]), _staging.RowsLast(parts["codes"]),
            parts["y"]))
        stage_ms.append(ms)
        held.append((parts, staged))
    wrong = 0
    for parts, staged in held:
        bucket = staged[2].shape[0]
        for dev, (a, axis) in zip(staged, ((parts["num"], -1),
                                           (parts["codes"], -1),
                                           (parts["y"], 0))):
            wrong += np.asarray(dev).tobytes() != \
                _np_pad(a, bucket, axis).tobytes()
        n = parts["y"].shape[0]
        wrong += np.asarray(staged[3]).tobytes() != \
            meshlib.row_mask(bucket, n).tobytes()
    counters = PROFILER.counters()
    out.update(stagings=reps, arrays_wrong=int(wrong), stage_ms=stage_ms,
               pad_warm=counters.get("staging.pad_warm", 0.0),
               pad_fresh=counters.get("staging.pad_fresh", 0.0),
               pool=_staging._PAD_POOL.stats())
    out["back_to_back"] = _back_to_back(_staging, mesh, 16 * rows)
    return out


def _back_to_back(_staging, mesh, n: int) -> dict:
    """Three arrays of one shape (0.4 GB at the default size) padded and put
    with nothing between them, through a pool with room for ONE buffer: the
    second and third pad have to wait the transfer before them out. Then
    what the wait guards against: the same warm buffer written over right
    after a put, with no wait."""
    import jax
    from sml_tpu.parallel import mesh as meshlib
    bucket = meshlib.bucket_rows(n, 1)
    sharding = meshlib.data_sharding(mesh, 1)
    arrays = [np.full(n - 1000 * i, float(i + 1), np.float32)
              for i in range(3)]
    shared, _staging._PAD_POOL = _staging._PAD_POOL, \
        _staging._PadPool(4 * bucket)
    try:
        placed, pad_ms = [], []
        for a in arrays:
            (padded, buf), ms = _timed(
                lambda: _staging._padded_rows(a, bucket, mesh))
            pad_ms.append(ms)
            placed.append(_staging._put(padded, sharding, buf))
        wrong = sum(np.asarray(dev).tobytes() != _np_pad(a, bucket, 0).tobytes()
                    for a, dev in zip(arrays, placed))
        padded, buf = _staging._padded_rows(arrays[0], bucket, mesh)
        late = jax.device_put(padded, sharding)
        padded[:] = -1.0                    # no wait: the fault, on purpose
        torn = int(np.count_nonzero(np.asarray(late) == -1.0))
    finally:
        _staging._PAD_POOL = shared
    return {"GB": 4 * bucket / 1e9, "pad_ms": pad_ms,
            "arrays_wrong": int(wrong),
            "entries_torn_with_no_wait": torn}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=6_400_000)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--host-only", action="store_true")
    args = ap.parse_args()
    report = {"host": host_half(args.rows, args.reps)}
    if not args.host_only:
        report["device"] = device_half(args.rows, args.reps)
    print("PAD MICRO " + json.dumps(report), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
