"""Concurrent program-prewarm manifest — amortize first-dispatch latency.

With the persistent compile cache warm, every program *loads* as a cache
hit, but each distinct executable still pays a first dispatch (python
trace + executable load onto the device), serially, one program at a
time as the caller first reaches it. How large that is on the chip is
not measured (ROADMAP S3).

This module turns that serial sum into an overlapped pool:

- RECORDING (always on): every
  program family dispatched through `ml._staging.cached_data_parallel`,
  the tree program caches (`tree_impl`), or `DeviceScorer` records a
  replayable signature — a family kind, the static build parameters, the
  padded operand shapes/dtypes, and the mesh signature — into
  `prewarm_manifest.json` in the compile-cache directory
  (`dispatch.ensure_compile_cache`).
  Recording is a dict lookup + an occasional atomic file write; it never
  touches the device.

- REPLAY (opt-in, `sml.prewarm.enabled`): `prewarm()` rebuilds every
  manifest program through the SAME per-process caches the real call
  sites hit and first-dispatches it on zero-filled operands of the
  recorded shapes from a `sml.prewarm.workers`-wide thread pool, so the
  per-program payments overlap instead of summing. Entries whose mesh
  signature (data-axis width + platform) doesn't match the live mesh are
  skipped — a manifest written under 8 virtual devices cannot be
  replayed onto 1 chip.

Every replay emits `prewarm.*` counters/events through the flight
recorder, so the overlap is visible in the trace and assertable in
tests. See docs/DESIGN_NOTES.md ("Dispatch economics").
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
from typing import Any, Callable, Dict, List, Optional

from ..conf import GLOBAL_CONF, _register, _to_bool
from ..obs import _context as _trace
from ..obs._recorder import RECORDER as _OBS
from ..obs._watchdog import WATCHDOG as _WATCHDOG
from ..utils.profiler import PROFILER, now as _now

_register("sml.prewarm.enabled", False, _to_bool,
          "Replay the program-prewarm manifest at process start: rebuild "
          "and first-dispatch every recorded program signature from a "
          "background thread pool (sml.prewarm.workers wide) so the "
          "per-program first-dispatch payments "
          "overlap instead of summing. Recording into the manifest is "
          "always on (passive, host-only); this knob gates only the "
          "replay")
_register("sml.prewarm.workers", 4, int,
          "Thread-pool width for manifest replay: how many recorded "
          "programs rebuild + first-dispatch concurrently")

_MANIFEST_VERSION = 1

_lock = threading.Lock()
_state: Dict[str, Any] = {"path": None, "entries": None}
_tls = threading.local()  # replay re-entrancy guard
#: replay guard, keyed per (manifest path, mesh signature) — NOT once
#: per process: N in-process fleet replicas share one warm set of
#: program caches only while they share BOTH the manifest and the live
#: mesh, so replica 2..N skip (counted prewarm.replica_skip) while a
#: re-pointed compile-cache dir or a reshaped mesh warms again
_ran: Dict[Any, bool] = {}

#: kind -> rebuilder(meta) — populated by tree_impl / inference /
#: _staging at import; prewarm() imports them before replaying.
_REBUILDERS: Dict[str, Callable[[dict], None]] = {}

#: family -> factory(meta) -> program fn. For program fns that are
#: FACTORY-made (closures over static params, not importable by name):
#: the factory must be memoized so replay resolves the SAME fn object
#: the live call sites use — program caches key on fn identity.
_FN_FACTORIES: Dict[str, Callable[[dict], Callable]] = {}


def register_rebuilder(kind: str, fn: Callable[[dict], None]) -> None:
    _REBUILDERS[kind] = fn


def register_fn_factory(family: str, fn: Callable[[dict], Callable]) -> None:
    _FN_FACTORIES[family] = fn


def resolve_fn(src: list):
    """The program fn behind a recorded `data_parallel` signature:
    ["import", module, qualname] resolves by import; ["factory", family,
    meta] through the registered memoized factory."""
    if src[0] == "import":
        import importlib
        return getattr(importlib.import_module(src[1]), src[2])
    return _FN_FACTORIES[src[1]](src[2])


def fn_src(fn) -> Optional[list]:
    """A recordable source for a program fn, or None (unrecordable —
    e.g. an untagged local closure). Tagged factory fns (`fn._prewarm =
    (family, meta)`) win; otherwise only a module-level name that
    round-trips back to the same object qualifies."""
    tag = getattr(fn, "_prewarm", None)
    if tag is not None:
        return ["factory", str(tag[0]), dict(tag[1])]
    mod = getattr(fn, "__module__", None)
    qual = getattr(fn, "__qualname__", "")
    if mod and qual and "." not in qual:
        import sys
        m = sys.modules.get(mod)
        if m is not None and getattr(m, qual, None) is fn:
            return ["import", mod, qual]
    return None


def arg_specs(*arrays) -> List[list]:
    """[[shape, dtype], ...] for device/host operands — the shape half of
    a program's replayable signature."""
    return [[list(a.shape), str(a.dtype)] for a in arrays]


def manifest_path() -> str:
    """The manifest lives next to the persistent XLA compile-cache
    artifacts (they describe the same executables)."""
    from . import dispatch
    return os.path.join(dispatch.ensure_compile_cache(),
                        "prewarm_manifest.json")


def _guard_key() -> tuple:
    """The replay-guard identity: what must match for a second replica's
    warm caches to genuinely be this replica's warm caches."""
    return (manifest_path(), tuple(_mesh_sig()))


def _mesh_sig() -> list:
    from . import mesh as meshlib
    m = meshlib.get_mesh()
    n = meshlib.data_width(m) if meshlib.is_hierarchical(m) \
        else int(m.shape.get(meshlib.DATA_AXIS, 1))
    plat = str(list(m.devices.flat)[0].platform)
    return [n, plat]


def _load(path: str) -> Dict[str, dict]:
    with _lock:
        if _state["path"] == path and _state["entries"] is not None:
            return _state["entries"]
    entries: Dict[str, dict] = {}
    try:
        with open(path) as f:
            doc = json.load(f)
        if doc.get("version") == _MANIFEST_VERSION:
            entries = dict(doc.get("entries", {}))
    except (OSError, ValueError):
        entries = {}
    with _lock:
        _state["path"] = path
        _state["entries"] = entries
    return entries


def _flush(path: str) -> None:
    """Atomic write (tmp + rename) so a concurrently-starting process
    never reads a torn manifest."""
    with _lock:
        doc = {"version": _MANIFEST_VERSION,
               "entries": dict(_state["entries"] or {})}
    tmp = path + ".tmp"
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass  # recording is best-effort; never fail a fit over it


def record(kind: str, meta: dict) -> None:
    """Record one replayable program signature (idempotent per distinct
    (kind, meta, mesh) — repeated dispatches of the same program cost one
    canonical-JSON hash and a set lookup)."""
    if getattr(_tls, "replaying", False):
        return  # replays must not re-record (or flush) their own entries
    path = manifest_path()
    entry = {"kind": kind, "meta": meta, "mesh": _mesh_sig()}
    try:
        blob = json.dumps(entry, sort_keys=True, default=str)
    except (TypeError, ValueError):
        return
    key = hashlib.sha1(blob.encode()).hexdigest()[:20]
    entries = _load(path)
    with _lock:
        if key in entries:
            return
        entries[key] = entry
    PROFILER.count("prewarm.recorded")
    _flush(path)


def _replay_one(entry: dict, stats: dict, stats_lock) -> None:
    _tls.replaying = True
    t0 = _now()
    ok = True
    # each replay is its own causal trace (obs/_context.py): the rebuild
    # + first-dispatch spans it triggers carry the replay's trace id,
    # and a replay wedged behind a lost device registers as an in-flight
    # watchdog ticket instead of silently pinning a pool worker
    ctx = _trace.new_trace()
    try:
        with _trace.activate(ctx), \
                _WATCHDOG.watch("prewarm", f"prewarm.{entry['kind']}",
                                trace=ctx):
            _REBUILDERS[entry["kind"]](entry["meta"])
    except Exception:
        ok = False
    finally:
        _tls.replaying = False
    dt = _now() - t0
    with stats_lock:
        stats["replayed" if ok else "failed"] += 1
        stats["serial_s"] += dt
    if ok:
        PROFILER.count("prewarm.replayed")
    else:
        PROFILER.count("prewarm.failed")
    if _OBS.enabled:
        args = {"kind": entry["kind"], "ok": ok, "seconds": round(dt, 4)}
        if ctx is not None:
            args["trace"] = ctx.trace_id
        _OBS.emit("prewarm", "prewarm.replay", args=args)


def prewarm(workers: Optional[int] = None) -> dict:
    """Rebuild + first-dispatch every matching manifest program from a
    thread pool. Returns {programs, replayed, failed, skipped, wall_s,
    serial_s}: serial_s is what the same payments would have cost one at
    a time — serial_s / wall_s is the overlap the pool bought."""
    # rebuilders live in the modules that own the program caches
    from ..ml import _staging, inference, tree_impl  # noqa: F401
    key = _guard_key()
    with _lock:
        _ran[key] = True
    entries = _load(manifest_path())
    sig = _mesh_sig()
    todo = [e for e in entries.values()
            if e.get("mesh") == sig and e.get("kind") in _REBUILDERS]
    stats = {"programs": len(todo), "replayed": 0, "failed": 0,
             "skipped": len(entries) - len(todo),
             "wall_s": 0.0, "serial_s": 0.0}
    if not todo:
        return stats
    if workers is None:
        workers = GLOBAL_CONF.getInt("sml.prewarm.workers")
    workers = max(1, int(workers))
    PROFILER.count("prewarm.programs", float(len(todo)))
    if _OBS.enabled:
        _OBS.emit("prewarm", "prewarm.start",
                  args={"programs": len(todo), "workers": workers})
    t0 = _now()
    stats_lock = threading.Lock()
    from concurrent.futures import ThreadPoolExecutor
    with ThreadPoolExecutor(max_workers=workers,
                            thread_name_prefix="sml-prewarm") as pool:
        for f in [pool.submit(_replay_one, e, stats, stats_lock)
                  for e in todo]:
            f.result()
    stats["wall_s"] = _now() - t0
    if _OBS.enabled:
        _OBS.emit("prewarm", "prewarm.done", args=dict(stats))
    return stats


def speculative_prewarm(fn: Callable, shapes: List[tuple],
                        workers: Optional[int] = None) -> dict:
    """Shape-bucket prewarm keyed off a DECLARED width mix instead of a
    recorded manifest: first-dispatch `fn` on zero-filled operands of
    each distinct shape from a thread pool, so a load trace's fat-tail
    widths (docs/LOADGEN.md) hit warm per-bucket programs instead of
    paying trace+dispatch inside the measured phases. `fn` takes one
    array; shapes are (rows, features) tuples, deduplicated. Failures
    are counted, never raised — speculation must not wedge a start-up.

    Returns {programs, warmed, failed, wall_s, serial_s} like
    `prewarm()`."""
    import numpy as np
    todo = sorted({tuple(int(d) for d in s) for s in shapes})
    stats = {"programs": len(todo), "warmed": 0, "failed": 0,
             "wall_s": 0.0, "serial_s": 0.0}
    if not todo:
        return stats
    if workers is None:
        workers = GLOBAL_CONF.getInt("sml.prewarm.workers")
    workers = max(1, int(workers))
    PROFILER.count("prewarm.speculative", float(len(todo)))
    stats_lock = threading.Lock()

    def _warm_one(shape: tuple) -> None:
        t0 = _now()
        ok = True
        ctx = _trace.new_trace()
        try:
            with _trace.activate(ctx), \
                    _WATCHDOG.watch("prewarm", "prewarm.speculative",
                                    trace=ctx):
                fn(np.zeros(shape, dtype=np.float32))
        except Exception:
            ok = False
        dt = _now() - t0
        with stats_lock:
            stats["warmed" if ok else "failed"] += 1
            stats["serial_s"] += dt
        if not ok:
            PROFILER.count("prewarm.failed")
        if _OBS.enabled:
            args = {"shape": list(shape), "ok": ok,
                    "seconds": round(dt, 4)}
            if ctx is not None:
                args["trace"] = ctx.trace_id
            _OBS.emit("prewarm", "prewarm.speculative", args=args)

    t0 = _now()
    from concurrent.futures import ThreadPoolExecutor
    with ThreadPoolExecutor(max_workers=workers,
                            thread_name_prefix="sml-spec-prewarm") as pool:
        for f in [pool.submit(_warm_one, s) for s in todo]:
            f.result()
    stats["wall_s"] = _now() - t0
    if _OBS.enabled:
        _OBS.emit("prewarm", "prewarm.speculative_done", args=dict(stats))
    return stats


def maybe_prewarm(block: bool = False) -> Optional[object]:
    """The opt-in replica-start hook (bench warmup, serving endpoint /
    fleet replica load): replay the manifest once per (manifest, mesh)
    when `sml.prewarm.enabled` is set — in a background thread by
    default, so model loads overlap the warmup instead of waiting on it.
    A second in-process replica under the SAME manifest and mesh shares
    the first replica's warm program caches, so it skips (counted
    `prewarm.replica_skip`); a replica starting after the compile-cache
    dir was re-pointed or the mesh reshaped warms its genuinely cold
    world instead of inheriting a stale guard."""
    if not GLOBAL_CONF.getBool("sml.prewarm.enabled"):
        return None
    key = _guard_key()
    with _lock:
        # claim BEFORE spawning: two replicas constructed back-to-back
        # must not both launch a replay (the thread sets nothing until it
        # is scheduled — check-then-act on the thread's own flag races)
        if _ran.get(key):
            PROFILER.count("prewarm.replica_skip")
            return None
        _ran[key] = True
    if block:
        return prewarm()
    t = threading.Thread(target=prewarm, daemon=True, name="sml-prewarm")
    t.start()
    return t
