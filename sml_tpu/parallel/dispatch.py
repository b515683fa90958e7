"""Device dispatch: host mesh vs. accelerator mesh.

The reference runs every job on the cluster because Spark's scheduler is
where its parallelism lives. Here every distributed program is a
`shard_map` over an abstract mesh; the SAME program runs on a 1-device
host-CPU mesh with zero semantic change (collectives degenerate to
identity), so a program can be routed to either. Spark has the same
concept — `spark.sql.adaptive` and broadcast-join thresholds pick an
execution strategy from measured sizes.

Policy (``sml.dispatch.mode`` = auto|device|host): an accelerator that is
ATTACHED TO THIS HOST — every device of the default backend belongs to
this process (`_locally_attached`) — takes every program in `auto`: its
dispatch round trip is far below any program worth routing, and deciding
from a property of the devices instead of a timed round trip means a busy
host cannot flip the route. Only for a device this process does not own
does `auto` price the two routes from a work estimate (`WorkHint`):

    t_device = rt_fixed + uncached_bytes/h2d_bw + flops/dev_rate + out/d2h_bw
    t_host   = flops/host_rate[kind]

with `rt_fixed`, `h2d_bw`, `d2h_bw` MEASURED once per process against the
device (`CALIBRATION`; `chip_smoke.py` reports them for the machine it
runs on). Tests that pin a mesh via `use_mesh`/`use_mesh_local` are
unaffected when the process backend is CPU (the active mesh IS the host).
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np

from ..conf import GLOBAL_CONF, _register, _to_bool
from ..obs import _audit as _obs_audit
from ..obs._recorder import RECORDER as _OBS
from ..utils.profiler import now as _now
from . import mesh as meshlib

_register("sml.dispatch.mode", "auto", str,
          "auto: route programs host/device by measured latency; "
          "device: always the accelerator mesh; host: always the host mesh")
_register("sml.dispatch.autoPromote", True, _to_bool,
          "In auto mode, asynchronously stage a dataset into HBM when a "
          "device-resident copy would beat the host, so repeated fits "
          "(CV folds, tuning trials) converge onto the chip")


# --------------------------------------------------- persistent compile cache
# Two layers keep repeated fits (and the bench warmup) from recompiling:
# 1. shape-bucketed padding — `mesh.bucket_rows` (re-exported below) rounds
#    row counts onto a coarse grid (≤12.5% padding) so near-size datasets
#    (CV folds, randomSplit variants, tuning re-fits) hit the SAME compiled
#    program signature;
# 2. XLA's persistent compilation cache — a fresh process replays earlier
#    compiles from disk instead of re-running XLA.
bucket_rows = meshlib.bucket_rows


#: cache EVERY program, whatever it took to compile or weighs: with a
#: compile-time threshold, a program that compiles near it is written by
#: one run and not by the next, so a second identical run still adds
#: entries (seen on the v5e: `jit__threefry_seed` at 0.2 s)
_CACHE_POLICY = (("jax_persistent_cache_min_compile_time_secs", 0.0),
                 ("jax_persistent_cache_min_entry_size_bytes", -1))


def ensure_compile_cache() -> str:
    """Place XLA's persistent compilation cache and return its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, jax itself reads it: the
    cache is there and this code sets no other directory (a cache placed
    from outside is found again by the next process only if nothing
    moves it — the path is part of the cache key). Otherwise the
    directory is `sml.compile.cacheDir` when that is set, else the fixed
    `<checkout>/.jax_cache` — never a temporary, pid- or time-derived
    path.

    Called at package import, and again whenever `sml.compile.cacheDir`
    is set (a conf on_set hook — jax reads the config per compile, so
    later programs land in the new directory)."""
    import os

    import jax
    # update only on change: the prewarm manifest path resolves through
    # here on every recorded dispatch
    for name, value in _CACHE_POLICY:
        if getattr(jax.config, name) != value:
            jax.config.update(name, value)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    cache = os.path.abspath(
        str(GLOBAL_CONF.get("sml.compile.cacheDir") or "").strip()
        or os.path.join(os.path.dirname(__file__), os.pardir, os.pardir,
                        ".jax_cache"))
    if jax.config.jax_compilation_cache_dir != cache:
        jax.config.update("jax_compilation_cache_dir", cache)
        if _OBS.enabled:
            _OBS.emit("compile", "compile.cache_dir", args={"dir": cache})
    return cache


# setting the knob re-points the cache immediately (without this hook the
# import-time call would latch the default and the conf key would be dead)
GLOBAL_CONF.on_set("sml.compile.cacheDir",
                   lambda: ensure_compile_cache())

# effective host rates (elementwise ops/s) per program family — the
# BOOTSTRAP values only: every hinted host execution feeds its measured
# flops/sec back into OBSERVED_HOST below, so routing converges onto this
# host's real throughput instead of a constant. Bootstraps stay
# conservative (over-crediting the host only steers SMALL jobs hostward,
# where the fixed device latency dominates any estimation error).
_HOST_RATES = {
    # measured on THIS host's 1-device mesh (XLA:CPU): Gram at 2M rows ran
    # 3.8e9 flops in ~0.7s; the ensemble one-hot program 4.6e9 in ~3.8s
    "blas": 6e9,       # dense matmul-shaped work (Gram, forward passes)
    "scatter": 1.2e9,  # histogram/one-hot accumulation
    "scan": 1.0e9,     # long sequential scans (boosting rounds, ARIMA)
    # per-tree numpy traversal loop (predict): measured ~2e8 ops/s at 800k
    # rows — 6x below the histogram kernels; pricing predicts with the
    # "scatter" rate routed every forest predict hostward and cost the r4
    # bench 13.6s of host traversal on data already resident in HBM
    "traverse": 2.5e8,
    # argsort + reduceat segment reductions (host ALS normal equations):
    # measured ~8e7 effective ops/s against the nnz·rank² estimate — the
    # "blas" rate over-credited the host ~75x and silently routed whole
    # MovieLens-scale ALS fits onto a 14s host path
    "segment": 8e7,
}
_DEVICE_RATE = 2e12  # sustained non-MXU-peak device throughput estimate


class _ObservedRates:
    """MEASURED host throughput per WorkHint kind.

    The router's host-side cost model can only be as good as its rates;
    hard-coded constants were wrong by 6x for tree traversal (r4). Every
    hinted host execution calls `observe(kind, flops, seconds)` with its
    wall time; `host_time` prefers the observed estimate.

    The estimate is THROUGHPUT-WEIGHTED over a window of recent large
    observations — sum(flops)/sum(seconds) — not an EWMA or a max:

    - an EWMA lets one compile-inflated first call flip marginal work onto
      the device, where no further host samples ever correct it;
    - a max-of-window lets one warm SMALL call (whose per-op overhead
      profile looks nothing like an 800k-row traversal) over-credit the
      host for big jobs — r4 saw exactly this flapping, with 266k-row CV
      evals bouncing to a host path that cost ~1.4s each;
    - throughput weighting makes big calls dominate the estimate in
      proportion to the work they did, which is what routing big calls
      needs, while the flops floor keeps tiny-call noise out entirely.

    Observations AGE OUT (`_MAX_AGE_S`): routing by observed rates is
    otherwise a one-way ratchet — once one contended/throttled window
    flips a kind's routing to the device, no further host samples are
    ever taken for that kind and the stale slow rate persists until
    process restart. Stale entries fall out of the window, and an empty
    window falls back to the bootstrap constant, so the host gets
    re-probed after recovery."""

    _WINDOW = 8
    _MIN_FLOPS = 1e8  # below this, per-call overhead ≈ the signal
    _MAX_AGE_S = 120.0  # contention windows are transient at this scale

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._recent: dict = {}  # kind -> deque of (flops, seconds, t)

    def observe(self, kind: str, flops: float, seconds: float) -> None:
        # sub-ms timings are dominated by timer noise / python overhead
        if seconds < 1e-3 or flops < self._MIN_FLOPS:
            return
        from collections import deque
        with self._lock:
            dq = self._recent.get(kind)
            if dq is None:
                dq = self._recent[kind] = deque(maxlen=self._WINDOW)
            dq.append((flops, seconds, time.monotonic()))

    def rate(self, kind: str):
        cutoff = time.monotonic() - self._MAX_AGE_S
        with self._lock:
            dq = self._recent.get(kind)
            if dq:
                while dq and dq[0][2] < cutoff:
                    dq.popleft()
            if not dq:
                return None
            return sum(f for f, _, _ in dq) / sum(s for _, s, _ in dq)


OBSERVED_HOST = _ObservedRates()


class QueuePressure:
    """Rows currently queued for (or in flight on) the device by online
    serving — the dispatcher's backpressure signal. The serving
    micro-batcher feeds it (`add` at admission, `sub` when a batch
    completes or sheds); admission control reads `rows()` to decide when
    the device lane is saturated and traffic should degrade to the host
    route instead of queueing behind it. Deliberately NOT a term in
    `device_time` — fits price a single dispatch, while serving pressure
    is a property of the standing queue, and mixing the two would let a
    transient burst reroute long training jobs.

    `parent` chains per-replica queues into the process-wide signal:
    a fleet replica's own `QueuePressure(parent=DEVICE_QUEUE)` gives the
    router per-replica attribution (this replica's standing rows, not
    the fleet total) while every add/sub still reaches the one
    dispatcher signal — the device lane is shared no matter how many
    batchers feed it."""

    def __init__(self, parent: "Optional[QueuePressure]" = None) -> None:
        self._lock = threading.Lock()
        self._rows = 0
        self._parent = parent

    def add(self, rows: int) -> None:
        with self._lock:
            self._rows += int(rows)
        parent = self._parent
        if parent is not None:
            parent.add(rows)

    def sub(self, rows: int) -> None:
        with self._lock:
            self._rows = max(0, self._rows - int(rows))
        parent = self._parent
        if parent is not None:
            parent.sub(rows)

    def rows(self) -> int:
        with self._lock:
            return self._rows


#: process-wide device-queue pressure (one device lane per process)
DEVICE_QUEUE = QueuePressure()


import contextlib as _contextlib


@_contextlib.contextmanager
def observe_host(kind: str, flops: float):
    """Time a host-route execution and feed the measured rate back into
    the router — the ONE definition of what gets observed, shared by every
    host predict path."""
    t0 = _now()
    try:
        yield
    finally:
        OBSERVED_HOST.observe(kind, flops, _now() - t0)


@dataclass(frozen=True)
class WorkHint:
    """Caller's estimate of one program invocation's cost."""
    flops: float                 # elementwise-op / flop count on the data path
    kind: str = "blas"           # which _HOST_RATES family
    out_bytes: float = 256.0     # device→host result size
    in_bytes: Optional[float] = None  # H2D bytes if NOT already staged


class _Calibration:
    """Measured host↔device link constants (one dispatch round trip and
    the two transfer bandwidths), taken lazily once per process against
    the first device of the default backend — a probe of the link, not a
    placement: programs span the whole mesh."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._done = False
        self.rt_fixed = 0.0       # s per dispatch+readback of a tiny program
        self.h2d_bw = float("inf")  # bytes/s host→device
        self.d2h_bw = float("inf")  # bytes/s device→host

    def ensure(self) -> "_Calibration":
        if self._done:
            return self
        with self._lock:
            if self._done:
                return self
            import jax
            import jax.numpy as jnp
            dev = jax.devices()[0]
            if dev.platform == "cpu":
                self._done = True
                return self
            f = jax.jit(lambda x: (x @ x).sum())
            x = jax.device_put(np.eye(8, dtype=np.float32), dev)
            jax.device_get(f(x))  # compile outside the timing
            trips = []
            for _ in range(3):
                t0 = _now()
                jax.device_get(f(x))
                trips.append(_now() - t0)
            self.rt_fixed = max(min(trips), 1e-4)
            blk = np.ones((4 * 1024 * 1024,), np.float32)  # 16 MB
            h2d = []
            for _ in range(2):  # best-of-2: a transfer can be noisy
                t0 = _now()
                d = jax.device_put(blk, dev)
                # graftlint: disable=host-sync-in-hot-path -- calibration probe: the synchronous H2D wait IS the bandwidth measurement
                d.block_until_ready()
                h2d.append(_now() - t0)
                del d
            d = jax.device_put(blk, dev)
            # graftlint: disable=host-sync-in-hot-path -- calibration probe: drain the transfer before timing the D2H leg
            d.block_until_ready()
            self.h2d_bw = max(blk.nbytes / min(h2d), 1e6)
            t0 = _now()
            # graftlint: disable=host-sync-in-hot-path -- calibration probe: the synchronous D2H pull IS the bandwidth measurement
            np.asarray(d)
            self.d2h_bw = max(blk.nbytes / (_now() - t0), 1e6)
            self._done = True
            return self


CALIBRATION = _Calibration()

_host_mesh_lock = threading.Lock()
_host_mesh: Optional[object] = None


def host_mesh():
    """A cached 1-device host-CPU mesh. The same shard_map programs run on
    it unchanged (psum over one device is identity), so routing here changes
    latency, never results. Needs jax's CPU backend next to the
    accelerator: a process started with `JAX_PLATFORMS` naming only the
    accelerator has no host route, and says so here."""
    global _host_mesh
    with _host_mesh_lock:
        if _host_mesh is None:
            import os

            import jax
            from jax.sharding import Mesh
            try:
                cpus = jax.devices("cpu")
            except RuntimeError as e:
                raise RuntimeError(
                    "the host route (sml.dispatch.mode=host, serving "
                    "overflow with sml.serve.hostFallback) needs jax's CPU "
                    "backend, which this process does not have "
                    f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}): "
                    "add ',cpu' to JAX_PLATFORMS, or turn the host route "
                    "off (sml.dispatch.mode=device, "
                    "sml.serve.hostFallback=false)") from e
            _host_mesh = Mesh(np.asarray(cpus[:1]), (meshlib.DATA_AXIS,))
        return _host_mesh


def is_host_mesh(mesh) -> bool:
    """True only for THE host-dispatch mesh. Deliberately identity-based:
    a platform check would also match the virtual CPU test meshes, which
    are *device* meshes from the dispatcher's point of view."""
    return _host_mesh is not None and mesh is _host_mesh


def _default_backend() -> str:
    import jax
    return jax.default_backend()


def _locally_attached() -> bool:
    """Whether the accelerator is attached to this host: every device of
    the default backend belongs to this process. Decided from the devices
    themselves, not from a timed round trip, so a loaded host or a first
    dispatch that happens to be slow cannot change where programs run."""
    import jax
    me = jax.process_index()
    return all(d.process_index == me for d in jax.devices())


def device_time(hint: WorkHint, cal: _Calibration) -> float:
    t = cal.rt_fixed + hint.flops / _DEVICE_RATE + hint.out_bytes / cal.d2h_bw
    if hint.in_bytes:
        t += hint.in_bytes / cal.h2d_bw
    return t


def host_time(hint: WorkHint) -> float:
    rate = OBSERVED_HOST.rate(hint.kind) \
        or _HOST_RATES.get(hint.kind, _HOST_RATES["blas"])
    return hint.flops / rate


def _preroute(hint: Optional[WorkHint]) -> Tuple[Optional[str], str]:
    """(route, reason) when the decision doesn't depend on work size or
    staging state, (None, "") when a real estimate (decide) is needed."""
    if _default_backend() == "cpu":
        return "device", "cpu-backend"  # the active mesh IS the host
    mode = str(GLOBAL_CONF.get("sml.dispatch.mode"))
    if mode == "host":  # forced host must also catch unhinted programs
        return "host", "forced-mode"
    if mode == "device":
        return "device", "forced-mode"
    if hint is None:
        return "device", "no-hint"
    if _locally_attached():
        return "device", "local-chip"
    return None, ""


def preroute(hint: Optional[WorkHint]) -> Optional[str]:
    """The decision when it is forced: "device"/"host" for forced modes,
    CPU backends and locally attached accelerators, None when a real
    estimate (decide) is needed. Lets callers skip the staging-cache
    probe (which hashes array windows) whenever the answer is forced."""
    return _preroute(hint)[0]


def audit_preroute(hint: Optional[WorkHint], route: str) -> None:
    """Record a preroute short-circuit in the dispatch audit (no-op with
    the flight recorder off, or for unhinted programs — there is nothing
    to price). Shared by decide() and the preroute fast paths in
    _staging._route_mesh / evaluation._stats_route.

    Deliberately does NOT run the link calibration: a forced route was
    never priced, and measuring bandwidths (seconds of probe traffic)
    just to stamp an audit row would make enabling observability change
    engine behavior. If calibration hasn't happened yet, the device
    prediction is the rate-only model and the record is marked
    uncalibrated so the audit's misroute logic won't trust it."""
    if not _OBS.enabled or hint is None:
        return
    _obs_audit.record(hint, route, host_time(hint),
                      device_time(hint, CALIBRATION), forced=True,
                      reason=_preroute(hint)[1],
                      calibrated=CALIBRATION._done)


def audit_decision(hint: Optional[WorkHint], route: str) -> None:
    """Record a priced, unforced decision a caller made from its own
    decide(..., _record=False) probes (see _staging._route_mesh's
    resident-cost fast path) — exactly one audit row per dispatch."""
    if not _OBS.enabled or hint is None:
        return
    cal = CALIBRATION.ensure()
    _obs_audit.record(hint, route, host_time(hint),
                      device_time(hint, cal), forced=False)


def decide(hint: Optional[WorkHint],
           _record: bool = True) -> Tuple[str, bool]:
    """(route, promote): route is "host"|"device"; promote is True when the
    device loses ONLY because of the one-time H2D staging cost — i.e. a
    device-resident copy of this dataset would win, so the caller should
    stage it in the background and let later fits ride the chip.

    `_record=False` suppresses the dispatch-audit row — for callers
    using decide() as an internal pricing PROBE rather than the decision
    itself (the audit must count dispatches, not probes)."""
    pre = preroute(hint)
    if pre is not None:
        if _record:
            audit_preroute(hint, pre)
        return pre, False
    cal = CALIBRATION.ensure()
    t_host = host_time(hint)
    t_device = device_time(hint, cal)
    if t_device <= t_host:
        if _record and _OBS.enabled:
            _obs_audit.record(hint, "device", t_host, t_device,
                              forced=False)
        return "device", False
    # Promote only on a DECISIVE resident-device win: flipping a dataset's
    # route costs a fresh trace/compile of every program it touches, so a
    # marginal (<3x) projected gain is not worth the switch.
    resident = WorkHint(hint.flops, hint.kind, hint.out_bytes, None)
    if _record and _OBS.enabled:
        _obs_audit.record(hint, "host", t_host, t_device, forced=False)
    return "host", 3.0 * device_time(resident, cal) <= t_host


def mesh_for(hint: Optional[WorkHint]):
    """Pick the execution mesh for one program invocation.

    Returns the active mesh (accelerator / placed submesh) or the host
    mesh. On a CPU-backend process this is just `get_mesh()`; with no
    hint it is `get_mesh()` UNLESS sml.dispatch.mode=host, which forces
    the host mesh even for unhinted programs.
    """
    route, _ = decide(hint)
    return meshlib.get_mesh() if route == "device" else host_mesh()


def routed(hint: Optional[WorkHint]):
    """Context manager binding the dispatch decision as the thread's active
    mesh, so every `get_mesh()` in the wrapped fit/predict body (staging,
    program caches) resolves to the chosen mesh."""
    return meshlib.use_mesh_local(mesh_for(hint))
