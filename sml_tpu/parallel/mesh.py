"""Device-mesh runtime: the substrate every distributed op rides on.

The reference scales by Spark row-partitions over executors (SURVEY §2.2 P1);
here rows shard over a `jax.sharding.Mesh` of TPU chips and every aggregation
becomes an XLA collective over ICI (SURVEY §2.4). This module owns mesh
construction (real chips or a virtual host-CPU mesh for tests), default axis
naming, and row-sharded staging of host arrays into HBM.
"""

from __future__ import annotations

import contextlib
import math
import threading
from typing import Iterator, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"    # row / batch parallelism (Spark partitions → chips)
MODEL_AXIS = "model"  # feature/block parallelism (Gram blocks, ALS factors)
TRIAL_AXIS = "trial"  # fused (grid point × fold) trial parallelism
DCN_AXIS = "dcn"      # inter-host hop of a hierarchical (host-grouped) mesh
ICI_AXIS = "ici"      # intra-host hop of a hierarchical (host-grouped) mesh


def shard_map_compat(f, *, mesh, in_specs, out_specs):
    """`jax.shard_map` with replication checking OFF — the one spelling
    every program wrapper uses."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


_lock = threading.RLock()
_active_mesh: Optional[Mesh] = None
_tls = threading.local()  # per-thread mesh override (trial placement)


def build_mesh(
    n_devices: Optional[int] = None,
    axis_names: Sequence[str] = (DATA_AXIS,),
    shape: Optional[Sequence[int]] = None,
) -> Mesh:
    """Build a mesh over available devices.

    1-D ``(data,)`` by default. For 2-D meshes pass ``axis_names=("data",
    "model")`` and optionally an explicit ``shape``; otherwise all devices go
    on the first axis.
    """
    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    n = len(devices)
    if shape is None:
        shape = [n] + [1] * (len(axis_names) - 1)
    if math.prod(shape) != n:
        raise ValueError(f"mesh shape {shape} != device count {n}")
    dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, tuple(axis_names))


def get_mesh() -> Mesh:
    """Return the active mesh: the calling thread's override if one is set
    (per-trial submesh placement), else the process-wide mesh (built lazily
    as a 1-D mesh over all devices)."""
    local = getattr(_tls, "mesh", None)
    if local is not None:
        return local
    global _active_mesh
    with _lock:
        if _active_mesh is None:
            _active_mesh = build_mesh()
        return _active_mesh


def set_mesh(mesh: Optional[Mesh]) -> None:
    global _active_mesh
    with _lock:
        _active_mesh = mesh


@contextlib.contextmanager
def use_mesh(mesh: Mesh) -> Iterator[Mesh]:
    """Temporarily swap the active mesh (tests, dryruns)."""
    global _active_mesh
    with _lock:
        prev = _active_mesh
        _active_mesh = mesh
    try:
        yield mesh
    finally:
        with _lock:
            _active_mesh = prev


@contextlib.contextmanager
def use_mesh_local(mesh: Optional[Mesh]) -> Iterator[Optional[Mesh]]:
    """Bind a mesh to the CURRENT THREAD only — the placement mechanism for
    task-parallel trials (SURVEY §2.2 P6/P7): each trial worker binds its
    own submesh so concurrent fits land on disjoint chips instead of
    serializing on one shared mesh."""
    prev = getattr(_tls, "mesh", None)
    _tls.mesh = mesh
    try:
        yield mesh
    finally:
        _tls.mesh = prev


_submesh_cache: dict = {}


def submeshes(k: int, mesh: Optional[Mesh] = None) -> list:
    """Partition the mesh's devices into min(k, n_devices) disjoint 1-D
    data-axis submeshes (cycled to length k when k > n_devices). Memoized so
    repeated tuning fits reuse identical Mesh objects and hit the per-mesh
    program caches instead of recompiling."""
    mesh = mesh or get_mesh()
    devices = list(mesh.devices.flat)
    n = len(devices)
    k = max(1, int(k))
    groups = min(k, n)
    key = (tuple(id(d) for d in devices), groups)
    if key not in _submesh_cache:
        per = n // groups
        extra = n % groups
        out = []
        start = 0
        for g in range(groups):
            size = per + (1 if g < extra else 0)
            if size == n and mesh.shape.get(DATA_AXIS) == n:
                # a "submesh" spanning the whole 1-D parent IS the parent:
                # returning the same object lets trial fits hit the parent
                # mesh's program caches instead of re-loading + re-warming
                # every executable on an identical-but-distinct Mesh (the
                # dominant warmup cost on a single chip)
                out.append(mesh)
            else:
                out.append(Mesh(np.asarray(devices[start:start + size]),
                                (DATA_AXIS,)))
            start += size
        _submesh_cache[key] = out
    cached = _submesh_cache[key]
    return [cached[i % groups] for i in range(k)]


def worker_mesh(num_workers: Optional[int] = None,
                mesh: Optional[Mesh] = None) -> Mesh:
    """The mesh a fit that names its `num_workers` runs on: `num_workers`
    data shards, as sparkdl's `XgboostRegressor(num_workers=k)` names the
    task slots its table is spread over (`SML/ML 11 - XGBoost.py:55-72`).

    None is the active mesh, as is a `num_workers` equal to the active
    mesh's row shards (the SAME object, so the per-mesh program and
    staging caches hit); another `num_workers` that divides the devices is
    the first of the `submeshes` that wide. Anything else is refused: a
    layout the host cannot give is never replaced by one it can."""
    mesh = mesh or get_mesh()
    if num_workers is None:
        return mesh
    k, n = int(num_workers), mesh_device_count(mesh)
    if k == data_width(mesh):
        return mesh
    if k < 1 or n % k:
        raise ValueError(
            f"num_workers={num_workers} data shards cannot be cut from the "
            f"{n} device(s) of the active mesh {dict(mesh.shape)}: give a "
            f"divisor of {n}, or None for the mesh as it is")
    return submeshes(n // k, mesh)[0]


_trial_mesh_cache: dict = {}


def trial_mesh(trial_dim: int, mesh: Optional[Mesh] = None) -> Mesh:
    """A 2-D ``("trial", "data")`` mesh over the SAME devices as the given
    (or active) 1-D data mesh: fused (grid point × fold) trial ELEMENTS
    shard over the leading axis while each trial lane keeps sharding its
    rows over the remaining devices — cross-chip trial parallelism
    (SURVEY §2.2 P6 re-expressed as a mesh axis instead of a thread pool).
    ``trial_dim`` must divide the device count. Memoized per (devices,
    trial_dim) so repeated fused grids reuse identical Mesh objects and
    hit the per-mesh program caches instead of recompiling."""
    mesh = mesh or get_mesh()
    devices = list(mesh.devices.flat)
    n = len(devices)
    trial_dim = max(1, int(trial_dim))
    if n % trial_dim:
        raise ValueError(f"trial axis {trial_dim} does not divide the "
                         f"{n}-device mesh")
    key = (tuple(id(d) for d in devices), trial_dim)
    if key not in _trial_mesh_cache:
        _trial_mesh_cache[key] = Mesh(
            np.asarray(devices).reshape(trial_dim, n // trial_dim),
            (TRIAL_AXIS, DATA_AXIS))
    return _trial_mesh_cache[key]


_host_mesh_cache: dict = {}


def host_mesh(hosts: Optional[int] = None,
              devices_per_host: Optional[int] = None,
              mesh: Optional[Mesh] = None) -> Mesh:
    """A 2-D ``(DCN_AXIS, ICI_AXIS)`` host-major mesh: row 0 is host group
    0's devices, row 1 host group 1's, ... — the topology a hierarchical
    allreduce exploits (cheap wide ICI within a row, narrow DCN across
    rows).

    On a single machine the groups are VIRTUAL hosts: the flat device set
    partitioned into `hosts` contiguous groups, so the whole multi-host
    code path is testable on the simulated 8-device CPU mesh. On a real
    multi-process TPU slice (`jax.process_count() > 1`) the groups are the
    `jax.process_index()` slices — one row per process — and `hosts`
    defaults to the process count.

    Because device d of the flat mesh lands at (d // per, d % per), row
    sharding over ``(DCN_AXIS, ICI_AXIS)`` places every global row on
    exactly the device the flat mesh would — the PR-6 layout-invariant
    sampling contract carries over unchanged, whatever the group shape.

    Memoized per (devices, hosts) so repeated fits reuse identical Mesh
    objects and hit the per-mesh program caches instead of recompiling."""
    import jax as _jax
    base = mesh.devices.flat if mesh is not None else _jax.devices()
    devices = list(base)
    n = len(devices)
    if hosts is None or hosts <= 0:
        from ..conf import GLOBAL_CONF as _CONF
        hosts = int(_CONF.get("sml.mesh.hostGroups") or 0)
    if hosts <= 0:
        pc = _jax.process_count()
        hosts = pc if pc > 1 else 1
    hosts = max(1, min(int(hosts), n))
    if devices_per_host is None:
        if n % hosts:
            raise ValueError(f"{hosts} host groups do not divide the "
                             f"{n}-device set")
        devices_per_host = n // hosts
    if hosts * devices_per_host != n:
        raise ValueError(f"host mesh {hosts}x{devices_per_host} != device "
                         f"count {n}")
    if _jax.process_count() > 1 and hosts == _jax.process_count():
        # real multi-host: one row per process, devices in process order
        devices = sorted(devices, key=lambda d: (d.process_index, d.id))
    key = (tuple(id(d) for d in devices), hosts)
    if key not in _host_mesh_cache:
        _host_mesh_cache[key] = Mesh(
            np.asarray(devices).reshape(hosts, devices_per_host),
            (DCN_AXIS, ICI_AXIS))
    return _host_mesh_cache[key]


def is_hierarchical(mesh: Optional[Mesh] = None) -> bool:
    """True when the mesh declares the two-hop host topology — the signal
    `sml.tree.hierarchicalAllreduce=auto` keys on."""
    mesh = mesh or get_mesh()
    return DCN_AXIS in mesh.shape and ICI_AXIS in mesh.shape


def row_axes(mesh: Optional[Mesh] = None) -> Tuple[str, ...]:
    """The mesh axes rows shard over: ``(DCN_AXIS, ICI_AXIS)`` on a
    hierarchical host mesh, ``(DATA_AXIS,)`` everywhere else."""
    mesh = mesh or get_mesh()
    if is_hierarchical(mesh):
        return (DCN_AXIS, ICI_AXIS)
    return (DATA_AXIS,)


def row_spec_entry(mesh: Optional[Mesh] = None):
    """The PartitionSpec element that shards rows on this mesh: the plain
    DATA_AXIS name, or the ("dcn", "ici") tuple that splits rows over both
    hops of a host mesh (host-major, so placement matches the flat mesh)."""
    ax = row_axes(mesh)
    return ax if len(ax) > 1 else ax[0]


def data_width(mesh: Optional[Mesh] = None) -> int:
    """Number of row shards: the flat data-axis size, or DCN×ICI on a
    hierarchical host mesh. Every `mesh.shape[DATA_AXIS]` site reads this
    instead so host meshes ride the same staging/padding arithmetic."""
    mesh = mesh or get_mesh()
    if is_hierarchical(mesh):
        return int(mesh.shape[DCN_AXIS]) * int(mesh.shape[ICI_AXIS])
    return int(mesh.shape[DATA_AXIS])


def host_group_of(mesh: Optional[Mesh] = None) -> dict:
    """device id → host-group index (the mesh's DCN row); flat meshes map
    every device to group 0 — the lookup straggler probes use to feed
    per-host skew lanes (obs/_skew.py)."""
    mesh = mesh or get_mesh()
    if not is_hierarchical(mesh):
        return {d.id: 0 for d in mesh.devices.flat}
    rows = mesh.devices.reshape(int(mesh.shape[DCN_AXIS]), -1)
    return {d.id: g for g, row in enumerate(rows) for d in row}


def host_partition(n_rows: int, hosts: int) -> list:
    """Contiguous [start, stop) global row ranges, one per host group —
    the per-host data-plane split. Host-major row sharding places block g
    exactly on group g's devices, so a ChunkSource host-view reading only
    its range feeds its own group's HBM without cross-host traffic.
    Remainder rows go to the leading groups (matching np.array_split)."""
    hosts = max(1, int(hosts))
    n = max(0, int(n_rows))
    per, extra = divmod(n, hosts)
    out, start = [], 0
    for g in range(hosts):
        stop = start + per + (1 if g < extra else 0)
        out.append((start, stop))
        start = stop
    return out


def host_row_blocks(arr, mesh: Optional[Mesh] = None) -> list:
    """Per-host view of a row-sharded array: one (group_index, [(device,
    shard_block), ...]) pair per host group, blocks ordered by row
    position within the group — the group-aware iteration a multi-host
    skew probe walks (each block resident on its device, so timing an op
    over it measures that chip alone, attributable to its host)."""
    mesh = mesh or get_mesh()
    groups = host_group_of(mesh)
    out: dict = {}
    for dev, blk in addressable_row_blocks(arr):
        out.setdefault(groups.get(dev.id, 0), []).append((dev, blk))
    return sorted(out.items())


def data_sharding(mesh: Optional[Mesh] = None, ndim: int = 2) -> NamedSharding:
    """Rows sharded over the mesh's row axes, everything else replicated."""
    mesh = mesh or get_mesh()
    spec = P(row_spec_entry(mesh), *([None] * (ndim - 1)))
    return NamedSharding(mesh, spec)


def replicated(mesh: Optional[Mesh] = None) -> NamedSharding:
    mesh = mesh or get_mesh()
    return NamedSharding(mesh, P())


def pad_rows(x: np.ndarray, multiple: int, fill: float = 0.0) -> Tuple[np.ndarray, int]:
    """Pad axis 0 to a multiple so row-sharding divides evenly (static shapes —
    XLA requires equal per-chip blocks; the pad tail is masked by callers)."""
    n = x.shape[0]
    rem = (-n) % multiple
    if rem == 0:
        return x, n
    pad_width = [(0, rem)] + [(0, 0)] * (x.ndim - 1)
    return np.pad(x, pad_width, constant_values=fill), n


def bucket_rows(n: int, multiple: int) -> int:
    """Round a row count up to a coarse power-of-two-fraction grid (≤12.5%
    padding) that also divides evenly by `multiple` (the mesh's data-axis
    size). Near-size datasets — CV folds, tuning-trial re-fits, randomSplit
    variations — land on the SAME padded shape and therefore the same
    compiled program, instead of paying one XLA compile per exact row count
    (SURVEY §7 hard-part #6; the padding tail is masked by every program)."""
    n = max(int(n), 1)
    multiple = max(int(multiple), 1)
    target = max(n, multiple)
    step = 1 << max(0, target.bit_length() - 4)  # grid of 8..16 * 2^k
    b = ((target + step - 1) // step) * step
    return ((b + multiple - 1) // multiple) * multiple


def shard_rows(x: np.ndarray, mesh: Optional[Mesh] = None) -> Tuple[jax.Array, int]:
    """Stage a host array into HBM sharded by rows over DATA_AXIS.

    Returns (device_array, true_row_count); rows are zero-padded to a
    per-chip-equal block, callers mask with the true count.
    """
    mesh = mesh or get_mesh()
    n_dev = data_width(mesh)
    padded, n_true = pad_rows(np.asarray(x), n_dev)
    arr = jax.device_put(padded, data_sharding(mesh, padded.ndim))
    return arr, n_true


def row_mask(n_padded: int, n_true: int, dtype=np.float32) -> np.ndarray:
    """Host-side 0/1 mask for padded rows (shard alongside the data)."""
    m = np.zeros((n_padded,), dtype=dtype)
    m[:n_true] = 1
    return m


def mesh_device_count(mesh: Optional[Mesh] = None) -> int:
    mesh = mesh or get_mesh()
    return math.prod(mesh.devices.shape)


def addressable_row_blocks(arr) -> list:
    """One (device, shard_block) pair per addressable shard of a
    row-sharded array, ordered by row position — the per-chip view a
    straggler probe iterates (each block is a jax.Array RESIDENT on its
    device, so timing an op over it measures that chip alone). See
    obs/_skew.py for the attribution these timings feed."""
    shards = sorted(arr.addressable_shards,
                    key=lambda s: tuple(sl.start or 0 for sl in s.index))
    return [(s.device, s.data) for s in shards]


PLACEMENT_LOG: list = []  # (trial_index, device_id tuple) per placed trial
_PLACEMENT_LOG_MAX = 4096


def _log_placement(idx: int, mesh: Mesh) -> None:
    with _lock:
        if len(PLACEMENT_LOG) >= _PLACEMENT_LOG_MAX:
            del PLACEMENT_LOG[: _PLACEMENT_LOG_MAX // 2]
        PLACEMENT_LOG.append((idx, tuple(d.id for d in mesh.devices.flat)))


def run_placed_trials(jobs: Sequence, fn, parallelism: int) -> list:
    """Run `fn(job)` for every job with REAL chip placement: `parallelism`
    worker threads, each bound (thread-locally) to its own disjoint submesh
    of the active mesh, so concurrent trials execute on different chips —
    the TPU replacement for Spark's driver thread pool + executor tasks
    (`SML/ML 07:120-130`, `SML/Labs/ML 08L:89-107`).

    Every trial's placement is recorded in `PLACEMENT_LOG` (trial index →
    submesh device ids), so placement is ASSERTABLE without wall-clock
    timing (VERDICT r2 #7)."""
    jobs = list(jobs)
    parallelism = max(1, int(parallelism))
    if parallelism <= 1 or len(jobs) <= 1:
        mesh = get_mesh()
        out = []
        for i, j in enumerate(jobs):
            _log_placement(i, mesh)
            out.append(fn(j))
        return out
    from concurrent.futures import ThreadPoolExecutor
    import queue as _queue

    meshes = submeshes(parallelism)
    q: _queue.SimpleQueue = _queue.SimpleQueue()
    for m in meshes:
        q.put(m)

    def bind_submesh():
        _tls.mesh = q.get_nowait()

    def run_one(args):
        i, job = args
        _log_placement(i, _tls.mesh)
        return fn(job)

    with ThreadPoolExecutor(max_workers=parallelism,
                            initializer=bind_submesh) as pool:
        return list(pool.map(run_one, enumerate(jobs)))
