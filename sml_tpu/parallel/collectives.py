"""The single communication backend (SURVEY §2.4).

Spark's shuffle, `treeAggregate`, Arrow IPC and XGBoost's Rabit allreduce all
collapse into XLA collectives over ICI (intra-slice) / DCN (multi-host):

- ``treeAggregate(gradient | Gram)``  → ``psum``            (allreduce)
- shuffle for keyed aggregation       → ``all_to_all`` on device, or the
  host-side Arrow repartition in ``sml_tpu.frame`` for string-heavy ops
- broadcast of models/params          → replication via sharding
- Rabit histogram allreduce           → the same ``psum``

These wrappers exist so estimator code never spells a raw `lax` collective —
one place to retarget if the axis naming or multi-host story changes.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..obs import _context as _trace
from ..obs._recorder import RECORDER as _OBS
from ..obs._watchdog import WATCHDOG as _WATCHDOG
from .mesh import DATA_AXIS, DCN_AXIS, ICI_AXIS


def _resolve_row_axis(axis):
    """`DATA_AXIS` spoken under a hierarchical (host-grouped) active mesh
    means "all row axes": a program written for the flat 1-D mesh (an
    evaluator's masked stats, a linear model's normal equations, a
    clustering step) reduces over ("dcn", "ici") without every call site
    learning about host groups — the two-axis mesh is a drop-in for the
    flat one. Explicit names and tuples pass through untouched, so
    topology-aware code (tree_impl threads `row_axes(mesh)` itself)
    keeps full control. Runs at TRACE time, like the flight-recorder
    notes: the active mesh is the one the enclosing shard_map is being
    built over."""
    if axis == DATA_AXIS:
        from . import mesh as _mesh
        m = _mesh.get_mesh()
        if _mesh.is_hierarchical(m):
            return _mesh.row_axes(m)
    return axis


def _payload_bytes(x) -> float:
    """Per-launch payload of one collective operand: every participating
    chip moves (shape x itemsize) bytes through the allreduce/gather ring.
    Computed from the TRACE-time abstract value (shapes are static), so it
    works on tracers and concrete arrays alike."""
    import numpy as _np
    try:
        dt = _np.dtype(getattr(x, "dtype", _np.float32))
    except TypeError:
        dt = _np.dtype(_np.float32)
    return float(_np.prod(_np.shape(x), dtype=_np.float64) * dt.itemsize)


def _note(op: str, x=None) -> None:
    """Flight-recorder collective event. These wrappers execute at TRACE
    time (the collective itself runs inside the compiled program), so one
    event marks one collective launch PER COMPILED PROGRAM — the static
    count a graph runtime can know without a device profiler; multiply by
    program executions for wire traffic. No-op when the recorder is off.

    With an operand `x`, the per-launch payload is counted into
    `collective.<op>_bytes` (rendered as a counter track by the trace
    exporter): the ICI allreduce volume of one split round is the
    histogram payload, and the histogram-subtraction trick's halving of
    it is directly visible in this counter.

    Tracing happens on the DISPATCHING thread, so the causal trace
    context riding it (obs/_context.py — e.g. a coalesced serving
    flush, a fused CV trial batch) tags the event: the collective hop
    of a request's causal chain, without a device profiler."""
    if _OBS.enabled:
        nbytes = None if x is None else _payload_bytes(x)
        _OBS.emit("collective", f"collective.{op}",
                  args=_trace.trace_args(
                      None if nbytes is None else {"bytes": nbytes}))
        _OBS.counter(f"collective.{op}")
        if nbytes:
            _OBS.counter(f"collective.{op}_bytes", nbytes)


def _note_hop(op: str, hop: str, x=None) -> None:
    """Per-HOP flight-recorder event for a hierarchical collective: same
    trace-time semantics as `_note`, but the launch and byte counters are
    keyed `collective.<op>.<hop>` / `collective.<op>_bytes.<hop>` so the
    cheap wide intra-host hop ("ici") and the narrow cross-host hop
    ("dcn") are separately visible — the DCN byte drop to the inter-group
    fraction is the whole point of the two-level reduce, and this counter
    is what asserts it (tests + the `multihost` bench block)."""
    if _OBS.enabled:
        nbytes = None if x is None else _payload_bytes(x)
        _OBS.emit("collective", f"collective.{op}.{hop}",
                  args=_trace.trace_args(
                      None if nbytes is None else {"bytes": nbytes}))
        _OBS.counter(f"collective.{op}.{hop}")
        if nbytes:
            _OBS.counter(f"collective.{op}_bytes.{hop}", nbytes)


def psum(x, axis=DATA_AXIS):
    """Allreduce-sum over the mesh axis — the `treeAggregate` replacement.
    `axis` may be a tuple of names (a host mesh's ("dcn", "ici") row axes);
    XLA reduces over their product as one flat allreduce. The default
    axis resolves against the active mesh (`_resolve_row_axis`)."""
    axis = _resolve_row_axis(axis)
    _note("psum", x)
    return lax.psum(x, axis_name=axis)


def psum_hierarchical(x, *, ici_axis: str = ICI_AXIS,
                      dcn_axis: str = DCN_AXIS, ici_size: int):
    """Two-level topology-aware allreduce for host-grouped meshes:

      1. reduce-scatter over the INTRA-group hop (`ici_axis`) — each of
         the `ici_size` group members ends holding the group-partial sum
         of one 1/ici_size chunk of the payload;
      2. allreduce the chunk over the INTER-group hop (`dcn_axis`) —
         the only cross-host traffic, payload/ici_size bytes per device
         instead of the full payload a flat allreduce would push through
         the ~10x-narrower DCN;
      3. allgather the reduced chunks back over `ici_axis`.

    The result equals `psum(x, (dcn_axis, ici_axis))` (bit-exact when the
    per-chunk sums are exact, e.g. integer-valued histogram counts;
    otherwise within float reduction-order noise, the same caveat as any
    mesh-width change). `ici_size` must be the static size of `ici_axis`
    (program makers read it from the mesh at trace time — `lax` has no
    axis-size query in the pinned jax). Chunking pads the flattened
    payload with zeros to a multiple of `ici_size`, which is exact for
    sums. ici_size<=1 degenerates to the flat psum over the DCN hop.

    Per-hop launches and bytes are recorded by `_note_hop`: the full
    payload on the ici reduce-scatter, payload/ici_size on the dcn
    allreduce and the ici allgather."""
    ici_size = int(ici_size)
    if ici_size <= 1:
        _note_hop("psum", "dcn", x)
        return lax.psum(x, axis_name=dcn_axis)
    shape = x.shape
    flat = x.reshape(-1)
    n = flat.shape[0]
    pad = (-n) % ici_size
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    _note_hop("psum", "ici", flat)
    chunk = lax.psum_scatter(flat, axis_name=ici_axis,
                             scatter_dimension=0, tiled=True)
    _note_hop("psum", "dcn", chunk)
    chunk = lax.psum(chunk, axis_name=dcn_axis)
    _note_hop("all_gather", "ici", chunk)
    out = lax.all_gather(chunk, axis_name=ici_axis, tiled=True)
    if pad:
        out = out[:n]
    return out.reshape(shape)


def psum_scalars(*xs, axis=DATA_AXIS):
    """ONE allreduce for several scalar statistics: stacks the operands and
    psums the vector, so k base/count reductions cost one collective launch
    instead of k (each launch pays fixed ICI latency). Elementwise across
    chips, so each result is bit-identical to its own psum. Returns the
    scalars in input order."""
    stacked = psum(jnp.stack([jnp.asarray(x, jnp.float32) for x in xs]), axis)
    return tuple(stacked[i] for i in range(len(xs)))


def pmean(x, axis=DATA_AXIS):
    axis = _resolve_row_axis(axis)
    _note("pmean", x)
    return lax.pmean(x, axis_name=axis)


def pmax(x, axis=DATA_AXIS):
    axis = _resolve_row_axis(axis)
    _note("pmax", x)
    return lax.pmax(x, axis_name=axis)


def pmin(x, axis=DATA_AXIS):
    axis = _resolve_row_axis(axis)
    _note("pmin", x)
    return lax.pmin(x, axis_name=axis)


def all_gather(x, axis: str = DATA_AXIS, *, tiled: bool = False):
    axis = _resolve_row_axis(axis)
    _note("all_gather", x)
    return lax.all_gather(x, axis_name=axis, tiled=tiled)


def reduce_scatter(x, axis: str = DATA_AXIS, *, scatter_dimension: int = 0):
    _note("reduce_scatter", x)
    return lax.psum_scatter(x, axis_name=axis, scatter_dimension=scatter_dimension, tiled=True)


def all_to_all(x, axis: str = DATA_AXIS, *, split_axis: int = 0, concat_axis: int = 0):
    """Device-side shuffle: exchange row blocks between chips over ICI."""
    _note("all_to_all", x)
    return lax.all_to_all(x, axis_name=axis, split_axis=split_axis, concat_axis=concat_axis, tiled=True)


def ppermute(x, perm, axis: str = DATA_AXIS):
    _note("ppermute", x)
    return lax.ppermute(x, axis_name=axis, perm=perm)


def axis_index(axis=DATA_AXIS):
    """Linear shard index over one axis name or a TUPLE of names (the
    ("dcn", "ici") row axes of a host mesh, major-to-minor): the flat
    position matches the flat mesh's index, so layout-keyed draws stay
    layout-invariant. The pinned jax has no `lax.axis_size`, so minor
    axis sizes come from `psum(1, axis)` — a constant fold at trace time,
    not a runtime collective."""
    axis = _resolve_row_axis(axis)
    if isinstance(axis, str):
        return lax.axis_index(axis_name=axis)
    idx = lax.axis_index(axis_name=axis[0])
    for name in axis[1:]:
        idx = idx * lax.psum(1, axis_name=name) + lax.axis_index(
            axis_name=name)
    return idx


def masked_count(mask, axis=DATA_AXIS):
    """Global true-row count given a per-shard 0/1 row mask."""
    return psum(jnp.sum(mask), axis)


class MultihostInitError(RuntimeError):
    """Typed failure surface of `initialize_multihost`: carries the
    coordinator / process context so a wedged bring-up is diagnosable
    from the exception alone (which peer config, which process slot)."""

    def __init__(self, msg: str, *, coordinator=None, num_processes=None,
                 process_id=None):
        super().__init__(msg)
        self.coordinator = coordinator
        self.num_processes = num_processes
        self.process_id = process_id


def initialize_multihost(coordinator: Optional[str] = None, num_processes: Optional[int] = None,
                         process_id: Optional[int] = None, *,
                         timeout_s: float = 300.0) -> bool:
    """Cross-host (DCN) bring-up. On a single host this is a no-op (fast
    path, returns False without touching `jax.distributed`); on a pod
    slice it wires `jax.distributed` so the same named collectives span
    hosts (the NCCL/MPI-equivalent bootstrap, without either) and returns
    True. Bring-up blocks until every process joins — bounded by
    `timeout_s` where the pinned jax supports `initialization_timeout` —
    and any failure (timeout, refused coordinator, double-init) surfaces
    as a typed `MultihostInitError` carrying the peer config instead of a
    bare RuntimeError from deep inside the runtime."""
    if num_processes is None or num_processes <= 1:
        return False
    import inspect
    kwargs = dict(coordinator_address=coordinator,
                  num_processes=num_processes, process_id=process_id)
    try:
        params = inspect.signature(jax.distributed.initialize).parameters
    except (TypeError, ValueError):  # builtins/C-accelerated: assume modern
        params = {"initialization_timeout": None}
    if "initialization_timeout" in params:
        kwargs["initialization_timeout"] = max(1, int(timeout_s))
    # the one HOST-SIDE collective wait in this module: bring-up blocks
    # until every process joins, which is exactly the hang a dead peer
    # produces — a watchdog ticket makes it a flagged stall with stacks
    # instead of a silent wedge (obs/_watchdog.py)
    with _WATCHDOG.watch("collective", "collective.initialize",
                         trace=_trace.current()):
        try:
            jax.distributed.initialize(**kwargs)
        except Exception as e:
            raise MultihostInitError(
                f"multi-host bring-up failed (coordinator={coordinator!r}, "
                f"num_processes={num_processes}, process_id={process_id}, "
                f"timeout_s={timeout_s}): {e}",
                coordinator=coordinator, num_processes=num_processes,
                process_id=process_id) from e
    return True
