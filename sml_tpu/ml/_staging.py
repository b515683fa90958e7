"""Host → HBM staging for estimator math.

Every distributed fit in this package has the same shape (SURVEY §3.1's TPU
mapping): pull the assembled feature column + label out of the host frame,
densify to (n, d) float arrays, zero-pad rows to a per-chip-equal block,
`jax.device_put` sharded over the mesh's data axis, and run a jitted
`shard_map` program whose cross-chip reductions are `psum` over ICI — the
replacement for Spark's executor→driver `treeAggregate`
(`SML/Labs/ML 02L - Linear Regression I Lab.py:70-77`).
"""

from __future__ import annotations

import contextlib
import math
import weakref
from functools import partial
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..parallel import dispatch
from ..parallel import mesh as meshlib
from .linalg import Vector, VectorArray, to_matrix


def extract_features(df, featuresCol: str) -> np.ndarray:
    """(n, d) float32 matrix from a vector/array column of a host frame.

    Columnar `VectorArray` columns (VectorAssembler/OHE output) hand over
    their backing (n, d) block directly — no per-row objects on the staging
    path (VERDICT r1 weak #3). A frame carrying a `_featurized` fast-path
    block (attached by Pipeline's fused fit, see base.Pipeline._fit) hands
    that over WITHOUT materializing its lazy transform chain at all."""
    feat = getattr(df, "_featurized", None)
    if feat is not None and featuresCol in feat:
        return feat[featuresCol][0]
    pdf = df.toPandas() if hasattr(df, "toPandas") else df
    col = pdf[featuresCol]
    if isinstance(getattr(col, "array", None), VectorArray):
        return np.ascontiguousarray(to_matrix(col), dtype=np.float32)
    vals = col.tolist()
    if vals and isinstance(vals[0], (Vector, list, tuple, np.ndarray)):
        X = to_matrix(vals)
    else:  # single numeric column used as a 1-feature matrix
        X = np.asarray(col, dtype=np.float64)[:, None]
    return np.ascontiguousarray(X, dtype=np.float32)


def extract_xy(df, featuresCol: str, labelCol: str,
               weightCol: Optional[str] = None) -> Tuple[np.ndarray, np.ndarray, Optional[np.ndarray]]:
    feat = getattr(df, "_featurized", None)
    if feat is not None and featuresCol in feat:
        # fused-fit fast path: X was assembled in one columnar pass over
        # the RAW frame; labels come from the same raw pandas (with the
        # featurizer's row-drop mask applied) — the lazy transform chain
        # never materializes
        X, keep, raw_pdf = feat[featuresCol]
        y = np.asarray(raw_pdf[labelCol], dtype=np.float32)
        w = np.asarray(raw_pdf[weightCol], dtype=np.float32) if weightCol \
            else None
        if keep is not None:
            y = y[keep]
            w = w[keep] if w is not None else None
        return X, y, w
    pdf = df.toPandas() if hasattr(df, "toPandas") else df
    X = extract_features(pdf, featuresCol)
    y = np.asarray(pdf[labelCol], dtype=np.float32)
    w = np.asarray(pdf[weightCol], dtype=np.float32) if weightCol else None
    return X, y, w


def extract_compact(df, featuresCol: str, labelCol: str):
    """(CompactParts, y) when the frame carries a compact featurized block
    (attached by base.Pipeline's fused fit for huge linear fits — see
    featurizer.CompactParts), else None. Labels come from the raw pandas
    with the featurizer's row-drop mask and the finite-label filter
    applied to BOTH sides, matching extract_xy's semantics."""
    feat = getattr(df, "_featurized_compact", None)
    if feat is None or featuresCol not in feat:
        return None
    parts, raw_pdf = feat[featuresCol]
    y = np.asarray(raw_pdf[labelCol], dtype=np.float32)
    if parts.keep is not None:
        y = y[parts.keep]
    ok = np.isfinite(y)
    if not ok.all():
        parts = parts.take(ok)
        y = y[ok]
    return parts, y


import threading as _threading

_stage_cache: "dict" = {}
_stage_cache_order: list = []
_stage_cache_bytes: list = [0]
_stage_lock = _threading.Lock()  # parallel tuning trials stage concurrently
_STAGE_CACHE_MAX_BYTES = 6 << 30  # device-bytes budget across all meshes
_FULL_HASH_MAX_BYTES = 1 << 24    # 16 MB
_SAMPLE_WINDOW = 1 << 16
_SAMPLE_COUNT = 16
_tls_keys = _threading.local()    # probe→stage key handoff


class RowsLast(NamedTuple):
    """A host block whose table rows run along its LAST axis (feature-
    major: `featurizer.CompactParts`). Staging pads and shards that axis
    and `run_data_parallel` hands the program the block split there; any
    other array has its rows first."""
    block: np.ndarray


def _rows_axis(a) -> Tuple[np.ndarray, bool]:
    """(the array, whether its rows are its last axis)."""
    return (a.block, True) if isinstance(a, RowsLast) else (a, False)


def _n_rows(a) -> int:
    a, rows_last = _rows_axis(a)
    return int(np.shape(a)[-1 if rows_last else 0])


def _normalize(a) -> np.ndarray:
    """The staging boundary: a C-contiguous ndarray (no copy when the
    caller already complies, which every internal extract path does)."""
    return np.ascontiguousarray(np.asarray(a))


#: an array of at least this many bytes has its staging steps recorded as
#: the spans `stage.key` / `stage.pad` / `stage.put` (`staging_step`). Under
#: it the three are microseconds and stay in the caller's own time: the
#: serving path pays the comparison and a note into `_NO_SPAN`'s dict
_SPAN_BYTES = 1 << 20
_NO_SPAN = contextlib.nullcontext({})   # its notes are read by nobody


def staging_step(step: str, nbytes: int):
    """The span `stage.<step>` (key / pad / put) noting `bytes`, for a
    step over an array of at least `_SPAN_BYTES`; under it `_NO_SPAN`.
    The functions here are shared by fits, scoring and serving, so the
    spans are named for the function: during a fit they lie inside
    `fit.stage`."""
    if nbytes < _SPAN_BYTES:
        return _NO_SPAN
    from ..utils.profiler import PROFILER
    return PROFILER.span(f"stage.{step}", bytes=int(nbytes))


def _keyed(a, tag: tuple, probe: Callable = _stage_cache.get):
    """The `stage.key` step: (the normalized array, its cache key: the
    content key and then `tag`, what `probe` finds cached under that key
    or None). Notes `copied` where the caller's array was not the
    C-contiguous ndarray the staging boundary takes as it is, and `hit`."""
    with staging_step("key", getattr(a, "nbytes", 0)) as note:
        given, a = a, _normalize(a)
        if a is not given:
            note["copied"] = True
        key = (_memo_key(a),) + tag
        hit = probe(key)
        note["hit"] = hit is not None
    return a, key, hit


#: host bytes of FREE warm pad buffers the pool may keep: one fit's set of
#: the largest deployment (1.17 GB: a clustering's feature-major float32
#: block of 6.8 M padded rows x 42 columns and its mask; a buffer larger
#: than the bound is never kept, and its pad is paid in fresh pages every
#: fit) and the sets of the smaller ones (0.63 GB: a compact linear block
#: of 8 M rows, its numeric columns, labels and mask)
_PAD_POOL_MAX_BYTES = 2 << 30


def _nothing_placed():
    return None


class _PadPool:
    """Host buffers a padded copy is written into, kept from one staging to
    the next, because the price of a pad is not the copy but the first
    touch of the pages of a fresh allocation (0.92 GB/s into untouched
    pages against 11.4 GB/s into pages written before, PERF.md). Keyed by
    bytes alone: a buffer is a flat uint8 array that `view` gives the
    shape and dtype of the hour, so every split of a table (one bucketed
    shape, `mesh.bucket_rows`) finds the buffers of the split before.

    It holds FREE buffers only, least recently used first, each with a weak
    reference to the array last put from it: `device_put` returns before
    the host buffer has been read, so a buffer is handed out again only
    once that array is ready (or gone). A buffer taken and never given back
    (a put that raised) is the garbage collector's."""

    def __init__(self, max_bytes: int):
        self.max_bytes = max_bytes
        self._free: list = []     # (flat buffer, () -> the array placed | None)
        self._bytes = 0

    def take(self, nbytes: int) -> Tuple[np.ndarray, bool]:
        """(a flat buffer of `nbytes`, whether its pages are warm). The
        longest-free buffer of that size whose transfer is over; where the
        only ones are still being read, a fresh one if the pool has room to
        keep both (two arrays of one shape staged back to back), else the
        oldest, after its transfer."""
        with _stage_lock:
            sized = [(i, placed()) for i, (buf, placed)
                     in enumerate(self._free) if buf.nbytes == nbytes]
            at, placed = next((s for s in sized
                               if s[1] is None or s[1].is_ready()),
                              (None, None))
            if at is None and sized \
                    and self._bytes + nbytes > self.max_bytes:
                at, placed = sized[0]
            if at is None:
                return np.empty(nbytes, np.uint8), False
            buf = self._free.pop(at)[0]
            self._bytes -= nbytes
        if placed is not None:
            # graftlint: disable=host-sync-in-hot-path -- the host buffer may be written only once the transfer out of it is over; by the next fit the array is ready and this returns at once
            placed.block_until_ready()
        return buf, True

    def give_back(self, buf: np.ndarray, placed=None) -> None:
        """`buf` is free for the next pad of its size once `placed`, the
        array put from it (None: nothing was), is ready. Over the bound the
        least recently used go; their pages live as long as a transfer
        reads them (the runtime holds the array it was given)."""
        if buf.nbytes > self.max_bytes:
            return
        ref = _nothing_placed if placed is None else weakref.ref(placed)
        with _stage_lock:
            self._free.append((buf, ref))
            self._bytes += buf.nbytes
            while self._bytes > self.max_bytes:
                self._bytes -= self._free.pop(0)[0].nbytes

    def stats(self) -> dict:
        """(buffers, bytes) snapshot: test/debug surface."""
        with _stage_lock:
            return {"buffers": len(self._free), "bytes": self._bytes}

    def clear(self) -> None:
        with _stage_lock:
            self._free.clear()
            self._bytes = 0


_PAD_POOL = _PadPool(_PAD_POOL_MAX_BYTES)


def _aliases_host(mesh) -> bool:
    """Whether an array placed on `mesh` may BE the host buffer it was put
    from. The CPU client takes an aligned NumPy array (and each aligned
    shard of one) without a copy: the "device" array the staging cache then
    keeps is the host memory, and a pad written over it at the next staging
    would change a cached array under its content key. So a padded copy for
    such a mesh (the tests' virtual one, the host route's) is never
    pooled."""
    return mesh.devices.flat[0].platform == "cpu"


def _zero_tailed(shape: tuple, dtype, axis: int, lead, n_lead: int,
                 nbytes_in: int, mesh):
    """The `stage.pad` step: (an array of `shape` that is `lead` in its
    first `n_lead` entries along `axis` and zero after them, the pad pool's
    buffer it lies in, for `_put` to give back). A fresh allocation and
    None, as `np.pad` makes it, where the step is over less than
    `_SPAN_BYTES` (scoring and serving stage small batches on many threads:
    no fresh-page cost worth a buffer, and nothing to queue on) or the
    array placed on `mesh` may alias the host. The tail is always written:
    a pooled buffer held another split's rows before. Notes the `bytes` of
    the array and whether its pages were `warm`."""
    from ..utils.profiler import PROFILER
    nbytes = math.prod(shape) * np.dtype(dtype).itemsize
    spanned = nbytes_in >= _SPAN_BYTES
    with staging_step("pad", nbytes_in) as note:
        buf, warm = _PAD_POOL.take(nbytes) \
            if spanned and not _aliases_host(mesh) else (None, False)
        out = np.empty(shape, dtype) if buf is None \
            else buf.view(dtype).reshape(shape)
        at = [slice(None)] * len(shape)
        at[axis] = slice(0, n_lead)
        out[tuple(at)] = lead
        at[axis] = slice(n_lead, None)
        out[tuple(at)] = 0
        note["bytes"] = nbytes
        note["warm"] = warm
    if spanned and warm:
        PROFILER.count("staging.pad_warm")
    elif spanned:
        PROFILER.count("staging.pad_fresh")
    return out, buf


def _padded_rows(a: np.ndarray, rows: int, mesh, axis: int = 0, dtype=None):
    """(`a`, as `dtype` where one is given, with zero rows appended along
    `axis` up to `rows`, the bucketed count of its own; the pad pool's
    buffer or None): `_zero_tailed`'s pad step. `a` itself, and no span,
    where it has the rows and the type already or has no row at all, as
    `mesh.pad_rows` leaves such an array."""
    pad = (-a.shape[axis]) % rows
    dtype = np.dtype(dtype or a.dtype)
    if not pad and dtype == a.dtype:
        return a, None
    shape = list(a.shape)
    shape[axis] += pad
    return _zero_tailed(tuple(shape), dtype, axis, a, a.shape[axis],
                        a.nbytes, mesh)


def _put(padded: np.ndarray, sharding, buf: Optional[np.ndarray] = None):
    """The `stage.put` step: `padded` placed as `sharding` says. The call
    returns before the host memory has been read, so the pad pool's buffer
    `padded` lies in (`buf`) goes back with the array to wait for."""
    with staging_step("put", padded.nbytes):
        # graftlint: disable=unsharded-device-put -- `sharding` is the caller's NamedSharding over the active mesh, every site's
        hit = jax.device_put(padded, sharding)
    if buf is not None:
        _PAD_POOL.give_back(buf, hit)
    return hit


_CKSUM_CHUNK = 1 << 20  # words per block (8MB) — bounds the arange temp


def _word_checksum(u8: np.ndarray) -> int:
    """Position-weighted wraparound uint64 checksum over EVERY byte:
    sum(w_i) and sum(w_i * (i+1)) mod 2^64, computed blockwise. A few
    vectorized memory-bandwidth passes (~60ms for 240MB) — far cheaper
    than a cryptographic hash, but both point edits (a delta UPDATE, an
    imputed cell) AND row permutations (orderBy/shuffle/compaction
    rewrites) perturb it: a plain commutative sum is permutation-blind,
    and serving a stale device X in pre-shuffle row order against freshly
    extracted labels would silently train on mispaired (X, y) (r4
    review). A collision now needs edits with both zero sum and zero
    position-weighted sum mod 2^64."""
    n8 = u8.size & ~7
    w = u8[:n8].view(np.uint64)
    idx = np.arange(1, min(_CKSUM_CHUNK, max(w.size, 1)) + 1,
                    dtype=np.uint64)
    s1 = 0
    s2 = 0
    for start in range(0, w.size, _CKSUM_CHUNK):
        blk = w[start:start + _CKSUM_CHUNK]
        b1 = int(blk.sum(dtype=np.uint64))
        # sum(blk * (start+1 .. start+len)) = sum(blk*local_idx) + start*b1
        b2 = int((blk * idx[:blk.size]).sum(dtype=np.uint64)) + start * b1
        s1 += b1
        s2 += b2
    if n8 != u8.size:  # tail bytes fold in with their own positions
        tail = u8[n8:].astype(np.uint64)
        s1 += int(tail.sum(dtype=np.uint64))
        s2 += int((tail * np.arange(w.size + 1, w.size + 1 + tail.size,
                                    dtype=np.uint64)).sum(dtype=np.uint64))
    return ((s1 & 0xFFFFFFFFFFFFFFFF) << 64) | (s2 & 0xFFFFFFFFFFFFFFFF)


def _content_key(a: np.ndarray) -> tuple:
    """Staging-cache fingerprint of a NORMALIZED array. Small arrays hash
    their full bytes (~1ms/4MB). Large arrays combine 16 evenly-spaced
    64KB window hashes (order-sensitive) with a whole-array wraparound
    word-sum (point-edit-sensitive) plus length/shape/dtype: a full
    SHA-class pass over a 240MB block costs ~0.4s PER FIT (r2 paid it on
    every large-N call), while windows + word-sum cost ~20ms total and
    catch both global byte shifts (CV folds, randomSplit variants) and
    point edits outside the sampled windows (ADVICE r3 medium)."""
    assert a.flags.c_contiguous
    if a.nbytes <= _FULL_HASH_MAX_BYTES:
        return ("h", a.shape, str(a.dtype), hash(a.tobytes()))
    u8 = a.reshape(-1).view(np.uint8)
    n = u8.size
    starts = np.linspace(0, n - _SAMPLE_WINDOW, _SAMPLE_COUNT).astype(np.int64)
    parts = tuple(hash(u8[s:s + _SAMPLE_WINDOW].tobytes()) for s in starts)
    return ("s", a.shape, str(a.dtype), hash((n, _word_checksum(u8)) + parts))


def _memo_key(a: np.ndarray) -> tuple:
    """_content_key with a per-thread (id → key) memo so a probe in
    _route_mesh and the stage in the same routed block hash a buffer once,
    not twice (fit_logistic re-probes every Newton iteration)."""
    memo = getattr(_tls_keys, "memo", None)
    if memo is not None:
        hit = memo.get(id(a))
        if hit is not None and hit[0] is a:
            return hit[1]
    key = _content_key(a)
    if memo is not None:
        memo[id(a)] = (a, key)
    return key


def _cache_put(key, value):
    from ..obs import LEDGER
    from ..utils.profiler import PROFILER
    evicted = 0
    with _stage_lock:
        if key in _stage_cache:
            return
        cost = value.nbytes
        _stage_cache[key] = value
        _stage_cache_order.append((key, cost))
        _stage_cache_bytes[0] += cost
        while _stage_cache_bytes[0] > _STAGE_CACHE_MAX_BYTES \
                and len(_stage_cache_order) > 1:
            old, old_cost = _stage_cache_order.pop(0)
            _stage_cache.pop(old, None)
            _stage_cache_bytes[0] -= old_cost
            evicted += old_cost
    LEDGER.alloc("stage_cache", cost)
    if evicted:
        from ..obs import RECORDER
        LEDGER.free("stage_cache", evicted)
        PROFILER.count("staging.evict_bytes", float(evicted))
        if RECORDER.enabled:
            RECORDER.emit("cache", "cache.evict",
                          args={"pool": "stage_cache", "bytes": evicted})


# QUANTIZED BIN-INDEX CACHE (the shared-histogram engine's hot operand):
# compact uint8/uint16 bin matrices staged ONCE per dataset content and
# reused by every tree, every boosting round, and every CV fold that
# re-fits on the same rows. Kept SEPARATE from the general staging cache
# (its own byte budget, sml.tree.binCacheBytes) so a burst of fold stacks
# or predict batches cannot evict the bins mid-grid; entries are LRU by
# touch order.
_bin_stage_cache: "dict" = {}
_bin_stage_bytes: list = [0]


def _bin_cache_budget() -> int:
    from ..conf import GLOBAL_CONF
    return GLOBAL_CONF.getInt("sml.tree.binCacheBytes")


def _bin_tag(mesh) -> tuple:
    return (id(mesh), "bins", meshlib.data_width(mesh))


def _bin_cache_key(a: np.ndarray, mesh) -> tuple:
    return (_memo_key(a),) + _bin_tag(mesh)


def _bin_cache_touch(key):
    """LRU probe: returns the cached device array (touched to the end of
    eviction order) or None."""
    with _stage_lock:
        hit = _bin_stage_cache.get(key)
        if hit is not None:
            # move-to-end LRU touch (dicts iterate in insertion order)
            _bin_stage_cache.pop(key)
            _bin_stage_cache[key] = hit
    return hit


def _bin_cache_store(key, hit) -> None:
    """Insert + LRU/ledger accounting shared by `stage_bins_cached` and
    the chunked-ingest assembly (`insert_bins_cached`)."""
    from ..obs import LEDGER, RECORDER
    from ..utils.profiler import PROFILER
    stored = evicted = 0
    with _stage_lock:
        if key not in _bin_stage_cache:
            _bin_stage_cache[key] = hit
            _bin_stage_bytes[0] += hit.nbytes
            stored = hit.nbytes
            budget = _bin_cache_budget()
            while _bin_stage_bytes[0] > budget and len(_bin_stage_cache) > 1:
                old = next(iter(_bin_stage_cache))
                old_bytes = _bin_stage_cache.pop(old).nbytes
                _bin_stage_bytes[0] -= old_bytes
                evicted += old_bytes
    if stored:
        LEDGER.alloc("bin_cache", stored)
    if evicted:
        LEDGER.free("bin_cache", evicted)
        PROFILER.count("staging.bin_evict_bytes", float(evicted))
        if RECORDER.enabled:
            RECORDER.emit("cache", "cache.evict",
                          args={"pool": "bin_cache", "bytes": evicted})


def stage_bins_cached(binned: np.ndarray) -> jax.Array:
    """device_put a quantized bin-index matrix through the bin cache.

    Rows are bucket-padded exactly like `stage_rows_cached`, so aligned
    per-row arrays (labels, masks) staged through the general cache land
    on the same padded shape."""
    from ..utils.profiler import PROFILER
    mesh = meshlib.get_mesh()
    n_dev = meshlib.data_width(mesh)
    a, key, hit = _keyed(binned, _bin_tag(mesh), _bin_cache_touch)
    if hit is not None:
        PROFILER.count("staging.bin_cache_hit")
        PROFILER.count("staging.h2d_bytes_saved", a.nbytes)
        return hit
    padded, buf = _padded_rows(a, meshlib.bucket_rows(a.shape[0], n_dev), mesh)
    hit = _put(padded, meshlib.data_sharding(mesh, padded.ndim), buf)
    _bin_cache_store(key, hit)
    PROFILER.count("staging.bin_cache_miss")
    PROFILER.count("staging.h2d_bytes", padded.nbytes)
    return hit


def bin_cache_probe(binned: np.ndarray) -> Optional[jax.Array]:
    """Cache probe WITHOUT staging on miss (the chunked ingest asks
    before paying a second pass over the source)."""
    mesh = meshlib.get_mesh()
    a = _normalize(binned)
    return _bin_cache_touch(_bin_cache_key(a, mesh))


def insert_bins_cached(binned_host: np.ndarray, dev: jax.Array) -> jax.Array:
    """Adopt an EXTERNALLY ASSEMBLED device bin matrix (the chunked
    ingest's per-chunk device-side assembly) into the bin cache under
    the standard content key of its host mirror, so every later fit,
    predict, and eval on the same rows hits the assembled copy exactly
    as if `stage_bins_cached` had staged it in one shot. The array is
    resharded to the canonical data sharding if assembly left it
    elsewhere (device-to-device, never back through the host)."""
    mesh = meshlib.get_mesh()
    a = _normalize(binned_host)
    expect = meshlib.data_sharding(mesh, dev.ndim)
    if getattr(dev, "sharding", None) != expect:
        dev = jax.device_put(dev, expect)
    key = _bin_cache_key(a, mesh)
    _bin_cache_store(key, dev)
    return _bin_cache_touch(key)


def bin_cache_stats() -> dict:
    """(entries, bytes) snapshot — test/debug surface for the bin cache."""
    with _stage_lock:
        return {"entries": len(_bin_stage_cache),
                "bytes": _bin_stage_bytes[0]}


def bin_cache_arrays() -> list:
    """The staged device bin matrices, least recently used first — where
    the quantized operand actually lives (`chip_smoke.py`'s placement
    proof reads each array's devices and addressable shards)."""
    with _stage_lock:
        return list(_bin_stage_cache.values())


# ----------------------------------------------------- chunked bin assembly
# The out-of-core ingest path (ml/_chunked.py) builds the device-resident
# compact matrix CHUNK BY CHUNK: each quantized block H2Ds into a small
# transient buffer (ledger pool `chunk_stage`) and a donated
# dynamic_update_slice program folds it into the padded bin matrix — the
# "bin accumulate" device work the prefetch pipeline overlaps with the
# next chunk's host quantization. HBM therefore holds the COMPACT matrix
# plus ~prefetchChunks chunk blocks, never the raw float data.
_chunk_assemble_prog: list = []


def _chunk_assemble_step(buf, block, start):
    """Rows [start, start+block_rows) of `buf` become `block`. `buf` is
    DONATED (arg 0): on real devices the update is in place, so assembly
    never holds two copies of the matrix in HBM (XLA:CPU ignores
    donation and copies — correct, just unamortized, like every other
    donation site on the test mesh)."""
    return jax.lax.dynamic_update_slice(buf, block, (start, 0))


def _chunk_assemble_program():
    """The one compiled assembly program. jit specializes per
    (buf, block) shape/dtype/sharding internally; the chunk OFFSET rides
    as a traced scalar, so every chunk of an ingest shares one
    executable (note_compile records the program once — per-shape
    re-specializations are jit-internal, like the other program
    caches)."""
    if not _chunk_assemble_prog:
        from ..obs import note_compile
        note_compile("chunk_assemble")
        _chunk_assemble_prog.append(
            jax.jit(_chunk_assemble_step, donate_argnums=(0,)))
    return _chunk_assemble_prog[0]


@contextlib.contextmanager
def transient_hbm(pool: str, nbytes: int):
    """Account a dispatch's dominant TRANSIENT device working set in the
    HBM ledger for the duration of the call (alloc on entry, free on
    exit) — live/peak visibility for program-internal buffers the staging
    caches never own. The tree fit paths charge the dispatch-long one-hot
    resident (`hist_onehot`) through this. No-ops on nbytes <= 0."""
    if nbytes <= 0:
        yield
        return
    from ..obs import LEDGER
    LEDGER.alloc(pool, int(nbytes))
    try:
        yield
    finally:
        LEDGER.free(pool, int(nbytes))


def stage_rows_cached(a, pad_to_multiple: bool = True) -> jax.Array:
    """device_put a row-sharded array through the content cache; of a
    `RowsLast` block the last axis is the one padded and sharded."""
    return _stage_rows(a, pad_to_multiple)


def stage_aligned_cached(arr: np.ndarray, n_padded: int) -> jax.Array:
    """device_put a per-row array as float32 with zero rows up to
    `n_padded`, the rows of a block staged before, through the content
    cache under the key of the PADDED array."""
    padded, buf = _padded_rows(arr, n_padded, meshlib.get_mesh(),
                               dtype=np.float32)
    return _stage_rows(padded, False, buf)


def _stage_rows(a, pad_to_multiple: bool, buf: Optional[np.ndarray] = None):
    """`stage_rows_cached`; `buf` is the pad pool's buffer that an array
    the caller padded itself lies in."""
    from ..utils.profiler import PROFILER
    mesh = meshlib.get_mesh()
    n_dev = meshlib.data_width(mesh)
    a, rows_last = _rows_axis(a)
    a, key, hit = _keyed(
        a, (id(mesh), "arrT" if rows_last else "arr", n_dev))
    if hit is None:
        padded = a
        if pad_to_multiple:
            axis = -1 if rows_last else 0
            padded, buf = _padded_rows(
                a, meshlib.bucket_rows(a.shape[axis], n_dev), mesh, axis)
        sharding = meshlib.data_sharding(mesh, padded.ndim)
        if rows_last:
            sharding = NamedSharding(mesh, P(
                *([None] * (padded.ndim - 1)), meshlib.row_spec_entry(mesh)))
        hit = _put(padded, sharding, buf)
        _cache_put(key, hit)
        PROFILER.count("staging.cache_miss")
        PROFILER.count("staging.h2d_bytes", padded.nbytes)
    else:
        if buf is not None:
            _PAD_POOL.give_back(buf)
        PROFILER.count("staging.cache_hit")
        PROFILER.count("staging.h2d_bytes_saved", a.nbytes)
    return hit


def stage_stacked_cached(a: np.ndarray) -> jax.Array:
    """device_put a FOLD-STACKED array (folds, rows, ...) through the
    content cache, rows (axis 1) sharded over the data axis, fold axis
    replicated across shards. The caller pre-pads axis 1 to a multiple of
    the mesh's data dimension. Used by the batched fold×param tree fits."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = meshlib.get_mesh()
    n_dev = meshlib.data_width(mesh)
    a, key, hit = _keyed(a, (id(mesh), "stack", n_dev))
    from ..utils.profiler import PROFILER
    if hit is None:
        spec = P(None, meshlib.row_spec_entry(mesh),
                 *([None] * (a.ndim - 2)))
        hit = _put(a, NamedSharding(mesh, spec))
        _cache_put(key, hit)
        PROFILER.count("staging.cache_miss")
        PROFILER.count("staging.h2d_bytes", a.nbytes)
    else:
        PROFILER.count("staging.cache_hit")
        PROFILER.count("staging.h2d_bytes_saved", a.nbytes)
    return hit


def stage_trial_stacked_cached(a: np.ndarray, mesh) -> jax.Array:
    """device_put an ELEMENT-STACKED array (elems, rows, ...) through the
    content cache onto a 2-D trial mesh (`meshlib.trial_mesh`): trial
    elements shard over TRIAL_AXIS, rows over DATA_AXIS — the resident
    layout of cross-chip trial parallelism. The caller pre-pads axis 0 to
    a multiple of the trial dim and axis 1 to a multiple of the FULL
    device count (so any data-axis width divides it)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    a, key, hit = _keyed(
        a, (id(mesh), "tstack", mesh.shape[meshlib.TRIAL_AXIS],
            mesh.shape[meshlib.DATA_AXIS]))
    from ..utils.profiler import PROFILER
    if hit is None:
        spec = P(meshlib.TRIAL_AXIS, meshlib.DATA_AXIS,
                 *([None] * (a.ndim - 2)))
        hit = _put(a, NamedSharding(mesh, spec))
        _cache_put(key, hit)
        PROFILER.count("staging.cache_miss")
        PROFILER.count("staging.h2d_bytes", a.nbytes)
    else:
        PROFILER.count("staging.cache_hit")
        PROFILER.count("staging.h2d_bytes_saved", a.nbytes)
    return hit


def stage_mask_cached(n_padded: int, n_true: int) -> jax.Array:
    mesh = meshlib.get_mesh()
    mkey = (n_padded, n_true, id(mesh), "mask",
            meshlib.data_width(mesh))
    hit = _stage_cache.get(mkey)
    if hit is None:
        # the mask is made here, not handed in: its fill is its pad step
        mask, buf = _zero_tailed((n_padded,), np.float32, 0, 1.0, n_true,
                                 4 * n_padded, mesh)
        hit = _put(mask, meshlib.data_sharding(mesh, 1), buf)
        _cache_put(mkey, hit)
    return hit


def _route_mesh(hint, arrays, may_promote: bool = True,
                stacked: bool = False) -> Tuple[object, str]:
    """Stage-aware dispatch: charge the H2D term only for bytes NOT already
    resident on the device mesh, and when the device loses solely because
    of that one-time staging cost, promote the arrays in the background
    (device_put is async) so the NEXT fit on this dataset rides the chip —
    repeated fits (CV folds, tuning trials, warm benchmarks) converge to
    device-resident execution without explicit placement.

    Returns (mesh, route): callers that want a plain-numpy fast path must
    branch on route == "host", NOT on the mesh's device platform — on a
    CPU-backend process the *device* route legitimately runs on a CPU mesh
    (the virtual test mesh).

    `may_promote` distinguishes fit paths (datasets that WILL be re-used:
    CV folds, tuning trials) from one-shot predict batches — promoting a
    streaming batch would waste H2D bandwidth on data never seen
    again."""
    import dataclasses

    from ..conf import GLOBAL_CONF
    pre = dispatch.preroute(hint)
    if pre is not None:  # forced route: skip the probe entirely
        dispatch.audit_preroute(hint, pre)  # flight-recorder receipt
        return (meshlib.get_mesh() if pre == "device"
                else dispatch.host_mesh()), pre
    resident = dispatch.WorkHint(hint.flops, hint.kind, hint.out_bytes, None)
    if dispatch.decide(resident, _record=False)[0] == "host":
        # the device loses even with everything resident: no point hashing
        # the arrays to price their H2D (hot on per-batch predict paths).
        # The probe was unrecorded (_record=False); THIS is the dispatch
        # decision, so it gets exactly one audit row
        dispatch.audit_decision(resident, "host")
        return dispatch.host_mesh(), "host"
    dev_mesh = meshlib.get_mesh()
    n_dev = meshlib.data_width(dev_mesh)
    eff = hint
    keyed = []
    if arrays:
        unstaged = 0.0
        for given in arrays:
            a, rows_last = _rows_axis(given)
            kind = "stack" if stacked else "arrT" if rows_last else "arr"
            a = _normalize(a)
            ck = _memo_key(a)
            key = (ck, id(dev_mesh), kind, n_dev)
            bkey = (ck, id(dev_mesh), "bins", n_dev)
            # quantized bin matrices live in their OWN cache (see
            # stage_bins_cached) — charge H2D only when absent from both
            if key not in _stage_cache and bkey not in _bin_stage_cache:
                unstaged += a.nbytes
            keyed.append(RowsLast(a) if rows_last else a)
        eff = dataclasses.replace(hint,
                                  in_bytes=unstaged if unstaged else None)
    route, promote = dispatch.decide(eff)
    if route == "device":
        return dev_mesh, "device"
    if promote and may_promote and keyed \
            and GLOBAL_CONF.getBool("sml.dispatch.autoPromote"):
        for a in keyed:
            # async put under the device mesh, in the layout AND cache the
            # program will actually read (probing "arr" keys while the
            # program stages "stack" layouts would promote dead copies;
            # likewise a compact bin matrix must land in the bin cache the
            # tree/predict programs probe, not the general rows cache)
            if stacked:
                stage_stacked_cached(a)
            elif not isinstance(a, RowsLast) and _is_bin_matrix(a):
                stage_bins_cached(a)
            else:
                stage_rows_cached(a)
    return dispatch.host_mesh(), "host"


def _is_bin_matrix(a: np.ndarray) -> bool:
    """The quantized engine's staging discriminator: compact (uint8/uint16)
    2-D matrices are quantized bin indices — only `tree_impl.bin_dtype`
    produces them. Wider integer matrices (CompactParts.codes is int32,
    ALS id columns are 1-D) stay in the general rows cache, so a burst of
    compact linear fits cannot evict hot bins from the tree budget."""
    return a.ndim == 2 and a.dtype.kind == "u" and a.dtype.itemsize <= 2


@contextlib.contextmanager
def shared_keys():
    """The per-thread key memo (`_memo_key`) for the length of the block:
    every array this thread stages or probes inside it is hashed once.
    `routed_for` opens one a program; a caller that dispatches several
    programs over the same arrays (a validator's folds and its refit)
    opens one round them all, and the inner ones then leave it be."""
    had_memo = getattr(_tls_keys, "memo", None)
    if had_memo is None:
        _tls_keys.memo = {}
    try:
        yield
    finally:
        if had_memo is None:
            _tls_keys.memo = None


@contextlib.contextmanager
def routed_for(hint, *arrays, stacked: bool = False):
    """Context manager binding the stage-aware dispatch decision as the
    thread's active mesh (see _route_mesh). Also installs the per-thread
    key memo so the probe's fingerprints are reused by the stage.
    `stacked=True` prices/promotes fold-stacked (folds, rows, ...) arrays
    in their axis-1-sharded layout."""
    with shared_keys():
        mesh, _ = _route_mesh(hint, arrays, stacked=stacked)
        with meshlib.use_mesh_local(mesh):
            yield mesh


def route_for_arrays(hint, *arrays) -> Tuple[object, str]:
    """One-shot stage-aware decision for predict paths that want a plain
    host-numpy fast path: returns (mesh, route). Never promotes — predict
    batches are one-shot; only fit paths (routed_for) bet on re-use."""
    return _route_mesh(hint, arrays, may_promote=False)


def stage_sharded(*arrays):
    """Pad + shard host arrays by rows over the data axis (a `RowsLast`
    block by its last axis).

    Returns (device_arrays..., mask_device, n_true). The mask is 1.0 for real
    rows, 0.0 for padding; all statistics must be mask-weighted so padding is
    inert under psum.

    Results are memoized by content: CV folds, hyperopt trials, and repeated
    fits re-stage identical arrays constantly, and each fresh H2D pays a
    transfer plus a fixed sync cost at first use.

    Quantized bin-index matrices (compact uint8/uint16 2-D — see
    `_is_bin_matrix`) stage through the dedicated bin cache so fit,
    predict, and eval-pushdown programs share ONE device copy per dataset
    under its own byte budget.
    """
    outs = [stage_rows_cached(a)
            if isinstance(a, RowsLast) or not _is_bin_matrix(np.asarray(a))
            else stage_bins_cached(a) for a in arrays]
    n_true = _n_rows(arrays[0])
    n_padded = outs[0].shape[-1 if isinstance(arrays[0], RowsLast) else 0]
    mask_dev = stage_mask_cached(n_padded, n_true)
    return (*outs, mask_dev, n_true)


def data_parallel(fn: Callable, *, out_replicated: bool = True,
                  replicated_argnums: Tuple[int, ...] = (),
                  name: Optional[str] = None,
                  rows_last_argnums: Tuple[int, ...] = ()) -> Callable:
    """jit(shard_map(fn)) over the active mesh's data axis.

    `fn` sees per-chip row blocks and may call `parallel.collectives.psum`
    etc. on the "data" axis; outputs are replicated (each chip returns the
    same reduced value) unless out_replicated=False (then row-sharded).
    Args listed in `replicated_argnums` (rng keys, small parameter vectors)
    are broadcast to every chip instead of row-sharded. `name` names the
    jitted program (`jit_<name>` in a profiler trace and in the compile
    cache's key) where "wrapped" would say nothing. Args listed in
    `rows_last_argnums` are sharded by their LAST axis (`RowsLast`).

    Donation is deliberately NOT offered here: any input of a
    data_parallel program may be a staging-cache-owned buffer, and
    donating one would poison every later cache hit. The one donation
    site (the chunked boosting scan's margin carry) builds its own
    shard_map+jit in `tree_impl._compiled_chunk`.
    """
    mesh = meshlib.get_mesh()
    out_spec = P() if out_replicated else P(meshlib.row_spec_entry(mesh))

    def spec_for(i, x):
        if i in replicated_argnums:
            return P()
        lead, rows = [None] * (np.ndim(x) - 1), [meshlib.row_spec_entry(mesh)]
        return P(*(lead + rows if i in rows_last_argnums else rows + lead))

    def wrapped(*args):
        specs = tuple(spec_for(i, a) for i, a in enumerate(args))
        mapped = meshlib.shard_map_compat(fn, mesh=mesh, in_specs=specs,
                                          out_specs=out_spec)
        return mapped(*args)

    if name:
        wrapped.__name__ = wrapped.__qualname__ = name
    return jax.jit(wrapped)


_compiled_cache: dict = {}


class _RecordingProgram:
    """Thin callable over a compiled data_parallel program that records a
    prewarm signature per DISTINCT arg-shape set (each set is its own XLA
    executable; the manifest must name them all). Per-call cost once a
    shape is seen: one tuple build + one set lookup."""

    __slots__ = ("_compiled", "_src", "_flags", "_seen")

    def __init__(self, compiled, src, flags):
        self._compiled = compiled
        self._src = src
        self._flags = flags
        self._seen: set = set()

    def __call__(self, *args):
        sig = tuple((np.shape(a), str(getattr(a, "dtype", type(a).__name__)))
                    for a in args)
        if sig not in self._seen:
            self._seen.add(sig)
            from ..parallel import prewarm as _prewarm
            out_rep, rep_nums = self._flags
            _prewarm.record("data_parallel", {
                "src": self._src, "out_replicated": bool(out_rep),
                "replicated_argnums": list(rep_nums),
                "args": [[list(s), d] for s, d in sig]})
        return self._compiled(*args)


def cached_data_parallel(fn: Callable, *, out_replicated: bool = True,
                         replicated_argnums: Tuple[int, ...] = (),
                         rows_last_argnums: Tuple[int, ...] = ()) -> Callable:
    """data_parallel with a program cache keyed by (fn, mesh, flags).

    jax.jit caches per function object; wrapping a fresh closure per fit
    would recompile every call. Callers must pass module-level fns (stable
    identity) for the cache to hit. Programs whose fn carries a
    replayable source (module-level name or a `_prewarm` factory tag) are
    wrapped to record their shapes into the prewarm manifest.
    """
    mesh = meshlib.get_mesh()
    key = (fn, id(mesh), out_replicated, replicated_argnums,
           rows_last_argnums)
    if key not in _compiled_cache:
        from ..obs import note_compile
        from ..parallel import prewarm as _prewarm
        note_compile(getattr(fn, "__name__", "fn"))
        compiled = data_parallel(
            fn, out_replicated=out_replicated,
            replicated_argnums=replicated_argnums,
            rows_last_argnums=rows_last_argnums)
        # the manifest's replay places rows on axis 0: a feature-major
        # program (a closure over its layout, never recordable) stays out
        src = None if rows_last_argnums else _prewarm.fn_src(fn)
        if src is not None:
            compiled = _RecordingProgram(
                compiled, src, (out_replicated, replicated_argnums))
        _compiled_cache[key] = compiled
    return _compiled_cache[key]


def _replay_data_parallel(meta: dict) -> None:
    """Prewarm rebuilder for `cached_data_parallel` programs: resolve the
    fn, build through the SAME cache, and first-dispatch on zero-filled
    operands placed like the live call sites place them (rows
    data-sharded, replicated argnums left to jit placement)."""
    from ..parallel import prewarm as _prewarm
    fn = _prewarm.resolve_fn(meta["src"])
    rep = tuple(int(i) for i in meta["replicated_argnums"])
    compiled = cached_data_parallel(fn,
                                    out_replicated=bool(meta["out_replicated"]),
                                    replicated_argnums=rep)
    mesh = meshlib.get_mesh()
    args = []
    for i, (shape, dtype) in enumerate(meta["args"]):
        a = np.zeros(tuple(shape), dtype=np.dtype(dtype))
        if i in rep or a.ndim == 0:
            args.append(a)
        else:
            args.append(jax.device_put(a, meshlib.data_sharding(mesh, a.ndim)))
    jax.device_get(compiled(*args))


from ..parallel import prewarm as _prewarm_mod

_prewarm_mod.register_rebuilder("data_parallel", _replay_data_parallel)


def run_data_parallel(fn: Callable, *arrays, out_replicated: bool = True,
                      replicated: Tuple = (),
                      work: "Optional[dispatch.WorkHint]" = None):
    """One-shot: stage arrays sharded, run fn(blocks..., mask, *replicated)
    under jit+shard_map, return host numpy results. `replicated` values are
    broadcast to all chips (small parameter vectors). An array given as
    `RowsLast` is padded, sharded and handed to `fn` by its last axis.

    `work` is the caller's cost estimate; when given, the program is routed
    host/device by the dispatcher (`parallel.dispatch`).

    Inside the program's span the four phases carry the tree fit's names
    (`tree_impl._dispatch_and_read`): the staging (`fit.stage`), the call
    until it returns (`fit.dispatch`), the device finishing
    (`fit.device_wait`), the copy to the host (`fit.readback`)."""
    from ..utils.profiler import PROFILER
    last = tuple(i for i, a in enumerate(arrays) if isinstance(a, RowsLast))
    with routed_for(work, *arrays) as mesh:
        route = "host" if dispatch.is_host_mesh(mesh) else "device"
        rows = _n_rows(arrays[0]) if arrays else 0
        with PROFILER.span(f"program.{getattr(fn, '__name__', 'fn')}",
                           rows=rows, route=route):
            with PROFILER.span("fit.stage", rows=rows):
                staged = stage_sharded(*arrays)
            dev_args, mask, _ = staged[:-2], staged[-2], staged[-1]
            n_lead = len(dev_args) + 1
            rep_nums = tuple(range(n_lead, n_lead + len(replicated)))
            with PROFILER.span("fit.dispatch"):
                compiled = cached_data_parallel(
                    fn, out_replicated=out_replicated,
                    replicated_argnums=rep_nums, rows_last_argnums=last)
                out = compiled(*dev_args, mask, *replicated)
            with PROFILER.span("fit.device_wait"):
                out = jax.block_until_ready(out)
            # ONE batched device→host transfer for the whole output tree:
            # per-leaf np.asarray pays the fixed cost of a device→host read
            # once PER ARRAY
            with PROFILER.span("fit.readback"):
                host = jax.device_get(out)
            PROFILER.count("staging.d2h_bytes", sum(
                np.asarray(x).nbytes for x in jax.tree_util.tree_leaves(host)))
            return host
