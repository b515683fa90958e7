"""Spark's randomSplit sampler, draw for draw (SURVEY §4 north star;
VERDICT r4 missing #1).

The course makes split mechanics a first-class lesson: `randomSplit(seed=42)`
results change with the partition layout (`SML/Scalable-Machine-Learning-
with-Apache-Spark/ML 02 - Linear Regression I.py:38-52`). Spark's mechanism
is a published pure algorithm, reimplemented here without a JVM:

- `Dataset.randomSplit` first SORTS each partition locally by every
  sortable column ascending (to make per-partition row order
  deterministic), then samples each weight cell
  (sql/core/.../Dataset.scala `randomSplit`).
- Each cell is a `BernoulliCellSampler(lb, ub)`: one uniform draw per row,
  row kept iff `lb <= x < ub` — no gap sampling
  (core/.../util/random/RandomSampler.scala).
- The per-partition RNG is `XORShiftRandom` seeded with
  `seed + partitionIndex`, whose init scrambles the seed through
  MurmurHash3 of a 64-BYTE buffer — `ByteBuffer.allocate(java.lang.
  Long.SIZE)` where `Long.SIZE` is 64 *bits*, so Spark actually hashes
  the 8 big-endian seed bytes followed by 56 zeros, with length-64
  finalization (core/.../util/random/XORShiftRandom.scala `hashSeed`) —
  and whose `nextDouble` is java.util.Random's two-word construction
  over the XORShift `next(bits)`.

Known deviation (documented): our frames store SQL NULL as NaN, so the
pre-split sort places missing doubles FIRST (pandas na_position) where
Spark places true NaN LAST and NULL first — frames with missing numeric
values can order ties differently. String sort is bytewise-equal to
Spark's UTF8 binary order for ASCII data.
"""

from __future__ import annotations

import ctypes
import functools
import threading
import weakref
from typing import Optional

import numpy as np
import pandas as pd

# ---------------------------------------------------------------- MurmurHash3
# scala.util.hashing.MurmurHash3.bytesHash over the buffer Spark builds in
# XORShiftRandom.hashSeed. Words are read little-endian (scala bytesHash);
# 64 bytes = 16 full words, no tail. The 56 zero words are NOT no-ops:
# each word still rotates and remixes h, and finalization xors the length.
_ARRAY_SEED = 0x3C074A61  # scala.util.hashing.MurmurHash3.arraySeed

_M = 0xFFFFFFFF


def _rotl(x: int, r: int) -> int:
    return ((x << r) | (x >> (32 - r))) & _M


def _mm3_bytes(data: bytes, seed: int) -> int:
    """murmur3_x86_32 over a word-aligned buffer (scala bytesHash
    semantics: little-endian words, length-xor finalization)."""
    h = seed & _M
    for i in range(0, len(data), 4):
        k = int.from_bytes(data[i:i + 4], "little")
        k = (k * 0xCC9E2D51) & _M
        k = _rotl(k, 15)
        k = (k * 0x1B873593) & _M
        h ^= k
        h = _rotl(h, 13)
        h = (h * 5 + 0xE6546B64) & _M
    h ^= len(data)  # finalize with length
    h ^= h >> 16
    h = (h * 0x85EBCA6B) & _M
    h ^= h >> 13
    h = (h * 0xC2B2AE35) & _M
    h ^= h >> 16
    return h


def hash_seed(seed: int) -> int:
    """XORShiftRandom.hashSeed: two chained MurmurHash3 passes over the
    64-byte buffer Spark actually hashes — `ByteBuffer.allocate(java.lang.
    Long.SIZE)` allocates Long.SIZE=64 BYTES (the constant is in bits), so
    the buffer is the seed's 8 big-endian bytes plus 56 zeros, finalized
    with length 64 -> 64-bit init state."""
    data = (seed & 0xFFFFFFFFFFFFFFFF).to_bytes(8, "big") + b"\x00" * 56
    low = _mm3_bytes(data, _ARRAY_SEED)
    high = _mm3_bytes(data, low)
    return ((high << 32) | low) & 0xFFFFFFFFFFFFFFFF


# ------------------------------------------------------------ XORShiftRandom
class XORShiftRandom:
    """Pure-python reference (the native kernel is the fast path)."""

    def __init__(self, seed: int):
        self._s = hash_seed(seed)

    def _next(self, bits: int) -> int:
        s = self._s
        x = (s ^ (s << 21)) & 0xFFFFFFFFFFFFFFFF
        x ^= x >> 35
        x = (x ^ (x << 4)) & 0xFFFFFFFFFFFFFFFF
        self._s = x
        return x & ((1 << bits) - 1)

    def next_double(self) -> float:
        return ((self._next(26) << 27) + self._next(27)) * (2.0 ** -53)


_lib_lock = threading.Lock()
_lib_state: dict = {}


def _xorshift_lib():
    with _lib_lock:
        if "lib" not in _lib_state:
            from ..native.build import load_library
            lib = load_library("xorshift")
            if lib is not None:
                lib.xorshift_fill_doubles.argtypes = [
                    ctypes.c_longlong, ctypes.c_longlong,
                    ctypes.POINTER(ctypes.c_double)]
                lib.xorshift_fill_doubles.restype = None
            _lib_state["lib"] = lib
        return _lib_state["lib"]


def partition_uniforms(seed: int, partition_index: int, n: int) -> np.ndarray:
    """The n sequential nextDouble draws Spark's sampler makes for one
    partition: XORShiftRandom(seed + partitionIndex). Every weight cell of
    one randomSplit re-draws this same sequence (Spark seeds each cell's
    sampler identically), which is what makes the splits disjoint and
    exhaustive."""
    out = np.empty(n, dtype=np.float64)
    if n == 0:
        return out
    hashed = hash_seed(seed + partition_index)
    lib = _xorshift_lib()
    if lib is not None:
        lib.xorshift_fill_doubles(
            ctypes.c_longlong(
                hashed - (1 << 64) if hashed >= (1 << 63) else hashed),
            ctypes.c_longlong(n),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
        return out
    rng = XORShiftRandom(seed + partition_index)
    rng._s = hashed  # skip re-hashing
    for i in range(n):
        out[i] = rng.next_double()
    return out


# ----------------------------------------------------- stateless per-row draws
# The out-of-core data plane (frame/_chunks.py) decides split/sample
# membership per GLOBAL ROW INDEX, not per partition stream: a stateless
# counter-based hash of (seed, row) is random-access, so any chunk can
# compute its own rows' draws without replaying a sequential stream —
# the host mirror of the PR-6 `tree_impl._sliced_draw` layout-invariance
# scheme (one replicated key, each shard slicing its block). Split
# membership is therefore bit-identical for ANY chunking of the same
# rows (tests/test_chunked_ingest.py pins it).

_U64 = np.uint64
_SPLITMIX_GAMMA = 0x9E3779B97F4A7C15  # splitmix64's golden-gamma increment


def row_uniforms(seed: int, start: int, n: int) -> np.ndarray:
    """Uniform [0, 1) draw per global row index in [start, start+n):
    splitmix64 finalizer over a (seed, index) counter — vectorized, no
    sequential state, identical per row regardless of the chunk layout
    that asked. (This is deliberately NOT the Spark-parity sampler: the
    XORShift stream is sequential per partition; the chunked plane needs
    random access.)"""
    if n == 0:
        return np.empty(0, dtype=np.float64)
    idx = np.arange(start, start + n, dtype=np.uint64)
    # mix the seed into the counter stream, then splitmix64-finalize
    z = (_U64((int(seed) * 0xD1B54A32D192ED03) & 0xFFFFFFFFFFFFFFFF)
         + (idx + _U64(1)) * _U64(_SPLITMIX_GAMMA))
    z = (z ^ (z >> _U64(30))) * _U64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> _U64(27))) * _U64(0x94D049BB133111EB)
    z = z ^ (z >> _U64(31))
    # top 53 bits -> double in [0, 1), the java/Random two-word convention
    return (z >> _U64(11)).astype(np.float64) * (2.0 ** -53)


# ------------------------------------------------------- pre-split local sort
# id(source pdf) -> (weak reference to the source, its sort order, bytes).
# What is kept is the PERMUTATION that sorts a partition (8 bytes a row),
# not a sorted copy of it, and the source is not kept alive: a cached
# frame's partitions live as long as the frame does, anything else dies
# with its materialization and takes its entry along. BYTE-bounded like
# the repo's other memos (sml.split.sortMemoBytes), least recently used
# first. (It held the source and a sorted copy, 230 bytes a row each for
# the benchmark's table: a frame of 8 M rows never fitted the bound, and
# every split of it sorted all of it again, 44 s a split: PERF.md §6.)
_sort_memo: dict = {}
_sort_memo_bytes: list = [0]
# re-entrant: an entry's weak-reference callback takes it too, and a
# source can die (and call back) while this thread holds it
_sort_lock = threading.RLock()


def _forget_order(key: int, ref) -> None:
    with _sort_lock:
        hit = _sort_memo.get(key)
        if hit is not None and hit[0] is ref:
            _sort_memo_bytes[0] -= _sort_memo.pop(key)[2]


def drop_sort_memo_for(parts) -> None:
    """Invalidate sort-memo entries sourced from these partition frames
    (DataFrame.unpersist calls this, so dropping a cached frame actually
    releases its pre-split sort copies too)."""
    if not parts:
        return
    ids = {id(p) for p in parts}
    with _sort_lock:
        for k in [k for k in _sort_memo if k in ids]:
            _sort_memo_bytes[0] -= _sort_memo.pop(k)[2]


def _sort_order(pdf: pd.DataFrame) -> Optional[np.ndarray]:
    """The permutation of Dataset.randomSplit's per-partition local sort:
    every sortable column ascending, in schema order, nulls first, stable.
    Unsortable columns (vector/extension payloads, mixed objects) are
    pruned from the sort order, as Spark prunes unsortable types; None
    where nothing is left to sort by."""
    cols = []
    for c in pdf.columns:
        dt = pdf[c].dtype
        if dt.kind in "ifubMm" or isinstance(dt, pd.StringDtype):
            cols.append(c)
        elif dt == object or "string" in str(dt) or "large_string" in str(dt):
            cols.append(c)
    keys = pdf[cols].reset_index(drop=True)   # its sorted index IS the order
    while cols:
        try:
            return _lexicographic_order(keys, cols)
        except Exception:
            # a column that passed the dtype screen but still won't sort
            # (mixed-type object payloads): drop offenders one at a time —
            # probing a head slice can miss a late mixed value
            cols.pop()
    return None


def _sorted_index(keys: pd.DataFrame, cols: list) -> np.ndarray:
    return keys.sort_values(cols, kind="stable",
                            na_position="first").index.to_numpy(copy=True)


def _lexicographic_order(keys: pd.DataFrame, cols: list) -> np.ndarray:
    """`_sorted_index(keys, cols)`, by way of the columns that decide it
    where that is shorter."""
    try:
        order = _order_by_leading_columns(keys, cols)
    except Exception:
        order = None        # whatever it tripped on, the full sort decides
    return _sorted_index(keys, cols) if order is None else order


def _order_by_leading_columns(keys: pd.DataFrame,
                              cols: list) -> Optional[np.ndarray]:
    """A sort by every column factorizes every column, and most decide
    nothing: once a leading run of columns tells the rows apart, the order
    is that run's. So: the shortest run that tells a strided sample's rows
    apart nearly as well as every column does, a sort by it, then the rows
    that still tie on it with a neighbour (equal values, or both null)
    sorted among themselves by every column. The result is the full
    sort's, row for row; None where this way is not the shorter one. For
    the benchmark's table 5 of 23 columns and a third of the time
    (PERF.md §6, PR 28)."""
    n = len(keys)
    sample = keys.iloc[::max(1, n // 4096)]
    enough = (~sample.duplicated(cols)).sum() - len(sample) // 16
    lead = next((m for m in range(1, len(cols))
                 if (~sample.duplicated(cols[:m])).sum() >= enough),
                len(cols))
    if lead == len(cols) or n < 2:
        return None
    order = _sorted_index(keys, cols[:lead])
    same = np.ones(n - 1, dtype=bool)       # row i + 1 ties with row i
    for c in cols[:lead]:
        codes = pd.factorize(keys[c].take(order))[0]      # null: -1
        same &= codes[1:] == codes[:-1]
    if not same.any():
        return order
    tied = np.flatnonzero(np.r_[same, False] | np.r_[False, same])
    if len(tied) > n // 4:
        return None
    # the tied rows hold the same places, group by group, as they do in
    # the full sort: the leading columns are the first of every column
    order[tied] = _sorted_index(keys.take(order[tied]), cols)
    return order


def presplit_order(pdf: pd.DataFrame) -> Optional[np.ndarray]:
    """`_sort_order(pdf)`, memoized per partition OBJECT: every weight cell
    of one randomSplit, and every later split of a cached frame, sorts the
    SAME partitions — k cells and n seeds must not pay k x n sorts."""
    key = id(pdf)
    with _sort_lock:
        hit = _sort_memo.get(key)
        if hit is not None and hit[0]() is pdf:
            # LRU touch (dicts iterate in insertion order)
            _sort_memo[key] = _sort_memo.pop(key)
            return hit[1]
    order = _sort_order(pdf)
    from ..conf import GLOBAL_CONF
    budget = GLOBAL_CONF.getInt("sml.split.sortMemoBytes")
    cost = 0 if order is None else int(order.nbytes)
    ref = weakref.ref(pdf, functools.partial(_forget_order, key))
    with _sort_lock:
        stale = _sort_memo.pop(key, None)
        if stale is not None:
            _sort_memo_bytes[0] -= stale[2]
        _sort_memo[key] = (ref, order, cost)
        _sort_memo_bytes[0] += cost
        # the NEWEST entry always stays: the split's remaining cells are
        # about to hit it
        while _sort_memo_bytes[0] > budget and len(_sort_memo) > 1:
            _sort_memo_bytes[0] -= _sort_memo.pop(next(iter(_sort_memo)))[2]
    return order


def presplit_sort(pdf: pd.DataFrame) -> pd.DataFrame:
    """The partition in its pre-split order (`presplit_order`)."""
    order = presplit_order(pdf)
    if order is None:
        return pdf
    return pdf.take(order).reset_index(drop=True)
