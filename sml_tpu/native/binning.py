"""ctypes wrapper for the C++ binning kernel, with NumPy parity fallback.

`row_binner(edges_list, remaps)` packs one quantization's edge rows and
category rank tables and returns a callable that bins a BLOCK OF ROWS, all
columns, straight into rows of the compact result — semantics identical to
the NumPy expressions

    np.searchsorted(edges_f, X[:, f], side="left")  # then non-finite → 0
    rank_f[np.clip(X[:, f].astype(np.int64), 0, len(rank_f) - 1)]

(on a block whose values equal to `missing`, where a fit has one, read NaN)
of `ml.tree_impl._bin_rows_numpy`, which runs where no compiler built the
library; parity tests pin the two implementations against each other. The
kernel starts no thread: `tree_impl._bin_columns` gives each block to one
task of the process's pool, and ctypes releases the interpreter lock for
the call.
"""

from __future__ import annotations

import ctypes
from typing import Dict, List, Optional

import numpy as np

from .build import load_library

_sig_ready = False
_OUT_DTYPES = (np.dtype(np.uint8), np.dtype(np.uint16), np.dtype(np.int32))


def _lib() -> Optional[ctypes.CDLL]:
    global _sig_ready
    lib = load_library("binning")
    if lib is not None and not _sig_ready:
        lib.bin_rows.argtypes = [
            ctypes.c_void_p, ctypes.c_int32, ctypes.c_int64, ctypes.c_int32,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_int32, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_double, ctypes.c_void_p, ctypes.c_int32]
        lib.bin_rows.restype = ctypes.c_int
        lib.group_labels.argtypes = [
            ctypes.c_void_p, ctypes.c_int32, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_int32, ctypes.c_void_p, ctypes.c_void_p]
        lib.group_labels.restype = ctypes.c_int
        _sig_ready = True
    return lib


class RowBinner:
    """One quantization's tables, packed once for every block: the edge
    rows padded with +inf into an (F, width) float32 block (width a
    multiple of the kernel's 16 lanes) with their lengths, the rank tables
    end to end with each slot's offset and cardinality (0 marks a
    continuous slot), and the value the kernel reads as NaN (`missing`;
    NaN, which equals nothing, where there is none). The arrays live as
    long as the binner, so a call may hand their addresses to the
    kernel."""

    def __init__(self, lib: ctypes.CDLL, edges_list: List[np.ndarray],
                 remaps: Dict[int, np.ndarray],
                 missing: Optional[float] = None):
        F = len(edges_list)
        self._fn = lib.bin_rows
        self.F = F
        self.missing = float("nan") if missing is None else float(missing)
        width = max((len(e) for e in edges_list), default=0)
        self.edges = np.full((F, max(-(-width // 16), 1) * 16), np.inf,
                             dtype=np.float32)
        self.n_edges = np.zeros(F, dtype=np.int32)
        self.cards = np.zeros(F, dtype=np.int64)
        self.rank_lo = np.zeros(F, dtype=np.int64)
        tables, lo = [], 0
        for f, e in enumerate(edges_list):
            rank = remaps.get(f)
            if rank is None:
                self.edges[f, :len(e)] = e
                self.n_edges[f] = len(e)
            else:
                self.cards[f], self.rank_lo[f] = len(rank), lo
                tables.append(np.asarray(rank, dtype=np.int32))
                lo += len(rank)
        self.ranks = np.concatenate(tables) if tables \
            else np.zeros(1, dtype=np.int32)

    def __call__(self, X: np.ndarray, out: np.ndarray) -> bool:
        """Bin the rows of X (n, F) into `out` (n, F); False, with nothing
        written, where the kernel has no form for these arrays (the
        caller then runs the NumPy implementation)."""
        n, F = X.shape
        if F != self.F or out.shape != X.shape \
                or out.dtype not in _OUT_DTYPES \
                or not out.flags.c_contiguous:
            return False
        # keep the input dtype: an f32 block (the fused feature path's
        # layout) binned through an f64 copy would double peak memory
        if X.dtype != np.float32 and X.dtype != np.float64:
            X = X.astype(np.float64)
        size = X.dtype.itemsize
        if X.strides[0] % size or X.strides[1] % size:
            X = np.ascontiguousarray(X)
        return self._fn(
            X.ctypes.data, size, n, F,
            X.strides[0] // size, X.strides[1] // size,
            self.edges.ctypes.data, self.n_edges.ctypes.data,
            self.edges.shape[1], self.ranks.ctypes.data,
            self.rank_lo.ctypes.data, self.cards.ctypes.data, self.missing,
            out.ctypes.data, out.dtype.itemsize) == 0


def row_binner(edges_list: List[np.ndarray], remaps: Dict[int, np.ndarray],
               missing: Optional[float] = None) -> Optional[RowBinner]:
    """The native kernel over these tables, or None when the library is
    unavailable (the caller uses the NumPy path)."""
    lib = _lib()
    if lib is None or any(len(r) == 0 for r in remaps.values()):
        return None
    return RowBinner(lib, edges_list, remaps, missing)


def group_labels(col: np.ndarray, card: int, y: Optional[np.ndarray]):
    """(rows a category, y's values category by category, each category's
    in row order) of one categorical column, from one grouping of its
    rows: `np.bincount(ids)` and `y[np.argsort(ids, kind="stable")]` for
    ids = clip(int64(col), 0, card - 1). The second is None where y is;
    the result is None where the kernel is unavailable or has no form for
    these arrays (the caller groups in NumPy)."""
    lib = _lib()
    if lib is None or card < 1 or col.ndim != 1 \
            or col.dtype not in (np.float32, np.float64) \
            or not col.flags.c_contiguous:
        return None
    if y is not None and (y.shape != col.shape or not y.flags.c_contiguous
                          or y.dtype.itemsize not in (4, 8)):
        return None
    counts = np.empty(card, dtype=np.int64)
    grouped = None if y is None else np.empty_like(y)
    failed = lib.group_labels(
        col.ctypes.data, col.dtype.itemsize, len(col), card,
        None if y is None else y.ctypes.data,
        0 if y is None else y.dtype.itemsize, counts.ctypes.data,
        None if y is None else grouped.ctypes.data)
    return None if failed else (counts, grouped)


def bin_continuous(X: np.ndarray, edges_list: List[np.ndarray],
                   categorical: Dict[int, int]) -> Optional[np.ndarray]:
    """(n, F) int32 bins of the CONTINUOUS slots via the native kernel
    (categorical slots left 0), or None when the kernel is unavailable."""
    n, F = X.shape
    # a one-entry rank table of 0 bins a slot to 0 whatever it holds
    binner = row_binner(edges_list, {int(f): np.zeros(1, dtype=np.int32)
                                     for f in categorical if 0 <= int(f) < F})
    if binner is None or n == 0 or F == 0:
        return None
    out = np.empty((n, F), dtype=np.int32)
    return out if binner(X, out) else None
