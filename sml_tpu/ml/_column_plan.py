"""Fit-time column plan: one job a raw column, the columns side by side.

`featurizer._try_fast_fit` recognises the course's chain
[Imputer?, StringIndexer?, OneHotEncoder?, VectorAssembler, estimator], or
[RFormula, estimator] (a formula IS such a chain: `RFormula._chain`), and
hands this module one JOB for every raw column the chain reads. A job
visits its column once and does everything the column needs: the stage's
fit statistic (the Imputer's surrogate, the StringIndexer's labels) AND
the column's values for the feature block. Jobs share nothing, so they run
side by side on one pool of host threads; the results do not depend on
how many workers there are or on the order the jobs finish in.

A job reads its column WHERE IT LIES: the frame's materialized partitions
(`Pieces`), piece by piece, in the order the table-wide concat would lay
them. No such concat is made on the plan's path (`DataFrame.toPandas` was
one thread copying every column of the table, the ones no job reads too,
before a job could start: PERF.md section 6, PR 33); a job gathers ITS
column alone where a statistic needs all of it (a median), beside the other
jobs. A frame that already holds its concat, or has one partition, is one
piece, and a job of one piece is the code as it was.

No two threads store into the same cache lines: a job writes its column
CONTIGUOUSLY, one row of a (jobs, n) float32 scratch, and the row-major
(n, d) block the estimators take is interleaved from the scratch in
blocks of rows, each block one task of the same pool, with the
assembler's finite check on the block just written (ten threads each
storing every tenth float of the block move it through memory ten times:
PERF.md section 6, PR 29).

Every bit of the result is the sequential code's (`Imputer._fit`,
`StringIndexer._fit`, `CompiledFeaturizer.transform_with_mask`): a column
is float64 until its write, and where a value can only be had from the
pandas call (a mean's summation order, a mode's tie) or the column's
storage has no path that releases the interpreter lock (object strings, a
numeric column fed to an indexer), the job makes today's call on its
column, correct and no faster (`legacy` in its result). A column whose
pieces disagree in storage (an all-null piece read back as object or
float, object strings in one piece) is gathered to the ONE pandas column
the concat would hold, and the job runs on that as on a table of one piece.

Jobs open no spans and bump no counters: the caller's thread does both,
so `span_s.*` stay wall seconds of one thread. What a job took is two
clock reads around it, and the slowest job's seconds (`Plan.longest_s`)
ride the caller's span `fit.featurize.plan.jobs`.
"""

from __future__ import annotations

import os
import threading
from concurrent.futures import ThreadPoolExecutor, wait
from typing import List, NamedTuple, Optional

import numpy as np
import pandas as pd

from ..parallel.pipeline import mark_host_worker, on_host_worker
from ..utils.profiler import PROFILER, now
from .feature import imputer_surrogate, indexer_labels, order_labels
from .featurizer import _IndexSource, _numeric

#: rows of the block one interleave task writes: 65,536 x 10 float32 is
#: 2.6 MB, read from ten contiguous runs and stored once (and the rows one
#: digitize task of the quantize plan bins, `tree_impl._bin_columns`)
_BLOCK_ROWS = 65536

#: below this many rows the jobs run inline on the calling thread: waking
#: the pool costs what the jobs of such a table do. The course's chain
#: (3 strings, 7 medians) on the chip tool's one-chip host, 13 cores, inline
#: against pool, median ms of 30: 16,000 rows 3.9 / 7.6, 48,000 8.7 / 9.5,
#: 64,000 11.1 / 10.4, 96,000 16.7 / 10.7, 256,000 41.8 / 12.8 (PERF.md
#: section 6, PR 29). The quantize plan shares the threshold; its own
#: crossing is lower (`make_bins` on the same host: 16,000 rows 6.5 / 6.4,
#: 32,000 10.7 / 8.2, 65,536 20.3 / 10.8; PR 31): one rule for both, at
#: most 10 ms dearer for a table between the two. Under it a frame of
#: several partitions is also read as its ONE concat (`Pieces.of`): a pandas
#: Series a piece a column costs more than the concat of such a table (same
#: chain and host, 8 partitions, inline, the concat's table / the pieces,
#: median ms of 30: 1,005 rows 3.60 / 6.16, 7,002 4.67 / 7.30, 64,897
#: 16.82 / 17.36; at 1.6 M rows, pooled, 272 / 97; PR 33)
_INLINE_ROWS = 65536

_pool: Optional[ThreadPoolExecutor] = None
_pool_lock = threading.Lock()


def _cores() -> int:
    try:
        return len(os.sched_getaffinity(0))
    except AttributeError:  # not Linux
        return os.cpu_count() or 1


def _executor() -> ThreadPoolExecutor:
    """THE pool of the process, sized from the cores the process may run
    on; the quantize plan (`tree_impl.make_bins`, `_bin_columns`) runs its
    jobs on it too. `TpuTrials` and `CrossValidator(parallelism>1)` fit
    pipelines from several threads at once, and a pool a fit would
    multiply the threads. A job never waits on another job, so fits
    sharing the pool cannot deadlock."""
    global _pool
    if _pool is None:
        with _pool_lock:
            if _pool is None:
                _pool = ThreadPoolExecutor(max_workers=_cores(),
                                           thread_name_prefix="sml-column",
                                           initializer=mark_host_worker)
    return _pool


def runs_inline(rows: int) -> bool:
    """Whether tasks over `rows` rows run on the calling thread: a table
    under `_INLINE_ROWS`, or a caller that is itself a worker of one of
    the process's host pools (`ml/_chunked.py` quantizes a chunk a worker;
    a task never submits to the pool it runs on)."""
    return rows < _INLINE_ROWS or on_host_worker()


def start_tasks(tasks: list, inline: bool):
    """The tasks started (on the pool; run here at once where `inline`),
    and a call that gives every task's result, in the tasks' order. All
    of them end before an error is raised, on the caller's thread: the
    first in the tasks' order, as the sequential pass would have met it.
    Between the start and the call the caller does what it likes (a
    validator dispatches its next fold while the last one's margins are
    ranked)."""
    if inline:
        results = [t() for t in tasks]
        return lambda: results
    pool = _executor()
    futures = [pool.submit(t) for t in tasks]

    def results():
        wait(futures)
        return [f.result() for f in futures]
    return results


def run_tasks(tasks: list, inline: bool) -> list:
    """Every task's result, in the tasks' order (`start_tasks`, waited
    for)."""
    return start_tasks(tasks, inline)()


class Pieces:
    """The rows of one fit as the jobs read them: pandas tables with the
    same columns, in the order the table-wide concat lays their rows
    (`parts`). `of(frame)` lists a frame's materialized partitions, no
    copy. It adapts on what the frame shows: one that already holds its
    concat (`_pdf_cache`: it was fitted or collected before), has one
    partition, fewer than `_INLINE_ROWS` rows (a Series a piece a column
    costs more than the concat of such a table: PERF.md section 6, PR 33)
    or partitions that differ in their columns, is that one table, as
    `toPandas` gives it."""

    def __init__(self, parts: List[pd.DataFrame]):
        self.parts = parts
        self.columns = parts[0].columns
        self.rows = sum(len(p) for p in parts)
        self._gathered: dict = {}

    @classmethod
    def of(cls, frame) -> "Pieces":
        if frame._pdf_cache is None:
            parts = [p for p in frame._materialize() if len(p.columns)]
            if len(parts) > 1 and sum(map(len, parts)) >= _INLINE_ROWS \
                    and all(p.columns.equals(parts[0].columns)
                            for p in parts[1:]):
                return cls(parts)
        return cls.collected(frame)

    @classmethod
    def collected(cls, frame) -> "Pieces":
        """The frame's table-wide concat as one piece (what the generic
        sequential fit reads), counted where this call makes it: the
        frame keeps it for whoever fits it again."""
        if getattr(frame, "_pdf_cache", None) is None:
            PROFILER.count("featurize.collect.concats")
        return cls([frame.toPandas()])

    def pieces(self, col: str) -> List[pd.Series]:
        return [p[col] for p in self.parts]

    def column(self, col: str) -> pd.Series:
        """The column as the concat holds it, ONE pandas column: the
        piece's own where there is one piece, else gathered (once) by
        pandas' concat, so with its rule for the common storage of pieces
        that disagree, and its values."""
        if len(self.parts) == 1:
            return self.parts[0][col]
        got = self._gathered.get(col)
        if got is None:
            got = self._gathered[col] = pd.concat(
                self.pieces(col), ignore_index=True)
        return got

    def table(self, cols: List[str]) -> pd.DataFrame:
        """A table that holds these columns of the concat: the piece
        itself where there is one, else the gathered columns (no copy of
        them)."""
        if len(self.parts) == 1:
            return self.parts[0]
        return pd.DataFrame({c: self.column(c) for c in cols}, copy=False)

    def schema(self):
        """The concat's schema without the concat: a column's storage
        follows from the pieces' (a zero-row concat applies the same rule),
        and only a column of objects is gathered, for the look at its
        values `infer_schema_from_pandas` takes. None for one piece: the
        frame infers it from that."""
        from ..frame.types import StructType, infer_schema_from_pandas
        if len(self.parts) == 1:
            return None
        empty = pd.concat([p.iloc[:0] for p in self.parts], ignore_index=True)
        fields = infer_schema_from_pandas(empty).fields
        for i, c in enumerate(self.columns):
            if empty[c].dtype == object:
                fields[i], = infer_schema_from_pandas(
                    self.column(c).to_frame()).fields
        return StructType(fields)


class JobResult(NamedTuple):
    surrogate: Optional[float] = None      # an imputed column's fill
    labels: Optional[List[str]] = None     # an indexed column's labels
    invalid: Optional[np.ndarray] = None   # rows an indexer "skip" drops
    legacy: bool = False                   # ran today's per-column code
    has_null: bool = False                 # an indexed column held a null


class NumericJob:
    """A numeric raw column: one extraction to float64, the Imputer's
    surrogate from it, the fill, the float32 write.

    `blockwise` says the assembler slot sits in a run of two or more plain
    numeric inputs: `transform_with_mask` extracts such a run as one block
    and turns what is not finite into the fill, or into NaN where nothing
    imputes the column, while a lone unimputed column keeps its +-inf."""

    def __init__(self, col: str, strategy: Optional[str] = None):
        self.col = col
        self.strategy = strategy
        self.blockwise = False
        self.row: Optional[int] = None   # its row of the scratch, if assembled

    def run(self, src: Pieces, out: Optional[np.ndarray]) -> JobResult:
        cols = src.pieces(self.col)
        fast = all(_plain_numbers(c) for c in cols)
        if not fast and len(cols) > 1:   # as the table of one piece reads it
            cols = [src.column(self.col)]
            fast = _plain_numbers(cols[0])
        vs = None   # the column in float64, a piece an array
        if fast:
            # (a view of a float64 piece)
            vs = [c.to_numpy(np.float64) for c in cols]
        elif out is not None:
            vs = [self._extract_as_today(cols[0])]
        fill = None
        if self.strategy == "median" and fast:
            # Series.median of the non-NaN values is np.median of them, in
            # the column's order: the one gather a median needs
            vals = []
            for v in vs:
                nan = np.isnan(v)
                vals.append(v[~nan] if nan.any() else v)
            gathered = len(vals) > 1   # a buffer of this job's own
            vals = np.concatenate(vals) if gathered else vals[0]
            fill = float(np.median(vals, overwrite_input=gathered)) \
                if len(vals) else 0.0
        elif self.strategy is not None:
            fill = imputer_surrogate(src.column(self.col), self.strategy)
        if out is not None:
            lo = 0
            for v in vs:
                piece = out[lo:lo + len(v)]
                lo += len(v)
                piece[:] = v   # the float32 cast of the block assignment
                if fill is not None or self.blockwise:
                    bad = ~np.isfinite(v)
                    if bad.any():
                        piece[bad] = np.nan if fill is None else fill
        return JobResult(surrogate=fill, legacy=not fast)

    def _extract_as_today(self, col: pd.Series) -> np.ndarray:
        if self.blockwise:   # extract_numeric_block's, a column at a time
            try:
                return col.to_numpy(np.float64, na_value=np.nan)
            except (TypeError, ValueError):
                pass
        return _numeric(col)


def _plain_numbers(col: pd.Series) -> bool:
    return isinstance(col.dtype, np.dtype) and col.dtype.kind in "fiu"


def _arrow_strings(col: pd.Series):
    """The column's Arrow chunks when it is Arrow-backed STRING storage
    (as `_IndexSource._arrow_codes` decides it), else None."""
    pa_arr = getattr(getattr(col, "array", None), "_pa_array", None)
    if pa_arr is None:
        return None
    import pyarrow as pa
    t = pa_arr.type
    if pa.types.is_string(t) or pa.types.is_large_string(t) \
            or pa.types.is_string_view(t):
        return pa_arr
    return None


class StringJob:
    """A StringIndexer input column: ONE encoding of the column (codes and
    distinct values), the labels from the counts of the codes, the codes
    carried through the rank table into the scratch row, and the column's
    own invalid-row handling (`error` raises the stage's message, `skip`
    returns the mask, `keep` maps to len(labels)). An unassembled column
    only has its labels made, as the sequential fast fit never resolved
    it either."""

    def __init__(self, col: str, order: str, invalid: str):
        self.col = col
        self.order = order
        self.invalid = invalid
        self.row: Optional[int] = None

    def run(self, src: Pieces, out: Optional[np.ndarray]) -> JobResult:
        cols = src.pieces(self.col)
        pa_arr = _arrow_strings(cols[0])
        if len(cols) > 1:
            # the pieces' chunks as ONE chunked array, no copy (what the
            # concat of Arrow string storage is); pieces that disagree in
            # storage run as the table of one piece does
            arrs = [_arrow_strings(c) for c in cols]
            if any(a is None or a.type != pa_arr.type for a in arrs) \
                    or any(c.dtype != cols[0].dtype for c in cols):
                pa_arr = _arrow_strings(src.column(self.col))
            else:
                import pyarrow as pa
                pa_arr = pa.chunked_array(
                    [ch for a in arrs for ch in a.chunks], type=pa_arr.type)
        if pa_arr is None:
            return self._run_as_today(src.column(self.col), out)
        enc = pa_arr.dictionary_encode().unify_dictionaries()
        values = enc.chunk(0).dictionary.to_pylist() if enc.num_chunks else []
        k = len(values)
        # nulls take code k: one table lookup resolves them with the rest
        codes = [c.indices.fill_null(k).to_numpy(zero_copy_only=False)
                 for c in enc.chunks]
        counts = np.zeros(k + 1, dtype=np.int64)
        for c in codes:
            counts += np.bincount(c, minlength=k + 1)
        labels = order_labels(values, counts[:k], self.order)
        has_null = bool(counts[k])
        if out is None:
            return JobResult(labels=labels, has_null=has_null)
        # at fit every value that is not null has a label: invalid == null
        invalid = None
        if counts[k]:
            if self.invalid == "error":
                first = np.concatenate([c == k for c in codes]).argmax()
                null = src.column(self.col).iloc[first]   # as pandas shows it
                raise ValueError(
                    f"Unseen label {null!r} in column "
                    f"{self.col!r} (handleInvalid='error')")
            if self.invalid == "skip":
                invalid = np.concatenate([c == k for c in codes])
        rank = {lab: i for i, lab in enumerate(labels)}
        table = np.empty(k + 1, dtype=np.float32)
        table[:k] = [rank[v] for v in values]
        table[k] = len(labels) if self.invalid == "keep" else np.nan
        lo = 0
        for c in codes:
            np.take(table, c, out=out[lo:lo + len(c)], mode="clip")
            lo += len(c)
        return JobResult(labels=labels, invalid=invalid, has_null=has_null)

    def _run_as_today(self, col: pd.Series, out) -> JobResult:
        labels = indexer_labels(col, self.order)
        has_null = bool(col.isna().any())
        if out is None:
            return JobResult(labels=labels, legacy=True, has_null=has_null)
        src = _IndexSource(self.col, np.asarray(labels, dtype=object),
                           self.invalid)
        drop = np.zeros(len(col), dtype=bool)
        out[:] = src.resolve(col.to_frame(self.col), drop)
        return JobResult(labels=labels, legacy=True, has_null=has_null,
                         invalid=drop if drop.any() else None)

    def category_size(self) -> int:
        """What a OneHotEncoder fitted on this column's indices counts:
        the labels, and the extra index "keep" gives a null when the
        fitted table holds one (max index + 1)."""
        r = self.result
        return len(r.labels) + int(self.invalid == "keep" and r.has_null)


class Plan:
    """The jobs of one fit, run over the table's pieces (each keeps its
    `result`), the scratch they wrote (row i is the assembler's input i,
    its rows in the pieces' order), `block()` to interleave it and
    `compact()` to hand it over as it is. `longest_s` is the wall seconds
    of the slowest job (two clock reads a job, on the thread that runs
    it): the jobs' step cannot end before it."""

    def __init__(self, src: Pieces, jobs: list):
        self.rows = src.rows
        self.inline = runs_inline(self.rows)
        self.workers = 1 if self.inline else _cores()
        self.jobs = jobs
        assembled = sum(j.row is not None for j in jobs)
        self.scratch = scratch = np.empty(
            (assembled, self.rows), dtype=np.float32)

        def timed(j):
            t0 = now()
            r = j.run(src, None if j.row is None else scratch[j.row])
            return r, now() - t0
        results = run_tasks([lambda j=j: timed(j) for j in jobs],
                            self.inline)
        for j, (r, _) in zip(jobs, results):
            j.result = r
        self.longest_s = max((wall for _, wall in results), default=0.0)

    def _dropped(self) -> Optional[np.ndarray]:
        masks = [j.result.invalid for j in self.jobs
                 if j.result.invalid is not None]
        return np.logical_or.reduce(masks) if masks else None

    def block(self, onehot: List[Optional[int]], invalid: str):
        """(X, keep) as `transform_with_mask` returns them: the row-major
        float32 block, rows an indexer skips dropped (keep None where none
        is), and the assembler's `invalid` applied to a row that is not
        finite: "error" raises, "skip" drops it too, "keep" leaves it.
        `onehot[i]` is None where row i of the scratch is one column of
        the block, else the width its codes are expanded to."""
        n, scratch = self.rows, self.scratch
        widths = [1 if w is None else w for w in onehot]
        los = np.cumsum([0] + widths)
        drop = self._dropped()
        out = np.empty((n, los[-1]), dtype=np.float32)
        plain = all(w is None for w in onehot)

        def task(r0: int) -> Optional[np.ndarray]:
            """Writes its block; the rows of it that are not finite and
            that no indexer drops, or None where there is none."""
            r1 = min(r0 + _BLOCK_ROWS, n)
            blk = out[r0:r1]
            if plain:
                blk[...] = scratch[:, r0:r1].T
            else:
                for lo, w, row in zip(los, onehot, scratch):
                    _write_slot(blk, lo, w, row[r0:r1])
            if invalid == "keep" or np.isfinite(blk).all():
                return None
            bad = ~np.isfinite(blk).all(axis=1)
            if drop is not None:
                bad &= ~drop[r0:r1]
            return bad if bad.any() else None

        starts = range(0, n, _BLOCK_ROWS)
        bads = run_tasks([lambda r0=r0: task(r0) for r0 in starts],
                         self.inline)
        if any(b is not None for b in bads):
            if invalid == "error":
                raise ValueError(
                    "VectorAssembler found NaN/null in assembled features; "
                    "set handleInvalid='skip' or impute first")
            drop = np.zeros(n, dtype=bool) if drop is None else drop
            for r0, bad in zip(starts, bads):
                if bad is not None:
                    drop[r0:r0 + len(bad)] |= bad
        if drop is None:
            return out, None
        keep = ~drop
        return out[keep], keep

    def compact(self, onehot: List[Optional[int]], invalid: str):
        """The scratch as `featurizer.CompactParts` (its rows ARE the
        feature-major form: the numeric rows as they are, the code rows
        as int32), or None where the expanded block would carry a value
        that is not finite and the assembler does not skip the row: the
        block path raises it, or keeps it, as the stage does."""
        from .featurizer import CompactParts
        scratch = self.scratch
        drop = self._dropped()

        def not_finite(i: int) -> Optional[np.ndarray]:
            fin = np.isfinite(scratch[i])
            return None if fin.all() else ~fin

        bads = [b for b in run_tasks(
            [lambda i=i: not_finite(i) for i in range(len(scratch))],
            self.inline) if b is not None]
        if bads:
            bad = np.logical_or.reduce(bads)
            if drop is not None:
                bad &= ~drop
            if bad.any():
                if invalid != "skip":
                    return None
                drop = bad if drop is None else drop | bad
        num_rows = [i for i, w in enumerate(onehot) if w is None]
        code_rows = [i for i, w in enumerate(onehot) if w is not None]
        at = {i: k for rows in (num_rows, code_rows)
              for k, i in enumerate(rows)}   # a scratch row's place in its array
        layout = [("num", at[i]) if w is None else ("oh", at[i], w)
                  for i, w in enumerate(onehot)]
        width = sum(1 if w is None else w for w in onehot)
        # a run of numeric rows is a view of the scratch: no copy
        run = num_rows and num_rows == list(range(num_rows[0],
                                                  num_rows[-1] + 1))
        num = scratch[num_rows[0]:num_rows[-1] + 1] if run \
            else scratch[num_rows]
        codes = scratch[code_rows]
        keep = None
        if drop is not None:
            keep = ~drop
            num, codes = num[:, keep], codes[:, keep]
        return CompactParts(np.ascontiguousarray(num),
                            np.ascontiguousarray(codes.astype(np.int32)),
                            tuple(layout), width, keep)


def _write_slot(blk: np.ndarray, lo: int, onehot: Optional[int],
                src: np.ndarray) -> None:
    if onehot is None:
        blk[:, lo] = src
        return
    # _OneHotSource.write on a block of rows: a code past the width (the
    # dropped last) is a row of zeros, an invalid one a row of NaN
    hi = lo + onehot
    na = ~np.isfinite(src)
    ok = ~na & (src >= 0) & (src < onehot)
    blk[:, lo:hi] = 0.0
    blk[np.nonzero(ok)[0], lo + src[ok].astype(np.intp)] = 1.0
    if na.any():
        blk[na, lo:hi] = np.nan
