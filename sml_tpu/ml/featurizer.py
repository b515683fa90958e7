"""Compiled columnar featurizer — the serving-path fusion pass.

The reference's ML 12 lesson streams Arrow batches into a pyfunc whose
sklearn pipeline re-runs preprocessing per batch
(`SML/ML 12 - Inference with Pandas UDFs.py:101-143`). The generic path
here does the same: each feature stage's pandas fn runs in sequence,
allocating intermediate columns. For inference throughput that is pure
overhead: the chain Imputer → StringIndexer → OneHotEncoder →
VectorAssembler is a STATIC column program, so `CompiledFeaturizer`
resolves it once at scorer build time into per-slot writers that scatter
straight into ONE preallocated (n, d) float32 block — the exact layout
`_staging` ships to the chip, with no intermediate frames, vector columns,
or per-stage copies.

Falls back to None (callers keep the generic path) for any stage or
option outside the supported chain, so behavior never silently diverges.
Supported: ImputerModel / StringIndexerModel (all handleInvalid modes,
with "skip" dropping rows exactly like the stage) / OneHotEncoderModel /
VectorAssembler(handleInvalid in ("error", "keep")).
"""

from __future__ import annotations

import itertools
from functools import partial
from typing import List, NamedTuple, Optional

import numpy as np
import pandas as pd

from ..obs._recorder import RECORDER as _OBS
from ..utils.profiler import PROFILER


#: rows of the block one job of the margin pass owns
#: (`CompactParts.predict_affine_agreeing`): eight of the column plan's. A
#: job is some seventy NumPy calls, each of which gives the interpreter
#: lock up and takes it again, and on a busy pool a hand-over costs what a
#: call on 65,536 values does, so smaller blocks run one after another.
#: The course's 22 slots on the chip tool's one-chip host, 13 cores, pool,
#: median ms of 9 by rows a block (PERF.md section 6, PR 37): 6.4 M rows
#: 65,536 299 / 131,072 168 / 262,144 103 / 524,288 79 / 1,048,576 101
#: (the whole-column pass 1,652; 65,536-row blocks inline 338); 1.6 M rows
#: 75 / 44 / 27 / 33 / 54 (the whole-column pass 85)
_MARGIN_BLOCK_ROWS = 524288


def margin_jobs(rows: int):
    """(workers, blocks) of a margin pass over `rows` rows, for the span
    around it to note: the threads its jobs run on (1: the calling one,
    `_column_plan.runs_inline`) and how many jobs there are."""
    from . import _column_plan as cp
    return (1 if cp.runs_inline(rows) else cp._cores(),
            -(-rows // _MARGIN_BLOCK_ROWS))


class CompactParts(NamedTuple):
    """Compact pre-expansion form of a numeric+one-hot feature block.

    The expanded (n, d) one-hot matrix never materializes: `num` holds the
    plain numeric slots, `codes` the integer category codes, and `layout`
    records the assembler's slot order as ("num", num_col) / ("oh",
    code_col, width) entries. The device programs expand one-hots ON CHIP
    (`linear_impl._expand_masked`) — staging ships n*(p+k) words instead
    of n*d, a ~6x H2D cut at the course's schema.

    Both arrays are FEATURE-MAJOR, a slot a row and the table's rows along
    the last axis: the column plan's scratch is written that way (a job a
    contiguous row), and on the chip the last axis is the 128-lane one, so
    (17, n) float32 takes n x 24 words of HBM where (n, 17) takes n x 128
    (PERF.md section 6, PR 32: the row-major program asked the v5e for
    26 GB at 6.8 M rows).
    """
    num: np.ndarray                 # (p, n) float32 numeric slots
    codes: np.ndarray               # (k, n) int32 category codes
    layout: tuple                   # slot-order expansion recipe
    width: int                      # expanded feature count d
    keep: Optional[np.ndarray]      # row-keep mask (indexer "skip" drops)

    @property
    def rows(self) -> int:
        return self.num.shape[1]

    def take(self, ok: np.ndarray) -> "CompactParts":
        """The rows `ok` (a mask over this block's rows) keeps; `keep`
        goes on describing the surviving rows of the RAW frame."""
        if self.keep is not None:
            keep = self.keep.copy()
            keep[keep] = ok
        else:
            keep = ok
        return self._replace(num=np.ascontiguousarray(self.num[:, ok]),
                             codes=np.ascontiguousarray(self.codes[:, ok]),
                             keep=keep)

    def expand_host(self) -> np.ndarray:
        """(n, d) float32 — the exact block the generic featurizer would
        build; the memory-heavy fallback for paths that need X itself."""
        out = np.zeros((self.rows, self.width), dtype=np.float32)
        lo = 0
        for item in self.layout:
            if item[0] == "num":
                out[:, lo] = self.num[item[1]]
                lo += 1
            else:
                _, j, width = item
                idx = self.codes[j]
                ok = (idx >= 0) & (idx < width)
                rows = np.nonzero(ok)[0]
                out[rows, lo + idx[rows].astype(np.intp)] = 1.0
                lo += width
        return out

    def predict_affine(self, coef: np.ndarray, intercept: float) -> np.ndarray:
        """X @ coef + intercept without expanding: numeric dot + one
        embedding-table lookup per encoded column (w·onehot(i) == w[i])."""
        return self.predict_affine_agreeing(coef, intercept, None)[0]

    def predict_affine_agreeing(self, coef: np.ndarray, intercept: float,
                                y: Optional[np.ndarray]):
        """(margin, agreeing): `predict_affine`'s float64 margin and, for
        0/1 labels `y`, the count of rows whose `margin > 0` is their
        label (0 without `y`), from the same visit of the rows.

        A job a BLOCK OF ROWS on the column plan's pool (`_column_plan`:
        the one pool of the process, inline under its row threshold and on
        a worker thread, the same result either way): a job fills its
        slice of the margin with the intercept and walks the layout once,
        in the layout's order, so its temporaries are a block long, not
        a table, and a row's additions happen in the order of the one
        whole-column pass, whose margin this is TO THE BIT
        (`tests/test_logistic_summary.py` keeps that pass). Jobs open no
        spans and bump no counters; this thread counts where they ran,
        `linear.summary.pooled` / `.inline`."""
        from . import _column_plan as cp
        coef = np.asarray(coef, dtype=np.float64)
        intercept = float(intercept)
        n = self.rows
        margin = np.empty(n, dtype=np.float64)

        def block(r0: int) -> int:
            r1 = min(r0 + _MARGIN_BLOCK_ROWS, n)
            acc = margin[r0:r1]
            acc[:] = intercept
            lo = 0
            for item in self.layout:
                if item[0] == "num":
                    acc += coef[lo] * self.num[item[1], r0:r1]   # float64
                    lo += 1
                else:
                    _, j, width = item
                    idx = self.codes[j, r0:r1]
                    # a code past the width (the dropped last, a "keep"
                    # overflow) is a row of zeros: it reads the appended 0
                    table = np.append(coef[lo:lo + width], 0.0)
                    acc += table[np.where((idx >= 0) & (idx < width),
                                          idx, width)]
                    lo += width
            if y is None:
                return 0
            return int(np.count_nonzero((acc > 0) == y[r0:r1]))

        inline = cp.runs_inline(n)
        agreeing = sum(cp.run_tasks(
            [partial(block, r0) for r0 in range(0, n, _MARGIN_BLOCK_ROWS)],
            inline))
        if inline:
            PROFILER.count("linear.summary.inline")
        else:
            PROFILER.count("linear.summary.pooled")
        return margin, agreeing


def _numeric(col) -> np.ndarray:
    return pd.to_numeric(col, errors="coerce").to_numpy(dtype=np.float64,
                                                        na_value=np.nan)


def extract_numeric_block(pdf: pd.DataFrame, cols: List[str],
                          fills: np.ndarray) -> np.ndarray:
    """(n, k) float64 block of `cols` with per-column NaN fills — ONE
    pandas extraction with a coercion fallback for non-numeric storage.
    Shared by the fused featurizer pass and the factorized scorer so their
    coercion semantics can never diverge."""
    try:
        block = pdf[cols].to_numpy(np.float64, na_value=np.nan)
    except (TypeError, ValueError):  # non-numeric storage: coerce
        block = pdf[cols].apply(
            lambda c: pd.to_numeric(c, errors="coerce")).to_numpy(
            np.float64, na_value=np.nan)
    return np.where(np.isfinite(block), block, fills[None, :])


class _Source:
    """One resolved input column: writes its slot(s) of the output block."""

    width = 1

    def write(self, pdf: pd.DataFrame, out: np.ndarray, lo: int) -> None:
        raise NotImplementedError


class _NumericSource(_Source):
    def __init__(self, col: str, fill: Optional[float] = None):
        self.col = col
        self.fill = fill  # imputer median/mean, applied on the fly

    def write(self, pdf, out, lo):
        v = _numeric(pdf[self.col])
        if self.fill is not None:
            v = np.where(np.isfinite(v), v, self.fill)
        out[:, lo] = v


class _IndexSource(_Source):
    """StringIndexerModel output: label → ordinal, with the stage's exact
    handleInvalid semantics (error raises; keep maps to len(labels); skip
    marks the row for dropping via the featurizer-level mask)."""

    def __init__(self, col: str, labels: np.ndarray, invalid: str):
        self.col = col
        self.labels = pd.Index(labels)
        self.invalid = invalid
        self._idx_by_dtype = {}  # dtype str -> Index in the COLUMN's dtype
        self._value_sets = {}    # arrow type str -> pa.Array of labels

    def _index_for(self, col: pd.Series) -> pd.Index:
        """get_indexer against an Index in the column's own dtype skips the
        per-batch arrow→object conversion (~2x on arrow-string batches)."""
        key = str(col.dtype)
        idx = self._idx_by_dtype.get(key)
        if idx is None:
            try:
                idx = pd.Index(pd.array([str(v) for v in self.labels],
                                        dtype=col.dtype))
            except Exception:
                idx = self.labels
            self._idx_by_dtype[key] = idx
        return idx

    def _arrow_codes(self, col: pd.Series):
        """pyarrow `index_in` over the column's native chunks: ~7x faster
        than Index.get_indexer on arrow-backed STRING columns AND releases
        the GIL (batch-scoring threads actually overlap). String columns
        only: labels are strings, and a string→string cast is injective,
        so unseen and null both yield -1 exactly like get_indexer against
        a unique label index. (A numeric cast could collapse distinct
        labels — "1" and "1.0" — onto one value; those columns keep the
        fallback's string-comparison semantics.) Returns None when the
        path doesn't apply."""
        pa_arr = getattr(getattr(col, "array", None), "_pa_array", None)
        if pa_arr is None:
            return None
        try:
            import pyarrow as pa
            import pyarrow.compute as pc
            if not (pa.types.is_string(pa_arr.type)
                    or pa.types.is_large_string(pa_arr.type)
                    or pa.types.is_string_view(pa_arr.type)):
                return None
            key = str(pa_arr.type)
            vs = self._value_sets.get(key)
            if vs is None:
                vs = pa.array([str(v) for v in self.labels]).cast(
                    pa_arr.type)
                self._value_sets[key] = vs
            r = pc.index_in(pa_arr, value_set=vs)
            return np.asarray(r.fill_null(-1).to_numpy(
                zero_copy_only=False), dtype=np.int64)
        except Exception:
            return None

    def codes(self, pdf) -> np.ndarray:
        """float codes with NaN for missing/unseen (pre-handleInvalid)."""
        col = pdf[self.col]
        c = self._arrow_codes(col)
        if c is None:
            notna = col.notna().to_numpy()
            try:
                c = self._index_for(col).get_indexer(col)
            except Exception:
                c = self.labels.get_indexer(
                    col.astype(str).to_numpy(dtype=object))
            c = c.astype(np.float64)
            c[(c < 0) | ~notna] = np.nan
            return c
        # arrow path: nulls are already -1 via fill_null — no notna pass
        c = c.astype(np.float64)
        c[c < 0] = np.nan
        return c

    def resolve(self, pdf, drop_mask, sink=None) -> np.ndarray:
        c = self.codes(pdf)
        missing = ~np.isfinite(c)
        if missing.any():
            if self.invalid == "error":
                bad = pdf[self.col][missing].iloc[0]
                raise ValueError(f"Unseen label {bad!r} in column "
                                 f"{self.col!r} (handleInvalid='error')")
            if self.invalid == "skip":
                drop_mask |= missing
            else:  # keep
                c[missing] = float(len(self.labels))
        if sink is not None:  # fused-transform interim capture (one pass)
            sink[id(self)] = c
        return c

    def write(self, pdf, out, lo, drop_mask=None, sink=None):
        out[:, lo] = self.resolve(
            pdf, drop_mask if drop_mask is not None
            else np.zeros(len(pdf), dtype=bool), sink)


class _OneHotSource(_Source):
    """OneHotEncoderModel over an indexed (or raw numeric-code) column."""

    def __init__(self, inner, width: int):
        self.inner = inner  # _IndexSource or _NumericSource
        self.width = int(width)

    def write(self, pdf, out, lo, drop_mask=None, sink=None):
        if isinstance(self.inner, _IndexSource):
            idx = self.inner.resolve(
                pdf, drop_mask if drop_mask is not None
                else np.zeros(len(pdf), dtype=bool), sink)
        else:
            idx = _numeric(pdf[self.inner.col])
            if self.inner.fill is not None:  # Imputer feeding the encoder
                idx = np.where(np.isfinite(idx), idx, self.inner.fill)
        na = ~np.isfinite(idx)
        ok = ~na & (idx >= 0) & (idx < self.width)
        rows = np.nonzero(ok)[0]
        out[:, lo:lo + self.width] = 0.0
        out[rows, lo + idx[ok].astype(np.intp)] = 1.0
        if na.any():  # matches OneHotEncoderModel: NaN input → NaN row
            out[na, lo:lo + self.width] = np.nan


class CompiledFeaturizer:
    """Fused replacement for a feature-stage chain; see module docstring."""

    def __init__(self, sources: List[_Source], handle_invalid: str):
        self.sources = sources
        self.handle_invalid = handle_invalid
        self.width = sum(s.width for s in sources)
        # (name, source) for every prep-stage output column in stage order —
        # the fused transform path rebuilds these interim columns from the
        # one-pass results instead of running per-stage pandas chains
        self.named_producers: List[tuple] = []

    @classmethod
    def from_stages(cls, stages, assembler) -> Optional["CompiledFeaturizer"]:
        from .feature import (ImputerModel, OneHotEncoder,
                              OneHotEncoderModel, StringIndexer,
                              StringIndexerModel, VectorAssembler)
        if not isinstance(assembler, VectorAssembler):
            return None
        invalid = assembler.getOrDefault("handleInvalid")
        if invalid not in ("error", "keep"):
            return None  # assembler "skip" drops by finiteness, not label

        producers = {}  # intermediate column name -> _Source
        for st in stages:
            if st is assembler:
                continue
            if isinstance(st, ImputerModel):
                ins = list(st.getOrDefault("inputCols") or [])
                outs = list(st.getOrDefault("outputCols") or ins)
                if any(c in producers for c in ins):
                    return None  # imputing a produced column: generic path
                for c, oc in zip(ins, outs):
                    producers[oc] = _NumericSource(c, float(st.surrogates[c]))
            elif isinstance(st, StringIndexerModel):
                ins, outs = StringIndexer._in_out(st)
                mode = st.getOrDefault("handleInvalid")
                if any(c in producers for c in ins):
                    return None  # indexing a produced column: generic path
                for c, oc, labels in zip(ins, outs, st.labelsArray):
                    producers[oc] = _IndexSource(
                        c, np.asarray(labels, dtype=object), mode)
            elif isinstance(st, OneHotEncoderModel):
                ins, outs = OneHotEncoder._in_out(st)
                drop_last = bool(st.getOrDefault("dropLast"))
                for c, oc, size in zip(ins, outs, st.categorySizes):
                    width = size - 1 if drop_last else size
                    inner = producers.get(c) or _NumericSource(c)
                    producers[oc] = _OneHotSource(inner, width)
            else:
                return None  # unknown stage: keep the generic path

        sources: List[_Source] = []
        for c in assembler.getOrDefault("inputCols"):
            sources.append(producers.get(c) or _NumericSource(c))
        out = cls(sources, invalid)
        out.named_producers = list(producers.items())
        return out

    def transform_with_mask(self, pdf: pd.DataFrame, sink=None):
        """(X, keep): the assembled block and the row-keep mask (None when
        no StringIndexer 'skip' drops happened) — callers that pair X with
        labels from the RAW frame must apply the same mask. `sink` captures
        resolved indexer codes by id(source) for the fused transform."""
        out = np.empty((len(pdf), self.width), dtype=np.float32)
        drop = np.zeros(len(pdf), dtype=bool)
        # contiguous runs of plain numeric sources extract as ONE pandas
        # block instead of a per-column to_numeric each (hot per batch)
        runs = []
        lo = 0
        for s in self.sources:
            simple = type(s) is _NumericSource
            if simple and runs and runs[-1][-1][0] + runs[-1][-1][1].width \
                    == lo and type(runs[-1][-1][1]) is _NumericSource:
                runs[-1].append((lo, s))
            elif simple:
                runs.append([(lo, s)])
            lo += s.width
        done = set()
        for run in runs:
            if len(run) < 2:
                continue
            cols = [s.col for _, s in run]
            fills = np.asarray([np.nan if s.fill is None else s.fill
                                for _, s in run])
            out[:, run[0][0]:run[0][0] + len(run)] = \
                extract_numeric_block(pdf, cols, fills)
            done.update(id(s) for _, s in run)
        lo = 0
        for s in self.sources:
            if id(s) in done:
                pass
            elif isinstance(s, (_IndexSource, _OneHotSource)):
                s.write(pdf, out, lo, drop, sink)
            else:
                s.write(pdf, out, lo)
            lo += s.width
        keep = None
        if drop.any():  # StringIndexer handleInvalid="skip" row drops
            keep = ~drop
            out = out[keep]
        if self.handle_invalid == "error" and not np.isfinite(out).all():
            raise ValueError(
                "VectorAssembler found NaN/null in assembled features; set "
                "handleInvalid='skip' or impute first")
        return out, keep

    def __call__(self, pdf: pd.DataFrame) -> np.ndarray:
        return self.transform_with_mask(pdf)[0]

    def compact_parts(self, pdf: pd.DataFrame) -> Optional[CompactParts]:
        """Extract the block in compact form (see CompactParts) when every
        source is numeric or one-hot-of-index — the standard course chain.
        Returns None (caller keeps the materialized path) for any other
        source shape, or when a value the expanded block would carry as
        NaN appears (the generic path's NaN semantics — error raises,
        NaN-poisoned fits — are not worth duplicating on the fast path)."""
        n = len(pdf)
        drop = np.zeros(n, dtype=bool)
        layout: List[tuple] = []
        num_srcs: List[_NumericSource] = []
        code_cols: List[np.ndarray] = []
        for s in self.sources:
            if type(s) is _NumericSource:
                layout.append(("num", len(num_srcs)))
                num_srcs.append(s)
            elif isinstance(s, _OneHotSource):
                if isinstance(s.inner, _IndexSource):
                    c = s.inner.resolve(pdf, drop)
                else:
                    c = _numeric(pdf[s.inner.col])
                    if s.inner.fill is not None:
                        c = np.where(np.isfinite(c), c, s.inner.fill)
                # rows the indexer marked for dropping may carry NaN codes
                # (they never reach the expanded block); any OTHER NaN
                # means a NaN one-hot row — generic-path semantics, bail
                if not np.isfinite(np.where(drop, 0.0, c)).all():
                    return None
                layout.append(("oh", len(code_cols), s.width))
                code_cols.append(np.where(drop, 0.0, c).astype(np.int32))
            else:
                return None
        if num_srcs:
            fills = np.asarray([np.nan if s.fill is None else s.fill
                                for s in num_srcs])
            num = extract_numeric_block(
                pdf, [s.col for s in num_srcs], fills).astype(np.float32)
            if not np.isfinite(num[~drop]).all():
                return None  # NaN feature: generic path raises/poisons
        else:
            num = np.zeros((n, 0), dtype=np.float32)
        codes = (np.stack(code_cols, axis=1) if code_cols
                 else np.zeros((n, 0), dtype=np.int32))
        keep = None
        if drop.any():
            keep = ~drop
            num, codes = num[keep], codes[keep]
        return CompactParts(np.ascontiguousarray(num.T),
                            np.ascontiguousarray(codes.T),
                            tuple(layout), self.width, keep)

    def _slot_map(self) -> dict:
        """assembler input position by source id: id(source) → (lo, width)."""
        m, lo = {}, 0
        for s in self.sources:
            m[id(s)] = (lo, s.width)
            lo += s.width
        return m

    def feature_attrs(self) -> dict:
        """The `_ml_attrs` entry the generic VectorAssembler transform would
        publish for its output column: categorical slot cardinalities (tree
        learners' maxBins semantics) + total width."""
        slots, lo = {}, 0
        for s in self.sources:
            if isinstance(s, _IndexSource):
                extra = 1 if s.invalid == "keep" else 0
                slots[lo] = len(s.labels) + extra
            lo += s.width
        return {"slots": slots, "numFeatures": self.width}

    def interim_attrs(self) -> dict:
        """Per-interim-column `_ml_attrs` matching the generic stage
        transforms (indexer 'categorical', OHE 'numFeatures')."""
        attrs = {}
        for name, src in self.named_producers:
            if isinstance(src, _IndexSource):
                extra = 1 if src.invalid == "keep" else 0
                attrs[name] = {"categorical": len(src.labels) + extra}
            elif isinstance(src, _OneHotSource):
                attrs[name] = {"numFeatures": src.width}
        return attrs

    def transform_with_columns(self, pdf: pd.DataFrame):
        """One-pass fused TRANSFORM: (X, keep, cols) where `cols` maps every
        prep-stage output column name to its value — a 1-D float array for
        scalar outputs or a `("block", arr2d, na_mask)` tuple for one-hot
        vector outputs. Everything is recovered from the single columnar
        pass: assembler-input producers read back their X slice, indexer
        codes consumed only by an encoder come from the resolve sink."""
        sink: dict = {}
        X, keep = self.transform_with_mask(pdf, sink)
        slot = self._slot_map()
        cols = {}
        for name, src in self.named_producers:
            sid = id(src)
            if sid in slot:
                lo, w = slot[sid]
                val = X[:, lo] if w == 1 else X[:, lo:lo + w]
            elif sid in sink:
                v = sink[sid]
                val = v[keep] if keep is not None else v
            elif isinstance(src, _NumericSource):
                v = _numeric(pdf[src.col])
                if src.fill is not None:
                    v = np.where(np.isfinite(v), v, src.fill)
                val = v[keep] if keep is not None else v
            else:  # an un-assembled encoder output: not worth a second pass
                return X, keep, None
            if isinstance(src, _OneHotSource) and np.ndim(val) == 2:
                na = ~np.isfinite(val).all(axis=1)
                cols[name] = ("block", val, na)
            else:
                cols[name] = np.asarray(val, dtype=np.float64).reshape(-1) \
                    if np.ndim(val) == 1 else val
        return X, keep, cols


def try_fast_fit(stages, raw, make_frame):
    """Whole-pipeline fused FIT over `raw`, the training table as
    `_column_plan.Pieces` (the frame's partitions where they lie; no
    table-wide concat is made here). For the standard course chain
    [Imputer?, StringIndexer?, OneHotEncoder?, VectorAssembler, estimator],
    or [RFormula, estimator] (the formula's own indexer, encoder and
    assembler: `RFormula._chain`),
    every prep stage reads RAW columns, so the chain becomes a column plan
    (`_column_plan`): one job a raw column makes the stage's fit statistic
    and the column's values of the feature block in one visit, the jobs
    side by side. The fitted stage models are made from the jobs' results
    (OneHotEncoder sizes from the indexer's labels: `max(idx)+1 ==
    len(labels)` when labels come from the same data), the assembler's
    slot metadata is reconstructed analytically, and the estimator gets a
    frame carrying the assembled block: NO transform chain ever
    materializes, and that frame lies over the same pieces (`make_frame`
    makes it from a list of them). An estimator that reads a featuresCol
    and NO label (a clustering) is handed the scratch as it was written,
    feature-major, where every assembled column is numeric. Returns
    (fitted_prep_stages, estimator_input_frame), or
    None where the plan declines (counter `featurize.plan.declined`, the
    reason on its event): the caller falls back to the generic sequential
    fit, which is always correct. The caller runs the estimator fit itself
    so estimator errors propagate unmasked.
    """
    if len(stages) < 2 or raw is None:
        return _decline("no chain of stages over a frame")
    try:
        return _try_fast_fit(stages, raw, make_frame)
    except Exception as e:
        _decline(f"a job raised {type(e).__name__}")
        raise


def _decline(reason: str) -> None:
    PROFILER.count("featurize.plan.declined")
    _OBS.emit("featurize", "featurize.plan.declined",
              args={"reason": reason})
    return None


def produced_columns(prep_stages) -> set:
    """Column names a prep chain WRITES. Stages with output params unset
    write in place (Imputer's outputCols default to inputCols), so the
    input columns count as produced in that case (r4 review)."""
    produced = set()
    for st in prep_stages:
        outs = set()
        for attr in ("outputCols", "outputCol"):
            try:
                v = st.getOrDefault(attr)
            except Exception:
                v = None
            if isinstance(v, str):
                outs.add(v)
            elif v:
                outs.update(v)
        if not outs:  # no explicit outputs: the stage overwrites its inputs
            for attr in ("inputCols", "inputCol"):
                try:
                    v = st.getOrDefault(attr)
                except Exception:
                    v = None
                if isinstance(v, str):
                    outs.add(v)
                elif v:
                    outs.update(v)
        produced |= outs
    return produced


def label_columns(est) -> List[str]:
    """The columns an estimator reads beside its features: labelCol and,
    where one is set, weightCol (none for a stage that has no labelCol)."""
    if not (hasattr(est, "hasParam") and est.hasParam("labelCol")):
        return []
    cols = [est.getOrDefault("labelCol")]
    if est.hasParam("weightCol") and est.getOrDefault("weightCol"):
        cols.append(est.getOrDefault("weightCol"))
    return cols


def prep_overwrites_label(prep_stages, est) -> bool:
    """True when any prep stage's OUTPUT columns collide with the
    estimator's labelCol/weightCol — the fused fast paths read labels from
    the RAW pandas, so a stage that rewrites the label there would make
    them train on pre-transform values."""
    return bool(produced_columns(prep_stages) & set(label_columns(est)))


def _try_fast_fit(stages, raw, make_frame):
    from . import _column_plan as cp
    from .base import Estimator
    from .feature import (Imputer, ImputerModel, OneHotEncoder,
                          OneHotEncoderModel, RFormula, StringIndexer,
                          StringIndexerModel, VectorAssembler)
    *prep, est = stages
    if not isinstance(est, Estimator):
        return _decline("the last stage is no estimator")
    # the last stage says whose block this is (`Estimator._block_reader`):
    # its own, or, for a validator that reads its folds off one staged
    # block, its estimator's, in the compact form the folds are masks
    # over; the caller fits the last stage on the block either way
    reader = est._block_reader()
    if reader is None:
        return _decline("a validator whose folds need their frames")
    est, compact_only = reader
    if not est.hasParam("featuresCol"):
        return _decline("the estimator reads no featuresCol")
    # an estimator with no label (a clustering) reads the block alone, and
    # reads it FEATURE-MAJOR, as the scratch is written: no interleave
    unlabelled = not est.hasParam("labelCol")
    # a formula IS an indexer, an encoder and an assembler over raw
    # columns (`RFormula._chain`): its jobs are theirs, and its model is
    # made from their models at the end. `renamed` is the formula's label
    # where the estimator reads it under the formula's labelCol and the
    # two names differ
    rformula = label_source = renamed = None
    if any(isinstance(st, RFormula) for st in prep):
        if len(prep) != 1:
            return _decline("a RFormula stage beside other prep stages")
        rformula = prep[0]
        label_source, str_terms, num_terms = rformula._terms(
            make_frame(raw.parts, raw.schema()))
        prep = rformula._chain(str_terms, num_terms)
        label_col = rformula.getOrDefault("labelCol")
        if label_source != label_col \
                and est.getOrDefault("labelCol") == label_col:
            if label_source not in raw.columns:
                return _decline("the formula's label is no raw column")
            renamed = label_source
    if not prep or not isinstance(prep[-1], VectorAssembler):
        return _decline("no VectorAssembler before the estimator")
    assembler = prep[-1]
    if est.getOrDefault("featuresCol") != assembler.getOrDefault("outputCol"):
        return _decline("the estimator does not read the assembler's output")
    if renamed is None and not unlabelled \
            and est.getOrDefault("labelCol") not in raw.columns:
        return _decline("labelCol is no raw column")
    if prep_overwrites_label(prep[:-1], est):
        return _decline("a prep stage rewrites the label")
    invalid = assembler.getOrDefault("handleInvalid")

    # one job a (stage, raw column). `produced`: an Imputer's or indexer's
    # output column -> its job; `encoded`: an encoder's output column ->
    # (the indexer's job, dropLast); `plans`: a prep stage with the jobs
    # its model is made from
    jobs, produced, encoded, plans = [], {}, {}, []
    for st in prep[:-1]:
        if isinstance(st, OneHotEncoder):
            ins, outs = st._in_out()
            made = [produced.get(c) for c in ins]
            if not all(isinstance(j, cp.StringJob) for j in made):
                return _decline("an encoder over a column no indexer made")
            drop_last = bool(st.getOrDefault("dropLast"))
            encoded.update((oc, (j, drop_last)) for oc, j in zip(outs, made))
            plans.append((st, made))
            continue
        if isinstance(st, Imputer):
            ins = list(st.getOrDefault("inputCols") or [])
            outs = list(st.getOrDefault("outputCols") or ins)
            made = [cp.NumericJob(c, st.getOrDefault("strategy"))
                    for c in ins]
        elif isinstance(st, StringIndexer):
            ins, outs = st._in_out()
            made = [cp.StringJob(c, st.getOrDefault("stringOrderType"),
                                 st.getOrDefault("handleInvalid"))
                    for c in ins]
        else:
            return _decline(f"a {type(st).__name__} stage outside the chain")
        if any(c not in raw.columns or c in produced or c in encoded
               for c in ins):
            return _decline(f"a {type(st).__name__} over a produced column")
        produced.update(zip(outs, made))
        jobs += made
        plans.append((st, made))

    # the assembler's inputs in its order, each a row of the scratch
    sources = []   # (job, None | the encoder's dropLast)
    for c in assembler.getOrDefault("inputCols"):
        job, drop_last = encoded.get(c) or (produced.get(c), None)
        if job is None:
            if c not in raw.columns:
                return _decline("an assembler input nothing makes")
            job = cp.NumericJob(c)
            jobs.append(job)
        elif job.row is not None:
            return _decline("a column assembled twice")
        job.row = len(sources)
        sources.append((job, drop_last))
    # `transform_with_mask` extracts a run of plain numeric inputs as one
    # block (see NumericJob.blockwise)
    numeric = [isinstance(job, cp.NumericJob) for job, _ in sources]
    for i, (job, _) in enumerate(sources):
        if numeric[i]:
            job.blockwise = any(numeric[max(i - 1, 0):i] + numeric[i + 1:i + 2])
    # an error is raised in the assembler's order, as the one pass met it
    jobs.sort(key=lambda j: len(jobs) if j.row is None else j.row)

    # huge linear fits skip X entirely: the compact block stages n*(p+k)
    # words and expands one-hots on-chip (CompactParts: the plan's scratch
    # as it was written, a job a row). Gated by size so course-scale fits
    # keep the materialized block and its golden-pinned numerics
    # bit-for-bit. The width follows the labels
    n = raw.rows
    compact_bytes = None
    if type(est).__name__ in ("LinearRegression", "LogisticRegression"):
        from ..conf import GLOBAL_CONF
        compact_bytes = 0 if compact_only else \
            GLOBAL_CONF.getInt("sml.linear.compactBytes")

    X = keep = parts = None
    with PROFILER.span("fit.featurize", rows=n, columns=len(jobs)) as note:
        # the plan's two steps as children: the column jobs, submit to
        # last result, and the scratch made the estimator's block
        with PROFILER.span("fit.featurize.plan.jobs") as step:
            plan = cp.Plan(raw, jobs)
            step["longest_s"] = plan.longest_s
        note["workers"] = plan.workers
        # an encoder's width follows its indexer's labels
        onehot = [None if drop_last is None
                  else job.category_size() - int(drop_last)
                  for job, drop_last in sources]
        los = list(itertools.accumulate(
            (1 if w is None else w for w in onehot), initial=0))
        width = los[-1]
        with PROFILER.span("fit.featurize.plan.block") as step:
            if (compact_bytes is not None
                    and n * width * 4 >= compact_bytes) \
                    or (unlabelled and all(w is None for w in onehot)):
                parts = plan.compact(onehot, invalid)
            if parts is None and compact_only:
                return _decline("a NaN the compact block would carry")
            if parts is None:   # also: a NaN the expanded block would carry
                X, keep = plan.block(onehot, invalid)
                note["bytes"] = int(X.nbytes)
            step["compact"] = parts is not None

    with PROFILER.span("fit.prep", stages=len(plans)):
        fitted = []
        attrs = {}          # column -> ml attrs (categorical cardinalities)
        for st, made in plans:
            if isinstance(st, Imputer):
                m = ImputerModel(surrogates={
                    j.col: j.result.surrogate for j in made})
            elif isinstance(st, StringIndexer):
                m = StringIndexerModel(labels=[j.result.labels for j in made])
                extra = 1 if st.getOrDefault("handleInvalid") == "keep" else 0
                for oc, ls in zip(st._in_out()[1], m.labelsArray):
                    attrs[oc] = {"categorical": len(ls) + extra}
            else:
                m = OneHotEncoderModel(
                    categorySizes=[j.category_size() for j in made])
            fitted.append(m._inherit_params(st))
        fitted.append(assembler)
        if rformula is not None:
            fitted = [rformula._model(fitted, label_source)]

    PROFILER.count("featurize.plan.fits")
    PROFILER.count("featurize.plan.pieces", len(raw.parts))
    legacy = sum(j.result.legacy for j in jobs)
    if legacy:
        PROFILER.count("featurize.plan.columns_legacy", legacy)

    # the assembler's slot metadata (VectorAssembler._transform computes
    # this from column attrs + row peeks; here widths are known statically)
    out_col = assembler.getOrDefault("outputCol")
    shim = make_frame(raw.parts)
    shim._ml_attrs = dict(attrs)
    shim._ml_attrs[out_col] = {
        "slots": {lo: attrs[c]["categorical"] for lo, c in zip(
            los, assembler.getOrDefault("inputCols")) if c in attrs},
        "numFeatures": width}
    # what the estimator reads beside the block: ONE gathered column a
    # name (`Pipeline._fit` gathered them inside `fit.collect`)
    cols = [c for c in label_columns(est) if c in raw.columns]
    if renamed is None:
        label_pdf = raw.table(cols)
    else:
        label_pdf = raw.table(cols + [renamed]).copy(deep=False)
        label_pdf[est.getOrDefault("labelCol")] = pd.to_numeric(
            label_pdf[renamed], errors="coerce")
    if parts is not None:
        shim._featurized_compact = {out_col: (parts, label_pdf)}
    else:
        shim._featurized = {out_col: (X, keep, label_pdf)}
    # the ESTIMATOR fit happens in the caller, OUTSIDE any fallback guard:
    # its errors (bad hyperparameters, device OOM) must propagate, not
    # trigger a silent re-fit through the generic path
    return fitted, shim
