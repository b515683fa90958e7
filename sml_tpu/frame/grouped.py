"""GroupedData: keyed aggregation + per-group pandas training fan-out.

Covers `groupBy().count()/agg(...)` (SURVEY L1) and
`groupBy(...).applyInPandas(fn, schema)` — the per-group sklearn-training
path of `SML/ML 13 - Training with Pandas Function API.py:119-161` (P8).
The shuffle is a Murmur3 hash repartition by key; per-group functions then
run host-side (the payload is arbitrary Python: sklearn/JAX/etc.), matching
the reference's executor-side Python workers.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Union

import numpy as np
import pandas as pd

from ..conf import GLOBAL_CONF
from .column import Column, EvalContext
from .dataframe import DataFrame, _concat, _hash_repartition, coerce_to_schema
from .types import StructType, parse_schema


import threading as _threading

# SHUFFLE REUSE (SURVEY L1): Spark reuses shuffle files when the same
# stage re-executes over unchanged lineage; here the per-key group split
# of a cached frame is the shuffle output, memoized by the identity of
# the frame's memoized concat (id-stable for cached frames, held strongly
# so the id cannot be recycled). Entries: (token, groups, bytes);
# byte-bounded LRU (sml.shuffle.reuseBytes) — a split pins a full copy of
# its dataset, so a count-only bound would hold multi-GB frames for the
# process lifetime. `DataFrame.unpersist` drops matching entries.
# Handed-out groups are CoW shallow copies, so a fn that mutates its
# input cannot pollute the cache (pandas>=3 copy-on-write is always on;
# under an older pandas with CoW disabled the handout deep-copies, the
# same defense DataFrame.toPandas applies).
_split_cache: Dict[tuple, tuple] = {}
_split_lock = _threading.Lock()


def _split_cache_put(ckey, token, groups) -> None:
    # deep accounting: the split's cost IS its string payloads (shallow
    # counts object columns at pointer size), plus the pinned token frame
    nbytes = int(sum(int(g.memory_usage(deep=True).sum()) for g in groups))
    nbytes += int(token.memory_usage(deep=True).sum())
    max_bytes = GLOBAL_CONF.getInt("sml.shuffle.reuseBytes")
    if nbytes > max_bytes:
        return
    with _split_lock:
        _split_cache[ckey] = (token, groups, nbytes)
        total = sum(e[2] for e in _split_cache.values())
        while len(_split_cache) > 1 and total > max_bytes:
            total -= _split_cache.pop(next(iter(_split_cache)))[2]


def drop_split_cache_for(token) -> None:
    """Invalidate shuffle-reuse entries for a frame's memoized concat
    (DataFrame.unpersist calls this so dropping a cached frame actually
    releases the split's memory)."""
    if token is None:
        return
    with _split_lock:
        for k in [k for k, v in _split_cache.items() if v[0] is token]:
            _split_cache.pop(k)


def _group_handout(g: pd.DataFrame) -> pd.DataFrame:
    """The frame a user fn receives: shallow under pandas' copy-on-write
    (always on in pandas>=3, which the package requires), so writes can't
    reach the cached split."""
    return g.copy(deep=False)


class GroupedData:
    def __init__(self, df: DataFrame, keys: List[Column]):
        self._df = df
        self._keys = keys

    def _grouped(self):
        # toPandas, not a fresh concat: the frame memoizes its concat, so
        # repeated grouped actions on a cached frame share one materialization
        if hasattr(self._df, "toPandas"):
            pdf = self._df.toPandas()
            token = self._df.__dict__.get("_pdf_cache")
        else:
            pdf = _concat(self._df._materialize())
            token = None
        key_names = [k._name for k in self._keys]
        for k in self._keys:
            if k._name not in pdf.columns:
                pdf[k._name] = k._eval(pdf, EvalContext()).values
                token = None  # computed key: beyond the memoized concat
        return pdf, key_names, token

    def agg(self, *exprs) -> DataFrame:
        if len(exprs) == 1 and isinstance(exprs[0], dict):
            from . import functions as F
            mapping = {"avg": F.avg, "mean": F.avg, "max": F.max, "min": F.min,
                       "sum": F.sum, "count": F.count, "stddev": F.stddev,
                       "first": F.first, "last": F.last}
            exprs = tuple(mapping[op](c) for c, op in exprs[0].items())

        parent = self

        def compute():
            pdf, key_names, _ = parent._grouped()
            results: Dict[str, pd.Series] = {}
            if key_names:
                gb_index = pdf.groupby(key_names, sort=False, dropna=False)
            for e in exprs:
                if e._agg is None:
                    raise ValueError(f"non-aggregate expression in agg: {e._name}")
                evaluated = e._eval(pdf, EvalContext()) if len(pdf) else pd.Series(dtype=float)
                if key_names:
                    if isinstance(evaluated, pd.DataFrame):
                        grouped = evaluated.groupby([pdf[k].values for k in key_names],
                                                    sort=False, dropna=False).apply(e._agg)
                    else:
                        grouped = evaluated.groupby([pdf[k].values for k in key_names],
                                                    sort=False, dropna=False).agg(e._agg)
                    results[e._name] = grouped
                else:
                    results[e._name] = pd.Series([e._agg(evaluated)])
            if key_names:
                keys_df = gb_index.size().reset_index()[key_names]
                out = keys_df.copy()
                for name, series in results.items():
                    series = series.reset_index(drop=True)
                    # align by recomputing group order: pandas groupby(sort=False)
                    # preserves first-appearance order in both paths
                    out[name] = series.values
            else:
                out = pd.DataFrame({k: v for k, v in results.items()})
            nparts = GLOBAL_CONF.getInt("sml.shuffle.partitions")
            if key_names:
                return _hash_repartition(out, key_names, nparts)
            return [out]

        return DataFrame(compute, session=self._df._session)

    def count(self) -> DataFrame:
        from . import functions as F
        out = self.agg(F.count("*").alias("count"))
        return out

    def _simple(self, op: str, cols) -> DataFrame:
        from . import functions as F
        fns = {"avg": F.avg, "mean": F.avg, "sum": F.sum, "min": F.min, "max": F.max}
        if not cols:
            pdf = _concat(self._df._materialize())
            cols = [c for c in pdf.columns if pdf[c].dtype.kind in "ifu"
                    and c not in [k._name for k in self._keys]]
        return self.agg(*[fns[op](c) for c in cols])

    def avg(self, *cols) -> DataFrame:
        return self._simple("avg", cols)

    mean = avg

    def sum(self, *cols) -> DataFrame:  # noqa: A003
        return self._simple("sum", cols)

    def min(self, *cols) -> DataFrame:  # noqa: A003
        return self._simple("min", cols)

    def max(self, *cols) -> DataFrame:  # noqa: A003
        return self._simple("max", cols)

    def applyInPandas(self, fn: Callable[[pd.DataFrame], pd.DataFrame],
                      schema: Union[str, StructType]) -> DataFrame:
        """Hash-shuffle by key, run `fn` once per group, enforce schema
        (`ML 13:119-127`). Group key columns are included in the input block,
        as in the reference."""
        sch = parse_schema(schema)
        parent = self

        def compute():
            pdf, key_names, token = parent._grouped()
            if len(pdf) == 0:
                return [coerce_to_schema(pd.DataFrame(), sch)]
            ckey = ((id(token), tuple(key_names))
                    if token is not None else None)
            groups = None
            if ckey is not None:
                with _split_lock:
                    hit = _split_cache.get(ckey)
                # `is` check: the strong ref in the entry keeps the id
                # valid, but a rebuilt concat for the same frame must miss
                if hit is not None and hit[0] is token:
                    groups = hit[1]
            par = GLOBAL_CONF.getInt("sml.applyInPandas.parallelism")
            from concurrent.futures import ThreadPoolExecutor
            if groups is not None:
                # shuffle reuse: the split is already materialized — the
                # leg is pure fn execution, fanned across workers
                if len(groups) > 1 and par > 1:
                    with ThreadPoolExecutor(
                            max_workers=min(par, len(groups))) as ex:
                        futs = [ex.submit(fn, _group_handout(g))
                                for g in groups]
                        outs = [coerce_to_schema(f.result(), sch)
                                for f in futs]
                else:
                    outs = [coerce_to_schema(fn(_group_handout(g)), sch)
                            for g in groups]
            else:
                gb = pdf.groupby(key_names, sort=False, dropna=False)
                collected = []

                def split():
                    for _, g in gb:
                        g = g.reset_index(drop=True)
                        if ckey is not None:  # else: never cached — don't
                            collected.append(g)  # pin a dataset copy
                        yield g

                if gb.ngroups > 1 and par > 1:
                    # per-group fns run concurrently, as on Spark executors
                    # (P8): sklearn/numpy payloads release the GIL in BLAS.
                    # Groups are SUBMITTED as the groupby iterator yields
                    # them, so worker fns overlap with the remaining group
                    # extraction (the per-group take of a wide
                    # object-column frame is the expensive half of the
                    # split).
                    # NOTE these are threads of ONE interpreter — a fn that
                    # mutates shared closure state needs
                    # sml.applyInPandas.parallelism=1 (Spark's
                    # process-isolated workers could never share state in
                    # the first place)
                    with ThreadPoolExecutor(
                            max_workers=min(par, gb.ngroups)) as ex:
                        futs = [ex.submit(fn, _group_handout(g))
                                for g in split()]
                        outs = [coerce_to_schema(f.result(), sch)
                                for f in futs]
                else:
                    outs = [coerce_to_schema(fn(_group_handout(g)), sch)
                            for g in split()]
                if ckey is not None:
                    _split_cache_put(ckey, token, collected)
            full = pd.concat(outs, ignore_index=True)
            nparts = min(len(outs), GLOBAL_CONF.getInt("sml.shuffle.partitions"))
            avail = [k for k in key_names if k in full.columns]
            if avail:
                return _hash_repartition(full, avail, max(1, nparts))
            return [full]

        return DataFrame(compute, session=self._df._session, schema=sch)

    def applyInPandasWithState(self, *a, **k):
        raise NotImplementedError("stateful streaming aggregation is not supported")

    def pivot(self, pivot_col: str, values=None) -> "GroupedData":
        raise NotImplementedError("pivot is not in the covered course surface")
