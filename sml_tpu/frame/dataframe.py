"""Partitioned, lazily-evaluated DataFrame (the L1 engine subset — SURVEY §7.2).

Design (TPU-first, no JVM):
- A DataFrame is a recipe (``_compute``) producing a list of pandas blocks
  ("partitions"); transformations compose recipes and nothing runs until an
  action (count/collect/show/write) — the laziness contract demonstrated in
  `SML/ML 00b - Spark Review.py:45`. First materialization is memoized (cache
  semantics are therefore `.cache()`-compatible).
- Narrow ops run per-partition with an EvalContext (partition index / global
  row offset) so partition-sensitive semantics — seeded `randomSplit`
  (`ML 02:38-52`), `rand`, `monotonically_increasing_id` — are deterministic
  and *documented* functions of (seed, partition layout), like the engine the
  course demonstrates.
- Wide ops (groupBy/join/orderBy/dropDuplicates) shuffle via Murmur3 hash
  partitioning (native kernel `sml_tpu/native/murmur3.cc`) into
  `sml.shuffle.partitions` blocks.
- Numeric compute that matters (ML fit/transform) never happens here: the ML
  layer stages columns into HBM sharded over the mesh
  (`sml_tpu/parallel/mesh.py`) and runs jitted XLA programs.
"""

from __future__ import annotations

import bisect
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Tuple, Union

import numpy as np
import pandas as pd

from ..conf import GLOBAL_CONF
from ..native.hashing import hash_columns, hash_partition_ids
from ..utils.profiler import PROFILER
from .column import Column, EvalContext, NamedColumn, ensure_column
from .types import Row, StructType, infer_schema_from_pandas, parse_schema

Partitions = List[pd.DataFrame]


def _split_rows(pdf: pd.DataFrame, n: int) -> Partitions:
    n = max(1, int(n))
    idx = np.array_split(np.arange(len(pdf)), n)
    return [pdf.iloc[ix].reset_index(drop=True) for ix in idx]


def _rows_from_pdf(pdf: pd.DataFrame) -> List[Row]:
    cols = list(pdf.columns)
    out = []
    for t in pdf.itertuples(index=False):
        vals = {c: (None if isinstance(v, float) and np.isnan(v) else v)
                for c, v in zip(cols, t)}
        out.append(Row(**vals))
    return out


def _concat(parts: Partitions) -> pd.DataFrame:
    parts = [p for p in parts if len(p.columns)]
    if not parts:
        return pd.DataFrame()
    return pd.concat(parts, ignore_index=True)


def coerce_to_schema(pdf: pd.DataFrame, schema: StructType) -> pd.DataFrame:
    """Project + cast a pandas block to a StructType (schema enforcement at
    pandas-fn boundaries, mirroring `mapInPandas`/`applyInPandas` contracts)."""
    # fast path: already exactly conforming (the common case for UDFs that
    # build their output frames from numpy results)
    names = [f.name for f in schema.fields]
    if list(pdf.columns) == names:
        want = {"double": "float64", "float": "float32",
                "bigint": "int64", "int": "int32", "boolean": "bool"}
        if all(want.get(f.dataType.simpleString()) == str(pdf[f.name].dtype)
               for f in schema.fields):
            return pdf.reset_index(drop=True)
    out = {}
    for f in schema.fields:
        if f.name in pdf.columns:
            s = pdf[f.name]
        else:
            s = pd.Series([None] * len(pdf))
        t = f.dataType.simpleString()
        if t in ("double", "float"):
            s = pd.to_numeric(s, errors="coerce").astype(np.float64 if t == "double" else np.float32)
        elif t in ("int", "bigint"):
            s = pd.to_numeric(s, errors="coerce")
            if not s.isna().any():
                s = s.astype(np.int64 if t == "bigint" else np.int32)
        elif t == "boolean":
            from .column import cast_to_boolean
            s = cast_to_boolean(s)
        elif t == "string":
            s = s.map(lambda v: None if v is None or (isinstance(v, float) and np.isnan(v)) else str(v))
        s = s.reset_index(drop=True)
        out[f.name] = s
    return pd.DataFrame(out)


class DataFrame:
    # real class attribute so `getattr(df, "isStreaming", False)` probes see
    # False instead of __getattr__'s NamedColumn fallback (which is TRUTHY —
    # it silently disabled every isStreaming-guarded fast path, r4)
    isStreaming = False

    def __init__(self, compute: Callable[[], Partitions],
                 session: Optional["TpuSession"] = None,
                 schema: Optional[StructType] = None,
                 op: Optional[str] = None):
        if op is None:
            # default tag: the engine method that built this frame — names
            # the `materialize.<op>` profiler spans (MLE 05-style per-op
            # engine observability) without threading labels everywhere
            import sys as _sys
            op = _sys._getframe(1).f_code.co_name
            if op in ("_derive", "_derive_rowlocal", "from_pandas",
                      "from_partitions"):
                op = _sys._getframe(2).f_code.co_name
        self._op = op
        self._compute = compute
        self._session = session
        self._schema_hint = schema
        self._parts: Optional[Partitions] = None
        self._offsets: Optional[List[int]] = None
        self._pdf_cache: Optional[pd.DataFrame] = None
        # ML column attributes (e.g. categorical cardinality set by
        # StringIndexer, per-slot metadata set by VectorAssembler) — the
        # equivalent of Spark ML's column metadata that tree learners read
        # for maxBins semantics (`ML 06:91-126`).
        self._ml_attrs: Dict[str, Any] = {}
        # (weights, seed) -> child frames: repeated identical randomSplits
        # return the same (immutable, deterministic) children so downstream
        # caches stay hot — see randomSplit
        self._split_memo: Dict[tuple, list] = {}

    # ------------------------------------------------------------------ core
    @classmethod
    def from_pandas(cls, pdf: pd.DataFrame, session=None,
                    num_partitions: Optional[int] = None,
                    schema: Optional[StructType] = None) -> "DataFrame":
        if num_partitions is None:
            num_partitions = GLOBAL_CONF.getInt("sml.default.parallelism")
        pdf = pdf.reset_index(drop=True)
        n = min(num_partitions, max(1, len(pdf)))
        return cls(lambda: _split_rows(pdf, n), session=session, schema=schema)

    @classmethod
    def from_partitions(cls, parts: Partitions, session=None,
                        schema: Optional[StructType] = None) -> "DataFrame":
        return cls(lambda: parts, session=session, schema=schema)

    def _materialize(self) -> Partitions:
        if self._parts is None:
            with PROFILER.span(f"materialize.{self._op}"):
                self._parts = self._compute()
                if not self._parts:
                    self._parts = [pd.DataFrame()]
            offs, acc = [], 0
            for p in self._parts:
                offs.append(acc)
                acc += len(p)
            self._offsets = offs
            # Release the recipe: the closure retains the whole parent chain,
            # which would otherwise pin every intermediate's partitions in
            # memory for the lifetime of this frame.
            self._compute = None  # type: ignore[assignment]
            # An evaluator-pushdown hook is dead once the frame is
            # materialized (the evaluator only consults it pre-materialize);
            # drop it so it stops pinning the parent frame's partitions.
            if self.__dict__.get("_fused_eval") is not None:
                self.__dict__["_fused_eval"] = None
        return self._parts

    def _contexts(self) -> List[EvalContext]:
        parts = self._materialize()
        return [EvalContext(i, len(parts), self._offsets[i]) for i in range(len(parts))]

    def _derive(self, fn: Callable[[pd.DataFrame, EvalContext], pd.DataFrame],
                schema: Optional[StructType] = None) -> "DataFrame":
        parent = self

        def compute() -> Partitions:
            parts = parent._materialize()
            ctxs = parent._contexts()
            return [fn(p, c) for p, c in zip(parts, ctxs)]

        out = DataFrame(compute, session=self._session, schema=schema)
        out._ml_attrs = dict(self._ml_attrs)
        return out

    def _derive_rowlocal(self, fn: Callable[[pd.DataFrame, EvalContext], pd.DataFrame],
                         schema: Optional[StructType] = None) -> "DataFrame":
        """_derive for ROW-LOCAL, row-count-preserving fns (model predicts):
        applies fn ONCE over the concatenated partitions and splits the
        result back on the same boundaries. One device round trip instead of
        one per partition — each device→host read has a fixed cost, so
        per-partition prediction was paying it 8x."""
        parent = self

        def compute() -> Partitions:
            parts = parent._materialize()
            if len(parts) <= 1:
                ctxs = parent._contexts()
                return [fn(p, c) for p, c in zip(parts, ctxs)]
            whole = pd.concat(parts, ignore_index=True)
            out = fn(whole, EvalContext(0, 1, 0))
            if len(out) != len(whole):
                raise ValueError("_derive_rowlocal fn must preserve row count")
            bounds = np.cumsum([len(p) for p in parts])[:-1]
            lo = 0
            split = []
            for hi in list(bounds) + [len(out)]:
                split.append(out.iloc[lo:hi].reset_index(drop=True))
                lo = hi
            return split

        out = DataFrame(compute, session=self._session, schema=schema)
        out._ml_attrs = dict(self._ml_attrs)
        return out

    # ------------------------------------------------------------ metadata
    @property
    def schema(self) -> StructType:
        if self._schema_hint is not None:
            return self._schema_hint
        parts = self._materialize()
        biggest = max(parts, key=len)
        sch = infer_schema_from_pandas(biggest)
        self._schema_hint = sch
        return sch

    @property
    def columns(self) -> List[str]:
        return self.schema.names

    @property
    def dtypes(self) -> List[Tuple[str, str]]:
        return [(f.name, f.dataType.simpleString()) for f in self.schema.fields]

    def printSchema(self) -> None:
        print(self.schema.treeString())

    def __getitem__(self, item) -> Column:
        return NamedColumn(item)

    def __getattr__(self, item) -> Column:
        if item.startswith("_"):
            raise AttributeError(item)
        sch = self.__dict__.get("_schema_hint")
        if sch is not None and item not in sch.names:
            raise AttributeError(item)
        return NamedColumn(item)

    # ------------------------------------------------------------- actions
    def count(self) -> int:
        return sum(len(p) for p in self._materialize())

    def isEmpty(self) -> bool:
        return self.count() == 0

    def toPandas(self) -> pd.DataFrame:
        """Concatenate all partitions; the result is memoized per frame.
        Frames are immutable once materialized, and under pandas>=3
        copy-on-write the returned shallow copy is mutation-safe for the
        caller, so repeated toPandas (every stage of the generic sequential
        `Pipeline.fit` calls it, and every collect) costs one concat total
        instead of one per call. The fit-time column plan does NOT call it
        for a frame of several partitions: its jobs read the partitions
        where they lie (`ml/_column_plan.Pieces`), and only a frame that
        already holds this memo is read from it."""
        if self._pdf_cache is None:
            self._pdf_cache = _concat(self._materialize()).reset_index(drop=True)
        return self._pdf_cache.copy(deep=False)

    def collect(self) -> List[Row]:
        return _rows_from_pdf(self.toPandas())

    def first(self) -> Optional[Row]:
        rows = self.limit(1).collect()
        return rows[0] if rows else None

    def head(self, n: int = 1):
        rows = self.limit(n).collect()
        if n == 1:
            return rows[0] if rows else None
        return rows

    def take(self, n: int) -> List[Row]:
        return self.limit(n).collect()

    def tail(self, n: int) -> List[Row]:
        """Last n rows as Rows (Spark's driver-collected tail)."""
        if n < 0:
            raise ValueError(f"tail expects a non-negative n, got {n}")
        pdf = self.toPandas()
        return _rows_from_pdf(pdf.iloc[max(0, len(pdf) - n):])

    def show(self, n: int = 20, truncate: bool = True) -> None:
        pdf = self.limit(n).toPandas()
        if truncate:
            pdf = pdf.map(lambda v: (str(v)[:17] + "...") if len(str(v)) > 20 else v)
        try:
            print(pdf.to_string(index=False))
        except Exception:
            print(pdf)

    # ------------------------------------------------------ narrow transforms
    def select(self, *cols) -> "DataFrame":
        if len(cols) == 1 and isinstance(cols[0], (list, tuple)):
            cols = tuple(cols[0])
        agg_cols = [c for c in cols if isinstance(c, Column) and c._agg is not None]
        if agg_cols and len(agg_cols) == len(cols):
            from .grouped import GroupedData
            return GroupedData(self, []).agg(*agg_cols)

        def fn(pdf: pd.DataFrame, ctx: EvalContext) -> pd.DataFrame:
            out: Dict[str, pd.Series] = {}
            for c in cols:
                if (isinstance(c, str) and c == "*") or \
                        (isinstance(c, NamedColumn) and c.ref == "*"):
                    for name in pdf.columns:
                        out[name] = pdf[name]
                    continue
                cc = ensure_column(c)
                out[cc._name] = cc._eval(pdf, ctx).reset_index(drop=True)
            return pd.DataFrame(out)

        return self._derive(fn)

    def selectExpr(self, *exprs: str) -> "DataFrame":
        from .sql import parse_simple_expr
        return self.select(*[parse_simple_expr(e) for e in exprs])

    def withColumn(self, name: str, col: Column) -> "DataFrame":
        cc = ensure_column(col)

        def fn(pdf, ctx):
            out = pdf.copy(deep=False)  # CoW: column adds never touch the parent
            out[name] = cc._eval(pdf, ctx).reset_index(drop=True).values
            return out

        out = self._derive(fn)
        # evaluator-pushdown propagation: replacing the prediction column
        # with a known elementwise link of ITSELF (the ML 11 shape —
        # train on log(price), exponentiate predictions, evaluate on the
        # original scale) keeps the fused-eval hook alive with the link
        # composed into its device program
        hook = getattr(self, "_fused_eval", None)
        unary = getattr(cc, "_unary_of", None)
        if hook is not None and unary is not None and unary[1] == name:
            # with_link verifies `name` is the hook's OWN prediction column
            # (a link over any other column must kill the hook, not wrap it)
            linked = hook.with_link(unary[0], name)
            if linked is not None:
                out._fused_eval = linked
        return out

    def withColumnRenamed(self, old: str, new: str) -> "DataFrame":
        return self._derive(lambda pdf, ctx: pdf.rename(columns={old: new}))

    def drop(self, *cols) -> "DataFrame":
        names = [c._name if isinstance(c, Column) else c for c in cols]
        return self._derive(lambda pdf, ctx: pdf.drop(columns=[c for c in names if c in pdf.columns]))

    def filter(self, condition: Union[Column, str]) -> "DataFrame":
        if isinstance(condition, str):
            from .sql import parse_simple_expr
            condition = parse_simple_expr(condition)

        def fn(pdf, ctx):
            mask = condition._eval(pdf, ctx).fillna(False).astype(bool)
            return pdf[mask.values].reset_index(drop=True)

        return self._derive(fn)

    where = filter

    def limit(self, n: int) -> "DataFrame":
        parent = self

        def compute() -> Partitions:
            taken, out = 0, []
            for p in parent._materialize():
                if taken >= n:
                    break
                take = min(n - taken, len(p))
                out.append(p.iloc[:take].reset_index(drop=True))
                taken += take
            return out or [pd.DataFrame()]

        return DataFrame(compute, session=self._session)

    def toDF(self, *names: str) -> "DataFrame":
        def fn(pdf, ctx):
            out = pdf.copy(deep=False)  # CoW: column adds never touch the parent
            out.columns = list(names)
            return out
        return self._derive(fn)

    def alias(self, name: str) -> "DataFrame":
        return self

    def dropna(self, how: str = "any", thresh: Optional[int] = None,
               subset: Optional[Sequence[str]] = None) -> "DataFrame":
        kwargs: Dict[str, Any] = {"thresh": thresh} if thresh is not None else {"how": how}
        return self._derive(lambda pdf, ctx: pdf.dropna(subset=subset, **kwargs)
                            .reset_index(drop=True))

    def fillna(self, value, subset: Optional[Sequence[str]] = None) -> "DataFrame":
        def fn(pdf, ctx):
            out = pdf.copy(deep=False)  # CoW: column adds never touch the parent
            if isinstance(value, dict):
                return out.fillna(value)
            cols = subset or out.columns
            for c in cols:
                if c in out.columns:
                    s = out[c]
                    if isinstance(value, (int, float)) and s.dtype.kind not in "ifu":
                        continue  # Spark: numeric fill only touches numeric cols
                    if isinstance(value, str) and s.dtype.kind in "ifub":
                        continue
                    out[c] = s.fillna(value)
            return out
        return self._derive(fn)

    @property
    def na(self) -> "DataFrameNaFunctions":
        return DataFrameNaFunctions(self)

    @property
    def stat(self) -> "DataFrameStatFunctions":
        return DataFrameStatFunctions(self)

    # -------------------------------------------------------- wide transforms
    def distinct(self) -> "DataFrame":
        return self.dropDuplicates()

    def dropDuplicates(self, subset: Optional[Sequence[str]] = None) -> "DataFrame":
        parent = self

        def compute() -> Partitions:
            with PROFILER.span("shuffle.dropDuplicates"):
                pdf = _concat(parent._materialize())
                pdf = pdf.drop_duplicates(subset=subset, keep="first").reset_index(drop=True)
                return _hash_repartition(pdf, subset or list(pdf.columns),
                                         GLOBAL_CONF.getInt("sml.shuffle.partitions"))

        return DataFrame(compute, session=self._session)

    drop_duplicates = dropDuplicates

    def union(self, other: "DataFrame") -> "DataFrame":
        parent = self

        def compute() -> Partitions:
            a = parent._materialize()
            b = other._materialize()
            cols = list(a[0].columns) if len(a[0].columns) else list(b[0].columns)

            def align(p: pd.DataFrame) -> pd.DataFrame:
                # Spark union is positional: rename right-side columns to the
                # left's names by position
                q = p.copy()
                q.columns = cols[:len(q.columns)]
                return q

            return [p for p in a if len(p)] + [align(p) for p in b if len(p)] or [pd.DataFrame()]

        return DataFrame(compute, session=self._session)

    unionAll = union

    def unionByName(self, other: "DataFrame", allowMissingColumns: bool = False) -> "DataFrame":
        parent = self

        def compute() -> Partitions:
            a = _concat(parent._materialize())
            b = _concat(other._materialize())
            if allowMissingColumns:
                out = pd.concat([a, b], ignore_index=True)
            else:
                out = pd.concat([a, b[list(a.columns)]], ignore_index=True)
            return _split_rows(out, GLOBAL_CONF.getInt("sml.shuffle.partitions"))

        return DataFrame(compute, session=self._session)

    def join(self, other: "DataFrame", on=None, how: str = "inner") -> "DataFrame":
        parent = self

        def compute() -> Partitions:
            with PROFILER.span("shuffle.join"):
                left = _concat(parent._materialize())
                right = _concat(other._materialize())
                keys = [on] if isinstance(on, str) else list(on) if on is not None else None
                hw = {"inner": "inner", "left": "left", "left_outer": "left",
                      "right": "right", "right_outer": "right", "outer": "outer",
                      "full": "outer", "full_outer": "outer", "cross": "cross"}.get(how)
                if hw is None and how in ("left_semi", "leftsemi"):
                    mask = left[keys].apply(tuple, axis=1).isin(right[keys].apply(tuple, axis=1))
                    out = left[mask].reset_index(drop=True)
                elif hw is None and how in ("left_anti", "leftanti"):
                    mask = left[keys].apply(tuple, axis=1).isin(right[keys].apply(tuple, axis=1))
                    out = left[~mask].reset_index(drop=True)
                elif hw == "cross":
                    out = left.merge(right, how="cross")
                else:
                    out = left.merge(right, on=keys, how=hw, suffixes=("", "_r"))
                nparts = GLOBAL_CONF.getInt("sml.shuffle.partitions")
                if keys:
                    return _hash_repartition(out, keys, nparts)
                return _split_rows(out, nparts)

        return DataFrame(compute, session=self._session)

    def crossJoin(self, other: "DataFrame") -> "DataFrame":
        return self.join(other, on=None, how="cross")

    def orderBy(self, *cols, ascending=None) -> "DataFrame":
        parent = self
        if len(cols) == 1 and isinstance(cols[0], (list, tuple)):
            cols = tuple(cols[0])

        def compute() -> Partitions:
            with PROFILER.span("shuffle.sort"):
                pdf = _concat(parent._materialize())
                by, asc_flags = [], []
                tmp_cols = []
                for i, c in enumerate(cols):
                    if isinstance(c, str):
                        by.append(c)
                        asc_flags.append(True)
                    else:
                        tmp = f"__sort_{i}"
                        pdf[tmp] = c._eval(pdf, EvalContext()).values
                        by.append(tmp)
                        tmp_cols.append(tmp)
                        asc_flags.append(not bool(c._sort_desc))
                if ascending is not None:
                    if isinstance(ascending, (list, tuple)):
                        asc_flags = list(ascending)
                    else:
                        asc_flags = [bool(ascending)] * len(by)
                pdf = pdf.sort_values(by=by, ascending=asc_flags, kind="mergesort")
                pdf = pdf.drop(columns=tmp_cols).reset_index(drop=True)
                return _split_rows(pdf, max(1, len(parent._materialize())))

        return DataFrame(compute, session=self._session)

    sort = orderBy

    def groupBy(self, *cols) -> "GroupedData":
        from .grouped import GroupedData
        if len(cols) == 1 and isinstance(cols[0], (list, tuple)):
            cols = tuple(cols[0])
        return GroupedData(self, [c if isinstance(c, Column) else NamedColumn(c) for c in cols])

    groupby = groupBy

    def agg(self, *cols) -> "DataFrame":
        return self.groupBy().agg(*cols)

    # ----------------------------------------------------- partitioning ops
    def repartition(self, num: Union[int, str, Column], *cols) -> "DataFrame":
        parent = self
        if not isinstance(num, int):
            cols = (num,) + cols
            num = GLOBAL_CONF.getInt("sml.shuffle.partitions")
        key_names = [c if isinstance(c, str) else c._name for c in cols]

        def compute() -> Partitions:
            with PROFILER.span("shuffle.repartition"):
                pdf = _concat(parent._materialize())
                if key_names:
                    return _hash_repartition(pdf, key_names, num)
                # round-robin exchange
                if len(pdf) == 0:
                    return [pd.DataFrame(columns=pdf.columns) for _ in range(num)]
                ids = np.arange(len(pdf)) % num
                return [pdf[ids == i].reset_index(drop=True) for i in range(num)]

        return DataFrame(compute, session=self._session)

    def coalesce(self, num: int) -> "DataFrame":
        parent = self

        def compute() -> Partitions:
            parts = parent._materialize()
            if num >= len(parts):
                return parts
            groups = np.array_split(np.arange(len(parts)), num)
            return [_concat([parts[i] for i in g]) for g in groups]

        return DataFrame(compute, session=self._session)

    @property
    def rdd(self) -> "_RDDShim":
        return _RDDShim(self)

    # -------------------------------------------------------------- sampling
    def randomSplit(self, weights: Sequence[float], seed: Optional[int] = None) -> List["DataFrame"]:
        """Spark's split, draw for draw (`frame/sampling.py`): each
        partition is locally sorted (Dataset.randomSplit's determinism
        sort), then every weight cell keeps row i iff its
        `XORShiftRandom(seed + partitionIndex)` uniform lands in the
        cell's [lo, hi) — so the result depends on the partition layout
        exactly as the course demonstrates (`ML 02:38-52`), with Spark's
        published sampler semantics (BernoulliCellSampler over the
        hashSeed-scrambled XORShift stream). Set
        ``sml.split.sampler=legacy`` for the pre-r5 numpy draws.

        Identical (weights, seed) splits of this frame return the SAME
        child frames (plan-cache reuse: frames are immutable and the
        sampler is deterministic, so the children are observationally
        identical — but repeated ML 02-style split→fit flows keep their
        downstream staging/shuffle caches hot)."""
        explicit_seed = seed is not None
        seed = int(seed) if explicit_seed else int(np.random.SeedSequence().entropy % (2 ** 31))
        sampler_mode = str(GLOBAL_CONF.get("sml.split.sampler"))
        memo_key = (tuple(float(w) for w in weights), seed, sampler_mode)
        if explicit_seed:
            hit = self._split_memo.get(memo_key)
            if hit is not None:
                return list(hit)
        total = float(sum(weights))
        bounds = np.cumsum([w / total for w in weights])
        parent = self
        legacy = sampler_mode == "legacy"

        def make(i: int) -> DataFrame:
            lo = 0.0 if i == 0 else bounds[i - 1]
            hi = bounds[i]

            def fn(pdf: pd.DataFrame, ctx: EvalContext) -> pd.DataFrame:
                if legacy:
                    rng = np.random.default_rng(
                        (seed << 16) + ctx.partition_index)
                    u = rng.random(len(pdf))
                    mask = (u >= lo) & (u < hi)
                    return pdf[mask].reset_index(drop=True)
                from .sampling import partition_uniforms, presplit_order
                order = presplit_order(pdf)
                u = partition_uniforms(seed, ctx.partition_index, len(pdf))
                mask = (u >= lo) & (u < hi)
                if order is None:
                    return pdf[mask].reset_index(drop=True)
                # row i of the SORTED partition is row order[i] of this one
                return pdf.take(order[mask]).reset_index(drop=True)

            out = parent._derive(fn)
            out._op = "randomSplit"
            return out

        outs = [make(i) for i in range(len(weights))]
        if explicit_seed:
            # 2-deep: each entry's children, once materialized, pin ~one
            # dataset copy each — a wider memo could hold several copies
            # of a large cached frame for no realistic reuse pattern
            if len(self._split_memo) >= 2:
                self._split_memo.pop(next(iter(self._split_memo)))
            self._split_memo[memo_key] = list(outs)
        return outs

    def sample(self, withReplacement: bool = False, fraction: float = 0.1,
               seed: Optional[int] = None) -> "DataFrame":
        seed = int(seed) if seed is not None else np.random.SeedSequence().entropy % (2 ** 31)

        def fn(pdf: pd.DataFrame, ctx: EvalContext) -> pd.DataFrame:
            rng = np.random.default_rng((seed << 16) + ctx.partition_index)
            if withReplacement:
                n = rng.poisson(fraction * len(pdf))
                idx = rng.integers(0, max(len(pdf), 1), size=n) if len(pdf) else []
                return pdf.iloc[idx].reset_index(drop=True)
            mask = rng.random(len(pdf)) < fraction
            return pdf[mask].reset_index(drop=True)

        return self._derive(fn)

    # ------------------------------------------------------------ caching
    def cache(self) -> "DataFrame":
        self._materialize()
        return self

    def persist(self, *_args) -> "DataFrame":
        return self.cache()

    def unpersist(self) -> "DataFrame":
        # materialization releases the recipe (see _materialize), so data can
        # only be dropped if it is still recomputable
        if self._parts is not None:
            from .sampling import drop_sort_memo_for
            drop_sort_memo_for(self._parts)
        if self._compute is not None:
            self._parts = None
            self._offsets = None
        if self._pdf_cache is not None:
            from .grouped import drop_split_cache_for
            drop_split_cache_for(self._pdf_cache)
        self._pdf_cache = None
        return self

    # ------------------------------------------------------------- stats
    def describe(self, *cols) -> "DataFrame":
        return self._describe(["count", "mean", "stddev", "min", "max"], cols)

    def summary(self, *stats) -> "DataFrame":
        stats = list(stats) or ["count", "mean", "stddev", "min", "25%", "50%", "75%", "max"]
        return self._describe(stats, ())

    def _describe(self, stats: List[str], cols) -> "DataFrame":
        pdf = self.toPandas()
        if cols:
            pdf = pdf[list(cols)]
        out: Dict[str, list] = {"summary": stats}
        for c in pdf.columns:
            s = pdf[c]
            numeric = s.dtype.kind in "ifu"
            sn = pd.to_numeric(s, errors="coerce") if not numeric else s
            vals = []
            for st in stats:
                try:
                    if st == "count":
                        v = int(s.notna().sum())
                    elif st == "mean":
                        v = sn.mean() if numeric else None
                    elif st == "stddev":
                        v = sn.std(ddof=1) if numeric else None
                    elif st == "min":
                        v = s.min()
                    elif st == "max":
                        v = s.max()
                    elif st.endswith("%"):
                        v = sn.quantile(float(st[:-1]) / 100) if numeric else None
                    else:
                        v = None
                except Exception:
                    v = None
                vals.append(None if v is None else str(v))
            out[c] = vals
        res = pd.DataFrame(out)
        return DataFrame.from_pandas(res, session=self._session, num_partitions=1)

    def approxQuantile(self, col: Union[str, List[str]], probabilities: Sequence[float],
                      relativeError: float = 0.0) -> List:
        pdf = self.toPandas()
        if isinstance(col, str):
            s = pd.to_numeric(pdf[col], errors="coerce").dropna()
            return [float(s.quantile(p)) for p in probabilities]
        return [[float(pd.to_numeric(pdf[c], errors="coerce").dropna().quantile(p))
                 for p in probabilities] for c in col]

    def corr(self, col1: str, col2: str) -> float:
        pdf = self.toPandas()
        return float(pd.to_numeric(pdf[col1], errors="coerce")
                     .corr(pd.to_numeric(pdf[col2], errors="coerce")))

    # ------------------------------------------------------------- pandas fn
    def mapInPandas(self, fn: Callable, schema: Union[str, StructType]) -> "DataFrame":
        """Iterator-of-batches map (`ML 12:125-143`); batch size follows
        `sml.arrow.maxRecordsPerBatch`.

        The UDF is invoked ONCE with an iterator streaming every partition's
        batches (Spark's contract is per-executor-task; any batch boundary
        is valid). One invocation lets expensive UDF state — a loaded model,
        a compiled device program — amortize across the whole dataset, and
        lets device-backed UDF bodies (`DeviceScorer.score_batches`)
        pipeline host staging under device compute across batches.

        The whole invocation is priced through `parallel.dispatch.decide`
        with a per-cell WorkHint: a SMALL pandas-fn leg binds the host
        mesh for the UDF's duration, so device-capable bodies inside it
        (scorers) stop paying a dispatch round trip per batch where the
        dispatcher prices that above the work. Large legs
        leave the inner per-batch routing untouched.
        """
        sch = parse_schema(schema)
        parent = self

        def compute():
            import contextlib

            from ..parallel import dispatch as _dispatch
            from ..parallel import mesh as _meshlib
            parts = parent._materialize()
            bs = GLOBAL_CONF.getInt("sml.arrow.maxRecordsPerBatch")

            def batches():
                for pdf in parts:
                    if len(pdf) == 0:
                        continue
                    for i in range(0, len(pdf), bs):
                        yield pdf.iloc[i:i + bs].reset_index(drop=True)

            n_rows = sum(len(p) for p in parts)
            n_cols = max((len(p.columns) for p in parts), default=1)
            # a linear-model-pass-per-cell estimate: generous to the fn
            # body, but the decision only flips SMALL legs hostward,
            # where the measured dispatch round trip dominates the body
            hint = _dispatch.WorkHint(flops=2.0 * n_rows * max(n_cols, 1),
                                      kind="blas", out_bytes=8.0 * n_rows)
            route, _ = _dispatch.decide(hint)
            ctx = (_meshlib.use_mesh_local(_dispatch.host_mesh())
                   if route == "host" else contextlib.nullcontext())
            with ctx:
                outs = [coerce_to_schema(b, sch) for b in fn(batches())]
            return outs if outs else [coerce_to_schema(pd.DataFrame(), sch)]

        out = DataFrame(compute, session=self._session, schema=sch)
        out._ml_attrs = dict(self._ml_attrs)
        return out

    # ------------------------------------------------------------- views / IO
    def createOrReplaceTempView(self, name: str) -> None:
        if self._session is None:
            raise RuntimeError("DataFrame has no session; use TpuSession.createDataFrame")
        self._session.catalog._register_view(name, self)

    @property
    def write(self):
        from .io import DataFrameWriter
        return DataFrameWriter(self)

    @property
    def writeStream(self):
        from ..streaming.stream import DataStreamWriter
        return DataStreamWriter(self)

    def checkpoint(self, eager: bool = True) -> "DataFrame":
        self._materialize()
        return self

    def to_koalas(self, index_col: Optional[str] = None):
        """Lift into the pandas-API layer (`ML 14:134-152`)."""
        from ..pandas_api import DataFrame as KDataFrame
        return KDataFrame(self, index_col=index_col)

    to_pandas_on_spark = to_koalas
    pandas_api = to_koalas

    def __repr__(self):
        try:
            cols = ", ".join(f"{n}: {t}" for n, t in self.dtypes[:8])
        except Exception:
            cols = "..."
        return f"DataFrame[{cols}]"


class DataFrameNaFunctions:
    def __init__(self, df: DataFrame):
        self._df = df

    def drop(self, how: str = "any", thresh: Optional[int] = None,
             subset: Optional[Sequence[str]] = None) -> DataFrame:
        return self._df.dropna(how=how, thresh=thresh, subset=subset)

    def fill(self, value, subset: Optional[Sequence[str]] = None) -> DataFrame:
        return self._df.fillna(value, subset=subset)


class DataFrameStatFunctions:
    def __init__(self, df: DataFrame):
        self._df = df

    def corr(self, col1: str, col2: str) -> float:
        return self._df.corr(col1, col2)

    def approxQuantile(self, col, probabilities, relativeError=0.0):
        return self._df.approxQuantile(col, probabilities, relativeError)


class _RDDShim:
    """`df.rdd.getNumPartitions()` — the partition-introspection surface used
    at `ML 00b:84` and the repartition demos."""

    def __init__(self, df: DataFrame):
        self._df = df

    def getNumPartitions(self) -> int:
        return len(self._df._materialize())

    def glom(self):
        return [p.to_dict("records") for p in self._df._materialize()]


def _hash_repartition(pdf: pd.DataFrame, keys: List[str], num: int) -> Partitions:
    """Murmur3 hash-partition rows by key columns (shuffle placement).
    Records the post-shuffle partition skew (max/mean rows) — the MLE 05
    debugging taxonomy's skew signal (`MLE 05:24-29`)."""
    if len(pdf) == 0:
        return [pdf.reset_index(drop=True)]
    hashes = hash_columns([pdf[k] for k in keys], n=len(pdf))
    ids = hash_partition_ids(hashes, num)
    parts = [pdf[ids == i].reset_index(drop=True) for i in range(num)]
    sizes = np.array([len(p) for p in parts], dtype=float)
    if sizes.sum() > 0:
        PROFILER.count("shuffle.rows", float(sizes.sum()))
        # shallow estimate (object columns count pointer width): the
        # relative shuffle-volume signal MLE 05 reads off the Spark UI,
        # cheap enough to take on every shuffle
        PROFILER.count("shuffle.bytes",
                       float(pdf.memory_usage(index=False).sum()))
        with PROFILER.span("shuffle.partition", rows=int(sizes.sum()),
                           skew=float(sizes.max() / max(sizes.mean(), 1.0))):
            pass
    return parts
