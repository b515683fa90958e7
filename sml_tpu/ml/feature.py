"""Feature transformers (the L3 surface exercised by the courseware).

Host-side metadata/string ops (vocab builds, category maps) stay on the host
frame — SURVEY §7 "Hard parts" #4: strings do not belong on the MXU — while
their numeric output columns are what the estimators stage into HBM.

Coverage and reference behavior:
- `Imputer(strategy="median")`                `SML/ML 01 - Data Cleansing.py:251-256`
- `VectorAssembler`                           `SML/ML 02 - Linear Regression I.py:103-107`
- `StringIndexer(handleInvalid="skip")`       `SML/ML 03 - Linear Regression II.py:54-61`
- `OneHotEncoder`                             `SML/ML 03 - Linear Regression II.py:54-61`
- `RFormula("price ~ .")`                     `SML/ML 04 - MLflow Tracking.py:110-117`
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional

import numpy as np
import pandas as pd

from .base import Estimator, Model, Transformer
from .linalg import (DenseVector, SparseVector, Vector, VectorArray,
                     to_matrix, vector_series)


def _as_object_series(values: List) -> pd.Series:
    arr = np.empty(len(values), dtype=object)
    arr[:] = values
    return pd.Series(arr)


# --------------------------------------------------------------------------
class VectorAssembler(Transformer):
    """Concatenate numeric / vector columns into one feature vector column."""

    def _init_params(self):
        self._declareParam("inputCols", doc="input column names")
        self._declareParam("outputCol", default="features", doc="output column")
        self._declareParam("handleInvalid", default="error", doc="error|skip|keep")

    def __init__(self, inputCols: Optional[List[str]] = None,
                 outputCol: Optional[str] = None, handleInvalid: Optional[str] = None):
        super().__init__()
        self._set(inputCols=inputCols, outputCol=outputCol, handleInvalid=handleInvalid)

    def getInputCols(self):
        return self.getOrDefault("inputCols")

    def getOutputCol(self):
        return self.getOrDefault("outputCol")

    def setInputCols(self, v):
        return self._set(inputCols=v)

    def setOutputCol(self, v):
        return self._set(outputCol=v)

    def _transform(self, df):
        in_cols = list(self.getOrDefault("inputCols"))
        out_col = self.getOrDefault("outputCol")
        invalid = self.getOrDefault("handleInvalid")

        def fn(pdf: pd.DataFrame, ctx) -> pd.DataFrame:
            if len(pdf) == 0:
                out = pdf.copy(deep=False)  # CoW: column adds never touch the parent
                out[out_col] = vector_series(np.zeros((0, 0)))
                return out
            blocks = []
            for c in in_cols:
                col = pdf[c]
                arr = getattr(col, "array", None)
                if isinstance(arr, VectorArray):
                    blocks.append(arr.block)   # columnar: no per-row objects
                elif len(col) and isinstance(col.iloc[0], Vector):
                    blocks.append(np.stack([v.toArray() for v in col]))
                else:
                    blocks.append(np.asarray(pd.to_numeric(col, errors="coerce"),
                                             dtype=np.float64)[:, None])
            # single-input case must not alias the input column's block
            mat = np.concatenate(blocks, axis=1) if len(blocks) > 1 \
                else blocks[0].copy()
            bad = ~np.isfinite(mat).all(axis=1)
            out = pdf.copy(deep=False)  # CoW: column adds never touch the parent
            if bad.any():
                if invalid == "error":
                    raise ValueError(
                        f"VectorAssembler found NaN/null in {in_cols}; set "
                        f"handleInvalid='skip' or impute first")
                if invalid == "skip":
                    out = out[~bad].reset_index(drop=True)
                    mat = mat[~bad]
            out[out_col] = vector_series(mat, index=out.index)
            return out

        res = df._derive(fn)
        # per-slot feature metadata: which assembled slots are categorical
        # (slot → cardinality), consumed by tree learners
        slots: Dict[int, int] = {}
        pos = 0
        pdf0 = None
        for c in in_cols:
            width = 1
            attrs = df._ml_attrs.get(c)
            if attrs is not None and "categorical" in attrs:
                slots[pos] = int(attrs["categorical"])
            elif attrs is not None and "numFeatures" in attrs:
                # previously-assembled vector column: attrs carry its width
                width = int(attrs["numFeatures"])
            elif not getattr(df, "isStreaming", False):
                # vector input columns occupy their own width; peek one row
                # (streaming frames can't peek — their numeric inputs are
                # width 1, which is the default)
                if pdf0 is None:
                    pdf0 = df.limit(1).toPandas()
                v = pdf0[c].iloc[0] if len(pdf0) else None
                if isinstance(v, Vector):
                    width = v.size
            pos += width
        res._ml_attrs[out_col] = {"slots": slots, "numFeatures": pos}
        return res


# --------------------------------------------------------------------------
def order_labels(labels, counts, order: str) -> List[str]:
    """Distinct `labels` (with their `counts`, read by the frequency
    orders only) in a `stringOrderType`'s order. Frequency ties break
    count descending then label ascending (MLlib), and the two reversed
    orders reverse the whole list."""
    if order.startswith("frequency"):
        lab = [k for k, _ in sorted(zip(labels, counts),
                                    key=lambda kv: (-kv[1], kv[0]))]
    else:
        lab = sorted(labels)
    return lab[::-1] if order in ("frequencyAsc", "alphabetDesc") else lab


def indexer_labels(col: pd.Series, order: str) -> List[str]:
    """One column's StringIndexer labels, whatever its storage (the
    column plan's jobs count an Arrow-backed column's codes instead:
    `_column_plan.StringJob`)."""
    s = col.dropna().astype(str)
    if order.startswith("frequency"):
        counts = s.value_counts()
        return order_labels(counts.index, counts.to_numpy(), order)
    return order_labels(s.unique(), None, order)


class StringIndexer(Estimator):
    """Map string categories → double indices ordered by descending frequency
    (ties broken lexically), matching MLlib's default `frequencyDesc`."""

    def _init_params(self):
        self._declareParam("inputCol", doc="input column")
        self._declareParam("outputCol", doc="output column")
        self._declareParam("inputCols", doc="input columns (multi)")
        self._declareParam("outputCols", doc="output columns (multi)")
        self._declareParam("handleInvalid", default="error", doc="error|skip|keep")
        self._declareParam("stringOrderType", default="frequencyDesc",
                           doc="frequencyDesc|frequencyAsc|alphabetDesc|alphabetAsc")

    def __init__(self, inputCol=None, outputCol=None, inputCols=None,
                 outputCols=None, handleInvalid=None, stringOrderType=None):
        super().__init__()
        self._set(inputCol=inputCol, outputCol=outputCol, inputCols=inputCols,
                  outputCols=outputCols, handleInvalid=handleInvalid,
                  stringOrderType=stringOrderType)

    def _in_out(self):
        multi_in = self.getOrDefault("inputCols")
        if multi_in:
            return list(multi_in), list(self.getOrDefault("outputCols"))
        return [self.getOrDefault("inputCol")], [self.getOrDefault("outputCol")]

    def _fit(self, df) -> "StringIndexerModel":
        in_cols, out_cols = self._in_out()
        order = self.getOrDefault("stringOrderType")
        pdf = df.toPandas()
        labels = [indexer_labels(pdf[c], order) for c in in_cols]
        m = StringIndexerModel(labels=labels)
        m._inherit_params(self)
        return m


class StringIndexerModel(Model):
    def _init_params(self):
        StringIndexer._init_params(self)

    def __init__(self, labels: Optional[List[List[str]]] = None):
        super().__init__()
        self.labelsArray: List[List[str]] = labels or []

    @property
    def labels(self) -> List[str]:
        return self.labelsArray[0] if self.labelsArray else []

    def _transform(self, df):
        in_cols, out_cols = StringIndexer._in_out(self)
        invalid = self.getOrDefault("handleInvalid")
        maps = [{lab: float(i) for i, lab in enumerate(ls)} for ls in self.labelsArray]

        def fn(pdf: pd.DataFrame, ctx) -> pd.DataFrame:
            out = pdf.copy(deep=False)  # CoW: column adds never touch the parent
            keep_mask = np.ones(len(pdf), dtype=bool)
            for c, oc, mapping in zip(in_cols, out_cols, maps):
                col = out[c]
                notna = col.notna().to_numpy()
                # vectorized dict lookup (C path), no per-row lambdas
                idx = col.astype(str).map(mapping)
                idx[~notna] = np.nan
                missing = idx.isna().to_numpy()
                if missing.any():
                    if invalid == "error":
                        bad = col[missing].iloc[0]
                        raise ValueError(f"Unseen label {bad!r} in column {c!r} "
                                         f"(handleInvalid='error')")
                    if invalid == "skip":
                        keep_mask &= ~missing
                    else:  # keep → extra index = numLabels
                        idx = idx.where(~pd.Series(missing, index=idx.index),
                                        float(len(mapping)))
                out[oc] = idx.astype(float)
            if not keep_mask.all():
                out = out[keep_mask].reset_index(drop=True)
            return out

        res = df._derive(fn)
        # column metadata the tree learners read for maxBins semantics:
        # an indexed column is categorical with known cardinality (ML 06:91-126)
        extra = 1 if invalid == "keep" else 0
        for oc, ls in zip(out_cols, self.labelsArray):
            res._ml_attrs[oc] = {"categorical": len(ls) + extra}
        return res

    def _extra_metadata(self):
        return {"labelsArray": self.labelsArray}

    def _load_state(self, path, meta):
        self.labelsArray = [list(x) for x in meta.get("labelsArray", [])]


class IndexToString(Transformer):
    def _init_params(self):
        self._declareParam("inputCol", doc="index column")
        self._declareParam("outputCol", doc="label column")
        self._declareParam("labels", doc="labels list")

    def __init__(self, inputCol=None, outputCol=None, labels=None):
        super().__init__()
        self._set(inputCol=inputCol, outputCol=outputCol, labels=labels)

    def _transform(self, df):
        labels = list(self.getOrDefault("labels"))
        ic, oc = self.getOrDefault("inputCol"), self.getOrDefault("outputCol")

        def fn(pdf, ctx):
            out = pdf.copy(deep=False)  # CoW: column adds never touch the parent
            out[oc] = out[ic].map(lambda i: labels[int(i)] if pd.notna(i) and
                                  int(i) < len(labels) else None)
            return out

        return df._derive(fn)


# --------------------------------------------------------------------------
class OneHotEncoder(Estimator):
    """Index column(s) → sparse one-hot vectors, `dropLast=True` like MLlib."""

    def _init_params(self):
        self._declareParam("inputCols", doc="input index columns")
        self._declareParam("outputCols", doc="output vector columns")
        self._declareParam("inputCol", doc="input index column")
        self._declareParam("outputCol", doc="output vector column")
        self._declareParam("dropLast", default=True, doc="drop last category")
        self._declareParam("handleInvalid", default="error", doc="error|keep")

    def __init__(self, inputCols=None, outputCols=None, inputCol=None,
                 outputCol=None, dropLast: Optional[bool] = None, handleInvalid=None):
        super().__init__()
        self._set(inputCols=inputCols, outputCols=outputCols, inputCol=inputCol,
                  outputCol=outputCol, handleInvalid=handleInvalid)
        if dropLast is not None:
            self._set(dropLast=dropLast)

    def _in_out(self):
        multi = self.getOrDefault("inputCols")
        if multi:
            return list(multi), list(self.getOrDefault("outputCols"))
        return [self.getOrDefault("inputCol")], [self.getOrDefault("outputCol")]

    def _fit(self, df) -> "OneHotEncoderModel":
        in_cols, _ = self._in_out()
        pdf = df.toPandas()
        sizes = [int(pd.to_numeric(pdf[c], errors="coerce").max()) + 1
                 if len(pdf) else 0 for c in in_cols]
        m = OneHotEncoderModel(categorySizes=sizes)
        m._inherit_params(self)
        return m


class OneHotEncoderModel(Model):
    def _init_params(self):
        OneHotEncoder._init_params(self)

    def __init__(self, categorySizes: Optional[List[int]] = None):
        super().__init__()
        self.categorySizes: List[int] = categorySizes or []

    def _transform(self, df):
        in_cols, out_cols = OneHotEncoder._in_out(self)
        drop_last = bool(self.getOrDefault("dropLast"))
        sizes = self.categorySizes

        def fn(pdf, ctx):
            out = pdf.copy(deep=False)  # CoW: column adds never touch the parent
            for c, oc, size in zip(in_cols, out_cols, sizes):
                width = size - 1 if drop_last else size
                idx = pd.to_numeric(out[c], errors="coerce").to_numpy(dtype=np.float64)
                na = ~np.isfinite(idx)
                block = np.zeros((len(idx), width))
                ok = ~na & (idx >= 0) & (idx < width)  # dropped-last → all-zero row
                block[np.nonzero(ok)[0], idx[ok].astype(np.intp)] = 1.0
                block[na] = np.nan
                # columnar one-hot: dense (n, width) block; elements
                # materialize as SparseVector on access for MLlib parity
                out[oc] = vector_series(block, index=out.index, sparse=True, na=na)
            return out

        res = df._derive(fn)
        # publish output widths as column metadata so VectorAssembler never
        # needs a data peek for OHE inputs (streaming frames cannot peek)
        for oc, size in zip(out_cols, sizes):
            res._ml_attrs[oc] = {
                "numFeatures": size - 1 if drop_last else size}
        return res

    def _extra_metadata(self):
        return {"categorySizes": self.categorySizes}

    def _load_state(self, path, meta):
        self.categorySizes = list(meta.get("categorySizes", []))


# --------------------------------------------------------------------------
def imputer_surrogate(col: pd.Series, strategy: str) -> float:
    """One column's Imputer fill: NaN (and what does not parse) is
    missing, +-inf is a value, an empty column fills with 0."""
    s = pd.to_numeric(col, errors="coerce").dropna()
    if not len(s):
        return 0.0
    if strategy == "median":
        return float(s.median())
    if strategy == "mode":
        return float(s.mode().iloc[0])
    return float(s.mean())


class Imputer(Estimator):
    """Fill numeric nulls with per-column median/mean/mode
    (`ML 01:251-256` uses strategy="median")."""

    def _init_params(self):
        self._declareParam("inputCols", doc="columns to impute")
        self._declareParam("outputCols", doc="imputed output columns")
        self._declareParam("strategy", default="mean", doc="mean|median|mode")
        self._declareParam("missingValue", default=float("nan"), doc="value treated as missing")

    def __init__(self, strategy: Optional[str] = None, inputCols=None, outputCols=None,
                 missingValue: Optional[float] = None):
        super().__init__()
        self._set(strategy=strategy, inputCols=inputCols, outputCols=outputCols,
                  missingValue=missingValue)

    def setStrategy(self, v):
        return self._set(strategy=v)

    def _fit(self, df) -> "ImputerModel":
        in_cols = list(self.getOrDefault("inputCols"))
        strategy = self.getOrDefault("strategy")
        pdf = df.toPandas()
        surrogates = {c: imputer_surrogate(pdf[c], strategy) for c in in_cols}
        m = ImputerModel(surrogates=surrogates)
        m._inherit_params(self)
        return m


class ImputerModel(Model):
    def _init_params(self):
        Imputer._init_params(self)

    def __init__(self, surrogates: Optional[Dict[str, float]] = None):
        super().__init__()
        self.surrogates = surrogates or {}

    @property
    def surrogateDF(self):
        from ..frame.session import get_session
        return get_session().createDataFrame(pd.DataFrame([self.surrogates]))

    def _transform(self, df):
        in_cols = list(self.getOrDefault("inputCols"))
        out_cols = list(self.getOrDefault("outputCols") or in_cols)
        surro = self.surrogates

        def fn(pdf, ctx):
            out = pdf.copy(deep=False)  # CoW: column adds never touch the parent
            for c, oc in zip(in_cols, out_cols):
                s = pd.to_numeric(out[c], errors="coerce")
                out[oc] = s.fillna(surro[c])
            return out

        return df._derive(fn)

    def _extra_metadata(self):
        return {"surrogates": self.surrogates}

    def _load_state(self, path, meta):
        self.surrogates = dict(meta.get("surrogates", {}))


# --------------------------------------------------------------------------
class StandardScaler(Estimator):
    def _init_params(self):
        self._declareParam("inputCol", doc="vector input")
        self._declareParam("outputCol", doc="scaled output")
        self._declareParam("withMean", default=False, doc="center")
        self._declareParam("withStd", default=True, doc="scale to unit std")

    def __init__(self, inputCol=None, outputCol=None, withMean=None, withStd=None):
        super().__init__()
        self._set(inputCol=inputCol, outputCol=outputCol, withMean=withMean,
                  withStd=withStd)

    def _fit(self, df) -> "StandardScalerModel":
        from ._staging import extract_features
        X = extract_features(df, self.getOrDefault("inputCol"))
        mean = X.mean(axis=0)
        std = X.std(axis=0, ddof=1)
        m = StandardScalerModel(mean=mean, std=std)
        m._inherit_params(self)
        return m


class StandardScalerModel(Model):
    def _init_params(self):
        StandardScaler._init_params(self)

    def __init__(self, mean=None, std=None):
        super().__init__()
        self.mean = np.asarray(mean) if mean is not None else None
        self.std = np.asarray(std) if std is not None else None

    def _transform(self, df):
        ic = self.getOrDefault("inputCol")
        oc = self.getOrDefault("outputCol")
        with_mean = bool(self.getOrDefault("withMean"))
        with_std = bool(self.getOrDefault("withStd"))
        mean, std = self.mean, np.where(self.std == 0, 1.0, self.std)

        def fn(pdf, ctx):
            out = pdf.copy(deep=False)  # CoW: column adds never touch the parent
            X = to_matrix(out[ic])   # zero-copy for columnar vector columns
            if with_mean:
                X = X - mean
            if with_std:
                X = X / std
            elif not with_mean:
                X = X.copy()
            out[oc] = vector_series(X, index=out.index)
            return out

        return df._derive(fn)

    def _save_state(self, path):
        from .base import save_arrays
        save_arrays(path, mean=self.mean, std=self.std)

    def _load_state(self, path, meta):
        from .base import load_arrays
        d = load_arrays(path)
        self.mean, self.std = d.get("mean"), d.get("std")


# --------------------------------------------------------------------------
class Bucketizer(Transformer):
    def _init_params(self):
        self._declareParam("splits", doc="bucket boundaries")
        self._declareParam("inputCol", doc="input column")
        self._declareParam("outputCol", doc="output column")
        self._declareParam("handleInvalid", default="error", doc="error|skip|keep")

    def __init__(self, splits=None, inputCol=None, outputCol=None, handleInvalid=None):
        super().__init__()
        self._set(splits=splits, inputCol=inputCol, outputCol=outputCol,
                  handleInvalid=handleInvalid)

    def _transform(self, df):
        splits = np.asarray(self.getOrDefault("splits"), dtype=float)
        ic, oc = self.getOrDefault("inputCol"), self.getOrDefault("outputCol")

        def fn(pdf, ctx):
            out = pdf.copy(deep=False)  # CoW: column adds never touch the parent
            x = pd.to_numeric(out[ic], errors="coerce").values
            idx = np.digitize(x, splits[1:-1], right=False).astype(float)
            idx[~np.isfinite(x)] = np.nan
            out[oc] = idx
            return out

        return df._derive(fn)


# --------------------------------------------------------------------------
class RFormula(Estimator):
    """R-style modeling formula: `label ~ .` / `label ~ a + b`
    (`ML 04:110-117`, `Labs/ML 03L:33-39`). Strings are indexed + one-hot
    encoded; numerics pass through; output = featuresCol + labelCol."""

    def _init_params(self):
        self._declareParam("formula", doc="R formula")
        self._declareParam("featuresCol", default="features", doc="features output")
        self._declareParam("labelCol", default="label", doc="label output")
        self._declareParam("handleInvalid", default="error", doc="error|skip|keep")

    def __init__(self, formula: Optional[str] = None, featuresCol=None,
                 labelCol=None, handleInvalid=None):
        super().__init__()
        self._set(formula=formula, featuresCol=featuresCol, labelCol=labelCol,
                  handleInvalid=handleInvalid)

    def _terms(self, df):
        """(label, string terms, numeric terms) of the formula over `df`:
        THE parser, shared by the sequential fit below and the column plan
        (`featurizer._try_fast_fit`)."""
        formula = self.getOrDefault("formula")
        m = re.match(r"\s*(.+?)\s*~\s*(.+)\s*", formula)
        if not m:
            raise ValueError(f"cannot parse formula {formula!r}")
        label, rhs = m.group(1), m.group(2)
        sch = {f.name: f.dataType.simpleString() for f in df.schema.fields}
        # strict op/term parse — `term (+ term | - term)*`, R/Spark
        # semantics where `-` EXCLUDES a term ("log_price ~ . - price",
        # `Labs/ML 03L:84`). Unknown terms or malformed sequences raise:
        # a formula that silently dropped or invented features would train
        # a different model than the user wrote.
        tokens = re.findall(r"[+-]|[^\s+-]+", rhs)
        if not tokens or tokens[0] in "+-" or tokens[-1] in "+-":
            raise ValueError(f"cannot parse formula {formula!r}")
        included, excluded = [], []
        op = "+"
        for tok in tokens:
            if tok in "+-":
                if op is not None:
                    raise ValueError(f"cannot parse formula {formula!r}")
                op = tok
                continue
            if op is None:
                raise ValueError(f"cannot parse formula {formula!r}")
            if tok != "." and tok != label and tok not in sch:
                raise ValueError(
                    f"formula {formula!r} references unknown column {tok!r}")
            (included if op == "+" else excluded).append(tok)
            op = None
        terms: List[str] = []
        for t in included:
            terms += [c for c in df.columns if c != label] if t == "." \
                else [t]
        seen = set()
        terms = [t for t in terms
                 if t not in set(excluded) and not
                 (t in seen or seen.add(t))]
        str_terms = [t for t in terms if sch.get(t) == "string"]
        num_terms = [t for t in terms if t not in str_terms]
        return label, str_terms, num_terms

    def _chain(self, str_terms: List[str], num_terms: List[str]) -> List:
        """The stages a formula IS, unfitted and in order: a StringIndexer
        and a OneHotEncoder over the string terms (none where there is no
        such term), then the assembler over the encoded columns and the
        numeric terms."""
        invalid = self.getOrDefault("handleInvalid")
        chain: List = []
        assembled: List[str] = []
        if str_terms:
            idx_cols = [f"{c}__idx" for c in str_terms]
            ohe_cols = [f"{c}__ohe" for c in str_terms]
            chain += [StringIndexer(inputCols=str_terms, outputCols=idx_cols,
                                    handleInvalid=invalid),
                      OneHotEncoder(inputCols=idx_cols, outputCols=ohe_cols)]
            assembled += ohe_cols
        # "error" must actually error on invalid rows (Spark contract);
        # "skip" drops them; "keep" passes NaN through
        chain.append(VectorAssembler(
            inputCols=assembled + num_terms,
            outputCol=self.getOrDefault("featuresCol"),
            handleInvalid=invalid))
        return chain

    def _model(self, stages: List[Transformer], label: str) -> "RFormulaModel":
        model = RFormulaModel(stages=stages, label=label,
                              labelCol=self.getOrDefault("labelCol"))
        model._inherit_params(self)
        return model

    def _fit(self, df) -> "RFormulaModel":
        label, str_terms, num_terms = self._terms(df)
        stages: List[Transformer] = []
        cur = df
        for stage in self._chain(str_terms, num_terms):
            if isinstance(stage, Estimator):
                stage = stage.fit(cur)
                cur = stage.transform(cur)
            stages.append(stage)
        return self._model(stages, label)


class RFormulaModel(Model):
    def _init_params(self):
        RFormula._init_params(self)

    def __init__(self, stages: Optional[List[Transformer]] = None,
                 label: Optional[str] = None, labelCol: str = "label"):
        super().__init__()
        self.stages = stages or []
        self.label_source = label
        self._label_col = labelCol

    def _transform(self, df):
        cur = df
        for s in self.stages:
            cur = s.transform(cur)
        src, dst = self.label_source, self._label_col

        def fn(pdf, ctx):
            out = pdf.copy(deep=False)  # CoW: column adds never touch the parent
            if src in out.columns and dst != src:
                out[dst] = pd.to_numeric(out[src], errors="coerce")
            return out

        return cur._derive(fn)

    def _extra_metadata(self):
        return {"label_source": self.label_source, "label_col": self._label_col,
                "n_stages": len(self.stages)}

    def _save_state(self, path):
        import os
        for i, s in enumerate(self.stages):
            s._save_to(os.path.join(path, "stages", f"{i:02d}_{s.uid}"))

    def _load_state(self, path, meta):
        import os
        from .base import Saveable
        self.label_source = meta.get("label_source")
        self._label_col = meta.get("label_col", "label")
        stage_dir = os.path.join(path, "stages")
        self.stages = []
        if os.path.exists(stage_dir):
            for d in sorted(os.listdir(stage_dir)):
                self.stages.append(Saveable.load(os.path.join(stage_dir, d)))
