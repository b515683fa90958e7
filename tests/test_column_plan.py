"""The fit-time column plan (`ml/_column_plan.py`, `featurizer.try_fast_fit`):
one job a raw column, the jobs side by side on one pool. Every bit of what
the estimator is handed (the block, the keep mask, the fitted prep models,
the shim's attrs) equals the generic sequential fit's, whatever the pool,
and whatever pieces the frame's rows lie in: the plan reads the partitions
where they lie and makes no table-wide concat."""

import sys
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor

import numpy as np
import pandas as pd
import pytest

import sml_tpu.ml._column_plan as cp
import sml_tpu.ml.featurizer as fz
from sml_tpu import obs
from sml_tpu.conf import GLOBAL_CONF
from sml_tpu.ml import Pipeline
from sml_tpu.ml.base import Estimator, Model
from sml_tpu.frame.dataframe import DataFrame
from sml_tpu.ml.feature import (Imputer, OneHotEncoder, RFormula,
                                StandardScaler, StringIndexer,
                                VectorAssembler)
from sml_tpu.ml.regression import RandomForestRegressor


class _SpyModel(Model):
    def _transform(self, df):
        return df


class _Spy(Estimator):
    """An estimator that keeps what `Pipeline.fit` hands it."""

    def _init_params(self):
        self._declareParam("featuresCol", default="features", doc="features")
        self._declareParam("labelCol", default="label", doc="label")

    def _fit(self, df):
        self.frame = df
        compact = getattr(df, "_featurized_compact", None)
        if compact is not None:
            (self.parts, self.labels), = compact.values()
            return _SpyModel()
        if not hasattr(df, "_featurized"):
            df.toPandas()   # the generic chain raises what is really wrong
        (X, keep, self.labels), = df._featurized.values()
        self.seen = (X, keep, dict(df._ml_attrs))
        return _SpyModel()


#: `try_fast_fit` hands the compact form to the linear family, by name
_CompactSpy = type("LinearRegression", (_Spy,), {})


def _table(n, seed, strings="arrow", nulls=True):
    rng = np.random.default_rng(seed)
    # "rare" is seen once; "a" and "b", and "c" and "d", tie in count
    cat = np.array(["a", "b"] * (n // 4) + ["c", "d"] * (n // 8)
                   + ["e"] * (n - 2 * (n // 4) - 2 * (n // 8) - 1) + ["rare"],
                   dtype=object)
    rng.shuffle(cat)
    other = rng.choice(["x", "y", "z"], n).astype(object)
    if nulls:
        cat[rng.random(n) < 0.05] = None
    pdf = pd.DataFrame({
        "x": rng.normal(size=n),
        "y": np.round(rng.normal(size=n), 1),     # many ties for the mode
        "z": rng.integers(0, 9, n),               # an integer column
        "w": rng.normal(size=n),                  # never imputed
        "cat": pd.Series(cat, dtype=object),
        "other": pd.Series(other, dtype=object),
        "label": rng.normal(size=n)})
    pdf.loc[rng.random(n) < 0.1, "x"] = np.nan
    pdf.loc[rng.random(n) < 0.1, "y"] = np.nan
    if strings == "arrow":
        pdf["cat"] = pdf["cat"].astype("str")
        pdf["other"] = pdf["other"].astype("str")
    return pdf


def _chain(strategy="median", invalid="skip", order="frequencyDesc",
           drop_last=None, assembler_invalid="error", cols=("x", "y", "z")):
    imp = [f"{c}_i" for c in cols]
    stages = [Imputer(strategy=strategy, inputCols=list(cols),
                      outputCols=imp),
              StringIndexer(inputCols=["cat", "other"],
                            outputCols=["cat_n", "other_n"],
                            handleInvalid=invalid, stringOrderType=order)]
    cats = ["cat_n", "other_n"]
    if drop_last is not None:
        stages.append(OneHotEncoder(inputCols=cats,
                                    outputCols=["cat_v", "other_v"],
                                    dropLast=drop_last))
        cats = ["cat_v", "other_v"]
    # "w" stands alone between the encoded columns; the imputed ones form
    # a run the sequential pass extracts as one block
    stages.append(VectorAssembler(
        inputCols=[cats[0], "w", cats[1]] + imp, outputCol="features",
        handleInvalid=assembler_invalid))
    return stages


def _fit(spark, pdf, stages, partitions=1):
    df = spark.createDataFrame(pdf, numPartitions=partitions)
    spy = _Spy()
    model = Pipeline(stages=stages + [spy]).fit(df)
    return model.stages[:-1], spy.seen


def _assert_same_fit(plan, generic):
    (p_stages, (pX, pkeep, pattrs)), (g_stages, (gX, gkeep, gattrs)) = \
        plan, generic
    assert pX.dtype == gX.dtype == np.float32
    assert pX.flags.c_contiguous and pX.shape == gX.shape
    assert pX.tobytes() == gX.tobytes()
    assert (pkeep is None) == (gkeep is None)
    if pkeep is not None:
        assert np.array_equal(pkeep, gkeep)
    for p, g in zip(p_stages, g_stages):
        assert type(p) is type(g)
        assert repr(p._params_to_dict()) == repr(g._params_to_dict())
        if hasattr(g, "surrogates"):
            assert list(p.surrogates) == list(g.surrogates)
            assert np.array(list(p.surrogates.values())).tobytes() \
                == np.array(list(g.surrogates.values())).tobytes()
        if hasattr(g, "labelsArray"):
            assert p.labelsArray == g.labelsArray
        if hasattr(g, "categorySizes"):
            assert p.categorySizes == g.categorySizes
    # the generic chain also publishes each encoder output's width
    assert pattrs == {c: gattrs[c] for c in pattrs}
    assert pattrs["features"] == gattrs["features"]


@pytest.fixture()
def counters():
    GLOBAL_CONF.set("sml.obs.enabled", True)
    obs.reset()
    try:
        yield obs.RECORDER
    finally:
        GLOBAL_CONF.set("sml.obs.enabled", False)
        obs.reset()


def _both(spark, monkeypatch, pdf, make_stages, partitions=1, legacy=0):
    """The plan's fit and the generic sequential fit of the same chain."""
    before = obs.RECORDER.counters()
    plan = _fit(spark, pdf, make_stages(), partitions)
    after = obs.RECORDER.counters()
    moved = {k: after.get(k, 0) - before.get(k, 0) for k in after
             if k.startswith("featurize.plan.")}
    assert moved.get("featurize.plan.fits") == 1, "the plan was not taken"
    assert moved.get("featurize.plan.declined", 0) == 0
    assert moved.get("featurize.plan.columns_legacy", 0) == legacy
    with monkeypatch.context() as m:
        m.setattr(fz, "try_fast_fit", lambda *a, **k: None)
        generic = _fit(spark, pdf, make_stages(), partitions)
    _assert_same_fit(plan, generic)
    return plan


CASES = {
    "median": dict(strategy="median"),
    "mean": dict(strategy="mean"),
    "mode": dict(strategy="mode"),
    "indexer_error": dict(invalid="error", nulls=False),
    "indexer_skip": dict(invalid="skip"),
    "indexer_keep": dict(invalid="keep"),
    "frequencyDesc": dict(order="frequencyDesc"),
    "frequencyAsc": dict(order="frequencyAsc"),
    "alphabetDesc": dict(order="alphabetDesc"),
    "alphabetAsc": dict(order="alphabetAsc"),
    "encoder_drop_last": dict(drop_last=True),
    "encoder_keep_last": dict(drop_last=False),
    "encoder_skip_nulls": dict(drop_last=True, invalid="skip"),
    "encoder_of_one_label": dict(drop_last=True, one_label=True),  # width 0
    "object_strings": dict(strings="object", legacy=2),
    "object_strings_keep": dict(strings="object", invalid="keep", legacy=2),
    "partitions": dict(partitions=4),
    "assembler_keep": dict(assembler_invalid="keep"),
}


@pytest.mark.parametrize("pooled", [False, True], ids=["inline", "pooled"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_plan_equals_the_generic_sequential_fit(spark, monkeypatch, counters,
                                                case, pooled):
    opts = dict(CASES[case])
    if pooled:   # every fit on the pool, several uneven blocks of rows
        monkeypatch.setattr(cp, "_INLINE_ROWS", 0)
        monkeypatch.setattr(cp, "_BLOCK_ROWS", 97)
    pdf = _table(801, seed=sorted(CASES).index(case),
                 strings=opts.pop("strings", "arrow"),
                 nulls=opts.pop("nulls", True))
    partitions, legacy = opts.pop("partitions", 1), opts.pop("legacy", 0)
    if opts.pop("one_label", False):
        pdf["other"] = pd.Series(["x"] * len(pdf), dtype="str")
    _both(spark, monkeypatch, pdf, lambda: _chain(**opts),
          partitions=partitions, legacy=legacy)


@pytest.mark.parametrize("rows", [cp._INLINE_ROWS - 1, cp._INLINE_ROWS + 1])
def test_both_sides_of_the_inline_threshold(spark, monkeypatch, counters,
                                            rows):
    pdf = _table(rows, seed=rows)
    workers = []
    real = cp.Plan.__init__

    def spying(self, *a, **k):
        real(self, *a, **k)
        workers.append((self.inline, self.workers))

    monkeypatch.setattr(cp.Plan, "__init__", spying)
    before = counters.counters()
    _both(spark, monkeypatch, pdf, lambda: _chain(drop_last=True),
          partitions=3)
    assert workers == [(rows < cp._INLINE_ROWS,
                        1 if rows < cp._INLINE_ROWS else cp._cores())]
    # under it the plan reads the frame's concat, as it always did for a
    # table that small; over it the three pieces, and only the generic
    # fit of `_both` makes a concat
    after = counters.counters()
    moved = [after.get(k, 0) - before.get(k, 0) for k in (
        "featurize.plan.pieces", "featurize.collect.concats")]
    assert moved == ([1, 2] if rows < cp._INLINE_ROWS else [3, 1])


@pytest.mark.parametrize("strategy", ["median", "mean", "mode"])
def test_nan_and_inf_columns(spark, monkeypatch, counters, strategy):
    """An all-NaN column fills with 0; +-inf is a VALUE to the surrogate
    and is filled in the block; an unimputed column keeps its inf where it
    stands alone and reads NaN inside a run of numeric inputs, as the
    sequential pass has it."""
    pdf = _table(400, seed=11)
    pdf["x"] = np.nan
    pdf.loc[[3, 50], "y"] = [np.inf, -np.inf]
    pdf.loc[[7, 9], "w"] = [np.inf, -np.inf]
    pdf["v"] = pdf["w"]

    def stages():
        st = _chain(strategy=strategy, assembler_invalid="keep")
        st[-1] = VectorAssembler(
            inputCols=["cat_n", "w", "other_n", "x_i", "y_i", "z_i", "v"],
            outputCol="features", handleInvalid="keep")
        return st

    prep, (X, keep, _attrs) = _both(spark, monkeypatch, pdf, stages)
    assert prep[0].surrogates["x"] == 0.0
    kept = np.nonzero(keep)[0]
    lone, in_run = X[:, 1], X[:, 6]
    assert np.isinf(lone[kept.searchsorted(7)]) \
        and np.isnan(in_run[kept.searchsorted(7)])
    if strategy != "mean":   # inf - inf: the mean, and so the fill, is NaN
        assert np.isfinite(X[:, 3:6]).all()


def test_a_numeric_column_fed_to_the_indexer_runs_todays_code(
        spark, monkeypatch, counters):
    pdf = _table(300, seed=5, nulls=False)

    def stages():
        return [StringIndexer(inputCols=["z", "cat"],
                              outputCols=["z_n", "cat_n"]),
                VectorAssembler(inputCols=["z_n", "cat_n", "x"],
                                outputCol="features", handleInvalid="keep")]

    _both(spark, monkeypatch, pdf, stages, legacy=1)


# -- the result does not depend on the pool ---------------------------------
class _LastFirst:
    """A one-thread executor that runs what it was given LAST first, once
    the submissions pause."""

    def __init__(self):
        self.lock = threading.Lock()
        self.tasks, self.ran, self.thread = [], [], None

    def submit(self, fn):
        future = Future()
        with self.lock:
            self.tasks.append((len(self.ran) + len(self.tasks), future, fn))
            if self.thread is None:
                self.thread = threading.Thread(target=self._drain)
                self.thread.start()
        return future

    def _drain(self):
        while True:
            time.sleep(0.05)
            with self.lock:
                if not self.tasks:
                    self.thread = None
                    return
                batch, self.tasks = self.tasks[::-1], []
            for i, future, fn in batch:
                self.ran.append(i)
                try:
                    future.set_result(fn())
                except BaseException as e:   # handed to the caller
                    future.set_exception(e)


def test_the_result_does_not_depend_on_the_pool(spark, monkeypatch, counters):
    pdf = _table(2000, seed=21)
    monkeypatch.setattr(cp, "_INLINE_ROWS", 0)
    monkeypatch.setattr(cp, "_BLOCK_ROWS", 256)
    fits = {}
    with ThreadPoolExecutor(1) as one, ThreadPoolExecutor(8) as eight:
        last_first = _LastFirst()
        for name, pool in [("one", one), ("eight", eight),
                           ("last_first", last_first)]:
            monkeypatch.setattr(cp, "_executor", lambda pool=pool: pool)
            fits[name] = _fit(spark, pdf, _chain(drop_last=True))
    jobs = last_first.ran[:5]
    assert jobs == sorted(jobs, reverse=True), "the jobs did not run reversed"
    _assert_same_fit(fits["one"], fits["eight"])
    _assert_same_fit(fits["one"], fits["last_first"])


def test_eight_fits_from_eight_threads_share_one_pool(spark, monkeypatch,
                                                      counters):
    """The `TpuTrials` shape: the models equal the serial ones, and the
    only threads started are the one pool's."""
    monkeypatch.setattr(cp, "_INLINE_ROWS", 0)
    monkeypatch.setattr(cp, "_BLOCK_ROWS", 128)
    tables = [_table(1500 + 10 * i, seed=30 + i) for i in range(8)]
    serial = [_fit(spark, t, _chain(drop_last=False)) for t in tables]
    before = set(threading.enumerate())
    got = [None] * 8

    def fit(i):
        got[i] = _fit(spark, tables[i], _chain(drop_last=False))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=fit, args=(i,)) for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    for mine, theirs in zip(got, serial):
        assert mine is not None
        _assert_same_fit(mine, theirs)
    started = set(threading.enumerate()) - before - set(threads)
    assert all(t.name.startswith("sml-column") for t in started)
    pool = [t for t in threading.enumerate()
            if t.name.startswith("sml-column")]
    assert 0 < len(pool) <= cp._cores()


@pytest.mark.parametrize("strings", ["arrow", "object"])
def test_an_unseen_label_raises_from_inside_a_job(spark, monkeypatch,
                                                  strings):
    """handleInvalid="error" over a column with nulls: the stage's own
    message, raised on the caller's thread whichever job met it."""
    monkeypatch.setattr(cp, "_INLINE_ROWS", 0)
    pdf = _table(600, seed=8, strings=strings)
    first_null = pdf["cat"][pdf["cat"].isna()].iloc[0]
    message = (f"Unseen label {first_null!r} in column 'cat' "
               f"(handleInvalid='error')")
    jobs = [cp.StringJob("cat", "frequencyDesc", "error"),
            cp.NumericJob("x", "median")]
    for i, job in enumerate(jobs):
        job.row = i
    raised_on = []
    real = cp.StringJob.run

    def spying(self, *a, **k):
        try:
            return real(self, *a, **k)
        except ValueError:
            raised_on.append(threading.current_thread())
            raise

    monkeypatch.setattr(cp.StringJob, "run", spying)
    with pytest.raises(ValueError) as err:
        cp.Plan(cp.Pieces([pdf]), jobs)
    assert str(err.value) == message
    assert raised_on and raised_on[0] is not threading.current_thread()
    with pytest.raises(ValueError) as err:
        _fit(spark, pdf, _chain(invalid="error"))
    assert str(err.value) == message


def test_the_assembler_error_rides_the_interleave(spark, monkeypatch):
    monkeypatch.setattr(cp, "_INLINE_ROWS", 0)
    monkeypatch.setattr(cp, "_BLOCK_ROWS", 64)
    pdf = _table(500, seed=9, nulls=False)
    pdf.loc[333, "w"] = np.nan
    with pytest.raises(ValueError, match="VectorAssembler found NaN/null"):
        _fit(spark, pdf, _chain())
    # a row an indexer skips may hold what it likes
    pdf["cat"] = pdf["cat"].astype(object)
    pdf.loc[333, "cat"] = None
    pdf["cat"] = pdf["cat"].astype("str")
    _prep, (X, keep, _attrs) = _fit(spark, pdf, _chain())
    assert len(X) == 499 and not keep[333]


# -- the frame's rows where they lie ----------------------------------------
def _cut(pdf, sizes):
    """The table's rows in pieces of these sizes, in order."""
    assert sum(sizes) == len(pdf)
    bounds = np.cumsum([0] + list(sizes))
    return [pdf.iloc[lo:hi].reset_index(drop=True)
            for lo, hi in zip(bounds, bounds[1:])]


def _null_strings(parts):
    """Piece 1 read back with no string in it: `cat` a column of None
    objects, `other` a column of NaN floats."""
    parts[1]["cat"] = pd.Series([None] * len(parts[1]), dtype=object)
    parts[1]["other"] = np.nan
    return parts


def _object_strings(parts):
    parts[1]["cat"] = parts[1]["cat"].astype(object)
    parts[1]["other"] = parts[1]["other"].astype(object)
    return parts


def _int_and_float(parts):
    z = parts[1]["z"].astype(np.float64)
    z.iloc[::9] = np.nan
    parts[1]["z"] = z
    parts[2]["z"] = parts[2]["z"].astype(np.int32)
    parts[0]["w"] = parts[0]["w"].astype(np.float32)
    return parts


#: the columns a layout's pieces disagree on: gathered to one column each
DISAGREE = {"a_piece_of_null_strings": {"cat", "other"},
            "object_strings_beside_arrow": {"cat", "other"}}

#: rows -> the pieces' sizes; what is done to the pieces
LAYOUTS = {
    "one_piece": (lambda n: [n], None),
    "three_uneven_pieces": (lambda n: [n // 2, 1, n - n // 2 - 1], None),
    "eight_pieces_one_empty": (
        lambda n: [n // 7] * 3 + [0] + [n // 7] * 3 + [n - 6 * (n // 7)],
        None),
    "a_piece_of_null_strings": (lambda n: [n // 3, n // 5, n - n // 3 - n // 5],
                                _null_strings),
    "object_strings_beside_arrow": (
        lambda n: [n // 3, n // 5, n - n // 3 - n // 5], _object_strings),
    "int_in_one_piece_float_in_another": (
        lambda n: [n // 4, n // 2, n - n // 4 - n // 2], _int_and_float),
}


def _tree_chain():
    return _chain(drop_last=True, assembler_invalid="keep")


def _formula_chain():
    return [RFormula(formula="label ~ .", handleInvalid="skip")]


def _fit_frame(df, stages, spy):
    before = obs.RECORDER.counters()
    model = Pipeline(stages=stages + [spy]).fit(df)
    after = obs.RECORDER.counters()
    moved = {k.split("featurize.")[1]: after[k] - before.get(k, 0)
             for k in after if k.startswith("featurize.")
             and after[k] != before.get(k, 0)}
    return model.stages[:-1], spy, moved


def _assert_same_formula(mine, theirs):
    assert (mine.label_source, mine._label_col) \
        == (theirs.label_source, theirs._label_col)
    assert mine._params_to_dict() == theirs._params_to_dict()
    assert [type(s) for s in mine.stages] == [type(s) for s in theirs.stages]
    for a, b in zip(mine.stages, theirs.stages):
        assert a._params_to_dict() == b._params_to_dict()
        assert getattr(a, "labelsArray", None) == getattr(b, "labelsArray", None)
        assert getattr(a, "categorySizes", None) \
            == getattr(b, "categorySizes", None)


def _same_bits(a, b):
    return a.dtype == b.dtype and a.shape == b.shape \
        and a.tobytes() == b.tobytes()


@pytest.mark.parametrize("pooled", [False, True], ids=["inline", "pooled"])
@pytest.mark.parametrize("chain", ["tree_block", "formula_compact"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_the_fit_on_the_pieces_is_the_fit_on_their_concat(
        spark, monkeypatch, counters, layout, chain, pooled):
    """Stage models, block, `keep`, compact parts and label: to the bit
    what the one-piece concat gives, which is what the generic sequential
    fit gives; and the concat is not made."""
    # (a table this small reads its concat: the threshold is taken away,
    # and the inline case keeps its jobs on the calling thread)
    monkeypatch.setattr(cp, "_INLINE_ROWS", 0)
    monkeypatch.setattr(cp, "_BLOCK_ROWS", 97)
    if not pooled:
        monkeypatch.setattr(cp, "runs_inline", lambda rows: True)
    held = GLOBAL_CONF.get("sml.linear.compactBytes")
    GLOBAL_CONF.set("sml.linear.compactBytes", 0)
    try:
        sizes, spoil = LAYOUTS[layout]
        pdf = _table(803, seed=60 + sorted(LAYOUTS).index(layout))
        pieces = _cut(pdf, sizes(len(pdf)))
        if spoil is not None:
            pieces = spoil(pieces)
        whole = pd.concat(pieces, ignore_index=True)   # the frame's concat
        stages, spy = (_tree_chain, _Spy) if chain == "tree_block" \
            else (_formula_chain, _CompactSpy)

        gathered, column = set(), cp.Pieces.column

        def spying(self, col):
            if len(self.parts) > 1:
                gathered.add(col)
            return column(self, col)

        df = DataFrame.from_partitions(pieces, session=spark)
        with monkeypatch.context() as m:
            m.setattr(cp.Pieces, "column", spying)
            prep, got, moved = _fit_frame(df, stages(), spy())
        # a column is read piece by piece: only the label, and a column
        # whose pieces disagree in storage, is gathered to one
        assert gathered == ({"label"} | DISAGREE.get(layout, set())
                            if len(pieces) > 1 else set())
        one = DataFrame.from_partitions([whole], session=spark)
        prep1, want, moved1 = _fit_frame(one, stages(), spy())

        # the counters: the pieces read, and no table-wide concat but the
        # one-piece frame's own (`toPandas` of one partition, as before)
        assert moved.pop("plan.pieces") == len(pieces)
        assert moved1.pop("plan.pieces") == 1
        assert moved1.pop("collect.concats") == 1
        if len(pieces) == 1:
            assert moved.pop("collect.concats") == 1
        else:
            assert "collect.concats" not in moved and df._pdf_cache is None
            # the estimator's frame lies over the same partitions
            assert all(a is b for a, b in zip(got.frame._materialize(),
                                              pieces))
        assert moved == moved1 and moved["plan.fits"] == 1   # legacy too

        label = "label"
        assert _same_bits(np.asarray(got.labels[label]),
                          np.asarray(want.labels[label]))
        assert _same_bits(np.asarray(got.labels[label]),
                          whole[label].to_numpy())
        if chain == "tree_block":
            _assert_same_fit((prep, got.seen), (prep1, want.seen))
            with monkeypatch.context() as m:   # the sequential reference
                m.setattr(fz, "try_fast_fit", lambda *a, **k: None)
                gprep, generic, gmoved = _fit_frame(df, stages(), _Spy())
            # (a frame of one piece holds its table since the first fit)
            assert gmoved == ({"collect.concats": 1} if len(pieces) > 1
                              else {})
            _assert_same_fit((prep, got.seen), (gprep, generic.seen))
            return
        _assert_same_formula(prep[0], prep1[0])
        mine, theirs = got.parts, want.parts
        assert _same_bits(mine.num, theirs.num)
        assert _same_bits(mine.codes, theirs.codes)
        assert (mine.layout, mine.width) == (theirs.layout, theirs.width)
        assert mine.keep is not None and _same_bits(mine.keep, theirs.keep)
        # the sequential reference: the formula's own fit and transform
        seq = stages()[0].fit(one)
        _assert_same_formula(prep[0], seq)
        from sml_tpu.ml.linalg import to_matrix
        out = seq.transform(one).toPandas()
        assert _same_bits(mine.expand_host(),
                          np.ascontiguousarray(to_matrix(out["features"]),
                                               dtype=np.float32))
        assert 0 < len(out) == int(mine.keep.sum()) < len(whole)
    finally:
        GLOBAL_CONF.set("sml.linear.compactBytes", held)


def test_a_frame_that_holds_its_concat_is_read_from_it(spark, monkeypatch,
                                                        counters):
    """A second fit of one frame (a grid, a cross-validation over one
    split) reads the memo; a plan that declines makes the concat, once."""
    monkeypatch.setattr(cp, "_INLINE_ROWS", 400)
    pdf = _table(500, seed=70)
    df = spark.createDataFrame(pdf, numPartitions=4)
    _prep, spy, moved = _fit_frame(df, _tree_chain(), _Spy())
    assert moved == {"plan.fits": 1, "plan.pieces": 4}
    assert df._pdf_cache is None

    declining = _tree_chain() + [StandardScaler(inputCol="features",
                                                outputCol="scaled")]
    tree = RandomForestRegressor(featuresCol="scaled", maxBins=8, maxDepth=2,
                                 numTrees=2, seed=1)
    _prep, _tree, moved = _fit_frame(df, declining, tree)
    # (the forest's own `_extract` handed its block on whole, as it was)
    assert moved == {"plan.declined": 1, "collect.concats": 1,
                     "extract.whole": 1}
    memo = df._pdf_cache
    assert memo is not None and len(memo) == 500
    _prep, _tree, moved = _fit_frame(df, declining, tree)
    # the memo: no second concat
    assert moved == {"plan.declined": 1, "extract.whole": 1}

    _prep, again, moved = _fit_frame(df, _tree_chain(), _Spy())
    assert moved == {"plan.fits": 1, "plan.pieces": 1}
    assert df._pdf_cache is memo
    piece, = again.frame._materialize()
    assert np.shares_memory(piece["w"].to_numpy(), memo["w"].to_numpy())
    _assert_same_fit((_prep, again.seen), (_prep, spy.seen))


# -- counters and spans -----------------------------------------------------
def test_counters_say_which_fits_took_the_plan(spark, monkeypatch, counters):
    from sml_tpu.obs import taxonomy
    for name in ("fits", "declined", "columns_legacy", "pieces"):
        assert taxonomy.is_registered("count", "featurize.plan." + name)
    assert taxonomy.is_registered("count", "featurize.collect.concats")
    assert taxonomy.is_registered("emit", "featurize.plan.declined")

    def moved(before, prefix="featurize.plan."):
        now = counters.counters()
        return {k[len(prefix):]: now[k] - before.get(k, 0)
                for k in now if k.startswith(prefix)
                and now[k] != before.get(k, 0)}

    pdf = _table(300, seed=40)
    df = spark.createDataFrame(pdf)
    start = counters.counters()
    Pipeline(stages=_chain() + [_Spy()]).fit(df)
    assert moved(start) == {"fits": 1, "pieces": 1}   # a small table: its concat
    assert moved(start, "featurize.collect.") == {"concats": 1}

    start = counters.counters()
    obj = _table(300, seed=41, strings="object")
    Pipeline(stages=_chain() + [_Spy()]).fit(spark.createDataFrame(obj))
    assert moved(start) == {"fits": 1, "pieces": 1, "columns_legacy": 2}

    # a stage outside the chain: the generic sequential fit, and why
    start = counters.counters()
    tree = RandomForestRegressor(featuresCol="scaled", maxBins=8, maxDepth=2,
                                 numTrees=2, seed=1)
    Pipeline(stages=_chain() + [
        StandardScaler(inputCol="features", outputCol="scaled"),
        tree]).fit(df)
    assert moved(start) == {"declined": 1}
    reasons = [e.args["reason"] for e in counters.events()
               if e.name == "featurize.plan.declined" and "reason" in e.args]
    assert reasons == ["no VectorAssembler before the estimator"]


def test_spans_stay_on_the_calling_thread(spark, monkeypatch, counters):
    """Jobs open no span: every span of a fit is the caller's, the phases
    stay disjoint inside the root, and `fit.featurize` (the whole plan)
    is at most the fit's wall."""
    monkeypatch.setattr(cp, "_INLINE_ROWS", 0)
    monkeypatch.setattr(cp, "_BLOCK_ROWS", 512)
    pdf = _table(4000, seed=50)
    df = spark.createDataFrame(pdf)
    df.cache()
    df.count()
    tree = RandomForestRegressor(maxBins=8, maxDepth=2, numTrees=2, seed=1)
    obs.reset()
    t0 = time.perf_counter()
    Pipeline(stages=_chain() + [tree]).fit(df)
    wall = time.perf_counter() - t0
    events = counters.events()
    spans = [e for e in events if e.kind == "span"]
    root, = [e for e in spans if e.name == "fit"]
    assert {e.tid for e in events} == {root.tid}
    children = sorted((e for e in spans
                       if e.args.get("parent") == root.args["span"]),
                      key=lambda e: e.ts)
    names = [e.name for e in children]
    assert names[:3] == ["fit.collect", "fit.featurize", "fit.prep"]
    for first, second in zip(children, children[1:]):
        assert first.ts + first.dur <= second.ts + 1e-6
    assert sum(e.dur for e in children) <= root.dur + 1e-6
    plan = children[1]
    assert plan.args["workers"] == cp._cores()
    assert plan.args["columns"] == 6 and plan.args["rows"] == 4000
    totals = counters.counters()
    assert totals["span_s.fit.featurize"] <= wall
    assert totals["featurize.plan.fits"] == 1
