"""The compact form's margin pass (`CompactParts.predict_affine` /
`predict_affine_agreeing`: a job a block of rows on the column plan's pool)
against the sequential whole-column pass it replaced, kept here in NumPy as
the plain reference: the float64 margin TO THE BIT, inline and on the pool,
at every block boundary; the logistic summary's accuracy and AUROC from it;
the counters that say where the jobs ran and the one `fit.summary` span."""

import threading
import time

import numpy as np
import pandas as pd
import pytest

from sml_tpu import obs
from sml_tpu.conf import GLOBAL_CONF
from sml_tpu.ml import Pipeline
from sml_tpu.ml import _column_plan as cp
from sml_tpu.ml import classification, featurizer, linear_impl
from sml_tpu.ml.classification import LogisticRegression
from sml_tpu.ml.feature import RFormula
from sml_tpu.ml.featurizer import CompactParts
from sml_tpu.ml.regression import LinearRegression
from sml_tpu.parallel import pipeline


# -- the reference: the margin as one thread made it, a whole column a pass ---
def reference_predict_affine(parts, coef, intercept):
    coef = np.asarray(coef, dtype=np.float64)
    acc = np.full(parts.rows, float(intercept), dtype=np.float64)
    lo = 0
    for item in parts.layout:
        if item[0] == "num":
            acc += coef[lo] * parts.num[item[1]]
            lo += 1
        else:
            _, j, width = item
            idx = parts.codes[j]
            table = np.append(coef[lo:lo + width], 0.0)
            acc += table[np.where((idx >= 0) & (idx < width), idx, width)]
            lo += width
    return acc


def reference_accuracy(margin, y):
    return float(np.mean(((margin > 0).astype(float)) == y))


# -- inputs -------------------------------------------------------------------
#: the course's formula over the listings table: 5 encoded columns
#: (dropLast: 2 + 36 + 6 + 3 + 3 labels) and 17 numeric ones, 62 slots
WIDTHS = (1, 35, 5, 2, 2)
LAYOUTS = {
    "numeric_only": tuple(("num", j) for j in range(17)),
    "encoded_only": tuple(("oh", j, w) for j, w in enumerate(WIDTHS)),
    # the numeric slots between the encoded ones: a slot's place in the
    # layout is not its place in its array
    "the_courses_mix": (("oh", 0, 1), ("num", 0), ("oh", 1, 35))
    + tuple(("num", j) for j in range(1, 9))
    + (("oh", 2, 5), ("oh", 3, 2))
    + tuple(("num", j) for j in range(9, 17)) + (("oh", 4, 2),),
}
ROWS = (1, 65_535, 65_536, 65_537, 200_001)
_tables: dict = {}


def _parts(layout: str, rows: int):
    """The first `rows` rows of one table a layout: numeric slots of several
    scales, codes that run from -1 (an invalid) over the width (the dropped
    last) to past it (a "keep" overflow), 0/1 labels and coefficients."""
    if layout not in _tables:
        n = max(ROWS)
        rng = np.random.default_rng(np.random.SeedSequence([37, len(layout)]))
        num = rng.normal(size=(17, n)) * 10.0 ** rng.integers(-3, 4, (17, 1))
        codes = np.stack([rng.integers(-1, w + 3, n) for w in WIDTHS])
        width = sum(1 if it[0] == "num" else it[2]
                    for it in LAYOUTS[layout])
        _tables[layout] = (num.astype(np.float32), codes.astype(np.int32),
                           width, rng.normal(size=width),
                           (rng.random(n) < 0.4).astype(np.float32))
    num, codes, width, coef, y = _tables[layout]
    parts = CompactParts(np.ascontiguousarray(num[:, :rows]),
                         np.ascontiguousarray(codes[:, :rows]),
                         LAYOUTS[layout], width, None)
    return parts, coef, y[:rows]


def _where(monkeypatch, where, block_rows=None):
    monkeypatch.setattr(cp, "_INLINE_ROWS",
                        0 if where == "pooled" else 1 << 40)
    if block_rows is not None:
        monkeypatch.setattr(featurizer, "_MARGIN_BLOCK_ROWS", block_rows)


# -- the margin, to the bit ---------------------------------------------------
@pytest.mark.parametrize("where", ["inline", "pooled"])
@pytest.mark.parametrize("rows", ROWS)
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_the_blocked_margin_is_the_sequential_pass_to_the_bit(
        monkeypatch, layout, rows, where):
    parts, coef, y = _parts(layout, rows)
    want = reference_predict_affine(parts, coef, -0.75)
    # 65,536 rows a block: the boundaries the row counts were chosen for,
    # and a last block that is short
    _where(monkeypatch, where, 65_536)
    margin, agreeing = parts.predict_affine_agreeing(coef, -0.75, y)
    assert margin.dtype == np.float64 and margin.shape == (rows,)
    assert np.array_equal(margin, want)
    assert type(agreeing) is int
    assert float(np.divide(agreeing, rows)) == reference_accuracy(want, y)
    assert np.array_equal(parts.predict_affine(coef, -0.75), want)


@pytest.mark.parametrize("where", ["inline", "pooled"])
@pytest.mark.parametrize("block_rows", [1, 7, 1000, 1 << 30])
def test_the_block_size_chooses_how_and_not_what(monkeypatch, where,
                                                 block_rows):
    """Whatever a job owns (one row, blocks that do not divide the table,
    the whole table), the margin and the count are the sequential pass's."""
    parts, coef, y = _parts("the_courses_mix", 5003)
    want = reference_predict_affine(parts, coef, 2.5)
    _where(monkeypatch, where, block_rows)
    margin, agreeing = parts.predict_affine_agreeing(coef, 2.5, y)
    assert np.array_equal(margin, want)
    assert agreeing == int(np.sum((want > 0) == y))


@pytest.mark.parametrize("code", [-1, 3, 4, 2**31 - 1, -2**31])
def test_a_code_out_of_range_is_a_row_of_zeros(monkeypatch, code):
    """-1 (invalid), the width itself (the dropped last) and anything past
    it add nothing: the row's margin is the intercept."""
    codes = np.full((1, 300), code, dtype=np.int32)
    parts = CompactParts(np.zeros((0, 300), np.float32), codes,
                         (("oh", 0, 3),), 3, None)
    _where(monkeypatch, "pooled", 64)
    margin = parts.predict_affine(np.array([1.0, 2.0, 4.0]), 0.125)
    assert np.array_equal(margin, np.full(300, 0.125))
    assert np.array_equal(
        margin, reference_predict_affine(parts, [1.0, 2.0, 4.0], 0.125))


def test_no_rows_is_an_empty_margin():
    parts, coef, y = _parts("the_courses_mix", 0)
    margin, agreeing = parts.predict_affine_agreeing(coef, 1.0, y)
    assert margin.shape == (0,) and margin.dtype == np.float64
    assert agreeing == 0


def test_the_threshold_chooses_where_and_not_what(monkeypatch):
    """At the thresholds as they stand: a table under `_INLINE_ROWS` runs
    on the calling thread, one from it on goes to the pool."""
    used = []
    real = cp._executor
    monkeypatch.setattr(cp, "_executor", lambda: used.append(1) or real())
    for rows, pooled in ((cp._INLINE_ROWS - 1, False),
                         (cp._INLINE_ROWS, True), (max(ROWS), True)):
        parts, coef, y = _parts("the_courses_mix", rows)
        del used[:]
        margin, agreeing = parts.predict_affine_agreeing(coef, 0.5, y)
        assert bool(used) is pooled
        want = reference_predict_affine(parts, coef, 0.5)
        assert np.array_equal(margin, want)
        assert float(np.divide(agreeing, rows)) == reference_accuracy(want, y)


def test_a_worker_thread_runs_the_margin_inline(monkeypatch):
    """A task never submits to the pool it runs on: on a worker of either
    host pool the jobs run on that worker."""
    _where(monkeypatch, "pooled", 512)
    parts, coef, y = _parts("the_courses_mix", 5003)
    want = reference_predict_affine(parts, coef, 0.5)

    def must_not_submit():
        raise AssertionError("a worker thread went to the pool")

    def margin():
        assert pipeline.on_host_worker()
        with monkeypatch.context() as m:
            m.setattr(cp, "_executor", must_not_submit)
            return parts.predict_affine(coef, 0.5)

    from_job, = cp.run_tasks([margin], inline=False)
    from_prep, = pipeline.prefetch_map([0], lambda _i: margin(), depth=2)
    assert np.array_equal(from_job, want)
    assert np.array_equal(from_prep, want)


def test_four_threads_take_their_margins_at_once(monkeypatch):
    """The tuning trials' path: every thread fans its blocks out over the
    one pool and waits for its own futures only."""
    _where(monkeypatch, "pooled", 2048)
    cases = [_parts(layout, rows) for layout, rows in
             (("the_courses_mix", 30_011), ("numeric_only", 20_000),
              ("encoded_only", 25_001), ("the_courses_mix", 9_999))]
    got, errors = [None] * len(cases), []

    def trial(i):
        try:
            parts, coef, y = cases[i]
            got[i] = parts.predict_affine_agreeing(coef, float(i), y)
        except BaseException as e:   # noqa: BLE001 (reported below)
            errors.append(e)

    threads = [threading.Thread(target=trial, args=(i,))
               for i in range(len(cases))]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 120
    for t in threads:
        t.join(max(deadline - time.monotonic(), 0.0))
    assert not any(t.is_alive() for t in threads), "a margin pass hung"
    assert not errors, errors
    for i, (parts, coef, y) in enumerate(cases):
        want = reference_predict_affine(parts, coef, float(i))
        assert np.array_equal(got[i][0], want)
        assert got[i][1] == int(np.sum((want > 0) == y))


# -- the summary of a fit -----------------------------------------------------
@pytest.fixture()
def recorder():
    GLOBAL_CONF.set("sml.obs.enabled", True)
    obs.reset()
    try:
        yield obs.RECORDER
    finally:
        GLOBAL_CONF.set("sml.obs.enabled", False)
        obs.reset()


@pytest.fixture()
def compact():
    old = GLOBAL_CONF.get("sml.linear.compactBytes")
    GLOBAL_CONF.set("sml.linear.compactBytes", 0)
    yield
    GLOBAL_CONF.set("sml.linear.compactBytes", old)


def _listings(n, seed=5):
    rng = np.random.default_rng(seed)
    room = rng.choice(["Entire home/apt", "Private room", "Shared room"], n,
                      p=[0.6, 0.3, 0.1])
    hood = rng.choice([f"hood_{i:02d}" for i in range(12)], n)
    beds = rng.integers(0, 6, n).astype(np.float64)
    reviews = rng.integers(0, 300, n).astype(np.float64)
    rating = np.clip(rng.normal(93, 6, n), 20, 100)
    z = 0.5 * (beds - 2.5) + 0.004 * (reviews - 150) + 0.05 * (rating - 93) \
        + 0.8 * (room == "Private room") + rng.logistic(size=n)
    return pd.DataFrame({"room_type": room, "neighbourhood": hood,
                         "beds": beds, "reviews": reviews, "rating": rating,
                         "label": (z > 0).astype(np.float64)})


def _fit_and_capture(spark, monkeypatch, n, partitions=4):
    """`Pipeline([RFormula, LogisticRegression]).fit` on the compact path,
    with the (parts, y) the fused program was handed."""
    seen = []
    real = linear_impl.fit_logistic_compact

    def spy(parts, y, **kw):
        seen.append((parts, y))
        return real(parts, y, **kw)

    monkeypatch.setattr(linear_impl, "fit_logistic_compact", spy)
    df = spark.createDataFrame(_listings(n)).repartition(partitions)
    model = Pipeline(stages=[
        RFormula(formula="label ~ .", handleInvalid="skip"),
        LogisticRegression(maxIter=8)]).fit(df)
    (parts, y), = seen
    return model.stages[-1], parts, y


@pytest.mark.parametrize("rows,where", [(3000, "inline"),
                                        (200_001, "pooled")])
def test_the_summary_of_a_fit_is_the_sequential_margins(
        spark, monkeypatch, compact, recorder, rows, where):
    """Both sides of `_INLINE_ROWS` as it stands: `summary.accuracy` and
    `summary.areaUnderROC` are the values the sequential margin gives, to
    the bit; the pass is eager (inside `fit`, ONE `fit.summary` span on the
    calling thread that notes its workers and blocks) and counted."""
    start = recorder.counters()
    lr, parts, y = _fit_and_capture(spark, monkeypatch, rows)
    now = recorder.counters()
    moved = {k: now[k] - start.get(k, 0) for k in now
             if k.startswith("linear.summary.")
             and now[k] != start.get(k, 0)}
    assert moved == {"linear.summary." + where: 1}
    spans = [e for e in recorder.events()
             if e.kind == "span" and e.name == "fit.summary"]
    assert len(spans) == 1
    assert {e.tid for e in recorder.events()} == {spans[0].tid}, \
        "jobs open no spans and bump no counters"
    pooled = where == "pooled"
    assert spans[0].args["workers"] == (cp._cores() if pooled else 1)
    assert spans[0].args["blocks"] \
        == -(-len(y) // featurizer._MARGIN_BLOCK_ROWS)
    assert spans[0].args["rows"] == len(y) == parts.rows

    # eager: the accuracy is there without the lazy closure having run
    assert lr.summary._lazy_fn is not None
    want = reference_predict_affine(parts, lr.coefficients.toArray(),
                                    lr.intercept)
    assert lr.summary.accuracy == reference_accuracy(want, y)
    assert lr.summary._lazy_fn is not None, "the accuracy forced the AUC"
    assert lr.summary.areaUnderROC == classification._fast_auc(want, y)
    assert 0.6 < lr.summary.areaUnderROC < 1.0
    assert lr.summary.numInstances == len(y)


def test_a_fit_on_a_host_pool_worker_counts_an_inline_pass(
        spark, monkeypatch, compact, recorder):
    """A tuning trial's fit runs on a worker of a host pool: over the
    threshold too its margin jobs run on that worker."""
    monkeypatch.setattr(cp, "_INLINE_ROWS", 0)
    monkeypatch.setattr(featurizer, "_MARGIN_BLOCK_ROWS", 1024)
    df = spark.createDataFrame(_listings(3000)).repartition(1)
    df.toPandas()   # the frame holds its concat: the plan reads one table

    def fit():
        assert pipeline.on_host_worker()
        return Pipeline(stages=[
            RFormula(formula="label ~ .", handleInvalid="skip"),
            LogisticRegression(maxIter=8)]).fit(df).stages[-1]

    start = recorder.counters()
    lr, = pipeline.prefetch_map([0], lambda _i: fit(), depth=2)
    now = recorder.counters()
    assert now.get("linear.summary.inline", 0) \
        - start.get("linear.summary.inline", 0) == 1
    assert now.get("linear.summary.pooled", 0) \
        == start.get("linear.summary.pooled", 0)
    span, = [e for e in recorder.events()
             if e.kind == "span" and e.name == "fit.summary"]
    assert span.args["workers"] == 1 and span.args["blocks"] == 3
    assert 0.5 < lr.summary.accuracy <= 1.0


def test_the_counters_and_the_span_are_registered():
    from sml_tpu.obs import taxonomy
    for name in ("linear.summary.pooled", "linear.summary.inline"):
        assert taxonomy.is_registered("count", name)
    assert taxonomy.is_registered("span", "fit.summary")


def test_a_linear_summarys_mae_takes_the_same_blocks(
        spark, monkeypatch, compact, recorder):
    """`LinearRegression`'s lazy `meanAbsoluteError` on the compact path
    reads the blocked margin when it is read, and counts the pass then."""
    pdf = _listings(3000)
    pdf["price"] = 50.0 + 20.0 * pdf["beds"] + 0.1 * pdf["reviews"]
    seen = []
    real = linear_impl.fit_linear_compact

    def spy(parts, y, **kw):
        seen.append((parts, y))
        return real(parts, y, **kw)

    monkeypatch.setattr(linear_impl, "fit_linear_compact", spy)
    df = spark.createDataFrame(pdf.drop(columns="label"))
    lr = Pipeline(stages=[RFormula(formula="price ~ .", labelCol="price"),
                          LinearRegression(labelCol="price")]
                  ).fit(df).stages[-1]
    (parts, y), = seen
    start = recorder.counters().get("linear.summary.inline", 0)
    mae = lr.summary.meanAbsoluteError
    assert recorder.counters()["linear.summary.inline"] - start == 1
    want = reference_predict_affine(parts, lr.coefficients.toArray(),
                                    lr.intercept)
    assert mae == float(np.mean(np.abs(y - want)))
