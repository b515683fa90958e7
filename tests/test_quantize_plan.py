"""The quantize plan (`tree_impl.make_bins`: a job a column for the bin
statistics, a job a block of rows for the bins, on the column plan's pool)
against the sequential quantizer it replaced, kept here in NumPy as the
plain reference: `binned`, its dtype, `edges` and `cat_remap` TO THE BIT,
inline and on the pool, through the C++ kernel and through NumPy; the
callers that bin concurrently or from a worker thread; the counters and
the two phases' spans."""

import threading
import time

import numpy as np
import pytest

from sml_tpu import obs
from sml_tpu.conf import GLOBAL_CONF
from sml_tpu.ml import _column_plan as cp
from sml_tpu.ml import _tree_models as tm
from sml_tpu.ml import tree_impl
from sml_tpu.native import binning as native_binning
from sml_tpu.parallel import pipeline


# -- the reference: the sequential quantizer, as it stood before the plan ----
def reference_make_bins(X, y, max_bins, categorical=None,
                        max_categories_error=True):
    """One thread, a whole-column pass a statistic: a compare, an `any`
    and a masked mean a CATEGORY; a finite filter and `np.quantile` a
    continuous slot; then `reference_bin_columns`."""
    n, F = X.shape
    categorical = categorical or {}
    cont_quantiles, cat_means = {}, {}
    for f in range(F):
        col = X[:, f]
        if f in categorical:
            card = int(categorical[f])
            means = np.full(card, np.inf)
            ids = col.astype(np.int64)
            ids = np.clip(ids, 0, card - 1)
            for c in range(card):
                sel = ids == c
                if sel.any():
                    means[c] = float(y[sel].mean()) if y is not None else c
            cat_means[f] = means
        else:
            finite = col[np.isfinite(col)]
            if len(finite) == 0:
                cont_quantiles[f] = None
                continue
            if len(finite) > 262_144:
                stride = -(-len(finite) // 262_144)
                finite = finite[::stride]
            cont_quantiles[f] = np.quantile(
                finite, np.linspace(0, 1, max_bins + 1)[1:-1])
    binning, edge_list, out_dtype = tree_impl.finalize_binning(
        F, max_bins, categorical, cont_quantiles, cat_means,
        max_categories_error=max_categories_error)
    binned = reference_bin_columns(X, edge_list, binning.cat_remap, out_dtype)
    return binned, binning


def reference_bin_columns(X, edge_list, remaps, out_dtype=np.int32):
    n, F = X.shape
    binned = np.zeros((n, F), dtype=out_dtype)
    for f in range(F):
        if f in remaps:
            continue
        qs = edge_list[f]
        if len(qs) == 0:
            continue
        col = X[:, f]
        binned[:, f] = np.searchsorted(qs, col, side="left").astype(out_dtype)
        binned[~np.isfinite(col), f] = 0
    for f, rank in remaps.items():
        ids = np.clip(X[:, f].astype(np.int64), 0, len(rank) - 1)
        binned[:, f] = rank[ids]
    return binned


# -- inputs -------------------------------------------------------------------
def _table(n, seed, F=6, cat=None, dtype=np.float32):
    """Continuous columns of several shapes (normal, heavy ties, skewed),
    categorical slots `cat` = {slot: cardinality} whose labels differ by
    category, and a float32 label."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 31]))
    X = rng.normal(size=(n, F))
    if F > 1:
        X[:, 1] = rng.integers(0, 5, n)            # a few distinct values
    if F > 2:
        X[:, 2] = np.exp(3 * rng.normal(size=n))   # long tail
    y = rng.normal(size=n)
    for slot, card in (cat or {}).items():
        ids = rng.integers(0, card, n)
        X[:, slot] = ids
        y += 0.1 * ids
    return X.astype(dtype), y.astype(np.float32)


def _case_non_finite(n=5000):
    X, y = _table(n, 1)
    rng = np.random.default_rng(2)
    X[rng.random(n) < 0.05, 0] = np.nan
    X[rng.random(n) < 0.02, 0] = np.inf
    X[rng.random(n) < 0.02, 3] = -np.inf
    X[:, 4] = np.nan                               # nothing finite at all
    X[:, 5] = 7.25                                 # one value
    return X, y, 32, None, {}


def _case_categories(n=6000):
    # absent categories (4 and 9 never drawn), ids under 0 and over the
    # cardinality, fractional ids, and what int64 cannot hold
    X, y = _table(n, 3, cat={0: 12, 4: 3})
    X[X[:, 0] == 4, 0] = 5
    X[X[:, 0] == 9, 0] = 2
    X[::97, 0] = -3
    X[::89, 0] = 40
    X[::83, 0] = 6.9
    X[::79, 0] = -0.5
    X[5, 0], X[6, 0], X[7, 0], X[8, 0] = np.nan, np.inf, -np.inf, 1e30
    return X, y, 16, {0: 12, 4: 3}, {}


def _case_stride(n=700_000):
    # over 262,144 finite rows: the strided subsample; one column with so
    # many NaN that it falls back under it
    X, y = _table(n, 4, F=4, cat={3: 36})
    X[np.random.default_rng(5).random(n) < 0.7, 0] = np.nan
    return X, y, 64, {3: 36}, {}


def _case_float64_fortran(n=9000):
    X, y = _table(n, 6, cat={5: 7}, dtype=np.float64)
    return np.asfortranarray(X), y, 40, {5: 7}, {}


def _case_wide_cardinality(n=20_000):
    X, y = _table(n, 7, F=3, cat={1: 300})
    return X, y, 64, {1: 300}, {"max_categories_error": False}


def _case_one_column(n=3000):
    X, y = _table(n, 8, F=1)
    return X, y, 32, None, {}


def _case_one_categorical_column(n=3000):
    X, y = _table(n, 9, F=1, cat={0: 5})
    return X, y, 8, {0: 5}, {}


def _case_no_label(n=4000):
    X, _ = _table(n, 10, cat={2: 6})
    return X, None, 16, {2: 6}, {}


def _case_float64_label(n=4000):
    X, y = _table(n, 11, cat={2: 6})
    return X, y.astype(np.float64) * 1e-3, 16, {2: 6}, {}


def _case_over_the_threshold(n=cp._INLINE_ROWS + 4321, seed=12):
    X, y = _table(n, seed, F=10, cat={0: 36, 1: 3, 2: 6})
    return X, y, 64, {0: 36, 1: 3, 2: 6}, {}


def _case_no_rows():
    X, y = _table(0, 13)
    return X, y, 16, None, {}


CASES = {
    "nan_inf_all_nan_constant": _case_non_finite,
    "categories_absent_and_out_of_range": _case_categories,
    "stride_over_262144_rows": _case_stride,
    "float64_fortran": _case_float64_fortran,
    "cardinality_over_255": _case_wide_cardinality,
    "one_column": _case_one_column,
    "one_categorical_column": _case_one_categorical_column,
    "no_label": _case_no_label,
    "float64_label": _case_float64_label,
    "over_the_inline_threshold": _case_over_the_threshold,
    "no_rows": _case_no_rows,
}


def _assert_same_bins(got, want):
    (binned, binning), (ref, ref_binning) = got, want
    assert binned.dtype == ref.dtype
    assert binned.shape == ref.shape
    np.testing.assert_array_equal(binned, ref)
    assert binning.edges.dtype == ref_binning.edges.dtype
    assert binning.edges.tobytes() == ref_binning.edges.tobytes()
    assert sorted(binning.cat_remap) == sorted(ref_binning.cat_remap)
    for slot, rank in ref_binning.cat_remap.items():
        assert binning.cat_remap[slot].dtype == rank.dtype
        np.testing.assert_array_equal(binning.cat_remap[slot], rank)


def _where(monkeypatch, where, kernel):
    # few rows a block, so that every case has several blocks
    monkeypatch.setattr(cp, "_BLOCK_ROWS", 1024)
    monkeypatch.setattr(cp, "_INLINE_ROWS",
                        0 if where == "pooled" else 1 << 40)
    if kernel == "numpy":
        monkeypatch.setattr(native_binning, "_lib", lambda: None)
    elif native_binning._lib() is None:
        pytest.skip("no compiler built native/binning.cc here")


@pytest.mark.parametrize("kernel", ["native", "numpy"])
@pytest.mark.parametrize("where", ["inline", "pooled"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_plan_is_the_sequential_quantizer_to_the_bit(
        monkeypatch, case, where, kernel):
    X, y, max_bins, categorical, kw = CASES[case]()
    with np.errstate(invalid="ignore"):   # NaN cast to int64, both sides
        want = reference_make_bins(X, y, max_bins, categorical, **kw)
        _where(monkeypatch, where, kernel)
        got = tree_impl.make_bins(X, y, max_bins, categorical, **kw)
        _assert_same_bins(got, want)
        # the saved binning quantizes fresh rows to the same bins
        np.testing.assert_array_equal(
            tree_impl.bin_with(X[:257], got[1]), want[0][:257])


def test_the_threshold_chooses_where_and_not_what(monkeypatch):
    """At the threshold as it stands: a table under it runs inline, one
    over it on the pool, and a block boundary inside the table (the last
    block is short) changes nothing."""
    used = []
    real = cp._executor
    monkeypatch.setattr(cp, "_executor", lambda: used.append(1) or real())
    X, y, max_bins, categorical, _ = _case_over_the_threshold()
    for rows, pooled in ((cp._INLINE_ROWS - 1, False), (len(X), True)):
        del used[:]
        got = tree_impl.make_bins(X[:rows], y[:rows], max_bins, categorical)
        assert bool(used) is pooled
        _assert_same_bins(got, reference_make_bins(
            X[:rows], y[:rows], max_bins, categorical))


def test_the_maxbins_error_is_raised_on_the_callers_thread():
    X, y = _table(500, 14, cat={0: 40})
    with pytest.raises(ValueError, match="categorical feature 0 has 40"):
        tree_impl.make_bins(X, y, 32, {0: 40})


def test_bin_columns_takes_any_result_dtype():
    """A result dtype the kernel has no form of runs the NumPy code."""
    X, y, max_bins, categorical, _ = _case_categories()
    binned, binning = tree_impl.make_bins(X, y, max_bins, categorical)
    edge_list, _ = tree_impl.binning_edges_and_dtype(binning)
    wide = tree_impl._bin_columns(X, edge_list, binning.cat_remap, np.int64)
    assert wide.dtype == np.int64
    np.testing.assert_array_equal(wide, binned)


# -- concurrency and nesting ---------------------------------------------------
def _join_all(threads, seconds=120):
    deadline = time.monotonic() + seconds
    for t in threads:
        t.join(max(deadline - time.monotonic(), 0.0))
    assert not any(t.is_alive() for t in threads), "a quantize hung"


def test_four_threads_bin_four_tables_at_once(monkeypatch):
    """The tuning trials' path: every thread fans out over the one pool
    and waits for its own futures only."""
    monkeypatch.setattr(cp, "_INLINE_ROWS", 0)
    monkeypatch.setattr(cp, "_BLOCK_ROWS", 2048)
    tables = [_table(30_000, 20 + i, F=8, cat={0: 9, 3: 4}) for i in range(4)]
    want = [reference_make_bins(X, y, 32, {0: 9, 3: 4}) for X, y in tables]
    got, errors = [None] * 4, []

    def trial(i):
        try:
            X, y = tables[i]
            got[i] = tm._cached_bins(X, y, 32, {0: 9, 3: 4})
        except BaseException as e:   # noqa: BLE001 (reported below)
            errors.append(e)

    threads = [threading.Thread(target=trial, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    _join_all(threads)
    assert not errors, errors
    for g, w in zip(got, want):
        _assert_same_bins(g, w)


def test_the_same_table_from_two_threads_quantizes_once(monkeypatch):
    monkeypatch.setattr(cp, "_INLINE_ROWS", 0)
    X, y = _table(40_000, 30, F=8, cat={0: 9})
    calls = []
    real = tree_impl.make_bins

    def counted(*a, **k):
        calls.append(threading.get_ident())
        time.sleep(0.2)            # the other thread arrives meanwhile
        return real(*a, **k)

    monkeypatch.setattr(tree_impl, "make_bins", counted)
    got = [None, None]

    def trial(i):
        got[i] = tm._cached_bins(X, y, 32, {0: 9})

    threads = [threading.Thread(target=trial, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    _join_all(threads)
    assert len(calls) == 1
    assert got[0] is got[1]
    _assert_same_bins(got[0], reference_make_bins(X, y, 32, {0: 9}))


def test_a_worker_thread_bins_inline(monkeypatch):
    """`ml/_chunked.py` bins a chunk on a worker of `parallel.pipeline`,
    and nothing keeps a column plan job from quantizing: on a worker of
    either pool the jobs run inline, so a task never submits to the pool
    it runs on (a pool of ONE thread would wait for itself for ever)."""
    monkeypatch.setattr(cp, "_INLINE_ROWS", 0)
    monkeypatch.setattr(cp, "_BLOCK_ROWS", 512)
    X, y, max_bins, categorical, _ = _case_categories()
    want = reference_make_bins(X, y, max_bins, categorical)
    edge_list, out_dtype = tree_impl.binning_edges_and_dtype(want[1])

    def must_not_submit():
        raise AssertionError("a worker thread went to the pool")

    def both():
        assert pipeline.on_host_worker()
        with monkeypatch.context() as m:
            m.setattr(cp, "_executor", must_not_submit)
            return (tree_impl.make_bins(X, y, max_bins, categorical),
                    tree_impl._bin_columns(X, edge_list, want[1].cat_remap,
                                           out_dtype))

    assert not pipeline.on_host_worker()
    # a job of the column plan's own pool ...
    from_job, = cp.run_tasks([both], inline=False)
    # ... and a prep of the chunk pipeline, as `_chunked.ingest_source`
    from_prep, = pipeline.prefetch_map([0], lambda _i: both(), depth=2)
    for bins, block in (from_job, from_prep):
        _assert_same_bins(bins, want)
        np.testing.assert_array_equal(block, want[0])


# -- the counters and the spans ----------------------------------------------
@pytest.fixture()
def recorder():
    GLOBAL_CONF.set("sml.obs.enabled", True)
    obs.reset()
    try:
        yield obs.RECORDER
    finally:
        GLOBAL_CONF.set("sml.obs.enabled", False)
        obs.reset()


def _moved(recorder, before):
    now = recorder.counters()
    return {k[len("quantize.plan."):]: now[k] - before.get(k, 0)
            for k in now if k.startswith("quantize.plan.")
            and now[k] != before.get(k, 0)}


def test_counters_say_where_the_jobs_ran(recorder):
    from sml_tpu.obs import taxonomy
    for name in ("quantize.plan.fits", "quantize.plan.inline"):
        assert taxonomy.is_registered("count", name)
    for name in ("fit.quantize.bins", "fit.quantize.stats",
                 "fit.quantize.digitize"):
        assert taxonomy.is_registered("span", name)

    # tables no other test has binned: the content-keyed cache misses
    X, y, max_bins, categorical, _ = _case_over_the_threshold(seed=40)
    start = recorder.counters()
    tm._cached_bins(X, y, max_bins, categorical)
    assert _moved(recorder, start) == {"fits": 1}

    start = recorder.counters()                     # the same table: a hit
    tm._cached_bins(X, y, max_bins, categorical)
    assert _moved(recorder, start) == {}

    start = recorder.counters()
    tm._cached_bins(X[:1000], y[:1000], max_bins, categorical)
    assert _moved(recorder, start) == {"inline": 1}


def test_the_two_phases_are_the_children_of_the_bins_span(recorder):
    X, y, max_bins, categorical, _ = _case_over_the_threshold(seed=41)
    with obs.activate_trace(obs.open_trace()):     # as under a fit's root
        tm._cached_bins(X, y, max_bins, categorical)
    events = recorder.events()
    spans = {e.name: e for e in events if e.kind == "span"}
    assert {e.tid for e in events} == {spans["fit.quantize"].tid}, \
        "jobs open no spans and bump no counters"
    bins = spans["fit.quantize.bins"]
    assert bins.args["parent"] == spans["fit.quantize"].args["span"]
    assert bins.args["columns"] == 10
    assert bins.args["workers"] == cp._cores()
    assert bins.args["blocks"] == -(-len(X) // cp._BLOCK_ROWS) == 2
    assert bins.args["native"] is (native_binning._lib() is not None)
    stats, digitize = spans["fit.quantize.stats"], \
        spans["fit.quantize.digitize"]
    for child in (stats, digitize):
        assert child.args["parent"] == bins.args["span"]
    assert bins.ts <= stats.ts
    assert stats.ts + stats.dur <= digitize.ts + 1e-6
    assert digitize.ts + digitize.dur <= bins.ts + bins.dur + 1e-6
    # what the two phases leave of their parent is its own remainder:
    # opening the spans and the counter
    assert 0 <= bins.dur - stats.dur - digitize.dur < 0.05

    obs.reset()
    tm._cached_bins(X[:999], y[:999], max_bins, categorical)
    bins = {e.name: e for e in recorder.events()
            if e.kind == "span"}["fit.quantize.bins"]
    assert bins.args["workers"] == 1 and bins.args["blocks"] == 1
