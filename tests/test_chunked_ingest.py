"""Out-of-core data plane (ISSUE 10): chunked columnar ingestion,
streamed bin quantization, double-buffered H2D prefetch, chunk-local
splits.

The load-bearing contracts:
- chunked == monolithic BIT-parity for fit / predict / randomSplit
  membership across chunkRows ∈ {64, 1000, all} (the sketch is exact on
  small data, split draws are stateless per global row, and everything
  downstream of quantization is the same code path);
- sketch-mode (compressed) bin edges within one bin width of exact;
- prefetch overlap proven from ingest.dispatch/ingest.drain event order;
- device residency ledger-bounded by the COMPACT representation
  (chunk_stage + bin_cache peaks << raw float bytes);
- the bin cache is REUSED across ingests of the same content (LRU hit,
  zero fresh H2D) and the ingest memo skips repeat passes.
"""

import numpy as np
import pytest

from sml_tpu.conf import GLOBAL_CONF
from sml_tpu.frame._chunks import (ArrayChunkSource, DatasetSketch,
                                   FeatureSketch, FilteredChunkSource,
                                   GeneratorChunkSource, chunk_random_split,
                                   split_assignments)
from sml_tpu.frame.sampling import row_uniforms
from sml_tpu.ml._chunked import (cross_validate_chunked, fit_ensemble_chunked,
                                 ingest_source, predict_chunked)
from sml_tpu.ml._tree_models import _fit_ensemble
from sml_tpu.ml.tree_impl import make_bins


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(3)
    n, F = 3000, 6
    X = rng.normal(size=(n, F))
    y = X[:, 0] * 2 - X[:, 1] ** 2 + rng.normal(0, 0.2, n)
    return X, y


@pytest.fixture()
def recorder_on():
    import sml_tpu.obs as obs
    old = GLOBAL_CONF.get("sml.obs.enabled")
    GLOBAL_CONF.set("sml.obs.enabled", True)
    obs.reset()
    yield obs
    GLOBAL_CONF.set("sml.obs.enabled", old)


def _trees_equal(a, b):
    for ta, tb in zip(a.trees, b.trees):
        assert np.array_equal(ta.split_feature, tb.split_feature)
        assert np.array_equal(ta.split_bin, tb.split_bin)
        assert np.array_equal(ta.leaf_value, tb.leaf_value)


# --------------------------------------------------------------- bit parity
def test_ingest_edges_and_bins_bit_parity(data):
    """Exact-mode sketch edges + the streamed per-chunk quantization are
    bit-identical to the monolithic make_bins on small data."""
    X, y = data
    binned_m, binning_m = make_bins(X, np.asarray(y, np.float32), 32)
    ing = ingest_source(ArrayChunkSource(X, y, chunk_rows=64), 32)
    assert ing.stats["sketch_exact"]
    assert np.array_equal(ing.binning.edges, binning_m.edges)
    assert np.array_equal(ing.binned, binned_m)
    assert np.array_equal(ing.y, np.asarray(y, np.float32))


@pytest.mark.parametrize("chunk_rows", [64, 1000, None])
def test_fit_and_predict_bit_parity(data, chunk_rows):
    """The chunked fit produces the SAME forest (bit-for-bit trees) and
    the SAME predictions as the monolithic path, for any chunking —
    including `None` (one chunk, the degenerate monolithic layout)."""
    X, y = data
    spec_m = _fit_ensemble(X, y, categorical={}, max_depth=4, max_bins=32,
                           min_instances=1, min_info_gain=0.0, n_trees=5,
                           feature_k=None, bootstrap=True, subsample=1.0,
                           seed=7, loss="squared")
    src = ArrayChunkSource(X, y, chunk_rows=chunk_rows)
    spec_c = fit_ensemble_chunked(src, max_depth=4, max_bins=32, n_trees=5,
                                  bootstrap=True, seed=7)
    _trees_equal(spec_m, spec_c)
    pm = spec_m.predict_margin(X[:500])
    pc = predict_chunked(spec_c, ArrayChunkSource(X[:500],
                                                  chunk_rows=chunk_rows))
    assert np.array_equal(pm, pc)


@pytest.mark.parametrize("chunk_rows", [64, 1000, None])
def test_random_split_membership_bit_parity(data, chunk_rows):
    """Split membership is a pure function of (seed, global row index):
    identical row sets for ANY chunking, disjoint and exhaustive."""
    X, y = data
    cells = split_assignments(42, 0, len(X), [0.7, 0.3])
    src = ArrayChunkSource(X, y, chunk_rows=chunk_rows)
    tr, te = chunk_random_split(src, [0.7, 0.3], 42)
    Xtr = np.concatenate([c[0] for c in tr.chunks()])
    Xte = np.concatenate([c[0] for c in te.chunks()])
    assert np.array_equal(Xtr, X[cells == 0])
    assert np.array_equal(Xte, X[cells == 1])
    assert len(Xtr) + len(Xte) == len(X)


def test_nested_split_chunk_invariant(data):
    """A split OF a split stays chunk-layout-invariant: the filtered
    source numbers rows by filtered position, which is itself
    layout-invariant."""
    X, y = data
    outs = {}
    for cr in (64, 999, None):
        src = ArrayChunkSource(X, y, chunk_rows=cr)
        tr, _ = chunk_random_split(src, [0.8, 0.2], 1)
        sub, _ = chunk_random_split(tr, [0.5, 0.5], 2)
        outs[cr] = np.concatenate([c[0] for c in sub.chunks()])
    assert np.array_equal(outs[64], outs[999])
    assert np.array_equal(outs[64], outs[None])


def test_cv_fold_fits_bit_identical_metrics_close(data):
    """Fold fits are bit-identical across chunkings; the STREAMED rmse
    accumulates per chunk, so metrics agree to reduction-order
    tolerance."""
    X, y = data
    cv_a = cross_validate_chunked(ArrayChunkSource(X, y, chunk_rows=500),
                                  3, 11, max_depth=3, max_bins=16,
                                  n_trees=2, bootstrap=True, seed=5)
    cv_b = cross_validate_chunked(ArrayChunkSource(X, y), 3, 11,
                                  max_depth=3, max_bins=16, n_trees=2,
                                  bootstrap=True, seed=5)
    np.testing.assert_allclose(cv_a["fold_rmse"], cv_b["fold_rmse"],
                               rtol=1e-12)
    assert cv_a["k"] == 3 and len(cv_a["fold_rmse"]) == 3


def test_estimator_fit_chunked_matches_fit(spark, data):
    """Estimator-level surface: RandomForestRegressor.fit_chunked on a
    ChunkSource fits the SAME model as .fit on the materialized frame."""
    import pandas as pd

    from sml_tpu.ml.feature import VectorAssembler
    from sml_tpu.ml.regression import RandomForestRegressor
    X, y = data
    pdf = pd.DataFrame({f"f{i}": X[:, i] for i in range(X.shape[1])})
    pdf["label"] = y
    df = spark.createDataFrame(pdf)
    va = VectorAssembler(inputCols=[f"f{i}" for i in range(X.shape[1])],
                        outputCol="features")
    rf = RandomForestRegressor(featuresCol="features", labelCol="label",
                               maxDepth=3, maxBins=16, numTrees=3, seed=9)
    m_frame = rf.fit(va.transform(df))
    m_chunk = rf.fit_chunked(ArrayChunkSource(X, y, chunk_rows=700))
    _trees_equal(m_frame._spec, m_chunk._spec)
    assert type(m_frame) is type(m_chunk)


def test_parquet_chunk_source_roundtrip(tmp_path, data):
    """frame/io.py's ParquetChunkSource streams the same rows the
    materialized reader would, and fits bit-identically to them."""
    import pandas as pd
    import pyarrow as pa
    import pyarrow.parquet as pq

    from sml_tpu.frame.io import read_parquet_chunks
    X, y = data
    cols = {f"f{i}": X[:, i] for i in range(X.shape[1])}
    cols["label"] = y
    # two part files, like a partitioned write
    half = len(X) // 2
    d = tmp_path / "part"
    d.mkdir()
    for i, sl in enumerate((slice(None, half), slice(half, None))):
        pq.write_table(pa.table({k: v[sl] for k, v in cols.items()}),
                       str(d / f"part-{i:05d}.parquet"))
    src = read_parquet_chunks(str(d), [f"f{i}" for i in range(X.shape[1])],
                              "label", chunkRows=512)
    Xs = np.concatenate([c[0] for c in src.chunks()])
    assert np.array_equal(Xs, X)
    assert src.n_rows == len(X)
    assert src.fingerprint() is not None
    spec_p = fit_ensemble_chunked(src, max_depth=3, max_bins=16, n_trees=2,
                                  bootstrap=True, seed=4)
    spec_m = _fit_ensemble(X, y, categorical={}, max_depth=3, max_bins=16,
                           min_instances=1, min_info_gain=0.0, n_trees=2,
                           feature_k=None, bootstrap=True, subsample=1.0,
                           seed=4, loss="squared")
    _trees_equal(spec_m, spec_p)


# ------------------------------------------------------------------- sketch
def test_sketch_compressed_edges_within_one_bin_width():
    """Past the exact cap the sketch compresses to weight-uniform
    centroids; quantile error stays under one bin width for
    sketchBuckets >> maxBins."""
    rng = np.random.default_rng(5)
    vals = rng.normal(size=50_000)
    sk = FeatureSketch(buckets=2048, exact_cap=10_000)
    for i in range(0, vals.size, 1000):
        sk.update(vals[i:i + 1000])
    assert not sk.exact and sk.compressions > 0
    probs = np.linspace(0, 1, 33)[1:-1]
    approx = sk.quantiles(probs)
    exact = np.quantile(vals, probs)
    assert np.abs(approx - exact).max() < np.diff(exact).max()


def test_sketch_merge_matches_single_stream():
    """Per-chunk sketches merged == one sketch over the whole stream
    (the mergeable-summary contract, exact mode bit-for-bit)."""
    rng = np.random.default_rng(6)
    X = rng.normal(size=(4000, 3))
    whole = DatasetSketch(3)
    whole.update(X)
    merged = DatasetSketch(3)
    for i in range(0, 4000, 256):
        part = DatasetSketch(3)
        part.update(X[i:i + 256])
        merged.merge(part)
    probs = np.linspace(0, 1, 17)[1:-1]
    for f in range(3):
        assert np.array_equal(whole.features[f].quantiles(probs),
                              merged.features[f].quantiles(probs))


def test_row_uniforms_stateless_and_uniform():
    """Random access == streaming; distribution sane."""
    a = row_uniforms(9, 0, 10_000)
    b = np.concatenate([row_uniforms(9, s, 1000)
                        for s in range(0, 10_000, 1000)])
    assert np.array_equal(a, b)
    assert 0.0 <= a.min() and a.max() < 1.0
    assert abs(a.mean() - 0.5) < 0.02


# ------------------------------------------------- prefetch + observability
def test_prefetch_overlap_event_ordering(data, recorder_on):
    """Chunk i+1's ingest.dispatch lands BEFORE chunk i's ingest.drain:
    the next chunk's host quantization + H2D genuinely overlaps the
    current chunk's device work (the PR-4 inference proof, for ingest)."""
    obs = recorder_on
    X, y = data
    GLOBAL_CONF.set("sml.data.prefetchChunks", 3)
    try:
        ingest_source(ArrayChunkSource(X, y, chunk_rows=256), 16)
    finally:
        GLOBAL_CONF.unset("sml.data.prefetchChunks")
    evs = [(e.name, e.args.get("chunk")) for e in obs.RECORDER.events()
           if e.name in ("ingest.dispatch", "ingest.drain")]
    first_drain = evs.index(("ingest.drain", 0))
    ahead = {c for name, c in evs[:first_drain]
             if name == "ingest.dispatch"}
    assert {0, 1, 2} <= ahead  # depth=3: three dispatches before drain 0
    # per-chunk walls land SKEW-style attribution: the slowest chunk is
    # NAMED in engine_health()'s ingest block
    health = obs.engine_health()
    assert health["ingest"] is not None
    assert health["ingest"]["n_devices"] >= 2  # lanes = chunk indices
    assert "slowest_device" in health["ingest"]


def test_ledger_bounded_residency(recorder_on):
    """The acceptance contract: fit end-to-end from a ChunkSource with
    device residency bounded by the COMPACT representation — peak
    chunk_stage + bin_cache delta ≪ the raw float bytes the source
    produced."""
    obs = recorder_on
    rng = np.random.default_rng(8)
    n, F = 200_000, 10
    raw_bytes = n * F * 8  # float64 raw chunks

    def make(start, stop):
        r = np.random.default_rng(start + 1)
        Xc = r.normal(size=(stop - start, F))
        return Xc, Xc[:, 0] + r.normal(0, 0.1, stop - start)

    src = GeneratorChunkSource(n, F, make, chunk_rows=16_384,
                               fingerprint=("ledger-test", n))
    led_before = obs.LEDGER.snapshot()
    bin_live_before = led_before.get("bin_cache", {}).get("live", 0)
    spec = fit_ensemble_chunked(src, max_depth=3, max_bins=32, n_trees=2,
                                bootstrap=True, seed=3)
    led = obs.LEDGER.snapshot()
    chunk_peak = led.get("chunk_stage", {}).get("peak", 0)
    bin_delta = led.get("bin_cache", {}).get("peak", 0) - bin_live_before
    assert chunk_peak > 0                      # the pool was exercised
    assert led["chunk_stage"]["live"] == 0     # and fully released
    # uint8 compact (1/8 of raw) + a few replicated chunk blocks: far
    # below raw float residency
    assert chunk_peak + bin_delta < raw_bytes / 3
    assert len(spec.trees) == 2
    rec = obs.RECORDER.counters()
    assert rec.get("ingest.raw_bytes", 0) >= raw_bytes  # SAW it all


def test_bin_cache_reuse_across_ingests(data, recorder_on):
    """Second fit on the same source: the ingest memo skips both passes,
    and the assembled device matrix is served from the bin cache (LRU
    hit, zero fresh chunk H2D)."""
    obs = recorder_on
    X, y = data
    src = ArrayChunkSource(X, y, chunk_rows=512)
    fit_ensemble_chunked(src, max_depth=3, max_bins=16, n_trees=2,
                         bootstrap=True, seed=3)
    c0 = obs.RECORDER.counters()
    fit_ensemble_chunked(src, max_depth=3, max_bins=16, n_trees=2,
                         bootstrap=True, seed=3)
    c1 = obs.RECORDER.counters()
    assert c1.get("ingest.memo_hit", 0) == c0.get("ingest.memo_hit", 0) + 1
    # no new chunk transfers; the fit's stage_sharded hit the bin cache
    assert c1.get("ingest.h2d_bytes", 0) == c0.get("ingest.h2d_bytes", 0)
    assert c1.get("staging.bin_cache_hit", 0) \
        > c0.get("staging.bin_cache_hit", 0)


def test_unlabeled_source_rejected_for_fit(data):
    X, _ = data
    with pytest.raises(ValueError, match="labeled"):
        fit_ensemble_chunked(ArrayChunkSource(X, chunk_rows=500),
                             max_depth=2, max_bins=8)


def test_pipeline_abandonment_releases_tickets_and_drains(recorder_on):
    """A caller abandoning the pipeline mid-stream (break / gen.close)
    must not leak watchdog tickets or in-flight resources: every
    dispatched item still gets its drain, and no ticket is left to rot
    into a false stall."""
    from sml_tpu.obs import WATCHDOG
    from sml_tpu.parallel.pipeline import prefetch_pipeline

    dispatched, drained = [], []
    gen = prefetch_pipeline(
        range(6), lambda x: x,
        lambda i, p: dispatched.append(i) or p,
        lambda i, h: drained.append(i) or h,
        depth=3, family="ingest", index_key="chunk")
    next(gen)    # one result out; more items in flight at depth=3
    gen.close()  # abandon
    assert WATCHDOG.report()["open"] == 0
    assert set(drained) == set(dispatched)  # cleanup drained the rest


# -------------------------------------------------------- regression sentry
# ------------------------------------------------------------- 1M-row smoke
def test_scale_smoke_1m_rows():
    """Tier-1-safe 1M-row synthetic smoke: chunked ingest + fit +
    streamed predict end-to-end from a generator source (raw data never
    materialized whole), compact device residency, finite outputs."""
    n, F = 1_000_000, 8

    def make(start, stop):
        r = np.random.default_rng(start * 7 + 5)
        Xc = r.normal(size=(stop - start, F)).astype(np.float32)
        yc = (Xc[:, 0] - 0.5 * Xc[:, 1] + r.normal(0, 0.3, stop - start)
              ).astype(np.float32)
        return Xc, yc

    src = GeneratorChunkSource(n, F, make, chunk_rows=131_072,
                               fingerprint=("smoke-1m", n))
    spec = fit_ensemble_chunked(src, max_depth=3, max_bins=32, n_trees=1,
                                seed=2)
    assert len(spec.trees) == 1
    # streamed predict on a 100k prefix regenerated from the same seeds
    psrc = GeneratorChunkSource(131_072, F, make, chunk_rows=131_072,
                                fingerprint=("smoke-1m-p", n))
    preds = predict_chunked(spec, psrc)
    assert preds.shape == (131_072,)
    assert np.isfinite(preds).all()
