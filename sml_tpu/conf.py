"""Typed layered configuration tree (the `spark.conf` equivalent).

The reference uses Spark conf as an ad-hoc KV store: course keys
(`SML/Includes/Classroom-Setup.py:2`), engine knobs such as
`spark.sql.shuffle.partitions` (`Solutions/Labs/ML 00L`) and the Arrow batch
size `spark.sql.execution.arrow.maxRecordsPerBatch`
(`SML/ML 12 - Inference with Pandas UDFs.py:90,121`), plus Delta retention
checks (`SML/ML 00c - Delta Review.py:235`).

Here the same surface is one typed config tree: known keys carry a type and a
default; unknown keys are allowed as free-form strings (the course stores its
own `com.databricks.training.*` keys that way).
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional


@dataclass(frozen=True)
class ConfEntry:
    key: str
    default: Any
    caster: Callable[[str], Any]
    doc: str = ""


def _to_bool(v: Any) -> bool:
    if isinstance(v, bool):
        return v
    return str(v).strip().lower() in ("true", "1", "yes", "on")


_KNOWN: Dict[str, ConfEntry] = {}


def _register(key: str, default: Any, caster: Callable[[str], Any], doc: str = "") -> None:
    _KNOWN[key] = ConfEntry(key, default, caster, doc)


# Engine knobs the courseware actually touches, plus our TPU-side knobs.
_register("sml.shuffle.partitions", 8, int, "Partition count after shuffles (spark.sql.shuffle.partitions)")
_register("spark.sql.shuffle.partitions", 8, int, "Alias kept for course compatibility")
_register("sml.arrow.maxRecordsPerBatch", 10000, int, "Arrow record-batch size for pandas-fn fan-out")
_register("spark.sql.execution.arrow.maxRecordsPerBatch", 10000, int, "Alias kept for course compatibility")
_register("sml.delta.retentionDurationCheck.enabled", True, _to_bool, "Refuse vacuum(0) unless disabled")
_register("spark.databricks.delta.retentionDurationCheck.enabled", True, _to_bool, "Alias for course compatibility")
_register("sml.default.parallelism", 8, int, "Default partition count for new data sources")
# (sml.tpu.mesh.axis was registered here until PR 3: the mesh axis name is
# the parallel.mesh.DATA_AXIS constant and the knob was never read — the
# graftlint conf-key-registry rule now keeps such dead keys out.)
_register("sml.tpu.donate", True, _to_bool, "Donate input buffers on training steps")
_register("sml.profiler.enabled", False, _to_bool, "Record op-level timings")
_register("sml.applyInPandas.parallelism", 8, int,
          "Concurrent per-group fn threads in applyInPandas; 1 = sequential "
          "(needed only by fns that mutate shared closure state)")
_register("sml.predict.binCacheBytes", 1 << 30, int,
          "LRU byte bound for memoized predict-time binned matrices (CV/"
          "tuning suites hold ~20 (matrix, model-edges) pairs at once)")
_register("sml.tree.histSubtraction", True, _to_bool,
          "Histogram-subtraction tree builds (right child = parent - "
          "left sibling): halves the hist matmul below the root. Exact "
          "counts with the built-in integer sampling weights; fractional "
          "fit_tree weights and grad/hess sums pick up depth-compounding "
          "cancellation noise")
_register("sml.tree.hierarchicalAllreduce", "auto", str,
          "Two-level histogram allreduce on host-grouped meshes: "
          "'auto' = intra-group reduce-scatter over the 'ici' hop + "
          "inter-group allreduce over the 'dcn' hop + allgather back "
          "whenever the active mesh declares a host axis "
          "(mesh.host_mesh); 'true' = same, but error-prone on flat "
          "meshes so it still requires the host axes; 'false' = always "
          "the flat single-hop psum. Per-hop launches/bytes land in "
          "collective.psum[_bytes].ici/.dcn (docs/OBSERVABILITY.md)")
_register("sml.mesh.hostGroups", 0, int,
          "Default host-group count for mesh.host_mesh() when called "
          "without an explicit `hosts`: 0 = auto (jax.process_count() "
          "on a real multi-host slice, else 1); N>0 = N virtual host "
          "groups partitioning the flat device set (the single-machine "
          "testing story for the multi-host code path)")
_register("sml.split.sampler", "spark", str,
          "randomSplit sampler: 'spark' = draw-for-draw Spark parity "
          "(per-partition determinism sort + XORShiftRandom Bernoulli "
          "cells); 'legacy' = the pre-r5 numpy draws")
_register("sml.shuffle.reuseBytes", 1 << 30, int,
          "Byte bound for the shuffle-reuse cache (memoized applyInPandas "
          "group splits of cached frames); 0 disables reuse")
_register("sml.linear.compactBytes", 1 << 28, int,
          "Expanded-block size (n*d*4) above which linear/logistic fits "
          "stage the compact numeric+code form and expand one-hot slots "
          "on-chip instead of materializing the (n, d) matrix")
_register("sml.fit.foldStackBytes", 1 << 30, int,
          "Byte bound for the fit-time fold-stack memo (stacked CV fold "
          "datasets reused across a tuning grid); independent of the "
          "predict bin cache's budget")
_register("sml.tree.binCacheBytes", 2 << 30, int,
          "Device-bytes budget for the quantized bin-index cache (compact "
          "uint8/uint16 bin matrices staged once per dataset and reused by "
          "every tree, boosting round, and CV fold); separate from the "
          "general staging budget so fold stacks cannot evict hot bins")
_register("sml.tree.roundsPerDispatch", 0, int,
          "Boosting rounds fused per device dispatch. 0 = the whole "
          "ensemble in one scan program (default). k > 0 chunks the scan "
          "into ceil(n_trees/k) dispatches whose margin carry stays in HBM "
          "with the input buffer DONATED between chunks — bounds compile "
          "time for very deep ensembles without per-round host transfers")
_register("sml.compile.cacheDir", "", str,
          "Persistent XLA compilation-cache directory, used only where "
          "JAX_COMPILATION_CACHE_DIR is NOT set (a cache placed from "
          "outside wins and the code sets no other). Empty = the fixed "
          "<checkout>/.jax_cache; applied at import and re-applied "
          "whenever this key is set "
          "(parallel.dispatch.ensure_compile_cache)")
_register("sml.split.sortMemoBytes", 1 << 30, int,
          "Byte bound for randomSplit's pre-split sort memo (each entry is "
          "the permutation that sorts one partition, 8 bytes a row; the "
          "partition itself is not kept alive); entries for a frame are "
          "also dropped by DataFrame.unpersist. A budget below one split's "
          "working set makes every later weight cell re-sort (the in-flight "
          "split's own entries are evicted)")
_register("sml.obs.enabled", False, _to_bool,
          "Flight-recorder event bus (sml_tpu.obs): record typed engine "
          "events (spans, counters, dispatch decisions, cache traffic, "
          "collectives, compiles, HBM ledger gauges) into a bounded ring "
          "buffer for Chrome-trace export, the dispatch audit, and run "
          "autologging. Disabled, every instrumentation site costs one "
          "attribute load")
_register("sml.obs.ringEvents", 65536, int,
          "Capacity of the flight recorder's in-memory event ring; the "
          "oldest events are dropped (and counted) once full. Resizing "
          "preserves the newest events")
_register("sml.obs.sinkPath", "", str,
          "Optional JSONL sink: every recorded event is also appended to "
          "this file as one JSON object per line (empty = ring only). "
          "Applied immediately when set")
_register("sml.obs.sinkMaxBytes", 64 << 20, int,
          "Byte bound for the JSONL sink file: past it the live file "
          "rotates ONCE to <sinkPath>.1 (replacing the previous roll) and "
          "reopens fresh, so the sink holds at most ~2x this bound on "
          "disk. 0 = unlimited (the pre-PR-7 behavior)")
_register("sml.obs.metricsWindowSec", 300, int,
          "Rolling-window span of the streaming metrics registry "
          "(obs/_metrics.py): windowed quantiles and rates cover the "
          "trailing this-many seconds (8 ring slots); all-time "
          "histograms are kept regardless")
_register("sml.obs.autoLogRunMetrics", True, _to_bool,
          "With the recorder enabled, every outermost Estimator.fit under "
          "an active tracking run logs engine.* metrics (h2d/d2h bytes, "
          "cache hit rates, route mix, compile count, peak HBM ledger "
          "bytes) to the run — the MLflow system-metrics equivalent")
_register("sml.obs.driftBaselineRows", 32768, int,
          "Fit-time drift-baseline capture (obs/drift.py): with the "
          "recorder on (sml.obs.enabled — an obs-off fit pays one "
          "attribute load, not a sketch pass), tree fits sketch up to "
          "this many deterministically-strided training rows (features "
          "+ label + the model's own predictions) into the fitted "
          "model's DriftBaseline, persisted with the model and logged "
          "through tracking.log_model; persisted sketches compress to "
          "the sml.data.sketchBuckets centroid budget. 0 disables "
          "capture. Also bounds the retained values per stream of "
          "serving live-window sketches. The chunked-ingest path "
          "reuses its full-data pass-1 sketch instead (no extra cost)")
_register("sml.obs.driftBins", 10, int,
          "PSI cell count for drift distances: live-vs-baseline "
          "population stability is measured over this many "
          "equal-probability cells cut at the BASELINE's quantiles")
_register("sml.obs.driftMargin", 2.0, float,
          "Drift flag threshold as a multiple of the noise floor (the "
          "max self-distance of resampled-baseline iid windows): a "
          "feature flags when its distance exceeds margin x floor. "
          "Higher = less sensitive")
_register("sml.obs.driftMinRows", 256, int,
          "Minimum live rows in a drift window before it is judged — "
          "tiny windows carry too much sampling noise to name a "
          "drifting feature honestly")
_register("sml.obs.driftResamples", 3, int,
          "Bootstrap resamples of the baseline used to set each "
          "feature's noise floor (deterministic seeds; floors cached "
          "per rounded-down power-of-two live-row count)")
_register("sml.obs.driftWindowSec", 300, int,
          "Rolling-window span of serving drift monitors: live sketches "
          "rotate in two half-window slots, so a drift report covers "
          "between half and one full window of recent traffic")
_register("sml.training.module-name", "", str,
          "Course module name stamped by the Classroom-Setup shim "
          "(courseware.CourseConfig)")
_register("sml.training.username", "", str,
          "Course username stamped by the Classroom-Setup shim")
_register("sml.infer.kernel", "auto", str,
          "Ensemble-traversal implementation for device-routed scoring "
          "(DeviceScorer.score_block / forest predict+eval programs): "
          "'xla' = the one-hot where-sum HLO chain (the pre-kernel path, "
          "kept verbatim); 'pallas' = the fused "
          "sml_tpu/native/traverse_kernel.py batched-traversal kernel "
          "(level-order SoA node tables resident in VMEM, depth-unrolled "
          "predicated descent, leaf sums accumulated in-register; runs "
          "in interpret mode on non-TPU backends — the tier-1 bit-parity "
          "testing story) and RAISES where it cannot launch; 'auto' = "
          "pallas on a TPU mesh (compiled; a failing toolchain probe "
          "there counts infer.kernel.fallback), xla everywhere else. "
          "See docs/KERNELS.md")
_register("sml.infer.kernelBlockRows", 2048, int,
          "Row-block size of the pallas traversal kernel's grid on "
          "hardware (bounds the VMEM per-level one-hot tile to "
          "~blockRows*(n_nodes+F) elements; the actual block is the "
          "largest 32-row-aligned divisor of the per-chip padded rows at "
          "or under this). Interpret mode always runs ONE "
          "block (the traversal has no cross-row reduction, so blocking "
          "never changes results — bit-parity either way)")
_register("sml.infer.prefetchBatches", 4, int,
          "DeviceScorer.score_batches lookahead: batches dispatched ahead "
          "of the drain point so batch i+1's prep + H2D staging overlaps "
          "batch i's compute and D2H (was a hard-coded 4). 1 = fully "
          "synchronous")
_register("sml.cv.batchFolds", True, _to_bool,
          "Fuse tree-regressor CV/TVS trial fits into vmapped device "
          "programs. With sml.cv.maxFusedTrials > 1 the GRID axis fuses "
          "too (per-trial hyperparameters pad to the grid maxima as "
          "traced scalars), so a G-point grid over k folds costs "
          "ceil(G*k/maxFusedTrials) tree-fit dispatches (a grid is "
          "dominated by dispatch COUNT, not kernel time). Metrics match the placed-trials path within "
          "float tolerance (below-max-depth trials derive terminal-level "
          "stats from the level histograms rather than the dedicated "
          "leaf pass); false forces placed trials")
_register("sml.cv.maxFusedTrials", 16, int,
          "Max (grid point x fold) trial fits fused into one device "
          "dispatch by the grid-fused CV path (bounds the stacked "
          "operand memory to ~maxFusedTrials fold copies); <= 1 falls "
          "back to fold-only fusion (one dispatch per parameter map)")
_register("sml.cv.trialAxisDevices", 0, int,
          "Devices spanned by the fused-trial ELEMENT axis: grid-fused "
          "(grid point x fold) trials shard over a second ('trial') mesh "
          "axis while each trial lane keeps sharding rows over the "
          "remainder — E trials progress on disjoint chips with an "
          "n_dev/t-wide (often allreduce-free) data axis apiece, instead "
          "of vmapping every trial onto one program spanning all chips. "
          "0 = auto (shard trials whenever one trial's padded rows fit a "
          "single chip comfortably — the small-rows regime where the "
          "per-level psum latency dominates the per-chip matmul); 1 = "
          "rows-only sharding (the pre-r6 layout); k > 1 clamps to the "
          "largest mesh divisor <= k. Results match the rows-only layout "
          "within float reduction-order tolerance (sampling draws are "
          "mesh-layout-invariant)")
_register("sml.data.chunkRows", 65536, int,
          "Row-block size of the out-of-core data plane (frame/_chunks.py): "
          "ChunkSources yield columnar chunks of at most this many rows, "
          "and the chunked ingest path quantizes + stages one chunk at a "
          "time so host residency is bounded by a few chunk buffers plus "
          "the COMPACT bin matrix, never the raw float data. See "
          "docs/DATAPLANE.md")
_register("sml.data.sketchBuckets", 2048, int,
          "Centroid budget per feature for the streamed-quantization "
          "quantile sketch: below the exact cap the sketch holds raw "
          "values (bin edges bit-identical to the monolithic "
          "make_bins), above it each feature compresses to this many "
          "weight-uniform centroids (edges within one bin width for "
          "buckets >> maxBins). Sketches merge like obs._metrics "
          "snapshots: per-chunk summaries sum into one")
_register("sml.data.prefetchChunks", 2, int,
          "Chunked-ingest lookahead: chunks dispatched (H2D + device "
          "bin-accumulate) ahead of the drain point, so chunk i+1's host "
          "quantization overlaps chunk i's transfer and device work — "
          "the double-buffered H2D prefetch. Also bounds the chunk_stage "
          "HBM pool to ~this many chunk blocks. 1 = fully synchronous")
_register("sml.tune.candidatesPerDispatch", 4, int,
          "TPE candidates proposed AND scored per generation for "
          "batch-capable fmin objectives (fn.score_batch): a "
          "tree-estimator objective backed by "
          "ml.tuning.fused_param_scores pays one fused device dispatch "
          "per generation instead of one per trial; <= 1 keeps the "
          "sequential propose-score loop")


class TpuConf:
    """Thread-safe KV config with typed known keys and free-form extras."""

    def __init__(self) -> None:
        self._lock = threading.RLock()
        self._values: Dict[str, Any] = {}
        self._on_set: Dict[str, Callable[[], None]] = {}

    def on_set(self, key: str, fn: Callable[[], None]) -> None:
        """Register a callback fired after `key` changes (one per key —
        used by knobs whose effect must be re-applied to process state,
        e.g. sml.compile.cacheDir re-pointing the XLA compile cache)."""
        with self._lock:
            self._on_set[key] = fn

    def set(self, key: str, value: Any) -> None:
        with self._lock:
            ent = _KNOWN.get(key)
            if ent is not None and not isinstance(value, type(ent.default)):
                value = ent.caster(value)
            self._values[key] = value
            # Keep spark.* aliases and sml.* keys in sync both ways.
            alias = _ALIASES.get(key)
            if alias is not None:
                self._values[alias] = value
            hook = self._on_set.get(key)
        if hook is not None:  # outside the lock: hooks may read conf
            hook()

    def get(self, key: str, default: Optional[Any] = None) -> Any:
        with self._lock:
            if key in self._values:
                return self._values[key]
            ent = _KNOWN.get(key)
            if ent is not None:
                return ent.default
            if default is not None:
                return default
            if key.startswith(("sml.", "spark.")):
                import difflib
                near = difflib.get_close_matches(key, _KNOWN, n=3,
                                                 cutoff=0.6)
                hint = ("; did you mean: " + ", ".join(near)
                        if near else "")
                raise KeyError(
                    f"No such config key: {key!r} — not registered in "
                    f"sml_tpu/conf.py and never set(){hint}")
            raise KeyError(f"No such config key: {key}")

    def getInt(self, key: str) -> int:
        return int(self.get(key))

    def getBool(self, key: str) -> bool:
        return _to_bool(self.get(key))

    def unset(self, key: str) -> None:
        with self._lock:
            self._values.pop(key, None)

    def asDict(self) -> Dict[str, Any]:
        with self._lock:
            d = {k: e.default for k, e in _KNOWN.items()}
            d.update(self._values)
            return d


def registered_keys() -> tuple:
    """Every registered key, sorted — the programmatic registry dump the
    graftlint conf-key-registry rule cross-checks call sites against
    (conf.py stays importable by path with zero heavy deps for exactly
    this reason)."""
    return tuple(sorted(_KNOWN))


def describe() -> Dict[str, Dict[str, Any]]:
    """key -> {default, type, doc} for the full registry (late registrars
    like parallel.dispatch appear once they have imported)."""
    return {k: {"default": e.default, "type": type(e.default).__name__,
                "doc": e.doc}
            for k, e in sorted(_KNOWN.items())}


_ALIASES = {
    "spark.sql.shuffle.partitions": "sml.shuffle.partitions",
    "sml.shuffle.partitions": "spark.sql.shuffle.partitions",
    "spark.sql.execution.arrow.maxRecordsPerBatch": "sml.arrow.maxRecordsPerBatch",
    "sml.arrow.maxRecordsPerBatch": "spark.sql.execution.arrow.maxRecordsPerBatch",
    "spark.databricks.delta.retentionDurationCheck.enabled": "sml.delta.retentionDurationCheck.enabled",
    "sml.delta.retentionDurationCheck.enabled": "spark.databricks.delta.retentionDurationCheck.enabled",
}

# Process-wide conf (one driver process; no JVM — see SURVEY §2.3 Py4J row).
GLOBAL_CONF = TpuConf()
