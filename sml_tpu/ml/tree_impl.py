"""Histogram tree engine — one second-order PLANET learner for DT/RF/GBT.

The reference trains trees by the PLANET recipe: discretize features into
`maxBins` bins, have each worker build per-(node, feature, bin) statistics
over its rows, merge "via tree reduce", pick splits centrally
(`SML/ML 06 - Decision Trees.py:98-118`); distributed XGBoost does the same
with gradient/hessian stats merged by Rabit allreduce (`SML/ML 11 -
XGBoost.py:55-69`). This module is the TPU-native re-design of both:

- binning on host (quantile edges; categorical slots get one bin per
  category, ordered by label mean — the ordered-categorical trick PLANET and
  Spark use for regression/binary targets);
- ONE jitted shard_map program builds a whole tree: level-wise scatter-add
  histograms of (grad, hess, weight) per chip → `psum` over ICI (the Rabit
  allreduce), replicated split selection from cumulative bin sums, and
  on-device node reassignment — no host round-trip per level;
- everything is second-order (XGBoost objective): squared loss ⇒ grad=-y,
  hess=1 reduces leaves to masked means and gain to SSE reduction, so plain
  decision trees, random forests and boosted trees are the same compiled
  program with different (grad, hess) streams and random masks.

Static shapes throughout: node arrays are full binary trees of size
2^(maxDepth+1)-1, rows are padded+masked, so one XLA compile per
(depth, features, bins, shard) signature serves every tree of a forest and
every boosting round (SURVEY §7 hard part #6).
"""

from __future__ import annotations

import math
import re
from functools import partial
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel import collectives as coll
from ..parallel import mesh as meshlib
from ..utils.profiler import PROFILER
from ._staging import data_parallel, stage_sharded, transient_hbm


class TreeSpec(NamedTuple):
    """Static (hashable) build configuration — part of the jit cache key."""
    max_depth: int
    n_bins: int
    n_features: int
    feature_k: int          # features considered per node (RF subspace); =n_features for DT/GBT
    min_instances: int
    min_info_gain: float
    reg_lambda: float       # L2 on leaf values (XGBoost lambda; 0 for plain trees)
    gamma: float            # min split loss (XGBoost gamma)


class FittedTree(NamedTuple):
    split_feature: np.ndarray   # (N,) int32, -1 for leaves
    split_bin: np.ndarray       # (N,) int32: go left iff bin <= split_bin
    leaf_value: np.ndarray      # (N,) float32
    gain: np.ndarray            # (N,) float32 split gains (importance source)
    cover: np.ndarray           # (N,) float32 hessian mass per node


class TrialDyn(NamedTuple):
    """Per-TRIAL hyperparameters as TRACED scalars (grid-fused batching):
    the program is compiled once at the grid MAXIMA (static shapes come
    from TreeSpec), and each vmapped trial gates itself down to its own
    hyperparameters at run time — a grid over maxDepth x numTrees x ...
    is ONE executable, not one per grid point."""
    depth: object           # splits allowed only at level < depth
    feature_k: object       # RF subspace width (== n_features disables)
    min_instances: object   # min hessian-count per child
    min_info_gain: object   # min split gain


class Binning(NamedTuple):
    edges: np.ndarray           # (F, B-1) float32 upper-inclusive thresholds (+inf padded)
    cat_remap: Dict[int, np.ndarray]  # slot -> category->rank map (label-mean order)


def bin_dtype(max_bins: int) -> np.dtype:
    """Narrowest unsigned dtype holding bin ids in [0, max_bins): the
    quantized engine ships and keeps bin matrices COMPACT (uint8 at the
    default maxBins ≤ 256 — 4x less H2D traffic and HBM residency than the
    int32 matrices the seed staged), widening only when maxBins demands."""
    if max_bins <= (1 << 8):
        return np.dtype(np.uint8)
    if max_bins <= (1 << 16):
        return np.dtype(np.uint16)
    return np.dtype(np.int32)


def finalize_binning(F: int, max_bins: int,
                     categorical: Optional[Dict[int, int]],
                     cont_quantiles: Dict[int, Optional[np.ndarray]],
                     cat_means: Dict[int, np.ndarray],
                     max_categories_error: bool = True):
    """Assemble a `Binning` from per-feature quantile values + per-slot
    category label means — the ONE edge-assembly shared by the monolithic
    `make_bins` and the streamed-sketch path (`frame/_chunks.py`'s
    DatasetSketch), so the two ingest paths cannot drift: same
    unique/float32 edge collapse, same label-mean category ordering, same
    maxBins cardinality error, same compact-dtype sizing.

    `cont_quantiles[f]` is the raw `np.quantile` output for continuous
    slot f (None/empty = no finite values — the slot bins to 0);
    `cat_means[f]` is the per-category mean-label array (inf for absent
    categories). Returns (Binning, edge_list, out_dtype)."""
    categorical = categorical or {}
    for slot, card in categorical.items():
        if card > max_bins and max_categories_error:
            raise ValueError(
                f"DecisionTree requires maxBins (= {max_bins}) to be at least "
                f"as large as the number of values in each categorical feature, "
                f"but categorical feature {slot} has {card} values. "
                f"Consider removing this and other categorical features with "
                f"a large number of values, or add more training examples.")
    edges = np.full((F, max_bins - 1), np.inf, dtype=np.float32)
    remaps: Dict[int, np.ndarray] = {}
    edge_list: list = [np.zeros(0, dtype=np.float32)] * F
    for f in range(F):
        if f in categorical:
            card = int(categorical[f])
            means = cat_means[f]
            order = np.argsort(means, kind="stable")
            rank = np.empty(card, dtype=np.int32)
            rank[order] = np.arange(card, dtype=np.int32)
            remaps[f] = rank
            edges[f, :] = np.inf  # traversal uses bins directly
        else:
            qs = cont_quantiles.get(f)
            if qs is None or len(qs) == 0:
                continue
            qs = np.unique(np.asarray(qs).astype(np.float32))
            edges[f, :len(qs)] = qs
            edge_list[f] = qs
    # dtype must hold the categorical ranks too: with
    # max_categories_error=False a cardinality may legally exceed
    # max_bins, and a uint8 matrix would silently wrap those ranks
    need = max([max_bins] + [len(r) for r in remaps.values()])
    return Binning(edges=edges, cat_remap=remaps), edge_list, bin_dtype(need)


def _missing_value(missing) -> Optional[float]:
    """A fit's `missing` as the quantizer takes it: the number whose equals
    read NaN, or None where there is none to look for (None, NaN)."""
    if missing is None or np.isnan(missing):
        return None
    return float(missing)


def make_bins(X: np.ndarray, y: np.ndarray, max_bins: int,
              categorical: Optional[Dict[int, int]] = None,
              max_categories_error: bool = True,
              missing: Optional[float] = None) -> Tuple[np.ndarray, Binning]:
    """Host-side discretization. Continuous features: quantile edges.
    Categorical slots: identity bins ordered by mean label; cardinality must
    fit in max_bins, reproducing Spark's maxBins error (`ML 06:91-126`).
    A value equal to `missing` (xgboost's: a number, not None or NaN) is
    read as NaN by both phases, in every slot: the bins and the `Binning`
    are those of a copy of X with NaN written there, and X is not written.

    A plan of two phases on the column plan's pool (`_column_plan`: the one
    pool of the process, inline under its row threshold and on a worker
    thread, the same result either way): a job a COLUMN makes the column's
    bin statistic from one visit (`_column_stats`), `finalize_binning`
    assembles the edges, then a job a BLOCK OF ROWS writes the bins once
    (`_bin_columns`). Jobs open no spans and bump no counters; this
    thread does: span `fit.quantize.bins` with its two phases as children,
    and `quantize.plan.fits` / `.inline` for where the jobs ran."""
    from ..native.build import load_library
    from . import _column_plan as cp
    n, F = X.shape
    categorical = categorical or {}
    missing = _missing_value(missing)
    y = None if y is None else np.asarray(y)
    inline = cp.runs_inline(n)
    with PROFILER.span("fit.quantize.bins",
                       native=load_library("binning") is not None,
                       workers=1 if inline else cp._cores(), columns=F,
                       blocks=-(-n // cp._BLOCK_ROWS)):
        with PROFILER.span("fit.quantize.stats"):
            probs = np.linspace(0, 1, max_bins + 1)[1:-1]
            stats = cp.run_tasks(
                [partial(_column_stats, X, y, f, categorical.get(f), probs,
                         missing) for f in range(F)], inline)
            binning, edge_list, out_dtype = finalize_binning(
                F, max_bins, categorical,
                {f: q for f, q in enumerate(stats) if f not in categorical},
                {f: stats[f] for f in categorical},
                max_categories_error=max_categories_error)
        with PROFILER.span("fit.quantize.digitize"):
            binned = _bin_columns(X, edge_list, binning.cat_remap, out_dtype,
                                  missing)
    if inline:
        PROFILER.count("quantize.plan.inline")
    else:
        PROFILER.count("quantize.plan.fits")
    return binned, binning


def _column_stats(X: np.ndarray, y: Optional[np.ndarray], f: int,
                  card: Optional[int], probs: np.ndarray,
                  missing: Optional[float] = None):
    """The bin statistic of column f from ONE visit: the job copies its
    column out of the block (contiguous; every further pass walks 1/F of
    the block) and returns the raw `np.quantile` values of a continuous
    slot (None where nothing is finite), or a categorical slot's
    per-category mean labels (inf for absent categories): what
    `finalize_binning` takes. The values equal to `missing` are NaN in
    the job's copy, before anything reads it."""
    if missing is None:
        col = np.ascontiguousarray(X[:, f])
    else:
        # ONE strided pass, then the compare on the contiguous copy (a
        # `where` over the view walks the block twice); `np.array`: the
        # job's own even where the block is one column
        col = np.array(X[:, f])
        np.putmask(col, col == missing, np.nan)
    if card is None:
        finite = col[np.isfinite(col)]
        if len(finite) == 0:
            return None
        # edges from a deterministic subsample above 256k rows — the
        # same approximation Spark's approxQuantile binning and
        # sklearn's HistGradientBoosting use; full-data quantiles cost
        # ~1.2s/fit at 1M rows and change edges negligibly
        if len(finite) > 262_144:
            stride = -(-len(finite) // 262_144)
            finite = finite[::stride]
        return np.quantile(finite, probs)
    counts, grouped = _group_labels(col, int(card), y)
    seen = np.nonzero(counts)[0]
    means = np.full(len(counts), np.inf)
    if y is None:
        means[seen] = seen
        return means
    # each category's mean by the arithmetic a masked `y[ids == c].mean()`
    # has, on the same values in the same order: the means decide the
    # ORDER of the categories, so they keep their last bit
    hi = np.cumsum(counts)
    for c in seen:
        means[c] = float(grouped[hi[c] - counts[c]:hi[c]].mean())
    return means


def _group_labels(col: np.ndarray, card: int, y: Optional[np.ndarray]):
    """The rows of a categorical column grouped ONCE: (rows a category,
    y's values category by category, each category's in row order), by
    the C++ kernel when available, else a stable sort of the ids."""
    from ..native import binning as _native_binning
    got = _native_binning.group_labels(col, card, y)
    if got is not None:
        return got
    ids = np.clip(col.astype(np.int64), 0, card - 1)
    counts = np.bincount(ids, minlength=card)
    if y is None:
        return counts, None
    # narrowed ids sort by counting (NumPy's stable sort of small integers)
    return counts, y[np.argsort(ids.astype(bin_dtype(card)), kind="stable")]


def _bin_columns(X: np.ndarray, edge_list, remaps: Dict[int, np.ndarray],
                 out_dtype=np.int32,
                 missing: Optional[float] = None) -> np.ndarray:
    """Discretization against known edges/remaps, a job a block of rows:
    every block is binned for all F columns and written once, as
    contiguous rows of the result in `out_dtype`, by the C++ kernel
    (`native/binning.cc`) when available, NumPy otherwise — identical
    semantics (searchsorted 'left'; non-finite → bin 0; a categorical
    slot's rank looked up in the same pass; a value equal to `missing`,
    where a fit gives one, is NaN as it is read). The blocks run on the
    column plan's pool, or inline for few rows (a serving batch) and on
    a worker thread (`ml/_chunked.py` calls this a chunk a worker).
    `out_dtype` is the quantized engine's compact storage dtype (see
    `bin_dtype`); callers size it over max_bins AND every categorical
    cardinality, so all bin ids fit by construction."""
    from ..native import binning as _native_binning
    from . import _column_plan as cp
    n, F = X.shape
    binned = np.empty((n, F), dtype=out_dtype)
    native = _native_binning.row_binner(edge_list, remaps, missing)

    def block(r0: int) -> None:
        rows, out = X[r0:r0 + cp._BLOCK_ROWS], binned[r0:r0 + cp._BLOCK_ROWS]
        if native is None or not native(rows, out):
            _bin_rows_numpy(rows, edge_list, remaps, out, missing)

    cp.run_tasks([partial(block, r0) for r0 in range(0, n, cp._BLOCK_ROWS)],
                 cp.runs_inline(n))
    return binned


def _bin_rows_numpy(X: np.ndarray, edge_list, remaps: Dict[int, np.ndarray],
                    out: np.ndarray, missing: Optional[float] = None) -> None:
    """`native/binning.cc`'s `bin_rows` in NumPy: the rows of X into the
    rows of `out`, every column."""
    for f, qs in enumerate(edge_list):
        col = X[:, f]
        if missing is not None:
            col = np.where(col == missing, col.dtype.type(np.nan), col)
        rank = remaps.get(f)
        if rank is not None:
            out[:, f] = rank[np.clip(col.astype(np.int64), 0, len(rank) - 1)]
        elif len(qs) == 0:
            out[:, f] = 0
        else:
            out[:, f] = np.searchsorted(qs, col, side="left")
            out[~np.isfinite(col), f] = 0  # missing → lowest bin


import threading as _threading

_predict_bin_cache: dict = {}
_predict_bin_lock = _threading.Lock()  # CV trials bin concurrently
# bytes-bounded LRU (sml.predict.binCacheBytes): the CV/tuning suite
# legitimately holds ~20 distinct (matrix, model-edges) pairs at once
# (each fold's models re-bin the val matrix with their OWN quantile
# edges); an 8-entry cap thrashed every pass and re-paid ~0.3s of
# digitize per eval (r4 profile: 6.2s/pass)


def binning_edges_and_dtype(binning: Binning):
    """(edge_list, out_dtype) for quantizing FRESH rows under a saved
    `Binning` — the one shared derivation behind predict-time `bin_with`
    and the pinned-binning warm-start ingest (`ml/_chunked
    .ingest_source(binning=)`), so the two can never drift: same
    finite-edge extraction, same compact-dtype sizing over max_bins AND
    every categorical cardinality (which may exceed max_bins when the
    guard was suppressed at fit time)."""
    edge_list = [binning.edges[f][np.isfinite(binning.edges[f])]
                 for f in range(binning.edges.shape[0])]
    need = max([binning.edges.shape[1] + 1]
               + [len(r) for r in binning.cat_remap.values()])
    return edge_list, bin_dtype(need)


def bin_with(X: np.ndarray, binning: Binning) -> np.ndarray:
    """Apply training-time bin edges / category ranks at predict time.

    Content-memoized: tuning loops (ML 08's TPE objective, CV fold
    evaluates) re-predict on the SAME feature matrix with models whose bin
    edges are value-identical (same data, same maxBins), so the digitize
    pass would otherwise re-run per eval (~0.4s at 800k x 10)."""
    from ._staging import _memo_key, _normalize
    Xn = _normalize(X)
    edge_key = hash(tuple(e.tobytes() for e in binning.edges)) \
        ^ hash(tuple(sorted((k, v.tobytes())
                            for k, v in binning.cat_remap.items())))
    key = (_memo_key(Xn), edge_key)
    with _predict_bin_lock:
        hit = _predict_bin_cache.get(key)
        if hit is not None:
            # move-to-end LRU touch: dicts iterate in insertion order
            _predict_bin_cache.pop(key)
            _predict_bin_cache[key] = hit
    if hit is not None:
        return hit
    edge_list, out_dtype = binning_edges_and_dtype(binning)
    out = _bin_columns(Xn, edge_list, binning.cat_remap, out_dtype)
    from ..conf import GLOBAL_CONF
    max_bytes = GLOBAL_CONF.getInt("sml.predict.binCacheBytes")
    with _predict_bin_lock:
        total = out.nbytes + sum(v.nbytes for v in _predict_bin_cache.values())
        while total > max_bytes and _predict_bin_cache:
            oldest = next(iter(_predict_bin_cache))
            total -= _predict_bin_cache.pop(oldest).nbytes
        _predict_bin_cache[key] = out
    return out


# ---------------------------------------------------------------------------
#: id(mesh) -> (mesh, platform). The entry HOLDS the mesh so a recycled
#: id() after garbage collection can never serve a stale platform (the
#: hit path re-checks identity); meshes are few and small per process.
_platform_memo: Dict[int, tuple] = {}


def _mesh_platform(mesh=None) -> str:
    """The active mesh's device platform, memoized per mesh identity:
    `_hist_dtype` and the scoring kernel's resolver run inside every fit
    and scoring setup, and walking `mesh.devices.flat` allocates a fresh
    device list per call. Mesh identity keys the memo (a new/rebuilt
    mesh re-probes); conf is deliberately NOT part of the memo — knobs
    like `sml.infer.kernel` are read fresh by their own resolvers on top
    of the memoized platform, so a conf change takes effect immediately."""
    mesh = mesh or meshlib.get_mesh()
    key = id(mesh)
    hit = _platform_memo.get(key)
    if hit is not None and hit[0] is mesh:
        return hit[1]
    plat = str(list(mesh.devices.flat)[0].platform)
    _platform_memo[key] = (mesh, plat)
    return plat


def _hist_dtype():
    """bf16 histogram operands on TPU (exact one-hot, f32 accumulation on
    the MXU); f32 elsewhere — XLA:CPU has no bf16xbf16=f32 dot. The type
    the dot MULTIPLIES in; what the one-hot side is STORED as between
    dots is `_operand_dtype`'s."""
    return jnp.bfloat16 if _mesh_platform() == "tpu" else jnp.float32


def _operand_dtype(hist_dtype):
    """The type the loop-invariant one-hot `B1t` is STORED in, decided
    here and nowhere else: int8 where the histogram dot multiplies in
    bf16 (the TPU, `_hist_dtype`), else `hist_dtype` itself. 0 and 1 are
    exact in both, so the stored type changes no number; it halves what
    every histogram dot reads from HBM and what stays resident for a
    dispatch. XLA:TPU fuses the widening into the dot's operand read (the
    compiled program holds no bf16 copy, tests/test_tree_operand.py);
    XLA:CPU would write the widened copy at every level, so there the
    operand is the float32 one-hot itself."""
    return jnp.int8 if hist_dtype == jnp.bfloat16 else hist_dtype


def _hist_subtract() -> bool:
    from ..conf import GLOBAL_CONF
    return GLOBAL_CONF.getBool("sml.tree.histSubtraction")


def _hier_ici(mesh=None) -> int:
    """Static ICI-hop width of the two-level histogram allreduce: the
    mesh's "ici" axis size when the mesh declares the host topology
    (`mesh.host_mesh`) and `sml.tree.hierarchicalAllreduce` allows it,
    else 0 (= flat single-hop psum). Resolved at PROGRAM BUILD time and
    part of every tree program cache key — toggling the knob or changing
    the group shape must compile a fresh program, never replay one traced
    under the other reduction structure."""
    from ..conf import GLOBAL_CONF
    mesh = mesh or meshlib.get_mesh()
    if not meshlib.is_hierarchical(mesh):
        return 0
    mode = str(GLOBAL_CONF.get("sml.tree.hierarchicalAllreduce")
               or "auto").strip().lower()
    if mode in ("false", "0", "off", "no"):
        return 0
    return int(mesh.shape[meshlib.ICI_AXIS])


def _make_tree_builder(spec: TreeSpec, hist_dtype=jnp.float32,
                       subtract: bool = True, axes=None, hier_ici: int = 0):
    """Pure per-chip tree-build fn (called inside shard_map): one level-wise
    pass, histograms as one-hot dots, psum merges. Returns stacked node
    arrays as a single (5, n_nodes) f32 pack (one transfer, one scan slot).

    `subtract` enables the classic HISTOGRAM-SUBTRACTION trick (LightGBM's
    parent-minus-sibling): below the root, only LEFT children histogram
    from rows; right children are parent − left, computed post-psum — the
    one-hot hist matmul (the build's dominant FLOPs and HBM traffic)
    halves at every level, and the psum payload halves with it. With the
    built-in estimators' INTEGER sampling weights (Poisson/Bernoulli
    draws, f32-exact ≤ 2^24) the count channel is exact, so the
    min_instances gates cannot drift; grad/hess sums — and, for callers
    passing arbitrary FRACTIONAL weights through fit_tree, the count
    channel too — pick up cancellation noise that compounds with depth
    (each parent was itself subtraction-derived), so a weight sum sitting
    exactly on the min_instances boundary can gate differently than the
    direct build. Nodes whose parent did NOT split are gated to zero,
    exactly matching the direct computation (no rows ever reach them).

    `build(..., dyn=TrialDyn(...))` swaps depth / feature_k /
    min_instances / min_info_gain for TRACED per-trial scalars (the
    grid-fused batching path): the loop still unrolls to spec.max_depth,
    but splits are gated off at level >= dyn.depth, so a shallower trial
    produces the tree its own static program would have (deeper nodes
    keep zero cover and inherit the parent value)."""
    D, B, F = spec.max_depth, spec.n_bins, spec.n_features
    n_nodes = 2 ** (D + 1) - 1
    axes = tuple(axes) if axes else (meshlib.DATA_AXIS,)

    def _psum_merge(part):
        # the post-histogram merge: hierarchical two-level reduce when the
        # program was built for a host mesh with the knob on (hier_ici is
        # the static ici width), else the flat allreduce over the row
        # axes — same result, different hop structure and byte counters
        with jax.named_scope("tree.hist.allreduce"):
            if hier_ici > 1:
                return coll.psum_hierarchical(
                    part, ici_axis=meshlib.ICI_AXIS,
                    dcn_axis=meshlib.DCN_AXIS, ici_size=hier_ici)
            return coll.psum(part, axes if len(axes) > 1 else axes[0])

    def build(B1t, binned, grad, hess, weight, feat_rng, dyn=None):
        min_inst = spec.min_instances if dyn is None else dyn.min_instances
        min_gain = spec.min_info_gain if dyn is None else dyn.min_info_gain
        n = binned.shape[0]
        node = jnp.zeros((n,), dtype=jnp.int32)
        # EVERY row routes down the tree (active = still on a splitting
        # path), so the returned terminal nodes are valid for rows the
        # sampling weights excluded from the HISTOGRAMS (wq masks those) —
        # boosting margins update out-of-sample rows too
        active = jnp.ones((n,), dtype=bool)
        split_feature = jnp.full((n_nodes,), -1, dtype=jnp.int32)
        split_bin = jnp.zeros((n_nodes,), dtype=jnp.int32)
        gains = jnp.zeros((n_nodes,), dtype=jnp.float32)
        node_G = jnp.zeros((n_nodes,), dtype=jnp.float32)
        node_H = jnp.zeros((n_nodes,), dtype=jnp.float32)
        node_W = jnp.zeros((n_nodes,), dtype=jnp.float32)

        hist_prev = None   # (F, B, width/2, 3) — previous level, post-psum
        split_prev = None  # (width/2,) — previous level's do_split
        for level in range(D):
            width = 2 ** level
            base = width - 1
            with jax.named_scope("tree.hist"):
                lid = node - base
                in_level = active & (lid >= 0) & (lid < width)
                lid_c = jnp.where(in_level, lid, 0)
                wq = jnp.where(in_level, weight, 0.0)
                if subtract and level > 0:
                    # rows histogram only into their LEFT-child slot; right
                    # children come from parent − left below
                    half = width // 2
                    is_left = (lid_c % 2) == 0
                    wl = jnp.where(is_left, wq, 0.0)
                    hw, lid_h, w_eff = half, lid_c // 2, wl
                else:
                    hw, lid_h, w_eff = width, lid_c, wq
                node1hot = jax.nn.one_hot(lid_h, hw, dtype=hist_dtype) \
                    * (w_eff > 0)[:, None].astype(hist_dtype)
                stats = jnp.stack([grad * w_eff, hess * w_eff, w_eff],
                                  axis=1)
                ns = (node1hot[:, :, None]
                      * stats[:, None, :].astype(hist_dtype)
                      ).reshape(n, hw * 3)
                # bf16 operands (the one-hot side is EXACT in bf16), f32
                # accumulation: the MXU's native mode. B1t comes
                # pre-transposed from `_tree_operand`, built once a
                # dispatch and held outside the loop over rounds by its
                # optimization_barrier (without it XLA's fusible sinking
                # rebuilds it every round); each dot reads all of it
                # from HBM, in the one byte an element it is stored at
                # on the chip (`_operand_dtype`): the widening here is
                # the only read of B1t in `build`, and it fuses into the
                # dot (a no-op where stored and histogram type agree)
                part = jax.lax.dot_general(
                    B1t.astype(hist_dtype), ns, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                hist = _psum_merge(part)
                if subtract and level > 0:
                    half = width // 2
                    left = hist.reshape(F, B, half, 3)
                    # a parent that did not split has no children: gate its
                    # whole histogram to zero, as the direct path computes
                    parent = hist_prev * \
                        split_prev.astype(jnp.float32)[None, None, :, None]
                    right = parent - left
                    hist = jnp.stack([left, right], axis=3) \
                        .reshape(F, B, width, 3)
                else:
                    hist = hist.reshape(F, B, width, 3)
            with jax.named_scope("tree.split"):
                if dyn is not None or spec.feature_k < F:
                    # under dyn the draw ALWAYS happens (feature_k is traced);
                    # with feature_k == F the mask is all-True, so a
                    # no-subspace trial sees the identical candidate set its
                    # own static program (which skips the draw) produces
                    u = jax.random.uniform(
                        jax.random.fold_in(
                            jax.random.wrap_key_data(feat_rng), level),
                        (width, F))
                    ranks = jnp.argsort(jnp.argsort(u, axis=1), axis=1)
                    fk = spec.feature_k if dyn is None else dyn.feature_k
                    fmask = ranks < fk                             # (width, F)
                else:
                    fmask = None
                hG = jnp.transpose(hist[..., 0], (2, 0, 1))  # (width,F,B)
                hH = jnp.transpose(hist[..., 1], (2, 0, 1))
                hW = jnp.transpose(hist[..., 2], (2, 0, 1))
                GL = jnp.cumsum(hG, axis=2)
                HL = jnp.cumsum(hH, axis=2)
                WL = jnp.cumsum(hW, axis=2)
                G = GL[:, :, -1:]
                H = HL[:, :, -1:]
                W = WL[:, :, -1:]
                lam = spec.reg_lambda
                score = (GL ** 2 / (HL + lam + 1e-12)
                         + (G - GL) ** 2 / (H - HL + lam + 1e-12)
                         - G ** 2 / (H + lam + 1e-12))
                ok = ((WL >= min_inst)
                      & ((W - WL) >= min_inst))
                ok = ok & (jnp.arange(B)[None, None, :] < B - 1)
                if fmask is not None:
                    ok = ok & fmask[:, :, None]
                score = jnp.where(ok, score, -jnp.inf)
                flat_best = jnp.argmax(score.reshape(width, F * B), axis=1)
                best_f = (flat_best // B).astype(jnp.int32)
                best_b = (flat_best % B).astype(jnp.int32)
                best_gain = 0.5 * jnp.take_along_axis(
                    score.reshape(width, F * B), flat_best[:, None],
                    axis=1)[:, 0] - spec.gamma
                gG, gH, gW = G[:, 0, 0], H[:, 0, 0], W[:, 0, 0]
                do_split = (best_gain > min_gain) & jnp.isfinite(best_gain)
                if dyn is not None:  # trial's own maxDepth: none beyond it
                    do_split = do_split & (level < dyn.depth)
                idx = base + jnp.arange(width)
                node_G = node_G.at[idx].set(gG)
                node_H = node_H.at[idx].set(gH)
                node_W = node_W.at[idx].set(gW)
                split_feature = split_feature.at[idx].set(
                    jnp.where(do_split, best_f, -1))
                split_bin = split_bin.at[idx].set(best_b)
                gains = gains.at[idx].set(jnp.where(do_split, best_gain, 0.0))
            with jax.named_scope("tree.route"):
                # row-dependent gathers (table[my_idx], take_along_axis) lower
                # to XLA's generic scratch-memory gather on TPU — ~22ms per
                # call at 800k rows, THE dominant cost of the whole build. The
                # same lookups as masked sums are plain VPU work.
                lid_eq = lid_c[:, None] == jnp.arange(width,
                                                      dtype=jnp.int32)[None, :]
                my_f = jnp.sum(jnp.where(lid_eq, best_f[None, :], 0), axis=1)
                my_b = jnp.sum(jnp.where(lid_eq, best_b[None, :], 0), axis=1)
                my_split = jnp.any(lid_eq & do_split[None, :], axis=1)
                feat_eq = my_f[:, None] == \
                    jnp.arange(F, dtype=jnp.int32)[None, :]
                xbin = jnp.sum(jnp.where(feat_eq, binned, 0), axis=1)
                go_right = xbin > my_b
                child = 2 * node + 1 + go_right.astype(jnp.int32)
                node = jnp.where(in_level & my_split, child, node)
                active = in_level & my_split
            hist_prev = hist
            split_prev = do_split

        # leaf stats for the last level
        width = 2 ** D
        base = width - 1
        with jax.named_scope("tree.hist"):
            lid = node - base
            in_level = (lid >= 0) & (lid < width) & (weight > 0)
            lid_c = jnp.where(in_level, lid, 0)
            wq = jnp.where(in_level, weight, 0.0)
            node1hot = jax.nn.one_hot(lid_c, width, dtype=jnp.float32) \
                * (wq > 0)[:, None]
            lstats = _psum_merge(node1hot.T @ jnp.stack(
                [grad * wq, hess * wq, wq], axis=1))
        with jax.named_scope("tree.update"):
            idx = base + jnp.arange(width)
            node_G = node_G.at[idx].set(lstats[:, 0])
            node_H = node_H.at[idx].set(lstats[:, 1])
            node_W = node_W.at[idx].set(lstats[:, 2])
            leaf_value = -node_G / (node_H + spec.reg_lambda + 1e-12)
            # empty nodes (zero cover) inherit the parent value so unseen
            # routes at predict time fall back gracefully; D passes
            # propagate top-down
            parent = jnp.maximum((jnp.arange(n_nodes) - 1) // 2, 0)
            for _ in range(D):
                leaf_value = jnp.where(node_W > 0, leaf_value,
                                       leaf_value[parent])
                split_feature = jnp.where(node_W > 0, split_feature, -1)
            pack = jnp.stack([split_feature.astype(jnp.float32),
                              split_bin.astype(jnp.float32),
                              leaf_value, gains, node_H])
        # `node` is each row's terminal node — the build IS the traversal,
        # so boosting margin updates need one gather, not a depth-long
        # re-walk of the tree it just built
        return pack, node

    return build


def _traverse(binned, split_feature, split_bin, leaf_value, depth: int):
    """Vectorized on-device tree traversal (shared by fit-time margin
    updates and predict)."""
    node = jnp.zeros((binned.shape[0],), dtype=jnp.int32)
    for _ in range(depth):
        f = split_feature[node]
        b = split_bin[node]
        is_internal = f >= 0
        xbin = jnp.take_along_axis(binned, jnp.maximum(f, 0)[:, None],
                                   axis=1)[:, 0]
        child = 2 * node + 1 + (xbin > b).astype(jnp.int32)
        node = jnp.where(is_internal, child, node)
    return leaf_value[node]


class EnsembleSpec(NamedTuple):
    """Static configuration of a whole-ensemble on-device build."""
    tree: TreeSpec
    n_trees: int
    loss: str           # "squared" | "logistic"
    boosting: bool
    bootstrap: bool
    subsample: float
    step_size: float


_ensemble_cache: Dict[EnsembleSpec, object] = {}


def _base_margin_fn(loss: str, axes=None):
    """Per-chip base-margin statistic (mean / log-odds of the masked
    labels) with ONE fused allreduce for both sufficient statistics —
    shared by the monolithic ensemble program and the chunked boosting
    path's standalone base program, so both produce bit-identical bases.
    `axes` generalizes the reduction to a host mesh's row-axis tuple."""
    ax = tuple(axes) if axes else (meshlib.DATA_AXIS,)
    ax = ax if len(ax) > 1 else ax[0]

    def base_fn(y, mask):
        n_tot, y_tot = coll.psum_scalars(jnp.sum(mask), jnp.sum(y * mask),
                                         axis=ax)
        if loss == "logistic":
            p0 = jnp.clip(y_tot / n_tot, 1e-6, 1 - 1e-6)
            return jnp.log(p0 / (1 - p0))
        return y_tot / n_tot
    base_fn.__name__ = f"tree_base_{loss}"
    return base_fn


def _sliced_draw(n: int, data_width: int, draw, axes=None):
    """Mesh-layout-INVARIANT sampling weights: every chip draws the FULL
    padded row space (`n * data_width` values — counter-based threefry,
    a few cheap VPU passes next to the histogram matmuls) from the same
    replicated key and slices out its own row block, so the selected
    weights are bit-identical to the single-device draw no matter how
    rows shard. Before r6 each chip folded its shard index into the key,
    which made every bootstrap forest a function of the mesh LAYOUT —
    adding chips silently changed the fitted model, and an 8-chip fit
    could never golden-match a 1-chip fit."""
    if data_width <= 1:
        return draw((n,))
    full = draw((n * data_width,))
    ax = tuple(axes) if axes else (meshlib.DATA_AXIS,)
    idx = coll.axis_index(ax if len(ax) > 1 else ax[0])
    return jax.lax.dynamic_slice(full, (idx * n,), (n,))


#: rows of the operand `_tree_operand` writes at a time: a block's bins
#: broadcast over the bin ids (`F·B × block` bytes, 117 MB at 28 columns of
#: 256 bins) is all the build holds beside the operand itself
_OPERAND_BLOCK_ROWS = 1 << 14


def _operand_blocks(rows: int) -> int:
    """Blocks `_tree_operand` walks to build the operand of `rows` rows (a
    device's): whole ones in its loop, and the remainder where a small
    table's rows do not divide."""
    return -(-int(rows) // min(max(int(rows), 1), _OPERAND_BLOCK_ROWS))


def _tree_operand(binned_c, n_bins: int, hist_dtype, barrier: bool = True):
    """The histogram operand of every tree program, built ONCE a
    dispatch: `(binned, B1t)` = the compact bins widened to int32 (what
    `tree.route` reads) and their one-hot, `(F·B, n)`, laid out as the
    histogram dot reads it (a `.T` at the dot would re-materialize a
    multi-gigabyte transpose every level of every tree). It is STORED in
    `_operand_dtype(hist_dtype)`: int8 on the chip, where the dot widens
    it to bf16 as it reads (one byte an element from HBM and not two:
    1.09 GB a dot at 1.7 M rows × 640 columns, 6.11 GB at 852 k rows ×
    7,168); the float32 one-hot itself elsewhere.

    It is built BY ROW BLOCKS, in the layout it is kept in: a loop over
    blocks of `_OPERAND_BLOCK_ROWS` rows compares the block's bins, as
    narrow as they were staged and feature-major, `(F, 1, block)`,
    against the bin ids `(1, B, 1)`, and writes the `(F·B, block)` slab
    in place (`dynamic_update_slice` along the row axis). Nothing
    table-wide exists beside the operand: `jax.nn.one_hot` over the whole
    table widened the bins to int32 and XLA:TPU wrote that broadcast out,
    `s32[n, F, B]`, four times the operand (4.36 GB at 1.7 M rows × 640
    columns; 24.4 GB at xgboost's 256 bins on 28 columns, which a v5e
    cannot hold), before comparing and transposing it. The same compare
    written as ONE pass over the table still writes a table-wide
    broadcast (one byte an element: the compiler moves the reshape above
    the compare and materializes what feeds it), so the loop it is:
    docs/KERNELS.md, "The histogram operand".

    The `optimization_barrier` is what keeps "once" true. The operand is
    loop-invariant, and XLA:TPU's fusible-sinking pass moves cheap
    loop-invariant producers INTO a while loop that consumes them, trading
    recomputation for live memory (the body's name then ends `…sunk`):
    the one-hot of PRs 27-48, though written outside the `lax.scan` over
    rounds, was then rebuilt every round (PERF.md §6, PR 27). Behind the
    barrier it is an opaque buffer the loop carries as an operand; the
    barrier is the identity on its value. `barrier=False` is for a
    program with NO loop to sink into (`_build_tree_program`): there it
    could only change how a backend fuses the operand into its consumers."""
    with jax.named_scope("tree.operand"):
        # compact uint8/uint16 bins widen ON-DEVICE (a fused VPU cast over
        # the 4x-smaller staged matrix), never on the host/H2D path
        binned = binned_c.astype(jnp.int32)
        n, F = binned_c.shape
        dtype = _operand_dtype(hist_dtype)
        ids = jnp.arange(n_bins, dtype=binned_c.dtype)
        bt = binned_c.T
        block = min(n, _OPERAND_BLOCK_ROWS)

        def slab(cols):
            return (cols[:, None, :] == ids[None, :, None]).astype(dtype) \
                .reshape(F * n_bins, cols.shape[1])

        def write(i, out):
            cols = jax.lax.dynamic_slice_in_dim(bt, i * block, block, axis=1)
            return jax.lax.dynamic_update_slice_in_dim(
                out, slab(cols), i * block, axis=1)

        B1t = jax.lax.fori_loop(0, n // block, write,
                                jax.lax.empty((F * n_bins, n), dtype))
        if n % block:
            tail = n - n % block
            B1t = jax.lax.dynamic_update_slice_in_dim(
                B1t, slab(bt[:, tail:]), tail, axis=1)
        return binned, (jax.lax.optimization_barrier(B1t) if barrier
                        else B1t)


def ops_in_loop_bodies(hlo_text: str, scope: str = "tree.operand") -> list:
    """Names of the instructions of a COMPILED program (`.compile()
    .as_text()`) whose `op_name` holds `scope` and that run inside a while
    loop: in a loop's body or condition, or in any computation those call
    (fusions, nested loops, branches). Empty is the proof that the
    compiler left `_tree_operand` outside the loop over rounds; chip_smoke
    and tests/test_tree_operand.py hold it to that, so that a jax or
    libtpu that learns to sink past the barrier fails loudly."""
    comps, name = {}, None
    for line in hlo_text.splitlines():
        head = re.match(r"(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$", line)
        if head and not line.startswith(" "):
            name = head.group(1)
            comps[name] = []
        elif name is not None and " = " in line:
            comps[name].append(line)
    # what a computation calls: the computations its instructions name in
    # an attribute (calls=, to_apply=, body=, branch_computations={a, b})
    called = re.compile(r"[={,]\s*%?([\w.\-]+)")
    calls = {c: {m for ln in lines for m in called.findall(
                 ln.split(" = ", 1)[1].split(", metadata=")[0]) if m in comps}
             for c, lines in comps.items()}
    held = re.compile(r'op_name="[^"]*' + re.escape(scope))
    # (a loop that is itself the scope's, such as `_tree_operand`'s walk
    # over its row blocks, is not a loop the scope was sunk INTO: where it
    # stands in another loop's body, its own line says so below)
    todo = [m for lines in comps.values() for ln in lines
            if re.search(r"\swhile\(", ln) and not held.search(ln)
            for m in re.findall(r"(?:body|condition)=%?([\w.\-]+)", ln)]
    inside = set()
    while todo:
        c = todo.pop()
        if c in comps and c not in inside:
            inside.add(c)
            todo.extend(calls[c])
    # (a constant computes nothing: the compiler shares one scalar zero
    # among every user, under the name of whichever it met first)
    return [ln.split(" = ", 1)[0].strip().removeprefix("ROOT ")
            for c in sorted(inside) for ln in comps[c] if held.search(ln)
            and not re.search(r"\sconstant\(", ln.split(", metadata=")[0])]


def _ensemble_pieces(es: EnsembleSpec, data_width: int = 1,
                     axes=None, hier_ici: int = 0):
    """The shared internals of every ensemble program shape: `prepare`
    builds the histogram operand once a dispatch (`_tree_operand`: the
    bins widened on-device, their one-hot behind a barrier the compiler
    cannot sink into the loop); `make_round` returns the per-round scan
    body. Factored so the monolithic program and the chunked boosting
    program are the SAME math — a parity test holds them together.
    `data_width` is the mesh's STATIC data-axis size (part of every
    program cache's mesh-id key): sampling draws span `local_rows *
    data_width` so every layout selects the same global weights (see
    `_sliced_draw`)."""
    spec = es.tree
    hist_dtype = _hist_dtype()
    build = _make_tree_builder(spec, hist_dtype, subtract=_hist_subtract(),
                               axes=axes, hier_ici=hier_ici)

    def prepare(binned, rng):
        binned, B1t = _tree_operand(binned, spec.n_bins, hist_dtype)
        # ONE replicated sampling stream (fold_in(0) preserves the
        # historical single-device draws bit-for-bit); per-chip weights
        # come from slicing the global draw, not from per-chip keys
        key = jax.random.fold_in(jax.random.wrap_key_data(rng), 0)
        return binned, B1t, key

    def make_round(binned, B1t, y, mask, key, rng):
        n = binned.shape[0]

        def round_fn(margin, t):
            with jax.named_scope("tree.update"):
                if es.boosting:
                    if es.loss == "logistic":
                        p = jax.nn.sigmoid(margin)
                        grad = p - y
                        hess = jnp.maximum(p * (1 - p), 1e-6)
                    else:
                        grad = margin - y
                        hess = jnp.ones_like(y)
                else:
                    grad = -y
                    hess = jnp.ones_like(y)
                kt = jax.random.fold_in(key, t)
                if es.bootstrap and es.n_trees > 1:
                    w = _sliced_draw(
                        n, data_width, lambda s: jax.random.poisson(
                            kt, es.subsample, s).astype(jnp.float32), axes)
                elif es.subsample < 1.0:
                    w = _sliced_draw(
                        n, data_width, lambda s: jax.random.bernoulli(
                            kt, es.subsample, s).astype(jnp.float32), axes)
                else:
                    w = jnp.ones((n,), jnp.float32)
                w = w * mask
                feat_rng = jax.random.key_data(jax.random.fold_in(
                    jax.random.wrap_key_data(rng), t))  # same across chips
            pack, node_fin = build(B1t, binned, grad, hess, w, feat_rng)
            if es.boosting:
                # the build routed every row to its terminal node already:
                # the margin update is one gather, not a depth-long re-walk
                with jax.named_scope("tree.update"):
                    margin = margin + es.step_size * pack[2][node_fin]
            return margin, pack

        return round_fn

    return prepare, make_round


def _data_width(mesh=None) -> int:
    """The mesh's static row-shard count — the sampling-slice factor every
    program maker threads into `_ensemble_pieces` (programs cache per
    mesh id, so the width is as static as the mesh). On a hierarchical
    host mesh this is DCN×ICI — rows shard over both hops."""
    mesh = mesh or meshlib.get_mesh()
    if meshlib.is_hierarchical(mesh):
        return meshlib.data_width(mesh)
    return int(mesh.shape.get(meshlib.DATA_AXIS, 1))


def _make_ensemble_program(es: EnsembleSpec, data_width: int = 1,
                           axes=None, hier_ici: int = 0):
    """The WHOLE forest/boosting fit as one XLA program: `lax.scan` over
    trees, margins and sampling weights living in HBM for the entire fit.
    One dispatch + one packed device→host transfer per ensemble — the
    per-tree host round-trips disappear."""
    prepare, make_round = _ensemble_pieces(es, data_width, axes, hier_ici)
    base_of = _base_margin_fn(es.loss, axes)

    def program(binned, y, mask, rng):
        binned, B1t, key = prepare(binned, rng)
        base = base_of(y, mask)
        margin0 = jnp.full((binned.shape[0],), base, dtype=jnp.float32)
        round_fn = make_round(binned, B1t, y, mask, key, rng)
        _, packs = jax.lax.scan(round_fn, margin0, jnp.arange(es.n_trees))
        return packs, base

    return program


def _make_chunk_program(es: EnsembleSpec, chunk: int, data_width: int = 1,
                        axes=None, hier_ici: int = 0):
    """`chunk` boosting rounds as one dispatch: the margin carry enters and
    leaves as a row-sharded HBM buffer (donated between dispatches by the
    caller), `t0` offsets the round index so sampling streams and feature
    subspaces match the monolithic scan round-for-round."""
    prepare, make_round = _ensemble_pieces(es, data_width, axes, hier_ici)

    def program(binned, y, mask, margin, rng, t0):
        binned, B1t, key = prepare(binned, rng)
        round_fn = make_round(binned, B1t, y, mask, key, rng)
        margin, packs = jax.lax.scan(
            round_fn, margin, t0 + jnp.arange(chunk, dtype=jnp.int32))
        return margin, packs

    return program


_chunk_cache: Dict[tuple, object] = {}
_base_prog_cache: Dict[tuple, object] = {}


def _compiled_chunk(es: EnsembleSpec, chunk: int):
    from ..parallel import mesh as _meshlib
    from ..conf import GLOBAL_CONF
    mesh = _meshlib.get_mesh()
    # donate the margin carry so chunk k+1 reuses chunk k's HBM (the
    # chain's only fresh buffer — bins/labels/mask stay cache-owned
    # and are never donated); XLA:CPU ignores donation, so skip it
    # there to avoid the unused-donation warning. The donate decision is
    # part of the cache key: toggling sml.tpu.donate must not replay a
    # program compiled under the other setting.
    plat = _mesh_platform(mesh)
    donate = (3,) if plat != "cpu" \
        and GLOBAL_CONF.getBool("sml.tpu.donate") else ()
    key = (es, chunk, id(mesh), _hist_subtract(), _hier_ici(mesh), donate)
    if key not in _chunk_cache:
        from ..obs import note_compile
        note_compile(f"tree_chunk_{chunk}")
        program = _make_chunk_program(es, chunk, _data_width(mesh),
                                      _meshlib.row_axes(mesh),
                                      _hier_ici(mesh))
        P = jax.sharding.PartitionSpec
        Dx = _meshlib.row_spec_entry(mesh)
        wrapped = _meshlib.shard_map_compat(
            program, mesh=mesh,
            in_specs=(P(Dx, None), P(Dx), P(Dx), P(Dx), P(), P()),
            out_specs=(P(Dx), P()))
        _chunk_cache[key] = jax.jit(wrapped, donate_argnums=donate)
    return _chunk_cache[key]


def _boost_rounds(binned_dev, y_dev, mask_dev, es: EnsembleSpec, seed: int,
                  chunk: int, margin, t0: int = 0, on_rounds=None):
    """The staged boosting dispatch loop: rounds [t0, es.n_trees) in
    ceil((n_trees - t0)/chunk) dispatches over a margin carry (donated
    between chunks). Shared by the fresh chunked fit (t0=0, margin =
    full(base)) and the warm-start resume (t0 = saved rounds, margin
    replayed from the saved trees), so an appended round t runs the
    exact program a fresh fit's round t would — the round index keys
    the sampling/feature streams, not the dispatch position.

    `on_rounds(t_done, new_trees)` fires after each non-final dispatch
    with the rounds appended SO FAR (one extra packed D2H per dispatch
    when set; the callers wrap in the fit's base as a third arg) — the
    round-level checkpoint hook of the continuous-training plane
    (sml_tpu/ct): an interrupted or preempted boost resumes from the
    last dispatch boundary instead of restarting the fit."""
    from ..parallel import prewarm as _prewarm
    rng = jax.random.key_data(jax.random.PRNGKey(seed))
    packs_parts = []   # no-hook path: device packs, ONE batched D2H at end
    host_packs = []    # hook path: each pack fetched ONCE at its boundary
    t = int(t0)
    with transient_hbm("hist_onehot",
                       _onehot_bytes(es.tree, binned_dev.shape[0])):
        while t < es.n_trees:
            c = min(chunk, es.n_trees - t)
            _prewarm.record("tree_chunk", {
                "es": _es_meta(es), "chunk": int(c),
                "args": _prewarm.arg_specs(binned_dev, y_dev, mask_dev,
                                           margin)})
            PROFILER.count("tree.fit_dispatch")
            _count_operand(es.tree, binned_dev.shape[0])
            with PROFILER.span("fit.dispatch"):
                margin, packs = _compiled_chunk(es, c)(
                    binned_dev, y_dev, mask_dev, margin, rng, jnp.int32(t))
            t += c
            if on_rounds is None:
                packs_parts.append(packs)
            else:
                host_packs.append(np.asarray(jax.device_get(packs)))
                if t < es.n_trees:
                    on_rounds(t, _unpack_trees(
                        np.concatenate(host_packs, axis=0)))
        if not host_packs:
            with PROFILER.span("fit.device_wait"):
                jax.block_until_ready(packs_parts)
            with PROFILER.span("fit.readback"):
                host_packs = jax.device_get(packs_parts)
        packs = np.concatenate(host_packs, axis=0)
    return _unpack_trees(packs)


def _fit_ensemble_chunked(binned_dev, y_dev, mask_dev, es: EnsembleSpec,
                          seed: int, chunk: int, on_rounds=None):
    """Boosting rounds in ceil(n_trees/chunk) dispatches. The margin never
    visits the host between chunks — it carries as a donated device buffer
    — and per-chunk tree packs are fetched once at the end (one batched
    D2H). Bit-identical to the monolithic program on equal backends."""
    from ..parallel import mesh as _meshlib
    mesh = _meshlib.get_mesh()
    bkey = (es.loss, id(mesh))
    if bkey not in _base_prog_cache:
        _base_prog_cache[bkey] = data_parallel(
            _base_margin_fn(es.loss, _meshlib.row_axes(mesh)))
    base = float(jax.device_get(_base_prog_cache[bkey](y_dev, mask_dev)))
    margin = jax.device_put(
        np.full((binned_dev.shape[0],), base, np.float32),
        _meshlib.data_sharding(mesh, 1))
    # the chain's one fresh HBM buffer: donated between chunks, so live
    # bytes stay ONE margin's worth for the whole chunked fit
    from ..obs import LEDGER
    margin_bytes = margin.nbytes
    LEDGER.alloc("boost_margin", margin_bytes)
    try:
        hook = None if on_rounds is None \
            else (lambda t, tr: on_rounds(t, tr, base))
        trees = _boost_rounds(binned_dev, y_dev, mask_dev, es, seed, chunk,
                              margin, t0=0, on_rounds=hook)
    finally:
        LEDGER.free("boost_margin", margin_bytes)
    return trees, base


_margin_replay_cache: Dict[tuple, object] = {}


def _margin_replay_compiled(depth: int, n_trees: int):
    """Sharded device replay of a saved ensemble's boosting margin:
    margin_0 = full(base); margin_{t+1} = margin_t + step * leaf_t(row)
    — the SAME mul-then-add sequence (and scan shape) the fit program's
    carry runs, so a warm start resumes from a margin bit-identical to
    the one an uninterrupted fit would be carrying. Padding rows replay
    too (their binned rows are the same zeros the fit traversed), so
    the carry matches over the whole padded buffer."""
    mesh = meshlib.get_mesh()
    key = (int(depth), int(n_trees), id(mesh))
    if key not in _margin_replay_cache:
        from ..obs import note_compile
        note_compile("tree_margin_replay")

        def program(binned, sf, sb, lv, base, step):
            binned32 = binned.astype(jnp.int32)
            margin0 = jnp.full((binned.shape[0],), base, dtype=jnp.float32)

            def round_fn(margin, t):
                leaf = _traverse(binned32, sf[t], sb[t], lv[t], depth)
                return margin + step * leaf, ()

            margin, _ = jax.lax.scan(
                round_fn, margin0, jnp.arange(n_trees, dtype=jnp.int32))
            return margin

        _margin_replay_cache[key] = data_parallel(
            program, out_replicated=False,
            replicated_argnums=(1, 2, 3, 4, 5))
    return _margin_replay_cache[key]


def resume_ensemble_on_device(binned_dev, y_dev, mask_dev, es: EnsembleSpec,
                              seed: int, init_trees, base: float,
                              rounds_per_dispatch: Optional[int] = None,
                              on_rounds=None):
    """Warm-start incremental boosting: append rounds len(init_trees)..
    es.n_trees-1 to a saved ensemble. The saved rounds' margin replays
    on device (`_margin_replay_compiled`), then the appended rounds run
    through the SAME staged `roundsPerDispatch` dispatch as a fresh
    chunked fit, with round indices offset so sampling streams and
    feature subspaces match the monolithic scan round-for-round: k
    rounds + warm-start (N-k) rounds == N rounds bit-identically on the
    same data/seed (tests/test_ct.py). Returns (new_trees, base) — the
    appended rounds only; the caller prepends the saved trees."""
    from ..conf import GLOBAL_CONF
    from ..parallel import dispatch as _dispatch
    if not es.boosting:
        raise ValueError("warm-start resume requires a boosting ensemble "
                         "(forest/DT rounds are independent — refit whole)")
    t0 = len(init_trees)
    if es.n_trees <= t0:
        return [], float(base)
    rounds = (rounds_per_dispatch if rounds_per_dispatch is not None
              else GLOBAL_CONF.getInt("sml.tree.roundsPerDispatch"))
    chunk = rounds if 0 < rounds else (es.n_trees - t0)
    mesh = meshlib.get_mesh()
    sf = np.stack([t.split_feature for t in init_trees])
    sb = np.stack([t.split_bin for t in init_trees])
    lv = np.stack([t.leaf_value for t in init_trees])
    with PROFILER.span(
            "program.tree_resume", rows=int(binned_dev.shape[0]),
            route="host" if _dispatch.is_host_mesh(mesh) else "device",
            trees=es.n_trees - t0):
        margin = _margin_replay_compiled(es.tree.max_depth, t0)(
            binned_dev, sf, sb, lv, np.float32(base),
            np.float32(es.step_size))
        from ..obs import LEDGER
        margin_bytes = margin.nbytes
        LEDGER.alloc("boost_margin", margin_bytes)
        try:
            hook = None if on_rounds is None \
                else (lambda t, tr: on_rounds(t, tr, float(base)))
            trees = _boost_rounds(binned_dev, y_dev, mask_dev, es, seed,
                                  chunk, margin, t0=t0, on_rounds=hook)
        finally:
            LEDGER.free("boost_margin", margin_bytes)
    return trees, float(base)


def fit_ensemble_on_device(binned_dev, y_dev, mask_dev, es: EnsembleSpec,
                           seed: int = 0,
                           rounds_per_dispatch: Optional[int] = None,
                           on_rounds=None):
    """Run the whole-ensemble program; returns (trees, base).
    `rounds_per_dispatch` overrides sml.tree.roundsPerDispatch (the
    sparkdl.xgboost surface exposes it per-estimator). `on_rounds` is
    the round-level checkpoint hook (boosting only — it forces the
    chunked dispatch path so the hook has dispatch boundaries to fire
    at; see `_boost_rounds`)."""
    from ..parallel import dispatch as _dispatch
    from ..parallel import mesh as _meshlib
    with PROFILER.span(
            "program.tree_ensemble", rows=int(binned_dev.shape[0]),
            route="host" if _dispatch.is_host_mesh(_meshlib.get_mesh())
            else "device", trees=es.n_trees):
        return _fit_ensemble_on_device(binned_dev, y_dev, mask_dev, es, seed,
                                       rounds_per_dispatch, on_rounds)


def _ensemble_compiled(es: EnsembleSpec):
    """The monolithic whole-ensemble program from its per-mesh cache —
    shared by the fit path and the prewarm rebuilder (warming must
    populate the SAME cache entry the fit will hit)."""
    mesh = meshlib.get_mesh()
    key = (es, id(mesh), _hist_subtract(), _hier_ici(mesh))
    if key not in _ensemble_cache:
        from ..obs import note_compile
        note_compile("tree_ensemble")
        _ensemble_cache[key] = data_parallel(
            _make_ensemble_program(es, _data_width(mesh),
                                   meshlib.row_axes(mesh), _hier_ici(mesh)),
            replicated_argnums=(3,), name="tree_ensemble")
    return _ensemble_cache[key]


def _onehot_bytes(spec: TreeSpec, rows: int) -> int:
    """HBM bytes of the one-hot resident (`B1t`: rows × F × bins in the
    type it is STORED in, `_operand_dtype`: one byte an element on the
    chip) — the dominant transient the ledger charges for
    the duration of a tree-fit dispatch (every tree program shape,
    fit_tree included). Dispatch-long BY CONSTRUCTION: `_tree_operand`
    builds it once and its optimization_barrier makes it a buffer the
    loop over rounds carries. While it is built a block's broadcast is
    alive beside it and no more (`_OPERAND_BLOCK_ROWS` rows × F × bins
    bytes), so this is what a fit's programs ask of the chip beyond the
    histogram dots' own operands (PERF.md §3, `memory_peak_bytes`)."""
    return int(rows) * spec.n_features * spec.n_bins \
        * np.dtype(_operand_dtype(_hist_dtype())).itemsize


def _count_operand(spec: TreeSpec, rows: int, table_rows: int = None,
                   mesh=None) -> None:
    """A dispatch's operand in the recorder's counters, beside
    `tree.fit_dispatch`: `tree.operand.bytes` += the stored operand's bytes
    over all devices (`_onehot_bytes` of all the dispatch's `rows`),
    `tree.operand.blocks` += the row blocks ONE device's loop walks to
    build it (of `table_rows`, one table's, where the dispatch stacks
    folds or trials: a block is then a block of every stacked table)."""
    PROFILER.count("tree.operand.bytes", _onehot_bytes(spec, rows))
    PROFILER.count("tree.operand.blocks", _operand_blocks(
        int(rows if table_rows is None else table_rows) // _data_width(mesh)))


def _run_and_read(compiled, *args):
    """One dispatch of a compiled fit program and ONE batched D2H of all
    it returns, as the three host phases of the fit's span tree: the call
    returns (`fit.dispatch`), the device finishes (`fit.device_wait`),
    the result is copied to the host (`fit.readback`). `device_get`
    alone would wait just the same; split, the wait has its own name."""
    with PROFILER.span("fit.dispatch"):
        out = compiled(*args)
    with PROFILER.span("fit.device_wait"):
        out = jax.block_until_ready(out)
    with PROFILER.span("fit.readback") as note:
        host = jax.device_get(out)
        note["bytes"] = sum(int(a.nbytes)
                            for a in jax.tree_util.tree_leaves(host))
    return host


def _fit_ensemble_on_device(binned_dev, y_dev, mask_dev, es: EnsembleSpec,
                            seed: int = 0,
                            rounds_per_dispatch: Optional[int] = None,
                            on_rounds=None):
    from ..conf import GLOBAL_CONF
    rounds = (rounds_per_dispatch if rounds_per_dispatch is not None
              else GLOBAL_CONF.getInt("sml.tree.roundsPerDispatch"))
    if es.boosting and (0 < rounds < es.n_trees or on_rounds is not None):
        return _fit_ensemble_chunked(binned_dev, y_dev, mask_dev, es,
                                     seed, rounds if 0 < rounds
                                     else es.n_trees, on_rounds=on_rounds)
    compiled = _ensemble_compiled(es)
    rng = jax.random.key_data(jax.random.PRNGKey(seed))
    from ..parallel import prewarm as _prewarm
    _prewarm.record("tree_ensemble", {
        "es": _es_meta(es),
        "args": _prewarm.arg_specs(binned_dev, y_dev, mask_dev)})
    PROFILER.count("tree.fit_dispatch")
    _count_operand(es.tree, binned_dev.shape[0])
    with transient_hbm("hist_onehot",
                       _onehot_bytes(es.tree, binned_dev.shape[0])):
        packs, base = _run_and_read(compiled, binned_dev, y_dev, mask_dev,
                                    rng)
    # ^ one batched D2H transfer for (packs, base): every device→host
    # read has a fixed cost, so never fetch leaves separately
    return _unpack_trees(packs), float(base)


_folds_cache: Dict[tuple, object] = {}
_stack_memo: Dict[tuple, tuple] = {}
_stack_memo_lock = _threading.Lock()  # tuning trials stack concurrently


def build_fold_stacks(binned_list, y_list):
    """(bst, yst, mst) fold stacks padded to a common bucket, memoized by
    source-array identity — `_cached_bins` returns id-stable arrays for
    repeated content, so a grid over maxDepth×numTrees builds the stack
    once, not once per parameter map (the memo holds the sources, keeping
    their ids valid)."""
    from ..parallel import mesh as _meshlib
    mesh = _meshlib.get_mesh()
    n_dev = _data_width(mesh)
    n_pad = max(_meshlib.bucket_rows(b.shape[0], n_dev)
                for b in binned_list)
    key = (tuple(id(b) for b in binned_list),
           tuple(id(y) for y in y_list), n_pad)
    # build under the lock: concurrent tuning trials share the key, and a
    # double-checked miss would have each thread allocate its own multi-GB
    # stack (transient 2x memory spike); the loser waits and hits instead
    with _stack_memo_lock:
        hit = _stack_memo.get(key)
        if hit is not None:
            return hit[2]
        fo = len(binned_list)
        F = binned_list[0].shape[1]
        bst = np.zeros((fo, n_pad, F), dtype=binned_list[0].dtype)
        yst = np.zeros((fo, n_pad), dtype=np.float32)
        mst = np.zeros((fo, n_pad), dtype=np.float32)
        for k, (b, y) in enumerate(zip(binned_list, y_list)):
            bst[k, :b.shape[0]] = b
            yst[k, :len(y)] = y
            mst[k, :len(y)] = 1.0
        # bytes-bounded like the predict bin cache (a count-only bound
        # pinned multi-GB fold stacks for the process lifetime on large CV
        # datasets). The NEWEST stack is always cached — the active grid
        # reuses it per parameter map, so the build-once promise must hold
        # even when one stack alone exceeds the budget; the bound trims
        # OLDER entries, capping steady-state memory at ~one active stack.
        new_bytes = bst.nbytes + yst.nbytes + mst.nbytes
        from ..conf import GLOBAL_CONF as _conf
        max_bytes = _conf.getInt("sml.fit.foldStackBytes")
        total = new_bytes + sum(e[3] for e in _stack_memo.values())
        while _stack_memo and (len(_stack_memo) >= 2 or total > max_bytes):
            total -= _stack_memo.pop(next(iter(_stack_memo)))[3]
        _stack_memo[key] = (list(binned_list), list(y_list),
                            (bst, yst, mst), new_bytes)
    return bst, yst, mst


def _unpack_trees(packs) -> list:
    """(T, 5, n_nodes) device pack → FittedTree list — the ONE place that
    knows the pack layout (shared by the single-fit and fold-batched
    unpack paths)."""
    with PROFILER.span("fit.unpack", trees=len(packs)):
        return [FittedTree(split_feature=p[0].astype(np.int32),
                           split_bin=p[1].astype(np.int32),
                           leaf_value=p[2].astype(np.float32),
                           gain=p[3].astype(np.float32),
                           cover=p[4].astype(np.float32)) for p in packs]


def fit_ensembles_folds(bst, yst, mst, es: EnsembleSpec, seed: int = 0):
    """Fit the SAME EnsembleSpec on stacked fold datasets as ONE vmapped
    device program (SURVEY §2.2 P6; VERDICT r3 #4): CV's k fold-fits per
    parameter map share every shape, so they stack on a leading fold axis
    — one dispatch, one compile, and k× wider matmuls for the MXU —
    instead of k sequential program launches. Rows shard over the data
    axis exactly as in the single-fit program (the fold axis is
    replicated), and the per-fold rng equals the sequential path's (each
    sequential fold fit used the estimator's one seed), so sampling
    weights match the unbatched semantics. Returns [(trees, base)] per
    fold."""
    from ..parallel import dispatch as _dispatch
    from ..parallel import mesh as _meshlib
    from ._staging import stage_stacked_cached

    mesh = _meshlib.get_mesh()
    fo, n_pad = bst.shape[0], bst.shape[1]
    b_dev = stage_stacked_cached(bst)
    y_dev = stage_stacked_cached(yst)
    m_dev = stage_stacked_cached(mst)

    compiled = _folds_compiled(es, fo)
    from ..parallel import prewarm as _prewarm
    _prewarm.record("tree_folds", {
        "es": _es_meta(es), "fo": int(fo),
        "args": _prewarm.arg_specs(b_dev, y_dev, m_dev)})
    rng = jax.random.key_data(jax.random.PRNGKey(seed))
    with PROFILER.span(
            "program.tree_ensemble_folds", rows=int(fo * n_pad),
            route="host" if _dispatch.is_host_mesh(mesh) else "device",
            trees=es.n_trees * fo), \
            transient_hbm("hist_onehot",
                          _onehot_bytes(es.tree, fo * n_pad)):
        PROFILER.count("tree.fit_dispatch")
        _count_operand(es.tree, fo * n_pad, n_pad)
        packs, bases = _run_and_read(compiled, b_dev, y_dev, m_dev, rng)
    return [(_unpack_trees(packs[k]), float(bases[k])) for k in range(fo)]


def _folds_compiled(es: EnsembleSpec, fo: int):
    """The fold-batched program from its per-mesh cache (shared with the
    prewarm rebuilder)."""
    mesh = meshlib.get_mesh()
    key = (es, fo, id(mesh), _hist_subtract(), _hier_ici(mesh))
    if key not in _folds_cache:
        from ..obs import note_compile
        note_compile(f"tree_ensemble_folds_{fo}")
        program = _make_ensemble_program(es, _data_width(mesh),
                                         meshlib.row_axes(mesh),
                                         _hier_ici(mesh))

        def batched(binned_f, y_f, mask_f, rng):
            return jax.vmap(program, in_axes=(0, 0, 0, None))(
                binned_f, y_f, mask_f, rng)

        P = jax.sharding.PartitionSpec
        D = meshlib.row_spec_entry(mesh)
        wrapped = meshlib.shard_map_compat(
            batched, mesh=mesh,
            in_specs=(P(None, D, None), P(None, D), P(None, D), P()),
            out_specs=(P(), P()))
        _folds_cache[key] = jax.jit(wrapped)
    return _folds_cache[key]


# ------------------------------------------------- grid-fused trial batching
_trials_cache: Dict[tuple, object] = {}


def _make_trials_program(es: EnsembleSpec, data_width: int = 1,
                         axes=None, hier_ici: int = 0):
    """Per-ELEMENT ensemble program with TRACED hyperparameters, vmapped
    over the trial axis by `fit_ensembles_trials`: `es` carries the grid
    MAXIMA as static shapes (max_depth, n_bins, n_trees), and each
    element's `TrialDyn` + sampling flags gate the build down to its own
    hyperparameters. Sampling weights select among poisson / bernoulli /
    ones draws from the SAME keys the per-trial static programs use —
    and through the same layout-invariant global-draw-then-slice
    (`_sliced_draw`), so the selected values match the unfused path
    draw-for-draw on ANY mesh layout (including the cross-chip
    trial-sharded one, whose data axis is only n_dev/trial_dim wide)."""
    spec = es.tree
    hist_dtype = _hist_dtype()
    build = _make_tree_builder(spec, hist_dtype, subtract=_hist_subtract(),
                               axes=axes, hier_ici=hier_ici)
    base_of = _base_margin_fn(es.loss, axes)

    def program(binned, y, mask, rng, depth, feature_k, min_inst, mig,
                bootstrap, subsample):
        n = binned.shape[0]
        binned, B1t = _tree_operand(binned, spec.n_bins, hist_dtype)
        key = jax.random.fold_in(jax.random.wrap_key_data(rng), 0)
        base = base_of(y, mask)
        dyn = TrialDyn(depth=depth, feature_k=feature_k,
                       min_instances=min_inst, min_info_gain=mig)

        def round_fn(carry, t):
            grad = -y
            hess = jnp.ones_like(y)
            kt = jax.random.fold_in(key, t)
            pois = _sliced_draw(n, data_width, lambda s: jax.random.poisson(
                kt, subsample, s).astype(jnp.float32), axes)
            bern = _sliced_draw(n, data_width, lambda s: jax.random.bernoulli(
                kt, subsample, s).astype(jnp.float32), axes)
            ones = jnp.ones((n,), jnp.float32)
            w = jnp.where(bootstrap, pois,
                          jnp.where(subsample < 1.0, bern, ones)) * mask
            feat_rng = jax.random.key_data(jax.random.fold_in(
                jax.random.wrap_key_data(rng), t))
            pack, _ = build(B1t, binned, grad, hess, w, feat_rng, dyn=dyn)
            return carry, pack

        _, packs = jax.lax.scan(round_fn, 0.0, jnp.arange(es.n_trees))
        return packs, base

    return program


def _trials_compiled(es: EnsembleSpec, n_elems: int, mesh=None):
    """The trial-batched program from its per-mesh cache (shared with the
    prewarm rebuilder). Cache key carries only STATIC maxima — a grid
    whose per-trial values change but whose maxima land on the same
    (depth, bins, trees) signature replays one executable. `mesh` may be
    a 2-D trial mesh (`meshlib.trial_mesh`): the element axis then SHARDS
    over TRIAL_AXIS (cross-chip trial parallelism) instead of
    replicating, and each trial lane's histogram psums span only its own
    n_dev/trial_dim-wide data axis."""
    mesh = mesh or meshlib.get_mesh()
    key = (es, n_elems, id(mesh), _hist_subtract(), _hier_ici(mesh))
    if key not in _trials_cache:
        from ..obs import note_compile
        note_compile(f"tree_ensemble_trials_{n_elems}")
        program = _make_trials_program(es, _data_width(mesh),
                                       meshlib.row_axes(mesh),
                                       _hier_ici(mesh))

        def batched(binned_e, y_e, mask_e, rngs, *dyns):
            return jax.vmap(program,
                            in_axes=(0,) * (4 + len(dyns)))(
                binned_e, y_e, mask_e, rngs, *dyns)

        P = jax.sharding.PartitionSpec
        D = meshlib.DATA_AXIS
        T = meshlib.TRIAL_AXIS
        if T in mesh.shape:
            in_specs = (P(T, D, None), P(T, D), P(T, D), P(T, None)) \
                + (P(T),) * 6
            out_specs = (P(T), P(T))
        else:
            # replicated-element layout: rows shard over the mesh's row
            # axes (the host mesh's ("dcn", "ici") tuple included — the
            # fused-trial path on a host-partitioned mesh)
            Dr = meshlib.row_spec_entry(mesh)
            in_specs = (P(None, Dr, None), P(None, Dr), P(None, Dr)) \
                + (P(),) * 7
            out_specs = (P(), P())
        wrapped = meshlib.shard_map_compat(
            batched, mesh=mesh, in_specs=in_specs, out_specs=out_specs)
        _trials_cache[key] = jax.jit(wrapped)
    return _trials_cache[key]


#: auto trial-sharding threshold: one trial's padded rows below this fit
#: a single chip's compute comfortably (the dispatch cost model's
#: small-rows regime, where the per-level psum's fixed ICI latency
#: rivals the per-chip histogram matmul it synchronizes)
_TRIAL_SHARD_MAX_ROWS = 1 << 18


def _trial_axis_width(E: int, n_pad: int) -> int:
    """Devices the fused-trial ELEMENT axis spans; the rest keep sharding
    rows. `sml.cv.trialAxisDevices`: 0 = auto, 1 = rows-only, k > 1 =
    the largest mesh divisor <= k (honored even when E % k != 0 — the
    element axis pads by repeating element 0, `_pad_elems`). Auto
    mirrors the `dispatch.decide` trade (WorkHint pricing of compute vs
    the fixed per-collective latency term): small per-trial row counts
    gain nothing from splitting rows across every chip but pay
    D-levels × n_trees of allreduce latency per trial, so trials spread
    across chips instead — each lane's data axis shrinks (to 1 at full
    width: allreduce-free trials). Auto never pads: among the divisors
    of E it picks the largest (wall-clock per dispatch scales with
    ceil(E/t)*t, so padded elements are pure waste absent an explicit
    user choice)."""
    from ..conf import GLOBAL_CONF
    mesh = meshlib.get_mesh()
    if tuple(mesh.axis_names) != (meshlib.DATA_AXIS,):
        return 1  # placed submeshes / 2-D dryrun meshes keep row layout
    n_dev = int(mesh.shape[meshlib.DATA_AXIS])
    if n_dev <= 1 or E <= 1:
        return 1
    conf = GLOBAL_CONF.getInt("sml.cv.trialAxisDevices")
    if conf == 1:
        return 1
    if conf <= 0 and n_pad > _TRIAL_SHARD_MAX_ROWS:
        return 1  # big rows: per-chip row blocks already feed the MXU
    cap = n_dev if conf <= 0 else min(conf, n_dev)
    divisors = [d for d in range(2, cap + 1) if n_dev % d == 0]
    if conf > 1:
        return max(divisors, default=1)
    best, best_pad = 1, E
    for d in divisors:
        if d > E:
            continue
        pad = -(-E // d) * d
        if pad < best_pad or (pad == best_pad and d > best):
            best, best_pad = d, pad
    return best


def _pad_elems(a: np.ndarray, e_pad: int) -> np.ndarray:
    """Pad the element axis by REPEATING element 0 (real rows, real
    hyperparameters — never an all-masked element whose base margin would
    divide by a zero row count); the caller slices the duplicates away."""
    if a.shape[0] == e_pad:
        return a
    reps = np.repeat(a[:1], e_pad - a.shape[0], axis=0)
    return np.concatenate([a, reps], axis=0)


def fit_ensembles_trials(bst, yst, mst, es: EnsembleSpec, rngs,
                         depth, feature_k, min_inst, min_gain,
                         bootstrap, subsample):
    """Fit E = bst.shape[0] (grid point × fold) TRIALS as ONE vmapped
    device program — the grid-fused extension of `fit_ensembles_folds`:
    per-trial hyperparameters ride as traced (E,)-vectors (padded to the
    grid maxima carried statically by `es`), so a G-point grid over k
    folds is ceil(G*k / sml.cv.maxFusedTrials) dispatches instead of G*k
    (or G).

    Placement (`sml.cv.trialAxisDevices`, see `_trial_axis_width`): on a
    multi-device 1-D data mesh the element axis can SHARD over a second
    ("trial") mesh axis — E trials run on disjoint chip groups, each
    lane's rows sharded over its own (often width-1 = allreduce-free)
    data axis — instead of vmapping every trial onto one program spanning
    all chips. Sampling draws are layout-invariant (`_sliced_draw`), so
    both placements produce the same models up to float reduction order.
    Width 1 keeps the classic layout: rows over the data axis, element
    axis replicated, exactly like the fold axis in the fold-only program.

    Returns the raw (E, n_trees, 5, n_nodes) pack stack + (E,) bases —
    the caller slices each element down to its own numTrees."""
    from ..parallel import dispatch as _dispatch
    from ..parallel import prewarm as _prewarm
    from ._staging import stage_stacked_cached, stage_trial_stacked_cached

    mesh = meshlib.get_mesh()
    E, n_pad = bst.shape[0], bst.shape[1]
    tdim = _trial_axis_width(E, n_pad)
    dyns = [np.asarray(depth, np.int32), np.asarray(feature_k, np.int32),
            np.asarray(min_inst, np.float32),
            np.asarray(min_gain, np.float32),
            np.asarray(bootstrap, bool), np.asarray(subsample, np.float32)]
    rngs = np.asarray(rngs)
    if tdim > 1:
        e_pad = -(-E // tdim) * tdim
        tmesh = meshlib.trial_mesh(tdim, mesh)
        bst, yst, mst = (_pad_elems(a, e_pad) for a in (bst, yst, mst))
        rngs = _pad_elems(rngs, e_pad)
        dyns = [_pad_elems(v, e_pad) for v in dyns]
        b_dev = stage_trial_stacked_cached(bst, tmesh)
        y_dev = stage_trial_stacked_cached(yst, tmesh)
        m_dev = stage_trial_stacked_cached(mst, tmesh)
        compiled = _trials_compiled(es, e_pad, tmesh)
    else:
        e_pad = E
        b_dev = stage_stacked_cached(bst)
        y_dev = stage_stacked_cached(yst)
        m_dev = stage_stacked_cached(mst)
        compiled = _trials_compiled(es, E)
    _prewarm.record("tree_trials", {
        "es": _es_meta(es), "n_elems": int(e_pad), "trial_dim": int(tdim),
        "args": _prewarm.arg_specs(b_dev, y_dev, m_dev)})
    with PROFILER.span(
            "program.tree_ensemble_trials", rows=int(e_pad * n_pad),
            route="host" if _dispatch.is_host_mesh(mesh) else "device",
            trees=es.n_trees * e_pad), \
            transient_hbm("hist_onehot",
                          _onehot_bytes(es.tree, e_pad * n_pad)):
        PROFILER.count("tree.fit_dispatch")
        _count_operand(es.tree, e_pad * n_pad, n_pad,
                       tmesh if tdim > 1 else mesh)
        packs, bases = _run_and_read(compiled, b_dev, y_dev, m_dev, rngs,
                                     *dyns)
    return packs[:E], bases[:E]


# ------------------------------------------------------- prewarm rebuilders
def _es_meta(es: EnsembleSpec) -> dict:
    """JSON-serializable EnsembleSpec for the prewarm manifest."""
    return {"tree": list(es.tree), "n_trees": int(es.n_trees),
            "loss": str(es.loss), "boosting": bool(es.boosting),
            "bootstrap": bool(es.bootstrap),
            "subsample": float(es.subsample),
            "step_size": float(es.step_size)}


def _es_from_meta(meta: dict) -> EnsembleSpec:
    meta = meta.get("es", meta)
    t = meta["tree"]
    return EnsembleSpec(
        tree=TreeSpec(int(t[0]), int(t[1]), int(t[2]), int(t[3]),
                      int(t[4]), float(t[5]), float(t[6]), float(t[7])),
        n_trees=int(meta["n_trees"]), loss=str(meta["loss"]),
        boosting=bool(meta["boosting"]), bootstrap=bool(meta["bootstrap"]),
        subsample=float(meta["subsample"]),
        step_size=float(meta["step_size"]))


def _replay_zeros(meta, n: int):
    """Zero-filled device operands in the recorded shapes/dtypes, placed
    exactly like the fit paths place them (data-sharded rows; stacked
    layouts keep the leading axis replicated) so the replayed dispatch
    hits the very executable the recorded call compiled."""
    mesh = meshlib.get_mesh()
    stacked = ("n_elems" in meta) or ("fo" in meta)
    out = []
    for shape, dtype in meta["args"][:n]:
        a = np.zeros(tuple(shape), dtype=np.dtype(dtype))
        if stacked and a.ndim >= 2:  # (elems/folds, rows, ...) layout
            spec = jax.sharding.PartitionSpec(
                None, meshlib.row_spec_entry(mesh), *([None] * (a.ndim - 2)))
            out.append(jax.device_put(
                a, jax.sharding.NamedSharding(mesh, spec)))
        else:
            out.append(jax.device_put(a, meshlib.data_sharding(mesh, a.ndim)))
    return out


def _replay_tree_ensemble(meta: dict) -> None:
    es = _es_from_meta(meta)
    b, y, m = _replay_zeros(meta, 3)
    rng = jax.random.key_data(jax.random.PRNGKey(0))
    jax.device_get(_ensemble_compiled(es)(b, y, m, rng))


def _replay_tree_chunk(meta: dict) -> None:
    es = _es_from_meta(meta)
    b, y, m, margin = _replay_zeros(meta, 4)
    rng = jax.random.key_data(jax.random.PRNGKey(0))
    jax.device_get(_compiled_chunk(es, int(meta["chunk"]))(
        b, y, m, margin, rng, jnp.int32(0)))


def _replay_tree_folds(meta: dict) -> None:
    es = _es_from_meta(meta)
    b, y, m = _replay_zeros(meta, 3)
    rng = jax.random.key_data(jax.random.PRNGKey(0))
    jax.device_get(_folds_compiled(es, int(meta["fo"]))(b, y, m, rng))


def _replay_tree_trials(meta: dict) -> None:
    es = _es_from_meta(meta)
    E = int(meta["n_elems"])
    tdim = int(meta.get("trial_dim", 1))
    if tdim > 1:
        # trial-sharded variant: rebuild the 2-D mesh over the live data
        # mesh's devices and place operands exactly like the fit path
        tmesh = meshlib.trial_mesh(tdim)
        P = jax.sharding.PartitionSpec
        arrs = []
        for shape, dtype in meta["args"][:3]:
            a = np.zeros(tuple(shape), dtype=np.dtype(dtype))
            spec = P(meshlib.TRIAL_AXIS, meshlib.DATA_AXIS,
                     *([None] * (a.ndim - 2)))
            arrs.append(jax.device_put(
                a, jax.sharding.NamedSharding(tmesh, spec)))
        b, y, m = arrs
        compiled = _trials_compiled(es, E, tmesh)
    else:
        b, y, m = _replay_zeros(meta, 3)
        compiled = _trials_compiled(es, E)
    rngs = np.zeros((E, 2), np.uint32)
    jax.device_get(compiled(
        b, y, m, rngs,
        np.full(E, es.tree.max_depth, np.int32),
        np.full(E, es.tree.n_features, np.int32),
        np.ones(E, np.float32), np.zeros(E, np.float32),
        np.zeros(E, bool), np.ones(E, np.float32)))


def _register_prewarm_rebuilders() -> None:
    from ..parallel import prewarm as _prewarm
    _prewarm.register_rebuilder("tree_ensemble", _replay_tree_ensemble)
    _prewarm.register_rebuilder("tree_chunk", _replay_tree_chunk)
    _prewarm.register_rebuilder("tree_folds", _replay_tree_folds)
    _prewarm.register_rebuilder("tree_trials", _replay_tree_trials)


_register_prewarm_rebuilders()


def _build_tree_program(spec: TreeSpec, hist_dtype=jnp.float32,
                        axes=None, hier_ici: int = 0):
    """Single-tree program (kept for the dryrun/compile-check path)."""
    build = _make_tree_builder(spec, hist_dtype, subtract=_hist_subtract(),
                               axes=axes, hier_ici=hier_ici)

    def program(binned, grad, hess, weight, feat_rng):
        # no loop over rounds here, so nothing to hold the operand out of
        # (and XLA:CPU sums the dot in another order behind a barrier: one
        # ulp in a leaf)
        binned, B1t = _tree_operand(binned, spec.n_bins, hist_dtype,
                                    barrier=False)
        pack, _ = build(B1t, binned, grad, hess, weight, feat_rng)
        return (pack[0].astype(jnp.int32), pack[1].astype(jnp.int32),
                pack[2], pack[3], pack[4])

    return program


_tree_cache: Dict[TreeSpec, object] = {}


def fit_tree(binned_dev, grad_dev, hess_dev, weight_dev, spec: TreeSpec,
             rng: int = 0, feat_key: Optional[np.ndarray] = None) -> FittedTree:
    """Build one tree on the mesh from pre-staged device arrays."""
    from ..parallel import mesh as _meshlib
    mesh = _meshlib.get_mesh()
    key = (spec, id(mesh), _hist_subtract(), _hier_ici(mesh))
    if key not in _tree_cache:
        from ..obs import note_compile
        note_compile("tree_single")
        _tree_cache[key] = data_parallel(
            _build_tree_program(spec, _hist_dtype(),
                                _meshlib.row_axes(mesh), _hier_ici(mesh)),
            replicated_argnums=(4,))
    compiled = _tree_cache[key]
    if feat_key is None:
        feat_key = jax.random.key_data(jax.random.PRNGKey(rng))
    PROFILER.count("tree.fit_dispatch")
    _count_operand(spec, binned_dev.shape[0])
    with transient_hbm("hist_onehot",
                       _onehot_bytes(spec, binned_dev.shape[0])):
        out = compiled(binned_dev, grad_dev, hess_dev, weight_dev, feat_key)
        sf, sb, lv, g, cov = jax.device_get(out)  # one batched transfer
    sf, lv = sf.copy(), lv.copy()
    # nodes never reached in training (zero cover) inherit the parent value so
    # unseen routes at predict time fall back gracefully
    for i in range(1, len(lv)):
        if cov[i] == 0:
            lv[i] = lv[(i - 1) // 2]
            sf[i] = -1
    return FittedTree(sf, sb, lv, g, cov)


# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("depth",))
def _predict_binned(binned, split_feature, split_bin, leaf_value, depth: int):
    n = binned.shape[0]
    binned = binned.astype(jnp.int32)  # compact bins widen on-device
    node = jnp.zeros((n,), dtype=jnp.int32)
    for _ in range(depth):
        f = split_feature[node]
        b = split_bin[node]
        is_internal = f >= 0
        xbin = jnp.take_along_axis(binned, jnp.maximum(f, 0)[:, None], axis=1)[:, 0]
        child = 2 * node + 1 + (xbin > b).astype(jnp.int32)
        node = jnp.where(is_internal, child, node)
    return leaf_value[node]


def predict_tree(binned: np.ndarray, tree: FittedTree, depth: int) -> np.ndarray:
    out = _predict_binned(jnp.asarray(binned), jnp.asarray(tree.split_feature),
                          jnp.asarray(tree.split_bin),
                          jnp.asarray(tree.leaf_value), depth)
    return np.asarray(out)


def predict_forest(binned: np.ndarray, trees, depth: int,
                   weights: Optional[np.ndarray] = None) -> np.ndarray:
    """Sum/average of per-tree predictions, evaluated as stacked vmapped
    traversals (one fused XLA program rather than T python loops)."""
    sf = jnp.stack([jnp.asarray(t.split_feature) for t in trees])
    sb = jnp.stack([jnp.asarray(t.split_bin) for t in trees])
    lv = jnp.stack([jnp.asarray(t.leaf_value) for t in trees])
    b = jnp.asarray(binned)
    per_tree = jax.vmap(lambda f, s, v: _predict_binned(b, f, s, v, depth))(sf, sb, lv)
    if weights is None:
        return np.asarray(per_tree.mean(axis=0))
    w = jnp.asarray(weights, dtype=jnp.float32)
    return np.asarray(jnp.tensordot(w, per_tree, axes=1))


def feature_importances(trees, n_features: int) -> np.ndarray:
    """Gain-weighted importance, normalized to sum 1 (Spark semantics:
    per-tree normalization, then averaged over trees)."""
    total = np.zeros(n_features, dtype=np.float64)
    for t in trees:
        imp = np.zeros(n_features, dtype=np.float64)
        for node, f in enumerate(t.split_feature):
            if f >= 0:
                imp[int(f)] += max(float(t.gain[node]), 0.0)
        s = imp.sum()
        if s > 0:
            total += imp / s
    s = total.sum()
    return total / s if s > 0 else total


# ---------------------------------------------------------------------------
class StagedData(NamedTuple):
    binned: np.ndarray          # host copy (training-time re-prediction)
    binned_dev: jax.Array
    mask_dev: jax.Array
    y: np.ndarray
    n_true: int
    binning: Binning
    n_padded: int


def stage_tree_data(X: np.ndarray, y: np.ndarray, max_bins: int,
                    categorical: Optional[Dict[int, int]] = None,
                    prebinned=None) -> StagedData:
    """`prebinned=(binned, binning)` skips re-binning when the caller
    already discretized (it bins BEFORE routing so the dispatcher can probe
    the staging cache with the actual device operand). The compact
    quantized matrix stages through the shared bin cache (`stage_sharded`
    routes 2-D integer matrices there), so every tree, boosting round, CV
    fold, and eval pushdown on the same rows reuses ONE device copy."""
    if prebinned is not None:
        binned, binning = prebinned
    else:
        binned, binning = make_bins(X, y, max_bins, categorical)
    binned_dev, mask_dev, n_true = stage_sharded(binned)
    # the layout this fit runs on, as staged: devices that hold a shard of
    # the bin matrix, and the rows (padding included) on the fullest
    shards = binned_dev.addressable_shards
    PROFILER.count("fit.shards", len({s.device for s in shards}))
    PROFILER.count("fit.shard_rows_max",
                   max(s.data.shape[0] for s in shards))
    return StagedData(binned=binned, binned_dev=binned_dev, mask_dev=mask_dev,
                      y=y, n_true=n_true, binning=binning,
                      n_padded=binned_dev.shape[0])


def stage_aligned(arr: np.ndarray, n_padded: int):
    """Shard a per-row array aligned with previously staged binned data."""
    from ._staging import stage_aligned_cached
    return stage_aligned_cached(arr, n_padded)
