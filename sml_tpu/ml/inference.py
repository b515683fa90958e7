"""Mesh-sharded batch inference — the TPU pandas-UDF path (SURVEY §2.2 P8).

The reference's pandas-UDF lesson is about inference THROUGHPUT
(`SML/ML 12 - Inference with Pandas UDFs.py:56-61`): Arrow batches stream
into a Python worker that predicts with a once-loaded model. Here the same
shape runs on the chip mesh: feature blocks stage into HBM sharded by rows
over the data axis, and a cached jitted program (linear forward or stacked
vmapped tree traversal) computes predictions on-device. `DeviceScorer` is
the load-once object the scalar-iterator UDF pattern amortizes
(`ML 12:101-112`); async dispatch pipelines batch i+1's staging under
batch i's compute.
"""

from __future__ import annotations

from typing import Iterable, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel import mesh as meshlib
from ..utils.profiler import PROFILER
from ._staging import cached_data_parallel, extract_features
from ..parallel import collectives as coll


# ------------------------------------------------------------- device programs
def _linear_forward(Xb, mask, w, b):
    return (Xb @ w + b) * mask


def _logistic_forward(Xb, mask, w, b):
    return jax.nn.sigmoid(Xb @ w + b) * mask


def _forest_margin(binned_b, sf, sb, lv, weights, depth: int):
    """Weighted stacked-ensemble margin for one row block — the SINGLE
    traversal kernel shared by the predict program and the fused
    predict+eval program (a semantics fix must land in exactly one place).

    GATHER-FREE: `table[node]` / take_along_axis lower to XLA's generic
    scratch-memory gather on TPU — a 25-tree/d6 eval at 800k rows ran ~4s
    (r4 profile). Every per-node and per-feature lookup here is a one-hot
    masked where-SUM (the same pattern as `xbin`), which rides the VPU and
    is EXACT in f32: each row's sum has exactly one nonzero term, so no
    accumulation rounding can occur, and — unlike a one-hot matmul — no
    MXU bf16 operand truncation either (TPU f32 dots round operands to
    bfloat16; leaf values, tree weights, and feature indices ≥257 are not
    bf16-exact, which both broke the fused-eval/materialize bit-parity
    contract and could mis-hit the exact `fiota == fa` select). The
    per-level `xbin` select scans all F features, so total work is
    O(rows * (n_nodes + F * depth)); at course-scale F (tens) the n_nodes
    term dominates, while very wide one-hot feature spaces pay the
    F*depth term — still far below the gather path's scratch traffic."""
    n_rows = binned_b.shape[0]
    n_feat = binned_b.shape[1]
    n_nodes = sf.shape[1]
    binned_f = binned_b.astype(jnp.float32)
    fiota = jnp.arange(n_feat, dtype=jnp.float32)

    def one_tree(f, s, v):
        fpos = jnp.maximum(f, 0).astype(jnp.float32)
        internal = f >= 0
        s_f = s.astype(jnp.float32)
        node = jnp.zeros((n_rows,), dtype=jnp.int32)
        for lvl in range(depth):
            width = min(2 ** (lvl + 1) - 1, n_nodes)
            iota = jnp.arange(width, dtype=jnp.int32)
            oh = node[:, None] == iota[None, :]
            fa = jnp.sum(jnp.where(oh, fpos[None, :width], 0.0), axis=1)
            ba = jnp.sum(jnp.where(oh, s_f[None, :width], 0.0), axis=1)
            isin = jnp.any(oh & internal[None, :width], axis=1)
            xbin = jnp.sum(jnp.where(fiota[None, :] == fa[:, None],
                                     binned_f, 0.0), axis=1)
            child = 2 * node + 1 + (xbin > ba).astype(jnp.int32)
            node = jnp.where(isin, child, node)
        leaf_oh = (node[:, None]
                   == jnp.arange(n_nodes, dtype=jnp.int32)[None, :])
        return jnp.sum(jnp.where(leaf_oh, v.astype(jnp.float32)[None, :],
                                 0.0), axis=1)

    per_tree = jax.vmap(one_tree)(sf, sb, lv)          # (T, rows/chip)
    # weighted tree sum as an elementwise reduce: operands stay exact f32
    # (no MXU bf16 rounding); the T-term accumulation order is
    # XLA-determined, so the final sum is f32-accurate but not
    # bit-ordered like the host path's sequential loop
    return jnp.sum(weights.astype(jnp.float32)[:, None] * per_tree, axis=0)


# -------------------------------------------------- traversal-kernel choice
#: last resolved traversal spec + fallback/demotion counts — the
#: `infer_kernel` block of obs.engine_health() (kernel_report below)
_KERNEL_STATE: dict = {"kernel": None, "block_rows": 0,
                       "resolutions": 0, "fallbacks": 0, "demotions": 0}


def _kernel_fallback() -> None:
    PROFILER.count("infer.kernel.fallback")
    _KERNEL_STATE["fallbacks"] += 1


def _note_spec(kernel: str, block_rows: int) -> None:
    changed = (_KERNEL_STATE["kernel"] != kernel
               or _KERNEL_STATE["block_rows"] != block_rows)
    _KERNEL_STATE.update(kernel=kernel, block_rows=int(block_rows))
    _KERNEL_STATE["resolutions"] += 1
    PROFILER.count(f"infer.kernel.{kernel}")
    if changed:
        from ..obs._recorder import RECORDER
        if RECORDER.enabled:
            RECORDER.emit("infer", "infer.kernel.spec", args={
                "kernel": kernel, "block_rows": int(block_rows)})


def _vmem_guard(block_rows: int, n_trees: int, n_nodes: int,
                n_feat: int):
    """Real-TPU VMEM guard for a pallas candidate → (block_rows,
    demoted). The block target shrinks to the largest block that fits
    `TRAVERSE_VMEM_BUDGET` (single source of the arithmetic:
    `traverse_kernel.max_block_rows`); a spec whose resident node
    tables alone bust the budget — oversized (block_rows × trees) at
    ANY useful block — demotes (0, True). Interpret mode (non-TPU) has
    no VMEM and never clamps or demotes."""
    from .tree_impl import _mesh_platform
    if _mesh_platform() != "tpu":
        return block_rows, False
    from ..native import traverse_kernel as _tk
    mb = _tk.max_block_rows(n_trees, n_nodes, n_feat)
    if mb == 0:
        return 0, True
    return min(block_rows, mb), False


def resolve_infer_kernel(n_trees: int, n_nodes: int, n_feat: int):
    """Per-dispatch traversal-spec resolution → (kernel, block_rows):
    the conf ladder (`sml.infer.kernel` + `sml.infer.kernelBlockRows`).
    A pallas candidate passes the real-TPU VMEM guard (`_vmem_guard`):
    the block clamps to the budget, and an unfittable spec falls back to
    xla with `infer.kernel.fallback` + demotion counts instead of
    failing to lower mid-trace. The resolved pair keys the program cache
    and the prewarm signature, so a change compiles fresh."""
    from ..conf import GLOBAL_CONF
    from ..native import traverse_kernel as _tk
    from .tree_impl import _mesh_platform
    # `sml.infer.kernel` for the ACTIVE mesh; `auto` on a TPU whose
    # toolchain probe fails is the one fallback, and it is counted
    kernel, fell_back = _tk.resolve_mode(
        GLOBAL_CONF.get("sml.infer.kernel"), _mesh_platform())
    if fell_back:
        _kernel_fallback()
    if kernel != "pallas":
        _note_spec("xla", 0)
        return "xla", 0
    block_rows, demoted = _vmem_guard(
        GLOBAL_CONF.getInt("sml.infer.kernelBlockRows"),
        n_trees, n_nodes, n_feat)
    if demoted:
        _kernel_fallback()
        _KERNEL_STATE["demotions"] += 1
        _note_spec("xla", 0)
        return "xla", 0
    _note_spec("pallas", block_rows)
    return "pallas", int(block_rows)


def kernel_report() -> dict:
    """The `infer_kernel` block of `obs.engine_health()`: the last
    resolved traversal spec (kernel, block rows) and the cumulative
    fallback/demotion counts — a replica silently scoring off the
    compiled kernel shows up here, not just in the counters."""
    return dict(_KERNEL_STATE)


def _forest_margin_path(binned_b, sf, sb, lv, weights, depth: int,
                        kernel: str, block_rows: int):
    """THE switch between the XLA where-sum traversal and the fused
    `native/traverse_kernel.py` launch — the one sanctioned invocation
    site of `forest_traverse` (graftlint's dispatch-bypass rule fences
    it here, mirroring the fit-kernel fence). The mask multiply, base
    offset, and eval psums stay in the callers, so both paths share
    every op outside the traversal itself."""
    if kernel == "pallas":
        from ..native import traverse_kernel as _tk
        from .tree_impl import _mesh_platform
        interp = _mesh_platform() != "tpu"
        # block_rows is the HOST-resolved spec value riding this
        # program's cache key; the kernel never reads conf at trace
        # time (0 means one full block)
        return _tk.forest_traverse(binned_b, sf, sb, lv, weights,
                                   depth=depth, interpret=interp,
                                   block_rows=block_rows)
    return _forest_margin(binned_b, sf, sb, lv, weights, depth)


_forest_forwards: dict = {}


def _make_forest_forward(depth: int, kernel: str = "xla",
                         block_rows: int = 0):
    """Memoized per (depth, kernel, block_rows): the prewarm manifest
    replays forest programs through this factory, and program caches key
    on fn IDENTITY — a fresh closure per call would compile a parallel
    universe of executables instead of warming the live ones. The
    resolved traversal spec is part of the identity (and the `_prewarm`
    meta) so a spec change compiles fresh and replay rebuilds the
    RECORDED spec regardless of live conf."""
    key = (depth, kernel, block_rows)
    fn = _forest_forwards.get(key)
    if fn is None:
        def forest_forward(binned_b, mask, sf, sb, lv, weights):
            return _forest_margin_path(binned_b, sf, sb, lv, weights,
                                       depth, kernel, block_rows) * mask

        forest_forward._prewarm = ("forest_forward", {
            "depth": int(depth), "kernel": str(kernel),
            "block_rows": int(block_rows)})
        _forest_forwards[key] = fn = forest_forward
    return fn


_forest_programs: dict = {}


def _forest_program(depth: int, kernel: str = "xla", block_rows: int = 0):
    mesh = meshlib.get_mesh()
    key = (depth, id(mesh), kernel, block_rows)
    if key not in _forest_programs:
        _forest_programs[key] = cached_data_parallel(
            _make_forest_forward(depth, kernel, block_rows),
            out_replicated=False, replicated_argnums=(2, 3, 4, 5))
    return _forest_programs[key]


_forest_eval_fns: dict = {}


def forest_eval_fn(depth: int, link: str = "identity",
                   kernel: str = "xla", block_rows: int = 0):
    """Fused predict+metric program for the evaluator pushdown: traverse
    the stacked ensemble AND reduce the five regression sufficient
    statistics in one dispatch — D2H is five scalars instead of a
    predictions column (3.2 MB per 800k-row eval, paid by every CV/tuning
    eval). `lmask` is 1.0 where the label is finite (matching
    `_pred_label`'s finite filter); labels are pre-zeroed at masked rows so
    padding and NaN labels are inert under psum.

    `link` applies a known elementwise fn to predictions INSIDE the
    program (the ML 11 shape: fit on log(label), metric on
    exp(prediction) — `SML/ML 11 - XGBoost.py`'s log-price flow).

    Module-level per-(depth, link, kernel, block_rows) fn identity so
    cached_data_parallel's program cache hits across calls — the
    resolved traversal spec keys the executable exactly like the
    forward program's."""
    key = (depth, link, kernel, block_rows)
    fn = _forest_eval_fns.get(key)
    if fn is not None:
        return fn
    # resolved from the ONE registry (base.RegStatsHook.LINKS holds the
    # names; np/jnp mirror them) — callers guard resolvability first
    link_fn = None if link == "identity" else getattr(jnp, link)

    def forest_eval(binned_b, l, lmask, mask, sf, sb, lv, weights, base):
        pred = base + _forest_margin_path(binned_b, sf, sb, lv, weights,
                                          depth, kernel, block_rows)
        if link_fn is not None:
            pred = link_fn(pred)
            # the link can produce NaN/inf (log of a <=0 margin, exp
            # overflow — including at PADDING rows, whose garbage margins
            # are otherwise inert): fold finiteness into the mask and
            # zero dead predictions so NaN*0 never reaches the psums.
            # Matches the host paths, which filter non-finite predictions
            ok = jnp.isfinite(pred)
            mask = mask * ok.astype(jnp.float32)
            pred = jnp.where(ok, pred, 0.0)
        m = mask * lmask
        d = (pred - l) * m
        from ..parallel import collectives as _coll
        n = _coll.psum(jnp.sum(m))
        se = _coll.psum(jnp.sum(d * d))
        ae = _coll.psum(jnp.sum(jnp.abs(d)))
        sl = _coll.psum(jnp.sum(m * l))
        sl2 = _coll.psum(jnp.sum(m * l * l))
        return n, se, ae, sl, sl2

    forest_eval.__name__ = f"forest_eval_d{depth}" + \
        ("" if link == "identity" else f"_{link}") + \
        ("" if kernel == "xla" else f"_{kernel}")
    forest_eval._prewarm = ("forest_eval", {
        "depth": int(depth), "link": str(link), "kernel": str(kernel),
        "block_rows": int(block_rows)})
    _forest_eval_fns[key] = forest_eval
    return forest_eval


def _register_prewarm_factories() -> None:
    # meta.get defaults keep pre-tuner manifests replayable (entries
    # recorded before the kernel/block_rows lanes existed are XLA specs)
    from ..parallel import prewarm as _prewarm
    _prewarm.register_fn_factory(
        "forest_forward",
        lambda m: _make_forest_forward(int(m["depth"]),
                                       str(m.get("kernel", "xla")),
                                       int(m.get("block_rows", 0))))
    _prewarm.register_fn_factory(
        "forest_eval",
        lambda m: forest_eval_fn(int(m["depth"]), str(m["link"]),
                                 str(m.get("kernel", "xla")),
                                 int(m.get("block_rows", 0))))


_register_prewarm_factories()


def _stage_rows(X: np.ndarray):
    from ._staging import (_is_bin_matrix, stage_bins_cached,
                           stage_mask_cached, stage_rows_cached)
    X = np.asarray(X)
    n_true = X.shape[0]
    # quantized bin matrices ride the shared bin cache: a predict/eval on
    # rows the fit already staged reuses the fit's device copy verbatim
    dev = stage_bins_cached(X) if _is_bin_matrix(X) else stage_rows_cached(X)
    mask_dev = stage_mask_cached(dev.shape[0], n_true)
    return dev, mask_dev, n_true


def predict_linear_sharded(X: np.ndarray, w: np.ndarray, b: float,
                           *, logistic: bool = False) -> np.ndarray:
    """Rows sharded over the mesh, coefficients replicated; returns host
    predictions for the true (unpadded) rows."""
    Xd, mask, n = _stage_rows(np.ascontiguousarray(X, dtype=np.float32))
    fwd = _logistic_forward if logistic else _linear_forward
    prog = cached_data_parallel(fwd, out_replicated=False,
                                replicated_argnums=(2, 3))
    out = prog(Xd, mask, jnp.asarray(w, dtype=jnp.float32),
               jnp.float32(b))
    return np.asarray(out, dtype=np.float64)[:n]


def predict_forest_sharded(binned: np.ndarray, sf: np.ndarray,
                           sb: np.ndarray, lv: np.ndarray,
                           weights: np.ndarray, depth: int,
                           base: float = 0.0) -> np.ndarray:
    """Stacked-ensemble traversal: rows sharded over the mesh, tree tensors
    replicated (they are KB-scale), one fused program for the whole forest.
    `binned` keeps its compact quantized dtype end-to-end (the program
    widens on-device). The traversal implementation (XLA where-sums vs
    the fused `native/traverse_kernel.py` launch) resolves per dispatch
    through `resolve_infer_kernel`."""
    binned = np.ascontiguousarray(binned)
    kernel, block_rows = resolve_infer_kernel(
        n_trees=sf.shape[0], n_nodes=sf.shape[1], n_feat=binned.shape[1])
    Bd, mask, n = _stage_rows(binned)
    prog = _forest_program(depth, kernel, block_rows)
    out = prog(Bd, mask, np.asarray(sf), np.asarray(sb),
               np.asarray(lv, dtype=np.float32),
               np.asarray(weights, dtype=np.float32))
    return base + np.asarray(out, dtype=np.float64)[:n]


# ----------------------------------------------------------------- DeviceScorer
class DeviceScorer:
    """Load-once, score-many wrapper for native models — the object an
    ML 12-style scalar-iterator UDF or `mapInPandas` body holds
    (`ML 12:101-143`): feature prep runs per batch on host, the model math
    runs as one sharded device program per batch.

    Accepts LinearRegressionModel / LogisticRegressionModel, the tree
    ensemble models, or a PipelineModel ending in one of those (earlier
    stages are applied as host feature prep).
    """

    def __init__(self, model):
        self._stages = []
        #: last traversal spec this scorer's device route resolved
        #: (None until a device-routed forest dispatch; linear models
        #: never traverse) — surfaced by ServingEndpoint.health_report()
        self._kernel_spec = None
        tail = model
        stages = getattr(model, "stages", None)
        if stages:
            self._stages = list(stages[:-1])
            tail = stages[-1]
        self._model = tail
        self._kind, self._params = self._compile_target(tail)
        # fuse the feature chain into one columnar pass when its shape is
        # the supported Imputer/StringIndexer/OHE/VectorAssembler program
        self._featurizer = None
        if self._stages:
            from .feature import VectorAssembler
            from .featurizer import CompiledFeaturizer
            last = self._stages[-1]
            if isinstance(last, VectorAssembler) and \
                    last.getOrDefault("outputCol") == self.featuresCol:
                self._featurizer = CompiledFeaturizer.from_stages(
                    self._stages[:-1], last)
        # linear model over one-hot slots is algebraically an EMBEDDING SUM:
        # w·onehot(idx) == w_slice[idx]. The factorized scorer skips
        # materializing the (n, d) one-hot block entirely — the ML 12
        # serving path's cost was almost all block assembly
        self._factorized = None
        if self._featurizer is not None and self._kind == "linear":
            self._factorized = self._build_factorized()

    @staticmethod
    def _compile_target(model):
        spec = getattr(model, "_spec", None)
        if spec is not None and hasattr(spec, "trees"):  # tree ensembles
            sf, sb, lv, w = spec.stacked()
            return "forest", (spec, sf, sb, lv, w)
        coef = getattr(model, "_coefficients", None)
        if coef is None and hasattr(model, "coefficients"):
            coef = np.asarray(model.coefficients.toArray())
        if coef is not None:
            intercept = float(getattr(model, "intercept", 0.0))
            logistic = hasattr(model, "numClasses")
            return "linear", (np.asarray(coef), intercept, logistic)
        raise TypeError(f"no device inference path for {type(model).__name__}")

    @property
    def featuresCol(self) -> str:
        return self._model.getOrDefault("featuresCol")

    def _dispatch(self, X: np.ndarray):
        """Stage + launch the scoring program; returns (out, n_true,
        finalize) without forcing the result — the pipelining hook. Each
        batch is routed host/device by the dispatcher (`parallel.dispatch`);
        `out` is a host array on the host route."""
        from ..parallel import dispatch as _dispatch_mod
        from ._staging import route_for_arrays
        if self._kind == "linear":
            w, b, logistic = self._params
            n, d = np.shape(X)
            X32 = np.ascontiguousarray(X, np.float32)
            hint = _dispatch_mod.WorkHint(flops=2.0 * n * d, kind="blas",
                                          out_bytes=4.0 * n)
            if route_for_arrays(hint, X32)[1] == "host":
                out = np.asarray(X, np.float64) @ np.asarray(w, np.float64) + b
                if logistic:
                    out = 1.0 / (1.0 + np.exp(-out))
                return out, n, lambda m: m
            Xd, mask, n = _stage_rows(X32)
            fwd = _logistic_forward if logistic else _linear_forward
            prog = cached_data_parallel(fwd, out_replicated=False,
                                        replicated_argnums=(2, 3))
            out = prog(Xd, mask, jnp.asarray(w, dtype=jnp.float32),
                       jnp.float32(b))
            return out, n, lambda m: m

        spec, sf, sb, lv, w = self._params
        finalize = self._finalize_forest

        from .tree_impl import bin_with, predict_forest
        binned = bin_with(np.asarray(X, dtype=np.float64), spec.binning)
        n = binned.shape[0]
        hint = _dispatch_mod.WorkHint(
            flops=4.0 * n * len(spec.trees) * spec.depth, kind="traverse",
            out_bytes=4.0 * n)
        mesh, route = route_for_arrays(hint, binned)
        if route == "host":
            import jax as _jax
            with _dispatch_mod.observe_host("traverse", hint.flops), \
                    _jax.default_device(list(mesh.devices.flat)[0]):
                margin = predict_forest(binned, spec.trees, spec.depth,
                                        spec.tree_weights)
            return margin, n, finalize
        binned = np.ascontiguousarray(binned)
        kernel, block_rows = resolve_infer_kernel(
            n_trees=sf.shape[0], n_nodes=sf.shape[1],
            n_feat=binned.shape[1])
        self._kernel_spec = {"kernel": kernel, "block_rows": block_rows}
        Bd, mask, n = _stage_rows(binned)
        prog = _forest_program(spec.depth, kernel, block_rows)
        # replicated operands go in as host arrays: the program's own
        # shardings place them on every chip (jnp.asarray would stage
        # them on the first chip and copy from there)
        out = prog(Bd, mask, np.asarray(sf), np.asarray(sb),
                   np.asarray(lv, dtype=np.float32),
                   np.asarray(w, dtype=np.float32))
        return out, n, finalize

    def _finalize_forest(self, margin: np.ndarray) -> np.ndarray:
        """Margin → prediction for the tree-ensemble kinds: boosted margins
        go through the sigmoid, probability-leaf forests clip."""
        spec = self._params[0]
        margin = spec.base + margin
        if spec.mode == "binary":
            if spec.tree_weights is not None:
                return 1.0 / (1.0 + np.exp(-margin))
            return np.clip(margin, 0.0, 1.0)
        return margin

    def score_block_host(self, X: np.ndarray) -> np.ndarray:
        """Predict a raw (n, d) feature block on the HOST route
        unconditionally — the serving layer's degradation target when the
        device queue saturates (admission control falls back here instead
        of deadlocking behind a full micro-batch queue). Same numerics as
        `score_block`'s host branch; never stages, never dispatches."""
        from ..parallel import dispatch as _dispatch_mod
        if self._kind == "linear":
            w, b, logistic = self._params
            out = np.asarray(X, np.float64) @ np.asarray(w, np.float64) + b
            if logistic:
                out = 1.0 / (1.0 + np.exp(-out))
            return out
        spec = self._params[0]
        from .tree_impl import bin_with, predict_forest
        binned = bin_with(np.asarray(X, dtype=np.float64), spec.binning)
        import jax as _jax
        host_dev = list(_dispatch_mod.host_mesh().devices.flat)[0]
        flops = 4.0 * binned.shape[0] * len(spec.trees) * spec.depth
        with _dispatch_mod.observe_host("traverse", flops), \
                _jax.default_device(host_dev):
            margin = predict_forest(binned, spec.trees, spec.depth,
                                    spec.tree_weights)
        return self._finalize_forest(margin)

    def kernel_spec(self) -> Optional[dict]:
        """The traversal spec this scorer's most recent device-routed
        forest dispatch resolved to ({kernel, block_rows}), or
        None (linear model / no device dispatch yet). Snapshot first:
        a concurrent `_dispatch` (prefetch/serving threads) rebinds
        `_kernel_spec` between a check and a `dict()` of it."""
        spec = self._kernel_spec
        return None if spec is None else dict(spec)

    def resident_bytes(self) -> int:
        """Approximate bytes a WARM scorer pins per mesh (model tensors
        replicated into HBM plus their host mirrors) — the cost model the
        serving multi-model cache budgets against. Feature-prep state is
        negligible next to the model tensors and is not counted."""
        if self._kind == "linear":
            arrays = [self._params[0]]
        else:
            arrays = [a for a in self._params[1:] if a is not None]
        return max(int(sum(np.asarray(a).nbytes for a in arrays)), 64)

    def _build_factorized(self):
        """(scalar_sources, scalar_weights, embeds): weight slices aligned
        to the featurizer's slot layout. Returns None when any source shape
        is unsupported."""
        from .featurizer import _IndexSource, _NumericSource, _OneHotSource
        # snapshot: `_prep` (running on a prefetch lookahead thread) can
        # null `_featurizer` between the width check and the source walk
        # — the same race PR 12 fixed in `_score_factorized`/`_prep`
        featurizer = self._featurizer
        if featurizer is None:
            return None
        w = np.asarray(self._params[0], dtype=np.float64)
        if w.ndim != 1 or w.shape[0] != featurizer.width:
            return None
        scalars, embeds = [], []
        lo = 0
        for s in featurizer.sources:
            if isinstance(s, _OneHotSource):
                embeds.append((s.inner, w[lo:lo + s.width].copy()))
            elif isinstance(s, (_NumericSource, _IndexSource)):
                scalars.append((s, float(w[lo])))
            else:
                return None
            lo += s.width
        return scalars, embeds

    def _score_factorized(self, pdf) -> np.ndarray:
        """Linear predict without the one-hot block: numeric dot + one
        embedding-table lookup per encoded column. Exactly the X·w result
        (NaN propagation, handleInvalid drops/keep-overflow included)."""
        import pandas as pd
        from .featurizer import (_IndexSource, _NumericSource,
                                 extract_numeric_block)
        # snapshot BOTH compiled layers: score_batches' factorized branch
        # runs __call__ on lookahead threads, so a concurrent batch that
        # lost a raw column may null self._factorized/_featurizer while
        # this thread is mid-score. A torn read must land on the same
        # KeyError fallback ladder the missing column itself takes — not
        # surface as AttributeError(None) out of the stream
        factorized, featurizer = self._factorized, self._featurizer
        if factorized is None or featurizer is None:
            raise KeyError("factorized scorer disabled concurrently")
        scalars, embeds = factorized
        _, b, logistic = self._params
        n = len(pdf)
        drop = np.zeros(n, dtype=bool)
        acc = np.full(n, float(b), dtype=np.float64)
        # numeric block in ONE pandas extraction (dominant scalar cost)
        num = [(s, wi) for s, wi in scalars if type(s) is _NumericSource]
        if num:
            cols = [s.col for s, _ in num]
            fills = np.asarray([np.nan if s.fill is None else s.fill
                                for s, _ in num])
            block = extract_numeric_block(pdf, cols, fills)
            # f32 quantization parity with the block path (X is float32)
            acc += block.astype(np.float32).astype(np.float64) \
                @ np.asarray([wi for _, wi in num])
        for s, wi in scalars:
            if isinstance(s, _IndexSource):
                acc += wi * s.resolve(pdf, drop)
        for inner, table in embeds:
            if isinstance(inner, _IndexSource):
                idx = inner.resolve(pdf, drop)
            else:
                idx = np.asarray(pd.to_numeric(pdf[inner.col],
                                               errors="coerce"), np.float64)
                if inner.fill is not None:
                    idx = np.where(np.isfinite(idx), idx, inner.fill)
            na = ~np.isfinite(idx)
            ok = ~na & (idx >= 0) & (idx < len(table))
            contrib = np.zeros(n, dtype=np.float64)
            oki = np.nonzero(ok)[0]
            contrib[oki] = table[idx[oki].astype(np.intp)]
            contrib[na] = np.nan  # NaN one-hot row → NaN prediction
            acc += contrib
        if featurizer.handle_invalid == "error" \
                and not np.isfinite(acc[~drop]).all():
            raise ValueError(
                "VectorAssembler found NaN/null in assembled features; set "
                "handleInvalid='skip' or impute first")
        if drop.any():
            acc = acc[~drop]
        if logistic:
            acc = 1.0 / (1.0 + np.exp(-acc))
        return acc

    def score_block(self, X: np.ndarray) -> np.ndarray:
        """Predict from a raw (n, d) feature block."""
        out, n, finalize = self._dispatch(X)
        return finalize(np.asarray(out, dtype=np.float64)[:n])

    def __call__(self, pdf) -> np.ndarray:
        """Predict from a host pandas batch: run feature stages, extract
        the columnar feature block, score on-device (or factorized on host
        for linear models — see _score_factorized)."""
        if self._factorized is not None and not isinstance(pdf, np.ndarray):
            try:
                return self._score_factorized(pdf)
            except KeyError:
                self._factorized = None  # batch missing a raw column
        return self.score_block(self._prep(pdf))

    def _prep(self, pdf) -> np.ndarray:
        if isinstance(pdf, np.ndarray):
            return pdf
        featurizer = self._featurizer  # snapshot: concurrent batches may
        if featurizer is not None:     # null it between check and call
            try:
                return featurizer(pdf)
            except KeyError:
                # a column the compiled chain assumed raw isn't in this
                # batch: permanently fall back to the generic stage path
                self._featurizer = None
        cur = pdf
        if self._stages:
            # single-partition wrap: stage fns run ONCE per batch — routing
            # a 10k-row batch through the session's default 8-way split ran
            # every stage 8x and dominated the ML 12 leg
            from ..frame.dataframe import DataFrame as _DF
            df = _DF.from_partitions([pdf])
            for s in self._stages:
                df = s.transform(df)
            cur = df.toPandas()
        return extract_features(cur, self.featuresCol)

    def score_batches(self, batches: Iterable,
                      depth: Optional[int] = None) -> Iterator[np.ndarray]:
        """Pipeline an iterator of pandas batches through the scorer:
        feature prep for upcoming batches runs on worker threads (pandas /
        numpy release the GIL in their C paths) while the current batch's
        math executes, and on the device route up to `depth` batches are
        dispatched ahead with async host copies started at dispatch — prep,
        H2D staging, device compute, and D2H transfers all overlap.

        `depth` defaults to `sml.infer.prefetchBatches` (conf). With the
        flight recorder on, every dispatch and drain emits an `infer.*`
        event, so the staging-of-batch-i+1-overlaps-compute-of-batch-i
        pipelining claim is ASSERTABLE from the event order (batch i+1's
        dispatch lands before batch i's drain — tested). The loop itself
        is the shared `parallel.pipeline` staging pipeline — the same
        machinery the out-of-core chunked ingest rides."""
        from ..conf import GLOBAL_CONF
        from ..parallel.pipeline import prefetch_map, prefetch_pipeline
        if depth is None:
            depth = max(GLOBAL_CONF.getInt("sml.infer.prefetchBatches"), 1)
        if self._factorized is not None:
            # factorized linear scoring is pure host numpy/pandas work:
            # bounded-lookahead thread map, no device dispatch to overlap
            yield from prefetch_map(batches, self.__call__, depth=depth)
            return

        def dispatch(_i, X):
            out, n, fin = self._dispatch(X)
            if hasattr(out, "copy_to_host_async"):  # host route: numpy
                out.copy_to_host_async()
            return out, n, fin

        def drain(_i, handle):
            out, n, fin = handle
            return fin(np.asarray(out, dtype=np.float64)[:n])

        yield from prefetch_pipeline(batches, self._prep, dispatch, drain,
                                     depth=depth, workers=4, family="infer",
                                     index_key="batch")
