"""Pallas fused batched tree-traversal kernel for the serving hot path.

The XLA ensemble traversal in `ml/inference.py` (`_forest_margin`)
scores level by level: the per-node one-hot, the feature-select
one-hot, and the `(T, rows)` per-tree margin stack are all separate
HLOs whose intermediates round-trip HBM between levels. This kernel
fuses the whole descent
ON-CHIP — the accelerator-side batched traversal of "Booster: An
Accelerator for Gradient Boosting Decision Trees" (arXiv:2011.02022),
with the batched node layout of "GPU-acceleration for Large-scale Tree
Boosting" (arXiv:1706.08359) — for a block of rows at a time:

- The ensemble rides as a level-order **SoA node table**: one lane per
  node attribute — feature id (`sf`, −1 at leaves), split bin (`sb`),
  leaf/node value (`lv`) — stacked `(T, n_nodes)` per tree, exactly the
  heap layout `_EnsembleSpec.stacked()` already produces (children of
  node *i* at 2i+1 / 2i+2, so descent needs no child-pointer gathers).
  The tables are KB-scale and stay resident in VMEM for every grid step.
- Rows stream HBM→VMEM in blocks; the **depth-unrolled predicated
  descent** (the per-level node one-hot, the feature-select against the
  compact bin matrix, the child step) and the per-tree **leaf sums
  accumulate in-register** — only the final `(block,)` weighted margin
  leaves the kernel. The per-level one-hots and the `(T, rows)` margin
  stack never touch HBM.

The kernel body is `ml/inference._forest_margin`'s math (same one-hot
where-sums — gather-free and exact in f32, see that docstring for why —
same select), written to the chip's tiling: every value is 2-D with rows
on sublanes (`(block, 1)` node ids, `(block, width)` one-hot tiles — a
1-D vector or a 1-D iota does not lower through Mosaic), trees run as a
`fori_loop` that reads one `(1, n_nodes)` table row per step, and the
weighted leaf sum accumulates tree by tree. The traversal has NO
cross-row operation, so row blocking cannot change any output bit; each
per-row where-sum has exactly one nonzero term, so only the order of the
T-term weighted tree sum can differ from the XLA path (sequential here,
XLA-determined there). Interpret mode (non-TPU backends, single block)
is bit-identical to the XLA path on CPU, which
tests/test_traverse_kernel.py asserts across DT/RF/xgboost, uint8/uint16
bin matrices, NaN rows, and the logistic finalize; the compiled kernel's
agreement on TPU is checked by `chip_smoke.py` (docs/KERNELS.md).

Every `pl.pallas_call` in the package must live in `sml_tpu/native/`,
and every *invocation* of `forest_traverse` must come from the
`score_block` dispatch glue (`ml/inference.py`) — graftlint's
`dispatch-bypass` rule flags both, so the `infer.kernel.*` counters and
the fallback ladder stay authoritative.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

from ..utils.profiler import PROFILER

#: minor-dimension tile of every VMEM array: what a VMEM guard must pad to
LANES = 128

#: interpret flag -> None (a launch worked) | the error text it raised
_avail: Dict[bool, Optional[str]] = {}


def probe(interpret: bool) -> Optional[str]:
    """Whether the Pallas toolchain can launch a kernel in this process,
    probed ONCE per mode with a tiny kernel: `interpret=True` on non-TPU
    backends, a Mosaic COMPILE and run on a TPU mesh. Returns None when
    the launch worked, else the error it raised, so callers can raise
    the compiler's own message (`resolve_mode`). This proves the
    toolchain, not that every kernel body lowers at every shape: a body
    that cannot compile fails at its own first launch, and nothing
    catches that."""
    if interpret not in _avail:
        try:
            import jax
            import jax.numpy as jnp
            from jax.experimental import pallas as pl

            def _probe(x_ref, o_ref):
                o_ref[...] = x_ref[...] + 1.0

            out = pl.pallas_call(
                _probe,
                out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
                interpret=interpret,
            )(jnp.ones((8, 128), jnp.float32))
            _avail[interpret] = None if float(out[0, 0]) == 2.0 \
                else "probe kernel returned a wrong value"
        except Exception as e:  # noqa: BLE001 — reported, never swallowed
            _avail[interpret] = f"{type(e).__name__}: {e}"
    return _avail[interpret]


def resolve_mode(mode, platform: str) -> Tuple[str, bool]:
    """(kernel, fell_back) for the value `mode` of `sml.infer.kernel` on
    a mesh of `platform` (docs/KERNELS.md). 'xla' short-circuits. 'auto'
    selects pallas only on a TPU mesh, where this kernel compiles for
    v5e (jax 0.9.0 / libtpu 0.0.34) at the course shapes and agrees with
    the XLA traversal (PR 21 chip run) — elsewhere xla is the resolver's
    answer for the platform, not a fallback; a TPU whose toolchain probe
    then fails is the one fallback (the caller counts it). An explicit
    'pallas' is a demand: interpret mode off-TPU, a compiled launch on
    TPU, and a toolchain that cannot launch raises its own error. Any
    other value raises (a typo must not silently land on either path)."""
    mode = str(mode).strip().lower()
    if mode not in ("auto", "pallas", "xla"):
        raise ValueError(
            f"sml.infer.kernel must be one of auto/pallas/xla, got {mode!r}")
    on_tpu = platform == "tpu"
    if mode == "xla" or (mode == "auto" and not on_tpu):
        return "xla", False
    err = probe(interpret=not on_tpu)
    if err is None:
        return "pallas", False
    if mode == "pallas":
        raise RuntimeError(f"sml.infer.kernel=pallas but a Pallas kernel "
                           f"cannot launch on this {platform} mesh: {err}")
    return "xla", True


#: compiled-path VMEM budget per grid step. Mosaic's scoped limit on v5e
#: is 16 MiB; `traverse_vmem_bytes` is within ~7% of what the compiler
#: reported at the course shapes, so 12 MiB leaves the rest as margin
TRAVERSE_VMEM_BUDGET = 12 << 20

_SUBLANES = 32  # row-block multiple that suits every bin dtype (uint8)


def _lane_pad(k: int) -> int:
    return -(-int(k) // LANES) * LANES


def traverse_vmem_bytes(block_rows: int, n_trees: int, n_nodes: int,
                        n_feat: int) -> int:
    """Per-grid-step VMEM estimate of the compiled traversal, counting
    the 128-lane padding of every minor dimension: per row, the
    `(block, width)` node one-hot and its where-select, the widened bin
    tile and its feature select, and the `(block, 1)` vectors (node,
    looked-up feature/bin, row bin, accumulator), each of which occupies
    a full lane tile; plus the resident SoA node tables (three
    `(T, n_nodes)` lanes). The guard in `ml/inference.py` demotes
    oversized (block_rows × trees) specs with this estimate instead of
    failing to compile mid-trace (block_rows=0 = the block-independent
    node-table term alone)."""
    blk = max(int(block_rows), 0)
    per_row = 4 * (2 * _lane_pad(n_nodes) + 2 * _lane_pad(n_feat)
                   + 4 * LANES)
    tables = 12 * (-(-int(n_trees) // 8) * 8) * _lane_pad(n_nodes)
    return int(blk * per_row + tables)


def max_block_rows(n_trees: int, n_nodes: int, n_feat: int) -> int:
    """Largest row block whose per-grid-step estimate fits
    `TRAVERSE_VMEM_BUDGET`, or 0 when even one 32-row tile cannot (the
    resident node tables alone bust the budget — the spec must demote
    to XLA). THE single source of the guard's arithmetic: the
    resolver in `ml/inference.py` clamps/demotes through this, so the
    budget math cannot drift from the `traverse_vmem_bytes` estimate."""
    fixed = traverse_vmem_bytes(0, n_trees, n_nodes, n_feat)
    per_row = traverse_vmem_bytes(1, n_trees, n_nodes, n_feat) - fixed
    blk = (TRAVERSE_VMEM_BUDGET - fixed) // max(per_row, 1)
    return int(blk) if blk >= _SUBLANES else 0


def _block_plan(n: int, interpret: bool,
                block_rows: Optional[int]) -> Tuple[int, int]:
    """(grid steps, rows per block). Interpret mode uses ONE block (no
    VMEM to bound; fewer traced ops). Compiled mode picks the largest
    divisor of `n` at or under the target that is a multiple of 32 rows
    (the sublane tile of a uint8 bin block; a block narrower than the
    array must be tile-aligned), so every grid step sees a full block —
    rows are bucket-padded by staging, so aligned divisors are dense.
    Blocking never changes results: the traversal has no cross-row
    reduction, so it is pure VMEM scheduling.

    `block_rows` is resolved HOST-side (`inference.resolve_infer_kernel`
    reads `sml.infer.kernelBlockRows` once per program build, and the
    value rides the inference program cache key); this function runs at
    TRACE time and must never consult live conf — a read here would be
    burned into the executable and silently diverge from the keyed
    value. None/0 means no blocking: one full block."""
    if interpret or not block_rows or n <= int(block_rows):
        return 1, n
    for k in range(-(-n // int(block_rows)), n // _SUBLANES + 1):
        if n % k == 0 and (n // k) % _SUBLANES == 0:
            return k, n // k
    raise ValueError(
        f"no {_SUBLANES}-row-aligned block of at most {block_rows} rows "
        f"divides the {n} rows on this chip; stage rows through "
        f"`mesh.bucket_rows` or score with sml.infer.kernel=xla")


def forest_traverse(binned, sf, sb, lv, weights, *, depth: int,
                    interpret: bool = False,
                    block_rows: Optional[int] = None):
    """Weighted stacked-ensemble margin for a per-chip row block, fused
    in one kernel launch: `(rows,)` f32 from the compact bin matrix.

    `binned` is the bin-cache operand as staged (uint8/uint16 — or int32
    on wide-bin models); `sf`/`sb`/`lv` are the level-order SoA node
    tables (`(T, n_nodes)`, `_EnsembleSpec.stacked()` layout) and
    `weights` the `(T,)` per-tree weights. Equivalent XLA-path
    computation, whose where-sums the kernel body reproduces per block:
    `ml/inference._forest_margin(binned, sf, sb, lv, weights, depth)`.

    The mask multiply, the base offset, and every psum of the fused
    eval program stay OUTSIDE the kernel in the `ml/inference.py` glue,
    so the kernel swap cannot change semantics — only where the per-level
    intermediates live."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    n, F = binned.shape
    T, n_nodes = sf.shape
    nblk, blk = _block_plan(n, interpret, block_rows)

    def kernel(b_ref, sf_ref, sb_ref, lv_ref, w_ref, out_ref):
        # _forest_margin's where-SUMs on one row block, exact in f32 —
        # no gathers, no MXU bf16 operand truncation. Everything stays
        # 2-D (rows on sublanes) and every iota is 2-D: Mosaic lowers
        # neither 1-D vectors reshaped to columns nor 1-D iota. The bin
        # block widens through int32 (no uint8 -> f32 cast on the chip).
        binned_f = b_ref[...].astype(jnp.int32).astype(jnp.float32)
        fio = jax.lax.broadcasted_iota(jnp.int32, (1, F), 1) \
            .astype(jnp.float32)
        nio = jax.lax.broadcasted_iota(jnp.int32, (1, n_nodes), 1)

        def one_tree(t, acc):
            f = sf_ref[pl.ds(t, 1), :]                     # (1, n_nodes)
            fpos = jnp.maximum(f, 0).astype(jnp.float32)
            internal = f >= 0
            s_f = sb_ref[pl.ds(t, 1), :].astype(jnp.float32)
            v = lv_ref[pl.ds(t, 1), :].astype(jnp.float32)
            node = jnp.zeros((blk, 1), dtype=jnp.int32)
            for lvl in range(depth):
                width = min(2 ** (lvl + 1) - 1, n_nodes)
                oh = node == nio[:, :width]                # (blk, width)
                fa = jnp.sum(jnp.where(oh, fpos[:, :width], 0.0),
                             axis=1, keepdims=True)
                ba = jnp.sum(jnp.where(oh, s_f[:, :width], 0.0),
                             axis=1, keepdims=True)
                isin = jnp.sum(
                    jnp.where(oh & internal[:, :width], 1.0, 0.0),
                    axis=1, keepdims=True) > 0.0
                xbin = jnp.sum(jnp.where(fio == fa, binned_f, 0.0),
                               axis=1, keepdims=True)
                child = 2 * node + 1 + (xbin > ba).astype(jnp.int32)
                node = jnp.where(isin, child, node)
            leaf = jnp.sum(jnp.where(node == nio, v, 0.0),
                           axis=1, keepdims=True)
            return acc + w_ref[pl.ds(t, 1), :].astype(jnp.float32) * leaf

        out_ref[...] = jax.lax.fori_loop(
            0, T, one_tree, jnp.zeros((blk, 1), jnp.float32))

    kwargs = {}
    if not interpret:
        from jax.experimental.pallas import tpu as pltpu
        # no grid step revisits an output block: row blocks are
        # independent
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel",))
    PROFILER.count("kernel.pallas_launch")
    if interpret:
        PROFILER.count("kernel.interpret")
    out = pl.pallas_call(
        kernel,
        grid=(nblk,),
        in_specs=[
            pl.BlockSpec((blk, F), lambda i: (i, 0)),
            pl.BlockSpec((T, n_nodes), lambda i: (0, 0)),
            pl.BlockSpec((T, n_nodes), lambda i: (0, 0)),
            pl.BlockSpec((T, n_nodes), lambda i: (0, 0)),
            pl.BlockSpec((T, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((blk, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.float32),
        interpret=interpret,
        **kwargs,
    )(binned, sf, sb, lv, weights.reshape(T, 1))
    return out[:, 0]
