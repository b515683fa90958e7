"""Pallas fused bin-accumulate + split-scan kernels for the tree hot path.

The XLA tree-build path (`ml/tree_impl._make_tree_builder`) emits the
level-wise histogram build as separate HLOs: a one-hot expansion of the
whole bin matrix into an (n, F*B) operand (`B1t`, materialized in HBM and
kept pre-transposed for the entire fit), a second (n, width*3) one-hot ×
stats product (`ns`), a dot, then a reshape/transpose/cumsum/argmax chain
— every level round-trips those intermediates through HBM. The custom
kernels here fuse each stage ON-CHIP (the approach of "GPU-acceleration
for Large-scale Tree Boosting", arXiv:1706.08359, and "Booster",
arXiv:2011.02022, ported to the TPU memory hierarchy):

- `hist_accumulate`: per-chip partial histogram straight FROM THE COMPACT
  BIN CACHE operand (uint8/uint16). Row blocks stream HBM→VMEM; the
  one-hot bin tile and the node×stats tile exist only in VMEM for the
  lifetime of one block's MXU contraction, and grid steps accumulate into
  the one resident (F*B, width*3) output block — the O(n×F×B) one-hot and
  the O(n×width×3) `ns` never touch HBM, and the fit-long `B1t` resident
  disappears entirely.
- `split_scan`: the per-level gain scan (cumsum over bins, XGBoost gain,
  min-instances / last-bin / feature-subspace masks, per-node argmax) on
  the post-psum (F, B, width, 3) histogram, in registers, emitting only a
  (6, width) best-split pack.

The psum stays OUTSIDE the kernels: per-chip partials are unchanged, so
the kernels compose with `shard_map` + `collectives.psum` (and the
histogram-subtraction halving, which operates on the post-psum histogram
between the two kernels) exactly like the XLA path.

INTERPRET-MODE CONTRACT (tier-1): on non-TPU backends the kernels run
under `pallas_call(interpret=True)` with a SINGLE row block, so the traced
kernel body is op-for-op the XLA path's math (same one-hot, same
`dot_general` dimension numbers, same cumsum/argmax) evaluated by the same
backend — fit outputs are BIT-IDENTICAL to the XLA path, which
tests/test_hist_kernel.py asserts. The row-block grid is what would bound
VMEM on hardware, but neither body compiles for the chip as written
(`AUTO_ON_TPU` below; docs/KERNELS.md "State on the chip").

Every `pl.pallas_call` in the package must live in `sml_tpu/native/` —
graftlint's `dispatch-bypass` rule flags raw kernel launches anywhere
else, the same way it fences bare `jax.jit`.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

from ..utils.profiler import PROFILER

#: `sml.tree.kernel=auto` does NOT select these kernels on a TPU mesh:
#: neither body compiles for v5e with jax 0.9.0 / libtpu 0.0.34 (PR 21,
#: docs/KERNELS.md "State on the chip"). `hist_accumulate`: "Mosaic
#: failed to compile TPU kernel: infer-vector-layout: unsupported shape
#: cast ... tpu.reshape (vector<4096xi1>) -> vector<4096x1xi1>" (the 1-D
#: row operands). `split_scan`: "Unimplemented primitive in Pallas TPU
#: lowering for KernelType.TC: cumsum". The two share one switch, so
#: `auto` resolves to the XLA build on TPU; an explicit 'pallas' there
#: raises the compiler's message.
AUTO_ON_TPU = False

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


def resolve_mode(key: str, mode, platform: str,
                 auto_on_tpu: bool) -> Tuple[str, bool]:
    """(kernel, fell_back) for the value `mode` of the kernel switch
    `key` (`sml.tree.kernel` / `sml.infer.kernel`) on a mesh of
    `platform` — the ONE resolution both switches share
    (docs/KERNELS.md). 'xla' short-circuits. 'auto' selects pallas only
    on a TPU mesh AND only while the switch's kernels are recorded as
    compiling there (`auto_on_tpu`) — otherwise xla is the resolver's
    answer for the platform, not a fallback; a TPU whose toolchain probe
    then fails is the one fallback (the caller counts it). An explicit
    'pallas' is a demand: interpret mode off-TPU, a compiled launch on
    TPU, and a toolchain that cannot launch raises its own error. Any
    other value raises (a typo must not silently land on either path)."""
    mode = str(mode).strip().lower()
    if mode not in ("auto", "pallas", "xla"):
        raise ValueError(
            f"{key} must be one of auto/pallas/xla, got {mode!r}")
    on_tpu = platform == "tpu"
    if mode == "xla" or (mode == "auto" and not (on_tpu and auto_on_tpu)):
        return "xla", False
    err = probe(interpret=not on_tpu)
    if err is None:
        return "pallas", False
    if mode == "pallas":
        raise RuntimeError(f"{key}=pallas but a Pallas kernel cannot "
                           f"launch on this {platform} mesh: {err}")
    return "xla", True


def _block_plan(n: int, interpret: bool,
                block_rows: Optional[int]) -> Tuple[int, int]:
    """(grid steps, rows per block) for the accumulate kernel.

    Interpret mode always uses ONE block: the whole per-chip row set goes
    through a single dot with the XLA path's exact dimension numbers —
    the bit-parity contract tier-1 asserts. Compiled mode picks the
    largest divisor of `n` at or under `block_rows` so every grid step
    sees a full block (no partial-block masking; rows are already
    bucket-padded by staging, so divisors are dense).

    `block_rows` is resolved HOST-side (`tree_impl._kernel_block_rows`
    reads `sml.tree.kernelBlockRows` once per program build, and the
    value rides every tree program cache key and the prewarm manifest);
    this function runs at TRACE time and must never consult live conf —
    a read here would be burned into the executable and silently diverge
    from the keyed value. None/0 means no blocking: one full block."""
    if interpret or not block_rows:
        return 1, n
    target = max(1, min(int(block_rows), n))
    k = -(-n // target)
    while n % k:
        k += 1
    return k, n // k


def hist_accumulate(binned, lid, grad, hess, weight, *, n_bins: int,
                    n_slots: int, hist_dtype=None, interpret: bool = False,
                    block_rows: Optional[int] = None):
    """Per-chip partial histogram for one tree level, fused in one kernel:
    (F*n_bins, n_slots*3) f32 from the COMPACT bin matrix.

    `binned` is the bin-cache operand as staged (uint8/uint16 — or int32
    on the single-tree path); `lid` is each row's one-hot slot at this
    level (the left-child slot under histogram subtraction), `weight` the
    effective per-row weight (0 excludes the row). Equivalent XLA-path
    computation, which the kernel body reproduces op-for-op per block:

        B1t  = one_hot(binned, B).reshape(n, F*B).T      # HBM resident
        ns   = (one_hot(lid, S) * (w>0)) ⊗ [g*w, h*w, w]  # HBM transient
        hist = B1t @ ns

    Here both one-hots are VMEM tiles of one row block; grid steps
    accumulate into the single resident output block."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if hist_dtype is None:
        hist_dtype = jnp.float32
    n, F = binned.shape
    B, S = int(n_bins), int(n_slots)
    nblk, blk = _block_plan(n, interpret, block_rows)

    def kernel(b_ref, lid_ref, g_ref, h_ref, w_ref, out_ref):
        @pl.when(pl.program_id(0) == 0)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)

        b = b_ref[...]
        w = w_ref[...]
        # the XLA path's exact ops on one row block: exact 0/1 one-hots
        # (bf16-safe on TPU), f32 MXU accumulation
        b1t = jax.nn.one_hot(b.astype(jnp.int32), B, dtype=hist_dtype) \
            .reshape(b.shape[0], F * B).T
        node1hot = jax.nn.one_hot(lid_ref[...], S, dtype=hist_dtype) \
            * (w > 0)[:, None].astype(hist_dtype)
        stats = jnp.stack([g_ref[...] * w, h_ref[...] * w, w], axis=1)
        ns = (node1hot[:, :, None]
              * stats[:, None, :].astype(hist_dtype)).reshape(b.shape[0],
                                                              S * 3)
        out_ref[...] += jax.lax.dot_general(
            b1t, ns, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    kwargs = {}
    if not interpret:
        from jax.experimental.pallas import tpu as pltpu
        # grid steps revisit the one output block: the grid is sequential
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",))
    PROFILER.count("kernel.pallas_launch")
    if interpret:
        PROFILER.count("kernel.interpret")
    return pl.pallas_call(
        kernel,
        grid=(nblk,),
        in_specs=[
            pl.BlockSpec((blk, F), lambda i: (i, 0)),
            pl.BlockSpec((blk,), lambda i: (i,)),
            pl.BlockSpec((blk,), lambda i: (i,)),
            pl.BlockSpec((blk,), lambda i: (i,)),
            pl.BlockSpec((blk,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((F * B, S * 3), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((F * B, S * 3), jnp.float32),
        interpret=interpret,
        **kwargs,
    )(binned, lid, grad, hess, weight)


def split_scan(hist, feat_mask, min_inst, *, reg_lambda: float,
               gamma: float, interpret: bool = False):
    """Fused per-level gain scan on the POST-PSUM histogram: cumulative
    bin sums, the second-order XGBoost gain, the min-instances / last-bin
    / feature-subspace candidate masks, and the per-node argmax — all in
    registers, emitting a (6, width) f32 pack:

        [best_feature, best_bin, best_gain - gamma, G, H, W]

    `hist` is (F, B, width, 3) f32; `feat_mask` is the (width, F) 0/1
    RF-subspace mask computed by the caller (the draw uses the engine's
    jax.random stream, which must stay outside the kernel so the pallas
    and XLA paths consume identical randomness); `min_inst` is a (1, 1)
    f32 scalar operand (traced per-trial under grid fusion). The body is
    op-for-op tree_impl's XLA scan, so interpret mode is bit-identical."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    F, B, width = hist.shape[0], hist.shape[1], hist.shape[2]
    lam = float(reg_lambda)
    gam = float(gamma)

    def kernel(h_ref, fm_ref, mi_ref, out_ref):
        h = h_ref[...]
        hG = jnp.transpose(h[..., 0], (2, 0, 1))              # (width,F,B)
        hH = jnp.transpose(h[..., 1], (2, 0, 1))
        hW = jnp.transpose(h[..., 2], (2, 0, 1))
        GL = jnp.cumsum(hG, axis=2)
        HL = jnp.cumsum(hH, axis=2)
        WL = jnp.cumsum(hW, axis=2)
        G = GL[:, :, -1:]
        H = HL[:, :, -1:]
        W = WL[:, :, -1:]
        score = (GL ** 2 / (HL + lam + 1e-12)
                 + (G - GL) ** 2 / (H - HL + lam + 1e-12)
                 - G ** 2 / (H + lam + 1e-12))
        mi = mi_ref[0, 0]
        ok = (WL >= mi) & ((W - WL) >= mi)
        # 2-D+ iota (TPU requires it); values identical to arange(B)<B-1
        ok = ok & (jax.lax.broadcasted_iota(jnp.int32, (1, 1, B), 2)
                   < B - 1)
        ok = ok & (fm_ref[...] > 0)[:, :, None]
        sc = jnp.where(ok, score, -jnp.inf)
        flat_best = jnp.argmax(sc.reshape(width, F * B), axis=1)
        best_f = (flat_best // B).astype(jnp.int32)
        best_b = (flat_best % B).astype(jnp.int32)
        best_gain = 0.5 * jnp.take_along_axis(
            sc.reshape(width, F * B), flat_best[:, None], axis=1)[:, 0] \
            - gam
        out_ref[...] = jnp.stack([
            best_f.astype(jnp.float32), best_b.astype(jnp.float32),
            best_gain, G[:, 0, 0], H[:, 0, 0], W[:, 0, 0]])

    PROFILER.count("kernel.pallas_launch")
    if interpret:
        PROFILER.count("kernel.interpret")
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((6, width), jnp.float32),
        interpret=interpret,
    )(hist, feat_mask, min_inst)
