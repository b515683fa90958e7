"""The histogram operand is built once a dispatch (ISSUE 27), stored at
one byte an element on the chip (ISSUE 41) and written by row blocks, with
nothing table-wide beside it (ISSUE 50).

`tree_impl._tree_operand` is the one place that widens the bins and builds
the one-hot `B1t`, and it ends in an `optimization_barrier`: without
it XLA:TPU's fusible sinking rebuilt the one-hot of ISSUES 27-48 inside the
loop over rounds. `tree_impl._operand_dtype` is the one place that says what
it is stored as: int8 where the histogram dot multiplies in bf16 (the chip),
the dot's own type elsewhere. Five things are held here, none of which needs
the chip:

  (a) the jaxpr of every looping program has the barrier once, outside the
      scan over rounds, and the histogram dot reads it as a scan constant;
  (b) compiled for a described v5e chip, no `tree.operand` instruction is
      inside the loop over rounds, that loop carries the one-hot as ONE int8
      operand, and no widened copy of it exists anywhere in the program;
  (c) the barrier is the identity: the fitted packs are bit-equal without it;
  (d) so is the stored type: the packs are bit-equal with the operand stored
      as int8 and as the histogram's own type;
  (e) so is the build: the packs are bit-equal with the operand built the
      way it was until ISSUE 50 (`jax.nn.one_hot` over the whole table and a
      transpose, kept HERE as `_whole_table_operand`), and compiled at the
      sizes of the benchmark's tree cells no program holds an array of rows
      x columns x bins elements but the operand itself.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sml_tpu.ml import tree_impl
from sml_tpu.parallel import mesh as meshlib

D = meshlib.DATA_AXIS
F, B = 3, 8


def _es(boosting: bool, n_trees: int = 2, depth: int = 2):
    spec = tree_impl.TreeSpec(
        max_depth=depth, n_bins=B, n_features=F,
        feature_k=F if boosting else 2, min_instances=1, min_info_gain=0.0,
        reg_lambda=1.0 if boosting else 0.0, gamma=0.0)
    return tree_impl.EnsembleSpec(
        tree=spec, n_trees=n_trees, loss="squared", boosting=boosting,
        bootstrap=not boosting, subsample=1.0, step_size=0.1)


def _rows(n: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    binned = rng.integers(0, B, size=(n, F)).astype(np.uint8)
    y = (binned[:, 0] * 0.5 - binned[:, 1] * 0.25
         + rng.normal(0, 0.1, n)).astype(np.float32)
    return binned, y, np.ones(n, np.float32), \
        np.asarray(jax.random.key_data(jax.random.PRNGKey(seed)))


def _program(which: str, es):
    """(per-chip program, its arguments at 64 rows, which are row-sharded)."""
    binned, y, mask, rng = _rows(64)
    made = (es, 1, (D,), 0)
    if which == "ensemble":
        return tree_impl._make_ensemble_program(*made), \
            (binned, y, mask, rng), (True, True, True, False)
    if which == "chunk":
        return tree_impl._make_chunk_program(es, 2, *made[1:]), \
            (binned, y, mask, np.zeros(64, np.float32), rng, np.int32(0)), \
            (True, True, True, True, False, False)
    assert which == "trials"
    return tree_impl._make_trials_program(*made), \
        (binned, y, mask, rng, np.int32(2), np.int32(2), np.float32(1.0),
         np.float32(0.0), np.bool_(True), np.float32(1.0)), \
        (True, True, True) + (False,) * 7


def _whole_table_operand(binned_c, n_bins: int, hist_dtype,
                         barrier: bool = True):
    """`tree_impl._tree_operand` as it was until ISSUE 50: `jax.nn.one_hot`
    of the whole table's bins widened to int32, reshaped and transposed.
    The control of (e), and what XLA:TPU sinks into the loop over rounds
    where nothing bars it."""
    with jax.named_scope("tree.operand"):
        binned = binned_c.astype(jnp.int32)
        n, width = binned.shape
        B1t = jax.nn.one_hot(
            binned, n_bins, dtype=tree_impl._operand_dtype(hist_dtype)) \
            .reshape(n, width * n_bins).T
        return binned, (jax.lax.optimization_barrier(B1t) if barrier
                        else B1t)


def _cpu_mesh():
    return Mesh(np.array(jax.devices()[:1]), (D,))


def _sharded(program, args, by_row, mesh):
    specs = tuple(P(D, *([None] * (np.ndim(a) - 1))) if r else P()
                  for a, r in zip(args, by_row))
    return jax.shard_map(program, mesh=mesh, in_specs=specs, out_specs=P(),
                         check_vma=False), specs


def _walk(jaxpr, in_scan=False):
    """Every equation of a jaxpr and of the jaxprs it holds, with whether a
    scan encloses it."""
    for eqn in jaxpr.eqns:
        yield eqn, in_scan
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _walk(sub, in_scan or eqn.primitive.name == "scan")


# ------------------------------------------------------------- (a) the jaxpr
@pytest.mark.parametrize("which,boosting", [
    ("ensemble", True), ("ensemble", False), ("chunk", True),
    ("trials", False)], ids=["ensemble-boosted", "ensemble-bagged", "chunk",
                             "trials"])
def test_one_barrier_outside_the_scan_feeds_the_histogram_dot(which, boosting):
    program, args, by_row = _program(which, _es(boosting))
    mapped, _ = _sharded(program, args, by_row, _cpu_mesh())
    eqns = list(_walk(jax.make_jaxpr(mapped)(*args).jaxpr))
    barriers = [(e, inside) for e, inside in eqns
                if e.primitive.name == "optimization_barrier"]
    assert len(barriers) == 1
    barrier, inside = barriers[0]
    assert not inside, "the barrier lies outside the scan over rounds"
    assert "tree.operand" in str(barrier.source_info.name_stack)
    (b1t,) = barrier.outvars
    assert (b1t.aval.shape, b1t.aval.dtype) == (
        (F * B, 64), tree_impl._operand_dtype(tree_impl._hist_dtype()))

    # (the operand's own walk over its row blocks is a scan too, of its
    # scope; the loop over rounds is the other one)
    scans = [e for e, _ in eqns if e.primitive.name == "scan"
             and "tree.operand" not in str(e.source_info.name_stack)]
    assert len(scans) == 1
    scan = scans[0]
    body = scan.params["jaxpr"].jaxpr
    consts = body.invars[:scan.params["num_consts"]]
    # (the leaves' statistics are a second, small dot under tree.hist, of
    # the node one-hot: it is made in the body, and rightly)
    dots = [e for e, _ in _walk(body) if e.primitive.name == "dot_general"
            and "tree.hist" in str(e.source_info.name_stack)
            and e.invars[0].aval.shape == (F * B, 64)]
    assert len(dots) == 2, "one histogram dot a level, in the scan body"
    widened = {e.outvars[0]: e.invars[0] for e, _ in _walk(body)
               if e.primitive.name == "convert_element_type"}
    for dot in dots:
        # (where the stored type is not the dot's, the widening at the call
        # is all that lies between the two)
        lhs = widened.get(dot.invars[0], dot.invars[0])
        assert lhs in consts, "the dot's operand is computed in the body"
        assert scan.invars[consts.index(lhs)] is b1t, \
            "the dot reads another array than the barrier's"


def test_the_single_tree_program_builds_it_the_same_way_without_a_barrier():
    """One helper, but no loop to keep the operand out of: no barrier, so
    the program XLA:CPU compiles is the one it always was."""
    spec = _es(True).tree
    program = tree_impl._build_tree_program(spec, jnp.bfloat16, (D,), 0)
    binned, y, mask, rng = _rows(64)
    args = (binned, -y, mask, mask, rng)
    mapped, _ = _sharded(program, args, (True, True, True, True, False),
                         _cpu_mesh())
    eqns = [e for e, _ in _walk(jax.make_jaxpr(mapped)(*args).jaxpr)]
    names = [e.primitive.name for e in eqns
             if "tree.operand" not in str(e.source_info.name_stack)
             or e.primitive.name != "scan"]     # (its walk over row blocks)
    assert "optimization_barrier" not in names and "scan" not in names
    assert any("tree.operand" in str(e.source_info.name_stack)
               and e.outvars[0].aval.shape == (F * B, 64)
               and e.outvars[0].aval.dtype == jnp.int8 for e in eqns)


# ------------------------------------- what the operand's type and size hang on
# (before (b): its module fixture gives `_hist_dtype` the chip's answer)
def test_mesh_platform_memo_and_invalidation(spark):
    """`_hist_dtype`'s platform probe is memoized per MESH identity (it
    used to walk mesh.devices.flat on every fit-setup call): the memo
    answers for the same mesh, and a different mesh re-probes."""
    mesh = meshlib.get_mesh()
    tree_impl._platform_memo.clear()
    try:
        assert tree_impl._hist_dtype() == jnp.float32
        assert tree_impl._platform_memo.get(id(mesh))[1] == "cpu"
        # memo is authoritative for the same mesh: poison it, no re-probe,
        # and the operand's type follows the memo's platform
        tree_impl._platform_memo[id(mesh)] = (mesh, "tpu")
        assert tree_impl._hist_dtype() == jnp.bfloat16
        # a DIFFERENT mesh identity re-probes (the poison doesn't leak) —
        # including an id() COLLISION after GC: the memo re-checks identity
        other = meshlib.build_mesh(1)
        assert tree_impl._mesh_platform(other) == "cpu"
        tree_impl._platform_memo[id(other)] = (mesh, "tpu")  # stale identity
        assert tree_impl._mesh_platform(other) == "cpu"
        tree_impl._platform_memo[id(mesh)] = (mesh, "cpu")
        assert tree_impl._hist_dtype() == jnp.float32
    finally:
        tree_impl._platform_memo.clear()


def test_the_operand_is_stored_at_one_byte_where_the_dot_is_bf16():
    """`_operand_dtype` is the one place that decides: int8 for the chip's
    bf16 dot, and the float32 one-hot as it was elsewhere (XLA:CPU would
    write the widened copy at every level)."""
    assert tree_impl._operand_dtype(jnp.bfloat16) == jnp.int8
    assert tree_impl._operand_dtype(jnp.float32) == jnp.float32


@pytest.mark.parametrize("hist_dtype,itemsize", [
    (jnp.float32, 4), (jnp.bfloat16, 1)], ids=["cpu-f32", "tpu-bf16"])
def test_onehot_ledger_reads_the_operand_for_a_fit_and_zero_after(
        spark, monkeypatch, hist_dtype, itemsize):
    """The HBM ledger charges the one-hot resident under `hist_onehot` for
    as long as a fit's dispatch lasts: rows (as staged, padding included) x
    F x bins x the STORED type's itemsize at its peak (`_onehot_bytes`: four
    bytes for this platform's float32 one-hot, one for the chip's int8
    behind a bf16 dot), nothing once the fit has returned."""
    from sml_tpu.ml._staging import stage_sharded
    from sml_tpu.obs import LEDGER
    # (the program cache is not keyed by the operand's type, which a
    # platform fixes for a process: give the other type a cache of its own)
    monkeypatch.setattr(tree_impl, "_hist_dtype", lambda: hist_dtype)
    monkeypatch.setattr(tree_impl, "_ensemble_cache", {})
    binned, y, _, _ = _rows(3000, seed=5)
    b_dev, mask_dev, _ = stage_sharded(binned)
    y_dev = tree_impl.stage_aligned(y, b_dev.shape[0])
    LEDGER.reset_peaks()
    tree_impl.fit_ensemble_on_device(b_dev, y_dev, mask_dev, _es(True),
                                     seed=7)
    pool = LEDGER.snapshot()["hist_onehot"]
    assert pool["allocs"] == 1 and pool["frees"] == 1
    assert pool["peak"] == tree_impl._onehot_bytes(_es(True).tree,
                                                   b_dev.shape[0]) \
        == b_dev.shape[0] * F * B * itemsize
    assert pool["live"] == 0


# ------------------------------------------- (b) compiled for a described v5e
@pytest.fixture(scope="module")
def one_chip_mesh():
    """A mesh over ONE described v5e chip: libtpu compiles for it with no
    chip attached (`.claude/skills/verify/SKILL.md`). Described here and not
    at import, so that only the worker given this file loads libtpu."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    patch = pytest.MonkeyPatch()
    for key, value in (("TPU_LOG_DIR", "disabled"),
                       ("TPU_ACCELERATOR_TYPE", "v5litepod-4"),
                       ("TPU_WORKER_HOSTNAMES", "localhost"),
                       ("TPU_SKIP_MDS_QUERY", "1")):
        patch.setenv(key, value)
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:1x1",
            chips_per_host_bounds=(1, 1, 1))
    except Exception as e:  # noqa: BLE001 — whatever libtpu raises, it is a skip
        patch.undo()
        pytest.skip(f"no v5e:1x1 topology can be described here: {e}")
    # an executable compiled for a described chip cannot be read back from
    # the persistent cache without one: keep these compiles out of it
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # the program asks the ACTIVE mesh, the CPU's, for its operand type:
    # give it the chip's
    patch.setattr(tree_impl, "_hist_dtype", lambda: jnp.bfloat16)
    yield Mesh(np.array(topo.devices[:1]), (D,))
    jax.config.update("jax_enable_compilation_cache", cached)
    compilation_cache.reset_cache()
    patch.undo()


AOT_ROWS = 8192


def _compile_for(mesh, es, which: str = "ensemble", rows: int = AOT_ROWS):
    """A program compiled for `mesh`'s chip at `rows` rows of the spec's
    own width (the arguments' types are `_program`'s)."""
    program, args, by_row = _program(which, es)
    mapped, specs = _sharded(program, args, by_row, mesh)
    shapes = [jax.ShapeDtypeStruct(
        ((rows,) + (es.tree.n_features,) * (np.ndim(a) - 1)) if r
        else np.shape(a),
        np.asarray(a).dtype, sharding=NamedSharding(mesh, s))
        for a, r, s in zip(args, by_row, specs)]
    return jax.jit(mapped).lower(*shapes).compile()


def _compiled_for(mesh, es, which: str = "ensemble") -> str:
    """A program's optimized HLO for `mesh`'s chip, at 8,192 rows."""
    return _compile_for(mesh, es, which).as_text()


def _wider_copies(hlo: str, columns: int, rows: int) -> list:
    """The types in which a bf16 or f32 copy of the (columns, rows) one-hot
    appears in a program: the dot widens as it reads, so a copy hoisted out
    of the loop or written inside it is exactly what ISSUE 41 took away."""
    return [wide for wide in ("bf16", "f32")
            if f"{wide}[{columns},{rows}]" in hlo]


@pytest.mark.parametrize("which,boosting", [
    ("ensemble", True), ("ensemble", False), ("chunk", True),
    ("trials", False)], ids=["boosted", "bagged", "chunk", "trials"])
def test_compiled_for_v5e_the_operand_is_outside_the_loop(one_chip_mesh,
                                                          which, boosting):
    hlo = _compiled_for(one_chip_mesh, _es(boosting, n_trees=3), which)
    assert "tree.operand" in hlo and "tree.hist" in hlo   # metadata is there
    assert tree_impl.ops_in_loop_bodies(hlo, "tree.hist"), \
        "the check finds the loop and what runs in it"
    assert tree_impl.ops_in_loop_bodies(hlo, "tree.operand") == []
    # the loop over rounds (the forest's Poisson draw is a loop too)
    carried = [ln.split(" while(")[0] for ln in hlo.splitlines()
               if re.search(r"\swhile\(", ln) and "tree.operand" not in ln]
    assert sum(c.count(f"s8[{F * B},{AOT_ROWS}]") for c in carried) == 1, \
        "the loop over rounds carries the one-hot as ONE int8 operand"
    assert _wider_copies(hlo, F * B, AOT_ROWS) == []


def test_the_check_sees_an_operand_that_was_sunk(one_chip_mesh, monkeypatch):
    """The control: without the barrier libtpu 0.0.34 sinks a one-hot
    written as one expression over the table (the build of ISSUES 27-48)
    into the loop, and `ops_in_loop_bodies` says so. Should a later compiler
    stop sinking, this test fails and the barrier can be reconsidered."""
    monkeypatch.setattr(jax.lax, "optimization_barrier", lambda x: x)
    monkeypatch.setattr(tree_impl, "_tree_operand", _whole_table_operand)
    hlo = _compiled_for(one_chip_mesh, _es(True, n_trees=3))
    assert tree_impl.ops_in_loop_bodies(hlo, "tree.operand")


#: the benchmark's tree cells at their own shapes (padded rows, columns,
#: bins, depth, rounds; cell 3 is cell 1's shapes a chip)
CELLS = {
    "ml11_xgb.fit": dict(rows=1_703_936, feats=10, bins=64, depth=4,
                         trees=100, boosting=True, loss="squared"),
    "ml07_rf.fit": dict(rows=1_703_936, feats=10, bins=40, depth=5,
                        trees=10, boosting=False, loss="squared",
                        feature_k=3),
    "xgb_higgs.fit_boost_logistic": dict(
        rows=851_968, feats=28, bins=256, depth=8, trees=24, boosting=True,
        loss="logistic"),
}
_cell_programs = {}


def _cell_compiled(mesh, cell: str):
    """(compiled program, its optimized HLO) of a tree cell's fit at the
    cell's own shapes, compiled once a module."""
    if cell not in _cell_programs:
        c = CELLS[cell]
        spec = tree_impl.TreeSpec(
            max_depth=c["depth"], n_bins=c["bins"], n_features=c["feats"],
            feature_k=c.get("feature_k", c["feats"]), min_instances=1,
            min_info_gain=0.0, reg_lambda=1.0 if c["boosting"] else 0.0,
            gamma=0.0)
        es = tree_impl.EnsembleSpec(
            tree=spec, n_trees=c["trees"], loss=c["loss"],
            boosting=c["boosting"], bootstrap=not c["boosting"],
            subsample=1.0, step_size=0.1)
        compiled = _compile_for(mesh, es, rows=c["rows"])
        _cell_programs[cell] = (compiled, compiled.as_text())
    return _cell_programs[cell]


def test_the_boosted_fit_holds_a_one_byte_operand_at_the_cells_size(
        one_chip_mesh):
    """`ml11_xgb.fit`'s program at its own shapes (1,703,936 padded rows x
    10 features, 64 bins, depth 4, 100 rounds), compiled for the chip:
    1.44 GB of temporaries with the one-hot resident as s8[640, rows] and
    built by row blocks (5.58 GB with the table-wide int32 broadcast beside
    it until ISSUE 50, 6.67 GB with it in bf16 until ISSUE 41), and no wider
    copy of it."""
    rows, feats, bins = 1_703_936, 10, 64
    compiled, hlo = _cell_compiled(one_chip_mesh, "ml11_xgb.fit")
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    assert temporaries < 1.6e9, f"{temporaries / 1e9:.2f} GB of temporaries"
    assert f"s8[{feats * bins},{rows}]" in hlo
    assert _wider_copies(hlo, feats * bins, rows) == []


@pytest.mark.parametrize("cell", list(CELLS))
def test_a_tree_cells_program_holds_nothing_table_wide_but_the_operand(
        one_chip_mesh, cell):
    """(e) Each tree cell's fit compiled for the chip at the cell's own
    shapes: the operand is s8[columns x bins, rows], no instruction of its
    scope runs inside the loop over rounds, that loop carries it once, and
    NO other array of the program has rows x columns x bins elements: not
    the `s32[rows, columns, bins]` broadcast `jax.nn.one_hot` was compared
    from (4.36 GB of cells 1-3's 5.58 GB; 24.4 GB at the new cell's 256
    bins), not the `pred` it gave, not a one-byte broadcast of the bins
    (what the same compare written as one pass over the table leaves).
    The new cell's arguments and temporaries fit the chip with room: under
    12 GiB of its 16."""
    c = CELLS[cell]
    rows, width = c["rows"], c["feats"] * c["bins"]
    compiled, hlo = _cell_compiled(one_chip_mesh, cell)
    assert f"s8[{width},{rows}]" in hlo
    assert tree_impl.ops_in_loop_bodies(hlo, "tree.hist")
    assert tree_impl.ops_in_loop_bodies(hlo, "tree.operand") == []
    loops = [ln for ln in hlo.splitlines() if re.search(r"\swhile\(", ln)]
    own = [ln for ln in loops if "tree.operand" in ln]
    assert len(own) == 1, "the operand's walk over its row blocks"
    assert sum(ln.split(" while(")[0].count(f"s8[{width},{rows}]")
               for ln in loops if ln not in own) == 1
    table_wide = {
        f"{kind}[{dims}]"
        for kind, dims in re.findall(r"\b([a-z]+[0-9]*)\[([0-9,]+)\]", hlo)
        if np.prod([int(x) for x in dims.split(",")]) >= rows * width}
    assert table_wide and all(a.startswith("s8[") for a in table_wide), \
        table_wide
    block = tree_impl._OPERAND_BLOCK_ROWS
    assert f"[{c['feats']},{c['bins']},{block}]" in hlo, \
        "a block's bins against the bin ids"
    memory = compiled.memory_analysis()
    held = memory.temp_size_in_bytes + memory.argument_size_in_bytes
    assert held < rows * width + (2 << 30), f"{held / 1e9:.2f} GB"
    assert held < 12 << 30


def test_the_fused_logistic_fit_fits_a_v5e_at_the_cells_size(one_chip_mesh):
    """`mle03_logreg.fit_logistic`'s program at its own shapes (6,815,744
    padded rows, 17 numeric and 5 coded columns, 62 slots), compiled for
    the chip: it fits (the row-major expansion asked for 26 GB, ISSUE 32),
    the block is (63, rows) and not a lane-padded (rows, 63), and the
    scopes the benchmark's readers look for are in the metadata. In this
    file because one worker holds libtpu (the fixture above)."""
    from sml_tpu.ml import linear_impl
    rows = 6_815_744
    layout = tuple(("oh", j, w) for j, w in enumerate((1, 35, 5, 2, 2))) \
        + tuple(("num", i) for i in range(17))
    fn = linear_impl._compact_irls_fn(layout, 100, 1e-6)
    last, flat = P(None, D), P(D)
    mapped = meshlib.shard_map_compat(
        fn, mesh=one_chip_mesh, in_specs=(last, last, flat, flat),
        out_specs=P())
    shapes = [jax.ShapeDtypeStruct(shape, dtype,
                                   sharding=NamedSharding(one_chip_mesh, s))
              for shape, dtype, s in (((17, rows), jnp.float32, last),
                                      ((5, rows), jnp.int32, last),
                                      ((rows,), jnp.float32, flat),
                                      ((rows,), jnp.float32, flat))]
    compiled = jax.jit(mapped).lower(*shapes).compile()
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert held < 6e9, f"{held / 1e9:.2f} GB of a 16 GB chip"
    hlo = compiled.as_text()
    assert f"f32[63,{rows}]" in hlo and f"f32[{rows},63]" not in hlo
    for scope in ("linear.expand", "linear.irls/", "linear.irls.margin",
                  "linear.irls.grad", "linear.irls.hess",
                  "linear.irls.solve"):
        assert scope in hlo, scope


def test_a_folds_penalized_fits_fit_a_v5e_at_the_cells_size(one_chip_mesh):
    """`mle03_logreg_cv.fit_cv`'s fold program at its own shapes (six grid
    points over 6,815,744 padded rows with a fold id a row), compiled for
    the chip: ONE fit's temporaries whatever the grid's width (the points
    run one after another: the block and one weighted copy, not six), the
    new scopes are in the metadata, and nothing in it sorts (a `lax.sort`
    of the margins took 141 s to compile for the v5e, ISSUE 40: the
    ranking is the host's)."""
    from sml_tpu.ml import linear_impl
    rows, points = 6_815_744, 6
    layout = tuple(("oh", j, w) for j, w in enumerate((1, 35, 5, 2, 2))) \
        + tuple(("num", i) for i in range(17))
    fn = linear_impl._compact_enet_fn(layout, 100, 1e-6, True)
    last, flat, whole = P(None, D), P(D), P()
    args = (((17, rows), jnp.float32, last), ((5, rows), jnp.int32, last),
            ((rows,), jnp.float32, flat), ((rows,), jnp.int8, flat),
            ((rows,), jnp.float32, flat), ((), jnp.float32, whole),
            ((points,), jnp.float32, whole), ((points,), jnp.float32, whole))
    with meshlib.use_mesh_local(one_chip_mesh):
        mapped = meshlib.shard_map_compat(
            fn, mesh=one_chip_mesh, in_specs=tuple(s for _, _, s in args),
            out_specs=P())
        compiled = jax.jit(mapped).lower(*[
            jax.ShapeDtypeStruct(shape, dtype,
                                 sharding=NamedSharding(one_chip_mesh, s))
            for shape, dtype, s in args]).compile()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 3.7e9, \
        f"{memory.temp_size_in_bytes / 1e9:.2f} GB of temporaries"
    assert memory.argument_size_in_bytes < 1.0e9
    hlo = compiled.as_text()
    assert f"f32[63,{rows}]" in hlo and f"f32[{rows},63]" not in hlo
    assert f"f32[{points},63,{rows}]" not in hlo
    assert not re.search(r"\ssort\(", hlo)
    for scope in ("linear.expand", "linear.irls/", "linear.irls.hess",
                  "linear.irls.solve", "linear.irls.prox", "cv.eval"):
        assert scope in hlo, scope


def test_the_fused_logistic_fit_leaves_its_loop_on_done(one_chip_mesh):
    """The loop over Newton steps stops where the fit has converged
    (ISSUE 39): compiled for the chip, the condition of the `linear.irls`
    loop reads a `pred[]` of the carry (`done`) beside the step count. A
    refactor back to a fixed length (a `scan`, whose condition is a bare
    trip count) fails here, before a chip run reads `steps_per_fit` 100."""
    from sml_tpu.ml import linear_impl
    layout = (("oh", 0, 3), ("num", 0), ("num", 1))
    fn = linear_impl._compact_irls_fn(layout, 100, 1e-6)
    last, flat = P(None, D), P(D)
    mapped = meshlib.shard_map_compat(
        fn, mesh=one_chip_mesh, in_specs=(last, last, flat, flat),
        out_specs=P())
    shapes = [jax.ShapeDtypeStruct(shape, dtype,
                                   sharding=NamedSharding(one_chip_mesh, s))
              for shape, dtype, s in (((2, AOT_ROWS), jnp.float32, last),
                                      ((1, AOT_ROWS), jnp.int32, last),
                                      ((AOT_ROWS,), jnp.float32, flat),
                                      ((AOT_ROWS,), jnp.float32, flat))]
    hlo = jax.jit(mapped).lower(*shapes).compile().as_text()
    loops = [ln for ln in hlo.splitlines()
             if re.search(r"\swhile\(", ln) and "linear.irls/while" in ln]
    assert len(loops) == 1, loops
    name = re.search(r"condition=%?([\w.\-]+)", loops[0]).group(1)
    condition = re.search(
        rf"^%?{re.escape(name)} \(.*?^}}", hlo, re.M | re.S).group(0)
    assert re.search(r"pred\[\]\S* get-tuple-element\(", condition), condition
    assert "s32[]" in condition and "constant(100)" in condition, condition


def test_a_blocks_segment_sums_are_a_tile_product_on_a_v5e(one_chip_mesh):
    """The factorization's fit program (ISSUE 43) compiled for the chip at
    blocks of 2^18 rows, rank 12: the loop over blocks holds a matrix
    product under `als.normal.tiles`, no array is shaped (tiles, 1, width)
    (the one-sublane layout of the scan it replaced), no loop is longer
    than a block's tiles, and the temporaries are no more than the
    program's before it at the same block (306,711,040 B: commit 8156e75
    compiled the same way). In this file because one worker holds libtpu
    (the fixture above); the sums themselves are held on the CPU in
    `tests/test_als_blocks.py`."""
    from sml_tpu.ml import recommendation
    rows, block, rank, users, items = 1 << 20, 1 << 18, 12, 4096, 2048
    width = recommendation._stat_width(rank)
    fn = recommendation._als_fit_program(users, items, rank, 0.1, 2, False,
                                         block)
    row, bounds = P(D), P(D, None, None)
    specs = (row, row, row, row, bounds, bounds, P(), P())
    mapped = meshlib.shard_map_compat(fn, mesh=one_chip_mesh, in_specs=specs,
                                      out_specs=P())
    shapes = [jax.ShapeDtypeStruct(shape, dtype,
                                   sharding=NamedSharding(one_chip_mesh, sp))
              for (shape, dtype), sp in zip(
                  (((rows,), jnp.int32), ((rows,), jnp.int32),
                   ((rows,), jnp.float32), ((rows,), jnp.float32),
                   ((1, 2, users), jnp.int32), ((1, 2, items), jnp.int32),
                   ((users, rank), jnp.float32),
                   ((items, rank), jnp.float32)), specs)]
    # every loop of the program is a scan of a static length: the
    # alternations, a side's blocks, the Cholesky's columns
    eqns = [e for e, _ in _walk(jax.make_jaxpr(mapped)(*shapes).jaxpr)]
    assert not [e for e in eqns if e.primitive.name == "while"]
    lengths = [e.params["length"] for e in eqns if e.primitive.name == "scan"]
    assert rows // block in lengths
    assert max(lengths) <= block // recommendation._TILE, lengths
    compiled = jax.jit(mapped).lower(*shapes).compile()
    hlo = compiled.as_text()
    in_loops = set(tree_impl.ops_in_loop_bodies(hlo, "als.normal.tiles"))
    products = [ln.split(" = ", 1)[0].strip().removeprefix("ROOT ")
                for ln in hlo.splitlines() if "als.normal.tiles" in ln
                and re.search(r"\s(convolution|dot)\(", ln)]
    assert products and set(products) <= in_loops, products
    assert f"f32[{block // recommendation._TILE},{recommendation._TILE}," \
        f"{width}]" in hlo
    assert not re.search(rf"f32\[\d+,1,{width}\]", hlo)
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    assert temporaries <= 306_711_040, temporaries
    for scope in ("als.gather", "als.normal.tiles", "als.normal.carry",
                  "als.normal.allreduce", "als.solve"):
        assert scope in hlo, scope


def test_ops_in_loop_bodies_follows_calls():
    hlo = """HloModule m
%fused (p: s32[4]) -> s32[4] {
  %p = s32[4] parameter(0)
  ROOT %a = s32[4] add(%p, %p), metadata={op_name="jit(f)/tree.operand/add"}
}
%body (t: (s32[], s32[4])) -> (s32[], s32[4]) {
  %t = (s32[], s32[4]) parameter(0)
  %x = s32[4] get-tuple-element(%t), index=1
  %f = s32[4] conditional(%p, %x, %x), branch_computations={%cond, %fused}
  ROOT %r = (s32[], s32[4]) tuple(%i, %f), metadata={op_name="jit(f)/while/body/tree.hist/t"}
}
%cond (t: (s32[], s32[4])) -> pred[] {
  %t = (s32[], s32[4]) parameter(0)
  ROOT %c = pred[] constant(false)
}
ENTRY %main (a: s32[4]) -> s32[4] {
  %a = s32[4] parameter(0)
  %o = s32[4] add(%a, %a), metadata={op_name="jit(f)/tree.operand/outside"}
  %w = (s32[], s32[4]) while(%tup), condition=%cond, body=%body
  ROOT %g = s32[4] get-tuple-element(%w), index=1
}
"""
    assert tree_impl.ops_in_loop_bodies(hlo, "tree.operand") == ["%a"]
    assert tree_impl.ops_in_loop_bodies(hlo, "tree.hist") == ["%r"]
    assert tree_impl.ops_in_loop_bodies(hlo.replace("%", ""),
                                        "tree.operand") == ["a"]


# --------------------------------------------- (c) the barrier is the identity
def _fit_on_cpu(es):
    """(packs, base, the traced program's text) of the ensemble program as
    it is built NOW, on 512 rows and one CPU device."""
    data = _rows(512, seed=3)
    program = tree_impl._make_ensemble_program(es, 1, (D,), 0)
    mapped, _ = _sharded(program, data, (True, True, True, False), _cpu_mesh())
    packs, base = jax.jit(mapped)(*data)
    return np.asarray(packs), float(base), str(jax.make_jaxpr(mapped)(*data))


@pytest.mark.parametrize("boosting", [True, False], ids=["boosted", "bagged"])
def test_fitted_packs_are_bit_equal_without_the_barrier(boosting, monkeypatch):
    es = _es(boosting, n_trees=4, depth=3)
    with_barrier = _fit_on_cpu(es)
    monkeypatch.setattr(jax.lax, "optimization_barrier", lambda x: x)
    without = _fit_on_cpu(es)
    assert with_barrier[0].shape == (4, 5, 15)
    assert (with_barrier[0][:, 0] >= 0).any(), "the trees split"
    np.testing.assert_array_equal(with_barrier[0], without[0])
    assert with_barrier[1] == without[1]


# ----------------------------------------- (d) and so is the stored type
@pytest.mark.parametrize("boosting", [True, False], ids=["boosted", "bagged"])
def test_fitted_packs_are_bit_equal_with_the_operand_stored_as_int8(
        boosting, monkeypatch):
    """0 and 1 are exact in int8 and in the histogram's type, and the dot
    multiplies in the histogram's type either way: on this platform the
    program with an int8 `B1t` fits the very trees of the one with the
    float32 `B1t`."""
    es = _es(boosting, n_trees=4, depth=3)
    monkeypatch.setattr(tree_impl, "_operand_dtype", lambda hist: hist)
    as_hist = _fit_on_cpu(es)
    monkeypatch.setattr(tree_impl, "_operand_dtype", lambda hist: jnp.int8)
    as_int8 = _fit_on_cpu(es)
    stored = f"i8[{F * B},512]"
    assert stored in as_int8[2] and stored not in as_hist[2], \
        "the patch reaches the operand"
    assert (as_hist[0][:, 0] >= 0).any(), "the trees split"
    np.testing.assert_array_equal(as_hist[0], as_int8[0])
    assert as_hist[1] == as_int8[1]


# ------------------------------------------ (e) and so is the build
@pytest.mark.parametrize("bins", [40, 64, 256])
@pytest.mark.parametrize("boosting", [True, False], ids=["boosted", "bagged"])
def test_fitted_packs_are_bit_equal_to_the_whole_table_builds(
        boosting, bins, monkeypatch):
    """The operand written by row blocks (two whole blocks of 200 rows and
    a remainder of 112 here) is the operand `jax.nn.one_hot` of the whole
    table gave, element for element: the program fits the very trees."""
    width = 5
    spec = tree_impl.TreeSpec(
        max_depth=3, n_bins=bins, n_features=width,
        feature_k=width if boosting else 2, min_instances=1,
        min_info_gain=0.0, reg_lambda=1.0 if boosting else 0.0, gamma=0.0)
    es = tree_impl.EnsembleSpec(
        tree=spec, n_trees=3, loss="logistic" if boosting else "squared",
        boosting=boosting, bootstrap=not boosting, subsample=1.0,
        step_size=0.3)
    rng = np.random.default_rng(bins)
    binned = rng.integers(0, bins, size=(512, width)).astype(np.uint8)
    y = ((binned[:, 0] > bins // 2) ^ (binned[:, 1] < bins // 3)) \
        .astype(np.float32)
    data = (binned, y, np.ones(512, np.float32),
            np.asarray(jax.random.key_data(jax.random.PRNGKey(1))))

    def fit():
        program = tree_impl._make_ensemble_program(es, 1, (D,), 0)
        mapped, _ = _sharded(program, data, (True, True, True, False),
                             _cpu_mesh())
        packs, base = jax.jit(mapped)(*data)
        return np.asarray(packs), float(base), \
            str(jax.make_jaxpr(mapped)(*data))

    monkeypatch.setattr(tree_impl, "_OPERAND_BLOCK_ROWS", 200)
    by_blocks = fit()
    assert "dynamic_update_slice" in by_blocks[2]
    assert f"[512,{width},{bins}]" not in by_blocks[2]
    monkeypatch.setattr(tree_impl, "_tree_operand", _whole_table_operand)
    whole = fit()
    assert f"[512,{width},{bins}]" in whole[2] \
        and "dynamic_update_slice" not in whole[2], \
        "the patch reaches the operand"
    assert (whole[0][:, 0] >= 0).any(), "the trees split"
    np.testing.assert_array_equal(by_blocks[0], whole[0])
    assert by_blocks[1] == whole[1]


@pytest.mark.parametrize("rows,blocks", [
    (64, 1), (1 << 14, 1), ((1 << 14) + 1, 2), (851_968, 52),
    (1_703_936, 104)])
def test_operand_blocks_counts_whole_blocks_and_a_remainder(rows, blocks):
    assert tree_impl._operand_blocks(rows) == blocks


# ----------------------------------- the clustering's blocked Lloyd step
def test_the_clustering_fit_holds_no_rows_by_k_array_at_the_cells_width(
        one_chip_mesh, monkeypatch):
    """`mle02_kmeans.fit_kmeans`'s program at its own widths (42 columns,
    k = 1000, two rounds of k-means||) over 262,144 rows in blocks of 8,192,
    compiled for the chip: the seeding, the Lloyd loop and the cost are
    there, the largest arrays are the table and a block's (k, block) tile,
    and nothing has rows x k elements (at the cell's size: 27 GB). Every
    distance product (the Lloyd step's, the cost pass's, the two seeding
    rounds' and the candidates' own) is ONE bfloat16 product over a
    contraction of 6d = 252 with a float32 output, `_nearest`'s six parts'
    products stacked (ISSUE 48): no float32 product over a contraction of
    d is left with a (centers, block) output, the compiler still writes
    no such tile (the temporaries stay far under 1 GiB), and it writes no
    (6d, block) operand out either (`_stacked_rows`). The
    compile goes here, where the others are: one worker loads libtpu."""
    from sml_tpu.ml import clustering
    rows, d, k, block = 262_144, 42, 1000, 8192
    monkeypatch.setattr(clustering, "_block_rows", lambda width: block)
    clustering.forget_programs()
    mesh = one_chip_mesh
    program = clustering._fit_program(k, "k-means||", 2)
    specs = (P(None, D), P(D), P(), P(), P(), P())
    shapes = [jax.ShapeDtypeStruct(shape, dtype,
                                   sharding=NamedSharding(mesh, spec))
              for (shape, dtype), spec in zip((
                  ((d, rows), jnp.float32), ((rows,), jnp.float32),
                  ((2,), jnp.uint32), ((), jnp.int32), ((), jnp.float32),
                  ((k,), jnp.int32)), specs)]
    with meshlib.use_mesh_local(mesh):
        mapped = jax.shard_map(program, mesh=mesh, in_specs=specs,
                               out_specs=P(), check_vma=False)
        compiled = jax.jit(mapped).lower(*shapes).compile()
    clustering.forget_programs()
    hlo = compiled.as_text()
    for scope in ("kmeans.init", "kmeans.assign", "kmeans.update",
                  "kmeans.cost"):
        assert scope in hlo, scope
    sizes = {}
    for kind, dims in re.findall(r"\b(f32|bf16|s32|u32|s8|pred)\[([0-9,]+)\]",
                                 hlo):
        elements = int(np.prod([int(x) for x in dims.split(",")]))
        sizes[f"{kind}[{dims}]"] = elements
    assert f"f32[{d},{rows}]" in sizes                   # the table
    assert f"f32[{k},{block}]" in sizes                  # a block's tile
    slots = clustering._candidate_slots(k)
    assert max(sizes.values()) <= max(d * rows, (128 + 2 * slots) * block)
    assert not [s for s, n in sizes.items() if n >= rows * k]
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
    # the distance products: (centers, 6d) x (6d, block), bfloat16 in and
    # float32 out, the Lloyd step's and the cost pass's against k centers,
    # a seeding round's against its slots, the candidates' own against k
    distance = {(k, block): 0, (slots, block): 0, (k, 128 + 2 * slots): 0}
    for computation in re.split(r"\n(?=\S)", hlo):
        shape_of = dict(re.findall(
            r"(%[\w.\-]+) = (\w+\[[0-9,]*\])", computation))
        for out, lhs, rhs in re.findall(
                r"= (\w+\[[0-9,]*\])\S* convolution\((%[\w.\-]+), "
                r"(%[\w.\-]+)\)", computation):
            kind, dims = out.rstrip("]").split("[")
            dims = tuple(int(x) for x in dims.split(","))
            if dims not in distance:
                continue
            assert kind == "f32", out
            assert {shape_of[lhs], shape_of[rhs]} == {
                f"bf16[{dims[0]},{6 * d}]", f"bf16[{6 * d},{dims[1]}]"}, \
                (out, shape_of[lhs], shape_of[rhs])
            distance[dims] += 1
    assert distance == {(k, block): 2, (slots, block): 2,
                        (k, 128 + 2 * slots): 1}, distance
    # the block's side is built inside the product's fusion: nothing
    # writes a (6d, block) array out before it
    assert not re.search(
        rf"= bf16\[{6 * d},{block}\]\S* concatenate\(", hlo)
