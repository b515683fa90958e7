"""The span tree of a fit (docs/OBSERVABILITY.md): one root `fit` span an
outermost `Estimator.fit`, its host phases as children that share its trace
id, the running totals a span name, and the `tree.*` scopes inside the
compiled tree program."""

import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from sml_tpu import obs
from sml_tpu.conf import GLOBAL_CONF
from sml_tpu.ml import Pipeline
from sml_tpu.ml import tree_impl
from sml_tpu.ml.feature import (Imputer, StandardScaler, StringIndexer,
                                VectorAssembler)
from sml_tpu.ml.regression import RandomForestRegressor
from sml_tpu.parallel import collectives as coll
from sml_tpu.xgboost import XgboostRegressor

#: direct children of the root, and of the spans that have children
PHASES = {"fit.collect", "fit.prep", "fit.featurize", "fit.quantize",
          "fit.stage", "program.tree_ensemble", "fit.baseline"}
QUANTIZE = {"fit.quantize.key", "fit.quantize.bins"}
SCOPES = {"tree.operand", "tree.hist", "tree.hist.allreduce", "tree.split",
          "tree.route", "tree.update"}


@pytest.fixture()
def recorder():
    GLOBAL_CONF.set("sml.obs.enabled", True)
    obs.reset()
    try:
        yield obs.RECORDER
    finally:
        GLOBAL_CONF.set("sml.obs.enabled", False)
        obs.reset()


def _frame(spark, seed, n=3000):
    """Rows no other test has fitted: the content-keyed bin and staging
    caches of the process miss, as they do for a table not seen."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 26]))
    pdf = pd.DataFrame({"a": rng.normal(size=n), "b": rng.normal(size=n),
                        "c": rng.choice(["x", "y", "z"], n)})
    pdf.loc[::7, "a"] = np.nan
    pdf["price"] = pdf["b"] * 2 + rng.normal(size=n)
    df = spark.createDataFrame(pdf)
    df.cache()
    df.count()
    return df


def _estimator(kind):
    if kind == "boosted":
        return XgboostRegressor(n_estimators=3, max_depth=2, max_bins=8,
                                labelCol="price", missing=0.0)
    return RandomForestRegressor(labelCol="price", maxBins=8, maxDepth=2,
                                 numTrees=3, seed=1)


def _pipeline(kind):
    return Pipeline(stages=[
        Imputer(strategy="median", inputCols=["a", "b"],
                outputCols=["a_i", "b_i"]),
        StringIndexer(inputCols=["c"], outputCols=["c_i"],
                      handleInvalid="skip"),
        VectorAssembler(inputCols=["a_i", "b_i", "c_i"],
                        outputCol="features"),
        _estimator(kind)])


def _spans(recorder):
    return [e for e in recorder.events() if e.kind == "span"]


def _children(spans, parent):
    return sorted((e for e in spans
                   if e.args.get("parent") == parent.args["span"]),
                  key=lambda e: e.ts)


def _assert_disjoint_inside(children, parent, slack=1e-6):
    for e in children:
        assert e.ts >= parent.ts - slack
        assert e.ts + e.dur <= parent.ts + parent.dur + slack
    for first, second in zip(children, children[1:]):
        assert first.ts + first.dur <= second.ts + slack, \
            f"{first.name} overlaps {second.name}"


@pytest.mark.parametrize("kind", ["boosted", "bagged"])
def test_one_span_tree_a_fit(spark, recorder, kind):
    df = _frame(spark, seed=1 if kind == "boosted" else 2)
    obs.reset()
    _pipeline(kind).fit(df)
    spans = _spans(recorder)

    roots = [e for e in spans if e.name == "fit"]
    assert len(roots) == 1, "stage fits fold into the outermost fit"
    root = roots[0]
    assert root.args["estimator"] == "Pipeline"
    assert root.args["rows"] == 3000
    assert root.args.get("parent") is None
    assert isinstance(root.args["trace"], int)

    children = _children(spans, root)
    assert {e.name for e in children} == PHASES
    _assert_disjoint_inside(children, root)
    by_name = {e.name: e for e in children}
    program = _children(spans, by_name["program.tree_ensemble"])
    assert [e.name for e in program] == [
        "fit.dispatch", "fit.device_wait", "fit.readback", "fit.unpack"]
    _assert_disjoint_inside(program, by_name["program.tree_ensemble"])
    quantize = _children(spans, by_name["fit.quantize"])
    assert {e.name for e in quantize} == QUANTIZE   # a table not seen
    assert by_name["fit.quantize"].args["hit"] is False
    assert by_name["fit.stage"].args["bytes"] > 0
    assert by_name["fit.stage"].args["hit"] is False
    assert by_name["fit.baseline"].args["trees"] == 3
    assert program[2].args["bytes"] > 0

    # one identifier a unit of work: every span of the fit carries it,
    # and a span id of its own
    named = [e for e in spans if e.name.startswith(("fit", "program."))]
    assert {e.args["trace"] for e in named} == {root.args["trace"]}
    assert len({e.args["span"] for e in named}) == len(named)

    # busy seconds and calls a span name equal the sums over the ring
    totals = recorder.counters()
    for name in {e.name for e in spans}:
        mine = [e.dur for e in spans if e.name == name]
        assert totals["span_n." + name] == len(mine)
        assert totals["span_s." + name] == pytest.approx(sum(mine), abs=1e-9)
    assert {k[7:] for k in totals if k.startswith("span_n.")} \
        == {e.name for e in spans}
    covered = sum(e.dur for e in children)
    assert covered <= root.dur + 1e-6


def test_a_fit_of_a_table_seen_before_says_hit(spark, recorder):
    df = _frame(spark, seed=3)
    _pipeline("bagged").fit(df)
    obs.reset()
    _pipeline("bagged").fit(df)
    by_name = {e.name: e for e in _spans(recorder)}
    assert by_name["fit.quantize"].args["hit"] is True
    assert "fit.quantize.bins" not in by_name
    assert by_name["fit.stage"].args["bytes"] == 0
    assert by_name["fit.stage"].args["hit"] is True


def test_the_generic_path_names_each_prep_stage(spark, recorder):
    """A chain the fused fit declines (a scaler between the assembler and
    the estimator) fits stage by stage: each prep stage's fit and
    transform is a `fit.prep`, and there is still one root."""
    df = _frame(spark, seed=4)
    stages = _pipeline("bagged").getStages()
    scaler = StandardScaler(inputCol="features", outputCol="scaled")
    tree = RandomForestRegressor(labelCol="price", featuresCol="scaled",
                                 maxBins=8, maxDepth=2, numTrees=2, seed=1)
    model = Pipeline(stages=stages[:3] + [scaler, tree]).fit(df)
    assert len(model.stages) == 5
    spans = _spans(recorder)
    roots = [e for e in spans if e.name == "fit"]
    assert len(roots) == 1
    children = _children(spans, roots[0])
    assert [e.name for e in children].count("fit.prep") == 4
    assert {"fit.featurize", "fit.quantize", "fit.stage",
            "program.tree_ensemble"} <= {e.name for e in children}
    _assert_disjoint_inside(children, roots[0])


def test_a_bare_estimator_is_its_own_root(spark, recorder):
    df = _frame(spark, seed=5)
    feats = _pipeline("bagged").getStages()[:3]
    featurized = Pipeline(stages=feats).fit(df).transform(df)
    obs.reset()
    _estimator("bagged").fit(featurized)
    roots = [e for e in _spans(recorder) if e.name == "fit"]
    assert len(roots) == 1
    assert roots[0].args["estimator"] == "RandomForestRegressor"


def test_spans_are_profiler_annotations(spark, recorder, tmp_path):
    """While the recorder is on a span is also a TraceAnnotation: any
    `jax.profiler` trace carries the fit's spans on its host plane."""
    from jax.profiler import ProfileData
    df = _frame(spark, seed=6)
    _pipeline("bagged").fit(df)     # compile outside the trace
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        _pipeline("bagged").fit(df)
    finally:
        jax.profiler.stop_trace()
    files = list(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    assert files
    names = {e.name for plane in ProfileData.from_file(str(files[0])).planes
             if not plane.name.startswith("/device:")
             for line in plane.lines for e in line.events}
    assert {"fit", "fit.stage", "fit.device_wait",
            "program.tree_ensemble"} <= names


def test_recorder_off_no_event_and_no_total(spark):
    assert not obs.RECORDER.enabled
    obs.reset()
    _pipeline("bagged").fit(_frame(spark, seed=7))
    assert obs.RECORDER.events() == []
    assert obs.RECORDER.counters() == {}


# ------------------------------------------- the host path seen from inside
STEPS = ["stage.key", "stage.pad", "stage.put"]


def _column_sums(xb, mask):
    return coll.psum(jnp.sum(xb * mask[:, None], axis=0))


def _staged_once(recorder, rows, seed):
    """One `run_data_parallel` over a float32 block of `rows` x 4 no test
    has staged: its `fit.stage` span and the `stage.*` spans inside it."""
    from sml_tpu.ml._staging import run_data_parallel
    x = np.random.default_rng(np.random.SeedSequence([seed, 38])) \
        .normal(size=(rows, 4)).astype(np.float32)
    obs.reset()
    got = run_data_parallel(_column_sums, x)
    np.testing.assert_allclose(got, x.sum(axis=0, dtype=np.float64),
                               rtol=1e-3, atol=0.5)
    spans = _spans(recorder)
    stage, = [e for e in spans if e.name == "fit.stage"]
    steps = sorted((e for e in spans if e.name in STEPS), key=lambda e: e.ts)
    return stage, steps


def test_the_steps_of_a_large_staging_sum_to_fit_stage(recorder):
    """An array over `_SPAN_BYTES` (64 MB, and its 16 MB mask): a key, a
    pad and a put each, disjoint inside `fit.stage` on its thread, and
    together all of it but the calls between them (0.1 ms a span once 64 MB
    have gone through the caches). The best of three tables: a worker of a
    loaded test host can lose its core between two spans."""
    short = []
    for seed in (1, 2, 3):
        rows = 4_000_000 + seed         # a mask of its own each time
        stage, steps = _staged_once(recorder, rows, seed)
        assert [e.name for e in steps] == STEPS + ["stage.pad", "stage.put"]
        assert {e.tid for e in steps} == {stage.tid}
        _assert_disjoint_inside(steps, stage)
        block = steps[:3]
        assert block[0].args["bytes"] == rows * 16
        assert block[0].args["hit"] is False and "copied" not in block[0].args
        # padded to the bucketed row count, and that is what is put
        assert block[1].args["bytes"] == block[2].args["bytes"] > rows * 16
        assert block[2].args["bytes"] % (4 * 4 * 8) == 0
        assert steps[3].args["bytes"] == block[2].args["bytes"] // 4
        # this backend may take the host buffer for the placed array, so
        # neither pad is pooled: fresh pages both (tests/test_staging_pad_pool)
        assert block[1].args["warm"] is False is steps[3].args["warm"]
        short.append(1.0 - sum(e.dur for e in steps) / stage.dur)
        if short[-1] <= 0.02:
            break
    assert min(short) <= 0.02, short
    totals = recorder.counters()
    assert sum(totals["span_s." + n] for n in STEPS) == \
        pytest.approx(sum(e.dur for e in steps), abs=1e-9)
    assert totals["staging.pad_fresh"] == 2.0      # the block's, the mask's
    assert "staging.pad_warm" not in totals


def test_a_small_staging_opens_no_step(recorder):
    stage, steps = _staged_once(recorder, 20_000, seed=4)   # 320 KB
    assert steps == [] and stage.dur > 0
    assert not [k for k in recorder.counters() if ".stage." in k
                and not k.endswith(".fit.stage")]
    assert not [k for k in recorder.counters() if "staging.pad_" in k]


def test_a_staging_cache_hit_opens_the_key_alone(recorder):
    from sml_tpu.ml import _staging
    x = np.random.default_rng(np.random.SeedSequence([5, 38])) \
        .normal(size=(300_001, 2)).astype(np.float32)      # 2.4 MB
    obs.reset()
    first = _staging.stage_rows_cached(x)
    assert [e.name for e in _spans(recorder)] == STEPS
    obs.reset()
    assert _staging.stage_rows_cached(x) is first
    key, = _spans(recorder)
    assert key.name == "stage.key" and key.args["hit"] is True
    assert key.args["bytes"] == x.nbytes and "copied" not in key.args
    # an array the staging boundary has to copy says so
    obs.reset()
    _staging.stage_rows_cached(x[::2])
    assert _spans(recorder)[0].args["copied"] is True
    # a bin matrix takes the bin cache through the same three steps
    bins = np.random.default_rng(6).integers(
        0, 200, size=(300_001, 5), dtype=np.uint8)
    obs.reset()
    _staging.stage_bins_cached(bins)
    _staging.stage_bins_cached(bins)
    assert [e.name for e in _spans(recorder)] == STEPS + ["stage.key"]


def test_the_featurize_children_lie_inside_a_featurize_span(spark, recorder):
    """`fit.featurize` keeps its name, its notes and its extent at every
    site; what each site does is a child of it on the same thread, and the
    root's children are what they were (`PHASES`)."""
    df = _frame(spark, seed=8)
    obs.reset()
    _pipeline("boosted").fit(df)
    spans = _spans(recorder)
    by_id = {e.args["span"]: e for e in spans}
    inside = [e for e in spans if e.name.startswith("fit.featurize.")]
    assert sorted(e.name for e in inside) == [
        "fit.featurize.extract",
        "fit.featurize.plan.block", "fit.featurize.plan.jobs"]
    for e in inside:
        parent = by_id[e.args["parent"]]
        assert parent.name == "fit.featurize" and parent.tid == e.tid
        _assert_disjoint_inside([e], parent)
    jobs, = [e for e in inside if e.name.endswith("plan.jobs")]
    assert 0.0 < jobs.args["longest_s"] <= jobs.dur
    block, = [e for e in inside if e.name.endswith("plan.block")]
    assert block.args["compact"] is False
    plan = by_id[jobs.args["parent"]]
    assert plan is by_id[block.args["parent"]]
    assert plan.args["columns"] == 3 and plan.args["rows"] == 3000
    _assert_disjoint_inside([jobs, block], plan)
    root, = [e for e in spans if e.name == "fit"]
    assert {e.name for e in _children(spans, root)} == PHASES
    # the phases the eight `fit.host.*` read still fit in the root
    totals = recorder.counters()
    phases = ("fit.collect", "fit.prep", "fit.featurize", "fit.quantize",
              "fit.stage", "fit.dispatch", "fit.device_wait", "fit.readback",
              "fit.unpack", "fit.baseline")
    assert sum(totals["span_s." + n] for n in phases) <= totals["span_s.fit"]
    assert sum(totals["span_s." + e.name] for e in inside) <= \
        totals["span_s.fit.featurize"]


def test_cpu_seconds_grow_only_for_the_spans_that_ask(spark, recorder):
    """The root, the spans the eight phases are made of and the three parts
    of the remainder that have spans (PR 52): not their children, not the
    staging steps."""
    from sml_tpu.obs.taxonomy import CPU_SPANS, FIT_PHASES
    assert CPU_SPANS == {"fit", "fit.summary", "fit.cv.folds", "fit.cv.eval"} \
        | {n for names in FIT_PHASES.values() for n in names}
    df = _frame(spark, seed=9)
    obs.reset()
    _pipeline("bagged").fit(df)
    spans = _spans(recorder)
    totals = recorder.counters()
    assert {k for k in totals if k.startswith("span_cpu_s.")} == \
        {"span_cpu_s." + e.name for e in spans if e.name in CPU_SPANS}
    assert {"fit.quantize.stats", "fit.featurize.plan.jobs"} \
        <= {e.name for e in spans} - CPU_SPANS
    for e in spans:
        assert ("cpu_s" in e.args) == (e.name in CPU_SPANS), e.name
    mine = [e.args["cpu_s"] for e in spans if e.name == "fit.featurize"]
    assert len(mine) == 2 and min(mine) >= 0.0     # the plan, `_extract`
    assert totals["span_cpu_s.fit.featurize"] == pytest.approx(
        sum(mine), abs=1e-9)


def test_cpu_seconds_are_the_process_s_every_thread(recorder):
    """Process-wide on purpose: what a pooled phase's workers burn while
    the span is open is in its `cpu_s`, more than the span's own wall."""
    import threading
    from sml_tpu.utils.profiler import PROFILER

    def spin(seconds=0.2):
        end = time.thread_time() + seconds
        while time.thread_time() < end:
            pass
    workers = [threading.Thread(target=spin) for _ in range(3)]
    with PROFILER.span("fit.featurize"):
        for w in workers:
            w.start()
        for w in workers:
            w.join()
    with PROFILER.span("stage.put"):
        spin(0.01)
    totals = recorder.counters()
    assert totals["span_cpu_s.fit.featurize"] >= 0.55    # 3 x 0.2 s
    assert "span_cpu_s.stage.put" not in totals


def test_recorder_off_reads_no_cpu_seconds(spark, monkeypatch):
    """Off, a span that asks still early-outs: no event, no total, and not
    one `process_time` call, the profiler on or not."""
    from sml_tpu.utils import profiler
    calls = []
    real = time.process_time
    monkeypatch.setattr(profiler.time, "process_time",
                        lambda: calls.append(1) or real())
    assert not obs.RECORDER.enabled
    obs.reset()
    GLOBAL_CONF.set("sml.profiler.enabled", True)
    try:
        with profiler.PROFILER.span("fit.featurize"):
            pass
        _pipeline("bagged").fit(_frame(spark, seed=10))
    finally:
        GLOBAL_CONF.set("sml.profiler.enabled", False)
        profiler.PROFILER.reset()
    assert calls == []
    assert obs.RECORDER.events() == [] and obs.RECORDER.counters() == {}
    GLOBAL_CONF.set("sml.obs.enabled", True)
    try:
        with profiler.PROFILER.span("fit.featurize"):
            pass
        with profiler.PROFILER.span("stage.put"):
            pass
        assert len(calls) == 2
    finally:
        GLOBAL_CONF.set("sml.obs.enabled", False)
        obs.reset()


def test_reset_keeps_the_two_facts_of_the_process(recorder):
    """`process.*` are facts of the process, not of an epoch: an enabled
    recorder's counters carry them from its first snapshot on, whatever was
    reset; they are no event of the timeline."""
    first = recorder.counters()
    assert first["process.import_s"] > 0.0
    assert first["process.age_at_import_s"] >= 0.0   # this host has /proc
    recorder.counter("staging.cache_hit")
    obs.reset()
    assert recorder.counters() == {
        "process.import_s": first["process.import_s"],
        "process.age_at_import_s": first["process.age_at_import_s"]}
    assert recorder.events() == []
    GLOBAL_CONF.set("sml.obs.enabled", False)
    obs.reset()
    assert recorder.counters() == {}                 # off: no total at all
    GLOBAL_CONF.set("sml.obs.enabled", True)         # on again: seeded
    assert set(recorder.counters()) == {"process.import_s",
                                        "process.age_at_import_s"}


# ------------------------------------------------------------ named scopes
def _lowered(boosting: bool):
    spec = tree_impl.TreeSpec(
        max_depth=2, n_bins=8, n_features=3, feature_k=3 if boosting else 2,
        min_instances=1, min_info_gain=0.0, reg_lambda=0.0, gamma=0.0)
    es = tree_impl.EnsembleSpec(
        tree=spec, n_trees=2, loss="squared", boosting=boosting,
        bootstrap=not boosting, subsample=1.0, step_size=0.1)
    n = 64
    return tree_impl._ensemble_compiled(es).lower(
        jnp.zeros((n, 3), jnp.uint8), jnp.zeros((n,), jnp.float32),
        jnp.ones((n,), jnp.float32),
        jax.random.key_data(jax.random.PRNGKey(0)))


@pytest.mark.parametrize("boosting", [True, False],
                         ids=["boosted", "bagged"])
def test_the_ensemble_program_carries_every_scope(boosting):
    lowered = _lowered(boosting)
    text = lowered.as_text(debug_info=True)
    assert "@jit_tree_ensemble" in text      # the program has a name
    stacks = set(re.findall(r'"([^"]*tree\.[^"]*)"', text))
    found = {s for stack in stacks
             for s in re.findall(r"tree\.[a-z_.]+", stack)}
    assert found == SCOPES
    reduces = [s for s in stacks if "tree.hist.allreduce" in s]
    assert reduces
    assert all("tree.hist/tree.hist.allreduce/" in s for s in reduces), \
        "the all-reduce of the histograms lies inside tree.hist"
    # the operand, the dots and the row routing are where they should be
    assert any("tree.operand/while/body" in s for s in stacks)
    assert any(s.endswith("tree.hist/dot_general") for s in stacks)
    assert any("tree.route/" in s for s in stacks)
    assert any(s.endswith("tree.operand/optimization_barrier")
               for s in stacks)
    # the operand is built before the loop over rounds, never inside it:
    # in the executable every op_name is whole, from the program down
    # (for the chip's compiler: tests/test_tree_operand.py)
    hlo = lowered.compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    assert any("while/body" in s and "tree.hist" in s for s in names)
    # (the operand's own walk over its row blocks is the one loop its
    # operations sit in: `tree.operand/while/body`, never `while/body/...
    # tree.operand`)
    assert not [s for s in names
                if re.search(r"while/body.*tree\.operand", s)]
    assert tree_impl.ops_in_loop_bodies(hlo, "tree.operand") == []
    assert tree_impl.ops_in_loop_bodies(hlo, "tree.hist")
