"""The span tree of a fit (docs/OBSERVABILITY.md): one root `fit` span an
outermost `Estimator.fit`, its host phases as children that share its trace
id, the running totals a span name, and the `tree.*` scopes inside the
compiled tree program."""

import re

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
    assert any(re.search(r"tree\.operand/.*_one_hot", s) for s in stacks)
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
    assert not [s for s in names if "tree.operand" in s and "while/body" in s]
    assert tree_impl.ops_in_loop_bodies(hlo, "tree.operand") == []
    assert tree_impl.ops_in_loop_bodies(hlo, "tree.hist")
