"""The readers of the fit's split: `fit.host.*` from the program's span totals
between the window's two counter snapshots, `fit.device.*` from the
`jax.named_scope` of each device operation inside `bench.fit`. On synthetic
traces and readings, and on the counters of a whole tiny run on the CPU."""

import json
import os
import time
import types

import pytest

import bench_tiny
from benchmark.harness import program, runner, spec, xplane
from benchmark.layer_metrics import _fit_scopes, _fit_spans

REPO = bench_tiny.REPO
BENCH = spec.load_benchmark(REPO)
CELLS = ["ml11_xgb.fit", "ml07_rf.fit"]
HOST = sorted(_fit_spans.PHASES) + ["fit.host.unattributed_s"]
DEVICE = ["fit.device.operand_s", "fit.device.hist_s", "fit.device.split_s",
          "fit.device.route_s", "fit.device.update_s",
          "fit.device.unscoped_s"]
ACCEPTED = ["staging.h2d_bytes_per_fit", "fit.device_busy_s",
            "compile.backend_s", "compile.in_window"]


def reader(name):
    return runner.load_module(os.path.join(
        REPO, "benchmark", "layer_metrics", name + ".py"), "bench_metric")


def reading(trace=None, fits=2, start=None, end=None, cell="no.such_cell"):
    return runner.Reading(
        cell=cell, config={}, traffic={}, seconds=1.0,
        facts={"fits": fits} if fits is not None else {},
        counters_start=start or {}, counters_end=end or {}, compiles=None,
        device={"platform": "tpu"}, program=None, trace=trace)


# --------------------------------------------------------- BENCHMARK.json
def test_the_enlarged_benchmark_is_valid_and_only_appended_to():
    assert spec.validate(REPO, BENCH) == []
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[:4] == ACCEPTED, "what was there stays first, as it was"
    assert sorted(names[4:]) == sorted(HOST + DEVICE)
    layers = {"fit.host.featurize_s": "featurize",
              "fit.host.quantize_s": "staging and quantize",
              "fit.host.stage_s": "staging and quantize",
              "fit.host.observe_s": "observability",
              "fit.host.unattributed_s": "pipeline fit"}
    for m in BENCH["per_layer"][4:]:
        assert m["workloads"] == CELLS and m["moves"] == "fit_s"
        assert (m["unit"], m["better"]) == ("s", "lower")
        assert m["source"] == ("program_span" if ".host." in m["name"]
                               else "device_trace")
        assert m["layer"] == layers.get(m["name"], "tree fit programs")
        assert os.path.isfile(os.path.join(
            REPO, "benchmark", "layer_metrics", m["name"] + ".py"))


# ------------------------------------------------------------- fit.host.*
def _device_plane():
    return xplane.Trace([[("%copy.1 = f32[8] copy(f32[8] %p)", 10.0, 20.0)]],
                        [("bench.window", 0.0, 100.0)])


def _totals(scale=1.0):
    spans = {"fit": 10.0, "fit.collect": 0.5, "fit.prep": 1.0,
             "fit.featurize": 1.5, "fit.quantize": 2.0,
             "fit.quantize.key": 0.25, "fit.quantize.bins": 1.75,
             "fit.stage": 0.25, "program.tree_ensemble": 4.0,
             "fit.dispatch": 0.125, "fit.device_wait": 3.0,
             "fit.readback": 0.5, "fit.unpack": 0.25, "fit.baseline": 0.5,
             "materialize.randomSplit": 7.0}
    out = {"span_s." + k: v * scale for k, v in spans.items()}
    out.update({"span_n." + k: 4.0 * scale for k in spans})
    return out


def test_host_phases_are_span_seconds_between_the_snapshots_over_fits():
    run = reading(_device_plane(), fits=2, start=_totals(1.0),
                  end=_totals(3.0))
    got = {name: reader(name).read(run) for name in HOST}
    assert got == {
        "fit.host.featurize_s": 3.0, "fit.host.quantize_s": 2.0,
        "fit.host.stage_s": 0.25, "fit.host.dispatch_s": 0.125,
        "fit.host.device_wait_s": 3.0, "fit.host.readback_s": 0.75,
        "fit.host.observe_s": 0.5,
        # 10 - (3 + 2 + .25 + .125 + 3 + .75 + .5): the program span's own
        # remainder (4 - 3.875) is part of it, the untimed split is not
        "fit.host.unattributed_s": 0.375}
    assert sum(got.values()) == 10.0        # they sum to the root span


@pytest.mark.parametrize("name", HOST)
def test_host_phases_find_nothing_to_read(name):
    read = reader(name).read
    end = _totals(3.0)
    assert read(reading(_device_plane(), fits=0, end=end)) is None
    assert read(reading(_device_plane(), fits=None, end=end)) is None
    assert read(reading(None, end=end)) is None           # untraced
    assert read(reading(xplane.Trace([], []), end=end)) is None  # no device
    # the parent commit: a recorder that keeps no totals a span name
    counters = {"staging.h2d_bytes": 1e6, "tree.fit_dispatch": 9.0}
    assert read(reading(_device_plane(), end=counters)) is None


# ----------------------------------------------------------- fit.device.*
def _hlo(name, scope=None, kind="fusion"):
    meta = f', metadata={{op_name="jit(tree_ensemble)/jit(main)/while/' \
           f'body/{scope}/mul" source_file="tree_impl.py"}}' if scope else ""
    return f"%{name} = f32[8]{{0}} {kind}(f32[8]{{0}} %p), kind=kLoop{meta}"


def _scoped_trace():
    """Two fits. Fit 1 [100, 1100): the operand fusion [100, 300); a while
    [300, 1000) over a dot [300, 600) under tree.hist, the all-reduce
    [600, 650) under tree.hist/tree.hist.allreduce, a fusion [650, 800)
    whose metadata names tree.split though tree.route was fused into it, a
    route fusion [800, 900), an update [900, 950) and an operation with no
    metadata [950, 1000). Fit 2 [2000, 3000): one dot [2000, 2400). An
    operation outside every fit [1500, 1600) under tree.hist."""
    ops = [(_hlo("convert_fusion.1", "tree.operand/jit(_one_hot)"), 100, 300),
           ("%while.7 = (f32[8]) while((f32[8]) %t), body=%b", 300, 1000),
           (_hlo("fusion.2", "tree.hist"), 300, 600),
           (_hlo("all-reduce.3", "tree.hist/tree.hist.allreduce",
                 "all-reduce"), 600, 650),
           (_hlo("fusion.4", "tree.split"), 650, 800),
           (_hlo("fusion.5", "tree.route"), 800, 900),
           (_hlo("fusion.6", "tree.update"), 900, 950),
           (_hlo("copy.8", kind="copy"), 950, 1000),
           (_hlo("fusion.9", "tree.hist"), 1500, 1600),
           (_hlo("fusion.2", "tree.hist"), 2000, 2400)]
    notes = [("bench.window", 0.0, 4000.0), ("bench.fit", 100.0, 1100.0),
             ("bench.split", 1100.0, 2000.0), ("bench.fit", 2000.0, 3000.0)]
    return xplane.Trace([[(n, float(a), float(b)) for n, a, b in ops]],
                        notes)


def test_device_seconds_by_the_scope_each_operation_names():
    run = reading(_scoped_trace(), fits=2)
    got = {name: reader(name).read(run) for name in DEVICE}
    per_fit = 1e-9 / 2
    assert got == pytest.approx({
        "fit.device.operand_s": 200 * per_fit,
        # the dots of both fits and the all-reduce nested in tree.hist; the
        # operation between the fits is not in a timed fit
        "fit.device.hist_s": (300 + 400 + 50) * per_fit,
        # a fusion under two scopes: the one its own metadata names
        "fit.device.split_s": 150 * per_fit,
        "fit.device.route_s": 100 * per_fit,
        "fit.device.update_s": 50 * per_fit,
        # the copy without metadata; the while's own time is all its body's
        "fit.device.unscoped_s": 50 * per_fit})
    busy = reader("fit.device_busy_s").read(run)
    assert sum(got.values()) == pytest.approx(busy)   # with it to busy


def test_scope_of_a_name_stack():
    scope = _fit_scopes.scope_in
    assert scope("jit(f)/jit(main)/while/body/tree.hist/dot_general") \
        == "tree.hist"
    assert scope("jit(f)/tree.hist/tree.hist.allreduce/psum") == "tree.hist"
    assert scope('op_name="tree.update/add"') == "tree.update"
    assert scope("jit(f)/vmap(tree.split)/cumsum") == "tree.split"
    assert scope("jit(f)/subtree.hist/add") is None
    assert scope("%fusion.4 = f32[8,128]{1,0} fusion(...)") is None


def test_scope_from_the_statistics_kept_with_an_operation(tmp_path):
    """Operations named by bare HLO text, their `op_name` in a statistic of
    the operation's metadata (which ProfileData does not show): read from the
    file's wire format. One operation has it on the event instead."""
    from jax.profiler import ProfileData
    text = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[2] fusion()"
    stats { metadata_id: 1 str_value: "jit(f)/while/body/tree.hist/dot" }
    stats { metadata_id: 3 uint64_value: 7 } } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2 = f32[2] fusion()"
    stats { metadata_id: 1 ref_value: 2 } } }
  event_metadata { key: 3 value { id: 3 name: "%copy.3 = f32[2] copy()" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 2 value { id: 2 name: "jit(f)/tree.route/select_n" } }
  stat_metadata { key: 3 value { id: 3 name: "flops" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 9000000 }
    events { metadata_id: 2 offset_ps: 500000 duration_ps: 8000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.fit" } }
  event_metadata { key: 3 value { id: 3 name: "%host.3 = f32[2] fusion()"
    stats { metadata_id: 1 str_value: "jit(f)/tree.split/x" } } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
}
'''
    cell = "scope_statistics.test_cell"
    trace_dir = os.path.join(REPO, runner.WORK_DIR, cell, "trace", "plugins",
                             "profile", "t")
    os.makedirs(trace_dir, exist_ok=True)
    path = os.path.join(trace_dir, "host.xplane.pb")
    try:
        with open(path, "wb") as f:
            f.write(ProfileData.text_proto_to_serialized_xspace(text))
        meta = _fit_scopes.operation_metadata(path)
        assert meta["%fusion.1 = f32[2] fusion()"] == {
            "tf_op": "jit(f)/while/body/tree.hist/dot", "flops": 7}
        assert set(meta) == {"%fusion.1 = f32[2] fusion()",
                             "%fusion.2 = f32[2] fusion()",
                             "%copy.3 = f32[2] copy()"}   # device planes only
        assert _fit_scopes.scopes_of_file(path) == {
            "%fusion.1 = f32[2] fusion()": "tree.hist",
            "%fusion.2 = f32[2] fusion()": "tree.route"}
        run = reading(xplane.Trace.from_file(path), fits=1, cell=cell)
        assert reader("fit.device.hist_s").read(run) == pytest.approx(1e-6)
        assert reader("fit.device.route_s").read(run) == pytest.approx(1e-6)
        assert reader("fit.device.split_s").read(run) == 0.0
        assert reader("fit.device.unscoped_s").read(run) \
            == pytest.approx(1e-6)
    finally:
        import shutil
        shutil.rmtree(os.path.join(REPO, runner.WORK_DIR, cell))


@pytest.mark.parametrize("name", DEVICE)
def test_device_scopes_find_nothing_to_read(name):
    read = reader(name).read
    assert read(reading(None)) is None
    assert read(reading(xplane.Trace([], []))) is None
    assert read(reading(_scoped_trace(), fits=0)) is None
    # the parent commit's program: not one operation names a scope
    bare = xplane.Trace(
        [[(_hlo("fusion.2"), 100.0, 300.0)]],
        [("bench.window", 0.0, 1000.0), ("bench.fit", 50.0, 900.0)])
    assert read(reading(bare, fits=1)) is None


# ------------------------------------------- a whole tiny run on the CPU
def test_a_tiny_run_gives_every_host_phase_a_number(tmp_path):
    """`bench_tiny`'s cell, traced, through `runner.run`: its line leaves
    the split out (the CPU has no device plane to hold it against, as for
    `fit.device_busy_s`); the run's own counter snapshots, read beside a
    device plane, give every `fit.host.*` reader a number, and the numbers
    sum to the fit's root span and stay under the window."""
    root, bench = bench_tiny.make_tiny_root(tmp_path)
    snapshots = []

    def counters():
        snapshots.append(program.counters())
        return snapshots[-1]

    shim = types.SimpleNamespace(**{k: getattr(program, k)
                                    for k in dir(program)
                                    if not k.startswith("__")})
    shim.counters = counters
    line = runner.run(root, "tiny_rf.tiny_fit", 2**31 + 2626, 1.5, True,
                      time.perf_counter(), require_chip=False, bench=bench,
                      program=shim)
    assert line["correct"] is True
    assert not [m for m in line["metrics"] if m.startswith("fit.host.")]
    json.dumps(line)
    start, end = snapshots
    run = reading(_device_plane(), fits=line["attempted"], start=start,
                  end=end)
    got = {name: reader(name).read(run) for name in HOST}
    assert all(isinstance(v, float) and v >= 0.0 for v in got.values()), got
    for name in ("fit.host.featurize_s", "fit.host.quantize_s",
                 "fit.host.stage_s", "fit.host.device_wait_s",
                 "fit.host.observe_s"):
        assert got[name] > 0.0, name
    fits = line["attempted"]
    assert end["span_n.fit"] - start.get("span_n.fit", 0.0) == fits
    root_s = (end["span_s.fit"] - start.get("span_s.fit", 0.0)) / fits
    assert sum(got.values()) == pytest.approx(root_s)
    assert sum(got.values()) * fits < line["device"]["window_s"]
    assert got["fit.host.unattributed_s"] < 0.2 * root_s
