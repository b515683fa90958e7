"""Whole runs of the harness on the CPU at a tiny size, through the same
`runner.run` the command calls, with only the look for a chip skipped:
sound runs come out correct, a run with the timed path broken underneath
comes out NOT correct, and without a TPU the command measures nothing."""

import json
import os
import subprocess
import sys
import time
import types

import numpy as np
import pytest

import bench_tiny
from benchmark.harness import device, program, runner

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return bench_tiny.make_tiny_root(tmp_path_factory.mktemp("run"))


def drive(tiny, cell, trace=False, seed=2**31 + 77, stand_in=None):
    root, bench = tiny
    return runner.run(root, cell, seed, 1.5, trace, time.perf_counter(),
                      require_chip=False, bench=bench, program=stand_in)


def stand_in(**replaced):
    """The program adapter with some of its functions replaced."""
    shim = types.SimpleNamespace(**{k: getattr(program, k)
                                    for k in dir(program)
                                    if not k.startswith("__")})
    for name, fn in replaced.items():
        setattr(shim, name, fn)
    return shim


@pytest.mark.parametrize("cell", ["tiny_xgb.tiny_fit", "tiny_rf.tiny_fit"])
def test_sound_run_is_correct_and_reports_its_metrics(tiny, cell, capsys):
    line = drive(tiny, cell)
    metric = "fit_s"
    out = capsys.readouterr().out
    assert set(line) == RESULT_KEYS
    assert line["correct"] is True, out
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["metrics"][metric]["value"] > 0
    assert line["metrics"]["setup_s"]["value"] > 0
    assert line["device"]["platform"] == "cpu"     # says where it ran
    # one line a comparison: name, verdict, observed, limit
    checks = [ln for ln in out.splitlines() if ln.startswith("check ")]
    assert len(checks) >= 7
    assert all(" observed=" in c and " limit=" in c for c in checks)
    assert any("all.compile_requests_in_window: PASS" in c for c in checks)
    json.dumps(line)
    # the traffic file's split, not the 80/20 of the cells it was added to
    assert " on 8" in out and "warm fit 1" not in out


@pytest.mark.parametrize("cell,wanted", [
    ("tiny_xgb.tiny_fit", {"staging.h2d_bytes_per_fit", "fit.device_busy_s",
                           "compile.backend_s", "compile.in_window"}),
])
def test_traced_run_reports_the_layers(tiny, cell, wanted):
    # another seed than the sound runs above: the same process has fitted
    # THAT table, and its bins would come from the engine's cache
    line = drive(tiny, cell, trace=True, seed=2**31 + 178)
    assert line["correct"] is True
    # no device plane on the CPU: the trace-fed readers find nothing to read
    # and are left out; the counter- and span-fed ones report
    assert set(line["metrics"]) == wanted - {"fit.device_busy_s"}
    assert "setup_s" not in line["metrics"]
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    if "staging.h2d_bytes_per_fit" in wanted:
        # a table not fitted before is staged again at every fit
        assert line["metrics"]["staging.h2d_bytes_per_fit"]["value"] > 50_000


def _scaled_leaves(monkeypatch):
    from sml_tpu.ml import tree_impl
    real = tree_impl._unpack_trees

    def wrong(packs):
        return [t._replace(leaf_value=t.leaf_value * np.float32(1.03))
                for t in real(packs)]
    monkeypatch.setattr(tree_impl, "_unpack_trees", wrong)


@pytest.mark.parametrize("cell", ["tiny_xgb.tiny_fit", "tiny_rf.tiny_fit"])
def test_a_fit_that_returns_wrong_leaves_is_not_correct(tiny, cell,
                                                        monkeypatch, capsys):
    _scaled_leaves(monkeypatch)
    line = drive(tiny, cell)
    out = capsys.readouterr().out
    assert line["correct"] is False
    assert "check fit.leaf_value_err.median: FAIL" in out
    # the wrong model still scores consistently: that check alone would pass
    assert "check fit.predictions_vs_descent.rel_gap_max: PASS" in out


def test_an_altered_prediction_is_not_correct(tiny, capsys):
    def predictions(model, df):
        out = program.predictions(model, df).copy()
        out[::7] += 1e-3      # the check draws a sample: alter enough
        return out
    line = drive(tiny, "tiny_xgb.tiny_fit",
                 stand_in=stand_in(predictions=predictions))
    assert line["correct"] is False
    assert "check fit.predictions_vs_descent.rel_gap_max: FAIL" in \
        capsys.readouterr().out


def test_a_fit_on_part_of_the_rows_is_not_correct(tiny, capsys):
    """The timed path broken underneath: the pipeline is fitted on the
    first half of the frame it was handed."""
    class Halved:
        def __init__(self, pipeline):
            self._pipeline = pipeline

        def fit(self, frame):
            return self._pipeline.fit(frame.limit(frame.count() // 2))

    line = drive(tiny, "tiny_rf.tiny_fit", stand_in=stand_in(
        build_pipeline=lambda config: Halved(program.build_pipeline(config))))
    assert line["correct"] is False
    assert "check fit.cover_gap.max: FAIL" in capsys.readouterr().out


def test_without_a_tpu_nothing_is_measured(tiny, capsys):
    root, _ = tiny
    with pytest.raises(device.NoChip):
        device.require_tpu(1)
    rc = runner.main(root, "tiny_xgb.tiny_fit", 1, 1.0, False, time.perf_counter())
    captured = capsys.readouterr()
    assert rc == 2
    assert "only on a TPU" in captured.err
    assert not any(ln.startswith("{") for ln in captured.out.splitlines())


def test_the_command_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(bench_tiny.REPO, "benchmark", "run.py"),
         "--workload", "ml11_xgb.fit", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=300, cwd=bench_tiny.REPO)
    assert done.returncode == 2
    assert "only on a TPU" in done.stderr
    assert not any(ln.startswith("{") for ln in done.stdout.splitlines())
