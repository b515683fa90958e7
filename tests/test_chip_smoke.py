"""chip_smoke.py, rehearsed on the CPU (ISSUE 21).

The smoke itself runs only on a TPU (through the chip tool). Tier-1 holds
the two things a CPU can check: the `--rows` rehearsal drives every phase
end to end at a tiny size — here with the Pallas traversal kernel selected
explicitly, so it runs in interpret mode — and the no-argument invocation
refuses any platform but `tpu` before it builds any data."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, SMOKE, *args], env=env,
                          capture_output=True, text=True, timeout=600)


def test_rehearsal_passes_with_pallas_interpreted(tmp_path):
    proc = _run("--rows", "6000", "--conf", "sml.infer.kernel=pallas",
                "--out", str(tmp_path))
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    lines = proc.stdout.strip().splitlines()
    assert lines[0].startswith("REHEARSAL")  # a rehearsal says it is one
    assert "[FAIL]" not in proc.stdout
    result = json.loads(lines[-1])
    assert result["ok"] is True and result["rehearsal"] is True
    assert result["device"]["platform"] == "cpu"
    with open(tmp_path / "summary.json") as f:
        summary = json.load(f)
    assert summary["score_kernel"] == "pallas"
    assert summary["failures"] == []
    # the pallas answers were compared with the XLA traversal's
    assert summary["pallas_vs_xla_max_abs_diff"] is not None
    # the executable it ran was read back: the operand is outside the loop
    assert "[PASS] the one-hot operand is built outside the loop" in proc.stdout
    # conftest provisions 8 virtual devices: the multi-chip checks ran
    if result["device"]["count"] > 1:
        assert "contains an all-reduce" in proc.stdout


def test_no_argument_run_refuses_a_cpu_before_building_data():
    proc = _run()
    assert proc.returncode != 0
    assert "runs only on a TPU" in proc.stderr
    # it printed no result and never reached the data phase
    assert '"ok"' not in proc.stdout
    assert "data:" not in proc.stdout and "train:" not in proc.stdout


def test_conf_overrides_are_rehearsal_only():
    proc = _run("--conf", "sml.infer.kernel=xla")
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
