"""CI enforcement (PR 3): the committed tree must pass graftlint, and the
linter must run jax-free from a cold interpreter and keep its exit-code
contract."""

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
RUNNER = os.path.join(REPO, "scripts", "graftlint.py")


def test_graftlint_clean_and_jax_free():
    """One subprocess proves both acceptance criteria: exit 0 on the
    repo with >=6 active rules, and no jax import anywhere in the lint
    path (the probe would raise before printing)."""
    probe = (
        "import importlib.util, json, sys\n"
        f"spec = importlib.util.spec_from_file_location('_g', {RUNNER!r})\n"
        "m = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(m)\n"
        "rc = m.main(['--json'])\n"
        "assert 'jax' not in sys.modules, 'linter imported jax'\n"
        "assert 'sml_tpu' not in sys.modules, 'linter imported sml_tpu'\n"
        "assert 'graftlint.traced' in sys.modules, "
        "'traced-region core not loaded standalone'\n"
        "assert 'graftlint.threads' in sys.modules, "
        "'thread-role core not loaded standalone'\n"
        "sys.exit(rc)\n")
    out = subprocess.run([sys.executable, "-c", probe], cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    payload = json.loads(out.stdout)
    assert payload["clean"] is True
    assert len(payload["rules"]) >= 6
    assert payload["violations"] == []
    # the extended machine surface: per-rule wall time for every active
    # rule, and per-violation status lists (active list is empty on the
    # clean tree; the suppressed list carries pragma/baseline entries)
    assert set(payload["rule_times"]) == set(payload["rules"])
    assert all(t >= 0 for t in payload["rule_times"].values())
    assert payload["suppressed_violations"], "suppression detail missing"
    assert {sv["status"] for sv in payload["suppressed_violations"]} \
        <= {"pragma", "baseline"}
    n_pragma = sum(1 for sv in payload["suppressed_violations"]
                   if sv["status"] == "pragma")
    assert n_pragma == payload["suppressed"]["pragma"]


def test_single_rule_run_is_clean_on_committed_tree():
    """`--rule NAME` must exit 0 on the clean tree: suppressions owned
    by the rules that did NOT run are out of scope (review finding)."""
    out = subprocess.run([sys.executable, RUNNER, "--rule",
                          "conf-key-registry"], cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr


def test_update_baseline_preserves_reviewed_entries(tmp_path):
    """--update-baseline on the clean tree must re-emit the reviewed
    timeseries entries (reasons intact), not erase them because the old
    baseline already suppressed them (review finding)."""
    for d in ("sml_tpu", "scripts"):
        shutil.copytree(os.path.join(REPO, d), tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, ".graftlint-baseline.json"), tmp_path)
    os.makedirs(tmp_path / "tests")
    out = subprocess.run(
        [sys.executable, str(tmp_path / "scripts" / "graftlint.py"),
         "--update-baseline", "--root", str(tmp_path)],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    with open(tmp_path / ".graftlint-baseline.json") as fh:
        entries = json.load(fh)["entries"]
    assert len(entries) == 3, entries
    assert all(e["file"] == "sml_tpu/timeseries.py" for e in entries)
    assert all(not e["reason"].startswith("TODO") for e in entries)
    # and the refreshed baseline still passes the lint
    out2 = subprocess.run(
        [sys.executable, str(tmp_path / "scripts" / "graftlint.py"),
         "--root", str(tmp_path)],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert out2.returncode == 0, out2.stdout + out2.stderr


def test_graftlint_json_reports_suppressions():
    out = subprocess.run([sys.executable, RUNNER, "--json"], cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    payload = json.loads(out.stdout)
    # every carried suppression is visible in the machine output
    assert payload["suppressed"]["baseline"] >= 1
    assert payload["suppressed"]["pragma"] >= 1


def test_exit_code_contract(tmp_path, capsys):
    """The documented contract (scripts/graftlint.py docstring): 0
    clean, 1 violations, 2 usage/internal error — relied on by CI. All
    three legs drive main() itself."""
    import importlib.util
    spec = importlib.util.spec_from_file_location("_g_contract", RUNNER)
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    assert m.main([]) == 0                          # clean tree
    assert m.main(["--rule", "no-such-rule"]) == 2  # usage error
    assert m.main(["--list-rules"]) == 0
    # violations -> 1: a minimal violated tree under --root (absent
    # targets are simply empty)
    os.makedirs(tmp_path / "sml_tpu")
    (tmp_path / "sml_tpu" / "a.py").write_text(
        "import time\nt = time.time()\n")
    capsys.readouterr()
    assert m.main(["--root", str(tmp_path)]) == 1
    assert "no-wallclock-in-engine" in capsys.readouterr().out
    out = subprocess.run([sys.executable, RUNNER, "--rule", "bogus"],
                         cwd=REPO, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 2


def test_changed_only_mode():
    """--changed-only keeps the exit-code contract: 0 on the clean tree
    against HEAD, 2 on a ref git cannot resolve; --json records the
    filter ref. The full tree is still analysed (cross-file rules), so
    the rule list stays complete."""
    out = subprocess.run([sys.executable, RUNNER, "--changed-only",
                          "HEAD", "--json"], cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    payload = json.loads(out.stdout)
    assert payload["clean"] is True
    assert payload["changed_only"] == "HEAD"
    assert len(payload["rules"]) >= 14
    bad = subprocess.run([sys.executable, RUNNER, "--changed-only",
                          "no-such-ref-xyz"], cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    assert bad.returncode == 2, bad.stdout + bad.stderr
    assert "--changed-only" in bad.stderr


def test_changed_only_filters_to_changed_files(tmp_path):
    """In a scratch git repo: a committed violation plus a changed-file
    violation — full run reports both (exit 1), --changed-only HEAD
    reports ONLY the changed file's, and a run scoped to an unchanged
    ref-clean file reports none."""
    import shutil as _sh
    if _sh.which("git") is None:
        pytest.skip("git unavailable")
    _sh.copytree(os.path.join(REPO, "scripts"), tmp_path / "scripts",
                 ignore=_sh.ignore_patterns("__pycache__"))
    _sh.copytree(os.path.join(REPO, "sml_tpu", "lint"),
                 tmp_path / "sml_tpu" / "lint",
                 ignore=_sh.ignore_patterns("__pycache__"))
    os.makedirs(tmp_path / "sml_tpu" / "obs")
    _sh.copy(os.path.join(REPO, "sml_tpu", "obs", "taxonomy.py"),
             tmp_path / "sml_tpu" / "obs" / "taxonomy.py")
    (tmp_path / "sml_tpu" / "old.py").write_text(
        "import time\nT0 = time.time()\n")
    env = dict(os.environ, GIT_AUTHOR_NAME="t", GIT_AUTHOR_EMAIL="t@t",
               GIT_COMMITTER_NAME="t", GIT_COMMITTER_EMAIL="t@t")

    def git(*args):
        subprocess.run(["git", *args], cwd=tmp_path, check=True,
                       env=env, capture_output=True, timeout=30)

    git("init", "-q")
    git("add", "-A")
    git("commit", "-qm", "seed")
    (tmp_path / "sml_tpu" / "new.py").write_text(
        "import time\nT1 = time.time()\n")
    runner = str(tmp_path / "scripts" / "graftlint.py")
    full = subprocess.run([sys.executable, runner, "--root",
                           str(tmp_path), "--json"], cwd=tmp_path,
                          capture_output=True, text=True, timeout=120)
    assert full.returncode == 1
    full_paths = {v["path"] for v in json.loads(full.stdout)["violations"]}
    assert {"sml_tpu/old.py", "sml_tpu/new.py"} <= full_paths
    part = subprocess.run([sys.executable, runner, "--root",
                           str(tmp_path), "--changed-only", "HEAD",
                           "--json"], cwd=tmp_path, capture_output=True,
                          text=True, timeout=120)
    assert part.returncode == 1
    part_paths = {v["path"] for v in json.loads(part.stdout)["violations"]}
    assert "sml_tpu/new.py" in part_paths
    assert "sml_tpu/old.py" not in part_paths
