"""Name-taxonomy lint (graftlint rule `obs-taxonomy`,
sml_tpu/lint/rules/taxonomy.py): every PROFILER.span/count and
RECORDER.emit/counter/gauge call site in the package must use a name
registered in sml_tpu/obs/taxonomy.py, so counter/span names cannot
silently drift between the modules that emit them and the
report/exporter/autologger that read them (PR 2 satellite); and every
registered pattern must have a call site that can emit it.
"""

import importlib.util
import os

from sml_tpu.lint.rules import taxonomy as checker

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def test_package_is_taxonomy_clean():
    violations = checker.check_tree()
    assert violations == [], "\n".join(
        f"{f}:{ln}: {msg}" for f, ln, msg in violations)


def test_every_pattern_has_an_emitter():
    """The reverse of the check above: a pattern that no call site can
    emit is registry left behind by code that went."""
    assert checker.unemitted_patterns() == []


def test_checker_catches_rogue_names(tmp_path):
    """The lint actually detects drift: unregistered literals, dynamic
    families outside any wildcard, and computed names outside obs/."""
    bad = tmp_path / "rogue.py"
    bad.write_text(
        "PROFILER.count('staging.h2dBytes')\n"              # drifted name
        "PROFILER.count('staging.h2d_bytes')\n"             # registered: ok
        "with PROFILER.span(f'mystery.{x}'):\n    pass\n"   # rogue family
        "RECORDER.emit('cache', name_var)\n"                # computed name
        "RECORDER.gauge('hbm.bin_cache_bytes', 1)\n")       # registered: ok
    taxonomy = checker.load_taxonomy()
    violations = checker.check_file(str(bad), taxonomy)
    msgs = "\n".join(m for _, _, m in violations)
    assert len(violations) == 3, msgs
    assert "staging.h2dBytes" in msgs
    assert "mystery." in msgs
    assert "computed" in msgs


def test_wildcards_and_exact_names():
    t = checker.load_taxonomy()
    assert t.is_registered("span", "shuffle.partition")
    assert t.is_registered("span", "program.tree_ensemble")
    assert t.is_registered("count", "staging.h2d_bytes")
    assert t.is_registered("count", "dispatch.route_host")
    assert t.is_registered("gauge", "hbm.bin_cache_bytes")
    assert not t.is_registered("count", "staging.h2dBytes")
    assert not t.is_registered("span", "mystery.op")
    assert t.prefix_registered("span", "materialize.")
    assert not t.prefix_registered("span", "mystery.")


def test_script_cli_exits_clean():
    """The committed tree passes the lint via the CLI entry too."""
    path = os.path.join(REPO, "scripts", "graftlint.py")
    spec = importlib.util.spec_from_file_location("_graftlint_cli", path)
    runner = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(runner)
    assert runner.main(["--rule", "obs-taxonomy"]) == 0
