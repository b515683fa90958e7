"""The documents name only what the tree has: a command, a path or a
linked file that a reader is sent to exists. One case a document, so a
stale one is named."""

import glob
import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = ["README.md", "sml_tpu/README.md"] + sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "docs", "*.md")))

#: `python[3] <path>.py` wherever it stands, in prose or in a code block
_COMMAND = re.compile(r"\bpython3?\s+([\w./-]+\.py)\b")
#: a backticked path under one of the tree's directories, with or without
#: a `:line[-line]` or `::test` tail; a placeholder (`<name>`, `*`) is none
_PATH = re.compile(
    r"`((?:sml_tpu|scripts|tests|benchmark|docs)/[\w./-]*?)"
    r"(?::\d+(?:-\d+)?|::[\w\[\]-]+)?`")
#: a markdown link to a file beside the document (no scheme, no bare anchor)
_LINK = re.compile(r"\]\((?!\w+:|#)([^)#\s]+)(?:#[^)\s]*)?\)")


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_a_document_names_only_what_the_tree_has(doc):
    with open(os.path.join(REPO, doc), encoding="utf-8") as fh:
        text = fh.read()
    here = os.path.dirname(doc)
    named = set(_COMMAND.findall(text)) | set(_PATH.findall(text)) | {
        os.path.normpath(os.path.join(here, link))
        for link in _LINK.findall(text)}
    missing = sorted(p for p in named
                     if not os.path.exists(os.path.join(REPO, p)))
    assert not missing, f"{doc} names what the tree has not: {missing}"


def _readme() -> str:
    with open(os.path.join(REPO, "README.md"), encoding="utf-8") as fh:
        return fh.read()


def _cells():
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as fh:
        return [w["name"] for w in json.load(fh)["workloads"]]


def test_the_readme_sends_a_reader_to_the_benchmark_and_its_records():
    text = _readme()
    for name in ("benchmark/run.py", "BENCHMARK.json", "PERF_LEDGER.jsonl",
                 "PERF.md"):
        assert f"`{name}`" in text or f" {name} " in text, name


@pytest.mark.parametrize("cell", _cells())
def test_the_readme_names_every_cell(cell):
    assert f"`{cell}`" in _readme()
