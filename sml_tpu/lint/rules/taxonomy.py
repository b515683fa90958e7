"""Rule 5 — obs-taxonomy (the PR-2 name-taxonomy lint, re-homed).

AST-greps every `PROFILER.span(...)` / `PROFILER.count(...)` and
`RECORDER.emit/counter/gauge(...)` call site under sml_tpu/ and checks
the event/span/counter name against the registered dotted-name taxonomy
(`sml_tpu/obs/taxonomy.py`), so names cannot silently drift between the
modules that emit them and the report/exporter/autologger that read them.

- a literal string name must be registered (exactly, or under a
  `prefix.*` wildcard);
- an f-string name's literal prefix (the part before the first
  interpolation) must sit under a registered wildcard — dynamic suffixes
  are only legal for registered families;
- any other (computed) name argument is a violation OUTSIDE sml_tpu/obs/
  (the recorder itself forwards names that originated at checked call
  sites; everyone else must write literals).

The reverse direction is `unemitted_patterns`: a registered pattern that
no call site of the package can emit is dead registry, found by the same
walk (tests/test_obs_taxonomy.py holds both directions).
"""

from __future__ import annotations

import ast
import os
import re
from typing import Iterator, List, Tuple

from ..core import Violation, rule
from ..project import Project

# receiver name -> {method -> (arg index of the NAME, taxonomy kind)}
TARGETS = {
    "PROFILER": {"span": (0, "span"), "count": (0, "count")},
    # `total`: a running total with no ring event (Recorder.total)
    "RECORDER": {"emit": (1, "emit"), "counter": (0, "counter"),
                 "total": (0, "counter"), "gauge": (0, "gauge")},
    "_OBS": {"emit": (1, "emit"), "counter": (0, "counter"),
             "total": (0, "counter"), "gauge": (0, "gauge")},
    # the recorder as obs/'s own classes hold it (`self._rec`)
    "_rec": {"emit": (1, "emit"), "counter": (0, "counter"),
             "total": (0, "counter"), "gauge": (0, "gauge")},
    # streaming-metrics histograms (obs/_metrics.py): observed names are
    # part of the same taxonomy (METRICS_NAMES, kind "observe")
    "METRICS": {"observe": (0, "observe")},
    "_METRICS": {"observe": (0, "observe")},
}

_HERE = os.path.dirname(os.path.abspath(__file__))
#: .../sml_tpu/lint/rules -> repo root
REPO = os.path.dirname(os.path.dirname(os.path.dirname(_HERE)))
PKG = os.path.join(REPO, "sml_tpu")


def _receiver_name(node: ast.expr) -> str:
    """The identifier a method is called on: PROFILER.span -> "PROFILER",
    obs.RECORDER.emit -> "RECORDER"."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return node.attr
    return ""


def _joined_prefix(node: ast.JoinedStr) -> str:
    """Literal prefix of an f-string up to the first interpolation."""
    prefix = ""
    for part in node.values:
        if isinstance(part, ast.Constant) and isinstance(part.value, str):
            prefix += part.value
        else:
            break
    return prefix


def _is_obs_internal(rel: str) -> bool:
    """The event bus itself (obs/) and its front-end (utils/profiler.py)
    forward names that were linted at their ORIGINATING call sites."""
    rel = rel.replace("\\", "/")
    return "/obs/" in f"/{rel}" or rel.endswith("utils/profiler.py")


def _name_sites(tree: ast.AST) -> Iterator[Tuple[int, str, str, str]]:
    """Every name argument of a TARGETS call → (line, kind, form, text):
    form "literal" (text = the name), "prefix" (an f-string; text = its
    literal prefix) or "computed" (text = ""; an f-string that begins
    with an interpolation is one)."""
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)):
            continue
        methods = TARGETS.get(_receiver_name(node.func.value))
        if methods is None or node.func.attr not in methods:
            continue
        arg_idx, kind = methods[node.func.attr]
        if len(node.args) <= arg_idx:
            continue  # name passed by keyword — obs-internal style only
        arg = node.args[arg_idx]
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            yield node.lineno, kind, "literal", arg.value
        elif isinstance(arg, ast.JoinedStr) and _joined_prefix(arg):
            yield node.lineno, kind, "prefix", _joined_prefix(arg)
        else:
            yield node.lineno, kind, "computed", ""


def check_source(text: str, rel: str, taxonomy,
                 in_obs: bool) -> List[Tuple[str, int, str]]:
    try:
        tree = ast.parse(text, filename=rel)
    except SyntaxError as e:
        return [(rel, e.lineno or 0, f"syntax error: {e.msg}")]
    out: List[Tuple[str, int, str]] = []
    for lineno, kind, form, name in _name_sites(tree):
        if form == "literal":
            if not taxonomy.is_registered(kind, name):
                out.append((rel, lineno,
                            f"unregistered {kind} name {name!r}"))
        elif form == "prefix":
            if not taxonomy.prefix_registered(kind, name):
                out.append((rel, lineno,
                            f"unregistered dynamic {kind} family "
                            f"(literal prefix {name!r} matches no "
                            f"wildcard entry)"))
        elif not in_obs:
            out.append((rel, lineno,
                        f"computed {kind} name (only literals/f-strings "
                        f"are lintable; computed names are reserved to "
                        f"sml_tpu/obs/)"))
    return out


def load_taxonomy(repo: str = REPO):
    """Load sml_tpu/obs/taxonomy.py by path: the registry is pure data
    and the lint must not pay (or require) a full jax-importing package
    load to run."""
    import importlib.util
    path = os.path.join(repo, "sml_tpu", "obs", "taxonomy.py")
    spec = importlib.util.spec_from_file_location("_obs_taxonomy", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _py_files(root: str) -> Iterator[str]:
    for dirpath, _dirs, files in os.walk(root):
        for f in sorted(files):
            if f.endswith(".py"):
                yield os.path.join(dirpath, f)


def check_file(path: str, taxonomy) -> List[Tuple[str, int, str]]:
    rel = os.path.relpath(path, REPO)
    in_obs = (os.sep + "obs" + os.sep in path
              or path.endswith(os.path.join("utils", "profiler.py")))
    with open(path, encoding="utf-8") as fh:
        return check_source(fh.read(), rel, taxonomy, in_obs)


def check_tree(root: str = PKG) -> List[Tuple[str, int, str]]:
    taxonomy = load_taxonomy()
    violations: List[Tuple[str, int, str]] = []
    for path in _py_files(root):
        violations.extend(check_file(path, taxonomy))
    return violations


#: a string literal that can be a family or a dotted prefix of names
_FAMILY = re.compile(r"[a-z_]+(\.[a-z_]+)*\.?")

#: registry of taxonomy.py -> the call-site kinds that feed it
_REGISTRY_KINDS = {"SPANS": ("span",), "COUNTERS": ("count", "counter"),
                   "GAUGES": ("gauge",), "EVENTS": ("emit",),
                   "METRICS_NAMES": ("observe",)}


def unemitted_patterns(root: str = PKG) -> List[Tuple[str, str]]:
    """(registry, pattern) for every registered pattern that nothing
    under `root` can emit. An emitter is a call site of the forward
    walk whose literal name (or f-string prefix) the pattern matches,
    or, for the names the forward check lets sml_tpu/obs/ COMPUTE
    (`"span_s." + name`, `SkewTracker("ingest")`), a string literal of
    an obs-internal file (the registry itself apart) that is the
    pattern's family or lies under it."""
    taxonomy = load_taxonomy()
    sites = set()      # (kind, form, text) of lintable call sites
    families = set()   # string literals of obs-internal files
    for path in _py_files(root):
        with open(path, encoding="utf-8") as fh:
            tree = ast.parse(fh.read(), filename=path)
        sites.update((kind, form, name)
                     for _, kind, form, name in _name_sites(tree)
                     if form != "computed")
        rel = os.path.relpath(path, root)
        if _is_obs_internal(rel) and rel != os.path.join("obs", "taxonomy.py"):
            families.update(
                n.value for n in ast.walk(tree)
                if isinstance(n, ast.Constant) and isinstance(n.value, str)
                and _FAMILY.fullmatch(n.value))

    def emitted(pattern: str, kinds) -> bool:
        wild = pattern.endswith("*")
        stem = pattern[:-1] if wild else pattern
        for kind, form, name in sites:
            if kind not in kinds:
                continue
            if name == stem or (wild and name.startswith(stem)) \
                    or (form == "prefix" and stem.startswith(name)):
                return True
        return wild and any(
            lit == stem.rstrip(".") or lit.startswith(stem)
            for lit in families)

    return [(registry, pattern)
            for registry, kinds in sorted(_REGISTRY_KINDS.items())
            for pattern in sorted(getattr(taxonomy, registry))
            if not emitted(pattern, kinds)]


@rule("obs-taxonomy",
      "PROFILER/RECORDER span/counter/event names must be registered in "
      "sml_tpu/obs/taxonomy.py")
def check(project: Project) -> List[Violation]:
    taxonomy = load_taxonomy(project.root
                             if os.path.isdir(os.path.join(
                                 project.root, "sml_tpu", "obs"))
                             else REPO)
    out: List[Violation] = []
    for f in project.files:
        if not f.rel.startswith("sml_tpu/") or f.rel.startswith("sml_tpu/lint/"):
            continue
        for rel, line, msg in check_source(f.text, f.rel, taxonomy,
                                           _is_obs_internal(f.rel)):
            out.append(Violation("obs-taxonomy", rel, line, msg))
    return out
