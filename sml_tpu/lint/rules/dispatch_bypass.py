"""Rule 2 — dispatch-bypass.

Every compile in the engine is supposed to flow through
`parallel/dispatch.py`-governed paths (the `data_parallel` /
`cached_data_parallel` helpers and the tree program caches) so that the
PR-2 routing audit, `obs.note_compile`, and the persistent compile cache
stay authoritative. A bare `jax.jit` / `pjit` / `pmap` anywhere else is a
compile the observability stack never sees.

Flagged forms (call or decorator):  `jax.jit(...)`, `pjit(...)`,
`jax.pmap(...)`, `@jax.jit`, `@partial(jax.jit, ...)` — and raw Pallas
kernel launches, `pl.pallas_call(...)` / `pallas_call(...)`: a custom
kernel is a compile AND a device launch the routing audit, the
`kernel.*` counters, and the interpret-mode fallback ladder must govern,
so kernels live only in the sanctioned `sml_tpu/native/` module
(docs/KERNELS.md).

Also flagged: direct invocation of the traversal kernel entry,
`forest_traverse(...)` / `traverse_kernel.forest_traverse(...)`, outside
the `score_block` dispatch glue (`ml/inference.py`'s
`_forest_margin_path`). A bypassing
call skips `resolve_infer_kernel`, so the VMEM demotion guard and the
`infer.kernel.*` counters never see the launch.

Suppression is an explicit ALLOWLIST of (file, enclosing function)
pairs — or a directory prefix ending in "/" — each carrying its
justification (the blessed compile owners), plus the usual
pragma/baseline machinery for one-offs.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional

from ..core import Violation, rule
from ..project import Project

COMPILE_ATTRS = {"jit", "pjit", "pmap"}  # jax.<attr> spellings only;
# pallas_call matches by attribute/name directly in _is_jax_jit_expr
# (its qualifier is a caller-chosen import alias, never `jax`)

#: rel (or directory prefix ending in "/") ->
#: {enclosing qualname ("<module>" for module level) -> reason}
ALLOWLIST: Dict[str, Dict[str, str]] = {
    "sml_tpu/parallel/dispatch.py": {
        "*": "the dispatcher itself: calibration probes and the compile "
             "cache are this rule's ground truth",
    },
    "sml_tpu/native/": {
        # form-scoped entry: blesses ONLY pallas_call launches (counted
        # via kernel.pallas_launch/kernel.interpret and governed by
        # inference.resolve_infer_kernel's fallback ladder — docs/KERNELS.md);
        # a bare jax.jit added under native/ still flags like anywhere
        "form:pallas_call": "THE sanctioned custom-kernel module: every "
                            "pallas_call here is counted and "
                            "fallback-governed",
        "form:forest_traverse": "kernel modules may compose their own "
                                "entries (self-tests, wrappers); counts "
                                "and fallback governance live here",
    },
    "sml_tpu/ml/inference.py": {
        "_forest_margin_path": "THE sanctioned traversal-kernel "
                               "invocation site: every forest_traverse "
                               "launch is resolved by "
                               "resolve_infer_kernel (VMEM guard, "
                               "infer.kernel.* counters) before "
                               "reaching it",
    },
    "sml_tpu/ml/_staging.py": {
        "data_parallel": "THE blessed jit+shard_map compile helper; every "
                         "cached build is reported via obs.note_compile in "
                         "cached_data_parallel",
        "_chunk_assemble_program": "chunked-ingest bin-assembly program "
                                   "(donated dynamic_update_slice); built "
                                   "once and reported via obs.note_compile"
                                   "('chunk_assemble')",
    },
    "sml_tpu/ml/tree_impl.py": {
        "_compiled_chunk": "chunked-boosting program cache; each build is "
                           "reported via obs.note_compile('tree_chunk_*')",
        "_folds_compiled": "batched CV-folds program cache; builds are "
                           "reported via obs.note_compile("
                           "'tree_ensemble_folds_*')",
        "_trials_compiled": "grid-fused trial-batch program cache; builds "
                            "are reported via obs.note_compile("
                            "'tree_ensemble_trials_*')",
        "_predict_binned": "module-level predict kernel (static depth); "
                           "host-side predict path whose traffic is visible "
                           "through the binning.predict span",
    },
}


def _is_jax_jit_expr(e: ast.expr) -> bool:
    """jax.jit / jax.pjit / jax.pmap as an attribute, a bare pjit name,
    or a Pallas launch: `pl.pallas_call` / `pallas.pallas_call` (any
    qualifier — the import alias is caller-chosen) / bare
    `pallas_call`."""
    if isinstance(e, ast.Attribute):
        if e.attr == "pallas_call":
            return True
        return (isinstance(e.value, ast.Name) and e.value.id == "jax"
                and e.attr in COMPILE_ATTRS)
    if isinstance(e, ast.Name):
        return e.id in ("pjit", "pallas_call")
    return False


def _is_traverse_kernel_expr(e: ast.expr) -> bool:
    """The traversal-kernel entry, any spelling: bare `forest_traverse`
    or `<alias>.forest_traverse` (the import alias is caller-chosen)."""
    if isinstance(e, ast.Attribute):
        return e.attr == "forest_traverse"
    return isinstance(e, ast.Name) and e.id == "forest_traverse"


def _compile_site(node: ast.expr) -> Optional[str]:
    """A human label when `node` is a compile constructor, else None."""
    if _is_jax_jit_expr(node):
        return ast.unparse(node) if hasattr(ast, "unparse") else "jax.jit"
    if isinstance(node, ast.Call):
        if _is_jax_jit_expr(node.func):
            return ast.unparse(node.func) if hasattr(ast, "unparse") \
                else "jax.jit"
        if _is_traverse_kernel_expr(node.func):
            return ast.unparse(node.func) if hasattr(ast, "unparse") \
                else "forest_traverse"
        # partial(jax.jit, ...) — the decorator spelling for static args
        if (isinstance(node.func, ast.Name) and node.func.id == "partial"
                and node.args and _is_jax_jit_expr(node.args[0])):
            return "partial(jax.jit, ...)"
    return None


@rule("dispatch-bypass",
      "bare jax.jit/pjit/pmap compiles outside parallel/dispatch.py must "
      "be allowlisted compile owners")
def check(project: Project) -> List[Violation]:
    out: List[Violation] = []
    for f in project.files:
        if f.tree is None:
            continue
        allow = ALLOWLIST.get(f.rel, {})
        if not allow:  # directory-prefix entries (sml_tpu/native/)
            for pref, entry in ALLOWLIST.items():
                if pref.endswith("/") and f.rel.startswith(pref):
                    allow = entry
                    break
        if "*" in allow:
            continue

        def report(node: ast.AST, label: str,
                   qual: Optional[str] = None) -> None:
            if qual is None:
                fn = project.enclosing_function(f.rel, node.lineno)
                qual = fn.qualname if fn else "<module>"
            if qual in allow or qual.rsplit(".", 1)[-1] in allow:
                return
            # form-scoped entries bless one compile FORM file-wide
            # (the native/ directory blesses pallas_call, not jax.jit)
            if "pallas_call" in label and "form:pallas_call" in allow:
                return
            if "forest_traverse" in label \
                    and "form:forest_traverse" in allow:
                return
            if "forest_traverse" in label:
                out.append(Violation(
                    "dispatch-bypass", f.rel, node.lineno,
                    f"direct traversal-kernel invocation `{label}` in "
                    f"`{qual}` bypasses the score_block dispatch path "
                    f"(resolve_infer_kernel's VMEM guard and "
                    f"infer.kernel.* counters never see the "
                    f"launch) — score through DeviceScorer/"
                    f"predict_forest_sharded (ml.inference."
                    f"_forest_margin_path is the one sanctioned call "
                    f"site) or add an allowlist entry with a reason"))
                return
            fix = ("move the kernel into sml_tpu/native/ (the sanctioned "
                   "kernel directory, behind a resolver that counts it)"
                   if "pallas_call" in label else
                   "compile through ml._staging.data_parallel/"
                   "cached_data_parallel")
            out.append(Violation(
                "dispatch-bypass", f.rel, node.lineno,
                f"bare `{label}` compile in `{qual}` bypasses "
                f"parallel.dispatch (routing audit + obs.note_compile + "
                f"compile cache never see it) — {fix} or add "
                f"an allowlist entry with a reason"))

        seen_decorators = set()
        for node in ast.walk(f.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    label = _compile_site(dec)
                    if label is not None:
                        seen_decorators.add(id(dec))
                        info = project.enclosing_function(f.rel, node.lineno)
                        report(dec, f"@{label}",
                               qual=info.qualname if info else node.name)
        for node in ast.walk(f.tree):
            if isinstance(node, ast.Call) and id(node) not in seen_decorators:
                label = _compile_site(node)
                # only the call form here; bare attributes were decorators
                if label is not None and not _is_jax_jit_expr(node):
                    report(node, label)
    return out
