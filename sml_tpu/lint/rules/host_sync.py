"""Rule 1 — host-sync-in-hot-path.

Every device->host synchronization has a fixed cost and stalls the
asynchronous dispatch queue; one stray `.item()` in a fit loop silently dominates
step time (the classic scaled-training regression). This rule flags the
sync idioms inside every function reachable from a dispatch entry point:

- entry points: functions that call `routed` / `routed_for` / `mesh_for`
  / `decide` (the measured-latency dispatcher's API — the boundary where
  code becomes "the hot path");
- reachability: the package call graph, resolved conservatively (see
  `Project.resolve_callees`);
- flagged inside the hot set:
    * `.item()` and `.block_until_ready()` on anything,
    * `np.asarray(x)` / `numpy.asarray(x)` where `x` is device-resident,
    * `float(x)` / `int(x)` / `bool(x)` where `x` is device-resident.

"Device-resident" is a per-function local dataflow: names bound from
`jax.device_put`, `jnp.*` calls, the staging helpers (`stage_*`), or a
call of a compiled program (a name bound from `data_parallel` /
`cached_data_parallel` / `_compiled_chunk` / `jax.jit`). `jax.device_get`
is the ONE blessed transfer (batched, counted by the profiler) — its
results are host values and reading them is fine.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set

from ..core import Violation, rule
from ..project import Project

ENTRY_CALLS = ("routed", "routed_for", "mesh_for", "decide")

#: staging helpers whose results live in HBM
STAGE_FUNCS = {"stage_sharded", "stage_rows_cached", "stage_bins_cached",
               "stage_mask_cached", "stage_stacked_cached", "device_put"}
#: helpers returning a compiled program: calling their RESULT yields
#: device arrays
COMPILE_FUNCS = {"data_parallel", "cached_data_parallel", "_compiled_chunk",
                 "jit"}

SYNC_METHODS = {"item": "`.item()` is a per-element device->host sync",
                "block_until_ready":
                    "`.block_until_ready()` stalls the host on the device "
                    "stream"}


class _FnChecker:
    """Linear (statement-order) device-taint scan of one hot function."""

    def __init__(self, rel: str, qualname: str, origin: str):
        self.rel = rel
        self.qualname = qualname
        self.origin = origin
        self.tracked: Set[str] = set()     # device-resident names
        self.compiled: Set[str] = set()    # names bound to compiled programs
        self.out: List[Violation] = []

    # -------------------------------------------------- device-ness of exprs
    def _is_device(self, e: ast.expr) -> bool:
        if isinstance(e, ast.Name):
            return e.id in self.tracked
        if isinstance(e, ast.Subscript):
            return self._is_device(e.value)
        if isinstance(e, ast.Starred):
            return self._is_device(e.value)
        if isinstance(e, ast.Call):
            f = e.func
            if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name):
                if f.value.id == "jnp":
                    return True
                if f.value.id == "jax" and f.attr == "device_put":
                    return True
            if isinstance(f, ast.Name):
                if f.id in STAGE_FUNCS or f.id in self.compiled:
                    return True
            if isinstance(f, ast.Call):  # _compiled_chunk(...)(args)
                inner = f.func
                if (isinstance(inner, ast.Name)
                        and inner.id in COMPILE_FUNCS):
                    return True
        return False

    def _is_compiled_binding(self, e: ast.expr) -> bool:
        if isinstance(e, ast.Call):
            f = e.func
            if isinstance(f, ast.Name) and f.id in COMPILE_FUNCS:
                return True
            if (isinstance(f, ast.Attribute) and f.attr in COMPILE_FUNCS):
                return True
        # compiled = _some_cache[key]
        if (isinstance(e, ast.Subscript) and isinstance(e.value, ast.Name)
                and e.value.id.endswith("_cache")):
            return True
        return False

    # ------------------------------------------------------------- flagging
    def _flag(self, node: ast.AST, msg: str) -> None:
        self.out.append(Violation(
            "host-sync-in-hot-path", self.rel, node.lineno,
            f"{msg} inside dispatch-hot `{self.qualname}` (reachable from "
            f"entry `{self.origin}`) — move it off the hot path, batch it "
            f"through jax.device_get, or pragma with a justification"))

    def _scan_expr(self, e: ast.expr) -> None:
        for node in ast.walk(e):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if isinstance(f, ast.Attribute) and f.attr in SYNC_METHODS \
                    and not node.args:
                self._flag(node, SYNC_METHODS[f.attr])
            elif (isinstance(f, ast.Attribute)
                  and f.attr == "asarray"
                  and isinstance(f.value, ast.Name)
                  and f.value.id in ("np", "numpy")
                  and node.args and self._is_device(node.args[0])):
                self._flag(node, "`np.asarray` on a device-resident array "
                                 "is an unbatched D2H transfer")
            elif (isinstance(f, ast.Name) and f.id in ("float", "int", "bool")
                  and node.args and self._is_device(node.args[0])):
                self._flag(node, f"`{f.id}()` on a device-resident value "
                                 f"forces a scalar D2H sync")

    # ---------------------------------------------------------- statements
    def _bind_target(self, target: ast.expr, device: bool) -> None:
        if isinstance(target, ast.Name):
            if device:
                self.tracked.add(target.id)
            else:
                self.tracked.discard(target.id)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for elt in target.elts:
                self._bind_target(elt, device)
        elif isinstance(target, ast.Starred):
            self._bind_target(target.value, device)

    def run(self, fn_node: ast.AST) -> List[Violation]:
        for stmt in fn_node.body:
            self._stmt(stmt)
        return self.out

    def _stmt(self, stmt: ast.stmt) -> None:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            return  # nested defs are separate call-graph nodes
        if isinstance(stmt, ast.Assign):
            self._scan_expr(stmt.value)
            device = self._is_device(stmt.value)
            if (len(stmt.targets) == 1
                    and isinstance(stmt.targets[0], ast.Name)
                    and self._is_compiled_binding(stmt.value)):
                self.compiled.add(stmt.targets[0].id)
            for t in stmt.targets:
                self._bind_target(t, device)
            return
        if isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
            if stmt.value is not None:
                self._scan_expr(stmt.value)
                self._bind_target(stmt.target, self._is_device(stmt.value))
            return
        if isinstance(stmt, ast.For):
            self._scan_expr(stmt.iter)
            self._bind_target(stmt.target, self._is_device(stmt.iter))
            for s in stmt.body + stmt.orelse:
                self._stmt(s)
            return
        if isinstance(stmt, (ast.While, ast.If)):
            self._scan_expr(stmt.test)
            for s in stmt.body + stmt.orelse:
                self._stmt(s)
            return
        if isinstance(stmt, ast.With):
            for item in stmt.items:
                self._scan_expr(item.context_expr)
                if item.optional_vars is not None:
                    self._bind_target(item.optional_vars,
                                      self._is_device(item.context_expr))
            for s in stmt.body:
                self._stmt(s)
            return
        if isinstance(stmt, ast.Try):
            for s in (stmt.body + stmt.orelse + stmt.finalbody
                      + [h for hh in stmt.handlers for h in hh.body]):
                self._stmt(s)
            return
        if isinstance(stmt, ast.Delete):
            for t in stmt.targets:
                self._bind_target(t, False)
            return
        # Return / Expr / Assert / Raise / ...: scan every expression
        for node in ast.iter_child_nodes(stmt):
            if isinstance(node, ast.expr):
                self._scan_expr(node)


@rule("host-sync-in-hot-path",
      "no .item()/block_until_ready/asarray/float() device syncs in "
      "functions reachable from dispatch entry points")
def check(project: Project) -> List[Violation]:
    out: List[Violation] = []
    hot = project.hot_functions(ENTRY_CALLS)
    index = project.function_index()
    for rel, fns in index.items():
        for fn in fns:
            origin = hot.get(f"{rel}::{fn.qualname}")
            if origin is None:
                continue
            out.extend(_FnChecker(rel, fn.qualname, origin).run(fn.node))
    return out
