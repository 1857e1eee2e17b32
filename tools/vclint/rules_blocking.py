"""VCL002: blocking calls reachable from cooperative Task bodies.

Entry points are the control plane's cooperative quanta — the
``_pump`` / ``_worker_quantum`` / ``_scan_quantum`` / ``_run_quantum``
functions plus ``reconcile`` / ``reconcile_batch`` / ``scan`` /
``scan_once`` / ``poll`` methods on ``Controller`` (and subclasses) and
on classes in the five core concurrency modules, plus the methods and
functions handed by reference to a ``ShardedPipeline`` (its shards call
those hooks from ``reconcile``). From each entry, the call graph is walked (best-effort resolution, virtual dispatch
included) and the following are flagged anywhere reachable:

- ``time.sleep(x)`` with non-zero x;
- ``.join(...)`` on ``threading.Thread`` / ``Task`` receivers;
- ``.wait(...)`` on ``threading.Event`` / ``threading.Condition``
  receivers (cooperative code must use the timer wheel instead).

A call to a queue-style ``get`` / ``get_batch`` / ``next`` / ``poll``
with a literal ``timeout=0`` or ``block=False`` is a non-blocking poll:
the walk does not descend into it, so the ``Condition.wait`` on the
queue's slow path is only flagged when some cooperative caller can
actually reach it blocking.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from .engine import Finding, Rule
from .model import (ClassInfo, FuncDef, Project, call_name, elem_type,
                    iter_functions, param_types, walk_in_scope)

ENTRY_FUNC_NAMES = {"_pump", "_worker_quantum", "_scan_quantum",
                    "_run_quantum"}
ENTRY_METHOD_NAMES = {"reconcile", "reconcile_batch", "scan", "scan_once",
                      "poll"}
ENTRY_MODULES = ("executor.py", "informer.py", "runtime.py", "syncer.py",
                 "upward.py",
                 # the serving data plane: the fleet controller runs on the
                 # cooperative runtime, and the engine/scheduler are called
                 # from its reconcile/scan — blocking there stalls a quantum
                 "serving/engine.py", "serving/scheduler.py",
                 "serving/host.py")
# constructors whose callable arguments run inside cooperative reconciles
HOOK_CONSUMERS = {"ShardedPipeline"}
POLL_GATED = {"get", "get_batch", "next", "poll"}
JOIN_TYPES = {"Thread", "Timer", "Task"}
WAIT_TYPES = {"Event", "Condition"}


def _literal_zero_or_false(node: ast.expr) -> bool:
    return isinstance(node, ast.Constant) and (
        node.value is False or node.value == 0)


def _is_nonblocking_poll(call: ast.Call) -> bool:
    for kw in call.keywords:
        if kw.arg in ("timeout", "block") and _literal_zero_or_false(kw.value):
            return True
    return False


def local_type_table(project: Project, ci: Optional[ClassInfo],
                     fn: FuncDef) -> Dict[str, str]:
    """Parameter annotations plus simple local inference: constructor
    calls, typed ``self.<attr>`` aliases, ``list(x)`` copies, and
    for-loop targets over typed lists."""
    table = param_types(fn)
    for node in walk_in_scope(fn):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            name = node.targets[0].id
            t = _expr_type(project, ci, node.value, table)
            if t is not None:
                table.setdefault(name, t)
        elif isinstance(node, ast.For) and isinstance(node.target, ast.Name):
            t = _expr_type(project, ci, node.iter, table)
            e = elem_type(t)
            if e is not None:
                table.setdefault(node.target.id, e)
    return table


def _expr_type(project: Project, ci: Optional[ClassInfo], expr: ast.expr,
               table: Dict[str, str]) -> Optional[str]:
    if isinstance(expr, ast.Name):
        return table.get(expr.id)
    if isinstance(expr, ast.Attribute) and isinstance(expr.value, ast.Name) \
            and expr.value.id == "self" and ci is not None:
        return project.attr_type(ci, expr.attr)
    if isinstance(expr, ast.Subscript):
        t = _expr_type(project, ci, expr.value, table)
        if isinstance(expr.slice, ast.Slice):
            return t                      # xs[n:] is still list[T]
        return elem_type(t)               # xs[i] is T
    if isinstance(expr, ast.Call):
        f = expr.func
        if isinstance(f, ast.Name):
            if f.id in ("list", "sorted") and expr.args:
                inner = _expr_type(project, ci, expr.args[0], table)
                if inner and inner.startswith("list["):
                    return inner
                return None
            if f.id in project.classes_by_name:
                return f.id
        if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name) \
                and f.value.id == "threading":
            return f"threading.{f.attr}"
    return None


class BlockingCallRule(Rule):
    id = "VCL002"
    description = "blocking calls reachable from cooperative task bodies"

    def check(self, project: Project) -> List[Finding]:
        self.project = project
        entries = self._entries()
        findings: List[Finding] = []
        seen_fp: Set[str] = set()
        visited: Set[Tuple[str, str, str]] = set()
        # (ci, fn, chain) BFS over the call graph
        queue: List[Tuple[Optional[ClassInfo], FuncDef, str]] = [
            (ci, fn, qual) for qual, ci, fn in entries]
        for ci, fn, qual in queue:
            visited.add(self._key(ci, fn))
        while queue:
            ci, fn, chain = queue.pop(0)
            relpath = ci.relpath if ci else self._module_of(fn)
            qualname = f"{ci.name}.{fn.name}" if ci else fn.name
            table = local_type_table(self.project, ci, fn)
            for node in walk_in_scope(fn):
                if not isinstance(node, ast.Call):
                    continue
                f = self._flag(relpath, qualname, ci, node, table, chain)
                if f is not None:
                    if f.fingerprint not in seen_fp:
                        seen_fp.add(f.fingerprint)
                        findings.append(f)
                    continue   # call site flagged: its interior is implied
                if isinstance(node.func, ast.Attribute) \
                        and node.func.attr in POLL_GATED \
                        and _is_nonblocking_poll(node):
                    continue   # non-blocking poll: don't descend
                if chain.count(" -> ") >= 12:
                    continue
                for tci, tfn in self.project.resolve_call(ci, node, table):
                    key = self._key(tci, tfn)
                    if key in visited:
                        continue
                    visited.add(key)
                    queue.append((tci, tfn, f"{chain} -> {qualname}"))
        findings.sort(key=lambda f: (f.relpath, f.line))
        return findings

    def _key(self, ci: Optional[ClassInfo], fn: FuncDef
             ) -> Tuple[str, str, str]:
        return (ci.name if ci else "", ci.relpath if ci else "", fn.name)

    def _module_of(self, fn: FuncDef) -> str:
        for mod in self.project.modules:
            if mod.functions.get(fn.name) is fn:
                return mod.relpath
        return "?"

    def _entries(self) -> List[Tuple[str, Optional[ClassInfo], FuncDef]]:
        out = []
        controllers = {"Controller"} | {
            ci.name for ci in self.project.subclasses("Controller")}
        for mod in self.project.modules:
            in_core5 = mod.relpath.endswith(ENTRY_MODULES)
            for qual, ci, fn in iter_functions(mod):
                if fn.name in ENTRY_FUNC_NAMES:
                    out.append((qual, ci, fn))
                elif fn.name in ENTRY_METHOD_NAMES and ci is not None and (
                        in_core5 or ci.name in controllers):
                    out.append((qual, ci, fn))
                out.extend(self._hook_entries(ci, fn))
        return out

    def _hook_entries(self, ci: Optional[ClassInfo], fn: FuncDef
                      ) -> List[Tuple[str, Optional[ClassInfo], FuncDef]]:
        """Functions ``fn`` hands by reference to a HOOK_CONSUMERS
        constructor (``sync_one=self._reconcile_down``, ``up.reconcile_one``,
        a module function)."""
        out: List[Tuple[str, Optional[ClassInfo], FuncDef]] = []
        table: Optional[Dict[str, str]] = None
        for node in walk_in_scope(fn):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id in HOOK_CONSUMERS):
                continue
            if table is None:
                table = local_type_table(self.project, ci, fn)
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                for hci, hfn in self._callable_ref(ci, arg, table):
                    qual = f"{hci.name}.{hfn.name}" if hci else hfn.name
                    out.append((qual, hci, hfn))
        return out

    def _callable_ref(self, ci: Optional[ClassInfo], expr: ast.expr,
                      table: Dict[str, str]
                      ) -> List[Tuple[Optional[ClassInfo], FuncDef]]:
        """The definitions a function reference (not a call) names."""
        if isinstance(expr, ast.Name):
            return [(None, f) for _, f in
                    self.project.functions_by_name.get(expr.id, [])]
        if not isinstance(expr, ast.Attribute):
            return []
        t = self.project._receiver_type(ci, expr.value, table)
        if t == "self" and ci is not None:
            owners = [ci]
        elif t is not None:
            owners = self.project.classes_by_name.get(
                t.split("[")[0].split(".")[-1], [])
        else:
            return []
        hits = [self.project.lookup_method(o, expr.attr) for o in owners]
        return [h for h in hits if h is not None]

    def _flag(self, relpath: str, qualname: str, ci: Optional[ClassInfo],
              call: ast.Call, table: Dict[str, str], chain: str
              ) -> Optional[Finding]:
        f = call.func
        via = f" (reachable from cooperative entry {chain.split(' -> ')[0]})"
        if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name) \
                and f.value.id == "time" and f.attr == "sleep":
            if call.args and _literal_zero_or_false(call.args[0]):
                return None
            return Finding(
                self.id, relpath, call.lineno, qualname,
                detail="time.sleep",
                message=f"time.sleep blocks a pool thread{via}")
        if not isinstance(f, ast.Attribute):
            return None
        if f.attr in ("join", "wait"):
            t = self.project._receiver_type(ci, f.value, table)
            if t == "self" or t is None:
                return None
            tail = t.split("[")[0].split(".")[-1]
            if f.attr == "join" and tail in JOIN_TYPES:
                return Finding(
                    self.id, relpath, call.lineno, qualname,
                    detail=f"join:{call_name(call)}",
                    message=f"{tail}.join blocks a pool thread{via}")
            if f.attr == "wait" and tail in WAIT_TYPES:
                if call.args and _literal_zero_or_false(call.args[0]):
                    return None
                return Finding(
                    self.id, relpath, call.lineno, qualname,
                    detail=f"wait:{call_name(call)}",
                    message=(f"threading.{tail}.wait blocks a pool thread — "
                             f"use the executor timer wheel{via}"))
        return None
