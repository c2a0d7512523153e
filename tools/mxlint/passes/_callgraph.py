"""Best-effort project call graph for the mxlint passes.

Name-based, flow-insensitive resolution — deliberately the same
fidelity as a reviewer reading the code: a call to a bare name binds to
the nested/module function of that name (or the function it was
imported from, project-wide); ``self.m(...)`` binds to method ``m`` of
the enclosing class; ``self.held.m(...)`` binds to method ``m`` of the
project classes that define it, up to ``_MAX_IMPLEMENTERS`` of them (an
engine calling the model it holds: a seam has a few implementers, a
name that half the project defines says nothing). A function NAMED as
an argument of a call that resolved, by position or by keyword, is
reached with it (project code handed a closure runs it: the serving
programs' ``attend`` and ``state``); what is handed to a library call
is not followed. Anything more dynamic (getattr, dict-of-functions) is out of
scope; the passes that ride on this are designed so a missed edge means
a missed finding, never a false one.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from ..core import Project, SourceUnit, dotted, enclosing_scopes, parent

FuncKey = int       # id(FunctionDef node)

# classes that may share a held object's method name and still be followed
_MAX_IMPLEMENTERS = 3


class FuncInfo:
    def __init__(self, node, unit: SourceUnit):
        self.node = node
        self.unit = unit
        scopes = enclosing_scopes(node)
        self.class_node = next(
            (s for s in scopes if isinstance(s, ast.ClassDef)), None)
        self.class_name = self.class_node.name if self.class_node else None


class CallGraph:
    """Function tables + call resolution over a whole Project."""

    def __init__(self, project: Project):
        self.project = project
        self.funcs: Dict[FuncKey, FuncInfo] = {}
        # module name -> {func name -> [module-level FunctionDef]}
        self.module_defs: Dict[str, Dict[str, List[ast.AST]]] = {}
        # (module, class, method) -> FunctionDef
        self.methods: Dict[Tuple[str, str, str], ast.AST] = {}
        # method name -> every FunctionDef of that name, project-wide
        self.methods_named: Dict[str, List[ast.AST]] = {}
        for unit in project.units:
            if unit.tree is None:
                continue
            mdefs: Dict[str, List[ast.AST]] = {}
            self.module_defs[unit.module] = mdefs
            for node in ast.walk(unit.tree):
                if not isinstance(node, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                    continue
                info = FuncInfo(node, unit)
                self.funcs[id(node)] = info
                par = parent(node)
                if isinstance(par, ast.Module):
                    mdefs.setdefault(node.name, []).append(node)
                elif isinstance(par, ast.ClassDef):
                    self.methods[(unit.module, par.name, node.name)] = node
                    self.methods_named.setdefault(node.name, []).append(node)

    # ------------------------------------------------------------------ #
    def _nested_lookup(self, name: str, from_node: ast.AST) \
            -> Optional[ast.AST]:
        """A def of ``name`` nested in the referencing function itself
        or any enclosing function scope (``jax.jit(local_fn)`` inside a
        builder method is the common case)."""
        scopes = [from_node] + enclosing_scopes(from_node)
        for scope in scopes:
            if isinstance(scope, ast.ClassDef):
                continue
            for child in ast.walk(scope):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)) \
                        and child.name == name and child is not from_node:
                    return child
        return None

    def resolve_name(self, name: str, unit: SourceUnit,
                     from_node: Optional[ast.AST] = None) -> List[ast.AST]:
        """Resolve a bare callee name to FunctionDef nodes."""
        out: List[ast.AST] = []
        if from_node is not None:
            nested = self._nested_lookup(name, from_node)
            if nested is not None:
                return [nested]
        mdefs = self.module_defs.get(unit.module, {})
        if name in mdefs:
            return list(mdefs[name])
        if name in unit.import_symbols:
            mod, orig = unit.import_symbols[name]
            tgt = self.module_defs.get(mod, {})
            if orig in tgt:
                return list(tgt[orig])
        return out

    def resolve_call(self, call: ast.Call, unit: SourceUnit,
                     from_node: Optional[ast.AST] = None) -> List[ast.AST]:
        func = call.func
        if isinstance(func, ast.Name):
            return self.resolve_name(func.id, unit, from_node)
        if isinstance(func, ast.Attribute):
            base = func.value
            if isinstance(base, ast.Name) and base.id in ("self", "cls") \
                    and from_node is not None:
                info = self.funcs.get(id(from_node))
                cls = info.class_name if info else None
                if cls is not None:
                    m = self.methods.get((unit.module, cls, func.attr))
                    if m is not None:
                        return [m]
                return []
            d = dotted(func)
            if d is None:
                return []
            head, _, rest = d.partition(".")
            if head == "self" and rest.count(".") == 1:
                # a method of an object the instance holds
                named = self.methods_named.get(func.attr, [])
                return list(named) \
                    if 1 <= len(named) <= _MAX_IMPLEMENTERS else []
            # module-alias call: `import x.y as z; z.f(...)` or
            # `from . import sub; sub.f(...)`
            mod = unit.import_modules.get(head)
            if mod is None and head in unit.import_symbols:
                src, orig = unit.import_symbols[head]
                mod = f"{src}.{orig}" if src else orig
            if mod is not None and rest and "." not in rest:
                tgt = self.module_defs.get(mod, {})
                if rest in tgt:
                    return list(tgt[rest])
        return []

    # ------------------------------------------------------------------ #
    def reachable(self, roots: List[ast.AST]) -> Set[FuncKey]:
        """BFS closure over resolvable call edges."""
        seen: Set[FuncKey] = set()
        work = [r for r in roots]
        while work:
            node = work.pop()
            if id(node) in seen:
                continue
            seen.add(id(node))
            info = self.funcs.get(id(node))
            unit = info.unit if info else None
            if unit is None:
                continue
            for sub in walk_own(node):
                if isinstance(sub, ast.Call):
                    callees = self.resolve_call(sub, unit, node)
                    work.extend(callees)
                    handed = list(sub.args) + [k.value for k in sub.keywords]
                    for arg in handed if callees else ():
                        if isinstance(arg, ast.Name):
                            work.extend(self.resolve_name(arg.id, unit,
                                                          node))
        return seen


def walk_own(func: ast.AST):
    """Walk a function's own body, NOT descending into nested
    def/class/lambda bodies (those are separate call-graph nodes)."""
    stack = list(ast.iter_child_nodes(func))
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef, ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(node))
