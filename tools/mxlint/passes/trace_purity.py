"""Pass 1 — retrace / host-leak hazards inside traced code.

Functions reachable from a ``jax.jit`` / ``pjit`` / ``pallas_call``
call site (or decorator) execute under a trace: their Python body runs
once per compilation, their array arguments are abstract tracers. Code
that is harmless on the host is a landmine there:

  - ``float()/int()/bool()/.item()`` on a traced operand either throws
    (ConcretizationTypeError) or — worse, on shape-dependent paths —
    silently bakes a host branch into the trace;
  - ``time.*`` / ``np.random.*`` / ``random.*`` freeze a single draw or
    timestamp into the compiled program forever (the PR-1 LARS
    schedule retrace and the frozen-dropout class of bug);
  - ``np.asarray``/``np.array`` on a traced value forces a host sync
    at trace time and constant-folds the tracer;
  - a closure-captured host scalar that the enclosing scope keeps
    rebinding is a retrace-per-call hazard (cache key churn).

The pass seeds discovery at every jit/pjit/pallas_call site in the
tree (the known entry points — optimizer/fused.py, serve/engine.py,
parallel/spmd.py, ops/ragged_attention.py — plus anything new), walks
the project call graph, and checks every reachable function.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set, Tuple

from ..core import (Finding, Project, SourceUnit, dotted, parent,
                    qualname_of)
from . import _callgraph
from ._callgraph import walk_own

RULE = "trace-host-leak"

_JIT_DOTTED = {"jax.jit", "jax.pjit", "pjit", "jit",
               "pl.pallas_call", "pallas_call", "pallas.pallas_call"}
_PARTIAL_DOTTED = {"functools.partial", "partial"}
_NP_CAST = {"asarray", "array"}


def _is_jit_ref(node: ast.AST, unit: SourceUnit) -> bool:
    d = dotted(node)
    if d is None:
        return False
    if d in ("jit", "pjit", "pallas_call"):
        sym = unit.import_symbols.get(d)
        return sym is not None and sym[0].startswith("jax")
    if d in _JIT_DOTTED:
        return True
    # e.g. jax.experimental.pjit.pjit / pltpu-style aliases
    return d.endswith(".pallas_call") or d.endswith(".pjit") \
        or d == "jax.jit"


def _jit_call_target(call: ast.Call, unit: SourceUnit) \
        -> Optional[ast.AST]:
    """For ``jit(f, ...)`` / ``pallas_call(kernel, ...)`` return the
    expression naming the traced function."""
    if not isinstance(call.func, (ast.Name, ast.Attribute)):
        return None
    if not _is_jit_ref(call.func, unit):
        return None
    return call.args[0] if call.args else None


def _decorator_is_jit(dec: ast.AST, unit: SourceUnit) -> bool:
    if isinstance(dec, (ast.Name, ast.Attribute)):
        return _is_jit_ref(dec, unit)
    if isinstance(dec, ast.Call):
        if isinstance(dec.func, (ast.Name, ast.Attribute)):
            if _is_jit_ref(dec.func, unit):
                return True                      # @jax.jit(...)
            d = dotted(dec.func)
            if d in _PARTIAL_DOTTED and dec.args:   # @partial(jax.jit,…)
                first = dec.args[0]
                return isinstance(first, (ast.Name, ast.Attribute)) \
                    and _is_jit_ref(first, unit)
    return False


def _param_names(func: ast.AST) -> Set[str]:
    a = func.args
    names = [p.arg for p in
             list(a.posonlyargs) + list(a.args) + list(a.kwonlyargs)]
    if a.vararg:
        names.append(a.vararg.arg)
    if a.kwarg:
        names.append(a.kwarg.arg)
    return {n for n in names if n not in ("self", "cls")}


def _names_in(node: ast.AST) -> Set[str]:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


def _module_scope_names(unit: SourceUnit) -> Set[str]:
    names: Set[str] = set(unit.import_modules) | set(unit.import_symbols)
    if unit.tree is None:
        return names
    for node in unit.tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            names.add(node.name)
        elif isinstance(node, ast.Assign):
            for t in node.targets:
                names.update(_names_in(t))
        elif isinstance(node, (ast.AnnAssign, ast.AugAssign)):
            names.update(_names_in(node.target))
    return names


class TracePurityPass:
    name = "trace-purity"
    rules = (RULE,)

    def run(self, project: Project) -> Iterable[Finding]:
        cg = _callgraph.CallGraph(project)
        roots: List[ast.AST] = []
        lambda_roots: List[Tuple[ast.Lambda, SourceUnit]] = []
        for unit in project.units:
            if unit.tree is None or unit.path.startswith("tests/"):
                continue
            for node in ast.walk(unit.tree):
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                    if any(_decorator_is_jit(d, unit)
                           for d in node.decorator_list):
                        roots.append(node)
                elif isinstance(node, ast.Call):
                    tgt = _jit_call_target(node, unit)
                    if tgt is None:
                        continue
                    if isinstance(tgt, ast.Lambda):
                        lambda_roots.append((tgt, unit))
                    elif isinstance(tgt, ast.Name):
                        roots.extend(cg.resolve_name(
                            tgt.id, unit, self._enclosing_func(node)))
                    elif isinstance(tgt, ast.Attribute):
                        roots.extend(self._resolve_attr_target(
                            tgt, unit, cg, node))
        reachable = cg.reachable(roots)
        findings: List[Finding] = []
        for key in reachable:
            info = cg.funcs.get(key)
            if info is None or info.unit.path.startswith("tests/"):
                continue
            findings.extend(self._check_function(info.node, info.unit))
        for lam, unit in lambda_roots:
            findings.extend(self._check_function(lam, unit,
                                                 is_lambda=True))
        return findings

    # ------------------------------------------------------------------ #
    @staticmethod
    def _enclosing_func(node: ast.AST) -> Optional[ast.AST]:
        cur = parent(node)
        while cur is not None and not isinstance(
                cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
            cur = parent(cur)
        return cur

    def _resolve_attr_target(self, tgt: ast.Attribute, unit: SourceUnit,
                             cg: _callgraph.CallGraph,
                             site: ast.AST) -> List[ast.AST]:
        """``jax.jit(self._decode_step_fn)`` → the method node."""
        fake = ast.Call(func=tgt, args=[], keywords=[])
        for n in ast.walk(fake):
            n._mxparent = getattr(tgt, "_mxparent", None)  # type: ignore
        return cg.resolve_call(fake, unit, self._enclosing_func(site))

    # ------------------------------------------------------------------ #
    def _check_function(self, func: ast.AST, unit: SourceUnit,
                        is_lambda: bool = False) -> List[Finding]:
        out: List[Finding] = []
        params = _param_names(func) if not is_lambda else \
            {a.arg for a in func.args.args}
        symbol = "<lambda>" if is_lambda else qualname_of(func)
        nodes = (ast.walk(func) if is_lambda else walk_own(func))

        def flag(node: ast.AST, msg: str, severity: str = "error"):
            out.append(Finding(RULE, unit.path, node.lineno, msg,
                               symbol=symbol, severity=severity))

        local_assigns = self._local_bindings(func, is_lambda)
        for node in nodes:
            if not isinstance(node, ast.Call):
                continue
            d = dotted(node.func) or ""
            # .item(): a device→host force that throws under trace
            if isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "item" and not node.args:
                flag(node, "`.item()` inside a traced function — "
                           "device→host force; fails or constant-folds "
                           "under trace")
                continue
            # host casts of traced operands
            if isinstance(node.func, ast.Name) \
                    and node.func.id in ("float", "int", "bool") \
                    and node.args:
                if _names_in(node.args[0]) & params:
                    flag(node, f"host `{node.func.id}()` cast of a "
                               f"traced operand — concretizes the "
                               f"tracer (ConcretizationTypeError or a "
                               f"baked-in constant)")
                continue
            # host clock / host RNG: frozen into the trace
            head = d.split(".")[0] if d else ""
            if head and unit.import_modules.get(head) == "time" \
                    and "." in d:
                flag(node, f"host clock `{d}()` inside a traced "
                           f"function — the timestamp freezes at trace "
                           f"time (and differs per retrace)")
                continue
            if self._is_host_rng(d, head, unit):
                flag(node, f"host RNG `{d}()` inside a traced function "
                           f"— the draw freezes at trace time; use "
                           f"jax.random with a traced key")
                continue
            # numpy materialization of traced values
            if head and unit.import_modules.get(head) == "numpy" \
                    and d.split(".")[-1] in _NP_CAST and node.args:
                if _names_in(node.args[0]) & params:
                    flag(node, f"`{d}()` on a traced operand — forces "
                               f"a host materialization at trace time")
                continue
        # closure-capture hazard: a captured name the enclosing scope
        # keeps rebinding makes the jit cache key (or the baked
        # constant) churn per call — advisory, host-side review needed
        encl = self._enclosing_func(func)
        if encl is not None:
            rebound = self._rebound_in(encl)
            captured = self._free_names(func, params, local_assigns, unit)
            for name, line in sorted(captured.items()):
                if name in rebound:
                    out.append(Finding(
                        RULE, unit.path, line,
                        f"traced closure captures `{name}`, which the "
                        f"enclosing scope rebinds — per-call retrace / "
                        f"stale-constant hazard",
                        symbol=symbol, severity="warn"))
        return out

    @staticmethod
    def _is_host_rng(d: str, head: str, unit: SourceUnit) -> bool:
        if not d or "." not in d:
            return False
        if unit.import_modules.get(head) == "numpy" \
                and d.split(".")[1:2] == ["random"]:
            return True
        return unit.import_modules.get(head) == "random"

    @staticmethod
    def _local_bindings(func: ast.AST, is_lambda: bool) -> Set[str]:
        if is_lambda:
            return set()
        bound: Set[str] = set()
        for node in walk_own(func):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    bound.update(_names_in(t))
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign,
                                   ast.For, ast.withitem)):
                tgt = getattr(node, "target",
                              getattr(node, "optional_vars", None))
                if tgt is not None:
                    bound.update(_names_in(tgt))
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                   ast.ClassDef)):
                bound.add(node.name)
            elif isinstance(node, ast.comprehension):
                bound.update(_names_in(node.target))
            elif isinstance(node, ast.ExceptHandler) and node.name:
                bound.add(node.name)
        return bound

    def _free_names(self, func: ast.AST, params: Set[str],
                    local: Set[str], unit: SourceUnit) -> Dict[str, int]:
        import builtins as _b
        module_names = _module_scope_names(unit)
        free: Dict[str, int] = {}
        for node in walk_own(func):
            if isinstance(node, ast.Name) \
                    and isinstance(node.ctx, ast.Load):
                n = node.id
                if n in params or n in local or n in module_names \
                        or hasattr(_b, n):
                    continue
                free.setdefault(n, node.lineno)
        return free

    @staticmethod
    def _rebound_in(encl: ast.AST) -> Set[str]:
        """Names the enclosing scope assigns more than once (its OWN
        statements — walk_own already excludes the traced function's
        body and other nested defs)."""
        def stored(target: ast.AST) -> Set[str]:
            # ``self.n += 1`` stores into the object, not the name
            return {n.id for n in ast.walk(target)
                    if isinstance(n, ast.Name)
                    and isinstance(n.ctx, ast.Store)}

        counts: Dict[str, int] = {}
        for node in walk_own(encl):
            tgt_names: Set[str] = set()
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    tgt_names.update(stored(t))
            elif isinstance(node, (ast.AugAssign, ast.For)):
                tgt_names.update(stored(node.target))
            for n in tgt_names:
                counts[n] = counts.get(n, 0) + 1
        return {n for n, c in counts.items() if c >= 2}
