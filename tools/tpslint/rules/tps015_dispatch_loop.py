"""TPS015 — dispatch-in-host-loop advisory (warn tier).

A compiled-program launch costs a fixed host->device dispatch latency
that no amount of on-chip speed amortizes.  A HOST-side ``for``/``while`` whose body
launches a compiled program per iteration multiplies that latency by the
trip count — the exact pathology the fused megasolve programs
(solvers/megasolve.py) remove by moving the outer recurrence into the
device program as a ``lax.while_loop``.

The check: for every host-side loop (a ``for``/``while`` statement not
inside a traced jit/shard_map/pallas context), look at each call in its
body and flag the loop when the call either

* invokes a compiled program DIRECTLY — the called name's reaching-defs
  provenance is a ``build_*program*`` factory call (``prog = \
build_ksp_program(...)`` ... ``prog(...)`` in a loop), or
* resolves through the :class:`~tools.tpslint.program.ProgramIndex`
  call graph to a function that TRANSITIVELY performs such an
  invocation (``self.solve(...)`` -> ``KSP._solve_impl`` ->
  ``prog(...)``), including one attribute hop through a ``self.<attr> =
  Class(...)`` constructor assignment (``self.inner.solve(...)`` — the
  RefinedKSP outer-loop shape).

Advisory only (``severity = "warn"``): some host loops over dispatches
are legitimate — retry/escalation ladders re-dispatch by design, chunked
``-ksp_batch_limit`` launches exist to fit VMEM, and the unfused
fallback paths remain load-bearing for configurations megasolve does not
cover.  The CI ``--warn-budget`` pins the COUNT of such sites so new
host-driven outer loops are a conscious choice (route through
``-ksp_megasolve`` where a fused program exists).  Dynamic callees the
index cannot resolve stay silent, like TPS008.
"""

from __future__ import annotations

import ast

from ..context import FUNCTION_NODES, qualifier_chain, terminal_name
from .base import Rule, register

#: compiled-program factory spellings: the explicit set plus the
#: build_*program* naming convention (krylov/megasolve/eps builders)
_BUILDER_NAMES = frozenset({
    "build_ksp_program", "build_ksp_program_many",
    "build_megasolve_program", "build_megasolve_program_many",
})


def _is_builder(func_expr) -> bool:
    name = terminal_name(func_expr)
    if name is None:
        return False
    return (name in _BUILDER_NAMES
            or (name.lstrip("_").startswith("build_")
                and "program" in name))


def _shallow_calls(nodes):
    """Every Call under ``nodes`` excluding nested def/class bodies
    (their calls run when THEY are called, not per loop iteration)."""
    stack = list(nodes)
    while stack:
        n = stack.pop()
        if isinstance(n, FUNCTION_NODES + (ast.ClassDef,)):
            continue
        if isinstance(n, ast.Call):
            yield n
        stack.extend(ast.iter_child_nodes(n))


def _invokes_program(index, module, call) -> bool:
    """Does this call site execute a compiled program? Either the
    immediate ``build_*program*(...)(args)`` shape or a name whose
    reaching-defs provenance is a builder call."""
    f = call.func
    if isinstance(f, ast.Call):
        return _is_builder(f.func)
    if isinstance(f, ast.Name):
        val = index.resolve_local_value(module, f)
        return isinstance(val, ast.Call) and _is_builder(val.func)
    return False


def _resolve(index, module, call):
    """``index.resolve_call`` plus ONE attribute hop for
    ``self.<attr>.method(...)`` where ``self.<attr> = Class(...)`` is
    assigned in the enclosing class (the RefinedKSP ``self.inner.solve``
    shape) — conservative: a unique constructor assignment only."""
    rec = index.resolve_call(module, call)
    if rec is not None:
        return rec
    func = call.func
    if not isinstance(func, ast.Attribute):
        return None
    chain = qualifier_chain(func)
    if not (chain and len(chain) == 2 and chain[0] in ("self", "cls")):
        return None
    cls_node = index._enclosing_class(module, call)
    entry = index.module_for(module.path)
    if cls_node is None or entry is None:
        return None
    ctor_names = set()
    for n in ast.walk(cls_node):
        if not (isinstance(n, ast.Assign)
                and isinstance(n.value, ast.Call)):
            continue
        for t in n.targets:
            if (isinstance(t, ast.Attribute) and t.attr == chain[1]
                    and isinstance(t.value, ast.Name)
                    and t.value.id == "self"):
                cname = terminal_name(n.value.func)
                if cname is not None:
                    ctor_names.add(cname)
    if len(ctor_names) != 1:
        return None                   # ambiguous/dynamic attribute
    cname = ctor_names.pop()
    rec = entry.symbols.get(f"{cname}.{func.attr}")
    if rec is not None:
        return rec
    imp = entry.imports.get(cname)
    if imp is None:
        return None
    base, sym = imp
    if sym is None:
        return None
    target = index._lookup_module(base)
    if target is None:
        return None
    return target.symbols.get(f"{sym}.{func.attr}")


def _dispatch_chain(index, rec, stack):
    """``None`` or the hop list down to a compiled-program invocation,
    memoized on the index (source-coordinate keys, like the TPS008 sync
    summaries)."""
    memo = index.__dict__.setdefault("_tps015_memo", {})
    key = index._node_key(rec)
    if key in memo:
        return memo[key]
    if key in stack:
        return None                   # cycle: judged by the other hops
    stack = stack | {key}
    module = rec.entry.analysis
    result = None
    for call in _shallow_calls(rec.node.body):
        if _invokes_program(index, module, call):
            result = [f"`{rec.qualname}` ({rec.path}:{call.lineno}) "
                      "invokes a compiled program"]
            break
        callee = _resolve(index, module, call)
        if callee is None or callee.node is rec.node:
            continue
        sub = _dispatch_chain(index, callee, stack)
        if sub is not None:
            result = ([f"`{rec.qualname}` ({rec.path}:{call.lineno}) "
                       f"calls `{callee.qualname}`"] + sub)
            break
    memo[key] = result
    return result


@register
class DispatchInHostLoopRule(Rule):
    id = "TPS015"
    name = "dispatch-in-host-loop"
    description = ("a host-side for/while loop whose body launches a "
                   "compiled program each iteration (directly or through "
                   "the call graph) — per-iteration dispatch latency the "
                   "fused megasolve programs exist to remove")
    severity = "warn"

    def check(self, module):
        index = module.program
        if index is None:
            return
        traced = {id(ctx.node) for ctx in module.contexts}
        for node in ast.walk(module.tree):
            if not isinstance(node, (ast.For, ast.AsyncFor, ast.While)):
                continue
            if self._in_traced(module, node, traced):
                continue
            body = list(node.body) + list(node.orelse)
            for call in _shallow_calls(body):
                chain = None
                if _invokes_program(index, module, call):
                    chain = ["the loop body invokes the compiled "
                             "program directly"]
                else:
                    callee = _resolve(index, module, call)
                    if callee is not None:
                        chain = _dispatch_chain(index, callee, set())
                if chain is not None:
                    yield self.finding(
                        node,
                        "host-side loop dispatches a compiled program "
                        f"per iteration (line {call.lineno}: "
                        f"`{ast.unparse(call.func)}`) — "
                        + " -> ".join(chain) +
                        "; per-iteration launch latency multiplies by "
                        "the trip count — fuse the recurrence into the "
                        "device program (-ksp_megasolve / "
                        "lax.while_loop) where a fused form exists")
                    break             # one finding per loop

    @staticmethod
    def _in_traced(module, node, traced) -> bool:
        cur = node
        while cur is not None:
            if id(cur) in traced:
                return True
            cur = module.parents.get(cur)
        return False
