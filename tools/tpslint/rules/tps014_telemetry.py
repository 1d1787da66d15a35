"""TPS014 — telemetry-coverage check.

Two registries back the observability layer, and both are enforced here
(the TPS007/TPS012 pattern applied to telemetry):

1. **Name registry** — every ``span("...")`` / ``start_span("...")`` /
   ``completed_span("...")`` / ``registry.counter("...")`` /
   ``.gauge("...")`` / ``.histogram("...")`` call site must name an entry of ``telemetry/names.NAMES``: a typo'd
   span or metric name otherwise records into a parallel universe — the
   dashboards and traces built on the registered name silently show
   nothing. (The runtime ALSO validates, but only on the paths a test
   happens to execute; the lint covers every site statically.)

2. **Flight fault coverage** — ``telemetry/names.FLIGHT_FAULT_POINTS``
   must cover every key of ``resilience/faults.FAULT_POINTS``: a fault
   point with no flight-recorder event site means a fired fault of that
   kind leaves no post-mortem trace. Checked when linting
   ``telemetry/names.py`` itself (both sides parsed from their ASTs —
   tpslint stays stdlib-only).

The reverse directions — every registered name has at least one call
site, and every FLIGHT_FAULT_POINTS entry is a real fault point — are
repo-level properties enforced by the meta-tests in
``tests/test_tpslint.py`` built on this module's helpers.

Dynamic name arguments (``span(name)``) are not statically checkable
and stay silent, like TPS007/TPS012.
"""

from __future__ import annotations

import ast
import functools
from pathlib import Path

from ..context import terminal_name
from .base import Rule, register
from .tps012_fault_registry import registered_fault_points

#: call shapes that take a telemetry NAME as their first argument
_SPAN_HOOKS = ("span", "start_span", "completed_span")
_METRIC_HOOKS = ("counter", "gauge", "histogram")
#: receivers the repo binds the span API / metrics registry to
_SPAN_RECEIVERS = ("telemetry", "_telemetry", "spans", "_spans")
_METRIC_RECEIVERS = ("registry", "_registry", "_REG", "metrics",
                     "_metrics")

_NAMES_REL = Path("mpi_petsc4py_example_tpu") / "telemetry" / "names.py"


@functools.lru_cache(maxsize=1)
def _names_module_tree():
    path = Path(__file__).resolve().parents[3] / _NAMES_REL
    try:
        return ast.parse(path.read_text(encoding="utf-8"))
    except (OSError, SyntaxError):
        return None


def _assigned(tree, target: str):
    for node in ast.walk(tree):
        if (isinstance(node, ast.Assign)
                and any(isinstance(t, ast.Name) and t.id == target
                        for t in node.targets)):
            return node.value
    return None


@functools.lru_cache(maxsize=1)
def registered_telemetry_names() -> frozenset:
    """String keys of ``telemetry/names.NAMES``, parsed from the module
    AST. Empty when unreadable — the rule then stays silent and the
    coverage meta-test fails loudly instead."""
    tree = _names_module_tree()
    if tree is None:
        return frozenset()
    value = _assigned(tree, "NAMES")
    if isinstance(value, ast.Dict):
        return frozenset(k.value for k in value.keys
                         if isinstance(k, ast.Constant)
                         and isinstance(k.value, str))
    return frozenset()


@functools.lru_cache(maxsize=1)
def flight_fault_points() -> frozenset:
    """``telemetry/names.FLIGHT_FAULT_POINTS``, parsed from the AST."""
    tree = _names_module_tree()
    if tree is None:
        return frozenset()
    value = _assigned(tree, "FLIGHT_FAULT_POINTS")
    if isinstance(value, (ast.Tuple, ast.List)):
        return frozenset(e.value for e in value.elts
                         if isinstance(e, ast.Constant)
                         and isinstance(e.value, str))
    return frozenset()


def telemetry_name_sites(tree):
    """Yield ``(name_or_None, call_node)`` for every span/metric call
    site in ``tree`` — ``None`` when the name argument is dynamic."""
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call) and node.args):
            continue
        func = node.func
        hook = terminal_name(func)
        if hook in _SPAN_HOOKS:
            # module-qualified only (_telemetry.span / telemetry.span):
            # a bare function that happens to be called span() is
            # somebody else's API
            if not (isinstance(func, ast.Attribute)
                    and terminal_name(func.value) in _SPAN_RECEIVERS):
                continue
        elif hook in _METRIC_HOOKS:
            if not (isinstance(func, ast.Attribute)
                    and terminal_name(func.value) in _METRIC_RECEIVERS):
                continue
        else:
            continue
        arg = node.args[0]
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            yield arg.value, node
        else:
            yield None, node


@register
class TelemetryCoverageRule(Rule):
    id = "TPS014"
    name = "telemetry-coverage"
    description = ("span()/registry.counter()/gauge()/histogram() call "
                   "sites must name an entry of telemetry/names.NAMES "
                   "(a typo'd name records into a parallel universe), "
                   "and FLIGHT_FAULT_POINTS must cover every "
                   "resilience/faults.FAULT_POINTS key")

    def check(self, module):
        known = registered_telemetry_names()
        if not known:
            return
        for name, node in telemetry_name_sites(module.tree):
            if name is not None and name not in known:
                yield self.finding(
                    node,
                    f"telemetry name {name!r} is not registered in "
                    "telemetry/names.NAMES — the span/metric would "
                    "record under an unregistered name; register it or "
                    "fix the spelling")
        # flight coverage: checked once, on the names module itself
        if str(module.path).replace("\\", "/").endswith(
                "telemetry/names.py"):
            missing = registered_fault_points() - flight_fault_points()
            if missing:
                yield self.finding(
                    module.tree,
                    "FLIGHT_FAULT_POINTS is missing fault point(s) "
                    f"{sorted(missing)} registered in resilience/faults."
                    "FAULT_POINTS — every fault point must have a "
                    "flight-recorder event site (telemetry.flight."
                    "record_fault covers the listed points)")
