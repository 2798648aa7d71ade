"""Telemetry rules: metric label matrices pre-declared, spans paired.

PR 3's /metrics plane renders only label sets it has SEEN — a counter
incremented lazily per edge/class materializes series one event at a
time, so dashboards and alerts watching the full matrix silently miss the
series that hasn't fired yet (the PR 7 shed matrix was pre-declared for
exactly this reason). PL501 requires every labeled counter family to
`declare()` its matrix somewhere in the linted tree. PL502 keeps span
probes exception-safe: `telemetry.span()` (or `telemetry.startup()`, the
same probe with a counter) outside a `with` risks an
__enter__ with no __exit__ on the error path (unbalanced spans corrupt
the bubble math); cross-thread pairs belong to `telemetry.record()`.
"""
from __future__ import annotations

import ast
from typing import Dict, Iterator, Optional, Set

from .lint import Finding, Module, Rule, SEVERITY_ERROR, SEVERITY_WARNING

# Counter.inc() kwargs that are NOT labels
_NON_LABEL_KWARGS = frozenset(("amount",))


def _counter_metric_name(node: ast.Call) -> Optional[str]:
    """Prometheus family name when `node` constructs a Counter:
    `reg.counter("name", ...)` or `reg.get_or_create(Counter, "name", ...)`."""
    func = node.func
    if not isinstance(func, ast.Attribute):
        return None
    if func.attr == "counter" and node.args \
            and isinstance(node.args[0], ast.Constant) \
            and isinstance(node.args[0].value, str):
        return node.args[0].value
    if func.attr == "get_or_create" and len(node.args) >= 2 \
            and isinstance(node.args[0], ast.Name) \
            and node.args[0].id == "Counter" \
            and isinstance(node.args[1], ast.Constant):
        return node.args[1].value
    return None


class UndeclaredMetricLabels(Rule):
    id = "PL501"
    name = "undeclared-metric-labels"
    severity = SEVERITY_WARNING
    fix_hint = ("declare() the label matrix where the counter's label "
                "domain becomes known (per-edge at context init, "
                "class x reason at controller construction)")
    rationale = ("a labeled counter that never declare()s its matrix "
                 "materializes series one increment at a time — scrapers "
                 "and alerts miss the series that hasn't fired yet")

    def __init__(self):
        # cross-file state (collect runs over every module first):
        # identifier (variable/attribute the counter is bound to) ->
        # family name; families with a declare() anywhere; identifiers
        # declare()d anywhere (when the binding couldn't be resolved)
        self._families: Dict[str, str] = {}
        self._declared_families: Set[str] = set()
        self._declared_idents: Set[str] = set()

    @staticmethod
    def _ident(node: ast.AST) -> Optional[str]:
        if isinstance(node, ast.Name):
            return node.id
        if isinstance(node, ast.Attribute):
            return node.attr
        return None

    def collect(self, module: Module) -> None:
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Assign) \
                    and isinstance(node.value, ast.Call):
                family = _counter_metric_name(node.value)
                if family is not None:
                    for t in node.targets:
                        ident = self._ident(t)
                        if ident is not None:
                            self._families[ident] = family
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "declare":
                ident = self._ident(node.func.value)
                if ident is not None:
                    self._declared_idents.add(ident)
                    if ident in self._families:
                        self._declared_families.add(self._families[ident])

    def check(self, module: Module) -> Iterator[Finding]:
        # resolve identifier->family declares recorded before the binding
        # was seen (collect order is file order, bindings cross files)
        for ident in self._declared_idents:
            if ident in self._families:
                self._declared_families.add(self._families[ident])
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call) \
                    or not isinstance(node.func, ast.Attribute) \
                    or node.func.attr != "inc":
                continue
            labels = [k.arg for k in node.keywords
                      if k.arg and k.arg not in _NON_LABEL_KWARGS]
            if not labels:
                continue
            ident = self._ident(node.func.value)
            if ident is None or ident not in self._families:
                continue     # not a counter we saw constructed
            family = self._families[ident]
            if family in self._declared_families \
                    or ident in self._declared_idents:
                continue
            yield self.finding(
                module, node,
                f"labeled increment of {family} "
                f"({', '.join(sorted(labels))}) but the family never "
                f"declare()s its label matrix")


class UnpairedSpan(Rule):
    id = "PL502"
    name = "unpaired-span"
    severity = SEVERITY_ERROR
    fix_hint = ("use `with telemetry.span(...)` so the exit stamp rides "
                "the exception path too; for cross-thread pairs record "
                "both stamps and call telemetry.record()")
    rationale = ("a span entered outside `with` leaks its begin stamp on "
                 "any error path — unbalanced spans corrupt busy/idle "
                 "attribution in trace_report")

    def check(self, module: Module) -> Iterator[Finding]:
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call) \
                    or not isinstance(node.func, ast.Attribute) \
                    or node.func.attr not in ("span", "startup"):
                continue
            parent = module.parent(node)
            if isinstance(parent, ast.withitem):
                continue
            # `return rec.span(...)` / `span(...)` as a factory return
            # value is the recorder's own API surface, not a probe site
            if isinstance(parent, ast.Return):
                continue
            yield self.finding(
                module, node,
                "telemetry span created outside a `with` block")


RULES = (UndeclaredMetricLabels, UnpairedSpan)
