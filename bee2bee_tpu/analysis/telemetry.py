"""Pass family 4: telemetry hygiene (ML-T*).

Span and metric NAMES are the aggregation keys of the whole observability
layer: the tracer groups percentiles per span name, and every distinct
metric name (or label value) is one Prometheus series forever. A name
built per request — ``span(f"gen.{rid}")`` — silently defeats the
per-name aggregation and grows the series table without bound (label/
cardinality explosion). Request-varying data belongs in span ATTRS or
metric LABELS (which are themselves chosen from bounded sets), never in
the name.

- ML-T001 — the name argument of a ``span(...)`` / ``phase(...)`` /
  ``part(...)`` / ``annotate(...)`` / ``prog_scope(...)`` /
  ``counter(...)`` / ``gauge(...)`` / ``histogram(...)`` call is built
  dynamically: an f-string, a ``%`` / ``+`` expression, or ``.format()``.
  Plain variables pass (a forwarding helper like the scheduler's
  ``_phase`` decorator is fine — the literal lives at ITS call site and
  is checked there, which is why ``_phase`` is a name call too).

Scope: the whole package — telemetry calls live in engine/, meshnet/,
services/, web/ and api.py alike.
"""

from __future__ import annotations

import ast

# call targets whose first argument is a span/metric/phase NAME
_NAME_CALLS = frozenset(
    {"span", "phase", "_phase", "part", "annotate", "prog_scope",
     "counter", "gauge", "histogram"}
)


def _last_attr(func: ast.AST) -> str:
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return ""


def _dynamic_kind(expr: ast.AST) -> str | None:
    """How the expression builds a string at runtime, or None when it
    doesn't (constants and plain variables both pass)."""
    if isinstance(expr, ast.JoinedStr):
        return "f-string"
    if isinstance(expr, ast.BinOp) and isinstance(expr.op, (ast.Add, ast.Mod)):
        return "concatenation" if isinstance(expr.op, ast.Add) else "%-format"
    if isinstance(expr, ast.Call) and _last_attr(expr.func) == "format":
        return ".format() call"
    return None


class TelemetryPass:
    family = "telemetry"
    rules = {
        "ML-T001": "span/metric name built dynamically (f-string/%/+/format)",
    }

    def applies(self, path: str) -> bool:
        return True  # telemetry calls live everywhere in the package

    def run(self, ctx) -> list:
        findings: list = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            if _last_attr(node.func) not in _NAME_CALLS:
                continue
            name_arg = None
            if node.args:
                name_arg = node.args[0]
            else:
                for kw in node.keywords:
                    if kw.arg == "name":
                        name_arg = kw.value
                        break
            if name_arg is None:
                continue
            kind = _dynamic_kind(name_arg)
            if kind is None:
                continue
            findings.append(
                ctx.finding(
                    "ML-T001",
                    name_arg,
                    f"span/metric name built via {kind} — names are "
                    "aggregation keys and every distinct one is a series "
                    "forever",
                    hint="use a literal dotted constant name; put the "
                    "varying part in span attrs / metric labels",
                )
            )
        return findings
