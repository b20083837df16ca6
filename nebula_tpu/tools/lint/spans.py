"""span-registry — every ``tracing.span("...")`` / ``start_trace("...")``
/ ``annotate("...")`` / ``emit("...")`` uses a LITERAL dotted name from the single
``SPAN_NAMES`` registry (common/tracing.py), and no dead registry
entries remain.

Mirrors the flag-registry contract: dynamic names (``span(name_var)``)
would make traces un-greppable and dashboards unstable, so the literal
rule is enforced package-wide; ``SPAN_NAMES`` is where reviewers see the
whole vocabulary at once.  The registry itself must exist exactly once.
"""
from __future__ import annotations

import ast
from typing import List, Optional, Tuple

from .core import PackageContext, Violation, dotted, enclosing_symbol, \
    qualname_map

_CALLS = ("span", "start_trace", "annotate", "emit")


def _literal(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _registry_names(node: ast.AST) -> Optional[List[str]]:
    """Names from a SPAN_NAMES = (tuple|list|set of str literals)."""
    if not isinstance(node, (ast.Tuple, ast.List, ast.Set)):
        return None
    out = []
    for el in node.elts:
        name = _literal(el)
        if name is None:
            return None
        out.append(name)
    return out


def check_span_registry(ctx: PackageContext) -> List[Violation]:
    registries: List[Tuple[str, int, List[str]]] = []
    uses: List[Tuple[Optional[str], str, int, str]] = []
    out: List[Violation] = []

    for mod in ctx.modules:
        qmap = qualname_map(mod.tree)

        def walk(node, stack):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, ast.Assign):
                    for tgt in child.targets:
                        if isinstance(tgt, ast.Name) \
                                and tgt.id == "SPAN_NAMES":
                            names = _registry_names(child.value)
                            if names is not None:
                                registries.append((mod.rel, child.lineno,
                                                   names))
                if isinstance(child, ast.Call):
                    d = dotted(child.func) or ""
                    parts = d.split(".")
                    if parts[-1] in _CALLS and "tracing" in parts[:-1]:
                        name = _literal(child.args[0]) if child.args \
                            else None
                        uses.append((name, mod.rel, child.lineno,
                                     enclosing_symbol(qmap, stack)))
                new_stack = stack + [child] if isinstance(
                    child, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.ClassDef)) else stack
                walk(child, new_stack)

        walk(mod.tree, [])

    if not uses and not registries:
        return out
    if len(registries) > 1:
        for rel, line, _ in registries[1:]:
            out.append(Violation(
                "span-registry", rel, line, "<module>",
                "second SPAN_NAMES registry — span names must come from "
                f"ONE registry (first at {registries[0][0]}:"
                f"{registries[0][1]})"))
    known = set(registries[0][2]) if registries else set()

    for name, rel, line, sym in uses:
        if name is None:
            out.append(Violation(
                "span-registry", rel, line, sym,
                "span name must be a literal dotted string from the "
                "SPAN_NAMES registry (dynamic names break trace "
                "dashboards and grep)"))
        elif not registries:
            out.append(Violation(
                "span-registry", rel, line, sym,
                f"span {name!r} used but no SPAN_NAMES registry exists "
                "in the package"))
        elif name not in known:
            out.append(Violation(
                "span-registry", rel, line, sym,
                f"span name {name!r} is not in the SPAN_NAMES registry "
                f"({registries[0][0]}:{registries[0][1]}) — add it "
                "there first"))

    used_names = {u[0] for u in uses if u[0] is not None}
    if registries:
        rel, line, names = registries[0]
        for name in names:
            if name not in used_names:
                out.append(Violation(
                    "span-registry", rel, line, "<module>",
                    f"span name {name!r} is registered but never used "
                    "by a tracing.span/start_trace call — delete it or "
                    "instrument the seam"))
    return out
