"""CKPT401: snapshot-immutability.

The paper's lazy async snapshot premise: once device state is captured
into a pinned host-cache reservation, those bytes are immutable until the
flush lane has drained them — any in-place mutation races the writer and
silently corrupts the checkpoint (no crash, wrong bytes on disk).

The rule taints every name bound to a ``reserve(...)`` result (or a
``.buf``/``.data``/``view()`` of one) and flags subscript stores or
augmented assignments through tainted names. Sanctioned lanes — the
capture path itself — are exempt: all of ``core/state_provider.py``
(providers own the capture protocol) and ``_copy_shard`` in
``core/engine.py`` (the copy lane's writes into the reservation).
"""

from __future__ import annotations

import ast
from typing import Iterator, List, Set

from .linter import Finding, Project, Rule, SourceModule, call_name, \
    dotted

SANCTIONED_MODULES = ("core/state_provider.py",)
SANCTIONED_FUNCTIONS = {"_copy_shard"}
_RESERVATION_ATTRS = ("buf", "data", "view", "memoryview")


def _reservation_taint(fn: ast.AST) -> Set[str]:
    tainted: Set[str] = set()
    assigns = [n for n in ast.walk(fn) if isinstance(n, ast.Assign)]
    for _ in range(3):
        changed = False
        for node in assigns:
            if not _value_tainted(node.value, tainted):
                continue
            for tgt in node.targets:
                if isinstance(tgt, ast.Name) and tgt.id not in tainted:
                    tainted.add(tgt.id)
                    changed = True
        if not changed:
            break
    return tainted


def _value_tainted(expr: ast.expr, tainted: Set[str]) -> bool:
    for node in ast.walk(expr):
        if isinstance(node, ast.Call) and call_name(node) == "reserve":
            return True
        if isinstance(node, ast.Name) and node.id in tainted:
            return True
        if isinstance(node, ast.Attribute) and \
                node.attr in _RESERVATION_ATTRS:
            base = dotted(node.value)
            last = base.rsplit(".", 1)[-1] if base else ""
            if last in tainted or "reservation" in last.lower() or \
                    last in ("res", "rsv"):
                return True
    return False


def _base_name(expr: ast.expr) -> str:
    """Leftmost-ish name a subscript/attribute store goes through."""
    cur = expr
    while isinstance(cur, (ast.Subscript, ast.Attribute)):
        cur = cur.value
    if isinstance(cur, ast.Name):
        return cur.id
    return ""


class SnapshotMutationRule(Rule):
    id = "CKPT401"
    summary = ("in-place mutation of a pinned snapshot reservation "
               "outside the capture lane")

    def check(self, module: SourceModule,
              project: Project) -> Iterator[Finding]:
        if module.rel.endswith(SANCTIONED_MODULES):
            return iter(())
        findings: List[Finding] = []
        for fn in ast.walk(module.tree):
            if not isinstance(fn, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                continue
            if fn.name in SANCTIONED_FUNCTIONS:
                continue
            tainted = _reservation_taint(fn)
            if not tainted:
                continue
            for node in ast.walk(fn):
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)) and \
                        node is not fn:
                    continue  # nested fns get their own taint pass
                targets: List[ast.expr] = []
                if isinstance(node, ast.Assign):
                    targets = [t for t in node.targets
                               if isinstance(t, ast.Subscript)]
                elif isinstance(node, ast.AugAssign) and \
                        isinstance(node.target, ast.Subscript):
                    targets = [node.target]
                for tgt in targets:
                    base = _base_name(tgt)
                    if base and base in tainted:
                        findings.append(Finding(
                            rule=self.id, path=module.rel,
                            line=node.lineno, col=node.col_offset,
                            message=(f"store into reservation-backed "
                                     f"buffer {base!r}; staged bytes "
                                     f"are immutable between capture "
                                     f"and flush")))
        return iter(findings)


def RULES() -> List[Rule]:
    return [SnapshotMutationRule()]
