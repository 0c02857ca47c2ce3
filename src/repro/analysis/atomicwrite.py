"""atomic-write: results artifacts are swapped in, never torn.

Every results artifact in the repo — bench json, perf history, trace
exports, the HTML ops report, checkpoints — is written tmp +
``os.replace`` so a concurrent reader (the perf-regression gate, a
collect-merge parent, a dashboard tailing the file) never observes a
half-written file, and a crashed writer never corrupts the previous
good copy. "Under a ``results/`` path" is not statically decidable
(paths arrive via ``--out`` flags), so this pass enforces the
discipline structurally: any write-mode ``open()`` must either

* live in a function that also calls ``os.replace`` (it *is* the
  atomic helper — e.g. ``repro.util.atomic_write_text``), or
* target a visibly-temporary path (a name containing ``tmp`` or a
  literal containing ``.tmp``) — the tmp half of the pattern when the
  replace lives a call away.

Append-mode streams (``"a"``) are exempt: JSONL appenders tolerate
torn trailing lines by contract (readers drop them), which is the
right discipline for incremental logs.
"""
from __future__ import annotations

import ast
from typing import Iterable, List, Optional, Tuple

from repro.analysis.framework import (Finding, LintPass, ModuleContext,
                                      dotted_name, register)


def _write_mode(call: ast.Call) -> Optional[str]:
    """The literal mode string when it opens for write/create."""
    mode_node: Optional[ast.AST] = None
    if len(call.args) >= 2:
        mode_node = call.args[1]
    for kw in call.keywords:
        if kw.arg == "mode":
            mode_node = kw.value
    if isinstance(mode_node, ast.Constant) \
            and isinstance(mode_node.value, str):
        m = mode_node.value
        if "w" in m or "x" in m:
            return m
    return None


def _tmpish(node: ast.AST) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and "tmp" in sub.id.lower():
            return True
        if isinstance(sub, ast.Attribute) and "tmp" in sub.attr.lower():
            return True
        if isinstance(sub, ast.Constant) and isinstance(sub.value, str) \
                and ".tmp" in sub.value:
            return True
    return False


class _Scopes(ast.NodeVisitor):
    """Each ``open()`` call paired with its nearest enclosing scope."""

    def __init__(self, tree: ast.Module):
        self.stack: List[ast.AST] = [tree]
        self.calls: List[Tuple[ast.Call, ast.AST]] = []
        self.visit(tree)

    def _scoped(self, node):
        self.stack.append(node)
        self.generic_visit(node)
        self.stack.pop()

    visit_FunctionDef = _scoped
    visit_AsyncFunctionDef = _scoped
    visit_Lambda = _scoped

    def visit_Call(self, node: ast.Call):
        self.calls.append((node, self.stack[-1]))
        self.generic_visit(node)


@register
class AtomicWrite(LintPass):
    name = "atomic-write"
    description = ("write-mode open() without tmp + os.replace in scope "
                   "— readers can observe a torn artifact")
    hint = ("use repro.util.atomic_write_text/_json, or write to a "
            "*.tmp.<pid> path and os.replace it into place")

    def findings(self, ctx: ModuleContext) -> Iterable[Finding]:
        scopes = _Scopes(ctx.tree)
        replaced = {
            id(scope) for call, scope in scopes.calls
            if dotted_name(call.func, ctx.imports) == "os.replace"}
        for call, scope in scopes.calls:
            if dotted_name(call.func, ctx.imports) not in ("open",
                                                           "io.open"):
                continue
            mode = _write_mode(call)
            if mode is None:
                continue
            if id(scope) in replaced:
                continue
            if call.args and _tmpish(call.args[0]):
                continue
            yield self.finding(
                ctx, call,
                f'open(..., "{mode}") is not atomic — a concurrent '
                f"reader can observe a torn file and a crash destroys "
                f"the previous good copy")
