"""RTL011 scope-across-await.

Invariant (PR 11's rule, now mechanized): loop-thread ambient scopes
must not leak across awaits. ``trace_scope(ctx)`` and
``ambient_deadline(d)`` install THREAD-scoped state (threading.local)
— on an event loop, every task interleaved at an ``await`` inside the
``with`` body runs with this request's context: its task specs get stamped with the
wrong trace parent and the wrong deadline, the exact leak class PR 11
documented in the serve proxy (which now deliberately wraps only the
synchronous submission window).

Flagged: inside any ``async def``, a ``with <scope>(...):`` whose body
contains a suspension point — ``await``, ``async for``, ``async with``,
or a ``yield`` (async-generator suspension hands the loop to the
consumer with the scope still installed).

Fix by binding the value before the await (stamp the spec, capture the
deadline) and scoping only the synchronous section, or by moving the
work to a dedicated thread (the proxy's per-stream feeder holds scopes
legally: the thread serves exactly one request). A deliberate span is
suppressed with ``# raylint: disable=scope-across-await`` naming why
the loop is single-tenant there.
"""

from __future__ import annotations

import ast
from typing import Iterable, Optional

from tools.raylint.core import (
    Check,
    Diagnostic,
    Module,
    Project,
    dotted_name,
    register_check,
)

DEFAULT_SCOPE_PATHS = ["ray_tpu/"]
# leaf callable names that install thread-scoped ambient state; new env
# scopes register here (raylint.toml [tool.raylint.scope-across-await])
DEFAULT_AMBIENT_SCOPES = [
    "trace_scope",
    "ambient_deadline",
]


def iter_own_nodes(fn: ast.AST):
    """Every node in a function's own body, excluding nested
    function/class bodies (they are analysed as their own functions)."""
    stack = list(fn.body)
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef, ast.Lambda)):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def first_suspension(body) -> Optional[ast.AST]:
    """The first suspension point in a statement list, ignoring nested
    function/class bodies (a nested def suspends its own caller, not
    this frame). Yield counts: in an async def it is an async-generator
    suspension."""
    stack = list(body)
    while stack:
        node = stack.pop(0)
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef, ast.Lambda)):
            continue
        if isinstance(node, (ast.Await, ast.AsyncFor, ast.AsyncWith,
                             ast.Yield, ast.YieldFrom)):
            return node
        stack.extend(ast.iter_child_nodes(node))
    return None


@register_check
class ScopeAcrossAwaitCheck(Check):
    name = "scope-across-await"
    check_id = "RTL011"
    description = ("thread-scoped ambient scope (trace_scope / "
                   "ambient_deadline / env scope) entered in a "
                   "coroutine and spanning an await — the scope leaks "
                   "to every task interleaved on the loop")

    def __init__(self, options: dict):
        super().__init__(options)
        self.scope_paths = tuple(options.get(
            "scope-paths", DEFAULT_SCOPE_PATHS))
        self.ambient_scopes = set(options.get(
            "ambient-scopes", DEFAULT_AMBIENT_SCOPES))

    def _scope_name(self, expr: ast.AST) -> Optional[str]:
        if not isinstance(expr, ast.Call):
            return None
        target = dotted_name(expr.func)
        if target is None:
            return None
        leaf = target.rsplit(".", 1)[-1]
        return leaf if leaf in self.ambient_scopes else None

    def run(self, project: Project) -> Iterable[Diagnostic]:
        for mod in project.target_modules():
            if not any(mod.relpath.startswith(p)
                       for p in self.scope_paths):
                continue
            yield from self._run_module(mod)

    def _run_module(self, mod: Module) -> Iterable[Diagnostic]:
        for cls, fn in mod.functions():
            if not isinstance(fn, ast.AsyncFunctionDef):
                continue
            qual = f"{cls + '.' if cls else ''}{fn.name}"
            for node in iter_own_nodes(fn):
                if not isinstance(node, ast.With):
                    continue
                for item in node.items:
                    scope = self._scope_name(item.context_expr)
                    if scope is None:
                        continue
                    susp = first_suspension(node.body)
                    if susp is None:
                        continue
                    what = ("await" if isinstance(susp, ast.Await)
                            else type(susp).__name__.lower())
                    yield Diagnostic(
                        self.check_id, self.name, mod.relpath,
                        node.lineno, node.col_offset,
                        f"ambient scope {scope}(...) in coroutine "
                        f"{qual} spans a suspension point ({what} at "
                        f"line {susp.lineno}) — thread-scoped state "
                        "leaks to every task interleaved on this loop; "
                        "bind the value before the await and scope "
                        "only the synchronous section")
