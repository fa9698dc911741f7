"""SKY007: tracing spans must be closed.

A span opened with `tracing.span(...)` or `tracing.start_span(...)`
records its Chrome-trace event only on `end()`, and a loop phase
opened with `tracing.phase(...)` reaches its accumulator and closes
its profiler annotation only on exit — a leaked span is a
silent hole in the merged trace (the request "disappears" mid-flight)
and, at volume, an unbounded pile of never-recorded Span objects. The
rule enforces the tracing module's own contract at every open site in
non-test code:

  - `with tracing.span(...):` / `with tracing.phase(...):` — closed
    by `__exit__`; always clean.
  - `sp = tracing.start_span(...)` + `sp.end()` inside a `finally`
    in the same function — clean (the manual-lifetime idiom).
  - `sp.end()` NOT under a `finally` — finding: any exception between
    open and close leaks the span.
  - result discarded (`tracing.span(...)` as a bare statement) or
    stored where the checker cannot see the close (attribute,
    subscript, tuple target) — finding.

Passing the freshly opened span to another call or returning it
transfers ownership and is out of scope (a factory is not a leak).
`tracing.record_span(...)` — the retroactive already-measured-interval
API — creates no open span and is exempt by construction.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Set, Tuple

from skypilot_tpu.analysis import core

_OPENERS = ('span', 'start_span', 'phase')


def _is_test_path(path: str) -> bool:
    return path.startswith('tests/') or '/tests/' in path or \
        path.split('/')[-1].startswith('test_')


@core.register
class SpanDisciplineChecker(core.Checker):
    rule = 'SKY007'
    name = 'span-discipline'
    description = ('Spans from tracing.span/start_span/phase must be '
                   'closed via `with` or `.end()` in a finally.')

    def __init__(self, ctx: core.FileContext) -> None:
        super().__init__(ctx)
        # Names bound to the tracing module ('tracing', aliases) and
        # names bound directly to span/start_span by import.
        self._mod_names: Set[str] = set()
        self._fn_names: Set[str] = set()

    @classmethod
    def applies_to(cls, path: str) -> bool:
        return not _is_test_path(path)

    # -- import tracking ---------------------------------------------
    def visit_Import(self, node: ast.Import) -> None:
        for alias in node.names:
            if alias.name.split('.')[-1] == 'tracing' and \
                    'observability' in alias.name:
                self._mod_names.add(alias.asname or alias.name)
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        mod = node.module or ''
        for alias in node.names:
            if alias.name == 'tracing' and \
                    mod.endswith('observability'):
                self._mod_names.add(alias.asname or 'tracing')
            elif alias.name in _OPENERS and mod.endswith('tracing'):
                self._fn_names.add(alias.asname or alias.name)
        self.generic_visit(node)

    # -- span-open detection -----------------------------------------
    def _is_open(self, node: ast.AST) -> bool:
        if not isinstance(node, ast.Call):
            return False
        name = core.dotted_name(node.func)
        if name is None:
            return False
        parts = name.split('.')
        if parts[-1] not in _OPENERS:
            return False
        if len(parts) == 1:
            return parts[0] in self._fn_names
        return '.'.join(parts[:-1]) in self._mod_names

    # -- scope analysis ----------------------------------------------
    def visit_Module(self, node: ast.Module) -> None:
        # Imports register via generic visiting; scopes are analyzed
        # from the top so each statement is owned by exactly one
        # function (or the module body).
        for stmt in node.body:
            self.visit(stmt)
        self._check_scope(node.body)

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self.generic_visit(node)  # nested defs get their own scope
        self._check_scope(node.body)

    def visit_AsyncFunctionDef(self,
                               node: ast.AsyncFunctionDef) -> None:
        self.generic_visit(node)
        self._check_scope(node.body)

    def _walk_scope(self, body: List[ast.stmt]):
        """Every node of this scope, not descending into nested
        function/class definitions (those are their own scopes)."""
        stack: List[ast.AST] = list(body)
        while stack:
            node = stack.pop()
            yield node
            if isinstance(node, (ast.FunctionDef,
                                 ast.AsyncFunctionDef,
                                 ast.ClassDef, ast.Lambda)):
                continue
            stack.extend(ast.iter_child_nodes(node))

    def _check_scope(self, body: List[ast.stmt]) -> None:
        opens: Dict[str, ast.Call] = {}  # var -> open call
        flagged: List[Tuple[ast.AST, str]] = []
        with_closed: Set[ast.Call] = set()
        # end-calls: var name -> under a finally?
        ends: Dict[str, bool] = {}
        finally_nodes: Set[int] = set()
        for node in self._walk_scope(body):
            if isinstance(node, ast.Try):
                for stmt in node.finalbody:
                    for sub in ast.walk(stmt):
                        finally_nodes.add(id(sub))
        for node in self._walk_scope(body):
            if isinstance(node, (ast.With, ast.AsyncWith)):
                for item in node.items:
                    if self._is_open(item.context_expr):
                        with_closed.add(item.context_expr)
            elif isinstance(node, ast.Assign) and \
                    self._is_open(node.value):
                if len(node.targets) == 1 and \
                        isinstance(node.targets[0], ast.Name):
                    opens[node.targets[0].id] = node.value
                else:
                    flagged.append(
                        (node, 'span stored where its close cannot '
                               'be verified; bind it to a local and '
                               '`.end()` it in a finally, or use '
                               '`with`'))
            elif isinstance(node, ast.Expr) and \
                    self._is_open(node.value):
                flagged.append(
                    (node, 'span result discarded — it can never be '
                           'closed; use `with span(...)`'))
            elif isinstance(node, ast.Call):
                name = core.dotted_name(node.func)
                if name and name.endswith('.end') and \
                        len(name.split('.')) == 2:
                    var = name.split('.')[0]
                    ends[var] = ends.get(var, False) or \
                        id(node) in finally_nodes
        for var, call in opens.items():
            if call in with_closed:
                continue
            if var not in ends:
                # No visible `.end()` at all: only flag when the
                # variable never escapes this scope (passing or
                # returning it transfers ownership).
                if self._escapes(body, var):
                    continue
                flagged.append(
                    (call, f'span {var!r} is never closed; call '
                           f'{var}.end() in a finally or use `with`'))
            elif not ends[var]:
                flagged.append(
                    (call, f'{var}.end() is not under a finally: an '
                           f'exception between open and close leaks '
                           f'the span'))
        for node, msg in flagged:
            self.add(node, msg)

    def _escapes(self, body: List[ast.stmt], var: str) -> bool:
        """True when `var` is returned, yielded, passed to a call, or
        stored onto an object — ownership leaves this scope."""
        for node in self._walk_scope(body):
            if isinstance(node, (ast.Return, ast.Yield)) and \
                    isinstance(node.value, ast.Name) and \
                    node.value.id == var:
                return True
            if isinstance(node, ast.Call):
                for arg in list(node.args) + \
                        [kw.value for kw in node.keywords]:
                    if isinstance(arg, ast.Name) and arg.id == var:
                        return True
            if isinstance(node, ast.Assign):
                for tgt in node.targets:
                    if isinstance(tgt, (ast.Attribute, ast.Subscript)):
                        val = node.value
                        if isinstance(val, ast.Name) and \
                                val.id == var:
                            return True
        return False
