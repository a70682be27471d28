"""Log-discipline AST pass (rule ``log-discipline``).

Library modules must log through module loggers so the trace-id
``logging.Filter`` (observability/tracing.py) can correlate every line
with a request — a bare ``print`` bypasses the logging pipeline
entirely, and ``logging.basicConfig`` from a library hijacks the root
logger configuration that belongs to whichever process entrypoint is
hosting it (the reference operator has the same split: cmd/ binaries
configure, internal/ packages only emit).

Flagged:

- ``print(...)`` calls where ``print`` is the builtin name (a local
  ``def print`` or ``self.print`` is not);
- ``logging.basicConfig(...)`` / ``basicConfig(...)`` calls.

Exempt (CLI surfaces that OWN their stdout/root-logger):

- any ``__main__.py`` (agent/manager/analysis runners);
- ``ctl.py`` (kubectl-style CLI: tables and JSON go to stdout);
- ``bench.py`` / ``__graft_entry__.py`` / ``chip_smoke.py`` (driver
  contracts: the JSON result lines ARE the interface);
- anything under ``scripts/`` (ad-hoc profiling tools);
- test files (pytest captures stdout; prints there are a debugging aid,
  not a logging-pipeline bypass).

Everything else needs a ``# lint: allow[log-discipline] reason``.
"""

from __future__ import annotations

import ast
from pathlib import Path

from kubeinfer_tpu.analysis.core import Finding, _is_test_file
from kubeinfer_tpu.analysis.jitlint import _dotted

__all__ = ["run"]

_EXEMPT_NAMES = {
    "__main__.py", "ctl.py", "bench.py", "__graft_entry__.py",
    "chip_smoke.py",
}


def _is_exempt(path: str) -> bool:
    p = Path(path)
    return (
        p.name in _EXEMPT_NAMES
        or "scripts" in p.parts
        or _is_test_file(path)
    )


class _Visitor(ast.NodeVisitor):
    def __init__(self, path: str) -> None:
        self.path = path
        self.findings: list[Finding] = []
        # scope stack of locally-bound names: a nested `def print(...)` or
        # `print = ...` rebinding shadows the builtin for that scope
        self._shadowed: list[set[str]] = [set()]

    def _print_is_builtin(self) -> bool:
        return not any("print" in s for s in self._shadowed)

    def _enter(self, node: ast.AST, names: set[str]) -> None:
        self._shadowed.append(names)
        self.generic_visit(node)
        self._shadowed.pop()

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._shadowed[-1].add(node.name)
        args = node.args
        bound = {
            a.arg
            for a in (
                list(args.posonlyargs) + list(args.args)
                + list(args.kwonlyargs)
                + ([args.vararg] if args.vararg else [])
                + ([args.kwarg] if args.kwarg else [])
            )
        }
        self._enter(node, bound)

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Assign(self, node: ast.Assign) -> None:
        for t in node.targets:
            if isinstance(t, ast.Name):
                self._shadowed[-1].add(t.id)
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call) -> None:
        chain = _dotted(node.func) or ""
        if chain == "print" and self._print_is_builtin():
            self.findings.append(Finding(
                self.path, node.lineno, "log-discipline",
                "bare print() in a library module — use a module logger "
                "so the trace-id filter can correlate the line",
            ))
        elif chain in ("logging.basicConfig", "basicConfig"):
            self.findings.append(Finding(
                self.path, node.lineno, "log-discipline",
                "logging.basicConfig() in a library module — root logger "
                "configuration belongs to the process entrypoint",
            ))
        self.generic_visit(node)


def run(tree: ast.AST, path: str) -> list[Finding]:
    if _is_exempt(path):
        return []
    v = _Visitor(path)
    v.visit(tree)
    return v.findings
