"""Rule base class + the AST plumbing every rule shares.

A rule gets two hooks: :meth:`Rule.check_module` per parsed file (most
rules) and :meth:`Rule.check_project` once per run with the whole
project (cross-file rules like the knob registry). Findings carry
file:line, the rule id, and the rule's fix hint.
"""

from __future__ import annotations

import ast
from typing import Iterable, Iterator, Optional

from incubator_predictionio_tpu.analysis.model import Finding, Module


class Project:
    """Everything a cross-file rule may need: the repo root and the
    parsed package modules (extra roots are scanned by the rule itself —
    e.g. R4 reads tests/ and drills.py for env reads)."""

    def __init__(self, root: str, modules: list):
        self.root = root
        self.modules = modules


class Rule:
    id: str = ""
    title: str = ""
    hint: str = ""

    def check_module(self, mod: Module) -> Iterable[Finding]:
        return ()

    def check_project(self, project: Project) -> Iterable[Finding]:
        return ()


# ---------------------------------------------------------------------------
# shared AST helpers
# ---------------------------------------------------------------------------

def dotted(node: ast.AST) -> str:
    """``a.b.c`` for Name/Attribute chains, "" otherwise."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    if isinstance(node, ast.Call):
        # e.g. ``asyncio.Lock().acquire`` — name the call's own chain
        inner = dotted(node.func)
        return f"{inner}()" if inner else ""
    return ""


def iter_async_nodes(tree: ast.AST) -> Iterator:
    """(async_def, node) for every node whose NEAREST enclosing function
    is an ``async def`` — a sync helper defined inside an async def is
    not executed on the event loop and is skipped; a nested async def is
    visited in its own right."""

    def walk(node: ast.AST, ctx: Optional[ast.AsyncFunctionDef]):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.AsyncFunctionDef):
                yield from walk(child, child)
            elif isinstance(child, (ast.FunctionDef, ast.Lambda)):
                yield from walk(child, None)
            else:
                if ctx is not None:
                    yield ctx, child
                yield from walk(child, ctx)

    yield from walk(tree, None)


def awaited_calls(tree: ast.AST) -> set:
    """id()s of Call nodes that are directly awaited — ``await
    sem.acquire()`` is the correct async idiom, not a blocking call."""
    out = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Await) and isinstance(node.value, ast.Call):
            out.add(id(node.value))
    return out


def imported_names(tree: ast.AST, module: str, names: tuple) -> set:
    """Local names bound by ``from <module> import <name> [as alias]``."""
    bound = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module == module:
            for alias in node.names:
                if alias.name in names:
                    bound.add(alias.asname or alias.name)
    return bound
