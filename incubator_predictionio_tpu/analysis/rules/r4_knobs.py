"""R4 — knob-registry: every ``PIO_*`` read has a docs row, and vice versa.

The generalized PR 13 meta-test: docs/configuration.md claims to be
"every knob the framework reads, in one place" — R4 makes that a
checked contract instead of a hope. The same cross-reference engine
(:mod:`incubator_predictionio_tpu.analysis.crossref`) runs twice:

- **knobs**: ``PIO_*`` env reads across the package, tests/ and
  drills.py (tests and drills read documented ``PIO_TEST_*`` /
  ``PIO_BENCH_*`` knobs — they are part of the configuration surface)
  ↔ `docs/configuration.md` table rows, exceptions in
  `docs/config_allowlist.txt`;
- **metrics**: registered ``pio_*`` metrics in the package ↔
  `docs/observability.md` table rows, exceptions in
  `docs/metrics_allowlist.txt` (the original parity test's contract,
  absorbed here; tests/test_metrics_docs_parity.py keeps its ids by
  delegating to the same engine).

Prefix semantics make pattern knobs first-class: code reading
``f"PIO_RESILIENCE_{key}"`` matches the documented
``PIO_RESILIENCE_<KEY>`` row. A dead allowlist entry — one parity would
pass without — fails the run, so the exception file shrinks back when a
debt is repaid.
"""

from __future__ import annotations

import os
from typing import Iterable

from incubator_predictionio_tpu.analysis import crossref
from incubator_predictionio_tpu.analysis.crossref import Name
from incubator_predictionio_tpu.analysis.model import Finding, load_module
from incubator_predictionio_tpu.analysis.rules.base import Project, Rule

#: roots scanned for env reads, relative to the repo root; the package
#: itself rides the engine's already-parsed modules (see check_project)
KNOB_CODE_ROOTS = ("incubator_predictionio_tpu", "tests", "drills.py")
#: the roots NOT covered by Project.modules
EXTRA_CODE_ROOTS = ("tests", "drills.py")
#: fixture trees containing DELIBERATE violations for the linter's own
#: tests must not count as project code
EXCLUDE_DIRS = ("__pycache__", "lint_cases")

KNOB_DOC = "docs/configuration.md"
KNOB_ALLOWLIST = "docs/config_allowlist.txt"
METRIC_DOC = "docs/observability.md"
METRIC_ALLOWLIST = "docs/metrics_allowlist.txt"
PKG = "incubator_predictionio_tpu"


def _read(root: str, rel: str) -> str:
    path = os.path.join(root, rel)
    if not os.path.exists(path):
        return ""
    with open(path, encoding="utf-8") as f:
        return f.read()


def knob_code_names(root: str, package_modules=None) -> list:
    """Every ``PIO_*`` env read under the knob code roots.

    ``package_modules`` lets the engine hand over its already-parsed
    package (Project.modules) so a lint run parses each file ONCE; the
    extra roots (tests/, drills.py) are always scanned here.
    """
    names = []
    if package_modules is not None:
        modules = list(package_modules)
        roots = EXTRA_CODE_ROOTS
    else:
        modules = []
        roots = KNOB_CODE_ROOTS
    for code_root in roots:
        path = os.path.join(root, code_root)
        if path.endswith(".py"):
            files = [path] if os.path.exists(path) else []
        else:
            files = list(crossref.walk_py_files(
                path, exclude_parts=EXCLUDE_DIRS)) \
                if os.path.isdir(path) else []
        for fpath in files:
            mod = load_module(fpath, root)
            if mod is not None:
                modules.append(mod)
    for mod in modules:
        for text, prefix, lineno in crossref.scan_env_reads(mod.tree):
            names.append(Name(text=text, prefix=prefix,
                              where=f"{mod.relpath}:{lineno}"))
    return names


def knob_doc_names(root: str) -> list:
    return crossref.doc_names(_read(root, KNOB_DOC), r"PIO_",
                              relpath=KNOB_DOC)


def metric_code_names(root: str, package_modules=None) -> list:
    names = []
    if package_modules is None:
        pkg = os.path.join(root, PKG)
        if not os.path.isdir(pkg):
            return names
        package_modules = [
            m for m in (load_module(f, root) for f in
                        crossref.walk_py_files(
                            pkg, exclude_parts=EXCLUDE_DIRS))
            if m is not None]
    for mod in package_modules:
        for text in crossref.scan_metric_registrations(mod.source):
            names.append(Name(text=text, where=mod.relpath))
    return names


def metric_doc_names(root: str) -> list:
    return crossref.doc_names(_read(root, METRIC_DOC), r"pio_",
                              relpath=METRIC_DOC)


def _where(name: Name, fallback: str) -> tuple:
    """(relpath, line) out of a Name's provenance."""
    if name.where and ":" in name.where:
        path, _, line = name.where.rpartition(":")
        try:
            return path, int(line)
        except ValueError:
            pass
    return fallback, 0


class KnobRegistryRule(Rule):
    id = "R4"
    title = "knob-registry: PIO_* knobs / pio_* metrics drifted from docs"
    hint = ("docs/configuration.md is the checked registry of every knob "
            "(docs/observability.md of every metric): add the missing "
            "table row, delete the stale one, or — sparingly — add an "
            "allowlist entry (docs/analysis.md#r4)")

    def check_project(self, project: Project) -> Iterable[Finding]:
        root = project.root
        yield from self._check(
            crossref.cross_reference(
                knob_code_names(root, package_modules=project.modules),
                knob_doc_names(root),
                crossref.load_allowlist(
                    os.path.join(root, KNOB_ALLOWLIST))),
            kind="knob", doc=KNOB_DOC, allowlist=KNOB_ALLOWLIST)
        yield from self._check(
            crossref.cross_reference(
                metric_code_names(root, package_modules=project.modules),
                metric_doc_names(root),
                crossref.load_allowlist(
                    os.path.join(root, METRIC_ALLOWLIST))),
            kind="metric", doc=METRIC_DOC, allowlist=METRIC_ALLOWLIST)

    def _check(self, res: crossref.CrossRefResult, kind: str, doc: str,
               allowlist: str) -> Iterable[Finding]:
        reg = "read in code" if kind == "knob" else "registered"
        for n in sorted(res.undocumented, key=lambda n: (n.where, n.text)):
            path, line = _where(n, doc)
            star = "*" if n.prefix else ""
            yield Finding(
                rule=self.id, path=path, line=line,
                message=f"{kind} {n.text}{star} {reg} but has no {doc} "
                        "table row",
                hint=self.hint, scope=kind, code=n.text)
        for d in sorted(res.stale_docs, key=lambda n: (n.where, n.text)):
            path, line = _where(d, doc)
            star = "*" if d.prefix else ""
            yield Finding(
                rule=self.id, path=path, line=line,
                message=f"documented {kind} {d.text}{star} is no longer "
                        f"{reg} anywhere — drop the row or fix the name",
                hint=self.hint, scope=kind, code=d.text)
        for a in res.dead_allowlist:
            yield Finding(
                rule=self.id, path=allowlist, line=0,
                message=f"allowlist entry {a} no longer needed — parity "
                        "passes without it; delete it",
                hint=self.hint, scope=kind, code=a)
