"""Lint engine: walk the package, run every rule, audit the exceptions.

The pipeline (docs/analysis.md):

1. parse every package module (stdlib ``ast``; cross-file rules scan
   their own extra roots — R4 reads tests/ and drills.py);
2. run each selected rule's per-module and per-project hooks;
3. apply inline suppressions (``# pio-lint: disable=R<n> (reason)``)
   and the checked-in baseline (conf/lint_baseline.txt);
4. append the audit findings: S1 (suppression without reason),
   S2 (stale suppression), B1 (stale baseline entry) — the exception
   surface is linted as hard as the code;
5. render a human table or ``--json``; exit 0 only when no ACTIVE
   finding remains.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from typing import Iterable, Optional

from incubator_predictionio_tpu.analysis import baseline as baseline_mod
from incubator_predictionio_tpu.analysis.model import Finding, load_module
from incubator_predictionio_tpu.analysis.suppress import Suppressions
from incubator_predictionio_tpu.analysis.rules import ALL_RULES, RULES_BY_ID
from incubator_predictionio_tpu.analysis.rules.base import Project

PKG_DIR = "incubator_predictionio_tpu"
DEFAULT_BASELINE = os.path.join("conf", "lint_baseline.txt")
#: directories never scanned (fixture trees hold DELIBERATE violations)
EXCLUDE_DIRS = ("__pycache__", "lint_cases")

JSON_SCHEMA_VERSION = 1


def default_root() -> str:
    """The repo root: parent of the installed package directory."""
    here = os.path.dirname(os.path.abspath(__file__))     # .../analysis
    return os.path.dirname(os.path.dirname(here))         # repo root


@dataclass
class LintResult:
    root: str
    #: findings that FAIL the run (not suppressed, not baselined)
    active: list = field(default_factory=list)
    #: inline-suppressed findings (each matched a reasoned directive)
    suppressed: list = field(default_factory=list)
    #: baseline-matched findings (accepted pre-existing debt)
    baselined: list = field(default_factory=list)
    #: rule ids that ran
    checked_rules: list = field(default_factory=list)
    files_scanned: int = 0

    @property
    def clean(self) -> bool:
        return not self.active

    def to_json(self) -> dict:
        return {
            "version": JSON_SCHEMA_VERSION,
            "root": self.root,
            "rules": {rid: RULES_BY_ID[rid].title
                      for rid in self.checked_rules},
            "filesScanned": self.files_scanned,
            "findings": [f.to_json() for f in self.active],
            "suppressed": [f.to_json() for f in self.suppressed],
            "baselined": [f.to_json() for f in self.baselined],
            "counts": {
                "active": len(self.active),
                "suppressed": len(self.suppressed),
                "baselined": len(self.baselined),
            },
            "clean": self.clean,
        }


def _sort_key(f: Finding) -> tuple:
    return (f.rule, f.path, f.line, f.message)


def run_lint(root: Optional[str] = None,
             rules: Optional[Iterable[str]] = None,
             baseline_path: Optional[str] = None,
             update_baseline: bool = False) -> LintResult:
    """Run the invariant linter over the repo at ``root``.

    ``rules`` restricts to the given ids (default: all). With
    ``update_baseline`` the surviving active findings are written to the
    baseline (sorted, path-relative, deterministic) and the result
    reports them as baselined instead.
    """
    root = root or default_root()
    if rules is None:
        selected = list(ALL_RULES)
    else:
        unknown = [r for r in rules if r not in RULES_BY_ID]
        if unknown:
            raise ValueError(
                f"unknown rule id(s) {unknown}; known: "
                f"{sorted(RULES_BY_ID)}")
        selected = [RULES_BY_ID[r] for r in rules]
    checked = {r.id for r in selected}

    pkg = os.path.join(root, PKG_DIR)
    modules = []
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = [d for d in dirnames if d not in EXCLUDE_DIRS]
        for fname in sorted(filenames):
            if not fname.endswith(".py"):
                continue
            mod = load_module(os.path.join(dirpath, fname), root)
            if mod is not None:
                modules.append(mod)
    project = Project(root=root, modules=modules)

    findings: list = []
    supp_tables: dict = {}
    for mod in modules:
        supp_tables[mod.relpath] = Suppressions(mod)
        for rule in selected:
            findings.extend(rule.check_module(mod))
    for rule in selected:
        findings.extend(rule.check_project(project))

    # inline suppressions — project-level findings that land in a scanned
    # module (e.g. an undocumented env read) are suppressible too
    by_path: dict = {}
    for f in findings:
        by_path.setdefault(f.path, []).append(f)
    for relpath, fs in by_path.items():
        table = supp_tables.get(relpath)
        if table is None:
            # R4 scans roots outside the package (tests/, drills.py):
            # build a table on demand so those sites can be suppressed
            path = os.path.join(root, relpath)
            if relpath.endswith(".py") and os.path.exists(path):
                mod = load_module(path, root)
                if mod is not None:
                    table = supp_tables[relpath] = Suppressions(mod)
        if table is not None:
            table.apply(fs)

    # suppression audit: S1 (no reason) + S2 (stale) per scanned module
    for table in supp_tables.values():
        findings.extend(table.meta_findings(checked))

    # baseline
    bl_path = os.path.join(root, baseline_path or DEFAULT_BASELINE)
    result = LintResult(root=root, checked_rules=sorted(checked),
                        files_scanned=len(modules))
    # only real rule findings are baselineable — the S1/S2 suppression
    # audit and B1 itself must stay un-accept-able, or the ledger could
    # bless its own rot
    baselineable = [f for f in findings
                    if not f.suppressed and f.rule.startswith("R")]
    if update_baseline:
        # entries owned by rules NOT in this run's selection were never
        # re-checked — a scoped `--rule R3 --update-baseline` must not
        # silently drop the accepted R1 debt
        retained = [k for k, count in
                    sorted(baseline_mod.load(bl_path).items())
                    if k.split("|", 1)[0] not in checked
                    for _ in range(count)]
        baseline_mod.save(bl_path, baselineable, retained_keys=retained)
        for f in baselineable:
            f.baselined = True
    else:
        entries = baseline_mod.load(bl_path)
        findings.extend(baseline_mod.apply(entries, baselineable))

    for f in sorted(findings, key=_sort_key):
        if f.suppressed:
            result.suppressed.append(f)
        elif f.baselined:
            result.baselined.append(f)
        else:
            result.active.append(f)
    return result


# ---------------------------------------------------------------------------
# rendering
# ---------------------------------------------------------------------------

def render_text(result: LintResult) -> str:
    """Human-readable report: findings grouped by rule, then the tally."""
    lines = []
    if result.active:
        current = None
        for f in result.active:
            if f.rule != current:
                current = f.rule
                title = RULES_BY_ID.get(f.rule)
                name = title.title if title else _meta_title(f.rule)
                lines.append(f"{f.rule} — {name}")
            loc = f.location() if f.line else f.path
            lines.append(f"  {loc}: {f.message}")
            if f.hint:
                lines.append(f"      hint: {f.hint}")
        lines.append("")
    tally = (f"{len(result.active)} finding(s), "
             f"{len(result.suppressed)} suppressed, "
             f"{len(result.baselined)} baselined; "
             f"{result.files_scanned} files, "
             f"rules {','.join(result.checked_rules)}")
    lines.append(("FAIL: " if result.active else "ok: ") + tally)
    return "\n".join(lines)


def _meta_title(rule: str) -> str:
    return {
        "S1": "suppression without a reason",
        "S2": "stale suppression",
        "B1": "stale baseline entry",
    }.get(rule, "finding")


def render_json(result: LintResult) -> str:
    return json.dumps(result.to_json(), indent=2, sort_keys=True)
