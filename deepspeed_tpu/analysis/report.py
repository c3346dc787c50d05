"""Structured findings shared by the graph sanitizer and ds-lint.

Plain dataclasses, not log lines: tests and CI consume them directly
(`SanitizerReport.ok` gates a pipeline; `LintReport.by_rule()` is the
count `scripts/ds_gate.py lint --json` prints). Rendering is a method,
never the storage format.
"""

import ast
import dataclasses
from collections import Counter
from typing import Dict, Iterable, List, Mapping


@dataclasses.dataclass(frozen=True)
class Finding:
    """One static-analysis finding.

    rule: "R001".."R005" for ds-lint, "S001".."S006" for the
          sanitizer/cost model
    path: file path (lint) or program/parameter label (sanitizer)
    line: 1-based source line (0 when the finding has no source anchor)
    severity: "error" | "warning" | "info"
    message: what is wrong
    fix_hint: how to fix it (or how to annotate it as intentional)
    """

    rule: str
    path: str
    line: int
    severity: str
    message: str
    fix_hint: str = ""

    def render(self) -> str:
        loc = f"{self.path}:{self.line}" if self.line else self.path
        s = f"{loc}: [{self.rule}/{self.severity}] {self.message}"
        if self.fix_hint:
            s += f"\n    hint: {self.fix_hint}"
        return s


def _qualname_at(tree: ast.AST, line: int) -> str:
    """Dotted name of the innermost def/class whose span holds `line`."""
    best = "<module>"

    def walk(node: ast.AST, prefix: str) -> None:
        nonlocal best
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                if child.lineno <= line <= (child.end_lineno or
                                            child.lineno):
                    best = prefix + child.name
                    walk(child, best + ".")
            else:
                walk(child, prefix)

    walk(tree, "")
    return best


def site_keys(findings: Iterable[Finding],
              sources: Mapping[str, str]) -> List[str]:
    """The keys a baseline holds for pragma-suppressed sites:
    `path::qualname RULE`, the n-th such site of one function `#n`
    from the second on. Never a line number: an edit that moves a site
    without adding or removing one changes no baseline byte.
    `sources` maps a finding's path to that file's text."""
    trees: Dict[str, ast.AST] = {}
    seen: Counter = Counter()
    keys = []
    for f in sorted(findings, key=lambda f: (f.path, f.line, f.rule)):
        if f.path not in trees:
            try:
                trees[f.path] = ast.parse(sources.get(f.path, ""))
            except SyntaxError:
                trees[f.path] = ast.Module(body=[], type_ignores=[])
        key = f"{f.path}::{_qualname_at(trees[f.path], f.line)} {f.rule}"
        seen[key] += 1
        keys.append(key if seen[key] == 1 else f"{key}#{seen[key]}")
    return sorted(keys)


@dataclasses.dataclass
class _Report:
    findings: List[Finding] = dataclasses.field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.findings

    def by_rule(self) -> Dict[str, int]:
        return dict(Counter(f.rule for f in self.findings))

    def render(self) -> str:
        if not self.findings:
            return "no findings"
        return "\n".join(f.render() for f in self.findings)


@dataclasses.dataclass
class SanitizerReport(_Report):
    """Findings from the graph sanitizer over one compiled program.

    `cost` carries the program's static CostReport (analysis/costmodel)
    when the producing check built one — engine.sanitize() attaches it
    so callers read footprint/comm numbers from the same object that
    gates CI."""

    label: str = ""
    cost: object = None  # Optional[costmodel.CostReport]

    def render(self) -> str:
        head = f"sanitizer[{self.label or 'program'}]: "
        body = ("clean" if not self.findings
                else f"{len(self.findings)} finding(s)\n" + super().render())
        if self.cost is not None:
            body += "\n" + self.cost.render()
        return head + body


@dataclasses.dataclass
class LintReport(_Report):
    """ds-lint findings over a file set, plus the suppressed tail
    (`suppressed_sites`: its `site_keys`, where the producer kept the
    sources to name them by)."""

    suppressed: List[Finding] = dataclasses.field(default_factory=list)
    suppressed_sites: List[str] = dataclasses.field(default_factory=list)
    files_checked: int = 0

    def summary(self) -> str:
        return (
            f"ds-lint: {self.files_checked} files, "
            f"{len(self.findings)} finding(s), "
            f"{len(self.suppressed)} suppressed by pragma"
        )


def merge_reports(label: str, *reports: _Report) -> SanitizerReport:
    """Fold several check results into one SanitizerReport."""
    out = SanitizerReport(label=label)
    for r in reports:
        out.findings.extend(r.findings if isinstance(r, _Report) else r)
    return out
