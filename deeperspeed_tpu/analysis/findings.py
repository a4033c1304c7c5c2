"""Finding model shared by both analysis levels.

A ``Finding`` is one violation: a rule id, a severity, where it was
found (a source file:line for AST rules, a program entry-point name for
compiled-program audits), and a human message. The CLI collects
findings from every checker, applies the suppression file, and exits
non-zero iff any *error*-level finding survives.

Suppressions live in ``ANALYSIS_SUPPRESSIONS.json`` at the repo root —
a list of ``{"rule": ..., "path": ..., "reason": ...}`` entries. The
``reason`` is mandatory: a suppression without one is itself an error,
so intent is always recorded next to the waiver. ``path`` matches the
finding's location (source path relative to the root, or the program
entry name for level-1 findings); an optional ``line`` pins the
suppression to one statement so it cannot silently absorb new
violations elsewhere in the file.
"""

from __future__ import annotations

import dataclasses
import fnmatch
import json
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

SEVERITIES = ("error", "warning", "info")

DEFAULT_SUPPRESSIONS_FILE = "ANALYSIS_SUPPRESSIONS.json"
DEFAULT_BASELINE_FILE = "ANALYSIS_BASELINE.json"


@dataclasses.dataclass
class Finding:
    rule: str
    severity: str  # "error" | "warning" | "info"
    path: str      # source file (relative) or program entry name
    line: int      # 0 for program-level findings
    message: str
    detail: Optional[Dict[str, Any]] = None

    def __post_init__(self):
        if self.severity not in SEVERITIES:
            raise ValueError(f"bad severity {self.severity!r} for rule {self.rule}")

    def to_dict(self) -> Dict[str, Any]:
        d = {
            "rule": self.rule,
            "severity": self.severity,
            "path": self.path,
            "line": self.line,
            "message": self.message,
        }
        if self.detail:
            d["detail"] = self.detail
        return d

    def key(self) -> str:
        return f"{self.rule}:{self.path}:{self.line}"


@dataclasses.dataclass
class Suppression:
    rule: str
    path: str
    reason: str
    line: Optional[int] = None
    used: bool = dataclasses.field(default=False, compare=False)

    def matches(self, f: Finding) -> bool:
        if self.rule != f.rule and not fnmatch.fnmatch(f.rule, self.rule):
            return False
        if self.path != f.path and not fnmatch.fnmatch(f.path, self.path):
            return False
        if self.line is not None and int(self.line) != int(f.line):
            return False
        return True


class SuppressionError(ValueError):
    """Malformed suppression file (missing reason, bad shape, ...)."""


def load_suppressions(path: str) -> List[Suppression]:
    """Parse the suppression file; a missing file means no suppressions.

    Every entry MUST carry a non-empty ``reason`` — the whole point of
    the file is that waivers are documented where they are granted.
    """
    if not os.path.exists(path):
        return []
    with open(path) as fh:
        raw = json.load(fh)
    entries = raw.get("suppressions", raw) if isinstance(raw, dict) else raw
    if not isinstance(entries, list):
        raise SuppressionError(f"{path}: expected a list of suppressions")
    out: List[Suppression] = []
    for i, e in enumerate(entries):
        if not isinstance(e, dict):
            raise SuppressionError(f"{path}[{i}]: entry must be an object")
        for field in ("rule", "path", "reason"):
            if not str(e.get(field, "")).strip():
                raise SuppressionError(
                    f"{path}[{i}]: missing mandatory field {field!r}"
                    + (" — every suppression needs a reason"
                       if field == "reason" else ""))
        out.append(Suppression(rule=e["rule"], path=e["path"],
                               reason=e["reason"], line=e.get("line")))
    return out


def apply_suppressions(
    findings: Sequence[Finding], sups: Sequence[Suppression]
) -> Tuple[List[Finding], List[Tuple[Finding, Suppression]]]:
    """Split findings into (kept, suppressed) and mark used waivers."""
    kept: List[Finding] = []
    suppressed: List[Tuple[Finding, Suppression]] = []
    for f in findings:
        hit = next((s for s in sups if s.matches(f)), None)
        if hit is None:
            kept.append(f)
        else:
            hit.used = True
            suppressed.append((f, hit))
    return kept, suppressed


def counts(findings: Sequence[Finding]) -> Dict[str, int]:
    c = {s: 0 for s in SEVERITIES}
    for f in findings:
        c[f.severity] += 1
    return c


def report(
    findings: Sequence[Finding],
    suppressed: Sequence[Tuple[Finding, Suppression]] = (),
    root: str = ".",
    extra: Optional[Dict[str, Any]] = None,
) -> Dict[str, Any]:
    """The findings JSON the CLI writes (and ``--write-baseline`` snapshots)."""
    c = counts(findings)
    c["suppressed"] = len(suppressed)
    out = {
        "version": 1,
        "root": os.path.abspath(root),
        "counts": c,
        "findings": sorted((f.to_dict() for f in findings),
                           key=lambda d: (SEVERITIES.index(d["severity"]),
                                          d["path"], d["line"], d["rule"])),
        "suppressed": [
            dict(f.to_dict(), reason=s.reason) for f, s in suppressed
        ],
    }
    if extra:
        out.update(extra)
    return out


def format_text(findings: Sequence[Finding],
                suppressed: Sequence[Tuple[Finding, Suppression]] = ()) -> str:
    lines = []
    for f in findings:
        loc = f.path if f.line == 0 else f"{f.path}:{f.line}"
        lines.append(f"{f.severity.upper():7s} {f.rule:24s} {loc}: {f.message}")
    if suppressed:
        lines.append(f"({len(suppressed)} finding(s) suppressed with reasons)")
    return "\n".join(lines)
