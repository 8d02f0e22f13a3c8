"""Reporters: human text and machine JSON for analysis results.

The JSON schema is pinned by tests/test_static_analysis.py — CI consumers
parse it, so additive evolution only.
"""
from __future__ import annotations

import json
from typing import Dict, List

from .core import AnalysisResult, Finding

__all__ = ["render_text", "render_json", "json_payload"]

JSON_SCHEMA_VERSION = 1


def render_text(result: AnalysisResult, verbose: bool = False) -> str:
    lines: List[str] = []
    for f in result.unsuppressed:
        lines.append(f.format())
    if verbose:
        for f in result.suppressed:
            lines.append(f"{f.format()}  [suppressed]")
        for f in result.baselined:
            lines.append(f"{f.format()}  [baselined]")
    totals = result.rule_totals("unsuppressed")
    summary = ", ".join(f"{k}={v}" for k, v in sorted(totals.items())) or "clean"
    lines.append(
        f"pdt-analyze: {len(result.unsuppressed)} finding(s) "
        f"({summary}); {len(result.suppressed)} suppressed, "
        f"{len(result.baselined)} baselined; "
        f"{result.files_scanned} files in {result.wall_s:.2f}s"
    )
    return "\n".join(lines)


def _finding_obj(f: Finding) -> Dict:
    return {
        "rule": f.rule,
        "severity": f.severity,
        "path": f.path,
        "line": f.line,
        "message": f.message,
    }


def json_payload(result: AnalysisResult) -> Dict:
    return {
        "version": JSON_SCHEMA_VERSION,
        "findings": [_finding_obj(f) for f in result.unsuppressed],
        "summary": {
            "unsuppressed": len(result.unsuppressed),
            "suppressed": len(result.suppressed),
            "baselined": len(result.baselined),
            "by_rule": result.rule_totals("unsuppressed"),
            "files_scanned": result.files_scanned,
            "wall_s": round(result.wall_s, 4),
        },
    }


def render_json(result: AnalysisResult) -> str:
    return json.dumps(json_payload(result), indent=2)
