"""Render an analysis :class:`~dlrover_tpu.analysis.core.Report` as
human text, machine JSON (what ``analysis/gate.py`` summarises), or
SARIF 2.1.0 for code-scanning UIs."""

import json

from dlrover_tpu.analysis.core import Report, all_checkers


def to_text(report: Report, show_suppressed: bool = False) -> str:
    lines = []
    for f in report.findings:
        lines.append(
            f"{f.path}:{f.line}:{f.col + 1}: {f.code} {f.message}"
        )
    if show_suppressed:
        for f in report.suppressed:
            lines.append(
                f"{f.path}:{f.line}:{f.col + 1}: {f.code} {f.message} "
                f"(suppressed)"
            )
    counts = report.counts()
    summary = (
        f"{len(report.findings)} finding"
        f"{'' if len(report.findings) == 1 else 's'}"
        f" ({len(report.suppressed)} suppressed) "
        f"in {report.checked_files} files"
    )
    if counts:
        summary += " [" + ", ".join(
            f"{code}: {n}" for code, n in sorted(counts.items())
        ) + "]"
    lines.append(summary)
    return "\n".join(lines)


def to_json(report: Report, indent: int = 2) -> str:
    return json.dumps(report.to_dict(), indent=indent, sort_keys=False)


def to_sarif(report: Report, indent: int = 2) -> str:
    """SARIF 2.1.0 — one run, one rule per checker code, suppressed
    findings carried with ``suppressions`` so dashboards can show the
    pragma debt."""
    rules = {}
    for c in all_checkers():
        for code in c.codes():
            rules[code] = {
                "id": code,
                "name": c.name,
                "shortDescription": {"text": c.description or c.name},
            }

    def result(f, suppressed):
        out = {
            "ruleId": f.code,
            "level": "error",
            "message": {"text": f.message},
            "locations": [
                {
                    "physicalLocation": {
                        "artifactLocation": {"uri": f.path},
                        "region": {
                            "startLine": max(f.line, 1),
                            "startColumn": f.col + 1,
                        },
                    }
                }
            ],
        }
        if suppressed:
            out["suppressions"] = [{"kind": "inSource"}]
        return out

    used = {f.code for f in report.findings}
    used.update(f.code for f in report.suppressed)
    doc = {
        "$schema": (
            "https://raw.githubusercontent.com/oasis-tcs/sarif-spec/"
            "master/Schemata/sarif-schema-2.1.0.json"
        ),
        "version": "2.1.0",
        "runs": [
            {
                "tool": {
                    "driver": {
                        "name": "dlrover-tpu-analysis",
                        "informationUri": (
                            "docs/STATIC_ANALYSIS.md"
                        ),
                        "rules": [
                            rules[c] for c in sorted(used)
                            if c in rules
                        ],
                    }
                },
                "results": [
                    result(f, False) for f in report.findings
                ] + [
                    result(f, True) for f in report.suppressed
                ],
            }
        ],
    }
    return json.dumps(doc, indent=indent, sort_keys=False)
