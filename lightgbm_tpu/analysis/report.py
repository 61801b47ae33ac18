"""Rendering for tpulint results: human text and machine JSON.

Text output groups by severity and marks baseline-known findings so a
human triaging a failed gate sees the NEW debt first; JSON output is
one self-describing document for CI annotation / trend dashboards.
"""
from __future__ import annotations

import json
from typing import Dict, List, Optional, Sequence

from .core import Finding, SEVERITIES, severity_counts


def summary_line(findings: Sequence[Finding],
                 new: Optional[Sequence[Finding]] = None,
                 stale_count: int = 0) -> str:
    counts = severity_counts(findings)
    parts = ["%d finding(s)" % len(findings)]
    parts.append("/".join("%s %d" % (s, counts[s]) for s in SEVERITIES))
    if new is not None:
        parts.append("%d new" % len(new))
    if stale_count:
        parts.append("%d stale baseline entr%s" %
                      (stale_count, "y" if stale_count == 1 else "ies"))
    return "tpulint: " + ", ".join(parts)


def render_text(findings: Sequence[Finding],
                new: Optional[Sequence[Finding]] = None,
                stale: Optional[Sequence[Dict]] = None) -> str:
    """Full human report.  With a baseline, known findings collapse to
    a one-line tally and only NEW findings print in full."""
    out: List[str] = []
    if new is None:
        shown: Sequence[Finding] = findings
    else:
        shown = new
        known_n = len(findings) - len(new)
        if known_n:
            out.append("%d baseline-known finding(s) not shown "
                       "(run tools/lint.py without --baseline to list "
                       "them)" % known_n)
    for sev in SEVERITIES:
        rows = [f for f in shown if f.severity == sev]
        if not rows:
            continue
        out.append("")
        out.append("-- %s (%d) --" % (sev, len(rows)))
        out.extend(f.format() for f in rows)
    if stale:
        out.append("")
        out.append("-- stale baseline entries (%d): fixed debt, regenerate "
                   "with --write-baseline --" % len(stale))
        out.extend("  %s %s %s:%s" % (e.get("severity", "?"),
                                      e.get("check", "?"),
                                      e.get("path", "?"), e.get("line", "?"))
                   for e in stale)
    out.append("")
    out.append(summary_line(findings, new,
                            len(stale) if stale else 0))
    return "\n".join(out).lstrip("\n")


def render_json(findings: Sequence[Finding],
                new: Optional[Sequence[Finding]] = None,
                stale: Optional[Sequence[Dict]] = None,
                baseline_path: Optional[str] = None) -> str:
    doc = {
        "tool": "tpulint",
        "counts": severity_counts(findings),
        "total": len(findings),
        "new": [f.to_dict() for f in (findings if new is None else new)],
        "findings": [f.to_dict() for f in findings],
        "baseline": {
            "path": baseline_path,
            "stale": list(stale or []),
        } if baseline_path else None,
    }
    return json.dumps(doc, indent=1) + "\n"
