#!/usr/bin/env python3
"""tpulint CLI — the CI gate over lightgbm_tpu/analysis/.

Runs without jax installed: the analysis package is loaded directly by
file path (never through ``lightgbm_tpu/__init__``, which imports jax).
The gate semantics are "zero NEW findings": pre-existing debt lives in
the committed baseline (tools/lint_baseline.json) and only findings
absent from it fail the run.

Usage:
    python tools/lint.py                              # whole repo, no gate
    python tools/lint.py --baseline tools/lint_baseline.json   # CI gate
    python tools/lint.py --only locks --only jit some/dir
    python tools/lint.py --changed --baseline tools/lint_baseline.json
    python tools/lint.py --json --baseline tools/lint_baseline.json
    python tools/lint.py --write-baseline tools/lint_baseline.json

Exit status: 0 = no new findings (or no gate requested and nothing at
all found... the ungated run exits 0 unless a parse error occurred),
1 = new findings, 2 = bad invocation/unreadable baseline.
"""
from __future__ import annotations

import argparse
import importlib.util
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_analysis():
    """Load lightgbm_tpu/analysis as a standalone top-level package so
    nothing imports lightgbm_tpu/__init__ (which needs jax)."""
    name = "lgbm_tpulint"
    if name in sys.modules:
        return sys.modules[name]
    pkg_dir = os.path.join(REPO, "lightgbm_tpu", "analysis")
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(pkg_dir, "__init__.py"),
        submodule_search_locations=[pkg_dir])
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _changed_files(root):
    """Repo-relative .py files changed vs HEAD plus untracked ones, or
    None when ``root`` is not a git checkout."""
    def _git(*args):
        return subprocess.run(
            ("git", "-C", root) + args, capture_output=True, text=True)
    diff = _git("diff", "--name-only", "HEAD", "--")
    if diff.returncode != 0:
        return None
    untracked = _git("ls-files", "--others", "--exclude-standard")
    names = set(diff.stdout.split()) | set(untracked.stdout.split())
    return sorted(n for n in names if n.endswith(".py")
                  and os.path.isfile(os.path.join(root, n)))


def run(argv=None):
    ap = argparse.ArgumentParser(
        prog="tpulint",
        description="AST lint for jit hazards, lock discipline, config "
                    "drift and resource hygiene (no jax required)")
    ap.add_argument("paths", nargs="*",
                    help="files/dirs to scan (default: %s)" %
                         ", ".join(("lightgbm_tpu", "tools", "chip_smoke.py")))
    ap.add_argument("--root", default=REPO,
                    help="project root for relative paths and "
                         "docs/Parameters.md (default: repo root)")
    ap.add_argument("--baseline", metavar="JSON",
                    help="gate against this baseline: only findings NOT "
                         "in it fail the run")
    ap.add_argument("--write-baseline", metavar="JSON",
                    help="write current findings as the new baseline "
                         "and exit 0")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable report on stdout")
    ap.add_argument("--only", action="append", metavar="CHECKER",
                    help="run only this checker family (repeatable): "
                         "jit, locks, config, hygiene, collectives, "
                         "wireproto, donation")
    ap.add_argument("--changed", action="store_true",
                    help="gate only findings in .py files changed vs "
                         "HEAD (plus untracked); the scan itself covers "
                         "the full scope so cross-file checkers keep "
                         "their context — same baseline semantics; "
                         "useful as a pre-commit gate")
    args = ap.parse_args(argv)

    if args.changed:
        if args.paths:
            ap.error("--changed and explicit paths are mutually "
                     "exclusive")
        changed = _changed_files(args.root or REPO)
        if changed is None:
            print("tpulint: --changed requires a git checkout",
                  file=sys.stderr)
            return 2

    analysis = load_analysis()
    root = os.path.abspath(args.root)
    if args.changed:
        # only files the full-repo gate would scan anyway — fixture
        # edits under tests/ must not fail the pre-commit run
        roots = tuple(analysis.DEFAULT_ROOTS)
        changed = [n for n in changed
                   if n in roots
                   or any(n.startswith(r.rstrip("/") + "/")
                          for r in roots)]
        if not changed:
            print("tpulint: no changed .py files in scan scope, "
                  "nothing to do")
            return 0
    findings = analysis.run_suite(root, args.paths or None,
                                  only=args.only)
    if args.changed:
        # the suite ran over the FULL scan scope — cross-file checkers
        # (config readers, call-graph lock/collective lookups) need the
        # unchanged files as context or they report false positives —
        # and only findings IN changed files gate the pre-commit run
        changed_set = set(changed)
        findings = [f for f in findings if f.path in changed_set]

    if args.write_baseline:
        analysis.baseline.save(args.write_baseline, findings)
        print("wrote %d finding(s) to %s"
              % (len(findings), args.write_baseline))
        return 0

    new = None
    stale = None
    if args.baseline:
        try:
            base = analysis.baseline.load(args.baseline)
        except (OSError, ValueError) as e:
            print("tpulint: cannot load baseline: %s" % e, file=sys.stderr)
            return 2
        new, _known, stale = analysis.baseline.diff(findings, base)

    if args.json:
        sys.stdout.write(analysis.report.render_json(
            findings, new, stale, args.baseline))
    else:
        print(analysis.report.render_text(findings, new, stale))

    if new is not None:
        return 1 if new else 0
    parse_errors = [f for f in findings if f.check == "parse-error"]
    return 1 if parse_errors else 0


def smoke(root=None):
    """One-line summary with per-family counts."""
    analysis = load_analysis()
    findings = analysis.run_suite(os.path.abspath(root or REPO))
    counts = analysis.severity_counts(findings)
    new = None
    base_path = os.path.join(REPO, "tools", "lint_baseline.json")
    if os.path.isfile(base_path):
        try:
            new, _k, _s = analysis.baseline.diff(
                findings, analysis.baseline.load(base_path))
        except (OSError, ValueError):
            pass
    line = "lint %d finding(s) HIGH %d MEDIUM %d LOW %d" % (
        len(findings), counts["HIGH"], counts["MEDIUM"], counts["LOW"])
    if new is not None:
        line += " new %d" % len(new)
    fam_of = analysis.checkers.CHECK_FAMILY
    per_family = {cls.id: 0 for cls in analysis.checkers.CHECKER_CLASSES}
    for f in findings:
        per_family[fam_of.get(f.check, "other")] = \
            per_family.get(fam_of.get(f.check, "other"), 0) + 1
    line += " | " + " ".join(
        "%s %d" % (fam, n) for fam, n in per_family.items())
    return line


if __name__ == "__main__":
    sys.exit(run())
