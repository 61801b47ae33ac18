#!/usr/bin/env python
"""Regression sentinel over a span-trace file.

Summarizes one trace (single-rank file or a trace_merge.py output) into
the numbers a perf PR argues with — per-phase p50/p95 latency and call
counts, XLA compile/retrace counts, the share of wall time spent blocked
on comm peers — and compares them against a committed baseline JSON,
exiting nonzero on any breach, so "this
PR made tree_grow 2x slower" or "this PR added 30 retraces" fails the
build instead of landing as an anecdote.

Baseline schema (only the keys present are enforced):

    {
      "phases": {
        "tree_grow": {"p95_ms_max": 120.0, "count_min": 5},
        "boosting":  {"p95_ms_max": 40.0}
      },
      "max_backend_compiles": 60,
      "max_retraces": 400,
      "max_comm_wait_share": 0.5
    }

Usage:
    python tools/trace_check.py TRACE [--baseline BASELINE.json]
    python tools/trace_check.py TRACE --write-baseline BASELINE.json \
        [--margin 1.5]
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List


def _percentile(sorted_vals: List[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(int(q * len(sorted_vals)), len(sorted_vals) - 1)
    return sorted_vals[idx]


def summarize(trace: Dict) -> Dict:
    """Trace-event object -> summary dict (the check's input)."""
    events = trace.get("traceEvents", [])
    meta = trace.get("metadata") or {}
    durs: Dict[str, List[float]] = {}
    wall_lo, wall_hi = float("inf"), 0.0
    comm_wait_us = 0.0
    compile_spans = 0
    for e in events:
        if e.get("ph") != "X":
            continue
        ts, dur = float(e.get("ts", 0)), float(e.get("dur", 0))
        wall_lo, wall_hi = min(wall_lo, ts), max(wall_hi, ts + dur)
        name = e.get("name", "")
        durs.setdefault(name, []).append(dur / 1e3)
        if name == "comm/wait":
            comm_wait_us += dur
        if e.get("cat") == "xla":
            compile_spans += 1
    wall_ms = (wall_hi - wall_lo) / 1e3 if wall_hi > wall_lo else 0.0

    phases = {}
    for name, vals in sorted(durs.items()):
        vals.sort()
        total = sum(vals)
        phases[name] = {
            "count": len(vals),
            "total_ms": round(total, 3),
            "p50_ms": round(_percentile(vals, 0.50), 3),
            "p95_ms": round(_percentile(vals, 0.95), 3),
            "share": round(total / wall_ms, 4) if wall_ms else 0.0,
        }
    compile_counts = meta.get("compile_counts") or {}
    return {
        "wall_ms": round(wall_ms, 3),
        "events": len(events),
        "phases": phases,
        "backend_compiles": int(compile_counts.get("backend_compiles",
                                                   compile_spans)),
        "retraces": int(compile_counts.get("traces", 0)),
        "compile_spans": compile_spans,
        "comm_wait_share": (round(comm_wait_us / 1e3 / wall_ms, 4)
                            if wall_ms else 0.0),
        "dropped_events": int(meta.get("dropped_events", 0)),
    }


def check(summary: Dict, baseline: Dict) -> List[str]:
    """-> list of human-readable breach descriptions (empty = pass)."""
    breaches: List[str] = []
    for name, limits in (baseline.get("phases") or {}).items():
        got = summary["phases"].get(name)
        if got is None:
            if limits.get("count_min", 0) > 0:
                breaches.append("phase %r missing from trace (count_min=%d)"
                                % (name, limits["count_min"]))
            continue
        p95_max = limits.get("p95_ms_max")
        if p95_max is not None and got["p95_ms"] > float(p95_max):
            breaches.append("phase %r p95 %.3f ms > baseline %.3f ms"
                            % (name, got["p95_ms"], float(p95_max)))
        count_min = limits.get("count_min")
        if count_min is not None and got["count"] < int(count_min):
            breaches.append("phase %r ran %d times < baseline min %d"
                            % (name, got["count"], int(count_min)))
    for key, field in (("max_backend_compiles", "backend_compiles"),
                       ("max_retraces", "retraces")):
        limit = baseline.get(key)
        if limit is not None and summary[field] > int(limit):
            breaches.append("%s %d > baseline %d"
                            % (field, summary[field], int(limit)))
    limit = baseline.get("max_comm_wait_share")
    if limit is not None and summary["comm_wait_share"] > float(limit):
        breaches.append("comm_wait_share %.4f > baseline %.4f"
                        % (summary["comm_wait_share"], float(limit)))
    return breaches


def make_baseline(summary: Dict, margin: float) -> Dict:
    """Derive a baseline from a known-good trace, padded by ``margin``
    so ordinary run-to-run noise does not trip the sentinel."""
    return {
        "phases": {
            name: {"p95_ms_max": round(p["p95_ms"] * margin, 3),
                   "count_min": 1}
            for name, p in summary["phases"].items()
        },
        "max_backend_compiles": int(summary["backend_compiles"] * margin) + 1,
        "max_retraces": int(summary["retraces"] * margin) + 1,
        "max_comm_wait_share": min(
            round(summary["comm_wait_share"] * margin + 0.05, 4), 1.0),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Summarize a span trace and enforce a perf baseline")
    ap.add_argument("trace", help="trace file (per-rank or merged)")
    ap.add_argument("--baseline", help="baseline JSON to enforce")
    ap.add_argument("--write-baseline", metavar="PATH",
                    help="derive a baseline from this trace instead of "
                         "checking")
    ap.add_argument("--margin", type=float, default=1.5,
                    help="headroom factor for --write-baseline "
                         "(default 1.5)")
    ap.add_argument("--json", action="store_true",
                    help="print the summary as JSON instead of a table")
    args = ap.parse_args(argv)

    try:
        with open(args.trace) as f:
            trace = json.load(f)
        if not isinstance(trace, dict) or "traceEvents" not in trace:
            raise ValueError("no traceEvents key — not a Chrome "
                             "trace-event JSON object")
    except (OSError, ValueError, json.JSONDecodeError) as exc:
        print("trace_check: cannot read %s: %s" % (args.trace, exc),
              file=sys.stderr)
        return 2

    summary = summarize(trace)
    if args.json:
        print(json.dumps(summary, indent=1, sort_keys=True))
    else:
        print("trace %s: %.1f ms wall, %d events, %d backend compiles, "
              "%d retraces, comm wait share %.2f%%"
              % (args.trace, summary["wall_ms"], summary["events"],
                 summary["backend_compiles"], summary["retraces"],
                 summary["comm_wait_share"] * 100))
        for name, p in summary["phases"].items():
            print("  %-24s %6d calls  p50 %9.3f ms  p95 %9.3f ms  "
                  "share %5.1f%%" % (name, p["count"], p["p50_ms"],
                                     p["p95_ms"], p["share"] * 100))

    if args.write_baseline:
        baseline = make_baseline(summary, args.margin)
        with open(args.write_baseline, "w") as f:
            json.dump(baseline, f, indent=1, sort_keys=True)
            f.write("\n")
        print("baseline written to %s (margin %.2fx)"
              % (args.write_baseline, args.margin))
        return 0

    if args.baseline:
        try:
            with open(args.baseline) as f:
                baseline = json.load(f)
        except (OSError, json.JSONDecodeError) as exc:
            print("trace_check: cannot read baseline %s: %s"
                  % (args.baseline, exc), file=sys.stderr)
            return 2
        breaches = check(summary, baseline)
        if breaches:
            for b in breaches:
                print("BREACH: %s" % b, file=sys.stderr)
            return 1
        print("baseline %s: OK (%d phase limits enforced)"
              % (args.baseline, len(baseline.get("phases") or {})))
    return 0


if __name__ == "__main__":
    sys.exit(main())
