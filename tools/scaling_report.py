#!/usr/bin/env python
"""Efficiency-waterfall scaling report: where each world size's round
wall goes, per dtype, against a committed baseline.

Runs the mesh scaling matrix (reusing tools/mesh_bench.py plumbing —
same dataset shapes, same partition-engine params, telemetry armed so
obs/scaling.py emits per-round step decompositions), averages the legs
per world, and fits them into the loss waterfall

    ideal -> +host_sync -> +dispatch_gap -> +psum -> +leader_wire
          -> measured

where ``ideal`` is the world-1 round wall divided by w and each loss
leg is that world's cost in EXCESS of perfect 1/w scaling.  The named
legs plus a residual sum to the measured wall identically (the
per-round decomposition partitions the wall exactly); |residual| /
measured is the health number gated here.

Exit codes follow the trace_check contract:

    0  waterfall healthy and within the committed baseline
    1  breach: residual above tolerance, efficiency below floor, or
       host share above ceiling for some world/dtype
    2  baseline missing/unreadable (or bench produced no decomposition)

Usage:

    python tools/scaling_report.py                       # report + gate
    python tools/scaling_report.py --json                # machine output
    python tools/scaling_report.py --write-baseline      # (re)pin
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        JAX_PLATFORMS=cpu python tools/scaling_report.py --worlds 1,2,4
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

BASELINE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "scaling_baseline.json")
DTYPES = ("f32", "int8")


def build_report(worlds, rows, features, iters, leaves):
    """Run the scaling matrix and fit the waterfall per dtype."""
    from lightgbm_tpu.obs import scaling as obs_scaling
    from tools import mesh_bench

    bench = mesh_bench.run(worlds, rows, features, iters, leaves)
    report = {"n_devices": bench["n_devices"], "rows": rows,
              "timed_iters": iters, "backend": bench["backend"],
              "worlds": sorted(bench_worlds(bench)), "waterfall": {}}
    for kind in DTYPES:
        per_world = {}
        for w in report["worlds"]:
            legs = (bench["runs"].get("w%d_%s" % (w, kind))
                    or {}).get("legs_ms")
            if legs:
                per_world[w] = legs
        wf = obs_scaling.efficiency_waterfall(per_world)
        if wf:
            report["waterfall"][kind] = {str(w): v for w, v in wf.items()}
    report["runs"] = bench["runs"]
    return report


def bench_worlds(bench):
    return {r["world"] for r in bench["runs"].values()}


def render(report) -> str:
    lines = ["scaling waterfall (%s, %d devices, %d rows)"
             % (report["backend"], report["n_devices"], report["rows"])]
    for kind, wf in sorted(report["waterfall"].items()):
        for w in sorted(wf, key=int):
            e = wf[w]
            legs = e["legs"]
            lines.append(
                "  %-4s w=%s measured %.1fms ideal %.1fms | %s | "
                "dominant=%s eff=%.3f host_share=%.3f resid=%.1f%%"
                % (kind, w, e["measured_ms"], legs["ideal"],
                   " ".join("%s+%.1f" % (k, legs[k])
                            for k in ("host_sync", "dispatch_gap",
                                      "psum", "leader_wire")),
                   e["dominant_loss"], e["efficiency"], e["host_share"],
                   100.0 * e["residual_share"]))
    return "\n".join(lines)


def check(report, baseline, margin) -> list:
    """Gate the waterfall against tolerance + committed floors/ceilings.
    Returns a list of breach strings (empty = pass)."""
    breaches = []
    resid_max = float(baseline.get("residual_share_max", 0.10))
    for kind, wf in report["waterfall"].items():
        base_k = (baseline.get("dtypes", {}).get(kind, {})
                  .get("worlds", {}))
        for w, e in wf.items():
            if e["residual_share"] > resid_max:
                breaches.append(
                    "%s w=%s: residual share %.3f > %.3f (legs do not "
                    "sum to the measured wall)"
                    % (kind, w, e["residual_share"], resid_max))
            pin = base_k.get(str(w))
            if not pin:
                continue
            floor = float(pin.get("efficiency_min", 0.0)) * (1.0 - margin)
            if e["efficiency"] < floor:
                breaches.append(
                    "%s w=%s: efficiency %.4f below floor %.4f"
                    % (kind, w, e["efficiency"], floor))
            ceil = pin.get("host_share_max")
            if ceil is not None and e["host_share"] > float(ceil):
                breaches.append(
                    "%s w=%s: host share %.4f above ceiling %.4f"
                    % (kind, w, e["host_share"], float(ceil)))
    return breaches


def pin_from(report) -> dict:
    """Baseline skeleton pinned at the current run's numbers: the
    measured efficiency becomes the floor (margin applied at check
    time) and the host share ceiling gets generous headroom."""
    dtypes = {}
    for kind, wf in report["waterfall"].items():
        worlds = {}
        for w, e in wf.items():
            worlds[w] = {
                "efficiency_min": e["efficiency"],
                "host_share_max": round(
                    min(1.0, max(0.25, 2.0 * e["host_share"] + 0.1)), 4),
            }
        dtypes[kind] = {"worlds": worlds}
    return {"residual_share_max": 0.10, "dtypes": dtypes}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--worlds", default=None,
                    help="comma-separated world sizes "
                         "(default 1,2,4,8 on tpu, 1,2,4 off)")
    ap.add_argument("--rows", type=int, default=None)
    ap.add_argument("--features", type=int, default=16)
    ap.add_argument("--iters", type=int, default=None)
    ap.add_argument("--leaves", type=int, default=None)
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="print the full report as one JSON object")
    ap.add_argument("--baseline", default=BASELINE)
    ap.add_argument("--write-baseline", action="store_true",
                    help="pin the committed baseline at this run")
    ap.add_argument("--margin", type=float, default=0.5,
                    help="fractional slack on efficiency floors "
                         "(default 0.5 — CPU-smoke timings are noisy)")
    args = ap.parse_args(argv)

    import jax
    from lightgbm_tpu.utils.backend import on_tpu as _on_tpu
    on_tpu = _on_tpu()
    worlds = sorted({int(w) for w in
                     (args.worlds or ("1,2,4,8" if on_tpu else "1,2,4")
                      ).split(",")})
    rows = args.rows if args.rows else (2_000_000 if on_tpu else 1024)
    iters = args.iters if args.iters else (50 if on_tpu else 2)
    leaves = args.leaves if args.leaves else (255 if on_tpu else 15)

    report = build_report(worlds, rows, args.features, iters, leaves)
    if not report["waterfall"]:
        print("scaling_report: no step decomposition in any run "
              "(telemetry disabled?)", file=sys.stderr)
        return 2

    if args.write_baseline:
        with open(args.baseline, "w") as fh:
            json.dump(pin_from(report), fh, indent=2, sort_keys=True)
            fh.write("\n")
        print("baseline written to %s" % args.baseline)

    try:
        with open(args.baseline) as fh:
            baseline = json.load(fh)
    except (OSError, ValueError) as exc:
        print(render(report))
        print("scaling_report: baseline unreadable (%s): %s"
              % (args.baseline, exc), file=sys.stderr)
        return 2

    breaches = check(report, baseline, args.margin)
    if args.as_json:
        report["breaches"] = breaches
        print(json.dumps(report))
    else:
        print(render(report))
        for b in breaches:
            print("BREACH: %s" % b)
    return 1 if breaches else 0


if __name__ == "__main__":
    sys.exit(main())
