"""Runs one cell of BENCHMARK.json once.

    python3 benchmarks/run.py --workload <cell> --seed <n>
                              --seconds <s> --trace <0|1>

A new process: it loads, warms up every shape the cell uses (all of that
is `setup_s`), measures for `--seconds`, checks that what the system
produced is correct, and prints one JSON object as the last line of
standard output with the keys `correct`, `attempted`, `failed`, `metrics`
and `device` (and `breakdown` in a traced run), then `compared`: each
number `correct` was decided by beside its limit, which are also the last
lines on standard error.  With `--trace 0` the metrics are the cell's
end-to-end metrics; with `--trace 1` a short slice after the window is
traced with jax.profiler and the metrics are the cell's per-layer metrics.
Everything else worth reading is on earlier `[bench] {...}` lines.

Without a TPU, or with fewer chips than the cell asks for, it exits 3 and
prints no result.  `--rehearse` runs the tiny preset the cell's files
carry, on whatever backend JAX has (the CPU, kernels in interpret mode):
it names that platform and reports counts only — every metric's value is
null, because a time taken off the chip is not a measurement.

A chip belongs to one process: this one starts no other, and must not be
started from a process that has touched JAX.
"""
import time

_T_START = time.perf_counter()          # before the heavy imports: set-up

import argparse                          # noqa: E402
import json                              # noqa: E402
import os                                # noqa: E402
import sys                               # noqa: E402
import types                             # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)       # the checkout: lightgbm_tpu, benchmarks

NO_CHIP_EXIT = 3


def _device(jax, chips):
    devices = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices[:chips]]
    peaks = [p for p in peaks if p is not None]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(peaks) if peaks else None}


def main(argv=None, root=ROOT):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from benchmarks.harness import trace_reduce
    from benchmarks.harness.bench import Bench
    from benchmarks.harness.manifest import Cell
    cell = Cell(root, args.workload, rehearse=args.rehearse)
    if args.seconds is None:
        args.seconds = cell.run_seconds

    import jax
    import lightgbm_tpu  # noqa: F401 — places the compile cache
    found = _device(jax, cell.chips)
    if not args.rehearse and (found["platform"] != "tpu"
                              or found["count"] < cell.chips):
        print("benchmarks/run.py: cell %s needs %d TPU chip(s); JAX reports "
              "%s.  Nothing was run (--rehearse runs the tiny preset here)."
              % (cell.name, cell.chips, json.dumps(found)), file=sys.stderr)
        return NO_CHIP_EXIT

    bench = Bench(root, cell, args.seed, args.seconds, args.trace, _T_START)
    bench.say("start", cell=cell.name, config=cell.config_name,
              traffic=cell.traffic_name, seed=args.seed,
              seconds=args.seconds, trace=args.trace, rehearse=args.rehearse,
              device=found, cache_dir=jax.config.jax_compilation_cache_dir)
    outcome = cell.driver().run(bench)
    problems = list(outcome["problems"])
    if bench.compiles_in_window:
        problems.append("%d compilation(s) inside the measured window"
                        % bench.compiles_in_window)

    device = _device(jax, cell.chips)
    values = dict(outcome["end_to_end"], setup_s=bench.setup_s)
    if device["memory_peak_bytes"] is not None:
        values["peak_hbm_gib"] = device["memory_peak_bytes"] / 2.0 ** 30
    wanted = cell.end_to_end
    traced = {}
    if args.trace:
        xplane = trace_reduce.find_xplane(bench.trace_dir)
        summary = trace_reduce.reduce(xplane) if xplane else None
        reading = types.SimpleNamespace(
            shape=outcome["shape"], phases=bench.phases,
            spans=bench.window_spans, trace=summary,
            device_kind=device["kind"])
        wanted, values = cell.per_layer, {}
        for metric, reader_args, reader in cell.layer_readers():
            values[metric["name"]] = reader.read(reading, reader_args)
        if summary is not None:
            device.update(busy_s=summary.busy_s, window_s=summary.window_s)
            traced["breakdown"] = summary.breakdown()
        bench.say("trace", xplane=xplane, reduced=summary is not None)
    bench.say("phases", setup_s=bench.setup_s, **bench.phases)
    # a metric whose reader found nothing to read is left out of the line
    # (on the chip that means BENCHMARK.json lists it for a cell that does
    # not have it: its `workloads` key is where to say so);
    # off the chip every value is null: a CPU time is not a measurement
    metrics = {m["name"]: {"value": None if args.rehearse
                           else values[m["name"]], "unit": m["unit"]}
               for m in wanted if values.get(m["name"]) is not None}
    bench.say("verdict", problems=problems,
              compiles_in_window=bench.compiles_in_window,
              left_out=[m["name"] for m in wanted
                        if m["name"] not in metrics])
    # each number `correct` was decided by, beside its limit: last on
    # standard error and last in the result's line
    compared = bench.compared_record()
    compared["problems"] = {"value": len(problems), "limit": 0}
    sys.stdout.flush()
    for name, pair in compared.items():
        print("compared %s %r limit %r" % (name, pair["value"],
                                           pair["limit"]), file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(dict(
        correct=not problems, attempted=outcome["attempted"],
        failed=outcome["failed"], metrics=metrics, device=device, **traced,
        compared=compared)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
