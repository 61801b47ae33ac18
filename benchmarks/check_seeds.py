"""A cell's check against the plain reference alone, over many seeds in
one process: what decides `correct` before the window opens, without the
training set, the window or the trace.

    python3 benchmarks/check_seeds.py --workload <cell> --seeds 1,2,3
        [--system-params '{"min_sum_hessian_in_leaf": 50}'] [--rehearse]

The check is a function of `--seed` and the configuration's sample alone,
and in the widest cell it is a minute of every four-minute run: a dozen
seeds, or a control on three, are one call to the chip here.  With
`--system-params` the run is a *control*: every booster the check trains
gets those parameters laid over the configuration's while the reference
keeps the configuration's own, so the line must read `correct: false`
(tests/benchmark/test_bench_reference.py keeps one at a size a test run can
hold).  One `[check]` line a seed, then a last line with the counts.
Exits 3 without a TPU unless `--rehearse`, as run.py does; it is no part
of a benchmark run and no metric is read from it.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

NO_CHIP_EXIT = 3


def _check_of(cell, name):
    """The check drivers/train.py would find for the cell under the name
    given, or under the cell's own (drivers/train_sparse.py takes the
    bundled one whatever is named)."""
    from benchmarks.harness import manifest
    if name is None and cell.traffic["kind"] == "train_sparse":
        name = "bundled"
    if name is not None:
        cell.config["correct"]["check"] = name
    return manifest.load_module(cell.root, "drivers",
                                "train")._reference_check(cell)


def main(argv=None, root=ROOT):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="whole numbers, separated by commas")
    ap.add_argument("--check", default=None,
                    help="harness/checks_<name>.py; by default the cell's")
    ap.add_argument("--system-params", type=json.loads, default={})
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from benchmarks.harness import checks
    from benchmarks.harness.bench import Bench
    from benchmarks.harness.manifest import Cell
    cell = Cell(root, args.workload, rehearse=args.rehearse)

    import jax
    import lightgbm_tpu as lgb
    platform = jax.devices()[0].platform
    if not args.rehearse and platform != "tpu":
        print("benchmarks/check_seeds.py: needs a TPU; JAX reports %s "
              "(--rehearse runs the tiny preset here)" % platform,
              file=sys.stderr)
        return NO_CHIP_EXIT

    against_reference = _check_of(cell, args.check)
    plain_train = checks._train
    if args.system_params:
        # the control: the system departs, the reference does not
        checks._train = lambda lgb_, params, ds, trees: plain_train(
            lgb_, dict(params, **args.system_params), ds, trees)
    verdicts = []
    try:
        for seed in (int(s) for s in args.seeds.split(",")):
            t0 = time.perf_counter()
            bench = Bench(root, cell, seed, 0, 0, t0)
            params = dict(cell.config["params"])
            for key in cell.config["seed_params"]:
                params[key] = seed
            problems = against_reference(bench, lgb, params)
            verdicts.append(not problems)
            print("[check] " + json.dumps(dict(
                cell=cell.name, seed=seed, correct=not problems,
                problems=problems, system_params=args.system_params,
                compared=bench.compared_record(),
                seconds=time.perf_counter() - t0)), flush=True)
    finally:
        checks._train = plain_train
    print(json.dumps(dict(cell=cell.name, platform=platform,
                          seeds=len(verdicts), correct=sum(verdicts),
                          system_params=args.system_params)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
