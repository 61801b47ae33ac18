"""sha256 of the model text after a few trees of one benchmark cell.

    python tools/model_hash.py --workload <cell> [--trees 5] [--seed 1]
                               [--rehearse]

Trains the cell's configuration as benchmarks/drivers/train.py does (same
data, parameters and binned cache) on the checkout in the CURRENT
directory, so two checkouts can be compared on one machine: a change that
must not move a tree prints the hash its parent prints.
"""
import argparse
import hashlib
import json
import os
import sys
import time

ROOT = os.getcwd()
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--trees", type=int, default=5)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    from benchmarks.harness import binned
    from benchmarks.harness.bench import Bench
    from benchmarks.harness.manifest import Cell
    import jax
    import lightgbm_tpu as lgb
    cell = Cell(ROOT, args.workload, rehearse=args.rehearse)
    bench = Bench(ROOT, cell, args.seed, 0, 0, time.perf_counter())
    cfg, traffic = cell.config, cell.traffic
    params = dict(cfg["params"], **traffic["params"])
    for key in list(cfg["seed_params"]) + list(traffic["seed_params"]):
        params[key] = args.seed
    data = cfg["data"]
    gen = cell.generator()
    X = gen.features(data["args"], "train", data["rows"])
    y, group = gen.labels(data["args"], args.seed, "train", X)
    ds, _ = binned.cached(bench, lgb, X, y, group, params, "%s-%d-%d" % (
        cell.config_name, data["rows"], data["args"]["feature_seed"]))
    booster = lgb.Booster(params, ds)
    for _ in range(args.trees):
        booster.update()
    text = booster.model_to_string()
    print(json.dumps({
        "workload": cell.name, "root": ROOT, "seed": args.seed,
        "trees": args.trees, "rows": int(ds.num_data()),
        "leaves": [t.num_leaves for t in booster._gbdt.models],
        "sha256": hashlib.sha256(text.encode()).hexdigest(),
        "device": jax.devices()[0].device_kind}))


if __name__ == "__main__":
    main()
