"""One traced run of a benchmark cell, and then what the run's own line
cannot carry: how well the split ledger and the trace were paired.

    python tools/row_ledger_fit.py --workload <cell> [--seed 1]
                                   [--seconds S] [--rehearse] [--save DIR]

Runs `benchmarks/run.py --trace 1` in this process (its lines and its
result line are printed as they are), then prints from the same trace and
the same ledger (`lightgbm_tpu.obs.device.split_ledgers()`,
benchmarks/readers/row_ledger.py) one `[fit] {"metrics": ...}` line with
the seven per-layer metrics of benchmarks/layer_metrics/ that read the
ledger (BENCHMARK.json lists them, with no `workloads` key: every train
cell on one chip reads them on its traced line), and one `[fit] {...}`
line per in-loop kernel:

- the Theil-Sen line seconds = a * rows + b through the slice's calls: a
  as ms a pass over the data set's rows, b in microseconds, and the median
  |residual| over the median call time;
- the same with the ledger shifted by one call in either direction (call
  i held against entry i + 1, and against entry i - 1): a pairing in the
  right order reads a smaller residual than both.  Two shares of each:
  the median |residual| over the median call time, and the summed
  |residual| over the summed time;
- the line against whole tiles of 2 048 rows in place of rows (time per
  tile, and an intercept that is the launch alone: the rows' line takes
  in the padding of a call's last tile), and the calls under one tile;
- the roofline share over all calls and over each tree's first call alone
  (for `partition_segment` the second is `partition_root_roofline`);
- for `partition_segment`, `pipelined_share`: the share of the slice's
  tiles whose predicate part was made a tile ahead, inside the iteration
  that moved the tile before them (PR 37: every tile but a call's first,
  1 - calls / tiles; 0 where the arena is cut into channel blocks, whose
  loop makes a tile's predicate part once for all its blocks and is not
  pipelined).

`--save DIR` leaves there every call's (rows, seconds) per kernel (JSON)
and, with `--rehearse`, the trace (gzip) and the slice's ledger entries:
how tests/benchmark/data/tiny_v5e_ledger.* were recorded on the chip
(`--workload higgs-int8.train --rehearse --seed 7`).
"""
import argparse
import gzip
import json
import os
import shutil
import sys
import types

ROOT = os.getcwd()
sys.path.insert(0, ROOT)

METRICS = ("kernel.partition.row_passes_per_iter",
           "kernel.partition.ms_per_pass", "partition_roofline",
           "kernel.partition.call_us", "kernel.seg_hist.ms_per_pass",
           "seg_hist_roofline", "kernel.seg_hist.call_us")
TILE = 2048      # rows a tile of either kernel today (ops/partition_pallas.py)
KERNELS = {
    "partition": ("^partition_segment\\b.* mosaic$", "partition_rows"),
    "seg_hist": ("^segment_histogram\\b.* mosaic$", "histogram_rows"),
}


def residual(reader, rows, seconds):
    """(seconds a row, intercept in us, median |residual| over the median
    call time, summed |residual| over the summed time) of the Theil-Sen
    line, or None.  The second share is the one to read where most calls
    are under one 2 048-row tile and so cost the same whatever their rows
    (Epsilon's histograms): any flat line then leaves a small median."""
    import numpy as np
    line = reader.theil_sen(rows, seconds)
    if line is None:
        return None
    a, b = line
    left = np.abs(np.asarray(seconds) - a * np.asarray(rows) - b)
    return (a, b * 1e6, float(np.median(left) / np.median(seconds)),
            float(left.sum() / np.sum(seconds)))


def fits(reader, run, name):
    pattern, field = KERNELS[name]
    trees = reader.paired(run, pattern, field)
    if trees is None:
        return {"kernel": name, "paired": False}
    rows = [r for tree in trees for r, _ in tree]
    seconds = [s for tree in trees for _, s in tree]
    a, b_us, left, left_sum = residual(reader, rows, seconds) or 4 * (None,)
    # call i against entry i + 1 / i - 1, tree by tree
    later = residual(reader,
                     [r for tree in trees for r, _ in tree[1:]],
                     [s for tree in trees for _, s in tree[:-1]])
    earlier = residual(reader,
                       [r for tree in trees for r, _ in tree[:-1]],
                       [s for tree in trees for _, s in tree[1:]])
    # the same line against whole tiles: both kernels work in tiles of
    # TILE rows, so a call's time is a step function of its rows
    tiled = residual(reader, [-(-r // TILE) for r in rows], seconds)
    args = {"pattern": pattern, "rows": field, "what": "roofline"}
    more = {}
    if name == "partition":
        from lightgbm_tpu.ops import partition_pallas as pp
        C = pp.arena_channels(run.shape["features"])
        blocks = C // pp.partition_channel_block(C)
        tiles = sum(-(-r // TILE) for r in rows)
        more = {"tiles": tiles, "channel_blocks": blocks,
                "pipelined_share":
                1.0 - len(rows) / tiles if blocks == 1 else 0.0}
    return dict(more, **{
        "kernel": name, "paired": True, "trees": len(trees),
        "calls": len(rows),
        "ms_per_pass_of_the_line": a and a * run.shape["rows"] * 1e3,
        "call_us": b_us, "residual_share": left,
        "residual_share_entry_later": later and later[2],
        "residual_share_entry_earlier": earlier and earlier[2],
        "summed_residual_share": left_sum,
        "summed_residual_share_entry_later": later and later[3],
        "summed_residual_share_entry_earlier": earlier and earlier[3],
        "per_tile_us": tiled and tiled[0] * 1e6,
        "launch_us_of_the_tile_line": tiled and tiled[1],
        "residual_share_of_the_tile_line": tiled and tiled[2],
        "summed_residual_share_of_the_tile_line": tiled and tiled[3],
        "calls_under_one_tile": sum(1 for r in rows if r <= TILE),
        "pairs": [[r, s] for tree in trees for r, s in tree],
        "roofline": reader.read(run, args),
        "roofline_first_calls": reader.read(run, dict(args, calls="first")),
        "passes": reader.read(run, dict(args, what="passes")),
        "ms_per_pass": reader.read(run, dict(args, what="ms_per_pass")),
    })


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--save", default=None)
    args = ap.parse_args()

    from benchmarks import run as bench_run
    from benchmarks.harness import manifest, xplane_names
    # the readers' view of the run (shape, reduced trace, device kind) is
    # not handed out by run.py: one more reader, of no metric, keeps it
    seen = []
    readers_of = manifest.Cell.layer_readers
    manifest.Cell.layer_readers = lambda cell: readers_of(cell) + [(
        {"name": "(tools/row_ledger_fit.py)"}, {},
        types.SimpleNamespace(read=lambda run, _: seen.append(run)))]
    argv = ["--workload", args.workload, "--seed", str(args.seed),
            "--trace", "1"]
    if args.seconds is not None:
        argv += ["--seconds", str(args.seconds)]
    if args.rehearse:
        argv.append("--rehearse")
    code = bench_run.main(argv, root=ROOT)
    if code or not seen:
        return code or 1
    run = seen[0]
    reader = manifest.load_module(ROOT, "readers", "row_ledger")
    specs = {m: manifest.load_json(ROOT, "benchmarks", "layer_metrics",
                                   m + ".json") for m in METRICS}
    print("[fit] " + json.dumps({"metrics": {
        m: {"value": None if args.rehearse
            else reader.read(run, specs[m]["args"]),
            "unit": specs[m]["entry"]["unit"]} for m in METRICS}}),
        flush=True)
    for name in KERNELS:
        fit = fits(reader, run, name)
        pairs = fit.pop("pairs", None)
        print("[fit] " + json.dumps(fit), flush=True)
        if args.save and pairs:
            # every call's (rows, seconds), for a look without the chip
            os.makedirs(args.save, exist_ok=True)
            with open(os.path.join(args.save, "pairs_%s_%s.json" % (
                    args.workload, name)), "w") as f:
                json.dump(pairs, f)
    if args.save and args.rehearse:
        from lightgbm_tpu.obs.device import split_ledgers
        os.makedirs(args.save, exist_ok=True)
        stem = os.path.join(args.save, "tiny_v5e_ledger")
        with open(xplane_names.trace_of(run), "rb") as src, \
                gzip.open(stem + ".xplane.pb.gz", "wb", 9) as dst:
            shutil.copyfileobj(src, dst)
        entries = [dict(e, partition_rows=[int(r) for r in e["partition_rows"]],
                        histogram_rows=[int(r) for r in e["histogram_rows"]])
                   for e in split_ledgers()[-run.shape["traced_units"]:]]
        with open(stem + ".json", "w") as f:
            json.dump({"shape": run.shape, "device_kind": run.device_kind,
                       "workload": args.workload, "seed": args.seed,
                       "ledgers": entries}, f)
        print("[fit] saved %s.{xplane.pb.gz,json}" % stem, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
