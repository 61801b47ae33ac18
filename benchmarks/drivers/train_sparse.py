"""Traffic of kind `train_sparse`: drivers/train.py's run on a training set
that arrives as a scipy CSR matrix and that the system bundles (io/efb.py).

The blocks are train.py's: `Booster.update()` back to back with no sync
inside a block, each block ended by train.py's `_wait`.  The phases, the
binned cache, the checks after the window and the keys of `shape` and
`end_to_end` are train.py's too.  What differs:

- **the window is a count, not a time, and `train_iter_ms` is read at a
  stated depth of the trees**.  Trees of one-hot data are deep and
  lopsided, a tree's time is its passes over the rows (the sum of its
  internal nodes' row counts over the rows) times a cost a pass plus a
  cost a tree, and another `--seed` (other labels) grows other trees
  within a few iterations: their passes differ by 1 to 3 % a block of
  five and by 1 % over 40 or 100 iterations, and the iteration time
  climbs by 40 % over the first 40.  A window that ends by the clock
  gave a faster program later, slower trees and spread by 1.3 % over
  seeds; the plain mean over a fixed 100 iterations still by 1.8 %
  (PERF.md, PR 32).  Time against passes, block by block, is one line to
  0.02 % whatever the seed.  So the window is the traffic file's
  `window_iterations`, the same iterations of a fresh model in every run
  whatever `--seconds` says (40 of them are 21 s at PR 32's speed, against
  `run_seconds` 20), the line ms = a * passes + b is laid through its
  blocks (Theil-Sen: the median of the pairwise slopes, then the median
  intercept, which one stalled block does not move), and `train_iter_ms`
  is that line at the traffic file's `reference_row_passes`: the time of
  an iteration whose trees make the configuration's usual passes.  A
  faster pass or a cheaper tree shows one for one; trees that are merely
  other trees do not.  The `trees` line gives a, b and each block's
  passes, the `window` line the plain mean;
- `X` is CSR from the generator to the Dataset: row slices stay sparse,
  and only the `walker_rows` rows of the plain walker are densified;
- the reference check is harness/checks_bundled.py's (the plain grower on
  per-column bins the check makes itself), because checks.py's stops at a
  bundled set; and because the system decides its bundles on a sample of
  the rows, the rows of the whole training set on which two columns of
  one bundle are both nonzero (one of the two entries is lost there) are
  counted and held to `correct.conflict_rows_max_share`;
- `shape["features"]` is the number of group columns G the arena holds,
  which is what the partition and root kernels move and what the roofline
  readers reckon their bytes from; the data set's column count goes under
  `shape["columns"]`.

train.py's run is one function, so the blocks are written here a second
time (PERF.md, Open questions: a `benchmark` issue folds the two).
"""
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmarks.harness import binned, checks, checks_bundled, manifest

# rows per block of the conflict count: a block's arrays (2M entries, 2.4M
# cells at 37 groups) stay under the allocator's 32 MB mmap threshold and
# are reused from block to block; at 2^19 rows every block touched fresh
# pages, 11 s against 2.3 s over the 13.18M rows (a CPU run, PR 32)
_ROWS = 1 << 16
_THREADS = 8              # set-up only, as in harness/rand.py


def _bundling(ds, gbdt):
    """What binning made of the columns and where the split scan ran."""
    b = ds._binned
    info = b.bundle
    plan = getattr(gbdt, "_engine_plan", None) or {}
    return {
        "columns": int(b.num_total_features),
        "columns_kept": int(b.num_features),
        "groups": int(info.num_groups) if info is not None
        else int(b.num_features),
        "largest_group_bins": int(info.group_num_bins.max())
        if info is not None else None,
        # the rows of the bin sample on which two columns of one group are
        # both nonzero; a program that does not count them says nothing
        "conflicts": getattr(info, "conflicts", None),
        # None: a program that does not say (it scans in feature space)
        "scan_space": plan.get("scan_space"),
    }


def _conflict_rows(X, b):
    """(rows of X on which two kept columns of one bundle are both
    nonzero, the entries lost there): the later column of the group wins
    such a row (io/efb.py) and the others read as zero.  The system counts
    this on its bin sample only; here it is every row of the CSR matrix,
    one pass over the stored entries."""
    info = b.bundle
    if info is None:
        return 0, 0
    group = np.asarray(info.feature_group, np.int64)
    shared = np.array([len(g) > 1 for g in info.groups])
    group_of = np.full(X.shape[1], -1, np.int64)     # column -> bundle
    group_of[np.asarray(b.real_feature_index)] = np.where(
        shared[group], group, -1)
    G = int(info.num_groups)

    def count(lo):
        hi = min(lo + _ROWS, X.shape[0])
        ptr = X.indptr[lo:hi + 1]
        g = group_of[X.indices[ptr[0]:ptr[-1]]]
        cell = np.repeat(np.arange(hi - lo) * G, np.diff(ptr)) + g
        keep = (g >= 0) & (X.data[ptr[0]:ptr[-1]] != 0)
        cells = np.bincount(cell[keep], minlength=(hi - lo) * G)
        twice = cells > 1         # a (row, bundle) cell with several entries
        return (int(twice.reshape(hi - lo, G).any(axis=1).sum()),
                int((cells[twice] - 1).sum()))

    with ThreadPoolExecutor(_THREADS) as pool:
        found = list(pool.map(count, range(0, X.shape[0], _ROWS)))
    return sum(r for r, _ in found), sum(e for _, e in found)


def _at_reference_depth(block_ms, block_passes, reference):
    """(ms an iteration at `reference` passes over the rows, ms a pass,
    ms a tree): the Theil-Sen line through the blocks' (passes, ms).  A
    window too short for a line (a rehearsal's two blocks) gives its plain
    mean."""
    ms, passes = np.asarray(block_ms), np.asarray(block_passes)
    i, j = np.triu_indices(len(ms), 1)
    run = passes[j] - passes[i]
    if np.count_nonzero(run) < 3:
        return float(ms.mean()), None, None
    a = float(np.median((ms[j] - ms[i])[run != 0] / run[run != 0]))
    b = float(np.median(ms - a * passes))
    return a * reference + b, a, b


def run(bench):
    import lightgbm_tpu as lgb
    train = manifest.load_module(bench.root, "drivers", "train")
    cell = bench.cell
    cfg, traffic = cell.config, cell.traffic
    c, data = cfg["correct"], cfg["data"]
    params = dict(cfg["params"])
    for key in cfg["seed_params"]:
        params[key] = bench.seed
    problems = []

    with bench.phase("check"):
        problems += checks_bundled.against_reference(bench, lgb, params)

    params.update(traffic["params"])
    for key in traffic["seed_params"]:
        params[key] = bench.seed
    gen = cell.generator()
    with bench.phase("data"):
        X = gen.features(data["args"], "train", data["rows"])
        y, group = gen.labels(data["args"], bench.seed, "train", X)
    with bench.phase("bin"):
        ds, from_cache = binned.cached(
            bench, lgb, X, y, group, params, "%s-%d-%d" % (
                cell.config_name, data["rows"], data["args"]["feature_seed"]))
    if bench.trace:
        # a fresh sparse ingest with its bundling, on record in runs that
        # loaded the cache
        with bench.phase("bin_256k"):
            head = slice(0, 1 << 18)
            binned.fresh(lgb, X[head], y[head], None, params)
    with bench.phase("check"):
        conflict_rows, lost_entries = _conflict_rows(X, ds._binned)
    floor = c["floor"]
    if floor["part"] == "train":
        Xq, yq = X[:floor["rows"]].copy(), y[:floor["rows"]]
    else:
        Xq = gen.features(data["args"], floor["part"], floor["rows"])
        yq, _ = gen.labels(data["args"], bench.seed, floor["part"], Xq)
    del X

    with bench.phase("booster"):
        booster = lgb.Booster(params, ds)
        gbdt = booster._gbdt
    with bench.phase("compile"):
        booster.update()
        train._wait(gbdt)
    with bench.phase("warmup"):
        for _ in range(traffic["warmup_iterations"] - 1):
            arena_before = gbdt._arena
            booster.update()
        train._wait(gbdt)
        gbdt._sync_model()
    if not arena_before.is_deleted():
        problems.append("the arena was not donated: the iteration keeps a "
                        "second copy of it")
    bundling = _bundling(ds, gbdt)
    rows = int(ds.num_data())
    bench.say("setup", binned_from_cache=from_cache, rows=rows,
              warmup_leaves=[t.num_leaves for t in gbdt.models],
              conflict_rows=conflict_rows, lost_entries=lost_entries,
              **bundling)
    if conflict_rows > c["conflict_rows_max_share"] * rows:
        problems.append(
            "bundling: on %d of %d rows two columns of one bundle are both "
            "nonzero and an entry is lost, over the share %g"
            % (conflict_rows, rows, c["conflict_rows_max_share"]))
    want = cfg["expect"].get("scan_space")
    if bundling["scan_space"] not in (None, want):
        problems.append("path: scan_space is %r, the cell states %r"
                        % (bundling["scan_space"], want))

    block = traffic["block_iterations"]
    block_ms, done = [], 0
    bench.open_window()
    for _ in range(traffic["window_iterations"] // block):
        tb = time.perf_counter()
        for _ in range(block):
            with bench.span("update"):
                booster.update()
        with bench.span("sync"):
            train._wait(gbdt)
        block_ms.append((time.perf_counter() - tb) / block * 1e3)
        done += block
    window_s = bench.close_window()
    bench.say("window", iterations=done, window_s=window_s,
              mean_ms_per_iter=window_s / done * 1e3,
              block_ms_per_iter=block_ms)

    if bench.trace:
        with bench.traced():
            for _ in range(traffic["trace_iterations"]):
                with bench.span("update"):
                    booster.update()
            with bench.span("sync"):
                train._wait(gbdt)

    gbdt._sync_model()
    first = traffic["warmup_iterations"]
    trees = gbdt.models[first:first + done]
    passes = [float(t.internal_count[:t.num_leaves - 1].sum()) / rows
              for t in trees]
    block_passes = [float(np.mean(passes[i:i + block]))
                    for i in range(0, done, block)]
    reference = traffic["reference_row_passes"]
    iter_ms, ms_per_pass, ms_per_tree = _at_reference_depth(
        block_ms, block_passes, reference)
    bench.say("trees", first=first, count=done,
              row_passes_per_iter=float(np.mean(passes)),
              block_row_passes_per_iter=block_passes,
              reference_row_passes=reference, ms_per_row_pass=ms_per_pass,
              ms_per_tree=ms_per_tree, ms_per_iter_at_reference=iter_ms)
    full = params["num_leaves"]
    failed = sum(1 for t in trees
                 if t.num_leaves < full
                 or not np.isfinite(t.leaf_value[:t.num_leaves]).all())
    if gbdt._truncation_warned:
        problems.append("a tree was truncated by the arena")
    if failed:
        problems.append("%d of %d trees of the window have fewer than %d "
                        "leaves or a non-finite value" % (failed, done, full))
    took, wrong = train._path_problems(gbdt, cell)
    problems += wrong
    walker_rows = np.asarray(Xq[:c["walker_rows"]].toarray(), np.float64)
    problems += checks.against_walker(bench, booster, walker_rows,
                                      c["walker_atol"])
    n_trees = min(floor["trees"], len(gbdt.models))
    q = checks.quality_of(floor["metric"], yq,
                          booster.predict(Xq, num_iteration=n_trees), None)
    bench.say("quality", metric=floor["metric"], value=q, trees=n_trees,
              rows=len(yq), part=floor["part"], path=took)
    if not q >= floor["min"]:
        problems.append("%s %.4f after %d trees is under the floor %.2f"
                        % (floor["metric"], q, n_trees, floor["min"]))
    return {
        "attempted": done, "failed": failed, "problems": problems,
        "end_to_end": {"train_iter_ms": iter_ms},
        "shape": {"rows": rows,
                  "features": bundling["groups"],
                  "columns": bundling["columns"],
                  "max_bin": params["max_bin"], "units": done,
                  "traced_units": traffic["trace_iterations"]},
    }
