"""Traffic of kind `train`: `Booster.update()` back to back on a fresh
booster over the configuration's whole data set.

The loop runs in blocks with no sync inside a block; each block ends in
`jax.block_until_ready` on what the iteration wrote, which is what bounds
how far the host runs ahead of the device.  The window is the first
dispatch to the last sync and stops at the first block boundary past
`--seconds`.  `train_iter_ms` is the interquartile mean of the blocks'
times per iteration (harness/steady.py says why); the window's plain mean
goes on the `[bench]` window line.
"""
import time

import numpy as np

from benchmarks.harness import binned, checks, manifest
from benchmarks.harness.steady import interquartile_mean


def _wait(gbdt):
    """Block until the iteration's device work is done, without reading
    the lazily materialised score of the carried path (a read would run a
    sort over all rows that training itself never pays)."""
    import jax
    state = gbdt.train_state
    live = [gbdt._arena]
    if state._score_thunk is None:
        live.append(state._score)
    jax.block_until_ready(live)


def _path_problems(gbdt, cell):
    """The engine, precision and spine the run took, against what the
    cell's files state under `expect`."""
    want = dict(cell.config["expect"], **cell.traffic["expect"])
    fused = bool(getattr(gbdt, "_fused_validated", False))
    took = {
        "engine": "partition" if gbdt._use_partition_engine else "label",
        "quantized": bool(gbdt._quantized),
        "spine": "fused" if fused else "unfused",
        "carried": bool(getattr(gbdt, "_carried_active", False)),
    }
    if want["spine"] != "fused":
        want["carried"] = False          # only the fused spine can carry
    return took, ["path: %s is %r, the cell states %r" % (k, took[k], want[k])
                  for k in took if took[k] != want[k]]


def _reference_check(cell):
    """The cell's check against the plain reference, by the name its
    configuration gives under `correct.check`: `plain` (the default) is
    harness/checks.py's, any other name harness/checks_<name>.py's
    `against_reference(bench, lgb, params)`, found as drivers and readers
    are: a cell whose reference differs only in the check adds a file."""
    name = cell.config["correct"].get("check", "plain")
    if name == "plain":
        return checks.against_reference
    return manifest.load_module(cell.root, "harness",
                                "checks_" + name).against_reference


def run(bench):
    import lightgbm_tpu as lgb
    cell = bench.cell
    cfg, traffic = cell.config, cell.traffic
    c, data = cfg["correct"], cfg["data"]
    params = dict(cfg["params"])
    for key in cfg["seed_params"]:
        params[key] = bench.seed
    problems = []

    with bench.phase("check"):
        problems += _reference_check(cell)(bench, lgb, params)

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
        # keeps the host's binning rate on record in runs that loaded the
        # cache; only traced runs report per-layer metrics, so only they pay
        with bench.phase("bin_256k"):
            head = slice(0, 1 << 18)
            binned.fresh(lgb, X[head], y[head], None, params)
    floor = c["floor"]
    if floor["part"] == "train":
        Xq, yq = X[:floor["rows"]].copy(), y[:floor["rows"]]
        # whole queries: the configuration's floor rows are a multiple
        gq = None if group is None else group[
            :np.searchsorted(np.cumsum(group), floor["rows"]) + 1]
    else:
        Xq = gen.features(data["args"], floor["part"], floor["rows"])
        yq, gq = gen.labels(data["args"], bench.seed, floor["part"], Xq)
    del X

    with bench.phase("booster"):
        booster = lgb.Booster(params, ds)
        gbdt = booster._gbdt
    with bench.phase("compile"):
        booster.update()
        _wait(gbdt)
    with bench.phase("warmup"):
        for _ in range(traffic["warmup_iterations"] - 1):
            arena_before = gbdt._arena
            booster.update()
        _wait(gbdt)
        gbdt._sync_model()
    if not arena_before.is_deleted():
        problems.append("the arena was not donated: the iteration keeps a "
                        "second copy of it")
    bench.say("setup", binned_from_cache=from_cache, rows=int(ds.num_data()),
              warmup_leaves=[t.num_leaves for t in gbdt.models])

    block = traffic["block_iterations"]
    block_ms, done = [], 0
    t0 = bench.open_window()
    while time.perf_counter() - t0 < bench.seconds:
        tb = time.perf_counter()
        for _ in range(block):
            with bench.span("update"):
                booster.update()
        with bench.span("sync"):
            _wait(gbdt)
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
                _wait(gbdt)

    gbdt._sync_model()
    first = traffic["warmup_iterations"]
    trees = gbdt.models[first:first + done]
    full = params["num_leaves"]
    failed = sum(1 for t in trees
                 if t.num_leaves < full
                 or not np.isfinite(t.leaf_value[:t.num_leaves]).all())
    if gbdt._truncation_warned:
        problems.append("a tree was truncated by the arena")
    if failed:
        problems.append("%d of %d trees of the window have fewer than %d "
                        "leaves or a non-finite value" % (failed, done, full))
    took, wrong = _path_problems(gbdt, cell)
    problems += wrong
    problems += checks.against_walker(bench, booster, Xq[:c["walker_rows"]],
                                      c["walker_atol"])
    n_trees = min(floor["trees"], len(gbdt.models))
    q = checks.quality_of(floor["metric"], yq,
                          booster.predict(Xq, num_iteration=n_trees), gq)
    bench.say("quality", metric=floor["metric"], value=q, trees=n_trees,
              rows=len(yq), part=floor["part"], path=took)
    if not q >= floor["min"]:
        problems.append("%s %.4f after %d trees is under the floor %.2f"
                        % (floor["metric"], q, n_trees, floor["min"]))
    return {
        "attempted": done, "failed": failed, "problems": problems,
        "end_to_end": {"train_iter_ms": interquartile_mean(block_ms)},
        "shape": {"rows": int(ds.num_data()), "features": data["features"],
                  "max_bin": params["max_bin"], "units": done,
                  "traced_units": traffic["trace_iterations"]},
    }
