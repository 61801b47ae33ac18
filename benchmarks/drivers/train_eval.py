"""Traffic of kind `train_eval`: drivers/train.py's run with the
configuration's validation set attached, an iteration being
`Booster.update()` then `Booster.eval_valid()`: the body of
`engine.train`'s loop when `valid_sets` is given.

One caller, closed loop.  `eval_valid()` returns host floats, so every
iteration ends in a blocking read of what the iteration's device work
produced: the host cannot run ahead as it does in train.py's blocks, and
what dispatching the next iteration costs is no longer hidden behind a
full queue.  That is part of what a validation set costs and stays in the
number.  Blocks, window and `train_iter_ms` (the interquartile mean of
the blocks' times per iteration) are train.py's, as are the phases, the
binned cache (under this configuration's own key), the checks after the
window and the keys of `shape`.  What this driver adds:

- the validation set: `lgb.Dataset(Xt, yt, reference=ds)`, its rows
  drawn by the configuration's generator under the part name the
  configuration gives, added with `add_valid` before the first iteration;
- `expect.valid_scoring`, read off the booster as train.py reads the
  spine: `device` when the iteration's own program scored the validation
  rows, `host` when the host built a tree and walked it.  A program that
  does not say (one from before it had two ways: the parent of the PR
  that added this cell) is held to the engine and the precision only, as
  train_sparse.py holds a program that names no scan space: what it
  returns is still compared with the reference, value by value, and its
  spine, its drains and its spans are on the `[bench]` lines;
- the series: every value `eval_valid()` returned, in warm-up, window and
  traced slice, is kept and held against the plain reference after the
  window (harness/checks_valid.py);
- the model must not be drained by the metric: `_sync_model` and the
  carried path's lazy materialisation of the training score are counted
  while the window runs (wrapped here, nothing in the program is
  switched), and in a traced run the slice's `lgbm:` spans are read for
  `sync_model`, `tree_fetch` and `materialize_score`.

train.py's run is one function, so the blocks are written here a third
time (PERF.md, Open questions: a `benchmark` issue folds them).
"""
import time

import numpy as np

from benchmarks.harness import (binned, checks, checks_valid, manifest,
                                trace_reduce, xplane_names)
from benchmarks.harness.steady import interquartile_mean

_UNWANTED_SPANS = ("sync_model", "tree_fetch", "materialize_score")


class _Calls:
    """Counts the calls of one of the booster's methods while it is
    wrapped; the method runs as it is."""

    def __init__(self, owner, name):
        self.count, self._owner, self._name = 0, owner, name
        inner = getattr(owner, name)

        def counted(*args, **kwargs):
            self.count += 1
            return inner(*args, **kwargs)

        setattr(owner, name, counted)

    def stop(self):
        delattr(self._owner, self._name)     # the class's method again
        return self.count


def _metric_values(returned, names):
    """One value per (validation set, metric) from what
    `Booster.eval_valid()` returned, in the order of `names`."""
    found = {(name, metric): value for name, metric, value, _ in returned}
    if len(found) != len(returned) or sorted(found) != sorted(names):
        raise ValueError("eval_valid() returned %r, the cell has %r"
                         % (sorted(found), sorted(names)))
    return [float(found[key]) for key in names]


def _slice_spans(bench):
    """{span: [count, ms in all]} of the `lgbm:` host spans of the traced
    slice."""
    path = trace_reduce.find_xplane(bench.trace_dir)
    found = {}
    for spans in (xplane_names.program_spans(path) if path else []):
        for start, end, name in spans:
            count, ms = found.get(name, (0, 0.0))
            found[name] = [count + 1, ms + (end - start) / 1e6]
    return found


def run(bench):
    import lightgbm_tpu as lgb
    train = manifest.load_module(bench.root, "drivers", "train")
    cell = bench.cell
    cfg, traffic = cell.config, cell.traffic
    c, data, valid = cfg["correct"], cfg["data"], cfg["valid"]
    params = dict(cfg["params"])
    for key in cfg["seed_params"]:
        params[key] = bench.seed
    problems = []

    with bench.phase("check"):
        problems += checks.against_reference(bench, lgb, params)

    params.update(traffic["params"])
    for key in traffic["seed_params"]:
        params[key] = bench.seed
    gen = cell.generator()
    with bench.phase("data"):
        X = gen.features(data["args"], "train", data["rows"])
        y, group = gen.labels(data["args"], bench.seed, "train", X)
        Xt = gen.features(data["args"], valid["part"], valid["rows"])
        yt, _ = gen.labels(data["args"], bench.seed, valid["part"], Xt)
    with bench.phase("bin"):
        ds, from_cache = binned.cached(
            bench, lgb, X, y, group, params, "%s-%d-%d" % (
                cell.config_name, data["rows"], data["args"]["feature_seed"]))
        dv = lgb.Dataset(Xt, yt, reference=ds).construct()
    if bench.trace:
        with bench.phase("bin_256k"):
            head = slice(0, 1 << 18)
            binned.fresh(lgb, X[head], y[head], None, params)
    floor = c["floor"]
    Xq = gen.features(data["args"], floor["part"], floor["rows"])
    yq, gq = gen.labels(data["args"], bench.seed, floor["part"], Xq)
    del X

    with bench.phase("booster"):
        booster = lgb.Booster(params, ds)
        booster.add_valid(dv, valid["name"])
        gbdt = booster._gbdt
    names = [(valid["name"], m.name) for m in gbdt.valid_states[0][2]]
    series = []

    def iteration():
        with bench.span("update"):
            booster.update()
        with bench.span("eval_valid"):
            series.append(_metric_values(booster.eval_valid(), names))

    with bench.phase("compile"):
        iteration()
        train._wait(gbdt)
    with bench.phase("warmup"):
        for _ in range(traffic["warmup_iterations"] - 1):
            arena_before = gbdt._arena
            iteration()
        train._wait(gbdt)
        gbdt._sync_model()
    if not arena_before.is_deleted():
        problems.append("the arena was not donated: the iteration keeps a "
                        "second copy of it")
    bench.say("setup", binned_from_cache=from_cache, rows=int(ds.num_data()),
              valid_rows=int(dv.num_data()), metrics=names,
              warmup_leaves=[t.num_leaves for t in gbdt.models])

    block = traffic["block_iterations"]
    block_ms, done = [], 0
    drains = _Calls(gbdt, "_sync_model")
    sorts = _Calls(gbdt, "_materialize_carried_score")
    t0 = bench.open_window()
    while time.perf_counter() - t0 < bench.seconds:
        tb = time.perf_counter()
        for _ in range(block):
            iteration()
        with bench.span("sync"):
            train._wait(gbdt)
        block_ms.append((time.perf_counter() - tb) / block * 1e3)
        done += block
    window_s = bench.close_window()
    drained, sorted_scores = drains.stop(), sorts.stop()
    bench.say("window", iterations=done, window_s=window_s,
              mean_ms_per_iter=window_s / done * 1e3,
              block_ms_per_iter=block_ms, sync_model_calls=drained,
              materialize_score_calls=sorted_scores)
    says = getattr(gbdt, "_valid_scoring", None)
    if says is not None and (drained or sorted_scores):
        problems.append(
            "inside the window the model was drained %d time(s) and the "
            "training score materialised %d time(s): eval_valid() must "
            "need neither" % (drained, sorted_scores))

    if bench.trace:
        with bench.traced():
            for _ in range(traffic["trace_iterations"]):
                iteration()
            with bench.span("sync"):
                train._wait(gbdt)
        spans = _slice_spans(bench)
        unwanted = {s: spans[s][0] for s in _UNWANTED_SPANS if s in spans}
        bench.say("slice-spans", spans=spans)
        if unwanted and says is not None:
            problems.append("the traced slice holds %r: the iteration "
                            "waited for the host's copy of the model"
                            % unwanted)

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
    took, wrong = train._path_problems(gbdt, cell)
    took["valid_scoring"] = says
    want = traffic["expect"]["valid_scoring"]
    if says is None:
        wrong = [w for w in wrong
                 if w.startswith(("path: engine", "path: quantized"))]
    elif says != want:
        wrong.append("path: valid_scoring is %r, the cell states %r"
                     % (says, want))
    problems += wrong
    with bench.phase("check"):
        auc_at = names.index((valid["name"], "auc"))
        problems += checks_valid.against_reference(
            bench, booster, valid["name"], Xt, yt,
            [values[auc_at] for values in series])
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
                  "valid_rows": int(dv.num_data()),
                  "max_bin": params["max_bin"], "units": done,
                  "traced_units": traffic["trace_iterations"]},
    }
