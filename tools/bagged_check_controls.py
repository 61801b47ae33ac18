"""What higgs-bagged.train's check reads when the row bag is handled
wrongly: the control readings behind `correct.oob_score_atol`, the bag
limits and `bag_stats` of benchmarks/configs/higgs-binary-int8-bagged.json.

    python tools/bagged_check_controls.py [--rehearse] [--seed N]
    python tools/bagged_check_controls.py --timed [--seconds S] [--seed N]

Runs the cell's reference check (benchmarks/harness/checks_bagged.py) on
the configuration's own sample, once as the program is and once under each
control, and prints what each compared number read beside its limit and
whether the check passed:

- short-bag: the program draws one row fewer than the configuration asks;
- every-row: the program grows every tree on every row (no bag);
- oob-bf16: the program scores the out-of-bag rows with leaf values
  rounded to bfloat16;
- prefix-bag: every bag is the first rows (a row's key is its row id);
- fixed-key: every redraw draws the first bag again.

And, computed from the clean run, what `sample_oob_score_diff` reads if
the out-of-bag rows' scores are kept in bfloat16, and what the bag
statistics read for bags that move 1 % of the rows at each redraw.

`--timed` runs the whole cell (benchmarks/run.py, a window of `--seconds`)
under oob-bf16 and prints `timed_score_diff`: the check of the timed
booster's own scores at the cell's full size (the cell's own runs give
its clean reading).  Any backend: off the
chip the kernels run in interpret mode; the readings are comparisons of
the check, not device measurements.
"""
import argparse
import contextlib
import os
import sys
import time
from unittest import mock

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _controls():
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.ops import bagging, valid_score
    rows, delta, draw = bagging.bag_rows, valid_score.tree_delta, bagging.draw

    def bf16_delta(*a, **k):
        return delta(*a, **k).astype(jnp.bfloat16).astype(jnp.float32)

    @contextlib.contextmanager
    def fresh(patch):
        # the draw and the row mask are jitted once per process: trace
        # them again under the control
        jax.clear_caches()
        with patch:
            yield
        jax.clear_caches()

    return {
        "clean": contextlib.nullcontext,
        "short-bag": lambda: mock.patch.object(
            bagging, "bag_rows", lambda f, n: rows(f, n) - 1),
        "every-row": lambda: mock.patch.object(
            GBDT, "_bag_in_use", lambda self: 0),
        "oob-bf16": lambda: mock.patch.object(
            valid_score, "tree_delta", bf16_delta),
        "prefix-bag": lambda: fresh(mock.patch.object(
            bagging, "row_keys", lambda bag, ids: ids.astype(jnp.uint32))),
        "fixed-key": lambda: mock.patch.object(
            bagging, "draw", lambda seed, index, **k: draw(seed, 0, **k)),
    }


def _shifted(bag, bags, share, seed):
    """`bags` bags, each the one before with `share` of the rows swapped
    in and out."""
    rng = np.random.default_rng(seed)
    out = [np.asarray(bag, bool)]
    moved = max(1, int(share * len(bag)))
    for _ in range(bags - 1):
        b = out[-1].copy()
        b[rng.choice(np.flatnonzero(b), moved, replace=False)] = False
        b[rng.choice(np.flatnonzero(~out[-1]), moved, replace=False)] = True
        out.append(b)
    return np.array(out)


def timed(args):
    """The whole cell under oob-bf16: its timed score check."""
    import io
    import json
    from benchmarks import run
    out = io.StringIO()
    with _controls()["oob-bf16"](), contextlib.redirect_stdout(out):
        run.main(["--workload", "higgs-bagged.train", "--seed",
                  str(args.seed), "--seconds", str(args.seconds),
                  "--trace", "0"] + ["--rehearse"] * args.rehearse)
    last = json.loads(out.getvalue().strip().splitlines()[-1])
    print("oob-bf16   timed correct=%s timed_score_diff %r" % (
        last["correct"], last["compared"].get("timed_score_diff")))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="the configuration's tiny preset")
    ap.add_argument("--seed", type=int, default=2147483777)
    ap.add_argument("--timed", action="store_true",
                    help="run the whole cell under oob-bf16")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    if args.timed:
        return timed(args)
    import jax.numpy as jnp
    import lightgbm_tpu as lgb
    from benchmarks.harness import checks_bagged
    from benchmarks.harness.bench import Bench
    from benchmarks.harness.manifest import Cell
    cell = Cell(ROOT, "higgs-bagged.train", rehearse=args.rehearse)
    params = dict(cell.config["params"])
    params["seed"] = args.seed
    held = {}
    kept = checks_bagged.trained_with_draws
    for name, control in _controls().items():
        bench = Bench(ROOT, cell, args.seed, 0, 0, time.perf_counter())
        bench.say = lambda what, **fields: None

        def watched(*a, **k):
            out = kept(*a, **k)
            held.setdefault(name, out)
            return out

        with control(), mock.patch.object(checks_bagged,
                                          "trained_with_draws", watched):
            problems = checks_bagged.against_reference(bench, lgb, params)
        print("%-10s correct=%s" % (name, not problems))
        for key, pair in bench.compared_record().items():
            print("    %-26s %r limit %r" % (key, pair["value"],
                                             pair["limit"]))
    booster, bags, _, scores = held["clean"]
    c = cell.config["correct"]
    X = cell.generator().features(cell.config["data"]["args"], "sample",
                                  c["sample_rows"])
    bf16 = [np.asarray(jnp.asarray(s, jnp.bfloat16), np.float64)
            for s in scores]

    class Held:
        compared = {}

        def hold(self, key, value, limit):
            self.compared[key] = (value, limit)

    reading = Held()
    checks_bagged.oob_score_problems(reading, c, booster.model_to_string(),
                                     X, bags, bf16)
    print("scores-bf16 sample_oob_score_diff %r limit %r"
          % reading.compared["sample_oob_score_diff"])
    masks = checks_bagged.program_bags(booster._gbdt, params,
                                       c["bag_stats"]["bags"])
    count = checks_bagged.expected_bag_rows(params, masks.shape[1])
    print("shift-1pc bag_stats (block, overlap, dispersion z) %r limit %r"
          % (checks_bagged.bag_stats(_shifted(masks[0], len(masks), 0.01, 0),
                                     count), c["bag_stats"]["z_max"]))


if __name__ == "__main__":
    main()
