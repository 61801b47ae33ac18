"""The check that decides `correct` for a configuration that samples rows
and columns (bagging_fraction and bagging_freq, feature_fraction): the
plain check of harness/checks.py, with each tree held to the rows and
columns it was drawn.

On a seeded sample at the configuration's full widths and leaf count the
system trains in float32 for `trees` iterations, and after each one the
check reads off the booster what it drew (its row bag, as the row mask
`_bagging` gives for the iteration, and the column subset each tree was
grown on, by watching `_feature_sample`) and the training score it holds.
Then, by the plain reference:

- the bag holds `int(bagging_fraction * rows)` rows (gbdt.cpp's
  `bag_data_cnt_`), is drawn again at every multiple of bagging_freq and
  only there, and every tree's root holds exactly its bag;
- the bags look drawn at random, whatever draws them: over `bag_stats`'
  bags, every block of consecutive row ids holds its share of each bag,
  consecutive bags overlap as two independent draws do, and the rows'
  counts of bags spread as a binomial's, each within its z limit;
- a tree draws `int(feature_fraction * columns)` columns (at least one)
  and splits on no other;
- each tree is replayed by reference/grower.py on the bins sliced down to
  its in-bag rows and drawn columns, from the reference's own float64
  gradients, and its leaf values compared as the plain check does;
- the score the booster holds for every out-of-bag row after every tree is
  the plain walker's over the saved model text (reference/walker.py): out
  of the bag, nothing but the walk gives a row its score;
- the system's holdout quality stays within the configuration's bands of
  the reference's, in float32 and at the cell's own precision.

And on the timed booster itself, at the cell's full size, after its
window (`timed_score_problems`, called by drivers/train_rowsampled.py):
the training score it holds for rows spread over every row id, each of
which was out of the bag for about a fifth of its trees, is the plain
walker's over its model text.

Every number decided by is held beside its limit (`Bench.hold`).
"""
import math
import time

import numpy as np

from benchmarks.harness import binned, checks
from benchmarks.reference import grower, objectives, walker


def expected_bag_rows(params, rows):
    if params.get("bagging_freq", 0) <= 0 \
            or params.get("bagging_fraction", 1.0) >= 1.0:
        return rows
    return int(params["bagging_fraction"] * rows)


def expected_columns(params, columns):
    return max(1, int(params.get("feature_fraction", 1.0) * columns))


def trained_with_draws(lgb, params, ds, trees, prepare=None):
    """(booster, [in-bag rows per tree, bool], [drawn columns per tree,
    bool], [training score after each tree]): what the booster drew and
    held, read off it after every `update()`.  `prepare(booster)`, where
    given, runs before the first."""
    booster = lgb.Booster(params, ds)
    if prepare is not None:
        prepare(booster)
    gbdt = booster._gbdt
    drawn, draw = [], gbdt._feature_sample

    def watched():
        mask = draw()
        drawn.append(np.asarray(mask, bool))
        return mask

    gbdt._feature_sample = watched
    n = ds._binned.num_data
    bags, scores = [], []
    for _ in range(trees):
        booster.update()
        bags.append(np.asarray(gbdt._bagging(gbdt.iter - 1)) == 0)
        scores.append(np.asarray(gbdt.train_state.score, np.float64)[0])
    gbdt._sync_model()
    return booster, bags, drawn, scores


def draw_problems(bench, params, bags, drawn, models, columns):
    """The bag and the columns against what the configuration asks."""
    rows = len(bags[0])
    want_rows = expected_bag_rows(params, rows)
    want_cols = expected_columns(params, columns)
    freq = max(int(params.get("bagging_freq", 0)), 1)
    bag_off = max(abs(int(b.sum()) - want_rows) for b in bags)
    roots = [int(t.internal_count[0]) if t.num_leaves > 1
             else int(t.leaf_count[0]) for t in models]
    root_off = max(abs(r - int(b.sum())) for r, b in zip(roots, bags))
    # a new bag at every multiple of bagging_freq, the same one between
    stale = sum(1 for t in range(1, len(bags))
                if (t % freq == 0) == bool(np.array_equal(bags[t],
                                                          bags[t - 1])))
    cols_off = (max(abs(int(d.sum()) - want_cols) for d in drawn)
                if len(drawn) == len(models) else columns)
    undrawn = sum(
        int(np.sum(~d[t.split_feature_inner[:t.num_leaves - 1]]))
        for t, d in zip(models, drawn))
    bench.hold("bag_rows_off", bag_off, 0)
    bench.hold("root_rows_off", root_off, 0)
    bench.hold("stale_bags", stale, 0)
    bench.hold("columns_off", cols_off, 0)
    bench.hold("undrawn_columns", undrawn, 0)
    bench.say("bag-check", rows=rows, bag_rows=[int(b.sum()) for b in bags],
              want_bag_rows=want_rows, roots=roots,
              columns=[int(d.sum()) for d in drawn], want_columns=want_cols,
              stale_bags=stale, undrawn_columns=undrawn)
    problems = []
    if bag_off:
        problems.append("a bag holds %d rows where the configuration asks "
                        "%d (off by %d)" % (int(bags[0].sum()), want_rows,
                                            bag_off))
    if root_off:
        problems.append("a tree's root holds %d rows more or less than its "
                        "bag" % root_off)
    if stale:
        problems.append("%d of %d iterations drew a bag where bagging_freq "
                        "%d says not to, or kept one where it says to draw"
                        % (stale, len(bags) - 1, freq))
    if cols_off:
        problems.append("a tree was grown on %d columns more or less than "
                        "feature_fraction asks" % cols_off)
    if undrawn:
        problems.append("%d split(s) on columns the tree did not draw"
                        % undrawn)
    return problems


def bag_stats(masks, count):
    """(block z, overlap z, dispersion z) of bags [B, n] bool of `count`
    rows each, against B independent uniform draws of exactly `count` of
    the n rows: the largest distance, in standard deviations, of a block of
    n // 64 consecutive row ids' share of a bag (hypergeometric), of two
    consecutive bags' overlap from count**2 / n (hypergeometric), and of
    the variance over rows of each row's count of bags from a binomial's,
    B f (1 - f) (the sample variance's spread from the binomial's fourth
    moment).  A prefix of the rows fails the first, a bag kept or barely
    moved at a redraw the second and third."""
    masks = np.asarray(masks, bool)
    bags, n = masks.shape
    f = count / n
    w = n // 64
    blocks = masks[:, :64 * w].reshape(bags, 64, w).sum(axis=2)
    block_z = float(np.max(np.abs(blocks - w * f)) / math.sqrt(
        w * f * (1 - f) * (n - w) / (n - 1)))
    overlap = (masks[1:] & masks[:-1]).sum(axis=1)
    overlap_z = float(np.max(np.abs(overlap - count * f)) / math.sqrt(
        count * f * (1 - f) * (n - count) / (n - 1)))
    var = bags * f * (1 - f)
    mu4 = var * (1 + 3 * (bags - 2) * f * (1 - f))
    spread = masks.sum(axis=0).var()
    dispersion_z = abs(spread - var) / math.sqrt(
        (mu4 - var * var * (n - 3) / (n - 1)) / n)
    return block_z, overlap_z, float(dispersion_z)


def bag_stat_problems(bench, c, params, masks):
    """bag_stats of the program's bags, each held to `bag_stats.z_max`."""
    count = expected_bag_rows(params, masks.shape[1])
    readings = dict(zip(("bag_block_z", "bag_overlap_z", "bag_dispersion_z"),
                        bag_stats(masks, count)))
    limit = c["bag_stats"]["z_max"]
    for name, value in readings.items():
        bench.hold(name, value, limit)
    bench.say("bag-stats", bags=len(masks), **readings)
    return ["the bags do not look drawn at random: %s %.3g against a limit "
            "of %g" % (name, value, limit)
            for name, value in readings.items() if not value <= limit]


def program_bags(gbdt, params, bags):
    """[bags, n] bool: the program's bags at the first `bags` multiples of
    bagging_freq, read as row masks."""
    freq = int(params["bagging_freq"])
    return np.array([np.asarray(gbdt._bagging(k * freq)) == 0
                     for k in range(bags)])


def timed_score_problems(bench, c, booster, X, ids):
    """The training score the booster holds for the rows `ids` (X: their
    raw columns) against the plain walker's sum of every tree of its model
    text, held as `timed_score_diff` to `oob_score_atol`."""
    gbdt = booster._gbdt
    held = np.asarray(gbdt.train_state.score, np.float64)[0][ids]
    ref = walker.raw_scores(booster.model_to_string(), X, len(gbdt.models))
    diff = np.abs(held - ref)
    out = np.asarray(gbdt._bagging(gbdt.iter - 1))[ids] != 0
    worst = float(diff.max())
    bench.hold("timed_score_diff", worst, c["oob_score_atol"])
    bench.say("timed-score-check", rows=len(ids), trees=len(gbdt.models),
              max_abs_diff=worst, out_of_bag_rows=int(out.sum()),
              out_of_bag_max_abs_diff=float(diff[out].max(initial=0.0)))
    return [] if worst <= c["oob_score_atol"] else [
        "the timed booster's training score differs from the plain "
        "walker's by %g (allowed %g) on rows spread over every row id"
        % (worst, c["oob_score_atol"])]


def oob_score_problems(bench, c, text, X, bags, scores):
    """The training score of every out-of-bag row after every tree against
    the plain walker's sum of that many trees of the model text."""
    worst = 0.0
    for t, (bag, held) in enumerate(zip(bags, scores)):
        out = ~bag
        if out.any():
            ref = walker.raw_scores(text, X[out], t + 1)
            worst = max(worst, float(np.max(np.abs(held[out] - ref))))
    bench.hold("sample_oob_score_diff", worst, c["oob_score_atol"])
    return [] if worst <= c["oob_score_atol"] else [
        "an out-of-bag row's training score differs from the plain walker's "
        "by %g (allowed %g)" % (worst, c["oob_score_atol"])]


def replay_problems(bench, c, params, b, ys, hold_bins, models, bags,
                    drawn):
    """(problems, the reference's holdout raw score): each tree replayed
    by the plain grower on the bins of its in-bag rows and drawn columns,
    from the reference's float64 gradients of every row's score."""
    problems = []
    rules = grower.SplitRules(params, c.get("bound_rtol", 0.0))
    lr = float(params["learning_rate"])
    num_bins = b.feature_num_bins()
    init = objectives.binary_init_score(ys)
    score = np.full(len(ys), init)
    ref_hold = np.full(len(hold_bins), init)
    for t, (sys_tree, bag, cols) in enumerate(zip(models, bags, drawn)):
        grad, hess = objectives.binary_gradients(score, ys)
        # the tree's own rows and columns; its splits renumbered into them
        kept = np.flatnonzero(cols)
        at = np.full(len(cols), -1)
        at[kept] = np.arange(len(kept))
        splits = [(leaf, int(at[f]), bin_) for leaf, f, bin_
                  in checks.system_splits(sys_tree)]
        bins = b.bins[:, kept]
        ref_tree, misses = grower.replay(
            bins[bag], num_bins[kept], grad[bag], hess[bag], rules, splits,
            c["gain_rtol"])
        if misses:
            checks.hold_tree(bench, c, ref_tree)
            problems.append(
                "tree %d: %d split(s) the reference does not accept on its "
                "bag and columns, first (step, gain, best gain) = %s"
                % (t, len(misses), misses[0]))
            break
        if not np.array_equal(ref_tree.leaf_count,
                              sys_tree.leaf_count[:sys_tree.num_leaves]):
            problems.append("tree %d: leaf counts differ" % t)
            break
        sys_values = (np.asarray(sys_tree.leaf_value[:sys_tree.num_leaves])
                      - (init if t == 0 else 0.0))
        ref_values = lr * ref_tree.leaf_value
        checks.hold_tree(bench, c, ref_tree, sys_values, ref_values)
        if not np.allclose(sys_values, ref_values, rtol=c["leaf_value_rtol"],
                           atol=c["leaf_value_atol_of_largest"]
                           * np.abs(ref_values).max()):
            problems.append("tree %d: leaf values differ from the "
                            "reference's by up to %g" % (t, float(np.max(
                                np.abs(sys_values - ref_values)))))
            break
        # every row, in the bag or out of it, takes the tree's value
        score += lr * ref_tree.leaf_value[ref_tree.leaf_of_rows(bins)]
        ref_hold += lr * ref_tree.leaf_value[
            ref_tree.leaf_of_rows(hold_bins[:, kept])]
    return problems, ref_hold


def against_reference(bench, lgb, params):
    cfg = bench.cell.config
    c, args = cfg["correct"], cfg["data"]["args"]
    gen = bench.cell.generator()
    clock = {"start": time.perf_counter()}

    def lap(name):
        now = time.perf_counter()
        clock[name] = clock.get(name, 0.0) + now - clock["start"]
        clock["start"] = now

    Xs = gen.features(args, "sample", c["sample_rows"])
    ys, gs = gen.labels(args, bench.seed, "sample", Xs)
    Xh = gen.features(args, "holdout", c["holdout_rows"])
    yh, gh = gen.labels(args, bench.seed, "holdout", Xh)
    ds = binned.fresh(lgb, Xs, ys, gs, params)
    b = ds._binned
    if b.bundle is not None or b.num_features != Xs.shape[1]:
        return ["the sample's columns were bundled or dropped by binning; "
                "the plain reference works column by column"]
    hold_bins = lgb.Dataset(Xh, yh, group=gh, reference=ds).construct() \
        ._binned.bins
    lap("data_and_binning_s")

    f32, bags, drawn, scores = trained_with_draws(
        lgb, dict(params, tpu_quantized_grad=False), ds, c["trees"])
    models = f32._gbdt.models
    lap("system_f32_s")
    problems = draw_problems(bench, params, bags, drawn, models,
                             b.num_features)
    problems += bag_stat_problems(
        bench, c, params,
        program_bags(f32._gbdt, params, c["bag_stats"]["bags"]))
    problems += oob_score_problems(bench, c, f32.model_to_string(), Xs,
                                   bags, scores)
    lap("draws_and_walk_s")
    if problems:
        return problems

    problems, ref_hold = replay_problems(bench, c, params, b, ys, hold_bins,
                                         models, bags, drawn)
    lap("reference_s")
    if problems:
        return problems

    q_ref = checks.quality_of(c["quality"], yh, ref_hold, gh)
    found = {"reference": q_ref}
    runs = [("f32", f32, c["f32_band"])]
    if params.get("tpu_quantized_grad"):
        runs.append(("own", checks._train(lgb, params, ds, c["trees"]),
                     c["own_band"]))
    lap("system_own_s")
    for name, booster, band in runs:
        q = checks.quality_of(c["quality"], yh,
                              booster.predict(Xh, raw_score=True), gh)
        found[name] = q
        checks.hold_quality(bench, name, q, q_ref, band)
        if not abs(q - q_ref) <= band * abs(q_ref):
            problems.append("%s after %d trees: holdout %s %.6f against the "
                            "reference's %.6f, band %g"
                            % (name, c["trees"], c["quality"], q, q_ref, band))
    lap("quality_s")
    del clock["start"]
    bench.say("reference-check", sample_rows=len(ys), trees=c["trees"],
              quality=c["quality"], **found, **clock)
    return problems
