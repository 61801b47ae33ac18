"""harness/checks.py's judgement for a training set the system bundles
(io/efb.py) and whose rare columns its binning drops: one-hot columns in a
scipy CSR matrix.

`checks.against_reference` stops at such a set, because it hands the plain
grower the system's own bin matrix, which here holds bundled group columns.
What is compared here is the system's path through bundling against the
un-bundled semantics: the **system** is trained on the sample as it bins
it (bundled, rare columns dropped); the **reference** gets plain
per-column bins that this file makes from the CSR sample itself (a one-hot
column's bin is `value != 0`), over the columns the system kept
(`real_feature_index`), and knows nothing of groups, offsets or
`BundleInfo`.  With `max_conflict_rate` 0 bundling is exact on the rows it
was decided on, and the sample is all of them, so the tolerances are those
of the un-bundled cells.
"""
import time

import numpy as np

from benchmarks.harness import binned, checks
from benchmarks.reference import grower, objectives


def plain_bins(X, columns):
    """[rows, len(columns)] uint8: 1 where the CSR matrix stores a
    nonzero, made without the system's mappers."""
    return (X[:, columns] != 0).toarray().astype(np.uint8)


def judge_trees(trees, bins, hold_bins, grad_of, rules, lr, init, c,
                bench=None):
    """Replay the system's float32 trees on the plain bins, split by split
    (reference/grower.replay), then compare leaf counts and leaf values.
    Returns (problems, the reference's raw holdout scores); a refused
    split is described on, and each tree's numbers are held by, the run's
    `bench` where one is given."""
    num_bins = np.full(bins.shape[1], 2, np.int64)
    score = np.full(len(bins), init)
    ref_hold = np.full(len(hold_bins), init)
    for t, sys_tree in enumerate(trees):
        grad, hess = grad_of(score)
        ref_tree, misses = grower.replay(
            bins, num_bins, grad, hess, rules,
            checks.system_splits(sys_tree), c["gain_rtol"])
        if misses:
            if bench is not None:
                checks.hold_tree(bench, c, ref_tree)
                bench.say("reference-check", tree=t,
                          miss=grower.explain_miss(
                              bins, num_bins, grad, hess, rules,
                              checks.system_splits(sys_tree), misses[0][0]))
            return ["tree %d: %d split(s) the reference does not accept, "
                    "first (step, gain, best gain) = %s"
                    % (t, len(misses), misses[0])], ref_hold
        if not np.array_equal(ref_tree.leaf_count,
                              sys_tree.leaf_count[:sys_tree.num_leaves]):
            return ["tree %d: leaf counts differ" % t], ref_hold
        sys_values = (np.asarray(sys_tree.leaf_value[:sys_tree.num_leaves])
                      - (init if t == 0 else 0.0))
        ref_values = lr * ref_tree.leaf_value
        if bench is not None:
            checks.hold_tree(bench, c, ref_tree, sys_values, ref_values)
        if not np.allclose(sys_values, ref_values, rtol=c["leaf_value_rtol"],
                           atol=c["leaf_value_atol_of_largest"]
                           * np.abs(ref_values).max()):
            worst = float(np.max(np.abs(sys_values - ref_values)))
            return ["tree %d: leaf values differ from the reference's by "
                    "up to %g" % (t, worst)], ref_hold
        score += lr * ref_tree.leaf_value[ref_tree.leaf_of_rows(bins)]
        ref_hold += lr * ref_tree.leaf_value[ref_tree.leaf_of_rows(hold_bins)]
    return [], ref_hold


def against_reference(bench, lgb, params):
    """`checks.against_reference` for a bundled one-hot set."""
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
    if b.bundle is None:
        return ["the sample's columns were not bundled: this check is for "
                "a set the system bundles (checks.against_reference judges "
                "the others)"]
    if any(m.num_bin != 2 or m.default_bin != 0 for m in b.bin_mappers):
        return ["a kept column has other bins than {zero, nonzero}: the "
                "plain bins of this check are one-hot columns'"]
    kept = np.asarray(b.real_feature_index)
    bins, hold_bins = plain_bins(Xs, kept), plain_bins(Xh, kept)
    lap("data_and_binning_s")

    objective = params["objective"]
    f32 = checks._train(lgb, dict(params, tpu_quantized_grad=False), ds,
                        c["trees"])
    lap("system_f32_s")
    init = objectives.binary_init_score(ys) if objective == "binary" else 0.0
    problems, ref_hold = judge_trees(
        f32._gbdt.models, bins, hold_bins,
        lambda score: checks._gradients(objective, score, ys, gs),
        grower.SplitRules(params, c.get("bound_rtol", 0.0)),
        float(params["learning_rate"]), init, c, bench=bench)
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
        raw = booster.predict(Xh, raw_score=True)
        q = checks.quality_of(c["quality"], yh, raw, gh)
        found[name] = q
        checks.hold_quality(bench, name, q, q_ref, band)
        if not abs(q - q_ref) <= band * abs(q_ref):
            problems.append("%s after %d trees: holdout %s %.6f against the "
                            "reference's %.6f, band %g"
                            % (name, c["trees"], c["quality"], q, q_ref, band))
    lap("quality_s")
    del clock["start"]
    bench.say("reference-check", sample_rows=len(ys), trees=c["trees"],
              columns=int(Xs.shape[1]), columns_kept=len(kept),
              groups=int(b.bundle.num_groups),
              quality=c["quality"], **found, **clock)
    return problems
