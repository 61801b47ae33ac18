"""The comparisons that decide `correct` for a training cell.  Each returns
a list of problems, empty when the system did what the cell states."""
import time

import numpy as np

from benchmarks.harness import binned, quality
from benchmarks.reference import grower, objectives, walker


def system_splits(tree):
    """[(leaf, column, bin)] in the order a host tree of the system was
    grown: node i is split i, a left child keeps its parent's leaf index
    and a right child is leaf i + 1 (LightGBM's numbering)."""
    n = tree.num_leaves - 1
    leaf_of_node = np.zeros(max(n, 1), np.int64)
    for i in range(n):
        left, right = int(tree.left_child[i]), int(tree.right_child[i])
        if left >= 0:
            leaf_of_node[left] = leaf_of_node[i]
        if right >= 0:
            leaf_of_node[right] = i + 1
    return [(int(leaf_of_node[i]), int(tree.split_feature_inner[i]),
             int(tree.threshold_in_bin[i])) for i in range(n)]


def _train(lgb, params, ds, trees):
    booster = lgb.Booster(params, ds)
    for _ in range(trees):
        booster.update()
    booster._gbdt._sync_model()
    return booster


def quality_of(kind, labels, scores, group):
    if kind == "logloss":
        return quality.logloss(labels, scores)
    if kind == "auc":
        return quality.auc(labels, scores)
    if kind == "ndcg10":
        return quality.ndcg_at_k(labels, scores, group, 10)
    raise ValueError("no quality measure %r" % kind)


def _gradients(objective, score, y, group):
    if objective == "binary":
        return objectives.binary_gradients(score, y)
    if objective == "lambdarank":
        return objectives.lambdarank_gradients(score, y, group)
    raise ValueError("the plain reference has no objective %r" % objective)


def hold_tree(bench, c, ref_tree, sys_values=None, ref_values=None):
    """Put a replayed tree's numbers on the run's record beside their
    limits (`Bench.hold` keeps the worst tree's): the largest shortfall of
    a chosen split's gain against `gain_rtol` and, where the leaf values
    were compared, the largest |difference| in units of what
    `leaf_value_rtol` and `leaf_value_atol_of_largest` allow that leaf."""
    bench.hold("gain_shortfall", ref_tree.gain_shortfall, c["gain_rtol"])
    if sys_values is not None:
        allowed = (c["leaf_value_atol_of_largest"] * np.abs(ref_values).max()
                   + c["leaf_value_rtol"] * np.abs(ref_values))
        bench.hold("leaf_value_off_of_allowed", np.max(
            np.abs(sys_values - ref_values) / allowed), 1.0)


def hold_quality(bench, name, q, q_ref, band):
    bench.hold(name + "_quality_gap", abs(q - q_ref) / abs(q_ref), band)


def against_reference(bench, lgb, params):
    """On a seeded sample at the configuration's full widths and leaf
    count: the system in float32 must grow trees the plain reference
    accepts split by split (reference/grower.replay), with its leaf counts
    and, within rounding, its leaf values; and the system at the cell's own
    precision must stay within the configuration's band of the reference's
    holdout quality."""
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

    problems = []
    rules = grower.SplitRules(params, c.get("bound_rtol", 0.0))
    lr = float(params["learning_rate"])
    objective = params["objective"]
    f32 = _train(lgb, dict(params, tpu_quantized_grad=False), ds, c["trees"])
    lap("system_f32_s")
    init = objectives.binary_init_score(ys) if objective == "binary" else 0.0
    score = np.full(len(ys), init)
    ref_hold = np.full(len(yh), init)
    for t, sys_tree in enumerate(f32._gbdt.models):
        grad, hess = _gradients(objective, score, ys, gs)
        ref_tree, misses = grower.replay(
            b.bins, b.feature_num_bins(), grad, hess, rules,
            system_splits(sys_tree), c["gain_rtol"])
        if misses:
            hold_tree(bench, c, ref_tree)
            problems.append(
                "tree %d: %d split(s) the reference does not accept, first "
                "(step, gain, best gain) = %s" % (t, len(misses), misses[0]))
            bench.say("reference-check", tree=t, miss=grower.explain_miss(
                b.bins, b.feature_num_bins(), grad, hess, rules,
                system_splits(sys_tree), misses[0][0]))
            break
        if not np.array_equal(ref_tree.leaf_count,
                              sys_tree.leaf_count[:sys_tree.num_leaves]):
            problems.append("tree %d: leaf counts differ" % t)
            break
        sys_values = (np.asarray(sys_tree.leaf_value[:sys_tree.num_leaves])
                      - (init if t == 0 else 0.0))
        ref_values = lr * ref_tree.leaf_value
        hold_tree(bench, c, ref_tree, sys_values, ref_values)
        if not np.allclose(sys_values, ref_values, rtol=c["leaf_value_rtol"],
                           atol=c["leaf_value_atol_of_largest"]
                           * np.abs(ref_values).max()):
            worst = float(np.max(np.abs(sys_values - ref_values)))
            problems.append("tree %d: leaf values differ from the "
                            "reference's by up to %g" % (t, worst))
            break
        score += lr * ref_tree.leaf_value[ref_tree.leaf_of_rows(b.bins)]
        ref_hold += lr * ref_tree.leaf_value[ref_tree.leaf_of_rows(hold_bins)]
    lap("reference_s")
    if problems:
        return problems

    q_ref = quality_of(c["quality"], yh, ref_hold, gh)
    found = {"reference": q_ref}
    runs = [("f32", f32, c["f32_band"])]
    if params.get("tpu_quantized_grad"):
        runs.append(("own", _train(lgb, params, ds, c["trees"]),
                     c["own_band"]))
    lap("system_own_s")
    for name, booster, band in runs:
        raw = booster.predict(Xh, raw_score=True)
        q = quality_of(c["quality"], yh, raw, gh)
        found[name] = q
        hold_quality(bench, name, q, q_ref, band)
        if not abs(q - q_ref) <= band * abs(q_ref):
            problems.append("%s after %d trees: holdout %s %.6f against the "
                            "reference's %.6f, band %g"
                            % (name, c["trees"], c["quality"], q, q_ref, band))
    lap("quality_s")
    del clock["start"]
    bench.say("reference-check", sample_rows=len(ys), trees=c["trees"],
              quality=c["quality"], **found, **clock)
    return problems


def against_walker(bench, booster, X, atol):
    """`Booster.predict` on X against the plain walker on the model text."""
    text = booster.model_to_string()
    diff = float(np.max(np.abs(np.asarray(booster.predict(X))
                               - walker.predict(text, X))))
    bench.say("walker-check", rows=len(X), max_abs_diff=diff)
    bench.hold("walker_diff", diff, atol)
    return [] if diff <= atol else [
        "Booster.predict differs from the plain walker on the model text "
        "by %g (allowed %g)" % (diff, atol)]
