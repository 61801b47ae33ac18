"""The plain reference against the system at a tiny size: its gradients,
its tree, its walker — and that it refuses what is wrong."""
import numpy as np
import pytest

import lightgbm_tpu as lgb
from benchmarks.data import higgs, mslr
from benchmarks.harness import checks
from benchmarks.reference import grower, objectives, walker

PARAMS = {"num_leaves": 15, "max_bin": 255, "min_data_in_leaf": 20,
          "learning_rate": 0.1, "verbose": -1}


def _case(objective):
    if objective == "binary":
        X = higgs.features({"feature_seed": 22}, "sample", 3000)
        y, group = higgs.labels({"label_seed": 22}, 7, "sample", X)
    else:
        args = {"feature_seed": 22, "label_seed": 22, "docs_per_query": 120}
        X = mslr.features(args, "sample", 2400)
        y, group = mslr.labels(args, 7, "sample", X)
    ds = lgb.Dataset(X, y, group=group, params={"max_bin": 255})
    ds.construct()
    return X, y, group, ds


def _first_gradients(objective, y, group):
    if objective == "binary":
        init = objectives.binary_init_score(y)
        return init, objectives.binary_gradients(np.full(len(y), init), y)
    return 0.0, objectives.lambdarank_gradients(np.zeros(len(y)), y, group)


@pytest.mark.parametrize("objective", ["binary", "lambdarank"])
def test_reference_gradients_match_the_systems(objective):
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.objective import create_objective
    _, y, group, ds = _case(objective)
    obj = create_objective(objective, Config({"objective": objective}))
    obj.init(ds._binned.metadata, len(y))
    score = np.random.default_rng(0).standard_normal(len(y))
    theirs = [np.asarray(a, np.float64) for a in obj.get_gradients(score)]
    ours = (objectives.binary_gradients(score, y) if objective == "binary"
            else objectives.lambdarank_gradients(score, y, group))
    for a, b in zip(ours, theirs):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-6)


@pytest.mark.parametrize("objective", ["binary", "lambdarank"])
def test_reference_grows_the_systems_first_tree(objective):
    """From the same gradients the plain grower and the system (float32,
    the engine the CPU runs) split the same leaves on the same columns in
    the same order, put the same rows into every leaf and give the same
    leaf values.  (Bins are compared by the rows they part: in a leaf of
    a hundred rows most of 255 bins are empty, and thresholds across an
    empty stretch are one split with exactly equal gains.)"""
    X, y, group, ds = _case(objective)
    params = dict(PARAMS, objective=objective)
    booster = lgb.Booster(params, ds)
    booster.update()
    booster._gbdt._sync_model()
    theirs = booster._gbdt.models[0]
    init, (grad, hess) = _first_gradients(objective, y, group)
    b = ds._binned
    ours = grower.grow(b.bins, b.feature_num_bins(), grad, hess,
                       grower.SplitRules(params))
    assert list(zip(ours.split_leaf, ours.split_feature)) \
        == [s[:2] for s in checks.system_splits(theirs)]
    assert np.array_equal(ours.leaf_count, theirs.leaf_count[:15])
    # a small leaf value is a float32 sum that nearly cancels: its error
    # is relative to the tree's scale, not to the value
    np.testing.assert_allclose(0.1 * ours.leaf_value + init,
                               theirs.leaf_value[:15], rtol=1e-4, atol=1e-5)
    # and replaying the system's choices finds nothing to object to
    _, misses = grower.replay(b.bins, b.feature_num_bins(), grad, hess,
                              grower.SplitRules(params),
                              checks.system_splits(theirs), 1e-5)
    assert misses == []


def test_replay_objects_to_a_worse_split_and_to_an_early_stop():
    X, y, group, ds = _case("binary")
    params = dict(PARAMS, objective="binary")
    _, (grad, hess) = _first_gradients("binary", y, group)
    b = ds._binned
    rules = grower.SplitRules(params)
    own = grower.grow(b.bins, b.feature_num_bins(), grad, hess, rules)
    splits = list(zip(own.split_leaf, own.split_feature, own.split_bin))
    # a root split far from the best one
    bad = [(0, (splits[0][1] + 1) % 28, 127)] + splits[1:]
    _, misses = grower.replay(b.bins, b.feature_num_bins(), grad, hess,
                              rules, bad, 1e-3)
    assert misses and misses[0][0] == 0
    # a tree that stops while the reference still finds a split
    _, misses = grower.replay(b.bins, b.feature_num_bins(), grad, hess,
                              rules, splits[:5], 1e-3)
    assert misses and misses[0][0] == 5
    # a dropped term: a tree grown without the hessian's weighting, at
    # scores where the hessian varies from row to row
    score = np.random.default_rng(1).standard_normal(len(y)) * 2
    grad, hess = objectives.binary_gradients(score, y)
    flat = grower.grow(b.bins, b.feature_num_bins(), grad,
                       np.full_like(hess, hess.mean()), rules)
    _, misses = grower.replay(
        b.bins, b.feature_num_bins(), grad, hess, rules,
        list(zip(flat.split_leaf, flat.split_feature, flat.split_bin)), 1e-3)
    assert misses


def test_min_data_in_leaf_is_respected():
    X, y, group, ds = _case("binary")
    _, (grad, hess) = _first_gradients("binary", y, group)
    b = ds._binned
    tree = grower.grow(b.bins, b.feature_num_bins(), grad, hess,
                       grower.SplitRules(dict(PARAMS, min_data_in_leaf=200)))
    assert tree.leaf_count.min() >= 200
    assert tree.leaf_count.sum() == len(y)
    assert np.array_equal(np.bincount(tree.leaf_of_rows(b.bins)),
                          tree.leaf_count)


@pytest.mark.parametrize("objective", ["binary", "lambdarank"])
def test_walker_equals_booster_predict(objective):
    X, y, group, ds = _case(objective)
    booster = lgb.Booster(dict(PARAMS, objective=objective), ds)
    for _ in range(4):
        booster.update()
    text = booster.model_to_string()
    np.testing.assert_allclose(walker.predict(text, X), booster.predict(X),
                               rtol=0, atol=1e-7)
    np.testing.assert_allclose(
        walker.raw_scores(text, X, num_trees=2),
        booster.predict(X, raw_score=True, num_iteration=2),
        rtol=0, atol=1e-7)


def test_walker_handles_missing_values_as_the_reference_project_does():
    text = "\n".join([
        "tree", "version=v2", "num_class=1", "num_tree_per_iteration=1",
        "max_feature_idx=1", "objective=regression", "",
        "Tree=0", "num_leaves=3", "num_cat=0", "split_feature=0 1",
        "threshold=0.5 1e-35",
        # node 0: NaN is missing and goes right (missing type NaN = 2 << 2);
        # node 1: zero is missing and goes left (type zero = 1 << 2, | 2)
        "decision_type=8 6", "left_child=1 -1", "right_child=-2 -3",
        "leaf_value=10 20 30", "shrinkage=1", "", "end of trees", ""])
    X = np.array([[0.0, 5.0],      # left at node 0, right at node 1
                  [0.0, 0.0],      # left, then zero -> default left
                  [np.nan, 0.0],   # NaN -> default right at node 0
                  [0.7, 0.0]])     # right at node 0
    assert list(walker.raw_scores(text, X)) == [30.0, 10.0, 20.0, 20.0]
    with pytest.raises(ValueError, match="categorical"):
        walker.parse_model(text.replace("num_cat=0", "num_cat=1"))
