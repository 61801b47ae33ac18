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


# ---- the hessian floor within the rounding of a float32 sum (PR 38) --------
FLOOR = 100.0
BAND = 1e-5


def _leaf_at_the_floor(off):
    """1 000 rows, two columns of two bins.  Column 0 parts the first 400
    rows, whose hessians sum to FLOOR * (1 + off), from the other 600 and
    has by far the larger gain; column 1 parts 500 from 500, both far over
    the floor.  -> (bins, num_bins, grad, hess)"""
    bins = np.zeros((1000, 2), np.uint8)
    bins[400:, 0] = 1
    bins[::2, 1] = 1
    hess = np.full(1000, 0.25)
    hess[:400] = FLOOR * (1 + off) / 400
    grad = np.where(bins[:, 0] == 0, -0.5, 0.3) + 0.1 * (bins[:, 1] - 0.5)
    return bins, np.array([2, 2]), grad, hess


def _judge(off, splits, bound_rtol):
    rules = grower.SplitRules(
        {"num_leaves": 2, "min_data_in_leaf": 1,
         "min_sum_hessian_in_leaf": FLOOR}, bound_rtol)
    return grower.replay(*_leaf_at_the_floor(off), rules, splits, 1e-3)[1]


def test_a_child_a_rounding_under_the_floor_is_allowed_within_the_band():
    """Epsilon's case: 400 rows of one hessian sum to a hair under the
    floor in float64 and onto it in float32; the system takes the child."""
    took_column_0 = [(0, 0, 0)]
    assert _judge(-5e-7, took_column_0, BAND) == []
    (miss,) = _judge(-5e-7, took_column_0, 0.0)
    assert miss[0] == 0 and miss[1] == -np.inf and miss[2] > 0
    # a child a thousandth under the floor is refused with the band too
    for bound_rtol in (BAND, 0.0):
        (miss,) = _judge(-1e-3, took_column_0, bound_rtol)
        assert miss[:2] == (0, -np.inf), bound_rtol
    # and on the floor or over it the band changes nothing
    for off in (0.0, 5e-7, 1e-3):
        assert _judge(off, took_column_0, BAND) == []
        assert _judge(off, took_column_0, 0.0) == []


def test_a_child_a_rounding_over_the_floor_is_not_demanded_within_the_band():
    """The other direction: float32 can round a sum down from the floor,
    and the system then does not see the split the reference likes best."""
    took_column_1 = [(0, 1, 0)]
    assert _judge(5e-7, took_column_1, BAND) == []
    (miss,) = _judge(5e-7, took_column_1, 0.0)
    assert miss[0] == 0 and 0 < miss[1] < miss[2] * (1 - 1e-3)
    # a thousandth over the floor it is demanded, band or none
    for bound_rtol in (BAND, 0.0):
        (miss,) = _judge(1e-3, took_column_1, bound_rtol)
        assert miss[0] == 0 and miss[1] < miss[2], bound_rtol


def test_a_stop_before_a_split_inside_the_band_is_no_early_stop():
    bins, num_bins, grad, hess = _leaf_at_the_floor(5e-7)
    one = bins[:, :1], num_bins[:1], grad, hess     # column 0 alone
    params = {"num_leaves": 2, "min_data_in_leaf": 1,
              "min_sum_hessian_in_leaf": FLOOR}
    assert grower.replay(*one, grower.SplitRules(params, BAND), [],
                         1e-3)[1] == []
    (miss,) = grower.replay(*one, grower.SplitRules(params), [], 1e-3)[1]
    assert miss[:2] == (0, None) and miss[2] > 0
    # a tree that took a split where not even the loose floor allows one
    (miss,) = grower.replay(bins[:, :1], num_bins[:1], grad,
                            _leaf_at_the_floor(-1e-3)[3],
                            grower.SplitRules(params, BAND), [(0, 0, 0)],
                            1e-3)[1]
    assert miss == (0, -np.inf, None)


def test_the_refused_split_is_described_by_its_floors():
    bins, num_bins, grad, hess = _leaf_at_the_floor(-5e-7)
    rules = grower.SplitRules({"num_leaves": 2, "min_data_in_leaf": 1,
                               "min_sum_hessian_in_leaf": FLOOR})
    told = grower.explain_miss(bins, num_bins, grad, hess, rules,
                               [(0, 0, 0)], 0)
    assert told["step"] == 0 and told["leaves"] == 1
    chosen, best = told["chosen"], told["best"]
    assert (chosen["column"], best["column"]) == (0, 1)
    assert chosen["rows"] == [400, 600] and best["rows"] == [500, 500]
    assert chosen["allowed_gain"] == -np.inf < best["gain"] < chosen["gain"]
    assert chosen["hessian_over_floor_rel"][0] == pytest.approx(-5e-7,
                                                                rel=1e-6)
    assert chosen["rows_over_floor"] == [399, 599]
    assert best["allowed_gain"] == best["gain"]


def _plain_gains(g, hist):
    """reference/grower.py's `_gains` as it stood before PR 38: every floor
    a bare float64 comparison."""
    r = g.rules
    left = np.cumsum(hist, axis=1)
    total = left[:, -1:, :]
    right = total - left
    ok = (g._real
          & (left[:, :, 2] >= r.min_data_in_leaf)
          & (right[:, :, 2] >= r.min_data_in_leaf)
          & (left[:, :, 1] >= r.min_sum_hessian_in_leaf)
          & (right[:, :, 1] >= r.min_sum_hessian_in_leaf))
    with np.errstate(divide="ignore", invalid="ignore"):
        gain = (grower._leaf_gain(left[:, :, 0], left[:, :, 1], r)
                + grower._leaf_gain(right[:, :, 0], right[:, :, 1], r)
                - grower._leaf_gain(total[:, :, 0], total[:, :, 1], r)
                - r.min_gain_to_split)
    return np.where(ok & (gain > 0.0), gain, -np.inf)


@pytest.mark.parametrize("objective", ["binary", "lambdarank"])
def test_without_a_band_the_grower_is_the_plain_rule_bit_for_bit(objective):
    """`bound_rtol` absent or 0: one gain table per leaf, equal to the bare
    comparison's in every element, after every split of the reference's
    own tree; `grow` keeps the plain rule whatever the rules' band, and
    `replay` gives the same verdicts as before on the same choices."""
    _, y, group, ds = _case(objective)
    _, (grad, hess) = _first_gradients(objective, y, group)
    b = ds._binned
    # a floor that binds: the plain rule must forbid some candidates
    params = dict(PARAMS, objective=objective,
                  min_sum_hessian_in_leaf=float(hess.sum() / 8))
    plain, banded = grower.SplitRules(params), grower.SplitRules(params, BAND)
    assert plain.bound_rtol == 0.0
    g = grower.LeafwiseGrower(b.bins, b.feature_num_bins(), grad, hess, plain)
    while len(g.rows) < plain.num_leaves and g.best() is not None:
        g.split(*g.best()[1:])
    assert len(g.rows) > 3
    forbidden = 0
    for leaf in g.rows:
        tight, loose = g._gains(g.hist[leaf])
        assert tight is loose
        expected = _plain_gains(g, g.hist[leaf])
        assert np.array_equal(g.gains[leaf], expected)
        forbidden += int(np.isneginf(expected[g._real]).sum())
    assert forbidden
    own = g.finish()
    for rules in (plain, banded):
        tree = grower.grow(b.bins, b.feature_num_bins(), grad, hess, rules)
        assert tree.split_leaf == own.split_leaf
        assert tree.split_feature == own.split_feature
        assert tree.split_bin == own.split_bin
        assert np.array_equal(tree.leaf_value, own.leaf_value)
    splits = list(zip(own.split_leaf, own.split_feature, own.split_bin))
    assert grower.replay(b.bins, b.feature_num_bins(), grad, hess, plain,
                         splits, 1e-3)[1] == []
    worse = [(0, (splits[0][1] + 1) % b.bins.shape[1], 127)] + splits[1:]
    a = grower.replay(b.bins, b.feature_num_bins(), grad, hess, plain,
                      worse, 1e-3)
    z = grower.replay(b.bins, b.feature_num_bins(), grad, hess,
                      grower.SplitRules(params, 0.0), worse, 1e-3)
    assert a[1] == z[1] and a[1] and a[1][0][0] == 0
    assert np.array_equal(a[0].leaf_count, z[0].leaf_count)
