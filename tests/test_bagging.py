"""Row bagging drawn on the device (ops/bagging.py) and grown on the fused
spine: the draw itself, the fused spine against the unfused one under the
same bags, every tree against the plain reference grower on its own rows
and columns, and the out-of-bag rows' scores against the plain walker.

Small sizes, seeded, the partition engine in interpret mode.  The unfused
spine is forced by what already forces it, a training metric
(`is_provide_training_metric`): there is no switch.  int8 trees are not
compared tree for tree: the spines draw the quantisation noise over rows
in other orders (tests/test_valid_fused.py), so only the first tree, grown
from equal gradients, must match the float32 one.
"""
import functools

import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from benchmarks.harness import checks_bagged
from benchmarks.reference import walker
from lightgbm_tpu.metric import create_metric
from lightgbm_tpu.ops import bagging

ITERS = 5
N, F = 1200, 6
FREQ = 2


# ---- the draw --------------------------------------------------------------
def _mask(seed, index, n, count):
    bag = bagging.draw(seed, index, num_data=n, count=count)
    return np.asarray(bagging.row_mask(bag, num_data=n)) == 0


@pytest.mark.parametrize("n,fraction", [(1000, 0.8), (4097, 0.3),
                                        (50, 0.99), (12345, 0.5)])
def test_a_bag_holds_exactly_int_fraction_times_rows(n, fraction):
    count = bagging.bag_rows(fraction, n)
    assert count == int(fraction * n)
    mask = _mask(3, 0, n, count)
    assert mask.sum() == count
    # a second call draws the same rows
    np.testing.assert_array_equal(_mask(3, 0, n, count), mask)


def test_every_draw_index_and_seed_draws_another_bag():
    masks = [_mask(3, i, 1000, 800) for i in range(6)] + [_mask(4, 0, 1000,
                                                                 800)]
    for i in range(len(masks)):
        assert masks[i].sum() == 800
        for j in range(i):
            assert not np.array_equal(masks[i], masks[j]), (i, j)


def test_each_row_is_in_a_bag_at_the_fraction_within_a_binomial_band():
    n, count, draws = 2000, 1600, 100
    hits = np.sum([_mask(3, i, n, count) for i in range(draws)], axis=0)
    # every draw is exact: the mean rate is the fraction itself
    assert hits.mean() == draws * 0.8
    sd = np.sqrt(draws * 0.8 * 0.2)                  # 4 draws
    assert np.all(np.abs(hits - 80) <= 6 * sd), (hits.min(), hits.max())
    assert 0.7 * sd ** 2 < hits.var() < 1.3 * sd ** 2, hits.var()
    # neighbours are drawn independently: both in at the square of the rate
    both = np.mean([(m[:-1] & m[1:]).mean() for m in
                    (_mask(3, i, n, count) for i in range(20))])
    assert abs(both - 0.64) < 0.02, both


def _prefix(bags, n, count):
    return np.array([np.arange(n) < count] * bags)


@pytest.mark.parametrize("control", ["fair", "prefix", "fixed-key"])
def test_the_checks_bag_statistics_pass_a_fair_draw_and_fail_its_controls(
        control):
    """checks_bagged.bag_stats: every z under the configuration's 5 for
    the program's draw; a bag of the first rows, or one bag at every
    redraw, far over it."""
    n, count, bags = 4096, 3276, 16
    fair = np.array([_mask(3, i, n, count) for i in range(bags)])
    masks = {"fair": fair, "prefix": _prefix(bags, n, count),
             "fixed-key": np.array([fair[0]] * bags)}[control]
    bench = _Bench()
    problems = checks_bagged.bag_stat_problems(
        bench, {"bag_stats": {"z_max": 5}},
        {"bagging_fraction": 0.8, "bagging_freq": 1}, masks)
    z = [bench.held[k][0] for k in ("bag_block_z", "bag_overlap_z",
                                    "bag_dispersion_z")]
    if control == "fair":
        assert problems == [] and max(z) < 5, z
    else:
        assert len(problems) >= 2 and z[1] > 50 and z[2] > 50, z


def test_membership_is_a_function_of_the_row_id_not_its_place():
    n, count = 3000, 1800
    bag = bagging.draw(11, 7, num_data=n, count=count)
    mask = np.asarray(bagging.row_mask(bag, num_data=n)) == 0
    order = np.random.RandomState(0).permutation(n).astype(np.int32)
    np.testing.assert_array_equal(np.asarray(bagging.member(bag, order)),
                                  mask[order])


# ---- the spines ------------------------------------------------------------
# name -> (objective, int8, carried on the fused spine)
CASES = {"binary-f32": ("binary", False, True),
         "huber-f32": ("huber", False, False),
         "binary-int8": ("binary", True, True)}


@functools.lru_cache(maxsize=None)
def _data(objective):
    r = np.random.RandomState(1)
    X = r.randn(N, F).astype(np.float32)
    s = X @ r.randn(F) + r.randn(N)
    y = (s > 0) if objective == "binary" else s
    return X, y.astype(np.float32)


def _params(case):
    # 0.7 of 6 columns: 4 by v2.2.4's cut (int) and by the program's
    # rounding alike, which part at 0.8 (PERF.md, open questions)
    objective, int8, _ = CASES[case]
    return {"objective": objective, "num_leaves": 7, "min_data_in_leaf": 40,
            "learning_rate": 0.3, "verbose": -1, "max_bin": 7,
            "tpu_tree_engine": "partition", "tpu_quantized_grad": int8,
            "bagging_fraction": 0.8, "bagging_freq": FREQ,
            "feature_fraction": 0.7}


@functools.lru_cache(maxsize=None)
def _trained(case, spine):
    """checks_bagged.trained_with_draws after ITERS iterations, and the
    training rows: (booster, bags, drawn columns, scores, X)."""
    objective = CASES[case][0]
    X, y = _data(objective)
    ds = lgb.Dataset(X, y, params={"max_bin": 7})

    def unfused(booster):
        # what engine.train does with is_provide_training_metric
        m = create_metric("binary_logloss" if objective == "binary"
                          else "l2", booster.config)
        m.init(ds._binned.metadata, ds._binned.num_data)
        booster._gbdt.train_metrics.append(m)

    booster, bags, drawn, scores = checks_bagged.trained_with_draws(
        lgb, _params(case), ds, ITERS,
        prepare=unfused if spine == "unfused" else None)
    gbdt = booster._gbdt
    assert ("fused" if getattr(gbdt, "_fused_validated", False)
            else "unfused") == spine
    if spine == "fused":
        assert bool(gbdt._carried_active) == CASES[case][2]
    return booster, bags, drawn, scores, X


class _Bench:
    """What the checks write to: the held numbers."""

    def __init__(self):
        self.held = {}

    def hold(self, name, value, limit):
        self.held[name] = (value, limit)

    def say(self, what, **fields):
        pass


@pytest.mark.parametrize("case", ["binary-f32", "huber-f32"])
def test_both_spines_draw_the_same_bags_and_root_every_tree_in_it(case):
    _, fbags, fdrawn, _, _ = _trained(case, "fused")
    unfused, ubags, udrawn, _, _ = _trained(case, "unfused")
    count = int(0.8 * N)
    for t, (a, b) in enumerate(zip(fbags, ubags)):
        np.testing.assert_array_equal(a, b)
        assert a.sum() == count
        # a new bag at every multiple of bagging_freq, the same between
        assert t == 0 or np.array_equal(a, fbags[t - 1]) == bool(t % FREQ)
    for a, b in zip(fdrawn, udrawn):
        np.testing.assert_array_equal(a, b)
    fused = _trained(case, "fused")[0]
    # the rows every tree grows on ride the set-up span's plan
    assert fused._gbdt._engine_plan["bag_rows"] == count
    for booster in (fused, unfused):
        for tree in booster._gbdt.models:
            assert tree.internal_count[0] == count
            assert tree.leaf_count[:tree.num_leaves].sum() == count


@pytest.mark.parametrize("case", ["binary-f32", "huber-f32"])
def test_fused_and_unfused_spines_grow_the_same_trees(case):
    fused = _trained(case, "fused")[0]._gbdt.models
    unfused = _trained(case, "unfused")[0]._gbdt.models
    assert len(fused) == len(unfused) == ITERS
    for a, b in zip(fused, unfused):
        n = a.num_leaves
        assert n == b.num_leaves == 7
        np.testing.assert_array_equal(a.split_feature_inner[:n - 1],
                                      b.split_feature_inner[:n - 1])
        np.testing.assert_array_equal(a.threshold_in_bin[:n - 1],
                                      b.threshold_in_bin[:n - 1])
        np.testing.assert_array_equal(a.leaf_count[:n], b.leaf_count[:n])
        # float32 sums in another order, the score held in other forms
        # (three bfloat16 planes on the carried spine): a few units of
        # 1.2e-7 over five iterations, not the bit
        np.testing.assert_allclose(a.leaf_value[:n], b.leaf_value[:n],
                                   rtol=2e-5, atol=1e-7)


def test_int8_draws_the_float32_bags_and_grows_its_first_tree_alike():
    """The draw is the configuration's, not the precision's or the
    spine's: the int8 fused booster holds the float32 unfused booster's
    bags, and its first tree, grown from equal gradients (which round
    alike whatever the noise), is that booster's."""
    fused, bags, _, _, _ = _trained("binary-int8", "fused")
    unfused, ubags, _, _, _ = _trained("binary-f32", "unfused")
    for a, b in zip(bags, ubags):
        np.testing.assert_array_equal(a, b)
    a, b = fused._gbdt.models[0], unfused._gbdt.models[0]
    n = a.num_leaves
    np.testing.assert_array_equal(a.split_feature_inner[:n - 1],
                                  b.split_feature_inner[:n - 1])
    np.testing.assert_array_equal(a.threshold_in_bin[:n - 1],
                                  b.threshold_in_bin[:n - 1])
    np.testing.assert_array_equal(a.leaf_count[:n], b.leaf_count[:n])
    for tree in fused._gbdt.models:
        assert tree.internal_count[0] == int(0.8 * N)


def test_every_tree_is_the_reference_growers_on_its_bag_and_columns():
    """The bagged check's draws and replay (benchmarks/harness/
    checks_bagged.py): each float32 tree of the fused spine replayed by
    the plain float64 grower on the bins of its in-bag rows and drawn
    columns, its leaf values within the check's rounding."""
    booster, bags, drawn, _, _ = _trained("binary-f32", "fused")
    b = booster._gbdt.train_set
    bench, params = _Bench(), _params("binary-f32")
    c = {"gain_rtol": 1e-3, "leaf_value_rtol": 1e-3,
         "leaf_value_atol_of_largest": 1e-4}
    models = booster._gbdt.models
    assert checks_bagged.draw_problems(bench, params, bags, drawn, models,
                                       F) == []
    problems, _ = checks_bagged.replay_problems(
        bench, c, params, b, _data("binary")[1], b.bins[:1], models, bags,
        drawn)
    assert problems == []
    assert bench.held["gain_shortfall"][0] <= 1e-3
    assert bench.held["leaf_value_off_of_allowed"][0] <= 1.0


@pytest.mark.parametrize("case", sorted(CASES))
def test_out_of_bag_scores_are_the_plain_walkers_after_every_tree(case):
    booster, bags, _, scores, X = _trained(case, "fused")
    bench = _Bench()
    text = booster.model_to_string()
    assert checks_bagged.oob_score_problems(
        bench, {"oob_score_atol": 1e-5}, text, X, bags, scores) == []
    # and in the bag too: every row holds the model's raw score
    for t, held in enumerate(scores):
        np.testing.assert_allclose(held, walker.raw_scores(text, X, t + 1),
                                   atol=1e-5)


def test_the_timed_score_check_holds_the_boosters_scores_to_the_walker():
    """checks_bagged.timed_score_problems on rows spread over the row ids:
    the fused booster's own scores pass, the same rounded to bfloat16 do
    not."""
    booster, _, _, _, X = _trained("binary-f32", "fused")
    from benchmarks.drivers.train_rowsampled import timed_rows
    ids = timed_rows(2147483777, N, 100)
    assert {0, N - 1, 511, 512, 1023, 1024} <= set(ids.tolist())
    c = {"oob_score_atol": 1e-5}
    bench = _Bench()
    assert checks_bagged.timed_score_problems(bench, c, booster, X[ids],
                                              ids) == []
    assert bench.held["timed_score_diff"][0] <= 1e-5
    gbdt = booster._gbdt
    held = gbdt.train_state.score
    gbdt.train_state.score = jnp.asarray(held, jnp.bfloat16).astype(
        jnp.float32)
    try:
        bench = _Bench()
        assert checks_bagged.timed_score_problems(bench, c, booster, X[ids],
                                                  ids)
        assert bench.held["timed_score_diff"][0] > 1e-5
    finally:
        gbdt.train_state.score = held


def test_the_checks_controls_each_fail():
    """A bag one row short, a tree on every row, out-of-bag scores kept in
    bfloat16: what the bagged check's limits are for."""
    booster, bags, drawn, scores, X = _trained("binary-f32", "fused")
    models, params = booster._gbdt.models, _params("binary-f32")
    short = [b.copy() for b in bags]
    for b in short:
        b[np.flatnonzero(b)[0]] = False
    every = [np.ones_like(b) for b in bags]
    for control in (short, every):
        bench = _Bench()
        assert checks_bagged.draw_problems(bench, params, control, drawn,
                                           models, F)
        assert bench.held["bag_rows_off"][0] >= 1
    bf16 = [np.asarray(jnp.asarray(s, jnp.bfloat16), np.float64)
            for s in scores]
    bench = _Bench()
    assert checks_bagged.oob_score_problems(
        bench, {"oob_score_atol": 5e-5}, booster.model_to_string(), X,
        bags, bf16)
    assert bench.held["sample_oob_score_diff"][0] > 5e-5
