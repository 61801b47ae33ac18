"""Regression tests for review findings: RF reload averaging, DART
max_drop<=0, bigger-is-better flag for lazily-imported metrics, GOSS
init-score handling on the default driver path."""
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.metric import is_bigger_better


def _reg_data(rng, n=200):
    X = rng.randn(n, 4)
    y = X[:, 0] * 2 + 0.1 * rng.randn(n)
    return X, y


class TestRFReload:
    def test_rf_predict_survives_save_load(self, rng, tmp_path):
        X, y = _reg_data(rng)
        ds = lgb.Dataset(X, y)
        bst = lgb.train({"objective": "regression", "boosting": "rf",
                         "bagging_freq": 1, "bagging_fraction": 0.7,
                         "num_leaves": 7, "verbose": -1},
                        ds, num_boost_round=12)
        before = bst.predict(X)
        path = str(tmp_path / "rf.txt")
        bst.save_model(path)
        loaded = lgb.Booster(model_file=path)
        after = loaded.predict(X)
        np.testing.assert_allclose(after, before, rtol=1e-5, atol=1e-6)
        # averaged output must be on the label scale, not the tree-sum scale
        assert np.abs(after - y.mean()).mean() < 5 * np.abs(y - y.mean()).mean()


class TestDartMaxDrop:
    def test_negative_max_drop_allows_multiple_drops(self, rng):
        X, y = _reg_data(rng)
        ds = lgb.Dataset(X, y)
        bst = lgb.train({"objective": "regression", "boosting": "dart",
                         "max_drop": -1, "drop_rate": 0.9, "skip_drop": 0.0,
                         "num_leaves": 7, "drop_seed": 3, "verbose": -1},
                        ds, num_boost_round=15)
        gbdt = bst._gbdt
        # with drop_rate 0.9 over 14 candidate iters, an unlimited max_drop
        # must have dropped >1 tree in at least one round
        assert max(len(gbdt._drop_index), gbdt.iter) > 0
        # train a second run recording per-iter drop counts via monkeypatch
        drops = []
        ds2 = lgb.Dataset(X, y)
        from lightgbm_tpu.models.dart import DART
        orig = DART._dropping_trees

        def record(self):
            orig(self)
            drops.append(len(self._drop_index))

        DART._dropping_trees = record
        try:
            lgb.train({"objective": "regression", "boosting": "dart",
                       "max_drop": -1, "drop_rate": 0.9, "skip_drop": 0.0,
                       "num_leaves": 7, "drop_seed": 3, "verbose": -1},
                      ds2, num_boost_round=15)
        finally:
            DART._dropping_trees = orig
        assert max(drops) > 1


class TestBiggerIsBetter:
    def test_rank_metrics_flagged(self):
        assert is_bigger_better("ndcg")
        assert is_bigger_better("ndcg@5")
        assert is_bigger_better("map")
        assert is_bigger_better("auc")
        assert not is_bigger_better("l2")
        assert not is_bigger_better("multi_logloss")
        assert not is_bigger_better("cross_entropy")

    def test_early_stopping_respects_ndcg_direction(self, rng):
        nq, per = 15, 12
        X = rng.randn(nq * per, 5)
        # noisy relevance so NDCG improves gradually instead of starting at 1
        y = np.clip(np.digitize(X[:, 0] + 1.2 * rng.randn(nq * per),
                                [-0.5, 0.5]), 0, 2)
        ds = lgb.Dataset(X, y, group=[per] * nq)
        vd = lgb.Dataset(X, y, group=[per] * nq, reference=ds)
        res = {}
        bst = lgb.train({"objective": "lambdarank", "metric": "ndcg",
                         "num_leaves": 7, "learning_rate": 0.1, "verbose": -1},
                        ds, num_boost_round=30, valid_sets=[vd],
                        valid_names=["v"], early_stopping_rounds=5,
                        evals_result=res)
        # NDCG improves on training data; early stopping must NOT fire at
        # iteration 5 with best_iteration stuck at 1
        assert bst.best_iteration > 1


class TestGossInitScore:
    def test_goss_keeps_boost_from_average(self, rng):
        X, y = _reg_data(rng)
        y = y + 100.0  # big offset: lost init score is obvious
        ds = lgb.Dataset(X, y)
        bst = lgb.train({"objective": "regression", "boosting": "goss",
                         "num_leaves": 7, "learning_rate": 0.1, "verbose": -1},
                        ds, num_boost_round=10)
        pred = bst.predict(X)
        assert abs(pred.mean() - 100.0) < 10.0

    def test_goss_custom_fobj_still_samples(self, rng):
        X, y = _reg_data(rng)
        ds = lgb.Dataset(X, y)

        def fobj(score, _ds):
            return score - y, np.ones_like(y)

        bst = lgb.train({"boosting": "goss", "num_leaves": 7, "top_rate": 0.3,
                         "other_rate": 0.3, "learning_rate": 0.3,
                         "objective": "none", "verbose": -1},
                        ds, num_boost_round=8, fobj=fobj)
        assert bst.num_trees() == 8


class TestForcedSplitAbandonment:
    """An invalid forced split must abandon its whole forced subtree
    (ForceSplits, serial_tree_learner.cpp:593-751) without desyncing the
    leaf addressing of entries from other branches."""

    def _grow(self, plan, rng):
        from lightgbm_tpu.ops.grow import grow_tree
        from lightgbm_tpu.ops.split import SplitParams
        import jax.numpy as jnp
        n, B = 256, 16
        bins = np.zeros((n, 3), np.uint8)
        bins[:, 0] = np.arange(n) % 16          # valid split anywhere
        bins[:, 1] = 9                          # constant: any split invalid
        bins[:, 2] = np.where(np.arange(n) % 2 == 0, 3, 12)
        grad = rng.randn(n)
        return grow_tree(
            jnp.asarray(bins), jnp.asarray(grad, jnp.float32),
            jnp.ones(n, jnp.float32), jnp.zeros(n, jnp.int32),
            jnp.ones(3, bool), jnp.full(3, B, jnp.int32),
            jnp.zeros(3, jnp.int32), jnp.zeros(3, jnp.int32),
            SplitParams(min_data_in_leaf=1, min_sum_hessian_in_leaf=0.0),
            forced_splits=plan, max_leaves=8, max_bin=B,
            hist_impl="scatter")

    @pytest.mark.slow
    def test_invalid_root_abandons_descendants(self, rng):
        # root entry forces constant feature 1 (empty child -> invalid);
        # its child entries must NOT be applied to the unsplit root
        plan = ((0, 1, 4, False), (0, 2, 7, False), (1, 2, 7, False))
        t_forced, _ = self._grow(plan, np.random.RandomState(7))
        t_plain, _ = self._grow((), np.random.RandomState(7))
        assert int(t_forced.num_leaves) == int(t_plain.num_leaves)
        np.testing.assert_array_equal(np.asarray(t_forced.split_feature),
                                      np.asarray(t_plain.split_feature))
        np.testing.assert_array_equal(np.asarray(t_forced.threshold_bin),
                                      np.asarray(t_plain.threshold_bin))

    @pytest.mark.slow
    def test_invalid_left_child_keeps_right_sibling(self, rng):
        # valid root; invalid left-child entry; valid right-child entry:
        # the right sibling must still land on the root's right child
        plan = ((0, 0, 7, False), (0, 1, 4, False), (1, 2, 7, False))
        tree, _ = self._grow(plan, np.random.RandomState(7))
        sf = np.asarray(tree.split_feature)
        thr = np.asarray(tree.threshold_bin)
        assert (sf[0], thr[0]) == (0, 7)
        assert (sf[1], thr[1]) == (2, 7)
        # node 1 must be the root's right child (leaf 1 was split)
        assert int(np.asarray(tree.right_child)[0]) == 1


class TestEngineFailurePropagates:
    def test_partition_failure_raises(self, monkeypatch):
        """A lowering/runtime failure in the partition fast path raises
        with its message — the fused single-dispatch iteration AND the
        plain per-tree grow (GOSS keeps the unfused spine), also under a
        row bag.  (It used to demote the booster to the label engine with
        a warning; a booster that quietly changed engine gets measured as
        something it is not.)"""
        from lightgbm_tpu.ops import grow_partition as gp_mod
        rng = np.random.default_rng(0)
        X = rng.normal(size=(500, 6)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.float32)

        def boom(*a, **k):
            raise RuntimeError("simulated Mosaic lowering failure")

        for extra in ({}, {"boosting": "goss"},
                      {"bagging_fraction": 0.8, "bagging_freq": 1}):
            bst = lgb.Booster(params=dict({"objective": "binary",
                                           "verbose": -1,
                                           "tpu_tree_engine": "partition"},
                                          **extra),
                              train_set=lgb.Dataset(X, label=y))
            g = bst._gbdt
            assert g._use_partition_engine, "partition engine not selected"
            monkeypatch.setattr(gp_mod, "grow_tree_partition_impl", boom)
            monkeypatch.setattr(gp_mod, "grow_tree_partition", boom)
            g._grow_partition = boom
            with pytest.raises(RuntimeError, match="simulated Mosaic"):
                bst.update()
            assert g._use_partition_engine     # not demoted either
            monkeypatch.undo()


class TestResetTrainingDataInvalidatesFusedTrace:
    def test_reset_clears_fused_caches(self, rng):
        """ResetTrainingData swaps the dataset under the booster; the
        fused-iteration jit baked the OLD dataset's bundle maps /
        categorical flags in as trace constants, so _setup_train must
        drop the caches or a same-shaped replacement silently trains on
        the old structure (round-3 advisor medium)."""
        X = rng.randn(400, 5).astype(np.float64)
        y = (X[:, 0] > 0).astype(np.float64)
        ds_a = lgb.Dataset(X, label=y, params={"verbose": -1})
        bst = lgb.Booster(params={"objective": "binary", "verbose": -1},
                          train_set=ds_a)
        bst.update()
        g = bst._gbdt
        # simulate a cached fused trace regardless of which engine the
        # CPU test environment selected
        g._fused_fn = object()
        g._fused_key = ("stale",)
        g._fused_fields = [("stale", "stale")]
        g._fused_validated = True
        g._partition_validated = True

        X2 = rng.randn(400, 5).astype(np.float64)
        y2 = (X2[:, 1] > 0).astype(np.float64)
        ds_b = lgb.Dataset(X2, label=y2, params={"verbose": -1})
        ds_b.construct()
        # a booster stopped on the old data must train again on the new
        g._deferred_stopped = True
        # drive the REAL c_api entry point (python-level objects satisfy
        # its duck-typed contract: bst._gbdt, ds.construct()/_binned)
        from lightgbm_tpu import c_api
        bh, dh = c_api._new_handle(bst), c_api._new_handle(ds_b)
        try:
            ret = c_api.LGBM_BoosterResetTrainingData(bh, dh)
        finally:
            c_api._handles.pop(bh, None)
            c_api._handles.pop(dh, None)
        assert ret == 0, c_api.LGBM_GetLastError()
        assert not g._deferred_stopped
        assert g._fused_fn is None
        assert g._fused_fields is None
        assert g._fused_key is None
        assert not g._fused_validated
        assert not g._partition_validated
        # training must continue cleanly on the new dataset
        bst.update()
        assert bst.num_trees() == 2


class TestClassWeight:
    def test_balanced_shifts_minority_probability(self, rng):
        """class_weight='balanced' must upweight the minority class: on
        a 9:1 imbalanced task the weighted model's mean predicted
        probability for the minority class must exceed the unweighted
        model's (reference fit path sklearn.py:488-493)."""
        from lightgbm_tpu.sklearn import LGBMClassifier
        n = 1200
        X = rng.randn(n, 4)
        # minority class needs some signal so probabilities move
        y = ((X[:, 0] + 0.5 * rng.randn(n)) > 1.28).astype(int)
        assert 0.03 < y.mean() < 0.25
        common = dict(n_estimators=30, num_leaves=15, verbose=-1)
        plain = LGBMClassifier(**common).fit(X, y)
        bal = LGBMClassifier(class_weight="balanced", **common).fit(X, y)
        p_plain = plain.predict_proba(X)[:, 1].mean()
        p_bal = bal.predict_proba(X)[:, 1].mean()
        assert p_bal > p_plain + 0.05

    def test_dict_weight_equals_sample_weight(self, rng):
        """A {class: w} dict must train identically to passing the same
        per-sample weights explicitly."""
        from lightgbm_tpu.sklearn import LGBMClassifier
        n = 800
        X = rng.randn(n, 3)
        y = (X[:, 0] > 0.8).astype(int)
        common = dict(n_estimators=15, num_leaves=7, verbose=-1)
        cw = LGBMClassifier(class_weight={0: 1.0, 1: 3.0}, **common).fit(X, y)
        sw = np.where(y == 1, 3.0, 1.0)
        ref = LGBMClassifier(**common).fit(X, y, sample_weight=sw)
        np.testing.assert_allclose(cw.predict_proba(X), ref.predict_proba(X),
                                   rtol=1e-6, atol=1e-7)
