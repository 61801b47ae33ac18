"""Carried-arena fast path: scores/labels ride the arena as residue
planes, so the per-tree rowid sort disappears from the training loop
(see gbdt._run_fused_iter_carried / partition_pallas.compact_carry).
These tests pin its engagement conditions and its equivalence to the
label engine."""
import numpy as np
import pytest

import lightgbm_tpu as lgb

pytestmark = pytest.mark.slow


def _data(rng, n=3000, F=8):
    X = rng.randn(n, F).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2]
         + 0.3 * rng.randn(n) > 0).astype(np.float32)
    return X, y


def test_carried_engages_and_matches_label_engine(rng):
    X, y = _data(rng)
    preds = {}
    for eng in ("partition", "label"):
        params = {"objective": "binary", "num_leaves": 31, "verbose": -1,
                  "min_data_in_leaf": 5, "tpu_tree_engine": eng}
        bst = lgb.train(params, lgb.Dataset(X, y), num_boost_round=12)
        if eng == "partition":
            assert getattr(bst._gbdt, "_carried_active", False) is True
        preds[eng] = bst.predict(X)
    # f32 reassociation noise only (the GPU-parity band)
    np.testing.assert_allclose(preds["partition"], preds["label"],
                               rtol=1e-3, atol=1e-5)


def test_carried_regression_objective(rng):
    X, _ = _data(rng)
    yr = (X[:, 0] * 2 + np.sin(X[:, 1]) + 0.1 * rng.randn(len(X))
          ).astype(np.float32)
    params = {"objective": "regression", "num_leaves": 31, "verbose": -1,
              "tpu_tree_engine": "partition"}
    bst = lgb.train(params, lgb.Dataset(X, yr), num_boost_round=10)
    assert getattr(bst._gbdt, "_carried_active", False) is True
    mse = float(np.mean((bst.predict(X) - yr) ** 2))
    assert mse < 0.5 * float(np.var(yr)), mse


def test_carried_subclassed_objective_opts_out(rng):
    """huber overrides _raw_gradients but not the carry pair — it must
    NOT engage the carried path (it would train with L2 math)."""
    X, _ = _data(rng)
    yr = (X[:, 0] + 0.1 * rng.randn(len(X))).astype(np.float32)
    params = {"objective": "huber", "num_leaves": 15, "verbose": -1,
              "tpu_tree_engine": "partition"}
    bst = lgb.train(params, lgb.Dataset(X, yr), num_boost_round=5)
    assert getattr(bst._gbdt, "_carried_active", True) is False


def test_carried_demotes_on_external_score_write(rng):
    """rollback writes train scores; the next iteration must demote the
    carried path (stale planes) and keep training correctly."""
    X, y = _data(rng)
    preds = {}
    for eng in ("partition", "label"):
        params = {"objective": "binary", "num_leaves": 31, "verbose": -1,
                  "min_data_in_leaf": 5, "tpu_tree_engine": eng}
        bst = lgb.Booster(params=params, train_set=lgb.Dataset(X, y))
        for _ in range(6):
            bst.update()
        g = bst._gbdt
        if eng == "partition":
            assert getattr(g, "_carried_active", False) is True
        bst.rollback_one_iter()
        # the standard path roots its trees in the pristine block, which
        # the carried trees overwrote: demotion must have rewritten it
        for _ in range(4):
            bst.update()
        if eng == "partition":
            assert g._carried_active is False     # demoted, not broken
        assert bst.num_trees() == 9
        preds[eng] = bst.predict(X)
    np.testing.assert_allclose(preds["partition"], preds["label"],
                               rtol=1e-3, atol=1e-5)


def test_carried_lazy_score_materializes(rng):
    """Reading the training score mid-run reconstructs the row order
    exactly (the materializer sort), matching eval-time expectations."""
    X, y = _data(rng)
    params = {"objective": "binary", "num_leaves": 31, "verbose": -1,
              "min_data_in_leaf": 5, "tpu_tree_engine": "partition"}
    ds = lgb.Dataset(X, y)
    bst = lgb.Booster(params=params, train_set=ds)
    for _ in range(5):
        bst.update()
    g = bst._gbdt
    assert g._carried_active
    score = np.asarray(g.train_state.score)[0]
    # raw-score predict over the same 5 trees must agree with the
    # training-state score (deferred pipeline drains on predict)
    raw = bst.predict(X, raw_score=True)
    np.testing.assert_allclose(score, raw, rtol=1e-3, atol=1e-5)


def test_carried_with_forced_splits(rng, tmp_path):
    """Forced splits inject cache rows before the grow loop — the
    carried root must serve them identically to the pristine path."""
    import json
    X, y = _data(rng)
    fs = {"feature": 0, "threshold": 0.0,
          "left": {"feature": 1, "threshold": 0.0}}
    p = tmp_path / "forced.json"
    p.write_text(json.dumps(fs))
    preds = {}
    for eng in ("partition", "label"):
        params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
                  "min_data_in_leaf": 5, "tpu_tree_engine": eng,
                  "forcedsplits_filename": str(p)}
        bst = lgb.train(params, lgb.Dataset(X, y), num_boost_round=6)
        model = bst._gbdt.models[0]
        assert int(model.split_feature[0]) == 0       # root forced
        preds[eng] = bst.predict(X)
    np.testing.assert_allclose(preds["partition"], preds["label"],
                               rtol=1e-3, atol=1e-5)


def test_carried_with_efb_bundles(rng):
    """EFB-bundled group columns ride the carried arena: bins_t holds
    GROUP columns and the carry planes sit after the group block."""
    n = 4000
    num = rng.randn(n, 3).astype(np.float32)
    cats = rng.randint(0, 3, (n, 6))
    onehot = np.zeros((n, 18), np.float32)
    onehot[np.arange(n)[:, None], cats + np.arange(6) * 3] = 1.0
    X = np.column_stack([num, onehot])
    y = (num[:, 0] + (cats[:, 0] == 1) + 0.3 * rng.randn(n) > 0.5
         ).astype(np.float32)
    preds = {}
    for eng in ("partition", "label"):
        params = {"objective": "binary", "num_leaves": 31, "verbose": -1,
                  "min_data_in_leaf": 5, "tpu_tree_engine": eng,
                  "enable_bundle": True}
        bst = lgb.train(params, lgb.Dataset(X, y), num_boost_round=8)
        if eng == "partition":
            assert getattr(bst._gbdt, "_carried_active", False) is True
            assert bst._gbdt.train_state.bundle is not None
        preds[eng] = bst.predict(X)
    np.testing.assert_allclose(preds["partition"], preds["label"],
                               rtol=1e-3, atol=1e-5)
