"""DART / GOSS / RF boosting modes (reference test_engine.py:51,735,752).

The data are the generated regression pair of tests/_fixtures.py (seed 30:
a continuous label of variance 5.98; predicting the training mean gives a
holdout l2 of 5.7662, the label's own noise 0.25).  Thresholds were set
from what the label engine reaches on the CPU backend (PR 30's run), about
a tenth above the measurement named beside each."""
import numpy as np
import pytest

import lightgbm_tpu as lgb


def _load(path):
    mat = np.loadtxt(path)
    return mat[:, 1:], mat[:, 0]


@pytest.fixture(scope="module")
def data(example_files):
    X, y = _load(example_files["regression.train"])
    Xt, yt = _load(example_files["regression.test"])
    return X, y, Xt, yt


@pytest.mark.slow
def test_dart(data):
    X, y, Xt, yt = data
    train = lgb.Dataset(X, y)
    valid = train.create_valid(Xt, yt)
    evals = {}
    bst = lgb.train({"objective": "regression", "boosting": "dart",
                     "metric": "l2", "verbose": -1, "drop_rate": 0.1},
                    train, num_boost_round=40, valid_sets=[valid],
                    evals_result=evals, verbose_eval=False)
    assert evals["valid_0"]["l2"][-1] < 3.0   # measured 2.6868 at 40 rounds
    assert np.isfinite(bst.predict(Xt)).all()


def test_goss(data):
    X, y, Xt, yt = data
    train = lgb.Dataset(X, y)
    valid = train.create_valid(Xt, yt)
    evals = {}
    bst = lgb.train({"objective": "regression", "boosting": "goss",
                     "metric": "l2", "verbose": -1, "learning_rate": 0.1},
                    train, num_boost_round=40, valid_sets=[valid],
                    evals_result=evals, verbose_eval=False)
    assert evals["valid_0"]["l2"][-1] < 1.5   # measured 1.3264 at 40 rounds
    # GOSS warm-up ends at iteration 10 (1/lr); training still converges after
    assert evals["valid_0"]["l2"][-1] < evals["valid_0"]["l2"][5]


def test_rf(data):
    X, y, Xt, yt = data
    train = lgb.Dataset(X, y)
    valid = train.create_valid(Xt, yt)
    evals = {}
    bst = lgb.train({"objective": "regression", "boosting": "rf",
                     "metric": "l2", "verbose": -1,
                     "bagging_freq": 1, "bagging_fraction": 0.7,
                     "feature_fraction": 0.8},
                    train, num_boost_round=30, valid_sets=[valid],
                    evals_result=evals, verbose_eval=False)
    # averaged-forest validation error beats predicting the mean
    base = np.mean((yt - y.mean()) ** 2)
    assert evals["valid_0"]["l2"][-1] < base
    pred = bst.predict(Xt)
    # predictions are averaged, not summed
    assert pred.min() > y.min() - 1 and pred.max() < y.max() + 1


def test_rf_requires_bagging(data):
    X, y, _, _ = data
    with pytest.raises(Exception):
        lgb.train({"objective": "regression", "boosting": "rf", "verbose": -1},
                  lgb.Dataset(X, y), num_boost_round=2)


def test_bagging(data):
    X, y, Xt, yt = data
    train = lgb.Dataset(X, y)
    valid = train.create_valid(Xt, yt)
    evals = {}
    lgb.train({"objective": "regression", "metric": "l2", "verbose": -1,
               "bagging_freq": 2, "bagging_fraction": 0.5},
              train, num_boost_round=30, valid_sets=[valid],
              evals_result=evals, verbose_eval=False)
    assert evals["valid_0"]["l2"][-1] < 2.1   # measured 1.8605 at 30 rounds
    # half the rows per tree is another model than all of them (2.0126)
    full = {}
    train = lgb.Dataset(X, y)
    lgb.train({"objective": "regression", "metric": "l2", "verbose": -1},
              train, num_boost_round=30,
              valid_sets=[train.create_valid(Xt, yt)],
              evals_result=full, verbose_eval=False)
    assert abs(full["valid_0"]["l2"][-1] - evals["valid_0"]["l2"][-1]) > 0.01


def test_feature_fraction(data):
    X, y, Xt, yt = data
    train = lgb.Dataset(X, y)
    bst = lgb.train({"objective": "regression", "verbose": -1,
                     "feature_fraction": 0.5}, train, num_boost_round=10)
    pred = bst.predict(Xt)
    assert np.isfinite(pred).all()
    assert np.mean((pred - yt) ** 2) < 4.1    # measured 3.6738 at 10 rounds
    # trees that saw half the columns are other trees (all columns: 3.3891)
    full = lgb.train({"objective": "regression", "verbose": -1},
                     lgb.Dataset(X, y), num_boost_round=10).predict(Xt)
    assert np.abs(pred - full).max() > 0.01


def test_shap_sums_to_prediction(data):
    X, y, Xt, _ = data
    train = lgb.Dataset(X, y)
    bst = lgb.train({"objective": "regression", "verbose": -1},
                    train, num_boost_round=5)
    sub = Xt[:20]
    contrib = bst.predict(sub, pred_contrib=True)
    raw = bst.predict(sub, raw_score=True)
    np.testing.assert_allclose(contrib.sum(axis=1), raw, rtol=1e-6)


def test_goss_device_sampling_semantics(rng):
    """_goss_sample: top rows kept unamplified, exactly other_k of the
    rest amplified by (n-top_k)/other_k, mask covers only selected rows."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.models.goss import _goss_sample
    n, top_k, other_k = 1000, 200, 100
    g = jnp.asarray(rng.randn(2, n), jnp.float32)
    h = jnp.asarray(np.abs(rng.randn(2, n)) + 0.1, jnp.float32)
    mult = (n - top_k) / other_k
    g2, h2, mask = _goss_sample(g, h, jax.random.PRNGKey(0),
                                jnp.float32(mult), top_k=top_k,
                                other_k=other_k)
    score = np.abs(np.asarray(g) * np.asarray(h)).sum(axis=0)
    thr = np.partition(score, n - top_k)[n - top_k]
    is_top = score >= thr
    mask = np.asarray(mask)
    amp = np.asarray(g2)[0] / np.asarray(g)[0]
    # top rows: kept, not amplified
    assert (mask[is_top] == 0).all()
    np.testing.assert_allclose(amp[is_top], 1.0, rtol=1e-6)
    # sampled others: amplified by mult and in the bag
    sampled = (~is_top) & (mask == 0)
    assert sampled.sum() == other_k
    np.testing.assert_allclose(amp[sampled], mult, rtol=1e-5)
    # dropped rows: out of bag
    assert (mask[(~is_top) & ~sampled] == -1).all()


def test_l1_renew_device_matches_host(rng):
    """renew_leaf_percentiles vs the per-leaf numpy oracle, weighted and
    unweighted, several alphas."""
    import jax.numpy as jnp
    from lightgbm_tpu.objective import percentile, weighted_percentile
    from lightgbm_tpu.ops.quantile import renew_leaf_percentiles
    n, L = 3000, 12
    residual = rng.randn(n)
    lids = rng.randint(-1, L, n)     # -1 = out of bag
    weights = rng.rand(n) + 0.05
    for alpha in (0.5, 0.1, 0.9):
        dev = np.asarray(renew_leaf_percentiles(
            jnp.asarray(residual), jnp.asarray(lids, jnp.int32),
            jnp.asarray(alpha), L=L))
        devw = np.asarray(renew_leaf_percentiles(
            jnp.asarray(residual), jnp.asarray(lids, jnp.int32),
            jnp.asarray(alpha), L=L, weights=jnp.asarray(weights)))
        for leaf in range(L):
            rows = np.flatnonzero(lids == leaf)
            if len(rows) == 0:
                continue
            np.testing.assert_allclose(
                dev[leaf], percentile(residual[rows], alpha),
                rtol=1e-5, atol=1e-7)
            np.testing.assert_allclose(
                devw[leaf], weighted_percentile(residual[rows],
                                                weights[rows], alpha),
                rtol=1e-5, atol=1e-7)
