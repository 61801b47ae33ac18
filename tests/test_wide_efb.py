"""Allstate-shaped stress: thousands of one-hot features through the
always-dense + EFB design.

The reference handles its 4,228-feature Allstate benchmark
(docs/Experiments.rst) with sparse bin storage (src/io/sparse_bin.hpp);
this framework deliberately dropped sparse bins (SURVEY §7, the GPU
learner's own densification precedent, gpu_tree_learner.cpp:233-251)
and relies on EFB to fold mutually-exclusive one-hot blocks into dense
bundles.  This test is the proof point at that feature count: the
bundling must recover ~categorical-variable-many dense columns from
~4k one-hot inputs, train, and separate held-out data.
"""
import numpy as np
import pytest

import lightgbm_tpu as lgb

sp = pytest.importorskip("scipy.sparse")


def _one_hot_dataset(rng, n_rows, n_vars, cats_per_var):
    """CSR one-hot of n_vars categoricals -> n_vars*cats_per_var cols."""
    F = n_vars * cats_per_var
    cats = rng.randint(0, cats_per_var, size=(n_rows, n_vars))
    cols = (cats + np.arange(n_vars) * cats_per_var).ravel()
    rows = np.repeat(np.arange(n_rows), n_vars)
    X = sp.csr_matrix(
        (np.ones(n_rows * n_vars, np.float32), (rows, cols)),
        shape=(n_rows, F))
    # signal: a handful of (var, category) indicator effects
    w = np.zeros(F, np.float32)
    sig = rng.choice(F, 25, replace=False)
    w[sig] = rng.randn(25) * 2.0
    logits = np.asarray(X @ w).ravel()
    y = (logits + 0.5 * rng.randn(n_rows) > 0).astype(np.float32)
    return X, y


@pytest.mark.slow
def test_allstate_shaped_wide_one_hot(rng):
    n_vars, cats = 211, 20            # 4,220 one-hot columns
    X, y = _one_hot_dataset(rng, 30_000, n_vars, cats)
    assert X.shape[1] == 4_220

    ds = lgb.Dataset(X[:25_000], y[:25_000])
    ds.construct()
    binned = ds._binned
    G = binned.bundle.num_groups if binned.bundle is not None else X.shape[1]
    # each categorical's one-hot block is perfectly exclusive, so EFB
    # must fold ~20x: anything near the raw width means bundling failed
    assert G <= 2 * n_vars, "EFB produced %d groups from %d columns" % (
        G, X.shape[1])

    bst = lgb.train({"objective": "binary", "num_leaves": 63,
                     "learning_rate": 0.2, "verbose": -1}, ds,
                    num_boost_round=15)
    from sklearn.metrics import roc_auc_score
    auc = roc_auc_score(y[25_000:], bst.predict(X[25_000:]))
    assert auc > 0.75, auc


def _allstate_args():
    """The benchmark's Allstate generator with the configuration's own
    column structure (benchmarks/configs/allstate-onehot-int8.json)."""
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "allstate-onehot-int8.json")) as f:
        return json.load(f)["data"]["args"]


def test_sparse_ingest_of_the_allstate_columns(tmp_path):
    """Tier-1 guard of the sparse ingest at the published width: 4 228
    one-hot columns in 32 variables as CSR, a few thousand rows.  Binning
    keeps the columns that have rows, bundling folds them into a few dozen
    group columns whose lane ranges tile each group, the binary file gives
    the same bundle and bins back, and a small model separates held-out
    rows."""
    from benchmarks.data import allstate
    from lightgbm_tpu.io.dataset import BinnedDataset
    args = dict(_allstate_args(), intercept=-2.5)   # a fifth positive here
    cards = args["cardinalities"]
    assert len(cards) == 32 and sum(cards) == 4228
    X = allstate.features(args, "train", 10000)
    y, _ = allstate.labels(args, 7, "train", X)
    assert sp.issparse(X) and X.shape == (10000, 4228)
    assert X.nnz == 32 * 10000

    params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
              "min_data_in_leaf": 20, "learning_rate": 0.2}
    ds = lgb.Dataset(X[:8000], y[:8000], params=params)
    ds.construct()
    binned = ds._binned
    info = binned.bundle
    assert info is not None and info.conflicts == 0
    F, G = binned.num_features, info.num_groups
    # rare columns have no row among 8 000 and are dropped; the rest fold
    # into about one group per variable (the three wide variables' kept
    # columns fit a group or two each at this row count)
    assert 500 < F < 4228
    assert 32 <= G <= 40, G
    assert binned.bins.shape == (8000, G) and binned.bins.dtype == np.uint8
    assert int(info.group_num_bins.max()) <= 256
    for g, feats in enumerate(info.groups):
        # one-hot columns, default bin 0: one lane each, the lanes of a
        # group's features adjacent from lane 1 on
        lo = np.array([info.feature_lo[f] for f in feats])
        hi = np.array([info.feature_hi[f] for f in feats])
        assert (hi - lo == 1).all() and lo[0] == 1
        assert (lo[1:] == hi[:-1]).all()
        assert info.group_num_bins[g] == len(feats) + 1
        assert binned.bins[:, g].max() <= len(feats)
    # every row has one column of each variable, so no group column of a
    # whole variable is ever at its all-default bin
    offsets = np.concatenate([[0], np.cumsum(cards)])
    runs = {(int(a), int(b)) for a, b in zip(offsets[:-1], offsets[1:])}
    raw = np.asarray(binned.real_feature_index)
    whole = [g for g, feats in enumerate(info.groups)
             if (int(raw[feats].min()), int(raw[feats].max()) + 1) in runs
             and len(feats) == raw[feats].max() + 1 - raw[feats].min()]
    assert len(whole) >= 10
    assert all(binned.bins[:, g].min() >= 1 for g in whole)

    path = str(tmp_path / "allstate.bin")
    ds.save_binary(path)
    back = BinnedDataset.load_binary(path)
    assert back.bundle.groups == info.groups
    assert back.bundle.conflicts == info.conflicts
    np.testing.assert_array_equal(back.bundle.feature_lo, info.feature_lo)
    np.testing.assert_array_equal(back.bundle.feature_shift,
                                  info.feature_shift)
    np.testing.assert_array_equal(back.bins, binned.bins)
    assert back.real_feature_index == binned.real_feature_index

    bst = lgb.train(params, ds, num_boost_round=8)
    from sklearn.metrics import roc_auc_score
    auc = roc_auc_score(y[8000:], bst.predict(X[8000:]))
    assert auc > 0.62, auc
