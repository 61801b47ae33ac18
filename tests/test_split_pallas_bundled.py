"""The split scan in group space (ops/split_pallas._group_scan_kernel)
against the scan it replaces on a bundled set: ops/grow.unbundle_hist to
[F, B, 3] and the feature-space kernel.  Interpret mode, on the CPU.

The histograms hold small whole numbers (and, in the float32 cases,
multiples of 1/64), so every partial sum is exact in float32 whatever its
order: the two scans then see the same sums and must pick the same
candidate, not one that is as good to rounding.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from lightgbm_tpu.io.efb import BundleInfo
from lightgbm_tpu.ops import grow as grow_ops
from lightgbm_tpu.ops import split_pallas as sp
from lightgbm_tpu.ops.split import SplitParams

NONE, ZERO, NAN = 0, 1, 2

# name -> (groups, num_bins, default_bins, missing_types)
LAYOUTS = {
    # no group holds more than one feature: every row a single segment
    "singletons": ([[0], [1], [2]], [7, 2, 12], [0, 0, 3], [NONE, NONE, NAN]),
    # one-hot blocks: every feature a segment of one lane
    "one_hot": ([[0, 1, 2, 3, 4], [5, 6, 7]], [2] * 8, [0] * 8, [NONE] * 8),
    # default bin 0 (no lane for it) beside a singleton
    "default_zero": ([[0, 2], [1]], [5, 9, 4], [0, 4, 0],
                     [NONE, NONE, NONE]),
    # a non-zero default: the hole
    "hole": ([[1, 0, 2]], [6, 5, 3], [2, 0, 1], [NONE, NONE, NONE]),
    # one variable's columns over two groups, the second shared
    "spans_two_groups": ([[0, 1, 2, 3], [4, 5, 6, 7]],
                         [2, 2, 2, 2, 2, 2, 5, 3], [0] * 8, [NONE] * 8),
    # NaN bins and zero-as-missing, bundled and not
    "missing": ([[0, 1], [2], [3, 4]], [6, 4, 8, 2, 7],
                [0, 0, 2, 0, 3], [NAN, ZERO, ZERO, NAN, NAN]),
}


def _maps(name):
    groups, nb, db, mt = LAYOUTS[name]
    info = BundleInfo(groups, nb, db)
    B = int(info.group_num_bins.max())
    maps = grow_ops.bundle_maps(info, np.asarray(nb), np.asarray(mt), B,
                                feature_scan=True, group_scan=True)
    return info, maps, B, (np.asarray(nb, np.int32), np.asarray(db, np.int32),
                           np.asarray(mt, np.int32))


def _histograms(rng, info, CH, B, scale):
    """[CH, G, B, 3] group histograms of CH leaves and their totals: every
    row of a leaf lands in exactly one bin of every group."""
    G = info.num_groups
    hist = np.zeros((CH, G, B, 3))
    for c in range(CH):
        n = rng.randint(300, 900)
        grad = rng.randint(-8, 9, n) * scale
        hess = rng.randint(1, 9, n) * scale
        for g in range(G):
            bins = rng.randint(0, int(info.group_num_bins[g]), n)
            # the empty lane of a non-zero default never holds a row
            for f in info.groups[g]:
                if info.needs_fix[f] and info.feature_default[f] != 0:
                    hole = info.feature_default[f] + info.feature_shift[f]
                    bins[bins == hole] = 0
            np.add.at(hist[c, g, :, 0], bins, grad)
            np.add.at(hist[c, g, :, 1], bins, hess)
            np.add.at(hist[c, g, :, 2], bins, 1.0)
    tot = hist[:, 0].sum(axis=1)                        # [CH, 3]
    return hist.astype(np.float32), tot.astype(np.float32)


def _both(name, CH, scale, seed, mask=None, monotone=None, penalty=None,
          cegb=None, params=SplitParams(min_data_in_leaf=5)):
    info, maps, B, (nb, db, mt) = _maps(name)
    rng = np.random.RandomState(seed)
    hist, tot = _histograms(rng, info, CH, B, scale)
    hist, tot = jnp.asarray(hist), jnp.asarray(tot)
    kw = dict(monotone=None if monotone is None else jnp.asarray(monotone),
              penalty=None if penalty is None else jnp.asarray(penalty),
              feature_mask=None if mask is None else jnp.asarray(mask),
              cegb_feature_penalty=None if cegb is None
              else jnp.asarray(cegb))
    mn = mx = None
    if monotone is not None:
        mn = jnp.full((CH,), -jnp.inf, jnp.float32)
        mx = jnp.full((CH,), jnp.inf, jnp.float32)
    fvec = sp.build_feature_statics(jnp.asarray(nb), jnp.asarray(db),
                                    jnp.asarray(mt), children=CH, **kw)
    unbundled = jnp.stack([
        grow_ops.unbundle_hist(hist[c], tot[c, 0], tot[c, 1], tot[c, 2],
                               maps, jnp.asarray(db)) for c in range(CH)])
    want = sp.best_split_rows_pallas(
        unbundled, tot[:, 0], tot[:, 1], tot[:, 2], fvec, params,
        min_constraints=mn, max_constraints=mx, interpret=True)
    planes = sp.group_lane_planes(maps.scan_lanes, **kw)
    got = sp.best_split_rows_group(
        hist, tot[:, 0], tot[:, 1], tot[:, 2], maps.scan_lanes, planes,
        params, min_constraints=mn, max_constraints=mx, interpret=True)
    return np.asarray(want), np.asarray(got)


def _assert_same_rows(want, got):
    assert got.shape == want.shape
    for w, g in zip(want, got):
        assert (w[sp._OG] > sp.NEG_GATE) == (g[sp._OG] > sp.NEG_GATE)
        if w[sp._OG] <= sp.NEG_GATE:
            assert g[sp._OF] == -1
            continue
        # feature, the feature's bin, default direction
        np.testing.assert_array_equal(g[[sp._OF, sp._OT, sp._ODL]],
                                      w[[sp._OF, sp._OT, sp._ODL]])
        np.testing.assert_allclose(g[sp._OG], w[sp._OG], rtol=1e-6)
        # the three left sums, the three right sums, both outputs
        np.testing.assert_allclose(g[sp._OLG:sp._ORO + 1],
                                   w[sp._OLG:sp._ORO + 1], rtol=1e-6,
                                   atol=1e-6)


@pytest.mark.parametrize("scale", [1.0, 1.0 / 64],
                         ids=["int8_codes", "float32"])
@pytest.mark.parametrize("CH", [1, 2])
@pytest.mark.parametrize("name", sorted(LAYOUTS))
def test_group_scan_equals_unbundle_then_feature_scan(name, CH, scale):
    for seed in range(2):
        want, got = _both(name, CH, scale, seed)
        assert (want[:, sp._OG] > sp.NEG_GATE).any(), "test setup: no split"
        _assert_same_rows(want, got)


@pytest.mark.parametrize("name", ["one_hot", "hole", "missing"])
def test_group_scan_masks_penalties_and_monotone(name):
    F = len(LAYOUTS[name][1])
    rng = np.random.RandomState(11)
    for seed in range(2):
        # the winner of the plain scan is masked out next
        want, _ = _both(name, 2, 1.0, seed)
        mask = np.ones(F, bool)
        mask[want[:, sp._OF].astype(int)] = False
        _assert_same_rows(*_both(name, 2, 1.0, seed, mask=mask))
        _assert_same_rows(*_both(
            name, 2, 1.0, seed, monotone=rng.randint(-1, 2, F),
            penalty=rng.rand(F).astype(np.float32) + 0.5))
        # CEGB: a price per split and per row, and one per unused feature
        _assert_same_rows(*_both(
            name, 2, 1.0, seed, cegb=rng.randint(0, 4, F) / 4.0,
            params=SplitParams(min_data_in_leaf=5,
                               cegb_split_penalty=1.0 / 1024)))


def test_group_scan_in_blocks_of_rows(monkeypatch):
    """A bundled set too wide for one step runs as a grid over blocks of
    group rows; the fold keeps the lowest feature among equal gains even
    where its group lies in a later block."""
    groups = [[2 * g + 1, 2 * g] for g in range(20)]     # 20 rows, 3 blocks
    LAYOUTS["wide"] = (groups, [4] * 40, [0] * 40, [NONE] * 40)
    try:
        whole = _both("wide", 2, 1.0, 0)
        row_bytes = sp._GROUP_SCAN_ARRAYS * 4 * 128
        monkeypatch.setattr(sp, "_SCAN_VMEM", 8 * row_bytes)
        assert sp._scan_block(2, 24, 128, sp._GROUP_SCAN_ARRAYS) == (8, 3)
        sp._run_scan.clear_cache()       # the block plan is read at trace
        blocked = _both("wide", 2, 1.0, 0)
    finally:
        del LAYOUTS["wide"]
        sp._run_scan.clear_cache()
    _assert_same_rows(*whole)
    np.testing.assert_array_equal(blocked[1], whole[1])


@pytest.mark.parametrize("G, B, plan", [
    (40, 256, (40, 0)),         # Allstate's 37 groups: one step, no grid
    (600, 256, (200, 3)),
    (1000, 256, (256, 4)),      # 512 rows a block needed 22 MB of 16
    (2000, 128, (504, 4)),      # Epsilon's shape, every feature a group
])
def test_group_scan_blocks_fit_the_kernels_vmem(G, B, plan):
    """The group-space scan holds twice the feature-space scan's arrays a
    row, so its blocks are half as tall: 1 000 rows of 128 lanes did not
    compile for a v5e (22.33 MB of scoped VMEM against 16)."""
    assert sp._scan_block(2, G, B, sp._GROUP_SCAN_ARRAYS) == plan
    rows = plan[0] if plan[1] else 2 * G
    assert rows * B * 4 * sp._GROUP_SCAN_ARRAYS <= sp._SCAN_VMEM + (1 << 20)


def test_no_split_is_the_sentinel_row():
    want, got = _both("one_hot", 2, 1.0, 0,
                      params=SplitParams(min_data_in_leaf=100000))
    assert (want[:, sp._OG] <= sp.NEG_GATE).all()
    _assert_same_rows(want, got)


# ---- end to end on the partition engine -------------------------------
def _one_hot_set(rows=4096, seed=5):
    """A one-hot CSR set from the benchmark's generator, small enough for
    interpret mode: five variables, one of them wider than a group."""
    from benchmarks.data import allstate
    args = {"feature_seed": 3, "label_seed": 3, "zipf_small": 1.0,
            "zipf_large": 1.4, "intercept": -1.0,
            "cardinalities": [6, 8, 300, 10, 5]}
    X = allstate.features(args, "train", rows)
    y, _ = allstate.labels(args, seed, "train", X)
    return X, y


def _grow(X, y, bundle, quantized, trees=3):
    import lightgbm_tpu as lgb
    params = {"objective": "binary", "num_leaves": 7, "verbose": -1,
              "min_data_in_leaf": 20, "enable_bundle": bundle,
              "tpu_tree_engine": "partition", "tpu_quantized_grad": quantized}
    ds = lgb.Dataset(X, y, params=params)
    booster = lgb.Booster(params, ds)
    for _ in range(trees):
        booster.update()
    gbdt = booster._gbdt
    gbdt._sync_model()
    assert gbdt._use_partition_engine and gbdt._fused_validated
    assert gbdt._carried_active and bool(gbdt._quantized) == quantized
    assert (ds._binned.bundle is not None) == bundle
    plan = gbdt._engine_plan
    assert plan["scan_space"] == ("group" if bundle else "feature")
    assert plan["groups"] == ds._binned.bins.shape[1]
    assert plan["features"] == ds._binned.num_features
    if bundle:
        # no feature-space map is built for this path, and none is read
        assert gbdt.train_state.bundle.unbundle_idx is None
        assert gbdt.train_state.bundle.scan_lanes is not None
    return gbdt.models


@pytest.mark.parametrize("quantized", [False, True], ids=["float32", "int8"])
def test_bundled_one_hot_set_grows_the_unbundled_trees(quantized):
    X, y = _one_hot_set()
    bundled = _grow(X, y, True, quantized)
    plain = _grow(X, y, False, quantized)
    assert len(bundled) == len(plain) == 3
    for a, b in zip(bundled, plain):
        assert a.num_leaves == b.num_leaves == 7
        n = a.num_leaves - 1
        for field in ("split_feature_inner", "threshold_in_bin",
                      "left_child", "right_child"):
            np.testing.assert_array_equal(getattr(a, field)[:n],
                                          getattr(b, field)[:n], field)
        np.testing.assert_array_equal(a.leaf_count[:n + 1],
                                      b.leaf_count[:n + 1])
        np.testing.assert_allclose(a.leaf_value[:n + 1], b.leaf_value[:n + 1],
                                   rtol=2e-5, atol=1e-7)
