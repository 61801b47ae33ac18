"""Partition (arena) growth engine vs the label engine oracle.

The two engines implement the same leaf-wise algorithm with different row
organizations (ops/grow_partition.py vs ops/grow.py); on identical inputs
they must grow identical trees.  Runs the pallas kernels in interpret mode
on the CPU test platform.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# interpret-mode Pallas dominates the whole-tree tests — they are excluded
# from the fast tier (pytest -m 'not slow'); run the full suite before
# committing engine changes.  The kernel-level tests at the end are fast.
slow = pytest.mark.slow

from lightgbm_tpu.ops import grow as g
from lightgbm_tpu.ops import grow_partition as gp
from lightgbm_tpu.ops import partition_pallas as pp
from lightgbm_tpu.ops.split import SplitParams


def _grow_both(bins, grad, hess, row0, nb, db, mt, params, max_leaves,
               max_bin, max_depth=-1):
    F = bins.shape[1]
    fmask = jnp.ones(F, bool)
    t1, l1 = g.grow_tree(
        jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
        jnp.asarray(row0), fmask, jnp.asarray(nb), jnp.asarray(db),
        jnp.asarray(mt), params, max_leaves=max_leaves, max_bin=max_bin,
        max_depth=max_depth, hist_impl="scatter")
    arena = jnp.zeros((pp.arena_channels(F), 8 * pp.TILE), pp.ARENA_DT)
    t2, l2, _, _ = gp.grow_tree_partition(
        arena, jnp.asarray(bins.T.astype(np.float32)),
        jnp.asarray(grad), jnp.asarray(hess), jnp.asarray(row0), fmask,
        jnp.asarray(nb), jnp.asarray(db), jnp.asarray(mt), params,
        max_leaves=max_leaves, max_bin=max_bin, max_depth=max_depth,
        interpret=True)
    return t1, l1, t2, l2


def _assert_trees_equal(t1, t2):
    for f in t1._fields:
        if f == "default_left":
            # two-direction scan ties break on sub-ulp f32 gain differences
            # between the engines' accumulation orders (the reference's
            # CPU-vs-GPU parity band has the same caveat,
            # docs/GPU-Performance.rst:132-134)
            continue
        a, b = np.asarray(getattr(t1, f)), np.asarray(getattr(t2, f))
        if a.shape != b.shape:
            continue  # cat_mask width differs (partition engine: 0)
        np.testing.assert_allclose(a.astype(np.float64), b.astype(np.float64),
                                   rtol=1e-4, atol=1e-5, err_msg=f)


def _case(rng, n=2500, F=6, B=48):
    bins = rng.randint(0, B, (n, F)).astype(np.uint8)
    grad = rng.randn(n).astype(np.float32)
    hess = (np.abs(rng.randn(n)) + 0.1).astype(np.float32)
    nb = np.full(F, B, np.int32)
    db = np.zeros(F, np.int32)
    mt = np.zeros(F, np.int32)
    return bins, grad, hess, nb, db, mt


@slow
def test_matches_label_engine(rng):
    bins, grad, hess, nb, db, mt = _case(rng)
    row0 = np.zeros(len(grad), np.int32)
    t1, l1, t2, l2 = _grow_both(bins, grad, hess, row0, nb, db, mt,
                                SplitParams(min_data_in_leaf=10), 15, 48)
    assert int(t1.num_leaves) == int(t2.num_leaves) == 15
    _assert_trees_equal(t1, t2)
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))


@slow
def test_matches_with_bagging(rng):
    bins, grad, hess, nb, db, mt = _case(rng)
    row0 = np.zeros(len(grad), np.int32)
    row0[rng.rand(len(grad)) < 0.4] = -1
    t1, l1, t2, l2 = _grow_both(bins, grad, hess, row0, nb, db, mt,
                                SplitParams(min_data_in_leaf=10), 15, 48)
    _assert_trees_equal(t1, t2)
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))


@slow
def test_early_stop_dead_slots(rng):
    """Leaves < max_leaves leaves unused slots whose start=0 must not shadow
    the live segment at position 0 during label recovery."""
    bins, grad, hess, nb, db, mt = _case(rng)
    row0 = np.zeros(len(grad), np.int32)
    t1, l1, t2, l2 = _grow_both(bins, grad, hess, row0, nb, db, mt,
                                SplitParams(min_data_in_leaf=1100), 15, 48)
    assert int(t1.num_leaves) == int(t2.num_leaves) < 15
    _assert_trees_equal(t1, t2)
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))


@slow
def test_missing_handling(rng):
    from lightgbm_tpu.ops.grow import MISSING_NAN, MISSING_ZERO
    bins, grad, hess, nb, db, mt = _case(rng)
    mt[0] = MISSING_NAN
    mt[1] = MISSING_ZERO
    db[1] = 3
    row0 = np.zeros(len(grad), np.int32)
    t1, l1, t2, l2 = _grow_both(bins, grad, hess, row0, nb, db, mt,
                                SplitParams(min_data_in_leaf=10), 15, 48)
    _assert_trees_equal(t1, t2)
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))


@slow
def test_max_depth(rng):
    bins, grad, hess, nb, db, mt = _case(rng)
    row0 = np.zeros(len(grad), np.int32)
    t1, l1, t2, l2 = _grow_both(bins, grad, hess, row0, nb, db, mt,
                                SplitParams(min_data_in_leaf=10), 31, 48,
                                max_depth=3)
    assert int(np.asarray(t2.leaf_depth)[:int(t2.num_leaves)].max()) <= 3
    _assert_trees_equal(t1, t2)


@slow
def test_end_to_end_train_partition_engine(rng):
    """Full driver with tpu_tree_engine=partition (interpret on CPU)."""
    import lightgbm_tpu as lgb

    n, F = 1200, 5
    X = rng.randn(n, F).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] + 0.2 * rng.randn(n) > 0).astype(
        np.float32)
    out = {}
    for eng in ("label", "partition"):
        params = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
                  "learning_rate": 0.2, "min_data_in_leaf": 5, "verbose": -1,
                  "tpu_tree_engine": eng}
        bst = lgb.train(params, lgb.Dataset(X, y), num_boost_round=8)
        out[eng] = bst.predict(X)
    # The engines match up to f32 reassociation noise in their (different)
    # histogram kernels.  This tie-rich config (max_bin=63,
    # min_data_in_leaf=5) plus 8 boosted rounds means a single near-tie
    # split flipped by that noise compounds through the score feedback —
    # pointwise equality is not guaranteed (the reference itself is not
    # bit-deterministic across num_threads).  Assert the guaranteed
    # contract: equal model QUALITY and close typical predictions.
    med = np.median(np.abs(out["label"] - out["partition"]))
    assert med < 0.01, med

    def logloss(p):
        p = np.clip(p, 1e-7, 1 - 1e-7)
        return -np.mean(y * np.log(p) + (1 - y) * np.log(1 - p))

    ll_l, ll_p = logloss(out["label"]), logloss(out["partition"])
    assert abs(ll_l - ll_p) < 0.05 * max(ll_l, ll_p) + 1e-4, (ll_l, ll_p)
    for eng in out:
        acc = ((out[eng] > 0.5) == y).mean()
        assert acc > 0.85, (eng, acc)


@slow
def test_partition_kernel_stability(rng):
    """Sequence of in-place partitions preserves payloads exactly."""
    F = 4
    C = pp.arena_channels(F)
    Fp = pp.feature_channels(F)
    cap = 8 * pp.TILE
    n = 3000
    arena = np.zeros((C, cap), np.float32)
    arena[:F, :n] = rng.randint(0, 200, (F, n))
    g3 = pp.split_f32(jnp.asarray(rng.randn(n), jnp.float32))
    h3 = pp.split_f32(jnp.asarray(np.abs(rng.randn(n)) + 0.1, jnp.float32))
    r3 = pp.split_rowid(jnp.arange(n))
    for i, plane in enumerate(list(g3) + list(h3) + list(r3)):
        arena[Fp + i, :n] = np.asarray(plane.astype(jnp.float32))
    A = jnp.asarray(arena, pp.ARENA_DT)
    ref = arena[:, :n]
    s, cnt, cursor = 0, n, 4096
    for step in range(3):
        goA = ref[step % F] > 80
        if goA.sum() * 2 < cnt:
            goA = ~goA
        pred = np.zeros((1, cap), np.float32)
        pred[0, s:s + cnt] = goA
        A, counts = pp.partition_segment(A, jnp.asarray(pred), s, cnt,
                                         s, cursor, interpret=True)
        nA, nB = int(goA.sum()), int((~goA).sum())
        assert list(np.asarray(counts)) == [nA, nB]
        got = np.asarray(A.astype(jnp.float32))
        np.testing.assert_array_equal(got[:, s:s + nA], ref[:, goA])
        np.testing.assert_array_equal(got[:, cursor:cursor + nB],
                                      ref[:, ~goA])
        ref = ref[:, goA]
        cnt = nA
        cursor += ((nB + pp.FLUSH_W - 1) // pp.FLUSH_W) * pp.FLUSH_W


@slow
def test_deferred_stop_matches_eager(rng):
    """The deferred-tree pipeline must stop training on degenerate
    iterations exactly like the eager path (same model length and
    predictions)."""
    import lightgbm_tpu as lgb

    n, F = 400, 4
    X = rng.randn(n, F).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    preds = {}
    for eng in ("label", "partition"):
        params = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
                  # min_data so large that no split is ever possible
                  "min_data_in_leaf": n, "verbose": -1,
                  "tpu_tree_engine": eng}
        bst = lgb.train(params, lgb.Dataset(X, y), num_boost_round=10)
        preds[eng] = bst.predict(X)
        assert bst.num_trees() <= 1
    np.testing.assert_allclose(preds["label"], preds["partition"], rtol=1e-6)


def _model_structure(bst):
    """(feature, threshold, count, kind) tuples in DFS order — the
    float-noise-free skeleton both engines must agree on."""
    out = []

    def walk(nd):
        if "leaf_value" in nd:
            out.append(("leaf", nd["leaf_count"]))
        else:
            out.append((nd["split_feature"], str(nd.get("threshold")),
                        nd["internal_count"], nd["decision_type"]))
            walk(nd["left_child"])
            walk(nd["right_child"])

    for t in bst.dump_model()["tree_info"]:
        walk(t["tree_structure"])
    return out


def _train_both(X, y, extra=None, rounds=3, **ds_kw):
    import lightgbm_tpu as lgb
    outs = {}
    for eng in ("partition", "label"):
        ds = lgb.Dataset(X, label=y, **ds_kw)
        p = {"objective": "binary", "num_leaves": 8, "verbose": -1,
             "min_data_in_leaf": 20, "tpu_tree_engine": eng}
        p.update(extra or {})
        bst = lgb.train(p, ds, num_boost_round=rounds)
        assert (bst._gbdt._use_partition_engine == (eng == "partition")), eng
        outs[eng] = _model_structure(bst)
    return outs


@slow
def test_categorical_parity():
    """Partition engine handles categorical (bitset) splits via the
    go-left mask decision; trees must match the label engine."""
    rng = np.random.RandomState(3)
    n = 3000
    Xn = rng.randn(n, 4).astype(np.float32)
    cat = rng.randint(0, 12, n)
    # noisy target: pure leaves would leave only ~0-gain tie splits,
    # which the engines break differently (both validly)
    flip = rng.rand(n) < 0.2
    y = (((Xn[:, 0] > 0).astype(int) ^ (cat % 3 == 1) ^ flip)
         .astype(np.float32))
    X = np.column_stack([Xn, cat.astype(np.float32)])
    outs = _train_both(X, y, categorical_feature=[4])
    assert any(k[3] == "==" for k in outs["label"] if len(k) == 4), \
        "test setup: no categorical split chosen"
    assert outs["partition"] == outs["label"]


@slow
def test_efb_bundle_parity():
    """EFB-bundled datasets run on the partition engine through the
    bundle-aware mask build + unbundled scans."""
    rng = np.random.RandomState(5)
    n = 4000
    dense = rng.randn(n, 3).astype(np.float32)
    # mutually exclusive one-hot-ish columns -> EFB bundles them
    group = rng.randint(0, 4, n)
    onehots = np.zeros((n, 4), np.float32)
    # constant nonzero value: keeps each column at 2 bins so the bundle
    # stays under the 256-bins-per-group cap
    onehots[np.arange(n), group] = 1.0
    X = np.column_stack([dense, onehots])
    # noisy target — pure leaves would leave only ~0-gain tie splits,
    # which the engines break differently (both validly)
    flip = rng.rand(n) < 0.2
    y = ((((dense[:, 0] + (group == 2)) > 0.5) ^ flip).astype(np.float32))
    import lightgbm_tpu as lgb
    ds = lgb.Dataset(X, label=y)
    ds.construct()
    assert ds._binned.bundle is not None, "test setup: EFB did not bundle"
    outs = _train_both(X, y)
    assert outs["partition"] == outs["label"]


@slow
def test_hist_pool_spill_matches_dense(rng):
    """A tiny slot cache (spill + recompute on every other split) must
    grow exactly the tree the unlimited cache grows."""
    bins, grad, hess, nb, db, mt = _case(rng)
    row0 = np.zeros(len(grad), np.int32)
    params = SplitParams(min_data_in_leaf=10)
    outs = []
    for slots in (0, 4):
        arena = jnp.zeros((pp.arena_channels(6), 8 * pp.TILE), pp.ARENA_DT)
        t, l, _, _ = gp.grow_tree_partition(
            arena, jnp.asarray(bins.T.astype(np.float32)),
            jnp.asarray(grad), jnp.asarray(hess), jnp.asarray(row0),
            jnp.ones(6, bool), jnp.asarray(nb), jnp.asarray(db),
            jnp.asarray(mt), params, max_leaves=15, max_bin=48,
            hist_slots=slots, interpret=True)
        outs.append((t, l))
    (t0, l0), (t1, l1) = outs
    assert int(t0.num_leaves) == int(t1.num_leaves) == 15
    _assert_trees_equal(t0, t1)
    np.testing.assert_array_equal(np.asarray(l0), np.asarray(l1))


@slow
def test_hist_pool_booster_wide(rng):
    """histogram_pool_size engages the pooled cache at the Booster level
    and training still works."""
    import lightgbm_tpu as lgb
    n, F = 1500, 40
    X = rng.randn(n, F).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    ds = lgb.Dataset(X, label=y)
    p = {"objective": "binary", "num_leaves": 31, "verbose": -1,
         "tpu_tree_engine": "partition",
         # tiny pool: forces slot spills every split
         "histogram_pool_size": 40 * 255 * 3 * 4 * 6 / (1 << 20)}
    bst = lgb.train(p, ds, num_boost_round=3)
    g = bst._gbdt
    assert g._use_partition_engine and 0 < g._hist_slots < 31
    assert bst.num_trees() == 3
    pred = bst.predict(X)
    assert np.mean((pred > 0.5) == y) > 0.9


# --------------------------------------------------------------------- #
# partition_segment against a numpy stable partition (interpret mode)
# --------------------------------------------------------------------- #
_SHARES = ("none", "all", "half", "one_row", "all_but_one", "straddle")
_COUNTS = (0, 1, 255, 256, 257, 2047, 2048, 2049, 3 * 2048 + 5)
_CAP = 16 * pp.TILE
_START, _DST_A, _DST_B = pp.TILE, 6 * pp.TILE, 11 * pp.TILE - 3 * pp.FLUSH_W


def _go_left(share, cnt):
    """Stream-A membership of the segment's rows."""
    i = np.arange(cnt)
    if share == "none":
        return np.zeros(cnt, bool)
    if share == "all":
        return np.ones(cnt, bool)
    if share == "half":
        return np.random.RandomState(cnt).rand(cnt) < 0.5
    if share == "one_row":
        return i == cnt // 2
    if share == "all_but_one":
        return i != cnt // 3
    if share == "alternate":
        return i % 2 == 0
    # every sub-block gives one stream SUB - 1 rows: from the second on,
    # each of its appends crosses a FLUSH_W boundary of the carry
    return i % pp.SUB != 77


_BASE = {}


def _base_arena(C):
    if C not in _BASE:
        _BASE[C] = np.random.RandomState(C).randint(
            0, 250, (C, _CAP)).astype(np.float32)
    return _BASE[C].copy()


def _aligned(n):
    return -(-n // pp.FLUSH_W) * pp.FLUSH_W


# destinations past a segment of up to seven tiles, in an arena eight tiles
# longer (the pipelined loop's cases)
_FAR_A, _FAR_B = 9 * pp.TILE, 17 * pp.TILE - 3 * pp.FLUSH_W


def _run_partition(F, cnt, share, in_place, mode, hist_stream=None,
                   max_bin=0, xr=None, far=False):
    """Run the kernel on a random arena; check streams, order, counts and
    that no column outside align(count, FLUSH_W) of each dst changed.
    Returns (before, go_to_A, outputs)."""
    C = pp.arena_channels(F)
    arena = _base_arena(C)
    dst_a, dst_b = (_FAR_A, _FAR_B) if far else (_DST_A, _DST_B)
    if far:
        arena = np.concatenate([arena, arena[:, ::-1][:, :8 * pp.TILE]], 1)
    cap = arena.shape[1]
    go = _go_left(share, cnt)
    dstA = _START if in_place else dst_a
    kw = {}
    if mode == 0:
        pred = np.zeros((1, cap), np.float32)
        pred[0, _START:_START + cnt] = go
        to_A = go
    else:
        # bin value < 100 goes left; xr = 1 sends the left rows to B
        xr = int(not in_place) if xr is None else xr
        arena[0, _START:_START + cnt] = np.where(go, 10, 200)
        pred = np.zeros((1, pp.TILE), np.float32)
        kw["decision"] = (0, jnp.asarray(np.arange(256) < 100, jnp.float32),
                          xr)
        to_A = ~go if xr else go
    if hist_stream is not None:
        kw.update(hist_stream=hist_stream, num_features=F, max_bin=max_bin)
    out = pp.partition_segment(jnp.asarray(arena, pp.ARENA_DT),
                               jnp.asarray(pred), _START, cnt, dstA, dst_b,
                               interpret=True, **kw)
    got = np.asarray(out[0].astype(jnp.float32))
    seg = arena[:, _START:_START + cnt]
    nA, nB = int(to_A.sum()), int((~to_A).sum())
    assert list(np.asarray(out[1])) == [nA, nB]
    np.testing.assert_array_equal(got[:, dstA:dstA + nA], seg[:, to_A])
    np.testing.assert_array_equal(got[:, dst_b:dst_b + nB], seg[:, ~to_A])
    untouched = np.ones(cap, bool)
    untouched[dstA:dstA + _aligned(nA)] = False
    untouched[dst_b:dst_b + _aligned(nB)] = False
    np.testing.assert_array_equal(got[:, untouched], arena[:, untouched])
    return seg, to_A, out


@pytest.mark.parametrize("mode", [0, 1])
@pytest.mark.parametrize("in_place", [True, False])
@pytest.mark.parametrize("share", _SHARES)
@pytest.mark.parametrize("cnt", _COUNTS)
@pytest.mark.parametrize("F", [28, 137])        # C = 48 (higgs), 160 (MSLR)
def test_partition_segment_is_stable_partition(F, cnt, share, in_place,
                                               mode):
    _run_partition(F, cnt, share, in_place, mode)


@pytest.mark.parametrize("mode", [0, 1])
@pytest.mark.parametrize("cnt", [257, 3 * 2048 + 5])
@pytest.mark.parametrize("hist_stream", [0, 1])
def test_partition_segment_fused_histogram(hist_stream, cnt, mode):
    """The hist_stream variant partitions as the plain kernel does and
    returns the chosen stream's [F, max_bin, 3] histogram."""
    _fused_histogram_case(28, cnt, mode, hist_stream)


def _fused_histogram_case(F, cnt, mode, hist_stream, **kw):
    B = 255
    Fp = pp.feature_channels(F)
    seg, to_A, out = _run_partition(F, cnt, "half", True, mode,
                                    hist_stream=hist_stream, max_bin=B, **kw)
    rows = seg[:, ~to_A if hist_stream else to_A]
    want = np.zeros((F, B, 3))
    g = rows[Fp:Fp + 3].sum(0)
    h = rows[Fp + 3:Fp + 6].sum(0)
    for f in range(F):
        b = rows[f].astype(int)
        np.add.at(want[f, :, 0], b, g)
        np.add.at(want[f, :, 1], b, h)
        np.add.at(want[f, :, 2], b, 1.0)
    np.testing.assert_array_equal(np.asarray(out[2], np.float64), want)


# --------------------------------------------------------------------- #
# the one-block loop's pipeline (PR 37): tile j + 1's predicate part is made
# in iteration j from a three-deep read ring, so a call's first tile (the
# prologue), its last (the part made past it is never used) and the ring's
# wrap are each a case: 0, 1, 2, 3, 4 and 7 tiles
# --------------------------------------------------------------------- #
_T = pp.TILE
# whole tiles and one row over; 1 534 and 4 606 rows split evenly leave each
# stream one row short of a FLUSH_W chunk (3 * 256 - 1 and 9 * 256 - 1 rows)
_TILED = (0, _T, 2 * _T, 2 * _T + 1, 3 * _T, 4 * _T, 7 * _T - 3)
_SHORT = (6 * pp.FLUSH_W - 2, 18 * pp.FLUSH_W - 2)


# in place by `pred`, in place by the decision with either `xr`, and stream A
# to a destination of its own
@pytest.mark.parametrize("in_place,mode,xr", [
    (True, 0, None), (True, 1, 0), (True, 1, 1), (False, 1, 1)])
@pytest.mark.parametrize("cnt", _TILED)
@pytest.mark.parametrize("F", [28, 37, 137])        # C = 48, 64, 160
def test_pipelined_loop_over_whole_tiles(F, cnt, in_place, mode, xr):
    _run_partition(F, cnt, "half", in_place, mode, xr=xr, far=True)


@pytest.mark.parametrize("share,cnt", [
    ("none", 3 * _T), ("all", 3 * _T), ("none", 7 * _T - 3),
    ("all", 7 * _T - 3), ("straddle", 4 * _T), ("alternate", _SHORT[0]),
    ("alternate", _SHORT[1])])
@pytest.mark.parametrize("F,mode", [(28, 0), (28, 1), (37, 1), (137, 1)])
def test_pipelined_loop_one_sided_and_chunk_short(F, mode, share, cnt):
    if share == "alternate":
        assert (cnt // 2 + 1) % pp.FLUSH_W == 0
    _run_partition(F, cnt, share, True, mode, far=True)


@pytest.mark.parametrize("hist_stream", [0, 1])
@pytest.mark.parametrize("F,cnt,mode", [
    (28, 0, 1), (28, _T, 0), (28, _T, 1), (28, 2 * _T + 1, 0),
    (28, 2 * _T + 1, 1), (28, 7 * _T - 3, 1), (37, 2 * _T, 1),
    (37, 4 * _T + 9, 0)])
def test_pipelined_loop_fused_histogram(F, cnt, mode, hist_stream):
    """Tile j + 1's rows are summed in iteration j, in tile order; the
    part made past the last tile sums nothing (a ring slot that was never
    read holds no row of the segment)."""
    _fused_histogram_case(F, cnt, mode, hist_stream, far=True)


# --------------------------------------------------------------------- #
# the split decision: every bin value, every kind of mask, every place of
# the split channel in its 16-row group
# --------------------------------------------------------------------- #
_MASKS = ("threshold", "missing_left", "missing_right", "bitset",
          "efb_range")


def _mask_of(kind):
    """A go-left mask over the 256 bin values, as ops/grow_partition.py
    bakes one: a numerical threshold, a threshold with the missing bin
    sent against it (the NaN bin, the last, going left; the zero bin going
    right), a categorical bitset, a feature's range inside an EFB bundle
    (the bins outside it are the feature's default bin)."""
    v = np.arange(256)
    if kind == "threshold":
        return v <= 117
    if kind == "missing_left":
        return (v <= 60) | (v == 254)
    if kind == "missing_right":
        return (v <= 200) & (v != 0)
    if kind == "bitset":
        return np.random.RandomState(5).rand(256) < 0.5
    lo, hi, shift, default = 37, 181, 36, 0
    inside = (v >= lo) & (v < hi)
    return np.where(inside, v - shift, default) <= 70


def _every_bin(n, seed):
    """n >= 256 bin values in which every value 0..255 occurs."""
    rs = np.random.RandomState(seed)
    return rs.permutation(np.concatenate(
        [np.arange(256), rs.randint(0, 256, n - 256)])).astype(np.float32)


@jax.jit
def _decide_alone(group, mask2, sc):
    """pp._decide by itself, on one tile's 16-row group."""
    K = pp.TILE // pp.SUB

    def kernel(sc_ref, group_ref, mask_ref, out_ref):
        out_ref[:] = pp._decide(group_ref[:], sc_ref[0], mask_ref, sc_ref[1],
                                K)
    return pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((K, pp.SUB), jnp.int32),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        interpret=True)(sc, group, mask2)


@pytest.mark.parametrize("row", range(16))
@pytest.mark.parametrize("xr", [0, 1])
@pytest.mark.parametrize("kind", _MASKS)
def test_decide_is_the_mask_at_every_bin_value(kind, xr, row):
    """The decision of a tile against numpy: the split channel on each of
    its group's 16 rows, every bin value 0..255 eight times, each kind of
    mask, both `xr`; the answer in the [K, SUB] subblock layout."""
    group = np.random.RandomState(row).randint(
        0, 256, (16, pp.TILE)).astype(np.float32)
    group[row] = _every_bin(pp.TILE, 16 * xr + row)
    mask = _mask_of(kind)
    got = _decide_alone(jnp.asarray(group, pp.ARENA_DT),
                        pp._decision_operands(0, mask)[0],
                        jnp.asarray([row, xr], jnp.int32))
    want = mask[group[row].astype(int)] ^ bool(xr)
    np.testing.assert_array_equal(
        np.asarray(got), want.reshape(-1, pp.SUB).astype(np.int32))


def _run_decision(F, chan, kind, cnt=2 * pp.TILE + 300):
    """partition_segment by decision on channel `chan`, both `xr`, against
    numpy: counts and both streams."""
    C = pp.arena_channels(F)
    arena = _base_arena(C)
    arena[chan, _START:_START + cnt] = _every_bin(cnt, chan)
    mask = _mask_of(kind)
    seg = arena[:, _START:_START + cnt]
    left = mask[seg[chan].astype(int)]
    for xr in (0, 1):
        out, counts = pp.partition_segment(
            jnp.asarray(arena, pp.ARENA_DT),
            jnp.zeros((1, pp.TILE), jnp.float32), _START, cnt, _START,
            _DST_B, decision=(chan, jnp.asarray(mask, jnp.float32), xr),
            interpret=True)
        to_A = left ^ bool(xr)
        nA = int(to_A.sum())
        got = np.asarray(out.astype(jnp.float32))
        assert list(np.asarray(counts)) == [nA, cnt - nA]
        np.testing.assert_array_equal(got[:, _START:_START + nA],
                                      seg[:, to_A])
        np.testing.assert_array_equal(got[:, _DST_B:_DST_B + cnt - nA],
                                      seg[:, ~to_A])


# the second 16-row group's sixteen places, then the last feature: in the
# last group before the payload planes (F = 137) or beside them (28, 37)
_PLACES = tuple(range(16)) + ("last",)


@pytest.mark.parametrize("place", _PLACES)
@pytest.mark.parametrize("F", [28, 37, 137])     # C = 48, 64, 160
def test_decision_reads_the_channel_wherever_it_sits(F, place):
    _run_decision(F, F - 1 if place == "last" else 16 + place, "bitset")


@pytest.mark.parametrize("kind", _MASKS)
@pytest.mark.parametrize("F", [28, 37, 137])
def test_decision_by_every_kind_of_mask(F, kind):
    _run_decision(F, 5, kind)


# --------------------------------------------------------------------- #
# the permutation operand against a numpy stable partition
# --------------------------------------------------------------------- #
def _streams(case):
    """(stream A, stream B) membership of one tile's rows."""
    n = pp.TILE
    i = np.arange(n)
    rnd = np.random.RandomState(3).rand(n) < 0.4
    if case == "invalid_tail":           # a segment's last tile
        valid = i < 5 * pp.SUB // 2 + 3
        return rnd & valid, ~rnd & valid
    if case == "empty_A":
        return np.zeros(n, bool), np.ones(n, bool)
    if case == "empty_B":
        return np.ones(n, bool), np.zeros(n, bool)
    if case == "empty_both":
        return np.zeros(n, bool), np.zeros(n, bool)
    if case == "single_row_A":
        return i == 777, np.zeros(n, bool)
    if case == "single_row_B":
        return np.zeros(n, bool), i == 2047
    return rnd, ~rnd                     # "mixed"


@pytest.mark.parametrize("case", ["mixed", "invalid_tail", "empty_A",
                                  "empty_B", "empty_both", "single_row_A",
                                  "single_row_B"])
def test_sort_operand_is_a_stable_partition(case):
    """`_sort_pos` and `_sort_P` by themselves: the product of a subblock
    with its operand holds the subblock's A rows in order, then its B
    rows, then zeros; rows of neither stream go nowhere."""
    K, S = pp.TILE // pp.SUB, pp.SUB
    a, b = _streams(case)
    pred2 = jnp.asarray(np.concatenate([a.reshape(K, S), b.reshape(K, S)]),
                        jnp.int32)
    pref2 = jnp.cumsum(pred2, axis=1)
    pos = pp._sort_pos(pref2, pred2, K)
    rows = np.random.RandomState(1).randint(-250, 250, (24, pp.TILE))
    for k in range(K):
        Pt = np.asarray(pp._sort_P(pos, k).astype(jnp.float32))
        assert set(np.unique(Pt)) <= {0.0, 1.0}
        chunk = rows[:, k * S:(k + 1) * S]
        ka, kb = a[k * S:(k + 1) * S], b[k * S:(k + 1) * S]
        want = np.zeros_like(chunk)
        want[:, :ka.sum()] = chunk[:, ka]
        want[:, ka.sum():ka.sum() + kb.sum()] = chunk[:, kb]
        np.testing.assert_array_equal(chunk @ Pt.T, want)


def _numpy_partition_segment(arena, pred, start, cnt, dstA, dstB,
                             decision=None, hist_stream=None, **_):
    """partition_segment's contract on the host: a stable partition of
    columns [start, start+cnt), each stream written as whole zero-padded
    FLUSH_W chunks at its dst."""
    assert hist_stream is None
    by_pred = decision is None
    feat, mask, xr = (0, np.zeros(256), 0) if by_pred else decision

    def host(arena, pred, start, cnt, dstA, dstB, feat, mask, xr):
        out = np.array(arena)
        start, cnt = int(start), int(cnt)
        seg = out[:, start:start + cnt].copy()
        if by_pred:
            to_A = np.asarray(pred)[0, start:start + cnt] > 0.5
        else:
            left = np.asarray(mask)[seg[int(feat)].astype(np.int64)] > 0.5
            to_A = left ^ bool(xr)
        for dst, rows in ((int(dstA), seg[:, to_A]),
                          (int(dstB), seg[:, ~to_A])):
            n = rows.shape[1]
            out[:, dst:dst + _aligned(n)] = 0
            out[:, dst:dst + n] = rows
        return out, np.asarray([to_A.sum(), cnt - to_A.sum()], np.int32)

    return jax.pure_callback(
        host, (jax.ShapeDtypeStruct(arena.shape, arena.dtype),
               jax.ShapeDtypeStruct((2,), jnp.int32)),
        arena, pred, start, cnt, dstA, dstB, feat,
        jnp.asarray(mask, jnp.float32), xr)


@pytest.mark.parametrize("quantized", [False, True])
def test_model_text_equals_numpy_partition_oracle(monkeypatch, quantized):
    """A small training through the kernel (interpret mode) writes the
    model text, byte for byte, that the same training writes with the
    kernel replaced by a numpy stable partition."""
    import lightgbm_tpu as lgb

    rng = np.random.RandomState(7)
    n, F = 5000, 6
    X = rng.randn(n, F).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] + 0.3 * rng.randn(n) > 0
         ).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
              "learning_rate": 0.2, "min_data_in_leaf": 5, "verbose": -1,
              "tpu_tree_engine": "partition",
              "tpu_quantized_grad": quantized}

    def train():
        jax.clear_caches()      # the grower's trace holds the kernel
        bst = lgb.train(params, lgb.Dataset(X, y), num_boost_round=3)
        assert bst._gbdt._use_partition_engine
        assert [t.num_leaves for t in bst._gbdt.models] == [15] * 3
        return bst.model_to_string()

    with_kernel = train()
    calls = []

    def oracle(*args, **kwargs):
        calls.append(1)
        return _numpy_partition_segment(*args, **kwargs)
    monkeypatch.setattr(pp, "partition_segment", oracle)
    assert train() == with_kernel
    assert calls
    monkeypatch.undo()
    jax.clear_caches()
