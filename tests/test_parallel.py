"""Distributed learner tests on the virtual 8-device CPU mesh.

The single-process multi-rank testing the reference lacks (SURVEY §4.5):
each tree_learner mode must reproduce the serial learner's trees exactly —
the collectives change where stats are computed, not their values.
"""
import numpy as np
import pytest

# interpret-mode Pallas dominates these — excluded from the
# fast tier (pytest -m 'not slow'); run the full suite before
# committing engine changes
pytestmark = pytest.mark.slow

import lightgbm_tpu as lgb
from lightgbm_tpu.ops import grow as grow_ops
from lightgbm_tpu.ops.split import SplitParams
from lightgbm_tpu.parallel.learners import ParallelGrower

MODES = ["data", "feature", "voting"]


def _toy(rng, n=600, F=10, B=24):
    import jax.numpy as jnp
    bins = jnp.asarray(rng.randint(0, B, (n, F)), jnp.uint8)
    grad = jnp.asarray(rng.randn(n), jnp.float32)
    hess = jnp.asarray(np.abs(rng.randn(n)) + 0.1, jnp.float32)
    meta = dict(
        row0=jnp.zeros(n, jnp.int32), fm=jnp.ones(F, bool),
        nb=jnp.full(F, B, jnp.int32), db=jnp.zeros(F, jnp.int32),
        mt=jnp.zeros(F, jnp.int32))
    return bins, grad, hess, meta


@pytest.mark.parametrize("mode", MODES)
def test_grower_matches_serial(rng, mode):
    bins, grad, hess, m = _toy(rng)
    params = SplitParams(min_data_in_leaf=5)
    kw = dict(max_leaves=31, max_depth=-1, max_bin=24, hist_impl="scatter")
    args = (bins, grad, hess, m["row0"], m["fm"], m["nb"], m["db"], m["mt"],
            params, None, None)
    ts, ls = grow_ops.grow_tree(*args, **kw)
    tp, lp = ParallelGrower(mode, 8, top_k=5)(*args, **kw)
    assert int(ts.num_leaves) == int(tp.num_leaves)
    np.testing.assert_array_equal(np.asarray(ts.split_feature),
                                  np.asarray(tp.split_feature))
    np.testing.assert_array_equal(np.asarray(ts.threshold_bin),
                                  np.asarray(tp.threshold_bin))
    np.testing.assert_array_equal(np.asarray(ls), np.asarray(lp))
    # f32 accumulation-order noise only (the GPU-vs-CPU parity band,
    # docs/GPU-Performance.rst:132-134)
    np.testing.assert_allclose(np.asarray(ts.leaf_value),
                               np.asarray(tp.leaf_value),
                               rtol=1e-3, atol=1e-5)


@pytest.mark.parametrize("mode", MODES)
def test_uneven_rows_and_features(rng, mode):
    # shapes not divisible by the 8-device mesh exercise the pad paths
    bins, grad, hess, m = _toy(rng, n=451, F=11)
    params = SplitParams(min_data_in_leaf=3)
    kw = dict(max_leaves=15, max_depth=-1, max_bin=24, hist_impl="scatter")
    args = (bins, grad, hess, m["row0"], m["fm"], m["nb"], m["db"], m["mt"],
            params, None, None)
    ts, ls = grow_ops.grow_tree(*args, **kw)
    tp, lp = ParallelGrower(mode, 8, top_k=4)(*args, **kw)
    np.testing.assert_array_equal(np.asarray(ts.split_feature),
                                  np.asarray(tp.split_feature))
    np.testing.assert_array_equal(np.asarray(ls), np.asarray(lp))


@pytest.mark.parametrize("mode", MODES)
def test_end_to_end_parallel_training(rng, mode):
    n = 500
    X = rng.randn(n, 8)
    y = (X[:, 0] + 0.5 * X[:, 1] ** 2 + 0.1 * rng.randn(n) > 0.3).astype(float)
    params = {"objective": "binary", "metric": "binary_logloss",
              "num_leaves": 15, "learning_rate": 0.1, "verbose": -1,
              "min_data_in_leaf": 5, "num_machines": 8}
    serial = lgb.train(dict(params, tree_learner="serial"),
                       lgb.Dataset(X, y), num_boost_round=10)
    par = lgb.train(dict(params, tree_learner=mode),
                    lgb.Dataset(X, y), num_boost_round=10)
    ps, pp = serial.predict(X), par.predict(X)
    # accumulation-order noise near gain ties can flip individual splits
    # over many iterations (the reference's CPU-vs-GPU parity has the same
    # property, docs/GPU-Performance.rst:132-162) — assert quality parity
    assert np.mean((ps > 0.5) == y) > 0.85
    assert np.mean((pp > 0.5) == y) > 0.85
    assert np.mean(np.abs(ps - pp)) < 0.02


def test_voting_differs_only_in_election(rng):
    # with top_k >= F the vote elects every feature → exact serial equality
    bins, grad, hess, m = _toy(rng, F=6)
    params = SplitParams(min_data_in_leaf=5)
    kw = dict(max_leaves=31, max_depth=-1, max_bin=24, hist_impl="scatter")
    args = (bins, grad, hess, m["row0"], m["fm"], m["nb"], m["db"], m["mt"],
            params, None, None)
    ts, _ = grow_ops.grow_tree(*args, **kw)
    tp, _ = ParallelGrower("voting", 8, top_k=6)(*args, **kw)
    np.testing.assert_array_equal(np.asarray(ts.split_feature),
                                  np.asarray(tp.split_feature))


def _partition_serial_tree(rng, n=1024, F=8, B=24):
    import jax.numpy as jnp

    from lightgbm_tpu.ops import grow_partition as gp
    from lightgbm_tpu.ops import partition_pallas as pp_mod

    bins = rng.randint(0, B, (n, F)).astype(np.float32)
    # dyadic-rational grad/hess: every partial sum is EXACT in f32
    # under any association, so serial / sharded / psum'd histograms are
    # bit-identical and exact tree equality is a valid oracle (real
    # workloads only get the GPU-parity band, docs/GPU-Performance.rst)
    grad = (rng.randint(-64, 65, n) / 64.0).astype(np.float32)
    hess = (rng.randint(1, 9, n) / 8.0).astype(np.float32)
    meta = dict(row0=jnp.zeros(n, jnp.int32), fm=jnp.ones(F, bool),
                nb=jnp.full(F, B, jnp.int32), db=jnp.zeros(F, jnp.int32),
                mt=jnp.zeros(F, jnp.int32))
    params = SplitParams(min_data_in_leaf=5)
    statics = dict(max_leaves=15, max_bin=B, emit="leaf_ids",
                   full_bag=True, interpret=True)
    C, cap = pp_mod.arena_geometry(n, F)
    arena = jnp.zeros((C, cap), pp_mod.ARENA_DT)
    ts, ls, _, _ = gp.grow_tree_partition(
        arena, jnp.asarray(bins.T, pp_mod.ARENA_DT), jnp.asarray(grad),
        jnp.asarray(hess), meta["row0"], meta["fm"], meta["nb"],
        meta["db"], meta["mt"], params, **statics)
    return bins, grad, hess, meta, params, statics, ts, ls


def _assert_trees_equal(ts, ls, tp, lp):
    assert int(ts.num_leaves) == int(tp.num_leaves)
    np.testing.assert_array_equal(np.asarray(ts.split_feature),
                                  np.asarray(tp.split_feature))
    np.testing.assert_array_equal(np.asarray(ts.threshold_bin),
                                  np.asarray(tp.threshold_bin))
    np.testing.assert_array_equal(np.asarray(ls), np.asarray(lp))
    np.testing.assert_allclose(np.asarray(ts.leaf_value),
                               np.asarray(tp.leaf_value),
                               rtol=1e-3, atol=1e-5)


def test_partition_engine_feature_parallel(rng):
    """Feature-parallel on the partition engine: data replicated, the
    best-split search sharded by features, winner all_gathered — must
    reproduce the serial partition trees exactly."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from lightgbm_tpu.ops import grow_partition as gp
    from lightgbm_tpu.ops import partition_pallas as pp_mod
    from lightgbm_tpu.parallel.collective import AXIS, shard_mapped

    (bins, grad, hess, m, params, statics,
     ts, ls) = _partition_serial_tree(rng)
    n, F = bins.shape
    d = 8
    C, cap = pp_mod.arena_geometry(n, F)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:d]), (AXIS,))

    def shard_fn(bins_t, g, h, r0):
        arena_l = jnp.zeros((C, cap), pp_mod.ARENA_DT)
        t, l, _, _ = gp.grow_tree_partition_impl(
            arena_l, bins_t, g, h, r0, m["fm"], m["nb"], m["db"], m["mt"],
            params, axis_name=AXIS, learner="feature", num_machines=d,
            **statics)
        return t, l

    fn = jax.jit(shard_mapped(
        shard_fn, mesh, (P(), P(), P(), P()), (P(), P())))
    tp, lp = fn(jnp.asarray(bins.T, pp_mod.ARENA_DT), jnp.asarray(grad),
                jnp.asarray(hess), m["row0"])
    _assert_trees_equal(ts, ls, tp, lp)


@pytest.mark.parametrize("top_k", [8, 3])
def test_partition_engine_voting_parallel(rng, top_k):
    """Voting-parallel on the partition engine: rows sharded, local
    histograms, per-leaf top-k election, psum of elected features only.
    With top_k >= F every feature is elected -> exact serial equality;
    with a small top_k the election is still a valid PV-tree (structure
    may legitimately differ near vote boundaries) — assert validity."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from lightgbm_tpu.ops import grow_partition as gp
    from lightgbm_tpu.ops import partition_pallas as pp_mod
    from lightgbm_tpu.parallel.collective import AXIS, shard_mapped

    (bins, grad, hess, m, params, statics,
     ts, ls) = _partition_serial_tree(rng)
    n, F = bins.shape
    d = 8
    n_loc = n // d
    C2, cap_loc = pp_mod.arena_geometry(n_loc, F)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:d]), (AXIS,))

    def shard_fn(bins_t, g, h, r0):
        arena_l = jnp.zeros((C2, cap_loc), pp_mod.ARENA_DT)
        t, l, _, _ = gp.grow_tree_partition_impl(
            arena_l, bins_t, g, h, r0, m["fm"], m["nb"], m["db"], m["mt"],
            params, axis_name=AXIS, learner="voting", num_machines=d,
            top_k=top_k, **statics)
        return t, l

    fn = jax.jit(shard_mapped(
        shard_fn, mesh,
        (P(None, AXIS), P(AXIS), P(AXIS), P(AXIS)),
        (P(), P(AXIS))))
    tp, lp = fn(jnp.asarray(bins.T, pp_mod.ARENA_DT), jnp.asarray(grad),
                jnp.asarray(hess), m["row0"])
    if top_k >= F:
        _assert_trees_equal(ts, ls, tp, lp)
    else:
        # elected-subset growth: a full tree over valid leaf ids whose
        # per-leaf counts match the partition
        assert int(tp.num_leaves) == int(ts.num_leaves)
        lp_np = np.asarray(lp)
        counts = np.bincount(lp_np, minlength=int(tp.num_leaves))
        np.testing.assert_array_equal(
            counts[:int(tp.num_leaves)],
            np.asarray(tp.leaf_count)[:int(tp.num_leaves)])


@pytest.mark.parametrize("mode", MODES)
def test_end_to_end_partition_parallel(rng, mode):
    """lgb.train with tpu_tree_engine=partition routes the distributed
    growers through ParallelGrower's shard_map'd partition path (no
    silent label fallback) and matches serial predictions."""
    n = 500
    X = rng.randn(n, 8)
    y = (X[:, 0] + 0.5 * X[:, 1] ** 2 + 0.1 * rng.randn(n) > 0.3).astype(float)
    params = {"objective": "binary", "metric": "binary_logloss",
              "num_leaves": 15, "learning_rate": 0.1, "verbose": -1,
              "min_data_in_leaf": 5, "num_machines": 8,
              "tpu_tree_engine": "partition"}
    serial = lgb.train(dict(params, tree_learner="serial"),
                       lgb.Dataset(X, y), num_boost_round=10)
    par = lgb.train(dict(params, tree_learner=mode),
                    lgb.Dataset(X, y), num_boost_round=10)
    g = par._gbdt._grower
    assert g is not None and g._partition is not None, \
        "partition engine silently fell back under %s" % mode
    ps, pp = serial.predict(X), par.predict(X)
    assert np.mean((pp > 0.5) == y) > 0.85
    assert np.mean(np.abs(ps - pp)) < 0.02


def test_parallel_grower_arena_uses_configured_factor(rng):
    """The shard-local arenas are sized from tpu_arena_factor like the
    serial arena, not from arena_geometry's minimum of 3 — at 3 the
    row-sharded overflow bound (the local PARENT size) stops a shard of
    millions of rows at 3 leaves, which the 16-tile tail hides at test
    sizes (four-chip run, PR 21)."""
    from lightgbm_tpu.ops import partition_pallas as pp_mod
    n, d = 512, 4
    X = rng.randn(n, 6)
    y = (X[:, 0] > 0).astype(float)
    par = lgb.train({"objective": "binary", "num_leaves": 7, "verbose": -1,
                     "min_data_in_leaf": 5, "tree_learner": "data",
                     "num_machines": d, "tpu_tree_engine": "partition",
                     "tpu_arena_factor": 8},
                    lgb.Dataset(X, y), num_boost_round=1)
    g = par._gbdt._grower
    assert g._partition is not None
    assert g._arena.shape == (d,) + pp_mod.arena_geometry(n // d, 6, 8)


def test_partition_engine_data_parallel(rng):
    """The partition (arena) engine under shard_map with rows sharded:
    psum'd histograms must reproduce the serial partition trees."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from lightgbm_tpu.ops import grow_partition as gp
    from lightgbm_tpu.ops import partition_pallas as pp_mod
    from lightgbm_tpu.parallel.collective import AXIS, shard_mapped

    n, F, B = 1024, 6, 24
    bins = rng.randint(0, B, (n, F)).astype(np.float32)
    grad = rng.randn(n).astype(np.float32)
    hess = (np.abs(rng.randn(n)) + 0.1).astype(np.float32)
    row0 = jnp.zeros(n, jnp.int32)
    fm = jnp.ones(F, bool)
    nb = jnp.full(F, B, jnp.int32)
    db = jnp.zeros(F, jnp.int32)
    mt = jnp.zeros(F, jnp.int32)
    params = SplitParams(min_data_in_leaf=5)
    statics = dict(max_leaves=15, max_bin=B, emit="leaf_ids",
                   full_bag=True, interpret=True)

    # serial reference
    C, cap = pp_mod.arena_geometry(n, F)
    arena = jnp.zeros((C, cap), pp_mod.ARENA_DT)
    ts, ls, _, _ = gp.grow_tree_partition(
        arena, jnp.asarray(bins.T, pp_mod.ARENA_DT), jnp.asarray(grad),
        jnp.asarray(hess), row0, fm, nb, db, mt, params, **statics)

    # 8-way data parallel: rows sharded, one local arena per device
    d = 8
    n_loc = n // d
    C2, cap_loc = pp_mod.arena_geometry(n_loc, F)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:d]), (AXIS,))

    def shard_fn(bins_t, g, h, r0):
        arena_l = jnp.zeros((C2, cap_loc), pp_mod.ARENA_DT)
        t, l, _, _ = gp.grow_tree_partition_impl(
            arena_l, bins_t, g, h, r0, fm, nb, db, mt, params,
            axis_name=AXIS, **statics)
        return t, l

    fn = jax.jit(shard_mapped(
        shard_fn, mesh,
        (P(None, AXIS), P(AXIS), P(AXIS), P(AXIS)),
        (P(), P(AXIS))))
    tp, lp = fn(jnp.asarray(bins.T, pp_mod.ARENA_DT), jnp.asarray(grad),
                jnp.asarray(hess), row0)

    assert int(ts.num_leaves) == int(tp.num_leaves)
    np.testing.assert_array_equal(np.asarray(ts.split_feature),
                                  np.asarray(tp.split_feature))
    np.testing.assert_array_equal(np.asarray(ts.threshold_bin),
                                  np.asarray(tp.threshold_bin))
    np.testing.assert_array_equal(np.asarray(ls), np.asarray(lp))
    np.testing.assert_allclose(np.asarray(ts.leaf_value),
                               np.asarray(tp.leaf_value),
                               rtol=1e-3, atol=1e-5)


def test_data_parallel_medium_scale_equivalence(rng):
    """DP == serial at real scale: ~120k rows, deep tree, and a
    min_data_in_leaf floor tight enough that many winning leaves sit just
    above it.  Each of the 8 shards holds only ~1/8 of any leaf's rows,
    so the constraint can ONLY be evaluated correctly on global counts
    (parallel_tree_learner.h:62-68); a shard-local count check, or any
    psum_scatter shard-boundary slip, produces a different tree."""
    import jax.numpy as jnp
    n, F, B = 119_731, 12, 64           # n % 8 != 0: pad path exercised
    bins = jnp.asarray(rng.randint(0, B, (n, F)), jnp.uint8)
    # piecewise signal so the grown tree is deep and data-dependent,
    # quantized to dyadic rationals (1/64 units): with |sum| < 2^24
    # units every partial sum is EXACT in f32 under any association, so
    # exact tree equality is a valid oracle even at this row count
    x0 = np.asarray(bins[:, 0], np.float32)
    x1 = np.asarray(bins[:, 1], np.float32)
    raw = np.sin(x0 / 5.0) + 0.3 * (x1 > 40) + 0.05 * rng.randn(n)
    grad = jnp.asarray(np.round((raw - raw.mean()) * 64) / 64, jnp.float32)
    hess = jnp.ones(n, jnp.float32)
    row0 = jnp.zeros(n, jnp.int32)
    fm = jnp.ones(F, bool)
    nb = jnp.full(F, B, jnp.int32)
    db = jnp.zeros(F, jnp.int32)
    mt = jnp.zeros(F, jnp.int32)
    params = SplitParams(min_data_in_leaf=800, min_sum_hessian_in_leaf=1e-3)
    kw = dict(max_leaves=127, max_depth=-1, max_bin=B, hist_impl="auto")
    args = (bins, grad, hess, row0, fm, nb, db, mt, params, None, None)

    ts, ls = grow_ops.grow_tree(*args, **kw)
    tp, lp = ParallelGrower("data", 8)(*args, **kw)

    nl = int(ts.num_leaves)
    assert nl == int(tp.num_leaves)
    assert nl > 60, "tree too shallow to stress the leaf floor (%d)" % nl
    # the floor must actually bind for the test to mean anything
    counts = np.asarray(ts.leaf_count)[:nl]
    assert counts.min() >= 800
    assert (counts < 1600).sum() > 10, counts.min()
    np.testing.assert_array_equal(np.asarray(ts.split_feature),
                                  np.asarray(tp.split_feature))
    np.testing.assert_array_equal(np.asarray(ts.threshold_bin),
                                  np.asarray(tp.threshold_bin))
    np.testing.assert_array_equal(np.asarray(ls), np.asarray(lp))
    np.testing.assert_allclose(np.asarray(ts.leaf_value),
                               np.asarray(tp.leaf_value),
                               rtol=1e-3, atol=1e-5)
