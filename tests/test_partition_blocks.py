"""The arena kernels in channel and feature blocks (ops/partition_pallas.py,
ops/split_pallas.py): what a data set too wide for one [channels, tile]
slab of VMEM runs.

On the CPU (interpret mode) a block plan is forced at the widths the
narrow cells have (C = 48 and 160), where the one-block kernel is the
oracle: a blocked kernel must write bit-equal arenas, counts, histograms
and split rows.  `partition_segment` is also held to a plain numpy stable
partition.  Then the plan itself, `_setup_tree_engine`'s choice at 2 016
channels, and a small wide training the float64 grower accepts split by
split.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import test_partition_engine as tpe
from lightgbm_tpu.ops import partition_pallas as pp
from lightgbm_tpu.ops import split_pallas as sp
from lightgbm_tpu.ops.split import SplitParams


_FORCED = [None]


def _plan_changes(plan):
    """The kernels' traces hold the plan they were made under: drop them
    where the forced plan changes, and only there (a retrace of an
    interpreted kernel is most of a test's time)."""
    if _FORCED[0] != plan:
        jax.clear_caches()
        _FORCED[0] = plan


@pytest.fixture(autouse=True)
def _own_plan(request):
    """A test that forces no plan runs the plan the shapes give; one that
    does says itself where it wants the unforced kernels first."""
    if "blocks" not in request.fixturenames:
        _plan_changes(None)


@pytest.fixture(scope="module", autouse=True)
def _leave_no_forced_trace():
    yield
    _plan_changes(None)


@pytest.fixture
def blocks(monkeypatch):
    """Force block plans: `blocks(partition=16)` cuts every arena into
    16-channel blocks, `blocks(features=2)` gives the histogram grid two
    radix blocks a step, `blocks(compact=16)`, `blocks(rowid=True)`.
    Consecutive tests under one plan share their traces."""
    def force(partition=None, features=None, compact=None, rowid=False):
        _plan_changes((partition, features, compact, rowid))
        if partition:
            monkeypatch.setattr(pp, "partition_channel_block",
                                lambda C: partition)
        if features:
            monkeypatch.setattr(pp, "_feature_block",
                                lambda n_blocks, f_blk, acc: features)
        if compact:
            monkeypatch.setattr(pp, "compact_channel_block",
                                lambda C: compact)
        if rowid:
            monkeypatch.setattr(
                pp, "_rowid_rows", lambda C, fp: (
                    (fp + 6) // 16 * 16,
                    -(-(fp + pp.N_AUX) // 16) * 16 - (fp + 6) // 16 * 16))
    yield force
    monkeypatch.undo()


# ------------------------------------------------------------------ #
# partition_segment in channel blocks against a numpy stable partition
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("mode", [0, 1])
@pytest.mark.parametrize("in_place", [True, False])
@pytest.mark.parametrize("share", ["none", "all", "half", "straddle"])
@pytest.mark.parametrize("cnt", [0, 257, 2048, 3 * 2048 + 5])
@pytest.mark.parametrize("F,cb", [(28, 16), (137, 80)])  # 3 and 2 blocks
def test_blocked_partition_is_a_stable_partition(blocks, F, cb, cnt, share,
                                                 in_place, mode):
    """Arenas and counts, in place and not, both `xr` (mode 1 sets xr =
    not in_place), empty and full segments, over two and three blocks."""
    blocks(partition=cb)
    assert pp.arena_channels(F) // cb in (2, 3)
    tpe._run_partition(F, cnt, share, in_place, mode)


@pytest.mark.parametrize("feat", [5, 17, 40])
@pytest.mark.parametrize("xr", [0, 1])
def test_blocked_partition_reads_the_decision_from_any_block(blocks, feat,
                                                             xr):
    """The split feature's channel lies in block 0, 1 or 2: the decision
    comes from its 16-row group, whichever block holds it."""
    blocks(partition=16)
    C, cnt = 48, 2 * pp.TILE + 100
    arena = tpe._base_arena(C)
    go = tpe._go_left("half", cnt)
    arena[feat, tpe._START:tpe._START + cnt] = np.where(go, 10, 200)
    out, counts = pp.partition_segment(
        jnp.asarray(arena, pp.ARENA_DT),
        jnp.zeros((1, pp.TILE), jnp.float32),
        tpe._START, cnt, tpe._START, tpe._DST_B,
        decision=(feat, jnp.asarray(np.arange(256) < 100, jnp.float32), xr),
        interpret=True)
    to_A = ~go if xr else go
    got = np.asarray(out.astype(jnp.float32))
    seg = arena[:, tpe._START:tpe._START + cnt]
    assert list(np.asarray(counts)) == [to_A.sum(), (~to_A).sum()]
    np.testing.assert_array_equal(
        got[:, tpe._START:tpe._START + to_A.sum()], seg[:, to_A])
    np.testing.assert_array_equal(
        got[:, tpe._DST_B:tpe._DST_B + (~to_A).sum()], seg[:, ~to_A])


@pytest.mark.parametrize("place", tpe._PLACES)
def test_blocked_decision_reads_the_channel_wherever_it_sits(blocks, place):
    """The decision's 16-row group comes by its own DMA: the split channel
    on each of the group's rows, every bin value, both `xr`."""
    blocks(partition=16)
    tpe._run_decision(28, 27 if place == "last" else 16 + place, "bitset")


@pytest.mark.parametrize("kind", tpe._MASKS)
@pytest.mark.parametrize("F,cb,chan", [(28, 16, 5), (137, 80, 130)])
def test_blocked_decision_by_every_kind_of_mask(blocks, F, cb, chan, kind):
    blocks(partition=cb)
    tpe._run_decision(F, chan, kind)


@pytest.mark.parametrize("F,cb", [(28, 16), (137, 80)])
def test_blocked_partition_equals_the_one_block_kernel(blocks, F, cb):
    """The same calls through the one-block kernel and through blocks:
    bit-equal arenas (uint16 view, so -0.0 and NaN patterns count)."""
    _plan_changes(None)
    C = pp.arena_channels(F)
    arena = tpe._base_arena(C)
    arena[-3:] = -arena[-3:]                     # negative payloads, -0.0
    cnt = 2 * pp.TILE + 300
    go = tpe._go_left("half", cnt)
    pred = np.zeros((1, tpe._CAP), np.float32)
    pred[0, tpe._START:tpe._START + cnt] = go

    def call():
        out, counts = pp.partition_segment(
            jnp.asarray(arena, pp.ARENA_DT), jnp.asarray(pred), tpe._START,
            cnt, tpe._START, tpe._DST_B, interpret=True)
        return (np.asarray(jax.lax.bitcast_convert_type(out, jnp.uint16)),
                np.asarray(counts))
    one = call()
    blocks(partition=cb)
    many = call()
    np.testing.assert_array_equal(one[0], many[0])
    np.testing.assert_array_equal(one[1], many[1])


@pytest.mark.parametrize("hist_stream", [0, 1])
def test_blocked_partition_with_histogram(blocks, hist_stream):
    """hist_stream on a blocked arena: the stream's histogram comes from a
    segment_histogram of what was just written, and equals the fused one."""
    blocks(partition=16)
    tpe.test_partition_segment_fused_histogram(hist_stream, 3 * 2048 + 5, 1)


# ------------------------------------------------------------------ #
# histograms, the fused root pass and the compactions in blocks against
# the one-block kernels
# ------------------------------------------------------------------ #
def _arena_for(F, B, rng):
    C, Fp = pp.arena_channels(F), pp.feature_channels(F)
    cap = 12 * pp.TILE
    a = rng.randint(0, B, (C, cap)).astype(np.float32)
    a[Fp:Fp + 6] = rng.randint(-120, 120, (6, cap))
    rid = np.arange(cap) % (1 << 16)
    a[Fp + 6], a[Fp + 7], a[Fp + 8] = 0, rid // 256, rid % 256
    return jnp.asarray(a, pp.ARENA_DT)


def _block_kernels(arena, F, B):
    """Everything the histogram and compaction kernels return on a fixed
    set of segments: empty, one row, across tiles, a whole tile."""
    codes = jnp.asarray(np.random.RandomState(5).randint(
        -100, 100, (2, 3 * pp.TILE + 77)), pp.ARENA_DT)
    starts = jnp.asarray([0, 2048, 4096, 3 * 2048, 0, 0, 0], jnp.int32)
    cnts = jnp.asarray([100, 2048, 0, 2049 + 300, 0, 0, 0], jnp.int32)
    out = []
    for s0, c0 in ((pp.TILE, 0), (pp.TILE, 1), (0, 3 * pp.TILE + 5),
                   (2 * pp.TILE, 2048)):
        for q in (False, True):
            out.append(pp.segment_histogram(
                arena, s0, c0, num_features=F, max_bin=B, quantized=q,
                interpret=True))
    out += pp.fused_refresh_histogram(arena, codes, pp.TILE, codes.shape[1],
                                      num_features=F, max_bin=B,
                                      interpret=True)
    out += pp.compact_carry(arena, starts, cnts, 4, 8 * pp.TILE,
                            interpret=True)
    stream, used = pp.compact_segments(
        arena, starts, cnts, jnp.arange(7, dtype=jnp.float32), 4, 1 << 20,
        num_features=F, capn=3 * 2048 + 7 * 2048, interpret=True)
    out += [stream[:, :int(used[0])], used]
    return [np.asarray(x.astype(jnp.float32) if x.dtype == pp.ARENA_DT else x)
            for x in out]


@pytest.mark.parametrize("F,B", [(28, 255), (137, 255), (48, 63)])
def test_blocked_histograms_and_compactions_equal_one_block(blocks, F, B):
    _plan_changes(None)
    arena = _arena_for(F, B, np.random.RandomState(F))
    one = _block_kernels(arena, F, B)
    # 4, 18 and 6 radix blocks of 8 features: a step's share divides them
    blocks(features=1 if F == 137 else 2, compact=16, rowid=True)
    many = _block_kernels(arena, F, B)
    assert len(one) == len(many) == 14
    for a, b in zip(one, many):
        np.testing.assert_array_equal(a, b)


def test_fused_root_writes_the_codes_once(blocks):
    """Blocked, only grid step 0 rewrites the payload group: the arena
    after the pass is the arena before with the two code planes set."""
    F, B = 40, 63
    blocks(features=1)
    Fp = pp.feature_channels(F)
    arena = _arena_for(F, B, np.random.RandomState(3))
    codes = jnp.asarray(np.random.RandomState(4).randint(
        -100, 100, (2, pp.TILE + 9)), pp.ARENA_DT)
    n = codes.shape[1]
    out, hist = pp.fused_refresh_histogram(
        arena, codes, 2 * pp.TILE, n, num_features=F, max_bin=B,
        interpret=True)
    want = np.asarray(arena.astype(jnp.float32)).copy()
    got = np.asarray(out.astype(jnp.float32))
    cols = slice(2 * pp.TILE, 2 * pp.TILE + n)
    want[Fp:Fp + 2, cols] = np.asarray(codes.astype(jnp.float32))
    np.testing.assert_array_equal(got[:, cols], want[:, cols])
    np.testing.assert_array_equal(got[:, :2 * pp.TILE],
                                  want[:, :2 * pp.TILE])
    np.testing.assert_array_equal(got[:, 4 * pp.TILE:],
                                  want[:, 4 * pp.TILE:])
    h = np.asarray(hist)
    assert h.shape == (F, B, 3) and h[..., 2].sum() == F * n


# ------------------------------------------------------------------ #
# the split scan in feature blocks
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("CH,F,B", [(2, 40, 63), (1, 37, 63), (2, 137, 255),
                                    (2, 24, 16)])
def test_blocked_scan_equals_one_block(monkeypatch, CH, F, B):
    """Blocks of 16 features, the last one padded with masked features:
    the per-feature rows and the per-child best rows are bit-equal, ties
    between blocks going to the lower feature as within one block."""
    rng = np.random.RandomState(F)
    cnt = rng.randint(0, 50, (CH, F, B)).astype(np.float32)
    cnt[:, 3] = 0
    hist = np.stack([rng.randn(CH, F, B).astype(np.float32) * cnt,
                     cnt * 0.25, cnt], -1)
    hist[:, 20 % F] = hist[:, 2]              # a tie across two blocks
    nb = rng.randint(2, B + 1, F).astype(np.int32)
    nb[2] = nb[20 % F] = B
    mt = rng.randint(0, 3, F).astype(np.int32)
    mt[2] = mt[20 % F] = 0
    fvec = sp.build_feature_statics(jnp.asarray(nb), jnp.zeros(F, jnp.int32),
                                    jnp.asarray(mt), children=CH)
    tot = hist[:, 0].sum(1)
    params = SplitParams(min_data_in_leaf=1, min_sum_hessian_in_leaf=1.0)
    pv, sv, h3 = sp._pack_inputs(jnp.asarray(hist), tot[:, 0], tot[:, 1],
                                 tot[:, 2], None, None, params)
    _plan_changes(None)
    one = sp._run_scan(pv, sv, fvec, h3, interpret=True)
    monkeypatch.setattr(sp, "_SCAN_VMEM",
                        16 * sp._SCAN_ARRAYS * 4 * (-(-B // 128) * 128))
    _plan_changes("scan")
    assert sp._scan_block(CH, F, B) == (16, -(-F // 16))
    many = sp._run_scan(pv, sv, fvec, h3, interpret=True)
    monkeypatch.undo()
    for a, b in zip(one, many):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------------ #
# the plan, from shapes alone
# ------------------------------------------------------------------ #
def test_plan_at_the_benchmark_widths():
    """One block at C = 48 and 160 (the parent's programs: no grid, no
    VMEM limit); at 2 000 columns six channel blocks of 336, three of 672
    for the carry compaction, 25 histogram steps of 80 features, two scan
    blocks of 1 000 features a child."""
    for F, B, q in ((28, 255, True), (137, 255, False)):
        plan = pp.engine_plan(F, B, q)
        assert (plan["partition_blocks"], plan["compact_blocks"],
                plan["hist_steps"]) == (1, 1, 1)
        assert plan["vmem_partition"] <= plan["vmem_default"]
    assert pp._partition_vmem_limit(48, []) is None
    assert pp._partition_vmem_limit(160, []) is None
    plan = pp.engine_plan(2000, 63, True)
    assert plan["channels"] == 2016
    assert (plan["partition_block"], plan["partition_blocks"]) == (336, 6)
    assert (plan["compact_block"], plan["compact_blocks"]) == (672, 3)
    assert (plan["hist_features_per_step"], plan["hist_steps"]) == (80, 25)
    for k in ("vmem_partition", "vmem_compact", "vmem_histogram"):
        assert plan[k] <= plan["vmem_default"], k
    assert sp._scan_block(2, 2000, 63) == (1000, 2)
    assert sp._scan_block(2, 137, 255) == (137, 0)
    assert pp._rowid_rows(48, 32) == (0, 48)
    assert pp._rowid_rows(2016, 2000) == (2000, 16)


@pytest.mark.parametrize("F", [600, 968, 1001, 4000, 16 * 127 - 9])
def test_every_width_has_a_plan(F):
    """Awkward widths (C = 16 * prime) get small blocks, not an error."""
    plan = pp.engine_plan(F, 255, False)
    assert plan["channels"] % plan["partition_block"] == 0
    assert plan["partition_block"] % 16 == 0
    assert plan["vmem_partition"] <= plan["vmem_default"]
    assert pp.feature_channels(F) % plan["hist_features_per_step"] == 0


def test_no_plan_raises_with_the_numbers():
    with pytest.raises(ValueError, match="no channel block serves 2016"):
        pp._channel_block(2016, 2 << 20)
    with pytest.raises(ValueError, match="no feature block serves"):
        pp._feature_block(250, 8, 32 << 20)


# ------------------------------------------------------------------ #
# the engine's choice
# ------------------------------------------------------------------ #
def _wide_booster(monkeypatch, engine="auto", columns=2000):
    import lightgbm_tpu as lgb
    from lightgbm_tpu.models import gbdt
    monkeypatch.setattr(gbdt, "on_tpu", lambda: True)
    monkeypatch.setattr(gbdt, "_device_memory_budget", lambda: 12 << 30)
    rng = np.random.RandomState(0)
    X = rng.randn(256, columns).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    return lgb.Booster({"objective": "binary", "num_leaves": 7,
                        "max_bin": 63, "min_data_in_leaf": 1, "verbose": -1,
                        "tpu_quantized_grad": True,
                        "tpu_tree_engine": engine}, lgb.Dataset(X, y))


def test_auto_on_a_tpu_keeps_2000_columns_on_the_partition_engine(
        monkeypatch):
    g = _wide_booster(monkeypatch)._gbdt
    assert g._use_partition_engine and g._quantized
    assert g._arena.shape[0] == 2016
    plan = g._engine_plan
    assert plan["partition_blocks"] == 6 and plan["hist_steps"] == 25
    assert plan["arena_bytes"] == 2016 * g._arena.shape[1] * 2


def test_a_width_without_a_plan_raises_and_does_not_demote(monkeypatch):
    from lightgbm_tpu.utils.log import LightGBMError

    def no_plan(*a):
        raise ValueError("no channel block serves 2016 arena channels")
    monkeypatch.setattr(pp, "engine_plan", no_plan)
    with pytest.raises(LightGBMError, match="no block plan for 2000 columns"):
        _wide_booster(monkeypatch)


def test_auto_still_demotes_what_device_memory_cannot_hold(monkeypatch):
    from lightgbm_tpu.models import gbdt
    import lightgbm_tpu as lgb
    monkeypatch.setattr(gbdt, "on_tpu", lambda: True)
    monkeypatch.setattr(gbdt, "_device_memory_budget", lambda: 1 << 20)
    rng = np.random.RandomState(0)
    X = rng.randn(256, 8).astype(np.float32)
    b = lgb.Booster({"objective": "binary", "verbose": -1, "num_leaves": 7},
                    lgb.Dataset(X, (X[:, 0] > 0).astype(np.float32)))
    assert not b._gbdt._use_partition_engine


def test_feature_major_bins_are_the_transposed_bins():
    bins = np.random.RandomState(0).randint(0, 63, (300, 17)).astype(np.uint8)
    got = pp.feature_major(jnp.asarray(bins))
    assert got.dtype == pp.ARENA_DT and got.shape == (17, 300)
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)),
                                  bins.T.astype(np.float32))


def test_a_new_arena_is_built_after_the_last_boosters_is_let_go():
    """An arena may be most of the chip: set-up collects what an earlier
    booster of the process still holds through reference cycles before it
    allocates its own (on the chip the peak otherwise followed the
    collector's timing: PERF.md, PR 27)."""
    import gc
    import weakref
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(0)
    X = rng.randn(256, 12).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 7, "verbose": -1,
              "tpu_tree_engine": "partition"}

    def booster():
        return lgb.Booster(params, lgb.Dataset(X, y))
    gc.disable()
    try:
        first = booster()
        first.update()
        gone = weakref.ref(first._gbdt)
        del first
        second = booster()
        assert gone() is None
        assert second._gbdt._use_partition_engine
    finally:
        gc.enable()


def test_the_plan_rides_the_set_up_span_and_the_log(tmp_path, capsys):
    """`lgbm:engine_plan` in a profiler trace of booster set-up, with the
    plan as the annotation's arguments; the same numbers on an Info line.
    A lambdarank booster's span also carries its query windows: what one
    iteration moves between rows and slots, as its query sizes imply."""
    import glob
    import lightgbm_tpu as lgb
    from jax.profiler import ProfileData
    rng = np.random.RandomState(0)
    X = rng.randn(256, 520).astype(np.float32)
    ds = lgb.Dataset(X, (X[:, 0] > 0).astype(np.float32),
                     params={"max_bin": 63, "verbose": -1}).construct()
    sizes = np.array([1, 7, 120, 130, 40, 0, 9])
    Xr = rng.randn(sizes.sum(), 6).astype(np.float32)
    ranked = lgb.Dataset(Xr, rng.randint(0, 5, sizes.sum()), group=sizes,
                         params={"verbose": -1}).construct()
    jax.profiler.start_trace(str(tmp_path))
    try:
        g = lgb.Booster({"objective": "binary", "num_leaves": 7,
                         "max_bin": 63, "verbose": 1,
                         "tpu_quantized_grad": True,
                         "tpu_tree_engine": "partition"}, ds)._gbdt
        r = lgb.Booster({"objective": "lambdarank", "num_leaves": 7,
                         "verbose": -1, "tpu_tree_engine": "partition"},
                        ranked)._gbdt
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    found = [dict(e.stats) for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events
             if e.name == "lgbm:engine_plan"]
    assert found == [g._engine_plan, r._engine_plan]
    assert found[0]["channels"] == 544 and found[0]["partition_blocks"] == 2
    assert found[0]["arena_bytes"] == 544 * g._arena.shape[1] * 2
    assert not any(k.startswith("rank_") for k in found[0])
    # slots: the next power of two from 8; aligned 128-row windows: those
    # that hold that many slots from any lane
    slots = [max(8, 1 << int(np.ceil(np.log2(s)))) for s in sizes if s]
    windows = [-(-(S + 127) // 128) for S in slots]
    assert found[1]["rank_windows"] == 6
    assert found[1]["rank_rows_moved"] == sum(windows) == 13
    assert found[1]["rank_rows"] == sizes.sum() == 307
    assert found[1]["rank_buckets"] == "8:2:2 16:1:2 64:1:2 128:1:2 256:1:3"
    out = capsys.readouterr().out
    assert "partition engine plan: channels=544, partition_block=272" in out


# ------------------------------------------------------------------ #
# a small wide training the float64 grower accepts split by split
# ------------------------------------------------------------------ #
def test_wide_training_is_accepted_by_the_float64_grower():
    """520 columns: 544 arena channels in two partition blocks, five
    histogram steps, a blocked scan.  Every split the system (float32,
    partition engine forced, interpret mode) chose is, by the plain numpy
    float64 grower's own gains, within 1e-3 of the best on offer."""
    import lightgbm_tpu as lgb
    from benchmarks.harness import checks
    from benchmarks.reference import grower, objectives
    rng = np.random.RandomState(1)
    n, F = 2048, 520
    X = rng.randn(n, F).astype(np.float32)
    w = rng.randn(F) / (1 + np.arange(F) / 20.0)
    y = (X @ w + rng.randn(n) > 0).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 7, "max_bin": 63,
              "learning_rate": 0.1, "min_data_in_leaf": 1,
              "min_sum_hessian_in_leaf": 10, "verbose": -1,
              "tpu_tree_engine": "partition"}
    ds = lgb.Dataset(X, y, params={"max_bin": 63, "verbose": -1})
    booster = lgb.Booster(params, ds)
    g = booster._gbdt
    plan = g._engine_plan
    assert (plan["channels"], plan["partition_blocks"], plan["hist_steps"]) \
        == (544, 2, 5)
    assert sp._scan_block(2, F, 63)[1] == 1
    booster.update()
    g._sync_model()
    tree = g.models[0]
    assert tree.num_leaves == 7
    b = ds._binned
    init = objectives.binary_init_score(y)
    grad, hess = objectives.binary_gradients(np.full(n, init), y)
    ref, misses = grower.replay(b.bins, b.feature_num_bins(), grad, hess,
                                grower.SplitRules(params),
                                checks.system_splits(tree), 1e-3)
    assert not misses, misses[:1]
    np.testing.assert_array_equal(ref.leaf_count, tree.leaf_count[:7])
    np.testing.assert_allclose(
        np.asarray(tree.leaf_value[:7]) - init, 0.1 * ref.leaf_value,
        rtol=1e-3, atol=1e-4 * np.abs(0.1 * ref.leaf_value).max())
