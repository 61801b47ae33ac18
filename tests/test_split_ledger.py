"""The split ledger (lightgbm_tpu/obs/device.py): for each trained tree,
the rows every step of the growth loop partitioned and summed, held
against an independent walk of the saved model text over the training
rows; and that keeping it touched no device program."""
import hashlib

import numpy as np
import pytest

import lightgbm_tpu as lgb
from benchmarks.reference import walker
from lightgbm_tpu.models.tree import Tree
from lightgbm_tpu.obs import device as obs_device

ROWS = 600


def _data(seed=0):
    rng = np.random.RandomState(seed)
    X = rng.rand(ROWS, 5)
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] + 0.2 * rng.randn(ROWS)
         > 0.7).astype(np.float64)
    return X, y


def _booster(X, y, **extra):
    # off the TPU `auto` is the label engine, whose programs compile in a
    # fraction of the interpreted Pallas kernels' time; the ledger is fed
    # by the spines, whichever engine grew the tree
    params = dict({"objective": "binary", "num_leaves": 7, "verbose": -1,
                   "min_data_in_leaf": 5}, **extra)
    return lgb.Booster(params, lgb.Dataset(X, label=y))


def _new_entries(before):
    return [e for e in obs_device.split_ledgers()
            if not any(e is b for b in before)]


def _rows_through(tree, X):
    """(rows arriving at each internal node, rows ending in each leaf) of
    one tree of the model text, by a walk of every row."""
    internal = np.zeros(tree["num_leaves"] - 1, np.int64)
    leaves = np.zeros(tree["num_leaves"], np.int64)
    for x in X:
        node = 0
        while node >= 0:
            internal[node] += 1
            kind = int(tree["decision_type"][node])
            assert (kind >> 2) & 3 == 0, "the data has no missing value"
            left = x[tree["split_feature"][node]] <= tree["threshold"][node]
            node = int(tree["left_child" if left else "right_child"][node])
        leaves[~node] += 1
    return internal, leaves


def _smaller_child(tree, internal, leaves):
    def rows_of(child):
        return np.where(child >= 0, internal[np.maximum(child, 0)],
                        leaves[np.maximum(~child, 0)])
    return np.minimum(rows_of(tree["left_child"]),
                      rows_of(tree["right_child"]))


def _held_to_the_model_text(entries, text, X):
    _, trees = walker.parse_model(text)
    assert len(entries) == len(trees)
    for slot, (entry, tree) in enumerate(zip(entries, trees)):
        internal, leaves = _rows_through(tree, X)
        assert (entry["iteration"], entry["slot"]) == (slot, slot)
        assert entry["num_data"] == ROWS == leaves.sum()
        assert entry["partition_rows"][0] == ROWS
        assert entry["partition_rows"].tolist() == internal.tolist()
        assert entry["histogram_rows"].tolist() \
            == _smaller_child(tree, internal, leaves).tolist()
        assert len(entry["partition_rows"]) == tree["num_leaves"] - 1


@pytest.fixture(scope="module")
def fused(tmp_path_factory):
    """Four iterations on the fused + carried spine (partition engine,
    int8), the one booster of this file that pays for the interpreted
    kernels: the second iteration's program lowered with the arguments it
    is about to be given, the last two and the drain inside a profiler
    trace."""
    import glob
    import jax
    X, y = _data()
    before = obs_device.split_ledgers()
    booster = _booster(X, y, tpu_tree_engine="partition",
                       tpu_quantized_grad=True)
    booster.update()                       # builds the fused program
    gbdt = booster._gbdt
    fn, seen = gbdt._carried_fn, {}

    def spy(*args):                        # lowered before they are donated
        seen["lowered"] = fn.lower(*args).as_text()
        return fn(*args)

    gbdt._carried_fn = spy
    booster.update()
    gbdt._carried_fn = fn
    trace_dir = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(trace_dir))
    try:
        booster.update()
        booster.update()
        inflight = len(gbdt._inflight)
        gbdt._sync_model()
    finally:
        jax.profiler.stop_trace()
    (xplane,) = glob.glob(str(trace_dir / "plugins/profile/*/*.xplane.pb"))
    return dict(seen, X=X, text=booster.model_to_string(), xplane=xplane,
                inflight=inflight, entries=_new_entries(before),
                fused=bool(gbdt._fused_validated and gbdt._carried_active))


def test_the_fused_spines_ledger_holds_what_a_walk_of_the_model_text_counts(
        fused):
    assert fused["fused"] and fused["inflight"] == 4   # deferred: the drain
    _held_to_the_model_text(fused["entries"], fused["text"], fused["X"])


def _logistic(score, dataset):
    p = 1.0 / (1.0 + np.exp(-score))
    return p - dataset.get_label(), p * (1.0 - p)


def _with_training_metric(booster):
    """As engine.train attaches a training metric: the tree then stays in
    the iteration (the unfused spine's tree_fetch)."""
    from lightgbm_tpu.basic import _metrics_from_config
    binned = booster._train_set._binned
    for m in _metrics_from_config(booster.config):
        m.init(binned.metadata, binned.num_data)
        booster._gbdt.train_metrics.append(m)


# the unfused spine's deferred trees (a custom gradient leaves the fused
# spine and still defers: the drain), and its tree_fetch
@pytest.mark.parametrize("extra,fobj,prepare,inflight", [
    ({}, _logistic, None, 3),
    ({"metric": "binary_logloss"}, None, _with_training_metric, 0),
])
def test_the_unfused_spines_ledger_holds_what_a_walk_counts(
        extra, fobj, prepare, inflight):
    X, y = _data()
    booster = _booster(X, y, **extra)
    if prepare:
        prepare(booster)
    before = obs_device.split_ledgers()
    for _ in range(3):
        booster.update(fobj=fobj)
    gbdt = booster._gbdt
    assert len(gbdt._inflight) == inflight
    assert len(_new_entries(before)) == 3 - inflight
    gbdt._sync_model()
    assert not getattr(gbdt, "_fused_validated", False)
    _held_to_the_model_text(_new_entries(before), booster.model_to_string(),
                            X)


def test_random_forest_feeds_the_ledger_too():
    X, y = _data(1)
    booster = _booster(X, y, boosting="rf", bagging_fraction=0.7,
                       bagging_freq=1)
    before = obs_device.split_ledgers()
    booster.update()
    (entry,) = _new_entries(before)
    tree = booster._gbdt.models[-1]
    assert entry["partition_rows"].tolist() \
        == tree.internal_count[:tree.num_leaves - 1].tolist()
    # a bag of 70 %: the counts are the bag's, which is what the calls move
    assert entry["partition_rows"][0] < entry["num_data"] == ROWS


def test_a_one_leaf_tree_records_empty_arrays():
    partition_rows, histogram_rows = Tree(1).split_ledger()
    assert len(partition_rows) == len(histogram_rows) == 0
    # a label no split can improve on: the iteration's tree is one leaf
    X, _ = _data(2)
    booster = _booster(X, np.ones(ROWS), objective="regression")
    before = obs_device.split_ledgers()
    booster.update()
    booster._gbdt._sync_model()
    (entry,) = _new_entries(before)
    assert entry["num_data"] == ROWS
    assert len(entry["partition_rows"]) == len(entry["histogram_rows"]) == 0


def test_the_ring_is_bounded_and_keeps_the_newest():
    for i in range(obs_device.SPLIT_LEDGER_TREES + 5):
        obs_device.record_split_ledger(i, i, 10, np.array([10]),
                                       np.array([4]))
    held = obs_device.split_ledgers()
    assert len(held) == obs_device.SPLIT_LEDGER_TREES == 64
    assert held[-1]["iteration"] == obs_device.SPLIT_LEDGER_TREES + 4
    assert held[0]["iteration"] == 5
    held.clear()                        # a copy: the ring is the module's
    assert len(obs_device.split_ledgers()) == 64


def test_loading_a_model_records_nothing(fused, tmp_path):
    path = tmp_path / "model.txt"
    path.write_text(fused["text"])
    before = obs_device.split_ledgers()
    loaded = lgb.Booster(model_file=str(path))
    other = lgb.Booster(model_str=fused["text"])
    X = fused["X"][:8]
    assert loaded.predict(X).shape == other.predict(X).shape == (8,)
    assert loaded._gbdt.models[0].split_ledger()[0][0] == ROWS
    assert not _new_entries(before)
    assert len(obs_device.split_ledgers()) == len(before)


def test_the_drain_span_carries_trees_and_row_passes(fused):
    """`lgbm:drain_inflight` in a profiler trace: the drained trees and
    their passes over the rows, known only once the trees are fetched,
    ride the annotation that was opened before."""
    from jax.profiler import ProfileData
    (drain,) = [e for plane in ProfileData.from_file(fused["xplane"]).planes
                if plane.name == "/host:CPU"
                for line in plane.lines for e in line.events
                if e.name == "lgbm:drain_inflight"]
    stats = {key: value for key, value in drain.stats}
    assert int(stats["trees"]) == 4
    assert float(stats["row_passes"]) == pytest.approx(sum(
        float(e["partition_rows"].sum()) / ROWS for e in fused["entries"]))


# sha256 of the fused iteration's StableHLO text at this file's tiny shape
# (CPU, jax 0.9.0, x64 on as conftest.py sets it).  Until PR 36 it was the
# text of the tree before the ledger (e8d55c5, d77d49c3...1565f802): the
# counter is host bookkeeping and touched no program.  PR 37 changed it on
# purpose: `partition_segment`'s one-block tile loop runs a stage ahead on
# a three-slot read ring (ops/partition_pallas.py), and in interpret mode
# the kernel's body is part of this text; the trees it grows are the
# parent's (tools/model_hash.py, tools/kernel_equal.py).  A PR that changes
# what the fused iteration computes changes it on purpose and says so.
PARENT_LOWERING = \
    "13d5f0649b182107909571ef73b133836b63ad0612dd1f22294adb939abe3a7b"


def test_the_fused_iteration_lowers_to_the_parents_text(fused):
    got = hashlib.sha256(fused["lowered"].encode()).hexdigest()
    assert got == PARENT_LOWERING, got
