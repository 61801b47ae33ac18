"""The data generators, the seeded model and the binned-data cache."""
import numpy as np
import pytest

import lightgbm_tpu as lgb
from benchmarks.data import higgs, mslr
from benchmarks.harness import binned, synth_model
from benchmarks.reference import walker

HIGGS = {"feature_seed": 22, "label_seed": 22}
MSLR = {"feature_seed": 22, "label_seed": 22, "docs_per_query": 120}


@pytest.mark.parametrize("gen,args,rows", [(higgs, HIGGS, 3000),
                                           (mslr, MSLR, 2400)])
def test_generators_repeat_in_the_seed_and_differ_across_seeds(gen, args,
                                                                rows):
    X = gen.features(args, "train", rows)
    assert X.dtype == np.float32 and X.shape == (rows, gen.FEATURES)
    assert np.array_equal(X, gen.features(args, "train", rows))
    assert not np.array_equal(X, gen.features(args, "holdout", rows))
    assert not np.array_equal(
        X, gen.features(dict(args, feature_seed=23), "train", rows))
    y1, g1 = gen.labels(args, 1, "train", X)
    y1b, _ = gen.labels(args, 1, "train", X)
    y2, _ = gen.labels(args, 2, "train", X)
    assert np.array_equal(y1, y1b)
    # another seed is another sample of the same problem: the noise moves
    # a share of the labels, another label_seed moves far more
    moved = (y1 != y2).mean()
    other, _ = gen.labels(dict(args, label_seed=23), 1, "train", X)
    assert 0.02 < moved < (y1 != other).mean()
    assert (g1 is None) or g1.sum() == rows


def test_a_longer_draw_starts_with_the_shorter_one():
    # blocks are seeded one by one, so the first rows do not depend on
    # how many follow (the predict pool and its edge sample rely on it)
    a = higgs.features(HIGGS, "pool", 1000)
    b = higgs.features(HIGGS, "pool", (1 << 19) + 1000)
    assert np.array_equal(a, b[:1000])


def test_mslr_grades_follow_the_ranking_cut_offs():
    X = mslr.features(MSLR, "train", 1200)
    y, group = mslr.labels(MSLR, 3, "train", X)
    assert list(group) == [120] * 10
    for q in y.reshape(10, 120):
        assert [int((q == g).sum()) for g in (4, 3, 2, 1, 0)] \
            == [2, 4, 9, 25, 80]
    with pytest.raises(ValueError, match="whole number"):
        mslr.labels(MSLR, 3, "train", X[:100])


def test_seeded_model_is_a_valid_leafwise_ensemble():
    rng = np.random.default_rng(0)
    sample = higgs.features(HIGGS, "pool", 4096)
    edges = synth_model.bin_edges(sample, 255)
    assert edges.shape == (28, 254) and (np.diff(edges, axis=1) > 0).all()
    arrays = synth_model.draw_trees(rng, 6, 31, edges, 0.02)
    text = synth_model.model_text(arrays, 28, 4096)
    header, trees = walker.parse_model(text)
    assert header["max_feature_idx"] == "27" and len(trees) == 6
    for t, tree in enumerate(trees):
        children = np.concatenate([tree["left_child"], tree["right_child"]])
        # every node but the root and every leaf is some node's child, once
        assert sorted(children[children >= 0]) == list(range(1, 30))
        assert sorted(~children[children < 0]) == list(range(31))
        assert np.isin(tree["threshold"], edges).all()
        assert np.isclose(arrays["leaf_share"][t].sum(), 1.0)
    # the box model keeps every leaf reachable: all of them get rows
    big = higgs.features(HIGGS, "pool", 60000).astype(np.float64)
    reached = {v for v in walker._walk(trees[0], big)}
    assert len(reached) >= 29
    # and the system reads the text as the plain walker does
    booster = lgb.Booster(model_str=text)
    assert np.allclose(booster.predict(sample[:512]),
                       walker.predict(text, sample[:512]), atol=1e-7)


def test_the_same_seed_draws_the_same_model():
    edges = synth_model.bin_edges(higgs.features(HIGGS, "pool", 2048), 63)
    draws = [synth_model.model_text(synth_model.draw_trees(
        np.random.default_rng(seed), 3, 15, edges, 0.02), 28, 2048)
        for seed in (5, 5, 6)]
    assert draws[0] == draws[1] != draws[2]


def _trees(booster):
    booster._gbdt._sync_model()
    return [t.to_string() for t in booster._gbdt.models]


@pytest.mark.parametrize("gen,args,objective,rows", [
    (higgs, HIGGS, "binary", 3000), (mslr, MSLR, "lambdarank", 2400)])
def test_binned_cache_grows_the_same_trees_as_fresh_binning(
        tmp_path, gen, args, objective, rows):
    """What every cached run rests on: a booster trained from the saved
    and reloaded binned set, with the label set afterwards, is the booster
    trained from fresh binning."""
    params = {"objective": objective, "num_leaves": 15, "max_bin": 255,
              "min_data_in_leaf": 20, "learning_rate": 0.1, "verbose": -1}
    X = gen.features(args, "train", rows)
    y, group = gen.labels(args, 4, "train", X)
    first = binned.fresh(lgb, X, y, group, params)
    path = str(tmp_path / "set.bin")
    first.save_binary(path)
    # the cache is keyed by the columns alone: another seed's label goes
    # onto the loaded set
    y5, group5 = gen.labels(args, 5, "train", X)
    grown = []
    for ds in (binned.fresh(lgb, X, y5, group5, params),
               binned.load(lgb, path, y5, group5, params)):
        booster = lgb.Booster(params, ds)
        for _ in range(3):
            booster.update()
        grown.append(_trees(booster))
    assert grown[0] == grown[1]
    assert grown[0][0].count("num_leaves=15") == 1
