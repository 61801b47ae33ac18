"""A booster with validation sets on the fused spine (PR 34): the fused
(and carried) iteration scores the validation rows itself, from the device
tree (ops/valid_score.py), `eval_valid()` evaluates AUC and binary log loss
on the device (ops/metric_device.py) and feeds every other metric a fetched
score vector, and nothing of that changes a tree.

Small sizes, seeded, the partition engine in interpret mode.  The unfused
spine is forced by what already forces it, a training metric
(`is_provide_training_metric`): there is no switch.

int8: the spines do NOT draw the quantisation noise alike.  The carried
spine quantises gradients in the arena's row order and the others in the
data set's, so the same key rounds other rows; int8 trees are therefore
compared fused against fused (with and without a validation set), never
across spines.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.metric import AUCMetric, BinaryLoglossMetric, create_metric
from lightgbm_tpu.objective import create_objective

ITERS = 12
N, NV, F = 1200, 500, 6


def _rows(seed, n, sparse_pairs=False):
    r = np.random.RandomState(seed)
    X = r.randn(n, F).astype(np.float32)
    if sparse_pairs:
        # mutually exclusive sparse columns, so EFB folds them into groups
        which = r.randint(0, 4, n)
        S = np.zeros((n, 4), np.float32)
        S[np.arange(n), which] = r.rand(n) + 0.5
        X = np.hstack([X, S]).astype(np.float32)
    return X


def _binary(seed, n, bundled):
    X = _rows(seed, n, bundled)
    w = np.random.RandomState(7).randn(X.shape[1])
    y = (X @ w + np.random.RandomState(seed + 1).randn(n) > 0)
    return X, y.astype(np.float32), None


def _ranking(seed, n, bundled):
    X = _rows(seed, n, bundled)
    w = np.random.RandomState(7).randn(X.shape[1])
    s = X @ w + np.random.RandomState(seed + 1).randn(n)
    y = np.clip(np.round(s + 1.5), 0, 4).astype(np.float32)
    return X, y, [20] * (n // 20)


# name -> (objective, metric, data maker, bundled, quantized, sets, carried)
CASES = {
    "binary-1set": ("binary", "auc,binary_logloss", _binary, False, False, 1,
                    True),
    "binary-2sets": ("binary", "auc", _binary, False, False, 2, True),
    "binary-bundled": ("binary", "binary_logloss,binary_error", _binary, True,
                       False, 1, True),
    "rank-ndcg": ("lambdarank", "ndcg", _ranking, False, False, 1, False),
    "binary-int8": ("binary", "auc", _binary, False, True, 1, True),
    "binary-int8-bundled-2sets": ("binary", "auc", _binary, True, True, 2,
                                  True),
}
F32_CASES = [c for c, v in CASES.items() if not v[4]]


def _params(case, **extra):
    objective, metric, _, bundled, quantized, _, _ = CASES[case]
    return dict({"objective": objective, "metric": metric, "num_leaves": 7,
                 "min_data_in_leaf": 40, "learning_rate": 0.3, "verbose": -1,
                 "tpu_tree_engine": "partition", "ndcg_eval_at": "5",
                 "enable_bundle": bundled, "tpu_quantized_grad": quantized},
                **extra)


def _datasets(case):
    _, _, make, bundled, _, sets, _ = CASES[case]
    X, y, g = make(1, N, bundled)
    # few bins and large leaves, so that no bin of a leaf is empty: two
    # thresholds that cut a leaf's rows alike tie exactly in gain, and
    # rounding picks the winner
    ds = lgb.Dataset(X, y, group=g, params={"max_bin": 7})
    valid = []
    for i in range(sets):
        Xt, yt, gt = make(10 + i, NV, bundled)
        valid.append((Xt, yt, gt, lgb.Dataset(Xt, yt, group=gt,
                                              reference=ds)))
    return ds, valid


@functools.lru_cache(maxsize=None)
def _trained(case, spine, with_valid=True):
    """(booster, [eval_valid() per iteration], validation sets): `ITERS`
    iterations of `update()` then `eval_valid()`."""
    extra = {"is_provide_training_metric": True} if spine == "unfused" else {}
    ds, valid = _datasets(case)
    booster = lgb.Booster(_params(case, **extra), ds)
    if spine == "unfused":
        # what engine.train does with is_provide_training_metric
        for name in CASES[case][1].split(","):
            m = create_metric(name, booster.config)
            m.init(ds._binned.metadata, ds._binned.num_data)
            booster._gbdt.train_metrics.append(m)
    if with_valid:
        for i, (_, _, _, dv) in enumerate(valid):
            booster.add_valid(dv, "v%d" % i)
    series = []
    for _ in range(ITERS):
        booster.update()
        series.append(booster.eval_valid())
    gbdt = booster._gbdt
    took = ("fused" if getattr(gbdt, "_fused_validated", False)
            else "unfused")
    assert took == spine
    if spine == "fused":
        assert bool(gbdt._carried_active) == CASES[case][6]
        assert gbdt._valid_scoring == ("device" if with_valid else "host")
        if CASES[case][3]:
            assert ds._binned.bundle is not None
    else:
        assert gbdt._valid_scoring == "host"
    return booster, series, valid


def _values(series):
    return np.array([[v for _, _, v, _ in it] for it in series])


@pytest.mark.parametrize("case", F32_CASES)
def test_fused_and_unfused_spines_grow_the_same_trees(case):
    fused, fused_series, _ = _trained(case, "fused")
    unfused, unfused_series, _ = _trained(case, "unfused")
    fused._gbdt._sync_model()
    unfused._gbdt._sync_model()
    assert len(fused._gbdt.models) == len(unfused._gbdt.models) == ITERS
    for a, b in zip(fused._gbdt.models, unfused._gbdt.models):
        n = a.num_leaves
        assert n == b.num_leaves
        np.testing.assert_array_equal(a.split_feature_inner[:n - 1],
                                      b.split_feature_inner[:n - 1])
        # a threshold may differ only between two cuts that send the
        # same rows each way (a leaf with an empty bin between them: the
        # gains tie exactly and rounding picks): same counts, same gain
        moved = a.threshold_in_bin[:n - 1] != b.threshold_in_bin[:n - 1]
        assert moved.sum() <= 1
        np.testing.assert_allclose(a.split_gain[:n - 1], b.split_gain[:n - 1],
                                   rtol=1e-5)
        np.testing.assert_array_equal(a.leaf_count[:n], b.leaf_count[:n])
        # float32: the two spines sum the same gradients in another order
        # and hold the score in other forms (three bfloat16 planes on the
        # carried spine), so values agree to a few units of 1.2e-7 that
        # add up over the iterations (5e-6 seen at 12), not to the bit
        np.testing.assert_allclose(a.leaf_value[:n], b.leaf_value[:n],
                                   rtol=2e-5, atol=1e-7)
    assert [[(s, m) for s, m, _, _ in it] for it in fused_series] == \
        [[(s, m) for s, m, _, _ in it] for it in unfused_series]
    np.testing.assert_allclose(_values(fused_series),
                               _values(unfused_series), rtol=5e-6)


@pytest.mark.parametrize("case", ["binary-1set", "binary-int8",
                                  "binary-int8-bundled-2sets"])
def test_validation_never_changes_a_tree(case):
    """Fused spine: the model text is byte-equal with and without
    validation sets, in float32 and int8."""
    with_valid, _, _ = _trained(case, "fused")
    without, _, _ = _trained(case, "fused", with_valid=False)
    assert with_valid.model_to_string() == without.model_to_string()


@pytest.mark.parametrize("case", list(CASES))
def test_series_is_the_host_metric_over_predict(case):
    """What eval_valid() returned at iteration i is metric.py's host code
    over Booster.predict(Xt, raw_score=True) of the first i + 1 trees."""
    booster, series, valid = _trained(case, "fused")
    cfg = booster.config
    objective = create_objective(cfg.objective, cfg)
    names = CASES[case][1].split(",")
    for s, (Xt, yt, gt, dv) in enumerate(valid):
        metrics = [create_metric(n, cfg) for n in names]
        for m in metrics:
            m.init(dv._binned.metadata, len(yt))
        for i in (0, 1, ITERS // 2, ITERS - 1):
            raw = booster.predict(Xt, raw_score=True, num_iteration=i + 1)
            want = [v for m in metrics for v in m.eval(
                np.asarray(raw, np.float64), objective)]
            got = [v for name, _, v, _ in series[i] if name == "v%d" % s]
            np.testing.assert_allclose(got, want, rtol=2e-6, atol=1e-7)


class _Meta:
    def __init__(self, label, weights):
        self.label, self.weights = label, weights


@pytest.mark.parametrize("weighted", [False, True], ids=["plain", "weights"])
@pytest.mark.parametrize("metric", [AUCMetric, BinaryLoglossMetric],
                         ids=["auc", "logloss"])
def test_device_metric_is_the_host_metric(metric, weighted):
    """Scores with ties (rounded to a tenth), with and without weights."""
    r = np.random.RandomState(3)
    n = 4000
    label = (r.rand(n) < 0.4).astype(np.float32)
    score = (np.round(r.randn(n) * 2, 1) + label).astype(np.float32)
    assert len(np.unique(score)) < n // 10
    weights = (r.rand(n) + 0.5).astype(np.float32) if weighted else None
    cfg = Config({"objective": "binary"})
    objective = create_objective("binary", cfg)
    m = metric(cfg)
    m.init(_Meta(label, weights), n)
    host = m.eval(score.astype(np.float64), objective)[0]
    for shaped in (score, score[None, :]):     # [n] and the booster's [1, n]
        sums = np.asarray(m.eval_device(jnp.asarray(shaped), objective))
        dev = m.finish_device(sums)[0]
        # without weights the device AUC is integer arithmetic: exact
        exact = metric is AUCMetric and not weighted
        assert abs(dev - host) <= (1e-12 if exact else 5e-7)


def test_host_fed_metric_declines_the_device():
    cfg = Config({"objective": "binary"})
    m = create_metric("binary_error", cfg)
    assert m.eval_device(jnp.zeros(4), create_objective("binary", cfg)) is None
    # log loss of another objective's scores has no device form either
    ll = create_metric("binary_logloss", cfg)
    ll.init(_Meta(np.ones(4, np.float32), None), 4)
    assert ll.eval_device(jnp.zeros(4), None) is None


def test_add_valid_after_five_iterations():
    """The replay of the model onto a set added late, then device scoring
    of what follows, gives the series of a set that was there from the
    start."""
    case = "binary-1set"
    _, series, _ = _trained(case, "fused")
    ds, valid = _datasets(case)
    booster = lgb.Booster(_params(case), ds)
    for _ in range(5):
        booster.update()
    booster.add_valid(valid[0][3], "v0")
    late = []
    for _ in range(5, ITERS):
        booster.update()
        late.append(booster.eval_valid())
    assert booster._gbdt._valid_scoring == "device"
    np.testing.assert_allclose(_values(late), _values(series[5:]), rtol=1e-6)


def test_early_stopping_is_the_same_on_both_spines():
    found = {}
    for spine, extra in (("fused", {}),
                         ("unfused", {"is_provide_training_metric": True})):
        ds, valid = _datasets("binary-1set")
        params = _params("binary-1set", learning_rate=0.6, metric="auc",
                         **extra)
        booster = lgb.train(params, ds, num_boost_round=60,
                            valid_sets=[valid[0][3]], valid_names=["v0"],
                            early_stopping_rounds=3, verbose_eval=False)
        took = ("fused" if getattr(booster._gbdt, "_fused_validated", False)
                else "unfused")
        assert took == spine
        found[spine] = (booster.best_iteration, booster.current_iteration,
                        booster.best_score["v0"]["auc"])
    assert found["fused"][:2] == found["unfused"][:2]
    assert found["fused"][0] < 60            # it did stop early
    assert found["fused"][2] == pytest.approx(found["unfused"][2], rel=1e-6)


def test_rollback_restores_both_scores():
    case = "binary-1set"
    ds, valid = _datasets(case)
    booster = lgb.Booster(_params(case), ds)
    booster.add_valid(valid[0][3], "v0")
    for _ in range(4):
        booster.update()
    gbdt = booster._gbdt
    before = {k: v.copy() for k, v in gbdt.capture_score_arrays().items()}
    evals = booster.eval_valid()
    booster.update()
    assert gbdt._valid_scoring == "device"
    booster.rollback_one_iter()
    after = gbdt.capture_score_arrays()
    assert set(after) == {"train", "valid:v0"}
    for key in before:
        np.testing.assert_allclose(after[key], before[key], atol=2e-6)
    # AUC over 500 rows moves by 1.6e-5 a pair, and rows that shared every
    # leaf were tied to the bit before the tree was added and taken away
    (_, _, auc, _), (_, _, loss, _) = booster.eval_valid()
    assert abs(auc - evals[0][2]) < 2e-4
    assert loss == pytest.approx(evals[1][2], rel=1e-6)
    booster.update()                         # and training goes on
    assert booster.current_iteration == 5


def test_eval_valid_does_not_drain_the_model():
    booster, _, _ = _trained("binary-1set", "fused")
    gbdt = booster._gbdt
    for _ in range(2):
        booster.update()
    pending = len(gbdt._inflight)
    assert pending >= 2
    out = booster.eval_valid()
    assert len(gbdt._inflight) == pending
    assert [(s, m) for s, m, _, _ in out] == [("v0", "auc"),
                                              ("v0", "binary_logloss")]
    assert all(np.isfinite(v) for _, _, v, _ in out)


def _fused_jaxpr(booster):
    """The text of the jaxpr the booster's next fused iteration traces."""
    gbdt = booster._gbdt
    name = "_carried_fn" if gbdt._carried_active else "_fused_fn"
    fn, seen = getattr(gbdt, name), {}

    def record(*args):
        seen["text"] = str(jax.make_jaxpr(fn.__wrapped__)(*args))
        return fn(*args)

    setattr(gbdt, name, record)
    try:
        booster.update()
    finally:
        setattr(gbdt, name, fn)
    return seen["text"]


@pytest.mark.parametrize("case", ["binary-1set", "rank-ndcg"])
def test_no_validation_set_traces_the_validation_free_program(
        case, monkeypatch):
    """Carried and not: without validation sets the fused iteration is the
    program of a build that has no validation scoring in it at all (the
    scorer replaced by one that must not be reached)."""
    from lightgbm_tpu.ops import valid_score

    def build():
        ds, _ = _datasets(case)
        booster = lgb.Booster(_params(case), ds)
        booster.update()
        return booster

    with_scorer = _fused_jaxpr(build())
    assert "valid" not in with_scorer

    def unreachable(scores, *args):
        assert not scores, "validation scoring in a validation-free build"
        return scores

    monkeypatch.setattr(valid_score, "add_tree", unreachable)
    assert _fused_jaxpr(build()) == with_scorer
    # and with a validation set the scorer IS in the program
    monkeypatch.undo()
    ds, valid = _datasets(case)
    booster = lgb.Booster(_params(case), ds)
    booster.add_valid(valid[0][3], "v0")
    booster.update()
    assert len(_fused_jaxpr(booster)) > len(with_scorer)
