"""Device ranking ops (ops/ranking.py) vs the numpy per-query oracles."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.io.metadata import Metadata
from lightgbm_tpu.metric_rank import NDCGMetric
from lightgbm_tpu.objective_rank import LambdarankNDCG
from lightgbm_tpu.ops import ranking


def _rank_data(rng, num_queries=60, max_docs=40):
    sizes = rng.randint(1, max_docs, num_queries)
    n = int(sizes.sum())
    labels = rng.randint(0, 5, n).astype(np.float64)
    meta = Metadata(n)
    meta.set_label(labels)
    meta.set_query(sizes)
    return meta, n, labels


def test_lambdarank_device_matches_host(rng):
    meta, n, _ = _rank_data(rng)
    obj = LambdarankNDCG(Config({"objective": "lambdarank"}))
    obj.init(meta, n)
    score = rng.randn(n)
    gd, hd = (np.asarray(a, np.float64) for a in obj.get_gradients(score))
    gh, hh = obj.get_gradients_host(score)
    np.testing.assert_allclose(gd, gh, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(hd, hh, rtol=2e-4, atol=2e-5)


def test_lambdarank_device_with_weights(rng):
    meta, n, _ = _rank_data(rng, num_queries=20)
    meta.set_weights(rng.rand(n) + 0.5)
    obj = LambdarankNDCG(Config({"objective": "lambdarank"}))
    obj.init(meta, n)
    score = rng.randn(n)
    gd, hd = (np.asarray(a, np.float64) for a in obj.get_gradients(score))
    gh, hh = obj.get_gradients_host(score)
    np.testing.assert_allclose(gd, gh, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(hd, hh, rtol=2e-4, atol=2e-5)


def test_lambdarank_singleton_and_allnegative_queries(rng):
    # size-1 queries and all-zero-label queries produce zero lambdas
    sizes = np.array([1, 5, 1, 7])
    n = int(sizes.sum())
    labels = np.zeros(n)
    labels[1] = 3        # only query 1 has signal
    meta = Metadata(n)
    meta.set_label(labels)
    meta.set_query(sizes)
    obj = LambdarankNDCG(Config({"objective": "lambdarank"}))
    obj.init(meta, n)
    score = rng.randn(n)
    gd, hd = (np.asarray(a, np.float64) for a in obj.get_gradients(score))
    gh, hh = obj.get_gradients_host(score)
    np.testing.assert_allclose(gd, gh, rtol=1e-4, atol=1e-6)
    assert np.all(gd[sizes[0] + sizes[1]:] == 0)   # queries 2,3: no signal


def test_ndcg_device_matches_host(rng):
    meta, n, _ = _rank_data(rng, num_queries=80)
    m = NDCGMetric(Config({"metric": "ndcg", "eval_at": [1, 3, 5, 10]}))
    m.init(meta, n)
    score = rng.randn(n)
    np.testing.assert_allclose(m.eval(score), m.eval_host(score),
                               rtol=1e-5, atol=1e-6)


def test_ndcg_device_weighted_and_allnegative(rng):
    sizes = np.array([4, 6, 3])
    n = int(sizes.sum())
    labels = np.zeros(n)
    labels[:4] = rng.randint(1, 4, 4)    # query 0 has signal; 1,2 all-neg
    meta = Metadata(n)
    meta.set_label(labels)
    meta.set_weights(rng.rand(n) + 0.1)  # induces query weights
    meta.set_query(sizes)
    m = NDCGMetric(Config({"metric": "ndcg", "eval_at": [2, 4]}))
    m.init(meta, n)
    score = rng.randn(n)
    np.testing.assert_allclose(m.eval(score), m.eval_host(score),
                               rtol=1e-5, atol=1e-6)


def test_ndcg_empty_query_counts_as_one(rng):
    # a zero-row query contributes NDCG=1 (maxDCG<=0 rule); device and
    # host must agree
    meta = Metadata(4)
    meta.set_label(np.array([1.0, 0.0, 2.0, 1.0]))
    meta.set_query(np.array([2, 0, 2]))
    m = NDCGMetric(Config({"metric": "ndcg", "eval_at": [2]}))
    m.init(meta, 4)
    score = rng.randn(4)
    np.testing.assert_allclose(m.eval(score), m.eval_host(score), rtol=1e-6)


def test_lambdarank_f32_path_matches_f64_oracle(rng):
    """The shipped production default runs the device kernels in f32
    (jax_enable_x64 off); the harness forces x64, so this test disables
    it to exercise the f32 tie-breaking and pair sums against the f64
    host oracle under a loosened tolerance."""
    meta, n, _ = _rank_data(rng, num_queries=30)
    obj = LambdarankNDCG(Config({"objective": "lambdarank"}))
    obj.init(meta, n)
    # distinct scores: f32 cannot re-order ties the f64 oracle resolves
    score = np.linspace(-2, 2, n)
    rng.shuffle(score)
    prev = jax.config.jax_enable_x64
    try:
        jax.config.update("jax_enable_x64", False)
        gd, hd = (np.asarray(a, np.float64)
                  for a in obj.get_gradients(score))
    finally:
        jax.config.update("jax_enable_x64", prev)
    gh, hh = obj.get_gradients_host(score)
    np.testing.assert_allclose(gd, gh, rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(hd, hh, rtol=2e-3, atol=2e-4)


def test_ndcg_f32_path_matches_f64_oracle(rng):
    meta, n, _ = _rank_data(rng, num_queries=30)
    m = NDCGMetric(Config({"metric": "ndcg", "eval_at": [5]}))
    m.init(meta, n)
    score = np.linspace(-1, 1, n)
    rng.shuffle(score)
    prev = jax.config.jax_enable_x64
    try:
        jax.config.update("jax_enable_x64", False)
        dev = m.eval(score)
    finally:
        jax.config.update("jax_enable_x64", prev)
    np.testing.assert_allclose(dev, m.eval_host(score), rtol=2e-4,
                               atol=2e-5)


# ---- the slot-order pair sums: ties, signed zeros, padding ---------------
CASES = ("all_equal", "partial_ties", "signed_zeros", "single_doc",
         "equal_labels", "mostly_padding")
LAYOUTS = {"S8": 8, "S128": 128}


def _case_rows(case, S, rng, num_queries=11):
    """(scores, labels, counts) of one padded bucket: [Q, S] float64 and
    [Q]; slots past a query's count are padding."""
    cnt = rng.randint(2, S + 1, num_queries)
    if case == "mostly_padding":
        cnt = rng.randint(1, max(S // 4, 3) + 1, num_queries)
    if case == "single_doc":
        cnt[[0, 3]] = 1
    score = rng.randn(num_queries, S)
    if case == "all_equal":
        score[:] = 0.0          # the first iteration: every rank is a tie
    elif case == "partial_ties":
        score = np.round(score * 2.0) / 2.0
    elif case == "signed_zeros":
        score = rng.choice([0.0, -0.0, 0.5], size=score.shape)
    label = rng.randint(0, 5, (num_queries, S)).astype(np.float64)
    if case == "equal_labels":  # no valid pair; a row of zeros has no DCG
        label[:] = rng.randint(0, 5, (num_queries, 1))
    return score, label, cnt


def _padded(score, label, cnt, obj, dtype):
    """The arguments DeviceLambdarank hands _lambda_bucket, from rows."""
    S = score.shape[1]
    real = np.arange(S)[None, :] < cnt[:, None]
    gains = np.where(real, obj.dcg.label_gain_np[label.astype(np.int64)], 0.0)
    inv = np.zeros(len(cnt))
    for q, c in enumerate(cnt):
        mdcg = obj.dcg.cal_maxdcg_at_k(obj.optimize_pos_at, label[q, :c])
        inv[q] = 1.0 / mdcg if mdcg > 0.0 else 0.0
    return (jnp.asarray(np.where(real, score, -np.inf), dtype),
            jnp.asarray(np.where(real, label, -1.0), dtype),
            jnp.asarray(gains, dtype), jnp.asarray(real),
            jnp.asarray(inv, dtype),
            jnp.asarray(1.0 / np.log2(2.0 + np.arange(S)), dtype),
            jnp.asarray(obj.sigmoid, dtype)), inv


TOL = {"float64": dict(rtol=1e-7, atol=1e-9),
       "float32": dict(rtol=2e-3, atol=2e-4)}


@pytest.mark.parametrize("dtype", ["float64", "float32"])
@pytest.mark.parametrize("layout", ["S8", "S128", "ragged"])
@pytest.mark.parametrize("case", CASES)
def test_lambda_sums_in_slot_order_match_oracle(case, layout, dtype, rng):
    """Against the float64 per-query oracle (which sorts): one bucket
    straight through _lambda_bucket at S = 8 and 128 (the chunk does not
    divide the queries: whole padded queries too), and a ragged set
    through DeviceLambdarank's two buckets."""
    obj = LambdarankNDCG(Config({"objective": "lambdarank"}))
    if layout == "ragged":
        score_s, label_s, cnt_s = _case_rows(case, 8, rng)
        score_l, label_l, cnt_l = _case_rows(case, 32, rng)
        cnt_l = np.maximum(cnt_l, 17)           # stays in the S = 32 bucket
        rows = [(score_s[q, :c], label_s[q, :c]) for q, c in enumerate(cnt_s)]
        rows += [(score_l[q, :c], label_l[q, :c]) for q, c in enumerate(cnt_l)]
        rows = [rows[i] for i in rng.permutation(len(rows))]
        score = np.concatenate([r[0] for r in rows])
        label = np.concatenate([r[1] for r in rows])
        meta = Metadata(len(score))
        meta.set_label(label)
        meta.set_query(np.array([len(r[0]) for r in rows]))
        obj.init(meta, len(score))
        dev = ranking.DeviceLambdarank(
            obj.query_boundaries, obj.label_np, obj.dcg.label_gain_np,
            obj.inverse_max_dcgs, obj.sigmoid, dtype=jnp.dtype(dtype))
        assert len(dev._buckets) == 2
        got = [np.asarray(a, np.float64) for a in dev(score)]
        want = obj.get_gradients_host(score)
    else:
        score, label, cnt = _case_rows(case, LAYOUTS[layout], rng)
        args, inv = _padded(score, label, cnt, obj, jnp.dtype(dtype))
        lam, hes = ranking._lambda_bucket(*args, chunk=4)
        got = [np.concatenate([np.asarray(a, np.float64)[q, :c]
                               for q, c in enumerate(cnt)])
               for a in (lam, hes)]
        pad = [np.asarray(a)[np.arange(score.shape[1])[None, :]
                             >= cnt[:, None]] for a in (lam, hes)]
        assert all(np.all(p == 0.0) for p in pad), "padding gets no lambda"
        per_query = [obj._one_query(score[q, :c], label[q, :c], inv[q])
                     for q, c in enumerate(cnt)]
        want = [np.concatenate([g[k] for g in per_query]) for k in (0, 1)]
    assert np.all(np.isfinite(got[0])) and np.all(np.isfinite(got[1]))
    if case == "equal_labels":
        assert not got[0].any() and not got[1].any()
    np.testing.assert_allclose(got[0], want[0], **TOL[dtype])
    np.testing.assert_allclose(got[1], want[1], **TOL[dtype])


@pytest.mark.parametrize("S", sorted(LAYOUTS.values()))
@pytest.mark.parametrize("case", CASES)
def test_counted_rank_is_the_stable_sorts_position(case, S, rng):
    score, _, cnt = _case_rows(case, S, rng)
    real = np.arange(S)[None, :] < cnt[:, None]
    neg = jnp.asarray(np.where(real, score, -np.inf), jnp.float32)
    want = jnp.argsort(jnp.argsort(-neg, axis=1, stable=True), axis=1)
    got = ranking._slot_rank(neg)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


_MOVES = re.compile(r"stablehlo\.(?:dynamic_)?(gather|sort|scatter)\b")


def _chunk_args(Q=256, S=128):
    """One MSLR chunk's arguments, as shapes."""
    def f(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype)
    return (f((Q, S)), f((Q, S)), f((Q, S)), f((Q, S), jnp.bool_),
            f((Q,)), f((S,)), f(()))


def test_lowered_pair_sums_move_no_element_by_index():
    """What slot order buys: the lowered function holds no gather, sort or
    scatter (on the chip each costs 7-10 ns an element, PERF.md §7)."""
    text = ranking._lambda_bucket.lower(
        *_chunk_args(), chunk=256).as_text(debug_info=True)
    assert "lgbm.gradient.pairs" in text
    assert "stablehlo.while" in text          # the chunk loop is there
    assert _MOVES.findall(text) == []

    # the same reading finds them in the form this replaced
    def sort_and_permute(s):
        order = jnp.argsort(-s, axis=1, stable=True)
        return jnp.take_along_axis(s, order, axis=1)

    old = jax.jit(sort_and_permute).lower(_chunk_args()[0]).as_text()
    assert set(_MOVES.findall(old)) == {"sort", "gather"}


# ---- the move between rows and query slots, by whole windows -------------
def _element_form(dev, score):
    """The move the windows replaced, kept as the oracle: `ext[idx]` into
    the slots, two `.at[].add` of single elements back to the rows."""
    score = jnp.asarray(score, dev.dtype).reshape(-1)
    ext = jnp.concatenate([score, jnp.asarray([-jnp.inf], dev.dtype)])
    grad = jnp.zeros(dev.n + 1, dev.dtype)
    hess = jnp.zeros(dev.n + 1, dev.dtype)
    slots = []
    for (idx, _), b in zip(dev.qb.buckets, dev._buckets):
        sp = ext[idx]
        slots.append(sp)
        lam, hes = ranking._lambda_bucket(
            sp, b["lab"], b["gains"], b["real"], b["inv"], b["disc"],
            jnp.asarray(dev.sigmoid, dev.dtype), chunk=b["chunk"])
        flat = jnp.where(b["real"], idx, dev.n).reshape(-1)
        grad = grad.at[flat].add(lam.reshape(-1), mode="drop")
        hess = hess.at[flat].add(hes.reshape(-1), mode="drop")
    return grad[:dev.n], hess[:dev.n], slots


def _ragged_sizes():
    """Query sizes in the buckets S = 8, 32, 128 and 256, one empty query,
    shuffled; the last query ends at n, and n is no multiple of 128."""
    r = np.random.RandomState(41)
    sizes = np.concatenate([r.randint(1, 9, 40), r.randint(17, 33, 12),
                            r.randint(65, 129, 8), r.randint(129, 257, 5),
                            [0]])
    sizes = np.append(r.permutation(sizes), 5)
    if sizes.sum() % ranking.LANES == 0:
        sizes[-1] += 1
    return sizes


def _bits(a):
    a = np.asarray(a)
    return a.view(np.uint64 if a.dtype == np.float64 else np.uint32)


@pytest.mark.parametrize("x64", [True, False], ids=["x64", "x32"])
@pytest.mark.parametrize("scores", ["normal", "ties_and_zeros"])
def test_window_move_is_the_element_move_bit_for_bit(x64, scores, rng,
                                                     monkeypatch):
    """Over a ragged set (four buckets, windows across 128-row boundaries,
    n % 128 != 0): the slots each bucket hands _lambda_bucket are ext[idx]
    bit for bit, -inf in every padded slot, and grad and hess are the
    element form's bit for bit."""
    sizes = _ragged_sizes()
    qb = np.concatenate([[0], np.cumsum(sizes)])
    n = int(qb[-1])
    assert n % ranking.LANES and sizes[-1] > 0
    labels = rng.randint(0, 5, n)
    score = rng.randn(n)
    if scores == "ties_and_zeros":
        score = rng.choice([0.0, -0.0, 0.5, -1.25], size=n)
    handed = []
    lambda_bucket = ranking._lambda_bucket

    def recording(sp, *args, **kw):
        handed.append(sp)
        return lambda_bucket(sp, *args, **kw)
    prev = jax.config.jax_enable_x64
    try:
        jax.config.update("jax_enable_x64", x64)
        dtype = jnp.float64 if x64 else jnp.float32
        dev = ranking.DeviceLambdarank(qb, labels, 2.0 ** np.arange(8) - 1,
                                       1.0 / (1.0 + np.arange(len(sizes))),
                                       1.0, dtype=dtype)
        want_g, want_h, want_slots = _element_form(dev, score)
        monkeypatch.setattr(ranking, "_lambda_bucket", recording)
        got_g, got_h = dev(score)
    finally:
        jax.config.update("jax_enable_x64", prev)
    assert [idx.shape[1] for idx, _ in dev.qb.buckets] == [8, 32, 128, 256]
    for (rows, shift), (idx, qids) in zip(dev.qb.windows, dev.qb.buckets):
        ends = shift + sizes[qids]
        assert (ends > ranking.LANES).any(), "a window crosses a row"
    assert len(handed) == len(want_slots)
    for sp, want, b in zip(handed, want_slots, dev._buckets):
        assert sp.dtype == dtype
        np.testing.assert_array_equal(_bits(sp), _bits(want))
        assert np.all(np.asarray(sp)[~np.asarray(b["real"])] == -np.inf)
    assert got_g.shape == got_h.shape == (n,)
    np.testing.assert_array_equal(_bits(got_g), _bits(want_g))
    np.testing.assert_array_equal(_bits(got_h), _bits(want_h))
    assert np.any(np.asarray(got_g) != 0.0)


_GATHER = re.compile(r'"stablehlo\.gather".*?slice_sizes = '
                     r'array<i64: ([\d, ]+)>')
_SCATTER = re.compile(r'"stablehlo\.scatter"(.*?)\}\) : \([^)]*, '
                      r'tensor<([\dx]+)x[a-z]\w*>\)', re.S)
_WINDOW_DIMS = re.compile(r"update_window_dims = \[([\d, ]*)\]")


def _elements_an_index(text):
    """For each gather and scatter in lowered StableHLO text, the number
    of elements one index moves: its slice, or its update window."""
    def ints(s, sep):
        return [int(v) for v in s.replace(" ", "").split(sep) if v]
    gathers = [int(np.prod(ints(m, ","))) for m in _GATHER.findall(text)]
    scatters = []
    for attrs, shape in _SCATTER.findall(text):
        dims = _WINDOW_DIMS.search(attrs)       # absent where it is empty
        dims = ints(dims.group(1), ",") if dims else []
        scatters.append(int(np.prod([ints(shape, "x")[d] for d in dims])))
    return gathers, scatters


def test_lowered_gradient_moves_whole_rows_not_elements():
    """At an MSLR-like shape (queries of 120 documents, S = 128) the
    lowered DeviceLambdarank.__call__ moves no element by an index: each
    gather and scatter index moves a row of 128.  The element form,
    lowered the same way, is found by the same reading."""
    n = 64 * 120
    qb = np.arange(0, n + 1, 120)
    dev = ranking.DeviceLambdarank(
        qb, np.random.RandomState(0).randint(0, 5, n),
        2.0 ** np.arange(8) - 1, np.ones(64), 1.0, dtype=jnp.float32)
    arg = jax.ShapeDtypeStruct((n,), jnp.float32)
    text = jax.jit(dev.__call__).lower(arg).as_text(debug_info=True)
    assert "lgbm.gradient.scatter" in text
    gathers, scatters = _elements_an_index(text)
    assert gathers == [ranking.LANES]
    assert scatters == [2 * ranking.LANES]     # lam and hes side by side
    old = jax.jit(lambda s: _element_form(dev, s)[:2]).lower(arg).as_text()
    assert _elements_an_index(old) == ([1], [1, 1])
