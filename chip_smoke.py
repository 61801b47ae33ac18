"""Does the system still start on the chip?  One process, a few minutes.

    python chip_smoke.py                          # on a machine with a TPU
    JAX_PLATFORMS=cpu python chip_smoke.py --rows 4096 --iters 2   # debug

Drives the main path the way a user would — `lgb.train` on the partition
engine with the fused, carried iteration and int8 histograms, then
`Booster.predict` on the device — at the full width of the headline model
(higgs-binary, 10.5M x 28, 255 leaves, max_bin 255; data from the seeded
generators below), and checks by the repo's own means that what came out is
right.  Then, at small sizes, it makes Mosaic lower every Pallas kernel the
repo has and compares the compiled partition engine with the XLA label
engine.  The phases run in order, none is guarded: the first failure ends
the run with its traceback.

Exit status 0 is possible only on platform `tpu`, and only then is the
result printed: a `[result] {...}` line with everything the run established,
then, as the last line of standard output, one JSON object with exactly
these keys, the device as JAX reports it:
`{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}`.
Without a chip the script stops at once — or, given reduced `--rows` /
`--iters`, runs every phase with the kernels in interpret mode (that is how
to debug it) — and exits 3 either way.  Times it prints are observations of
one run, not a benchmark: no repeats, no spread.

A chip belongs to one process: this script starts no other, and must not be
started from a process that has already touched JAX.
"""
import argparse
import json
import sys
import time

import numpy as np

NO_CHIP_EXIT = 3
# loose quality floor for the full-size run: 13 trees on the synthetic set
# reached 0.88 on the chip (PR 21)
AUC_FLOOR = 0.75
LEAVES = 255
# f32 partition engine vs label engine: typical raw-score distance after
# three boosted rounds (see equivalence())
EQUIV_MEDIAN_ATOL = 1e-4
# the eight pallas_call sites, by kernel body
ALL_KERNELS = {
    "_partition_kernel", "_compact_carry_kernel", "_compact_rows_kernel",
    "_seg_hist_kernel", "_fused_root_kernel", "_hist_kernel",
    "_hist_kernel_q", "_split_scan_kernel"}


def _auc(y, p):
    order = np.argsort(p)
    ranks = np.empty(len(p))
    ranks[order] = np.arange(1, len(p) + 1)
    pos = y > 0.5
    np_, nn = pos.sum(), (~pos).sum()
    return (ranks[pos].sum() - np_ * (np_ + 1) / 2) / (np_ * nn)


HIGGS_ROWS = 10_500_000   # docs/Experiments.rst:103-115
HIGGS_FEATURES = 28


def higgs_data(n, n_hold, seed=7):
    """(X, y, X_holdout, y_holdout) of the Higgs shape: n x 28 Gaussian
    columns, a noisy label with one interaction term; the holdout is
    drawn from the same distribution and never trained on."""
    F = HIGGS_FEATURES
    rng = np.random.RandomState(seed)
    X = rng.randn(n, F).astype(np.float32)
    w = rng.randn(F)

    def label_of(Xg):
        logits = Xg @ w * 0.5 + 0.8 * np.sin(Xg[:, 0] * 2) * Xg[:, 1]
        return (logits + rng.randn(len(Xg)) > 0).astype(np.float32)

    y = label_of(X)
    Xh = rng.randn(n_hold, F).astype(np.float32)
    return X, y, Xh, label_of(Xh)


def higgs_params(quantized):
    """The headline configuration (docs/Experiments.rst:41-99); with
    `quantized`, the int8-histogram path (docs/Quantized.md).  Warnings
    stay on: an engine the run did not ask for announces itself there."""
    params = {
        "objective": "binary", "num_leaves": 255, "learning_rate": 0.1,
        "max_bin": 255, "min_data_in_leaf": 20, "verbose": 0,
    }
    if quantized:
        params["tpu_quantized_grad"] = True
    return params


MSLR_FEATURES = 137


def mslr_data(n_query, docs_per_q=120, seed=11):
    """(X, labels, qid, group) of the MSLR-WEB30K shape: ~120 docs per
    query, 137 features, graded 0-4 relevance from a per-query ranking
    of a sparse linear utility (docs/Experiments.rst:34,137-144)."""
    F = MSLR_FEATURES
    n = n_query * docs_per_q
    rng = np.random.RandomState(seed)
    X = rng.randn(n, F).astype(np.float32)
    # sparse signal: learnable within the timed budget, so the NDCG floor
    # actually separates healthy training from a wrong-trees regression
    w = np.zeros(F)
    w[:10] = rng.randn(10)
    util = X @ w + 0.3 * rng.randn(n)
    qid = np.repeat(np.arange(n_query), docs_per_q)
    labels = np.zeros(n, np.float32)
    order = np.argsort(-util.reshape(n_query, docs_per_q), axis=1)
    grades = [(2, 4), (6, 3), (15, 2), (40, 1)]   # top-k cutoffs -> grade
    for qi in range(n_query):
        prev = 0
        lab_row = labels[qi * docs_per_q:(qi + 1) * docs_per_q]
        for cut, g in grades:
            lab_row[order[qi, prev:cut]] = g
            prev = cut
    return X, labels, qid, np.full(n_query, docs_per_q)


def _say(phase, **kv):
    print("[%s] %s" % (phase, " ".join(
        "%s=%s" % (k, ("%.4g" % v) if isinstance(v, float) else v)
        for k, v in kv.items())), flush=True)


def _record_pallas_calls(seen):
    """Wrap pl.pallas_call so every kernel lowered in this process is
    recorded as {(kernel body name, variant): interpret flag}.  Tracing
    runs even when the executable comes from the compilation cache."""
    from jax.experimental import pallas as pl
    real = pl.pallas_call

    def recording(kernel, *args, **kwargs):
        body = getattr(kernel, "func", kernel)
        kw = getattr(kernel, "keywords", None) or {}
        variant = ""
        if "payload" in kw:
            variant = "payload=%d" % kw["payload"]
        if kw.get("hist_plan"):
            variant = "hist payload=%d" % kw["hist_plan"][-1]
        seen[(body.__name__, variant)] = bool(kwargs.get("interpret", False))
        return real(kernel, *args, **kwargs)

    pl.pallas_call = recording


def main_path(lgb, jax, jnp, args, on_chip, full):
    """lgb.Dataset -> lgb.train(2 rounds: compile + warm) -> timed
    update() steps -> assertions read off the booster."""
    from lightgbm_tpu.ops import predict as predict_ops
    n_trees = 2 + args.iters + 1
    # enough holdout rows that the predict phase is over the device
    # threshold (below it Booster.predict takes the host walk)
    n_hold = max(100_000, -(-predict_ops.MIN_DEVICE_WORK // n_trees) + 1)
    t0 = time.perf_counter()
    X, y, Xh, yh = higgs_data(args.rows, n_hold)
    t_data = time.perf_counter() - t0

    params = higgs_params(quantized=True)
    if not on_chip:
        # off the chip `auto` means the label engine; the debug run forces
        # the engine under test (kernels in interpret mode)
        params["tpu_tree_engine"] = "partition"
    t0 = time.perf_counter()
    ds = lgb.Dataset(X, y)
    ds.construct()
    t_bin = time.perf_counter() - t0

    t0 = time.perf_counter()
    booster = lgb.train(params, ds, num_boost_round=2)
    g = booster._gbdt
    jax.block_until_ready(g.train_state.score)
    t_first = time.perf_counter() - t0

    # does block_until_ready block?  One step, three clocks: dispatch
    # returned, block_until_ready returned, a dependent scalar arrived.
    probe = jax.jit(lambda a: jnp.sum(a[0, :8].astype(jnp.float32)))
    float(probe(g._arena))                      # compiled before the clock
    old_arena = g._arena
    t0 = time.perf_counter()
    booster.update()
    t_dispatch = time.perf_counter() - t0
    jax.block_until_ready(g._arena)
    t_bur = time.perf_counter() - t0
    float(probe(g._arena))
    t_fetch = time.perf_counter() - t0
    # the arena is donated every iteration; a lost donation would keep
    # the old 6 GB buffer alive next to the new one
    assert old_arena.is_deleted(), "the arena was not donated"

    t0 = time.perf_counter()
    for _ in range(args.iters):
        booster.update()
    jax.block_until_ready(g.train_state.score)
    t_steps = time.perf_counter() - t0

    tiny = jnp.ones((8,), jnp.float32)
    float(jnp.sum(tiny))
    lat = []
    for _ in range(20):
        t0 = time.perf_counter()
        float(jnp.sum(tiny))
        lat.append(time.perf_counter() - t0)

    g._sync_model()          # drain: truncation flags ride the tree fetch
    leaves = [t.num_leaves for t in g.models]
    mem = jax.devices()[0].memory_stats() or {}
    obs = dict(
        data_s=t_data, binning_s=t_bin, first_train_2_rounds_s=t_first,
        warm_iteration_ms=t_steps / args.iters * 1e3,
        step_dispatch_ms=t_dispatch * 1e3,
        step_block_until_ready_ms=t_bur * 1e3,
        step_then_scalar_fetch_ms=t_fetch * 1e3,
        scalar_fetch_ms_median=float(np.median(lat)) * 1e3,
        scalar_fetch_ms_max=float(np.max(lat)) * 1e3)
    # it blocks if the step's time was spent inside it, and the fetch
    # that followed found the result already there
    bur_blocks = (t_bur - t_dispatch) > 10 * (t_fetch - t_bur)
    # said before it is judged: a failing run still reports what it saw
    _say("main", rows=args.rows, features=X.shape[1], iters=args.iters,
         leaves=leaves, arena_shape=tuple(g._arena.shape),
         truncated=g._truncation_warned,
         block_until_ready_blocks=bur_blocks, **obs)
    _say("main", bytes_limit=mem.get("bytes_limit"),
         peak_bytes_in_use=mem.get("peak_bytes_in_use"),
         bytes_in_use=mem.get("bytes_in_use"))

    assert len(g.models) == n_trees, (len(g.models), n_trees)
    assert g._use_partition_engine, "not on the partition engine"
    assert g._fused_validated, "the fused iteration never ran"
    assert g._carried_active is True, "the carried arena is not active"
    assert g._quantized is True, "int8 histograms are not active"
    if full:
        assert not g._truncation_warned, "a tree was truncated by the arena"
        assert min(leaves) >= LEAVES - 5, leaves
    else:
        # a few thousand rows in 255 leaves: 256-column allocation
        # granules, not rows, fill the arena — truncation is expected
        assert leaves[-1] > 1, leaves
    if on_chip:
        assert mem.get("bytes_limit"), "the TPU reports no bytes_limit"
        assert mem["peak_bytes_in_use"] < mem["bytes_limit"]
    return booster, Xh, yh, dict(
        rows=args.rows, features=int(X.shape[1]), num_leaves=LEAVES,
        trees=n_trees, last_tree_leaves=int(leaves[-1]),
        engine="partition", fused=True, carried=True, quantized_active=True,
        truncated=bool(g._truncation_warned), arena_donated=True,
        block_until_ready_blocks=bool(bur_blocks),
        memory={k: mem.get(k) for k in
                ("bytes_limit", "peak_bytes_in_use", "bytes_in_use")},
        observed={k: round(v, 3) for k, v in obs.items()})


def device_predict(booster, Xh, yh, full):
    """Booster.predict over the holdout is served by the DeviceEnsemble
    (signature matmul) and agrees with the host walk."""
    from lightgbm_tpu.ops import predict as predict_ops
    g = booster._gbdt
    assert Xh.shape[0] * len(g.models) >= predict_ops.MIN_DEVICE_WORK
    assert getattr(g, "_dev_ens_cache", None) is None
    t0 = time.perf_counter()
    pred = booster.predict(Xh)
    t_pred = time.perf_counter() - t0
    cached = getattr(g, "_dev_ens_cache", None)
    assert cached is not None and cached[1] is not None, \
        "Booster.predict took the host walk"
    assert pred.shape == (Xh.shape[0],) and np.isfinite(pred).all()
    host = g.predict(Xh[:10_000], device=False)
    err = float(np.max(np.abs(pred[:10_000] - host)))
    assert err < 1e-5, "device predict differs from the host walk by %g" % err
    auc = float(_auc(yh, pred))
    if full:
        assert auc >= AUC_FLOOR, "holdout AUC %.4f < %.2f" % (auc, AUC_FLOOR)
    else:
        assert auc > 0.5, auc
    _say("predict", rows=Xh.shape[0], trees=len(g.models),
         first_predict_s=t_pred, max_abs_err_vs_host=err, holdout_auc=auc)
    return dict(rows=int(Xh.shape[0]), served_by="DeviceEnsemble",
                max_abs_err_vs_host=err, holdout_auc=round(auc, 4),
                observed={"first_predict_s": round(t_pred, 3)})


def _train_small(lgb, jax, X, y, extra, rounds=3, **ds_kw):
    params = {"objective": "binary", "num_leaves": 63, "max_bin": 255,
              "min_data_in_leaf": 20, "learning_rate": 0.1, "verbose": 0}
    params.update(extra)
    booster = lgb.train(params, lgb.Dataset(X, y, **ds_kw),
                        num_boost_round=rounds)
    jax.block_until_ready(booster._gbdt.train_state.score)
    booster._gbdt._sync_model()
    return booster


def equivalence(lgb, jax, args):
    """Compiled kernels compute what the XLA label engine computes: f32
    partition engine vs label engine on the same data.  The contract is
    tests/test_partition_engine.py's: from identical gradients the two
    grow the same tree (the first one); over boosted rounds a near-tie
    split flipped by f32 reassociation noise may compound, so later
    rounds are held to close typical scores, not pointwise equality."""
    n = min(args.rows, 200_000)
    X, y, Xh, _ = higgs_data(n, 20_000, seed=3)
    part = _train_small(lgb, jax, X, y, {"tpu_tree_engine": "partition"})
    label = _train_small(lgb, jax, X, y, {"tpu_tree_engine": "label"})
    assert part._gbdt._use_partition_engine
    assert not label._gbdt._use_partition_engine

    def same_tree(tp, tl):
        k = tp.num_leaves - 1
        return (tp.num_leaves == tl.num_leaves
                and np.array_equal(tp.split_feature_inner[:k],
                                   tl.split_feature_inner[:k])
                and np.array_equal(tp.threshold_in_bin[:k],
                                   tl.threshold_in_bin[:k])
                and np.allclose(tp.leaf_value[:k + 1], tl.leaf_value[:k + 1],
                                rtol=1e-4, atol=1e-6))

    same = [same_tree(tp, tl) for tp, tl in
            zip(part._gbdt.models, label._gbdt.models)]
    diff = np.abs(part._gbdt.predict(Xh, raw_score=True, device=False)
                  - label._gbdt.predict(Xh, raw_score=True, device=False))
    med, mx = float(np.median(diff)), float(np.max(diff))
    _say("equivalence", rows=n, trees=len(same), identical_trees=sum(same),
         median_abs_raw_score_diff=med, max_abs_raw_score_diff=mx)
    assert same[0], "partition and label engines grew different first trees"
    assert med < EQUIV_MEDIAN_ATOL, med
    return dict(rows=n, trees=len(same), identical_trees=int(sum(same)),
                first_tree_identical=True, median_abs_raw_score_diff=med,
                max_abs_raw_score_diff=mx)


def validation(lgb, jax, args):
    """A booster with a validation set stays on the fused + carried spine:
    the iteration's own program scores the validation rows (XLA products,
    ops/valid_score.py: no Mosaic kernel of its own to lower) and
    eval_valid() evaluates AUC on the device, equal to metric.py's host
    code over Booster.predict at every iteration."""
    from lightgbm_tpu.metric import AUCMetric
    n, nv, rounds = min(args.rows, 262_144), min(args.rows, 32_768), 6
    X, y, Xv, yv = higgs_data(n, nv, seed=9)
    params = dict(higgs_params(True), metric="auc",
                  tpu_tree_engine="partition")
    ds = lgb.Dataset(X, y)
    booster = lgb.Booster(params, ds)
    booster.add_valid(lgb.Dataset(Xv, yv, reference=ds), "hold")
    series = []
    for _ in range(rounds):
        booster.update()
        (_, name, value, _), = booster.eval_valid()
        assert name == "auc" and np.isfinite(value), (name, value)
        series.append(value)
    g = booster._gbdt
    pending = len(g._inflight)
    assert g._fused_validated and g._carried_active, \
        "a validation set took the booster off the fused + carried spine"
    assert g._valid_scoring == "device", g._valid_scoring
    assert pending == rounds, "eval_valid() drained the model (%d of %d " \
        "trees still deferred)" % (pending, rounds)
    host = AUCMetric(booster.config)
    host.init(g.valid_states[0][1].ds.metadata, nv)
    worst = 0.0
    for i, value in enumerate(series):
        raw = booster.predict(Xv, raw_score=True, num_iteration=i + 1)
        worst = max(worst, abs(value - host.eval(np.asarray(raw, np.float64),
                                                 None)[0]))
    assert worst <= 1e-6, "device AUC series off the host metric by %g" % worst
    _say("validation", rows=n, valid_rows=nv, spine="fused",
         valid_scoring=g._valid_scoring, auc_last=series[-1],
         max_abs_diff_vs_host=worst)
    return dict(rows=n, valid_rows=nv, spine="fused", carried=True,
                valid_scoring=g._valid_scoring, iterations=rounds,
                valid_kernels="none: XLA products (ops/valid_score.py)",
                observed={"auc_last": round(series[-1], 6),
                          "max_abs_diff_vs_host": worst})


def kernel_coverage(lgb, jax, jnp, args):
    """The configurations that reach the kernels the main path does not:
    real widths, small rows; each asserts the engine it should be on."""
    from lightgbm_tpu.ops import histogram_pallas as hp
    from lightgbm_tpu.utils.backend import pallas_interpret
    n = min(args.rows, 50_000)
    X, y, _, _ = higgs_data(n, 16, seed=5)
    force = {"tpu_tree_engine": "partition"}
    done = []

    def check(name, booster, partition=True):
        g = booster._gbdt
        on_part = (g._grower._partition is not None
                   if g._grower is not None else g._use_partition_engine)
        assert on_part == partition, "%s: partition engine %s" % (
            name, on_part)
        assert all(t.num_leaves > 1 for t in g.models), name
        assert not g._truncation_warned, name
        done.append(name)

    # bagging: the fused spine under a row bag (carried for binary), whose
    # root pass fuses partition + histogram (f32 7-plane and int8
    # 3-plane) and whose out-of-bag rows are scored in the program
    bag = {"bagging_fraction": 0.8, "bagging_freq": 1}
    check("bagging-f32", _train_small(lgb, jax, X, y, dict(force, **bag)))
    check("bagging-int8", _train_small(
        lgb, jax, X, y, dict(force, tpu_quantized_grad=True, **bag)))
    # multiclass: three trees per fused iteration, score emitted through
    # compact_segments (no carried arena)
    y3 = np.digitize(X[:, 0] + 0.5 * X[:, 1], [-0.5, 0.5]).astype(np.float32)
    check("multiclass", _train_small(
        lgb, jax, X, y3, dict(force, objective="multiclass", num_class=3)))
    # one categorical column: bitset go-left masks, XLA categorical scan
    Xc = X.copy()
    Xc[:, 5] = np.floor(np.abs(Xc[:, 5]) * 3) % 8
    check("categorical", _train_small(lgb, jax, Xc, y, dict(force),
                                      categorical_feature=[5]))
    # lambdarank at 137 features: C=160 arena channels, the widest any
    # record used
    Xr, lab, _, group = mslr_data(max(8, min(n, 24_000) // 120))
    rank = _train_small(lgb, jax, Xr, lab,
                        dict(force, objective="lambdarank", metric="ndcg"),
                        group=group)
    assert rank._gbdt._arena.shape[0] == 160, rank._gbdt._arena.shape
    check("lambdarank-137", rank)
    check("lambdarank-137-int8", _train_small(
        lgb, jax, Xr, lab, dict(force, objective="lambdarank",
                                metric="ndcg", tpu_quantized_grad=True),
        group=group))
    # wide dense data: 2 000 columns are 2 016 arena channels, past what one
    # [C, tile] slab of VMEM holds, so every arena kernel runs in channel or
    # feature blocks (ops/partition_pallas.engine_plan) and `auto` must
    # still choose the partition engine.  Fused + carried int8 (partition,
    # segment and fused root histograms, carry compaction, the blocked
    # scan), then bagged (the pred-routed root partition with its
    # histogram, leaf ids through compact_segments).  Off the chip the
    # interpreter gets 520 columns: blocked all the same, in a tenth of
    # the time.
    from lightgbm_tpu.ops import partition_pallas as pp
    wide_f = 2000 if jax.default_backend() == "tpu" else 520
    Xw, yw, _, _ = higgs_data(min(n, 32_768), 16, seed=7)
    rng_w = np.random.RandomState(11)
    Xw = np.concatenate([Xw, rng_w.randn(len(Xw), wide_f - Xw.shape[1])
                         .astype(np.float32)], axis=1)
    wide = {"max_bin": 63, "tpu_quantized_grad": True,
            "tpu_tree_engine": ("auto" if jax.default_backend() == "tpu"
                                else "partition")}
    for name, extra in (("wide-int8", {}), ("wide-bagging-int8", bag)):
        b = _train_small(lgb, jax, Xw, yw, dict(wide, **extra), rounds=2)
        plan = b._gbdt._engine_plan
        assert plan["channels"] == pp.arena_channels(wide_f) \
            and plan["partition_blocks"] > 1 and plan["hist_steps"] > 1, plan
        check(name, b)
    _say("kernels", wide_columns=wide_f, **{
        k: plan[k] for k in ("channels", "partition_block",
                             "hist_features_per_step")})
    del Xw
    # label engine with the masked Pallas histogram
    check("label-pallas", _train_small(
        lgb, jax, X, y, {"tpu_tree_engine": "label",
                         "tpu_histogram_impl": "pallas"}), partition=False)

    # the quantized masked histogram has no caller on a training path;
    # lower it directly against the f32 kernel on the same integer codes
    rng = np.random.RandomState(0)
    m = min(n, 16_384)
    bins = jnp.asarray(rng.randint(0, 255, (m, 28)), jnp.uint8)
    gc = jnp.asarray(rng.randint(-127, 128, m), jnp.float32)
    hc = jnp.asarray(rng.randint(0, 128, m), jnp.float32)
    lid = jnp.asarray(rng.randint(0, 3, m), jnp.int32)
    interp = pallas_interpret()
    hq = hp.leaf_histogram_quantized(bins, gc, hc, lid, jnp.int32(1), 255,
                                     interpret=interp)
    hf = hp.leaf_histogram(bins, gc, hc, lid, jnp.int32(1), 255,
                           interpret=interp)
    assert np.array_equal(np.asarray(hq), np.asarray(hf)), \
        "quantized masked histogram differs from the f32 kernel"
    done.append("leaf_histogram_quantized")
    _say("kernels", rows=n, configs=",".join(done))
    return done


def _report(device, result):
    """The two lines a passing run ends with: what it established, one JSON
    object on a `[result]` line of its own, and then the verdict, last —
    exactly these keys, the device as JAX reports it (the driver parses the
    last line and refuses any other shape)."""
    print("[result] " + json.dumps(dict(device=device, **result)), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=None,
                    help="training rows (default: the full 10.5M)")
    ap.add_argument("--iters", type=int, default=10,
                    help="timed update() steps after the 2 warm-up rounds")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    on_chip = device["platform"] == "tpu"
    if not on_chip and args.rows is None:
        print("chip_smoke: no chip found (JAX reports %s).  Nothing was run; "
              "pass --rows/--iters to debug the phases on this backend."
              % json.dumps(device), file=sys.stderr)
        return NO_CHIP_EXIT

    import jaxlib
    import lightgbm_tpu as lgb

    if args.rows is None:
        args.rows = HIGGS_ROWS
    full = args.rows == HIGGS_ROWS and args.iters >= 10
    try:
        import libtpu
        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = None
    versions = {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                "libtpu": libtpu_version}
    _say("device", cache_dir=jax.config.jax_compilation_cache_dir,
         **device, **versions)
    if not full:
        _say("device", REDUCED="rows=%d iters=%d (full: %d, >=10)"
             % (args.rows, args.iters, HIGGS_ROWS))

    seen = {}
    _record_pallas_calls(seen)
    t_all = time.perf_counter()
    booster, Xh, yh, main_out = main_path(lgb, jax, jnp, args, on_chip,
                                          full)
    predict_out = device_predict(booster, Xh, yh, full)
    del booster, Xh, yh
    equiv_out = equivalence(lgb, jax, args)
    valid_out = validation(lgb, jax, args)
    configs = kernel_coverage(lgb, jax, jnp, args)

    kernels = sorted({name for name, _v in seen})
    assert set(kernels) == ALL_KERNELS, \
        "kernels never lowered: %s" % sorted(ALL_KERNELS - set(kernels))
    want_interpret = not on_chip
    wrong = sorted(k for k, interp in seen.items() if interp != want_interpret)
    assert not wrong, "kernels with interpret=%s: %s" % (not want_interpret,
                                                         wrong)
    variants = sorted("%s[%s]" % kv for kv in seen)
    _say("kernels", lowered=len(variants), interpret=want_interpret,
         variants=";".join(variants))
    total_s = time.perf_counter() - t_all
    _say("done", total_s=total_s)

    if not on_chip:
        print("chip_smoke: no chip found (JAX reports %s).  Every phase ran "
              "on this backend with the kernels in interpret mode; that "
              "proves nothing about Mosaic, so this is not a pass."
              % json.dumps(device), file=sys.stderr)
        return NO_CHIP_EXIT
    _report(device, dict(
        versions=versions, full_size=bool(full),
        main=main_out, device_predict=predict_out, equivalence=equiv_out,
        validation=valid_out,
        kernel_configs=configs, kernels_compiled=kernels,
        kernels_interpret=False,
        observed={"total_s": round(total_s, 1)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
