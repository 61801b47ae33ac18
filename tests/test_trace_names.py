"""The names the program puts into a jax.profiler trace: `lgbm.<purpose>`
named scopes inside the fused device programs (read off the lowered
program's debug locations, where a dropped name shows on the CPU) and
`lgbm:<span>` annotations around the host's phases (read off a profiler
trace of two CPU iterations)."""
import glob
import re

import jax
import numpy as np
import pytest

import lightgbm_tpu as lgb

# every scope of docs/Tracing.md's table that the path runs
GROW = {"lgbm.root", "lgbm.grow.partition", "lgbm.grow.hist",
        "lgbm.grow.scan", "lgbm.grow.cache", "lgbm.grow.book",
        "lgbm.grow.carry", "lgbm.finish"}
BINARY_INT8 = GROW | {"lgbm.gradient", "lgbm.quantize", "lgbm.score"}
LAMBDARANK = GROW | {"lgbm.gradient", "lgbm.gradient.pairs",
                     "lgbm.gradient.scatter", "lgbm.score"}


def _booster(objective, **extra):
    rng = np.random.RandomState(7)
    X = rng.rand(480, 6)
    if objective == "lambdarank":
        y = rng.randint(0, 4, 480).astype(np.float64)
        ds = lgb.Dataset(X, label=y, group=[24] * 20)
    else:
        ds = lgb.Dataset(X, label=(X[:, 0] + 0.3 * rng.randn(480) > 0.5)
                         .astype(np.float64))
    params = dict({"objective": objective, "num_leaves": 7, "verbose": -1,
                   "min_data_in_leaf": 5, "tpu_tree_engine": "partition"},
                  **extra)
    return lgb.Booster(params, ds)


def _lowered_text_of_next_call(gbdt, attr):
    """The debug-info text of the fused program, lowered with the very
    arguments the next iteration passes (they are donated, so before the
    call)."""
    fn, seen = getattr(gbdt, attr), {}

    def spy(*args):
        seen["text"] = fn.lower(*args).as_text(debug_info=True)
        return fn(*args)

    setattr(gbdt, attr, spy)
    return seen


@pytest.mark.parametrize("objective,extra,attr,scopes", [
    ("binary", {"tpu_quantized_grad": True}, "_carried_fn", BINARY_INT8),
    ("lambdarank", {}, "_fused_fn", LAMBDARANK),
])
def test_fused_program_names_every_purpose(objective, extra, attr, scopes):
    booster = _booster(objective, **extra)
    booster.update()                       # builds the fused program
    gbdt = booster._gbdt
    assert getattr(gbdt, attr, None) is not None, "the path under test"
    seen = _lowered_text_of_next_call(gbdt, attr)
    booster.update()
    named = set(re.findall(r"lgbm\.[a-z.]+[a-z]", seen["text"]))
    assert named == scopes
    # a scope is one path component: purposes nest by dots, never slashes
    assert "/lgbm.gradient/" in seen["text"]
    # the loop's body sits under the loop's scope and keeps its own
    assert "lgbm.grow.carry/while/body/lgbm.grow.cache/" in seen["text"]
    if objective == "lambdarank":
        # the pairwise chain is a jitted function of its own: its
        # operations are named from there, and XLA puts the call site's
        # path (jit(fused)/lgbm.gradient/jit(_lambda_bucket)) in front
        assert '"lgbm.gradient.pairs/while/body/' in seen["text"]
        assert "lgbm.gradient/lgbm.gradient.scatter/" in seen["text"]


def _host_spans(booster, tmp_path):
    """[(start, end, name)] of the `lgbm:` annotations in a profiler
    trace of two iterations and a model read."""
    from jax.profiler import ProfileData
    booster.update()
    jax.profiler.start_trace(str(tmp_path))
    try:
        booster.update()
        booster.update()
        booster._gbdt._sync_model()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    return sorted((e.start_ns, e.start_ns + e.duration_ns, e.name[5:])
                  for plane in ProfileData.from_file(path).planes
                  if plane.name == "/host:CPU"
                  for line in plane.lines for e in line.events
                  if e.name.startswith("lgbm:"))


def _each_inside(inner, outer):
    return all(any(o0 <= i0 and i1 <= o1 for o0, o1, _ in outer)
               for i0, i1, _ in inner)


def test_profiler_trace_holds_the_fused_spines_spans(tmp_path):
    spans = _host_spans(_booster("binary", tpu_quantized_grad=True,
                                 feature_fraction=0.8), tmp_path)
    named = {n: [s for s in spans if s[2] == n] for *_, n in spans}
    assert set(named) == {"train/iteration", "fused_iter", "feature_sample",
                          "sync_model", "drain_inflight"}
    assert len(named["train/iteration"]) == 2
    assert len(named["fused_iter"]) == 2
    for (i0, i1, _), (d0, d1, _) in zip(named["train/iteration"],
                                        named["fused_iter"]):
        assert i0 <= d0 and d1 <= i1
    assert _each_inside(named["feature_sample"], named["fused_iter"])
    assert _each_inside(named["drain_inflight"], named["sync_model"])


def test_profiler_trace_holds_the_unfused_spines_spans(tmp_path):
    # GOSS samples rows by their gradients, which the fused spine does
    # not: it takes the unfused one and walks the out-of-bag rows.  Its
    # warm-up lasts int(1 / learning_rate) iterations: none at 2, so the
    # untraced first iteration compiles what the traced ones run
    spans = _host_spans(_booster("binary", boosting="goss",
                                 learning_rate=2.0), tmp_path)
    named = {n: [s for s in spans if s[2] == n] for *_, n in spans}
    assert set(named) == {"train/iteration", "boosting(gradients)",
                          "bagging/sampling", "tree_grow", "score_update",
                          "oob_walk", "sync_model", "drain_inflight"}
    assert len(named["train/iteration"]) == 2
    for phase in ("boosting(gradients)", "bagging/sampling", "tree_grow",
                  "score_update"):
        assert len(named[phase]) == 2
        assert _each_inside(named[phase], named["train/iteration"])
    assert _each_inside(named["oob_walk"], named["score_update"])


@pytest.mark.parametrize("verbose,lines", [(-1, False), (1, True)])
def test_verbosity_routes_the_programs_own_lines(capsys, verbose, lines):
    """verbose=-1 is fatal-only: nothing of Dataset construction, Booster
    set-up or training reaches standard output or standard error; at 1
    the Info lines are there."""
    rng = np.random.RandomState(3)
    X = rng.rand(300, 4)
    y = (X[:, 0] > 0.5).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 4, "verbose": verbose,
              "min_data_in_leaf": 5}
    capsys.readouterr()
    lgb.train(params, lgb.Dataset(X, label=y, params=params),
              num_boost_round=2, verbose_eval=False)
    cap = capsys.readouterr()
    assert ("[LightGBM-TPU]" in cap.out) == lines, cap.out
    if not lines:
        assert "[LightGBM-TPU]" not in cap.err, cap.err


def test_the_compile_cache_is_keyed_by_op_metadata():
    """The scopes are op metadata, which JAX's default cache key leaves
    out: an executable cached before a scope moved would keep the old
    names.  The package sets the key to include it (utils/backend.py)."""
    assert jax.config.jax_compilation_cache_include_metadata_in_key
