"""Quantized histogram training (ops/quantize + the int8 kernel paths).

Covers the code/scale math, the f32 integer-exactness envelope the
overflow guards are built on, the three quantized Pallas kernels in
interpret mode against numpy integer references, quantized-vs-f32
training parity on the Higgs feature shape, and bitwise kill-and-resume
determinism of the stochastic rounding (docs/Quantized.md).  The byte
floor of the fused root pass is the benchmark's
(tests/benchmark/test_bench_costs.py, `fused_root_roofline`).
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.ops import quantize as qz
from lightgbm_tpu.utils.log import LightGBMError


# ---------------------------------------------------------------- codes


class TestCodes:
    def test_codes_are_small_integers(self):
        rng = np.random.RandomState(0)
        g = rng.randn(4096).astype(np.float32)
        h = np.abs(rng.randn(4096)).astype(np.float32)
        gc, hc, gs, hs = qz.quantize_gradients(g, h, qz.quantize_key(7, 0))
        for c in (np.asarray(gc), np.asarray(hc)):
            assert c.dtype == np.float32
            assert np.all(c == np.round(c))          # integer-valued
            assert np.all(np.abs(c) <= qz.CODE_MAX)
        # scales recover magnitudes to within one code step
        assert float(gs) == pytest.approx(np.abs(g).max() / qz.CODE_MAX)
        assert float(hs) == pytest.approx(np.abs(h).max() / qz.CODE_MAX)

    def test_hessian_rounds_to_nearest(self):
        # hessians sit in denominators: deterministic nearest rounding,
        # so each code is within half a step of h / h_scale
        rng = np.random.RandomState(1)
        h = np.abs(rng.randn(2048)).astype(np.float32)
        _, hc, _, hs = qz.quantize_gradients(
            np.zeros_like(h), h, qz.quantize_key(7, 0))
        err = np.asarray(hc) - h / float(hs)
        assert np.abs(err).max() <= 0.5 + 1e-5

    def test_stochastic_rounding_is_unbiased(self):
        # the rounding noise is zero-mean: the dequantized per-row mean
        # tracks the true mean to well under one code step
        rng = np.random.RandomState(2)
        g = rng.randn(65536).astype(np.float32)
        gc, _, gs, _ = qz.quantize_gradients(
            g, np.ones_like(g), qz.quantize_key(3, 1))
        mean_err = float(np.mean(np.asarray(gc) * float(gs) - g))
        assert abs(mean_err) < float(gs) * 0.05

    def test_key_determinism(self):
        g = np.linspace(-1, 1, 512).astype(np.float32)
        h = np.ones(512, np.float32)
        a = qz.quantize_gradients(g, h, qz.quantize_key(11, 4))
        b = qz.quantize_gradients(g, h, qz.quantize_key(11, 4))
        c = qz.quantize_gradients(g, h, qz.quantize_key(11, 5))
        assert np.array_equal(np.asarray(a[0]), np.asarray(b[0]))
        assert not np.array_equal(np.asarray(a[0]), np.asarray(c[0]))

    def test_dequantize_hist(self):
        hist = np.zeros((2, 3, 3), np.float32)
        hist[0, 1] = (254.0, -127.0, 2.0)
        out = np.asarray(qz.dequantize_hist(jnp.asarray(hist), 0.5, 0.25))
        assert out[0, 1, 0] == pytest.approx(127.0)
        assert out[0, 1, 1] == pytest.approx(-31.75)
        assert out[0, 1, 2] == 2.0                   # count plane untouched


# ------------------------------------------------- overflow envelope


class TestOverflowGuard:
    def test_exact_rows_value(self):
        assert qz.exact_rows(8) == (1 << 24) // 127 == 132104
        assert qz.overflow_safe(qz.exact_rows())
        assert not qz.overflow_safe(qz.exact_rows() + 1)

    def test_f32_accumulation_exact_at_envelope(self):
        # the guard's premise: |code sum| <= CODE_MAX * exact_rows stays
        # below 2^24, where every integer is exactly representable in f32
        worst = qz.CODE_MAX * qz.exact_rows()
        assert worst < (1 << 24)
        acc = np.cumsum(np.full(qz.exact_rows(), qz.CODE_MAX, np.float32),
                        dtype=np.float32)
        assert int(acc[-1]) == worst                 # no rounding anywhere
        # ... and one row past the envelope the accumulator CAN round
        beyond = qz.CODE_MAX * (qz.exact_rows() + 1)
        assert float(np.float32(beyond)) != float(beyond)


# --------------------------------------------------------------- config


class TestConfig:
    def test_bits_other_than_8_rejected(self):
        with pytest.raises(LightGBMError):
            Config({"tpu_quantized_bits": 4})

    def test_negative_seed_rejected(self):
        with pytest.raises(LightGBMError):
            Config({"tpu_quantized_seed": -1})

    def test_defaults_off(self):
        cfg = Config()
        assert cfg.tpu_quantized_grad is False
        assert cfg.tpu_quantized_bits == 8


# ------------------------------------- interpret-mode Pallas kernels


def _int_hist_ref(bins, g_code, h_code, mask, max_bin):
    """Numpy integer reference: [F, max_bin, 3] (sum g, sum h, count)."""
    n, F = bins.shape
    out = np.zeros((F, max_bin, 3), np.int64)
    for f in range(F):
        for i in range(n):
            if mask[i]:
                b = int(bins[i, f])
                out[f, b, 0] += int(g_code[i])
                out[f, b, 1] += int(h_code[i])
                out[f, b, 2] += 1
    return out


@pytest.fixture(scope="module")
def code_data():
    rng = np.random.RandomState(5)
    n, F, B = 1024, 4, 16
    bins = rng.randint(0, B, size=(n, F)).astype(np.uint8)
    g_code = rng.randint(-qz.CODE_MAX, qz.CODE_MAX + 1, n).astype(np.float32)
    h_code = rng.randint(0, qz.CODE_MAX + 1, n).astype(np.float32)
    return n, F, B, bins, g_code, h_code


class TestKernelsInterpret:
    def test_leaf_histogram_quantized(self, code_data):
        from lightgbm_tpu.ops import histogram_pallas as hp
        n, F, B, bins, g_code, h_code = code_data
        leaf_ids = np.zeros(n, np.int32)
        leaf_ids[n // 2:] = 3
        hist = np.asarray(hp.leaf_histogram_quantized(
            jnp.asarray(bins), jnp.asarray(g_code), jnp.asarray(h_code),
            jnp.asarray(leaf_ids), 3, max_bin=B, tile=256, interpret=True))
        ref = _int_hist_ref(bins, g_code, h_code, leaf_ids == 3, B)
        np.testing.assert_array_equal(hist.astype(np.int64), ref)

    def _arena(self, bins, g_code, h_code, cap):
        """Assemble a pristine-layout quantized arena: bins rows 0..G-1,
        code planes at Fp+0/Fp+1, rowid byte planes at Fp+6..8."""
        from lightgbm_tpu.ops import partition_pallas as pp
        n, F = bins.shape
        Fp = pp.feature_channels(F)
        C = pp.arena_channels(F)
        arena = np.zeros((C, cap), np.float32)
        arena[:F, :n] = bins.T
        codes = np.asarray(pp.pack_code_planes(
            jnp.asarray(g_code), jnp.asarray(h_code)), np.float32)
        arena[Fp:Fp + 2, :n] = codes
        hi, mid, lo = (np.asarray(p, np.float32) for p in
                       pp.split_rowid(jnp.arange(n, dtype=jnp.int32)))
        arena[Fp + 6, :n], arena[Fp + 7, :n], arena[Fp + 8, :n] = hi, mid, lo
        return jnp.asarray(arena, pp.ARENA_DT)

    def test_segment_histogram_quantized(self, code_data):
        from lightgbm_tpu.ops import partition_pallas as pp
        n, F, B, bins, g_code, h_code = code_data
        arena = self._arena(bins, g_code, h_code, 2 * pp.TILE)
        hist = np.asarray(pp.segment_histogram(
            arena, 0, n, num_features=F, max_bin=B,
            quantized=True, interpret=True))
        ref = _int_hist_ref(bins, g_code, h_code,
                            np.ones(n, bool), B)
        np.testing.assert_array_equal(hist.astype(np.int64), ref)

    def test_fused_refresh_histogram(self, code_data):
        # the mega-kernel must (a) return the same integer histogram and
        # (b) leave the arena identical to an explicit code-plane write
        from lightgbm_tpu.ops import partition_pallas as pp
        n, F, B, bins, g_code, h_code = code_data
        Fp = pp.feature_channels(F)
        stale = self._arena(bins, np.zeros(n, np.float32),
                            np.zeros(n, np.float32), 2 * pp.TILE)
        arena2, hist = pp.fused_refresh_histogram(
            stale, pp.pack_code_planes(jnp.asarray(g_code),
                                       jnp.asarray(h_code)),
            0, n, num_features=F, max_bin=B, interpret=True)
        ref = _int_hist_ref(bins, g_code, h_code, np.ones(n, bool), B)
        np.testing.assert_array_equal(
            np.asarray(hist).astype(np.int64), ref)
        want = self._arena(bins, g_code, h_code, 2 * pp.TILE)
        np.testing.assert_array_equal(
            np.asarray(arena2[Fp:Fp + 2, :n], np.float32),
            np.asarray(want[Fp:Fp + 2, :n], np.float32))
        # bins and rowid planes must come through untouched
        np.testing.assert_array_equal(
            np.asarray(arena2[:F], np.float32),
            np.asarray(want[:F], np.float32))
        np.testing.assert_array_equal(
            np.asarray(arena2[Fp + 6:Fp + 9], np.float32),
            np.asarray(want[Fp + 6:Fp + 9], np.float32))


# --------------------------------------------------- end-to-end parity


def _higgs_shape(n=2500, f=28, seed=9):
    # one FIXED labeling function; `seed` only draws the sample, so a
    # second call yields a genuine holdout set for the same task
    w = np.random.RandomState(7).randn(f)
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    logits = X @ w * 0.5 + 0.8 * np.sin(X[:, 0] * 2) * X[:, 1]
    y = (logits + rng.randn(n) > 0).astype(np.float32)
    return X, y


def _auc(y, p):
    order = np.argsort(p)
    ranks = np.empty(len(p))
    ranks[order] = np.arange(1, len(p) + 1)
    pos = y > 0.5
    npos, nneg = pos.sum(), (~pos).sum()
    return (ranks[pos].sum() - npos * (npos + 1) / 2) / (npos * nneg)


class TestTrainingParity:
    def test_quantized_matches_f32_auc(self):
        # the ISSUE-8 quality bar, at test scale: int8 codes on the
        # Higgs feature shape stay within a hair of the f32 AUC
        X, y = _higgs_shape()
        Xh, yh = _higgs_shape(seed=10)
        base = {"objective": "binary", "num_leaves": 31, "verbose": -1,
                "min_data_in_leaf": 5, "seed": 3,
                "tpu_tree_engine": "partition"}
        aucs = {}
        for name, extra in (("f32", {}), ("int8",
                                          {"tpu_quantized_grad": True})):
            bst = lgb.train(dict(base, **extra), lgb.Dataset(X, y),
                            num_boost_round=20)
            aucs[name] = _auc(yh, bst.predict(Xh))
        assert aucs["f32"] > 0.85            # the task is learnable
        assert aucs["int8"] > 0.85
        assert abs(aucs["f32"] - aucs["int8"]) < 0.02

    def test_quantized_engages_on_partition_engine_only(self):
        X, y = _higgs_shape(n=600, f=8)
        bst = lgb.train({"objective": "binary", "num_leaves": 7,
                         "verbose": -1, "tpu_tree_engine": "partition",
                         "tpu_quantized_grad": True, "seed": 3},
                        lgb.Dataset(X, y), num_boost_round=3)
        assert bst._gbdt._quantized is True
        bst = lgb.train({"objective": "binary", "num_leaves": 7,
                         "verbose": -1, "tpu_tree_engine": "label",
                         "tpu_quantized_grad": True, "seed": 3},
                        lgb.Dataset(X, y), num_boost_round=3)
        assert bst._gbdt._quantized is False  # warned + fell back


# -------------------------------------- bitwise resume determinism


@pytest.mark.slow
class TestKillAndResume:
    """Checkpoint kill-and-resume must replay IDENTICAL stochastic
    rounding: the key is a pure function of (seed, restored iteration),
    so the resumed model is bitwise equal to the uninterrupted one."""

    @pytest.mark.parametrize("mode", ["gbdt", "goss"])
    def test_bitwise_resume(self, mode, tmp_path):
        X, y = _higgs_shape(n=400, f=10, seed=1)
        params = {"objective": "regression", "num_leaves": 7,
                  "verbosity": -1, "min_data_in_leaf": 5, "seed": 3,
                  "tpu_tree_engine": "partition",
                  "tpu_quantized_grad": True}
        if mode == "goss":
            params.update(boosting="goss", top_rate=0.3, other_rate=0.3)
        else:
            params.update(bagging_fraction=0.8, bagging_freq=1,
                          feature_fraction=0.8)
        ds = lgb.Dataset(X, y)
        full = lgb.train(params, ds, num_boost_round=8)
        root = str(tmp_path / mode)
        lgb.train(dict(params, tpu_checkpoint_path=root,
                       tpu_checkpoint_interval=2),
                  ds, num_boost_round=5)
        resumed = lgb.train(dict(params, tpu_checkpoint_path=root,
                                 tpu_checkpoint_interval=2),
                            ds, num_boost_round=8, resume_from=root)
        assert resumed.model_to_string() == full.model_to_string()
