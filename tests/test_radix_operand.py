"""The radix product of the histogram kernels (ops/partition_pallas.py:
`_radix_accumulate`, `split_radix_epilogue`): its left operand is built in
dense 8-sublane slabs, rows (c, hi, f) within a product group.

On the CPU (interpret mode) the three kernels that share the tile body are
held to a numpy `bincount` histogram, exactly: integer code sums in
quantized mode, float32 sums of integer-valued (bf16-exact) planes in
float32 mode.  Two shapes are also held to what the parent commit's kernels
returned on the same arenas (`fixtures/radix_operand_parent.npz`), and the
kernel's jaxpr to the two properties the new operand was built for.
"""
import os

import numpy as np
import jax
import jax.numpy as jnp
import ml_dtypes
import pytest

from test_partition_blocks import (  # noqa: F401  (fixtures)
    _leave_no_forced_trace, _own_plan, blocks)
from lightgbm_tpu.ops import partition_pallas as pp

_CAP = 16 * pp.TILE
_START, _DST_B = pp.TILE, 11 * pp.TILE - 3 * pp.FLUSH_W
_PARENT = os.path.join(os.path.dirname(__file__), "fixtures",
                       "radix_operand_parent.npz")

# (F, max_bin, quantized, radix blocks a grid step takes or None): the
# higgs and MSLR plans, Epsilon's plan with and without its grid (the
# `pay_row` path), hi_n = 1, hi_n = 4 at m = 4, and the two plans whose odd
# hi_n `_hist_radix` rounds up to whole slabs (70 bins: 3 -> 4, 200 bins:
# 7 -> 8; a dead hi level, other `_comp_chunks`, the `[:max_bin]` slice)
_SHAPES = [(28, 255, True, None), (137, 255, False, None),
           (48, 63, True, None), (48, 63, True, 2), (20, 16, True, None),
           (12, 100, False, None), (12, 70, True, None),
           (12, 200, False, None)]
_COUNTS = [0, 1, 2047, 2048, 2049, 3 * 2048 + 5]
_CASES = ([("segment", s) for s in _SHAPES]
          + [("fused_root", s) for s in _SHAPES if s[2]]
          + [("partition", s) for s in _SHAPES])


def _arena(F, B, real_planes=False):
    """A seeded arena: bins in the feature rows, integer-valued payload
    planes (or, `real_planes`, the three-plane split of real gradients)."""
    C, Fp = pp.arena_channels(F), pp.feature_channels(F)
    rs = np.random.RandomState(1000 * F + B)
    a = rs.randint(0, B, (C, _CAP)).astype(np.float32)
    a[Fp:Fp + 6] = rs.randint(-120, 120, (6, _CAP))
    if real_planes:
        for r0 in (Fp, Fp + 3):
            x = rs.randn(_CAP).astype(np.float32)
            for i in range(3):
                a[r0 + i] = x.astype(ml_dtypes.bfloat16).astype(np.float32)
                x = x - a[r0 + i]
    return a


def _bincount_hist(feat_rows, g, h, F, B):
    want = np.zeros((F, B, 3))
    for f in range(F):
        b = feat_rows[f].astype(np.int64)
        want[f, :, 0] = np.bincount(b, weights=g, minlength=B)
        want[f, :, 1] = np.bincount(b, weights=h, minlength=B)
        want[f, :, 2] = np.bincount(b, minlength=B)
    return want


def _planes(rows, Fp, quantized):
    if quantized:
        return rows[Fp], rows[Fp + 1]
    return rows[Fp:Fp + 3].sum(0), rows[Fp + 3:Fp + 6].sum(0)


@pytest.mark.parametrize("cnt", _COUNTS)
@pytest.mark.parametrize(
    "kernel,shape", _CASES,
    ids=["%s-%dx%d-%s%s" % (k, s[0], s[1], "q" if s[2] else "f32",
                            "-grid%d" % s[3] if s[3] else "")
         for k, s in _CASES])
def test_histogram_kernels_equal_bincount(blocks, kernel, shape, cnt):
    """Every element of the three kernels' histograms, against numpy."""
    F, B, quantized, step = shape
    blocks(features=step)
    Fp = pp.feature_channels(F)
    a = _arena(F, B)
    arena = jnp.asarray(a, pp.ARENA_DT)
    seg = a[:, _START:_START + cnt]
    if kernel == "segment":
        hist = pp.segment_histogram(arena, _START, cnt, num_features=F,
                                    max_bin=B, quantized=quantized,
                                    interpret=True)
        g, h = _planes(seg, Fp, quantized)
    elif kernel == "fused_root":
        codes = np.random.RandomState(7).randint(
            -100, 100, (2, 4 * pp.TILE)).astype(np.float32)
        out, hist = pp.fused_refresh_histogram(
            arena, jnp.asarray(codes, pp.ARENA_DT), _START, cnt,
            num_features=F, max_bin=B, interpret=True)
        g, h = codes[0, :cnt], codes[1, :cnt]
        got = np.asarray(out.astype(jnp.float32))
        np.testing.assert_array_equal(got[Fp:Fp + 2, _START:_START + cnt],
                                      codes[:, :cnt])
    else:
        go = np.random.RandomState(cnt).rand(cnt) < 0.5
        pred = np.zeros((1, _CAP), np.float32)
        pred[0, _START:_START + cnt] = go
        stream = cnt % 2                 # the histogram of B or of A
        _, counts, hist = pp.partition_segment(
            arena, jnp.asarray(pred), _START, cnt, _START, _DST_B,
            hist_stream=stream, num_features=F, max_bin=B,
            quantized=quantized, interpret=True)
        assert list(np.asarray(counts)) == [go.sum(), cnt - go.sum()]
        seg = seg[:, ~go if stream else go]
        g, h = _planes(seg, Fp, quantized)
    assert hist.shape == (F, B, 3) and hist.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(hist, np.float64),
                                  _bincount_hist(seg, g, h, F, B))


def _parent_cases():
    """name -> histogram, the calls whose results the parent commit's
    kernels gave (stored by running this function there)."""
    cnt = 3 * pp.TILE + 5
    a = jnp.asarray(_arena(48, 63), pp.ARENA_DT)
    b = jnp.asarray(_arena(12, 100, real_planes=True), pp.ARENA_DT)
    return {
        "q_48x63": pp.segment_histogram(
            a, _START, cnt, num_features=48, max_bin=63, quantized=True,
            interpret=True),
        "f32_12x100_real": pp.segment_histogram(
            b, _START, cnt, num_features=12, max_bin=100, interpret=True),
    }


@pytest.mark.parametrize("name", ["q_48x63", "f32_12x100_real"])
def test_histograms_equal_the_parents(name):
    """Bit-equal to the kernels before the operand's row order changed:
    the integer code sums, and float32 sums of real gradients' residue
    planes, where the order of a sum would show."""
    want = np.load(_PARENT)[name]
    got = np.asarray(_parent_cases()[name])
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))


def _kernel_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _kernel_eqns(inner)


@pytest.mark.parametrize("F,B,quantized", [(48, 63, True), (28, 255, True),
                                           (137, 255, False)])
def test_left_operand_is_built_in_dense_slabs(F, B, quantized):
    """In the segment-histogram kernel (DMAs and `_radix_accumulate`, no
    other arithmetic): no multiply of bf16 operands, and no value with a
    whole tile on the lanes and 2 to 7 rows on the sublanes (single rows,
    the payload planes and masks, are what they have always been)."""
    arena = jax.ShapeDtypeStruct((pp.arena_channels(F), _CAP), pp.ARENA_DT)
    jaxpr = jax.make_jaxpr(
        lambda a: pp.segment_histogram(a, 0, 5000, num_features=F, max_bin=B,
                                       quantized=quantized, interpret=True)
    )(arena)
    calls = [e for e in _kernel_eqns(jaxpr.jaxpr)
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    inside = [e for e in _kernel_eqns(calls[0].params["jaxpr"])]
    assert any(e.primitive.name == "dot_general" for e in inside)
    for e in inside:
        avals = [v.aval for v in list(e.invars) + list(e.outvars)
                 if hasattr(v.aval, "shape")]
        if e.primitive.name == "mul":
            assert all(a.dtype != jnp.bfloat16 for a in avals), e
        for a in avals:
            if len(a.shape) >= 2 and a.shape[-1] == pp.TILE:
                assert a.shape[-2] == 1 or a.shape[-2] >= 8, e
