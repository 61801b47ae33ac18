"""Pallas TPU kernel for per-leaf gradient/hessian/count histograms.

The TPU-native re-design of the reference's OpenCL histogram kernels
(src/treelearner/ocl/histogram{16,64,256}.cl) and of the CPU hot loop
(src/io/dense_bin.hpp:105-185).  Those kernels scatter into per-workgroup
local-memory sub-histograms with hand-rolled float atomics; a TPU has no
fast scatter, so this kernel factorizes the bin one-hot over a radix pair
and rides the MXU:

    bin = hi * lo_n + lo
    hist[f, c, hi, lo] = sum_t (hi_t == hi) * (lo_t == lo) * gh[c, t]

Per row tile the kernel builds `lhs[(f, c, hi), t] = gh[c,t] * (hi_t==hi)`
and `rhs[(f', lo), t] = (lo_t==lo)` in VMEM and contracts them with ONE
MXU matmul covering a group of `m` features.  The (f, f') off-diagonal
blocks are wasted work, but they fill lanes that would otherwise idle —
radix/group sizes are chosen per max_bin so M<=128 and N==128, i.e. one
full 128x128 MXU tile per feature group (the analogue of the reference
GPU learner's 16/64/256-bin kernel specialization, gpu_tree_learner
.cpp:689-751).  VPU work is hi_n + lo_n comparisons per (row, feature)
instead of B, and the [T, F, 3*hi_n] intermediate never touches HBM (the
reason this is a Pallas kernel and not an XLA einsum).

Grid: (feature_groups, row_tiles), row tiles innermost; each feature
group's output block is revisited across row tiles and accumulated in
place, relying on the TPU's sequential grid iteration order.

The row→leaf label mask (leaf_ids == leaf) is fused into gh inside the
kernel, so per-leaf histogramming is one pass with no host-side compaction.
Accumulation is f32 (single-precision like the reference GPU default,
GPUHistogramBinEntry gpu_tree_learner.h:74-78; the gpu_use_dp analogue is
the XLA f64 fallback path in ops/histogram.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _radix_plan(max_bin: int):
    """(lo_n, hi_n, m): bin radix split and features-per-matmul group so
    that N = m*lo_n == 128 and M = 3*hi_n*m <= 128."""
    if max_bin <= 16:
        lo_n, hi_n = 16, 1
    elif max_bin <= 64:
        lo_n, hi_n = 16, -(-max_bin // 16)
    elif max_bin <= 128:
        lo_n, hi_n = 32, -(-max_bin // 32)
    elif max_bin <= 256:
        lo_n, hi_n = 32, -(-max_bin // 32)
    else:
        raise ValueError("pallas histogram kernel supports max_bin <= 256, "
                         "got %d" % max_bin)
    m = 128 // lo_n
    assert 3 * hi_n * m <= 128
    return lo_n, hi_n, m


def _radix_matmul(gh, bins, out_ref, i, *, lo_n: int, hi_n: int, m: int,
                  k: int, tile: int):
    """Shared radix-pair MXU contraction + in-place grid accumulation:
    gh [3, tile] payload planes, bins [k*m, tile] int32 bin codes."""
    hi = bins // lo_n
    lo = bins - hi * lo_n
    hi_iota = jax.lax.broadcasted_iota(jnp.int32, (1, hi_n, 1), 1)
    lo_iota = jax.lax.broadcasted_iota(jnp.int32, (1, lo_n, 1), 1)
    hihot = (hi[:, None, :] == hi_iota).astype(jnp.float32)   # [k*m, hi_n, T]
    lohot = (lo[:, None, :] == lo_iota).astype(jnp.float32)   # [k*m, lo_n, T]

    # lhs[g, (f, c, hi), t] = gh[c, t] * hihot[g*m + f, hi, t]
    lhs = (gh[None, :, None, :] * hihot[:, None, :, :]).reshape(
        k, m * 3 * hi_n, tile)
    rhs = lohot.reshape(k, m * lo_n, tile)
    part = jax.lax.dot_general(
        lhs, rhs, dimension_numbers=(((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)                  # [k, M, N]

    @pl.when(i == 0)
    def _():
        out_ref[:] = part

    @pl.when(i != 0)
    def _():
        out_ref[:] = out_ref[:] + part


def _hist_kernel(leaf_ref, bins_ref, lid_ref, grad_ref, hess_ref, out_ref,
                 *, lo_n: int, hi_n: int, m: int, k: int, tile: int):
    """One (feature_block, row_tile) step; a feature block is k groups of m
    features, one MXU-tile matmul each (batched).

    bins_ref: [k * m, tile] uint8 (feature-major block slice)
    lid_ref:  [1, tile] int32 row→leaf labels
    grad/hess_ref: [1, tile] f32
    out_ref:  [k, 3 * hi_n * m, lo_n * m] f32 — rows (f, c, hi), cols (f', lo)
    """
    i = pl.program_id(1)
    bins = bins_ref[:].astype(jnp.int32)                      # [k*m, T]
    msk = (lid_ref[:] == leaf_ref[0]).astype(jnp.float32)     # [1, T]
    g = grad_ref[:] * msk
    h = hess_ref[:] * msk
    gh = jnp.concatenate([g, h, msk], axis=0)                 # [3, T]
    _radix_matmul(gh, bins, out_ref, i, lo_n=lo_n, hi_n=hi_n, m=m, k=k,
                  tile=tile)


def _hist_kernel_q(leaf_ref, bins_ref, lid_ref, code_ref, out_ref,
                   *, lo_n: int, hi_n: int, m: int, k: int, tile: int):
    """Quantized variant: g/h arrive as ONE [2, tile] int8 code block and
    leaf labels as uint8, so the per-row HBM read is F+3 bytes instead of
    F+12.  The MXU contraction is identical — the accumulator holds exact
    integer code sums (f32-exact below 2^24, ops/quantize.exact_rows)."""
    i = pl.program_id(1)
    bins = bins_ref[:].astype(jnp.int32)                      # [k*m, T]
    msk = (lid_ref[:].astype(jnp.int32) == leaf_ref[0]).astype(jnp.float32)
    gh = jnp.concatenate([code_ref[:].astype(jnp.float32) * msk, msk],
                         axis=0)                              # [3, T]
    _radix_matmul(gh, bins, out_ref, i, lo_n=lo_n, hi_n=hi_n, m=m, k=k,
                  tile=tile)


@functools.partial(jax.jit, static_argnames=("max_bin", "tile", "interpret"))
def leaf_histogram(bins, grad, hess, leaf_ids, leaf, max_bin: int,
                   tile: int = 2048, interpret: bool = False) -> jnp.ndarray:
    """[F, max_bin, 3] f32 histogram of rows with leaf_ids == leaf.

    bins [n, F] uint8; grad/hess [n] float; leaf_ids [n] int32; leaf scalar.
    Requires max_bin <= 256 (uint8 bin storage — the same cap the reference
    GPU learner has, gpu_tree_learner.cpp:233-251).
    """
    n, F = bins.shape
    lo_n, hi_n, m = _radix_plan(max_bin)
    M, N = 3 * hi_n * m, lo_n * m
    f_blk = max(m, 8)          # bins block sublane dim must be a multiple of 8
    k = f_blk // m             # matmul groups per block (batched in-kernel)

    f_pad = -F % f_blk
    n_pad = -n % tile
    bins_t = jnp.pad(bins.astype(jnp.uint8), ((0, n_pad), (0, f_pad))).T
    lid = jnp.pad(leaf_ids.astype(jnp.int32), (0, n_pad),
                  constant_values=-2)[None, :]                # never a leaf id
    g32 = jnp.pad(grad.astype(jnp.float32), (0, n_pad))[None, :]
    h32 = jnp.pad(hess.astype(jnp.float32), (0, n_pad))[None, :]
    Fp = F + f_pad
    n_blocks = Fp // f_blk
    n_tiles = (n + n_pad) // tile
    leaf_arr = jnp.asarray(leaf, jnp.int32).reshape(1)

    kernel = functools.partial(_hist_kernel, lo_n=lo_n, hi_n=hi_n, m=m, k=k,
                               tile=tile)
    out = pl.pallas_call(
        kernel,
        grid=(n_blocks, n_tiles),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),             # leaf scalar
            pl.BlockSpec((f_blk, tile), lambda f, i: (f, i)),  # bins
            pl.BlockSpec((1, tile), lambda f, i: (0, i)),      # leaf_ids
            pl.BlockSpec((1, tile), lambda f, i: (0, i)),      # grad
            pl.BlockSpec((1, tile), lambda f, i: (0, i)),      # hess
        ],
        out_specs=pl.BlockSpec((k, M, N), lambda f, i: (f, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_blocks * k, M, N), jnp.float32),
        interpret=interpret,
    )(leaf_arr, bins_t, lid, g32, h32)

    hist = radix_epilogue(out, n_blocks * k, m, hi_n, lo_n)
    return hist[:F, :max_bin, :].astype(grad.dtype)


@functools.partial(jax.jit, static_argnames=("max_bin", "tile", "interpret"))
def leaf_histogram_quantized(bins, g_code, h_code, leaf_ids, leaf,
                             max_bin: int, tile: int = 2048,
                             interpret: bool = False) -> jnp.ndarray:
    """[F, max_bin, 3] f32 INTEGER-CODE histogram of rows with
    leaf_ids == leaf: (sum g_code, sum h_code, count).

    bins [n, F] uint8; g_code/h_code [n] int8-valued (any real dtype —
    packed to int8 on the wire); leaf_ids [n] with values < 255 (uint8 on
    the wire; pass zeros with leaf=0 for a whole-dataset/root histogram,
    where order-invariance lets this kernel read the row-order packed
    bins instead of streaming the bf16 arena).  Recover real g/h sums
    with ops.quantize.dequantize_hist.
    """
    n, F = bins.shape
    lo_n, hi_n, m = _radix_plan(max_bin)
    M, N = 3 * hi_n * m, lo_n * m
    f_blk = max(m, 8)
    k = f_blk // m

    f_pad = -F % f_blk
    n_pad = -n % tile
    bins_t = jnp.pad(bins.astype(jnp.uint8), ((0, n_pad), (0, f_pad))).T
    # pad value 255 is never a leaf id (leaf < 255 enforced by callers)
    lid = jnp.pad(leaf_ids.astype(jnp.uint8), (0, n_pad),
                  constant_values=255)[None, :]
    codes = jnp.stack([
        jnp.pad(g_code.astype(jnp.int8), (0, n_pad)),
        jnp.pad(h_code.astype(jnp.int8), (0, n_pad))])        # [2, n+pad]
    Fp = F + f_pad
    n_blocks = Fp // f_blk
    n_tiles = (n + n_pad) // tile
    leaf_arr = jnp.asarray(leaf, jnp.int32).reshape(1)

    kernel = functools.partial(_hist_kernel_q, lo_n=lo_n, hi_n=hi_n, m=m,
                               k=k, tile=tile)
    out = pl.pallas_call(
        kernel,
        grid=(n_blocks, n_tiles),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),             # leaf scalar
            pl.BlockSpec((f_blk, tile), lambda f, i: (f, i)),  # bins
            pl.BlockSpec((1, tile), lambda f, i: (0, i)),      # leaf_ids u8
            pl.BlockSpec((2, tile), lambda f, i: (0, i)),      # g/h codes i8
        ],
        out_specs=pl.BlockSpec((k, M, N), lambda f, i: (f, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_blocks * k, M, N), jnp.float32),
        interpret=interpret,
    )(leaf_arr, bins_t, lid, codes)

    hist = radix_epilogue(out, n_blocks * k, m, hi_n, lo_n)
    return hist[:F, :max_bin, :]


def radix_epilogue(out, G: int, m: int, hi_n: int, lo_n: int):
    """Unscramble the [G*M, N] radix-matmul accumulator into [G*m, B, 3]
    histograms: [G, f, 3, hi_n, f', lo_n] -> diagonal f == f' -> transpose.
    Shared by the masked (leaf_histogram) and the segment
    (partition_pallas.segment_histogram) kernels — the two must stay layout
    identical."""
    out = out.reshape(G, m, 3, hi_n, m, lo_n)
    diag = jnp.moveaxis(jnp.diagonal(out, axis1=1, axis2=4), -1, 1)
    return diag.reshape(G * m, 3, hi_n * lo_n).transpose(0, 2, 1)
