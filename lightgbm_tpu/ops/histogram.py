"""Gradient/hessian/count histograms over the binned feature matrix.

The TPU replacement for the reference's histogram construction hot loop
(src/io/dense_bin.hpp:105-185, dataset.cpp:760-949 ConstructHistograms and
the OpenCL kernels in src/treelearner/ocl/): per-leaf histograms are built by
one pass over the row-sharded bin matrix.  Rows are selected by a leaf-label
vector (`row→leaf`), not by the reference's reordered index array — masking
keeps shapes static for XLA.

Implementations (select via Config.tpu_histogram_impl):
- "onehot": chunked one-hot × (g,h,1) matmul — rides the MXU, the TPU-native
  choice (mirrors what the OpenCL kernels do with local-memory atomics).
- "scatter": jnp scatter-add — best on CPU backends / small data; also the
  all-leaves variant used for root and level-batched growth.
- "auto": scatter on CPU, onehot on TPU.

All accumulate in f32 by default; pass f64 arrays for the gpu_use_dp
analogue (Config.tpu_double_precision).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..utils import log
from ..utils.backend import on_tpu, pallas_interpret


def _gh1(grad, hess, mask, dtype):
    m = mask.astype(dtype)
    return jnp.stack([grad.astype(dtype) * m, hess.astype(dtype) * m, m], axis=-1)


def leaf_histogram_scatter(bins, grad, hess, leaf_ids, leaf,
                           max_bin: int) -> jnp.ndarray:
    """[F, B, 3] histogram of rows with leaf_ids == leaf via scatter-add."""
    n, F = bins.shape
    dtype = grad.dtype
    mask = leaf_ids == leaf
    gh1 = _gh1(grad, hess, mask, dtype)                       # [n, 3]
    flat_idx = bins.astype(jnp.int32) + (jnp.arange(F, dtype=jnp.int32) * max_bin)[None, :]
    out = jnp.zeros((F * max_bin, 3), dtype=dtype)
    # one scatter per row-feature pair; values broadcast over features
    out = out.at[flat_idx.reshape(-1)].add(
        jnp.repeat(gh1, F, axis=0).reshape(n * F, 3))
    return out.reshape(F, max_bin, 3)


def leaf_histogram_onehot(bins, grad, hess, leaf_ids, leaf,
                          max_bin: int, rows_per_chunk: int = 16384) -> jnp.ndarray:
    """[F, B, 3] histogram via chunked one-hot contraction on the MXU.

    Per chunk: onehot[n_c, F, B] contracted with gh1[n_c, 3] over rows —
    a [F*B, n_c] x [n_c, 3] matmul after reshape.
    """
    n, F = bins.shape
    dtype = grad.dtype
    mask = (leaf_ids == leaf)
    gh1 = _gh1(grad, hess, mask, dtype)                       # [n, 3]

    pad = (-n) % rows_per_chunk
    if pad:
        bins = jnp.pad(bins, ((0, pad), (0, 0)))
        gh1 = jnp.pad(gh1, ((0, pad), (0, 0)))
    n_chunks = (n + pad) // rows_per_chunk
    bins_c = bins.reshape(n_chunks, rows_per_chunk, F)
    gh1_c = gh1.reshape(n_chunks, rows_per_chunk, 3)

    def body(acc, chunk):
        b, g = chunk
        onehot = jax.nn.one_hot(b, max_bin, dtype=dtype)      # [rows, F, B]
        # HIGHEST: TPU einsum otherwise rounds the f32 payloads to bf16
        # MXU passes (~0.5% histogram error -> wrong recorded gains)
        acc = acc + jnp.einsum("rfb,rc->fbc", onehot, g,
                               preferred_element_type=dtype,
                               precision=jax.lax.Precision.HIGHEST)
        return acc, None

    init = jnp.zeros((F, max_bin, 3), dtype=dtype)
    acc, _ = jax.lax.scan(body, init, (bins_c, gh1_c))
    return acc


def all_leaves_histogram(bins, grad, hess, leaf_ids, num_leaves: int,
                         max_bin: int) -> jnp.ndarray:
    """[L, F, B, 3] histograms for every leaf in one scatter pass (root /
    level-batched growth; rows with leaf_ids outside [0, L) are dropped)."""
    n, F = bins.shape
    dtype = grad.dtype
    in_range = (leaf_ids >= 0) & (leaf_ids < num_leaves)
    gh1 = _gh1(grad, hess, in_range, dtype)
    leaf_c = jnp.clip(leaf_ids, 0, num_leaves - 1).astype(jnp.int32)
    flat_idx = (leaf_c[:, None] * (F * max_bin)
                + jnp.arange(F, dtype=jnp.int32)[None, :] * max_bin
                + bins.astype(jnp.int32))
    out = jnp.zeros((num_leaves * F * max_bin, 3), dtype=dtype)
    out = out.at[flat_idx.reshape(-1)].add(
        jnp.repeat(gh1, F, axis=0).reshape(n * F, 3))
    return out.reshape(num_leaves, F, max_bin, 3)


def leaf_histogram_compact(bins, grad, hess, leaf_ids, leaf,
                           max_bin: int, tile: int = 16384) -> jnp.ndarray:
    """[F, B, 3] histogram touching only the leaf's rows.

    The TPU answer to the reference's ordered-index partition
    (data_partition.hpp:17-222 + dense_bin.hpp:105-185): the leaf's row
    indices are compacted into a prefix of an index buffer (cumsum +
    scatter, O(n) vector work), then a lax.while_loop with a *data-dependent
    trip count* of ceil(leaf_rows/tile) iterations gathers each tile and
    accumulates its histogram.  Per-tree work drops from
    O(num_leaves * n * F) to O(sum of smaller-child sizes * F) ~=
    O(n * depth * F) — the same asymptotics as the reference's
    smaller-leaf scheduling.
    """
    n, F = bins.shape
    dtype = grad.dtype
    mask = leaf_ids == leaf
    gh1 = _gh1(grad, hess, mask, dtype)                       # [n, 3]

    pos = jnp.cumsum(mask.astype(jnp.int32))
    count = pos[-1]
    # idx[0:count] = member rows; the rest point at the zero dummy row n
    idx = jnp.full(n + tile, n, jnp.int32)
    idx = idx.at[jnp.where(mask, pos - 1, n + tile)].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop")
    bins_p = jnp.pad(bins, ((0, 1), (0, 0)))                  # dummy row -> bin 0
    gh1_p = jnp.pad(gh1, ((0, 1), (0, 0)))                    # dummy row -> 0

    def body(carry):
        i, acc = carry
        sl = jax.lax.dynamic_slice(idx, (i * tile,), (tile,))
        bb = jnp.take(bins_p, sl, axis=0)                     # [T, F]
        gg = jnp.take(gh1_p, sl, axis=0)                      # [T, 3]
        onehot = jax.nn.one_hot(bb, max_bin, dtype=dtype)     # [T, F, B]
        acc = acc + jnp.einsum("rfb,rc->fbc", onehot, gg,
                               preferred_element_type=dtype,
                               precision=jax.lax.Precision.HIGHEST)
        return i + 1, acc

    init = (jnp.asarray(0, jnp.int32), jnp.zeros((F, max_bin, 3), dtype))
    _, acc = jax.lax.while_loop(lambda c: c[0] * tile < count, body, init)
    return acc


def leaf_histogram(bins, grad, hess, leaf_ids, leaf,
                   max_bin: int, impl: str = "auto",
                   rows_per_chunk: int = 16384) -> jnp.ndarray:
    if impl == "pallas":
        if max_bin <= 256 and bins.dtype == jnp.uint8:
            from . import histogram_pallas
            return histogram_pallas.leaf_histogram(
                bins, grad, hess, leaf_ids, leaf, max_bin,
                interpret=pallas_interpret())
        log.warning("Pallas histogram kernel needs uint8 bins and "
                    "max_bin <= 256; falling back to onehot")
        impl = "onehot"
    if impl == "auto":
        impl = "compact" if on_tpu() else "scatter"
    if impl == "scatter":
        return leaf_histogram_scatter(bins, grad, hess, leaf_ids, leaf, max_bin)
    if impl == "onehot":
        return leaf_histogram_onehot(bins, grad, hess, leaf_ids, leaf,
                                     max_bin, rows_per_chunk)
    if impl == "compact":
        return leaf_histogram_compact(bins, grad, hess, leaf_ids, leaf,
                                      max_bin, rows_per_chunk)
    raise ValueError("unknown histogram impl: %s" % impl)


def subtract(parent_hist: jnp.ndarray, child_hist: jnp.ndarray) -> jnp.ndarray:
    """Sibling histogram by subtraction (FeatureHistogram::Subtract,
    feature_histogram.hpp:67-73) — the communication/work saver."""
    return parent_hist - child_hist
