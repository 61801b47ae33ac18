"""Gradient/hessian quantization for histogram training.

LightGBM's quantized-training mode ("Quantized Training of Gradient
Boosting Decision Trees", NeurIPS 2022) observes that histogram
construction is bandwidth-bound and that low-bit gradient codes keep
split quality when gradients are STOCHASTICALLY rounded (the rounding
noise stays zero-mean, so bin sums are unbiased estimates).  Here int8
buys BYTES, not FLOPs: one packed payload plane instead of two float
planes per row (docs/Quantized.md; what the chip measured for the
histogram kernels is in PERF.md).

Codes here are int8 in [-127, 127] with ONE scale per (tree, g|h):

    g_code = stochastic_round(g / g_scale),   g_scale = max|g| / 127
    h_code = nearest_round(h / h_scale),      h_scale = max h  / 127

Histogram kernels accumulate the integer codes (plus a count plane) in
f32, which is EXACT while every partial sum stays below 2^24 — the
bin-count-aware envelope `exact_rows()` reports.  Within that envelope
recovered bin sums `code_sum * scale` are float64-exact functions of
the integer sums, so sibling subtraction and leaf-output recovery lose
nothing beyond the initial rounding itself.

Stochastic rounding uses `jax.random` (threefry) with a key folded from
(tpu_quantized_seed or seed, iteration) — a pure function of restored
trainer state, so checkpoint kill-and-resume is bitwise identical.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# int8 code range is symmetric [-127, 127]: reserving -128 keeps the
# negation of every code representable (sibling subtraction in code
# space) and matches LightGBM's grad_quant convention.
CODE_MAX = 127

# f32 accumulates integers exactly below 2^24; a single bin's |code sum|
# is bounded by CODE_MAX * rows_in_bin, so this many rows in ONE bin is
# the worst-case exactness envelope.
_F32_EXACT = 1 << 24


def exact_rows(bits: int = 8) -> int:
    """Max rows a single histogram bin may hold with the integer code
    sums still exactly representable in the f32 accumulator (the
    bin-count-aware overflow guard: occupancy of the fullest bin, not
    the bin count, is what bounds exactness)."""
    code_max = (1 << (bits - 1)) - 1
    return _F32_EXACT // code_max


def overflow_safe(segment_rows: int, bits: int = 8) -> bool:
    """True when a segment of `segment_rows` rows cannot overflow the
    integer-exactness envelope even if every row lands in one bin."""
    return int(segment_rows) <= exact_rows(bits)


def quantize_gradients(grad, hess, key):
    """(g_code, h_code, g_scale, h_scale): int8-valued f32 codes plus the
    per-call scales.

    Gradients are stochastically rounded (unbiased — split gains stay
    unbiased estimates of the f32 gains); hessians are deterministically
    rounded to nearest (they sit in denominators, where zero-mean noise
    does NOT cancel).  Codes are returned as f32 arrays holding exact
    small integers so they can be cast losslessly to the bf16 arena
    payload planes (bf16 represents every integer up to 256 exactly).
    """
    with jax.named_scope("lgbm.quantize"):
        g = jnp.asarray(grad, jnp.float32)
        h = jnp.asarray(hess, jnp.float32)
        g_scale = jnp.maximum(jnp.max(jnp.abs(g)), 1e-30) / CODE_MAX
        h_scale = jnp.maximum(jnp.max(jnp.abs(h)), 1e-30) / CODE_MAX
        u = jax.random.uniform(key, g.shape, jnp.float32)
        g_code = jnp.clip(jnp.floor(g / g_scale + u), -CODE_MAX, CODE_MAX)
        h_code = jnp.clip(jnp.round(h / h_scale), -CODE_MAX, CODE_MAX)
    return g_code, h_code, g_scale, h_scale


def quantize_key(seed: int, iteration) -> jax.Array:
    """Stochastic-rounding key for one boosting iteration — a pure
    function of (config seed, iteration index) so a resumed run draws
    the identical rounding noise."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              jnp.asarray(iteration, jnp.int32))


def dequantize_hist(hist_code, g_scale, h_scale):
    """Recover f32 (g, h, count) histograms from integer code sums.

    hist_code [..., 3] carries (sum g_code, sum h_code, count); the
    count plane is already exact.  Within the exact_rows() envelope the
    code sums are exact integers, so this multiply IS the float64-exact
    recovery (one rounding per bin, from the scale multiply itself).
    """
    scale = jnp.stack([jnp.asarray(g_scale, jnp.float32),
                       jnp.asarray(h_scale, jnp.float32),
                       jnp.float32(1.0)])
    return hist_code.astype(jnp.float32) * scale


def global_scales(grad, hess, collective):
    """(g_scale, h_scale) agreed across the collective's world.

    The distributed hazard this solves: integer histograms only psum
    correctly when every rank encodes with the SAME scale, but each
    rank sees only its shard's maxima.  One extra allreduce-max of the
    two per-tree maxima (ISSUE's "one extra psum" — any symmetric
    combine agrees across ranks; max keeps the code range tight)
    before encoding makes the scales global, after which the summed
    codes are exactly what a single encoder would have produced.

    Under the single-controller mesh backend host values are already
    global, so this degenerates to the serial computation — which is
    exactly why mesh quantized training is bitwise-identical to serial.
    """
    g = jnp.asarray(grad, jnp.float32)
    h = jnp.asarray(hess, jnp.float32)
    local = jnp.stack([jnp.max(jnp.abs(g)), jnp.max(jnp.abs(h))])
    agreed = collective.allreduce(local, "max") if collective is not None \
        else local
    agreed = jnp.asarray(agreed, jnp.float32)
    g_scale = jnp.maximum(agreed[0], 1e-30) / CODE_MAX
    h_scale = jnp.maximum(agreed[1], 1e-30) / CODE_MAX
    return g_scale, h_scale


def encode_with_scales(grad, hess, key, g_scale, h_scale,
                       global_rows=None, row_start=0, row_ids=None):
    """(g_code, h_code) encoded with GIVEN (globally-agreed) scales.

    When this rank holds rows [row_start, row_start+n) of a
    `global_rows`-row dataset, the stochastic-rounding noise is drawn
    from the GLOBAL uniform stream and sliced — so the union of every
    rank's codes is bitwise what a single encoder drawing
    uniform(key, (global_rows,)) would produce, and distributed
    quantized training matches serial bit-for-bit (the
    kill-and-resume invariant extends across world sizes).

    `row_ids` covers NON-contiguous partitions (pre_partition_rows'
    random per-row draw): the noise is gathered at this rank's global
    row indices instead of a contiguous slice.
    """
    g = jnp.asarray(grad, jnp.float32)
    h = jnp.asarray(hess, jnp.float32)
    if row_ids is not None:
        u = jax.random.uniform(key, (int(global_rows),),
                               jnp.float32)[jnp.asarray(row_ids, jnp.int32)]
    elif global_rows is None:
        u = jax.random.uniform(key, g.shape, jnp.float32)
    else:
        u = jax.lax.dynamic_slice_in_dim(
            jax.random.uniform(key, (int(global_rows),), jnp.float32),
            int(row_start), g.shape[0])
    g_code = jnp.clip(jnp.floor(g / g_scale + u), -CODE_MAX, CODE_MAX)
    h_code = jnp.clip(jnp.round(h / h_scale), -CODE_MAX, CODE_MAX)
    return g_code, h_code
