"""Single-kernel best-split scan (numerical features) for the grow loops.

The XLA formulation in ops/split.py is ~200 small [F, B] ops per call;
inside the tree-growth while-loop that chain is pure per-op dispatch
latency (~0.45 ms per split pair measured on the round-4 chip — more
than the partition kernel itself).  This kernel computes the SAME
numerical two-direction scan semantics (FindBestThresholdSequentially,
reference src/treelearner/feature_histogram.hpp:437-636) for BOTH
children of a split in ONE Pallas launch:

- children are sublane-stacked: rows = CH*F, lanes = bins;
- inclusive prefix sums via log-step rolls;
- missing-direction enumeration (asc scan only for features with
  missing values, desc always), L1/L2/max_delta_step gain math,
  min_data/min_hessian/min_gain masks, monotone clamp+veto, feature
  penalty, CEGB penalties — bit-for-bit the formulas of ops/split.py;
- tie-breaking preserved: desc beats asc at equal gain, higher
  threshold wins inside desc, lower inside asc (split_info.hpp:131-158).

The categorical path stays in XLA (ops/split.py) — the engines dispatch
here only for all-numerical datasets, which is also the only case the
reference's GPU learner accelerates (gpu_tree_learner.cpp:xxx dense
numerical feature groups).

Outputs ride a [CH*F, 128] f32 block whose first 11 lanes are the
PerFeatureSplit fields; masked gains use a -1e38 sentinel that the
wrapper maps back to K_MIN_SCORE (-inf survives no kernel arithmetic).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.backend import pallas_interpret
from .split import K_EPSILON, K_MIN_SCORE, PerFeatureSplit, SplitParams

NEG = -1e38        # in-kernel "no split" sentinel (python float: a
NEG_GATE = -1e37   # module-level jnp scalar would be a captured const)

# fvec column layout (per-feature statics, [R, 8] f32)
_NB, _DB, _MT, _MONO, _PEN, _FMASK, _CEGBF = range(7)
# svec column layout (per-child scalars, [CH, 8] f32)
_SG, _SH, _ND, _MINC, _MAXC = range(5)
# pvec layout (params, [8] f32 SMEM)
_L1, _L2, _MDS, _MINCNT, _MINH, _MINGAIN, _CEGBS = range(7)
# output lane layout (shared by the per-feature block and the selected
# best-rows: lane 1 holds the feature id so a best-row is a complete,
# directly-scatterable SplitResult record)
(_OG, _OF, _OT, _ODL, _OLG, _OLH, _OLC, _OLO,
 _ORG, _ORH, _ORC, _ORO) = range(12)
ROW_W = 128        # lane width of one packed split row


def _prefix_lanes(x):
    """Inclusive prefix sum along lanes (Hillis-Steele log rolls)."""
    n = x.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    sh = 1
    while sh < n:
        x = x + jnp.where(lane >= sh, pltpu.roll(x, sh, axis=x.ndim - 1), 0.0)
        sh *= 2
    return x


def _split_scan_kernel(pvec_ref, svec_ref, fvec_ref, hist_ref, out_ref,
                       best_ref, *, CH: int, F: int, B: int,
                       blocks: int = 0):
    """blocks > 0: the call is a grid of CH * blocks steps over blocks of
    F features of one child each (`_scan_block`); a step scans its own
    rows, and the cross-feature selection folds its best row into the
    child's row of best_ref, which stays in VMEM across the grid."""
    if blocks:
        step = pl.program_id(0)
        child = step // blocks
        f0 = (step - child * blocks) * F
        CH = 1
    R = CH * F
    l1 = pvec_ref[_L1]
    l2 = pvec_ref[_L2]
    mds = pvec_ref[_MDS]
    min_cnt = jnp.maximum(pvec_ref[_MINCNT], 1.0)
    min_hess = pvec_ref[_MINH]
    min_gain = pvec_ref[_MINGAIN]
    cegb_split = pvec_ref[_CEGBS]

    fv = fvec_ref[:]                                    # [R, 8]
    nb = fv[:, _NB:_NB + 1]
    db = fv[:, _DB:_DB + 1]
    mt = fv[:, _MT:_MT + 1]
    mono = fv[:, _MONO:_MONO + 1]
    pen = fv[:, _PEN:_PEN + 1]
    fmask = fv[:, _FMASK:_FMASK + 1]
    cegb_f = fv[:, _CEGBF:_CEGBF + 1]

    # per-row child scalars: rows [ch*F, (ch+1)*F) take svec[ch] —
    # SMEM permits scalar loads only, so read element-wise and select
    row = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0)

    def per_child(col):
        if blocks:
            return jnp.full((R, 1), 0.0, jnp.float32) + svec_ref[child, col]
        v = jnp.full((R, 1), 0.0, jnp.float32) + svec_ref[0, col]
        for ch in range(1, CH):
            v = jnp.where(row >= ch * F, svec_ref[ch, col], v)
        return v

    sum_g = per_child(_SG)
    sum_h = per_child(_SH) + 2 * K_EPSILON              # hpp:79
    num_data = per_child(_ND)
    minc = per_child(_MINC)
    maxc = per_child(_MAXC)

    bins = jax.lax.broadcasted_iota(jnp.int32, (R, B), 1)
    bins_f = bins.astype(jnp.float32)
    in_range = bins_f < nb
    excl = (((mt == 1.0) & (bins_f == db))
            | ((mt == 2.0) & (bins_f == nb - 1.0))) & in_range & (nb > 2.0)
    live = in_range & ~excl

    G = jnp.where(live, hist_ref[0], 0.0)               # [R, B]
    H = jnp.where(live, hist_ref[1], 0.0)
    Cc = jnp.where(live, hist_ref[2], 0.0)

    pref = _prefix_lanes(jnp.concatenate([G, H, Cc], axis=0))
    cg, ch_, cc = pref[:R], pref[R:2 * R], pref[2 * R:]
    tg, th, tc = cg[:, B - 1:B], ch_[:, B - 1:B], cc[:, B - 1:B]

    def thr_l1(s):
        return jnp.sign(s) * jnp.maximum(0.0, jnp.abs(s) - l1)

    def leaf_out(g, h):
        ret = -thr_l1(g) / (h + l2)
        clipped = jnp.sign(ret) * mds
        use_clip = (mds > 0.0) & (jnp.abs(ret) > mds)
        return jnp.where(use_clip, clipped, ret)

    def gain_given(g, h, out):
        return -(2.0 * thr_l1(g) * out + (h + l2) * out * out)

    # no-split shift from the parent (scalar per row)
    parent_out = leaf_out(sum_g, sum_h)
    min_gain_shift = gain_given(sum_g, sum_h, parent_out) + min_gain

    def eval_dir(lg, lh, lc):
        rg = sum_g - lg
        rh = sum_h - lh
        rc = num_data - lc
        lo = jnp.clip(leaf_out(lg, lh), minc, maxc)
        ro = jnp.clip(leaf_out(rg, rh), minc, maxc)
        gain = gain_given(lg, lh, lo) + gain_given(rg, rh, ro)
        violates = ((mono > 0.0) & (lo > ro)) | ((mono < 0.0) & (lo < ro))
        gain = jnp.where(violates, 0.0, gain)
        valid = ((lc >= min_cnt) & (rc >= min_cnt)
                 & (lh >= min_hess) & (rh >= min_hess))
        return gain, lo, ro, valid, (lg, lh, lc, rg, rh, rc)

    asc = eval_dir(cg, ch_ + K_EPSILON, cc)
    d_rg, d_rh, d_rc = tg - cg, th - ch_ + K_EPSILON, tc - cc
    desc = eval_dir(sum_g - d_rg, sum_h - d_rh, num_data - d_rc)

    thr_ok = bins_f <= nb - 2.0
    asc_ok = thr_ok & (mt != 0.0) & (nb > 2.0)
    desc_ok = thr_ok

    def masked(d, ok):
        gain = d[0]
        valid = d[3]
        return jnp.where(ok & valid & (gain > min_gain_shift), gain, jnp.float32(NEG))

    asc_m = masked(asc, asc_ok)
    desc_m = masked(desc, desc_ok)

    BIG = 1e9
    asc_best = jnp.max(asc_m, axis=1, keepdims=True)
    asc_thr = jnp.min(jnp.where(asc_m == asc_best, bins_f, BIG),
                      axis=1, keepdims=True)             # low θ wins ties
    desc_best = jnp.max(desc_m, axis=1, keepdims=True)
    desc_thr = jnp.max(jnp.where(desc_m == desc_best, bins_f, -BIG),
                       axis=1, keepdims=True)            # high θ wins ties
    use_desc = desc_best >= asc_best                     # desc wins ties
    best_gain = jnp.maximum(desc_best, asc_best)
    best_thr = jnp.where(use_desc, desc_thr, asc_thr)

    oh = jnp.where(bins_f == best_thr, 1.0, 0.0)

    def pick(asc_v, desc_v):
        v = jnp.where(use_desc, desc_v, asc_v)
        # select, don't multiply: unselected lanes may hold inf/NaN from
        # degenerate-bin divisions and NaN*0 would poison the reduction
        return jnp.sum(jnp.where(oh > 0.5, v, 0.0), axis=1, keepdims=True)

    lo_p = pick(asc[1], desc[1])
    ro_p = pick(asc[2], desc[2])
    stats = [pick(a, d) for a, d in zip(asc[4], desc[4])]

    rel = best_gain - min_gain_shift
    rel = rel * pen - cegb_split * num_data - cegb_f
    has = best_gain > NEG_GATE
    feat_gain = jnp.where(has & (rel > 0.0) & (fmask > 0.5), rel, NEG)

    two_bin_nan = (mt == 2.0) & (nb <= 2.0)
    dl = jnp.where(use_desc & ~two_bin_nan, 1.0, 0.0)

    feat_id = (row - (row // F) * F).astype(jnp.float32)
    if blocks:
        feat_id = feat_id + f0.astype(jnp.float32)
    cols = [feat_gain, feat_id, best_thr, dl, stats[0], stats[1], stats[2],
            lo_p, stats[3], stats[4], stats[5], ro_p]
    block = jnp.concatenate(
        cols + [jnp.zeros((R, ROW_W - len(cols)), jnp.float32)], axis=1)
    out_ref[:] = block

    # in-kernel cross-feature selection (select_best_feature): per child,
    # max gain over its F rows, lowest feature id on ties — emitted as a
    # ready-to-scatter [CH, ROW_W] result row for the packed grow state.
    # The gain lane keeps the NEG sentinel when no feature has a valid
    # split (feature lane -1), and the +eps directional hessian bias is
    # removed exactly like select_best_feature.
    best_rows = []
    row_f = row.astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, ROW_W), 1)
    for ch in range(CH):
        in_ch = (row >= ch * F) & (row < (ch + 1) * F)
        mgain = jnp.where(in_ch, feat_gain, jnp.float32(NEG))
        bg = jnp.max(mgain)
        brow = jnp.min(jnp.where(mgain == bg, row_f, jnp.float32(BIG)))
        sel = row_f == brow
        picked = jnp.sum(jnp.where(sel, block, 0.0), axis=0, keepdims=True)
        has = bg > jnp.float32(NEG_GATE)
        # no-valid-split guard: with bg == NEG the tie-break row may be
        # ANOTHER child's (out-of-child rows are also NEG), leaking the
        # sibling's gain/stats into this child's row — mask the whole
        # row back to the no-split sentinel (gain NEG, feature -1)
        picked = jnp.where(has, picked, 0.0)
        picked = jnp.where(lane == _OG,
                           jnp.where(has, picked, jnp.float32(NEG)), picked)
        feat_lane = jnp.where(has, picked[:, _OF:_OF + 1], -1.0)
        picked = jnp.where(lane == _OF, feat_lane, picked)
        picked = jnp.where((lane == _OLH) | (lane == _ORH),
                           picked - jnp.float32(K_EPSILON), picked)
        best_rows.append(picked)
    if not blocks:
        best_ref[:] = jnp.concatenate(best_rows, axis=0)
        return
    # fold into the child's row: a later block wins only with a strictly
    # larger gain, so the lowest feature id keeps a tie, as in one block
    picked, = best_rows
    at = pl.ds(child, 1)

    @pl.when(f0 == 0)
    def _():
        best_ref[at, :] = picked

    @pl.when(f0 > 0)
    def _():
        kept = best_ref[at, :]
        better = picked[:, _OG:_OG + 1] > kept[:, _OG:_OG + 1]
        best_ref[at, :] = jnp.where(better, picked, kept)


# the scan holds about two dozen [rows, lanes(B)] f32 arrays at once:
# 22.61 MB at 2 000 rows of 63 bins, compiled for a v5e
_SCAN_ARRAYS = 24
_SCAN_VMEM = 12 << 20


def _scan_block(CH: int, F: int, B: int) -> tuple:
    """(features per block, blocks per child) of the scan: (F, 0) — one
    step, no grid, what narrow data has always compiled — while all CH * F
    rows fit the kernel's VMEM, else blocks of one child's features, a
    multiple of 8 rows each (the last one padded with masked features)."""
    row_bytes = _SCAN_ARRAYS * 4 * (-(-B // 128) * 128)
    if CH * F * row_bytes <= _SCAN_VMEM:
        return F, 0
    n = -(-F * row_bytes // _SCAN_VMEM)
    return -(-F // (8 * n)) * 8, n


@functools.partial(jax.jit, static_argnames=("interpret",))
def _run_scan(pvec, svec, fvec, hist3, *, interpret: bool):
    CH_F, _ = fvec.shape
    _, R, B = hist3.shape
    CH = svec.shape[0]
    F = R // CH
    Fb, blocks = _scan_block(CH, F, B)
    if blocks:
        # pad each child's features to whole blocks: an all-zero fvec row
        # has feature_mask 0, so its gain is the no-split sentinel
        pad = blocks * Fb - F
        if pad:
            fvec = jnp.pad(fvec.reshape(CH, F, -1),
                           ((0, 0), (0, pad), (0, 0))).reshape(-1, 8)
            hist3 = jnp.pad(hist3.reshape(3, CH, F, B),
                            ((0, 0), (0, 0), (0, pad), (0, 0))
                            ).reshape(3, -1, B)
        kernel = functools.partial(_split_scan_kernel, CH=CH, F=Fb, B=B,
                                   blocks=blocks)
        out, best = pl.pallas_call(
            kernel,
            grid=(CH * blocks,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                      pl.BlockSpec(memory_space=pltpu.SMEM),
                      pl.BlockSpec((Fb, 8), lambda i: (i, 0)),
                      pl.BlockSpec((3, Fb, B), lambda i: (0, i, 0))],
            out_specs=(pl.BlockSpec((Fb, ROW_W), lambda i: (i, 0)),
                       pl.BlockSpec((CH, ROW_W), lambda i: (0, 0))),
            out_shape=(jax.ShapeDtypeStruct((CH * blocks * Fb, ROW_W),
                                            jnp.float32),
                       jax.ShapeDtypeStruct((CH, ROW_W), jnp.float32)),
            interpret=interpret,
        )(pvec, svec, fvec, hist3)
        if pad:
            out = out.reshape(CH, blocks * Fb, ROW_W)[:, :F].reshape(
                R, ROW_W)
        return out, best
    kernel = functools.partial(_split_scan_kernel, CH=CH, F=F, B=B)
    return pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=(pl.BlockSpec(memory_space=pltpu.VMEM),
                   pl.BlockSpec(memory_space=pltpu.VMEM)),
        out_shape=(jax.ShapeDtypeStruct((R, ROW_W), jnp.float32),
                   jax.ShapeDtypeStruct((CH, ROW_W), jnp.float32)),
        interpret=interpret,
    )(pvec, svec, fvec, hist3)


def index_per_feature(pf: PerFeatureSplit, i: int) -> PerFeatureSplit:
    """[CH, F]-batched PerFeatureSplit -> child i's [F] view."""
    return PerFeatureSplit(*[None if v is None else v[i] for v in pf])


def build_feature_statics(num_bins, default_bins, missing_types,
                          monotone=None, penalty=None, feature_mask=None,
                          cegb_feature_penalty=None, children: int = 2):
    """[CH*F, 8] f32 per-feature static matrix for best_splits_pallas —
    build ONCE per tree (outside the grow while-loop) and thread through;
    only feature_mask changes between trees."""
    F = num_bins.shape[0]
    z = jnp.zeros(F, jnp.float32)
    cols = [num_bins.astype(jnp.float32),
            default_bins.astype(jnp.float32),
            missing_types.astype(jnp.float32),
            z if monotone is None else monotone.astype(jnp.float32),
            jnp.ones(F, jnp.float32) if penalty is None
            else penalty.astype(jnp.float32),
            jnp.ones(F, jnp.float32) if feature_mask is None
            else feature_mask.astype(jnp.float32),
            z if cegb_feature_penalty is None
            else cegb_feature_penalty.astype(jnp.float32),
            z]
    one = jnp.stack(cols, axis=1)                       # [F, 8]
    return jnp.concatenate([one] * children, axis=0)


def _pack_inputs(hist, sum_g, sum_h, num_data, min_constraints,
                 max_constraints, params: SplitParams,
                 quant_scales=None):
    """(pvec, svec, hist3) shared by both kernel entry points — ONE place
    owns the lane layouts (_SG.._MAXC / _L1.._CEGBS).

    quant_scales=(g_scale, h_scale) accepts CODE-domain histograms and
    sums (integer code sums from ops/quantize) and folds the dequantize
    multiply into this pack pass, so the scan itself always runs on real
    g/h values: leaf outputs recover as -(Σg_code·gs) / (Σh_code·hs + λ)
    — float64-exact functions of the integer sums within the
    qz.exact_rows() envelope, one rounding per scale multiply.  The
    partition grow loop instead dequantizes each histogram as it leaves
    its kernel (grow_partition `deq`): cached, psum'd and
    sibling-subtracted histograms there mix with REAL-domain sums read
    back from earlier scan outputs, so a single domain everywhere beats
    saving one [F, B, 3] multiply."""
    CH, F, B, _ = hist.shape
    f32 = jnp.float32
    hist3 = jnp.moveaxis(hist.astype(f32), 3, 0).reshape(3, CH * F, B)
    if quant_scales is not None:
        gs = jnp.asarray(quant_scales[0], f32)
        hs = jnp.asarray(quant_scales[1], f32)
        hist3 = hist3 * jnp.stack([gs, hs, jnp.float32(1.0)])[:, None, None]
        sum_g = jnp.asarray(sum_g, f32) * gs
        sum_h = jnp.asarray(sum_h, f32) * hs
    ninf = jnp.full((CH,), -jnp.inf, f32)
    pinf = jnp.full((CH,), jnp.inf, f32)
    svec = jnp.stack([
        jnp.asarray(sum_g, f32).reshape(CH),
        jnp.asarray(sum_h, f32).reshape(CH),
        jnp.asarray(num_data, f32).reshape(CH),
        (ninf if min_constraints is None
         else jnp.asarray(min_constraints, f32).reshape(CH)),
        (pinf if max_constraints is None
         else jnp.asarray(max_constraints, f32).reshape(CH)),
        jnp.zeros(CH, f32), jnp.zeros(CH, f32), jnp.zeros(CH, f32)],
        axis=1)                                         # [CH, 8]
    pvec = jnp.stack([
        jnp.asarray(params.lambda_l1, f32),
        jnp.asarray(params.lambda_l2, f32),
        jnp.asarray(params.max_delta_step, f32),
        jnp.asarray(params.min_data_in_leaf, f32),
        jnp.asarray(params.min_sum_hessian_in_leaf, f32),
        jnp.asarray(params.min_gain_to_split, f32),
        jnp.asarray(params.cegb_split_penalty, f32)] + [jnp.float32(0.0)])
    return pvec, svec, hist3


def best_splits_pallas(hist,            # [CH, F, B, 3]
                       sum_g, sum_h, num_data,          # [CH] each
                       fvec,            # [CH*F, 8] from build_feature_statics
                       params: SplitParams,
                       min_constraints=None, max_constraints=None,  # [CH]
                       quant_scales=None,
                       interpret: bool = False) -> PerFeatureSplit:
    """Numerical best split per feature for CH children in one kernel
    launch.  Returns a PerFeatureSplit with [CH, F] fields (cat_mask
    None) matching ops/split.py best_split_per_feature vmapped over
    children, up to f32 prefix-sum association order.

    NOTE: counts ride f32 prefix sums in-kernel — exact only for
    num_data < 2^24; callers gate on that (the same bound as the
    partition engine's rowid planes)."""
    CH, F, B, _ = hist.shape
    pvec, svec, hist3 = _pack_inputs(hist, sum_g, sum_h, num_data,
                                     min_constraints, max_constraints,
                                     params, quant_scales=quant_scales)
    out, _ = _run_scan(pvec, svec, fvec, hist3, interpret=interpret)
    out = out.reshape(CH, F, ROW_W)
    gain = out[..., _OG]
    gain = jnp.where(gain <= NEG_GATE, K_MIN_SCORE, gain)
    return PerFeatureSplit(
        gain=gain,
        threshold=out[..., _OT].astype(jnp.int32),
        default_left=out[..., _ODL] > 0.5,
        left_sum_gradient=out[..., _OLG],
        left_sum_hessian=out[..., _OLH],
        left_count=jnp.round(out[..., _OLC]).astype(jnp.int32),
        left_output=out[..., _OLO],
        right_sum_gradient=out[..., _ORG],
        right_sum_hessian=out[..., _ORH],
        right_count=jnp.round(out[..., _ORC]).astype(jnp.int32),
        right_output=out[..., _ORO],
    )


def best_split_rows_pallas(hist, sum_g, sum_h, num_data, fvec,
                           params: SplitParams,
                           min_constraints=None, max_constraints=None,
                           quant_scales=None,
                           interpret: bool = False):
    """[CH, ROW_W] packed best-split rows (lane layout _O*): the kernel's
    in-kernel select_best_feature output, ready to scatter into the
    packed split cache of the grow loop.  gain lane uses the NEG
    sentinel (compare against NEG_GATE), feature lane is -1 when no
    valid split."""
    pvec, svec, hist3 = _pack_inputs(hist, sum_g, sum_h, num_data,
                                     min_constraints, max_constraints,
                                     params, quant_scales=quant_scales)
    _, best = _run_scan(pvec, svec, fvec, hist3, interpret=interpret)
    return best


def pack_split_row(res, cat_width: int = 0):
    """SplitResult -> [ROW_W (+cat_width)] packed row (XLA fallback used
    by the categorical/mixed path and forced splits; keeps K_MIN_SCORE
    gains as-is — any gain <= NEG_GATE means no split)."""
    f32 = jnp.float32
    vals = [jnp.asarray(res.gain, f32), jnp.asarray(res.feature, f32),
            jnp.asarray(res.threshold, f32),
            jnp.asarray(res.default_left, f32),
            jnp.asarray(res.left_sum_gradient, f32),
            jnp.asarray(res.left_sum_hessian, f32),
            jnp.asarray(res.left_count, f32),
            jnp.asarray(res.left_output, f32),
            jnp.asarray(res.right_sum_gradient, f32),
            jnp.asarray(res.right_sum_hessian, f32),
            jnp.asarray(res.right_count, f32),
            jnp.asarray(res.right_output, f32)]
    row = jnp.zeros(ROW_W + cat_width, f32)
    row = row.at[:12].set(jnp.stack(vals))
    if cat_width:
        row = row.at[ROW_W:].set(jnp.asarray(res.cat_mask, f32))
    return row

def scan_single(hist, sum_g, sum_h, cnt, params: SplitParams,
                fvec_pre=None, num_bins=None, default_bins=None,
                missing_types=None, monotone=None, penalty=None,
                feature_mask=None, cegb_pen=None, mn=None, mx=None,
                interpret=None) -> PerFeatureSplit:
    """One-child kernel dispatch shared by the serial/feature-parallel
    and voting scans in ops/grow.py — the two call sites must stay
    bit-identical (voting elects against serial gains) so the argument
    massaging lives HERE once."""
    if interpret is None:
        interpret = pallas_interpret()
    if fvec_pre is not None:
        fvec = fvec_pre
    else:
        fvec = build_feature_statics(
            num_bins, default_bins, missing_types, monotone=monotone,
            penalty=penalty, feature_mask=feature_mask, children=1)
    if cegb_pen is not None:
        fvec = fvec.at[:, _CEGBF].set(cegb_pen.astype(jnp.float32))
    pf = best_splits_pallas(
        hist[None], jnp.reshape(sum_g, (1,)), jnp.reshape(sum_h, (1,)),
        jnp.reshape(cnt, (1,)), fvec, params,
        min_constraints=None if mn is None else mn[:1],
        max_constraints=None if mx is None else mx[:1],
        interpret=interpret)
    return index_per_feature(pf, 0)
