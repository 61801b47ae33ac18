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


def _gain_math(l1, l2, mds):
    """(leaf_out, gain_given) of ops/split.py for the L1 / L2 /
    max_delta_step scalars of a scan: shared by both scan kernels."""
    def thr_l1(s):
        return jnp.sign(s) * jnp.maximum(0.0, jnp.abs(s) - l1)

    def leaf_out(g, h):
        ret = -thr_l1(g) / (h + l2)
        clipped = jnp.sign(ret) * mds
        use_clip = (mds > 0.0) & (jnp.abs(ret) > mds)
        return jnp.where(use_clip, clipped, ret)

    def gain_given(g, h, out):
        return -(2.0 * thr_l1(g) * out + (h + l2) * out * out)

    return leaf_out, gain_given


def _direction(leaf_out, gain_given, sum_g, sum_h, num_data, minc, maxc,
               mono, min_cnt, min_hess):
    """eval_dir(left sums) -> (gain, left output, right output, valid,
    the six sums) of one scan direction: shared by both scan kernels."""
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

    return eval_dir


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

    leaf_out, gain_given = _gain_math(l1, l2, mds)

    # no-split shift from the parent (scalar per row)
    parent_out = leaf_out(sum_g, sum_h)
    min_gain_shift = gain_given(sum_g, sum_h, parent_out) + min_gain

    eval_dir = _direction(leaf_out, gain_given, sum_g, sum_h, num_data,
                          minc, maxc, mono, min_cnt, min_hess)

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


# ---- the scan in group space (EFB-bundled data sets) --------------------
# lane statics of a bundled data set (`group_lane_statics`): int32 planes
# [_NLANE, Gp, Bp] over (group column, group bin)
_LOWN, _LTHR, _LREL, _LRELR, _LFLAGS = range(5)
_NLANE = 5
# flag bits of plane _LFLAGS
_F_LIVE = 1        # the lane's value counts in prefix sums and totals
_F_EXCL = 2        # counted in the owner's lane total only (its NaN bin)
_F_CAND = 4        # the lane stands for a threshold (owner bin - 1)
_F_ASC = 8         # ... which the ascending scan may take too
_F_FIXL = 16       # the owner's rebuilt default bin lies left of it
_F_FIXR = 32       # ... or right of it
_F_NAN2 = 64       # owner has two bins, one of them NaN: never default-left
# per-tree planes [_NTREE, Gp, Bp] f32 (`group_lane_planes`)
_TMONO, _TPEN, _TMASK, _TCEGB = range(4)
_NTREE = 4


def group_lane_statics(groups, feature_lo, feature_hi, feature_shift,
                       needs_fix, num_bins, default_bins, missing_types,
                       lanes: int):
    """Host-side, once per data set: what the group-space scan knows of
    every (group column, group bin) lane, as int32 [_NLANE, Gp, Bp]
    (Gp: groups padded to 8 rows, Bp: `lanes` padded to 128).

    A feature owns the lanes [lo, hi) of its group's row; lane l holds the
    feature's bin l - shift.  A bundled feature (needs_fix) has no lane for
    its default bin (default bin 0), or an empty one (the hole): that
    bin's entry is the leaf's total less the feature's lanes, as
    Dataset::FixHistogram and ops/grow.unbundle_hist rebuild it.  The
    threshold t = bin - 1 is judged on lane `bin`: its right side is what
    lies on this lane and above it, its left side the rest."""
    import numpy as np
    G = len(groups)
    Gp = -(-G // 8) * 8
    Bp = -(-int(lanes) // 128) * 128
    out = np.zeros((_NLANE, Gp, Bp), np.int32)
    out[_LOWN] = -1
    lane = np.arange(Bp)
    for g, feats in enumerate(groups):
        for f in feats:
            lo, hi = int(feature_lo[f]), int(feature_hi[f])
            nb, db = int(num_bins[f]), int(default_bins[f])
            mt, fix = int(missing_types[f]), bool(needs_fix[f])
            sl = slice(lo, hi)
            fb = lane[sl] - int(feature_shift[f])        # feature bins
            wide = nb > 2
            excluded = wide & (((mt == 1) & (fb == db))
                               | ((mt == 2) & (fb == nb - 1)))
            hole = fix & (fb == db)
            default_live = fix and not (wide and mt == 1)
            cand = fb >= 1
            flags = (np.where(~excluded & ~hole, _F_LIVE, 0)
                     | np.where(excluded & ~hole, _F_EXCL, 0)
                     | np.where(cand, _F_CAND, 0)
                     | np.where(cand & (mt != 0) & wide, _F_ASC, 0)
                     | np.where(default_live & (db <= fb - 1), _F_FIXL, 0)
                     | np.where(default_live & (db > fb - 1), _F_FIXR, 0)
                     | (_F_NAN2 if (mt == 2 and not wide) else 0))
            out[_LOWN, g, sl] = f
            out[_LTHR, g, sl] = fb - 1
            out[_LREL, g, sl] = lane[sl] - lo
            out[_LRELR, g, sl] = hi - 1 - lane[sl]
            out[_LFLAGS, g, sl] = flags
    return out


def group_lane_planes(lanes, monotone=None, penalty=None, feature_mask=None,
                      cegb_feature_penalty=None):
    """[_NTREE, Gp, Bp] f32: the per-feature vectors that may change from
    tree to tree (or, CEGB, from split to split), spread over the lanes of
    their owners — the group-space counterpart of `build_feature_statics`'
    monotone / penalty / mask / CEGB columns."""
    own = lanes[_LOWN]
    owned = own >= 0
    at = jnp.maximum(own, 0)

    def spread(vec, none, unowned):
        if vec is None:
            return jnp.where(owned, jnp.float32(none), jnp.float32(unowned))
        return jnp.where(owned, vec.astype(jnp.float32)[at],
                         jnp.float32(unowned))

    return jnp.stack([spread(monotone, 0.0, 0.0), spread(penalty, 1.0, 1.0),
                      spread(feature_mask, 1.0, 0.0),
                      spread(cegb_feature_penalty, 0.0, 0.0)])


def _segmented_sums(x, rel, towards_higher: bool):
    """Inclusive running sums along lanes that restart at every owner's
    first lane (`rel` = lane - first lane of the owner) or, downwards, at
    its last (`rel` = last lane - lane): `_prefix_lanes` with the roll
    masked to lanes of the same owner."""
    n = x.shape[-1]
    sh = 1
    while sh < n:
        moved = pltpu.roll(x, sh if towards_higher else n - sh,
                           axis=x.ndim - 1)
        x = x + jnp.where(rel >= sh, moved, 0.0)
        sh *= 2
    return x


def _group_scan_kernel(pvec_ref, svec_ref, lanes_ref, tree_ref, hist_ref,
                       best_ref, *, CH: int, G: int, B: int,
                       blocks: int = 0):
    """The scan of `_split_scan_kernel` on the bundled histogram itself:
    rows = group columns (of CH children), lanes = group bins, and a
    candidate threshold on every lane a feature owns.  The sums of a
    feature's bins are segmented sums over its lanes, its default bin is
    the leaf's total less its lanes, and the gain, the limits, both scan
    directions and the order among equal gains are the feature-space
    kernel's, evaluated per lane.  Only the packed best row per child is
    produced.  blocks > 0: a grid over blocks of G group rows of one child,
    folded into best_ref as in `_split_scan_kernel`."""
    if blocks:
        step = pl.program_id(0)
        child = step // blocks
        first_block = step - child * blocks == 0
        CH = 1
    R = CH * G
    f32 = jnp.float32
    l1 = pvec_ref[_L1]
    l2 = pvec_ref[_L2]
    mds = pvec_ref[_MDS]
    min_cnt = jnp.maximum(pvec_ref[_MINCNT], 1.0)
    min_hess = pvec_ref[_MINH]
    min_gain = pvec_ref[_MINGAIN]
    cegb_split = pvec_ref[_CEGBS]

    def tiled(ref, k):
        one = ref[k]
        return one if CH == 1 else jnp.concatenate([one] * CH, axis=0)

    own = tiled(lanes_ref, _LOWN).astype(f32)
    thr = tiled(lanes_ref, _LTHR).astype(f32)
    rel = tiled(lanes_ref, _LREL)
    relr = tiled(lanes_ref, _LRELR)
    flags = tiled(lanes_ref, _LFLAGS)
    mono = tiled(tree_ref, _TMONO)
    pen = tiled(tree_ref, _TPEN)
    fmask = tiled(tree_ref, _TMASK)
    cegb_f = tiled(tree_ref, _TCEGB)

    def flag(bit):
        return (flags & bit) != 0

    row = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0)

    def per_child(col):
        if blocks:
            return jnp.full((R, 1), 0.0, f32) + svec_ref[child, col]
        v = jnp.full((R, 1), 0.0, f32) + svec_ref[0, col]
        for ch in range(1, CH):
            v = jnp.where(row >= ch * G, svec_ref[ch, col], v)
        return v

    sum_g = per_child(_SG)
    tot_h = per_child(_SH)
    sum_h = tot_h + 2 * K_EPSILON                       # hpp:79
    num_data = per_child(_ND)
    minc = per_child(_MINC)
    maxc = per_child(_MAXC)

    live, excl = flag(_F_LIVE), flag(_F_EXCL)
    M = jnp.concatenate([jnp.where(live, hist_ref[k], 0.0)
                         for k in range(3)], axis=0)    # [3R, B]
    E = jnp.concatenate([jnp.where(excl, hist_ref[k], 0.0)
                         for k in range(3)], axis=0)
    rel3 = jnp.concatenate([rel] * 3, axis=0)
    relr6 = jnp.concatenate([relr] * 6, axis=0)
    P = _segmented_sums(M, rel3, True)                  # bins <= this lane's
    QE = _segmented_sums(jnp.concatenate([M, E], axis=0), relr6, False)
    Q, Qe = QE[:3 * R], QE[3 * R:]                      # bins >= this lane's
    # the owner's rebuilt default bin: the leaf's total less all its lanes
    fixv = (jnp.concatenate([sum_g, tot_h, num_data], axis=0)
            - (P + Q - M + Qe))
    fixl3 = jnp.concatenate([flag(_F_FIXL)] * 3, axis=0)
    fixr3 = jnp.concatenate([flag(_F_FIXR)] * 3, axis=0)
    left = P - M + jnp.where(fixl3, fixv, 0.0)          # bins <= threshold
    right = Q + jnp.where(fixr3, fixv, 0.0)             # live bins above it
    cg, ch_, cc = left[:R], left[R:2 * R], left[2 * R:]
    d_rg, d_rh, d_rc = right[:R], right[R:2 * R] + K_EPSILON, right[2 * R:]

    leaf_out, gain_given = _gain_math(l1, l2, mds)

    parent_out = leaf_out(sum_g, sum_h)
    min_gain_shift = gain_given(sum_g, sum_h, parent_out) + min_gain

    eval_dir = _direction(leaf_out, gain_given, sum_g, sum_h, num_data,
                          minc, maxc, mono, min_cnt, min_hess)

    asc = eval_dir(cg, ch_ + K_EPSILON, cc)
    desc = eval_dir(sum_g - d_rg, sum_h - d_rh, num_data - d_rc)

    def masked(d, ok):
        return jnp.where(ok & d[3] & (d[0] > min_gain_shift), d[0], f32(NEG))

    raw = (masked(asc, flag(_F_ASC)), masked(desc, flag(_F_CAND)))

    def scored(r):
        # a feature's gain as the feature-space kernel reports it: the
        # same function of its best raw gain, here of every candidate's
        s = (r - min_gain_shift) * pen - cegb_split * num_data - cegb_f
        return jnp.where((r > NEG_GATE) & (s > 0.0) & (fmask > 0.5), s,
                         f32(NEG))

    score = (scored(raw[0]), scored(raw[1]))
    BIG = 1e9
    lane_out = jax.lax.broadcasted_iota(jnp.int32, (1, ROW_W), 1)

    def over(x, reduce, where, fill):
        """One number from the lanes of a child where `where` holds."""
        return reduce(reduce(jnp.where(where, x, f32(fill)), axis=1,
                             keepdims=True))

    best_rows = []
    for c in range(CH):
        in_ch = (row >= c * G) & (row < (c + 1) * G)
        # the order of ops/split: the best gain; among equals the lowest
        # feature; within it the best raw gain, the descending scan before
        # the ascending one, there the highest threshold, here the lowest
        bg = jnp.maximum(over(score[0], jnp.max, in_ch, NEG),
                         over(score[1], jnp.max, in_ch, NEG))
        at_best = ((score[0] == bg) | (score[1] == bg)) & in_ch
        bf = over(own, jnp.min, at_best, BIG)
        in_f = in_ch & (own == bf)
        br = jnp.maximum(over(raw[0], jnp.max, in_f, NEG),
                         over(raw[1], jnp.max, in_f, NEG))
        asc_hit = in_f & (raw[0] == br)
        desc_hit = in_f & (raw[1] == br)
        desc_thr = over(thr, jnp.max, desc_hit, -BIG)
        use_desc = desc_thr > -BIG / 2
        bthr = jnp.where(use_desc, desc_thr,
                         over(thr, jnp.min, asc_hit, BIG))
        sel = in_f & (thr == bthr)

        def pick(asc_v, desc_v):
            # select, don't multiply: other lanes may hold inf or NaN
            v = jnp.where(use_desc, desc_v, asc_v)
            return jnp.sum(jnp.sum(jnp.where(sel, v, 0.0), axis=1,
                                   keepdims=True))

        has = bg > f32(NEG_GATE)
        nan2 = over(jnp.where(flag(_F_NAN2), 1.0, 0.0), jnp.max, sel,
                    0.0) > 0.5
        stats = [pick(a, d) for a, d in zip(asc[4], desc[4])]
        vals = [bg, bf, bthr, jnp.where(use_desc & ~nan2, 1.0, 0.0),
                stats[0], stats[1] - K_EPSILON, stats[2],
                pick(asc[1], desc[1]),
                stats[3], stats[4] - K_EPSILON, stats[5],
                pick(asc[2], desc[2])]
        picked = jnp.zeros((1, ROW_W), f32)
        for k, v in enumerate(vals):
            picked = jnp.where(lane_out == k, v, picked)
        # no valid split: the no-split sentinel (gain NEG, feature -1)
        picked = jnp.where(has, picked, 0.0)
        picked = jnp.where(lane_out == _OG,
                           jnp.where(has, picked, f32(NEG)), picked)
        picked = jnp.where(lane_out == _OF,
                           jnp.where(has, picked, -1.0), picked)
        best_rows.append(picked)
    if not blocks:
        best_ref[:] = jnp.concatenate(best_rows, axis=0)
        return
    # fold into the child's row: a larger gain wins, and among equal gains
    # the lower feature, wherever its group's block lies
    picked, = best_rows
    at = pl.ds(child, 1)

    @pl.when(first_block)
    def _():
        best_ref[at, :] = picked

    @pl.when(jnp.logical_not(first_block))
    def _():
        kept = best_ref[at, :]
        pg, kg = picked[:, _OG:_OG + 1], kept[:, _OG:_OG + 1]
        pf, kf = picked[:, _OF:_OF + 1], kept[:, _OF:_OF + 1]
        better = (pg > kg) | ((pg == kg) & (pg > NEG_GATE) & (pf < kf))
        best_ref[at, :] = jnp.where(better, picked, kept)


# the scan holds about two dozen [rows, lanes(B)] f32 arrays at once:
# 22.61 MB at 2 000 rows of 63 bins, compiled for a v5e.  The group-space
# scan holds about twice that: twelve input planes a row (five lane
# statics, four tree planes, the histogram's three) where the feature-
# space scan has three, each held twice by a grid's pipeline, besides the
# segmented sums: 22.33 MB at 1 000 rows of 128 lanes.
_SCAN_ARRAYS = 24
_GROUP_SCAN_ARRAYS = 48
_SCAN_VMEM = 12 << 20


def _scan_block(CH: int, F: int, B: int, arrays: int = _SCAN_ARRAYS) -> tuple:
    """(features per block, blocks per child) of the scan: (F, 0) — one
    step, no grid, what narrow data has always compiled — while all CH * F
    rows fit the kernel's VMEM, else blocks of one child's features, a
    multiple of 8 rows each (the last one padded with masked features)."""
    row_bytes = arrays * 4 * (-(-B // 128) * 128)
    if CH * F * row_bytes <= _SCAN_VMEM:
        return F, 0
    n = -(-F * row_bytes // _SCAN_VMEM)
    return -(-F // (8 * n)) * 8, n


def _run_group_scan(pvec, svec, tree, hist3, lanes, interpret: bool):
    """The pallas_call of `_group_scan_kernel`: [CH, ROW_W] best rows."""
    _, R, B = hist3.shape
    CH = svec.shape[0]
    G = R // CH
    Gb, blocks = _scan_block(CH, G, B, _GROUP_SCAN_ARRAYS)
    out_shape = jax.ShapeDtypeStruct((CH, ROW_W), jnp.float32)
    if not blocks:
        return pl.pallas_call(
            functools.partial(_group_scan_kernel, CH=CH, G=G, B=B),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                      pl.BlockSpec(memory_space=pltpu.SMEM)]
            + [pl.BlockSpec(memory_space=pltpu.VMEM)] * 3,
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            out_shape=out_shape, interpret=interpret,
        )(pvec, svec, lanes, tree, hist3)
    # whole blocks per child: a padded group row has no owner on any lane,
    # so none of its lanes is a candidate
    pad = blocks * Gb - G
    if pad:
        lanes = jnp.pad(lanes, ((0, 0), (0, pad), (0, 0)))
        lanes = lanes.at[_LOWN, G:].set(-1)
        tree = jnp.pad(tree, ((0, 0), (0, pad), (0, 0)))
        hist3 = jnp.pad(hist3.reshape(3, CH, G, B),
                        ((0, 0), (0, 0), (0, pad), (0, 0))).reshape(3, -1, B)

    def of_block(i):
        return (0, i - (i // blocks) * blocks, 0)

    return pl.pallas_call(
        functools.partial(_group_scan_kernel, CH=CH, G=Gb, B=B,
                          blocks=blocks),
        grid=(CH * blocks,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((_NLANE, Gb, B), of_block),
                  pl.BlockSpec((_NTREE, Gb, B), of_block),
                  pl.BlockSpec((3, Gb, B), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((CH, ROW_W), lambda i: (0, 0)),
        out_shape=out_shape, interpret=interpret,
    )(pvec, svec, lanes, tree, hist3)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _run_scan(pvec, svec, fvec, hist3, lanes=None, *, interpret: bool):
    """`lanes` (group_lane_statics) given: the scan in group space, `fvec`
    being the per-tree lane planes (group_lane_planes) and `hist3` the
    bundled histograms [3, CH * Gp, Bp]; the per-feature block is None."""
    if lanes is not None:
        return None, _run_group_scan(pvec, svec, fvec, hist3, lanes,
                                     interpret)
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


def best_split_rows_group(hist, sum_g, sum_h, num_data, lanes, tree_planes,
                          params: SplitParams,
                          min_constraints=None, max_constraints=None,
                          interpret: bool = False):
    """`best_split_rows_pallas` for an EFB-bundled data set, on the bundled
    histograms themselves: hist [CH, G, B, 3] over group columns and group
    bins, `lanes` from group_lane_statics (built once per data set),
    `tree_planes` from group_lane_planes.  The packed rows carry the
    feature id and the feature's bin, as the feature-space rows do."""
    CH, G, B, _ = hist.shape
    _, Gp, Bp = lanes.shape
    if Gp != G or Bp != B:
        hist = jnp.pad(hist, ((0, 0), (0, Gp - G), (0, Bp - B), (0, 0)))
    pvec, svec, hist3 = _pack_inputs(hist, sum_g, sum_h, num_data,
                                     min_constraints, max_constraints,
                                     params)
    _, best = _run_scan(pvec, svec, tree_planes, hist3, lanes,
                        interpret=interpret)
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
