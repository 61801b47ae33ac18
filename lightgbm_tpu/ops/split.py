"""Best-split search over histograms — fully vectorized XLA scans.

TPU-native re-design of FeatureHistogram::FindBestThreshold*
(src/treelearner/feature_histogram.hpp:29-645): instead of the reference's
per-feature sequential two-direction loops, all features × all thresholds ×
both default-directions are evaluated at once as cumulative sums along the
bin axis of a `[F, B, 3]` histogram tensor, followed by a masked argmax.
Semantics preserved exactly:

- gain math with L1 thresholding, L2, max_delta_step clamps
  (feature_histogram.hpp:437-498);
- missing handling: MissingType None/Zero/NaN with the default bin (zeros) or
  the NaN bin riding the chosen default direction, both directions scanned
  when the feature has missing values (feature_histogram.hpp:84-110, 500-636);
- min_data_in_leaf / min_sum_hessian_in_leaf / min_gain_to_split masks;
- tie-breaking: descending scan beats ascending at equal gain, higher
  threshold wins inside the descending scan, lower inside the ascending one,
  lower feature index wins across features (split_info.hpp:131-158).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

K_EPSILON = 1e-15  # meta.h:38
K_MIN_SCORE = -jnp.inf

MISSING_NONE = 0
MISSING_ZERO = 1
MISSING_NAN = 2


class SplitParams(NamedTuple):
    """Split hyper-parameters (subset of Config used by the scans).  Leaves
    ride the jit pytree, so every field may be a tracer at scan time —
    except max_cat_threshold, which bounds a scan and must stay static."""
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    max_delta_step: float = 0.0
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    min_gain_to_split: float = 0.0
    # categorical optimal-split knobs (config.h:394-437)
    max_cat_to_onehot: int = 4
    cat_smooth: float = 10.0
    cat_l2: float = 10.0
    min_data_per_group: int = 100
    # CEGB (cost-effective gradient boosting): gain -= cegb_split_penalty *
    # num_data_in_leaf, applied after the per-feature threshold search like
    # the reference (serial_tree_learner.cpp:533)
    cegb_split_penalty: float = 0.0


class SplitResult(NamedTuple):
    """Per-leaf best split (all scalars / [()] arrays); the jax analogue of
    SplitInfo (src/treelearner/split_info.hpp:17-130)."""
    feature: jnp.ndarray        # int32, -1 = no valid split
    threshold: jnp.ndarray      # int32 bin threshold (inner, <= goes left)
    gain: jnp.ndarray           # f32/f64
    default_left: jnp.ndarray   # bool
    left_sum_gradient: jnp.ndarray
    left_sum_hessian: jnp.ndarray
    left_count: jnp.ndarray     # int32
    left_output: jnp.ndarray
    right_sum_gradient: jnp.ndarray
    right_sum_hessian: jnp.ndarray
    right_count: jnp.ndarray    # int32
    right_output: jnp.ndarray
    # categorical split payload: [B] bool membership mask over bins (goes
    # left), all-False for numerical splits.  The array analogue of
    # SplitInfo::cat_threshold (split_info.hpp:36-39); packed to the
    # reference's uint32 bitset on the host (Tree::ConstructBitset).
    # None only in cat-free contexts (never mixed inside one jit trace).
    cat_mask: Optional[jnp.ndarray] = None


class PerFeatureSplit(NamedTuple):
    """Best split of every feature of one leaf — all fields [F].  The array
    analogue of the per-feature SplitInfo vector the reference reduces over
    (serial_tree_learner.cpp:506-591) and the payload voting-parallel gathers
    (LightSplitInfo, split_info.hpp:203-285)."""
    gain: jnp.ndarray           # [F], K_MIN_SCORE = no valid split
    threshold: jnp.ndarray      # [F] int32
    default_left: jnp.ndarray   # [F] bool
    left_sum_gradient: jnp.ndarray
    left_sum_hessian: jnp.ndarray   # includes the +eps directional bias
    left_count: jnp.ndarray
    left_output: jnp.ndarray
    right_sum_gradient: jnp.ndarray
    right_sum_hessian: jnp.ndarray
    right_count: jnp.ndarray
    right_output: jnp.ndarray
    cat_mask: Optional[jnp.ndarray] = None   # [F, B]


def threshold_l1(s, l1):
    """sign(s) * max(0, |s| - l1) (feature_histogram.hpp:437-440)."""
    reg = jnp.maximum(0.0, jnp.abs(s) - l1)
    return jnp.sign(s) * reg


def calculate_splitted_leaf_output(sum_grad, sum_hess, l1, l2, max_delta_step):
    """feature_histogram.hpp:442-449."""
    ret = -threshold_l1(sum_grad, l1) / (sum_hess + l2)
    clipped = jnp.sign(ret) * max_delta_step
    use_clip = (max_delta_step > 0.0) & (jnp.abs(ret) > max_delta_step)
    return jnp.where(use_clip, clipped, ret)


def leaf_split_gain_given_output(sum_grad, sum_hess, l1, l2, output):
    """-(2*T_l1(g)*w + (h+l2)*w^2) (feature_histogram.hpp:494-497)."""
    sg_l1 = threshold_l1(sum_grad, l1)
    return -(2.0 * sg_l1 * output + (sum_hess + l2) * output * output)


def leaf_split_gain(sum_grad, sum_hess, l1, l2, max_delta_step):
    out = calculate_splitted_leaf_output(sum_grad, sum_hess, l1, l2, max_delta_step)
    return leaf_split_gain_given_output(sum_grad, sum_hess, l1, l2, out)


def split_gains(lg, lh, rg, rh, l1, l2, max_delta_step,
                min_constraint=-jnp.inf, max_constraint=jnp.inf, monotone=0):
    """Gain of a (left,right) pair with monotone zeroing
    (feature_histogram.hpp:452-463)."""
    lo = jnp.clip(calculate_splitted_leaf_output(lg, lh, l1, l2, max_delta_step),
                  min_constraint, max_constraint)
    ro = jnp.clip(calculate_splitted_leaf_output(rg, rh, l1, l2, max_delta_step),
                  min_constraint, max_constraint)
    gain = (leaf_split_gain_given_output(lg, lh, l1, l2, lo)
            + leaf_split_gain_given_output(rg, rh, l1, l2, ro))
    violates = ((monotone > 0) & (lo > ro)) | ((monotone < 0) & (lo < ro))
    return jnp.where(violates, 0.0, gain), lo, ro


def best_split_per_feature(hist: jnp.ndarray,
                           sum_gradient, sum_hessian, num_data,
                           num_bins: jnp.ndarray,
                           default_bins: jnp.ndarray,
                           missing_types: jnp.ndarray,
                           params: SplitParams,
                           monotone: Optional[jnp.ndarray] = None,
                           penalty: Optional[jnp.ndarray] = None,
                           min_constraints: Optional[jnp.ndarray] = None,
                           max_constraints: Optional[jnp.ndarray] = None,
                           feature_mask: Optional[jnp.ndarray] = None,
                           cegb_feature_penalty: Optional[jnp.ndarray] = None
                           ) -> PerFeatureSplit:
    """Best numerical split of *every* feature of one leaf (fields [F]).

    hist: [F, B, 3] (grad, hess, count) including every bin (the default bin
    is stored explicitly — no FixHistogram reconstruction step is needed in
    this design, unlike dataset.cpp:928-949).
    num_bins/default_bins/missing_types: [F] int32 per-feature statics.
    feature_mask: [F] bool — feature_fraction sampling (col_sampler).
    """
    F, B, _ = hist.shape
    dtype = hist.dtype
    l1 = jnp.asarray(params.lambda_l1, dtype)
    l2 = jnp.asarray(params.lambda_l2, dtype)
    mds = jnp.asarray(params.max_delta_step, dtype)

    sum_gradient = jnp.asarray(sum_gradient, dtype)
    # FindBestThreshold adds 2*eps to the parent hessian (hpp:79)
    sum_hessian = jnp.asarray(sum_hessian, dtype) + 2 * K_EPSILON
    num_data = jnp.asarray(num_data, jnp.int32)

    bins = jnp.arange(B, dtype=jnp.int32)                       # [B]
    in_range = bins[None, :] < num_bins[:, None]                # [F, B]
    # bins riding the default direction (excluded from directional sums)
    excl = ((missing_types[:, None] == MISSING_ZERO) &
            (bins[None, :] == default_bins[:, None])) | \
           ((missing_types[:, None] == MISSING_NAN) &
            (bins[None, :] == num_bins[:, None] - 1))
    # with <=2 bins the reference falls into the single plain scan with no
    # default-direction bin (feature_histogram.hpp:89,97-103)
    excl = excl & in_range & (num_bins[:, None] > 2)

    g = jnp.where(in_range & ~excl, hist[..., 0], 0.0)
    h = jnp.where(in_range & ~excl, hist[..., 1], 0.0)
    # counts stay integral: f32 loses exactness above 2^24 rows per leaf,
    # which would flip min_data_in_leaf masks on billion-row data
    c = jnp.where(in_range & ~excl, hist[..., 2], 0.0)
    c_int = jnp.round(c).astype(jnp.int64 if c.dtype == jnp.float64 else jnp.int32)

    # ascending: left(θ) = Σ_{b<=θ, not excl};  descending: right(θ) = Σ_{b>θ}
    cg = jnp.cumsum(g, axis=1)
    ch = jnp.cumsum(h, axis=1)
    cc = jnp.cumsum(c_int, axis=1)
    tg, th, tc = cg[:, -1:], ch[:, -1:], cc[:, -1:]

    def eval_dir(left_g, left_h, left_c):
        right_g = sum_gradient - left_g
        right_h = sum_hessian - left_h
        right_c = num_data - left_c
        gain, lo, ro = split_gains(left_g, left_h, right_g, right_h, l1, l2, mds,
                                   (-jnp.inf if min_constraints is None
                                    else min_constraints[:, None]),
                                   (jnp.inf if max_constraints is None
                                    else max_constraints[:, None]),
                                   0 if monotone is None else monotone[:, None])
        min_cnt = jnp.maximum(params.min_data_in_leaf, 1)
        valid = ((left_c >= min_cnt)
                 & (right_c >= min_cnt)
                 & (left_h >= params.min_sum_hessian_in_leaf)
                 & (right_h >= params.min_sum_hessian_in_leaf))
        return gain, lo, ro, valid, (left_g, left_h, left_c, right_g, right_h, right_c)

    # dir == +1 (default right): left accumulates from the low end, +eps
    asc_lg, asc_lh, asc_lc = cg, ch + K_EPSILON, cc
    asc = eval_dir(asc_lg, asc_lh, asc_lc)
    # dir == -1 (default left): right accumulates from the high end, +eps;
    # right(θ) = total_directional - cum(θ); left = parent - right
    desc_rg, desc_rh, desc_rc = tg - cg, th - ch + K_EPSILON, tc - cc
    desc = eval_dir(sum_gradient - desc_rg, sum_hessian - desc_rh,
                    num_data - desc_rc)

    # threshold validity: θ in [0, num_bin-2]
    thr_ok = bins[None, :] <= num_bins[:, None] - 2
    # ascending scan only runs for features with missing values and >2 bins
    # (feature_histogram.hpp:89-96); descending always runs
    asc_ok = thr_ok & (missing_types[:, None] != MISSING_NONE) & (num_bins[:, None] > 2)
    desc_ok = thr_ok

    # no-split gain threshold (strict >)
    gain_shift = leaf_split_gain(sum_gradient, sum_hessian, l1, l2, mds)
    min_gain_shift = gain_shift + params.min_gain_to_split

    def masked_gain(d, ok):
        gain, lo, ro, valid, _ = d
        return jnp.where(ok & valid & (gain > min_gain_shift), gain, K_MIN_SCORE)

    asc_gain = masked_gain(asc, asc_ok)
    desc_gain = masked_gain(desc, desc_ok)

    # scan-order tie-breaking: desc scans high→low θ then asc scans low→high,
    # strict-greater updates.  Build candidates in that order per feature.
    cand_gain = jnp.concatenate([desc_gain[:, ::-1], asc_gain], axis=1)  # [F, 2B]
    best_idx = jnp.argmax(cand_gain, axis=1)                             # [F]
    best_gain = jnp.take_along_axis(cand_gain, best_idx[:, None], 1)[:, 0]
    is_desc = best_idx < B
    best_thr = jnp.where(is_desc, B - 1 - best_idx, best_idx - B).astype(jnp.int32)

    def pick(d, which):
        return jnp.take_along_axis(d, jnp.where(which, best_thr, 0)[:, None], 1)[:, 0]

    (asc_gain_, asc_lo, asc_ro, _, asc_sums) = asc
    (desc_gain_, desc_lo, desc_ro, _, desc_sums) = desc

    def sel(asc_v, desc_v):
        return jnp.where(is_desc, pick(desc_v, is_desc), pick(asc_v, ~is_desc))

    lg = sel(asc_sums[0], desc_sums[0])
    lh = sel(asc_sums[1], desc_sums[1])
    lc = sel(asc_sums[2], desc_sums[2])
    rg = sel(asc_sums[3], desc_sums[3])
    rh = sel(asc_sums[4], desc_sums[4])
    rc = sel(asc_sums[5], desc_sums[5])
    lo = sel(asc_lo, desc_lo)
    ro = sel(asc_ro, desc_ro)

    # per-feature reported gain relative to no-split, times feature penalty
    rel_gain = best_gain - min_gain_shift
    if penalty is not None:
        rel_gain = rel_gain * penalty
    # CEGB penalties are subtracted AFTER the threshold search
    # (serial_tree_learner.cpp:533-539): they shift whole features/leaves,
    # not individual thresholds
    rel_gain = rel_gain - jnp.asarray(params.cegb_split_penalty,
                                      dtype) * num_data
    if cegb_feature_penalty is not None:
        rel_gain = rel_gain - cegb_feature_penalty
    # penalties can push the gain non-positive: such splits never apply
    # (the reference's gain <= 0 stop, serial_tree_learner.cpp:220-223)
    feat_gain = jnp.where((best_gain > K_MIN_SCORE) & (rel_gain > 0),
                          rel_gain, K_MIN_SCORE)
    if feature_mask is not None:
        feat_gain = jnp.where(feature_mask, feat_gain, K_MIN_SCORE)

    # 2-bin NaN features report default_right even from the single descending
    # scan (feature_histogram.hpp:99-102)
    two_bin_nan = (missing_types == MISSING_NAN) & (num_bins <= 2)
    default_left_f = is_desc & ~two_bin_nan

    return PerFeatureSplit(
        gain=feat_gain,
        threshold=best_thr,
        default_left=default_left_f,
        left_sum_gradient=lg,
        left_sum_hessian=lh,
        left_count=lc.astype(jnp.int32),
        left_output=lo,
        right_sum_gradient=rg,
        right_sum_hessian=rh,
        right_count=rc.astype(jnp.int32),
        right_output=ro,
    )


def select_best_feature(pf: PerFeatureSplit,
                        feature_index: Optional[jnp.ndarray] = None
                        ) -> SplitResult:
    """Cross-feature argmax of a PerFeatureSplit → SplitResult.

    feature_index: optional [F] int32 mapping row → global feature id (used
    by the feature-parallel shard offset and the voting-parallel gather);
    defaults to arange.  Ties -> smaller array position (argmax first-hit),
    matching the reference's ascending-feature update loop
    (serial_tree_learner.cpp:575-587).
    """
    best_f = jnp.argmax(pf.gain, axis=0).astype(jnp.int32)
    has_split = pf.gain[best_f] > K_MIN_SCORE
    if feature_index is None:
        out_f = best_f
    else:
        out_f = feature_index[best_f].astype(jnp.int32)
    best_f_out = jnp.where(has_split, out_f, -1)

    def at(v):
        return v[best_f]

    return SplitResult(
        feature=best_f_out,
        threshold=at(pf.threshold),
        gain=at(pf.gain),
        default_left=at(pf.default_left),
        left_sum_gradient=at(pf.left_sum_gradient),
        left_sum_hessian=at(pf.left_sum_hessian) - K_EPSILON,
        left_count=at(pf.left_count),
        left_output=at(pf.left_output),
        right_sum_gradient=at(pf.right_sum_gradient),
        right_sum_hessian=at(pf.right_sum_hessian) - K_EPSILON,
        right_count=at(pf.right_count),
        right_output=at(pf.right_output),
        cat_mask=None if pf.cat_mask is None else pf.cat_mask[best_f],
    )


def best_split_per_feature_mixed(hist: jnp.ndarray,
                                 sum_gradient, sum_hessian, num_data,
                                 num_bins: jnp.ndarray,
                                 default_bins: jnp.ndarray,
                                 missing_types: jnp.ndarray,
                                 is_categorical: jnp.ndarray,   # [F] bool
                                 params: SplitParams,
                                 monotone: Optional[jnp.ndarray] = None,
                                 penalty: Optional[jnp.ndarray] = None,
                                 min_constraints=None, max_constraints=None,
                                 feature_mask: Optional[jnp.ndarray] = None,
                                 cegb_feature_penalty=None,
                                 *, max_cat_threshold: int = 32
                                 ) -> PerFeatureSplit:
    """Per-feature best split with the numerical/categorical scan selected
    per feature by bin type (the find_best_threshold_fun_ dispatch,
    feature_histogram.hpp:49-58)."""
    pf_num = best_split_per_feature(
        hist, sum_gradient, sum_hessian, num_data,
        num_bins, default_bins, missing_types, params,
        monotone=monotone, penalty=penalty,
        min_constraints=min_constraints, max_constraints=max_constraints,
        feature_mask=feature_mask, cegb_feature_penalty=cegb_feature_penalty)
    pf_cat = best_split_categorical_per_feature(
        hist, sum_gradient, sum_hessian, num_data,
        num_bins, missing_types, params,
        penalty=penalty,
        min_constraints=min_constraints, max_constraints=max_constraints,
        feature_mask=feature_mask, cegb_feature_penalty=cegb_feature_penalty,
        max_cat_threshold=max_cat_threshold)

    def sel(num_v, cat_v):
        ic = is_categorical
        if cat_v.ndim == 2:
            ic = is_categorical[:, None]
        return jnp.where(ic, cat_v, num_v)

    merged = PerFeatureSplit(*[
        sel(n, c) for n, c in
        zip(pf_num._replace(cat_mask=jnp.zeros_like(pf_cat.cat_mask)),
            pf_cat)])
    return merged


def best_split_categorical_per_feature(hist: jnp.ndarray,
                                       sum_gradient, sum_hessian, num_data,
                                       num_bins: jnp.ndarray,
                                       missing_types: jnp.ndarray,
                                       params: SplitParams,
                                       penalty: Optional[jnp.ndarray] = None,
                                       min_constraints=None,
                                       max_constraints=None,
                                       feature_mask: Optional[jnp.ndarray] = None,
                                       cegb_feature_penalty=None,
                                       *, max_cat_threshold: int = 32
                                       ) -> PerFeatureSplit:
    """Categorical optimal split of every feature (FindBestThresholdCategorical,
    feature_histogram.hpp:110-271), vectorized over features:

    - one-hot mode when num_bin <= max_cat_to_onehot: each category vs rest,
      evaluated for every bin at once;
    - sorted mode: bins with cnt >= cat_smooth sorted by g/(h+cat_smooth),
      prefixes from both directions scanned up to
      min(max_cat_threshold, (used_bin+1)/2) with the min_data_per_group
      group-accumulation walk (a lax.scan over <= max_cat_threshold steps,
      vectorized over F).

    Returns PerFeatureSplit whose threshold is unused (-1) and whose
    cat_mask [F, B] holds the left-going category set.
    """
    F, B, _ = hist.shape
    dtype = hist.dtype
    l1 = jnp.asarray(params.lambda_l1, dtype)
    l2n = jnp.asarray(params.lambda_l2, dtype)
    l2 = l2n + jnp.asarray(params.cat_l2, dtype)   # hpp:172
    mds = jnp.asarray(params.max_delta_step, dtype)
    sum_gradient = jnp.asarray(sum_gradient, dtype)
    sum_hessian = jnp.asarray(sum_hessian, dtype) + 2 * K_EPSILON  # hpp:79
    num_data = jnp.asarray(num_data, jnp.int32)
    minc1 = -jnp.inf if min_constraints is None else min_constraints   # [F]
    maxc1 = jnp.inf if max_constraints is None else max_constraints
    minc = minc1 if min_constraints is None else minc1[:, None]        # [F,1]
    maxc = maxc1 if max_constraints is None else maxc1[:, None]

    bins = jnp.arange(B, dtype=jnp.int32)
    # used_bin = num_bin - 1 + (missing_type == None) (hpp:121-122)
    used_bin = num_bins - 1 + (missing_types == MISSING_NONE).astype(jnp.int32)
    in_used = bins[None, :] < used_bin[:, None]                  # [F, B]

    g = jnp.where(in_used, hist[..., 0], 0.0)
    h = jnp.where(in_used, hist[..., 1], 0.0)
    c = jnp.round(jnp.where(in_used, hist[..., 2], 0.0)).astype(jnp.int32)

    # min_gain_shift against the PLAIN-l2 no-split gain (hpp:119-120)
    gain_shift = leaf_split_gain(sum_gradient, sum_hessian, l1, l2n, mds)
    min_gain_shift = gain_shift + params.min_gain_to_split

    min_cnt = jnp.maximum(params.min_data_in_leaf, 1)
    min_hess = params.min_sum_hessian_in_leaf

    # ---------------- one-hot mode (hpp:129-160) ----------------------- #
    other_g = sum_gradient - g
    other_h = sum_hessian - h - K_EPSILON
    other_c = num_data - c
    oh_gain, oh_lo, oh_ro = split_gains(other_g, other_h, g, h + K_EPSILON,
                                        l1, l2, mds, minc, maxc, 0)
    oh_valid = (in_used
                & (c >= min_cnt) & (h >= min_hess)
                & (other_c >= min_cnt) & (other_h >= min_hess))
    oh_gain = jnp.where(oh_valid & (oh_gain > min_gain_shift),
                        oh_gain, K_MIN_SCORE)
    oh_best = jnp.argmax(oh_gain, axis=1)                         # [F]
    oh_bgain = jnp.take_along_axis(oh_gain, oh_best[:, None], 1)[:, 0]
    oh_mask = jax.nn.one_hot(oh_best, B, dtype=jnp.int32).astype(bool)

    def at_b(v):
        return jnp.take_along_axis(v, oh_best[:, None], 1)[:, 0]

    onehot = dict(
        gain=oh_bgain,
        lg=at_b(g), lh=at_b(h) + K_EPSILON, lc=at_b(c),
        mask=oh_mask)

    # ---------------- sorted mode (hpp:161-238) ------------------------ #
    eligible = in_used & (c.astype(dtype) >= params.cat_smooth)   # hpp:163
    n_elig = jnp.sum(eligible, axis=1).astype(jnp.int32)          # [F]
    ratio = jnp.where(eligible, g / (h + params.cat_smooth), jnp.inf)
    order = jnp.argsort(ratio, axis=1).astype(jnp.int32)          # [F, B]
    # per-direction prefix walk with group accumulation; dir 0 = ascending
    # (+1), dir 1 = descending (-1: walk from the high end of the order)
    max_steps = min(max_cat_threshold, B)
    # max_num_cat = min(max_cat_threshold, (used_bin+1)/2) (hpp:185)
    max_num_cat = jnp.minimum(max_cat_threshold, (n_elig + 1) // 2)

    og = jnp.take_along_axis(g, order, axis=1)                    # [F, B]
    oh_ = jnp.take_along_axis(h, order, axis=1)
    oc = jnp.take_along_axis(c, order, axis=1)

    def scan_dir(descending: bool):
        if descending:
            sg, sh, sc = og[:, ::-1], oh_[:, ::-1], oc[:, ::-1]
            # descending starts at position n_elig-1: shift the reversed
            # arrays so step 0 reads the last *eligible* bin
            shift = B - n_elig                                    # [F]
            idx = (jnp.arange(B)[None, :] + shift[:, None]) % B
            sg = jnp.take_along_axis(sg, idx, axis=1)
            sh = jnp.take_along_axis(sh, idx, axis=1)
            sc = jnp.take_along_axis(sc, idx, axis=1)
        else:
            sg, sh, sc = og, oh_, oc

        def step(carry, i):
            cnt_grp, lg, lh, lc = carry
            lg = lg + sg[:, i]
            lh = lh + sh[:, i]
            lc = lc + sc[:, i]
            cnt_grp = cnt_grp + sc[:, i]
            in_range = (i < n_elig) & (i < max_num_cat)
            rc = num_data - lc
            rh = sum_hessian - lh
            # break conditions poison all later steps (hpp:207-212)
            brk = (rc < min_cnt) | (rc < params.min_data_per_group) | \
                  (rh < min_hess)
            cont = (lc < min_cnt) | (lh < min_hess)
            # the group resets whenever the walk reaches an evaluation,
            # before the gain test (hpp:216-218)
            evalable = in_range & ~brk & ~cont & \
                (cnt_grp >= params.min_data_per_group)
            gain, _lo, _ro = split_gains(lg, lh, sum_gradient - lg, rh,
                                         l1, l2, mds, minc1, maxc1, 0)
            gain = jnp.where(evalable & (gain > min_gain_shift),
                             gain, K_MIN_SCORE)
            cnt_grp = jnp.where(evalable, 0, cnt_grp)
            new_dead = brk & in_range
            return ((cnt_grp, lg, lh, lc), (gain, lg, lh, lc, new_dead))

        init = (jnp.zeros(F, jnp.int32), jnp.zeros(F, dtype) ,
                jnp.full(F, K_EPSILON, dtype), jnp.zeros(F, jnp.int32))
        _, (gains, lgs, lhs, lcs, dead) = jax.lax.scan(
            step, init, jnp.arange(max_steps))
        # poison every step after the first break
        dead_before = jnp.cumsum(dead.astype(jnp.int32), axis=0) \
            - dead.astype(jnp.int32)
        gains = jnp.where(dead_before > 0, K_MIN_SCORE, gains)   # [S, F]
        best_i = jnp.argmax(gains, axis=0)                        # [F]
        bg = jnp.take_along_axis(gains, best_i[None, :], 0)[0]

        def at_i(v):
            return jnp.take_along_axis(v, best_i[None, :], 0)[0]

        # membership mask: first (best_i+1) positions of the walk
        rank = jnp.argsort(order, axis=1)                         # bin -> pos
        if descending:
            pos_from_end = n_elig[:, None] - 1 - rank
            member = (pos_from_end >= 0) & (pos_from_end <= best_i[:, None])
        else:
            member = rank <= best_i[:, None]
        member = member & eligible
        return dict(gain=bg, lg=at_i(lgs), lh=at_i(lhs), lc=at_i(lcs),
                    mask=member)

    asc = scan_dir(False)
    desc = scan_dir(True)
    # strict-greater update: ascending wins ties (it is scanned first,
    # hpp:186-238 out_i order)
    use_desc = desc["gain"] > asc["gain"]

    def sel(a, d):
        if a.ndim == 2:
            return jnp.where(use_desc[:, None], d, a)
        return jnp.where(use_desc, d, a)

    sorted_res = {k: sel(asc[k], desc[k]) for k in asc}

    # ---------------- mode select + outputs ---------------------------- #
    use_onehot = num_bins <= params.max_cat_to_onehot             # [F]

    def pick(o, s):
        if o.ndim == 2:
            return jnp.where(use_onehot[:, None], o, s)
        return jnp.where(use_onehot, o, s)

    res = {k: pick(onehot[k], sorted_res[k]) for k in onehot}
    gain, lg, lh, lc = res["gain"], res["lg"], res["lh"], res["lc"]
    rg = sum_gradient - lg
    rh = sum_hessian - lh
    rc = num_data - lc
    lo = jnp.clip(calculate_splitted_leaf_output(lg, lh, l1, l2, mds),
                  minc1, maxc1)
    ro = jnp.clip(calculate_splitted_leaf_output(rg, rh, l1, l2, mds),
                  minc1, maxc1)

    rel_gain = gain - min_gain_shift
    if penalty is not None:
        rel_gain = rel_gain * penalty
    rel_gain = rel_gain - jnp.asarray(params.cegb_split_penalty,
                                      dtype) * num_data
    if cegb_feature_penalty is not None:
        rel_gain = rel_gain - cegb_feature_penalty
    feat_gain = jnp.where((gain > K_MIN_SCORE) & (rel_gain > 0),
                          rel_gain, K_MIN_SCORE)
    if feature_mask is not None:
        feat_gain = jnp.where(feature_mask, feat_gain, K_MIN_SCORE)
    cat_mask = res["mask"] & (feat_gain > K_MIN_SCORE)[:, None]

    return PerFeatureSplit(
        gain=feat_gain,
        threshold=jnp.full(F, -1, jnp.int32),
        default_left=jnp.zeros(F, bool),      # hpp:113 default_left=false
        left_sum_gradient=lg,
        left_sum_hessian=lh,
        left_count=lc,
        left_output=lo,
        right_sum_gradient=rg,
        right_sum_hessian=rh,
        right_count=rc,
        right_output=ro,
        cat_mask=cat_mask,
    )


def forced_split_result(hist, feat, thr_bin, sum_gradient, sum_hessian,
                        num_data, num_bins, default_bins, missing_types,
                        params: SplitParams, default_left) -> SplitResult:
    """Stats of the numerical split (feat, thr_bin) on this leaf — the
    forced-split analogue of FeatureHistogram::GatherInfoForThreshold
    (feature_histogram.hpp:273-411).  Returns a SplitResult whose gain is
    +inf when both children are nonempty (forced splits apply regardless
    of gain) and K_MIN_SCORE otherwise."""
    dtype = hist.dtype
    B = hist.shape[1]
    l1 = jnp.asarray(params.lambda_l1, dtype)
    l2 = jnp.asarray(params.lambda_l2, dtype)
    mds = jnp.asarray(params.max_delta_step, dtype)
    sum_gradient = jnp.asarray(sum_gradient, dtype)
    sum_hessian = jnp.asarray(sum_hessian, dtype) + 2 * K_EPSILON
    num_data = jnp.asarray(num_data, jnp.int32)

    h_f = hist[feat]                                           # [B, 3]
    bins = jnp.arange(B, dtype=jnp.int32)
    nb = num_bins[feat]
    in_range = bins < nb
    mt = missing_types[feat]
    excl = (((mt == MISSING_ZERO) & (bins == default_bins[feat])) |
            ((mt == MISSING_NAN) & (bins == nb - 1))) & in_range & (nb > 2)
    take_left = in_range & ~excl & (bins <= thr_bin)
    lg = jnp.sum(jnp.where(take_left, h_f[:, 0], 0.0))
    lh = jnp.sum(jnp.where(take_left, h_f[:, 1], 0.0))
    lc = jnp.sum(jnp.where(take_left, h_f[:, 2], 0.0))
    excl_g = jnp.sum(jnp.where(excl, h_f[:, 0], 0.0))
    excl_h = jnp.sum(jnp.where(excl, h_f[:, 1], 0.0))
    excl_c = jnp.sum(jnp.where(excl, h_f[:, 2], 0.0))
    dl = jnp.asarray(default_left, bool)
    lg = lg + jnp.where(dl, excl_g, 0.0)
    lh = lh + jnp.where(dl, excl_h, 0.0)
    lc = lc + jnp.where(dl, excl_c, 0.0)
    rg = sum_gradient - lg
    rh = sum_hessian - lh
    rc = num_data - jnp.round(lc).astype(jnp.int32)
    lc_i = jnp.round(lc).astype(jnp.int32)
    lo = calculate_splitted_leaf_output(lg, lh, l1, l2, mds)
    ro = calculate_splitted_leaf_output(rg, rh, l1, l2, mds)
    valid = (lc_i > 0) & (rc > 0)
    return SplitResult(
        feature=jnp.where(valid, feat, -1).astype(jnp.int32),
        threshold=jnp.asarray(thr_bin, jnp.int32),
        gain=jnp.where(valid, jnp.asarray(jnp.inf, dtype),
                       jnp.asarray(K_MIN_SCORE, dtype)),
        default_left=dl,
        left_sum_gradient=lg, left_sum_hessian=lh - K_EPSILON,
        left_count=lc_i, left_output=lo,
        right_sum_gradient=rg, right_sum_hessian=rh - K_EPSILON,
        right_count=rc, right_output=ro,
        cat_mask=None)


def best_split_for_leaf(hist: jnp.ndarray,
                        sum_gradient, sum_hessian, num_data,
                        num_bins: jnp.ndarray,
                        default_bins: jnp.ndarray,
                        missing_types: jnp.ndarray,
                        params: SplitParams,
                        monotone: Optional[jnp.ndarray] = None,
                        penalty: Optional[jnp.ndarray] = None,
                        min_constraints: Optional[jnp.ndarray] = None,
                        max_constraints: Optional[jnp.ndarray] = None,
                        feature_mask: Optional[jnp.ndarray] = None) -> SplitResult:
    """Best numerical split across all features of one leaf (see
    best_split_per_feature for the argument contract)."""
    pf = best_split_per_feature(hist, sum_gradient, sum_hessian, num_data,
                                num_bins, default_bins, missing_types, params,
                                monotone=monotone, penalty=penalty,
                                min_constraints=min_constraints,
                                max_constraints=max_constraints,
                                feature_mask=feature_mask)
    return select_best_feature(pf)
