"""Jitted leaf-wise tree growth.

The TPU re-design of SerialTreeLearner::Train (src/treelearner/
serial_tree_learner.cpp:169-233): the whole best-first growth loop runs as a
single compiled `lax.while_loop` on device — no host↔device ping-pong per
split.  Differences from the reference dictated by XLA:

- the row partition is a `row→leaf` label vector relabelled in place, not a
  reordered index array (DataPartition, data_partition.hpp:17-222);
- per-leaf histograms live in a fixed `[max_leaves, F, B, 3]` cache instead
  of the LRU HistogramPool (feature_histogram.hpp:646-818) — the smaller
  child is histogrammed by a masked pass, the sibling by subtraction
  (serial_tree_learner.cpp:506-591's smaller/larger choreography);
- per-leaf best splits are cached as stacked SplitResult arrays, so each
  iteration is argmax → split → 1 histogram pass → 2 split scans.

Tree node layout matches the reference Tree (include/LightGBM/tree.h:20-391):
internal nodes indexed by split order, leaves referenced as `~leaf`.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..parallel import collective as coll
from . import histogram as hist_ops
from .split import (K_MIN_SCORE, SplitParams, SplitResult,
                    best_split_for_leaf, best_split_per_feature,
                    best_split_per_feature_mixed, select_best_feature)

MISSING_NONE = 0
MISSING_ZERO = 1
MISSING_NAN = 2


class BundleMaps(NamedTuple):
    """Device-side EFB layout (io/efb.py BundleInfo): the bin matrix holds
    [n, G] bundled group columns; scans and splits address original
    features through these maps (FeatureGroup::SubFeatureIterator +
    Dataset::FixHistogram, feature_group.h:146-152, dataset.cpp:928-949).
    The two scan maps are built only for the engine that reads them."""
    feat_col: jnp.ndarray       # [F] int32 group column of each feature
    feat_lo: jnp.ndarray        # [F] int32 group-bin range of the feature's
    feat_hi: jnp.ndarray        #          mapped (non-default) bins
    feat_shift: jnp.ndarray     # [F] int32 group_bin = feature_bin + shift
    needs_fix: jnp.ndarray      # [F] bool default bin reconstructed at scan
    # [F, B] int32 into flat [G*B] (+1 sentinel): scans in feature space
    # (unbundle_hist: the label engine; categorical, voting and forced
    # splits on the partition engine)
    unbundle_idx: Optional[jnp.ndarray] = None
    # [5, Gp, Bp] int32 per-lane statics of the scan in group space
    # (split_pallas.group_lane_statics: the partition engine's numerical
    # path, which never makes an [F, B, 3] histogram)
    scan_lanes: Optional[jnp.ndarray] = None


def bundle_maps(info, num_bins, missing_types, hist_bins: int,
                feature_scan: bool, group_scan: bool) -> BundleMaps:
    """Host BundleInfo (io/efb.py) -> BundleMaps, with the scan maps asked
    for.  hist_bins: bins per histogram column (the largest group's)."""
    import numpy as np
    nbf = np.asarray(num_bins)
    db = info.feature_default
    idx = lanes = None
    if feature_scan:
        G, B = info.num_groups, int(hist_bins)
        b = np.arange(B, dtype=np.int64)[None, :]
        g = info.feature_group.astype(np.int64)[:, None]
        shift = np.where(info.needs_fix, info.feature_shift, 0)[:, None]
        valid = b < nbf[:, None]
        is_def = info.needs_fix[:, None] & (b == db[:, None])
        idx = jnp.asarray(np.where(valid & ~is_def, g * B + b + shift,
                                   G * B).astype(np.int32))
    if group_scan:
        from .split_pallas import group_lane_statics
        lanes = jnp.asarray(group_lane_statics(
            info.groups, info.feature_lo, info.feature_hi,
            info.feature_shift, info.needs_fix, nbf, db,
            np.asarray(missing_types), int(hist_bins)))
    return BundleMaps(
        feat_col=jnp.asarray(info.feature_group),
        feat_lo=jnp.asarray(info.feature_lo),
        feat_hi=jnp.asarray(info.feature_hi),
        feat_shift=jnp.asarray(info.feature_shift),
        needs_fix=jnp.asarray(info.needs_fix),
        unbundle_idx=idx, scan_lanes=lanes)


def build_forced_candidate(hist, cnt, f_feat, f_thr, f_dl, unbundle,
                           num_bins, default_bins, missing_types, params,
                           cat_width: int = 0):
    """One forced-split plan entry -> the SplitResult to inject into the
    split cache (shared by the label and partition engines so the
    candidate semantics cannot drift; ForceSplits,
    serial_tree_learner.cpp:593-751)."""
    from .split import forced_split_result
    f_g = jnp.sum(hist[0, :, 0])
    f_h = jnp.sum(hist[0, :, 1])
    fsp = forced_split_result(
        unbundle(hist, f_g, f_h, cnt),
        jnp.int32(f_feat), jnp.int32(f_thr), f_g, f_h, cnt,
        num_bins, default_bins, missing_types, params,
        jnp.asarray(bool(f_dl)))
    if cat_width:
        fsp = fsp._replace(cat_mask=jnp.zeros(cat_width, bool))
    return fsp


def unbundle_hist(hist, sum_g, sum_h, cnt, bundle: Optional[BundleMaps],
                  default_bins):
    """[G, B, 3] group histogram -> [F, B, 3] per-feature view.

    Each feature's non-default bins are a gather from its group's bins;
    bundled features' default-bin entries are reconstructed as leaf
    totals minus the gathered sums (Dataset::FixHistogram,
    dataset.cpp:928-949).  Identity without EFB.  Shared by the label
    and partition engines — the two must stay math-identical."""
    if bundle is None:
        return hist
    if bundle.unbundle_idx is None:
        raise ValueError("these BundleMaps were built without the "
                         "feature-space scan map (bundle_maps)")
    F = bundle.feat_col.shape[0]
    flat = jnp.concatenate(
        [hist.reshape(-1, 3), jnp.zeros((1, 3), hist.dtype)], axis=0)
    hf = flat[bundle.unbundle_idx]                      # [F, B, 3]
    tot = jnp.stack([jnp.asarray(sum_g, hist.dtype),
                     jnp.asarray(sum_h, hist.dtype),
                     jnp.asarray(cnt, hist.dtype)])
    fix = tot[None, :] - jnp.sum(hf, axis=1)            # [F, 3]
    upd = jnp.where(bundle.needs_fix[:, None], fix, 0.0)
    return hf.at[jnp.arange(F), default_bins].add(upd)


def feature_bin_of(bins, feat, default_bins, bundle: Optional[BundleMaps]):
    """[n] feature-bin values of `feat` from the (possibly bundled) bin
    matrix: identity without EFB; otherwise the group column decoded back
    to feature bins, rows outside the feature's range -> its default bin."""
    if bundle is None:
        return jax.lax.dynamic_index_in_dim(
            bins, feat, axis=1, keepdims=False).astype(jnp.int32)
    col = jax.lax.dynamic_index_in_dim(
        bins, bundle.feat_col[feat], axis=1, keepdims=False).astype(jnp.int32)
    inside = (col >= bundle.feat_lo[feat]) & (col < bundle.feat_hi[feat])
    return jnp.where(inside, col - bundle.feat_shift[feat],
                     default_bins[feat])


class TreeArrays(NamedTuple):
    """SoA tree storage (tree.h:318-374).  Node arrays sized [max_leaves-1],
    leaf arrays [max_leaves]; children encode leaves as ~leaf_index."""
    split_feature: jnp.ndarray    # int32 [N] inner feature index
    threshold_bin: jnp.ndarray    # int32 [N]
    default_left: jnp.ndarray     # bool  [N]
    missing_type: jnp.ndarray     # int32 [N]
    left_child: jnp.ndarray       # int32 [N]
    right_child: jnp.ndarray      # int32 [N]
    split_gain: jnp.ndarray       # f     [N]
    internal_value: jnp.ndarray   # f     [N] output the node would have as leaf
    internal_count: jnp.ndarray   # int32 [N]
    leaf_value: jnp.ndarray       # f     [L]
    leaf_count: jnp.ndarray       # int32 [L]
    leaf_parent: jnp.ndarray      # int32 [L]
    leaf_depth: jnp.ndarray       # int32 [L]
    num_leaves: jnp.ndarray       # int32 scalar
    is_cat: jnp.ndarray           # bool  [N] categorical decision node
    cat_mask: jnp.ndarray         # bool  [N, W] left-going bins; W=0 when
    #                               the dataset has no categorical features

    @property
    def max_leaves(self) -> int:
        return self.leaf_value.shape[0]


def empty_tree(max_leaves: int, dtype=jnp.float32, cat_bins: int = 0
               ) -> TreeArrays:
    n = max(max_leaves - 1, 1)
    zf = jnp.zeros(n, dtype)
    zi = jnp.zeros(n, jnp.int32)
    return TreeArrays(
        split_feature=zi, threshold_bin=zi, default_left=jnp.zeros(n, bool),
        missing_type=zi, left_child=zi, right_child=zi, split_gain=zf,
        internal_value=zf, internal_count=zi,
        leaf_value=jnp.zeros(max_leaves, dtype),
        leaf_count=jnp.zeros(max_leaves, jnp.int32),
        leaf_parent=jnp.full(max_leaves, -1, jnp.int32),
        leaf_depth=jnp.zeros(max_leaves, jnp.int32),
        num_leaves=jnp.asarray(1, jnp.int32),
        is_cat=jnp.zeros(n, bool),
        cat_mask=jnp.zeros((n, cat_bins), bool),
    )


class GrowState(NamedTuple):
    tree: TreeArrays
    leaf_ids: jnp.ndarray          # [n] int32, -1 = not in this tree (bagging)
    hist_cache: jnp.ndarray        # [L, F, B, 3]
    split_cache: SplitResult       # stacked [L]
    done: jnp.ndarray              # bool scalar
    cegb_used: jnp.ndarray         # [F] bool — features used so far (CEGB
    #                                coupled penalty, feature_used in
    #                                serial_tree_learner.cpp:534-536)
    leaf_min: jnp.ndarray          # [L] per-leaf output lower bound (monotone
    #                                mid-constraint propagation, serial_tree_
    #                                learner.cpp:837-846 + leaf_splits.hpp)
    leaf_max: jnp.ndarray          # [L] per-leaf output upper bound


def _stack_split(res: SplitResult, cache: SplitResult, idx) -> SplitResult:
    return SplitResult(*[None if c is None else c.at[idx].set(v)
                         for c, v in zip(cache, res)])


def _index_split(cache: SplitResult, idx) -> SplitResult:
    return SplitResult(*[None if c is None else c[idx] for c in cache])


def grow_tree_impl(bins: jnp.ndarray,       # [n, F] uint8/16
              grad: jnp.ndarray,            # [n]
              hess: jnp.ndarray,            # [n]
              row_leaf_init: jnp.ndarray,   # [n] int32: 0 in-bag, -1 out
              feature_mask: jnp.ndarray,    # [F] bool
              num_bins: jnp.ndarray,        # [F] int32
              default_bins: jnp.ndarray,    # [F] int32
              missing_types: jnp.ndarray,   # [F] int32
              params: SplitParams,
              monotone: Optional[jnp.ndarray] = None,   # [F] int8 or None
              penalty: Optional[jnp.ndarray] = None,    # [F] or None
              is_categorical: Optional[jnp.ndarray] = None,  # [F] bool or None
              cegb_coupled: Optional[jnp.ndarray] = None,    # [F] or None:
              #   tradeoff * cegb_penalty_feature_coupled, charged while the
              #   feature is unused
              cegb_used_init: Optional[jnp.ndarray] = None,  # [F] bool
              bundle: Optional[BundleMaps] = None,  # EFB layout; bins is
              #   then [n, G] group columns (io/efb.py)
              *,
              forced_splits: tuple = (),   # static BFS list of
              #   (leaf_id, inner_feature, threshold_bin, default_left) from
              #   forcedsplits_filename (ForceSplits,
              #   serial_tree_learner.cpp:593-751); applied before the
              #   best-first loop by injecting +inf-gain cache entries
              max_leaves: int,
              max_depth: int = -1,
              max_bin: int,
              hist_impl: str = "auto",
              rows_per_chunk: int = 16384,
              learner: str = "serial",
              axis_name: Optional[str] = None,
              num_machines: int = 1,
              top_k: int = 20,
              max_cat_threshold: int = 32):
    """Grow one leaf-wise tree; returns (TreeArrays, leaf_ids).

    learner/axis_name select the distributed mode when called inside
    shard_map over a Mesh axis (the TPU re-design of the {serial, feature,
    data, voting} learner family, src/treelearner/tree_learner.cpp:9-33):

    - "serial": single shard, no collectives.
    - "data"  (DataParallelTreeLearner, data_parallel_tree_learner.cpp):
      rows sharded over axis_name; histograms reduce-scattered so each
      device aggregates + scans only its feature shard (full psum fallback
      for EFB/forced splits), winner synced like feature-parallel; rows
      are relabelled locally.
    - "feature" (FeatureParallelTreeLearner, feature_parallel_tree_learner
      .cpp): full data replicated; each shard builds histograms and scans
      only its contiguous F/num_machines feature slice; best split synced by
      all_gather + argmax (SyncUpGlobalBestSplit, parallel_tree_learner
      .h:186-209); splits applied locally everywhere.
    - "voting" (VotingParallelTreeLearner, voting_parallel_tree_learner
      .cpp): rows sharded; local top-k feature vote → global top-2k elected
      features → psum of elected histograms only → global best split.
    """
    n = bins.shape[0]
    F = num_bins.shape[0]        # scan features (== bins columns sans EFB)
    dtype = grad.dtype
    distributed = axis_name is not None and learner != "serial"
    if bundle is not None and learner == "feature":
        raise ValueError("EFB-bundled datasets do not support the "
                         "feature-parallel learner (bundling is disabled "
                         "at dataset construction for it)")
    # DP histogram exchange: reduce-scatter the [F,B,3] histogram so each
    # device aggregates and scans only its own contiguous feature shard,
    # then sync the winner — the reference's ReduceScatter + per-machine
    # FindBestSplitsFromHistograms + SyncUpGlobalBestSplit schedule
    # (data_parallel_tree_learner.cpp:146-245).  d× less collective
    # volume and d× less scan work than a full psum at pod scale.
    # Falls back to the full psum when any consumer needs non-local
    # features: EFB unbundling gathers across group boundaries, forced
    # splits read arbitrary features from the cached histogram, and the
    # coupled-CEGB penalty is a full-width per-feature vector.
    scatter_dp = (distributed and learner == "data"
                  and bundle is None and not forced_splits
                  and cegb_coupled is None
                  and num_machines > 1)
    scatter_pad = 0
    if scatter_dp:
        scatter_pad = -(-F // num_machines) * num_machines - F

    def _pad_feat(a, fill):
        """Pad per-feature statics so F divides the mesh; padded slots are
        inert in the scan (num_bins=1 -> no threshold exists)."""
        if a is None or not scatter_pad:
            return a
        return jnp.concatenate(
            [jnp.asarray(a),
             jnp.full((scatter_pad,), fill, jnp.asarray(a).dtype)])

    if distributed and (learner == "feature" or scatter_dp):
        # contiguous per-shard feature slice (deterministic sharding, the
        # analogue of the bin-count-balanced shuffle at
        # feature_parallel_tree_learner.cpp:30-49).  Feature-parallel
        # slices the BIN MATRIX (each shard histograms only its columns);
        # scatter-DP keeps full local histograms and shards post-reduce.
        if learner == "feature" and F % num_machines:
            raise ValueError(
                "feature-parallel requires num_features (%d) divisible by "
                "num_machines (%d); pad features first (ParallelGrower does)"
                % (F, num_machines))
        f_local = (F + scatter_pad) // num_machines
        f_off = coll.axis_index(axis_name).astype(jnp.int32) * f_local

        p_num_bins = _pad_feat(num_bins, 1)
        p_default_bins = _pad_feat(default_bins, 0)
        p_missing = _pad_feat(missing_types, 0)
        p_feature_mask = feature_mask
        if scatter_pad and p_feature_mask is None:
            p_feature_mask = jnp.ones((F,), jnp.float32)
        p_feature_mask = _pad_feat(p_feature_mask, 0)
        p_monotone = _pad_feat(monotone, 0)
        p_penalty = _pad_feat(penalty, 1)
        p_is_categorical = _pad_feat(is_categorical, False)

        def _slice(a):
            return (None if a is None
                    else jax.lax.dynamic_slice_in_dim(a, f_off, f_local))
        if learner == "feature":
            hist_bins = jax.lax.dynamic_slice_in_dim(bins, f_off, f_local,
                                                     axis=1)
        else:
            hist_bins = bins
        l_num_bins, l_default_bins, l_missing = map(
            _slice, (p_num_bins, p_default_bins, p_missing))
        l_monotone, l_penalty, l_feature_mask = map(
            _slice, (p_monotone, p_penalty, p_feature_mask))
        l_is_categorical = _slice(p_is_categorical)
        l_feature_index = f_off + jnp.arange(f_local, dtype=jnp.int32)
    else:
        hist_bins = bins
        l_num_bins, l_default_bins, l_missing = num_bins, default_bins, missing_types
        l_monotone, l_penalty, l_feature_mask = monotone, penalty, feature_mask
        l_is_categorical = is_categorical
        l_feature_index = None

    def reduce_hist(h):
        # DP: one collective per histogrammed leaf — psum_scatter when
        # each device can scan its own shard (see scatter_dp above),
        # full psum for the EFB/forced-split fallbacks (§3.4.2)
        if distributed and learner == "data":
            if scatter_dp:
                if scatter_pad:
                    h = jnp.concatenate(
                        [h, jnp.zeros((scatter_pad,) + h.shape[1:],
                                      h.dtype)], axis=0)
                return coll.psum_scatter(h, axis_name,
                                            scatter_dimension=0, tiled=True)
            return coll.psum(h, axis_name)
        return h

    def unbundle(hist, sum_g, sum_h, cnt):
        return unbundle_hist(hist, sum_g, sum_h, cnt, bundle, default_bins)

    def _bounds(minc, maxc, nf):
        """Per-leaf scalar output bounds -> per-feature arrays for the
        scans, or None when no monotone constraints exist (zero cost)."""
        if monotone is None or minc is None:
            return None, None
        return (jnp.broadcast_to(jnp.asarray(minc, dtype), (nf,)),
                jnp.broadcast_to(jnp.asarray(maxc, dtype), (nf,)))

    # feature statics for the Pallas scan, hoisted out of the while loop
    # (only the CEGB column is leaf-dependent and is patched per call)
    from . import split_pallas as sp_pl
    # n < 2^24 bound: the kernel's counts ride f32 prefix sums, which
    # are integer-exact only below 2^24 rows per leaf — the XLA path
    # keeps integer cumsums precisely for the billion-row regime
    use_scan_kernel = (is_categorical is None and dtype == jnp.float32
                       and n < (1 << 24))
    _shard_scan = distributed and (learner == "feature" or scatter_dp)
    if use_scan_kernel:
        _fvec_full = sp_pl.build_feature_statics(
            num_bins, default_bins, missing_types, monotone=monotone,
            penalty=penalty, feature_mask=feature_mask, children=1)
        _fvec_local = (_fvec_full if not _shard_scan
                       else sp_pl.build_feature_statics(
                           l_num_bins, l_default_bins, l_missing,
                           monotone=l_monotone, penalty=l_penalty,
                           feature_mask=l_feature_mask, children=1))
    else:
        _fvec_full = _fvec_local = None

    def local_scan(hist, sum_g, sum_h, cnt, nb, db, mt, mono, pen, fmask,
                   icat, findex=None, used=None, minc=None, maxc=None,
                   fvec_pre=None):
        """Per-feature scan (numerical or bin-type-dispatched) + argmax."""
        cegb_pen = None
        if cegb_coupled is not None and used is not None:
            cegb_pen = jnp.where(used, 0.0, cegb_coupled)
        mn, mx = _bounds(minc, maxc, hist.shape[0])
        if use_scan_kernel and icat is None and hist.dtype == jnp.float32:
            # single-launch Pallas scan (ops/split_pallas.py) — the XLA
            # op chain is ~0.45 ms of dispatch latency per call; the
            # kernel matches it up to f32 prefix-sum association, and
            # BOTH engines route here so their trees stay identical
            pf = sp_pl.scan_single(
                hist, sum_g, sum_h, cnt, params, fvec_pre=fvec_pre,
                num_bins=nb, default_bins=db, missing_types=mt,
                monotone=mono, penalty=pen, feature_mask=fmask,
                cegb_pen=cegb_pen, mn=mn, mx=mx)
        elif icat is None:
            pf = best_split_per_feature(hist, sum_g, sum_h, cnt, nb, db, mt,
                                        params, monotone=mono, penalty=pen,
                                        min_constraints=mn, max_constraints=mx,
                                        feature_mask=fmask,
                                        cegb_feature_penalty=cegb_pen)
        else:
            pf = best_split_per_feature_mixed(
                hist, sum_g, sum_h, cnt, nb, db, mt, icat, params,
                monotone=mono, penalty=pen, feature_mask=fmask,
                min_constraints=mn, max_constraints=mx,
                cegb_feature_penalty=cegb_pen,
                max_cat_threshold=max_cat_threshold)
        return select_best_feature(pf, feature_index=findex)

    def leaf_best_split(hist, sum_g, sum_h, cnt, depth, used=None,
                        minc=None, maxc=None):
        if _shard_scan:
            # used (CEGB) stays None here: scatter_dp is disabled when
            # cegb_coupled is set, and feature mode never wired it
            local = local_scan(
                hist, sum_g, sum_h, cnt,
                l_num_bins, l_default_bins, l_missing,
                l_monotone, l_penalty, l_feature_mask, l_is_categorical,
                used=None, minc=minc, maxc=maxc, fvec_pre=_fvec_local)
            # map the local winner to its global feature id
            local = local._replace(feature=jnp.where(
                local.feature >= 0, l_feature_index[local.feature],
                local.feature))
            # SyncUpGlobalBestSplit: pack the candidate into one float + one
            # int vector (the reference packs SplitInfo into a single wire
            # buffer, parallel_tree_learner.h:186-209), gather both in two
            # collectives, argmax on gain; first-hit tie-break = lowest
            # shard = lowest feature id
            fdt = local.gain.dtype
            fvec = jnp.stack([
                local.gain, local.default_left.astype(fdt),
                local.left_sum_gradient, local.left_sum_hessian,
                local.left_output, local.right_sum_gradient,
                local.right_sum_hessian, local.right_output])
            ivec = jnp.stack([local.feature, local.threshold,
                              local.left_count, local.right_count])
            if local.cat_mask is not None:
                ivec = jnp.concatenate(
                    [ivec, local.cat_mask.astype(jnp.int32)])
            fall = coll.all_gather(fvec, axis_name)             # [d, 8]
            iall = coll.all_gather(ivec, axis_name)             # [d, 4+W]
            winner = jnp.argmax(fall[:, 0]).astype(jnp.int32)
            fw, iw = fall[winner], iall[winner]
            res = SplitResult(
                feature=iw[0], threshold=iw[1], gain=fw[0],
                default_left=fw[1] > 0.5,
                left_sum_gradient=fw[2], left_sum_hessian=fw[3],
                left_count=iw[2], left_output=fw[4],
                right_sum_gradient=fw[5], right_sum_hessian=fw[6],
                right_count=iw[3], right_output=fw[7],
                cat_mask=(None if local.cat_mask is None
                          else iw[4:] > 0))
        elif distributed and learner == "voting":
            # voting scans LOCAL histograms first: the unbundle fix needs
            # local leaf totals, recovered from group 0's bins (each
            # in-leaf local row lands in exactly one of them)
            if bundle is not None:
                loc = jnp.sum(hist[0], axis=0)
                hist = unbundle(hist, loc[0], loc[1], loc[2])
            mn, mx = _bounds(minc, maxc, F)
            res = _voting_best_split(
                hist, sum_g, sum_h, cnt,
                num_bins, default_bins, missing_types, params,
                monotone, penalty, feature_mask, is_categorical,
                axis_name=axis_name, num_machines=num_machines,
                top_k=top_k, max_cat_threshold=max_cat_threshold,
                min_constraints=mn, max_constraints=mx,
                fvec_local=_fvec_full, use_kernel=use_scan_kernel)
        else:
            res = local_scan(unbundle(hist, sum_g, sum_h, cnt),
                             sum_g, sum_h, cnt,
                             num_bins, default_bins, missing_types,
                             monotone, penalty, feature_mask, is_categorical,
                             used=used, minc=minc, maxc=maxc,
                             fvec_pre=_fvec_full)
        depth_ok = (max_depth <= 0) | (depth < max_depth)
        blocked = (res.feature < 0) | ~depth_ok
        return res._replace(gain=jnp.where(blocked, K_MIN_SCORE, res.gain),
                            feature=jnp.where(depth_ok, res.feature, -1))

    # ---- root ----------------------------------------------------------
    tree = empty_tree(max_leaves, dtype,
                      cat_bins=(max_bin if is_categorical is not None else 0))
    root_hist = hist_ops.leaf_histogram(hist_bins, grad, hess, row_leaf_init, 0,
                                        max_bin, hist_impl, rows_per_chunk)
    root_hist = reduce_hist(root_hist)
    in_bag = row_leaf_init == 0
    root_g = jnp.sum(grad * in_bag)
    root_h = jnp.sum(hess * in_bag)
    root_c = jnp.sum(in_bag).astype(jnp.int32)
    if distributed and learner in ("data", "voting"):
        # root (cnt, Σg, Σh) Allreduce (data_parallel_tree_learner.cpp:116-142)
        root_g = coll.psum(root_g, axis_name)
        root_h = coll.psum(root_h, axis_name)
        root_c = coll.psum(root_c, axis_name)
    tree = tree._replace(leaf_count=tree.leaf_count.at[0].set(root_c))

    cegb_used0 = (cegb_used_init if cegb_used_init is not None
                  else jnp.zeros(F, bool))
    ninf = jnp.asarray(-jnp.inf, dtype)
    pinf = jnp.asarray(jnp.inf, dtype)
    root_split = leaf_best_split(root_hist, root_g, root_h, root_c,
                                 jnp.asarray(0, jnp.int32), used=cegb_used0,
                                 minc=ninf, maxc=pinf)

    L = max_leaves
    hist_cache = jnp.zeros((L,) + root_hist.shape, dtype).at[0].set(root_hist)
    split_cache = SplitResult(*[
        None if v is None else
        jnp.zeros((L,) + jnp.shape(jnp.asarray(v)), jnp.asarray(v).dtype)
        for v in root_split])
    split_cache = _stack_split(root_split, split_cache, 0)
    # non-existent leaves must never win the argmax
    split_cache = split_cache._replace(
        gain=split_cache.gain.at[1:].set(K_MIN_SCORE))

    state = GrowState(tree=tree, leaf_ids=row_leaf_init, hist_cache=hist_cache,
                      split_cache=split_cache, done=jnp.asarray(False),
                      cegb_used=cegb_used0,
                      leaf_min=jnp.full(L, ninf, dtype),
                      leaf_max=jnp.full(L, pinf, dtype))

    def cond(state: GrowState):
        return (~state.done) & (state.tree.num_leaves < max_leaves)

    def body(state: GrowState) -> GrowState:
        tree = state.tree
        nl = tree.num_leaves                      # current leaf count
        node = nl - 1                             # new internal node index

        best_leaf = jnp.argmax(state.split_cache.gain).astype(jnp.int32)
        sp = _index_split(state.split_cache, best_leaf)
        no_split = sp.gain <= K_MIN_SCORE  # includes min_gain (already masked)

        def do_split(state: GrowState) -> GrowState:
            tree = state.tree
            new_leaf = nl                          # right child leaf id
            feat = sp.feature
            thr = sp.threshold
            # -- relabel rows (DataPartition::Split, data_partition.hpp:108) --
            col = feature_bin_of(bins, feat, default_bins, bundle)
            mt = missing_types[feat]
            db = default_bins[feat]
            mb = num_bins[feat] - 1
            is_missing = ((mt == MISSING_ZERO) & (col == db)) | \
                         ((mt == MISSING_NAN) & (col == mb))
            go_left = jnp.where(is_missing, sp.default_left, col <= thr)
            if is_categorical is not None:
                # categorical: bitset membership decides; bins outside the
                # mask (incl. the NaN bin) go right (CategoricalDecision,
                # tree.h:259-273)
                go_left = jnp.where(is_categorical[feat],
                                    sp.cat_mask[col], go_left)
            in_leaf = state.leaf_ids == best_leaf
            leaf_ids = jnp.where(in_leaf & ~go_left, new_leaf, state.leaf_ids)

            # -- histograms: smaller child by masked pass, sibling by
            #    subtraction (the reference's core scheduling trick) --------
            left_smaller = sp.left_count <= sp.right_count
            small_leaf = jnp.where(left_smaller, best_leaf, new_leaf)
            parent_hist = state.hist_cache[best_leaf]
            small_hist = hist_ops.leaf_histogram(hist_bins, grad, hess, leaf_ids,
                                                 small_leaf, max_bin,
                                                 hist_impl, rows_per_chunk)
            small_hist = reduce_hist(small_hist)
            large_hist = parent_hist - small_hist
            left_hist = jnp.where(left_smaller, small_hist, large_hist)
            right_hist = jnp.where(left_smaller, large_hist, small_hist)
            hist_cache = state.hist_cache.at[best_leaf].set(left_hist)
            hist_cache = hist_cache.at[new_leaf].set(right_hist)

            # -- tree bookkeeping (Tree::Split, tree.h:393-423) -------------
            parent_of = tree.leaf_parent[best_leaf]
            # fix the parent's child pointer that referenced ~best_leaf
            was_left = jnp.where(parent_of >= 0,
                                 tree.left_child[parent_of] == ~best_leaf, False)
            left_child = jnp.where(
                (parent_of >= 0) & was_left,
                tree.left_child.at[parent_of].set(node), tree.left_child)
            right_child = jnp.where(
                (parent_of >= 0) & ~was_left,
                tree.right_child.at[parent_of].set(node), tree.right_child)

            depth = tree.leaf_depth[best_leaf]
            new_is_cat = tree.is_cat
            new_cat_mask = tree.cat_mask
            if is_categorical is not None:
                new_is_cat = new_is_cat.at[node].set(is_categorical[feat])
                new_cat_mask = new_cat_mask.at[node].set(sp.cat_mask)
            tree = tree._replace(
                is_cat=new_is_cat,
                cat_mask=new_cat_mask,
                split_feature=tree.split_feature.at[node].set(feat),
                threshold_bin=tree.threshold_bin.at[node].set(thr),
                default_left=tree.default_left.at[node].set(sp.default_left),
                missing_type=tree.missing_type.at[node].set(missing_types[feat]),
                left_child=left_child.at[node].set(~best_leaf),
                right_child=right_child.at[node].set(~new_leaf),
                split_gain=tree.split_gain.at[node].set(sp.gain.astype(dtype)),
                internal_value=tree.internal_value.at[node].set(
                    tree.leaf_value[best_leaf]),
                internal_count=tree.internal_count.at[node].set(
                    sp.left_count + sp.right_count),
                leaf_value=tree.leaf_value.at[best_leaf].set(
                    sp.left_output.astype(dtype)).at[new_leaf].set(
                    sp.right_output.astype(dtype)),
                leaf_count=tree.leaf_count.at[best_leaf].set(
                    sp.left_count).at[new_leaf].set(sp.right_count),
                leaf_parent=tree.leaf_parent.at[best_leaf].set(node)
                    .at[new_leaf].set(node),
                leaf_depth=tree.leaf_depth.at[best_leaf].set(depth + 1)
                    .at[new_leaf].set(depth + 1),
                num_leaves=nl + 1,
            )

            # -- monotone mid-constraint propagation ------------------------
            # (serial_tree_learner.cpp:837-846): children inherit the
            # parent's [min, max] output bounds; a NUMERICAL split on a
            # monotone feature pins the shared boundary at the mid of the
            # two child outputs so every descendant respects the ancestor
            minP = state.leaf_min[best_leaf]
            maxP = state.leaf_max[best_leaf]
            minL, maxL, minR, maxR = minP, maxP, minP, maxP
            leaf_min, leaf_max = state.leaf_min, state.leaf_max
            if monotone is not None:
                mono_t = monotone[feat].astype(jnp.int32)
                if is_categorical is not None:
                    mono_t = jnp.where(is_categorical[feat], 0, mono_t)
                mid = ((sp.left_output + sp.right_output) / 2).astype(dtype)
                maxL = jnp.where(mono_t > 0, mid, maxP)
                minR = jnp.where(mono_t > 0, mid, minP)
                minL = jnp.where(mono_t < 0, mid, minP)
                maxR = jnp.where(mono_t < 0, mid, maxP)
                leaf_min = leaf_min.at[best_leaf].set(minL).at[new_leaf].set(minR)
                leaf_max = leaf_max.at[best_leaf].set(maxL).at[new_leaf].set(maxR)

            # -- children best splits ---------------------------------------
            used2 = state.cegb_used.at[feat].set(True)
            lsp = leaf_best_split(left_hist, sp.left_sum_gradient,
                                  sp.left_sum_hessian, sp.left_count,
                                  depth + 1, used=used2, minc=minL, maxc=maxL)
            rsp = leaf_best_split(right_hist, sp.right_sum_gradient,
                                  sp.right_sum_hessian, sp.right_count,
                                  depth + 1, used=used2, minc=minR, maxc=maxR)
            split_cache = _stack_split(lsp, state.split_cache, best_leaf)
            split_cache = _stack_split(rsp, split_cache, new_leaf)

            return GrowState(tree=tree, leaf_ids=leaf_ids,
                             hist_cache=hist_cache, split_cache=split_cache,
                             done=jnp.asarray(False), cegb_used=used2,
                             leaf_min=leaf_min, leaf_max=leaf_max)

        return jax.lax.cond(no_split,
                            lambda s: s._replace(done=jnp.asarray(True)),
                            do_split, state)

    # Forced splits first (trace-time unrolled: the BFS plan is static):
    # overwrite the target leaf's cache entry with a +inf-gain forced
    # result and run one standard body step to apply it.  The plan's
    # static leaf numbering assumes every entry applies (entry i targets
    # static leaf plan[i][0] and creates static leaf i+1), but an entry
    # can be invalid at runtime (empty child, leaf budget).  A traced
    # static->dynamic leaf map keeps later entries addressed correctly
    # regardless: an invalid entry leaves its created leaf mapped to -1,
    # so its whole forced subtree is abandoned (ForceSplits,
    # serial_tree_learner.cpp:593-751) while siblings from other branches
    # still resolve to the right dynamic leaf ids.
    leafmap = jnp.full((len(forced_splits) + 1,), -1, jnp.int32).at[0].set(0)
    for i, (f_leaf, f_feat, f_thr, f_dl) in enumerate(forced_splits):
        if i >= max_leaves - 1:
            break      # each applied split adds one leaf; bound the count
        dyn_leaf = leafmap[f_leaf]
        safe_leaf = jnp.maximum(dyn_leaf, 0)
        fsp = build_forced_candidate(
            state.hist_cache[safe_leaf], state.tree.leaf_count[safe_leaf],
            f_feat, f_thr, f_dl, unbundle,
            num_bins, default_bins, missing_types, params,
            cat_width=(state.split_cache.cat_mask.shape[1]
                       if state.split_cache.cat_mask is not None else 0))
        valid = (dyn_leaf >= 0) & (fsp.gain > K_MIN_SCORE) & \
                (state.tree.num_leaves < max_leaves)
        injected = state._replace(
            split_cache=_stack_split(fsp, state.split_cache, safe_leaf))
        dyn_new = state.tree.num_leaves    # right-child leaf id body assigns
        stepped = body(injected)._replace(done=jnp.asarray(False))

        def _sel(a, b):
            if a is None:
                return None
            return jnp.where(valid, a, b)

        state = jax.tree_util.tree_map(
            _sel, stepped, state,
            is_leaf=lambda x: x is None)
        leafmap = leafmap.at[i + 1].set(jnp.where(valid, dyn_new, -1))
        # on failure also unmap the target: the only later entry that
        # references static id f_leaf is this entry's LEFT-child entry
        # (each static leaf is split at most once), which must be
        # abandoned along with the right subtree
        leafmap = leafmap.at[f_leaf].set(jnp.where(valid, dyn_leaf, -1))

    state = jax.lax.while_loop(cond, body, state)
    return state.tree, state.leaf_ids


_TREE_FLOAT_FIELDS = ("split_gain", "internal_value", "leaf_value")


def _tree_field_spec(max_leaves: int, cat_bins: int):
    import numpy as np

    n = max(max_leaves - 1, 1)
    L = max_leaves
    return [("split_feature", (n,), np.int32),
            ("threshold_bin", (n,), np.int32),
            ("default_left", (n,), bool),
            ("missing_type", (n,), np.int32),
            ("left_child", (n,), np.int32),
            ("right_child", (n,), np.int32),
            ("split_gain", (n,), None),
            ("internal_value", (n,), None),
            ("internal_count", (n,), np.int32),
            ("leaf_value", (L,), None),
            ("leaf_count", (L,), np.int32),
            ("leaf_parent", (L,), np.int32),
            ("leaf_depth", (L,), np.int32),
            ("num_leaves", (), np.int32),
            ("is_cat", (n,), bool),
            ("cat_mask", (n, cat_bins), bool)]


@jax.jit
def pack_tree_arrays(t: TreeArrays):
    """Flatten a TreeArrays into TWO device vectors (ints exactly as int32,
    floats in their own dtype).  One host fetch of this pair replaces ~17
    per-field transfers, each of which pays a full round-trip on
    remote-attached TPUs."""
    ints, floats = [], []
    for name, x in zip(TreeArrays._fields, t):
        if name in _TREE_FLOAT_FIELDS:
            floats.append(jnp.ravel(x))
        else:
            ints.append(jnp.ravel(x).astype(jnp.int32))
    return jnp.concatenate(ints), jnp.concatenate(floats)


def unpack_tree_vectors(ivec, fvec, max_leaves: int,
                        cat_bins: int) -> TreeArrays:
    """Host-side inverse of pack_tree_arrays (numpy in, numpy out)."""
    import numpy as np

    out, ioff, foff = {}, 0, 0
    for name, shape, dtype in _tree_field_spec(max_leaves, cat_bins):
        size = int(np.prod(shape)) if shape else 1
        if name in _TREE_FLOAT_FIELDS:
            out[name] = fvec[foff:foff + size].reshape(shape)
            foff += size
        else:
            out[name] = (ivec[ioff:ioff + size].reshape(shape)
                         .astype(dtype))
            ioff += size
    return TreeArrays(**out)


def fetch_tree_arrays(t: TreeArrays) -> TreeArrays:
    """Device TreeArrays -> host (numpy) TreeArrays in one bulk transfer."""
    ivec, fvec = jax.device_get(pack_tree_arrays(t))
    return unpack_tree_vectors(ivec, fvec, t.max_leaves, t.cat_mask.shape[1])


grow_tree = partial(jax.jit, static_argnames=(
    "max_leaves", "max_depth", "max_bin", "hist_impl", "rows_per_chunk",
    "learner", "axis_name", "num_machines", "top_k",
    "max_cat_threshold", "forced_splits"))(grow_tree_impl)


def _voting_best_split(local_hist, sum_g, sum_h, cnt,
                       num_bins, default_bins, missing_types,
                       params: SplitParams,
                       monotone, penalty, feature_mask, is_categorical,
                       *, axis_name: str, num_machines: int, top_k: int,
                       max_cat_threshold: int = 32,
                       min_constraints=None,
                       max_constraints=None,
                       fvec_local=None,
                       use_kernel: bool = True) -> SplitResult:
    """PV-tree best split (voting_parallel_tree_learner.cpp:257-460).

    local_hist [F, B, 3] holds *local-shard* rows only.  Protocol:
    1. local per-feature scan against 1/num_machines-rescaled min-data
       thresholds (the locally-rescaled config, voting...cpp:50-57);
    2. local top-k features by gain → Allgather (the LightSplitInfo
       allgather, voting...cpp:322-356);
    3. GlobalVoting: vote count per feature, elect top-2k
       (voting...cpp:166-195), smaller feature id on ties;
    4. psum of the elected features' histograms only (CopyLocalHistogram +
       ReduceScatter, voting...cpp:198-254) — O(2k·B) bytes instead of
       O(F·B);
    5. full-threshold scan on the global histograms, winner selected among
       the elected features.
    """
    F = local_hist.shape[0]
    k = min(top_k, F)
    # local parent sums: every in-leaf row lands in exactly one bin of
    # feature 0, so its bin-sum recovers the local leaf totals
    loc_g = jnp.sum(local_hist[0, :, 0])
    loc_h = jnp.sum(local_hist[0, :, 1])
    loc_c = jnp.round(jnp.sum(local_hist[0, :, 2])).astype(jnp.int32)

    def scan(hist, sg, sh, sc, nb, db, mt, mono, pen, fmask, icat, p,
             mn=None, mx=None, fvec_pre=None):
        if (icat is None and hist.dtype == jnp.float32
                and use_kernel):
            # same Pallas kernel as the serial scan — voting must elect
            # and score with bit-identical gains or its trees drift from
            # the serial learner on prefix-sum association ties
            from . import split_pallas as sp_pl
            return sp_pl.scan_single(
                hist, sg, sh, sc, p, fvec_pre=fvec_pre,
                num_bins=nb, default_bins=db, missing_types=mt,
                monotone=mono, penalty=pen, feature_mask=fmask,
                mn=mn, mx=mx)
        if icat is None:
            return best_split_per_feature(hist, sg, sh, sc, nb, db, mt, p,
                                          monotone=mono, penalty=pen,
                                          min_constraints=mn,
                                          max_constraints=mx,
                                          feature_mask=fmask)
        return best_split_per_feature_mixed(
            hist, sg, sh, sc, nb, db, mt, icat, p,
            monotone=mono, penalty=pen, feature_mask=fmask,
            min_constraints=mn, max_constraints=mx,
            max_cat_threshold=max_cat_threshold)

    # params leaves may be tracers (SplitParams rides the jit pytree)
    local_params = params._replace(
        min_data_in_leaf=jnp.maximum(params.min_data_in_leaf // num_machines, 1),
        min_sum_hessian_in_leaf=params.min_sum_hessian_in_leaf / num_machines)
    pf_local = scan(local_hist, loc_g, loc_h, loc_c,
                    num_bins, default_bins, missing_types,
                    monotone, penalty, feature_mask, is_categorical,
                    local_params, min_constraints, max_constraints,
                    fvec_pre=fvec_local)

    _, top_idx = jax.lax.top_k(pf_local.gain, k)                # [k]
    top_valid = jnp.take(pf_local.gain, top_idx) > K_MIN_SCORE
    all_top = coll.all_gather(top_idx, axis_name)            # [d, k]
    all_valid = coll.all_gather(top_valid, axis_name)        # [d, k]

    votes = jnp.zeros(F, jnp.int32).at[all_top.reshape(-1)].add(
        all_valid.reshape(-1).astype(jnp.int32))                # [F]
    n_elect = min(2 * k, F)
    # lax.top_k is stable (lower index first on ties) → equal-vote ties
    # break toward the smaller feature id (stable sort in GlobalVoting)
    _, elected = jax.lax.top_k(votes, n_elect)                  # [n_elect]
    elected = elected.astype(jnp.int32)

    glob_hist = coll.psum(jnp.take(local_hist, elected, axis=0), axis_name)

    def take(a):
        return None if a is None else jnp.take(a, elected, axis=0)

    pf_glob = scan(glob_hist, sum_g, sum_h, cnt,
                   take(num_bins), take(default_bins), take(missing_types),
                   take(monotone), take(penalty), take(feature_mask),
                   take(is_categorical), params,
                   take(min_constraints), take(max_constraints))
    return select_best_feature(pf_glob, feature_index=elected)


@jax.jit
def predict_leaf_inner(bins: jnp.ndarray, tree: TreeArrays,
                       num_bins: jnp.ndarray, default_bins: jnp.ndarray,
                       bundle: Optional[BundleMaps] = None) -> jnp.ndarray:
    """Leaf index per row by walking the tree over *inner* bin values
    (Tree::GetLeafAt + DecisionInner, tree.h:233-248, 289-296).

    Vectorized node walk: every row holds a current node (>=0 internal,
    negative = ~leaf); iterate until all rows rest at leaves.  With EFB
    `bins` holds group columns decoded per node through `bundle`.
    """
    n = bins.shape[0]
    start = jnp.where(tree.num_leaves > 1, 0, ~0)
    node = jnp.full((n,), start, jnp.int32)

    def cond(node):
        return jnp.any(node >= 0)

    def body(node):
        nd = jnp.maximum(node, 0)
        feat = tree.split_feature[nd]
        if bundle is None:
            gcol = feat
        else:
            gcol = bundle.feat_col[feat]
        col = jnp.take_along_axis(bins, gcol[:, None].astype(jnp.int32),
                                  axis=1)[:, 0].astype(jnp.int32)
        if bundle is not None:
            inside = (col >= bundle.feat_lo[feat]) & \
                     (col < bundle.feat_hi[feat])
            col = jnp.where(inside, col - bundle.feat_shift[feat],
                            default_bins[feat])
        mt = tree.missing_type[nd]
        db = default_bins[tree.split_feature[nd]]
        mb = num_bins[tree.split_feature[nd]] - 1
        is_missing = ((mt == MISSING_ZERO) & (col == db)) | \
                     ((mt == MISSING_NAN) & (col == mb))
        go_left = jnp.where(is_missing, tree.default_left[nd],
                            col <= tree.threshold_bin[nd])
        if tree.cat_mask.shape[1] > 0:
            go_left = jnp.where(tree.is_cat[nd], tree.cat_mask[nd, col],
                                go_left)
        nxt = jnp.where(go_left, tree.left_child[nd], tree.right_child[nd])
        return jnp.where(node >= 0, nxt, node)

    node = jax.lax.while_loop(cond, body, node)
    return ~node  # leaf index


def predict_value_inner(bins: jnp.ndarray, tree: TreeArrays,
                        num_bins: jnp.ndarray, default_bins: jnp.ndarray,
                        bundle: Optional[BundleMaps] = None) -> jnp.ndarray:
    leaf = predict_leaf_inner(bins, tree, num_bins, default_bins, bundle)
    return tree.leaf_value[leaf]
