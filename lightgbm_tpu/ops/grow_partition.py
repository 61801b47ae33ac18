"""Partition-engine leaf-wise tree growth (serial learner, TPU fast path).

The arena re-design of SerialTreeLearner::Train (reference
src/treelearner/serial_tree_learner.cpp:169-233): instead of the label
engine's per-split masked pass over all n rows (ops/grow.py), rows live
physically grouped by leaf in the feature-major bf16-plane arena of
ops/partition_pallas.py, so each split costs O(parent) to partition and
O(smaller_child) to histogram — the reference's asymptotics
(DataPartition::Split data_partition.hpp:108-160 + the smaller/larger
histogram choreography serial_tree_learner.cpp:360-437, with the sibling
recovered by subtraction, feature_histogram.hpp:67-73).

Segment allocation is a device-side bump allocator in 256-column units:
the larger child overwrites the parent segment in place, the smaller
child is appended at the cursor.  On overflow the tree simply stops
growing (the truncated flag is returned; raise tpu_arena_factor) — the
default arena budget covers a balanced 255-leaf tree, and the GBDT
driver chooses the label engine up front for configs this engine does
not cover.

Supports categorical bitset splits, EFB-bundled datasets (both via the
go-left mask decision), forced splits (the same cache-injection scheme
as the label engine) and all three distributed learners (axis_name +
learner):

- "data":    rows sharded, local arenas, psum'd histograms — the
  DataParallelTreeLearner schedule (data_parallel_tree_learner.cpp:
  116-245) with ReduceScatter/Allreduce collapsed into psum;
- "feature": data replicated (every device has the full arena — the
  reference's FP learner replicates data too, feature_parallel_tree_
  learner.cpp:30-74), the best-split SEARCH sharded by features, winner
  synced with an all_gather of packed split rows (SyncUpGlobalBestSplit,
  parallel_tree_learner.h:186-209); the partition itself is local
  because every device holds all feature channels;
- "voting":  rows sharded + per-leaf top-k election so only the ~2k
  elected features' histograms ride the psum (PV-tree,
  voting_parallel_tree_learner.cpp:166-460).

Remaining restrictions vs the label engine (the GBDT driver
auto-selects): f32 only, max_bin <= 256, n < 2^24 (rowids ride three
byte planes exactly).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..parallel import collective as coll
from . import partition_pallas as pp
from . import quantize as qz
from . import split_pallas as sp_pl
from . import valid_score
from .bagging import member
from .grow import MISSING_NAN, MISSING_ZERO, BundleMaps, TreeArrays
from .split import (K_MIN_SCORE, SplitParams,
                    best_split_per_feature_mixed, select_best_feature)

ALLOC = pp.FLUSH_W         # allocation granularity (columns)


def _align(x, unit):
    return (x + unit - 1) // unit * unit


class PartState(NamedTuple):
    """Packed grow-loop state: matrices instead of per-field arrays so
    each split is a handful of row scatters (see the packed-rows note in
    grow_tree_partition_impl)."""
    node_mat: jnp.ndarray          # [N, 16] f32 node table: feat, thr,
    #   default_left, missing_type, left_child, right_child, gain,
    #   internal_value, internal_count, is_cat, pad...
    leaf_mat: jnp.ndarray          # [L, 8] f32 leaf table: value, count,
    #   parent, depth, min, max, seg_start, seg_local (LOCAL lengths —
    #   differ from count under data-parallel sharding)
    node_cat: jnp.ndarray          # [N, cat_w] f32 0/1 left-going bins
    nl: jnp.ndarray                # int32 num_leaves
    arena: jnp.ndarray             # [C, cap] bf16
    cursor: jnp.ndarray            # int32 bump cursor (256-aligned)
    hist_cache: jnp.ndarray        # [K, G, B, 3] slot cache (HistogramPool,
    #   feature_histogram.hpp:646-818: K < L spills by LRU; a missed
    #   parent is recomputed from its still-intact segment)
    slot_leaf: jnp.ndarray         # [K] int32 leaf whose hist each slot holds
    slot_tick: jnp.ndarray         # [K] int32 write-recency for eviction
    tick: jnp.ndarray              # int32 monotone write counter
    split_cache: jnp.ndarray       # [L, ROW_W + cat_w] f32 packed rows
    done: jnp.ndarray
    cegb_used: jnp.ndarray         # [F] bool (CEGB coupled feature_used)
    truncated: jnp.ndarray         # bool: growth stopped by arena overflow


def grow_tree_partition_impl(
        arena_buf: jnp.ndarray,       # [C, cap] bf16 scratch (donated)
        bins_t: jnp.ndarray,          # [F, n] bf16/f32 feature-major bins
        grad: jnp.ndarray,            # [n] f32
        hess: jnp.ndarray,            # [n] f32
        row_leaf_init: jnp.ndarray,   # [n] int32: 0 in-bag, -1 out; the
        #   ops/bagging.py bag instead where bag_rows > 0
        feature_mask: jnp.ndarray,    # [F] bool
        num_bins: jnp.ndarray,        # [F] int32
        default_bins: jnp.ndarray,    # [F] int32
        missing_types: jnp.ndarray,   # [F] int32
        params: SplitParams,
        monotone: Optional[jnp.ndarray] = None,
        penalty: Optional[jnp.ndarray] = None,
        cegb_coupled: Optional[jnp.ndarray] = None,
        cegb_used_init: Optional[jnp.ndarray] = None,
        is_categorical: Optional[jnp.ndarray] = None,
        bundle: Optional[BundleMaps] = None,
        *,
        max_leaves: int,
        max_depth: int = -1,
        max_bin: int,
        emit: str = "leaf_ids",
        full_bag: bool = False,
        max_cat_threshold: int = 32,
        axis_name: Optional[str] = None,
        learner: str = "data",
        num_machines: int = 1,
        top_k: int = 20,
        hist_slots: int = 0,
        forced_splits: tuple = (),
        pristine: bool = False,
        carried_root=None,            # traced col offset of an ALREADY-
        #   assembled root segment (carried-arena mode): bins/rowids AND
        #   score/label planes live at [carried_root, carried_root+n);
        #   assembly only refreshes the g/h planes there.  Requires
        #   full_bag or bag_rows; emit="carry" compacts the finished tree's segments
        #   to carry_dst for the next iteration's root.
        carry_dst=None,               # traced col offset for emit="carry"
        carried_bump0: int = 0,       # static first bump column (past
        #                               both root slots) in carried mode
        quantized: bool = False,      # static: grad/hess arrive as int8
        #   CODES (ops/quantize) riding TWO payload planes instead of six
        #   residue planes; histogram kernels run the 3-component radix
        #   and results are dequantized per-kernel via quant_scales
        quant_scales=None,            # traced (g_scale, h_scale) f32
        bag_rows: int = 0,            # static rows in the bag, which
        #   row_leaf_init then is: the root's in-bag rows are those it
        #   holds, by their row ids, in whatever order the root segment
        #   has them (not with full_bag).  The out-of-bag rows
        #   (n - bag_rows) are scored in the program from the
        #   tree (scope lgbm.oob_score), emit="score" returns every row's
        #   value and emit="carry" carries them behind the in-bag rows
        interpret: bool = False):
    """Grow one leaf-wise tree.

    bins_t holds the (possibly EFB-bundled) GROUP columns [G, n]; the
    per-feature arrays (feature_mask/num_bins/...) address ORIGINAL
    features.  With bundles the numerical scan reads the bundled
    histogram itself (bundle.scan_lanes, split_pallas._group_scan_kernel);
    the categorical scan, the voting learner and forced splits go through
    the bundle unbundling, exactly like the label engine
    (Dataset::FixHistogram, dataset.cpp:928-949).

    With axis_name (inside shard_map), rows are sharded per device: each
    device runs its own arena over local rows while histograms are
    psum'd, so split decisions are globally identical — the reference's
    DataParallelTreeLearner schedule (data_parallel_tree_learner.cpp:
    116-245) with the ReduceScatter/Allreduce pair collapsed into psum.

    Returns (TreeArrays, leaf_ids [n] int32, arena, truncated) — the arena
    scratch is returned so the caller can thread (and donate) it across
    trees instead of re-materializing a multi-GB zero buffer per
    iteration; `truncated` (bool scalar) reports growth stopped early by
    arena overflow so the driver can warn (raise tpu_arena_factor).
    """
    G, n = bins_t.shape               # group (arena) columns
    F = num_bins.shape[0]             # original features
    C, cap = arena_buf.shape
    if n >= (1 << 24):
        raise ValueError("partition engine supports n < 2^24 rows")
    if C != pp.arena_channels(G):
        raise ValueError("arena_buf channel dim mismatch")
    dist = axis_name is not None
    dp = dist and learner == "data"
    fp = dist and learner == "feature"
    vp = dist and learner == "voting"
    if fp and bundle is not None:
        raise ValueError("EFB-bundled datasets do not support the "
                         "feature-parallel learner (bundling is disabled "
                         "at dataset construction for it)")
    if fp and F % num_machines:
        raise ValueError(
            "feature-parallel requires num_features (%d) divisible by "
            "num_machines (%d); pad features first (ParallelGrower does)"
            % (F, num_machines))
    # quantized + distributed is legal since the Collective refactor:
    # callers agree code scales globally first (qz.global_scales — one
    # allreduce-max of the two per-tree maxima), after which the psum'd
    # integer histograms are exactly a single encoder's sums
    if quantized and quant_scales is None:
        raise ValueError("quantized=True requires quant_scales")
    dtype = jnp.float32
    Fp = pp.feature_channels(G)
    L = max_leaves
    seg = partial(pp.segment_histogram, num_features=G, max_bin=max_bin,
                  quantized=quantized, interpret=interpret)
    part = partial(pp.partition_segment, interpret=interpret)
    if quantized:
        _gs, _hs = quant_scales

        def deq(h):
            # integer code sums -> f32 (g, h, count); exact within the
            # qz.exact_rows() envelope (docs/Quantized.md)
            return qz.dequantize_hist(h, _gs, _hs)
    else:
        def deq(h):
            return h.astype(dtype)

    # ---- arena assembly --------------------------------------------------
    # Pristine layout (the driver's path): feature bins + rowid planes
    # were written ONCE per dataset by pp.init_pristine and pristine rows
    # are never overwritten (the first split's stream A is redirected to
    # the work region), so per-tree assembly only refreshes the six g/h
    # payload planes — 6/48 channels instead of a full rebuild.  Legacy
    # layout (pristine=False) rebuilds everything into the scratch; stale
    # columns beyond n are never read (kernels mask by segment counts).
    adt = pp.ARENA_DT
    n_al = _align(n, pp.TILE)
    carried = carried_root is not None
    if carried and dist:
        raise ValueError("carried-arena mode is serial")
    bagged = bag_rows > 0
    if full_bag and bagged or bag_rows > n:
        raise ValueError("a bag of %d rows under full_bag=%s, n=%d"
                         % (bag_rows, full_bag, n))
    if carried and not full_bag and not bagged:
        raise ValueError("carried-arena mode draws its bag by row id: "
                         "pass `bag_rows`")
    work0 = pp.pristine_work0(n) if pristine else 0
    with jax.named_scope("lgbm.root"):
        if quantized:
            # TWO code planes at [Fp, Fp+2) (g_code, h_code as exact small
            # integers in bf16); planes Fp+2..Fp+5 go stale and are never
            # read — the 3-component radix stops at the count plane
            gh = pp.pack_code_planes(grad, hess)
        else:
            gh = jnp.concatenate(
                [c[None] for c in pp.split_f32(grad)]
                + [c[None] for c in pp.split_f32(hess)], axis=0)
        # full_bag quantized roots skip the XLA plane write entirely: the
        # fused root kernel below DMAs the fresh codes into the arena while
        # it streams the feature rows for the root histogram — one pass pays
        # for both
        fuse_root = quantized and full_bag
        if carried:
            # bins/rowids AND the score/label planes already sit at the
            # carried root (compacted there by the previous tree's
            # emit="carry"); only the g/h planes need this tree's gradients
            arena = (arena_buf if fuse_root else
                     jax.lax.dynamic_update_slice(
                         arena_buf, gh,
                         (jnp.int32(Fp),
                          jnp.asarray(carried_root, jnp.int32))))
        elif pristine:
            arena = (arena_buf if fuse_root else
                     jax.lax.dynamic_update_slice(arena_buf, gh, (Fp, 0)))
        else:
            chans = [bins_t.astype(adt)]
            if Fp > G:
                chans.append(jnp.zeros((Fp - G, n), adt))
            chans += [gh]
            if quantized:
                # keep the rowid planes at their fixed rows Fp+6..Fp+8
                chans.append(jnp.zeros((pp.N_AUX - 3 - gh.shape[0], n), adt))
            chans += [c[None] for c in
                      pp.split_rowid(jnp.arange(n, dtype=jnp.int32))]
            if C > Fp + pp.N_AUX:
                chans.append(jnp.zeros((C - Fp - pp.N_AUX, n), adt))
            arena = jax.lax.dynamic_update_slice(
                arena_buf, jnp.concatenate(chans, axis=0), (0, 0))

        # ---- root: in-bag rows compacted into one segment ----------------
        # decision-mode partition calls never read the pred stream; they get
        # a tile-sized dummy (a [1, cap] buffer would be constant-sunk into
        # the while loop and re-materialized every split)
        pred_dummy = jnp.zeros((1, pp.TILE), dtype)
        if full_bag:
            # no bagging: every row is in-bag, the root segment IS the
            # assembled prefix — skip the O(n) compaction pass and the
            # OOB dump region entirely
            root_c = jnp.int32(n)
            if carried:
                root_s0 = jnp.asarray(carried_root, jnp.int32)
                cursor0 = jnp.int32(carried_bump0)
            else:
                root_s0 = jnp.int32(0)
                cursor0 = jnp.int32(work0 + n_al if pristine
                                    else n_al + pp.TILE)
        else:
            if carried:
                # the carried root holds the rows in the previous tree's
                # leaf order: in-bag rows compacted in place (lagging
                # writes), out-of-bag rows dumped past both root slots
                root_at = jnp.asarray(carried_root, jnp.int32)
                bag_dst = root_at
                oob_at = carried_bump0
                oob_end = oob_at + _align(n - bag_rows, pp.TILE)
                # the pred stream is read by absolute column: it spans the
                # higher root slot (the carried layout's second slot is
                # pristine_work0 wide)
                width = pp.pristine_work0(n) + n_al + pp.TILE
            else:
                # pristine: in-bag rows copied to the work region (pristine
                # rows intact for the next tree); legacy: compacted in place
                root_at = jnp.int32(0)
                bag_dst = jnp.int32(work0 if pristine else 0)
                oob_at = (work0 if pristine else 0) + n_al
                oob_end = oob_at + n_al
                width = n_al + pp.TILE
            oob_dst = jnp.int32(oob_at)
            if bagged:
                rid = (pp.merge_rowid(*jax.lax.dynamic_slice(
                    arena, (jnp.int32(Fp + 6), root_at), (3, n)))
                    if carried else jnp.arange(n, dtype=jnp.int32))
                in_bag = member(row_leaf_init, rid)
                pred0 = jax.lax.dynamic_update_slice(
                    jnp.zeros((1, width), dtype),
                    in_bag.astype(dtype)[None, :], (jnp.int32(0), root_at))
            else:
                in_bag = (row_leaf_init == 0)
                pred0 = jnp.pad(in_bag.astype(dtype), (0, cap - n))[None, :]
            # fused compaction + in-bag (stream A) histogram: the root
            # histogram covers every row the pass reads anyway, so here the
            # fusion is pure saving (one full-n re-read + a launch)
            arena, counts0, root_hist_b = part(
                arena, pred0, root_at, jnp.int32(n),
                bag_dst, oob_dst, hist_stream=0,
                num_features=G, max_bin=max_bin, quantized=quantized)
            root_c = counts0[0]
            root_s0 = bag_dst
            cursor0 = jnp.int32(oob_end)     # past the oob dump space

        if full_bag:
            if quantized:
                # fused mega-kernel (ISSUE 8 tentpole): ONE double-buffered
                # pass over the root segment writes the fresh code planes
                # AND accumulates the root histogram — replacing the XLA
                # plane update plus a separate full-read seg() launch.
                # Unlike the per-child fusion dead end below (the fh gate),
                # the root histogram covers every row the refresh touches
                # anyway, so this fusion is pure byte saving (the same
                # argument as the bagging hist_stream above).
                arena, root_hist = pp.fused_refresh_histogram(
                    arena, gh, root_s0, root_c, num_features=G,
                    max_bin=max_bin, interpret=interpret)
            else:
                root_hist = seg(arena, root_s0, root_c)
        else:
            root_hist = root_hist_b.astype(dtype)
        root_c_local = root_c
        if dp:
            # DP: one histogram allreduce; global sums/counts fall out of it.
            # The psum runs BEFORE dequantization: integer code sums reduce
            # exactly in f32, so the global quantized histogram is bitwise a
            # single encoder's sums (the module docstring's contract); the
            # unquantized histogram is f32 either way.
            root_hist = coll.psum(root_hist, axis_name)
            root_c = coll.psum(root_c, axis_name)
        root_hist = deq(root_hist)
        root_g = jnp.sum(root_hist[0, :, 0])
        root_h = jnp.sum(root_hist[0, :, 1])
        if vp:
            # voting keeps histograms LOCAL; only the scalar root stats ride
            # an allreduce (data_parallel_tree_learner.cpp:116-142)
            root_g = coll.psum(root_g, axis_name)
            root_h = coll.psum(root_h, axis_name)
            root_c = coll.psum(root_c, axis_name)

    def unbundle(hist, sum_g, sum_h, cnt):
        from .grow import unbundle_hist
        return unbundle_hist(hist, sum_g, sum_h, cnt, bundle, default_bins)

    # ---- packed split rows & tree state ---------------------------------
    # The while-loop body ran ~900 XLA ops per iteration when every
    # SplitResult / TreeArrays field was its own array (round-4 jaxpr
    # audit: 159 select_n, 50 scatter, 49 dynamic_slice, ...) — per-op
    # dispatch latency made that the biggest cost after the kernels.
    # Inside the loop a leaf's best split is ONE [ROW_W(+cat)] f32 row
    # (lane layout split_pallas._O*, produced in-kernel by the scan's
    # select stage), the node table and the leaf table are ONE matrix
    # each, so applying a split is a handful of row scatters instead of
    # ~45 per-field ones.  TreeArrays materializes once after the loop.
    RW = sp_pl.ROW_W
    cat_w = max_bin if is_categorical is not None else 0
    RWC = RW + cat_w
    NEGF = jnp.float32(sp_pl.NEG)
    NEG_GATE = jnp.float32(sp_pl.NEG_GATE)
    N = max(L - 1, 1)
    use_scan_kernel = is_categorical is None
    if fp:
        # contiguous per-shard feature slice (the analogue of the
        # bin-count-balanced shuffle, feature_parallel_tree_learner.cpp:
        # 30-49): each device SCANS only its own features; data (and so
        # histograms and partitions) are replicated
        f_local = F // num_machines
        _dev = coll.axis_index(axis_name).astype(jnp.int32)
        scan_feature_mask = feature_mask & (
            (jnp.arange(F, dtype=jnp.int32) // f_local) == _dev)
    else:
        scan_feature_mask = feature_mask
    # a bundled set's numerical scan works on the bundled histogram
    # itself (split_pallas._group_scan_kernel): rows are the G group
    # columns, never the F features, and no [F, B, 3] array is made per
    # split.  Voting elects per feature and keeps the feature-space scan.
    # The caller decides (models/gbdt.py::_setup_tree_engine) by the map
    # it hands over.
    group_scan = bundle is not None and bundle.scan_lanes is not None
    if group_scan and (vp or not use_scan_kernel):
        raise ValueError("the group-space scan map (bundle.scan_lanes) "
                         "serves the numerical scan of the serial and "
                         "data-parallel learners only")
    fvec1 = fvec2 = lane_planes = None
    with jax.named_scope("lgbm.root"):
        if group_scan:
            lane_planes = sp_pl.group_lane_planes(
                bundle.scan_lanes, monotone=monotone, penalty=penalty,
                feature_mask=scan_feature_mask)
        elif use_scan_kernel:
            fvec1 = sp_pl.build_feature_statics(
                num_bins, default_bins, missing_types, monotone=monotone,
                penalty=penalty, feature_mask=scan_feature_mask, children=1)
            fvec2 = jnp.concatenate([fvec1, fvec1], axis=0)

    def _patch_cegb(fvec, used, children):
        if cegb_coupled is None or used is None:
            return fvec
        pen = jnp.where(used, 0.0, cegb_coupled).astype(jnp.float32)
        return fvec.at[:, sp_pl._CEGBF].set(
            jnp.concatenate([pen] * children) if children > 1 else pen)

    def _group_rows(hist, sum_g, sum_h, cnt, used, mn, mx):
        """[CH, RWC] best rows of CH leaves from their bundled histograms
        [CH, G, B, 3]."""
        planes = lane_planes
        if cegb_coupled is not None and used is not None:
            own = bundle.scan_lanes[sp_pl._LOWN]
            pen = jnp.where(used, 0.0, cegb_coupled).astype(jnp.float32)
            planes = planes.at[sp_pl._TCEGB].set(
                jnp.where(own >= 0, pen[jnp.maximum(own, 0)], 0.0))
        return sp_pl.best_split_rows_group(
            hist, sum_g, sum_h, cnt, bundle.scan_lanes, planes, params,
            min_constraints=mn, max_constraints=mx, interpret=interpret)

    def _gate(rows, depth_ok):
        """Mask rows that can never apply (depth limit): gain -> NEG,
        feature -> -1 (the old leaf_best_split's blocked semantics)."""
        lane = jnp.arange(RWC, dtype=jnp.int32)[None, :]
        rows = jnp.where((lane == sp_pl._OG) & ~depth_ok, NEGF, rows)
        return jnp.where((lane == sp_pl._OF) & ~depth_ok, -1.0, rows)

    def _fp_sync(rows):
        """SyncUpGlobalBestSplit (parallel_tree_learner.h:186-209): each
        device scanned only its feature shard; all_gather the packed
        rows and keep the max-gain winner per child.  argmax first-hit =
        lowest shard = lowest feature id, the reference's tie-break."""
        allr = coll.all_gather(rows, axis_name)       # [d, CH, RWC]
        win = jnp.argmax(allr[:, :, sp_pl._OG], axis=0)  # [CH]
        return jnp.take_along_axis(allr, win[None, :, None], axis=0)[0]

    k_top = min(top_k, F)
    n_elect = min(2 * k_top, F)

    def _vote_rows(hist_l, sg, sh, cn, mn, mx):
        """PV-tree election (voting_parallel_tree_learner.cpp:166-460)
        over CH children in ONE all_gather + ONE psum: local scans with
        1/num_machines-rescaled min-data thresholds -> local top-k ->
        all_gather -> vote -> psum of the <=2k elected features'
        histograms -> global scan -> packed [CH, RWC] winner rows.

        hist_l [CH, G, B, 3] holds LOCAL-shard rows; sg/sh/cn [CH] are
        the GLOBAL child stats (they ride the packed split rows)."""
        CH = hist_l.shape[0]

        def _unb1(h):
            lg = jnp.sum(h[0, :, 0])
            lh = jnp.sum(h[0, :, 1])
            lc = jnp.sum(h[0, :, 2])
            return unbundle(h, lg, lh, lc), jnp.stack([lg, lh, lc])

        hu, locs = jax.vmap(_unb1)(hist_l)     # [CH, F, B, 3], [CH, 3]
        loc_cnt = jnp.round(locs[:, 2]).astype(jnp.int32)
        # locally-rescaled config (voting...cpp:50-57)
        lparams = params._replace(
            min_data_in_leaf=jnp.maximum(
                params.min_data_in_leaf // num_machines, 1),
            min_sum_hessian_in_leaf=(params.min_sum_hessian_in_leaf
                                     / num_machines))
        mn_a = None if monotone is None else mn
        mx_a = None if monotone is None else mx
        if use_scan_kernel:
            fvecCH = fvec1 if CH == 1 else fvec2
            pf_loc = sp_pl.best_splits_pallas(
                hu, locs[:, 0], locs[:, 1], loc_cnt, fvecCH, lparams,
                min_constraints=mn_a, max_constraints=mx_a,
                interpret=interpret)
            gains = pf_loc.gain                            # [CH, F]
        else:
            gains = jnp.stack([
                best_split_per_feature_mixed(
                    hu[i], locs[i, 0], locs[i, 1], loc_cnt[i],
                    num_bins, default_bins, missing_types,
                    is_categorical, lparams,
                    monotone=monotone, penalty=penalty,
                    feature_mask=scan_feature_mask,
                    min_constraints=(None if mn_a is None else
                                     jnp.broadcast_to(mn_a[i], (F,))),
                    max_constraints=(None if mx_a is None else
                                     jnp.broadcast_to(mx_a[i], (F,))),
                    max_cat_threshold=max_cat_threshold).gain
                for i in range(CH)])

        # local top-k -> Allgather (the LightSplitInfo allgather) ->
        # GlobalVoting; lax.top_k is stable so equal-vote ties break
        # toward the smaller feature id (voting...cpp:166-195)
        _, top_idx = jax.lax.top_k(gains, k_top)           # [CH, k]
        top_ok = jnp.take_along_axis(gains, top_idx, axis=1) > K_MIN_SCORE
        allt = coll.all_gather(top_idx, axis_name)      # [d, CH, k]
        allv = coll.all_gather(top_ok, axis_name)

        def _tally(t, v):
            return jnp.zeros(F, jnp.int32).at[t.reshape(-1)].add(
                v.reshape(-1).astype(jnp.int32))

        votes = jax.vmap(_tally, in_axes=(1, 1))(allt, allv)   # [CH, F]
        _, elected = jax.lax.top_k(votes, n_elect)
        elected = elected.astype(jnp.int32)                # [CH, n_elect]
        # psum of the elected features' histograms only — O(2k*B) bytes
        # instead of O(F*B) (CopyLocalHistogram + ReduceScatter)
        sel = jax.vmap(lambda h, e: jnp.take(h, e, axis=0))(hu, elected)
        glob = coll.psum(sel, axis_name)        # [CH, n_elect, B, 3]

        rows = []
        if use_scan_kernel:
            fv = jax.vmap(lambda e: fvec1[e])(elected).reshape(
                CH * n_elect, fvec1.shape[1])
            pf_g = sp_pl.best_splits_pallas(
                glob, sg, sh, cn, fv, params,
                min_constraints=mn_a, max_constraints=mx_a,
                interpret=interpret)
            for i in range(CH):
                res = select_best_feature(
                    sp_pl.index_per_feature(pf_g, i),
                    feature_index=elected[i])
                rows.append(sp_pl.pack_split_row(res, cat_width=cat_w))
        else:
            for i in range(CH):
                def _tk(a):
                    return None if a is None else jnp.take(a, elected[i],
                                                           axis=0)
                pf = best_split_per_feature_mixed(
                    glob[i], sg[i], sh[i], cn[i], _tk(num_bins),
                    _tk(default_bins), _tk(missing_types),
                    _tk(is_categorical), params,
                    monotone=_tk(monotone), penalty=_tk(penalty),
                    feature_mask=_tk(scan_feature_mask),
                    min_constraints=(None if mn_a is None else
                                     jnp.broadcast_to(mn_a[i], (n_elect,))),
                    max_constraints=(None if mx_a is None else
                                     jnp.broadcast_to(mx_a[i], (n_elect,))),
                    max_cat_threshold=max_cat_threshold)
                res = select_best_feature(pf, feature_index=elected[i])
                rows.append(sp_pl.pack_split_row(res, cat_width=cat_w))
        return jnp.stack(rows)

    def leaf_best_result(hist, sum_g, sum_h, cnt, used=None,
                         minc=None, maxc=None):
        """XLA SplitResult scan — categorical/mixed datasets only."""
        cegb_pen = None
        if cegb_coupled is not None and used is not None:
            cegb_pen = jnp.where(used, 0.0, cegb_coupled)
        mn = mx = None
        if monotone is not None and minc is not None:
            mn = jnp.broadcast_to(jnp.asarray(minc, dtype), (F,))
            mx = jnp.broadcast_to(jnp.asarray(maxc, dtype), (F,))
        hist = unbundle(hist, sum_g, sum_h, cnt)
        pf = best_split_per_feature_mixed(
            hist, sum_g, sum_h, cnt, num_bins, default_bins,
            missing_types, is_categorical, params,
            monotone=monotone, penalty=penalty,
            feature_mask=scan_feature_mask,
            min_constraints=mn, max_constraints=mx,
            cegb_feature_penalty=cegb_pen,
            max_cat_threshold=max_cat_threshold)
        return select_best_feature(pf)

    def single_best_row(hist, sum_g, sum_h, cnt, depth, used=None,
                        minc=None, maxc=None):
        depth_ok = (max_depth <= 0) | (depth < max_depth)
        if vp:
            rows = _vote_rows(
                hist[None], jnp.reshape(sum_g, (1,)),
                jnp.reshape(sum_h, (1,)),
                jnp.reshape(jnp.asarray(cnt, dtype), (1,)),
                None if minc is None else jnp.reshape(
                    jnp.asarray(minc, dtype), (1,)),
                None if maxc is None else jnp.reshape(
                    jnp.asarray(maxc, dtype), (1,)))
        elif use_scan_kernel:
            h1 = (hist if group_scan
                  else unbundle(hist, sum_g, sum_h, cnt))[None]
            mn1 = mx1 = None
            if monotone is not None and minc is not None:
                mn1 = jnp.reshape(jnp.asarray(minc, dtype), (1,))
                mx1 = jnp.reshape(jnp.asarray(maxc, dtype), (1,))
            one = (jnp.reshape(sum_g, (1,)), jnp.reshape(sum_h, (1,)),
                   jnp.reshape(cnt, (1,)))
            if group_scan:
                rows = _group_rows(h1, *one, used, mn1, mx1)
            else:
                rows = sp_pl.best_split_rows_pallas(
                    h1, *one, _patch_cegb(fvec1, used, 1), params,
                    min_constraints=mn1, max_constraints=mx1,
                    interpret=interpret)
        else:
            res = leaf_best_result(hist, sum_g, sum_h, cnt, used=used,
                                   minc=minc, maxc=maxc)
            rows = sp_pl.pack_split_row(res, cat_width=cat_w)[None]
        if fp:
            rows = _fp_sync(rows)
        return _gate(rows, depth_ok)[0]

    def pair_best_rows(hist2, sg2, sh2, cnt2_, depth, used, mn2, mx2):
        """[2, RWC] packed best rows of both children — one kernel
        launch on the numerical path."""
        depth_ok = (max_depth <= 0) | (depth < max_depth)
        if vp:
            rows = _vote_rows(hist2, sg2, sh2, cnt2_,
                              mn2 if monotone is not None else None,
                              mx2 if monotone is not None else None)
        elif group_scan:
            rows = _group_rows(hist2, sg2, sh2, cnt2_, used,
                               mn2 if monotone is not None else None,
                               mx2 if monotone is not None else None)
        elif use_scan_kernel:
            h2 = jax.vmap(lambda hh, gg, hs, cc: unbundle(hh, gg, hs, cc))(
                hist2, sg2, sh2, cnt2_)
            rows = sp_pl.best_split_rows_pallas(
                h2, sg2, sh2, cnt2_, _patch_cegb(fvec2, used, 2), params,
                min_constraints=(mn2 if monotone is not None else None),
                max_constraints=(mx2 if monotone is not None else None),
                interpret=interpret)
        else:
            rows = jnp.stack([
                sp_pl.pack_split_row(
                    leaf_best_result(hist2[i], sg2[i], sh2[i], cnt2_[i],
                                     used=used, minc=mn2[i], maxc=mx2[i]),
                    cat_width=cat_w)
                for i in range(2)])
        if fp:
            rows = _fp_sync(rows)
        return _gate(rows, depth_ok)

    with jax.named_scope("lgbm.root"):
        cegb_used0 = (cegb_used_init if cegb_used_init is not None
                      else jnp.zeros(F, bool))
        ninf = jnp.asarray(-jnp.inf, dtype)
        pinf = jnp.asarray(jnp.inf, dtype)
        root_row = single_best_row(root_hist, root_g, root_h, root_c,
                                   jnp.asarray(0, jnp.int32), used=cegb_used0,
                                   minc=ninf, maxc=pinf)

        # histogram slot cache: K < L spills by LRU (hist_slots; 0 = one slot
        # per leaf, never spills — leaf-indexed, no lookup machinery traced)
        K = max(min(hist_slots, L), 4) if hist_slots and hist_slots > 0 else L
        pooled = K < L
        if forced_splits and pooled:
            raise ValueError(
                "forced_splits require the dense histogram cache "
                "(hist_slots=0): the injection indexes it by leaf")
        hist_cache = jnp.zeros((K,) + root_hist.shape,
                               dtype).at[0].set(root_hist)
        if pooled:
            slot_leaf0 = jnp.full(K, -1, jnp.int32).at[0].set(0)
            slot_tick0 = jnp.zeros(K, jnp.int32).at[0].set(1)
        else:
            slot_leaf0 = jnp.zeros(1, jnp.int32)    # placeholders (untraced)
            slot_tick0 = jnp.zeros(1, jnp.int32)
        split_cache0 = (jnp.zeros((L, RWC), dtype)
                        .at[:, sp_pl._OG].set(NEGF)
                        .at[:, sp_pl._OF].set(-1.0)
                        .at[0].set(root_row))
        # leaf_mat lanes: value, count, parent, depth, min, max, start, local
        leaf_mat0 = (jnp.zeros((L, 8), dtype)
                     .at[:, 2].set(-1.0)
                     .at[:, 4].set(-jnp.inf)
                     .at[:, 5].set(jnp.inf)
                     .at[0].set(jnp.stack([
                         jnp.asarray(0.0, dtype), root_c.astype(dtype),
                         jnp.asarray(-1.0, dtype), jnp.asarray(0.0, dtype),
                         ninf, pinf, root_s0.astype(dtype),
                         root_c_local.astype(dtype)])))

        state = PartState(
            node_mat=jnp.zeros((N, 16), dtype),
            leaf_mat=leaf_mat0,
            node_cat=jnp.zeros((N, cat_w), dtype),
            nl=jnp.asarray(1, jnp.int32),
            arena=arena, cursor=cursor0,
            hist_cache=hist_cache, slot_leaf=slot_leaf0, slot_tick=slot_tick0,
            tick=jnp.asarray(2, jnp.int32),
            split_cache=split_cache0,
            done=jnp.asarray(False), cegb_used=cegb_used0,
            truncated=jnp.asarray(False))

    def cond(state: PartState):
        with jax.named_scope("lgbm.grow.book"):
            return (~state.done) & (state.nl < L)

    def body(state: PartState) -> PartState:
        # The arena flows UNCONDITIONALLY through the (aliased) partition
        # kernel: a lax.cond keeping the old arena value live on the
        # not-taken path would force XLA to copy the multi-GB buffer every
        # split.  When no split applies (done, or the bump allocator is
        # full) the partition degenerates to cnt=0 — a no-op pass — and the
        # small state is masked instead.
        with jax.named_scope("lgbm.grow.book"):
            best_leaf = jnp.argmax(
                state.split_cache[:, sp_pl._OG]).astype(jnp.int32)
            row = state.split_cache[best_leaf]                     # [RWC]
            gain = row[sp_pl._OG]
            no_split = gain <= NEG_GATE

            nl = state.nl
            node = nl - 1
            new_leaf = nl
            feat = jnp.maximum(row[sp_pl._OF].astype(jnp.int32), 0)
            thr = row[sp_pl._OT].astype(jnp.int32)
            dl = row[sp_pl._ODL] > 0.5
            lg, lh = row[sp_pl._OLG], row[sp_pl._OLH]
            lc_f, lo = row[sp_pl._OLC], row[sp_pl._OLO]
            rg, rh = row[sp_pl._ORG], row[sp_pl._ORH]
            rc_f, ro = row[sp_pl._ORC], row[sp_pl._ORO]
            lc_i = lc_f.astype(jnp.int32)
            rc_i = rc_f.astype(jnp.int32)

            lrow = state.leaf_mat[best_leaf]                       # [8]
            old_value = lrow[0]
            parent_of = lrow[2].astype(jnp.int32)
            depth = lrow[3]
            minP, maxP = lrow[4], lrow[5]
            s0 = lrow[6].astype(jnp.int32)
            cntP_local = lrow[7].astype(jnp.int32)

            left_smaller = lc_i <= rc_i
            small_cnt = jnp.minimum(lc_i, rc_i)
            # bump-allocator overflow: stop growing this tree (the arena
            # budget covers balanced trees; pathological shapes truncate —
            # the flag is surfaced so the driver can warn the user to raise
            # tpu_arena_factor).  Serial: the smaller-child count is exact.
            # Data-parallel/voting: the LOCAL smaller-child size is only
            # known after the kernel runs, so the bound is the local parent
            # size; the flag is all-reduced so every shard truncates
            # together.  Feature-parallel replicates data, so counts (and
            # the overflow decision) are identical on every device.
            if axis_name is None or fp:
                need_bound = _align(small_cnt, ALLOC)
            else:
                need_bound = _align(cntP_local, ALLOC)
            overflow = (~no_split) & (
                state.cursor + need_bound + pp.TILE > cap)
            if dp or vp:
                overflow = coll.psum(overflow.astype(jnp.int32),
                                        axis_name) > 0
            no_split = no_split | overflow

            cntP = jnp.where(no_split, 0, cntP_local)
            dstB = state.cursor
            if pristine:
                # the pristine row block is read-only: the first split of the
                # root (s0 inside pristine) writes its larger child to the
                # start of the work region instead of in place
                dstA = jnp.where(s0 < work0, jnp.int32(work0), s0)
            else:
                dstA = s0

        with jax.named_scope("lgbm.grow.cache"):
            if pooled:
                # parent histogram: slot-cache lookup (HistogramPool::Get),
                # with a recompute from the parent's STILL-INTACT segment on
                # miss — this must run before the partition overwrites the
                # segment.  The recompute kernel degenerates to cnt=0 (free)
                # on a hit.
                in_slot = state.slot_leaf == best_leaf
                found = jnp.any(in_slot)
                pslot = jnp.argmax(in_slot).astype(jnp.int32)
                recomputed = seg(state.arena, s0,
                                 jnp.where(found | no_split, 0,
                                           cntP_local))
                # under DP the recompute's allreduce is BATCHED with the
                # smaller-child histogram's below (one collective per split
                # even in pooled mode); only the kernel must run pre-split
            else:
                # dense cache (one slot per leaf): direct index, no extra
                # kernel or collective on the split critical path
                parent_hist = state.hist_cache[best_leaf]

        # the go-left decision is evaluated INSIDE the kernel via a
        # [1, B] mask vector over arena bin values — built here to encode
        # numerical threshold + missing direction (NumericalDecision,
        # tree.h:429-465), categorical bitsets (CategoricalDecision,
        # tree.h:259-273) and EFB bundle-local ranges uniformly.  An
        # XLA-side per-row predicate would cost an O(cap) pass per split.
        # Stream A (in place over the parent) takes the LARGER child:
        # go_left XOR left_smaller == "row goes to the larger side".
        with jax.named_scope("lgbm.grow.partition"):
            bv = jnp.arange(256, dtype=jnp.int32)
            if bundle is None:
                chan = feat
                fbin = bv
            else:
                chan = bundle.feat_col[feat]
                inside = ((bv >= bundle.feat_lo[feat])
                          & (bv < bundle.feat_hi[feat]))
                fbin = jnp.where(inside, bv - bundle.feat_shift[feat],
                                 default_bins[feat])
            mt = missing_types[feat]
            db = default_bins[feat]
            mb = num_bins[feat] - 1
            is_missing = ((mt == MISSING_ZERO) & (fbin == db)) | \
                         ((mt == MISSING_NAN) & (fbin == mb))
            go_left = jnp.where(is_missing, dl, fbin <= thr)
            if is_categorical is not None:
                cm = jnp.pad(row[RW:] > 0.5, (0, 256 - cat_w))
                go_left = jnp.where(is_categorical[feat],
                                    cm[jnp.clip(fbin, 0, 255)], go_left)
            decision = (chan, go_left.astype(jnp.float32),
                        left_smaller.astype(jnp.int32))
            # NOT fused with the histogram: slope-corrected round-4 profiling
            # (tools/kernel_slope.py — the earlier "fusion is free" reading
            # came from fetch-latency-biased microbenches) confirms the fused
            # pass pays the radix contraction over the WHOLE parent stream
            # (+6.9 ms/4M rows) while the separate kernel touches only the
            # compacted smaller child — O(small) beats O(parent) here.
            # Round 5 re-tested a PARENT-SIZE-GATED fusion (in-kernel fh
            # gate + small-parent fused path, partition_pallas fused_gate/
            # raw_hist): ~10% WORSE end-to-end — requesting the hist output
            # on every partition launch adds its buffer setup/writeback to
            # all ~254 splits, which costs more than the separate kernel's
            # fixed cost ever did.  Two launches stay the right shape here.
            arena, counts = part(state.arena, pred_dummy, s0, cntP, dstA, dstB,
                                 decision=decision)
        with jax.named_scope("lgbm.grow.hist"):
            small_hist = seg(arena, dstB, jnp.where(no_split, 0, counts[1]))
            if dp:
                # DP: ONE collective per split — the smaller child's
                # histogram allreduce (the sibling still comes from
                # subtraction, §3.4.2); in pooled mode the parent
                # recompute rides the same allreduce.
                # Voting and feature-parallel skip this: voting keeps local
                # histograms (the election psums only elected features),
                # feature-parallel's histograms are replicated already.
                # As with the root, the psum reduces the raw (code-sum)
                # histograms so quantized DP stays bitwise-serial.
                if pooled:
                    both_h = coll.psum(jnp.stack([small_hist, recomputed]),
                                          axis_name)
                    small_hist, recomputed = both_h[0], both_h[1]
                else:
                    small_hist = coll.psum(small_hist, axis_name)
            small_hist = deq(small_hist)
        with jax.named_scope("lgbm.grow.cache"):
            if pooled:
                parent_hist = jnp.where(found, state.hist_cache[pslot],
                                        deq(recomputed).astype(dtype))
        with jax.named_scope("lgbm.grow.hist"):
            large_hist = parent_hist - small_hist
            left_hist = jnp.where(left_smaller, small_hist, large_hist)
            right_hist = jnp.where(left_smaller, large_hist, small_hist)
        with jax.named_scope("lgbm.grow.cache"):
            if pooled:
                # store both children: the parent's slot (if cached) is
                # reused for the left child, the right child evicts the
                # least-recently-written slot (HistogramPool::Move + LRU)
                slotL = jnp.where(
                    found, pslot,
                    jnp.argmin(state.slot_tick).astype(jnp.int32))
                tickL = state.slot_tick.at[slotL].set(state.tick)
                slotR = jnp.argmin(tickL).astype(jnp.int32)
                write_at = (slotL, slotR)       # distinct: K >= 4
                kept = jnp.stack([state.hist_cache[slotL],
                                  state.hist_cache[slotR]])
                slot_leaf = state.slot_leaf.at[slotL].set(best_leaf)
                slot_leaf = slot_leaf.at[slotR].set(new_leaf)
                slot_tick = tickL.at[slotR].set(state.tick + 1)
                tick = state.tick + 2
            else:
                # under no_split both writes go to best_leaf: new_leaf is L,
                # past the cache, when a forced entry runs the body on a
                # full tree, and dynamic_update_slice clamps an index
                write_at = (best_leaf,
                            jnp.where(no_split, best_leaf, new_leaf))
                kept = jnp.stack([parent_hist, parent_hist])
                slot_leaf, slot_tick, tick = (state.slot_leaf, state.slot_tick,
                                              state.tick)
            # The cache changes by these two slice writes and by nothing
            # else, so the loop carries it in one buffer, updated in place
            # (why it is not masked whole: see `sel` below).  The two VALUES
            # are masked: under no_split the writes put back what the
            # entries held.  Every read of state.hist_cache (parent_hist,
            # kept) feeds child_hists, and the barrier keeps the compiler
            # from fusing one of them into a write, behind the other write:
            # reads first, then the writes.  The forced-split steps unrolled
            # before the loop read the entry off the state their predecessor
            # returned, so the order holds there too.
            child_hists = jax.lax.optimization_barrier(jnp.where(
                no_split, kept, jnp.stack([left_hist, right_hist])))
            hist_cache = state.hist_cache
            for child, at in enumerate(write_at):
                hist_cache = jax.lax.dynamic_update_slice_in_dim(
                    hist_cache, child_hists[child:child + 1], at, axis=0)

        with jax.named_scope("lgbm.grow.book"):
            startL = jnp.where(left_smaller, dstB, dstA).astype(dtype)
            startR = jnp.where(left_smaller, dstA, dstB).astype(dtype)
            localL = jnp.where(left_smaller, counts[1],
                               counts[0]).astype(dtype)
            localR = jnp.where(left_smaller, counts[0],
                               counts[1]).astype(dtype)
            cursor = dstB + _align(counts[1], ALLOC)

            # monotone mid-constraint propagation (serial_tree_learner.cpp:
            # 837-846); categorical splits never carry monotone constraints
            minL, maxL, minR, maxR = minP, maxP, minP, maxP
            if monotone is not None:
                mono_t = monotone[feat].astype(jnp.int32)
                if is_categorical is not None:
                    mono_t = jnp.where(is_categorical[feat], 0, mono_t)
                mid = ((lo + ro) / 2).astype(dtype)
                maxL = jnp.where(mono_t > 0, mid, maxP)
                minR = jnp.where(mono_t > 0, mid, minP)
                minL = jnp.where(mono_t < 0, mid, minP)
                maxR = jnp.where(mono_t < 0, mid, maxP)

            # -- tree bookkeeping (Tree::Split, tree.h:393-423): one node row
            # + two leaf rows + the parent's child-pointer fix-up ------------
            node_f = node.astype(dtype)
            safe_p = jnp.maximum(parent_of, 0)
            prow = state.node_mat[safe_p]
            was_left = prow[4] == -(best_leaf + 1).astype(dtype)
            node_mat = state.node_mat.at[safe_p, 4].set(
                jnp.where((parent_of >= 0) & was_left, node_f, prow[4]))
            node_mat = node_mat.at[safe_p, 5].set(
                jnp.where((parent_of >= 0) & ~was_left, node_f, prow[5]))
            is_cat_f = (is_categorical[feat].astype(dtype)
                        if is_categorical is not None
                        else jnp.asarray(0.0, dtype))
            nrow = jnp.concatenate([jnp.stack([
                feat.astype(dtype), thr.astype(dtype), dl.astype(dtype),
                missing_types[feat].astype(dtype),
                -(best_leaf + 1).astype(dtype), -(new_leaf + 1).astype(dtype),
                gain, old_value, lc_f + rc_f, is_cat_f]),
                jnp.zeros(6, dtype)])
            node_mat = node_mat.at[node].set(nrow)
            node_cat = state.node_cat
            if cat_w:
                node_cat = node_cat.at[node].set(row[RW:])

            lrow_l = jnp.stack([lo, lc_f, node_f, depth + 1, minL, maxL,
                                startL, localL])
            lrow_r = jnp.stack([ro, rc_f, node_f, depth + 1, minR, maxR,
                                startR, localR])
            leaf_mat = state.leaf_mat.at[best_leaf].set(lrow_l) \
                                     .at[new_leaf].set(lrow_r)

            used2 = state.cegb_used.at[feat].set(True)
        # ONE scan over both children (single Pallas launch incl. the
        # cross-feature select on the numerical path)
        with jax.named_scope("lgbm.grow.scan"):
            rows2 = pair_best_rows(
                child_hists,
                jnp.stack([lg, rg]), jnp.stack([lh, rh]),
                jnp.stack([lc_f, rc_f]), depth + 1, used2,
                jnp.stack([minL, minR]), jnp.stack([maxL, maxR]))
        with jax.named_scope("lgbm.grow.book"):
            split_cache = state.split_cache.at[best_leaf].set(rows2[0]) \
                                           .at[new_leaf].set(rows2[1])

        # merge: the arena is already unchanged when no_split (cnt=0 pass)
        # and the histogram cache got its two entries back (above): neither
        # is masked here, because a mask keeps the old value live beside the
        # new one and costs a copy of the whole buffer per split.  Every
        # other field is bytes to kilobytes (node, leaf and split tables,
        # slot_leaf, slot_tick, cegb_used, the scalars) and is masked back
        # to its previous value
        keep = no_split

        def sel(old_v, new_v):
            return jnp.where(keep, old_v, new_v)

        with jax.named_scope("lgbm.grow.cache"):
            slot_leaf = sel(state.slot_leaf, slot_leaf)
            slot_tick = sel(state.slot_tick, slot_tick)
        with jax.named_scope("lgbm.grow.book"):
            return PartState(
                node_mat=sel(state.node_mat, node_mat),
                leaf_mat=sel(state.leaf_mat, leaf_mat),
                node_cat=(sel(state.node_cat, node_cat) if cat_w
                          else state.node_cat),
                nl=sel(nl, nl + 1),
                arena=arena, cursor=sel(state.cursor, cursor),
                hist_cache=hist_cache,
                slot_leaf=slot_leaf, slot_tick=slot_tick,
                tick=sel(state.tick, tick),
                split_cache=sel(state.split_cache, split_cache),
                done=keep, cegb_used=sel(state.cegb_used, used2),
                truncated=state.truncated | overflow)

    # Forced splits first (trace-time unrolled, same scheme as the label
    # engine: inject a +inf-gain forced row into the split cache and
    # run one standard body step; a static->dynamic leaf map abandons
    # invalid subtrees — ForceSplits, serial_tree_learner.cpp:593-751).
    # NOTE: the dense-cache path indexes hist_cache by leaf id; forced
    # splits require hist_slots == 0 (the driver only offers them there).
    if forced_splits:
        from .grow import build_forced_candidate
        lane1 = jnp.arange(RWC, dtype=jnp.int32)
        leafmap = jnp.full((len(forced_splits) + 1,), -1,
                           jnp.int32).at[0].set(0)
        for i, (f_leaf, f_feat, f_thr, f_dl) in enumerate(forced_splits):
            if i >= L - 1:
                break
            dyn_leaf = leafmap[f_leaf]
            safe_leaf = jnp.maximum(dyn_leaf, 0)
            fsp = build_forced_candidate(
                state.hist_cache[safe_leaf],
                state.leaf_mat[safe_leaf, 1].astype(jnp.int32),
                f_feat, f_thr, f_dl, unbundle,
                num_bins, default_bins, missing_types, params,
                cat_width=cat_w)
            frow = sp_pl.pack_split_row(fsp, cat_width=cat_w)
            pre_valid = (dyn_leaf >= 0) & (fsp.gain > K_MIN_SCORE) & \
                        (state.nl < L)
            # An INVALID entry masks every gain in the injected cache to
            # NEG so body() itself no-ops (cnt=0 kernel pass, arena
            # genuinely untouched, small state kept); only the split
            # cache must be restored afterwards (the no-op path would
            # otherwise keep the masked gains and end growth).
            inj = state.split_cache.at[safe_leaf].set(frow)
            inj = jnp.where((lane1[None, :] == sp_pl._OG) & ~pre_valid,
                            NEGF, inj)
            saved_cache = state.split_cache
            prev_leaves = state.nl
            dyn_new = prev_leaves
            stepped = body(state._replace(split_cache=inj))
            # the split may ALSO no-op on arena overflow inside body —
            # gate the leaf map on whether it actually applied, so an
            # abandoned entry's forced subtree is dropped
            applied = stepped.nl == prev_leaves + 1
            state = stepped._replace(
                done=jnp.asarray(False),
                split_cache=jnp.where(applied, stepped.split_cache,
                                      saved_cache))
            leafmap = leafmap.at[i + 1].set(jnp.where(applied, dyn_new, -1))
            # on failure also unmap the target: the only later entry that
            # references static id f_leaf is this entry's LEFT-child
            # entry, which must be abandoned with the right subtree
            leafmap = leafmap.at[f_leaf].set(
                jnp.where(applied, dyn_leaf, -1))

    # the loop itself under a scope: what the compiler does at the loop's
    # level with the carried state takes the while's op_name, not the
    # body's.  A copy of the histogram cache here means the body no longer
    # updates it in place (tests/test_hist_cache_inplace.py)
    with jax.named_scope("lgbm.grow.carry"):
        state = jax.lax.while_loop(cond, body, state)

    # ---- materialize TreeArrays from the packed tables -------------------
    with jax.named_scope("lgbm.finish"):
        nm, lm = state.node_mat, state.leaf_mat
        tree = TreeArrays(
            split_feature=nm[:, 0].astype(jnp.int32),
            threshold_bin=nm[:, 1].astype(jnp.int32),
            default_left=nm[:, 2] > 0.5,
            missing_type=nm[:, 3].astype(jnp.int32),
            left_child=nm[:, 4].astype(jnp.int32),
            right_child=nm[:, 5].astype(jnp.int32),
            split_gain=nm[:, 6].astype(dtype),
            internal_value=nm[:, 7].astype(dtype),
            internal_count=nm[:, 8].astype(jnp.int32),
            leaf_value=lm[:, 0].astype(dtype),
            leaf_count=lm[:, 1].astype(jnp.int32),
            leaf_parent=lm[:, 2].astype(jnp.int32),
            leaf_depth=lm[:, 3].astype(jnp.int32),
            num_leaves=state.nl,
            is_cat=nm[:, 9] > 0.5,
            cat_mask=state.node_cat > 0.5)

    if bagged and emit in ("score", "carry"):
        # the out-of-bag rows, still where the root pass dumped them: each
        # finds its leaf in the tree just grown by ops/valid_score.py's
        # products over its bins, and has its row id beside them
        n_oob = n - bag_rows
        rows = valid_score.padded_rows(n_oob)
        if oob_at + rows > cap:
            raise ValueError("the arena has no room to score %d out-of-bag "
                             "rows" % n_oob)
        with jax.named_scope("lgbm.oob_score"):
            oob_bins = jax.lax.dynamic_slice(
                state.arena, (jnp.int32(0), oob_dst), (G, rows))
            oob_val = valid_score.tree_delta(
                oob_bins, tree, valid_score.tree_signatures(tree),
                num_bins, default_bins, bundle)[:n_oob]
            oob_rid = pp.merge_rowid(*jax.lax.dynamic_slice(
                state.arena, (jnp.int32(Fp + 6), oob_dst), (3, n_oob)))

    with jax.named_scope("lgbm.finish"):
        if emit == "carry":
            # carried-arena boundary: compact the live segments (leaf-index
            # order, full channels incl. score/label planes) into the other
            # root slot — NO row-order recovery, NO sort; the caller updates
            # the score planes from leaf_value/leaf_count and roots the next
            # tree at carry_dst (per-row leaf values derive from
            # cumsum(leaf_count) over the same leaf order).  Under a bag the
            # out-of-bag dump is one more segment, behind the leaves: the
            # second output is then its rows' leaf values, in that order
            starts = lm[:, 6].astype(jnp.int32)
            cnts = lm[:, 7].astype(jnp.int32)
            live = state.nl
            if bagged:
                starts = jnp.append(starts, 0).at[live].set(oob_dst)
                cnts = jnp.append(cnts, 0).at[live].set(n_oob)
                live = live + 1
            arena2, used = pp.compact_carry(
                state.arena, starts, cnts, live,
                jnp.asarray(carry_dst, jnp.int32), interpret=interpret)
            return (tree, oob_val if bagged else used, arena2,
                    state.truncated)

        # ---- recover per-row outputs from the final segments -------------
        # The compact kernel streams ONLY the live segments (O(n) work,
        # independent of cap — the old step-function recovery paid three
        # cumsums plus a scatter over the whole ~6n-column arena) and emits a
        # dense (rowid, value) stream; one n-sized scatter finishes the job.
        capn = -(-n // pp.TILE) * pp.TILE + L * pp.TILE
        vals = (lm[:, 0].astype(jnp.float32) if emit == "score"
                else jnp.arange(L, dtype=jnp.int32).astype(jnp.float32))
        stream, used = pp.compact_segments(
            state.arena, lm[:, 6].astype(jnp.int32),
            lm[:, 7].astype(jnp.int32),
            vals, state.nl, n, G, capn, interpret=interpret)
        # positions >= used are never written by the kernel (garbage, not
        # dummy) — mask them to the dummy rowid before the reorder
        written = jnp.arange(capn, dtype=jnp.int32) < used[0]
        rid = jnp.where(written, stream[0].astype(jnp.int32), n)
        if full_bag or (bagged and emit == "score"):
            # every rowid in [0, n) appears exactly once (segments partition
            # the full root segment; under a bag the out-of-bag rows join
            # them), so a key/value sort puts the values in row order
            # directly — measured ~2x faster than the XLA scatter (TPU
            # scatters serialize; sort is a fast bitonic primitive)
            vals_all = stream[1]
            if bagged:
                rid = jnp.concatenate([rid, oob_rid])
                vals_all = jnp.concatenate([vals_all, oob_val])
            _, sv = jax.lax.sort((rid, vals_all), num_keys=1)
            if emit == "score":
                return tree, sv[:n].astype(dtype), state.arena, state.truncated
            return (tree, jnp.round(sv[:n]).astype(jnp.int32), state.arena,
                    state.truncated)
        if emit == "score":
            # scatter each row's LEAF VALUE directly — the driver's separate
            # 255-table leaf_value[leaf_ids] gather is a pure serial-gather
            # cost on TPU and is skipped entirely
            delta = jnp.zeros(n + 1, dtype).at[rid].set(
                stream[1].astype(dtype), mode="drop")[:n]
            return tree, delta, state.arena, state.truncated
        leaf_ids = jnp.full(n + 1, -1, jnp.int32).at[rid].set(
            stream[1].astype(jnp.int32), mode="drop")[:n]
        return tree, leaf_ids, state.arena, state.truncated


# donate_argnums=(0,): the arena is the only donated input — bins_t and
# row_leaf_init persist across trees, and g/h stay the caller's.  The
# donation audit (obs/device.donation_audit) marks them resident rather
# than un-donated; lgbm_xla_undonated_bytes stays at the committed floor
# of zero for this executable.
grow_tree_partition = partial(jax.jit, static_argnames=(
    "max_leaves", "max_depth", "max_bin", "emit", "full_bag",
    "max_cat_threshold", "axis_name", "learner", "num_machines", "top_k",
    "hist_slots", "forced_splits", "pristine", "carried_bump0",
    "quantized", "bag_rows", "interpret"),
    donate_argnums=(0,))(grow_tree_partition_impl)
