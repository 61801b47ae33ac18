"""Pallas TPU kernels for the partitioned (arena) tree-growth engine.

The TPU re-design of the reference's ordered row partition
(`DataPartition`, src/treelearner/data_partition.hpp:17-222) plus the
per-leaf histogram construction it feeds (src/io/dense_bin.hpp:105-185):
rows live physically grouped by leaf in a feature-major f32 "arena"
`[C, cap]` whose channels are the F binned features followed by
(grad, hess, rowid).  Leaf segments are contiguous column ranges, so

- `partition_segment` splits a parent segment into its two children with
  one sequential pass: per 256-lane sub-block it builds a compaction
  permutation (prefix-scan of the go-left predicate -> the rows' sorted
  positions -> a one-hot the MXU takes as the mask of a weight push) and
  applies it as an MXU matmul — a TPU has no fast scatter, so row
  movement is expressed as dense matrix products.  Stream A may be
  written back in place over the parent (writes provably lag reads); the
  other child goes to the bump-allocator cursor.  This mirrors the
  reference's smaller/larger split choreography where only the smaller
  leaf is rebuilt (serial_tree_learner.cpp:360-437).
- `segment_histogram` builds the [F, B, 3] grad/hess/count histogram of
  one leaf by streaming its contiguous segment tiles through the same
  radix-factorized MXU contraction as ops/histogram_pallas.py — per-leaf
  cost is O(leaf_rows), the reference's asymptotics, with sequential HBM
  reads instead of gathers.

All arena payloads ride bf16 with EXACT semantics: bin channels hold
integers <= 256 (bf16-exact), and each f32 payload (grad, hess) rides as
THREE bf16 channels (hi/mid/lo residue split — 8 mantissa bits each
reconstruct the f32 exactly); rowid rides as three 8-bit byte planes
(2^24-row cap checked by the caller).  The permutation and histogram
matmuls then run as single bf16 MXU passes instead of f32
Precision.HIGHEST multi-pass emulation, and arena HBM traffic halves.
Histogram accumulation stays f32 (MXU accumulators), matching the
reference GPU learner's single-precision default.

Pipeline invariant in both kernels: tile j's read is complete when its
loop iteration starts.  `segment_histogram` (and `partition_segment` over
an arena cut into channel blocks) issues read j+1 in iteration j, computes
j, then waits read j+1.  `partition_segment`'s one-block loop runs a stage
ahead on a ring of three read slots: iteration j waits read j+1 and issues
read j+2 at its top, moves tile j and makes tile j+1's predicate part
beside it (`_partition_kernel`).  Either way tile j's output writes (the
FLUSH_W chunks its appends completed) are issued inside iteration j,
beside a read still in flight, and the in-place stream is safe all the
same: those writes end at most (j+1)*tile columns past the segment start,
columns read before iteration j began, and every read in flight covers a
tile after them.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .histogram_pallas import _radix_plan

SUB = 256          # compaction sub-block width (lanes per permutation matmul)
TILE = 2048        # rows per streamed tile
N_AUX = 9          # g_hi,g_mid,g_lo, h_hi,h_mid,h_lo, r_hi,r_mid,r_lo
ARENA_DT = jnp.bfloat16
# sublane tiling granularity for the arena dtype (bf16 memrefs tile at 16)
_SUBL = 16


def feature_channels(num_features: int) -> int:
    """Feature channels padded to the histogram kernel's block width; the
    padding rows hold zeros and their (garbage) histograms are sliced off."""
    return num_features + (-num_features % 8)


def arena_channels(num_features: int) -> int:
    """Total arena channels: padded features, then the split payload
    planes, padded for sublane tiling."""
    c = feature_channels(num_features) + N_AUX
    return c + (-c % _SUBL)


def arena_geometry(num_data: int, num_features: int,
                   factor: int = 3) -> tuple:
    """(C, cap) of the arena for a dataset — the SINGLE sizing formula
    shared by GBDT._setup_tree_engine and the parallel growers
    (parallel/learners.py).  `factor` multiples of the row
    footprint cover root + OOB dump + bump-allocated child segments
    (pristine layout: pristine bins + root copy + dump + bump -> pass
    factor >= 4); the 16-tile tail is kernel read-overrun headroom."""
    base = -(-max(num_data, 1) // TILE) * TILE
    cap = max(factor, 3) * base + 16 * TILE
    return arena_channels(max(num_features, 1)), cap


def pristine_work0(num_data: int) -> int:
    """First work-region column in the pristine arena layout: the
    pristine row block [0, align(n)) plus one guard tile (kernel reads
    overrun segments by < TILE)."""
    return -(-max(num_data, 1) // TILE) * TILE + TILE


@jax.jit
def feature_major(bins):
    """[n, G] binned rows -> [G, n] feature-major bins in the arena's
    type, as one program: transposed and converted eagerly, a second
    [n, G] copy in the arena's type waits for the transposition (1.6 GB
    at 400 000 x 2 000) while the arena is being allocated."""
    return bins.T.astype(ARENA_DT)


@functools.partial(jax.jit, donate_argnums=(0,))
def init_pristine(arena, bins_t):
    """Write the PER-DATASET arena channels (feature bins + rowid byte
    planes + padding) into the pristine region [0, n) once.  Per-tree
    assembly then touches only the six g/h payload planes — the other
    42-of-48 channels of the old full re-assembly were identical every
    tree (the bins never change and pristine rows stay in row order).
    g/h plane rows are left untouched (overwritten per tree)."""
    C, cap = arena.shape
    G, n = bins_t.shape
    Fp = feature_channels(G)
    adt = ARENA_DT
    chans = [bins_t.astype(adt)]
    if Fp > G:
        chans.append(jnp.zeros((Fp - G, n), adt))
    arena = jax.lax.dynamic_update_slice(
        arena, jnp.concatenate(chans, axis=0), (0, 0))
    rid = jnp.stack(split_rowid(jnp.arange(n, dtype=jnp.int32)))
    arena = jax.lax.dynamic_update_slice(arena, rid, (Fp + 6, 0))
    if C > Fp + N_AUX:
        arena = jax.lax.dynamic_update_slice(
            arena, jnp.zeros((C - Fp - N_AUX, n), adt), (Fp + N_AUX, 0))
    return arena


def split_f32(x):
    """f32 [n] -> three bf16 planes whose f32 sum reconstructs x exactly
    (8 mantissa bits each; 24 total covers the f32 significand).

    The residue split MUST round through reduce_precision, not
    astype(bf16).astype(f32): under --xla_allow_excess_precision (set in
    this environment) XLA elides the cast round-trip inside jit, which
    zeroes the mid/lo planes and silently degrades payloads to single
    bf16 (~0.5% histogram error).  reduce_precision is semantically a
    rounding op XLA must honor."""
    x = x.astype(jnp.float32)
    hi = jax.lax.reduce_precision(x, 8, 7)
    r1 = x - hi
    mid = jax.lax.reduce_precision(r1, 8, 7)
    lo = r1 - mid
    return (hi.astype(jnp.bfloat16), mid.astype(jnp.bfloat16),
            lo.astype(jnp.bfloat16))


def pack_code_planes(g_code, h_code):
    """int8-valued g/h codes (f32 arrays from ops.quantize) -> [2, n]
    bf16 payload planes for arena rows Fp+0/Fp+1.  bf16 represents every
    integer in [-256, 256] exactly, so the cast is lossless — quantized
    mode replaces the SIX f32-residue planes with these TWO."""
    return jnp.stack([g_code, h_code]).astype(ARENA_DT)


def _align8(rows: int) -> int:
    """Round an arena row count up to the 8-sublane DMA granule."""
    return -(-rows // 8) * 8


_VMEM_DEFAULT = 16 << 20     # Mosaic's scoped VMEM limit when none is given
# partition_segment's VMEM per arena channel, one block: the read ring's
# three slots (12 KiB), the two carries (2 KiB), the staging (16 KiB) and
# what Mosaic spills of the sort products and the appends (8 KiB: compiled
# for a v5e, C = 432 fits the default limit, C = 448 does not and C = 464
# asks for 16.75 MB; the tile's predicate part holds nothing per channel
# and, since PR 33, no one-hot)
_VMEM_PER_CHANNEL = 38 << 10


def _side_effect_params():
    """The kernels that write HBM through manual DMAs must not be
    dead-code-eliminated or reordered as pure functions."""
    return pltpu.CompilerParams(has_side_effects=True)


def split_rowid(r):
    """int32 [n] (< 2^24) -> three byte planes as bf16 (values <= 255)."""
    r = r.astype(jnp.int32)
    return ((r // 65536).astype(ARENA_DT),
            ((r // 256) % 256).astype(ARENA_DT),
            (r % 256).astype(ARENA_DT))


def merge_rowid(hi, mid, lo):
    return (hi.astype(jnp.int32) * 65536 + mid.astype(jnp.int32) * 256
            + lo.astype(jnp.int32))


def _prefix_scan_lanes(x):
    """Inclusive prefix sum along the last (lane) axis via log-step rolls."""
    n = x.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    sh = 1
    while sh < n:
        x = x + jnp.where(lane >= sh, pltpu.roll(x, sh, axis=x.ndim - 1), 0)
        sh *= 2
    return x


FLUSH_W = SUB          # flush chunk width; all HBM write offsets are
#                        multiples of FLUSH_W (tiled-memref alignment).
#                        SUB = FLUSH_W = 128 RE-TESTED on the chip with
#                        PR 33's tile body: 11.44 vs 9.77 ms per 10.5M-row
#                        pass at C = 48, 3.90 vs 3.68 at C = 160 — the
#                        halved operand does not pay for twice the appends'
#                        and flushes' bookkeeping (PERF.md §6, PR 33)
CARRY_W = FLUSH_W + SUB    # compact_carry's carry width (append window)
# partition_segment's append rotates inside one FLUSH_W window and completes
# at most one chunk
assert FLUSH_W == SUB


def _sort_pos(pref2, pred2, K: int):
    """Where each row of a tile's K subblocks lands in its sorted subblock:
    pos [K, SUB] int32 — a stream-A row at its rank among the subblock's A
    rows (columns [0, ca_k), compacted, in order), a stream-B row at ca_k +
    its rank among the B rows, and -1 for a row in neither stream (the
    invalid tail of a segment's last tile), which no column of the one-hot
    matches: validity is folded into the position.

    pref2/pred2: [2K, SUB] int32 — A-rows then B-rows (inclusive prefix
    sums and 0/1 predicates)."""
    ca = pref2[:K, SUB - 1].reshape(K, 1)              # [K, 1]
    return jnp.where(pred2[:K] == 1, pref2[:K] - 1,
                     jnp.where(pred2[K:] == 1, pref2[K:] - 1 + ca, -1))


def _sort_P(pos, k: int):
    """Subblock k's permutation operand, TRANSPOSED: Pt [SUB, SUB] bf16
    with Pt[t, s] = (pos[k, s] == t), so that ONE product
    `chunk[C, s] . Pt[t, s]` (both operands contracted over their last
    axis, as the histogram kernels' products are) SORTS the subblock into
    an A-prefix and a B-suffix; the appends separate the two streams again
    with lane masks and the usual carry roll.

    Built in this orientation, `pos` stays along the lanes as the prefix
    scan leaves it (one sublane broadcast of row k) and the iota runs down
    the sublanes: ONE compare an element, and Mosaic feeds the i1 result to
    the MXU as the mask of a transposed weight push (`vmatpush.xpose.msk`)
    without ever selecting or packing a bf16 value.  The orientation
    P[s, t] it replaces moved `pos` and a validity array from lanes to
    sublanes (3 500 XLU operations a tile, a quarter of the tile body at
    C = 48), compared twice, ANDed, selected in f32 and packed."""
    t = jax.lax.broadcasted_iota(jnp.int32, (SUB, SUB), 0)
    # f32 then bf16: an i1 mask from 32-bit compares can't relayout onto
    # 16-bit vector selects in Mosaic
    return jnp.where(pos[k:k + 1, :] == t, jnp.float32(1.0),
                     jnp.float32(0.0)).astype(jnp.bfloat16)


_SORT_DIMS = (((1,), (1,)), ((), ()))     # chunk[C, s] . Pt[t, s] -> [C, t]


def _split_column(group, row, K: int):
    """The split feature's bin values of one tile, [K, SUB] int32 in the
    subblock layout the prefix scan wants (subblock k on sublane k):
    `group` is the [16, tile] bf16 row group that holds the feature's
    channel (a 16-aligned slice: the bf16 sublane tile), `row` the
    channel's place in it.  No product and no [1, tile] strip: the 8-row
    half with the channel is chosen by a scalar, and subblock k's lanes of
    it are rotated down the sublanes until the channel sits on sublane
    k % 8, where one select takes it.  Exact: the values are moved, never
    summed."""
    g = group.astype(jnp.float32)
    half = jnp.where(row >= 8, g[8:], g[:8])               # [8, tile]
    sub = jax.lax.broadcasted_iota(jnp.int32, (8, SUB), 0)
    slabs = []
    for k0 in range(0, K, 8):
        slab = jnp.zeros((8, SUB), jnp.float32)
        for k in range(k0, min(k0 + 8, K)):
            moved = pltpu.roll(half[:, k * SUB:(k + 1) * SUB],
                               (k - row) & 7, axis=0)
            slab = jnp.where(sub == k - k0, moved, slab)
        slabs.append(slab)
    return jnp.concatenate(slabs, axis=0)[:K].astype(jnp.int32)


def _go_left(col, mask_ref):
    """The go-left MASK VECTOR looked up at every bin value of `col`
    ([K, SUB] int32, values < 256): 1 -> the row goes left.  mask_ref
    ([2, 128] int32) holds mask[v] at [v // 128, v % 128]; a lane gather
    per 128-lane piece and mask half (`tpu.dynamic_gather`), chosen by the
    bin's top bit: no one-hot of the bins and no product.  The mask is
    built in XLA per split and encodes ALL decision semantics — numerical
    threshold + missing direction (NumericalDecision, tree.h:429-465),
    categorical bitsets (CategoricalDecision, tree.h:259-273) and EFB
    bundle-local bin ranges — so the kernel needs no per-kind logic."""
    halves = [jnp.broadcast_to(mask_ref[h:h + 1, :], (col.shape[0], 128))
              for h in range(2)]
    out = []
    for c in range(0, col.shape[1], 128):
        v = col[:, c:c + 128]
        lane = v & 127
        lo, hi = (jnp.take_along_axis(m, lane, axis=1) for m in halves)
        out.append(jnp.where(v >= 128, hi, lo))
    return jnp.concatenate(out, axis=1)


def _decide(group, row, mask_ref, xr, K: int):
    """In-kernel split decision of one tile: [K, SUB] int32, 1 -> stream
    A.  The split feature's bin values (`_split_column`) looked up in the
    go-left mask (`_go_left`), XOR'd with xr."""
    return _go_left(_split_column(group, row, K), mask_ref) ^ xr


def _decision_operands(feat, mask_vec):
    """What the kernel's decision reads, from a split's (feature channel,
    go-left mask over at most 256 bin values): the mask as mask_ref wants
    it ([2, 128] int32, mask[v] at [v // 128, v % 128]) and the two
    scalars after sc_ref's first seven — the first channel of the 16-row
    group around the channel and the channel's place in it."""
    feat = jnp.asarray(feat, jnp.int32)
    mv = jnp.asarray(mask_vec, jnp.float32).reshape(-1) > 0.5
    goleft = jnp.pad(mv, (0, 256 - mv.shape[0])).astype(jnp.int32)
    return goleft.reshape(2, 128), [feat // _SUBL * _SUBL, feat % _SUBL]


def _append_plan(fill, counts):
    """Scalar plan of one stream's K appends of a tile, straight-line from
    the tile-start `fill` (< FLUSH_W) and the sub-blocks' row counts: per
    append the carry fill it meets, whether it completes a FLUSH_W chunk
    (at most one: an append adds <= SUB = FLUSH_W rows) and how many
    chunks the tile completed before it.  Returns (fills, flushed,
    chunk_no, end) with `end` = fill + sum(counts)."""
    fills, flushed, chunk_no = [], [], []
    t = fill
    for c in counts:
        done = jax.lax.div(t, jnp.int32(FLUSH_W))
        fills.append(t - done * FLUSH_W)
        chunk_no.append(done)
        t = t + c
        flushed.append(jax.lax.div(t, jnp.int32(FLUSH_W)) > done)
    return fills, flushed, chunk_no, t


def _staging_shape(rows: int, tile: int) -> tuple:
    """partition_segment's staging slots: (stream, parity, append k) of
    the `rows` channels the kernel holds at a time."""
    return (2, 2, tile // SUB, rows, FLUSH_W)


def _partition_vmem_limit(C: int, hist_shapes):
    """None (Mosaic's default) while the one-block kernel fits it, else
    what it needs: only the fused histogram's accumulator can ask for
    more, since a C that does not fit by itself is cut into channel
    blocks (`_channel_block`).  The accumulator counts twice (the output
    and the products added into it).  The limit is raised only when it
    must be: XLA runs the fusions around the kernel slower under a larger
    one (higgs, C = 48: 0.8 ms per iteration in `broadcast_select_fusion`,
    PERF.md PR 26)."""
    need = C * _VMEM_PER_CHANNEL + sum(
        2 * 4 * math.prod(h.shape) for h in hist_shapes)
    return need if need > _VMEM_DEFAULT else None


def _channel_block(C: int, per_channel: int, resident: int = 0,
                   fixed: int = 0) -> int:
    """Channels an arena kernel holds in VMEM at a time: the largest
    divisor of C that is a multiple of the bf16 sublane tile and whose
    `per_channel` bytes a channel fit Mosaic's default scoped VMEM beside
    what the kernel keeps for every channel (`resident` bytes each) and
    for none (`fixed`).  Derived from static shapes only; the default
    limit is never raised for a block (see `_partition_vmem_limit` for
    what a larger one costs)."""
    room = _VMEM_DEFAULT - C * resident - fixed
    for n in range(1, C // _SUBL + 1):
        if C % (n * _SUBL) == 0 and (C // n) * per_channel <= room:
            return C // n
    raise ValueError(
        "no channel block serves %d arena channels: %d B of VMEM per "
        "channel of a block, %d B resident per channel and %d B fixed "
        "leave no multiple of %d channels under the %d B a kernel may use"
        % (C, per_channel, resident, fixed, _SUBL, _VMEM_DEFAULT))


# Blocked, partition_segment holds per channel of a block the read slots
# (8 KiB), the staging (16 KiB) and the sort products Mosaic spills (8 KiB);
# of EVERY channel the two streams' carries; of none the tile's permutation
# operands (`P_ref`, 1 MiB: every block replays them), the decision's 16-row
# group (`dec_buf`, 128 KiB) and the pred tiles.  Compiled for a v5e at
# C = 2016: blocks of 336 fit the default limit, 20.86 MB of scratch at
# 672 do not.
_PART_PER_BLOCK_CHANNEL = 32 << 10
_PART_RESIDENT = 2 * FLUSH_W * 4
_PART_FIXED = (TILE // SUB) * SUB * SUB * 2 + (256 << 10)


def partition_channel_block(C: int) -> int:
    """partition_segment's channel block at C arena channels: C itself
    up to the width whose one-block kernel fits the default VMEM."""
    if C * _VMEM_PER_CHANNEL <= _VMEM_DEFAULT:
        return C
    return _channel_block(C, _PART_PER_BLOCK_CHANNEL, _PART_RESIDENT,
                          _PART_FIXED)


def _partition_kernel(sc_ref, mask_ref, arena_any, pred_any,
                      out_any, cnt_ref, *rest,
                      C: int, tile: int, hist_plan=None, cb: int = 0):
    """sc_ref (SMEM [9] i32): start, cnt, dstA, dstB, mode, xr, hs, then
    the first channel of the 16-row group around the split feature's
    channel and the channel's place in that group — start, dstA and dstB
    must be multiples of `tile` resp. FLUSH_W (the bump allocator aligns).
    mask_ref (VMEM [2, 128] i32): the go-left mask over the 256 bin values.
    arena_any/out_any: [C, cap] bf16 in HBM, aliased (same buffer).
    Routing: mode=0 reads pred_any ([1, cap] f32, 1.0 -> stream A);
    mode=1 computes the split decision in-kernel (`_decide`), XOR'd with
    xr (1 when the left child is the smaller/bump-allocated stream-B
    side).  The caller bakes ALL decision semantics (numerical threshold,
    missing direction, categorical bitsets, EFB ranges) into the mask.
    cnt_ref (SMEM out [2] i32): rows written to A and B.

    What a tile computes before its first row moves does not scale with C
    and is kept small (PR 33; at C = 48 it was over half of the tile's
    instructions): the decision is a 16-row slice of the tile already in
    VMEM, one row of it rotated into the [K, SUB] subblock layout
    (`_split_column`) and looked up in the mask by a lane gather
    (`_go_left`) — no [1, C] or [1, 256] product with M = 1 and no
    256-row one-hot of the bins; validity, the prefix scan and the rows'
    positions stay in that layout (two dense vregs an array); and the
    permutation operand is built transposed, one compare an element
    (`_sort_P`), and handed to the MXU as a push mask.

    Each SUB-lane sub-block is compacted with an MXU permutation matmul
    and appended onto its stream's carry, a [C, FLUSH_W] f32 window that
    holds the rows not yet written (fill < FLUSH_W between appends).  The
    tile's 2K appends are STRAIGHT-LINE code: every shift, fill, flush
    predicate and flush destination is a function of the tile-start
    (fill, written) and the sub-blocks' counts, all known from the prefix
    scan before the first matmul (`_append_plan`), so no scalar
    recurrence and no conditional region sits between two appends.  An
    append rolls its chunk to the carry's fill (a rotation inside the
    FLUSH_W window: what wraps past the window's end is the start of the
    next chunk), adds the unwrapped part, stores the window — complete or
    not — as bf16 into a staging slot with a static index (stream,
    parity, k), and keeps as the new carry the window (no chunk
    completed) or the wrapped part (chunk completed).  After the last
    append the completed slots are DMA'd to the stream's next
    FLUSH_W-aligned arena columns, under their predicates; those DMAs are
    waited, under the same predicates (one bit mask per stream and tile in
    the loop state), before the parity is staged again two tiles later,
    and after the loop: exactly one wait per started DMA.

    THE ONE-BLOCK LOOP RUNS ONE STAGE AHEAD (PR 37).  Inside a tile
    everything waits for the prefix scan (positions -> operands -> products
    -> appends; counts -> append plan -> every shift and flush predicate),
    and the scan is a chain of eight dependent lane rotations, 114 cycles
    each from issue to `vpop.permute` by the compiler's own latencies:
    944 cycles in about 56 bundles, a third of the 1.70 us the tile took
    while that chain stood alone before the first product.  Between two
    tiles nothing depends but four scalars (fill and written per stream).
    So iteration j MOVES tile j (products, appends, flushes) from the
    positions and the 2K subblock counts that ride the loop's carry (the
    counts as scalars: the vector-to-scalar pops are paid a tile ahead
    too) and MAKES tile j+1's predicate part (`tile_permutation`) beside
    it; a prologue makes tile 0's, and the part made past the last tile
    is dropped (its `valid` is false everywhere; the fused histogram, a
    side effect, is skipped there).  Order inside an iteration, and why:
      1. wait the flushes of tile j-2 (same staging parity), wait read
         j+1, start read j+2.  EVERY WAIT IS AT THE TOP: Mosaic starts a
         new scheduling block at each `dma.done` wait and moves nothing
         across one, and a chain is hidden only by work of its own block
         (the wait of read j+1 put behind the flush starts, where the
         read would have had half an iteration more, left tile j+1's
         part alone in a last block: slower than no pipeline at all,
         PERF.md section 6).  The read ring has THREE slots because tile
         j+1 is read from (its decision group, its pred tile, the fused
         histogram's rows) while tile j+2 arrives; one read is in
         flight, for a whole iteration, and takes 0.7 / 0.8 / 1.45 us
         from start to done at C = 48 / 64 / 160, under iterations of
         1.17 / 1.32 / 2.67.
      2. tile j's products and appends, each subblock's two flush DMAs
         started right behind its two appends: a DMA start cuts no block
         but is issued behind every vector store before it in program
         order, so started behind the tile's last append (as the blocked
         loop below does) the 32 starts are 270 bundles of scalar work
         with nothing beside them.
      3. tile j+1's predicate part, in the same block as 2.
    Staging and its two-parity flush waits are as before the pipeline.

    Stream A may write over the parent segment in place: tile j's flushes
    reach at most dstA + wA + FLUSH_W <= start + (j+1)*tile, columns whose
    reads (tiles 0..j) completed before iteration j's products began; the
    read in flight beside them is of tile j+2 (tile j+1's, waited at the
    top, lies beyond them as well).  Nothing past the segment's last tile
    is read: reads are started under j+2 < n_tiles, and what a ring slot
    holds past the last tile decides nothing (`valid`).

    CHANNEL BLOCKS (cb < C: an arena too wide for one [C, tile] slab in
    VMEM) keep the two-slot loop and its schedule: there the predicate part
    is made once a tile for C/cb block steps (0.7 of Epsilon's 30 us a
    tile), `P_ref` would need a second 1 MiB parity to be made ahead, and
    `dec_buf` already is a tile ahead.  The two loops differ by a shape
    (cb < C), not by a knob, and share `tile_permutation`, `sort_append`,
    `start_flush`, `flushed_state` and `_append_plan`.  Where a row goes depends on the predicate only, so the tile's
    decision, prefix scan, K [SUB, SUB] permutation operands (stored as
    bf16 in `P_ref`) and append plan are made ONCE per tile, from the
    16-row group that holds the split feature's channel (the same code as
    in one block, the group read by a small DMA of its own; `pred` in
    mode 0), and every block of cb channels replays them: its own read
    DMA, sort products, appends onto its own carries (kept for all C/cb
    blocks, 2 KiB a channel) and flushes to its own rows.  The pipeline's unit becomes
    the step (tile j, block b), tile-major: step t waits the flushes of
    step t-2 (same staging parity), starts read t+1, computes, starts its
    flushes, waits read t+1.  Fill and written are the same for every
    block of a tile (one permutation), so they stay two scalars a stream.
    The in-place invariant holds per block: step (j, b) writes block b's
    rows only, at columns <= start + (j+1)*tile, which that block's reads
    of tiles 0..j (steps (0..j, b), all complete before step (j, b)
    starts) have consumed; the read in flight beside them is either
    another block's rows of tile j or block 0's rows of tile j+1, and the
    decision group of tile j+1 (read during step (j, 0)) lies in tile
    j+1's columns too: all disjoint from every write made so far.  Blocks
    tile C exactly (`_channel_block`): a shifted last block would re-read
    rows an earlier block has already overwritten in place.
    """
    cb = cb or C
    n_cb = C // cb
    blocked = n_cb > 1
    K = tile // SUB
    hist_ref = P_ref = dec_buf = carries = carryA = carryB = None
    if blocked:
        assert hist_plan is None and n_cb * cb == C
        (in_buf, pred_buf, dec_buf, carries, stage, P_ref,
         read_sems, pred_sems, dec_sems, write_sems) = rest
    elif hist_plan is None:
        (in_buf, pred_buf, carryA, carryB, stage,
         read_sems, pred_sems, write_sems) = rest
    else:
        # fused smaller-child histogram: one extra VMEM output, stream-B
        # rows accumulated with the radix contraction while they are
        # already in VMEM for compaction — saves the separate
        # segment_histogram kernel launch AND its re-read of the child
        (hist_ref, in_buf, pred_buf, carryA, carryB, stage,
         read_sems, pred_sems, write_sems) = rest
        hist_ref[:] = jnp.zeros_like(hist_ref)
    s, cnt = sc_ref[0], sc_ref[1]
    dsts = (sc_ref[2], sc_ref[3])
    mode = sc_ref[4]
    xr = sc_ref[5]    # XOR'd into the decision: 1 when the left child is
    #                   the smaller (stream-B) side
    hs = sc_ref[6]    # fused-histogram stream: 1 -> B, 0 -> A
    # the 16-row group (the bf16 sublane tile) that holds the split
    # feature's channel, and the channel's place in it
    grp = pl.multiple_of(sc_ref[7], _SUBL)
    dec_row = sc_ref[8]
    n_tiles = jax.lax.div(cnt + jnp.int32(tile - 1), jnp.int32(tile))
    lane_s = jax.lax.broadcasted_iota(jnp.int32, (1, SUB), 1)

    def flush_dma(stream, slot, k, dst_col, row0=None):
        cols = pl.ds(pl.multiple_of(dst_col, 128), FLUSH_W)
        ref = (out_any.at[:, cols] if row0 is None else
               out_any.at[pl.ds(pl.multiple_of(row0, _SUBL), cb), cols])
        return pltpu.make_async_copy(stage.at[stream, slot, k], ref,
                                     write_sems.at[stream, slot, k])

    # the wait of a flush needs its shape only: any block's rows do
    wait_row0 = 0 if blocked else None

    def wait_flushes(slot, pending):
        """Wait the flushes a step started from parity `slot`; `pending`
        holds per stream the bit mask of the appends that flushed."""
        for stream in range(2):
            for k in range(K):
                @pl.when(((pending[stream] >> k) & 1) == 1)
                def _(stream=stream, k=k):
                    flush_dma(stream, slot, k, 0, wait_row0).wait()

    def append(carry, chunk, lo, fill, flushed, stage_at):
        """chunk ([rows, SUB] f32) holds this stream's rows at lanes
        [lo, lo+ck), zeros elsewhere; rotate them onto window lanes
        [fill, fill+ck) mod FLUSH_W.  Lanes >= fill continue the carry's
        chunk, lanes < fill are what wrapped: the head of the next chunk.
        The carry is f32 precisely so the positioning can be a dynamic
        pltpu.roll (32-bit-only op) instead of MXU MACs; values are exact
        bf16 payloads so the f32->bf16 cast at the staging store is
        lossless.  Returns the new carry."""
        rolled = pltpu.roll(chunk, (fill - lo) & (FLUSH_W - 1), axis=1)
        tail = lane_s >= fill
        window = carry + jnp.where(tail, rolled, jnp.float32(0.0))
        stage[stage_at] = window.astype(ARENA_DT)
        keep = lane_s < jnp.where(flushed, 0, FLUSH_W)
        return jnp.where(keep, window,
                         jnp.where(tail, jnp.float32(0.0), rolled))

    sub_idx = (jax.lax.broadcasted_iota(jnp.int32, (K, SUB), 0) * SUB
               + jax.lax.broadcasted_iota(jnp.int32, (K, SUB), 1))

    def tile_permutation(j, group_at, pred_at, rows_at=None):
        """The predicate's part of tile j, made once whatever the number
        of channel blocks, all of it in the [K, SUB] subblock layout (a
        tile's 2 048 rows in two dense vregs, not sixteen one-row ones):
        the rows' streams, ONE batched prefix scan for all subblocks of
        both streams and the rows' sorted positions.  `group_at()` gives
        the 16-row group the decision reads, `rows_at()` the rows the
        fused histogram reads.  Returns (pos [K, SUB], the 2K subblock
        counts)."""
        valid = sub_idx < (cnt - j * tile)
        by_pred = (pred_at().reshape(K, SUB) > 0.5).astype(jnp.int32)
        on = (mode * _decide(group_at(), dec_row, mask_ref, xr, K)
              + (1 - mode) * by_pred)
        predA = jnp.where(valid, on, 0)
        predB = jnp.where(valid, 1 - on, 0)

        if hist_plan is not None:
            hmask = (hs * predB + (1 - hs) * predA).astype(
                jnp.float32).reshape(1, tile)
            nb_h, k_h, m_h, lo_h, hi_h, pay_h = hist_plan

            # the pipelined loop makes this a tile ahead, past the last
            # tile too: a ring slot no read has filled may hold anything
            # (the mask is a factor of the payload, and 0 * NaN is no 0)
            @pl.when(j < n_tiles)
            def _():
                _radix_accumulate(hist_ref, rows_at(), hmask, n_blocks=nb_h,
                                  k=k_h, m=m_h, lo_n=lo_h, hi_n=hi_h,
                                  payload=pay_h)

        pred2 = jnp.concatenate([predA, predB], axis=0)    # [2K, SUB]
        pref2 = _prefix_scan_lanes(pred2)
        return _sort_pos(pref2, pred2, K), pref2[:, SUB - 1]

    def sort_append(block, P_at, plan_of, carries_in, slot, after=None):
        """K dependency-free SORT matmuls (chunk[rows, s] . Pt[t, s]:
        A-prefix + B-suffix in a single product — the split point ca_k
        is known from the prefix scan before any product, so the two
        streams share one SUB-wide output) and the 2K straight-line
        appends of one block of channels.  `plan_of()` gives the tile's
        (cA, cB) subblock counts, `plan_of(cA, cB)` its two append plans;
        `after(k)`, if given, is traced behind subblock k's two appends.
        Returns (the two new carries, the plans)."""
        comps = [jax.lax.dot_general(block[:, k * SUB:(k + 1) * SUB],
                                     P_at(k), _SORT_DIMS,
                                     preferred_element_type=jnp.float32)
                 for k in range(K)]                        # [rows, S] f32
        # split each sorted block into its A-prefix / B-suffix; the
        # B chunk is a subtraction, not a second select
        cA, cB = plan_of()
        chunksA = [jnp.where(lane_s < cA[k], comps[k], jnp.float32(0.0))
                   for k in range(K)]
        chunksB = [comps[k] - chunksA[k] for k in range(K)]
        plans = plan_of(cA, cB)
        zero = jnp.int32(0)
        out = carries_in()
        for k in range(K):
            for stream, chunk, lo in ((0, chunksA[k], zero),
                                      (1, chunksB[k], cA[k])):
                p_fill, p_flushed, _, _ = plans[stream]
                out[stream] = append(
                    out[stream], chunk, lo, p_fill[k], p_flushed[k],
                    (stream, slot, k))
            if after is not None:
                after(k)
        return out, plans

    def start_flush(plans, written, slot, stream, k, row0=None):
        """Start the DMA of the chunk that `stream`'s append k completed,
        if it completed one."""
        _, p_flushed, p_chunk_no, _ = plans[stream]

        @pl.when(p_flushed[k])
        def _():
            flush_dma(stream, slot, k,
                      dsts[stream] + written[stream]
                      + p_chunk_no[k] * FLUSH_W, row0).start()

    def flushed_state(plans, written, each=None):
        """Per stream (fill, written) after the tile and the bit mask of
        the appends that flushed: scalars of the plans alone.  `each
        (stream, k)`, if given, is traced at append k's turn."""
        new_fills, new_written, new_pending = [], [], []
        for stream in range(2):
            _, p_flushed, _, end = plans[stream]
            bits = jnp.int32(0)
            for k in range(K):
                if each is not None:
                    each(stream, k)
                bits = bits | (p_flushed[k].astype(jnp.int32) << k)
            done = jax.lax.div(end, jnp.int32(FLUSH_W))
            new_fills.append(end - done * FLUSH_W)
            new_written.append(written[stream] + done * FLUSH_W)
            new_pending.append(bits)
        return tuple(new_fills), tuple(new_written), tuple(new_pending)

    def start_flushes(plans, written, slot, row0=None):
        """Start the DMAs of the chunks a step's appends completed, all
        of them behind the step's last append.  Returns `flushed_state`."""
        return flushed_state(
            plans, written, lambda stream, k: start_flush(
                plans, written, slot, stream, k, row0))

    z2 = (jnp.int32(0), jnp.int32(0))
    if not blocked:
        def read_dmas(j, slot):
            src = pl.multiple_of(s + j * tile, 128)
            # the pred stream is only consumed in mode 0; in decision mode
            # the caller passes a [1, tile] dummy (a full [1, cap] zeros
            # buffer gets constant-sunk into the grow while-loop by XLA and
            # re-materialized EVERY split — measured 75 ms/iter) and the
            # DMA pins its read to offset 0
            psrc = jnp.where(mode == 0, src, 0)
            return (pltpu.make_async_copy(
                        arena_any.at[:, pl.ds(src, tile)],
                        in_buf.at[slot], read_sems.at[slot]),
                    pltpu.make_async_copy(
                        pred_any.at[:, pl.ds(pl.multiple_of(psrc, 128), tile)],
                        pred_buf.at[slot], pred_sems.at[slot]))

        def permutation_of(j, slot):
            """Tile j's predicate part from ring slot `slot`: its rows'
            positions and the 2K subblock counts AS SCALARS, so that the
            iteration that moves the tile starts at its products."""
            pos, cnt2 = tile_permutation(
                j, lambda: in_buf[slot, pl.ds(grp, _SUBL), :],
                lambda: pred_buf[slot], lambda: in_buf[slot])
            return pos, tuple(cnt2[k] for k in range(2 * K))

        def next_slot(slot):
            return jnp.where(slot == 2, 0, slot + 1)

        # prologue: tiles 0 and 1 on their way, tile 0's predicate part
        for t in range(2):
            @pl.when(n_tiles > t)
            def _(t=t):
                for d in read_dmas(t, t):
                    d.start()
        carryA[:] = jnp.zeros((C, FLUSH_W), jnp.float32)
        carryB[:] = jnp.zeros((C, FLUSH_W), jnp.float32)

        @pl.when(n_tiles > 0)
        def _():
            for d in read_dmas(0, 0):
                d.wait()

        def loop(j, state):
            fills, written, pending, pending2, slot, pos, counts = state
            parity = jax.lax.rem(j, jnp.int32(2))
            slot1 = next_slot(slot)
            # tile j-2 staged this parity: its flushes must have landed
            wait_flushes(parity, pending2)

            # tile j+1, whose read was started a whole iteration ago
            @pl.when(j + 1 < n_tiles)
            def _():
                for d in read_dmas(j + 1, slot1):
                    d.wait()

            @pl.when(j + 2 < n_tiles)
            def _():
                for d in read_dmas(j + 2, next_slot(slot1)):
                    d.start()

            cA, cB = list(counts[:K]), list(counts[K:])
            plans = (_append_plan(fills[0], cA), _append_plan(fills[1], cB))

            def plan_of(a=None, b=None):
                return (cA, cB) if a is None else plans

            def flush_after(k):
                # a DMA start is issued behind every vector store before
                # it in program order: started behind the tile's last
                # append, as the blocked loop starts them, the flushes
                # are a tail of scalar work with nothing beside it
                for stream in range(2):
                    start_flush(plans, written, parity, stream, k)

            (carryA[:], carryB[:]), _ = sort_append(
                in_buf[slot], lambda k: _sort_P(pos, k), plan_of,
                lambda: [carryA[:], carryB[:]], parity, flush_after)
            return (*flushed_state(plans, written), pending, slot1,
                    *permutation_of(j + 1, slot1))

        fills, written, pending, pending2, _, _, _ = jax.lax.fori_loop(
            0, n_tiles, loop,
            (z2, z2, z2, z2, jnp.int32(0)) + permutation_of(0, 0))
        n_steps = n_tiles
    else:
        def rows_dma(j, row0, rows, buf, sems, slot):
            """Read `rows` channels from `row0` of tile j."""
            src = pl.multiple_of(s + j * tile, 128)
            return pltpu.make_async_copy(
                arena_any.at[pl.ds(pl.multiple_of(row0, _SUBL), rows),
                             pl.ds(src, tile)],
                buf.at[slot], sems.at[slot])

        def tile_dmas(j, slot):
            """What tile j's predicate is made from: the decision's row
            group and, for mode 0, the pred tile (pinned to offset 0 in
            decision mode, as in the one-block kernel)."""
            src = pl.multiple_of(s + j * tile, 128)
            psrc = jnp.where(mode == 0, src, 0)
            return (rows_dma(j, grp, _SUBL, dec_buf, dec_sems, slot),
                    pltpu.make_async_copy(
                        pred_any.at[:, pl.ds(pl.multiple_of(psrc, 128), tile)],
                        pred_buf.at[slot], pred_sems.at[slot]))

        @pl.when(n_tiles > 0)
        def _():
            first = tile_dmas(0, 0) + (
                rows_dma(0, 0, cb, in_buf, read_sems, 0),)
            for d in first:
                d.start()
            for d in first:
                d.wait()

        def clear(b, _):
            carries[b] = jnp.zeros((2, cb, FLUSH_W), jnp.float32)
            return 0
        jax.lax.fori_loop(0, n_cb, clear, 0)

        def tile_loop(j, state):
            fills, written, pending, pending2 = state
            tslot = jax.lax.rem(j, jnp.int32(2))
            pos, cnt2 = tile_permutation(
                j, lambda: dec_buf[tslot], lambda: pred_buf[tslot])
            for k in range(K):
                P_ref[k] = _sort_P(pos, k)
            cA = [cnt2[k] for k in range(K)]
            cB = [cnt2[K + k] for k in range(K)]
            plans = (_append_plan(fills[0], cA), _append_plan(fills[1], cB))
            more_tiles = j + 1 < n_tiles

            def plan_of(a=None, b=None):
                return (cA, cB) if a is None else plans

            def block_step(b, pend):
                pending, pending2 = pend
                t = j * n_cb + b
                slot = jax.lax.rem(t, jnp.int32(2))
                nslot = 1 - slot
                # step t-2 staged this parity: its flushes must have landed
                wait_flushes(slot, pending2)
                wraps = b + 1 == n_cb
                nj = jnp.where(wraps, j + 1, j)
                nrow = jnp.where(wraps, 0, (b + 1) * cb)
                has_next = nj < n_tiles
                next_tile = more_tiles & (b == 0)

                @pl.when(has_next)
                def _():
                    rows_dma(nj, nrow, cb, in_buf, read_sems, nslot).start()

                @pl.when(next_tile)
                def _():
                    for d in tile_dmas(j + 1, 1 - tslot):
                        d.start()

                (carries[b, 0], carries[b, 1]), _ = sort_append(
                    in_buf[slot], lambda k: P_ref[k], plan_of,
                    lambda: [carries[b, 0], carries[b, 1]], slot)
                _, _, new_pending = start_flushes(plans, written, slot,
                                                  b * cb)

                @pl.when(has_next)
                def _():
                    rows_dma(nj, nrow, cb, in_buf, read_sems, nslot).wait()

                @pl.when(next_tile)
                def _():
                    for d in tile_dmas(j + 1, 1 - tslot):
                        d.wait()
                return new_pending, pending

            pending, pending2 = jax.lax.fori_loop(
                0, n_cb, block_step, (pending, pending2))
            ends = [plans[stream][3] for stream in range(2)]
            done = [jax.lax.div(e, jnp.int32(FLUSH_W)) for e in ends]
            return (tuple(e - d * FLUSH_W for e, d in zip(ends, done)),
                    tuple(w + d * FLUSH_W for w, d in zip(written, done)),
                    pending, pending2)

        fills, written, pending, pending2 = jax.lax.fori_loop(
            0, n_tiles, tile_loop, (z2, z2, z2, z2))
        n_steps = n_tiles * n_cb

    # Drain the last two steps' flushes (parities of steps n-2 and n-1),
    # then write each stream's partial chunk from a staging slot that is
    # free again.
    last = jax.lax.rem(n_steps + jnp.int32(1), jnp.int32(2))
    wait_flushes(1 - last, pending2)
    wait_flushes(last, pending)

    def write_partials(carry_of, row0=None):
        for stream in range(2):
            @pl.when(fills[stream] > 0)
            def _(stream=stream):
                # + 0.0: rows that wrapped in the tile's last flushing
                # append have not passed through an add yet (-0.0 -> +0.0,
                # as every other row)
                stage[stream, 0, 0] = (carry_of(stream) + jnp.float32(0.0)
                                       ).astype(ARENA_DT)
                final = flush_dma(stream, 0, 0,
                                  dsts[stream] + written[stream], row0)
                final.start()
                final.wait()

    if not blocked:
        write_partials(lambda stream: (carryA, carryB)[stream][:])
    else:
        def partials(b, _):
            write_partials(lambda stream: carries[b, stream], b * cb)
            return 0
        jax.lax.fori_loop(0, n_cb, partials, 0)

    cnt_ref[0] = written[0] + fills[0]
    cnt_ref[1] = written[1] + fills[1]


@functools.partial(jax.jit, static_argnames=("tile", "interpret",
                                             "num_features", "max_bin",
                                             "quantized"))
def partition_segment(arena, pred, start, cnt, dstA, dstB,
                      decision=None, hist_stream=None,
                      num_features: int = 0, max_bin: int = 0,
                      tile: int = TILE, interpret: bool = False,
                      quantized: bool = False):
    """Partition arena columns [start, start+cnt) into stream A at dstA
    (dstA == start allowed: in-place with lagging writes) and stream B at
    dstB (must not overlap [start, start+cnt+tile)).

    Routing: by `pred` ([1, cap] f32, 1.0 -> A) when decision is None,
    else by the in-kernel split decision — decision = (feat_channel,
    goleft_mask [MB] 0/1, xor_flag): a row whose arena value on the
    feature channel is v follows goleft_mask[v] (XOR xor_flag); the mask
    encodes numerical/missing/categorical/EFB semantics uniformly.  pred
    is then ignored (pass a [1, tile] dummy).

    When hist_stream is given (0 -> stream A, 1 -> stream B; requires
    num_features/max_bin), the kernel also accumulates that stream's
    [F, max_bin, 3] histogram in the same pass and returns it third —
    the partition + histogram fusion (used for the bagging root pass;
    a parent-size-gated fusion on the split path was measured ~10%
    WORSE end-to-end in round 5 — the hist output's per-launch setup
    outweighs the separate O(child) kernel's fixed cost).  An arena cut
    into channel blocks (`partition_channel_block`) has its payload
    planes in the last block only, so there the histogram is a
    `segment_histogram` of the stream just written.

    Returns (new_arena, counts[2] int32[, hist]).  Writes stay within
    align(count, FLUSH_W) columns of each stream's dst; reads overrun the
    segment by < tile columns, so callers keep cap >= last segment + tile.
    """
    C, cap = arena.shape
    cb = partition_channel_block(C)
    blocked = cb < C
    if decision is None:
        mode, (feat, mask_vec, xr) = 0, (0, jnp.zeros(256), 0)
    else:
        mode, (feat, mask_vec, xr) = 1, decision
    goleft, group_and_row = _decision_operands(feat, mask_vec)
    with_hist = hist_stream is not None
    sc = jnp.stack([jnp.asarray(v, jnp.int32) for v in [
        start, cnt, dstA, dstB, mode, xr, hist_stream if with_hist else 0]
        + group_and_row])
    hist_plan = None
    out_specs = (pl.BlockSpec(memory_space=pl.ANY),
                 pl.BlockSpec(memory_space=pltpu.SMEM))
    out_shape = [jax.ShapeDtypeStruct((C, cap), ARENA_DT),
                 jax.ShapeDtypeStruct((2,), jnp.int32)]
    payload = 3 if quantized else 7
    K = tile // SUB
    if blocked:
        scratch = [
            pltpu.VMEM((2, cb, tile), ARENA_DT),
            pltpu.VMEM((2, 1, tile), jnp.float32),
            pltpu.VMEM((2, _SUBL, tile), ARENA_DT),
            pltpu.VMEM((C // cb, 2, cb, FLUSH_W), jnp.float32),
            pltpu.VMEM(_staging_shape(cb, tile), ARENA_DT),
            pltpu.VMEM((K, SUB, SUB), jnp.bfloat16),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2, 2, K)),
        ]
    else:
        scratch = [
            pltpu.VMEM((3, C, tile), ARENA_DT),
            pltpu.VMEM((3, 1, tile), jnp.float32),
            pltpu.VMEM((C, FLUSH_W), jnp.float32),
            pltpu.VMEM((C, FLUSH_W), jnp.float32),
            pltpu.VMEM(_staging_shape(C, tile), ARENA_DT),
            pltpu.SemaphoreType.DMA((3,)),
            pltpu.SemaphoreType.DMA((3,)),
            pltpu.SemaphoreType.DMA((2, 2, K)),
        ]
    if with_hist and not blocked:
        lo_n, hi_n, m = _hist_radix(max_bin)
        f_blk = max(m, 8)
        k = f_blk // m
        n_blocks = feature_channels(num_features) // f_blk
        hist_plan = (n_blocks, k, m, lo_n, hi_n, payload)
        Mc, N = payload * hi_n * m, lo_n * m
        out_specs = out_specs + (pl.BlockSpec(memory_space=pltpu.VMEM),)
        out_shape.append(
            jax.ShapeDtypeStruct((n_blocks * k * Mc, N), jnp.float32))
    kernel = functools.partial(_partition_kernel, C=C, tile=tile,
                               hist_plan=hist_plan, cb=cb)
    outs = pl.pallas_call(
        kernel,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=out_specs,
        out_shape=tuple(out_shape),
        scratch_shapes=scratch,
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True,
            vmem_limit_bytes=(None if blocked else
                              _partition_vmem_limit(C, out_shape[2:]))),
        interpret=interpret,
    )(sc, goleft, arena, pred)
    if not with_hist:
        return outs[0], outs[1]
    if blocked:
        # (XLA glue of the block plan takes the caller's scope: this runs
        # under lgbm.root, the bagging root pass)
        hs = jnp.asarray(hist_stream, jnp.int32)
        hist = segment_histogram(
            outs[0], jnp.where(hs == 1, jnp.asarray(dstB, jnp.int32),
                               jnp.asarray(dstA, jnp.int32)),
            outs[1][hs], num_features=num_features, max_bin=max_bin,
            tile=tile, interpret=interpret, quantized=quantized)
        return outs[0], outs[1], hist
    hist = split_radix_epilogue(outs[2], n_blocks * k, m, hi_n=hi_n,
                                lo_n=lo_n,
                                payload=payload)[:num_features, :max_bin, :]
    return outs[0], outs[1], hist


def _compact_carry_kernel(sc_ref, starts_ref, cnts_ref, arena_any, out_any,
                          used_ref, in_buf, carry, flush_buf,
                          read_sems, write_sems, *, C: int, tile: int,
                          blocked: bool = False):
    """Compact the live leaf segments' FULL channel rows into one dense
    contiguous block at dst0 — the carried-arena tree boundary: instead
    of extracting (rowid, value) pairs and sorting scores back to row
    order (O(n log^2 n) bitonic, ~64 ms at 10.5M rows), the next tree
    simply roots at the compacted block, and score/label planes ride
    along as channels.  Valid rows are a PREFIX of every segment tile,
    so appends need no permutation matmul: static SUB-wide slices roll
    into the carry window exactly like the partition kernel's append
    (same FLUSH_W-aligned write discipline; dst0 must be FLUSH_W-aligned
    and the destination block must not overlap any live segment).

    sc_ref (SMEM [2] i32): num_live_leaves, dst0.
    starts/cnts (SMEM [L] i32): per-leaf segment start and count; the
    output packs segments in LEAF-INDEX order (callers derive per-row
    leaf values from cumsum(cnts)).
    used_ref (SMEM [1] i32): rows written (= sum of cnts).

    `blocked`: C is one block of a wider arena's channels and the call a
    grid over the blocks; each step compacts its own rows of every
    segment, start to drain, so nothing but the row offset differs (the
    destination is disjoint from every live segment: no step reads what
    another wrote).
    """
    nseg, dst0 = sc_ref[0], sc_ref[1]
    K = tile // SUB
    lane_w = jax.lax.broadcasted_iota(jnp.int32, (C, CARRY_W), 1)
    lane_s = jax.lax.broadcasted_iota(jnp.int32, (1, SUB), 1)
    if blocked:
        rows = pl.ds(pl.multiple_of(pl.program_id(0) * C, _SUBL), C)
    else:
        rows = slice(None)

    def read_dma(start, j, slot):
        src = pl.multiple_of(start + j * tile, 128)
        return pltpu.make_async_copy(
            arena_any.at[rows, pl.ds(src, tile)],
            in_buf.at[slot], read_sems.at[slot])

    def flush_dma(slot, dst_col):
        return pltpu.make_async_copy(
            flush_buf.at[slot],
            out_any.at[rows, pl.ds(pl.multiple_of(dst_col, 128), FLUSH_W)],
            write_sems.at[slot])

    carry[:] = jnp.zeros((C, CARRY_W), jnp.float32)

    def append(chunk, ck, fill, written, fslot):
        """The partition kernel's append/flush, single-stream, lo=0."""
        padded = jnp.concatenate(
            [chunk, jnp.zeros((C, CARRY_W - SUB), jnp.float32)], axis=1)
        carry[:] = carry[:] + pltpu.roll(padded, fill, axis=1)
        fill = fill + ck
        for _ in range(-(-SUB // FLUSH_W)):
            @pl.when(fill >= FLUSH_W)
            def _(fill=fill, written=written, fslot=fslot):
                @pl.when(written >= 2 * FLUSH_W)
                def _():
                    flush_dma(fslot, 0).wait()
                flush_buf[fslot] = carry[:, 0:FLUSH_W].astype(ARENA_DT)
                flush_dma(fslot, dst0 + written).start()
                shifted = jnp.concatenate(
                    [carry[:, FLUSH_W:CARRY_W],
                     jnp.zeros((C, FLUSH_W), jnp.float32)], axis=1)
                carry[:] = jnp.where(lane_w < fill - FLUSH_W, shifted,
                                     jnp.float32(0.0))
            flushed = fill >= FLUSH_W
            fill = jnp.where(flushed, fill - FLUSH_W, fill)
            written = jnp.where(flushed, written + FLUSH_W, written)
            fslot = jnp.where(flushed, 1 - fslot, fslot)
        return fill, written, fslot

    def seg_body(s, st):
        fill, written, fslot, rd = st
        start, cnt = starts_ref[s], cnts_ref[s]
        n_t = jax.lax.div(cnt + jnp.int32(tile - 1), jnp.int32(tile))

        @pl.when(n_t > 0)
        def _():
            read_dma(start, 0, jax.lax.rem(rd, jnp.int32(2))).start()

        def tile_body(j, st2):
            fill, written, fslot, rd = st2
            rslot = jax.lax.rem(rd, jnp.int32(2))
            read_dma(start, j, rslot).wait()

            @pl.when(j + 1 < n_t)
            def _():
                read_dma(start, j + 1, 1 - rslot).start()
            vt = cnt - j * tile          # valid prefix of this tile
            block = in_buf[rslot]
            for k2 in range(K):
                ck = jnp.clip(vt - k2 * SUB, 0, SUB)
                chunk = jnp.where(
                    lane_s < ck,
                    block[:, k2 * SUB:(k2 + 1) * SUB].astype(jnp.float32),
                    jnp.float32(0.0))
                fill, written, fslot = append(chunk, ck, fill, written,
                                              fslot)
            return fill, written, fslot, rd + 1

        return jax.lax.fori_loop(0, n_t, tile_body,
                                 (fill, written, fslot, rd))

    z = jnp.int32(0)
    fill, written, fslot, _rd = jax.lax.fori_loop(
        0, nseg, seg_body, (z, z, z, z))

    @pl.when(fill > 0)
    def _():
        @pl.when(written >= 2 * FLUSH_W)
        def _():
            flush_dma(fslot, 0).wait()
        flush_buf[fslot] = carry[:, 0:FLUSH_W].astype(ARENA_DT)
        flush_dma(fslot, dst0 + written).start()
        flush_dma(fslot, 0).wait()

    @pl.when((fill == 0) & (written >= 2 * FLUSH_W))
    def _():
        flush_dma(fslot, 0).wait()

    @pl.when(written >= FLUSH_W)
    def _():
        flush_dma(1 - fslot, 0).wait()

    used_ref[0] = written + fill


# compact_carry per channel: the read slots (8 KiB), the carry window
# (2 KiB), the flush slots (1 KiB) and the append's padded, rolled and
# shifted copies of the window Mosaic spills
_COMPACT_PER_CHANNEL = 24 << 10


def compact_channel_block(C: int) -> int:
    """compact_carry's channel block at C arena channels."""
    return _channel_block(C, _COMPACT_PER_CHANNEL)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def compact_carry(arena, starts, cnts, num_live, dst0,
                  tile: int = TILE, interpret: bool = False):
    """Compact live segments (leaf-index order) into a dense full-channel
    block at dst0; returns (arena', rows_written).  dst0 must be
    FLUSH_W-aligned and its block disjoint from every live segment."""
    C, cap = arena.shape
    cb = compact_channel_block(C)
    # a grid over the channel blocks only where there is more than one:
    # narrow data compiles the call it always has
    grid = {"grid": (C // cb,)} if cb < C else {}
    sc = jnp.stack([jnp.asarray(num_live),
                    jnp.asarray(dst0)]).astype(jnp.int32)
    kernel = functools.partial(_compact_carry_kernel, C=cb, tile=tile,
                               blocked=bool(grid))
    out, used = pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=(pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pltpu.SMEM)),
        out_shape=(jax.ShapeDtypeStruct((C, cap), ARENA_DT),
                   jax.ShapeDtypeStruct((1,), jnp.int32)),
        scratch_shapes=[
            pltpu.VMEM((2, cb, tile), ARENA_DT),
            pltpu.VMEM((cb, CARRY_W), jnp.float32),
            pltpu.VMEM((2, cb, FLUSH_W), ARENA_DT),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        input_output_aliases={3: 0},
        compiler_params=_side_effect_params(),
        interpret=interpret,
        **grid,
    )(sc, jnp.asarray(starts, jnp.int32), jnp.asarray(cnts, jnp.int32),
      arena)
    return out, used[0]


def _compact_rows_kernel(sc_ref, starts_ref, cnts_ref, vals_ref, arena_any,
                         out_any, used_ref, in_buf, out_buf,
                         read_sems, write_sems, *, fp: int, tile: int,
                         row0: int = 0):
    """Compact the live leaf segments' (rowid, value) pairs into one
    dense stream — the cap-independent replacement for the old
    step-function label recovery (three O(cap) cumsums + an O(cap)
    scatter; cap is ~6x rows, so recovery dominated the fixed per-tree
    cost).  Only segment tiles are streamed: O(rows) work total.

    sc_ref (SMEM [2] i32): num_live_leaves, dummy_rowid.
    starts/cnts (SMEM [L] i32), vals (SMEM [L] f32): per-leaf segment
    start, count and emitted value (leaf value or leaf index).
    arena_any: [C, cap] bf16; rowid byte planes at rows fp+6..fp+8.
    out_any: [2, capn] f32 — row 0 rowid (exact: n < 2^24), row 1 value.
    used_ref (SMEM [1] i32): columns written (= Σ ceil(cnt/tile)*tile).

    Each segment writes ceil(cnt/tile) full tiles at a tile-aligned
    output cursor; slots beyond the segment count carry dummy_rowid and
    are dropped by the consumer's scatter.  Double-buffered on both the
    read and write sides.

    in_buf holds all C channels, or, for an arena too wide for that
    (`_rowid_rows`), the sublane-tile-aligned rows from `row0` that span
    the rowid planes.
    """
    nseg, dummy = sc_ref[0], sc_ref[1]
    dummy_f = dummy.astype(jnp.float32)
    rows = in_buf.shape[1]
    fp = fp - row0

    def read_dma(start, j, slot):
        # full channel block: a 3-row sublane slice at fp+6 may violate
        # the (16, 128) bf16 memref tiling; the extra bandwidth is ~2 ms
        # at 4M rows, well under what this kernel replaces
        src = pl.multiple_of(start + j * tile, 128)
        ref = (arena_any.at[pl.ds(row0, rows), pl.ds(src, tile)] if row0
               else arena_any.at[:, pl.ds(src, tile)])
        return pltpu.make_async_copy(ref, in_buf.at[slot],
                                     read_sems.at[slot])

    def write_dma(dst_col, slot):
        dst = pl.multiple_of(dst_col, 128)
        return pltpu.make_async_copy(
            out_buf.at[slot], out_any.at[:, pl.ds(dst, tile)],
            write_sems.at[slot])

    def seg_body(s, carry):
        ocur, w_total = carry
        start, cnt = starts_ref[s], cnts_ref[s]
        val = vals_ref[s]
        n_t = jax.lax.div(cnt + jnp.int32(tile - 1), jnp.int32(tile))

        @pl.when(n_t > 0)
        def _():
            read_dma(start, 0, 0).start()

        def tile_body(j, wt):
            rslot = jax.lax.rem(j, jnp.int32(2))
            read_dma(start, j, rslot).wait()

            @pl.when(j + 1 < n_t)
            def _():
                read_dma(start, j + 1, 1 - rslot).start()

            rid = (in_buf[rslot][fp + 6:fp + 7].astype(jnp.float32) * 65536.0
                   + in_buf[rslot][fp + 7:fp + 8].astype(jnp.float32) * 256.0
                   + in_buf[rslot][fp + 8:fp + 9].astype(jnp.float32))
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
            live = (lane < (cnt - j * tile)).astype(jnp.float32)
            # write slots cycle on the GLOBAL write counter (segments
            # restart j at 0, so per-tile parity would double-book a
            # semaphore); wait the write that used this slot 2 writes ago
            wslot = jax.lax.rem(wt, jnp.int32(2))
            @pl.when(wt >= 2)
            def _():
                write_dma(0, wslot).wait()
            out_buf[wslot, 0:1] = rid * live + dummy_f * (1.0 - live)
            out_buf[wslot, 1:2] = val * live
            write_dma(ocur + j * tile, wslot).start()
            return wt + 1

        w_total = jax.lax.fori_loop(0, n_t, tile_body, w_total)
        return ocur + n_t * tile, w_total

    ocur, w_total = jax.lax.fori_loop(0, nseg, seg_body,
                                      (jnp.int32(0), jnp.int32(0)))
    # drain outstanding writes: the last two used parities (w-1)%2, w%2
    @pl.when(w_total >= 1)
    def _():
        write_dma(0, jax.lax.rem(w_total + jnp.int32(1), jnp.int32(2))).wait()

    @pl.when(w_total >= 2)
    def _():
        write_dma(0, jax.lax.rem(w_total, jnp.int32(2))).wait()
    used_ref[0] = ocur


def _rowid_rows(C: int, fp: int) -> tuple:
    """(first row, rows) compact_segments reads of each tile: all C
    channels while the two read slots fit the default VMEM beside the
    output slots (what narrow data has always compiled), else only the
    16-row groups that span the rowid planes fp+6 .. fp+8."""
    if 2 * C * TILE * 2 + (1 << 20) <= _VMEM_DEFAULT:
        return 0, C
    row0 = (fp + 6) // _SUBL * _SUBL
    return row0, -(-(fp + N_AUX) // _SUBL) * _SUBL - row0


@functools.partial(jax.jit, static_argnames=("num_features", "capn", "tile",
                                             "interpret"))
def compact_segments(arena, starts, cnts, vals, num_live, dummy_rowid,
                     num_features: int, capn: int,
                     tile: int = TILE, interpret: bool = False):
    """[2, capn] f32 (rowid, value) compact stream over the live leaf
    segments + used-columns count.  Slots with rowid == dummy_rowid are
    padding.  capn must be >= align(total_rows, tile) + num_leaves*tile."""
    C, cap = arena.shape
    fp = feature_channels(num_features)
    L = starts.shape[0]
    sc = jnp.stack([jnp.asarray(num_live), jnp.asarray(dummy_rowid)]
                   ).astype(jnp.int32)
    row0, rows = _rowid_rows(C, fp)
    kernel = functools.partial(_compact_rows_kernel, fp=fp, tile=tile,
                               row0=row0)
    out, used = pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=(pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pltpu.SMEM)),
        out_shape=(jax.ShapeDtypeStruct((2, capn), jnp.float32),
                   jax.ShapeDtypeStruct((1,), jnp.int32)),
        scratch_shapes=[
            pltpu.VMEM((2, rows, tile), ARENA_DT),
            pltpu.VMEM((2, 2, tile), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=_side_effect_params(),
        interpret=interpret,
    )(sc, jnp.asarray(starts, jnp.int32), jnp.asarray(cnts, jnp.int32),
      jnp.asarray(vals, jnp.float32), arena)
    return out, used


def _comp_chunks(hi_n: int, m: int, payload: int = 7):
    """Split the payload components (f32: g_hi,g_mid,g_lo, h_hi,h_mid,h_lo,
    cnt; quantized: g_code, h_code, cnt) into dot chunks with
    chunk*hi_n*m <= 128 rows each."""
    per = max(1, 128 // (hi_n * m))
    chunks = []
    i = 0
    while i < payload:
        chunks.append(min(per, payload - i))
        i += chunks[-1]
    return chunks


def _hist_radix(max_bin: int) -> tuple:
    """(lo_n, hi_n, m) of the histogram kernels: `_radix_plan`'s digits,
    hi_n rounded up to whole 8-sublane slabs of the left operand (8 // m
    hi levels a slab; a level no bin reaches stays zero and the callers'
    `[:max_bin]` slices it off)."""
    lo_n, hi_n, m = _radix_plan(max_bin)
    per_slab = 8 // m
    return lo_n, -(-hi_n // per_slab) * per_slab, m


def _radix_planes(block, mask, planes, *, n_blocks: int, payload: int):
    """The masked payload planes of a tile, each on all 8 sublanes: payload
    x [8, tile] f32 (the count's plane is the mask).  Masking by 0/1 keeps
    every entry a bf16-exact plane value (residue planes or int8 codes);
    one sublane broadcast per plane and tile."""
    mask = mask.astype(jnp.float32)
    if planes is None:
        Fp = n_blocks * 8
        planes = [block[Fp + i:Fp + i + 1, :] for i in range(payload - 1)]
    slab = (8, mask.shape[1])
    gh = [jnp.broadcast_to(p.astype(jnp.float32) * mask, slab)
          for p in planes]
    return gh + [jnp.broadcast_to(mask, slab)]


def _radix_digits(rows, lo_n: int):
    """(hi, lo) f32 digits of a radix block's 8 feature rows [8, tile]
    bf16: bin = hi * lo_n + lo."""
    bins = rows.astype(jnp.float32)
    hi = jnp.floor(bins * (1.0 / lo_n))
    return hi, bins - hi * lo_n


def _radix_rhs(lo, *, k: int, lo_n: int):
    """The product's right operand, [k, m*lo_n, tile] bf16 one-hot rows
    (f', lo) of each of the block's k groups; lo_n = 16 or 32 on the
    sublanes, so the reshape moves nothing."""
    loh = jnp.where(
        lo.astype(jnp.int32)[:, None, :]
        == jax.lax.broadcasted_iota(jnp.int32, (1, lo_n, 1), 1),
        jnp.float32(1.0), jnp.float32(0.0)).astype(jnp.bfloat16)
    return loh.reshape(k, lo.shape[0] // k * lo_n, lo.shape[1])


def _radix_hits(hi, *, k: int, m: int, hi_n: int):
    """The compare masks of the left operands of a block's k groups, one
    dense [8, tile] mask per 8-row slab: slab j of a group holds its m
    features' hi levels j*k .. j*k + k - 1, row r the level j*k + r // m
    of feature r % m."""
    if k == 1:
        rels = [hi]
    else:
        # m = 4: a group's four hi rows on both halves of the sublanes
        # (rows r and r + 4 change places: a rotation by half of 8, the
        # same either way), the upper half one level ahead
        assert k == 2
        upper = jax.lax.broadcasted_iota(jnp.int32, hi.shape, 0) >= m
        swapped = pltpu.roll(hi, m, axis=0)
        rels = [jnp.where(upper, swapped - 1.0, hi),
                jnp.where(upper, hi - 1.0, swapped)]
    return [[rel == jnp.float32(lvl) for lvl in range(0, hi_n, k)]
            for rel in rels]


def _radix_lhs(hits, gh, c0: int, csz: int):
    """The product's left operand for components c0 .. c0 + csz - 1,
    [csz*hi_n*m, tile] bf16, rows (c, hi, f): per slab one 32-bit select
    of the component's payload rows by the slab's mask; the slabs
    concatenate at 8-row boundaries and are cast once."""
    return jnp.concatenate(
        [jnp.where(h, gh[c], jnp.float32(0.0))
         for c in range(c0, c0 + csz) for h in hits],
        axis=0).astype(jnp.bfloat16)


def _radix_accumulate(out_ref, block, mask, *, n_blocks: int, k: int,
                      m: int, lo_n: int, hi_n: int, payload: int = 7,
                      planes=None):
    """Accumulate the radix-factorized split-payload histogram of `block`
    [C, tile] bf16 rows selected by `mask` [1, tile] f32 (0/1) into
    out_ref [n_blocks*k*payload*hi_n*m, lo_n*m] f32 — the shared inner
    loop of the segment-histogram kernel and the fused
    partition/refresh+histogram passes.  payload=7 is the f32-exact mode
    (6 residue planes + count); payload=3 is the quantized mode (int8
    g/h codes + count — the accumulator then holds exact integer code
    sums, see ops/quantize).  The payload planes are the block's rows
    after the feature rows, or `planes` (payload-1 rows of [1, tile])
    when the caller holds them apart from the feature rows.

    A bin is two digits, bin = hi * lo_n + lo.  Per product group of m
    features and per 2 048-row tile one MXU product sums, over the rows,
    left[(c, hi, f), t] = payload_c[t] * (hi_f[t] == hi) against the
    one-hot right[(f', lo), t] = (lo_f'[t] == lo); the blocks f == f' of
    the result are the histograms (`split_radix_epilogue`).

    Row order, of the left operand and of the accumulator alike: group,
    then (c, hi, f) — component, hi level, feature within the group.  The
    8 feature rows of a radix block (f_blk = k * m = 8) fill the sublanes
    of an f32 vreg, and they stay there: an 8-row slab of the operand is
    8 // m consecutive hi levels of the group's m features, made by ONE
    compare of a dense [8, tile] array (`_radix_hits`, shared by the
    components) and one 32-bit select per component (`_radix_lhs`).
    Nothing is built with fewer than 8 rows on the sublanes (a
    [.., 4, tile] piece costs what an 8-row one does) and nothing is
    multiplied in bf16 (the v5e has no bf16 VALU: unpack, multiply,
    pack).  `part`'s rows being the accumulator's, each product is added
    by one read-modify-write of a contiguous row range (per chunk in f32
    mode: a chunk of components is a contiguous range in this order)."""
    Mc = payload * hi_n * m
    f_blk = k * m
    assert f_blk == 8 and hi_n % k == 0
    chunks = _comp_chunks(hi_n, m, payload)
    gh = _radix_planes(block, mask, planes, n_blocks=n_blocks,
                       payload=payload)

    for b in range(n_blocks):
        hi, lo = _radix_digits(block[b * f_blk:(b + 1) * f_blk, :], lo_n)
        rhs = _radix_rhs(lo, k=k, lo_n=lo_n)
        for kk, hits in enumerate(_radix_hits(hi, k=k, m=m, hi_n=hi_n)):
            dst, c0 = (b * k + kk) * Mc, 0
            for csz in chunks:
                part = jax.lax.dot_general(
                    _radix_lhs(hits, gh, c0, csz), rhs[kk],
                    dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                sz = csz * hi_n * m                   # part: [sz, lo_n*m]
                out_ref[dst:dst + sz, :] = out_ref[dst:dst + sz, :] + part
                dst, c0 = dst + sz, c0 + csz


def _feature_block(n_blocks: int, f_blk: int, acc_block_bytes: int) -> int:
    """Radix blocks (of f_blk features) a histogram kernel takes per grid
    step.  All of them, in one step and no grid, while the accumulator
    and the two read slots fit three quarters of Mosaic's default scoped
    VMEM and the statically unrolled block loop (`_radix_accumulate`) has
    at most 64 bodies: what narrow data has always compiled.  Else the
    largest divisor of n_blocks that fits, at 16 bodies or fewer: Mosaic
    gives every unrolled body its own stack, and 50 bodies of the
    quantized kernel asked for 35 MB when compiled for a v5e (10 compile
    in 3 s).  That was the body before PR 31; with the left operand in
    8-row slabs 50 bodies of both quantized kernels compile for a v5e
    within the default limit (5 s; 25 in 2 s, 10 in 1.5 s), so the bound
    of 16 is now a choice and not the compiler's (PERF.md, open
    questions).  A step's rows start at a multiple of f_blk >= 8, which the
    DMA takes (40-row steps compiled); one block a step always fits, so
    every width has a plan.  `acc_block_bytes`: the accumulator's bytes
    per radix block.  From static shapes only."""
    room = _VMEM_DEFAULT // 4 * 3
    slots = 2 * f_blk * TILE * 2
    if n_blocks <= 64 and n_blocks * (acc_block_bytes + slots) <= room:
        return n_blocks
    for n in range(2, n_blocks + 1):
        d = n_blocks // n
        if (n_blocks % n == 0 and d <= 16
                and d * (acc_block_bytes + slots) <= room):
            return d
    raise ValueError(
        "no feature block serves %d radix blocks of %d features: one "
        "block's accumulator, %d B, does not fit %d B"
        % (n_blocks, f_blk, acc_block_bytes, room))


def _hist_plan(num_features: int, max_bin: int, payload: int) -> tuple:
    """(lo_n, hi_n, m, f_blk, k, n_blocks, nb) of a histogram kernel: the
    radix plan of `max_bin`, the features per radix block and blocks per
    data set, and `nb`, the radix blocks a grid step takes
    (`_feature_block`; nb == n_blocks: one step, no grid)."""
    lo_n, hi_n, m = _hist_radix(max_bin)
    f_blk = max(m, 8)
    k = f_blk // m
    n_blocks = feature_channels(num_features) // f_blk
    acc = k * payload * hi_n * m * lo_n * m * 4
    return (lo_n, hi_n, m, f_blk, k, n_blocks,
            _feature_block(n_blocks, f_blk, acc))


def _seg_hist_kernel(sc_ref, arena_any, out_ref, in_buf, read_sems, *rest,
                     C: int, F: int,
                     n_blocks: int, k: int, m: int, lo_n: int, hi_n: int,
                     tile: int, payload: int = 7, read_rows: int = 0,
                     pay_row: int = 0):
    """sc_ref (SMEM [2] i32): start, cnt.  out_ref VMEM
    [n_blocks*k*payload*hi_n*m, N]: per product group (n_blocks*k of them,
    m features each) payload*hi_n*m rows in (component, hi level, feature)
    order against N = m*lo_n columns (feature', lo digit); the blocks
    feature == feature' are the histograms (`split_radix_epilogue`).
    Every lhs entry is a bf16-exact payload plane value or zero,
    so the dots run as single bf16 MXU passes and the f32 values are
    reconstructed exactly in the epilogue.  read_rows < C (quantized
    mode) restricts the per-tile DMA to the leading arena rows that the
    3-component payload actually consumes — the row stripe is the
    kernel's whole byte bill, so this IS the quantized bandwidth win.

    pay_row > 0: the call is a grid over feature blocks (`_feature_block`)
    and this step histograms the n_blocks radix blocks from row
    program_id * read_rows, into its own block of the output; the payload
    planes then come by a DMA of their own (the 8-row group at pay_row,
    each step: 8 rows against read_rows), so every feature row is read
    once however many steps there are.  out_ref is then the whole
    histogram in HBM, pinned there (XLA otherwise places a result of a
    few MB in VMEM, whole, beside the kernel's scratch), and the step's
    accumulator a scratch copied out at the step's end."""
    s, cnt = sc_ref[0], sc_ref[1]
    n_tiles = jax.lax.div(cnt + jnp.int32(tile - 1), jnp.int32(tile))
    rows = read_rows or C
    if pay_row:
        pay_buf, pay_sems, acc, out_sem = rest
        out_hbm, out_ref = out_ref, acc
        row0 = pl.multiple_of(pl.program_id(0) * rows, 8)

        def read_dmas(j, slot):
            src = pl.multiple_of(s + j * tile, 128)
            return (pltpu.make_async_copy(
                        arena_any.at[pl.ds(row0, rows), pl.ds(src, tile)],
                        in_buf.at[slot], read_sems.at[slot]),
                    pltpu.make_async_copy(
                        arena_any.at[pl.ds(pay_row, _PAY_ROWS),
                                     pl.ds(src, tile)],
                        pay_buf.at[slot], pay_sems.at[slot]))
    else:
        def read_dmas(j, slot):
            src = pl.multiple_of(s + j * tile, 128)
            return (pltpu.make_async_copy(
                arena_any.at[pl.ds(0, rows), pl.ds(src, tile)],
                in_buf.at[slot], read_sems.at[slot]),)

    out_ref[:] = jnp.zeros_like(out_ref)

    @pl.when(n_tiles > 0)
    def _():
        for d in read_dmas(0, 0):
            d.start()
        for d in read_dmas(0, 0):
            d.wait()

    def loop(j, _):
        slot = jax.lax.rem(j, jnp.int32(2))

        @pl.when(j + 1 < n_tiles)
        def _():
            for d in read_dmas(j + 1,
                               jax.lax.rem(j + jnp.int32(1), jnp.int32(2))):
                d.start()

        block = in_buf[slot]                              # [rows, T] bf16
        valid = (jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
                 < (cnt - j * tile)).astype(jnp.float32)
        planes = None
        if pay_row:
            pay = pay_buf[slot]
            planes = [pay[i:i + 1, :] for i in range(payload - 1)]
        _radix_accumulate(out_ref, block, valid, n_blocks=n_blocks, k=k,
                          m=m, lo_n=lo_n, hi_n=hi_n, payload=payload,
                          planes=planes)

        @pl.when(j + 1 < n_tiles)
        def _():
            for d in read_dmas(j + 1,
                               jax.lax.rem(j + jnp.int32(1), jnp.int32(2))):
                d.wait()
        return 0

    jax.lax.fori_loop(0, n_tiles, loop, 0)
    if pay_row:
        _copy_out_block(acc, out_hbm, out_sem)


def _copy_out_block(acc, out_hbm, sem):
    """Write a grid step's accumulator to its block of the HBM output."""
    n = acc.shape[0]
    out = pltpu.make_async_copy(
        acc, out_hbm.at[pl.ds(pl.multiple_of(pl.program_id(0) * n, 8), n)],
        sem.at[0])
    out.start()
    out.wait()


def split_radix_epilogue(out, G: int, m: int, hi_n: int, lo_n: int,
                         payload: int = 7):
    """[G*payload*hi_n*m, N] split-component accumulator -> [G*m, B, 3]:
    payload=7 sums each f32 value's three split-plane partials; payload=3
    (quantized) passes the integer code sums through unchanged.  Rows are
    (group, c, hi, f), columns (f', lo) (`_radix_accumulate`): feature f's
    histogram is the block f == f', its bins (hi, lo) with lo minor-most
    as in the accumulator's columns."""
    out = out.reshape(G, payload, hi_n, m, m, lo_n)
    diag = jnp.moveaxis(jnp.diagonal(out, axis1=3, axis2=4), -1, 1)
    comp = diag.reshape(G * m, payload, hi_n * lo_n)
    if payload == 3:
        return jnp.stack([comp[:, 0], comp[:, 1], comp[:, 2]], axis=-1)
    g = comp[:, 0] + comp[:, 1] + comp[:, 2]
    h = comp[:, 3] + comp[:, 4] + comp[:, 5]
    return jnp.stack([g, h, comp[:, 6]], axis=-1)         # [G*m, B, 3]


@functools.partial(jax.jit,
                   static_argnames=("num_features", "max_bin", "tile",
                                    "interpret", "quantized"))
def segment_histogram(arena, start, cnt, num_features: int, max_bin: int,
                      tile: int = TILE, interpret: bool = False,
                      quantized: bool = False):
    """[F, max_bin, 3] f32 histogram of arena columns [start, start+cnt).

    quantized=True reads the two int8-code payload planes (arena rows
    Fp+0/Fp+1, see pack_code_planes) instead of the six f32-residue
    planes AND restricts the per-tile DMA to the leading Fp+2 arena rows
    — the returned planes are then exact integer (g_code, h_code, count)
    sums to recover with ops.quantize.dequantize_hist."""
    C, cap = arena.shape
    F = num_features
    payload = 3 if quantized else 7
    lo_n, hi_n, m, f_blk, k, n_blocks, nb = _hist_plan(F, max_bin, payload)
    if n_blocks * f_blk + N_AUX > C:
        raise ValueError("arena channels too small for feature layout")
    Mc, N = payload * hi_n * m, lo_n * m
    sc = jnp.stack([jnp.asarray(start), jnp.asarray(cnt)]).astype(jnp.int32)
    if nb == n_blocks:
        # quantized rows: features + the two code planes, DMA-aligned to
        # the 8-sublane granule; everything past that row never leaves HBM
        read_rows = min(C, _align8(n_blocks * f_blk + 2)) if quantized else C
        kernel = functools.partial(
            _seg_hist_kernel, C=C, F=F, n_blocks=n_blocks, k=k, m=m,
            lo_n=lo_n, hi_n=hi_n, tile=tile, payload=payload,
            read_rows=read_rows)
        out = pl.pallas_call(
            kernel,
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((n_blocks * k * Mc, N),
                                           jnp.float32),
            scratch_shapes=[
                pltpu.VMEM((2, read_rows, tile), ARENA_DT),
                pltpu.SemaphoreType.DMA((2,)),
            ],
            interpret=interpret,
        )(sc, arena)
    else:
        # a grid over blocks of nb * f_blk features: each step reads its
        # own feature rows and the payload group, fills its own block of
        # the output
        kernel = functools.partial(
            _seg_hist_kernel, C=C, F=F, n_blocks=nb, k=k, m=m,
            lo_n=lo_n, hi_n=hi_n, tile=tile, payload=payload,
            read_rows=nb * f_blk, pay_row=n_blocks * f_blk)
        out = pl.pallas_call(
            kernel,
            grid=(n_blocks // nb,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pltpu.HBM),
            out_shape=jax.ShapeDtypeStruct((n_blocks * k * Mc, N),
                                           jnp.float32),
            scratch_shapes=[
                pltpu.VMEM((2, nb * f_blk, tile), ARENA_DT),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((2, _PAY_ROWS, tile), ARENA_DT),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((nb * k * Mc, N), jnp.float32),
                pltpu.SemaphoreType.DMA((1,)),
            ],
            interpret=interpret,
        )(sc, arena)
    hist = split_radix_epilogue(out, n_blocks * k, m, hi_n=hi_n, lo_n=lo_n,
                                payload=payload)
    return hist[:F, :max_bin, :]


# rows of the arena's payload group the fused root kernel rewrites: Mosaic
# (libtpu 0.0.34) refuses a DMA slice of a tiled memref whose sublane
# extent is not a multiple of 8, so the two code planes cannot be written
# alone
_PAY_ROWS = 8


def _fused_root_kernel(sc_ref, codes_any, arena_any, out_any, hist_ref,
                       in_buf, code_buf, pay_buf, read_sems, code_sems,
                       pay_sems, write_sems, *rest,
                       n_blocks: int, k: int, m: int, lo_n: int,
                       hi_n: int, tile: int, pay_row: int = 0):
    """Fused per-tree g/h-plane refresh + root histogram over ONE arena
    pass (quantized mode): per tile, DMA in the feature rows, the fresh
    code tile and the arena's 8-row payload group [Fp, Fp+8); put the
    codes into the group's first two rows in VMEM and DMA the group back
    (dynamic-destination HBM DMA); accumulate the 3-component radix
    histogram from the values already in VMEM.

    The payload group is read, merged and written whole because a 2-row
    DMA slice is not legal (_PAY_ROWS): its rows 2..5 are the stale
    residue planes quantized mode never reads, rows 6..7 are the rowid
    hi/mid byte planes, which go back as they came.

    This replaces the XLA plane update + separate segment_histogram
    launch of the separate-pass schedule: the root segment's feature
    rows are read ONCE, and the fresh codes are touched once on the way
    in instead of write-then-re-read.  Naive per-CHILD fusion was
    measured ~10% worse (see grow_partition's dead-end note); the root
    is different — its histogram covers every row of a segment the
    refresh must stream anyway, exactly like the bagging root
    partition's hist_stream.

    sc_ref (SMEM [2] i32): start, cnt.  codes_any [2, n_al] bf16 code
    planes in segment order; arena_any/out_any [C, cap] bf16 aliased;
    hist_ref VMEM [n_blocks*k*3*hi_n*m, lo_n*m] f32.

    Write-DMA discipline: write j leaves pay_buf slot j%2; it is waited
    at iteration j+1 (before that slot is refilled for tile j+2), and
    the final two writes are drained after the loop — strict per-slot
    alternation, no global counters.  Tiles are column-disjoint, so the
    read of tile j+1 never races the write of tile j in the aliased
    buffer.

    pay_row > 0: the call is a grid over feature blocks (`_feature_block`)
    and this step histograms the n_blocks radix blocks from row
    program_id * n_blocks * k * m into its own block of hist_ref.  Every
    step reads the codes (the histogram's planes: 2 rows against the
    block's hundreds); only step 0 reads, merges and writes the payload
    group at pay_row, so the refresh is done once and no later step
    reads a row another step writes (the feature rows are never written).
    hist_ref is then the whole histogram, pinned in HBM, and the step's
    accumulator a scratch copied out at the step's end (`_seg_hist_kernel`
    says why).
    """
    s, cnt = sc_ref[0], sc_ref[1]
    n_tiles = jax.lax.div(cnt + jnp.int32(tile - 1), jnp.int32(tile))
    rows = n_blocks * k * m
    if pay_row:
        acc, out_sem = rest
        hist_hbm, hist_ref = hist_ref, acc
        Fp = pay_row
        row0 = pl.multiple_of(pl.program_id(0) * rows, 8)
        refresh = pl.program_id(0) == 0

        def when_refresh(f):
            pl.when(refresh)(f)
    else:
        Fp = rows
        row0 = 0

        def when_refresh(f):
            f()

    def feat_dma(j, slot):
        src = pl.multiple_of(s + j * tile, 128)
        return pltpu.make_async_copy(
            arena_any.at[pl.ds(row0, rows), pl.ds(src, tile)],
            in_buf.at[slot], read_sems.at[slot])

    def code_read_dma(j, slot):
        src = pl.multiple_of(j * tile, 128)
        return pltpu.make_async_copy(
            codes_any.at[:, pl.ds(src, tile)],
            code_buf.at[slot], code_sems.at[slot])

    def pay_read_dma(j, slot):
        src = pl.multiple_of(s + j * tile, 128)
        return pltpu.make_async_copy(
            arena_any.at[pl.ds(Fp, _PAY_ROWS), pl.ds(src, tile)],
            pay_buf.at[slot], pay_sems.at[slot])

    def pay_write_dma(j, slot):
        dst = pl.multiple_of(s + j * tile, 128)
        return pltpu.make_async_copy(
            pay_buf.at[slot],
            out_any.at[pl.ds(Fp, _PAY_ROWS), pl.ds(dst, tile)],
            write_sems.at[slot])

    def reads(j, slot):
        return feat_dma(j, slot), code_read_dma(j, slot), \
            pay_read_dma(j, slot)

    def start_reads(j, slot):
        if not pay_row:
            for d in reads(j, slot):
                d.start()
            return
        feat_dma(j, slot).start()
        code_read_dma(j, slot).start()
        when_refresh(lambda: pay_read_dma(j, slot).start())

    def wait_reads(j, slot):
        if not pay_row:
            for d in reads(j, slot):
                d.wait()
            return
        feat_dma(j, slot).wait()
        code_read_dma(j, slot).wait()
        when_refresh(lambda: pay_read_dma(j, slot).wait())

    hist_ref[:] = jnp.zeros_like(hist_ref)

    @pl.when(n_tiles > 0)
    def _():
        start_reads(0, 0)
        wait_reads(0, 0)

    row = jax.lax.broadcasted_iota(jnp.int32, (_PAY_ROWS, tile), 0)

    def loop(j, _):
        slot = jax.lax.rem(j, jnp.int32(2))
        nslot = jax.lax.rem(j + jnp.int32(1), jnp.int32(2))

        @pl.when(j + 1 < n_tiles)
        def _():
            # nslot's outbound write (issued at j-1) must land before the
            # slot's payload buffer is refilled
            @pl.when(j >= 1)
            def _():
                when_refresh(lambda: pay_write_dma(0, nslot).wait())
            start_reads(j + 1, nslot)

        # codes over the group's rows 0..1, in f32: the values are small
        # integers and byte planes, exact either way, and 32-bit selects
        # are the ones Mosaic lowers without a relayout
        cod = code_buf[slot].astype(jnp.float32)          # [2, T]

        @when_refresh
        def _():
            merged = jnp.where(
                row == 0, cod[0:1, :],
                jnp.where(row == 1, cod[1:2, :],
                          pay_buf[slot].astype(jnp.float32)))
            pay_buf[slot] = merged.astype(ARENA_DT)
            pay_write_dma(j, slot).start()

        valid = (jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
                 < (cnt - j * tile)).astype(jnp.float32)
        _radix_accumulate(hist_ref, in_buf[slot], valid, n_blocks=n_blocks,
                          k=k, m=m, lo_n=lo_n, hi_n=hi_n, payload=3,
                          planes=(cod[0:1, :], cod[1:2, :]))

        @pl.when(j + 1 < n_tiles)
        def _():
            wait_reads(j + 1, nslot)
        return 0

    jax.lax.fori_loop(0, n_tiles, loop, 0)

    # drain: writes n_tiles-1 and n_tiles-2 are still outstanding (the
    # in-loop wait is skipped on the last iteration)
    @pl.when(n_tiles >= 2)
    def _():
        when_refresh(lambda: pay_write_dma(
            0, jax.lax.rem(n_tiles - 2, jnp.int32(2))).wait())

    @pl.when(n_tiles >= 1)
    def _():
        when_refresh(lambda: pay_write_dma(
            0, jax.lax.rem(n_tiles - 1, jnp.int32(2))).wait())
    if pay_row:
        _copy_out_block(acc, hist_hbm, out_sem)


@functools.partial(jax.jit,
                   static_argnames=("num_features", "max_bin", "tile",
                                    "interpret"))
def fused_refresh_histogram(arena, codes, start, cnt, num_features: int,
                            max_bin: int, tile: int = TILE,
                            interpret: bool = False):
    """(arena', hist): write the quantized code planes for arena columns
    [start, start+cnt) AND build the segment's integer-code histogram in
    one pass.  codes [2, n] bf16-castable int8-valued planes in segment
    order (pack_code_planes); hist is [F, max_bin, 3] exact integer
    (g_code, h_code, count) sums — recover with quantize.dequantize_hist.
    """
    C, cap = arena.shape
    F = num_features
    lo_n, hi_n, m, f_blk, k, n_blocks, nb = _hist_plan(F, max_bin, 3)
    if n_blocks * f_blk + N_AUX > C:
        raise ValueError("arena channels too small for feature layout")
    Fp = n_blocks * f_blk
    # the rewritten group [Fp, Fp+8) holds the code planes, the stale
    # residue planes and the rowid hi/mid planes: all inside N_AUX
    assert _PAY_ROWS <= N_AUX and Fp % _PAY_ROWS == 0
    Mc, N = 3 * hi_n * m, lo_n * m
    n = codes.shape[1]
    n_al = -(-n // tile) * tile
    codes = jnp.pad(codes.astype(ARENA_DT), ((0, 0), (0, n_al - n)))
    sc = jnp.stack([jnp.asarray(start), jnp.asarray(cnt)]).astype(jnp.int32)
    if nb == n_blocks:
        blocked, more = {}, []
        hist_spec = pl.BlockSpec(memory_space=pltpu.VMEM)
    else:
        # a grid over blocks of nb * f_blk features; step 0 refreshes
        blocked = {"grid": (n_blocks // nb,)}
        more = [pltpu.VMEM((nb * k * Mc, N), jnp.float32),
                pltpu.SemaphoreType.DMA((1,))]
        hist_spec = pl.BlockSpec(memory_space=pltpu.HBM)
    kernel = functools.partial(
        _fused_root_kernel, n_blocks=nb, k=k, m=m, lo_n=lo_n,
        hi_n=hi_n, tile=tile, pay_row=Fp if blocked else 0)
    outs = pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=(pl.BlockSpec(memory_space=pl.ANY), hist_spec),
        out_shape=(jax.ShapeDtypeStruct((C, cap), ARENA_DT),
                   jax.ShapeDtypeStruct((n_blocks * k * Mc, N),
                                        jnp.float32)),
        scratch_shapes=[
            pltpu.VMEM((2, nb * f_blk, tile), ARENA_DT),
            pltpu.VMEM((2, 2, tile), ARENA_DT),
            pltpu.VMEM((2, _PAY_ROWS, tile), ARENA_DT),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ] + more,
        input_output_aliases={2: 0},
        compiler_params=_side_effect_params(),
        interpret=interpret,
        **blocked,
    )(sc, codes, arena)
    hist = split_radix_epilogue(outs[1], n_blocks * k, m, hi_n=hi_n,
                                lo_n=lo_n, payload=3)
    return outs[0], hist[:F, :max_bin, :]


def engine_plan(num_features: int, max_bin: int, quantized: bool) -> dict:
    """What the arena kernels derive from a data set's width: channels,
    channel blocks, feature blocks of the histogram grid and the VMEM each
    kernel's scratch asks for.  Raises ValueError (with the numbers) where
    no block plan serves the width; GBDT._setup_tree_engine calls it
    before it builds an arena, logs it and puts it on the
    `lgbm:engine_plan` span."""
    C = arena_channels(num_features)
    cb, ccb = partition_channel_block(C), compact_channel_block(C)
    payload = 3 if quantized else 7
    lo_n, hi_n, m, f_blk, k, n_blocks, nb = _hist_plan(num_features, max_bin,
                                                       payload)
    acc = k * payload * hi_n * m * lo_n * m * 4
    return {
        "channels": C,
        "partition_block": cb, "partition_blocks": C // cb,
        "compact_block": ccb, "compact_blocks": C // ccb,
        "hist_features_per_step": nb * f_blk, "hist_steps": n_blocks // nb,
        "vmem_partition": (
            C * _VMEM_PER_CHANNEL if cb == C else
            cb * _PART_PER_BLOCK_CHANNEL + C * _PART_RESIDENT + _PART_FIXED),
        "vmem_compact": ccb * _COMPACT_PER_CHANNEL,
        "vmem_histogram": nb * (acc + 2 * f_blk * TILE * 2),
        "vmem_default": _VMEM_DEFAULT,
    }
