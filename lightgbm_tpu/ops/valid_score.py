"""Validation rows through ONE freshly grown tree, on the device, without
a walk: the fused iteration (models/gbdt.py) scores its validation sets
from the tree arrays it has just produced, so no host tree and no fetch is
needed before `eval_valid()`.

A node walk (ops/grow.predict_leaf_inner) is a `while_loop` of per-row
gathers, one trip per level: on a TPU that is tens of milliseconds a level
at 500 000 rows.  The lookup here is ops/predict.py's signature product
cut down to one tree and binned inputs, three dense steps per block of
rows, all of them products or elementwise:

1. every node's bin for every row: a one-hot product `sel[N, G] @
   bins_t[G, rows]` (bins are at most 255, exact in bfloat16), then the
   node's own decision (EFB decode, missing-value rule, threshold or
   category bitset) elementwise, as +1 (left) / -1 (right);
2. `sig[L, N] @ D[N, rows]`: a leaf's signature row holds +1 / -1 for the
   side each of its ancestors must take and 0 off its path, so the product
   equals the leaf's depth exactly where the row reaches the leaf (sums of
   at most 255 terms of +-1: exact in float32);
3. the reached leaf's value: a masked sum over the leaf axis (one term is
   nonzero, so it is the float32 value itself).

The signatures come from the device tree too (`tree_signatures`): the
child links as 0/1 matrices and their transitive closure by repeated
squaring, a handful of 256 x 256 products per tree.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..utils.backend import on_tpu
from .grow import MISSING_NAN, MISSING_ZERO, TreeArrays

# rows per block of the lookup: the [N, rows] and [L, rows] intermediates
# are 32 MB each at 255 leaves
BLOCK_ROWS = 1 << 15


def padded_rows(n: int) -> int:
    """Rows of a validation set's feature-major bins: whole blocks."""
    return -(-max(n, 1) // BLOCK_ROWS) * BLOCK_ROWS


def _operand():
    """The products' operand type: bfloat16 on the TPU, where every
    operand here (0, +-1, a bin up to 255) is exact in it and the MXU
    takes it at full rate; float32 elsewhere (XLA's CPU backend has no
    bfloat16 product inside a larger program)."""
    return jnp.bfloat16 if on_tpu() else jnp.float32


def _product(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def tree_signatures(tree: TreeArrays):
    """(sig [L, N] in {+1, -1, 0}, in the products' operand type; depth
    [L] float32; live [L] bool): per leaf the side each ancestor takes,
    the number of ancestors, and whether the leaf exists.  A tree that did
    not split has no live leaf, so it scores nothing (as the fused
    training update adds 0)."""
    N, L = tree.left_child.shape[0], tree.leaf_value.shape[0]
    dt = _operand()
    grown = tree.num_leaves > 1
    active = (jnp.arange(N) < tree.num_leaves - 1)[:, None]

    def links(child):
        # [N, N + L] 0/1: node -> child entity (nodes first, then leaves)
        entity = jnp.where(child >= 0, child, N + ~child)
        return (jax.nn.one_hot(entity, N + L, dtype=jnp.float32)
                * active).astype(dt)

    left, right = links(tree.left_child), links(tree.right_child)
    step = jnp.eye(N, dtype=jnp.float32) + (left + right)[:, :N].astype(
        jnp.float32)
    reach = step.astype(dt)              # node m in the subtree of node n
    for _ in range(max(N, 2).bit_length()):      # paths of up to 2^k links
        reach = jnp.minimum(_product(reach, reach), 1.0).astype(dt)
    below = _product(reach, (left + right)[:, N:]).astype(dt)   # [N, L]

    def side(link):                       # leaves under that child of n
        return link[:, N:].astype(jnp.float32) + _product(link[:, :N], below)

    sig = (side(left) - side(right)).T    # [L, N]
    depth = jnp.abs(sig).sum(axis=1)
    live = (jnp.arange(L) < tree.num_leaves) & grown
    return sig.astype(dt), depth, live


def tree_delta(bins_t, tree: TreeArrays, signatures, num_bins, default_bins,
               bundle=None):
    """[rows] float32: the value of the leaf each row of `bins_t`
    ([G, rows] feature-major bins in the arena's type, rows a multiple of
    BLOCK_ROWS) reaches in `tree`.  Decisions are
    ops/grow.predict_leaf_inner's, node by node."""
    sig, depth, live = signatures
    dt = sig.dtype
    N = tree.left_child.shape[0]
    G, rows = bins_t.shape
    feat = tree.split_feature
    col = feat if bundle is None else bundle.feat_col[feat]
    sel = jax.nn.one_hot(col, G, dtype=dt)                   # [N, G]

    def per_node(x):
        return x[:, None]

    thr = per_node(tree.threshold_bin)
    default = per_node(default_bins[feat])
    last = per_node(num_bins[feat] - 1)
    zero_missing = per_node(tree.missing_type == MISSING_ZERO)
    nan_missing = per_node(tree.missing_type == MISSING_NAN)
    default_left = per_node(tree.default_left)
    values = jnp.where(live, tree.leaf_value.astype(jnp.float32), 0.0)
    W = tree.cat_mask.shape[1]
    if W:
        # category bitsets as 32-bit words, one row per node
        pad = -W % 32
        bits = jnp.pad(tree.cat_mask, ((0, 0), (0, pad))).reshape(N, -1, 32)
        words = (bits.astype(jnp.uint32)
                 << jnp.arange(32, dtype=jnp.uint32)).sum(
                     axis=2, dtype=jnp.uint32)                # [N, W / 32]

    def block(b):
        v = _product(sel, b.astype(dt)).astype(jnp.int32)    # [N, rows]
        if bundle is not None:
            inside = ((v >= per_node(bundle.feat_lo[feat]))
                      & (v < per_node(bundle.feat_hi[feat])))
            v = jnp.where(inside, v - per_node(bundle.feat_shift[feat]),
                          default)
        missing = (zero_missing & (v == default)) | (nan_missing & (v == last))
        go_left = jnp.where(missing, default_left, v <= thr)
        if W:
            word = jnp.zeros(v.shape, jnp.uint32)
            for j in range(words.shape[1]):
                word = jnp.where((v >> 5) == j, per_node(words[:, j]), word)
            member = ((word >> (v & 31).astype(jnp.uint32)) & 1) > 0
            go_left = jnp.where(per_node(tree.is_cat), member, go_left)
        decisions = jnp.where(go_left, 1.0, -1.0).astype(dt)
        reached = _product(sig, decisions) == depth[:, None]  # [L, rows]
        return jnp.where(reached, values[:, None], 0.0).sum(axis=0)

    blocks = bins_t.reshape(G, rows // BLOCK_ROWS, BLOCK_ROWS)
    return jax.lax.map(block, jnp.moveaxis(blocks, 1, 0)).reshape(rows)


def add_tree(scores, class_id: int, bins_list, tree: TreeArrays, shrink,
             num_bins, default_bins, bundles):
    """Each validation set's [k, n] score with `shrink` times the tree's
    leaf values added to class `class_id`, in the score's own dtype.  The
    signatures are built once per tree, whatever the number of sets."""
    if not scores:
        return scores
    with jax.named_scope("lgbm.valid.score"):
        signatures = tree_signatures(tree)
        out = []
        for score, bins_t, bundle in zip(scores, bins_list, bundles):
            delta = tree_delta(bins_t, tree, signatures, num_bins,
                               default_bins, bundle)[:score.shape[1]]
            out.append(score.at[class_id].add(
                shrink.astype(score.dtype) * delta.astype(score.dtype)))
        return out
