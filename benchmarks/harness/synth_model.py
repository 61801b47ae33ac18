"""A tree ensemble of a stated shape, drawn from a seed, as LightGBM v2
model text.

Prediction cost depends on the model's shape — trees, leaves per tree,
columns, thresholds that lie on the data's bin edges — and not on what the
trees learned, so the predict cells draw their model instead of training
500 iterations in every set-up.  Each tree is grown leaf-wise over a box
model of the data: a leaf is a range of bins per column and holds that
share of the rows; the leaf to split is drawn by row share (large leaves
split first, as they do under a gain criterion), the column uniformly,
the threshold uniformly among the bin edges inside the leaf's range.
Every leaf is therefore reachable, and depths spread as in a grown tree.
All trees advance one split per step, so 500 trees cost 254 vector steps.
"""
import numpy as np

_DEFAULT_LEFT = 2                 # decision_type of a numerical split


def bin_edges(column_sample, bins):
    """[F, bins - 1] upper edges of equal-frequency bins, per column."""
    q = np.arange(1, bins) / bins
    return np.quantile(np.asarray(column_sample, np.float64), q, axis=0).T


def draw_trees(rng, trees, leaves, edges, leaf_scale):
    """Arrays [trees, leaves - 1] of a leaf-wise ensemble in LightGBM's
    numbering: split i makes node i; its left child keeps the leaf's index
    and its right child is leaf i + 1; a child c < 0 is leaf ~c."""
    F, E = edges.shape                     # thresholds are edges 0..E-1
    T, L = trees, leaves
    t_ix = np.arange(T)
    lo = np.zeros((T, L, F), np.int16)     # a leaf's bins are lo..hi
    hi = np.full((T, L, F), E, np.int16)
    share = np.zeros((T, L))
    share[:, 0] = 1.0
    holder = np.full((T, L), -1, np.int64)  # node whose child slot is the leaf
    out = {k: np.zeros((T, L - 1), np.int64)
           for k in ("split_feature", "left_child", "right_child")}
    out["threshold"] = np.zeros((T, L - 1))
    for node in range(L - 1):
        new = node + 1
        pick = rng.random(T) * share.sum(axis=1)
        leaf = (np.cumsum(share, axis=1) < pick[:, None]).sum(axis=1)
        leaf = np.minimum(leaf, node)      # rounding at the upper end
        width = (hi[t_ix, leaf] - lo[t_ix, leaf]).astype(np.int64)  # [T, F]
        feat = rng.integers(0, F, T)
        feat = np.where(width[t_ix, feat] > 0, feat, width.argmax(axis=1))
        a, b = lo[t_ix, leaf, feat], hi[t_ix, leaf, feat]
        if (b <= a).any():
            raise ValueError("a leaf ran out of bins to split")
        cut = a + (rng.random(T) * (b - a)).astype(np.int16)   # a..b-1
        left_share = share[t_ix, leaf] * (cut - a + 1) / (b - a + 1)
        # the new leaf copies the old leaf's box, then each takes its side
        lo[:, new], hi[:, new] = lo[t_ix, leaf], hi[t_ix, leaf]
        hi[t_ix, leaf, feat] = cut
        lo[t_ix, new, feat] = cut + 1
        share[:, new] = share[t_ix, leaf] - left_share
        share[t_ix, leaf] = left_share
        up = holder[t_ix, leaf]
        has_up = up >= 0
        was_left = out["left_child"][t_ix, np.maximum(up, 0)] == ~leaf
        out["left_child"][t_ix[has_up & was_left],
                          up[has_up & was_left]] = node
        out["right_child"][t_ix[has_up & ~was_left],
                           up[has_up & ~was_left]] = node
        out["split_feature"][:, node] = feat
        out["threshold"][:, node] = edges[feat, cut]
        out["left_child"][:, node] = ~leaf
        out["right_child"][:, node] = ~new
        holder[t_ix, leaf] = node
        holder[:, new] = node
    out["leaf_value"] = rng.standard_normal((T, L)) * leaf_scale
    out["leaf_share"] = share
    return out


def model_text(arrays, columns, rows, objective="binary sigmoid:1"):
    """LightGBM v2 model text of `draw_trees`' arrays."""
    T, n_nodes = arrays["split_feature"].shape

    def ints(a):
        return " ".join(map(str, a.tolist()))

    def reals(a):
        return " ".join(map(repr, a.tolist()))

    blocks = []
    for t in range(T):
        counts = np.maximum(np.rint(arrays["leaf_share"][t] * rows), 1)
        blocks.append("\n".join([
            "Tree=%d" % t,
            "num_leaves=%d" % (n_nodes + 1),
            "num_cat=0",
            "split_feature=" + ints(arrays["split_feature"][t]),
            "split_gain=" + ints(np.ones(n_nodes, np.int64)),
            "threshold=" + reals(arrays["threshold"][t]),
            "decision_type=" + ints(np.full(n_nodes, _DEFAULT_LEFT)),
            "left_child=" + ints(arrays["left_child"][t]),
            "right_child=" + ints(arrays["right_child"][t]),
            "leaf_value=" + reals(arrays["leaf_value"][t]),
            "leaf_count=" + ints(counts.astype(np.int64)),
            "internal_value=" + ints(np.zeros(n_nodes, np.int64)),
            "internal_count=" + ints(np.zeros(n_nodes, np.int64)),
            "shrinkage=1", "", ""]))
    head = "\n".join([
        "tree", "version=v2", "num_class=1", "num_tree_per_iteration=1",
        "label_index=0", "max_feature_idx=%d" % (columns - 1),
        "objective=" + objective,
        "feature_names=" + " ".join("Column_%d" % i for i in range(columns)),
        "feature_infos=" + " ".join(["[-10:10]"] * columns),
        "tree_sizes=" + " ".join(str(len(b)) for b in blocks), "", ""])
    return head + "".join(blocks) + "end of trees\n"
