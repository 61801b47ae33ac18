"""A plain leaf-wise histogram grower in numpy float64, independent of
lightgbm_tpu/ops: per-leaf (gradient, hessian, count) histograms over the
binned columns, the reference project's split gain and leaf output
(feature_histogram.hpp: GetSplitGains / CalculateSplittedLeafOutput), the
smaller child built from its rows and the larger by subtraction, and the
leaf with the best gain split next.

It covers what the benchmark's configurations use: numerical columns with
no missing values, `max_delta_step` 0, no monotone or categorical
handling.  It works on the bin matrix the system made (binning is
io/bin_mapper.py, not the code under test here) and can either grow its
own tree (`grow`) or follow a tree the system grew split by split and
judge each choice by its own gains (`replay`).
"""
import numpy as np


class SplitRules:
    """The parameters of a split search, by their LightGBM names."""

    def __init__(self, params):
        self.num_leaves = int(params.get("num_leaves", 31))
        self.min_data_in_leaf = int(params.get("min_data_in_leaf", 20))
        self.min_sum_hessian_in_leaf = float(
            params.get("min_sum_hessian_in_leaf", 1e-3))
        self.lambda_l1 = float(params.get("lambda_l1", 0.0))
        self.lambda_l2 = float(params.get("lambda_l2", 0.0))
        self.min_gain_to_split = float(params.get("min_gain_to_split", 0.0))


def _threshold_l1(g, l1):
    return np.sign(g) * np.maximum(np.abs(g) - l1, 0.0)


def leaf_output(g, h, rules):
    return -_threshold_l1(g, rules.lambda_l1) / (h + rules.lambda_l2)


def _leaf_gain(g, h, rules):
    t = _threshold_l1(g, rules.lambda_l1)
    return t * t / (h + rules.lambda_l2)


class Tree:
    """What a grown tree is compared by: for split i the leaf it split,
    the column and the last bin that goes left (the right child becomes
    leaf i + 1); per leaf its output and row count."""

    def __init__(self):
        self.split_leaf, self.split_feature, self.split_bin = [], [], []
        self.leaf_value = None
        self.leaf_count = None

    def leaf_of_rows(self, bins):
        """Leaf index of every row of a bin matrix."""
        leaf = np.zeros(len(bins), np.int64)
        for i, (l, f, t) in enumerate(zip(self.split_leaf,
                                          self.split_feature,
                                          self.split_bin)):
            leaf[(leaf == l) & (bins[:, f] > t)] = i + 1
        return leaf


class LeafwiseGrower:
    """State of one tree while it grows: rows, histogram and the gain of
    every candidate split, per leaf."""

    def __init__(self, bins, num_bins, grad, hess, rules):
        self.bins = np.ascontiguousarray(bins)
        self.num_bins = np.asarray(num_bins, np.int64)
        self.grad = np.asarray(grad, np.float64)
        self.hess = np.asarray(hess, np.float64)
        self.rules = rules
        self.width = int(self.num_bins.max())
        # a threshold is the last bin of the left side: never the last bin
        self._real = (np.arange(self.width)[None, :]
                      < (self.num_bins - 1)[:, None])
        rows = np.arange(len(self.bins))
        self.rows = {0: rows}
        self.hist = {0: self._histogram(rows)}
        self.gains, self._best = {}, {}
        self._search(0)
        self.tree = Tree()

    def _histogram(self, rows):
        """[F, width, 3] sums of gradient, hessian and count per bin, one
        column at a time (a column's bins stay in the processor's cache)."""
        columns = np.ascontiguousarray(self.bins[rows].T)
        grad, hess = self.grad[rows], self.hess[rows]
        out = np.empty((len(columns), self.width, 3))
        for f, column in enumerate(columns):
            out[f, :, 0] = np.bincount(column, grad, self.width)
            out[f, :, 1] = np.bincount(column, hess, self.width)
            out[f, :, 2] = np.bincount(column, None, self.width)
        return out

    def _gains(self, hist):
        """[F, width] gain of splitting after each bin; -inf where the
        split is not allowed."""
        r = self.rules
        left = np.cumsum(hist, axis=1)
        total = left[:, -1:, :]
        right = total - left
        ok = (self._real
              & (left[:, :, 2] >= r.min_data_in_leaf)
              & (right[:, :, 2] >= r.min_data_in_leaf)
              & (left[:, :, 1] >= r.min_sum_hessian_in_leaf)
              & (right[:, :, 1] >= r.min_sum_hessian_in_leaf))
        with np.errstate(divide="ignore", invalid="ignore"):
            gain = (_leaf_gain(left[:, :, 0], left[:, :, 1], r)
                    + _leaf_gain(right[:, :, 0], right[:, :, 1], r)
                    - _leaf_gain(total[:, :, 0], total[:, :, 1], r)
                    - r.min_gain_to_split)
        return np.where(ok & (gain > 0.0), gain, -np.inf)

    def _search(self, leaf):
        """Gains of every candidate split of `leaf` and the best of them.
        Equal gains resolve as in the reference project: the lowest
        column, then the highest bin (its scan runs from the last bin
        down and keeps the first best)."""
        gains = self._gains(self.hist[leaf])
        feature, from_top = divmod(int(np.argmax(gains[:, ::-1])),
                                   self.width)
        bin_ = self.width - 1 - from_top
        self.gains[leaf] = gains
        self._best[leaf] = (float(gains[feature, bin_]), leaf, feature, bin_)

    def best(self):
        """(gain, leaf, column, bin) of the best split of any leaf (the
        lowest leaf among equals), or None when no leaf can be split."""
        found = max(self._best.values(), key=lambda b: (b[0], -b[1]))
        return found if np.isfinite(found[0]) else None

    def gain_of(self, leaf, feature, bin_):
        return float(self.gains[leaf][feature, bin_])

    def split(self, leaf, feature, bin_):
        """Rows of `leaf` whose bin in `feature` is above `bin_` become a
        new leaf, numbered after all existing ones."""
        new = len(self.rows)
        rows = self.rows[leaf]
        goes_right = self.bins[rows, feature] > bin_
        left, right = rows[~goes_right], rows[goes_right]
        parent = self.hist[leaf]
        if len(left) <= len(right):
            h_left = self._histogram(left)
            h_right = parent - h_left
        else:
            h_right = self._histogram(right)
            h_left = parent - h_right
        self.rows[leaf], self.rows[new] = left, right
        self.hist[leaf], self.hist[new] = h_left, h_right
        self._search(leaf)
        self._search(new)
        self.tree.split_leaf.append(leaf)
        self.tree.split_feature.append(int(feature))
        self.tree.split_bin.append(int(bin_))

    def finish(self):
        n = len(self.rows)
        self.tree.leaf_count = np.array(
            [len(self.rows[i]) for i in range(n)], np.int64)
        self.tree.leaf_value = np.array(
            [leaf_output(self.grad[self.rows[i]].sum(),
                         self.hess[self.rows[i]].sum(), self.rules)
             for i in range(n)])
        return self.tree


def grow(bins, num_bins, grad, hess, rules):
    """The reference's own tree."""
    g = LeafwiseGrower(bins, num_bins, grad, hess, rules)
    while len(g.rows) < rules.num_leaves:
        found = g.best()
        if found is None:
            break
        g.split(*found[1:])
    return g.finish()


def replay(bins, num_bins, grad, hess, rules, splits, gain_rtol):
    """Follow `splits` — the (leaf, column, bin) choices of a tree grown
    elsewhere from the same gradients — and judge each by this grower's
    own gains: a choice passes when its gain is within `gain_rtol` of the
    best gain any leaf offers at that step.  Returns (tree, misses), a
    miss being (step, the choice's gain, the best gain)."""
    g = LeafwiseGrower(bins, num_bins, grad, hess, rules)
    misses = []
    for step, (leaf, feature, bin_) in enumerate(splits):
        found = g.best()
        gain = g.gain_of(leaf, feature, bin_) if leaf in g.gains else -np.inf
        if found is None or not gain >= found[0] * (1.0 - gain_rtol):
            misses.append((step, gain, found[0] if found else None))
            if not np.isfinite(gain):
                break            # not a split this grower allows at all
        g.split(leaf, feature, bin_)
    if not misses and len(g.rows) < rules.num_leaves and g.best() is not None:
        misses.append((len(splits), None, g.best()[0]))   # stopped early
    return g.finish(), misses
