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

A floor on a child's hessian sum is a comparison of sums, and the system
under test makes its sums in float32: a sum that float64 puts a hair under
the floor float32 can round onto it, and the other way round.  With
`SplitRules.bound_rtol` above 0, `replay` therefore judges by two gain
tables per leaf: what the reference demands (`best`, the early stop) is
read from the tight one, where a child must reach the floor times
1 + bound_rtol, and what it allows the system (`gain_of`) from the loose
one, where the floor times 1 - bound_rtol suffices.  A split with a child
inside that band is neither demanded nor forbidden; everything outside it
is judged as with bound_rtol 0, which is one table and the plain rule.
"""
import numpy as np


class SplitRules:
    """The parameters of a split search, by their LightGBM names, and
    `bound_rtol`: the relative band about `min_sum_hessian_in_leaf` inside
    which `replay` neither demands nor forbids a split (row counts are
    integers: `min_data_in_leaf` has no band)."""

    def __init__(self, params, bound_rtol=0.0):
        self.num_leaves = int(params.get("num_leaves", 31))
        self.min_data_in_leaf = int(params.get("min_data_in_leaf", 20))
        self.min_sum_hessian_in_leaf = float(
            params.get("min_sum_hessian_in_leaf", 1e-3))
        self.lambda_l1 = float(params.get("lambda_l1", 0.0))
        self.lambda_l2 = float(params.get("lambda_l2", 0.0))
        self.min_gain_to_split = float(params.get("min_gain_to_split", 0.0))
        self.bound_rtol = float(bound_rtol)


def _threshold_l1(g, l1):
    return np.sign(g) * np.maximum(np.abs(g) - l1, 0.0)


def leaf_output(g, h, rules):
    return -_threshold_l1(g, rules.lambda_l1) / (h + rules.lambda_l2)


def _leaf_gain(g, h, rules):
    t = _threshold_l1(g, rules.lambda_l1)
    return t * t / (h + rules.lambda_l2)


def _split_gain(left, right, total, rules):
    """Gain of parting a leaf of sums `total` into `left` and `right`,
    arrays whose last axis is (gradient sum, hessian sum, count); nan or
    inf where a side is empty, which the callers' floors rule out."""
    with np.errstate(divide="ignore", invalid="ignore"):
        return (_leaf_gain(left[..., 0], left[..., 1], rules)
                + _leaf_gain(right[..., 0], right[..., 1], rules)
                - _leaf_gain(total[..., 0], total[..., 1], rules)
                - rules.min_gain_to_split)


class Tree:
    """What a grown tree is compared by: for split i the leaf it split,
    the column and the last bin that goes left (the right child becomes
    leaf i + 1); per leaf its output and row count."""

    def __init__(self):
        self.split_leaf, self.split_feature, self.split_bin = [], [], []
        self.leaf_value = None
        self.leaf_count = None
        # of a replayed tree: the largest share by which a chosen split's
        # gain fell short of the best on offer (what `gain_rtol` limits)
        self.gain_shortfall = 0.0

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
    every candidate split, per leaf.  `bound_rtol` 0 (what `grow` uses) is
    the plain rule; `replay` passes the rules' own."""

    def __init__(self, bins, num_bins, grad, hess, rules, bound_rtol=0.0):
        self.bins = np.ascontiguousarray(bins)
        self.num_bins = np.asarray(num_bins, np.int64)
        self.grad = np.asarray(grad, np.float64)
        self.hess = np.asarray(hess, np.float64)
        self.rules = rules
        self.bound_rtol = float(bound_rtol)
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
        """(tight, loose): [F, width] gain of splitting after each bin,
        -inf where the split is not allowed, a child's hessian sum held
        to the floor times 1 + bound_rtol and times 1 - bound_rtol.  With
        bound_rtol 0 both are one table."""
        r = self.rules
        left = np.cumsum(hist, axis=1)
        total = left[:, -1:, :]
        right = total - left
        allowed = (self._real
                   & (left[:, :, 2] >= r.min_data_in_leaf)
                   & (right[:, :, 2] >= r.min_data_in_leaf))
        gain = _split_gain(left, right, total, r)
        allowed &= gain > 0.0

        def table(floor):
            return np.where(allowed & (left[:, :, 1] >= floor)
                            & (right[:, :, 1] >= floor), gain, -np.inf)

        if not self.bound_rtol:
            tight = loose = table(r.min_sum_hessian_in_leaf)
        else:
            tight = table(r.min_sum_hessian_in_leaf * (1 + self.bound_rtol))
            loose = table(r.min_sum_hessian_in_leaf * (1 - self.bound_rtol))
        return tight, loose

    def _search(self, leaf):
        """Gains of every candidate split of `leaf` and the best of them.
        Equal gains resolve as in the reference project: the lowest
        column, then the highest bin (its scan runs from the last bin
        down and keeps the first best)."""
        tight, loose = self._gains(self.hist[leaf])
        feature, from_top = divmod(int(np.argmax(tight[:, ::-1])),
                                   self.width)
        bin_ = self.width - 1 - from_top
        self.gains[leaf] = loose
        self._best[leaf] = (float(tight[feature, bin_]), leaf, feature, bin_)

    def best(self):
        """(gain, leaf, column, bin) of the best split of any leaf (the
        lowest leaf among equals), or None when no leaf can be split."""
        found = max(self._best.values(), key=lambda b: (b[0], -b[1]))
        return found if np.isfinite(found[0]) else None

    def gain_of(self, leaf, feature, bin_):
        """The gain of a split someone else chose, -inf where not even
        the loose floor allows it."""
        return float(self.gains[leaf][feature, bin_])

    def describe(self, leaf, feature, bin_):
        """What a candidate split is judged by, as plain numbers: its
        float64 gain whether or not a floor forbids it, each child's row
        count and hessian sum, and how far each stands from its floor
        (rows over `min_data_in_leaf`; hessian sum over
        `min_sum_hessian_in_leaf`, relative, to be read against
        `bound_rtol`).  For the line a refused run prints."""
        r = self.rules
        total = self.hist[leaf][feature].sum(axis=0)
        left = np.cumsum(self.hist[leaf][feature], axis=0)[bin_]
        right = total - left
        return {
            "leaf": int(leaf), "column": int(feature), "bin": int(bin_),
            "gain": float(_split_gain(left, right, total, r)),
            "allowed_gain": self.gain_of(leaf, feature, bin_),
            "rows": [int(left[2]), int(right[2])],
            "hessian": [float(left[1]), float(right[1])],
            "rows_over_floor": [int(left[2]) - r.min_data_in_leaf,
                                int(right[2]) - r.min_data_in_leaf],
            "hessian_over_floor_rel": [
                float(left[1] / r.min_sum_hessian_in_leaf - 1.0),
                float(right[1] / r.min_sum_hessian_in_leaf - 1.0)]}

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
    best gain any leaf offers at that step.  Within `rules.bound_rtol` of
    the hessian floor (the module's docstring) the best is taken over the
    splits whose children clear the floor with the band to spare, and the
    choice may be any split whose children come within the band of it.
    Returns (tree, misses), a miss being (step, the choice's gain, the
    best gain)."""
    g = LeafwiseGrower(bins, num_bins, grad, hess, rules, rules.bound_rtol)
    misses = []
    for step, (leaf, feature, bin_) in enumerate(splits):
        found = g.best()
        gain = g.gain_of(leaf, feature, bin_) if leaf in g.gains else -np.inf
        # no split is demanded (found is None): one inside the band is
        # still allowed; with bound_rtol 0 there is none, and it is a miss
        if found is not None:
            g.tree.gain_shortfall = max(g.tree.gain_shortfall,
                                        1.0 - gain / found[0])
        if not (np.isfinite(gain) if found is None
                else gain >= found[0] * (1.0 - gain_rtol)):
            misses.append((step, gain, found[0] if found else None))
            if not np.isfinite(gain):
                break            # not a split this grower allows at all
        g.split(leaf, feature, bin_)
    if not misses and len(g.rows) < rules.num_leaves and g.best() is not None:
        misses.append((len(splits), None, g.best()[0]))   # stopped early
    return g.finish(), misses


def explain_miss(bins, num_bins, grad, hess, rules, splits, step):
    """What `replay` saw at `step` of `splits`, for the line a refused run
    prints: the system's choice and the reference's best, each as
    `LeafwiseGrower.describe` gives it.  A second replay up to the step:
    paid by a run that has failed, never by one that passes."""
    g = LeafwiseGrower(bins, num_bins, grad, hess, rules, rules.bound_rtol)
    for leaf, feature, bin_ in splits[:step]:
        g.split(leaf, feature, bin_)
    found = g.best()
    out = {"step": step, "leaves": len(g.rows),
           "best": g.describe(*found[1:]) if found else None}
    if step < len(splits) and splits[step][0] in g.gains:
        out["chosen"] = g.describe(*splits[step])
    return out
