"""Validation metrics of the plain reference, in numpy float64.  Imports
nothing from lightgbm_tpu: harness/checks_valid.py holds what the system's
`eval_valid()` returned against these, computed from the reference
walker's raw scores."""
import numpy as np


def auc(y, scores, weights=None):
    """Area under the ROC curve by the rank-sum formula, rows of equal
    score sharing the mean of their ranks (the reference project's
    binary_metric.hpp AUCMetric counts a tied positive-negative pair as
    half): sum over the positives of their tie-averaged (weighted) rank,
    less what the positives contribute among themselves, over the number
    of positive-negative pairs."""
    y = np.asarray(y) > 0.5
    s = np.asarray(scores, np.float64)
    w = (np.ones(len(s)) if weights is None
         else np.asarray(weights, np.float64))
    order = np.argsort(s, kind="stable")
    s, y, w = s[order], y[order], w[order]
    below = np.concatenate([[0.0], np.cumsum(w)])   # weight of rows before i
    first = np.flatnonzero(np.concatenate([[True], s[1:] != s[:-1]]))
    last = np.concatenate([first[1:], [len(s)]])
    group = np.cumsum(np.concatenate([[True], s[1:] != s[:-1]])) - 1
    # a group's rows all stand at the middle of the weight the group spans
    rank = (below[first] + below[last])[group] / 2.0
    pos, neg = float(w[y].sum()), float(w[~y].sum())
    if pos <= 0 or neg <= 0:
        return 0.5
    return float(((rank * w)[y].sum() - pos * pos / 2.0) / (pos * neg))


def auc_by_pairs(y, scores):
    """The same number by its definition, every positive against every
    negative, a tie counting half: O(n^2), for the tests."""
    y = np.asarray(y) > 0.5
    s = np.asarray(scores, np.float64)
    p, n = s[y][:, None], s[~y][None, :]
    return float(((p > n).sum() + 0.5 * (p == n).sum()) / (p.size * n.size))
