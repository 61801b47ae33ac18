"""Model-quality arithmetic, in numpy float64.  `auc` and `ndcg_at_k` are
copied from bench.py (`_auc`, `_ndcg_at_k`; ISSUE 22: sound, copy them),
the latter reshaped for equal-sized queries."""
import numpy as np


def auc(y, p):
    """Area under the ROC curve by the rank-sum formula (ties keep their
    sort order, as in bench.py)."""
    y, p = np.asarray(y), np.asarray(p, np.float64)
    ranks = np.empty(len(p))
    ranks[np.argsort(p)] = np.arange(1, len(p) + 1)
    pos = y > 0.5
    n_pos, n_neg = pos.sum(), (~pos).sum()
    return float((ranks[pos].sum() - n_pos * (n_pos + 1) / 2)
                 / (n_pos * n_neg))


def logloss(y, raw):
    """Mean binary log loss of raw scores (sigmoid 1)."""
    y, raw = np.asarray(y, np.float64), np.asarray(raw, np.float64)
    return float(np.mean(np.logaddexp(0.0, -np.where(y > 0.5, raw, -raw))))


def ndcg_at_k(labels, scores, group, k=10):
    """Mean NDCG@k over the queries that have a relevant document; gain
    2^label - 1, discount 1/log2(1 + position)."""
    labels = np.asarray(labels, np.float64)
    scores = np.asarray(scores, np.float64)
    total, counted, start = 0.0, 0, 0
    for size in np.asarray(group, np.int64):
        lab, sc = labels[start:start + size], scores[start:start + size]
        start += size
        if lab.max() <= 0:
            continue
        top = np.argsort(-sc, kind="stable")[:k]
        ideal = np.sort(lab)[::-1][:k]
        disc = 1.0 / np.log2(np.arange(2, len(top) + 2))
        total += (((2.0 ** lab[top] - 1) * disc).sum()
                  / ((2.0 ** ideal - 1) * disc).sum())
        counted += 1
    return total / max(counted, 1)
