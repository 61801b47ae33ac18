"""The two objectives' gradients in numpy float64, written from the
reference project's formulae (LightGBM v2.2.4 binary_objective.hpp and
rank_objective.hpp), independent of lightgbm_tpu.

Departures from the C++: the lambdarank sigmoid is the exact expression
2 / (1 + exp(2 * sigmoid * x)), not the reference's 1M-entry lookup table
of it, and there are no row weights.
"""
import numpy as np

SIGMOID = 1.0            # both configurations leave `sigmoid` at its default
MAX_POSITION = 20        # lambdarank's `max_position` default


def binary_init_score(y):
    """BoostFromScore: the log-odds of the positive share."""
    p = float(np.mean(np.asarray(y) > 0.5))
    return float(np.log(p / (1.0 - p)) / SIGMOID)


def binary_gradients(score, y):
    sign = np.where(np.asarray(y) > 0.5, 1.0, -1.0)
    response = -sign * SIGMOID / (1.0 + np.exp(sign * SIGMOID * score))
    mag = np.abs(response)
    return response, mag * (SIGMOID - mag)


def _max_dcg(labels, k):
    top = np.sort(labels)[::-1][:k]
    return float(((2.0 ** top - 1.0)
                  / np.log2(np.arange(2, len(top) + 2))).sum())


def lambdarank_gradients(score, labels, group):
    """LambdarankNDCG::GetGradientsForOneQuery for every query: all pairs
    (high, low) of one query with label[high] > label[low]."""
    score = np.asarray(score, np.float64)
    labels = np.asarray(labels, np.float64)
    grad, hess = np.zeros_like(score), np.zeros_like(score)
    start = 0
    for size in np.asarray(group, np.int64):
        sl = slice(start, start + size)
        start += size
        lab = labels[sl]
        max_dcg = _max_dcg(lab, MAX_POSITION)
        if size < 2 or max_dcg <= 0.0:
            continue
        order = np.argsort(-score[sl], kind="stable")
        s, lab = score[sl][order], lab[order]
        gain = 2.0 ** lab - 1.0
        disc = 1.0 / np.log2(np.arange(2, size + 2))
        delta = s[:, None] - s[None, :]                  # high - low
        pair = lab[:, None] > lab[None, :]
        dndcg = ((gain[:, None] - gain[None, :])
                 * np.abs(disc[:, None] - disc[None, :]) / max_dcg)
        if s[0] != s[-1]:
            dndcg = dndcg / (0.01 + np.abs(delta))
        sig = 2.0 / (1.0 + np.exp(2.0 * SIGMOID * delta))
        lam = np.where(pair, -sig * dndcg, 0.0)
        hes = np.where(pair, sig * (2.0 - sig) * 2.0 * dndcg, 0.0)
        g = np.empty(size)
        h = np.empty(size)
        g[order] = lam.sum(axis=1) - lam.sum(axis=0)
        h[order] = hes.sum(axis=1) + hes.sum(axis=0)
        grad[sl], hess[sl] = g, h
    return grad, hess
