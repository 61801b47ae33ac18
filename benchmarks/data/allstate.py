"""Synthetic data of the Allstate shape (Experiments.rst: 13 184 290 x
4 228 one-hot columns, binary classification): a CSR matrix, never a
dense array (dense float32 it is 223 GB).

The idiom is data/higgs.py's: the configuration fixes the problem (the
columns from its `feature_seed`, the label's effects from its
`label_seed`) and `--seed` draws the label's noise, so every seed shares
one binned cache.

The structure is what one-hot encoding makes of a table of categorical
variables.  `args["cardinalities"]` lists the variables' category counts,
which sum to the column count: a variable owns a run of adjacent columns
and every row has exactly one nonzero (1.0) in each run, so a row stores
one entry per variable.  Within a variable the categories follow a Zipf
law, p(rank) ~ rank^-s, in an order shuffled by `feature_seed` (an encoder
numbers categories by first appearance or alphabet, not by frequency); s
is `zipf_small` for the variables of up to 255 categories and `zipf_large`
for the larger ones, whose long tails hold the columns that have a few
rows in a million.  The label is a logistic model: a fixed effect per
category of every variable, two variable x variable interactions, unit
logistic-scale noise from `--seed`, and an intercept that puts the share
of positives near `base_rate`.
"""
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import scipy.sparse as sp

from benchmarks.harness.rand import normal_f32, stream

_BLOCK = 1 << 19          # rows per independently seeded block
_THREADS = 8              # set-up only, as in harness/rand.py


def layout(args):
    """(offsets [K + 1], per variable: the cumulative distribution over
    its columns' ranks and the column of each rank)."""
    cards = np.asarray(args["cardinalities"], np.int64)
    offsets = np.concatenate([[0], np.cumsum(cards)])
    rng = np.random.default_rng(stream(args["feature_seed"], "layout"))
    cdfs, columns = [], []
    for v, card in enumerate(cards):
        s = args["zipf_large"] if card > 255 else args["zipf_small"]
        p = np.arange(1, card + 1, dtype=np.float64) ** -float(s)
        cdf = np.cumsum(p / p.sum())
        cdf[-1] = 1.0
        cdfs.append(cdf.astype(np.float32))
        columns.append((offsets[v] + rng.permutation(card)).astype(np.int32))
    return offsets, cdfs, columns


def block_indices(cdfs, columns, entropy, b, count):
    """[count, K] int32 column indices of block b's rows."""
    rng = np.random.default_rng(list(entropy) + [b])
    out = np.empty((count, len(cdfs)), np.int32)
    for v, (cdf, column) in enumerate(zip(cdfs, columns)):
        rank = np.searchsorted(cdf, rng.random(count, dtype=np.float32))
        np.minimum(rank, len(cdf) - 1, out=rank)
        out[:, v] = column[rank]
    return out


def as_csr(indices, columns):
    """One stored 1.0 per entry of `indices` [rows, K], a row's entries in
    the variables' order (which is the columns' order)."""
    rows, K = indices.shape
    X = sp.csr_matrix(
        (np.ones(rows * K, np.float32), indices.reshape(-1),
         np.arange(0, rows * K + 1, K, dtype=np.int32)),
        shape=(rows, columns), copy=False)
    X.has_sorted_indices = True
    return X


def features(args, part, rows):
    """[rows, sum(cardinalities)] CSR, float32 ones and int32 indices,
    a function of (feature_seed, part) only.  Block b of 2^19 rows is
    drawn from its own generator, so the result does not depend on the
    number of threads."""
    offsets, cdfs, columns = layout(args)
    indices = np.empty((rows, len(cdfs)), np.int32)
    entropy = stream(args["feature_seed"], "x", part)

    def fill(b):
        lo, hi = b * _BLOCK, min((b + 1) * _BLOCK, rows)
        indices[lo:hi] = block_indices(cdfs, columns, entropy, b, hi - lo)

    with ThreadPoolExecutor(_THREADS) as pool:
        list(pool.map(fill, range(-(-rows // _BLOCK))))
    return as_csr(indices, int(offsets[-1]))


def effects(args):
    """The label's fixed part: (per-column effect [columns], the two
    interactions as (variable a, variable b, table [card a, card b]),
    intercept)."""
    offsets, _, _ = layout(args)
    cards = np.diff(offsets)
    rng = np.random.default_rng(stream(args["label_seed"], "w"))
    # every variable matters a little, a few matter much
    scale = 0.15 + 0.5 * rng.random(len(cards)) ** 3
    w = np.concatenate([scale[v] * rng.standard_normal(cards[v])
                        for v in range(len(cards))]).astype(np.float32)
    small = [v for v in np.argsort(cards, kind="stable") if cards[v] <= 32]
    pairs = [(small[0], small[1]), (small[2], small[3])]
    tables = [(a, b, (0.5 * rng.standard_normal((cards[a], cards[b])))
               .astype(np.float32)) for a, b in pairs]
    return w, tables, np.float32(args["intercept"])


def labels(args, seed, part, X):
    """Per-row targets and no query groups: (y, None).  `--seed` draws the
    label's noise of every part, the train part's too: trees of one-hot
    data then differ from seed to seed within a few iterations, which is
    why the `train_sparse` driver measures a fixed run of iterations and
    not a time (drivers/train_sparse.py)."""
    offsets, _, _ = layout(args)
    w, tables, intercept = effects(args)
    K = len(offsets) - 1
    logits = X @ w + intercept
    cat = X.indices.reshape(len(logits), K)     # one entry per variable
    for a, b, table in tables:
        logits += table[cat[:, a] - offsets[a], cat[:, b] - offsets[b]]
    noise = normal_f32(stream(seed, "noise", part), len(logits), 1)[:, 0]
    return (logits + np.float32(1.6) * noise > 0).astype(np.float32), None
