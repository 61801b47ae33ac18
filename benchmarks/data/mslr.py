"""Synthetic data of the MSLR-WEB30K shape (Experiments.rst "MS LTR":
2.27M x 137, here a fixed number of documents per query).

Copied from bench.py `mslr_data`: Gaussian columns, a sparse linear
utility, graded 0-4 relevance from each query's ranking of it (the top 2
documents grade 4, the next 4 grade 3, then 9 of grade 2 and 25 of grade
1).  As in data/higgs.py the configuration fixes the problem — columns
from `feature_seed`, the utility's weights from `label_seed` — and
`--seed` draws the utility's noise; the per-query Python loop of the
original is a rank lookup here.
"""
import numpy as np

from benchmarks.harness.rand import normal_f32, stream

FEATURES = 137
SIGNAL_FEATURES = 10
_GRADE_CUTS = np.array([2, 6, 15, 40])        # rank < cut -> grade 4, 3, 2, 1


def features(args, part, rows):
    """[rows, 137] float32; `rows` is a whole number of queries."""
    return normal_f32(stream(args["feature_seed"], "x", part), rows, FEATURES)


def labels(args, seed, part, X):
    """(graded relevance per document, documents per query)."""
    docs = int(args["docs_per_query"])
    if len(X) % docs:
        raise ValueError("%d rows is not a whole number of %d-document "
                         "queries" % (len(X), docs))
    queries = len(X) // docs
    w = np.random.default_rng(stream(args["label_seed"], "w"))
    w = w.standard_normal(SIGNAL_FEATURES).astype(np.float32)
    noise = normal_f32(stream(seed, "noise", part), len(X), 1)[:, 0]
    util = (X[:, :SIGNAL_FEATURES] @ w + np.float32(0.3) * noise)
    order = np.argsort(-util.reshape(queries, docs), axis=1, kind="stable")
    rank = np.empty_like(order)
    np.put_along_axis(rank, order, np.arange(docs)[None, :], axis=1)
    grade = len(_GRADE_CUTS) - np.searchsorted(_GRADE_CUTS, rank, "right")
    return (grade.reshape(-1).astype(np.float32),
            np.full(queries, docs, np.int64))
