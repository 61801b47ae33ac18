"""Synthetic data of the Higgs shape (Experiments.rst: 10.5M x 28, binary).

Copied from bench.py `higgs_data` (ISSUE 22: sound generator, copy it):
Gaussian columns, a noisy linear label with one interaction term.  Two
things differ.  The features are float32 from `default_rng` in parallel
blocks (bench.py's float64 `RandomState.randn` + cast was most of 14 s).
And the configuration, not `--seed`, fixes the problem: the columns come
from its `feature_seed` and the label's weight vector from its
`label_seed`; `--seed` draws the label's noise, i.e. another sample of
the same problem.  Fixed columns let every seed share one binned cache
(binning is the most expensive thing a run does, and i.i.d. columns
redrawn are, in distribution, these permuted).  A fixed weight vector
keeps the work fixed: with the weights drawn from `--seed` the iteration
time moved by 2.4 % from seed to seed while two runs of one seed agreed
to 0.006 % (PERF.md, PR 22) — tree shapes follow the label function.
"""
import numpy as np

from benchmarks.harness.rand import normal_f32, stream

FEATURES = 28


def features(args, part, rows):
    """[rows, 28] float32, a function of (feature_seed, part) only."""
    return normal_f32(stream(args["feature_seed"], "x", part), rows, FEATURES)


def labels(args, seed, part, X):
    """Per-row targets and no query groups: (y, None)."""
    w = np.random.default_rng(stream(args["label_seed"], "w"))
    w = w.standard_normal(FEATURES).astype(np.float32)
    noise = normal_f32(stream(seed, "noise", part), len(X), 1)[:, 0]
    logits = X @ w * np.float32(0.5) \
        + np.float32(0.8) * np.sin(X[:, 0] * 2) * X[:, 1]
    return (logits + noise > 0).astype(np.float32), None
