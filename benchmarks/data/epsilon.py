"""Synthetic data of the Epsilon shape (GPU-Performance.rst: 400 000 x
2 000 dense, binary; PASCAL Large Scale Learning Challenge).

The idiom is data/higgs.py's: the configuration fixes the problem (the
columns from its `feature_seed`, the label's weights from its
`label_seed`) and `--seed` draws the label's noise, so every seed shares
one binned cache and the trees' shapes, and with them the work, stay
fixed.  What differs is the label.  With 2 000 columns a weight vector of
equal variances would give each column a 1/2000 share of the signal and
the first trees nothing to find; the real set's columns are a spectrum.
So the weights decay as (1 + j/50)^-0.75 over the columns (a few dozen
strong ones, a long tail that still offers splits), scaled to a linear
term of unit variance, beside two interactions and unit noise: moderate
probabilities, so that hessian sums stay large enough for every tree of
a window to reach 255 leaves under `min_sum_hessian_in_leaf` = 100.
"""
import numpy as np

from benchmarks.harness.rand import normal_f32, stream


def _needs_a_block_plan(columns):
    """The cell states the partition engine (`expect`), and a checkout
    whose arena kernels hold a whole [channels, tile] slab in VMEM stops at
    512 channels: under `auto` it would bin 2 000 columns for minutes and
    then train on the label engine, a run that is `correct: false` by the
    cell's own files, and with the engine forced Mosaic compiles its
    2 000-feature histogram kernel for a quarter of an hour before it runs
    out of VMEM (PERF.md, PR 27).  Such a checkout cannot run this
    configuration; say so at once, before any data is made, instead of
    after a long wrong run."""
    from lightgbm_tpu.ops import partition_pallas
    if columns > 500 and not hasattr(partition_pallas, "engine_plan"):
        raise SystemExit(
            "benchmarks/data/epsilon.py: this checkout's partition engine "
            "has no block plan (ops/partition_pallas.engine_plan): it "
            "cannot keep %d columns on the engine the cell states"
            % columns)


def features(args, part, rows):
    """[rows, features] float32, a function of (feature_seed, part)."""
    _needs_a_block_plan(args["features"])
    return normal_f32(stream(args["feature_seed"], "x", part), rows,
                      args["features"])


def weights(args):
    """The label's fixed weight vector: unit-variance linear term."""
    f = args["features"]
    w = np.random.default_rng(stream(args["label_seed"], "w"))
    w = w.standard_normal(f) * (1.0 + np.arange(f) / 50.0) ** -0.75
    return (w / np.sqrt(np.sum(w * w))).astype(np.float32)


def labels(args, seed, part, X):
    """Per-row targets and no query groups: (y, None)."""
    noise = normal_f32(stream(seed, "noise", part), len(X), 1)[:, 0]
    logits = X @ weights(args) \
        + np.float32(0.6) * np.sin(X[:, 0] * 2) * X[:, 1] \
        + np.float32(0.4) * X[:, 2] * X[:, 3]
    return (logits + noise > 0).astype(np.float32), None
