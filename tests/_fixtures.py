"""Seeded stand-ins for the reference's `examples/` files.

The reference checkout is not mounted where these tests run, so the files
its end-to-end tests read are generated: TSV, label first, in the
reference's layout and sizes (`binary.train` 7 000 rows x label + 28
features, `binary.test` 500 rows, a `binary.train.weight` side file, and a
regression pair of the same layout with a continuous label).  The columns
are Higgs-like: unit Gaussians printed to three decimals, four of them
clipped at zero (half their values are exact zeros, as the b-tag columns
of the real set are); the label is a noisy linear term plus one
interaction.  The same seed gives the same bytes
(tests/test_layering.py::test_example_files_are_reproducible).
"""
import os

import numpy as np

TRAIN_ROWS, TEST_ROWS, FEATURES = 7000, 500, 28
SEED = 30
ZERO_CLIPPED = (8, 12, 16, 20)
FILES = ("binary.train", "binary.test", "binary.train.weight",
         "regression.train", "regression.test")


def _save(path, label, X, label_fmt):
    np.savetxt(path, np.column_stack([label, X]), delimiter="\t",
               fmt=[label_fmt] + ["%.3f"] * X.shape[1])


def write_examples(root, seed=SEED):
    """Write the five files under `root`; returns {name: path}."""
    rng = np.random.RandomState(seed)
    n = TRAIN_ROWS + TEST_ROWS
    X = rng.randn(n, FEATURES)
    X[:, ZERO_CLIPPED] = np.maximum(X[:, ZERO_CLIPPED], 0.0)
    X = np.round(X, 3)
    w = rng.randn(FEATURES)
    signal = 0.5 * (X @ w) + 0.8 * np.sin(2 * X[:, 0]) * X[:, 1]
    binary = (signal + 2.0 * rng.randn(n) > 0).astype(np.float64)
    regression = signal + 0.5 * rng.randn(n)
    weight = rng.uniform(0.5, 1.5, TRAIN_ROWS)
    paths = {name: os.path.join(str(root), name) for name in FILES}
    tr, te = slice(0, TRAIN_ROWS), slice(TRAIN_ROWS, n)
    _save(paths["binary.train"], binary[tr], X[tr], "%d")
    _save(paths["binary.test"], binary[te], X[te], "%d")
    _save(paths["regression.train"], regression[tr], X[tr], "%.4f")
    _save(paths["regression.test"], regression[te], X[te], "%.4f")
    np.savetxt(paths["binary.train.weight"], weight, fmt="%.3f")
    return paths
