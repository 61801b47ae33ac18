"""Traffic of kind `train_rowsampled`: drivers/train.py's loop, check and
path check, unchanged, over a configuration that samples rows
(bagging_fraction below 1, bagging_freq above 0), and two things more.

The timed booster's own training scores are checked after its window and
traced slice: `correct.timed_rows` rows spread over every row id (random
ones from `--seed`, the first and the last, and each side of every power
of two below the row count, where a row id takes another word) are
generated again, and the score the booster holds for each is held to the
plain walker's over its model text (harness/checks_bagged.py).  Every one
of those rows was out of the bag for about a fifth of the trees, so the
out-of-bag scoring of the cell's own arena, at its own size, decides
`correct` too, and not only the sample's.

The shape's `rows` is the rows every tree is grown on, the bag's
`int(bagging_fraction * rows)` (gbdt.cpp's `bag_data_cnt_`); the data
set's are under `data_rows`.  A bagged tree's root segment is its bag, so
its first partition moves the bag's rows: the row ledger's reader
(readers/row_ledger.py) pairs a tree with its kernel calls only where that
first entry equals `rows`, and counts passes over the rows a tree has.
"""
import numpy as np

from benchmarks.harness import checks_bagged, manifest
from benchmarks.harness.rand import stream


def timed_rows(seed, n, count):
    """Sorted distinct row ids of [0, n): `count` drawn from `seed`, the
    first and the last, and 2**k - 1 and 2**k for every 2**k < n."""
    edges = [0, n - 1] + [r for k in range(1, n.bit_length())
                          for r in (2 ** k - 1, 2 ** k) if r < n]
    drawn = np.random.default_rng(stream(seed, "timed-rows")).choice(
        n, min(count, n), replace=False)
    return np.unique(np.concatenate([drawn, edges]))


def run(bench):
    import lightgbm_tpu as lgb
    train = manifest.load_module(bench.cell.root, "drivers", "train")
    data, c = bench.cell.config["data"], bench.cell.config["correct"]
    timed = []
    plain = lgb.Booster

    class Kept(plain):
        """Keeps the timed booster, the one over the whole data set,
        which drivers/train.py holds only while it runs."""

        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            if self._gbdt.num_data == data["rows"]:
                timed[:] = [self]

    lgb.Booster = Kept
    try:
        outcome = train.run(bench)
    finally:
        lgb.Booster = plain
    booster, = timed
    ids = timed_rows(bench.seed, data["rows"], c["timed_rows"])
    X = bench.cell.generator().features(data["args"], "train",
                                        data["rows"])[ids]
    outcome["problems"] += checks_bagged.timed_score_problems(
        bench, c, booster, X, ids)
    params = dict(bench.cell.config["params"], **bench.cell.traffic["params"])
    shape = outcome["shape"]
    shape["data_rows"] = shape["rows"]
    shape["rows"] = checks_bagged.expected_bag_rows(params, shape["rows"])
    return outcome
