"""The in-loop kernels against the rows they worked on: each
`partition_segment` / `segment_histogram` call of the traced slice paired
with the program's count of its rows.

The program keeps, for each of its last trained trees, the rows every step
of the growth loop partitioned and summed (`lightgbm_tpu.obs.device
.split_ledgers()`, fed wherever a trained tree reaches the host; read
in-process, as harness/bench.py reads `compile_counts()`).  The trace
holds the kernel's calls in order inside each execution of the growth
`while` (harness/loop_calls.py).  Every driver trains nothing after the
slice, so the slice's trees are the ledger's last `traced_units` entries:
tree k pairs with loop k, call i with entry i.

Nothing is guessed.  The reader returns None without a reduced trace,
without the ledger (the program before PR 36), on more than one chip (the
ledger's counts are global, a chip's calls are its shard's), where the
loops are not `traced_units`, where a loop's calls are not its entry's
steps (a pooled histogram cache makes two histogram calls a split), and
where a tree's first partition is not every row of the data set (on a
full bag it is: the check that the right trees were paired).

args {"pattern": regex of the kernel, over trace_reduce's labels,
      "rows": "partition_rows" | "histogram_rows" (the ledger's field),
      "what": "passes" | "ms_per_pass" | "roofline" | "call_us",
      "calls": "first" keeps each tree's first call only (the root
               segment: what partition_root_roofline reads)}

- passes: rows of all calls / the data set's rows / traced trees.
- ms_per_pass: the calls' time per pass of that many rows, in ms; it does
  not depend on which trees the slice fell on.
- roofline: byte floor of all calls (harness/costs_inloop.py) over the
  published HBM bandwidth (harness/peaks.py) over their time, in %; bound
  by bytes.
- call_us: the intercept b of the Theil-Sen line seconds = a * rows + b
  through the slice's calls (drivers/train_sparse.py lays the same line
  through blocks), in microseconds: what a call costs before its first
  row.
"""
import numpy as np

from benchmarks.harness import costs_inloop, loop_calls, peaks
from benchmarks.harness import xplane_names


def theil_sen(x, y):
    """(slope, intercept) of the line through (x, y): the median of the
    pairwise slopes, then the median of what it leaves."""
    x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
    i, j = np.triu_indices(len(x), 1)
    run = x[j] - x[i]
    if not np.count_nonzero(run):
        return None
    a = float(np.median((y[j] - y[i])[run != 0] / run[run != 0]))
    return a, float(np.median(y - a * x))


def paired(run, pattern, field):
    """[(rows, seconds) per call] per traced tree, or None (see above)."""
    if run.trace is None or run.trace.chips != 1:
        return None
    path = xplane_names.trace_of(run)
    try:
        from lightgbm_tpu.obs.device import split_ledgers
    except ImportError:
        return None
    trees = run.shape["traced_units"]
    entries = split_ledgers()[-trees:]
    chips = loop_calls.in_loop_calls(path, pattern) if path else []
    if len(chips) != 1 or len(chips[0]) != trees or len(entries) != trees:
        return None
    out = []
    for calls, entry in zip(chips[0], entries):
        rows = entry[field]
        if len(calls) != len(rows) or not len(rows) \
                or entry["partition_rows"][0] != run.shape["rows"]:
            return None
        out.append(list(zip((int(r) for r in rows), calls)))
    return out


def read(run, args):
    trees = paired(run, args["pattern"], args["rows"])
    if trees is None:
        return None
    if args.get("calls") == "first":
        trees = [tree[:1] for tree in trees]
    rows, seconds = (np.array(column, np.float64) for column in
                     zip(*(call for tree in trees for call in tree)))
    shape, what = run.shape, args["what"]
    if what == "passes":
        return float(rows.sum()) / shape["rows"] / len(trees)
    if not rows.sum() or not seconds.sum():
        return None
    if what == "ms_per_pass":
        return float(seconds.sum() / rows.sum()) * shape["rows"] * 1e3
    if what == "roofline":
        floor = sum(costs_inloop.partition_bytes(r, shape["features"])
                    if args["rows"] == "partition_rows" else
                    costs_inloop.histogram_bytes(r, shape["features"],
                                                 shape["max_bin"])
                    for r in rows)
        return 100.0 * floor / peaks.peaks_of(
            run.device_kind)["hbm_bytes_per_s"] / float(seconds.sum())
    if what == "call_us":
        line = theil_sen(rows, seconds)
        return None if line is None else line[1] * 1e6
    raise ValueError("row_ledger: no reading %r" % (what,))
