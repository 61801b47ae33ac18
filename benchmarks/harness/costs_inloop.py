"""Successor file of harness/costs.py (which no PR edits): what the two
kernels inside the growth loop have to move for a call over `rows` rows,
from the data set's shape alone and never from what a kernel reports.
The rows of each call come from the program's split ledger
(lightgbm_tpu/obs/device.py `split_ledgers`; readers/row_ledger.py).

Both are bound by bytes, and both floors are lower bounds.

`partition_segment` reads every arena channel of its rows once and writes
it once (to one child or the other): harness/costs_partition.py's own
convention for the root segment, so that this floor over a tree's first
call IS `partition_root_bytes`.  Its permutation products are 2 * 256
multiply-adds a row and channel, a few per cent of the chip's arithmetic
at its byte rate.  Not counted: the tile read past the segment's end, a
channel-blocked kernel's decision rows, each child's padding to 256
columns.

`segment_histogram` reads of its rows the feature channels and the
gradient and hessian planes (three bfloat16 planes each), not the row id
and not the arena's padding to 16 channels, and writes one (gradient,
hessian, count) float32 triple per feature and bin.  A histogram is three
adds per row and feature, far under the chip's arithmetic at that byte
rate; that the kernel makes them as MXU products over one-hot operands
(PERF.md section 5: 78 / 86 / 97 % of its products' time) is the
implementation's affair, so its share of the byte roof reads low by
nature: the distance is what the one-hot formulation costs.
"""
from benchmarks.harness import costs_partition

_ARENA_BYTES = 2          # the arena is bfloat16
_GRADIENT_PLANES = 6      # gradient and hessian, three planes each
_COMPONENTS = 3           # gradient, hessian, count
_F32 = 4


def partition_bytes(rows, features):
    """HBM bytes of one partition_segment call over `rows` rows."""
    return 2 * rows * costs_partition.arena_channels(features) * _ARENA_BYTES


def histogram_bytes(rows, features, max_bin):
    """HBM bytes of one segment_histogram call over `rows` rows."""
    padded = features + (-features % 8)
    return (rows * (padded + _GRADIENT_PLANES) * _ARENA_BYTES
            + features * max_bin * _COMPONENTS * _F32)
