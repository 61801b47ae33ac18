"""Successor file of harness/costs.py (which no PR edits): what the
partition kernel has to move for the one call per tree whose size is
known from the data set's shape alone.

A tree's first split partitions the root segment, which is every row of
the data set, so its bytes need no ledger of rows per call: every arena
channel of every row is read once and written once (to one child or the
other).  The channels are computed here from the feature count, never
taken from anything the kernel reports: features padded to 8, the nine
payload planes (gradient, hessian and row id, three bfloat16 planes
each), padded to the bfloat16 sublane tile of 16.  A lower bound: the
tile the kernel reads past the segment's end, the decision rows a
channel-blocked kernel reads besides, and the padding of each child to
256 columns are not counted.
"""

_ARENA_BYTES = 2          # the arena is bfloat16
_PAYLOAD_PLANES = 9
_SUBLANE_TILE = 16


def arena_channels(features):
    padded = features + (-features % 8) + _PAYLOAD_PLANES
    return padded + (-padded % _SUBLANE_TILE)


def partition_root_bytes(rows, features):
    """HBM bytes of the partition of a tree's root segment."""
    return 2 * rows * arena_channels(features) * _ARENA_BYTES
