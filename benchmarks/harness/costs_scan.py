"""Successor file of harness/costs.py (which no PR edits): what a split
scan has to read, from the data set's shape alone.

Whatever implements it, the scan of a leaf's candidate splits must read
that leaf's histogram once: one (gradient, hessian, count) float32 triple
per bin of every column the histogram kernels wrote.  Those columns are
the arena's G group columns (with bundling, io/efb.py; without it, the
features), padded to a multiple of 8 as the arena pads them, each with
`max_bin` + 1 bins (a bundled group column's 256 at `max_bin` 255).  A
scan that first expands the bundled
histogram to one row per original column reads more than this; the floor
counts the bundled histogram all the same, so that it is the same work on
both sides of a change to the scan.  A tree scans its root alone, once,
and then both children of every split in one call; the reader counts the
calls in the trace.  A lower bound: the per-column statics and the result
rows are not counted.
"""

_COMPONENTS = 3           # gradient, hessian, count
_F32 = 4


def _padded_groups(groups):
    return groups + (-groups % 8)


def scan_bytes(groups, max_bin, children):
    """HBM bytes one scan call has to read for `children` leaves."""
    return (children * _padded_groups(groups) * (max_bin + 1)
            * _COMPONENTS * _F32)

