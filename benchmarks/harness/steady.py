"""The run's one number from its per-block times."""
import numpy as np


def interquartile_mean(values):
    """Mean of what is left when the lowest and the highest quarter of the
    values (rounded down) are set aside.

    Why not the plain mean: about one run in ten has a block that stalls
    by 100-170 ms (host or machine, not the program), which moves a 20 s
    mean by 0.6-1.3 % when runs otherwise agree to 0.08 %; two such runs
    in a set of six would read as a spread the bound cannot carry.  Why
    not the median: the iteration time climbs by 13 % over the window as
    the trees deepen, so the median is one block's time and spreads by
    0.5-0.9 %.  On the 22 recorded runs of the two cells this spreads by
    0.05-0.16 % and holds a stalled run to +0.4 % (PERF.md, PR 22)."""
    ordered = np.sort(np.asarray(values, np.float64))
    k = len(ordered) // 4
    return float(ordered[k:len(ordered) - k].mean())
