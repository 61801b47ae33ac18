"""Share of its HBM roofline the split scan reaches: the bytes of the
histograms any scan must read (harness/costs_scan.py) over the chip's
published bandwidth (harness/peaks.py), over the scan kernel's time in the
device trace.  Bound by bytes: a candidate costs a few dozen floating-point
operations per 12 bytes read, far under the chip's arithmetic.  It reads
low: a call is a few microseconds of bytes and is bound by its latency;
that is what the metric is for.  The calls are counted from the trace: the
root's call reads one leaf's histogram and every other call two.
args {"pattern": regex of the kernel}."""
from benchmarks.harness import costs_scan, peaks


def read(run, args):
    if run.trace is None:
        return None
    seconds, calls = run.trace.family(args["pattern"])
    trees = run.trace.chips * run.shape["traced_units"]
    if not calls or not seconds or calls < trees:
        return None
    groups, max_bin = run.shape["features"], run.shape["max_bin"]
    floor_bytes = (trees * costs_scan.scan_bytes(groups, max_bin, 1)
                   + (calls - trees)
                   * costs_scan.scan_bytes(groups, max_bin, 2))
    return 100.0 * floor_bytes \
        / peaks.peaks_of(run.device_kind)["hbm_bytes_per_s"] / seconds
