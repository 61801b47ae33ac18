"""Share of its HBM roofline the once-per-tree fused root pass reaches:
the bytes the pass has to move (harness/costs.py) over the chip's
published bandwidth (harness/peaks.py), over its time in the device
trace.  Bound by bytes, not FLOPs: 3 accumulates per (row, feature) is far
under the chip's arithmetic.  args {"pattern": regex of the kernel}."""
from benchmarks.harness import costs, peaks


def read(run, args):
    if run.trace is None:
        return None
    seconds, calls = run.trace.family(args["pattern"])
    if not calls:
        return None
    floor_s = calls * costs.fused_root_bytes(
        run.shape["rows"], run.shape["features"], run.shape["max_bin"]) \
        / peaks.peaks_of(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * floor_s / seconds
