"""Share of its HBM roofline the partition kernel reaches on the one call
per tree whose bytes are known without a ledger of rows per call: a tree's
FIRST split, whose segment is every row of the data set
(harness/costs_partition.py).  Bound by bytes: the kernel's permutation
matmuls are 2 * 256 multiply-adds a row and channel, a few per cent of
the chip's arithmetic at its byte rate.

The reduced trace sums a kernel's events, so this reader goes back to the
trace file for the single events: inside the traced slice (the
benchmark's `bench:` spans), on each chip's `XLA Ops` line, the kernel's
events in time order, a tree's being those one execution of the growth
`while` encloses; where the line shows no `while` around them they are
cut into `traced_units` equal runs (every tree of a window has the same
number of splits, or the run is not `correct`).  The share is the byte
floor of all first calls over their summed time.
args {"pattern": regex of the kernel, over trace_reduce's labels}."""
import re

from benchmarks.harness import costs_partition, peaks, trace_reduce
from benchmarks.harness import xplane_names


def first_calls(path, pattern, trees):
    """Durations in seconds of each tree's first matching kernel event,
    per chip that ran any."""
    from jax.profiler import ProfileData
    rx = re.compile(pattern)
    spans, chips = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == trace_reduce.HOST_PLANE:
            for line in plane.lines:
                spans += [(e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events
                          if e.name.startswith(trace_reduce.SPAN_PREFIX)]
        elif trace_reduce.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    chips.append([(e.start_ns, e.start_ns + e.duration_ns,
                                   trace_reduce.op_label(e.name))
                                  for e in line.events])
    if not spans:
        return []
    lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    out = []
    for events in chips:
        inside = [e for e in events if e[1] > lo and e[0] < hi]
        calls = sorted(e for e in inside if rx.search(e[2]))
        if not calls:
            continue
        loops = sorted((s, e) for s, e, label in inside
                       if label.startswith("while"))
        firsts, seen = [], set()
        for start, end, _ in calls:
            # the innermost loop around the call: the growth loop
            around = [(e - s, s) for s, e in loops if s <= start and end <= e]
            if not around:
                firsts = None
                break
            loop = min(around)[1]
            if loop not in seen:
                seen.add(loop)
                firsts.append((end - start) / 1e9)
        if firsts is None:
            if not trees or len(calls) % trees:
                continue
            step = len(calls) // trees
            firsts = [(e - s) / 1e9 for s, e, _ in calls[::step]]
        out += firsts
    return out


def read(run, args):
    if run.trace is None:
        return None
    path = xplane_names.trace_of(run)
    if not path:
        return None
    firsts = first_calls(path, args["pattern"], run.shape["traced_units"])
    if not firsts or not sum(firsts):
        return None
    floor_s = len(firsts) * costs_partition.partition_root_bytes(
        run.shape["rows"], run.shape["features"]) \
        / peaks.peaks_of(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * floor_s / sum(firsts)
