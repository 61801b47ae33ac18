"""A kernel's single calls inside a loop, from the trace file: what
`trace_reduce` sums away.

Inside the traced slice (the benchmark's `bench:` spans), on each chip's
`XLA Ops` line, the events whose label matches a pattern, grouped by the
innermost `while` that encloses them, groups and calls in time order.  The
growth loop of `ops/grow_partition.py` is one `while` a tree, and its step
i makes the i-th `partition_segment` and the i-th `segment_histogram` call
of that execution, so a group is a tree and a call's place in it is the
node it made.  An event no `while` encloses (a float32 root histogram
under `lgbm.root`) is no in-loop call and is left out.  The same walk as
readers/roofline_partition_root.py, which keeps each tree's first call
only and which no PR edits.
"""
import functools
import re

from benchmarks.harness import trace_reduce


@functools.lru_cache(maxsize=4)
def in_loop_calls(path, pattern):
    """[[[seconds of a call, ...] per execution of the enclosing loop] per
    chip that ran a matching event]; [] where the trace holds no
    benchmark span.  Kept per (file, pattern): seven metrics read two
    walks."""
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
        loops = [(s, e) for s, e, label in inside
                 if label.startswith("while")]
        groups = {}
        for start, end, _ in calls:
            around = [(e - s, s) for s, e in loops
                      if s <= start and end <= e]
            if around:
                groups.setdefault(min(around)[1], []).append(
                    (end - start) / 1e9)
        out.append([groups[loop] for loop in sorted(groups)])
    return out
