"""From a jax.profiler trace (.xplane.pb) to what the per-layer metrics
read: device busy and idle time, time per device operation, top-level
program launches, and each idle gap named by the benchmark span the host
was in.  Reads the file with `jax.profiler.ProfileData` and nothing else.

What a TPU trace of this installation holds (looked at by hand, PR 22;
`python -m benchmarks.harness.trace_reduce <file>` prints it for any
trace): one plane per chip, `/device:TPU:<n>`.  Its line `XLA Modules`
has one event per launched program, named `jit_<function>(<id>)`.  Its
line `XLA Ops` has one event per executed HLO instruction, named by the
instruction's whole text (`%partition_segment.13 = (bf16[48,63033344]...)
custom-call(...), custom_call_target="tpu_custom_call", ...`); a `while`
encloses the events of its body in time.  A Pallas kernel is a
`tpu_custom_call` whose instruction carries the name of the jitted Python
function around its `pallas_call` (`partition_segment`,
`segment_histogram`, `fused_refresh_histogram`, `compact_carry`,
`compact_segments`, `_run_scan`, `leaf_histogram`,
`leaf_histogram_quantized` today).  `Async XLA Ops` holds the
asynchronous copies, which overlap the operations and are not counted.
The host's threads are lines of the plane `/host:CPU`; the benchmark's
`bench:<name>` annotations are events of the line `python3`, on the same
clock as the device's.

An operation is labelled `<instruction> <result type>`, with ` mosaic`
appended for a Pallas kernel, e.g. `partition_segment.13
bf16[48,63033344] mosaic` or `fusion.6 u8[10500000]`.  Time is attributed
to the innermost operation (self time), so a `while` that encloses a
thousand kernel calls keeps only its own bookkeeping.  Busy is the union
of the operations' intervals; a gap is a stretch of the window in which
no operation of that chip ran.
"""
import collections
import glob
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench:"
BETWEEN = "between-calls"
MOSAIC = 'custom_call_target="tpu_custom_call"'
_INSTRUCTION = re.compile(r"^%?(\S+) = \(?(\w+\[[\d,]*\])?")


def op_label(text):
    """`<instruction> <result type>[ mosaic]` of an HLO instruction's text
    (the first element's type for a tuple result)."""
    m = _INSTRUCTION.match(text)
    if not m:
        return text[:80]
    label = m.group(1) + (" " + m.group(2) if m.group(2) else "")
    return label + " mosaic" if MOSAIC in text else label


def find_xplane(trace_dir):
    """The one .xplane.pb a `jax.profiler` session left under trace_dir."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def _intervals(line):
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for e in line.events]


def _self_times(events):
    """[(start, end, name, self_ns)] for properly nested intervals: an
    event's self time is its length less that of the events it encloses."""
    out, stack = [], []                  # stack of [start, end, name, self]
    for start, end, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][1] <= start:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][3] -= min(end, stack[-1][1]) - start
        stack.append([start, end, name, end - start])
    while stack:
        out.append(tuple(stack.pop()))
    return out


def _union(events, lo, hi):
    """Merged [start, end] stretches of `events` clipped to [lo, hi]."""
    merged = []
    for start, end, _ in sorted(events):
        start, end = max(start, lo), min(end, hi)
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _span_at(spans, t):
    """Name of the innermost benchmark span that covers time t."""
    best = None
    for start, end, name in spans:
        if start <= t < end and (best is None or start >= best[0]):
            best = (start, name)
    return best[1] if best else BETWEEN


class TraceSummary:
    """window_s, busy_s: seconds, averaged over the chips that ran
    anything.  ops: {operation name: (self seconds, calls)} summed over
    chips.  programs: program launches.  gaps: [(span name, seconds)],
    longest first.  chips: how many device planes ran anything."""

    def __init__(self, window_s, busy_s, ops, programs, gaps, chips):
        self.window_s, self.busy_s = window_s, busy_s
        self.ops, self.programs, self.gaps = ops, programs, gaps
        self.chips = chips

    def family(self, pattern, invert=False):
        """(self seconds, calls) of the operations whose name matches."""
        rx = re.compile(pattern)
        hit = [v for k, v in self.ops.items()
               if bool(rx.search(k)) != bool(invert)]
        return sum(s for s, _ in hit), sum(c for _, c in hit)

    def breakdown(self, top_ops=10, top_gaps=5):
        ops = sorted(((k, s) for k, (s, _) in self.ops.items()),
                     key=lambda kv: -kv[1])[:top_ops]
        return {"device_ops": [[k, s] for k, s in ops],
                "idle_gaps": [[k, s] for k, s in self.gaps[:top_gaps]]}


def reduce(path):
    """TraceSummary of one .xplane.pb, or None when it holds no device
    plane with operations (a CPU trace) or no benchmark span."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    spans, devices = [], []
    for plane in data.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                spans += [(s, e, n[len(SPAN_PREFIX):])
                          for s, e, n in _intervals(line)
                          if n.startswith(SPAN_PREFIX)]
        elif DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE in lines:
                devices.append(([(s, e, op_label(n)) for s, e, n
                                 in _intervals(lines[OPS_LINE])],
                                _intervals(lines[MODULES_LINE])
                                if MODULES_LINE in lines else []))
    if not spans or not devices:
        return None
    lo = min(s for s, _, _ in spans)
    hi = max(e for _, e, _ in spans)
    ops = collections.defaultdict(lambda: [0.0, 0])
    busy_ns, programs, gaps, chips = 0, 0, [], 0
    for op_events, module_events in devices:
        inside = [e for e in op_events if e[1] > lo and e[0] < hi]
        if not inside:
            continue
        chips += 1
        for start, end, name, self_ns in _self_times(inside):
            ops[name][0] += self_ns / 1e9
            ops[name][1] += 1
        programs += sum(1 for s, _, _ in module_events if lo <= s < hi)
        cursor = lo
        for start, end in _union(inside, lo, hi) + [[hi, hi]]:
            if start > cursor:
                gaps.append((_span_at(spans, cursor), (start - cursor) / 1e9))
            busy_ns += end - start
            cursor = max(cursor, end)
    if not chips:
        return None
    gaps.sort(key=lambda gap: -gap[1])
    return TraceSummary((hi - lo) / 1e9, busy_ns / 1e9 / chips,
                        {k: tuple(v) for k, v in ops.items()},
                        programs, gaps, chips)


def describe(path, top=40):
    """Print what a trace holds: planes, lines, and per line the names
    that took most time, with one event's stats — for looking at a trace
    by hand before writing a metric against it."""
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(path).planes:
        print("PLANE %r" % plane.name)
        for line in plane.lines:
            total = collections.defaultdict(lambda: [0.0, 0, None])
            first = last = None
            for e in line.events:
                t = total[e.name]
                t[0] += e.duration_ns
                t[1] += 1
                if t[2] is None:
                    t[2] = {k: str(v)[:120] for k, v in e.stats}
                first = e.start_ns if first is None else min(first, e.start_ns)
                last = max(last or 0, e.start_ns + e.duration_ns)
            n = sum(t[1] for t in total.values())
            print("  LINE %r events=%d names=%d span_ms=%.3f" % (
                line.name, n, len(total),
                ((last or 0) - (first or 0)) / 1e6))
            for name, (ns, count, stats) in sorted(
                    total.items(), key=lambda kv: -kv[1][0])[:top]:
                print("    %10.3f ms %7d x %s  %s" % (ns / 1e6, count,
                                                      name[:100], stats))


if __name__ == "__main__":
    describe(sys.argv[1])
