"""The names the program itself puts into a jax.profiler trace, which
`trace_reduce` does not read: the scope path of each device operation and
the host's `lgbm:<span>` annotations.

A device operation's path.  Each event of a device plane's `XLA Ops` line
points at an `XEventMetadata` whose stats carry `tf_op`: the HLO `op_name`,
e.g. `jit(fused)/while/body/jit(segment_histogram)/pallas_call:` or
`jit(fused)/lgbm.gradient/div:`.  A `jax.named_scope("lgbm.x")` in the
program is one component of that path.  `jax.profiler.ProfileData` (jax
0.9.0) shows an event's name and times but nothing of its metadata's
stats, so the file is read a second time here, straight off the protobuf
wire format and only as deep as the metadata tables (the events, which are
nearly all of the file, are skipped unread):

    XSpace.planes = 1
    XPlane.name = 2, .event_metadata = 4, .stat_metadata = 5
        (both maps: an entry's key = 1, value = 2)
    XEventMetadata.name = 2, .stats = 5
    XStatMetadata.id = 1, .name = 2
    XStat.metadata_id = 1, .str_value = 5, .ref_value = 7
        (a reference resolves through stat_metadata[ref].name)

`ProfileData`'s event name is `XEventMetadata.name`, and
`trace_reduce.op_label(name)` is the key of `TraceSummary.ops`; so
`{op_label(name): tf_op}` groups the self times a reduced trace already
holds, with no second reduction.  Not every operation has a `tf_op`: the
`while` itself, asynchronous copies and fusions the compiler made up carry
none.

The host's spans.  `lightgbm_tpu.obs.tracing.span(name)` enters
`jax.profiler.TraceAnnotation("lgbm:" + name)`; like the benchmark's own
`bench:` annotations these are events of the host plane's thread lines, on
the device's clock, and `ProfileData` shows them.
"""
import functools
import glob
import os

from benchmarks.harness.trace_reduce import (
    DEVICE_PLANE, HOST_PLANE, SPAN_PREFIX, _intervals, op_label)

PROGRAM_PREFIX = "lgbm:"        # host annotations of the program
SCOPE_PREFIX = "lgbm."          # named scopes inside the device programs
PATH_STAT = "tf_op"

_VARINT, _FIXED64, _BYTES, _FIXED32 = 0, 1, 2, 5


def _fields(buf):
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields are
    skipped.  A message that ends mid-field raises ValueError."""
    pos, end = 0, len(buf)

    def varint():
        nonlocal pos
        value = shift = 0
        while True:
            byte = buf[pos]
            pos += 1
            value |= (byte & 0x7F) << shift
            shift += 7
            if byte < 0x80:
                return value

    try:
        while pos < end:
            key = varint()
            field, wire = key >> 3, key & 7
            if wire == _VARINT:
                yield field, varint()
            elif wire == _BYTES:
                size = varint()
                if pos + size > end:
                    raise IndexError
                pos += size
                yield field, buf[pos - size:pos]
            elif wire == _FIXED64:
                pos += 8
            elif wire == _FIXED32:
                pos += 4
            else:
                raise ValueError("wire type %d is not one an .xplane.pb "
                                 "holds" % wire)
        if pos > end:
            raise IndexError
    except IndexError:
        raise ValueError("the .xplane.pb is cut short: a field runs past "
                         "the end of its message") from None


def _text(view):
    return bytes(view).decode("utf-8", "replace")


def _map_value(entry):
    for field, value in _fields(entry):
        if field == 2:
            return value
    return b""


def _plane_paths(plane):
    """(plane name, {event name: tf_op}) of one XPlane message; events
    whose metadata has no `tf_op` map to ''."""
    name, events, stat_names = "", [], {}
    for field, value in _fields(plane):
        if field == 2:
            name = _text(value)
        elif field == 4:
            events.append(_map_value(value))
        elif field == 5:
            ident, text = 0, ""
            for f, v in _fields(_map_value(value)):
                if f == 1:
                    ident = v
                elif f == 2:
                    text = _text(v)
            stat_names[ident] = text
    paths = {}
    for event in events:
        event_name, path = "", ""
        for field, value in _fields(event):
            if field == 2:
                event_name = _text(value)
            elif field == 5:
                stat = dict(_fields(value))
                if stat_names.get(stat.get(1)) == PATH_STAT:
                    path = (_text(stat[5]) if 5 in stat
                            else stat_names.get(stat.get(7), ""))
        paths[event_name] = path
    return name, paths


def device_paths(path):
    """{event name: tf_op} over the device planes of one .xplane.pb."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, paths = _plane_paths(plane)
        if DEVICE_PLANE.match(name):
            for event_name, tf_op in paths.items():
                if tf_op or event_name not in out:
                    out[event_name] = tf_op
    return out


@functools.lru_cache(maxsize=4)
def label_paths(path):
    """{trace_reduce.op_label(event name): tf_op}: the key of
    `TraceSummary.ops` to the operation's path.  Where two events share a
    label the path that is not empty stands."""
    out = {}
    for event_name, tf_op in device_paths(path).items():
        label = op_label(event_name)
        if tf_op or label not in out:
            out[label] = tf_op
    return out


def scope_of(tf_op):
    """The innermost `lgbm.` scope of a path: its last component that
    starts so, or None."""
    for part in reversed(tf_op.rstrip(":").split("/")):
        if part.startswith(SCOPE_PREFIX):
            return part
    return None


def newest_xplane(root):
    """The newest .xplane.pb under <root>/<cell>/plugins/profile/*/: the
    one the running process has just written (run.py does not hand the
    readers its path)."""
    found = glob.glob(os.path.join(root, "*", "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def trace_of(run):
    """The trace file behind a run's reduced trace: `run.xplane` where the
    harness (or a test) names it, else the newest under this checkout's
    benchmarks/.cache/trace/."""
    return getattr(run, "xplane", None) or newest_xplane(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".cache", "trace"))


@functools.lru_cache(maxsize=4)
def program_spans(path):
    """[[(start_ns, end_ns, span name)] per host thread] of the `lgbm:`
    annotations that lie inside the traced slice (first start to last end
    of the benchmark's `bench:` spans; the whole trace where it has none),
    prefix taken off.  Threads without one are left out."""
    from jax.profiler import ProfileData
    lines, bench = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            events = _intervals(line)
            bench += [e for e in events if e[2].startswith(SPAN_PREFIX)]
            lines.append([(s, e, n[len(PROGRAM_PREFIX):]) for s, e, n
                          in events if n.startswith(PROGRAM_PREFIX)])
    if bench:
        lo = min(s for s, _, _ in bench)
        hi = max(e for _, e, _ in bench)
        lines = [[e for e in line if e[0] >= lo and e[1] <= hi]
                 for line in lines]
    return [line for line in lines if line]
