"""Distributed span tracing: Chrome trace-event JSON with cross-rank ids.

The timeline half of the observability layer.  The registry (PR 2)
answers "how much, in total"; the recorder answers "what happened each
round"; neither can answer "WHY was round 137 150 ms slower" — that
needs a timeline of nested spans: dispatch gaps between host phases,
an XLA retrace stalling the loop, one rank's allgather leg waiting on a
straggler.  The reference's TIMETAG accumulators
(serial_tree_learner.cpp:15-42) are aggregate-only; this module is the
TPU-native upgrade: structured spans with monotonic clocks, emitted as
Chrome trace-event JSON loadable in Perfetto / ``chrome://tracing``.

One span path.  ``span(name)`` is the program's one span site
(``utils.profiling.Profiler.phase`` gets its span here too).  Armed or
not, a span enters ``jax.profiler.TraceAnnotation("lgbm:" + name)``: when
a ``jax.profiler`` trace is being taken (``tpu_profile_trace_dir``, the
benchmark's traced slice) the span is an event of the host plane of the
``.xplane.pb``, on the same clock as the device's operations; when none
is, the annotation does nothing.  When the tracer is armed
(``tpu_trace_path``) the same span is also recorded here and written as
Chrome JSON.  docs/Tracing.md lists the span names.

Design contract (mirrors the recorder's):

- NOTHING OF THE TRACER WHEN DISABLED: every public helper checks one
  attribute; a disabled ``span`` allocates nothing in the tracer, takes
  no lock and reads no clock.  What is left is the one
  ``TraceAnnotation`` (enter + exit measured at 0.4 us with no profiler
  session, against 0.2 us for a ``nullcontext``).  Training output is
  bitwise-identical with tracing on or off (tests/test_tracing.py
  asserts this, same guarantee as telemetry).
- THREAD-SAFE: spans nest per thread (thread-local stacks); the event
  buffer is lock-guarded because serving records from many HTTP worker
  threads and the XLA compile listener fires from whatever thread
  compiles.
- MONOTONIC: timestamps come from ``time.perf_counter_ns`` so NTP steps
  can't fold a span negative; the wall-clock epoch of ts=0 is stored in
  the file metadata so tools/trace_merge.py can align ranks (refined by
  the SocketComm handshake clock-offset estimate).
- BOUNDED: the in-memory buffer caps at ``tpu_trace_max_events``;
  overflow increments a drop counter (reported in metadata) instead of
  growing without bound.

Cross-rank correlation: every SocketComm frame carries (trace-id,
span-id) in its header and every collective op opens a span tagged with
a cluster-wide collective id (comm session + sequence number), so
``tools/trace_merge.py`` can fuse per-rank files into ONE timeline in
which an allgather's send/wait/recv legs line up across the world.

File format: ``{"traceEvents": [...], "metadata": {...}}`` — the JSON
object form of the Chrome trace-event spec.  Span durations also feed
``lgbm_trace_span_ms{kind=...}`` histograms in the default registry, so
/metrics carries p50/p99 per span kind without parsing the trace file.
"""
from __future__ import annotations

import json
import threading
import time
import uuid
from typing import Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

from ..utils import log

SCHEMA_VERSION = 1

# bucket bounds for the per-kind span-duration histograms (ms): spans
# range from sub-ms host phases to multi-second compiles
_SPAN_MS_BOUNDS = (0.05, 0.2, 1.0, 5.0, 20.0, 100.0, 500.0, 2000.0, 10000.0)

#: what every span is called in a jax.profiler trace: "lgbm:" + its name
ANNOTATION_PREFIX = "lgbm:"


def _scalars(args: Dict) -> Dict:
    return {k: v for k, v in args.items()
            if isinstance(v, (bool, int, float, str))}


def _annotation(name: str, args: Optional[Dict]) -> TraceAnnotation:
    """The profiler's annotation of a span; its scalar arguments ride it
    into the trace file: those given at the span's start
    (`lgbm:engine_plan`) and those `set_metadata` adds while it is open
    (`lgbm:drain_inflight`)."""
    if not args:
        return TraceAnnotation(ANNOTATION_PREFIX + name)
    return TraceAnnotation(ANNOTATION_PREFIX + name, **_scalars(args))


class _Span:
    """One live span: a reusable context manager pushed on the calling
    thread's stack at enter, turned into a complete ('X') event at exit."""

    __slots__ = ("tracer", "name", "cat", "args", "span_id", "parent_id",
                 "t0_us", "tid", "annotation")

    def __init__(self, tracer: "SpanTracer", name: str, cat: str,
                 args: Optional[Dict]):
        self.tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self.annotation = _annotation(name, args)

    def __enter__(self) -> "_Span":
        self.annotation.__enter__()
        tr = self.tracer
        stack = tr._stack()
        self.parent_id = stack[-1].span_id if stack else 0
        self.span_id = tr._next_span_id()
        self.tid = tr._tid()
        self.t0_us = tr._now_us()
        stack.append(self)
        return self

    def set_metadata(self, **kv) -> None:
        """Attach args discovered mid-span (the trees a drain fetched);
        named as `TraceAnnotation.set_metadata`, which is what `span()`
        hands out when the tracer is off, and passed on to it."""
        if self.args is None:
            self.args = {}
        self.args.update(kv)
        self.annotation.set_metadata(**_scalars(kv))

    def __exit__(self, exc_type, exc, tb) -> None:
        tr = self.tracer
        stack = tr._stack()
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:            # mismatched exits: drop to self
            del stack[stack.index(self):]
        dur = tr._now_us() - self.t0_us
        args = dict(self.args) if self.args else {}
        args["span_id"] = self.span_id
        if self.parent_id:
            args["parent_id"] = self.parent_id
        if exc_type is not None:
            args["error"] = exc_type.__name__
        tr._emit({"name": self.name, "cat": self.cat or "span", "ph": "X",
                  "ts": self.t0_us, "dur": dur, "pid": tr.pid,
                  "tid": self.tid, "args": args})
        tr._observe_kind(self.cat or self.name, dur / 1e3)
        self.annotation.__exit__(exc_type, exc, tb)


class SpanTracer:
    """Process-wide span recorder; disabled (and free) until configured."""

    def __init__(self):
        self.enabled = False
        self.path: Optional[str] = None
        self.pid = 0                       # Chrome pid slot: the rank
        self.world = 1
        self.max_events = 500_000
        self.trace_id = ""                 # 32-hex run id, shared via comm
        self._events: List[Dict] = []
        self._dropped = 0
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._span_seq = 0
        self._ts0_us = 0
        self._wall_epoch_us = 0
        self._clock_offset_us = 0.0        # estimated local-wall - hub-wall
        self._metadata: Dict = {}
        self._tid_map: Dict[int, int] = {}
        self._thread_names: Dict[int, str] = {}
        self._hist_cache: Dict[str, object] = {}

    # -- configuration -------------------------------------------------- #
    def configure(self, path: str, rank: int = 0, world: int = 1,
                  max_events: int = 500_000) -> "SpanTracer":
        """Arm the tracer.  Reconfiguring with a new path starts a fresh
        buffer (one trace file per run); re-arming the same path mid-run
        is a no-op so serving + training in one process share the buffer."""
        resolved = "%s.rank%d" % (path, rank) if world > 1 else path
        with self._lock:
            if self.enabled and self.path == resolved:
                return self
            self._events = []
            self._dropped = 0
            self._span_seq = 0
            self._tid_map = {}
            self._thread_names = {}
            self.path = resolved
            self.pid = max(int(rank), 0)
            self.world = max(int(world), 1)
            self.max_events = max(int(max_events), 1024)
            self.trace_id = uuid.uuid4().hex
            now_ns = time.perf_counter_ns()
            self._ts0_us = now_ns // 1000
            self._wall_epoch_us = time.time_ns() // 1000 - (
                time.perf_counter_ns() // 1000 - self._ts0_us)
            self.enabled = True
        return self

    def disable(self) -> None:
        with self._lock:
            self.enabled = False

    def set_metadata(self, **kv) -> None:
        """Attach run facts to the file metadata (rank coordinates, comm
        session, clock offset).  Cheap and safe when disabled."""
        with self._lock:
            self._metadata.update(kv)

    def set_clock_offset(self, offset_s: float, rtt_s: float = 0.0) -> None:
        """Record the handshake-estimated wall-clock offset of THIS rank
        relative to the comm hub (hub clock minus local clock, seconds);
        trace_merge ADDS it to local wall timestamps to express every
        rank's spans in hub time."""
        offset_us = float(offset_s) * 1e6
        with self._lock:
            self._clock_offset_us = offset_us
        self.set_metadata(clock_offset_us=round(offset_us, 1),
                          clock_rtt_us=round(float(rtt_s) * 1e6, 1))

    # -- recording ------------------------------------------------------ #
    def span(self, name: str, cat: str = "",
             args: Optional[Dict] = None):
        if not self.enabled:
            return _annotation(name, args)
        return _Span(self, name, cat, args)

    def instant(self, name: str, cat: str = "", **args) -> None:
        if not self.enabled:
            return
        args["span_id"] = self._next_span_id()
        self._emit({"name": name, "cat": cat or "instant", "ph": "i",
                    "ts": self._now_us(), "pid": self.pid,
                    "tid": self._tid(), "s": "t", "args": args})

    def complete(self, name: str, dur_s: float, cat: str = "",
                 **args) -> None:
        """Record a span that ENDED now with a known duration — the shape
        the XLA compile listeners deliver (event + elapsed seconds)."""
        if not self.enabled:
            return
        end = self._now_us()
        dur = max(int(dur_s * 1e6), 0)
        args["span_id"] = self._next_span_id()
        self._emit({"name": name, "cat": cat or "span", "ph": "X",
                    "ts": end - dur, "dur": dur, "pid": self.pid,
                    "tid": self._tid(), "args": args})
        self._observe_kind(cat or name, dur / 1e3)

    def current_context(self) -> Tuple[str, int]:
        """(trace_id, innermost live span id) for wire propagation; a
        disabled tracer or bare thread yields ("", 0)."""
        if not self.enabled:
            return "", 0
        stack = self._stack()
        return self.trace_id, (stack[-1].span_id if stack else 0)

    # -- per-kind rollup (the recorder's per-round span summaries) ------ #
    def kind_snapshot(self) -> Dict[str, Dict[str, float]]:
        """Cumulative {kind: {ms, count}} across every recorded span —
        the recorder diffs consecutive snapshots into per-round summaries."""
        out: Dict[str, Dict[str, float]] = {}
        with self._lock:
            events = list(self._events)
        for e in events:
            if e.get("ph") != "X":
                continue
            kind = e.get("cat") or e.get("name", "")
            agg = out.setdefault(kind, {"ms": 0.0, "count": 0})
            agg["ms"] += e.get("dur", 0) / 1e3
            agg["count"] += 1
        for agg in out.values():
            agg["ms"] = round(agg["ms"], 3)
        return out

    # -- output --------------------------------------------------------- #
    def flush(self) -> Optional[str]:
        """Write the buffered trace to ``path`` (atomic rewrite; call as
        often as you like).  Returns the path written, or None."""
        if self.path is None:
            return None
        with self._lock:
            events = list(self._events)
            meta = dict(self._metadata)
            thread_names = dict(self._thread_names)
            dropped = self._dropped
        for tid, tname in sorted(thread_names.items()):
            events.append({"name": "thread_name", "ph": "M", "pid": self.pid,
                           "tid": tid, "args": {"name": tname}})
        events.append({"name": "process_name", "ph": "M", "pid": self.pid,
                       "tid": 0, "args": {"name": "rank %d" % self.pid}})
        events.append({"name": "process_sort_index", "ph": "M",
                       "pid": self.pid, "tid": 0,
                       "args": {"sort_index": self.pid}})
        meta.update({
            "schema": SCHEMA_VERSION,
            "trace_id": self.trace_id,
            "rank": self.pid,
            "world": self.world,
            "wall_epoch_us": self._wall_epoch_us,
            "dropped_events": dropped,
        })
        meta.setdefault("clock_offset_us", round(self._clock_offset_us, 1))
        try:
            from . import device
            meta["compile_counts"] = device.compile_counts()
        except Exception as exc:  # noqa: BLE001 — metadata only
            log.debug("compile counts unavailable: %s", exc)
        payload = {"traceEvents": events, "displayTimeUnit": "ms",
                   "metadata": meta}
        try:
            from ..io.file_io import atomic_write_text
            atomic_write_text(self.path,
                              json.dumps(payload, separators=(",", ":")))
        except Exception as exc:  # noqa: BLE001 — tracing must not raise
            log.warning("trace: could not write %s: %s", self.path, exc)
            return None
        if dropped:
            log.warning("trace: %d events dropped (tpu_trace_max_events=%d)",
                        dropped, self.max_events)
        return self.path

    def close(self) -> Optional[str]:
        """Flush and disarm; subsequent spans are free no-ops again."""
        path = self.flush()
        with self._lock:
            self.enabled = False
        return path

    # -- internals ------------------------------------------------------ #
    def _now_us(self) -> int:
        return time.perf_counter_ns() // 1000 - self._ts0_us

    def _stack(self) -> List[_Span]:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def _next_span_id(self) -> int:
        with self._lock:
            self._span_seq += 1
            return self._span_seq

    def _tid(self) -> int:
        ident = threading.get_ident()
        tid = self._tid_map.get(ident)
        if tid is None:
            with self._lock:
                tid = self._tid_map.setdefault(ident, len(self._tid_map) + 1)
                self._thread_names[tid] = threading.current_thread().name
        return tid

    def _emit(self, event: Dict) -> None:
        with self._lock:
            if len(self._events) >= self.max_events:
                self._dropped += 1
                return
            self._events.append(event)

    def _observe_kind(self, kind: str, ms: float) -> None:
        hist = self._hist_cache.get(kind)
        if hist is None:
            try:
                from . import default_registry
                hist = default_registry().histogram(
                    "lgbm_trace_span_ms", bounds=_SPAN_MS_BOUNDS,
                    help="Recorded span durations (ms) per span kind",
                    kind=kind)
            except Exception:  # noqa: BLE001 — metrics must not kill a span
                return
            # benign last-wins race: the registry dedupes children by
            # label key, so concurrent builders store the same object
            self._hist_cache[kind] = hist  # tpulint: ok=lock-shared-write
        try:
            hist.observe(ms)
        except Exception as exc:  # noqa: BLE001
            log.debug("span histogram observe failed: %s", exc)


_tracer = SpanTracer()


def get_tracer() -> SpanTracer:
    """The process-wide tracer (disabled until configured)."""
    return _tracer


def configure_from_config(config) -> Optional[SpanTracer]:
    """Arm the process tracer from Config.tpu_trace_path; no-op (None)
    when the param is empty.  Call sites: GBDT construction, serving
    Server construction, the CLI."""
    path = getattr(config, "tpu_trace_path", "")
    if not path:
        return None
    rank = max(int(getattr(config, "machine_rank", -1)), 0)
    world = max(int(getattr(config, "num_machines", 1)), 1)
    return _tracer.configure(
        path, rank=rank, world=world,
        max_events=int(getattr(config, "tpu_trace_max_events", 500_000)))


def span(name: str, cat: str = "", **args):
    """Open a nested span on the current thread: an ``lgbm:<name>``
    annotation in a jax.profiler trace, and a recorded span as well when
    the tracer is armed."""
    return _tracer.span(name, cat, args or None)


def instant(name: str, cat: str = "", **args) -> None:
    t = _tracer
    if t.enabled:
        t.instant(name, cat, **args)


def complete(name: str, dur_s: float, cat: str = "", **args) -> None:
    t = _tracer
    if t.enabled:
        t.complete(name, dur_s, cat, **args)


def current_context() -> Tuple[str, int]:
    return _tracer.current_context()


def flush() -> Optional[str]:
    return _tracer.flush() if _tracer.path else None
