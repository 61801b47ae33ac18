"""Per-phase timing — the TIMETAG analogue.

The reference accumulates per-phase std::chrono durations in the tree
learner and prints them at destruction (serial_tree_learner.cpp:15-42)
plus per-iteration wall clock in GBDT::Train (gbdt.cpp:251-254).  On TPU
the compute phases live inside ONE compiled lax.while_loop, which the
host's clock cannot see into; so time is named in two places:

- this module: host-side phase accumulators around every dispatch the
  driver makes (gradients / grow / drain / score / eval), with an
  optional per-phase device sync so the numbers mean device time and
  not dispatch time.  Enabled via Config.tpu_profile; report printed at
  booster teardown (GBDT.__del__) or on demand via profile_report().
  Every phase is also a span of obs/tracing.py (``lgbm:<phase>`` in a
  jax.profiler trace), whether the accumulators are on or not.
- inside the device programs, ``jax.named_scope("lgbm.<purpose>")``
  around the gradient, the quantisation, each part of the growth loop
  and the score update: in a jax.profiler trace every device operation
  carries its scope in its ``tf_op`` path, so the in-loop attribution
  comes from the trace of the real iteration (docs/Tracing.md lists the
  names; ``benchmarks/readers/trace_scope.py`` sums them).

jax.profiler traces: set Config.tpu_profile_trace_dir to wrap training
in start_trace/stop_trace; the ``.xplane.pb`` holds both kinds of name.
"""
from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Dict, Optional

from . import log
from ..obs import tracing


class Profiler:
    """Named wall-clock accumulators with optional device sync.

    sync_fn, when provided, is called at phase exit before the clock
    stops (a scalar device fetch), so asynchronously dispatched work is
    charged to the phase that launched it.  Without it, phases measure
    dispatch time only — still useful for host-overhead attribution.

    Accumulation is lock-guarded: the serving request path updates one
    shared Profiler from many HTTP worker threads.
    """

    def __init__(self, enabled: bool = False, sync_fn=None):
        self.enabled = enabled
        self.sync_fn = sync_fn
        self.totals: Dict[str, float] = {}
        self.counts: Dict[str, int] = {}
        self.mins: Dict[str, float] = {}
        self.maxs: Dict[str, float] = {}
        self._lock = threading.Lock()
        self._t0 = time.perf_counter()

    @contextmanager
    def phase(self, name: str):
        # every phase site is a span site (obs/tracing.span): an
        # lgbm:<name> annotation in a jax.profiler trace, and a recorded
        # span when tpu_trace_path arms the tracer, with the accumulators
        # on or off.  The span closes AFTER sync_fn, so it covers device
        # time like the clock.  The span is yielded for `set_metadata`.
        with tracing.span(name, "phase") as span:
            if not self.enabled:
                yield span
                return
            start = time.perf_counter()
            try:
                yield span
            finally:
                if self.sync_fn is not None:
                    try:
                        self.sync_fn()
                    except Exception as exc:  # noqa: BLE001 — must not kill train
                        log.debug("profiler sync failed: %s", exc)
                dt = time.perf_counter() - start
                with self._lock:
                    self.totals[name] = self.totals.get(name, 0.0) + dt
                    self.counts[name] = self.counts.get(name, 0) + 1
                    if dt < self.mins.get(name, float("inf")):
                        self.mins[name] = dt
                    if dt > self.maxs.get(name, float("-inf")):
                        self.maxs[name] = dt

    def reset(self) -> None:
        """Zero every accumulator and restart the wall clock — serving
        /stats and long-running boosters can re-baseline instead of
        accumulating unboundedly stale totals."""
        with self._lock:
            self.totals.clear()
            self.counts.clear()
            self.mins.clear()
            self.maxs.clear()
            self._t0 = time.perf_counter()

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        """Machine-readable view of the accumulators (the /stats wire
        format of the serving subsystem): {phase: {total_s, calls,
        ms_per_call, min_ms, max_ms}}."""
        with self._lock:
            return {
                name: {
                    "total_s": round(total, 6),
                    "calls": self.counts[name],
                    "ms_per_call": round(
                        1e3 * total / max(self.counts[name], 1), 3),
                    "min_ms": round(1e3 * self.mins[name], 3),
                    "max_ms": round(1e3 * self.maxs[name], 3),
                }
                for name, total in self.totals.items()
            }

    def report(self, header: str = "profile") -> Optional[str]:
        if not self.enabled or not self.totals:
            return None
        wall = time.perf_counter() - self._t0
        tracked = sum(self.totals.values())
        lines = ["[%s] wall %.3fs, tracked %.3fs" % (header, wall, tracked)]
        for name, total in sorted(self.totals.items(), key=lambda kv: -kv[1]):
            c = self.counts[name]
            lines.append("  %-24s %8.3fs  (%6d calls, %7.2f ms/call)"
                         % (name, total, c, 1e3 * total / max(c, 1)))
        text = "\n".join(lines)
        log.info(text)
        return text


class TraceSession:
    """jax.profiler trace wrapper keyed off Config.tpu_profile_trace_dir."""

    def __init__(self, trace_dir: Optional[str]):
        self.trace_dir = trace_dir or None
        self._live = False

    def start(self):
        if not self.trace_dir or self._live:
            return
        import jax
        try:
            jax.profiler.start_trace(self.trace_dir)
        except RuntimeError as exc:
            # another profiler session is already live (e.g. two boosters
            # sharing one process) — don't claim ownership of it, and
            # don't let a double start_trace kill training
            log.warning("[profile] start_trace skipped: %s", exc)
            return
        self._live = True

    def stop(self):
        """Idempotent; callers run this in a `finally` (engine.train /
        GBDT.finish_telemetry) so a raising training loop cannot leak a
        live profiler session."""
        if not self._live:
            return
        self._live = False
        import jax
        try:
            jax.profiler.stop_trace()
        except Exception as exc:  # noqa: BLE001 — teardown must not raise
            log.warning("[profile] stop_trace failed: %s", exc)
            return
        log.info("[profile] jax trace written to %s", self.trace_dir)
