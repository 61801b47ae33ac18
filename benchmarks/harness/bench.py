"""What one run carries from set-up to its last line: the clocks, the
benchmark's own spans, the compile counters around the window and the
profiler session of a traced run."""
import contextlib
import json
import math
import os
import shutil
import time

import jax

from benchmarks.harness.manifest import BENCH_DIR


class Bench:
    def __init__(self, root, cell, seed, seconds, trace, t_start):
        self.root = root
        self.cell = cell
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.t_start = t_start          # perf_counter at process start
        self.phases = {}                # set-up phase -> seconds
        self.spans = []                 # (name, start, end), perf_counter
        self.window_spans = ()          # those of the measured window
        self.window = None              # (start, end), perf_counter
        self.compiles_in_window = None
        self.compared = {}              # name -> [number, its limit]
        self.trace_dir = None
        self._compiles_at_open = None

    # -- everything but the last line is said here ---------------------- #
    def say(self, what, **fields):
        print("[bench] " + json.dumps(dict(what=what, **fields)), flush=True)

    def hold(self, name, value, limit):
        """Put on record a number `correct` was decided by, beside its
        limit: run.py prints them last on standard error and under
        `compared` in the result's line.  A name held twice (once a tree,
        say) keeps its worst reading; `None` is a number that could not
        be read, which the problem list explains."""
        value = None if value is None else float(value)
        old = self.compared.get(name)
        if old is None or old[0] is None or (
                value is not None and value > old[0]):
            self.compared[name] = [value, float(limit)]

    def compared_record(self):
        """{name: {"value", "limit"}} of what was held, for a line of
        JSON: a number that is not finite (the gain of a split the
        reference forbids is -inf) is null there, and the problem list
        says why."""
        return {name: {"value": value if value is None
                       or math.isfinite(value) else None, "limit": limit}
                for name, (value, limit) in self.compared.items()}

    # -- files the run may write: all under benchmarks/.cache ------------ #
    def cache_path(self, *parts):
        path = os.path.join(self.root, BENCH_DIR, ".cache", *parts)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        return path

    # -- clocks --------------------------------------------------------- #
    @contextlib.contextmanager
    def phase(self, name):
        """Host clock over a part of set-up; parts of one name add up."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.phases[name] = (self.phases.get(name, 0.0)
                                 + time.perf_counter() - t0)

    @contextlib.contextmanager
    def span(self, name):
        """A benchmark span around a call into a layer: host clock here,
        and a `bench:<name>` annotation in the profiler's trace when one
        is being taken, so device gaps can be named by it."""
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench:" + name):
            try:
                yield
            finally:
                self.spans.append((name, t0, time.perf_counter()))

    # -- the measured window --------------------------------------------- #
    def _compiles(self):
        from lightgbm_tpu.obs import device as obs_device
        counts = obs_device.compile_counts()
        # a program loaded from the persistent cache stalls the window as
        # a compilation does, so both count
        return counts["backend_compiles"] + counts["cache_hits"]

    def open_window(self):
        from lightgbm_tpu.obs import device as obs_device
        obs_device.install_compile_listeners()
        self._compiles_at_open = self._compiles()
        self.spans.clear()
        self.window = (time.perf_counter(), None)
        return self.window[0]

    def close_window(self):
        self.window = (self.window[0], time.perf_counter())
        self.window_spans = tuple(self.spans)
        self.compiles_in_window = self._compiles() - self._compiles_at_open
        return self.window[1] - self.window[0]

    @property
    def setup_s(self):
        return self.window[0] - self.t_start

    # -- the traced slice ------------------------------------------------ #
    @contextlib.contextmanager
    def traced(self):
        """A jax.profiler trace of what runs inside; the .xplane.pb lands
        under benchmarks/.cache/trace/<cell>/, in place of the last one."""
        self.trace_dir = os.path.dirname(
            self.cache_path("trace", self.cell.name, "_"))
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0      # the benchmark's spans suffice
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
