"""Per-iteration training event log: one JSONL line per boosting round.

The offline twin of the TIMETAG teardown report (serial_tree_learner.cpp:
15-42): where the reference prints aggregate phase totals once at
destruction, the recorder appends a structured event per iteration —
metric values, per-phase time deltas from the Profiler, tree shape,
sample sizes, cumulative XLA compile/retrace counts, live device state
and comm traffic — to Config.tpu_telemetry_path, so a training run can
be replayed, diffed and regression-tracked after the fact
(tools/telemetry_report.py renders the summary table).

Event stream (schema v1; every line is one JSON object):
- {"event": "start", ...}       run header: params diff, rank/world
- {"event": "iteration", ...}   one per boosting round
- {"event": "tree_stats", ...}  backfill for rounds whose trees were
                                still deferred (pipelined) when their
                                iteration event flushed
- {"event": "summary", ...}     cumulative phase totals + final counts

Buffering contract: the iteration event is held PENDING until the next
round starts (or finalize), because the eval callback delivers this
round's metric values after train_one_iter returns — engine.py runs
callbacks after update().  Deferred-pipeline rounds flush with
trees=null, deferred=true, and finalize() backfills their tree stats
once the caller has drained the pipeline (_sync_model).

The recorder is strictly read-only on the training state: it never
forces a device sync, never drains the pipeline, and the driver wraps
every call in a try/except — telemetry failure degrades to a warning,
never to a failed run.  Models train bitwise-identically with it on or
off (tests/test_obs.py asserts this).
"""
from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Optional

import numpy as np

from ..utils import log
from . import adapters, device, tracing
from .registry import MetricsRegistry

SCHEMA_VERSION = 1


def tree_summary(tree) -> Dict[str, float]:
    """Shape stats for one host tree: leaf count, max depth (edges on
    the longest root->leaf path), total split gain."""
    nl = int(tree.num_leaves)
    if nl <= 1:
        return {"leaves": nl, "depth": 0, "gain": 0.0}
    gain = float(np.sum(tree.split_gain[:nl - 1]))
    depth = 0
    stack = [(0, 1)]          # (internal node, depth of its children)
    while stack:
        node, d = stack.pop()
        for child in (int(tree.left_child[node]),
                      int(tree.right_child[node])):
            if child < 0:     # ~leaf encoding
                depth = max(depth, d)
            else:
                stack.append((child, d + 1))
    return {"leaves": nl, "depth": depth, "gain": round(gain, 6)}


class TrainingRecorder:
    """Appends the event stream for ONE booster to `path`."""

    def __init__(self, path: str, config, registry: Optional[MetricsRegistry] = None):
        from . import default_registry
        self.path = path
        self.config = config
        self.registry = registry if registry is not None else default_registry()
        self.sample_device_stats = bool(
            getattr(config, "tpu_telemetry_device_stats", True))
        self._file = None
        self._pending: Optional[Dict] = None
        self._last_phases: Dict[str, Dict[str, float]] = {}
        self._last_spans: Dict[str, Dict[str, float]] = {}
        self._deferred_iters: List[int] = []
        self._closed = False
        self._write_failed = False
        # trend observatory: a per-run series store feeding the RUNHIST
        # artifact (obs/timeseries.py) — phase deltas, eval metrics and
        # a registry sweep per round.  Only built when a RUNHIST was
        # asked for; read-only, so bitwise identity is untouched
        self.runhist_path = str(getattr(config, "tpu_runhist_path", "")
                                or "")
        self.series = None
        self._trend_include = None
        self._trend_window = max(4, int(getattr(config, "tpu_trend_window",
                                                64) or 64))
        if self.runhist_path:
            from .timeseries import SeriesStore
            self.series = SeriesStore(capacity=self._trend_window)
            pats = str(getattr(config, "tpu_trend_metrics", "") or "")
            self._trend_include = [p.strip() for p in pats.split(",")
                                   if p.strip()] or None
        adapters.ensure_device_metrics(self.registry)
        self._m_iters = self.registry.counter(
            "lgbm_train_iterations_total", help="Boosting rounds completed")
        self._m_seconds = self.registry.counter(
            "lgbm_train_seconds_total", help="Wall seconds spent in update()")
        self._m_trees = self.registry.counter(
            "lgbm_train_trees_total", help="Trees added to the ensemble")
        self._write({
            "event": "start", "schema": SCHEMA_VERSION,
            "boosting": getattr(config, "boosting", ""),
            "objective": getattr(config, "objective", ""),
            "num_leaves": getattr(config, "num_leaves", 0),
            "learning_rate": getattr(config, "learning_rate", 0.0),
            "rank": max(int(getattr(config, "machine_rank", -1)), 0),
            "world": max(int(getattr(config, "num_machines", 1)), 1),
        })

    # -- event construction -------------------------------------------- #
    def on_iteration(self, gbdt, iteration: int, wall_s: float,
                     finished: bool) -> None:
        """Called by the driver after every train_one_iter; `iteration`
        is the round index BEFORE the iter counter moved."""
        self._flush_pending()
        k = max(gbdt.num_tree_per_iteration, 1)
        slot = gbdt.models[iteration * k:(iteration + 1) * k]
        deferred = any(t is None for t in slot)
        trees = (None if deferred
                 else [tree_summary(t) for t in slot])
        if deferred:
            self._deferred_iters.append(iteration)
        event: Dict = {
            "event": "iteration",
            "iter": iteration,
            "wall_ms": round(wall_s * 1e3, 3),
            "finished": bool(finished),
            "deferred": deferred,
            "trees": trees,
            "metrics": {},
            "phases": self._phase_deltas(gbdt.profiler),
            "sample": self._sample_stats(gbdt),
            "compile": device.compile_counts(),
        }
        spans = self._span_deltas()
        if spans is not None:
            event["spans"] = spans
        if self.sample_device_stats:
            event["device"] = device.device_stats()
        comm = adapters.comm_totals(self.registry)
        if comm is not None:
            event["comm"] = comm
        self._m_iters.inc()
        self._m_seconds.inc(wall_s)
        if not finished:
            self._m_trees.inc(len(slot))
        if self.series is not None:
            from .timeseries import PHASE_PREFIX
            tick = iteration + 1
            self.series.observe("train/wall_ms", tick, event["wall_ms"])
            for name, entry in event["phases"].items():
                self.series.observe(PHASE_PREFIX + name, tick,
                                    entry["ms"])
            self.series.sample_registry(self.registry, tick,
                                        include=self._trend_include)
        self._pending = event

    def record_eval(self, iteration: int, results) -> None:
        """Merge (dataset, metric, value, ...) tuples from the engine's
        eval pass into the pending event for `iteration`."""
        if self._pending is None or self._pending.get("iter") != iteration:
            return
        metrics = self._pending["metrics"]
        for v in results or ():
            metrics.setdefault(str(v[0]), {})[str(v[1])] = float(v[2])
            if self.series is not None:
                self.series.observe("eval/%s/%s" % (v[0], v[1]),
                                    int(iteration) + 1, float(v[2]))

    def record_checkpoint(self, round_idx: int, path: str,
                          wall_s: float) -> None:
        """One event per checkpoint written (resilience.CheckpointManager
        calls this after the atomic rename lands)."""
        if self._closed:
            return
        self._flush_pending()
        self._write({"event": "checkpoint", "round": int(round_idx),
                     "path": str(path),
                     "wall_ms": round(wall_s * 1e3, 3)})

    def finalize(self, gbdt) -> None:
        """Flush the last pending event, backfill tree stats for rounds
        that were deferred (the caller must have drained the pipeline
        first — GBDT.finish_telemetry does), write the summary, close."""
        if self._closed:
            return
        self._flush_pending()
        k = max(gbdt.num_tree_per_iteration, 1)
        for it in self._deferred_iters:
            slot = [t for t in gbdt.models[it * k:(it + 1) * k]
                    if t is not None]
            self._write({"event": "tree_stats", "iter": it,
                         "trees": [tree_summary(t) for t in slot]})
        self._deferred_iters = []
        summary: Dict = {
            "event": "summary",
            "iterations": int(gbdt.iter),
            "num_trees": len(gbdt.models),
            "phases": gbdt.profiler.snapshot(),
            "compile": device.compile_counts(),
        }
        comm = adapters.comm_totals(self.registry)
        if comm is not None:
            summary["comm"] = comm
        self._write(summary)
        if self.series is not None and self.runhist_path:
            from .timeseries import write_runhist
            write_runhist(self.runhist_path, {
                "schema": SCHEMA_VERSION,
                "kind": "train",
                "iterations": int(gbdt.iter),
                "num_trees": len(gbdt.models),
                "objective": str(getattr(self.config, "objective", "")),
                "boosting": str(getattr(self.config, "boosting", "")),
                "rank": max(int(getattr(self.config, "machine_rank", -1)),
                            0),
                "world": max(int(getattr(self.config, "num_machines", 1)),
                             1),
            }, self.series, window=self._trend_window)
        self._closed = True
        if self._file is not None:
            try:
                # durability: flush + fsync before close so a crash right
                # after training still leaves every event on disk
                self._file.flush()
                os.fsync(self._file.fileno())
            except Exception as exc:  # noqa: BLE001 — telemetry never raises
                log.warning("telemetry: fsync of %s failed: %s",
                            self.path, exc)
            try:
                self._file.close()
            except Exception as exc:  # noqa: BLE001
                log.debug("telemetry: close of %s failed: %s",
                          self.path, exc)
            self._file = None
        log.debug("telemetry: event log written to %s", self.path)

    # -- internals ------------------------------------------------------ #
    def _phase_deltas(self, profiler) -> Dict[str, Dict[str, float]]:
        snap = profiler.snapshot()
        out: Dict[str, Dict[str, float]] = {}
        for name, cur in snap.items():
            prev = self._last_phases.get(name, {"total_s": 0.0, "calls": 0})
            d_total = cur["total_s"] - prev["total_s"]
            d_calls = cur["calls"] - prev["calls"]
            if d_calls > 0 or d_total > 1e-9:
                out[name] = {"ms": round(d_total * 1e3, 3), "calls": d_calls}
                self.registry.counter(
                    "lgbm_train_phase_seconds_total",
                    help="Per-phase training seconds",
                    phase=name).inc(d_total)
        self._last_phases = snap
        return out

    def _sample_stats(self, gbdt) -> Dict:
        out: Dict = {"rows": int(gbdt.num_data)}
        bag = getattr(gbdt, "_bag_count", None)
        out["bagging_rows"] = int(bag) if bag is not None else None
        goss = getattr(gbdt, "_goss_counts", None)
        if goss is not None:
            out["goss_top"], out["goss_other"] = int(goss[0]), int(goss[1])
        return out

    def _span_deltas(self) -> Optional[Dict[str, Dict[str, float]]]:
        """Per-round span summary: the tracer's cumulative per-kind
        rollup diffed against last round's.  None when tracing is off."""
        tracer = tracing.get_tracer()
        if not tracer.enabled:
            return None
        snap = tracer.kind_snapshot()
        out: Dict[str, Dict[str, float]] = {}
        for kind, cur in snap.items():
            prev = self._last_spans.get(kind, {"ms": 0.0, "count": 0})
            d_count = cur["count"] - prev["count"]
            if d_count > 0:
                out[kind] = {"ms": round(cur["ms"] - prev["ms"], 3),
                             "count": d_count}
        self._last_spans = snap
        return out

    def _flush_pending(self) -> None:
        if self._pending is not None:
            event, self._pending = self._pending, None
            self._write(event)

    def _write(self, event: Dict) -> None:
        """Append one event line.  A failing write (disk full, path
        yanked) degrades to ONE warning and stops the stream — prior
        lines stay intact, training never sees the exception."""
        if self._closed or self._write_failed or not self.path:
            return
        try:
            if self._file is None:
                self._file = open(self.path, "a")
            self._file.write(json.dumps(event, default=_json_default,
                                        separators=(",", ":")) + "\n")
            self._file.flush()
        except Exception as exc:  # noqa: BLE001 — telemetry never raises
            self._write_failed = True
            log.warning("telemetry: write to %s failed (%s); event "
                        "recording stopped, prior events intact",
                        self.path, exc)
            if self._file is not None:
                try:
                    self._file.close()
                except Exception as exc:  # noqa: BLE001
                    log.debug("telemetry: close after failed write "
                              "also failed: %s", exc)
                self._file = None


def _json_default(o):
    if hasattr(o, "item") and not hasattr(o, "__len__"):
        return o.item()
    if hasattr(o, "tolist"):
        return o.tolist()
    return str(o)


def elastic_event(config, what: str, **fields) -> None:
    """Append one elastic-lifecycle event ({"event": "elastic",
    "what": "reform"|"complete", ...}) to Config.tpu_telemetry_path.

    The supervisor lives OUTSIDE any single booster's TrainingRecorder
    (a world re-formation spans two boosters), so this appends directly
    — same file, same one-line-per-event JSONL contract, best-effort
    like every other telemetry write."""
    path = getattr(config, "tpu_telemetry_path", "")
    if not path:
        return
    # wall-clock stamp: elastic events come from SEVERAL processes
    # appending to one file, so ordering/latency questions (petition ->
    # epoch -> wake, asserted by the chaos drills) need a shared clock
    event = {"event": "elastic", "what": str(what),
             "ts": round(time.time(), 6)}
    event.update(fields)
    try:
        with open(path, "a") as f:
            f.write(json.dumps(event, default=_json_default,
                               separators=(",", ":")) + "\n")
    except Exception as exc:  # noqa: BLE001 — telemetry never raises
        log.warning("telemetry: elastic event write to %s failed: %s",
                    path, exc)


def supervisor_event(config, what: str, **fields) -> None:
    """Append one continuous-learning event ({"event": "supervisor",
    "what": "refit"|"shadow"|"promote"|"rollback"|"reject"|"resume",
    ...}) to Config.tpu_telemetry_path.  The supervisor spans boosters
    (live + candidate) exactly like the elastic lifecycle, so it appends
    directly — same JSONL contract, best-effort; the chaos drills and
    bench grep these lines for the promote/rollback observables."""
    path = getattr(config, "tpu_telemetry_path", "")
    if not path:
        return
    event = {"event": "supervisor", "what": str(what)}
    event.update(fields)
    try:
        with open(path, "a") as f:
            f.write(json.dumps(event, default=_json_default,
                               separators=(",", ":")) + "\n")
    except Exception as exc:  # noqa: BLE001 — telemetry never raises
        log.warning("telemetry: supervisor event write to %s failed: %s",
                    path, exc)


def comm_backend_event(config, backend: str, **fields) -> None:
    """Append one backend-selection event ({"event": "comm_backend",
    "backend": "mesh"|"socket"|"none", "requested": ...}) to
    Config.tpu_telemetry_path.  Emitted by make_collective each time a
    booster resolves tpu_comm_backend, so chaos drills (and operators)
    can assert which path training actually took — the mesh_unavailable
    drill greps for exactly this line."""
    path = getattr(config, "tpu_telemetry_path", "")
    if not path:
        return
    event = {"event": "comm_backend", "backend": str(backend)}
    event.update(fields)
    try:
        with open(path, "a") as f:
            f.write(json.dumps(event, default=_json_default,
                               separators=(",", ":")) + "\n")
    except Exception as exc:  # noqa: BLE001 — telemetry never raises
        log.warning("telemetry: comm_backend event write to %s failed: %s",
                    path, exc)


def cluster_event(config, **fields) -> None:
    """Append one federated-telemetry aggregate ({"event": "cluster",
    "round": ..., "hosts": [...]}) to Config.tpu_telemetry_path.  The
    federation hub aggregates EVERY rank's digest, so like the elastic
    and fleet events it appends directly rather than through one
    booster's TrainingRecorder — same JSONL contract, best-effort;
    tools/round_report.py and tools/telemetry_report.py render these."""
    path = getattr(config, "tpu_telemetry_path", "")
    if not path:
        return
    event = {"event": "cluster"}
    event.update(fields)
    try:
        with open(path, "a") as f:
            f.write(json.dumps(event, default=_json_default,
                               separators=(",", ":")) + "\n")
    except Exception as exc:  # noqa: BLE001 — telemetry never raises
        log.warning("telemetry: cluster event write to %s failed: %s",
                    path, exc)


def round_ledger_event(config, **fields) -> None:
    """Append one critical-path ledger line ({"event": "round_ledger",
    "round": ..., "critical_host": ..., ...}, see
    obs/critical_path.build_ledger) to Config.tpu_telemetry_path —
    same JSONL contract, best-effort."""
    path = getattr(config, "tpu_telemetry_path", "")
    if not path:
        return
    event = {"event": "round_ledger"}
    event.update(fields)
    try:
        with open(path, "a") as f:
            f.write(json.dumps(event, default=_json_default,
                               separators=(",", ":")) + "\n")
    except Exception as exc:  # noqa: BLE001 — telemetry never raises
        log.warning("telemetry: round_ledger event write to %s failed: %s",
                    path, exc)


def alert_event(config, **fields) -> None:
    """Append one alert transition ({"event": "alert", "rule": ...,
    "state": "firing"|"cleared", ...}) to Config.tpu_telemetry_path —
    same JSONL contract, best-effort; the slow_host chaos drill greps
    these lines for the fire-then-clear observable."""
    path = getattr(config, "tpu_telemetry_path", "")
    if not path:
        return
    event = {"event": "alert"}
    event.update(fields)
    try:
        with open(path, "a") as f:
            f.write(json.dumps(event, default=_json_default,
                               separators=(",", ":")) + "\n")
    except Exception as exc:  # noqa: BLE001 — telemetry never raises
        log.warning("telemetry: alert event write to %s failed: %s",
                    path, exc)


def policy_event(config, **fields) -> None:
    """Append one control-plane decision ({"event": "policy_action",
    "rule": ..., "action": ..., "status": "ok"|"dry_run"|..., "round":
    ..., "args": {...}}) to Config.tpu_telemetry_path.  The policy
    engine runs on the federation hub and its decisions span hosts, so
    like the cluster/alert events it appends directly — same JSONL
    contract, best-effort; the policy_loop chaos drill and the report
    tools grep these lines to audit each demote/expand next to the
    alert that caused it."""
    path = getattr(config, "tpu_telemetry_path", "")
    if not path:
        return
    event = {"event": "policy_action"}
    event.update(fields)
    try:
        with open(path, "a") as f:
            f.write(json.dumps(event, default=_json_default,
                               separators=(",", ":")) + "\n")
    except Exception as exc:  # noqa: BLE001 — telemetry never raises
        log.warning("telemetry: policy event write to %s failed: %s",
                    path, exc)


def sync_event(config, **fields) -> None:
    """Append one runtime-sync-sentinel observation ({"event":
    "sync_event", "kind": "item"|"__float__"|..., "site": "file:line
    (func)", ...}) to Config.tpu_telemetry_path.  The sentinel
    (obs/scaling.SyncSentinel) fires from INSIDE a hooked jax array
    conversion — routing through one booster's TrainingRecorder from
    there would re-enter its buffering, so like the elastic/fleet events
    it appends directly — same JSONL contract, best-effort; the tests
    grep these lines."""
    path = getattr(config, "tpu_telemetry_path", "")
    if not path:
        return
    event = {"event": "sync_event"}
    event.update(fields)
    try:
        with open(path, "a") as f:
            f.write(json.dumps(event, default=_json_default,
                               separators=(",", ":")) + "\n")
    except Exception as exc:  # noqa: BLE001 — telemetry never raises
        log.warning("telemetry: sync event write to %s failed: %s",
                    path, exc)


def fleet_event(config, what: str, **fields) -> None:
    """Append one fleet-residency event ({"event": "fleet", "what":
    "admit"|"spill"|"promote"|"demote"|"degrade"|"spill_corrupt"|
    "oversize"|"release", "model": ..., ...}) to
    Config.tpu_telemetry_path.  The residency manager spans every tenant
    of a serving process (a spill is caused by one model and suffered by
    another), so it appends directly like the elastic/supervisor events
    — same JSONL contract, best-effort; the tenant_storm chaos drill
    greps these lines for the spill/promote/degrade observables."""
    path = getattr(config, "tpu_telemetry_path", "")
    if not path:
        return
    event = {"event": "fleet", "what": str(what)}
    event.update(fields)
    try:
        with open(path, "a") as f:
            f.write(json.dumps(event, default=_json_default,
                               separators=(",", ":")) + "\n")
    except Exception as exc:  # noqa: BLE001 — telemetry never raises
        log.warning("telemetry: fleet event write to %s failed: %s",
                    path, exc)
