"""Scaling forensics: per-round host/device step decomposition, the
runtime sync sentinel, and the efficiency-waterfall math.

Mesh scaling was blocked on attribution, not code: efficiency on the
8-virtual-device CPU mesh at 4096 rows was 0.01-0.035 (a CPU run of
tools/mesh_bench.py — no mesh run on a TPU is on record) and the
suspects are named — per-round host sync, un-donated shard buffers,
psum placement, leader-callback serialization — but nothing in obs/
could say which one dominates.  This module makes the loss explain
itself:

- ``StepDecomposer`` splits every boosting round's wall time into
  attributable legs using ONLY numbers the obs stack already collects
  (profiler phase deltas, comm counters, the hybrid axis' wire-wait
  accumulator) plus one chain probe per window (a dependent scalar
  ``float()`` fetch, the obs/perf timing discipline).  The recorder
  attaches the result as a ``step_decomp``
  section per iteration event, publishes ``lgbm_scaling_*`` gauges and
  (when the tracer is armed) ``scaling/`` spans.

  Legs, per round (all milliseconds):

  ==============  ======================================================
  wall_ms         measured round wall (train_one_iter)
  host_sync_ms    host blocked on device→host fetches: the drain /
                  tree-fetch / metric-fetch profiler phases
  leader_wire_ms  io_callback leader-wire serialization (hybrid axis
                  wire-wait delta, or the socket sync-wait counter)
  psum_ms         analytic ICI cost of the round's mesh collective
                  payload: bytes moved / tpu_scaling_ici_gbps
  dispatch_ms     everything else — Python driver, trace/dispatch and
                  device compute overlapped behind it (the
                  "dispatch gap" the waterfall charges scaling loss to)
  device_est_ms   windowed chain-probe estimate of the device tail
                  still executing when the host finished dispatching
                  (informational; overlaps dispatch_ms by construction)
  ==============  ======================================================

  wall = host_sync + leader_wire + psum + dispatch by construction
  (dispatch is the clamped remainder), which is what lets the waterfall
  legs sum to the measured wall exactly instead of "within noise".

- ``SyncSentinel`` is the dynamic complement to tpulint's static
  ``jit-host-sync`` rule: armed (``tpu_sync_guard=log|fail``) it wraps
  the round in ``jax.transfer_guard_device_to_host("log")`` AND hooks
  the jax array scalar-conversion methods (``item`` / ``tolist`` /
  ``__float__`` / ``__int__`` / ``__bool__`` / ``__index__``) so every
  implicit device→host scalar fetch inside the round becomes a counted,
  stack-attributed ``sync_event`` telemetry event.  The method hooks are
  what makes the sentinel testable on the CPU backend, where jax's
  transfer guard is inert for device→host fetches; on a real TPU
  backend the entered transfer-guard context logs the bulk transfers
  the scalar hooks cannot see.  Known-legitimate syncs (the perf
  probe's single ``float()``) run under the scoped ``exempt()``
  context, not a global opt-out.  ``fail`` mode raises LightGBMError at
  the first un-exempted sync — after recording it.

- ``efficiency_waterfall`` fits per-world mean round legs into the
  ideal → +host-sync → +dispatch-gap → +psum → +leader-wire → measured
  decomposition ``tools/scaling_report.py`` renders and gates on.

Everything here is read-only on training state: models train
bitwise-identically with the full forensics stack on or off
(tests/test_scaling.py pins this for gbdt serial and mesh-w2).
"""
from __future__ import annotations

import threading
import time
import traceback
from typing import Dict, List, Optional

from ..utils import log

# sentinel kinds recorded per hooked conversion method
_WATCHED_METHODS = ("item", "tolist", "__float__", "__int__", "__bool__",
                    "__index__")
# full stack attribution is captured for at most this many events per
# process; past the cap events are still counted (a sync storm must not
# turn the sentinel itself into the bottleneck)
MAX_RECORDED_EVENTS = 100

# profiler phases that ARE host-blocking device→host fetches — the
# host_sync leg is their per-round delta sum (names from models/gbdt.py)
SYNC_PHASES = ("drain_inflight", "tree_fetch", "metric_eval(fetch)")

WATERFALL_LEGS = ("ideal", "host_sync", "dispatch_gap", "psum",
                  "leader_wire", "residual")
LOSS_LEGS = WATERFALL_LEGS[1:]


# --------------------------------------------------------------------- #
# Runtime sync sentinel
# --------------------------------------------------------------------- #
class _SentinelTLS(threading.local):
    """Per-thread watch state: only the thread that entered guard() has
    its conversions counted (worker threads draining telemetry must not
    trip the training thread's sentinel)."""
    def __init__(self):
        self.active = 0        # guard() nesting depth
        self.allow = 0         # exempt() nesting depth
        self.recording = False  # re-entrancy latch for _record itself


_tls = _SentinelTLS()
_install_lock = threading.Lock()
_install_refs = 0
_orig_methods: Dict[str, object] = {}
_active_sentinels: List["SyncSentinel"] = []     # guard() stack (LIFO)
_sync_counts: Dict[str, int] = {}                # kind -> count
_sync_total = 0
_sync_events: List[Dict] = []                    # bounded attribution log


def _array_impl_class():
    """The concrete jax array class whose conversion methods get hooked.
    Plain Python functions on the class in every jax in the container;
    None when the private module moved (sentinel degrades to the
    transfer-guard context only)."""
    try:
        from jax._src.array import ArrayImpl
        return ArrayImpl
    except Exception:  # noqa: BLE001 — private path; absent -> degrade
        return None


def _attribute_site() -> str:
    """Topmost stack frame outside this module and outside jax — the
    user/framework line that forced the sync."""
    try:
        for frame in reversed(traceback.extract_stack()):
            fn = frame.filename.replace("\\", "/")
            if "obs/scaling" in fn or "/jax/" in fn or "/jax/_src" in fn \
                    or "/_src/array" in fn:
                continue
            return "%s:%d (%s)" % (fn.rsplit("/", 1)[-1], frame.lineno,
                                   frame.name)
    except Exception as exc:  # noqa: BLE001 — attribution is best-effort
        log.debug("sync sentinel: site attribution failed: %s", exc)
    return "unknown"


def _record_sync(kind: str, arr) -> None:
    """Count + attribute one un-exempted device→host conversion, then
    (fail mode) raise.  Every telemetry side effect is fenced — the
    sentinel observes training, it must never corrupt it beyond the
    explicit fail-mode raise."""
    global _sync_total
    sentinel = _active_sentinels[-1] if _active_sentinels else None
    event: Dict = {"kind": kind}
    _tls.recording = True
    try:
        with _install_lock:
            _sync_total += 1
            _sync_counts[kind] = _sync_counts.get(kind, 0) + 1
            want_detail = len(_sync_events) < MAX_RECORDED_EVENTS
        if want_detail:
            event["site"] = _attribute_site()
            try:
                event["shape"] = list(getattr(arr, "shape", ()) or ())
                event["dtype"] = str(getattr(arr, "dtype", ""))
            except Exception as exc:  # noqa: BLE001 — donated arrays raise
                log.debug("sync sentinel: shape fetch failed: %s", exc)
            if sentinel is not None and sentinel.round_idx is not None:
                event["iter"] = sentinel.round_idx
            with _install_lock:
                if len(_sync_events) < MAX_RECORDED_EVENTS:
                    _sync_events.append(event)
            try:
                from . import default_registry
                default_registry().counter(
                    "lgbm_sync_events_total",
                    help="Implicit device->host syncs caught by the "
                         "runtime sentinel", kind=kind).inc()
            except Exception as exc:  # noqa: BLE001 — registry optional
                log.debug("sync sentinel: counter publish failed: %s", exc)
            try:
                from . import tracing
                tracing.instant("scaling/sync_event", cat="scaling",
                                **event)
            except Exception as exc:  # noqa: BLE001 — tracer optional
                log.debug("sync sentinel: trace instant failed: %s", exc)
            if sentinel is not None:
                from .recorder import sync_event as _emit
                _emit(sentinel.config, **event)
            log.warning("sync sentinel: implicit device->host sync via "
                        ".%s() at %s", kind, event.get("site", "unknown"))
    except Exception as exc:  # noqa: BLE001 — telemetry never raises
        log.debug("sync sentinel: event recording failed: %s", exc)
    finally:
        _tls.recording = False
    if sentinel is not None and sentinel.mode == "fail":
        raise log.LightGBMError(
            "tpu_sync_guard=fail: implicit device->host sync via .%s() "
            "at %s (wrap known-legitimate fetches in "
            "obs.scaling.exempt())" % (kind, event.get("site", "?")))


def _make_hook(kind: str, orig):
    def hook(self, *args, **kwargs):
        if _tls.active > 0 and _tls.allow == 0 and not _tls.recording:
            _record_sync(kind, self)
        return orig(self, *args, **kwargs)
    hook.__name__ = getattr(orig, "__name__", kind)
    hook._lgbm_sync_hook = True
    return hook


def _install_hooks() -> bool:
    """Patch the conversion methods (refcounted, idempotent).  Returns
    True when the hooks are live."""
    global _install_refs
    cls = _array_impl_class()
    if cls is None:
        return False
    with _install_lock:
        if _install_refs == 0:
            for kind in _WATCHED_METHODS:
                orig = getattr(cls, kind, None)
                if orig is None or getattr(orig, "_lgbm_sync_hook", False):
                    continue
                _orig_methods[kind] = orig
                setattr(cls, kind, _make_hook(kind, orig))
        _install_refs += 1
    return True


def _uninstall_hooks() -> None:
    global _install_refs
    cls = _array_impl_class()
    with _install_lock:
        if _install_refs > 0:
            _install_refs -= 1
        if _install_refs == 0 and cls is not None:
            for kind, orig in _orig_methods.items():
                setattr(cls, kind, orig)
            _orig_methods.clear()


def sync_stats() -> Dict:
    """Cumulative sentinel observations: total count, per-kind counts,
    and the bounded attribution log (copies)."""
    with _install_lock:
        return {"total": _sync_total, "by_kind": dict(_sync_counts),
                "events": [dict(e) for e in _sync_events]}


def reset_sync_stats() -> None:
    """Zero the sentinel counters/log (test isolation)."""
    global _sync_total
    with _install_lock:
        _sync_total = 0
        _sync_counts.clear()
        del _sync_events[:]


class _Exempt:
    """Scoped opt-out for a known-legitimate sync (the perf probe's one
    dependent ``float()`` per window).  Nests a jax d2h "allow" guard so
    a TPU backend's transfer log stays clean too — scoped, not global."""
    def __enter__(self):
        _tls.allow += 1
        self._jax_cm = None
        if _tls.active > 0:
            try:
                import jax
                self._jax_cm = jax.transfer_guard_device_to_host("allow")
                self._jax_cm.__enter__()
            except Exception:  # noqa: BLE001 — guard API is best-effort
                self._jax_cm = None
        return self

    def __exit__(self, *exc):
        if self._jax_cm is not None:
            try:
                self._jax_cm.__exit__(*exc)
            except Exception as e:  # noqa: BLE001 — guard API best-effort
                log.debug("sync sentinel: allow-guard exit failed: %s", e)
        _tls.allow -= 1
        return False


def exempt() -> _Exempt:
    """Context manager marking the enclosed device→host fetch as
    intentional; the sentinel neither counts nor fails on it."""
    return _Exempt()


class _Guard:
    def __init__(self, sentinel: "SyncSentinel", round_idx: Optional[int]):
        self._sentinel = sentinel
        self._round_idx = round_idx
        self._jax_cm = None
        self._hooked = False

    def __enter__(self):
        self._sentinel.round_idx = self._round_idx
        _active_sentinels.append(self._sentinel)
        self._hooked = _install_hooks()
        _tls.active += 1
        try:
            import jax
            self._jax_cm = jax.transfer_guard_device_to_host("log")
            self._jax_cm.__enter__()
        except Exception:  # noqa: BLE001 — old jax: scalar hooks only
            self._jax_cm = None
        return self

    def __exit__(self, *exc):
        if self._jax_cm is not None:
            try:
                self._jax_cm.__exit__(*exc)
            except Exception as e:  # noqa: BLE001 — guard API best-effort
                log.debug("sync sentinel: log-guard exit failed: %s", e)
        _tls.active -= 1
        if self._hooked:
            _uninstall_hooks()
        if _active_sentinels and _active_sentinels[-1] is self._sentinel:
            _active_sentinels.pop()
        return False


class SyncSentinel:
    """Param-gated (tpu_sync_guard=off|log|fail) runtime sync watcher.
    ``guard(it)`` wraps ONE boosting round; telemetry's own fetches run
    outside the guard by construction (models/gbdt.py wraps only the
    training impl), so a clean round reports zero events."""

    def __init__(self, config, mode: Optional[str] = None):
        self.config = config
        self.mode = (mode if mode is not None
                     else str(getattr(config, "tpu_sync_guard", "off")
                              or "off")).lower()
        self.round_idx: Optional[int] = None

    @classmethod
    def from_config(cls, config) -> Optional["SyncSentinel"]:
        mode = str(getattr(config, "tpu_sync_guard", "off") or "off").lower()
        return cls(config, mode) if mode in ("log", "fail") else None

    def guard(self, round_idx: Optional[int] = None) -> _Guard:
        return _Guard(self, round_idx)


# --------------------------------------------------------------------- #
# Per-round step decomposition
# --------------------------------------------------------------------- #
class StepDecomposer:
    """Turns one round's already-collected numbers into the host/device
    legs.  Strictly read-only apart from ONE dependent scalar fetch per
    tpu_scaling_window rounds (under exempt()), amortized into the
    device_est leg exactly like obs/perf's chain discipline."""

    def __init__(self, config, registry):
        self.window = max(1, int(getattr(config, "tpu_scaling_window", 8)
                                 or 8))
        self.ici_gbps = float(getattr(config, "tpu_scaling_ici_gbps", 45.0)
                              or 45.0)
        self.registry = registry
        self._rounds = 0
        self._last_wire_s = None       # cumulative leader-wire seconds
        self._last_mesh_bytes = None   # cumulative mesh collective bytes
        self._last_sync_total = 0
        self._device_est_ms = 0.0      # EWMA of the probe's drain time

    # -- cumulative source reads (deltas taken per round) -------------- #
    def _wire_total_s(self, gbdt) -> float:
        """Cumulative leader-wire wait: the hybrid axis accumulator when
        present, else the socket sync-wait counter family.  max() of the
        two because the hybrid leader's wire exchange also ticks the
        socket counter — charging it twice would invent loss."""
        wire = 0.0
        try:
            grower = getattr(gbdt, "_grower", None)
            axis = getattr(grower, "_axis", None) if grower else None
            if axis is not None:
                wire = float(getattr(axis, "_wire_wait_s", 0.0) or 0.0)
        except Exception as exc:  # noqa: BLE001 — source is best-effort
            log.debug("step decomp: axis wire read failed: %s", exc)
        try:
            fam = self.registry.family_sum(
                "lgbm_comm_sync_wait_seconds_total")
            if fam is not None:
                wire = max(wire, float(fam))
        except Exception as exc:  # noqa: BLE001 — source is best-effort
            log.debug("step decomp: wire counter read failed: %s", exc)
        return wire

    def _mesh_bytes_total(self, gbdt) -> float:
        """Cumulative bytes moved by the in-process mesh collective
        (psum'd histogram payloads) — MeshCollective._m_sent, or the
        hybrid backend's inner mesh stage."""
        try:
            grower = getattr(gbdt, "_grower", None)
            coll = getattr(grower, "collective", None) if grower else None
            if coll is None:
                return 0.0
            m = getattr(coll, "_m_sent", None)
            if m is None:
                m = getattr(getattr(coll, "_mesh_coll", None), "_m_sent",
                            None)
            return float(m.value) if m is not None else 0.0
        except Exception:  # noqa: BLE001
            return 0.0

    def _probe_device_ms(self, gbdt) -> Optional[float]:
        """One dependent scalar fetch: time-to-scalar AFTER the host
        finished the round = the device tail still in flight.  Same
        fetch _profile_sync uses, exempted from the sentinel by
        construction."""
        state = getattr(gbdt, "train_state", None)
        score = getattr(state, "score", None) if state is not None else None
        if score is None:
            return None
        import jax.numpy as jnp
        t0 = time.perf_counter()
        with exempt():
            float(jnp.sum(score[:, :1]))
        return (time.perf_counter() - t0) * 1e3

    # -- the per-round section ----------------------------------------- #
    def on_round(self, gbdt, iteration: int, wall_s: float,
                 phases: Dict[str, Dict[str, float]]) -> Dict:
        wall_ms = wall_s * 1e3
        host_sync_ms = sum(phases[p]["ms"] for p in SYNC_PHASES
                           if p in phases)

        wire_total = self._wire_total_s(gbdt)
        if self._last_wire_s is None:
            self._last_wire_s = wire_total
        leader_wire_ms = max(wire_total - self._last_wire_s, 0.0) * 1e3
        self._last_wire_s = wire_total

        mesh_bytes = self._mesh_bytes_total(gbdt)
        if self._last_mesh_bytes is None:
            self._last_mesh_bytes = mesh_bytes
        psum_bytes = max(mesh_bytes - self._last_mesh_bytes, 0.0)
        self._last_mesh_bytes = mesh_bytes
        psum_ms = psum_bytes / (self.ici_gbps * 1e9) * 1e3

        # dispatch is the remainder; clamping both it and the subtracted
        # legs keeps the identity wall == sum(legs) when timers jitter
        budget = wall_ms
        host_sync_ms = min(host_sync_ms, budget)
        budget -= host_sync_ms
        leader_wire_ms = min(leader_wire_ms, budget)
        budget -= leader_wire_ms
        psum_ms = min(psum_ms, budget)
        dispatch_ms = budget - psum_ms

        self._rounds += 1
        probe_ms = None
        if self._rounds % self.window == 1 or self.window == 1:
            probe_ms = self._probe_device_ms(gbdt)
            if probe_ms is not None:
                self._device_est_ms = (probe_ms if self._device_est_ms == 0.0
                                       else 0.5 * self._device_est_ms
                                       + 0.5 * probe_ms)

        stats = sync_stats()
        sync_delta = stats["total"] - self._last_sync_total
        self._last_sync_total = stats["total"]

        decomp = {
            "wall_ms": round(wall_ms, 3),
            "host_sync_ms": round(host_sync_ms, 3),
            "leader_wire_ms": round(leader_wire_ms, 3),
            "psum_ms": round(psum_ms, 4),
            "psum_bytes": int(psum_bytes),
            "dispatch_ms": round(dispatch_ms, 3),
            "device_est_ms": round(self._device_est_ms, 3),
            "host_share": round((host_sync_ms + leader_wire_ms)
                                / max(wall_ms, 1e-9), 4),
            "sync_events": int(sync_delta),
        }
        if probe_ms is not None:
            decomp["probe_ms"] = round(probe_ms, 3)

        self._publish(decomp, wall_s, probe_ms)
        return decomp

    def _publish(self, decomp: Dict, wall_s: float,
                 probe_ms: Optional[float]) -> None:
        for leg in ("host_sync", "leader_wire", "psum", "dispatch",
                    "device_est"):
            self.registry.gauge(
                "lgbm_scaling_leg_ms",
                help="Step-decomposition leg of the last boosting round "
                     "(ms)", leg=leg).set(decomp[leg + "_ms"])
        self.registry.gauge(
            "lgbm_scaling_host_share",
            help="Host-blocked share of the last round "
                 "(host_sync + leader_wire over wall)").set(
            decomp["host_share"])
        from . import tracing
        tracer = tracing.get_tracer()
        if tracer.enabled:
            tracing.complete(
                "scaling/decomp", wall_s, cat="scaling",
                host_sync_ms=decomp["host_sync_ms"],
                leader_wire_ms=decomp["leader_wire_ms"],
                psum_ms=decomp["psum_ms"],
                dispatch_ms=decomp["dispatch_ms"],
                host_share=decomp["host_share"])
            if probe_ms is not None:
                tracing.complete("scaling/probe", probe_ms / 1e3,
                                 cat="scaling", window=self.window)


# --------------------------------------------------------------------- #
# Efficiency waterfall
# --------------------------------------------------------------------- #
def mean_decomposition(rounds: List[Dict]) -> Optional[Dict[str, float]]:
    """Mean per-round legs over a run's step_decomp sections (skips
    rounds that carry no decomposition)."""
    rows = [r for r in rounds or [] if r and "wall_ms" in r]
    if not rows:
        return None
    keys = ("wall_ms", "host_sync_ms", "leader_wire_ms", "psum_ms",
            "dispatch_ms", "device_est_ms")
    return {k: sum(float(r.get(k, 0.0)) for r in rows) / len(rows)
            for k in keys}


def efficiency_waterfall(per_world: Dict[int, Dict[str, float]]) -> Dict:
    """Fit mean per-round legs at each world size into the loss
    waterfall: ideal → +host_sync → +dispatch_gap → +psum →
    +leader_wire → measured.

    ``ideal`` is the world-1 round wall divided by w (perfect scaling);
    each loss leg is that world's leg in EXCESS of the ideally-scaled
    world-1 leg (a cost that shrank 1/w with the work contributes
    nothing).  Because the per-round legs partition the wall exactly,
    the named legs + residual sum to the measured wall identically;
    residual only absorbs clamping noise, and |residual|/measured is
    the health number the report gates on (≤ 10% by acceptance)."""
    if not per_world:
        return {}
    worlds = sorted(per_world)
    base = per_world.get(1) or per_world[worlds[0]]
    base_w = 1 if 1 in per_world else worlds[0]
    out: Dict = {}
    for w, legs in ((w, per_world[w]) for w in worlds):
        scale = float(w) / float(base_w)
        measured = float(legs["wall_ms"])
        ideal = float(base["wall_ms"]) / scale
        excess = {
            "host_sync": max(float(legs["host_sync_ms"])
                             - float(base["host_sync_ms"]) / scale, 0.0),
            "dispatch_gap": max(float(legs["dispatch_ms"])
                                - float(base["dispatch_ms"]) / scale, 0.0),
            "psum": max(float(legs["psum_ms"])
                        - float(base["psum_ms"]) / scale, 0.0),
            "leader_wire": max(float(legs["leader_wire_ms"])
                               - float(base["leader_wire_ms"]) / scale,
                               0.0),
        }
        residual = measured - ideal - sum(excess.values())
        ordered = {"ideal": round(ideal, 3)}
        ordered.update({k: round(v, 3) for k, v in excess.items()})
        ordered["residual"] = round(residual, 3)
        dominant = max(excess, key=lambda k: excess[k])
        if abs(residual) > excess[dominant]:
            dominant = "residual"
        if max(excess[max(excess, key=lambda k: excess[k])],
               abs(residual)) < 0.01 * max(measured, 1e-9):
            dominant = "none"      # scaling is clean at this world size
        out[w] = {
            "measured_ms": round(measured, 3),
            "legs": ordered,
            "dominant_loss": dominant,
            "residual_share": round(abs(residual) / max(measured, 1e-9), 4),
            "efficiency": round(float(base["wall_ms"])
                                / max(scale * measured, 1e-9), 4),
            "host_share": round((float(legs["host_sync_ms"])
                                 + float(legs["leader_wire_ms"]))
                                / max(measured, 1e-9), 4),
        }
    return out
