"""The runtime sync sentinel: every implicit device→host scalar fetch
inside a boosting round, counted and attributed to its call site.

``SyncSentinel`` is the dynamic complement to tpulint's static
``jit-host-sync`` rule: armed (``tpu_sync_guard=log|fail``) it wraps
the round in ``jax.transfer_guard_device_to_host("log")`` AND hooks
the jax array scalar-conversion methods (``item`` / ``tolist`` /
``__float__`` / ``__int__`` / ``__bool__`` / ``__index__``) so every
implicit device→host scalar fetch inside the round becomes a counted,
stack-attributed ``sync_event`` telemetry event.  The method hooks are
what makes the sentinel testable on the CPU backend, where jax's
transfer guard is inert for device→host fetches; on a real TPU
backend the entered transfer-guard context logs the bulk transfers
the scalar hooks cannot see.  Known-legitimate syncs (the one-shot
fault-surfacing fetches of models/gbdt.py, the engine's metric fetch)
run under the scoped ``exempt()`` context, not a global opt-out.
``fail`` mode raises LightGBMError at the first un-exempted sync —
after recording it.

The sentinel is read-only on training state: models train
bitwise-identically with it armed or off (tests/test_scaling.py pins
this for gbdt serial and mesh-w2).  Where a round's time goes is read
from the profiler's trace (docs/Tracing.md, benchmarks/README.md), not
from the host's clock.
"""
from __future__ import annotations

import threading
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
    """Scoped opt-out for a known-legitimate sync (a one-shot
    fault-surfacing fetch).  Nests a jax d2h "allow" guard so
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
