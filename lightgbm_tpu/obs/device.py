"""Device-side observability: what the process counts about its own
device work, in plain Python, process-wide, bounded.

- **Compile counters.**  On TPU the dominant hidden cost is not FLOPs but
  compilation: a retrace in the middle of training stalls every iteration
  behind XLA.  `jax.monitoring` fires named events for every backend
  compile and jaxpr trace; ONE process-wide listener (idempotent) counts
  them (`compile_counts`), and `analyze_compiled` keeps XLA's own cost and
  peak-HBM estimate of a jitted callable.
- **Live-buffer probe** (`device_stats`): buffer count and bytes via
  jax.live_arrays, jit cache occupancy via the pjit inference cache, for
  the per-iteration telemetry events and the /metrics gauges.
- **Donation audit** (`donation_audit`): which large inputs of a jitted
  callable the caller donated.
- **Split ledger** (`record_split_ledger` / `split_ledgers`): the rows
  each step of the growth loop worked on, for the last
  `SPLIT_LEDGER_TREES` trained trees; always on, like the compile
  counters (docs/Tracing.md, "Counters").

Everything is guarded: a jax version without an event name, without
jax.monitoring, or without the private pjit cache degrades to zeros,
never to an exception — telemetry must not be able to kill training.
"""
from __future__ import annotations

import collections
import threading
from typing import Dict, List, Optional

from ..utils import log

_lock = threading.Lock()
_installed = False
_install_count = 0           # registration attempts that found hooks live
_counts = {
    "backend_compiles": 0,   # XLA backend compilations (the expensive ones)
    "traces": 0,             # jaxpr traces (retraces included)
    "cache_hits": 0,         # compilation-cache hits
}
# high-water mark over every analyze_compiled result this process — the
# live-gauge view of XLA's own peak-HBM estimate (recorder dicts only
# see the per-retrace values)
_hbm = {"peak_hbm_bytes": 0, "analyses": 0}
# donation audit tables, label -> table dict (see donation_audit); the
# lgbm_xla_undonated_bytes{fn} gauges pull from here
_donation: Dict[str, Dict] = {}
# inputs smaller than this are noise, not donation candidates
DONATION_MIN_BYTES = 1 << 16
# the split ledgers of the last trained trees, oldest first (a ring: a
# long job keeps the newest, a benchmark's traced slice is the last few)
SPLIT_LEDGER_TREES = 64
_split_ledgers: collections.deque = collections.deque(
    maxlen=SPLIT_LEDGER_TREES)

# event name fragments -> counter key; matched by substring so minor
# renames across jax versions keep counting instead of silently zeroing
_EVENT_MAP = (
    ("backend_compile", "backend_compiles"),
    ("trace", "traces"),
    ("use_cache", "cache_hits"),
    ("using_cache", "cache_hits"),
)


def _on_event(event: str, *_args, **_kw) -> None:
    for frag, key in _EVENT_MAP:
        if frag in event:
            with _lock:
                _counts[key] += 1
            return


def _on_event_duration(event: str, dur: float) -> None:
    _on_event(event)
    # compile attribution for the span timeline: every backend compile
    # becomes an "xla/compile" span so a mid-training retrace is visible
    # as the stall it is, not a mystery gap
    if "backend_compile" in event:
        from . import tracing
        tracing.complete("xla/compile", dur, cat="xla", event=event)


def install_compile_listeners() -> bool:
    """Register the jax.monitoring listeners AT MOST once per process —
    idempotent by contract: every GBDT/Server constructor calls this and
    the counters must not double-count.  The lock is held across the
    check AND the registration so two racing constructors cannot both
    register.  Returns True when the hooks are live."""
    global _installed, _install_count
    with _lock:
        if _installed:
            _install_count += 1
            return True
        try:
            from jax import monitoring
            monitoring.register_event_duration_secs_listener(
                lambda event, dur, **kw: _on_event_duration(event, dur))
            monitoring.register_event_listener(
                lambda event, **kw: _on_event(event))
        except Exception:  # noqa: BLE001 — no monitoring API -> zeros
            return False
        _installed = True
        _install_count += 1
    return True


def install_count() -> int:
    """How many install_compile_listeners calls found the hooks live —
    the idempotency contract's witness (tests assert registrations == 1
    no matter how many times this ran)."""
    with _lock:
        return _install_count


def analyze_compiled(fn, args, signature: str = "",
                     donation_resident=()) -> Optional[Dict]:
    """XLA kernel attribution for one jitted callable at concrete args:
    flops / bytes accessed from ``Lowered.cost_analysis``, peak HBM
    from ``Compiled.memory_analysis``, and the input-layout donation
    walk (``donation_audit`` over the same lowering — un-donated large
    buffers land in the per-executable audit table and the
    ``lgbm_xla_undonated_bytes{fn}`` gauge), recorded as a "compile"
    span tagged with the triggering shape signature.

    jax caches the executable, so the ``.lower().compile()`` here reuses
    the compilation the training step already paid for; still, callers
    gate this on tpu_trace_xla_analysis + an armed tracer and invoke it
    once per retrace only.  Returns the stats dict, or None when the
    version of jax in the container exposes neither analysis."""
    from . import tracing
    import time as _time
    t0 = _time.perf_counter()
    stats: Dict = {}
    try:
        lowered = fn.lower(*args)
    except Exception:  # noqa: BLE001 — analysis is best-effort
        return None
    table = donation_audit(fn, args, label=signature or "jit",
                           resident=donation_resident, lowered=lowered)
    if table is not None:
        stats["undonated_bytes"] = table["undonated_bytes"]
    try:
        cost = lowered.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        for key in ("flops", "bytes accessed",
                    "utilization operand 0", "transcendentals"):
            if cost and key in cost:
                stats[key.replace(" ", "_")] = float(cost[key])
    except Exception as exc:  # noqa: BLE001
        log.debug("cost analysis unavailable: %s", exc)
    try:
        mem = lowered.compile().memory_analysis()
        for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                     "output_size_in_bytes", "generated_code_size_in_bytes"):
            v = getattr(mem, attr, None)
            if v is not None:
                stats[attr] = int(v)
        if "temp_size_in_bytes" in stats:
            stats["peak_hbm_bytes"] = (stats["temp_size_in_bytes"]
                                       + stats.get("output_size_in_bytes", 0))
    except Exception as exc:  # noqa: BLE001
        log.debug("memory analysis unavailable: %s", exc)
    if not stats:
        return None
    stats["signature"] = signature
    with _lock:
        _hbm["analyses"] += 1
        if stats.get("peak_hbm_bytes", 0) > _hbm["peak_hbm_bytes"]:
            _hbm["peak_hbm_bytes"] = int(stats["peak_hbm_bytes"])
    tracing.complete("compile", _time.perf_counter() - t0, cat="xla",
                     **stats)
    return stats


def _donated_params(mlir_text: str) -> Optional[set]:
    """Parameter indices of @main carrying a donation marker
    (``tf.aliasing_output`` / ``jax.buffer_donor``) in the lowered
    StableHLO text — jax records donation intent there on every backend,
    including CPU where the runtime then ignores it.  None when the
    signature cannot be located (renamed entry point)."""
    start = mlir_text.find("@main(")
    if start < 0:
        return None
    # the signature region ends at the arrow/body; params carry no
    # parens so the first ')' closes the list
    end = mlir_text.find(")", start)
    if end < 0:
        return None
    sig = mlir_text[start:end]
    donated = set()
    idx = 0
    while True:
        cur = sig.find("%%arg%d:" % idx)
        if cur < 0:
            break
        nxt = sig.find("%%arg%d:" % (idx + 1))
        chunk = sig[cur:nxt if nxt > 0 else len(sig)]
        if "tf.aliasing_output" in chunk or "jax.buffer_donor" in chunk:
            donated.add(idx)
        idx += 1
    return donated if idx else None


def _leaf_bytes(leaf) -> int:
    try:
        v = getattr(leaf, "nbytes", None)
        if v is not None:
            return int(v)
    except Exception:  # noqa: BLE001 — donated/deleted arrays raise
        return 0
    try:
        import numpy as np
        shape = getattr(leaf, "shape", ())
        dtype = getattr(leaf, "dtype", None)
        size = 1
        for s in shape:
            size *= int(s)
        return size * (np.dtype(dtype).itemsize if dtype is not None else 8)
    except Exception:  # noqa: BLE001
        return 0


def donation_audit(fn, args, label: str = "",
                   min_bytes: int = DONATION_MIN_BYTES,
                   resident=(), lowered=None) -> Optional[Dict]:
    """Walk one jitted callable's input layout at concrete args and
    table which large inputs the caller donated: un-donated large
    buffers force XLA to keep input AND output alive across the
    dispatch — double HBM residency plus a copy the aliasing would have
    elided (an arena that is not donated is a second 6-10 GB on a 16 GB
    chip: the benchmark's `correct` holds every train cell to it).

    ``resident`` lists the flattened-argument indices that are
    semantically impossible to donate (buffers reused on later rounds,
    e.g. the binned feature planes); they are excluded from
    ``undonated_bytes`` but stay in the table flagged resident, so the
    committed floor tracks real omissions only.  The table lands in the
    process-wide store (``donation_stats``) and feeds the
    ``lgbm_xla_undonated_bytes{fn}`` gauge.  Best-effort: returns None
    when lowering or the donation markers are unavailable."""
    try:
        import jax
        if lowered is None:
            lowered = fn.lower(*args)
        donated = _donated_params(lowered.as_text())
        if donated is None:
            return None
        leaves = jax.tree_util.tree_leaves(args)
    except Exception as exc:  # noqa: BLE001 — audit is best-effort
        log.debug("donation audit unavailable for %s: %s", label, exc)
        return None
    resident = set(int(i) for i in resident)
    rows = []
    undonated = 0
    donated_bytes = 0
    for i, leaf in enumerate(leaves):
        nbytes = _leaf_bytes(leaf)
        if nbytes < min_bytes:
            continue
        is_donated = i in donated
        row = {"arg": i, "bytes": nbytes,
               "shape": list(getattr(leaf, "shape", ()) or ()),
               "dtype": str(getattr(leaf, "dtype", "")),
               "donated": is_donated}
        if is_donated:
            donated_bytes += nbytes
        elif i in resident:
            row["resident"] = True
        else:
            undonated += nbytes
        rows.append(row)
    table = {"fn": label, "undonated_bytes": int(undonated),
             "donated_bytes": int(donated_bytes),
             "donated_args": sorted(donated), "rows": rows}
    with _lock:
        _donation[label or ("fn%d" % len(_donation))] = table
    try:
        from . import default_registry
        default_registry().gauge(
            "lgbm_xla_undonated_bytes",
            help="Large un-donated input bytes of this cached executable "
                 "(resident buffers excluded)", fn=label).set(undonated)
    except Exception as exc:  # noqa: BLE001 — registry is optional here
        log.debug("donation audit: gauge publish failed: %s", exc)
    return table


def donation_stats() -> Dict[str, Dict]:
    """Per-executable donation audit tables recorded so far (copies)."""
    with _lock:
        return {k: dict(v) for k, v in _donation.items()}


def hbm_stats() -> Dict[str, int]:
    """Process-wide peak-HBM high-water mark (max peak_hbm_bytes across
    every analyze_compiled call) + how many analyses fed it."""
    with _lock:
        return dict(_hbm)


def record_split_ledger(iteration: int, slot: int, num_data: int,
                        partition_rows, histogram_rows) -> None:
    """One trained tree's split ledger into the ring (the one writer is
    GBDT._record_split_ledger, wherever a trained tree reaches the host).
    Entry i of `partition_rows` is the rows step i's `partition_segment`
    call moved, of `histogram_rows` the rows its `segment_histogram` call
    summed (models/tree.py `Tree.split_ledger`); a tree of one leaf has
    two empty arrays.  The counts are what the tree records: under a mesh
    the global ones, not one chip's share."""
    entry = {"iteration": int(iteration), "slot": int(slot),
             "num_data": int(num_data),
             "partition_rows": partition_rows,
             "histogram_rows": histogram_rows}
    with _lock:
        _split_ledgers.append(entry)


def split_ledgers() -> List[Dict]:
    """The split ledgers of the last SPLIT_LEDGER_TREES trained trees of
    this process, oldest first (the one reader is the benchmark's
    readers/row_ledger.py; an operator calls it after a run)."""
    with _lock:
        return list(_split_ledgers)


def compile_counts() -> Dict[str, int]:
    """Cumulative compile/trace/cache counts since process start (or
    since the listeners were installed)."""
    with _lock:
        return dict(_counts)


def jit_cache_size() -> int:
    """Entries in the pjit call cache — growth across iterations means
    the training loop is retracing (shape instability)."""
    try:
        from jax._src.pjit import _infer_params_cached
        return int(_infer_params_cached.cache_info().currsize)
    except Exception:  # noqa: BLE001 — private API; absent -> 0
        return 0


def device_stats() -> Dict[str, int]:
    """Live device-memory view: buffer count, total bytes, jit cache
    occupancy.  Cheap (host-side bookkeeping only, no device sync)."""
    buffers = 0
    nbytes = 0
    try:
        import jax
        for a in jax.live_arrays():
            buffers += 1
            try:
                nbytes += int(a.nbytes)
            # donated arrays raise on .nbytes by design, once per
            # buffer per scan; logging would spam every telemetry tick
            # tpulint: disable-next-line=except-swallow
            except Exception:  # noqa: BLE001 — deleted/donated arrays
                pass
    except Exception as exc:  # noqa: BLE001
        log.debug("live-array scan unavailable: %s", exc)
    return {"live_buffers": buffers, "live_bytes": nbytes,
            "jit_cache_entries": jit_cache_size()}
