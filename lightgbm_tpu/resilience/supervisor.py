"""Continuous-learning supervisor: the loop that keeps a served model
fresh without ever serving a silently-worse one.

    ingest ──> bounded validated buffer (crash-safe spool)
                      │  tpu_refit_interval_s AND tpu_refit_min_rows
                      v
    REFIT:  candidate = Booster.refit(buffer)        (tpu_refit_mode=refit)
            or live trees + init_model continuation  (tpu_refit_mode=continue)
                      │  candidate persisted, spool trimmed
                      v
    SHADOW: mirror served traffic onto the candidate (serving/shadow.py)
            + paired loss on the held-out label window
                      │  delta >= tpu_promote_min_delta over
                      │  >= tpu_promote_min_samples held-out rows
                      v
    PROMOTE: registry hot-swap (version advances)       else: discard
                      │
                      v
    WATCH:  live loss on FRESH held-out rows for tpu_promote_watch_s
                      │  breach of baseline + tpu_promote_rollback_delta
                      v
    ROLLBACK: registry reinstalls the prior version, loop returns to idle

Crash consistency: every accepted ingest block is spooled to disk
(`supervisor_spool/seg_*.npz`) BEFORE it is acknowledged, and segments
are deleted only after a candidate built from them has been persisted —
so a SIGKILL anywhere in the loop (the `kill_refit` chaos drill lands
one mid-refit) loses zero ingested rows.  The supervisor's own state
rides `SUPERVISOR.json` next to the spool, written with the same
atomic temp+fsync+replace sequence as model files.  Serving is never
gated on any of this: the live model keeps answering through refit,
kill, resume, promote and rollback alike.

The tick() state machine is synchronous and single-threaded by
construction (one `_tick_lock` serializes tick and force_promote), so
the unit tests drive it without threads; start() merely runs tick on a
daemon loop.
"""
from __future__ import annotations

import glob
import json
import os
import signal
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from .. import engine
from ..basic import Booster, Dataset
from ..config import Config
from ..io.dataset import IngestError, validate_ingest_block
from ..io.file_io import atomic_write_text
from ..obs import default_registry
from ..obs.recorder import supervisor_event
from ..utils import log

SPOOL_DIR = "supervisor_spool"
STATE_FILE = "SUPERVISOR.json"
CANDIDATE_FILE = "candidate.txt"

IDLE, REFIT, SHADOW, WATCH = "idle", "refit", "shadow", "watch"


def _shed_overflow(rows: int) -> None:
    default_registry().counter(
        "lgbm_ingest_shed_total",
        help="ingest rows shed at the validation boundary",
        reason="overflow").inc(rows)


class IngestBuffer:
    """Bounded, validated, crash-safe buffer of fresh labeled rows.

    Accepted blocks are split row-wise into a TRAINING part and a
    HELD-OUT part (`holdout_fraction`, never trained on — the shadow
    metric window).  Each accepted block becomes one numbered spool
    segment on disk; `discard_upto(seq)` removes segments only after the
    caller has durably consumed them.  Over `capacity` training rows the
    OLDEST blocks are shed (with the overflow counter) — ingest pressure
    degrades freshness, never the process."""

    def __init__(self, num_features: int, capacity: int,
                 holdout_fraction: float, spool_dir: Optional[str] = None,
                 window_rows: int = 4096, seed: int = 0):
        self.num_features = int(num_features)
        self.capacity = max(1, int(capacity))
        self.holdout_fraction = float(holdout_fraction)
        self.window_rows = max(1, int(window_rows))
        self.spool_dir = spool_dir
        self._rng = np.random.RandomState(seed)
        self._lock = threading.Lock()
        self._seq = 0                      # next segment number
        self._blocks: List[Dict] = []      # pending TRAIN blocks
        self._window: List[Dict] = []      # held-out eval blocks
        self._shed_overflow_rows = 0
        if spool_dir:
            os.makedirs(spool_dir, exist_ok=True)

    # -- ingest --------------------------------------------------------- #
    def add(self, X, label=None, weight=None) -> int:
        """Validate, spool and buffer one block; rows with NaN/inf
        labels are shed (counted), block-level malformations raise
        IngestError.  Returns the number of ACCEPTED rows."""
        X, y, w = validate_ingest_block(
            X, label, weight, num_features=self.num_features, shed=True)
        n = int(X.shape[0])
        if n == 0:
            return 0
        hold = self._rng.random_sample(n) < self.holdout_fraction
        keep = ~hold
        with self._lock:
            seq = self._seq
            self._seq += 1
            if keep.any():
                blk = {"seq": seq, "X": X[keep],
                       "y": y[keep] if y is not None else None,
                       "w": w[keep] if w is not None else None}
                self._spool_write("seg", blk)
                self._blocks.append(blk)
            if hold.any() and y is not None:
                blk = {"seq": seq, "X": X[hold], "y": y[hold],
                       "w": w[hold] if w is not None else None}
                self._spool_write("win", blk)
                self._window.append(blk)
            self._trim_locked()
        return n

    def _trim_locked(self) -> None:
        # every caller holds self._lock (the _locked suffix contract)
        while (len(self._blocks) > 1
               and sum(b["X"].shape[0] for b in self._blocks)
               > self.capacity):
            dead = self._blocks.pop(0)  # tpulint: ok=lock-unguarded-write
            self._shed_overflow_rows += dead["X"].shape[0]  # tpulint: ok=lock-unguarded-write
            _shed_overflow(dead["X"].shape[0])
            self._spool_unlink("seg", dead["seq"])
        while (len(self._window) > 1
               and sum(b["X"].shape[0] for b in self._window)
               > self.window_rows):
            dead = self._window.pop(0)  # tpulint: ok=lock-unguarded-write
            self._spool_unlink("win", dead["seq"])

    # -- spool ---------------------------------------------------------- #
    # Two segment families: "seg" (training rows, deleted once a
    # candidate built from them is persisted) and "win" (held-out metric
    # rows, deleted when trimmed out of the window) — so a SIGKILL loses
    # neither the next refit's data nor the shadow verdict's window.
    def _seg_path(self, kind: str, seq: int) -> str:
        return os.path.join(self.spool_dir, "%s_%08d.npz" % (kind, seq))

    def _spool_write(self, kind: str, blk: Dict) -> None:
        if not self.spool_dir:
            return
        path = self._seg_path(kind, blk["seq"])
        tmp = path + ".tmp"
        y, w = blk["y"], blk["w"]
        with open(tmp, "wb") as f:
            np.savez(f, X=blk["X"],
                     y=y if y is not None else np.zeros(0),
                     has_y=np.array(y is not None),
                     w=w if w is not None else np.zeros(0),
                     has_w=np.array(w is not None))
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)

    def _spool_unlink(self, kind: str, seq: int) -> None:
        if not self.spool_dir:
            return
        try:
            os.unlink(self._seg_path(kind, seq))
        except OSError:
            pass

    def _spool_read(self, path: str) -> Optional[Dict]:
        try:
            with np.load(path) as z:
                return {
                    "seq": int(os.path.basename(path)[4:-4]),
                    "X": z["X"],
                    "y": z["y"] if bool(z["has_y"]) else None,
                    "w": z["w"] if bool(z["has_w"]) else None}
        except Exception as exc:  # noqa: BLE001 — torn tail segment
            log.warning("supervisor: dropping unreadable spool segment "
                        "%s (%s)", path, exc)
            return None

    def restore(self, consumed_upto: int = -1) -> int:
        """Rebuild the buffer from spool segments.  Training segments
        with seq <= `consumed_upto` were consumed by a persisted
        candidate and are deleted; window segments always reload (the
        shadow verdict must survive a kill too).  Returns restored
        training-row count."""
        if not self.spool_dir:
            return 0
        restored = 0
        with self._lock:
            for path in sorted(glob.glob(
                    os.path.join(self.spool_dir, "seg_*.npz"))):
                seq = int(os.path.basename(path)[4:-4])
                if seq <= consumed_upto:
                    os.unlink(path)
                    continue
                blk = self._spool_read(path)
                if blk is None:
                    continue
                self._blocks.append(blk)
                self._seq = max(self._seq, seq + 1)
                restored += int(blk["X"].shape[0])
            for path in sorted(glob.glob(
                    os.path.join(self.spool_dir, "win_*.npz"))):
                blk = self._spool_read(path)
                if blk is None or blk["y"] is None:
                    continue
                self._window.append(blk)
                self._seq = max(self._seq, blk["seq"] + 1)
            self._trim_locked()
        return restored

    # -- consumption ---------------------------------------------------- #
    def train_rows(self) -> int:
        with self._lock:
            return sum(b["X"].shape[0] for b in self._blocks)

    def window_rows_count(self, after_seq: int = -1) -> int:
        with self._lock:
            return sum(b["X"].shape[0] for b in self._window
                       if b["seq"] > after_seq)

    def current_seq(self) -> int:
        with self._lock:
            return self._seq - 1

    def take_training(self):
        """Snapshot every pending training block: (X, y, w, upto_seq).
        Blocks stay buffered (and spooled) until discard_upto — a kill
        between here and candidate persistence replays them."""
        with self._lock:
            blocks = list(self._blocks)
        if not blocks:
            return None
        X = np.vstack([b["X"] for b in blocks])
        n = X.shape[0]
        y = (np.concatenate([np.zeros(b["X"].shape[0])
                             if b["y"] is None else b["y"] for b in blocks])
             if any(b["y"] is not None for b in blocks) else None)
        w = (np.concatenate([np.ones(b["X"].shape[0])
                             if b["w"] is None else b["w"] for b in blocks])
             if any(b["w"] is not None for b in blocks) else None)
        return X, y, w, max(b["seq"] for b in blocks)

    def window(self, after_seq: int = -1):
        """The held-out metric window (optionally only rows newer than
        `after_seq` — the WATCH phase's freshness cut)."""
        with self._lock:
            blocks = [b for b in self._window if b["seq"] > after_seq]
        if not blocks:
            return None
        X = np.vstack([b["X"] for b in blocks])
        y = np.concatenate([b["y"] for b in blocks])
        w = (np.concatenate([np.ones(b["X"].shape[0])
                             if b["w"] is None else b["w"] for b in blocks])
             if any(b["w"] is not None for b in blocks) else None)
        return X, y, w

    def discard_upto(self, seq: int) -> None:
        """Drop consumed training blocks and their spool segments.
        Window blocks up to `seq` stay in memory (still useful for the
        shadow metric) but lose crash persistence — acceptable, the
        window is advisory."""
        with self._lock:
            self._blocks = [b for b in self._blocks if b["seq"] > seq]
            if self.spool_dir:
                for path in glob.glob(
                        os.path.join(self.spool_dir, "seg_*.npz")):
                    if int(os.path.basename(path)[4:-4]) <= seq:
                        try:
                            os.unlink(path)
                        except OSError:
                            pass

    def shed_overflow_rows(self) -> int:
        with self._lock:
            return self._shed_overflow_rows


def _loss(booster, X, y, w, objective: str) -> float:
    """Held-out quality metric: logloss on probabilities for binary and
    multiclass objectives, weighted MSE otherwise — enough signal to
    rank live vs candidate, cheap enough to run every tick."""
    pred = np.asarray(booster._gbdt.predict(X, device=False), np.float64)
    y = np.asarray(y, np.float64)
    wt = np.ones(len(y)) if w is None else np.asarray(w, np.float64)
    wsum = max(float(wt.sum()), 1e-12)
    if pred.ndim == 2:     # multiclass probabilities [n, k]
        k = pred.shape[1]
        p = np.clip(pred[np.arange(len(y)), y.astype(np.int64) % k],
                    1e-12, 1.0)
        return float(-(wt * np.log(p)).sum() / wsum)
    pred = pred.reshape(-1)
    if objective.startswith("binary"):
        p = np.clip(pred, 1e-12, 1 - 1e-12)
        return float(-(wt * (y * np.log(p)
                             + (1 - y) * np.log(1 - p))).sum() / wsum)
    d = pred - y
    return float((wt * d * d).sum() / wsum)


class ContinuousLearningSupervisor:
    """Drives one served model name through the refit -> shadow ->
    promote -> watch -> rollback loop against a `serving.Server`."""

    def __init__(self, server, config: Optional[Config] = None,
                 model_name: Optional[str] = None,
                 train_params: Optional[Dict] = None,
                 base_dataset: Optional[Dataset] = None, **overrides):
        if isinstance(config, Config) and not overrides:
            cfg = config
        elif isinstance(config, Config):
            cfg = Config(dict(config.raw_params, **overrides))
        else:
            cfg = Config(dict(config or {}, **overrides))
        self.config = cfg
        self.server = server
        self.name = model_name or cfg.serve_model_name
        self.base_dataset = base_dataset
        entry = server.registry.get(self.name)
        self.train_params = dict(train_params
                                 or getattr(entry.booster, "params", None)
                                 or {})
        # the candidate trains serially, in-process, and must not write
        # over the serving checkpoints or recurse into the supervisor
        for k in ("machines", "machine_list_filename", "num_machines",
                  "tpu_elastic", "tpu_continuous_learning",
                  "tpu_checkpoint_path", "tpu_telemetry_path", "task"):
            self.train_params.pop(k, None)
        self.train_params.setdefault("verbosity", -1)
        self.root = cfg.tpu_checkpoint_path or os.path.join(
            ".", "lgbm_supervisor")
        os.makedirs(self.root, exist_ok=True)
        self.buffer = IngestBuffer(
            num_features=entry.num_features,
            capacity=cfg.tpu_refit_buffer_rows,
            holdout_fraction=cfg.tpu_refit_holdout_fraction,
            spool_dir=os.path.join(self.root, SPOOL_DIR),
            window_rows=max(4 * cfg.tpu_promote_min_samples, 1024),
            seed=cfg.seed if cfg.seed else 0)
        # _tick_lock serializes the state machine (tick / force_promote);
        # _state_lock guards the fields snapshot() reads.  Heavy work
        # (training, loads) runs under _tick_lock only.
        self._tick_lock = threading.Lock()
        self._state_lock = threading.Lock()
        self.state = IDLE
        self._last_refit_t = time.monotonic()
        self._refits = 0
        self._promotes = 0
        self._rollbacks = 0
        self._candidate: Optional[Booster] = None
        self._cand_built_t: Optional[float] = None
        self._cand_consumed_upto = -1
        self._mirror = None
        self._shadow_deadline: Optional[float] = None
        self._last_shadow: Optional[Dict] = None
        self._baseline: Optional[float] = None
        self._watch_deadline: Optional[float] = None
        self._watch_from_seq = -1
        self._stop_event = threading.Event()
        self._thread: Optional[threading.Thread] = None
        obj = str(self.train_params.get("objective") or "")
        if not obj:
            g = getattr(entry.booster, "_gbdt", None)
            if g is not None and g.objective is not None:
                obj = g.objective.to_string()
        self.objective = obj or str(cfg.objective or "regression")
        reg = default_registry()
        reg.gauge("lgbm_supervisor_buffer_rows",
                  help="Ingested rows buffered for the next refit",
                  model=self.name).set_fn(self.buffer.train_rows)
        reg.gauge("lgbm_supervisor_candidate_age_s",
                  help="Age of the current shadow candidate",
                  model=self.name).set_fn(self._candidate_age)
        self._shadow_gauge = reg.gauge(
            "lgbm_supervisor_shadow_delta",
            help="Last shadow eval: live loss minus candidate loss",
            model=self.name)
        self._restore()
        server.attach_supervisor(self)
        self._policy_levers = self._bind_policy_levers()

    def _bind_policy_levers(self):
        """Control-plane lever: the policy engine reacts to a
        ``supervisor_rollbacks`` burn-rate alert by tightening the
        promote floor, so a regressing refit stream has to clear a
        higher quality bar before the next promote.  Mutates
        ``self.config.tpu_promote_min_delta``, which ``_tick_shadow``
        reads fresh every tick.  Returns the (name, fn) pairs so
        ``stop()`` can unbind them."""
        if not bool(getattr(self.config, "tpu_policy", False)):
            return None
        from ..control import default_actuator

        def tighten_promote_floor(args):
            factor = float(args.get("factor", 2.0))
            floor = float(args.get("min_delta", 0.0))
            old = float(self.config.tpu_promote_min_delta)
            new = max(old * factor, floor)
            self.config.tpu_promote_min_delta = new
            return "promote floor %.6g -> %.6g" % (old, new)

        act = default_actuator()
        levers = [("tighten_promote_floor", tighten_promote_floor)]
        for name, fn in levers:
            act.bind(name, fn)
        return levers

    # -- ingest (HTTP + in-process edge) -------------------------------- #
    def ingest(self, rows, labels=None, weights=None):
        """Feed fresh labeled rows.  Returns (accepted, shed); malformed
        blocks/rows are shed with the obs counter, never an exception —
        a poisoned producer cannot crash the loop."""
        try:
            X = np.asarray(rows, np.float64)
            n_in = int(X.shape[0]) if X.ndim == 2 else 1
            # IngestBuffer serializes internally; no supervisor lock here
            accepted = self.buffer.add(  # tpulint: ok=lock-unguarded-write
                X, labels, weights)
            return accepted, n_in - accepted
        except (IngestError, ValueError, TypeError) as exc:
            try:
                n_in = int(np.asarray(rows, np.float64).shape[0])
            except Exception:  # noqa: BLE001 — unparseable payload
                n_in = 0
            log.warning("supervisor: shed ingest block (%s)", exc)
            return 0, n_in

    # -- lifecycle ------------------------------------------------------ #
    def start(self, poll_s: Optional[float] = None) -> None:
        poll = poll_s if poll_s is not None else min(
            1.0, self.config.tpu_refit_interval_s / 4.0)

        def _loop():
            while not self._stop_event.wait(poll):
                try:
                    self.tick()
                except Exception as exc:  # noqa: BLE001 — loop must survive
                    log.warning("supervisor tick failed: %s", exc)
        with self._state_lock:
            if self._thread is not None:
                return
            self._stop_event.clear()
            self._thread = thread = threading.Thread(
                target=_loop, name="lgbm-supervisor", daemon=True)
        thread.start()

    def stop(self, timeout_s: float = 10.0) -> None:
        self._stop_event.set()
        with self._state_lock:
            thread, self._thread = self._thread, None
            mirror, self._mirror = self._mirror, None
            levers, self._policy_levers = self._policy_levers, None
        if thread is not None:
            thread.join(timeout=timeout_s)
        if mirror is not None:
            self.server.detach_shadow(self.name)
        if levers:
            from ..control import default_actuator
            act = default_actuator()
            for name, fn in levers:
                act.unbind(name, fn)

    # -- the state machine ---------------------------------------------- #
    def tick(self, now: Optional[float] = None) -> str:
        """One synchronous step; returns the state after the step."""
        with self._tick_lock:
            now = time.monotonic() if now is None else now
            state = self.state
            if state == IDLE:
                self._tick_idle(now)
            elif state == SHADOW:
                self._tick_shadow(now)
            elif state == WATCH:
                self._tick_watch(now)
            return self.state

    def _set_state(self, state: str) -> None:
        with self._state_lock:
            self.state = state

    def _tick_idle(self, now: float) -> None:
        cfg = self.config
        if now - self._last_refit_t < cfg.tpu_refit_interval_s:
            return
        if self.buffer.train_rows() < cfg.tpu_refit_min_rows:
            return
        self._build_candidate(now)

    def _build_candidate(self, now: float) -> None:
        cfg = self.config
        self._set_state(REFIT)
        self._persist()
        taken = self.buffer.take_training()
        if taken is None:
            self._set_state(IDLE)
            return
        X, y, w, upto = taken
        self._chaos_kill_refit()
        live = self.server.registry.get(self.name)
        t0 = time.monotonic()
        try:
            if cfg.tpu_refit_mode == "continue":
                cand = self._continue_candidate(live.booster, X, y, w)
            else:
                cand = live.booster.refit(
                    X, y, decay_rate=cfg.refit_decay_rate, weight=w)
        except Exception as exc:  # noqa: BLE001 — a bad refit sheds, not dies
            log.warning("supervisor: candidate build failed (%s); rows stay "
                        "buffered for the next interval", exc)
            with self._state_lock:
                self._last_refit_t = now
                self.state = IDLE
            self._persist()
            return
        cand._gbdt._sync_model()
        cand_str = cand.model_to_string()
        # durability order: candidate first, then the watermark, then the
        # spool trim — a kill between any two steps replays, never loses
        atomic_write_text(os.path.join(self.root, CANDIDATE_FILE), cand_str)
        with self._state_lock:
            self._candidate = cand
            self._cand_built_t = time.monotonic()
            self._cand_consumed_upto = upto
            self._refits += 1
            self._last_refit_t = now
            self.state = SHADOW
            self._shadow_deadline = now + 20.0 * cfg.tpu_refit_interval_s
            self._last_shadow = None
        self._persist()
        self.buffer.discard_upto(upto)
        self._attach_mirror(cand)
        default_registry().counter(
            "lgbm_supervisor_refits_total",
            help="Candidate models built by the supervisor",
            model=self.name).inc()
        supervisor_event(self.config, "refit", model=self.name,
                         mode=cfg.tpu_refit_mode, rows=int(X.shape[0]),
                         live_version=live.version,
                         num_trees=cand.num_trees(),
                         build_s=round(time.monotonic() - t0, 3))

    def _continue_candidate(self, live_booster: Booster, X, y, w) -> Booster:
        """Continued training: new trees fit on the buffer with the live
        model's raw predictions as init_score, then grafted onto a copy
        of the live ensemble (raw scores add exactly, so the merged model
        is servable standalone — engine.train's init_model output alone
        carries only the NEW trees)."""
        cfg = self.config
        params = dict(self.train_params)
        ref = self.base_dataset if (
            self.base_dataset is not None
            and getattr(self.base_dataset, "_binned", None) is not None) \
            else None
        with log.keep_level():      # train_params' verbosity is ours alone
            ds = Dataset(X, label=y, weight=w, params=params, reference=ref)
            new = engine.train(params, ds,
                               num_boost_round=cfg.tpu_refit_rounds,
                               init_model=live_booster, verbose_eval=False)
            new._gbdt._sync_model()
            merged = Booster(model_str=live_booster.model_to_string(),
                             params=params)
        merged._gbdt.models.extend(new._gbdt.models)
        return merged

    def _attach_mirror(self, cand: Booster) -> None:
        from ..serving.shadow import ShadowMirror
        mirror = ShadowMirror(self.name, cand)
        with self._state_lock:
            self._mirror = mirror
        self.server.attach_shadow(self.name, mirror)

    def _tick_shadow(self, now: float) -> None:
        cfg = self.config
        win = self.buffer.window()
        samples = 0 if win is None else int(win[0].shape[0])
        if samples < cfg.tpu_promote_min_samples:
            if (self._shadow_deadline is not None
                    and now > self._shadow_deadline):
                self._reject("shadow_window_starved", samples)
            return
        X, y, w = win
        live = self.server.registry.get(self.name)
        live_loss = _loss(live.booster, X, y, w, self.objective)
        cand_loss = _loss(self._candidate, X, y, w, self.objective)
        delta = live_loss - cand_loss
        mirror_snap = self._mirror.snapshot() if self._mirror else None
        with self._state_lock:
            self._last_shadow = {
                "samples": samples, "live_loss": live_loss,
                "cand_loss": cand_loss, "delta": delta,
                "mirror": mirror_snap}
        self._shadow_gauge.set(delta)
        supervisor_event(self.config, "shadow", model=self.name,
                         samples=samples, live_loss=live_loss,
                         cand_loss=cand_loss, delta=delta,
                         mirror_rows=(mirror_snap or {}).get("rows", 0))
        if delta > cfg.tpu_promote_min_delta:
            self._promote(live, live_loss, now)
        else:
            self._reject("below_floor", samples, delta=delta)

    def _promote(self, live_entry, live_loss: float, now: float,
                 forced: bool = False) -> None:
        cfg = self.config
        cand = self._candidate
        entry = self.server.load_model(
            self.name, model_str=cand.model_to_string())
        self.server.detach_shadow(self.name)
        shadow = self._last_shadow or {}
        with self._state_lock:
            self._mirror = None
            self._candidate = None
            self._cand_built_t = None
            self._promotes += 1
            # rollback floor: what the DEMOTED model achieved — a
            # promotion that then does worse than the model it replaced
            # is exactly the breach the watch window exists to catch
            self._baseline = live_loss
            self._watch_deadline = now + cfg.tpu_promote_watch_s
            self._watch_from_seq = self.buffer.current_seq()
            self.state = WATCH
        self._persist()
        default_registry().counter(
            "lgbm_supervisor_promotes_total",
            help="Candidates promoted to live",
            model=self.name).inc()
        supervisor_event(self.config, "promote", model=self.name,
                         version=entry.version,
                         prior_version=live_entry.version,
                         delta=shadow.get("delta"),
                         samples=shadow.get("samples"),
                         baseline_loss=live_loss, forced=forced)
        log.info("supervisor: promoted %s v%d -> v%d (shadow delta %s)",
                 self.name, live_entry.version, entry.version,
                 shadow.get("delta"))

    def _reject(self, why: str, samples: int, **fields) -> None:
        self.server.detach_shadow(self.name)
        with self._state_lock:
            self._mirror = None
            self._candidate = None
            self._cand_built_t = None
            self.state = IDLE
        self._persist()
        supervisor_event(self.config, "reject", model=self.name,
                         why=why, samples=samples, **fields)
        log.info("supervisor: candidate for %s rejected (%s)", self.name,
                 why)

    def _tick_watch(self, now: float) -> None:
        cfg = self.config
        win = self.buffer.window(after_seq=self._watch_from_seq)
        samples = 0 if win is None else int(win[0].shape[0])
        breached = False
        live_loss = None
        if samples >= min(cfg.tpu_promote_min_samples, 32):
            X, y, w = win
            live = self.server.registry.get(self.name)
            live_loss = _loss(live.booster, X, y, w, self.objective)
            if self._baseline is None or not np.isfinite(self._baseline):
                # forced promote before any labeled window existed: the
                # demoted model is still warm in the registry — score it
                # on the same rows so the floor is what it WOULD achieve
                prior = self.server.registry.prior_entry(self.name)
                if prior is not None:
                    with self._state_lock:
                        self._baseline = _loss(prior.booster, X, y, w,
                                               self.objective)
            if self._baseline is not None and np.isfinite(self._baseline):
                breached = (live_loss > self._baseline
                            + cfg.tpu_promote_rollback_delta)
        if breached:
            self._rollback(live_loss, samples)
            return
        if now > (self._watch_deadline or now):
            with self._state_lock:
                self.state = IDLE
                self._baseline = None
                self._watch_deadline = None
            self._persist()
            supervisor_event(self.config, "watch", model=self.name,
                             outcome="pass", samples=samples,
                             live_loss=live_loss)

    def _rollback(self, live_loss: float, samples: int) -> None:
        entry = self.server.registry.rollback(self.name)
        baseline = self._baseline
        with self._state_lock:
            self._rollbacks += 1
            self.state = IDLE
            self._baseline = None
            self._watch_deadline = None
        self._persist()
        default_registry().counter(
            "lgbm_supervisor_rollbacks_total",
            help="Automatic post-promotion rollbacks",
            model=self.name).inc()
        supervisor_event(self.config, "rollback", model=self.name,
                         version=entry.version, live_loss=live_loss,
                         baseline_loss=baseline, samples=samples)
        log.warning("supervisor: rolled %s back to v%d (live loss %.6g "
                    "breached baseline %.6g)", self.name, entry.version,
                    live_loss, baseline)

    def force_promote(self, model_str: Optional[str] = None,
                      booster: Optional[Booster] = None) -> None:
        """Skip the quality gate and promote `booster`/`model_str` NOW —
        the bad_promote chaos drill's lever (and an operator override).
        The watch window still applies, so a degraded forced candidate
        is auto-rolled back like any other breach."""
        if (model_str is None) == (booster is None):
            raise ValueError("force_promote needs exactly one of "
                             "model_str / booster")
        if booster is None:
            with log.keep_level():
                booster = Booster(model_str=model_str,
                                  params=dict(self.train_params))
        booster._gbdt._sync_model()
        with self._tick_lock:
            now = time.monotonic()
            live = self.server.registry.get(self.name)
            win = self.buffer.window()
            live_loss = (_loss(live.booster, win[0], win[1], win[2],
                               self.objective) if win is not None
                         else float("inf"))
            with self._state_lock:
                self._candidate = booster
                self._last_shadow = None
            self._promote(live, live_loss, now, forced=True)

    # -- chaos ----------------------------------------------------------- #
    def _chaos_kill_refit(self) -> None:
        """LGBM_TPU_CHAOS=kill_refit:<rank>:<n> — SIGKILL this process at
        the n-th refit, AFTER the buffer snapshot and BEFORE the
        candidate persists: the exact window where a naive loop would
        lose ingested rows."""
        spec = os.environ.get("LGBM_TPU_CHAOS", "")
        if not spec.startswith("kill_refit:"):
            return
        parts = spec.split(":")
        n = int(parts[2]) if len(parts) > 2 else 0
        if self._refits == n:
            log.warning("CHAOS: SIGKILL mid-refit (refit #%d)", n)
            os.kill(os.getpid(), signal.SIGKILL)

    # -- persistence ----------------------------------------------------- #
    def _state_path(self) -> str:
        return os.path.join(self.root, STATE_FILE)

    def _persist(self) -> None:
        with self._state_lock:
            doc = {
                "model": self.name,
                "state": self.state,
                "consumed_upto": self._cand_consumed_upto,
                "refits": self._refits,
                "promotes": self._promotes,
                "rollbacks": self._rollbacks,
                "baseline_loss": self._baseline,
                "watch_from_seq": self._watch_from_seq,
                "objective": self.objective,
                "updated_at": time.time(),
            }
        try:
            atomic_write_text(self._state_path(),
                              json.dumps(doc, indent=1, sort_keys=True))
        except OSError as exc:
            log.warning("supervisor: state persist failed: %s", exc)

    def _restore(self) -> None:
        doc = read_state(self.root)
        if doc is None:
            self.buffer.restore(-1)
            return
        consumed = int(doc.get("consumed_upto", -1))
        state = doc.get("state", IDLE)
        restored = self.buffer.restore(
            consumed if state in (SHADOW, WATCH) else -1)
        with self._state_lock:
            self._refits = int(doc.get("refits", 0))
            self._promotes = int(doc.get("promotes", 0))
            self._rollbacks = int(doc.get("rollbacks", 0))
            self._cand_consumed_upto = consumed
        resumed_as = IDLE
        if state == SHADOW:
            # the persisted candidate resumes its shadow audition
            cand_path = os.path.join(self.root, CANDIDATE_FILE)
            if os.path.exists(cand_path):
                try:
                    with open(cand_path) as f, log.keep_level():
                        cand = Booster(model_str=f.read(),
                                       params=dict(self.train_params))
                    with self._state_lock:
                        self._candidate = cand
                        self._cand_built_t = time.monotonic()
                        self.state = SHADOW
                        self._shadow_deadline = (
                            time.monotonic()
                            + 20.0 * self.config.tpu_refit_interval_s)
                    self._attach_mirror(cand)
                    resumed_as = SHADOW
                except Exception as exc:  # noqa: BLE001 — stale candidate
                    log.warning("supervisor: candidate restore failed "
                                "(%s); back to idle", exc)
        elif state == WATCH and doc.get("baseline_loss") is not None:
            with self._state_lock:
                self.state = WATCH
                self._baseline = float(doc["baseline_loss"])
                self._watch_deadline = (time.monotonic()
                                        + self.config.tpu_promote_watch_s)
                self._watch_from_seq = int(doc.get("watch_from_seq", -1))
            resumed_as = WATCH
        # REFIT means we died mid-build: the spool replayed above, the
        # next interval rebuilds the candidate — zero ingest loss
        supervisor_event(self.config, "resume", model=self.name,
                         persisted_state=state, resumed_state=resumed_as,
                         restored_rows=restored, refits=self._refits)
        log.info("supervisor: restored state=%s -> %s (%d spooled rows)",
                 state, resumed_as, restored)

    # -- observability ---------------------------------------------------- #
    def _candidate_age(self) -> float:
        t = self._cand_built_t
        return time.monotonic() - t if t is not None else 0.0

    def snapshot(self) -> Dict:
        try:
            version = self.server.registry.get(self.name).version
        except KeyError:
            version = None
        with self._state_lock:
            return {
                "model": self.name,
                "state": self.state,
                "live_version": version,
                "buffer_rows": self.buffer.train_rows(),
                "window_rows": self.buffer.window_rows_count(),
                "shed_overflow_rows": self.buffer.shed_overflow_rows(),
                "refits": self._refits,
                "promotes": self._promotes,
                "rollbacks": self._rollbacks,
                "candidate_age_s": round(self._candidate_age(), 3),
                "last_shadow": self._last_shadow,
                "baseline_loss": self._baseline,
            }


def read_state(root: str) -> Optional[Dict]:
    """Parse `SUPERVISOR.json` under a checkpoint root (shared with
    tools/ckpt_inspect.py); None when absent/unreadable."""
    path = os.path.join(root, STATE_FILE)
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None
