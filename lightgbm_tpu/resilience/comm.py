"""Comm robustness primitives: retry policy, fault injection, liveness.

The reference aborts all ranks when one socket operation fails
(src/network/linkers_socket.cpp has no retry beyond the initial connect
loop).  For a fleet-scale TPU deployment that is the wrong trade: a
transient RST during the find-bin exchange kills a run that would have
retraced hours of XLA compiles on restart.  This module supplies the
pieces `parallel/distributed.SocketComm` wraps around its wire ops:

- ``RetryPolicy``       exponential backoff + jitter with a bounded budget
- ``FaultInjector``     deterministic chaos hook (fail/delay/drop/partition/
                        kill), used by tests and tools/chaos_run.py
- ``CommFailure``       typed abort naming the dead peer rank
- ``WorldChangedError`` typed abort meaning "the MEMBERSHIP is wrong, not
                        the wire" — re-form the world instead of retrying
- ``Heartbeat``         background rank-liveness probe thread with
                        consecutive-miss suspicion (flap suppression)

Retry semantics are whole-frame: an operation that fails before its
frame hits the wire (connection refused, peer reset, injected fault)
retries cleanly; a peer that stays dead exhausts the budget and raises
``CommFailure`` carrying the peer rank, the operation name and the last
underlying error.  Retries and aborts are counted in the process-wide
obs registry (``lgbm_comm_retries_total`` / ``lgbm_comm_failures_total``)
so they surface in /metrics scrapes and TrainingRecorder events.
"""
from __future__ import annotations

import os
import random
import signal
import threading
import time
from typing import Callable, Dict, Iterable, List, Optional

from ..utils import log


class WorldChangedError(ConnectionError):
    """The comm world's MEMBERSHIP changed: a peer was fenced (poison
    frame / suspicion timeout), this rank itself was fenced by the
    survivors, or a frame arrived stamped with a stale generation.

    Retrying the wire op is pointless — the fix is topology-level:
    tear the ring down and re-form it (resilience.elastic does exactly
    that).  ``dead_ranks`` names the ranks believed gone, ``generation``
    the generation the error was observed under, and ``fenced`` is True
    when THIS rank is the one the survivors cut off.

    The same exception also carries the scale-UP boundary: ``epoch`` is
    True for a DELIBERATE formation epoch (ElasticComm.announce_epoch —
    nobody died, the world is re-forming to ADMIT hosts) and
    ``readmit`` names the ranks the supervisor should put back in its
    alive view before re-forming.
    """

    def __init__(self, message: str, dead_ranks: Iterable[int] = (),
                 generation: int = 0, fenced: bool = False,
                 epoch: bool = False, readmit: Iterable[int] = ()):
        self.dead_ranks = sorted(int(r) for r in dead_ranks)
        self.generation = int(generation)
        self.fenced = bool(fenced)
        self.epoch = bool(epoch)
        self.readmit = sorted(int(r) for r in readmit)
        super().__init__("%s (dead=%s, generation=%d%s%s)"
                         % (message, self.dead_ranks, self.generation,
                            ", self-fenced" if fenced else "",
                            ", epoch readmit=%s" % self.readmit
                            if epoch else ""))


class CommFailure(ConnectionError):
    """A comm operation exhausted its retry budget against one peer.

    Carries enough to act on: ``rank`` (the peer observed dead), ``op``
    (send/recv/allgather), ``attempts`` and the last underlying error.
    """

    def __init__(self, op: str, rank: int, attempts: int,
                 cause: Optional[BaseException] = None):
        self.op = op
        self.rank = int(rank)
        self.attempts = int(attempts)
        self.cause = cause
        super().__init__(
            "comm %s failed against rank %d after %d attempt(s): %s"
            % (op, rank, attempts, cause))


class RetryPolicy:
    """Bounded exponential backoff with jitter.

    ``retries`` is the number of RE-tries after the first attempt, so a
    policy with retries=4 makes at most 5 attempts.  Delay for attempt
    ``n`` (1-based) is ``base_ms * 2**(n-1)`` capped at ``max_ms``, then
    scaled by a uniform jitter in [0.5, 1.0] so a whole fleet retrying
    the same dead hub does not thundering-herd in lockstep.  Jitter
    affects timing only — never training output — so the seeded RNG here
    has no bearing on model determinism.
    """

    def __init__(self, retries: int = 4, base_ms: float = 50.0,
                 max_ms: float = 2000.0, jitter: float = 0.5,
                 seed: Optional[int] = None):
        self.retries = max(int(retries), 0)
        self.base_ms = max(float(base_ms), 0.0)
        self.max_ms = max(float(max_ms), self.base_ms)
        self.jitter = min(max(float(jitter), 0.0), 1.0)
        self._rng = random.Random(seed)

    def backoff_s(self, attempt: int) -> float:
        """Sleep before retry `attempt` (1-based), in seconds."""
        raw = min(self.base_ms * (2.0 ** max(attempt - 1, 0)), self.max_ms)
        scale = 1.0 - self.jitter * self._rng.random()
        return raw * scale / 1e3

    @classmethod
    def from_config(cls, config) -> "RetryPolicy":
        return cls(retries=getattr(config, "tpu_comm_retries", 4),
                   base_ms=getattr(config, "tpu_comm_backoff_ms", 50.0),
                   max_ms=getattr(config, "tpu_comm_backoff_max_ms", 2000.0))


class FaultInjector:
    """Deterministic chaos hook for the comm layer, used by tests and
    tools/chaos_run.py.

    Armed per (operation name); ``check(op)`` is called by SocketComm
    immediately before the real wire operation and either raises (fail),
    sleeps (delay), tells the caller to silently lose the frame (drop),
    or terminates the process outright (kill — SIGKILL, so no cleanup
    handler can soften the failure the survivors must ride out).
    ``count=-1`` arms a fault forever: ``partition`` is sugar for an
    infinite drop, the network-partition model where every frame to/from
    this rank vanishes but the process stays up.  Unarmed operations
    cost one dict lookup.

        inj = FaultInjector()
        inj.fail("allgather", count=2)        # next 2 allgathers raise
        inj.delay("send", count=1, seconds=0.2)
        inj.drop("send", count=1)             # frame silently lost
        inj.partition("send")                 # every frame lost, forever
        inj.kill("allgather", after=3)        # 4th allgather: SIGKILL
        comm = SocketComm(..., injector=inj)
    """

    OK, DROP = "ok", "drop"

    def __init__(self):
        self._lock = threading.Lock()
        self._faults: Dict[str, List[dict]] = {}
        self.injected = 0

    def fail(self, op: str, count: int = 1,
             exc_factory: Optional[Callable[[], BaseException]] = None) -> None:
        self._arm(op, {"kind": "fail", "count": int(count),
                       "exc": exc_factory})

    def delay(self, op: str, count: int = 1, seconds: float = 0.05) -> None:
        self._arm(op, {"kind": "delay", "count": int(count),
                       "seconds": float(seconds)})

    def drop(self, op: str, count: int = 1) -> None:
        self._arm(op, {"kind": "drop", "count": int(count)})

    def partition(self, op: str) -> None:
        """Permanent silent frame loss on `op` — the process stays alive
        but is unreachable through this operation (network partition)."""
        self._arm(op, {"kind": "drop", "count": -1})

    def kill(self, op: str, after: int = 0) -> None:
        """SIGKILL this process on the (after+1)-th `op`.  The real
        rank-death fault: no exception propagates, no socket is closed
        gracefully — peers see RST/EOF, exactly like an OOM-kill or a
        preempted VM."""
        if after > 0:
            self._arm(op, {"kind": "noop", "count": int(after)})
        self._arm(op, {"kind": "kill", "count": 1})

    def reset(self) -> None:
        with self._lock:
            self._faults.clear()

    def armed(self, op: Optional[str] = None) -> bool:
        with self._lock:
            if op is None:
                return any(self._faults.values())
            return bool(self._faults.get(op))

    def _arm(self, op: str, fault: dict) -> None:
        with self._lock:
            self._faults.setdefault(op, []).append(fault)

    def check(self, op: str) -> str:
        """Consume one armed fault for `op`.  Returns OK or DROP; raises
        for fail faults (a ConnectionError by default, so the retry loop
        treats it exactly like a real transient wire error).  A count of
        -1 never depletes (partition)."""
        with self._lock:
            queue = self._faults.get(op)
            if not queue:
                return self.OK
            fault = queue[0]
            if fault["count"] > 0:
                fault["count"] -= 1
                if fault["count"] <= 0:
                    queue.pop(0)
            self.injected += 1
        kind = fault["kind"]
        if kind == "noop":
            return self.OK
        if kind == "delay":
            time.sleep(fault["seconds"])
            return self.OK
        if kind == "drop":
            return self.DROP
        if kind == "kill":
            log.warning("fault injector: SIGKILL on %s", op)
            os.kill(os.getpid(), signal.SIGKILL)
            time.sleep(60)  # unreachable; keep the op blocked while dying
        exc_factory = fault.get("exc")
        raise (exc_factory() if exc_factory is not None
               else ConnectionError("injected fault: %s" % op))


class Heartbeat:
    """Rank-liveness monitor: a daemon thread calling ``probe()`` every
    ``interval_s`` seconds.  ``probe`` returns the list of peer ranks
    currently UNRESPONSIVE this round (SocketComm supplies a passive
    socket health check; ElasticComm an active ping/pong age check).

    Suspicion, not reflex: a rank is only declared dead after
    ``suspect_after`` CONSECUTIVE unresponsive rounds, so a single
    missed probe — GC pause, packet loss, a briefly saturated NIC —
    never flaps the world (detection latency is therefore bounded by
    ``interval_s * suspect_after`` plus one probe).  A suspect that
    answers again before conviction has its miss count reset, and a
    CONVICTED rank that comes back (transient stall, partition healed)
    is un-declared: the ``lgbm_comm_alive_ranks`` gauge recovers.

    ``on_change(dead_set)`` fires on every conviction-set transition —
    ElasticComm fences + poisons from it; tests observe it.
    """

    def __init__(self, probe: Callable[[], List[int]], interval_s: float,
                 rank: int = 0, world: int = 1, registry=None,
                 suspect_after: int = 1,
                 on_change: Optional[Callable[[set], None]] = None):
        self.probe = probe
        self.interval_s = max(float(interval_s), 1e-3)
        self.rank, self.world = int(rank), int(world)
        self.suspect_after = max(int(suspect_after), 1)
        self.on_change = on_change
        self._dead: set = set()
        self._misses: Dict[int, int] = {}
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._gauge = None
        self._miss_gauge = None
        if registry is not None:
            self._gauge = registry.gauge(
                "lgbm_comm_alive_ranks",
                help="Ranks the heartbeat currently considers alive",
                rank=str(rank), world=str(world))
            self._gauge.set(world)
            # worst consecutive-miss streak across peers: the alert
            # engine's heartbeat_miss rule watches this — it climbs
            # BEFORE conviction flips alive_ranks
            self._miss_gauge = registry.gauge(
                "lgbm_comm_heartbeat_miss_streak",
                help="Max consecutive missed heartbeat probes over peers",
                rank=str(rank), world=str(world))
            self._miss_gauge.set(0)

    def start(self) -> "Heartbeat":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._run, name="lgbm-heartbeat", daemon=True)
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2 * self.interval_s + 1.0)
            self._thread = None
        if self._miss_gauge is not None:
            # a stopped monitor watches no peer: a streak left in the
            # process-wide registry would keep the alert engine's
            # heartbeat_miss rule firing on every later server of the process
            self._miss_gauge.set(0)

    def dead_ranks(self) -> List[int]:
        return sorted(self._dead)

    def suspect_ranks(self) -> List[int]:
        """Ranks with at least one miss but not yet convicted."""
        return sorted(r for r, m in self._misses.items()
                      if 0 < m < self.suspect_after and r not in self._dead)

    def alive(self) -> bool:
        return not self._dead

    def poll_once(self) -> List[int]:
        """One probe round (also what the thread loop runs)."""
        try:
            missing = set(self.probe())
        except Exception as exc:  # noqa: BLE001 — liveness must not raise
            log.debug("heartbeat probe failed: %s", exc)
            return self.dead_ranks()
        for r in missing:
            self._misses[r] = self._misses.get(r, 0) + 1
        for r in list(self._misses):
            if r not in missing:
                self._misses[r] = 0
        dead = {r for r, m in self._misses.items()
                if m >= self.suspect_after}
        for r in sorted(dead - self._dead):
            log.warning("heartbeat: rank %d declared dead after %d "
                        "consecutive missed probe(s)", r, self._misses[r])
        for r in sorted(self._dead - dead):
            log.warning("heartbeat: rank %d responded again — liveness "
                        "restored", r)
        changed = dead != self._dead
        self._dead = dead
        if self._gauge is not None:
            self._gauge.set(self.world - len(dead))
        if self._miss_gauge is not None:
            self._miss_gauge.set(max(self._misses.values(), default=0))
        if changed and self.on_change is not None:
            try:
                self.on_change(set(dead))
            except Exception as exc:  # noqa: BLE001 — liveness must not raise
                log.warning("heartbeat on_change callback failed: %s", exc)
        return self.dead_ranks()

    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            self.poll_once()
