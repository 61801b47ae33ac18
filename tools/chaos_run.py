"""Chaos driver for elastic distributed training (resilience/elastic.py).

Launches a REAL multi-process world on localhost, injures one rank
mid-training, and verifies the survivors detect the failure, re-form at
the reduced world size, resume from the newest checkpoint and finish —
printing one JSON summary with the measured recovery time.

    python tools/chaos_run.py --scenario kill_rank          # SIGKILL
    python tools/chaos_run.py --scenario slow_rank          # hang > suspect
    python tools/chaos_run.py --scenario partition          # ctrl cut
    python tools/chaos_run.py --scenario kill_hub           # kill rank 0
    python tools/chaos_run.py --scenario mesh_unavailable   # backend fallback
    python tools/chaos_run.py --scenario none               # control run
    python tools/chaos_run.py --scenario kill_rank --fast   # CI smoke

Two continuous-learning drills ride the same driver against the
serving supervisor (resilience/supervisor.py) instead of the elastic
trainer:

    python tools/chaos_run.py --scenario kill_refit   # SIGKILL mid-refit
    python tools/chaos_run.py --scenario bad_promote  # forced rollback

One fleet-residency drill hammers a 64-tenant model fleet through a
byte budget sized for 8 resident models (serving/fleet.py), killing
promotions mid-flight:

    python tools/chaos_run.py --scenario tenant_storm

Two hybrid-topology drills run a multi-host world where every host
process carries its own local device mesh (parallel/hybrid.py) — the
fault domain is the whole host, not a single device:

    python tools/chaos_run.py --scenario kill_host   # SIGKILL one mesh's host
    python tools/chaos_run.py --scenario slow_host   # leader lag: slow, not dead

kill_host requires the surviving hosts to re-form, resume from the
newest checkpoint and finish with bitwise-identical models on every
survivor.  slow_host delays one host's leader phase every round; the
hub must mark it *slow* (a hybrid_slow telemetry event) without ever
convicting it — all hosts finish at full world, models identical.

One closed-loop control-plane drill exercises the policy engine
(lightgbm_tpu/control/) end to end — alert-driven demote, rejoin
petition, elastic scale-UP back to full world, plus the dry-run
bitwise-identity contract against a policy-off control leg:

    python tools/chaos_run.py --scenario policy_loop

Exit code 0 iff the scenario's expectations held (survivors completed
at the expected world size with a usable model).  The injury rides the
LGBM_TPU_CHAOS env hook (kind:orig_rank:round[:secs]) the supervisor's
sync callback honours at generation 0.
"""
import argparse
import json
import multiprocessing as mp
import os
import socket
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def _free_port() -> int:
    s = socket.socket()  # tpulint: ok=socket-no-with
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _data(n: int, f: int = 8, seed: int = 7):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.3 * X[:, 1] > 0).astype(np.float64)
    return X, y


def _worker(orig_rank, machines, params, n_rows, rounds, q):
    """One rank's process: build the shared synthetic dataset and run
    the supervisor; report the outcome on the queue."""
    from lightgbm_tpu.resilience.elastic import (ElasticAborted,
                                                 ElasticFenced,
                                                 ElasticSupervisor)
    X, y = _data(n_rows)
    sup = ElasticSupervisor(dict(params), X, y, orig_rank=orig_rank,
                            machines=machines, num_boost_round=rounds,
                            port_offset=0, timeout_s=30.0)
    try:
        r = sup.run()
        q.put((orig_rank, {
            "outcome": "complete", "rank": r.rank, "world": r.world,
            "generation": r.generation, "reforms": r.reforms,
            "dead_ranks": r.dead_ranks,
            "recovery_s": round(r.recovery_s, 3),
            "num_trees": r.booster.num_trees(),
        }))
    except ElasticFenced as e:
        q.put((orig_rank, {"outcome": "fenced", "error": str(e)}))
    except ElasticAborted as e:
        q.put((orig_rank, {"outcome": "aborted", "error": str(e)}))


SCENARIOS = ("kill_rank", "kill_hub", "slow_rank", "partition",
             "mesh_unavailable", "none")
# hybrid-topology drills (parallel/hybrid.py): hosts × local devices,
# dispatched to run_hybrid_scenario
HYBRID_SCENARIOS = ("kill_host", "slow_host")
# continuous-learning drills (resilience/supervisor.py), dispatched to
# run_supervisor_scenario instead of the elastic world driver
SUPERVISOR_SCENARIOS = ("kill_refit", "bad_promote")
# fleet-residency drill (serving/fleet.py)
FLEET_SCENARIOS = ("tenant_storm",)
# closed-loop control-plane drill (control/ + elastic scale-up)
POLICY_SCENARIOS = ("policy_loop",)
# replicated-serving drill (serving/replicas.py)
REPLICA_SCENARIOS = ("kill_device",)


def run_scenario(scenario: str, world: int = 3, rounds: int = 8,
                 n_rows: int = 240, chaos_round: int = 3,
                 join_timeout_s: float = 120.0) -> dict:
    """Run one chaos scenario; returns the summary dict (see main)."""
    assert scenario in SCENARIOS, scenario
    victim = {"kill_rank": world - 1, "kill_hub": 0,
              "slow_rank": world - 1, "partition": world - 1}.get(scenario)
    tmp = tempfile.mkdtemp(prefix="lgbm_chaos_")
    machines = ",".join("127.0.0.1:%d" % _free_port() for _ in range(world))
    params = {
        "objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
        "verbosity": -1,
        "num_machines": world, "machines": machines,
        "tree_learner": "data", "pre_partition": True,
        "tpu_elastic": True,
        "tpu_elastic_heartbeat_ms": 100.0, "tpu_elastic_suspect_ms": 500.0,
        # min_world=2 is the quorum knob: a stalled/partitioned victim
        # that never heard the poison aborts instead of re-forming a
        # zombie world of one (the split-brain caveat in Elasticity.md)
        "tpu_elastic_rejoin_s": 1.0,
        "tpu_elastic_min_world": max(1, min(2, world - 1)),
        "tpu_checkpoint_path": os.path.join(tmp, "ckpts"),
        "tpu_checkpoint_interval": 1,
    }
    telemetry = None
    if scenario == "mesh_unavailable":
        # backend-fallback drill: every rank ASKS for the mesh backend
        # while the chaos hook makes the device mesh report empty;
        # training must fall back to the socket collective cleanly and
        # say so via the recorder's comm_backend telemetry event
        telemetry = os.path.join(tmp, "telemetry.jsonl")
        params["tpu_comm_backend"] = "mesh"
        params["tpu_telemetry_path"] = telemetry
    env_chaos = None
    if scenario in ("kill_rank", "kill_hub"):
        env_chaos = "kill:%d:%d" % (victim, chaos_round)
    elif scenario == "slow_rank":
        env_chaos = "slow:%d:%d:%.1f" % (victim, chaos_round, 20.0)
    elif scenario == "partition":
        env_chaos = "partition:%d:%d:%.1f" % (victim, chaos_round, 20.0)
    elif scenario == "mesh_unavailable":
        # rank -1 never matches, so no rank self-injures; only the kind
        # prefix matters (collective._mesh_devices_available reads it)
        env_chaos = "mesh_unavailable:-1:0"
    if env_chaos is not None:
        os.environ["LGBM_TPU_CHAOS"] = env_chaos
    else:
        os.environ.pop("LGBM_TPU_CHAOS", None)
    try:
        ctx = mp.get_context("spawn")
        q = ctx.Queue()
        mlist = machines.split(",")
        procs = [ctx.Process(target=_worker,
                             args=(r, mlist, params, n_rows, rounds, q))
                 for r in range(world)]
        t0 = time.monotonic()
        for p in procs:
            p.start()
        results = {}
        deadline = time.monotonic() + join_timeout_s
        # wait for the survivors only; a stalled victim's abort report
        # can arrive minutes later and is informational
        want = world if victim is None else world - 1
        while len(results) < want and time.monotonic() < deadline:
            try:
                rank, out = q.get(timeout=1.0)
                results[rank] = out
            except Exception:   # noqa: BLE001 — queue.Empty
                if not any(p.is_alive() for p in procs):
                    break
        total_s = time.monotonic() - t0
        for p in procs:
            p.join(timeout=5.0)
            if p.is_alive():
                p.terminate()
    finally:
        os.environ.pop("LGBM_TPU_CHAOS", None)
    completed = {r: o for r, o in results.items()
                 if o.get("outcome") == "complete"}
    fenced = sorted(r for r, o in results.items()
                    if o.get("outcome") == "fenced")
    expect_world = world if victim is None else world - 1
    ok = bool(completed) and all(
        o["world"] == expect_world and o["num_trees"] >= rounds
        for o in completed.values())
    if victim is not None:
        ok = ok and all(o["reforms"] >= 1 and victim in o["dead_ranks"]
                        for o in completed.values())
    backend_events = None
    if telemetry is not None:
        # the drill's observable: every rank REQUESTED mesh but trained
        # on the socket backend (make_collective's comm_backend event)
        backend_events = []
        try:
            with open(telemetry) as f:
                for line in f:
                    ev = json.loads(line)
                    if ev.get("event") == "comm_backend":
                        backend_events.append(ev)
        except (OSError, ValueError):
            pass
        ok = ok and any(e.get("requested") == "mesh"
                        and e.get("backend") == "socket"
                        for e in backend_events)
    recovery = max((o.get("recovery_s", 0.0)
                    for o in completed.values()), default=None)
    return {
        "scenario": scenario, "world": world, "victim": victim,
        "rounds": rounds, "ok": ok, "final_world": expect_world,
        "completed_ranks": sorted(completed),
        "fenced_ranks": fenced,
        "recovery_s": recovery,
        "total_s": round(total_s, 3),
        "comm_backend_events": backend_events,
        "results": results,
    }


def _hybrid_worker(orig_rank, machines, params, n_rows, rounds, local, q):
    """One HOST's process in a hybrid world: force `local` CPU devices
    so this process carries a real local mesh, then run the elastic
    supervisor with the hybrid backend.  Reports a model digest so the
    driver can assert bitwise agreement across hosts."""
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=%d" % local)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from lightgbm_tpu.resilience.elastic import (ElasticAborted,
                                                 ElasticFenced,
                                                 ElasticSupervisor)
    X, y = _data(n_rows)
    sup = ElasticSupervisor(dict(params), X, y, orig_rank=orig_rank,
                            machines=machines, num_boost_round=rounds,
                            port_offset=0, timeout_s=30.0)
    try:
        r = sup.run()
        import hashlib
        digest = hashlib.sha256(
            r.booster.model_to_string().encode("utf-8")).hexdigest()[:16]
        q.put((orig_rank, {
            "outcome": "complete", "rank": r.rank, "world": r.world,
            "generation": r.generation, "reforms": r.reforms,
            "dead_ranks": r.dead_ranks,
            "recovery_s": round(r.recovery_s, 3),
            "num_trees": r.booster.num_trees(),
            "model_digest": digest,
        }))
    except ElasticFenced as e:
        q.put((orig_rank, {"outcome": "fenced", "error": str(e)}))
    except ElasticAborted as e:
        q.put((orig_rank, {"outcome": "aborted", "error": str(e)}))


def run_hybrid_scenario(scenario: str, hosts: int = 3, local: int = 2,
                        rounds: int = 8, n_rows: int = 240,
                        chaos_round: int = 3,
                        join_timeout_s: float = 180.0) -> dict:
    """Hybrid drills: `hosts` processes, each a whole local mesh of
    `local` devices, composed by the hybrid collective.

    kill_host: SIGKILL one host mid-round.  The whole mesh behind that
    host leaves as one fault domain; survivors must re-form at
    hosts-1, resume from the newest checkpoint and finish with
    bitwise-identical models (same model digest on every survivor).

    slow_host: delay one host's leader phase for a bounded window of
    rounds (the `lag` chaos kind sleeps only in the train thread, so
    heartbeats keep flowing).  The hub must mark the host slow
    (hybrid_slow telemetry event, policy=observe) WITHOUT convicting
    it: every host finishes at full world with identical models and
    zero re-forms.  Federation + alerting run alongside: the round
    ledger must name the victim as the critical host (straggler_wait)
    while it lags, and the straggler_host alert must fire during the
    lag and clear after recovery — all bitwise-invisible to training."""
    assert scenario in HYBRID_SCENARIOS, scenario
    victim = hosts - 1
    tmp = tempfile.mkdtemp(prefix="lgbm_chaos_hyb_")
    telemetry = os.path.join(tmp, "telemetry.jsonl")
    machines = ",".join("127.0.0.1:%d" % _free_port() for _ in range(hosts))
    params = {
        "objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
        "verbosity": -1,
        # boost_from_average stays ON: the init score is now computed
        # from globally-allreduced sufficient stats, so the one-digest
        # assertion must hold with it enabled
        "boost_from_average": True,
        "num_machines": hosts, "machines": machines,
        "tree_learner": "data", "pre_partition": True,
        "tpu_comm_backend": "hybrid", "tpu_hybrid_local_devices": local,
        "tpu_elastic": True,
        "tpu_elastic_heartbeat_ms": 100.0, "tpu_elastic_suspect_ms": 500.0,
        "tpu_elastic_rejoin_s": 1.0,
        "tpu_elastic_min_world": max(1, min(2, hosts - 1)),
        "tpu_checkpoint_path": os.path.join(tmp, "ckpts"),
        "tpu_checkpoint_interval": 1,
        "tpu_telemetry_path": telemetry,
    }
    lag_until = None
    if scenario == "slow_host":
        # federation + alerting ride the drill: the hub must NAME the
        # lagged host in the round ledger and fire/clear the straggler
        # alert, all while staying read-only on training
        lag_until = rounds - 1      # recover before the end: clear must fire
        params.update({
            "tpu_hybrid_slow_ms": 50.0,
            "tpu_hybrid_slow_rounds": 2,
            "tpu_hybrid_slow_policy": "observe",
            "tpu_federation": True,
            "tpu_alert": True,
            "tpu_alert_sustain_rounds": 2,
        })
        env_chaos = "lag:%d:%d:%.1f:%d" % (victim, chaos_round, 0.4,
                                           lag_until)
        expect_world = hosts
    else:
        env_chaos = "kill:%d:%d" % (victim, chaos_round)
        expect_world = hosts - 1
    os.environ["LGBM_TPU_CHAOS"] = env_chaos
    try:
        ctx = mp.get_context("spawn")
        q = ctx.Queue()
        mlist = machines.split(",")
        procs = [ctx.Process(target=_hybrid_worker,
                             args=(r, mlist, params, n_rows, rounds,
                                   local, q))
                 for r in range(hosts)]
        t0 = time.monotonic()
        for p in procs:
            p.start()
        results = {}
        deadline = time.monotonic() + join_timeout_s
        want = expect_world
        while len(results) < want and time.monotonic() < deadline:
            try:
                rank, out = q.get(timeout=1.0)
                results[rank] = out
            except Exception:   # noqa: BLE001 — queue.Empty
                if not any(p.is_alive() for p in procs):
                    break
        total_s = time.monotonic() - t0
        for p in procs:
            p.join(timeout=5.0)
            if p.is_alive():
                p.terminate()
    finally:
        os.environ.pop("LGBM_TPU_CHAOS", None)
    completed = {r: o for r, o in results.items()
                 if o.get("outcome") == "complete"}
    digests = sorted({o.get("model_digest") for o in completed.values()})
    ok = (len(completed) == expect_world and all(
        o["world"] == expect_world and o["num_trees"] >= rounds
        for o in completed.values()) and len(digests) == 1)
    slow_events = []
    backend_events = []
    ledger_events = []
    alert_events = []
    try:
        with open(telemetry) as f:
            for line in f:
                ev = json.loads(line)
                if (ev.get("event") == "elastic"
                        and ev.get("what") == "hybrid_slow"):
                    slow_events.append(ev)
                elif ev.get("event") == "comm_backend":
                    backend_events.append(ev)
                elif ev.get("event") == "round_ledger":
                    ledger_events.append(ev)
                elif ev.get("event") == "alert":
                    alert_events.append(ev)
    except (OSError, ValueError):
        pass
    hybrid_backends = [e for e in backend_events
                       if e.get("backend") == "hybrid"]
    ok = ok and bool(hybrid_backends)
    if scenario == "kill_host":
        ok = ok and all(o["reforms"] >= 1 and victim in o["dead_ranks"]
                        for o in completed.values())
    else:
        # slow, not dead: the victim completed, nobody re-formed, and
        # the hub called the victim out as slow under the observe policy
        ok = (ok and victim in completed
              and all(o["reforms"] == 0 for o in completed.values())
              and any(e.get("slow_host") == victim
                      and e.get("policy") == "observe"
                      for e in slow_events))
        # the ledger must attribute the lag to the victim — via the
        # hub-side straggler wait, BEFORE the slow policy could convict
        ok = ok and any(
            e.get("critical_host") == victim
            and e.get("critical_phase") == "straggler_wait"
            for e in ledger_events
            if chaos_round <= e.get("round", -1) < (lag_until or rounds))
        # and the straggler alert must fire during the lag and clear
        # after recovery
        straggler = [e.get("state") for e in alert_events
                     if e.get("rule") == "straggler_host"]
        ok = ok and straggler == ["firing", "cleared"]
    recovery = max((o.get("recovery_s", 0.0)
                    for o in completed.values()), default=None)
    return {
        "scenario": scenario, "hosts": hosts, "local_devices": local,
        "victim": victim, "rounds": rounds, "ok": ok,
        "final_world": expect_world,
        "completed_ranks": sorted(completed),
        "model_digests": digests,
        "hybrid_slow_events": len(slow_events),
        "round_ledger_events": len(ledger_events),
        "ledger_critical_hosts": sorted({e.get("critical_host")
                                         for e in ledger_events}),
        "alert_transitions": [(e.get("rule"), e.get("state"))
                              for e in alert_events],
        "comm_backend_events": hybrid_backends[:2],
        "recovery_s": recovery,
        "total_s": round(total_s, 3),
        "results": results,
    }


def _run_policy_leg(hosts, local, rounds, n_rows, chaos_round, lag_s,
                    lag_until, policy, dry_run, join_timeout_s):
    """One training run for the policy_loop drill: a hybrid world with
    a lagging victim host, federation + alerting on, and the policy
    engine in the requested mode.  Returns (results, events) where
    events is the parsed telemetry JSONL."""
    victim = hosts - 1
    tmp = tempfile.mkdtemp(prefix="lgbm_chaos_pol_")
    telemetry = os.path.join(tmp, "telemetry.jsonl")
    machines = ",".join("127.0.0.1:%d" % _free_port() for _ in range(hosts))
    params = {
        "objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
        "verbosity": -1, "boost_from_average": True,
        "num_machines": hosts, "machines": machines,
        "tree_learner": "data", "pre_partition": True,
        "tpu_comm_backend": "hybrid", "tpu_hybrid_local_devices": local,
        "tpu_elastic": True,
        "tpu_elastic_heartbeat_ms": 100.0, "tpu_elastic_suspect_ms": 500.0,
        "tpu_elastic_rejoin_s": 1.0,
        "tpu_elastic_min_world": max(1, min(2, hosts - 1)),
        # the scale-up listener stays open in EVERY leg so the dry-run
        # and policy-off runs share the live leg's config shape
        "tpu_elastic_scale_up": True,
        "tpu_elastic_scale_up_wait_s": 60.0,
        "tpu_checkpoint_path": os.path.join(tmp, "ckpts"),
        "tpu_checkpoint_interval": 1,
        "tpu_telemetry_path": telemetry,
        # slow_policy=observe: the straggler DEMOTE must come from the
        # policy engine reacting to the straggler_host alert, not from
        # the in-loop slow-host policy
        "tpu_hybrid_slow_ms": 50.0, "tpu_hybrid_slow_rounds": 2,
        "tpu_hybrid_slow_policy": "observe",
        "tpu_federation": True, "tpu_alert": True,
        "tpu_alert_sustain_rounds": 2,
        "tpu_policy": policy, "tpu_policy_dry_run": dry_run,
    }
    os.environ["LGBM_TPU_CHAOS"] = "lag:%d:%d:%.2f:%d" % (
        victim, chaos_round, lag_s, lag_until)
    try:
        ctx = mp.get_context("spawn")
        q = ctx.Queue()
        mlist = machines.split(",")
        procs = [ctx.Process(target=_hybrid_worker,
                             args=(r, mlist, params, n_rows, rounds,
                                   local, q))
                 for r in range(hosts)]
        for p in procs:
            p.start()
        results = {}
        deadline = time.monotonic() + join_timeout_s
        while len(results) < hosts and time.monotonic() < deadline:
            try:
                rank, out = q.get(timeout=1.0)
                results[rank] = out
            except Exception:   # noqa: BLE001 — queue.Empty
                if not any(p.is_alive() for p in procs):
                    break
        for p in procs:
            p.join(timeout=5.0)
            if p.is_alive():
                p.terminate()
    finally:
        os.environ.pop("LGBM_TPU_CHAOS", None)
    events = []
    try:
        with open(telemetry) as f:
            for line in f:
                try:
                    events.append(json.loads(line))
                except ValueError:
                    pass
    except OSError:
        pass
    return results, events


def run_policy_scenario(scenario: str, hosts: int = 3, local: int = 2,
                        rounds: int = 12, n_rows: int = 240,
                        chaos_round: int = 2,
                        join_timeout_s: float = 180.0) -> dict:
    """policy_loop: the closed-loop control-plane drill, three legs.

    LIVE (tpu_policy=true): a lagging host trips the straggler_host
    alert; the policy engine demotes it (proactive fence + re-shard at
    hosts-1), the now-healthy victim petitions to rejoin, the
    pending_join signal triggers expand_world, and a formation epoch
    re-admits it — every host finishes at FULL world with one shared
    model digest, with recorded policy_action events for both the
    demote and the expansion.

    DRY RUN (tpu_policy_dry_run=true): the same incident is decided
    but nothing is dispatched — no fence, zero re-forms, and the final
    model must be BITWISE identical to the policy-off leg.

    OFF (tpu_policy=false): the control leg the dry run is compared
    against; no policy_action events at all."""
    assert scenario in POLICY_SCENARIOS, scenario
    victim = hosts - 1
    t0 = time.monotonic()
    # live leg: keep lagging until demoted (the lag only fires at
    # generation 0, so the readmitted victim is healthy)
    live_res, live_ev = _run_policy_leg(
        hosts, local, rounds, n_rows, chaos_round, 0.6, rounds,
        policy=True, dry_run=False, join_timeout_s=join_timeout_s)
    # dry-run + off legs: a bounded lag window (the alert must clear),
    # identical in everything except the policy switch
    lag_until = max(chaos_round + 4, rounds - 4)
    dry_res, dry_ev = _run_policy_leg(
        hosts, local, rounds, n_rows, chaos_round, 0.6, lag_until,
        policy=True, dry_run=True, join_timeout_s=join_timeout_s)
    off_res, off_ev = _run_policy_leg(
        hosts, local, rounds, n_rows, chaos_round, 0.6, lag_until,
        policy=False, dry_run=False, join_timeout_s=join_timeout_s)

    def _complete(results):
        return {r: o for r, o in results.items()
                if o.get("outcome") == "complete"}

    def _digests(results):
        return sorted({o.get("model_digest")
                       for o in _complete(results).values()})

    def _policy_actions(events):
        return [e for e in events if e.get("event") == "policy_action"]

    def _alert_states(events, rule):
        return [e.get("state") for e in events
                if e.get("event") == "alert" and e.get("rule") == rule]

    live_c, dry_c, off_c = (_complete(r)
                            for r in (live_res, dry_res, off_res))
    live_actions = _policy_actions(live_ev)
    dry_actions = _policy_actions(dry_ev)
    off_actions = _policy_actions(off_ev)
    elastic_whats = [e.get("what") for e in live_ev
                     if e.get("event") == "elastic"]

    def _elastic_ts(events, what, orig=None):
        return [float(e["ts"]) for e in events
                if e.get("event") == "elastic" and e.get("what") == what
                and e.get("ts") is not None
                and (orig is None or e.get("orig_rank") == orig)]

    # rejoin-latency bound: once the epoch is announced the victim must
    # be back in the world fast — its parked petition connection gets
    # the announcement PUSHED (petition_wake) or its next knock lands
    # straight in the new formation window; either way the victim's
    # "rejoined" event must land within 1.5 s of the first epoch, well
    # under a petition-poll timeout plus back-off.  (petition_wake is
    # reported when the parked path was exercised; the unit tests pin
    # its sub-second push bound deterministically.)
    epoch_ts = _elastic_ts(live_ev, "epoch")
    rejoin_ts = _elastic_ts(live_ev, "rejoined", orig=victim)
    wake_ts = _elastic_ts(live_ev, "petition_wake", orig=victim)
    rejoin_latency = (min(t - min(epoch_ts) for t in rejoin_ts
                          if t >= min(epoch_ts))
                      if epoch_ts and any(t >= min(epoch_ts)
                                          for t in rejoin_ts) else None)
    ok_wake = rejoin_latency is not None and rejoin_latency <= 1.5
    # LIVE: full-world finish through demote -> petition -> epoch, with
    # both actions recorded as dispatched ("ok")
    ok_live = (len(live_c) == hosts and len(_digests(live_res)) == 1
               and all(o["world"] == hosts and o["num_trees"] >= rounds
                       for o in live_c.values())
               and any(a.get("action") == "demote_host"
                       and a.get("status") == "ok"
                       and a.get("args", {}).get("orig") == victim
                       for a in live_actions)
               and any(a.get("action") == "expand_world"
                       and a.get("status") == "ok"
                       for a in live_actions)
               and "petition" in elastic_whats
               and "epoch" in elastic_whats
               and ok_wake
               and "firing" in _alert_states(live_ev, "straggler_host"))
    # DRY RUN: decisions recorded, nothing dispatched, zero re-forms,
    # and the incident plays out exactly like policy-off
    ok_dry = (len(dry_c) == hosts and len(_digests(dry_res)) == 1
              and all(o["reforms"] == 0 for o in dry_c.values())
              and bool(dry_actions)
              and all(a.get("status") == "dry_run" for a in dry_actions)
              and any(a.get("action") == "demote_host"
                      for a in dry_actions)
              and _alert_states(dry_ev, "straggler_host")
              == ["firing", "cleared"])
    # OFF: the control leg — and the dry run is bitwise-identical to it
    ok_off = (len(off_c) == hosts and len(_digests(off_res)) == 1
              and not off_actions
              and _digests(dry_res) == _digests(off_res))
    ok = ok_live and ok_dry and ok_off
    return {
        "scenario": scenario, "hosts": hosts, "local_devices": local,
        "victim": victim, "rounds": rounds, "ok": ok,
        "ok_live": ok_live, "ok_dry_run": ok_dry, "ok_off": ok_off,
        "final_world": hosts,
        "live_digests": _digests(live_res),
        "dry_run_digests": _digests(dry_res),
        "off_digests": _digests(off_res),
        "dry_run_bitwise_identical":
            _digests(dry_res) == _digests(off_res),
        "live_policy_actions": [
            (a.get("rule"), a.get("action"), a.get("status"))
            for a in live_actions],
        "dry_run_policy_actions": [
            (a.get("rule"), a.get("action"), a.get("status"))
            for a in dry_actions],
        "live_elastic_events": elastic_whats,
        "rejoin_latency_s": (round(rejoin_latency, 4)
                             if rejoin_latency is not None else None),
        "rejoin_latency_ok": ok_wake,
        "petition_wakes": len(wake_ts),
        "live_alerts": _alert_states(live_ev, "straggler_host"),
        "dry_run_alerts": _alert_states(dry_ev, "straggler_host"),
        "total_s": round(time.monotonic() - t0, 3),
        "results": {"live": live_res, "dry_run": dry_res, "off": off_res},
    }


def _drift_data(n: int, f: int = 6, seed: int = 11, drift: float = 0.0):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, f)
    y = (X[:, 0] * 2.0 + X[:, 1] + drift * 3.0 * X[:, 2]
         + 0.01 * rng.randn(n))
    return X, y


def _sup_worker(root, model_str, cfg, train_params, n_rows, seed, q):
    """One life of the continuous-learning loop: serve the base model,
    ingest drifted rows, tick until promotion (or death by the
    kill_refit chaos hook, in which case nothing reaches the queue)."""
    from lightgbm_tpu.resilience.supervisor import (
        ContinuousLearningSupervisor)
    from lightgbm_tpu.serving import Server
    srv = Server(verbosity=-1)
    srv.load_model("m", model_str=model_str)
    sup = ContinuousLearningSupervisor(srv, cfg, model_name="m",
                                       train_params=train_params)
    snap = sup.snapshot()
    restored = snap["buffer_rows"] + snap["window_rows"]
    if restored < cfg["tpu_refit_min_rows"]:
        # first life: ingest fresh drifted traffic (spooled before the
        # refit the chaos hook murders, so the second life replays it)
        X, y = _drift_data(n_rows, seed=seed, drift=1.0)
        sup.ingest(X, y)
    Xq, _ = _drift_data(16, seed=99, drift=1.0)
    predict_failures = 0
    state = snap["state"]
    deadline = time.monotonic() + 60.0
    while time.monotonic() < deadline:
        try:
            srv.predict(Xq, model="m")
        except Exception:   # noqa: BLE001 — the drill counts ANY failure
            predict_failures += 1
        state = sup.tick()   # kill_refit SIGKILLs inside this call
        if state == "watch":
            break
        time.sleep(0.05)
    q.put({
        "restored_rows": restored,
        "state": state,
        "version": srv.registry.get("m").version,
        "predict_failures": predict_failures,
        "snapshot": {k: v for k, v in sup.snapshot().items()
                     if k != "last_shadow"},
    })
    srv.shutdown()


def _telemetry_events(path):
    events = []
    try:
        with open(path) as f:
            for line in f:
                ev = json.loads(line)
                if ev.get("event") == "supervisor":
                    events.append(ev)
    except (OSError, ValueError):
        pass
    return events


def run_supervisor_scenario(scenario: str, n_rows: int = 600,
                            join_timeout_s: float = 120.0) -> dict:
    """Continuous-learning drills.

    kill_refit: SIGKILL the serving+supervisor process mid-refit (after
    the training snapshot, before the candidate persists), restart it on
    the same state directory and require the second life to replay every
    spooled row, rebuild the candidate and promote — with zero failed
    client predictions in the surviving life.

    bad_promote: force-promote a deliberately degraded candidate while
    prediction threads hammer the server; the watch loop must roll the
    registry back to the prior version on fresh labeled traffic, again
    with zero failed client predictions.
    """
    assert scenario in SUPERVISOR_SCENARIOS, scenario
    import lightgbm_tpu as lgb
    tmp = tempfile.mkdtemp(prefix="lgbm_chaos_sup_")
    telemetry = os.path.join(tmp, "telemetry.jsonl")
    Xb, yb = _drift_data(1500, seed=3)
    train_params = {"objective": "regression", "num_leaves": 15,
                    "min_data_in_leaf": 5, "learning_rate": 0.1,
                    "verbosity": -1}
    base = lgb.train(dict(train_params), lgb.Dataset(Xb, label=yb),
                     num_boost_round=12)
    cfg = {
        "tpu_continuous_learning": True, "tpu_checkpoint_path": tmp,
        "tpu_telemetry_path": telemetry, "objective": "regression",
        "tpu_refit_interval_s": 0.05, "tpu_refit_min_rows": 200,
        "tpu_refit_mode": "refit", "tpu_refit_holdout_fraction": 0.3,
        "tpu_promote_min_samples": 40, "tpu_promote_min_delta": 0.0,
        "tpu_promote_watch_s": 30.0, "verbosity": -1,
    }
    t0 = time.monotonic()
    if scenario == "kill_refit":
        summary = _run_kill_refit(tmp, base, cfg, train_params, n_rows,
                                  join_timeout_s)
    else:
        summary = _run_bad_promote(tmp, base, cfg, train_params, n_rows)
    events = _telemetry_events(telemetry)
    summary["supervisor_events"] = [e.get("what") for e in events]
    if scenario == "kill_refit":
        promote = [e for e in events if e.get("what") == "promote"]
        summary["ok"] = (summary["ok"] and "refit" in
                         summary["supervisor_events"] and bool(promote)
                         and "delta" in promote[0])
    else:
        summary["ok"] = (summary["ok"]
                         and "rollback" in summary["supervisor_events"])
    summary.update(scenario=scenario,
                   total_s=round(time.monotonic() - t0, 3))
    return summary


def _run_kill_refit(tmp, base, cfg, train_params, n_rows,
                    join_timeout_s) -> dict:
    ctx = mp.get_context("spawn")
    model_str = base.model_to_string()
    # life 1: the chaos hook SIGKILLs the process inside its first refit
    os.environ["LGBM_TPU_CHAOS"] = "kill_refit:0:0"
    try:
        q1 = ctx.Queue()
        p1 = ctx.Process(target=_sup_worker,
                         args=(tmp, model_str, cfg, train_params,
                               n_rows, 21, q1))
        p1.start()
        p1.join(timeout=join_timeout_s)
        if p1.is_alive():
            p1.terminate()
            p1.join(timeout=5.0)
    finally:
        os.environ.pop("LGBM_TPU_CHAOS", None)
    killed = p1.exitcode == -9
    spool = sorted(os.listdir(os.path.join(tmp, "supervisor_spool"))) \
        if os.path.isdir(os.path.join(tmp, "supervisor_spool")) else []
    # life 2: same state directory, no chaos — must replay the spool,
    # rebuild the candidate and promote
    q2 = ctx.Queue()
    p2 = ctx.Process(target=_sup_worker,
                     args=(tmp, model_str, cfg, train_params,
                           n_rows, 21, q2))
    p2.start()
    try:
        life2 = q2.get(timeout=join_timeout_s)
    except Exception:   # noqa: BLE001 — queue.Empty
        life2 = None
    p2.join(timeout=10.0)
    if p2.is_alive():
        p2.terminate()
    ok = (killed and bool(spool) and life2 is not None
          and life2["restored_rows"] >= n_rows       # zero ingest loss
          and life2["state"] == "watch"
          and life2["version"] == 2                  # promoted exactly once
          and life2["predict_failures"] == 0)
    return {"ok": ok, "killed_exitcode": p1.exitcode,
            "spool_after_kill": spool, "life2": life2}


def _run_bad_promote(tmp, base, cfg, train_params, n_rows) -> dict:
    import threading
    import lightgbm_tpu as lgb
    from lightgbm_tpu.resilience.supervisor import (
        ContinuousLearningSupervisor)
    from lightgbm_tpu.serving import Server
    Xb, yb = _drift_data(1500, seed=3)
    rng = np.random.RandomState(0)
    degraded = lgb.train(dict(train_params),
                         lgb.Dataset(Xb, label=rng.permutation(yb)),
                         num_boost_round=4)
    srv = Server(verbosity=-1)
    srv.load_model("m", model_str=base.model_to_string())
    sup = ContinuousLearningSupervisor(srv, cfg, model_name="m",
                                       train_params=train_params)
    X1, y1 = _drift_data(400, seed=31)
    sup.ingest(X1, y1)                       # window -> promote baseline
    Xq, _ = _drift_data(16, seed=99)
    failures = [0]
    stop = threading.Event()

    def hammer():
        while not stop.is_set():
            try:
                srv.predict(Xq, model="m")
            except Exception:   # noqa: BLE001 — the drill counts ANY failure
                failures[0] += 1

    threads = [threading.Thread(target=hammer, daemon=True)
               for _ in range(4)]
    for t in threads:
        t.start()
    v1 = srv.registry.get("m").version
    sup.force_promote(booster=degraded)
    v2 = srv.registry.get("m").version
    X2, y2 = _drift_data(400, seed=32)       # fresh labels for the watch
    sup.ingest(X2, y2)
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        sup.tick()
        if sup.snapshot()["rollbacks"] >= 1:
            break
        time.sleep(0.05)
    stop.set()
    for t in threads:
        t.join(timeout=5.0)
    v3 = srv.registry.get("m").version
    served = srv.registry.get("m").booster.predict(Xq)
    restored = bool(np.allclose(served, base.predict(Xq)))
    srv.shutdown()
    ok = (v2 == v1 + 1 and v3 == v2 + 1 and restored
          and sup.snapshot()["rollbacks"] == 1 and failures[0] == 0)
    return {"ok": ok, "versions": [v1, v2, v3],
            "served_matches_prior": restored,
            "predict_failures": failures[0],
            "rollbacks": sup.snapshot()["rollbacks"]}


def run_fleet_scenario(scenario: str, tenants: int = 64,
                       resident_cap: int = 8,
                       duration_s: float = 6.0) -> dict:
    """tenant_storm: `tenants` models share an HBM budget sized for
    `resident_cap` of them, under mixed traffic — a hot subset hammered
    continuously, the cold tail swept round-robin — while promotion
    faults are injected mid-storm.  The drill's contract is the fleet's:
    ZERO failed predictions (cold/degraded tenants ride the host walk,
    never an error) and the byte accounting NEVER exceeds the budget
    (asserted on the peak high-water mark, not a sample)."""
    assert scenario in FLEET_SCENARIOS, scenario
    import threading

    import lightgbm_tpu as lgb
    from lightgbm_tpu.ops import predict as predict_ops
    from lightgbm_tpu.serving import FleetFaultInjector, Server

    train_params = {"objective": "regression", "num_leaves": 15,
                    "min_data_in_leaf": 5, "verbosity": -1}
    model_strs = []
    for seed in range(4):
        X, y = _drift_data(400, seed=seed)
        model_strs.append(lgb.train(
            dict(train_params), lgb.Dataset(X, label=y),
            num_boost_round=8).model_to_string())
    probe = lgb.Booster(model_str=model_strs[0])
    est = predict_ops.estimate_device_bytes(
        probe._gbdt.models, probe._gbdt.num_tree_per_iteration)
    budget_bytes = est * resident_cap
    srv = Server(verbosity=-1,
                 serve_min_device_work=1,
                 serve_max_models=tenants + 1,
                 serve_max_batch_rows=64,
                 serve_warmup_buckets=[16, 64],
                 tpu_fleet_hbm_budget_mb=budget_bytes / float(1 << 20))
    inj = FleetFaultInjector()
    srv.fleet.injector = inj
    srv.fleet.degrade_cooldown_s = 0.5
    names = ["t%02d" % i for i in range(tenants)]
    for i, name in enumerate(names):
        srv.load_model(name, model_str=model_strs[i % len(model_strs)])
    hot = names[:max(resident_cap // 2, 1)]
    cold = names[len(hot):]
    Xq, _ = _drift_data(16, seed=99)
    failures, preds = [0], [0]
    flock = threading.Lock()
    stop = threading.Event()

    def hammer(targets, pause_s):
        i = 0
        while not stop.is_set():
            name = targets[i % len(targets)]
            i += 1
            try:
                srv.predict(Xq, model=name)
                with flock:
                    preds[0] += 1
            except Exception:   # noqa: BLE001 — the drill counts ANY failure
                with flock:
                    failures[0] += 1
            if pause_s:
                time.sleep(pause_s)

    threads = ([threading.Thread(target=hammer, args=(hot, 0.0),
                                 daemon=True) for _ in range(4)]
               + [threading.Thread(target=hammer, args=(cold, 0.01),
                                   daemon=True) for _ in range(2)])
    t0 = time.monotonic()
    for t in threads:
        t.start()
    # mid-storm: kill the next promotions in flight — the affected
    # tenants must degrade to the host walk, then heal after cool-down
    time.sleep(duration_s / 3.0)
    inj.fail("promote", count=3)
    time.sleep(duration_s * 2.0 / 3.0)
    stop.set()
    for t in threads:
        t.join(timeout=10.0)
    snap = srv.fleet.snapshot()
    # sampled correctness on a few tenants (device path is f32 on the
    # fast tier, hence the tolerance)
    sampled_ok = True
    for name in (hot[0], cold[0], cold[-1]):
        entry = srv.registry.get(name)
        got = np.asarray(srv.predict(Xq, model=name)).ravel()
        ref = np.asarray(entry.booster.predict(Xq)).ravel()
        sampled_ok &= bool(np.allclose(got, ref, rtol=1e-4, atol=1e-5))
    srv.shutdown()
    ok = (failures[0] == 0 and sampled_ok
          and snap["peak_resident_bytes"] <= budget_bytes
          and snap["resident_bytes"] <= budget_bytes
          and snap["evictions"] > 0
          and snap["promotions"] >= resident_cap
          and snap["promote_failures"] + snap["promote_retries"] >= 1)
    return {
        "scenario": scenario, "ok": ok,
        "tenants": tenants, "resident_cap": resident_cap,
        "budget_bytes": budget_bytes,
        "predictions": preds[0], "predict_failures": failures[0],
        "sampled_outputs_match": sampled_ok,
        "fleet": {k: snap[k] for k in
                  ("peak_resident_bytes", "resident_bytes", "promotions",
                   "promote_retries", "promote_failures", "evictions",
                   "host_serves", "device_hits", "compile_cache")},
        "total_s": round(time.monotonic() - t0, 3),
    }


def run_replica_scenario(scenario: str, replicas: int = 3,
                         duration_s: float = 6.0) -> dict:
    """kill_device: a 3-replica tenant under steady threaded traffic has
    one replica's dispatches forced to fail mid-drill.  The contract is
    the fault-domain promise: ZERO failed or lost predictions, ZERO
    host-walk fallbacks (the siblings absorb every rerouted batch),
    degraded throughput no worse than (N-1)/N of the healthy baseline,
    the victim's breaker opens and then half-open re-admits it with no
    operator action, and the telemetry names the victim device."""
    assert scenario in REPLICA_SCENARIOS, scenario
    import threading

    # distinct fault domains need distinct devices.  The drill never
    # tears down a live backend: in a fresh process it asks for the
    # 8-device virtual CPU platform before JAX starts; in a process
    # whose backend is already up (the test suite, or a caller holding
    # a chip) it uses the devices there are, or refuses.
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax
    if len(jax.devices()) < replicas:
        raise RuntimeError(
            "%s needs %d devices, this process has %d on %s; run it in "
            "a fresh process (python tools/chaos_run.py --scenario %s)"
            % (scenario, replicas, len(jax.devices()),
               jax.default_backend(), scenario))

    import lightgbm_tpu as lgb
    from lightgbm_tpu.serving import FleetFaultInjector, Server

    X, y = _drift_data(400, seed=5)
    booster = lgb.train({"objective": "regression", "num_leaves": 15,
                         "min_data_in_leaf": 5, "verbosity": -1},
                        lgb.Dataset(X, label=y), num_boost_round=8)
    srv = Server(verbosity=-1,
                 serve_min_device_work=1,
                 serve_max_batch_rows=64,
                 serve_warmup_buckets=[1, 16, 64],
                 serve_batch_wait_ms=1.0,
                 tpu_replica_count=replicas,
                 tpu_replica_breaker_failures=2,
                 tpu_replica_breaker_reset_s=0.5,
                 # slow enough that the ROUTER (not the prober) eats the
                 # injected faults and proves loss-free rerouting; the
                 # prober still backstops re-admission
                 tpu_replica_probe_interval_s=1.0,
                 tpu_replica_probe_deadline_ms=60_000.0)
    srv.load_model("m", model_str=booster.model_to_string())
    rset = srv.registry.replica_set("m")
    assert rset is not None and rset.count == replicas, \
        "replica set failed to place"
    inj = FleetFaultInjector()
    rset.arm_injector(inj)
    victim_slot = 1
    victim_dev = next(r["device"] for r in rset.snapshot()["replicas"]
                      if r["slot"] == victim_slot)
    Xq, _ = _drift_data(16, seed=99)
    failures, preds = [0], [0]
    flock = threading.Lock()
    stop = threading.Event()

    def hammer():
        while not stop.is_set():
            try:
                srv.predict(Xq, model="m")
                with flock:
                    preds[0] += 1
            except Exception:   # noqa: BLE001 — the drill counts ANY failure
                with flock:
                    failures[0] += 1

    threads = [threading.Thread(target=hammer, daemon=True)
               for _ in range(4)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    phase_s = duration_s / 3.0
    # phase 1: healthy baseline throughput
    time.sleep(phase_s)
    with flock:
        baseline = preds[0]
    # phase 2: kill the victim's next dispatches (router AND prober see
    # the faults; breaker_failures=2, so the breaker opens mid-phase)
    inj.fail("replica:%d" % victim_slot, count=8)
    time.sleep(phase_s)
    with flock:
        degraded = preds[0] - baseline
    # phase 3: the faults are consumed; half-open must re-admit the
    # victim with no operator action
    readmit_ok = False
    deadline = time.monotonic() + max(phase_s, 10.0)
    while time.monotonic() < deadline:
        snap = rset.snapshot()
        v = next(r for r in snap["replicas"] if r["slot"] == victim_slot)
        if v["healthy"] and v["breaker"]["open_count"] >= 1:
            readmit_ok = True
            break
        time.sleep(0.05)
    stop.set()
    for t in threads:
        t.join(timeout=10.0)
    snap = rset.snapshot()
    victim = next(r for r in snap["replicas"] if r["slot"] == victim_slot)
    events = rset.events()
    failover_evs = [e for e in events if e["what"] == "failover"]
    victim_named = bool(failover_evs) and all(
        e["victim"] == victim_slot and e["device"] == victim_dev
        for e in failover_evs)
    # the per-device gauge told the story: breaker open -> healthy 0
    healthy_gauge = srv.metrics.get("lgbm_replica_healthy", model="m",
                                    slot=str(victim_slot),
                                    device=str(victim_dev))
    gauge_ok = (healthy_gauge is not None
                and healthy_gauge.value == float(victim["healthy"]))
    # sampled correctness (device path is f32 on the fast tier)
    got = np.asarray(srv.predict(Xq, model="m")).ravel()
    ref = np.asarray(booster.predict(Xq)).ravel()
    sampled_ok = bool(np.allclose(got, ref, rtol=1e-4, atol=1e-5))
    srv.shutdown()
    floor = baseline * (replicas - 1) / float(replicas)
    ok = (failures[0] == 0
          and snap["host_fallbacks"] == 0
          and snap["failovers"] >= 1
          and victim["breaker"]["open_count"] >= 1
          and readmit_ok
          and degraded >= floor
          and victim_named
          and gauge_ok
          and sampled_ok)
    return {
        "scenario": scenario, "ok": ok,
        "replicas": replicas, "victim_slot": victim_slot,
        "victim_device": victim_dev,
        "predictions": preds[0], "predict_failures": failures[0],
        "baseline_preds": baseline, "degraded_preds": degraded,
        "throughput_floor": floor,
        "failovers": snap["failovers"],
        "host_fallbacks": snap["host_fallbacks"],
        "breaker_open_count": victim["breaker"]["open_count"],
        "readmitted": readmit_ok,
        "failover_events_name_victim": victim_named,
        "healthy_gauge_consistent": gauge_ok,
        "sampled_outputs_match": sampled_ok,
        "total_s": round(time.monotonic() - t0, 3),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--scenario",
                    choices=SCENARIOS + SUPERVISOR_SCENARIOS
                    + FLEET_SCENARIOS + HYBRID_SCENARIOS
                    + POLICY_SCENARIOS + REPLICA_SCENARIOS,
                    default="kill_rank")
    ap.add_argument("--world", type=int, default=3)
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--rows", type=int, default=240)
    ap.add_argument("--chaos-round", type=int, default=3)
    ap.add_argument("--fast", action="store_true",
                    help="CI smoke: fewer rounds/rows, shorter timeouts")
    ap.add_argument("--timeout", type=float, default=120.0)
    args = ap.parse_args(argv)
    if args.fast:
        args.rounds = min(args.rounds, 5)
        args.rows = min(args.rows, 180)
        args.chaos_round = min(args.chaos_round, 2)
    if args.scenario in REPLICA_SCENARIOS:
        summary = run_replica_scenario(
            args.scenario, replicas=3,
            duration_s=3.0 if args.fast else 6.0)
    elif args.scenario in FLEET_SCENARIOS:
        summary = run_fleet_scenario(
            args.scenario,
            tenants=16 if args.fast else 64,
            resident_cap=4 if args.fast else 8,
            duration_s=3.0 if args.fast else 6.0)
    elif args.scenario in SUPERVISOR_SCENARIOS:
        summary = run_supervisor_scenario(args.scenario,
                                          n_rows=max(args.rows, 400),
                                          join_timeout_s=args.timeout)
    elif args.scenario in POLICY_SCENARIOS:
        summary = run_policy_scenario(
            args.scenario,
            rounds=8 if args.fast else 12,
            n_rows=args.rows, chaos_round=args.chaos_round,
            join_timeout_s=max(args.timeout, 180.0))
    elif args.scenario in HYBRID_SCENARIOS:
        # kill_host keeps 3 hosts even in --fast so two survivors can
        # re-form a quorum; slow_host convicts nobody, so 2 suffice
        hosts = 2 if (args.fast and args.scenario == "slow_host") else 3
        summary = run_hybrid_scenario(
            args.scenario, hosts=hosts,
            rounds=args.rounds, n_rows=args.rows,
            chaos_round=args.chaos_round, join_timeout_s=args.timeout)
    else:
        summary = run_scenario(args.scenario, world=args.world,
                               rounds=args.rounds, n_rows=args.rows,
                               chaos_round=args.chaos_round,
                               join_timeout_s=args.timeout)
    print(json.dumps(summary, indent=2, sort_keys=True))
    return 0 if summary["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
