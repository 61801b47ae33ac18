"""Resilience subsystem tests (lightgbm_tpu/resilience/).

Three families:

- checkpoint/resume: a run killed mid-training and resumed from its
  newest checkpoint produces a model BITWISE-identical to the
  uninterrupted run, for every boosting mode; resume refuses on
  config/dataset mismatch; atomic writes, retention, manifests.
- continued training: ``train(n2, init_model=model_n1)`` is the
  additive complement of ``train(n1 + n2)`` (the continued booster
  holds only the new trees; the init model rides in as init scores).
- comm robustness: SocketComm survives injected transient faults
  below the retry budget with bitwise-identical collectives, and
  raises a typed CommFailure naming the dead rank past it.
"""
import os
import socket
import threading

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.io.file_io import atomic_write_text
from lightgbm_tpu.obs import adapters as obs_adapters
from lightgbm_tpu.obs import default_registry
from lightgbm_tpu.parallel.distributed import SocketComm
from lightgbm_tpu.resilience import (CheckpointError, CheckpointManager,
                                     CheckpointMismatchError, CommFailure,
                                     FaultInjector, Heartbeat, RetryPolicy,
                                     list_checkpoints, verify)
from lightgbm_tpu.resilience import checkpoint as ckpt_mod
from lightgbm_tpu.utils import log


def _data(seed=0, n=200, f=10):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, f)
    return X, X[:, 0] * 2 + rng.rand(n) * 0.1


BASE = dict(objective="regression", num_leaves=7, verbosity=-1,
            min_data_in_leaf=5, seed=3)

# every boosting mode with its nondeterminism sources switched ON
# (bagging + feature sampling RNGs, DART drop RNG + in-place tree
# mutation, GOSS sampling key past its warm-up window)
MODES = {
    "gbdt": dict(bagging_fraction=0.8, bagging_freq=1,
                 feature_fraction=0.8, learning_rate=0.1),
    "dart": dict(boosting="dart", drop_rate=0.5, learning_rate=0.1),
    "goss": dict(boosting="goss", learning_rate=0.5, top_rate=0.3,
                 other_rate=0.3),
    "rf": dict(boosting="rf", bagging_fraction=0.6, bagging_freq=1),
}


class TestKillAndResume:
    @pytest.mark.parametrize("mode", sorted(MODES))
    def test_bitwise_identical_resume(self, mode, tmp_path):
        X, y = _data()
        params = dict(BASE, **MODES[mode])
        root = str(tmp_path / "ckpts")

        full = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=8)
        # "crash" at round 5 with checkpoints every 2 rounds (so the
        # newest checkpoint is round 4, NOT the kill point — resume
        # replays rounds 5-8)
        lgb.train(dict(params, tpu_checkpoint_path=root,
                       tpu_checkpoint_interval=2),
                  lgb.Dataset(X, label=y), num_boost_round=5)
        resumed = lgb.train(params, lgb.Dataset(X, label=y),
                            num_boost_round=8, resume_from=root)
        assert resumed.model_to_string() == full.model_to_string()

    def test_resume_refuses_config_mismatch(self, tmp_path):
        X, y = _data()
        root = str(tmp_path / "ckpts")
        lgb.train(dict(BASE, tpu_checkpoint_path=root,
                       tpu_checkpoint_interval=2),
                  lgb.Dataset(X, label=y), num_boost_round=3)
        with pytest.raises(CheckpointMismatchError):
            lgb.train(dict(BASE, num_leaves=15), lgb.Dataset(X, label=y),
                      num_boost_round=5, resume_from=root)

    def test_resume_refuses_dataset_mismatch(self, tmp_path):
        X, y = _data()
        root = str(tmp_path / "ckpts")
        lgb.train(dict(BASE, tpu_checkpoint_path=root,
                       tpu_checkpoint_interval=2),
                  lgb.Dataset(X, label=y), num_boost_round=3)
        X2, y2 = _data(seed=7)
        with pytest.raises(CheckpointMismatchError):
            lgb.train(dict(BASE), lgb.Dataset(X2, label=y2),
                      num_boost_round=5, resume_from=root)

    def test_resume_excludes_init_model(self, tmp_path):
        X, y = _data()
        root = str(tmp_path / "ckpts")
        bst = lgb.train(dict(BASE, tpu_checkpoint_path=root,
                             tpu_checkpoint_interval=1),
                        lgb.Dataset(X, label=y), num_boost_round=2)
        with pytest.raises(log.LightGBMError, match="mutually exclusive"):
            lgb.train(dict(BASE), lgb.Dataset(X, label=y), num_boost_round=4,
                      resume_from=root, init_model=bst)


class TestCheckpointStore:
    def _train_with_ckpts(self, tmp_path, interval=1, keep=3, rounds=5):
        X, y = _data()
        root = str(tmp_path / "ckpts")
        lgb.train(dict(BASE, tpu_checkpoint_path=root,
                       tpu_checkpoint_interval=interval,
                       tpu_checkpoint_keep=keep),
                  lgb.Dataset(X, label=y), num_boost_round=rounds)
        return root

    def test_retention_keeps_newest(self, tmp_path):
        root = self._train_with_ckpts(tmp_path, interval=1, keep=2, rounds=5)
        assert [r for _, r in list_checkpoints(root)] == [4, 5]

    def test_manifest_verifies(self, tmp_path):
        root = self._train_with_ckpts(tmp_path, interval=2, rounds=4)
        for ckpt_dir, round_idx in list_checkpoints(root):
            manifest = verify(ckpt_dir)
            assert manifest["round"] == round_idx
            assert set(manifest["files"]) == {
                ckpt_mod.MODEL_FILE, ckpt_mod.STATE_FILE,
                ckpt_mod.SCORES_FILE}

    def test_latest_skips_corrupted(self, tmp_path):
        root = self._train_with_ckpts(tmp_path, interval=2, rounds=4)
        ckpts = list_checkpoints(root)
        assert [r for _, r in ckpts] == [2, 4]
        # bit-rot the newest checkpoint's model text: latest() must fall
        # back to the older hash-verified one instead of resuming onto
        # garbage
        with open(os.path.join(ckpts[-1][0], ckpt_mod.MODEL_FILE), "a") as f:
            f.write("corrupted\n")
        with pytest.raises(CheckpointError, match="mismatch"):
            verify(ckpts[-1][0])
        assert CheckpointManager.latest(root) == ckpts[0][0]

    def test_stale_tmp_swept_on_save(self, tmp_path):
        root = self._train_with_ckpts(tmp_path, interval=1, rounds=2)
        # a crash mid-save leaves a temp dir; the next save sweeps it
        stale = os.path.join(root, ckpt_mod._TMP_PREFIX + "deadbeef")
        os.makedirs(stale)
        X, y = _data()
        lgb.train(dict(BASE, tpu_checkpoint_path=root,
                       tpu_checkpoint_interval=1),
                  lgb.Dataset(X, label=y), num_boost_round=2)
        assert not os.path.exists(stale)

    def test_checkpoint_metrics_published(self, tmp_path):
        reg = default_registry()
        before = reg.counter("lgbm_checkpoint_saves_total").value
        self._train_with_ckpts(tmp_path, interval=1, rounds=3)
        assert reg.counter("lgbm_checkpoint_saves_total").value >= before + 3
        assert reg.gauge("lgbm_checkpoint_last_round").value == 3

    def test_serving_registry_loads_latest(self, tmp_path):
        root = self._train_with_ckpts(tmp_path, interval=2, rounds=4)
        from lightgbm_tpu.serving.registry import ModelRegistry
        registry = ModelRegistry()
        entry = registry.load("m", checkpoint_dir=root, warmup=False)
        assert entry.num_trees == 4
        with pytest.raises(ValueError, match="not both"):
            registry.load("m", model_file="x.txt", checkpoint_dir=root)


class TestAtomicWrites:
    def test_save_model_leaves_no_temp(self, tmp_path):
        X, y = _data()
        bst = lgb.train(dict(BASE), lgb.Dataset(X, label=y),
                        num_boost_round=2)
        path = tmp_path / "model.txt"
        bst.save_model(str(path))
        assert lgb.Booster(model_file=str(path)).model_to_string() \
            == bst.model_to_string()
        assert os.listdir(tmp_path) == ["model.txt"]

    def test_failed_replace_preserves_target(self, tmp_path, monkeypatch):
        target = tmp_path / "model.txt"
        target.write_text("the good model")

        def boom(src, dst):
            raise OSError("disk full")
        monkeypatch.setattr(os, "replace", boom)
        with pytest.raises(OSError, match="disk full"):
            atomic_write_text(str(target), "half-written garbage")
        monkeypatch.undo()
        # target untouched, temp file cleaned up
        assert target.read_text() == "the good model"
        assert os.listdir(tmp_path) == ["model.txt"]


class TestContinuedTraining:
    def _check_additive(self, params, n1, n2):
        X, y = _data(seed=1, n=150, f=8)

        def ds():
            return lgb.Dataset(X, label=y)
        full = lgb.train(params, ds(), num_boost_round=n1 + n2)
        m1 = lgb.train(params, ds(), num_boost_round=n1)
        m2 = lgb.train(params, ds(), num_boost_round=n2, init_model=m1)
        # the continued booster holds only the NEW trees (the init model
        # entered as init scores), so the uninterrupted run's raw score
        # decomposes as the sum of the two stages
        assert len(m2._gbdt.models) == n2
        pf = full.predict(X, raw_score=True)
        pc = m1.predict(X, raw_score=True) + m2.predict(X, raw_score=True)
        np.testing.assert_allclose(pc, pf, rtol=1e-5, atol=1e-6)

    def test_gbdt(self):
        self._check_additive(dict(BASE, learning_rate=0.2), 3, 3)

    def test_goss(self):
        # inside GOSS's 1/learning_rate warm-up window (no sampling yet)
        # continuation is exact; past it the sampling key chain restarts
        # with the new booster — resuming a sampled run mid-stream is
        # the checkpoint path's job (test_bitwise_identical_resume)
        self._check_additive(dict(BASE, boosting="goss", learning_rate=0.1,
                                  top_rate=0.3, other_rate=0.3), 4, 4)


# ---------------------------------------------------------------------- #
# comm robustness
# ---------------------------------------------------------------------- #

def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _run_allgather(rank, world, machines, results, injector=None, retries=4):
    comm = SocketComm(rank, world, machines, timeout_s=10.0, port_offset=0,
                      retry=RetryPolicy(retries=retries, base_ms=5.0,
                                        max_ms=20.0),
                      op_timeout_s=5.0, injector=injector)
    try:
        results[rank] = comm.allgather({"rank": rank, "v": rank * 10})
    except CommFailure as e:
        results[rank] = e
    finally:
        comm.close()


def _threaded_allgather(injector, retries=4, world=2):
    machines = ["127.0.0.1:%d" % _free_port()]
    results = {}
    threads = [threading.Thread(
        target=_run_allgather,
        args=(r, world, machines, results, injector if r == 0 else None,
              retries)) for r in range(world)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    return results


class TestCommFaults:
    def test_faults_below_budget_are_invisible(self):
        reg = default_registry()
        m = obs_adapters.ensure_comm_metrics(reg, 0, 2)
        before = m["lgbm_comm_retries_total"].value
        inj = FaultInjector()
        inj.fail("allgather", count=2)
        results = _threaded_allgather(inj, retries=4)
        expect = [{"rank": 0, "v": 0}, {"rank": 1, "v": 10}]
        assert results[0] == expect and results[1] == expect
        assert inj.injected == 2
        assert m["lgbm_comm_retries_total"].value == before + 2

    def test_exhausted_budget_raises_typed_failure(self):
        inj = FaultInjector()
        inj.fail("allgather", count=10)
        results = _threaded_allgather(inj, retries=2)
        e = results[0]
        assert isinstance(e, CommFailure)
        assert (e.op, e.rank, e.attempts) == ("allgather", 1, 3)
        assert "rank 1" in str(e)


class TestRetryPolicy:
    def test_backoff_exponential_and_capped(self):
        p = RetryPolicy(retries=3, base_ms=100.0, max_ms=400.0, jitter=0.0)
        assert [p.backoff_s(n) for n in (1, 2, 3, 4)] == \
            [0.1, 0.2, 0.4, 0.4]

    def test_jitter_bounded(self):
        p = RetryPolicy(base_ms=100.0, max_ms=100.0, jitter=0.5, seed=0)
        for n in range(1, 20):
            assert 0.05 <= p.backoff_s(n) <= 0.1

    def test_from_config(self):
        from lightgbm_tpu.config import Config
        p = RetryPolicy.from_config(Config(tpu_comm_retries=7,
                                           tpu_comm_backoff_ms=9,
                                           tpu_comm_backoff_max_ms=90))
        assert (p.retries, p.base_ms, p.max_ms) == (7, 9.0, 90.0)


class TestFaultInjector:
    def test_fail_consumes_then_ok(self):
        inj = FaultInjector()
        inj.fail("send", count=2)
        assert inj.armed("send")
        for _ in range(2):
            with pytest.raises(ConnectionError, match="injected fault"):
                inj.check("send")
        assert inj.check("send") == FaultInjector.OK
        assert not inj.armed() and inj.injected == 2

    def test_drop_and_reset(self):
        inj = FaultInjector()
        inj.drop("send", count=1)
        assert inj.check("send") == FaultInjector.DROP
        inj.fail("recv", count=5)
        inj.reset()
        assert inj.check("recv") == FaultInjector.OK


class TestHeartbeat:
    def test_poll_tracks_dead_ranks_and_gauge(self):
        reg = default_registry()
        dead = []
        hb = Heartbeat(lambda: list(dead), interval_s=60.0, rank=0, world=4,
                       registry=reg)
        gauge = reg.gauge("lgbm_comm_alive_ranks", rank="0", world="4")
        assert hb.poll_once() == [] and hb.alive()
        assert gauge.value == 4
        dead.extend([2, 3])
        assert hb.poll_once() == [2, 3] and not hb.alive()
        assert gauge.value == 2
        dead.remove(2)  # a rank coming back is observed too
        assert hb.poll_once() == [3]
        assert gauge.value == 3
        # rank 3 has missed two probes: stop() takes that streak out of the
        # process-wide registry, where the alert engine of any later server
        # in this process would read it (test_federation.py's serving case)
        hb.stop()

    def test_a_stopped_monitor_leaves_no_miss_streak(self):
        """The streak gauge is process-wide and the alert engine's
        heartbeat_miss rule reads it by name: a comm that missed probes
        and was closed must not leave a later server of the process
        firing (tests/test_federation.py's serving case did, under load)."""
        reg = default_registry()
        hb = Heartbeat(lambda: [1], interval_s=60.0, rank=0, world=3,
                       registry=reg, suspect_after=5)
        streak = reg.gauge("lgbm_comm_heartbeat_miss_streak", rank="0",
                           world="3")
        hb.poll_once()
        hb.poll_once()
        assert streak.value == 2
        hb.stop()
        assert streak.value == 0

    def test_detection_latency_bounded(self):
        """A silent rank is convicted within interval_s * suspect_after
        plus one probe (the documented bound), not eventually."""
        import time
        dead = set()
        interval, after = 0.02, 3
        hb = Heartbeat(lambda: sorted(dead), interval_s=interval,
                       rank=0, world=3, suspect_after=after).start()
        try:
            time.sleep(4 * interval)          # healthy warm-up window
            assert hb.alive() and hb.dead_ranks() == []
            t0 = time.monotonic()
            dead.add(2)
            while hb.alive() and time.monotonic() - t0 < 5.0:
                time.sleep(interval / 4)
            latency = time.monotonic() - t0
            assert hb.dead_ranks() == [2]
            # bound plus generous CI scheduling slack
            assert latency < interval * (after + 1) + 1.0
        finally:
            hb.stop()

    def test_single_miss_never_flaps(self):
        """With suspect_after=2 an alternating miss/answer pattern —
        GC pause, one dropped packet — never convicts; two CONSECUTIVE
        misses do."""
        hb = Heartbeat(lambda: [], interval_s=60.0, world=2,
                       suspect_after=2)
        for missing in ([1], [], [1], [], [1]):
            hb.probe = lambda m=missing: m
            hb.poll_once()
            assert hb.alive(), "a lone miss must not convict"
        assert hb.suspect_ranks() == [1]   # last round left one miss
        hb.probe = lambda: [1]
        hb.poll_once()                     # second consecutive miss
        assert hb.dead_ranks() == [1] and not hb.alive()

    def test_gauge_recovers_after_transient_stall(self):
        """A CONVICTED rank that answers again is un-declared and the
        alive-ranks gauge climbs back to the full world."""
        reg = default_registry()
        missing = [3]
        hb = Heartbeat(lambda: list(missing), interval_s=60.0, rank=1,
                       world=4, registry=reg, suspect_after=2)
        gauge = reg.gauge("lgbm_comm_alive_ranks", rank="1", world="4")
        transitions = []
        hb.on_change = lambda d: transitions.append(sorted(d))
        hb.poll_once()
        assert gauge.value == 4            # suspected, not yet convicted
        hb.poll_once()
        assert gauge.value == 3 and hb.dead_ranks() == [3]
        missing.clear()                    # stall heals
        hb.poll_once()
        assert gauge.value == 4 and hb.alive()
        assert transitions == [[3], []]
