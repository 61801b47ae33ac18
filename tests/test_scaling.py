"""The runtime sync sentinel and the donation audit (obs/scaling.py,
obs/device.py), and the read-only guarantee: sentinel on/off trains
bitwise-identical models.

The sentinel tests exercise the REAL hook path (patched ArrayImpl
conversion methods), so they also pin the restore discipline: after
every guard exits, the class methods must be the originals again.
"""
import json
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.obs import device as obs_device
from lightgbm_tpu.obs import scaling
from lightgbm_tpu.utils.log import LightGBMError


# --------------------------------------------------------------------- #
# Runtime sync sentinel
# --------------------------------------------------------------------- #
class TestSyncSentinel:
    def setup_method(self):
        scaling.reset_sync_stats()

    def test_off_mode_builds_nothing(self):
        assert scaling.SyncSentinel.from_config(Config()) is None
        s = scaling.SyncSentinel.from_config(
            Config({"tpu_sync_guard": "log"}))
        assert s is not None and s.mode == "log"

    def test_planted_sync_is_caught_and_attributed(self):
        sent = scaling.SyncSentinel.from_config(
            Config({"tpu_sync_guard": "log"}))
        with sent.guard(round_idx=3):
            x = jnp.arange(8.0)
            x.sum().item()                 # planted implicit sync
            float(jnp.sum(x))              # and another, distinct kind
        stats = scaling.sync_stats()
        assert stats["total"] == 2
        assert stats["by_kind"] == {"item": 1, "__float__": 1}
        sites = [e.get("site", "") for e in stats["events"]]
        assert any("test_scaling" in s for s in sites)
        assert all(e.get("iter") == 3 for e in stats["events"])

    def test_clean_loop_is_silent(self):
        sent = scaling.SyncSentinel.from_config(
            Config({"tpu_sync_guard": "log"}))
        with sent.guard(0):
            x = jnp.arange(16.0)
            y = jnp.sum(x * 2.0)
            _ = jax.device_get(y)          # bulk fetch, not a hidden sync
        assert scaling.sync_stats()["total"] == 0

    def test_fail_mode_raises_but_exempt_allows(self):
        sent = scaling.SyncSentinel.from_config(
            Config({"tpu_sync_guard": "fail"}))
        with sent.guard(0):
            with scaling.exempt():
                float(jnp.sum(jnp.arange(4.0)))   # the perf-probe shape
            with pytest.raises(LightGBMError):
                float(jnp.sum(jnp.arange(4.0)))
        # the raise still recorded the event first
        assert scaling.sync_stats()["total"] == 1

    def test_hooks_fully_restored_after_guard(self):
        cls = scaling._array_impl_class()
        sent = scaling.SyncSentinel.from_config(
            Config({"tpu_sync_guard": "log"}))
        with sent.guard(0):
            assert getattr(cls.item, "_lgbm_sync_hook", False)
        for name in scaling._WATCHED_METHODS:
            fn = getattr(cls, name, None)
            assert not getattr(fn, "_lgbm_sync_hook", False), name
        # and conversions work normally again, uncounted
        scaling.reset_sync_stats()
        assert float(jnp.asarray(2.5)) == 2.5
        assert scaling.sync_stats()["total"] == 0


# --------------------------------------------------------------------- #
# Donation audit
# --------------------------------------------------------------------- #
class TestDonationAudit:
    def test_table_matches_jit_signature(self):
        @partial(jax.jit, donate_argnums=(0,))
        def f(a, b):
            return a + b, b * 2.0

        a = jnp.zeros((256, 256), jnp.float32)     # 256 KiB
        b = jnp.ones((256, 256), jnp.float32)
        table = obs_device.donation_audit(f, (a, b), label="test/donated")
        assert table is not None
        assert table["donated_args"] == [0]
        rows = {r["arg"]: r for r in table["rows"]}
        assert rows[0]["donated"] and not rows[1]["donated"]
        assert table["undonated_bytes"] == 256 * 256 * 4
        assert table["donated_bytes"] == 256 * 256 * 4
        assert "test/donated" in obs_device.donation_stats()

    def test_resident_args_excluded_from_floor(self):
        @partial(jax.jit, donate_argnums=(0,))
        def g(a, b):
            return a * 2.0 + b

        a = jnp.zeros((256, 256), jnp.float32)
        b = jnp.ones((256, 256), jnp.float32)
        table = obs_device.donation_audit(g, (a, b), label="test/resident",
                                          resident=(1,))
        assert table["undonated_bytes"] == 0
        rows = {r["arg"]: r for r in table["rows"]}
        assert rows[1]["resident"] is True and not rows[1]["donated"]

    def test_small_buffers_ignored(self):
        @jax.jit
        def h(a):
            return a + 1.0

        table = obs_device.donation_audit(h, (jnp.zeros(8),),
                                          label="test/small")
        assert table is not None and table["rows"] == []
        assert table["undonated_bytes"] == 0


# --------------------------------------------------------------------- #
# Read-only guarantee: sentinel on/off, bit for bit
# --------------------------------------------------------------------- #
def _train_model(tmp_path, sentinel: bool, mesh: bool) -> str:
    params = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
              "learning_rate": 0.1, "verbose": -1, "seed": 11,
              "deterministic": True}
    if mesh:
        params.update(tree_learner="data", num_machines=2,
                      tpu_comm_backend="mesh", tpu_tree_engine="partition")
    if sentinel:
        params.update(tpu_sync_guard="log",
                      tpu_telemetry_path=str(tmp_path / "tel.jsonl"))
    rng = np.random.RandomState(3)
    X = rng.rand(256, 6).astype(np.float32)
    y = (X[:, 0] + X[:, 1] > 1.0).astype(np.float32)
    ds = lgb.Dataset(X, label=y, params=dict(params))
    booster = lgb.train(params, ds, num_boost_round=3)
    return booster.model_to_string()


def test_sentinel_bitwise_identity_serial(tmp_path):
    off = _train_model(tmp_path / "off", False, mesh=False)
    (tmp_path / "on").mkdir()
    on = _train_model(tmp_path / "on", True, mesh=False)
    assert on == off


@pytest.mark.slow
def test_sentinel_bitwise_identity_mesh_w2(tmp_path):
    off = _train_model(tmp_path / "off", False, mesh=True)
    (tmp_path / "on").mkdir()
    on = _train_model(tmp_path / "on", True, mesh=True)
    assert on == off


def test_clean_training_rounds_trip_no_sync_event(tmp_path):
    """With the sentinel armed, the round path of a plain training run
    makes no implicit device->host fetch, and no sync_event line reaches
    the telemetry stream."""
    scaling.reset_sync_stats()
    params = {"objective": "binary", "num_leaves": 7, "verbose": -1,
              "seed": 11, "tpu_sync_guard": "log",
              "tpu_telemetry_path": str(tmp_path / "tel.jsonl")}
    rng = np.random.RandomState(3)
    X = rng.rand(256, 6).astype(np.float32)
    y = (X[:, 0] > 0.5).astype(np.float32)
    ds = lgb.Dataset(X, label=y, params=dict(params))
    lgb.train(params, ds, num_boost_round=3)
    assert scaling.sync_stats()["total"] == 0
    with open(tmp_path / "tel.jsonl") as fh:
        events = [json.loads(line)["event"] for line in fh]
    assert events.count("iteration") == 3
    assert "sync_event" not in events
