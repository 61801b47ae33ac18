"""chip_smoke.py and the rules it rests on, as far as a CPU can check them:
the script runs every phase in interpret mode and still refuses to pass
without a chip; a fast-path failure raises instead of changing engine; the
compile cache is placed from outside."""
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NO_CHIP_EXIT = 3


def _run(args, env_extra=None, env_drop=(), cwd=REPO, timeout=900):
    env = dict(os.environ)
    for k in env_drop:
        env.pop(k, None)
    env.update(env_extra or {})
    return subprocess.run([sys.executable] + args, capture_output=True,
                          text=True, cwd=cwd, env=env, timeout=timeout)


def test_chip_smoke_runs_every_phase_on_cpu_and_refuses_to_pass():
    res = _run([os.path.join(REPO, "chip_smoke.py"),
                "--rows", "4096", "--iters", "2"])
    assert res.returncode == NO_CHIP_EXIT, (res.stdout[-3000:],
                                           res.stderr[-3000:])
    assert "no chip found" in res.stderr
    out = res.stdout
    for phase in ("[main]", "[predict]", "[equivalence]", "[kernels]",
                  "[done]"):
        assert phase in out, (phase, out[-3000:])
    assert "REDUCED=rows=4096 iters=2" in out
    assert "interpret=True" in out
    # no result without a chip: the last line is not the JSON object
    assert not out.strip().splitlines()[-1].startswith("{")


def test_chip_smoke_without_chip_or_sizes_stops_at_once():
    res = _run([os.path.join(REPO, "chip_smoke.py")], timeout=120)
    assert res.returncode == NO_CHIP_EXIT
    assert "no chip found" in res.stderr and "Nothing was run" in res.stderr
    assert res.stdout.strip() == ""


def test_chip_smoke_last_line_is_exactly_the_verdict(capsys):
    """The driver parses the last stdout line of a passing run: one JSON
    object with the keys `ok` and `device` and no other, `device` with
    `platform`, `kind`, `count` and no other.  Everything else the run
    established goes on the `[result]` line before it."""
    import json

    import chip_smoke
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    chip_smoke._report(device, {"versions": {"jax": "0.9.0"},
                                "main": {"rows": 10_500_000}})
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": device}
    assert lines[-2].startswith("[result] ")
    detail = json.loads(lines[-2][len("[result] "):])
    assert detail["device"] == device and detail["main"]["rows"] == 10_500_000


def _tiny():
    rng = np.random.RandomState(0)
    X = rng.randn(600, 6).astype(np.float32)
    y = (X[:, 0] + 0.3 * X[:, 1] > 0).astype(np.float32)
    return X, y


@pytest.mark.parametrize("extra", [
    {},                                          # fused iteration
    {"boosting": "goss"},                        # unfused spine
    {"bagging_fraction": 0.8, "bagging_freq": 1},   # fused, under a bag
    {"tree_learner": "data", "num_machines": 2,
     "tpu_comm_backend": "mesh"},                # shard_map'd grower
    {"tree_learner": "data", "num_machines": 2,
     "tpu_comm_backend": "mesh", "tpu_quantized_grad": True},
])
def test_fast_path_kernel_failure_raises(monkeypatch, extra):
    """Once the partition engine is chosen, a kernel failure propagates
    out of lgb.train with its message; it does not demote the booster to
    another engine or precision."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.ops import partition_pallas as pp

    def boom(*a, **k):
        raise RuntimeError("injected Mosaic failure")

    monkeypatch.setattr(pp, "partition_segment", boom)
    X, y = _tiny()
    params = dict({"objective": "binary", "num_leaves": 7, "verbose": -1,
                   "min_data_in_leaf": 5, "tpu_tree_engine": "partition"},
                  **extra)
    with pytest.raises(RuntimeError, match="injected Mosaic failure"):
        lgb.train(params, lgb.Dataset(X, y), num_boost_round=2)


_CACHE_PROBE = ("import jax, lightgbm_tpu;"
                "print(jax.config.jax_compilation_cache_dir);"
                "print(jax.config.jax_persistent_cache_min_compile_time_secs);"
                "from jax._src import xla_bridge as xb;"
                "print(xb.backends_are_initialized())")


def test_compile_cache_defaults_to_the_checkout():
    res = _run(["-c", _CACHE_PROBE],
               env_drop=("JAX_COMPILATION_CACHE_DIR",
                         "JAX_ENABLE_COMPILATION_CACHE"))
    assert res.returncode == 0, res.stderr[-2000:]
    cache_dir, min_secs, initialised = res.stdout.split()
    assert cache_dir == os.path.join(REPO, ".jax_cache")
    assert float(min_secs) == 0.0
    assert initialised == "False"     # placing the cache takes no chip


def test_compile_cache_env_is_left_alone(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the package sets no directory in
    code: entries land where the variable says, none under the checkout."""
    where = str(tmp_path / "cache")
    probe = (_CACHE_PROBE + ";import jax.numpy as jnp;"
             "jax.jit(lambda a: a * 2 + 1)(jnp.ones((64,))).block_until_ready()")
    before = set(os.listdir(os.path.join(REPO, ".jax_cache"))) \
        if os.path.isdir(os.path.join(REPO, ".jax_cache")) else set()
    res = _run(["-c", probe],
               env_extra={"JAX_COMPILATION_CACHE_DIR": where},
               env_drop=("JAX_ENABLE_COMPILATION_CACHE",))
    assert res.returncode == 0, res.stderr[-2000:]
    assert res.stdout.split()[0] == where
    assert os.listdir(where), "no cache entry was written"
    after = set(os.listdir(os.path.join(REPO, ".jax_cache"))) \
        if os.path.isdir(os.path.join(REPO, ".jax_cache")) else set()
    assert after == before
