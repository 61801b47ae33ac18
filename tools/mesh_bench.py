#!/usr/bin/env python
"""Data-parallel mesh scaling bench: Higgs-shape throughput at
world={1,2,4,8} over the local device mesh, f32 and int8-quantized.

The measurement behind ISSUE 10's acceptance line: the MeshCollective
backend (parallel/collective.py) runs the partition engine shard_map'd
over the local devices with psum'd histograms, so throughput should
scale near-linearly with world size while the quantized mode stays
active (globally-agreed code scales — no serial-only ValueError).

Prints one JSON line:

    python tools/mesh_bench.py                      # device defaults
    python tools/mesh_bench.py --rows 2000000 --iters 50
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        JAX_PLATFORMS=cpu python tools/mesh_bench.py --rows 4096

Off-TPU the numbers are a smoke (interpret-mode kernels) that shows the
scaling STRUCTURE — every world size trains, quantized_active stays
true, the mesh backend engages — and nothing about speed; the output
names the backend it ran on.  A world larger than the device count is
an error, never a silently smaller sweep.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def run(worlds, n_rows, n_features, iters, num_leaves):
    import jax
    import numpy as np

    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils import log as lgb_log

    lgb_log.set_level(0)        # warnings on: an engine change says so
    n_dev = jax.device_count()
    too_big = [w for w in worlds if w > n_dev]
    if too_big:
        raise ValueError(
            "world size(s) %s exceed the %d %s device(s) visible; ask for "
            "worlds that fit (or, on the CPU, set XLA_FLAGS="
            "--xla_force_host_platform_device_count)"
            % (too_big, n_dev, jax.default_backend()))
    rng = np.random.RandomState(7)
    X = rng.randn(n_rows, n_features).astype(np.float32)
    wvec = rng.randn(n_features)
    y = ((X @ wvec * 0.5 + rng.randn(n_rows)) > 0).astype(np.float32)

    out = {"n_devices": n_dev, "rows": n_rows, "timed_iters": iters,
           "backend": jax.default_backend(), "runs": {}}
    for world in worlds:
        for quant in (False, True):
            params = {"objective": "binary", "num_leaves": num_leaves,
                      "learning_rate": 0.1, "max_bin": 255,
                      "min_data_in_leaf": 20, "verbose": -1,
                      "tpu_tree_engine": "partition",
                      "tpu_quantized_grad": quant}
            if world > 1:
                params.update(tree_learner="data", num_machines=world,
                              tpu_comm_backend="mesh")
            ds = lgb.Dataset(X, label=y, params=dict(params))
            booster = lgb.Booster(params=params, train_set=ds)
            booster.update()                                    # compile
            g = booster._gbdt
            float(jax.numpy.sum(g.train_state.score))           # sync
            t0 = time.perf_counter()
            for _ in range(iters):
                booster.update()
            float(jax.numpy.sum(g.train_state.score))
            dt = time.perf_counter() - t0
            g._sync_model()
            grower = g._grower
            mem = [d.memory_stats() or {} for d in jax.local_devices()]
            engine_on = (grower._partition is not None if grower is not None
                         else g._use_partition_engine)
            key = "w%d_%s" % (world, "int8" if quant else "f32")
            out["runs"][key] = {
                "world": world,
                # 5 decimals: CPU smoke throughputs are ~1e-4 Mrows
                "mrows_iter_s": round(n_rows * iters / dt / 1e6, 5),
                "elapsed_s": round(dt, 3),
                "round_wall_ms": round(dt / iters * 1e3, 3),
                "quantized_active": bool(getattr(g, "_quantized", False)),
                "engine": "partition" if engine_on else "label",
                # a truncated tree is a smaller model than the one asked
                # for: its time is not this configuration's time
                "last_tree_leaves": int(g.models[-1].num_leaves),
                "truncated": bool(g._truncation_warned),
                "comm_backend": (grower.collective.backend
                                 if grower is not None else "serial"),
                # one entry per local device: a sharded arena shows as
                # `world` devices holding a share each (None where the
                # backend reports no memory stats, i.e. the CPU)
                "device_bytes_in_use": [m.get("bytes_in_use") for m in mem],
                # high-water mark of the process so far, not of this run
                "device_peak_bytes_in_use": [m.get("peak_bytes_in_use")
                                             for m in mem],
            }
            # the next run's arena must not sit next to this one's
            del booster, g, grower, ds
    # scaling efficiency against the world=1 run of the same dtype
    for kind in ("f32", "int8"):
        base = out["runs"].get("w1_%s" % kind)
        if not base:
            continue
        for world in worlds:
            r = out["runs"].get("w%d_%s" % (world, kind))
            if r and base["mrows_iter_s"] > 0:
                speedup = r["mrows_iter_s"] / base["mrows_iter_s"]
                r["speedup"] = round(speedup, 3)
                r["efficiency"] = round(speedup / world, 3)
    # headline keys carry the world they were taken at: mesh8_* exists
    # only when world 8 ran
    w_top = max(worlds)
    top = out["runs"].get("w%d_int8" % w_top) or {}
    out["mesh%d_mrows_iter_s" % w_top] = top.get("mrows_iter_s")
    out["mesh%d_quantized_active" % w_top] = top.get("quantized_active")
    out["mesh%d_f32_speedup" % w_top] = (
        out["runs"].get("w%d_f32" % w_top) or {}).get("speedup")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--worlds", default="1,2,4,8",
                    help="comma-separated world sizes (default 1,2,4,8)")
    ap.add_argument("--rows", type=int, default=None,
                    help="rows (default: 2M on tpu, 4096 off)")
    ap.add_argument("--features", type=int, default=28)
    ap.add_argument("--iters", type=int, default=None,
                    help="timed iterations (default: 50 on tpu, 2 off)")
    ap.add_argument("--leaves", type=int, default=None,
                    help="num_leaves (default: 255 on tpu, 15 off)")
    args = ap.parse_args(argv)

    import jax
    from lightgbm_tpu.utils.backend import on_tpu as _on_tpu
    on_tpu = _on_tpu()
    worlds = sorted({int(w) for w in args.worlds.split(",")})
    rows = args.rows if args.rows else (2_000_000 if on_tpu else 4096)
    iters = args.iters if args.iters else (50 if on_tpu else 2)
    leaves = args.leaves if args.leaves else (255 if on_tpu else 15)
    out = run(worlds, rows, args.features, iters, leaves)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
