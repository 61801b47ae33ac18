"""Device-phase microbenchmarks for the partition engine.

The grow loop is ONE compiled lax.while_loop, so host timers cannot
attribute time to its internal phases (partition / segment-histogram /
split-scan); this tool times each kernel standalone at real workload
shapes — the other half of the profiling subsystem (see
utils/profiling.py; reference taxonomy serial_tree_learner.cpp:15-42).

    python tools/phase_bench.py [--rows N] [--features F] [--max-bin B]

Timing protocol: dispatch is async, so each measurement chains K calls
and fetches one dependent scalar; reported per-call time includes
amortized dispatch.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def _timer(sync):
    def measure(fn, reps):
        fn()  # warmup/compile
        sync()
        t0 = time.perf_counter()
        for _ in range(reps):
            fn()
        sync()
        return (time.perf_counter() - t0) / reps
    return measure


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=4_000_000)
    ap.add_argument("--features", type=int, default=28)
    ap.add_argument("--max-bin", type=int, default=255)
    ap.add_argument("--leaves", type=int, default=255)
    ap.add_argument("--reps", type=int, default=5)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.ops import grow_partition as gp
    from lightgbm_tpu.ops import partition_pallas as pp
    from lightgbm_tpu.ops.split import SplitParams, best_split_per_feature
    from lightgbm_tpu.utils.backend import pallas_interpret

    n, F, B, L = args.rows, args.features, args.max_bin, args.leaves
    interp = pallas_interpret()
    rng = np.random.RandomState(0)

    C, cap = pp.arena_geometry(n, F)
    bins = rng.randint(0, B, (F, n)).astype(np.float32)
    g = rng.randn(n).astype(np.float32)
    h = (np.abs(rng.randn(n)) + 0.1).astype(np.float32)

    arena = jnp.zeros((C, cap), pp.ARENA_DT)
    Fp = pp.feature_channels(F)
    chans = [jnp.asarray(bins, pp.ARENA_DT)]
    if Fp > F:
        chans.append(jnp.zeros((Fp - F, n), pp.ARENA_DT))
    chans += [c[None] for c in pp.split_f32(jnp.asarray(g))]
    chans += [c[None] for c in pp.split_f32(jnp.asarray(h))]
    chans += [c[None] for c in pp.split_rowid(jnp.arange(n, dtype=jnp.int32))]
    if C > Fp + pp.N_AUX:
        chans.append(jnp.zeros((C - Fp - pp.N_AUX, n), pp.ARENA_DT))
    arena = jax.lax.dynamic_update_slice(
        arena, jnp.concatenate(chans, axis=0), (0, 0))
    jax.block_until_ready(arena)

    def sync():
        float(jnp.sum(arena[0, :8]))

    measure = _timer(sync)
    out = {"rows": n, "features": F, "max_bin": B, "backend":
           jax.default_backend()}

    pred = jnp.ones((1, cap), jnp.float32)
    dstB = -(-n // pp.TILE) * pp.TILE

    goleft = (jnp.arange(256) <= B // 2).astype(jnp.float32)

    def run_partition(cnt):
        nonlocal arena
        arena, counts = pp.partition_segment(
            arena, pred, jnp.int32(0), jnp.int32(cnt), jnp.int32(0),
            jnp.int32(dstB),
            decision=(jnp.int32(0), goleft, jnp.int32(0)),
            interpret=interp)
        return counts

    def run_hist(cnt):
        return pp.segment_histogram(arena, jnp.int32(0), jnp.int32(cnt),
                                    F, B, interpret=interp)

    for frac, tag in ((1.0, "full"), (0.25, "quarter"), (1 / 64, "64th")):
        cnt = int(n * frac)
        out["partition_%s_ms" % tag] = round(
            1e3 * measure(lambda: run_partition(cnt), args.reps), 3)
        out["seg_hist_%s_ms" % tag] = round(
            1e3 * measure(lambda: run_hist(cnt), args.reps), 3)

    # split scan over one [F, B, 3] histogram (per-leaf cost in the loop)
    hist = run_hist(n)
    jax.block_until_ready(hist)
    params = SplitParams(min_data_in_leaf=20)
    nb = jnp.full(F, B, jnp.int32)
    zb = jnp.zeros(F, jnp.int32)

    scan = jax.jit(lambda hh: best_split_per_feature(
        hh, jnp.sum(hh[0, :, 0]), jnp.sum(hh[0, :, 1]),
        jnp.int32(n), nb, zb, zb, params).gain)
    out["split_scan_ms"] = round(1e3 * measure(lambda: scan(hist), args.reps), 3)

    # full production grow at several leaf counts: leaves=2 isolates the
    # fixed per-tree cost (arena assembly + root partition/hist + label
    # recovery); the slope against leaves is the per-split loop cost
    fmask = jnp.ones(F, bool)
    row0 = jnp.zeros(n, jnp.int32)
    bins_dev = jax.device_put(jnp.asarray(bins, pp.ARENA_DT))
    g_dev, h_dev = jax.device_put(jnp.asarray(g)), jax.device_put(jnp.asarray(h))
    jax.block_until_ready(bins_dev)

    def grow_at(leaves, emit):
        def run():
            nonlocal arena
            arrays, out_ids, arena, _ = gp.grow_tree_partition(
                arena, bins_dev, g_dev, h_dev, row0, fmask, nb, zb, zb,
                params, max_leaves=leaves, max_bin=B, emit=emit,
                interpret=interp)
            return out_ids
        return run

    for leaves in (2, 64, L):
        out["tree_%dleaf_score_ms" % leaves] = round(
            1e3 * measure(grow_at(leaves, "score"), args.reps), 1)
    out["tree_%dleaf_leafids_ms" % L] = round(
        1e3 * measure(grow_at(L, "leaf_ids"), args.reps), 1)
    per_split = (out["tree_%dleaf_score_ms" % L]
                 - out["tree_2leaf_score_ms"]) / (L - 2)
    out["per_split_ms"] = round(per_split, 3)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
